"""Serving launcher: jitted continuous-batching over the VBI-paged engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 6 --max-new 24 --shared-prefix 32

``--smoke`` (the default) serves the reduced CPU-test config;
``--no-smoke`` serves the config's published widths.

Default path: serve/engine.py (single jitted decode dispatch, device-side
delayed page allocation) driven by serve/scheduler.py (admission, chunked
prefill, eviction, preemption), with all KV page lifecycle flowing through
the VBI memory API (core/vbi/blocks.py::VBIAllocator, DESIGN.md §6) and
the VBI prefix cache enabled (serve/prefix_cache.py — cross-request KV
page sharing, DESIGN.md §5.1; disable with ``--no-prefix-cache``).
``--shared-prefix N`` prepends an N-token system prompt to every request
so the sharing is visible in the stats.  ``--host-swap-pages N`` enables
the host swap tier: preemption victims are demoted to host memory and
resume with one device scatter instead of re-prefilling.
``--decode-horizon K`` sets the fused decode horizon (DESIGN.md §7):
decoding slots advance K tokens per jitted dispatch — sampling, token
feedback and stopping all on device — so the host syncs once per horizon
instead of once per token.  ``--legacy`` runs the per-sequence reference
path (serve/paged.py, uniform stacks only) for comparison.

Any decoder-only ``--arch`` serves through property-typed cache blocks
(DESIGN.md §8): gemma3's local/global pattern, mixtral's SWA MoE,
recurrentgemma's RG-LRU hybrid and mamba2's SSM included — windowed
layers on capped RING frames, recurrent layers on constant-size state.
The prefix cache auto-disables for such stacks (RING/RECURRENT blocks
are ineligible for sharing).  ``--attn-impl {gather,kernel}`` selects
the XLA gather path or the Pallas paged-attention kernel.

``--traffic {poisson,bursty}`` switches from the closed-loop batch to
continuous open-loop serving (DESIGN.md §9): a seeded mixed workload
(chat / RAG / agent / summarization, serve/traffic.py) arrives at
``--rate`` requests/s on the wall clock, and the run reports TTFT/TPOT
percentiles, SLO attainment against ``--slo-ttft``/``--slo-tpot`` and
goodput-under-SLO instead of aggregate tok/s.  ``--overlap`` enables
double-buffered dispatch in either mode: the host stages horizon N+1
(admission, reservation, prefix lookup) while the device still runs
horizon N — same output bits, fewer stalls.

``--disagg`` switches to the disaggregated prefill/decode topology
(DESIGN.md §11): prompts prefill on a many-slot prefill engine
(``--prefill-slots``), and at prompt completion each request's exact KV
state hands off as a self-describing ``BlockImage`` to a separately
provisioned decode engine (``--decode-slots``, deep horizon, the swap
tier).  Decode-pool pressure stalls the handoff admission, never the
prefill engine.  Works with ``--traffic`` and ``--trace`` — the trace
then carries both pools' event streams, pool-labelled.

``--trace out.jsonl`` records the VBI telemetry trace (DESIGN.md §10):
request lifecycle spans, per-tick host timeline, every block op with its
declared properties, and per-tick occupancy gauges.  The run self-checks
the trace against the allocator conservation invariants on exit;
``--trace-format chrome`` writes Chrome ``trace_event`` JSON for
Perfetto instead, and ``--metrics`` prints the metrics-registry
snapshot.  Offline: ``python -m repro.serve.telemetry trace.jsonl``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, smoke_config
from ..models.model import init_params
from ..serve.engine import PagedEngine
from ..serve.prefix_cache import PrefixCache
from ..serve.scheduler import Scheduler
from ..serve.telemetry import Telemetry
from .compile_cache import enable_compile_cache


def serve_config(arch: str, smoke: bool = True):
    """Float32 serve config for the paged serve paths (shared by the
    launcher, benchmarks, and tests so they can never diverge).

    With property-typed cache blocks (DESIGN.md §8) the engine serves any
    decoder-only stack — uniform GQA, gemma3 local/global, mixtral SWA MoE,
    recurrentgemma rglru-hybrid, mamba2 SSM.  Only encoder-decoder
    (whisper) falls back to the dense stand-in."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    if cfg.is_encdec:
        cfg = dataclasses.replace(
            smoke_config("qwen3-0.6b"), name=cfg.name + "-as-dense")
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32", n_vis_tokens=0)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced CPU-test config (default); "
                         "--no-smoke serves the published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode topology (DESIGN.md "
                         "§11): two independently-geometried engines — "
                         "prompts prefill on one, a self-describing "
                         "BlockImage hands each request's exact KV off to "
                         "the other for decode; decode-pool pressure "
                         "stalls the handoff, never prefill")
    ap.add_argument("--prefill-slots", type=int, default=6,
                    help="prefill-engine slots for --disagg (many slots, "
                         "prompt-sized pool)")
    ap.add_argument("--decode-slots", type=int, default=3,
                    help="decode-engine slots for --disagg (fewer slots, "
                         "lifetime-sized pool, deep horizon)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="shared system-prompt tokens prepended to every "
                         "request (exercises the prefix cache)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request KV page sharing")
    ap.add_argument("--host-swap-pages", type=int, default=0,
                    help="host swap tier capacity in pages (0 = off); "
                         "SWAPPABLE preemption victims demote to host "
                         "memory and resume without re-prefilling")
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="fused decode horizon K (DESIGN.md §7): decode "
                         "slots advance K tokens per jitted dispatch with "
                         "on-device sampling and stopping; the host syncs "
                         "once per horizon instead of once per token")
    ap.add_argument("--attn-impl", default="gather",
                    choices=("gather", "kernel"),
                    help="paged attention implementation: 'gather' (XLA "
                         "batched gather, default) or 'kernel' (the Pallas "
                         "paged-attention kernel — lowers for real on TPU, "
                         "interpret mode elsewhere)")
    ap.add_argument("--mesh-shape", default=None, metavar="DATAxMODEL",
                    help="serve on a device mesh, e.g. '1x4' = 4-way "
                         "model-axis sharding (DESIGN.md §13): KV pools, "
                         "ring frames and recurrent state shard over "
                         "'model', the page table / allocator / scheduler "
                         "stay host-global; MoE stacks dispatch expert-"
                         "parallel.  Needs that many devices (on the CPU "
                         "backend only, XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N fakes them)")
    ap.add_argument("--kv-layout", default="auto",
                    choices=("auto", "shard", "replicate"),
                    help="pool layout on a >1-device mesh: 'auto' picks by "
                         "the hlo_cost-predicted collective bytes of the "
                         "compiled decode step")
    ap.add_argument("--traffic", default=None,
                    choices=("poisson", "bursty"),
                    help="open-loop continuous traffic (DESIGN.md §9): a "
                         "seeded mixed workload arrives at --rate req/s on "
                         "the wall clock; reports TTFT/TPOT percentiles and "
                         "goodput-under-SLO instead of aggregate tok/s")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="offered load for --traffic, requests per second")
    ap.add_argument("--slo-ttft", type=float, default=float("inf"),
                    help="TTFT SLO in seconds (for goodput accounting)")
    ap.add_argument("--slo-tpot", type=float, default=float("inf"),
                    help="TPOT SLO in seconds (for goodput accounting)")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered dispatch: stage horizon N+1 on "
                         "the host while the device runs horizon N "
                         "(bit-exact; works in batch and --traffic modes)")
    ap.add_argument("--trace", metavar="OUT", default=None,
                    help="record a VBI telemetry trace (DESIGN.md §10): "
                         "request lifecycle, tick timeline spans, every "
                         "block op with its declared properties, per-tick "
                         "gauges; verify/convert offline with "
                         "python -m repro.serve.telemetry")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=("jsonl", "chrome"),
                    help="trace file format: 'jsonl' (one event per line, "
                         "the checker's input) or 'chrome' (trace_event "
                         "JSON for Perfetto / chrome://tracing)")
    ap.add_argument("--faults", action="store_true",
                    help="chaos mode (DESIGN.md §12): inject seeded faults "
                         "at every VBI boundary — transient alloc "
                         "exhaustion, swap I/O failure, block-image loss "
                         "and corruption, poisoned decode ticks — and "
                         "recover exactly (bounded retry, re-prefill, "
                         "degradation ladder); outputs are bit-identical "
                         "to the fault-free run")
    ap.add_argument("--fault-rate", type=float, default=0.05,
                    help="per-boundary fault firing probability for "
                         "--faults (flat across fault classes)")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="seed of the rate-independent fault streams: one "
                         "seed sweeps intensities over identical traffic")
    ap.add_argument("--fault-model", default=None,
                    help="derive fault rates from the SIMDRAM reliability "
                         "model instead of --fault-rate, e.g. "
                         "'simdram:node=22' (core/reliability.py): the "
                         "multi-row activation failure rate at that node "
                         "becomes the per-boundary fault probability")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics-registry snapshot (counters, "
                         "gauges with high-water marks, latency "
                         "histograms) at the end of the run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="per-sequence reference path (serve/paged.py)")
    return ap


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.legacy and (args.traffic or args.overlap):
        ap.error("--traffic/--overlap need the jitted engine path "
                 "(drop --legacy)")
    if args.legacy and (args.trace or args.metrics):
        ap.error("--trace/--metrics need the jitted engine path "
                 "(drop --legacy)")
    if args.legacy and args.disagg:
        ap.error("--disagg needs the jitted engine path (drop --legacy)")
    if args.legacy and (args.faults or args.fault_model):
        ap.error("--faults needs the VBI allocator boundaries "
                 "(drop --legacy)")
    mesh = None
    if args.mesh_shape is not None:
        if args.legacy:
            ap.error("--mesh-shape needs the jitted engine path "
                     "(drop --legacy)")
        try:
            data, model = (int(x) for x in args.mesh_shape.split("x"))
        except ValueError:
            ap.error(f"--mesh-shape must look like '1x4', "
                     f"got {args.mesh_shape!r}")
        if data * model > jax.device_count():
            ap.error(f"--mesh-shape {args.mesh_shape} needs {data * model} "
                     f"devices but only {jax.device_count()} exist — on "
                     f"CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                     f"count={data * model}")
        if model > 1 and args.attn_impl == "kernel":
            # fail loudly HERE: the Pallas kernel is not sharding-aware,
            # and letting it through would crash deep inside jit (or
            # silently gather the whole pool per device)
            ap.error("--attn-impl kernel is not supported on a >1-device "
                     "mesh: the Pallas paged-attention kernel assumes a "
                     "single-device page pool. Use --attn-impl gather, or "
                     "--mesh-shape 1x1.")
        from .mesh import make_host_mesh
        mesh = make_host_mesh(data=data, model=model)

    cfg = serve_config(args.arch, args.smoke)
    if args.legacy and (cfg.family not in ("dense", "vlm")
                        or cfg.local_global_period or cfg.rglru_period
                        or cfg.window):
        ap.error(f"--legacy (serve/paged.py) only supports uniform dense "
                 f"GQA stacks; {cfg.name} needs the property-typed engine "
                 f"(drop --legacy)")
    if mesh is not None:
        # created in their shardings: never whole on one device
        from ..distributed.sharding import init_sharded_params
        params = init_sharded_params(cfg, jax.random.key(args.seed), mesh)
    else:
        params = init_params(cfg, jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    system = rng.integers(0, cfg.vocab, args.shared_prefix).tolist()
    prompts = [system + rng.integers(0, cfg.vocab, args.prompt_len).tolist()
               for _ in range(args.requests)]

    t0 = time.time()
    if args.legacy:
        decoded = _run_legacy(cfg, params, prompts, args)
    else:
        page_size = 8
        p_eng = None
        if args.disagg:
            # prefill engine: many slots over a prompt-sized pool; decode
            # engine: fewer slots, lifetime-sized pool + the swap tier
            p_eng = PagedEngine(
                cfg, params, page_size=page_size,
                max_seqs=args.prefill_slots,
                n_pages=1 + args.prefill_slots * (8 + args.shared_prefix
                                                  // page_size),
                attn_impl=args.attn_impl, mesh=mesh,
                kv_layout=args.kv_layout)
            engine = PagedEngine(
                cfg, params, page_size=page_size,
                max_seqs=args.decode_slots,
                n_pages=1 + args.decode_slots * (32 + args.shared_prefix
                                                 // page_size),
                host_swap_pages=args.host_swap_pages,
                attn_impl=args.attn_impl, mesh=mesh,
                kv_layout=args.kv_layout)
        else:
            engine = PagedEngine(
                cfg, params, page_size=page_size, max_seqs=args.batch_slots,
                n_pages=1 + args.batch_slots * (32 + args.shared_prefix
                                                // page_size),
                host_swap_pages=args.host_swap_pages,
                attn_impl=args.attn_impl, mesh=mesh,
                kv_layout=args.kv_layout)
        g = engine.geom
        print(f"[serve] {cfg.name}: layer kinds full={g.n_full} "
              f"ring={g.n_ring} (window={g.window}) rglru={g.n_rg} "
              f"ssm={g.n_ssm} — attn_impl={args.attn_impl}")
        if mesh is not None:
            print(f"[serve] mesh {dict(mesh.shape)}: kv_layout="
                  f"{engine.kv_layout}, placement={engine.placement}")
            if engine.layout_report is not None:
                print(f"[serve] layout probe: {engine.layout_report}")
        cache = (None if args.no_prefix_cache
                 else PrefixCache(page_size=page_size))
        if cache is not None and not engine.supports_prefix_sharing:
            print("[serve] prefix cache disabled: RING/RECURRENT layers "
                  "are ineligible for cross-request page sharing "
                  "(DESIGN.md §8)")
            cache = None
        telem = (Telemetry(trace=args.trace is not None)
                 if args.trace or args.metrics else None)
        plan = None
        if args.faults or args.fault_model:
            from ..serve.faults import plan_from_args
            plan = plan_from_args(args.fault_rate, args.fault_seed,
                                  model=args.fault_model)
            print(f"[serve] chaos mode: fault rates "
                  f"{ {k: f'{v:g}' for k, v in plan.rates.items()} } "
                  f"seed={args.fault_seed} (DESIGN.md §12)")
        if args.disagg:
            from ..serve.disagg import DisaggScheduler
            print(f"[serve] disagg topology: prefill "
                  f"{args.prefill_slots} slots/{p_eng.n_pages} pages -> "
                  f"decode {args.decode_slots} slots/{engine.n_pages} "
                  f"pages (BlockImage handoff, DESIGN.md §11)")
            sched = DisaggScheduler(p_eng, engine,
                                    prefill_chunk=args.prefill_chunk,
                                    decode_horizon=args.decode_horizon,
                                    overlap=args.overlap,
                                    prefix_cache=cache, telemetry=telem,
                                    faults=plan)
        else:
            sched = Scheduler(engine, prefill_chunk=args.prefill_chunk,
                              prefix_cache=cache,
                              decode_horizon=args.decode_horizon,
                              overlap=args.overlap, telemetry=telem,
                              faults=plan)
        if args.traffic:
            finished = _run_traffic(cfg, sched, args)
        else:
            for p in prompts:
                sched.add_request(p, max_new=args.max_new)
            finished = sched.run()
        for req in finished:
            print(f"[serve] req {req.rid} done: "
                  f"{req.prompt[-4:]} -> {req.out[:8]}...")
        decoded = (sum(len(r.prompt) + len(r.out) for r in finished)
                   if args.traffic
                   else args.requests * (len(prompts[0]) + args.max_new))
        if p_eng is not None:
            print(f"[serve] prefill engine stats {p_eng.stats} "
                  f"allocator stats {p_eng.alloc.stats}")
            print(f"[serve] disagg stats {dict(sched.stats)} — "
                  f"prefill sched {dict(sched.prefill.stats)} / "
                  f"decode sched {dict(sched.decode.stats)}")
        print(f"[serve] engine stats {engine.stats} "
              f"allocator stats {engine.alloc.stats} "
              f"sched stats {sched.stats}")
        if cache is not None:
            print(f"[serve] prefix cache: hit_rate={cache.hit_rate:.2f} "
                  f"stats {cache.stats}")
        if plan is not None:
            print(f"[serve] fault plan: {plan.stats}")
            assert plan.stats["unresolved"] == 0, \
                "chaos run left injected faults unresolved"
        if telem is not None:
            _emit_telemetry(telem, args)
    dt = time.time() - t0
    print(f"[serve] {args.requests} requests, {decoded} token-steps in "
          f"{dt:.1f}s ({decoded / dt:.1f} tok/s)")


def _emit_telemetry(telem, args) -> None:
    """Write the recorded trace (JSONL or Chrome trace_event), self-check
    it against the allocator conservation invariants, and print the
    metrics snapshot when asked (DESIGN.md §10)."""
    import json

    from ..serve.telemetry import check_trace
    if telem.tracer is not None:
        rec = telem.tracer
        if args.trace_format == "chrome":
            rec.write_chrome(args.trace)
        else:
            rec.write_jsonl(args.trace)
        summary = check_trace(rec.events)
        print(f"[serve] trace: {len(rec.events)} events -> {args.trace} "
              f"({args.trace_format}); checker OK — {summary}")
    if args.metrics:
        print("[serve] metrics snapshot:")
        print(json.dumps(telem.metrics.snapshot(), indent=2, sort_keys=True))


def _run_traffic(cfg, sched, args):
    """Open-loop serving on the wall clock: requests arrive whether or not
    the engine has capacity, and the user-visible numbers are latency
    percentiles + goodput-under-SLO (DESIGN.md §9)."""
    from ..serve.traffic import TrafficDriver, make_trace
    trace = make_trace(cfg.vocab, args.requests, rate=args.rate,
                       seed=args.seed, process=args.traffic)
    mix = {}
    for tr in trace:
        mix[tr.profile] = mix.get(tr.profile, 0) + 1
    print(f"[serve] open-loop {args.traffic} traffic: {args.requests} "
          f"requests @ {args.rate:g} req/s, mix {mix}, "
          f"overlap={'on' if args.overlap else 'off'}")
    drv = TrafficDriver(sched, trace)                 # wall clock
    finished = drv.run()
    s = drv.acct.summary(slo_ttft=args.slo_ttft, slo_tpot=args.slo_tpot)
    print(f"[serve] ttft p50={s['ttft_p50']*1e3:.1f}ms "
          f"p99={s['ttft_p99']*1e3:.1f}ms | "
          f"tpot p50={s['tpot_p50']*1e3:.1f}ms "
          f"p99={s['tpot_p99']*1e3:.1f}ms")
    print(f"[serve] throughput={s['throughput_req_s']:.2f}req/s "
          f"({s['throughput_tok_s']:.1f}tok/s) "
          f"slo_attainment={s['slo_attainment']:.2f} "
          f"goodput={s['goodput_req_s']:.2f}req/s")
    return finished


def _run_legacy(cfg, params, prompts, args) -> int:
    from ..serve.paged import PagedServer
    srv = PagedServer(cfg, params, n_pages=1 + args.batch_slots * 32,
                      page_size=8, max_seqs=args.batch_slots)
    pending = [{"id": i, "prompt": p, "out": []}
               for i, p in enumerate(prompts)]
    active = {}
    decoded = 0
    while pending or active:
        while pending and len(active) < args.batch_slots:
            req = pending.pop(0)
            slot = next(s for s in range(args.batch_slots)
                        if s not in active)
            srv.admit(slot)
            active[slot] = {"req": req, "fed": 0}
        slots = sorted(active)
        toks = []
        for s in slots:
            st = active[s]
            seq = st["req"]["prompt"] + st["req"]["out"]
            toks.append(seq[st["fed"]] if st["fed"] < len(seq) else seq[-1])
        logits = srv.decode(jnp.asarray(toks, jnp.int32)[:, None], slots)
        decoded += len(slots)
        nxt = np.asarray(jnp.argmax(logits[:, 0], -1))
        done = []
        for i, s in enumerate(slots):
            st = active[s]
            st["fed"] += 1
            if st["fed"] >= len(st["req"]["prompt"]):
                st["req"]["out"].append(int(nxt[i]))
            if len(st["req"]["out"]) >= args.max_new:
                done.append(s)
        for s in done:
            req = active.pop(s)["req"]
            srv.evict(s)
            print(f"[serve] req {req['id']} done: "
                  f"{req['prompt']} -> {req['out'][:8]}...")
    print(f"[serve] legacy VBI stats {srv.kv.stats}")
    return decoded


if __name__ == "__main__":
    main()
