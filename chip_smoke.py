#!/usr/bin/env python3
"""Chip smoke: the serving path at published widths on a TPU, end to end.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the mesh phase only

Everything runs in this one process, built from the checkout and
``--seed``; the weights are random.

* Phase A: qwen3-0.6b at its published widths (``serve_config(arch,
  smoke=False)``) served through ``Scheduler`` -> ``PagedEngine`` ->
  ``VBIAllocator`` with the launcher's defaults (gather attention, decode
  horizon 8, prefix cache on, page size 8).  Eight requests share a
  64-token system prefix.  Greedy tokens are compared with the
  ``models/model.py`` reference (``prefill`` + ``decode_step``), and the
  engine's first-step logits with the reference's.
* Phase B: the same requests with ``attn_impl="kernel"``: the compiled
  decode step must hold the Pallas kernel (``tpu_custom_call``), and the
  tokens must agree with the reference as Phase A's do and equal Phase A's.
* Phase C: 16 requests of seeded Poisson traffic through ``TrafficDriver``
  with overlap staging and the telemetry trace on; ``check_trace`` must
  replay the trace clean.
* ``--four-chips``: mixtral-8x7b at its published widths, depth cut to 2
  layers, served on a 1x4 (data x model) mesh with expert-parallel MoE
  dispatch and ``kv_layout="auto"``, then on device 0 alone; the tokens
  must be equal, and no device may hold the whole expert set.

Both sides of every comparison run under ``jax.default_matmul_precision(
"highest")``.  A token may differ from the reference only where the
reference's own logits put the two candidates within ``LOGIT_TOL`` (a
near tie that rounding can flip); any such case is printed.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line
of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: |engine - reference| bound on f32 logits at ``highest`` precision.  The
#: gap measured on a v5e is under 1e-5; bf16 arithmetic misses by ~1e-2, so
#: a lower-precision engine fails this check.
LOGIT_TOL = 1e-3
PAGE_SIZE = 8
BATCH_SLOTS = 4
PREFILL_CHUNK = 8
HORIZON = 8
SHARED_PREFIX = 64


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, per phase, read
    from JAX's monitoring events (a cache hit's "compile" is its read)."""

    def __init__(self) -> None:
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs

    def _ev(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.secs, self.hits, self.misses)

    def since(self, mark: tuple) -> str:
        return (f"compile {self.secs - mark[0]:.2f}s, persistent-cache "
                f"hits {self.hits - mark[1]} misses {self.misses - mark[2]}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


# --------------------------------------------------------------------------
# requests, serving and the reference
# --------------------------------------------------------------------------
def make_prompts(vocab: int, n: int, prefix: int, lo: int, hi: int,
                 seed: int):
    """``n`` prompts: one shared ``prefix``-token system prompt plus
    ``lo``..``hi`` seeded tokens each."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, prefix).tolist()
    return [system + rng.integers(0, vocab, int(rng.integers(lo, hi + 1))
                                  ).tolist() for _ in range(n)]


def make_engine(cfg, params, shared_prefix, **kw):
    """A ``PagedEngine`` sized the way the launcher sizes it: 32 pages a
    slot plus the shared prefix."""
    from repro.serve.engine import PagedEngine
    n_pages = 1 + BATCH_SLOTS * (32 + shared_prefix // PAGE_SIZE)
    return PagedEngine(cfg, params, page_size=PAGE_SIZE,
                       max_seqs=BATCH_SLOTS, n_pages=n_pages, **kw)


def serve(engine, prompts, max_new, prefix_cache=None):
    """The served path: ``Scheduler`` over ``engine``; outputs by rid."""
    from repro.serve.scheduler import Scheduler
    sched = Scheduler(engine, prefill_chunk=PREFILL_CHUNK,
                      prefix_cache=prefix_cache, decode_horizon=HORIZON)
    for p in prompts:
        sched.add_request(p, max_new=max_new)
    fin = sched.run()
    if len(fin) != len(prompts):
        raise RuntimeError(f"{len(fin)} of {len(prompts)} requests finished")
    outs = {r.rid: r.out for r in fin}
    short = {rid: len(o) for rid, o in outs.items() if len(o) != max_new}
    if short:
        raise RuntimeError(f"requests ended short of {max_new}: {short}")
    return [outs[i] for i in range(len(prompts))], sched


def reference_greedy(cfg, params, prompts, max_new, top=8):
    """``models/model.py`` greedy decode, one request at a time: whole
    prompt ``prefill`` then ``decode_step``.  Returns the tokens, each
    step's top-``top`` (values, ids), and each first-step logits row."""
    from repro.models.model import decode_step, prefill
    pre = jax.jit(partial(prefill, cfg), static_argnames=("max_len",))
    dec = jax.jit(partial(decode_step, cfg))
    top_k = jax.jit(lambda row: jax.lax.top_k(row, top))
    max_len = max(len(p) for p in prompts) + max_new
    toks, tops, first = [], [], []
    for p in prompts:
        logits, caches = pre(params, {"tokens": jnp.asarray(p, jnp.int32)[None]},
                             max_len=max_len)
        first.append(np.asarray(logits[0, -1]))
        out, steps = [], []
        pos = len(p)
        for t in range(max_new):
            row = logits[0, -1]
            v, i = top_k(row)
            steps.append((np.asarray(v), np.asarray(i)))
            out.append(int(jnp.argmax(row)))
            if t + 1 < max_new:
                logits, caches = dec(params, caches,
                                     jnp.asarray([[out[-1]]], jnp.int32),
                                     jnp.int32(pos))
                pos += 1
        toks.append(out)
        tops.append(steps)
    return toks, tops, first


def compare_tokens(name, got, ref_toks, ref_tops):
    """Tokens must equal the reference's.  Where one differs, the request
    passes only if the reference's logits put the two tokens within
    ``LOGIT_TOL`` at that step (a near tie); the rest of that request is
    then not comparable.  Raises on any other difference."""
    exact, ties, bad = 0, [], []
    for r, (g, w) in enumerate(zip(got, ref_toks)):
        t = next((i for i, (a, b) in enumerate(zip(g, w)) if a != b), None)
        if t is None and len(g) == len(w):
            exact += 1
            continue
        if t is None:
            bad.append(f"req {r}: length {len(g)} vs {len(w)}")
            continue
        vals, ids = ref_tops[r][t]
        hit = np.nonzero(ids == g[t])[0]
        margin = float(vals[0] - vals[hit[0]]) if hit.size else float("inf")
        (ties if margin <= LOGIT_TOL else bad).append(
            f"req {r} step {t}: {g[t]} vs reference {w[t]}, reference "
            f"margin {margin:.3g}")
    log(f"{name}: {exact}/{len(got)} requests token-exact vs reference")
    for s in ties:
        log(f"{name}: near tie admitted (margin <= {LOGIT_TOL:g}): {s}")
    if bad:
        raise AssertionError(f"{name} diverged from the reference: {bad}")
    return exact


def first_step_logits(engine, prompts):
    """Logits of each prompt's first generated token through the engine's
    own ``prefill_chunk`` and ``decode`` programs: all but the last prompt
    token prefilled, the last one decoded.  Leaves the engine's pool as it
    found it."""
    S = engine.max_seqs
    batch = prompts[:S]
    blocks = [engine.alloc.alloc(s) for s in range(len(batch))]
    for blk, p in zip(blocks, batch):
        engine.alloc.reserve(blk, len(p))
    body = [p[:-1] for p in batch]
    for c0 in range(0, max(map(len, body)), PREFILL_CHUNK):
        toks = np.zeros((S, PREFILL_CHUNK), np.int32)
        cnt = np.zeros((S,), np.int32)
        for s, b in enumerate(body):
            seg = b[c0:c0 + PREFILL_CHUNK]
            toks[s, :len(seg)] = seg
            cnt[s] = len(seg)
        engine.prefill_chunk(jnp.array(toks), jnp.array(cnt))
    last = np.zeros((S,), np.int32)
    mask = np.zeros((S,), bool)
    for s, p in enumerate(batch):
        last[s], mask[s] = p[-1], True
    logits = np.asarray(engine.decode(jnp.array(last), jnp.array(mask)))
    for blk in blocks:
        engine.alloc.free(blk)
    return logits[:len(batch), 0]


def decode_program_text(engine, k: int) -> str:
    """Optimized HLO of the engine's K-step decode program, as it ran."""
    S = engine.max_seqs
    z = jnp.zeros((S,), jnp.int32)
    return engine._horizon_fn(k).lower(
        engine.params, engine.state, z, jnp.zeros((S,), bool), z
    ).compile().as_text()


# --------------------------------------------------------------------------
# the phases
# --------------------------------------------------------------------------
def phase_a(cfg, params, prompts, max_new, ref):
    """Served path, gather attention, prefix cache on."""
    from repro.serve.prefix_cache import PrefixCache
    ref_toks, ref_tops, ref_first = ref
    eng = make_engine(cfg, params, SHARED_PREFIX)
    got = first_step_logits(eng, prompts)
    err = float(np.abs(got - np.stack(ref_first[:len(got)])).max())
    log(f"phase A: first-step logits of {len(got)} requests, max "
        f"|engine - reference| = {err:.3g} (tolerance {LOGIT_TOL:g})")
    if not err <= LOGIT_TOL:
        raise AssertionError(f"phase A logits off by {err} > {LOGIT_TOL}")
    cache = PrefixCache(page_size=PAGE_SIZE)
    toks = serve(eng, prompts, max_new, prefix_cache=cache)[0]
    log(f"phase A: {len(toks)} requests finished, {max_new} tokens each; "
        f"prefix cache {cache.stats}")
    if cache.stats["hits"] == 0:
        raise AssertionError("phase A: the prefix cache reported no hits")
    compare_tokens("phase A", toks, ref_toks, ref_tops)
    return toks


def phase_b(cfg, params, prompts, max_new, ref, toks_a):
    """Same requests through the Pallas paged-attention kernel; returns
    whether the compiled decode program holds a ``tpu_custom_call``."""
    from repro.serve.prefix_cache import PrefixCache
    eng = make_engine(cfg, params, SHARED_PREFIX, attn_impl="kernel")
    toks = serve(eng, prompts, max_new,
                 prefix_cache=PrefixCache(page_size=PAGE_SIZE))[0]
    compare_tokens("phase B", toks, ref[0], ref[1])
    same = sum(a == b for a, b in zip(toks, toks_a))
    log(f"phase B: {same}/{len(toks)} requests token-equal to phase A")
    if same != len(toks):
        raise AssertionError("phase B: the kernel path's tokens differ "
                             "from the gather path's")
    custom = "tpu_custom_call" in decode_program_text(eng, HORIZON)
    log(f"phase B: tpu_custom_call in the compiled decode program: {custom}")
    return custom


def phase_c(cfg, params, n_requests, rate, seed):
    """Open-loop Poisson traffic, overlap staging, traced: every request
    finishes and the trace replays clean through ``check_trace``."""
    from repro.serve.prefix_cache import PrefixCache
    from repro.serve.scheduler import Scheduler
    from repro.serve.telemetry import Telemetry, check_trace
    from repro.serve.traffic import MIXED_PROFILES, TrafficDriver, make_trace
    trace = make_trace(cfg.vocab, n_requests, rate=rate, seed=seed)
    eng = make_engine(cfg, params,
                      max(p.shared_prefix for p in MIXED_PROFILES))
    telem = Telemetry(trace=True)
    sched = Scheduler(eng, prefill_chunk=PREFILL_CHUNK,
                      prefix_cache=PrefixCache(page_size=PAGE_SIZE),
                      decode_horizon=HORIZON, overlap=True,
                      telemetry=telem)
    drv = TrafficDriver(sched, trace)
    fin = drv.run()
    if len(fin) != n_requests:
        raise RuntimeError(f"phase C: {len(fin)} of {n_requests} finished")
    checked = check_trace(telem.tracer.events)
    log(f"phase C: {n_requests} requests at {rate:g} req/s finished; trace "
        f"of {len(telem.tracer.events)} events replays clean: {checked}")
    s = drv.acct.summary()
    log(f"phase C smoke timings, not benchmark numbers: TTFT p50 "
        f"{s['ttft_p50']:.4f}s p99 {s['ttft_p99']:.4f}s, TPOT p50 "
        f"{s['tpot_p50']:.4f}s p99 {s['tpot_p99']:.4f}s")


def four_chips(seed: int) -> None:
    """mixtral-8x7b (2 layers) on a 1x4 mesh, expert-parallel, against the
    same config on device 0 alone."""
    from repro.distributed.sharding import init_sharded_params
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import serve_config
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    full = serve_config("mixtral-8x7b", smoke=False)
    cfg = dataclasses.replace(full, n_layers=2)
    log(f"config {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv}, expert_d_ff {cfg.expert_d_ff}, experts "
        f"{cfg.n_experts} top-{cfg.top_k}, window {cfg.window}, vocab "
        f"{cfg.vocab}, {cfg.param_dtype}; reduced: n_layers "
        f"{full.n_layers} -> {cfg.n_layers}")
    prompts = make_prompts(cfg.vocab, 4, 16, 16, 32, seed)
    max_new = 16
    key = jax.random.key(seed)

    meter = CompileMeter()
    m = meter.mark()
    t0 = time.perf_counter()
    mesh = make_host_mesh(data=1, model=4, devices=devs[:4])
    params = init_sharded_params(cfg, key, mesh)
    eng = make_engine(cfg, params, 0, mesh=mesh, kv_layout="auto")
    toks_mesh = serve(eng, prompts, max_new)[0]
    w1 = eng.params["stages"][0][0]["moe"]["w1"]
    held = {s.device.id: int(s.data.shape[1]) for s in w1.addressable_shards}
    peaks = {d.id: peak_bytes(d) for d in devs[:4]}
    log(f"mesh 1x4: kv_layout={eng.kv_layout}, placement {eng.placement}, "
        f"{time.perf_counter() - t0:.1f}s, {meter.since(m)}")
    log(f"mesh 1x4: experts held per device (of {cfg.n_experts}): {held}")
    log(f"mesh 1x4: peak_bytes_in_use per device: {peaks}")
    if max(held.values()) >= cfg.n_experts:
        raise AssertionError(f"a device holds every expert: {held}")
    del eng, params, w1
    gc.collect()

    m = meter.mark()
    t0 = time.perf_counter()
    one = make_host_mesh(data=1, model=1, devices=devs[:1])
    params = init_sharded_params(cfg, key, one)
    eng = make_engine(cfg, params, 0)
    toks_one = serve(eng, prompts, max_new)[0]
    mem = eng._horizon_fn(HORIZON).lower(
        eng.params, eng.state, *(jnp.zeros((BATCH_SLOTS,), t)
                                 for t in (jnp.int32, bool, jnp.int32))
    ).compile().memory_analysis()
    log(f"device 0 alone: {time.perf_counter() - t0:.1f}s, "
        f"{meter.since(m)}; decode program arguments "
        f"{mem.argument_size_in_bytes} B, temporaries "
        f"{mem.temp_size_in_bytes} B; peak_bytes_in_use "
        f"{peak_bytes(devs[0])}")
    same = sum(a == b for a, b in zip(toks_mesh, toks_one))
    log(f"mesh vs device 0: {same}/{len(prompts)} requests token-equal")
    if same != len(prompts):
        raise AssertionError(f"mesh tokens {toks_mesh} != one-chip "
                             f"tokens {toks_one}")


def run_one_chip(seed: int) -> None:
    """Phases A, B and C on qwen3-0.6b at its published widths."""
    from repro.launch.serve import serve_config
    from repro.models.model import init_params
    dev = jax.devices()[0]
    cfg = serve_config("qwen3-0.6b", smoke=False)
    log(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv} x {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, tied {cfg.tie_embeddings}, {cfg.param_dtype}")
    meter = CompileMeter()
    prompts = make_prompts(cfg.vocab, 8, SHARED_PREFIX, 16, 48, seed)
    max_new = 32

    def timed(name, fn):
        m, t0 = meter.mark(), time.perf_counter()
        out = fn()
        log(f"{name}: {time.perf_counter() - t0:.1f}s, {meter.since(m)}, "
            f"peak_bytes_in_use {peak_bytes(dev)}")
        return out

    params = timed("init", lambda: jax.jit(partial(init_params, cfg))(
        jax.random.key(seed)))
    ref = timed("reference", lambda: reference_greedy(cfg, params, prompts,
                                                      max_new))
    toks_a = timed("phase A", lambda: phase_a(cfg, params, prompts, max_new,
                                              ref))
    custom = timed("phase B", lambda: phase_b(cfg, params, prompts, max_new,
                                              ref, toks_a))
    if not custom:
        raise AssertionError("phase B: the kernel path ran interpreted")
    timed("phase C", lambda: phase_c(cfg, params, 16, 8.0, seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mixtral mesh phase")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    with jax.default_matmul_precision("highest"):
        if args.four_chips:
            four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
