"""Mesh-sharded paged serving: decode scaling over host-device meshes.

ISSUE 10 tentpole bench.  The parent process spawns one worker
subprocess per mesh size n ∈ {1, 2, 4}, each with
``XLA_FLAGS=--xla_force_host_platform_device_count=n`` set *before* jax
imports (device count is fixed at backend init, so sizes cannot share a
process).  Every worker serves the SAME seeded closed-loop workload
through a mesh-sharded :class:`~repro.serve.engine.PagedEngine`
(1×n ``(data, model)`` mesh, ``kv_layout='auto'``) and reports decode
tok/s plus every request's output tokens.

The parent then assembles ``BENCH_serving.json::mesh``:

  * **scaling** — decode tok/s per mesh size, for a dense arch
    (qwen3-0.6b) and an MoE arch (mixtral-8x7b, real expert-parallel
    dispatch inside the fused decode scan);
  * **outputs_match** — per size, tokens bit-identical to the 1-device
    engine (sharding is a layout property, never a value change);
  * **comms share** — the hlo_cost-predicted collective share from the
    engine's layout probe vs the measured parallel-overhead share
    ``1 - tok_s_n / (n · tok_s_1)``;
  * **mixtral EP** — per-device expert FLOPs of the EP decode-shape MoE
    vs the dense (replicated) path, from
    :func:`~repro.distributed.hlo_cost.analyze_hlo` on the compiled HLO.

The largest worker records a telemetry trace (under
``benchmarks/results/``) whose ``place`` events carry the full mesh
placement; the parent replays it through the offline checker.

CPU only.  ``--xla_force_host_platform_device_count`` fakes devices on the
CPU backend and means nothing on a TPU host: there every worker sees the
host's real chips, and since the parent never initialises a backend the
workers do not fight it for them.  Its timings are CPU timings, not device
numbers; ``chip_smoke.py --four-chips`` is what runs the mesh path on
chips, in one process.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from .common import RESULTS, emit

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# worker: one mesh size, one process
# ---------------------------------------------------------------------------

def _worker(model_axis: int, n_requests: int, seed: int, max_new: int,
            trace_path: "str | None") -> dict:
    import jax
    import numpy as np

    from repro.distributed.axes import logical_axes
    from repro.distributed.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import serve_config
    from repro.models.layers import moe
    from repro.models.model import init_params
    from repro.serve.engine import PagedEngine
    from repro.serve.scheduler import Scheduler
    from repro.serve.telemetry import Telemetry

    assert jax.device_count() >= model_axis, \
        f"worker needs {model_axis} devices (XLA_FLAGS not inherited?)"
    mesh = make_host_mesh(data=1, model=model_axis)

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, 50, size=rng.randint(4, 12)).tolist()
               for _ in range(n_requests)]

    report: dict = {"model_axis": model_axis, "archs": {}}
    for arch in ("qwen3-0.6b", "mixtral-8x7b"):
        cfg = serve_config(arch)
        params = init_params(cfg, jax.random.key(0))
        eng = PagedEngine(cfg, params, n_pages=65, page_size=8,
                          max_seqs=4, max_pages_per_seq=8,
                          mesh=mesh, kv_layout="auto")
        telem = None
        if trace_path and arch == "mixtral-8x7b":
            telem = Telemetry(trace=True)

        def run(telemetry=None):
            sched = Scheduler(eng, prefill_chunk=8, decode_horizon=8,
                              telemetry=telemetry)
            for rid, p in enumerate(prompts):
                sched.add_request(p, max_new, rid=rid)
            t0 = time.perf_counter()
            fin = sched.run()
            dt = time.perf_counter() - t0
            return dt, {str(r.rid): list(map(int, r.out)) for r in fin}

        run()                                   # compile + warm
        dts = []
        for _ in range(3):
            dt, outs = run()
            dts.append(dt)
        if telem is not None:
            _, outs = run(telem)
            telem.tracer.write_jsonl(trace_path)
            eng.alloc.attach_tracer(None)
        new_tok = sum(len(o) for o in outs.values())
        tok_s = new_tok / min(dts)
        chosen = eng.kv_layout
        cand = (eng.layout_report or {}).get("candidates", {}).get(
            chosen or "", {})
        report["archs"][arch] = {
            "tok_s": tok_s, "new_tokens": new_tok,
            "kv_layout": chosen,
            "predicted_comms_share": cand.get("predicted_comms_share", 0.0),
            "placement": list(eng.placement),
            "outputs": outs,
        }

    # -- mixtral per-device expert FLOPs: EP decode-shape vs dense ----------
    import dataclasses
    cfg = serve_config("mixtral-8x7b")
    E, K = cfg.n_experts, cfg.top_k
    cfg = dataclasses.replace(cfg, capacity_factor=max(
        cfg.capacity_factor, E / K))            # the engine's serve bump
    d = cfg.d_model
    ff = cfg.expert_d_ff or cfg.d_ff
    k0 = jax.random.key(1)
    ks = jax.random.split(k0, 4)
    mp = {"router": jax.random.normal(ks[0], (d, E)),
          "w1": jax.random.normal(ks[1], (E, d, ff)),
          "w3": jax.random.normal(ks[2], (E, d, ff)),
          "w2": jax.random.normal(ks[3], (E, ff, d))}
    x = jax.numpy.zeros((4, 1, d))              # decode shape [slots, 1, d]

    # distinct function objects: jax.jit keys its trace cache on the
    # function identity, and moe() reads the logical_axes contextvar at
    # trace time — one shared `f` would serve the dense trace to both
    def f_dense(p, xx):
        return moe(p, xx, cfg)

    def f_ep(p, xx):
        return moe(p, xx, cfg)

    dense_txt = jax.jit(f_dense).lower(mp, x).compile().as_text()
    with logical_axes(mesh, cfg.n_experts):
        ep_txt = jax.jit(f_ep).lower(mp, x).compile().as_text()
    report["moe_flops"] = {
        "dense_per_device": analyze_hlo(dense_txt)["flops"],
        "ep_per_device": analyze_hlo(ep_txt)["flops"],
    }
    return report


# ---------------------------------------------------------------------------
# parent: spawn one worker per mesh size, assemble the section
# ---------------------------------------------------------------------------

def bench_mesh(sizes=(1, 2, 4), n_requests: int = 6, seed: int = 0,
               max_new: int = 24,
               trace_path: "str | None" = None) -> "tuple[list[str], dict]":
    reports = {}
    for n in sizes:
        out = RESULTS / f"mesh_worker_{n}.json"
        env = dict(os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        cmd = [sys.executable, "-m", "benchmarks.bench_mesh", "--worker",
               "--model-axis", str(n), "--out", str(out),
               "--requests", str(n_requests), "--seed", str(seed),
               "--max-new", str(max_new)]
        if trace_path and n == max(sizes):
            cmd += ["--worker-trace", str(trace_path)]
        subprocess.run(cmd, cwd=REPO, env=env, check=True)
        reports[n] = json.loads(out.read_text())

    base = min(sizes)
    lines, results = [], {"sizes": {}, "moe_flops": {}}
    for n in sizes:
        rep = reports[n]
        entry = {"archs": {}}
        for arch, r in rep["archs"].items():
            ref = reports[base]["archs"][arch]
            match = r["outputs"] == ref["outputs"]
            tok_s1 = ref["tok_s"]
            measured = max(0.0, 1.0 - r["tok_s"] / (n * tok_s1 / base))
            entry["archs"][arch] = {
                "tok_s": r["tok_s"],
                "outputs_match": match,
                "kv_layout": r["kv_layout"],
                "predicted_comms_share": r["predicted_comms_share"],
                "measured_comms_share": measured,
                "placement": r["placement"],
            }
            lines.append(emit(
                f"serve_mesh_{arch}_n{n}",
                1e6 / max(r["tok_s"], 1e-9),
                f"tok_s={r['tok_s']:.1f} outputs_match={match} "
                f"layout={r['kv_layout']} "
                f"comms_pred={r['predicted_comms_share']:.3f} "
                f"comms_meas={measured:.3f}"))
        entry["outputs_match"] = all(
            a["outputs_match"] for a in entry["archs"].values())
        results["sizes"][str(n)] = entry

        mf = rep["moe_flops"]
        results["moe_flops"][str(n)] = mf
        lines.append(emit(
            f"moe_decode_flops_n{n}", 0.0,
            f"dense/device={mf['dense_per_device']:.3g} "
            f"ep/device={mf['ep_per_device']:.3g} "
            f"ratio={mf['ep_per_device'] / max(mf['dense_per_device'], 1):.3f}"))

    if trace_path and Path(trace_path).exists():
        from repro.serve.telemetry import check_trace, read_jsonl
        check_trace(read_jsonl(str(trace_path)))
        results["trace_checked"] = True
        lines.append(emit("mesh_trace_check", 0.0,
                          f"events_ok trace={trace_path}"))
    return lines, results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sizes", default="1,2,4",
                    help="comma-separated model-axis sizes")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--trace", metavar="OUT.jsonl",
                    default=str(RESULTS / "serve_trace_mesh.jsonl"),
                    help="telemetry trace from the largest worker "
                         "(verify with python -m repro.serve.telemetry)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--model-axis", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker-trace", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker:
        rep = _worker(args.model_axis, args.requests, args.seed,
                      args.max_new, args.worker_trace)
        Path(args.out).write_text(json.dumps(rep))
        sys.exit(0)

    sizes = tuple(int(s) for s in args.sizes.split(","))
    print("name,us_per_call,derived")
    lines, results = bench_mesh(sizes=sizes, n_requests=args.requests,
                                seed=args.seed, max_new=args.max_new,
                                trace_path=args.trace)
    if args.smoke:
        from .bench_lm_serving import write_bench_json
        write_bench_json({"mesh": results})
