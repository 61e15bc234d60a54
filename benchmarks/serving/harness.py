"""One run of one cell: set-up, the measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Everything a cell needs is found by name from its entry in
``BENCHMARK.json``: the configuration file (``configs/<name>.json``, with
its reference module ``configs/<reference>.py``), the traffic mix
(``traffic/<name>.json``), one reader per metric (``metrics/<name>.py``)
and the limits of the comparison (``limits/<cell>.json``).

The program under test is driven through its normal path, built the way
``repro.launch.serve`` builds it: ``Scheduler`` -> ``PagedEngine`` ->
``VBIAllocator``, with the serving defaults read from
``launch/serve.py::build_parser()``.  The harness calls
``Scheduler.add_request`` and ``Scheduler.step`` itself, with the
scheduler's streaming callbacks, and wraps ``Scheduler.step`` and the
engine's ``prefill_chunk`` and ``decode_many`` in profiler annotations.
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CLOCK = time.perf_counter
WINDOW = "window"              # host span around the measured window
SAMPLE_TOKENS = 512            # served tokens the comparison reads at least
SAMPLE_MAX = 16                # requests it reads at most
SPANS = {"step": "tick", "prefill_chunk": "prefill_chunk",
         "decode_many": "decode_many", "wait": "wait_for_arrival",
         "submit": "submit"}

from traffic.generator import (Timing, closed_streams, load_mix,  # noqa: E402
                               open_schedule, percentile, system_prompts,
                               warmup_seconds)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# the cell, from BENCHMARK.json
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    hp: Dict                    # the configuration file
    mix: Dict                   # the traffic mix
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    ws = {w["name"]: w for w in bench["workloads"]}
    if name not in ws:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(ws)}")
    w = ws[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    hp = json.loads((root / conf["file"]).read_text())
    mix = load_mix(w["traffic"], here / "traffic")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    lim_path = here / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.is_file() else {}
    return Cell(name, int(w["chips"]), hp, mix, e2e, per, limits)


def reference_module(hp: Dict, here: Path = HERE):
    return load_module(here / "configs" / f"{hp['reference']}.py",
                       f"bench_ref_{hp['reference']}")


def serve_defaults() -> Dict:
    """The launcher's defaults for what a cell does not choose."""
    from repro.launch.serve import build_parser
    a = build_parser().parse_args([])
    return dict(decode_horizon=a.decode_horizon,
                prefill_chunk=a.prefill_chunk, attn_impl=a.attn_impl,
                kv_layout=a.kv_layout, overlap=a.overlap)


def model_config(hp: Dict):
    """The program's ``ModelConfig`` for the configuration file: the
    program's serve config of ``program_config`` (which fixes the dtypes
    the engine serves in) with every shape the file states."""
    from repro.launch.serve import serve_config
    base = serve_config(hp["program_config"], smoke=False)
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    upd = dict(n_layers=hp["num_hidden_layers"], d_model=d, n_heads=H,
               n_kv=hp["num_key_value_heads"],
               head_dim=hp.get("head_dim") or d // H,
               vocab=hp["vocab_size"], norm_eps=float(hp["rms_norm_eps"]),
               rope_theta=float(hp["rope_theta"]),
               tie_embeddings=bool(hp["tie_word_embeddings"]),
               window=int(hp.get("sliding_window") or 0),
               qk_norm=hp["model_type"] == "qwen3")
    if hp.get("num_local_experts"):
        upd.update(n_experts=hp["num_local_experts"],
                   top_k=hp["num_experts_per_tok"],
                   expert_d_ff=hp["intermediate_size"], d_ff=0)
    else:
        upd.update(d_ff=hp["intermediate_size"], n_experts=0, top_k=0)
    return dataclasses.replace(base, **upd)


# --------------------------------------------------------------------------
# compile accounting (after chip_smoke.py::CompileMeter)
# --------------------------------------------------------------------------
class CompileMeter:
    """Backend compiles, traces and persistent-cache hits and misses, read
    from JAX's monitoring events; ``window`` counts them apart."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}
    DURATIONS = {"/jax/core/compile/backend_compile_duration": "compiles",
                 "/jax/core/compile/jaxpr_trace_duration": "traces"}

    _listening: Optional["CompileMeter"] = None

    def __init__(self) -> None:
        import jax
        self.setup = {k: 0 for k in ("cache_hits", "cache_misses",
                                     "compiles", "traces")}
        self.setup["compile_s"] = 0.0
        self.in_window = dict(self.setup)
        self.active = self.setup
        if CompileMeter._listening is None:
            jax.monitoring.register_event_listener(
                lambda *a, **k: CompileMeter._listening._ev(*a, **k))
            jax.monitoring.register_event_duration_secs_listener(
                lambda *a, **k: CompileMeter._listening._dur(*a, **k))
        CompileMeter._listening = self

    def _ev(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.active[self.EVENTS[event]] += 1

    def _dur(self, event: str, duration_secs: float, **_) -> None:
        if event in self.DURATIONS:
            self.active[self.DURATIONS[event]] += 1
            if event.endswith("backend_compile_duration"):
                self.active["compile_s"] += duration_secs


# --------------------------------------------------------------------------
# the served system
# --------------------------------------------------------------------------
class Served:
    """The program under test, built through its normal path."""

    def __init__(self, cell: Cell, seed: int, trace: bool) -> None:
        import jax
        from repro.serve.engine import PagedEngine
        from repro.serve.prefix_cache import PrefixCache
        from repro.serve.scheduler import Scheduler
        from repro.serve.telemetry import Telemetry
        hp, dep = cell.hp, cell.hp["deployment"]
        self.cfg = model_config(hp)
        self.devices = jax.devices()[:cell.chips]
        ref = reference_module(hp)
        mesh, shardings = None, None
        if cell.chips > 1:
            from repro.distributed.sharding import param_specs, shardings_of
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(data=1, model=cell.chips,
                                  devices=self.devices)
            shardings = shardings_of(param_specs(
                self.cfg, ref.program_shapes(hp), mesh), mesh)
        params = ref.program_params(hp, seed, shardings)
        self.defaults = serve_defaults()
        ps = dep["page_size"]
        self.max_pages = -(-cell.mix["max_context"] // ps)
        self.engine = PagedEngine(
            self.cfg, params, n_pages=dep["pool_pages"], page_size=ps,
            max_seqs=dep["slots"], max_pages_per_seq=self.max_pages,
            attn_impl=self.defaults["attn_impl"], mesh=mesh,
            kv_layout=self.defaults["kv_layout"])
        del params
        cache = PrefixCache(page_size=ps) if dep["prefix_cache"] else None
        self.telemetry = (Telemetry(trace=True, clock=CLOCK) if trace
                          else None)
        self.sched = Scheduler(
            self.engine, prefill_chunk=self.defaults["prefill_chunk"],
            prefix_cache=cache,
            decode_horizon=self.defaults["decode_horizon"],
            overlap=self.defaults["overlap"], telemetry=self.telemetry)

    def free(self) -> None:
        """Drop every device buffer the program holds."""
        self.sched = None
        self.engine = None
        self.telemetry = None
        gc.collect()


class Recorder:
    """Wraps the engine's fast paths in profiler annotations and, in a
    traced run, records the work of each dispatch: the context length of
    every token computed."""

    def __init__(self, served: Served, trace: bool) -> None:
        import jax
        self.trace = trace
        self.active = False
        self.prefill_ctx: List[int] = []       # per prefilled token
        self.decode_steps: List[List[int]] = []  # per fused step: ctxs
        eng, sched = served.engine, served.sched
        ann = jax.profiler.TraceAnnotation
        pre, dec = eng.prefill_chunk, eng.decode_many

        def prefill_chunk(tokens, n_tokens):
            if self.trace and self.active:
                counts = np.asarray(n_tokens)
                for s, st in sched.slots.items():
                    n = int(counts[s])
                    self.prefill_ctx.extend(range(st.fed + 1, st.fed + n + 1))
            with ann(SPANS["prefill_chunk"]):
                return pre(tokens, n_tokens)

        def decode_many(tokens, slot_mask, steps_left, k):
            if self.trace and self.active:
                steps = np.asarray(steps_left)
                mask = np.asarray(slot_mask)
                for j in range(k):
                    self.decode_steps.append(
                        [sched.slots[s].fed + j + 1
                         for s in np.nonzero(mask)[0] if steps[s] > j])
            with ann(SPANS["decode_many"]):
                return dec(tokens, slot_mask, steps_left, k)

        eng.prefill_chunk = prefill_chunk
        eng.decode_many = decode_many


# --------------------------------------------------------------------------
# warm-up
# --------------------------------------------------------------------------
def horizon_ladder(k: int) -> List[int]:
    """The horizons the scheduler can dispatch: it halves K from its
    default under pressure and near the end of generation."""
    out = []
    while k >= 1:
        out.append(k)
        k //= 2
    return out


def warm_programs(served: Served, vocab: int, seed: int) -> None:
    """Compile every program the window can run, once: the prefill chunk,
    each decode horizon of the ladder, and (with the prefix cache) a
    full-page share, a copy-on-write partial share and an eviction."""
    sched, eng = served.sched, served.engine
    rng = np.random.default_rng([int(seed) % (2 ** 63), 7])
    ps = eng.page_size
    base = rng.integers(0, vocab, 3 * ps + 3).tolist()
    for i, k in enumerate(horizon_ladder(served.defaults["decode_horizon"])):
        prompt = (base if i == 0 else
                  base[:2 * ps + 5] + rng.integers(0, vocab, ps + i).tolist())
        sched.add_request(prompt, max_new=k + 1)
        sched.run()
    cache = sched.prefix_cache
    if cache is not None:
        sched._evict_cache(cache.evictable_pages)
        assert cache.n_pages == 0, "warm-up left pages in the prefix cache"


def warm_cache(served: Served, mix: Dict, vocab: int, seed: int) -> None:
    """Put the mix's shared system prompts in the prefix cache, all at
    once, as a server that has been running holds them."""
    sched = served.sched
    prompts = system_prompts(mix, vocab, seed)
    if sched.prefix_cache is None or not prompts:
        return
    for p in prompts:
        sched.add_request(p.tolist(), max_new=1)
    sched.run()


# --------------------------------------------------------------------------
# the load generator
# --------------------------------------------------------------------------
class Driver:
    """Offers the mix to the scheduler on the host clock and timestamps
    every token through the scheduler's streaming callbacks."""

    def __init__(self, served: Served, mix: Dict, vocab: int, seed: int,
                 horizon_s: float) -> None:
        import jax
        self.sched = served.sched
        self.ann = jax.profiler.TraceAnnotation
        self.mix = mix
        self.closed = mix["loop"] == "closed"
        self.timing: Dict[int, Timing] = {}
        self.delivered: List = []            # (t, n)
        self.failed = 0
        self.late: List = []                 # (due, seconds sent late)
        self.sched.on_tokens = self._on_tokens
        self.sched.on_finish = self._on_finish
        self.heap: List = []                 # (due, seq, request)
        self._seq = 0
        if self.closed:
            self.streams = closed_streams(mix, vocab, seed,
                                          mix["requests_per_client"])
            self.next = [0] * len(self.streams)
            self.client_of: Dict[int, int] = {}
        else:
            n = int(mix["rate"] * horizon_s * 1.5) + 64
            self.schedule = open_schedule(mix, vocab, seed, mix["rate"], n)

    def start(self, t: float) -> None:
        if self.closed:
            for c in range(len(self.streams)):
                self._queue_next(c, t)
        else:
            for r in self.schedule:
                self._push(t + r.arrival, r)

    def _push(self, due: float, r) -> None:
        heapq.heappush(self.heap, (due, self._seq, r))
        self._seq += 1

    def _queue_next(self, client: int, t: float) -> None:
        i = self.next[client]
        if i < len(self.streams[client]):
            r = self.streams[client][i]
            self.next[client] = i + 1
            self._push(t + r.think, r)

    def _on_tokens(self, req, n: int) -> None:
        t = CLOCK()
        tm = self.timing[req.rid]
        if tm.first is None:
            tm.first = t
        tm.last = t
        tm.n_tokens += n
        self.delivered.append((t, n))

    def _on_finish(self, req) -> None:
        t = CLOCK()
        self.timing[req.rid].finish = t
        if self.closed:
            self._queue_next(self.client_of[req.rid], t)

    def _submit_due(self, now: float) -> None:
        while self.heap and self.heap[0][0] <= now:
            due, _, r = heapq.heappop(self.heap)
            try:
                rid = self.sched.add_request(r.prompt.tolist(), r.max_new)
            except ValueError as e:
                self.failed += 1
                log(f"request refused: {e}")
                continue
            self.timing[rid] = Timing(arrival=due)
            self.late.append((due, now - due))
            if self.closed:
                self.client_of[rid] = r.client

    def run_until(self, t_end: float) -> None:
        sched = self.sched
        while True:
            now = CLOCK()
            if now >= t_end:
                return
            with self.ann(SPANS["submit"]):
                self._submit_due(now)
            if sched.queue or sched.slots:
                with self.ann(SPANS["step"]):
                    sched.step()
            else:
                nxt = self.heap[0][0] if self.heap else t_end
                with self.ann(SPANS["wait"]):
                    time.sleep(max(0.0, min(nxt, t_end) - CLOCK()))


# --------------------------------------------------------------------------
# what a metric reader is given
# --------------------------------------------------------------------------
@dataclasses.dataclass
class RunData:
    cell: Cell
    seconds: float
    t0: float
    t_end: float
    setup_s: float
    timings: List[Timing]
    delivered: List
    sched_stats: Dict[str, float]
    engine_stats: Dict[str, float]
    max_seqs: int
    chips: int
    ticks: List[Dict] = dataclasses.field(default_factory=list)
    events: List[Dict] = dataclasses.field(default_factory=list)
    device: Optional[Dict] = None
    prefill_ctx: List[int] = dataclasses.field(default_factory=list)
    decode_steps: List[List[int]] = dataclasses.field(default_factory=list)
    peak: Optional[Dict] = None

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t < self.t_end


def read_metric(entry: Dict, run: RunData, here: Path = HERE):
    mod = load_module(here / "metrics" / f"{entry['name']}.py",
                      "bench_metric_" + entry["name"].replace(".", "_"))
    return mod.read(run)


def _stats(obj) -> Dict[str, float]:
    return {k: float(obj[k]) for k in list(obj.keys())}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------
def pick_sample(finished, rng: np.random.Generator) -> List:
    """The longest finished request, then others drawn from the seed,
    until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX`` requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(len(r.prompt) + len(r.out)),
                                            r.rid))
    out, rest = [order[0]], order[1:]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    n = len(order[0].out)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.out)
    return out


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    g = np.asarray(gaps, np.float64)
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "off_best_share": float((g > 0).mean())}


def compare(cell: Cell, seed: int, sample, control: bool = False,
            device=None) -> Dict:
    """Reference (and control) over the sampled requests: for every served
    position, the gap between the reference's best logit and the served
    token's.  Returns the statistics of the program's and, with
    ``control``, of the control's gaps."""
    ref_mod = reference_module(cell.hp)
    ref = ref_mod.Reference(cell.hp, seed, cell.mix["max_context"],
                            device=device)
    seqs = [(np.asarray(r.prompt + r.out, np.int32), len(r.prompt) - 1)
            for r in sample]
    res = ref.run(seqs, control=control)
    out = {"requests": len(sample),
           "tokens": int(sum(len(g["served_gap"]) for g in res))}
    out["program"] = gap_stats(np.concatenate([g["served_gap"]
                                               for g in res]))
    if control:
        out["control"] = gap_stats(np.concatenate([g["control_gap"]
                                                   for g in res]))
    return out


def judge(limits: Dict, stats: Dict) -> Dict[str, Dict]:
    """Each compared number beside its limit."""
    out = {}
    for name, lim in limits.items():
        if name.startswith("_"):
            continue
        v = stats[name]
        out[name] = {"value": v, "limit": lim["limit"],
                     "ok": bool(v <= lim["limit"])}
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> Dict:
    import jax
    meter = CompileMeter()
    served = Served(cell, seed, trace)
    rec = Recorder(served, trace)
    vocab = cell.hp["vocab_size"]
    warm_programs(served, vocab, seed)
    t_c = CLOCK()
    warm_cache(served, cell.mix, vocab, seed)
    log(f"system prompts cached in {CLOCK() - t_c:.3f}s")
    warmup_s = warmup_seconds(cell.mix)
    drv = Driver(served, cell.mix, vocab, seed, warmup_s + seconds)
    profile_dir = None
    if trace:
        profile_dir = tempfile.mkdtemp(prefix="bench_profile_")
    t_w = CLOCK()
    drv.start(t_w)
    drv.run_until(t_w + warmup_s)
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # spans, not every Python call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    sched0 = _stats(served.sched.stats)
    eng0 = _stats(served.engine.stats)
    meter.active = meter.in_window
    rec.active = True
    ticks: List[Dict] = []
    t0 = t_w + warmup_s          # as scheduled: a late tick shifts no arrival
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation(WINDOW):
        if trace:
            _run_traced(drv, served, t0 + seconds, ticks)
        else:
            drv.run_until(t0 + seconds)
        t_end = t0 + seconds
    jax.block_until_ready(served.engine.state)
    rec.active = False
    meter.active = meter.setup
    sched1 = _stats(served.sched.stats)
    eng1 = _stats(served.engine.stats)
    device = None
    if trace:
        t_r = CLOCK()
        jax.profiler.stop_trace()
        t_l = CLOCK()
        from trace_reduce import load, reduce
        names = set(SPANS.values()) | {WINDOW}
        device = reduce(load(profile_dir, names), span_names=names)
        shutil.rmtree(profile_dir, ignore_errors=True)
        log(f"trace written in {t_l - t_r:.3f}s, read and reduced in "
            f"{CLOCK() - t_l:.3f}s")
    peak_mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in served.devices)
    events = (served.telemetry.tracer.events if trace else [])
    kind = served.devices[0].device_kind
    from counts import peaks
    run = RunData(
        cell=cell, seconds=seconds, t0=t0, t_end=t_end, setup_s=setup_s,
        timings=list(drv.timing.values()), delivered=drv.delivered,
        sched_stats={k: sched1[k] - sched0.get(k, 0) for k in sched1},
        engine_stats={k: eng1[k] - eng0.get(k, 0) for k in eng1},
        max_seqs=served.engine.max_seqs, chips=cell.chips, ticks=ticks,
        events=events, device=device, prefill_ctx=rec.prefill_ctx,
        decode_steps=rec.decode_steps, peak=peaks(kind) if trace else None)
    finished = [r for r in served.sched.finished
                if r.rid in drv.timing and run.in_window(
                    drv.timing[r.rid].finish)]
    attempted = sum(1 for tm in run.timings if run.in_window(tm.arrival))
    log(f"window {seconds:g}s: {attempted} requests arrived, "
        f"{len(finished)} finished, "
        f"{sum(n for t, n in drv.delivered if run.in_window(t))} tokens "
        f"delivered; scheduler {run.sched_stats}")
    log(f"set-up {setup_s:.3f}s: {meter.setup}")
    log(f"compiles inside the window: {meter.in_window['compiles']} "
        f"(traces {meter.in_window['traces']}, cache hits "
        f"{meter.in_window['cache_hits']}, misses "
        f"{meter.in_window['cache_misses']})")
    _log_latency(run)
    late = [x for due, x in drv.late if run.in_window(due)]
    if late:
        log(f"generator lateness p50 {percentile(late, 50)} p90 "
            f"{percentile(late, 90)} max {max(late)} s over {len(late)} "
            f"requests")
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = read_metric(m, run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    sample = pick_sample(finished, np.random.default_rng(
        [int(seed) % (2 ** 63), 11]))
    result = {"attempted": attempted, "failed": drv.failed}
    drv = rec = None
    served.free()
    del served
    gc.collect()
    return _finish(cell, seed, run, metrics, sample, peak_mem, kind, device,
                   control, result)


def _run_traced(drv: Driver, served: Served, t_end: float,
                ticks: List[Dict]) -> None:
    """The window with per-tick gauges read after every scheduler tick."""
    sched, eng = served.sched, served.engine
    gauge = sched.metrics.gauge("slots.decoding")
    orig = sched.step

    def step():
        n0 = eng.stats["decode_dispatches"]
        out = orig()
        ticks.append({"decoding": gauge.value,
                      "decode": eng.stats["decode_dispatches"] > n0})
        return out

    sched.step = step
    try:
        drv.run_until(t_end)
    finally:
        sched.step = orig


def _log_latency(run: RunData) -> None:
    ttft = [tm.ttft(run.t_end) for tm in run.timings
            if run.in_window(tm.arrival)]
    tpot = [tm.tpot() for tm in run.timings
            if run.in_window(tm.finish) and tm.n_tokens > 1]
    if ttft:
        log(f"TTFT p50 {percentile(ttft, 50)} p90 {percentile(ttft, 90)} "
            f"s over {len(ttft)} requests")
    if tpot:
        log(f"TPOT p50 {percentile(tpot, 50)} p90 {percentile(tpot, 90)} "
            f"s over {len(tpot)} requests")


def _finish(cell, seed, run, metrics, sample, peak_mem, kind, device,
            control, result) -> Dict:
    import jax
    checks: Dict[str, Dict] = {}
    if not sample:
        log("no request finished in the window: nothing to compare")
        correct = False
        cmp = None
    else:
        cmp = compare(cell, seed, sample, control=control)
        log(f"reference over {cmp['requests']} requests, {cmp['tokens']} "
            f"served tokens: program {cmp['program']}")
        if control:
            log(f"control (int8 reference in the program's place): "
                f"{cmp['control']}")
        checks = judge(cell.limits, cmp["program"])
        correct = bool(checks) and all(c["ok"] for c in checks.values())
        if not checks:
            log("no limit set for this cell: not correct")
    result = {"correct": correct, "attempted": result["attempted"],
              "failed": result["failed"], "metrics": metrics,
              "device": {"platform": jax.devices()[0].platform, "kind": kind,
                         "count": cell.chips,
                         "memory_peak_bytes": peak_mem}}
    if device is not None:
        result["device"]["busy_s"] = device["busy_s"]
        result["device"]["window_s"] = device["window_s"]
        result["breakdown"] = {"device_ops": device["device_ops"],
                               "idle_gaps": device["idle_gaps"]}
    if cmp is not None and control:
        result["comparison"] = cmp
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k}: {v['value']} limit {v['limit']} "
            f"{'ok' if v['ok'] else 'FAILED'}")
    return result
