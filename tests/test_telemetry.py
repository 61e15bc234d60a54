"""VBI telemetry (serve/telemetry.py — DESIGN.md §10):

  * the metrics registry: counter/gauge/histogram instruments, the
    pinned-edge histogram sharing ONE percentile implementation with the
    SLO math, and the dict-compatible StatsView the scheduler's ``stats``
    now lives behind;
  * zero-cost-when-disabled: the SAME tight-pool traffic run (preemption
    + host-swap pressure included) with tracing on vs off produces
    bit-identical outputs and identical ``host_syncs`` — recording may
    observe the run, never steer it;
  * the offline trace checker: a recorded mixed-profile run (incl.
    preemption, swap-out/swap-in) replays clean; a tampered or truncated
    trace must NOT;
  * exports: JSONL round-trips through the checker, and the Chrome
    ``trace_event`` conversion is well-formed (every async request span
    opened is closed, instants/counters carry valid phases).
"""
import json
import math

import jax
import pytest

from repro.core.vbi.address_space import VBProps
from repro.launch.serve import serve_config
from repro.models.model import init_params
from repro.serve.engine import PagedEngine
from repro.serve.scheduler import Scheduler
from repro.serve.telemetry import (LATENCY_EDGES_S, Histogram,
                                   MetricsRegistry, StatsView, Telemetry,
                                   TraceCheckError, TraceRecorder,
                                   check_trace, percentile, props_str,
                                   read_jsonl)
from repro.serve.traffic import TrafficDriver, VirtualClock, make_trace


# --------------------------------------------------------------------------
# the metrics registry
# --------------------------------------------------------------------------
def test_histogram_buckets_and_exact_percentiles():
    h = Histogram(edges=(1.0, 2.0, 4.0))
    h.observe_many([0.5, 1.0, 1.5, 3.0, 8.0])
    # bisect_left: x == edge lands in the bucket BELOW the edge (le_edge)
    assert h.buckets == [2, 1, 1, 1]
    assert h.count == 5 and h.sum == 14.0 and h.mean == 2.8
    # exact percentiles come from the retained samples, not the buckets,
    # through the one pinned linear-interpolation rule
    assert h.percentile(50) == 1.5
    assert h.percentile(0) == 0.5 and h.percentile(100) == 8.0
    assert h.percentile(50) == percentile(h.samples, 50)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["buckets"]["inf"] == 1
    assert snap["p50"] == 1.5 and snap["min"] == 0.5 and snap["max"] == 8.0
    assert math.isnan(Histogram().percentile(99))


def test_registry_get_or_create_and_snapshot():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(2)
    g = m.gauge("pool.free")
    g.set(7)
    g.set(3)                                   # high-water mark survives
    m.histogram("lat", edges=LATENCY_EDGES_S).observe(0.002)
    assert m.counter("a") is m.counter("a")    # get-or-create, same object
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["pool.free"] == {"value": 3, "max": 7}
    assert snap["histograms"]["lat"]["count"] == 1


def test_stats_view_is_dict_compatible():
    """The backward-compat satellite: ``stats["x"] += 1`` and every other
    dict idiom the tests/benches use must work verbatim while the storage
    lives in the shared registry under a prefix."""
    m = MetricsRegistry()
    sv = StatsView(m, prefix="sched.", keys=("preemptions", "steps"))
    assert dict(sv) == {"preemptions": 0, "steps": 0}
    sv["preemptions"] += 1
    sv["steps"] = 5
    assert sv["preemptions"] == 1 and len(sv) == 2
    assert m.counter("sched.preemptions").value == 1   # registry-backed
    assert m.counter("sched.steps").value == 5
    assert "preemptions" in sv and "nope" not in sv
    with pytest.raises(KeyError):
        sv["nope"]
    assert repr(sv) == repr(dict(sv))


def test_props_str_renders_declared_properties():
    p = VBProps.KV_CACHE | VBProps.EVICTABLE | VBProps.SWAPPABLE
    s = props_str(p)
    assert "KV_CACHE" in s and "SWAPPABLE" in s and "PINNED" not in s
    assert props_str(VBProps.NONE) == "NONE"


# --------------------------------------------------------------------------
# tracing must observe, never steer: bit-identical on vs off
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def qwen():
    cfg = serve_config("qwen3-0.6b")
    return cfg, init_params(cfg, jax.random.key(0))


def _tight_run(cfg, params, telemetry):
    """The hard case from the traffic suite: a pool small enough that
    preemption to the host swap tier is guaranteed, overlap on, virtual
    clock — fully deterministic."""
    trace = make_trace(cfg.vocab, n_requests=8, rate=2.0, seed=9,
                       max_prompt=8, max_new_cap=12)
    eng = PagedEngine(cfg, params, n_pages=9, page_size=4, max_seqs=4,
                      max_pages_per_seq=5, host_swap_pages=16)
    sched = Scheduler(eng, prefill_chunk=4, decode_horizon=4,
                      overlap=True, telemetry=telemetry)
    drv = TrafficDriver(sched, trace, clock=VirtualClock())
    fin = drv.run()
    assert sched.stats["preemptions"] >= 1     # pressure was real
    assert sched.stats["swap_ins"] >= 1
    assert eng.pages_in_use == 0 and eng.alloc.swap.used_pages == 0
    return {r.rid: r.out for r in fin}, dict(sched.stats)


@pytest.fixture(scope="module")
def recorded(qwen):
    """One traced mixed-profile run under preemption + swap pressure,
    shared by the checker/export tests below."""
    cfg, params = qwen
    telem = Telemetry(trace=True, clock=VirtualClock().now)
    out, stats = _tight_run(cfg, params, telem)
    return telem, out, stats


def test_tracing_on_vs_off_bit_identical(qwen, recorded):
    """The tier-1 overhead guard: recording a full trace (every block op,
    request event, tick span, gauge sample) must not change one output
    token or add one host sync."""
    cfg, params = qwen
    _, out_on, stats_on = recorded
    out_off, stats_off = _tight_run(cfg, params, telemetry=None)
    assert out_off == out_on                       # bit-identical outputs
    assert stats_off["host_syncs"] == stats_on["host_syncs"]
    # every scheduling decision agrees; only the ready-vs-wait *timing*
    # diagnostic may differ run to run (it races the real device queue)
    timing = ("sync_device_ready", "sync_device_wait")
    assert {k: v for k, v in stats_off.items() if k not in timing} \
        == {k: v for k, v in stats_on.items() if k not in timing}


def test_checker_passes_on_recorded_mixed_profile_run(recorded):
    telem, _, stats = recorded
    events = telem.tracer.events
    summary = check_trace(events)
    assert summary["live_blocks"] == 0 and summary["swap_pages_held"] == 0
    assert summary["peak_pages_used"] > 0
    ops = [e["op"] for e in events if e["type"] == "block"]
    assert "swap_out" in ops and "swap_in" in ops  # the hard paths traced
    evs = [e["ev"] for e in events if e["type"] == "req"]
    assert evs.count("arrive") == evs.count("finish") == 8
    assert "preempt" in evs and "first_token" in evs
    # every block op carries the declared properties it was placed by
    assert all("props" in e for e in events
               if e["type"] == "block" and "bid" in e)
    # gauge samples covered the run (they are what the checker
    # cross-validates against its replay)
    assert any(e["type"] == "gauge" for e in events)
    names = {e["name"] for e in events if e["type"] == "span"}
    assert {"tick.admit", "tick.prefill_stage", "tick.prefill_launch",
            "tick.decode_dispatch", "tick.decode_reconcile"} <= names


def test_corrupted_traces_must_fail(recorded):
    """The trace format is a correctness tool only if tampering is
    detectable: mutate the recorded run three different ways and the
    checker must refuse each."""
    telem = recorded[0]
    events = telem.tracer.events

    def clone():
        return [dict(e) for e in events]

    # (a) inflate one reservation: the redundant running total disagrees
    bad = clone()
    i = next(i for i, e in enumerate(bad)
             if e["type"] == "block" and e["op"] == "reserve")
    bad[i]["grow"] = bad[i]["grow"] + 1
    with pytest.raises(TraceCheckError):
        check_trace(bad)
    # (b) drop a free: the drained run now leaks its pages
    bad = [e for e in clone()
           if not (e["type"] == "block" and e["op"] == "free")]
    with pytest.raises(TraceCheckError):
        check_trace(bad)
    # (c) tamper a sampled gauge: replay disagrees with the observation
    bad = clone()
    i = next(i for i, e in enumerate(bad) if e["type"] == "gauge")
    bad[i]["values"] = dict(bad[i]["values"])
    bad[i]["values"]["alloc.free_pages"] += 1
    with pytest.raises(TraceCheckError):
        check_trace(bad)
    # (d) a swap-in that releases the wrong charge is asymmetric
    bad = clone()
    i = next((i for i, e in enumerate(bad)
              if e["type"] == "block" and e["op"] == "swap_in"), None)
    assert i is not None
    bad[i]["charge"] = bad[i]["charge"] + 1
    with pytest.raises(TraceCheckError):
        check_trace(bad)


def test_jsonl_round_trip(recorded, tmp_path):
    telem = recorded[0]
    p = tmp_path / "trace.jsonl"
    telem.tracer.write_jsonl(str(p))
    events = read_jsonl(str(p))
    assert len(events) == len(telem.tracer.events)
    assert check_trace(events) == check_trace(telem.tracer.events)


def test_chrome_export_is_valid_trace_event_json(recorded, tmp_path):
    """The export must load as the Chrome Trace Event Format: a
    ``traceEvents`` list whose entries carry a known phase, microsecond
    timestamps, and balanced async begin/end per request id."""
    telem = recorded[0]
    p = tmp_path / "trace.json"
    telem.tracer.write_chrome(str(p))
    doc = json.loads(p.read_text())                # valid JSON by parse
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    begins, ends = {}, {}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "b", "e", "i", "C", "M")
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "b":
            begins[ev["id"]] = begins.get(ev["id"], 0) + 1
        if ev["ph"] == "e":
            ends[ev["id"]] = ends.get(ev["id"], 0) + 1
    assert begins and begins == ends               # every span closed
    # block instants surface the declared properties in their args
    blocks = [ev for ev in doc["traceEvents"]
              if ev.get("cat") == "vbi" and "props" in ev.get("args", {})]
    assert blocks and all("props_s" in ev["args"] for ev in blocks)


def test_trace_recorder_span_and_clock_injection():
    t = {"now": 0.0}
    rec = TraceRecorder(clock=lambda: t["now"])
    with rec.span("tick.test", tick=3) as ext:
        t["now"] = 0.25
        ext["slots"] = 2
    (ev,) = rec.events
    assert ev["type"] == "span" and ev["name"] == "tick.test"
    assert ev["ts"] == 0.0 and ev["dur"] == 0.25
    assert ev["tick"] == 3 and ev["slots"] == 2


# --------------------------------------------------------------------------
# the profiler bridge: spans on the device trace's clock, scopes in the HLO
# --------------------------------------------------------------------------
TICK_PHASES = {"tick.admit", "tick.prefill_stage", "tick.prefill_launch",
               "tick.prefill_finish", "tick.decode_plan",
               "tick.decode_dispatch", "tick.decode_reconcile",
               "tick.evict", "tick.gauges"}


def _small_sched(cfg, params, telemetry, overlap=False):
    eng = PagedEngine(cfg, params, n_pages=17, page_size=4, max_seqs=2,
                      max_pages_per_seq=8)
    return Scheduler(eng, prefill_chunk=4, decode_horizon=2,
                     overlap=overlap, telemetry=telemetry)


def _serve_two(sched):
    sched.add_request([3, 1, 4, 1, 5, 9], max_new=4)
    sched.add_request([2, 7, 1], max_new=3)
    return sched.run()


@pytest.mark.parametrize("overlap", [False, True])
def test_profiler_trace_holds_the_scheduler_spans(qwen, tmp_path, overlap):
    """With a tracer, every span the scheduler records is also a host
    event of a ``jax.profiler`` trace, under its bare name, and each tick
    phase lies inside the ``sched.step`` span of its tick."""
    from jax.profiler import ProfileData
    cfg, params = qwen
    _serve_two(_small_sched(cfg, params, None, overlap))   # compile first
    telem = Telemetry(trace=True)
    sched = _small_sched(cfg, params, telem, overlap)
    with jax.profiler.trace(str(tmp_path)):
        _serve_two(sched)
    recorded = {e["name"] for e in telem.tracer.events
                if e["type"] == "span"}
    assert {"sched.step"} | TICK_PHASES <= recorded
    (path,) = tmp_path.glob("**/*.xplane.pb")
    lines = [[(e.name, e.start_ns, e.end_ns) for e in line.events]
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:CPU") for line in plane.lines]
    seen = {n for evs in lines for n, _, _ in evs}
    assert recorded <= seen
    n_steps = sum(n == "sched.step" for evs in lines for n, _, _ in evs)
    assert n_steps == sched.stats["steps"]
    for evs in lines:
        steps = [(s, e) for n, s, e in evs if n == "sched.step"]
        for n, s, e in evs:
            if n in TICK_PHASES:
                assert any(a <= s and e <= b for a, b in steps), n


def test_no_tracer_enters_no_trace_annotation(qwen, monkeypatch):
    """The profiler spans exist exactly when the scheduler holds a
    tracer: without one, not one ``TraceAnnotation`` is entered."""
    import repro.serve.telemetry as telemetry_mod
    entered = []

    class Counting:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", Counting)
    cfg, params = qwen
    _serve_two(_small_sched(cfg, params, None))
    assert entered == []
    _serve_two(_small_sched(cfg, params, Telemetry(trace=True)))
    assert "sched.step" in entered and TICK_PHASES & set(entered)


@pytest.mark.parametrize("program", ["decode_many", "prefill"])
def test_compiled_programs_carry_the_named_scopes(qwen, program):
    """The token step's parts reach the compiled HLO's ``op_name``
    metadata, where a profile reader maps device ops to them."""
    import jax.numpy as jnp
    cfg, params = qwen
    eng = PagedEngine(cfg, params, n_pages=17, page_size=4, max_seqs=2,
                      max_pages_per_seq=8)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    if program == "decode_many":
        fn = eng._horizon_fn(2)
        args = (i32, jax.ShapeDtypeStruct((2,), jnp.bool_), i32)
    else:
        fn = eng._prefill
        args = (jax.ShapeDtypeStruct((2, 4), jnp.int32), i32)
    text = fn.lower(eng.params, eng.state, *args).compile().as_text()
    for scope in ("kv_write", "attention", "ffn", "logits"):
        assert f"/{scope}/" in text, scope


def test_duplicate_gauges_are_gone(recorded):
    """``alloc.pages_used`` and ``placement.<device>.pages_used`` only
    repeated ``n_pages - 1 - alloc.free_pages``; the sample keeps the
    gauges the checker replays."""
    telem = recorded[0]
    for e in telem.tracer.events:
        if e["type"] == "gauge":
            assert "alloc.free_pages" in e["values"]
            assert "swap.pages_used" in e["values"]
            assert not any(k.endswith(".pages_used") and k != "swap.pages_used"
                           for k in e["values"])
