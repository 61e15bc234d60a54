"""Idle time by the program's spans and device time by its named scopes
(``phases.py``), on hand-made events and on a small profile recorded on a
TPU v5 lite with the HLO texts of the programs it ran (one layer of
qwen3-0.6b at its widths, two slots, a one-second window of the short
mix: ``phases.py --workload qwen3-short --seconds 1 --cut --keep``)."""
from __future__ import annotations

import gzip
import json
import shutil
from types import SimpleNamespace

import pytest

import harness
import phases as ph
import trace_reduce as tr
from conftest import HERE

MS = 1_000_000
FIXTURE = HERE / "tests" / "data" / "v5e_phases"
STEP = "sched.step"
SCOPES = ("kv_write", "attention", "ffn", "logits")


# --------------------------------------------------------------------------
# the exact idle split
# --------------------------------------------------------------------------
def split_trace():
    """One chip busy 20-50 ms of a 100 ms window.  A gap (50-62 ms)
    straddles the end of one tick's ``sched.step`` and the harness span
    after it; a piece of the gap before the device ran lies under the
    harness's ``record``."""
    return {
        "host": [("window", 0, 100 * MS), ("tick", 0, 60 * MS),
                 (STEP, 1 * MS, 59 * MS), ("tick.admit", 2 * MS, 10 * MS),
                 ("tick.decode_dispatch", 10 * MS, 30 * MS),
                 (ph.RECORD, 12 * MS, 16 * MS),
                 ("decode_many", 14 * MS, 16 * MS),
                 ("tick.decode_reconcile", 30 * MS, 55 * MS),
                 ("submit", 60 * MS, 62 * MS),
                 ("wait_for_arrival", 62 * MS, 100 * MS),
                 (STEP, 99 * MS, 120 * MS)],
        "chips": {0: {"XLA Modules": [("jit__many(1)", 20 * MS, 50 * MS)],
                      "XLA Ops": [("%while.1 = (s32[]) while(x)", 20 * MS,
                                   50 * MS)]}}}


def test_idle_goes_to_the_innermost_span():
    r = ph.idle_split(split_trace(), step=STEP)
    assert r["idle_by_span"] == pytest.approx({
        "tick": 0.001 + 0.001, STEP: 0.001 + 0.004 + 0.001,
        "tick.admit": 0.008, "tick.decode_dispatch": 0.002 + 0.004,
        ph.RECORD: 0.002, "decode_many": 0.002,
        "tick.decode_reconcile": 0.005, "submit": 0.002,
        "wait_for_arrival": 0.037})
    # complete: the map sums to window - busy
    assert sum(r["idle_by_span"].values()) == pytest.approx(0.070, rel=1e-9)
    # the gap 50-62 ms is cut at 55 (reconcile ends), 59 (the step ends)
    # and 60 (the tick ends): only 50-59 lies inside the step
    assert r["idle_in_step"] == pytest.approx({
        STEP: 0.001 + 0.004 + 0.001, "tick.admit": 0.008,
        "tick.decode_dispatch": 0.006, ph.RECORD: 0.002,
        "decode_many": 0.002, "tick.decode_reconcile": 0.005})
    # the step span at 99 ms started in the window; its piece counts
    assert r["step_spans"] == 2


def test_idle_split_matches_the_midpoint_rule_where_it_is_exact():
    """Where every gap lies under one innermost span, the exact split and
    ``trace_reduce``'s midpoint labels agree, span by span."""
    trace = {"host": [("window", 0, 100 * MS), ("tick", 0, 50 * MS),
                      ("wait_for_arrival", 50 * MS, 100 * MS)],
             "chips": split_trace()["chips"]}
    exact = ph.idle_split(trace)["idle_by_span"]
    mid = dict(tr.reduce(trace)["idle_gaps"])
    assert exact == pytest.approx(mid)


def test_spans_outside_the_names_are_ignored_and_outside_is_labelled():
    trace = split_trace()
    r = ph.idle_split(trace, span_names={"wait_for_arrival"})
    assert r["idle_by_span"] == pytest.approx(
        {ph.OUTSIDE: 0.032, "wait_for_arrival": 0.038})
    assert r["idle_in_step"] == {} and r["step_spans"] == 2


# --------------------------------------------------------------------------
# the scope map
# --------------------------------------------------------------------------
def hlo(module, body):
    return f"HloModule {module}, is_scheduled=true\n\n" + body


FUSED = """%fused_computation.1 (p: f32[8]) -> bf16[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %convert.1 = bf16[8]{0} convert(%p), metadata={op_name="jit(_many)/while/body/attention/convert_element_type"}
}

"""
K1 = hlo("jit__many", FUSED + """ENTRY %main.2 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(_many)/while/body/ffn/dot_general"}
  ROOT %while.3 = (s32[]) while(%a), body=%b, metadata={op_name="jit(_many)/while"}
}
""")
K2 = hlo("jit__many", """ENTRY %main.2 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(_many)/while/body/kv_write/scatter"}
  ROOT %dot.7 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(_many)/while/body/logits/dot_general"}
}
""")
PREFILL = hlo("jit__prefill", """ENTRY %main (a: f32[8]) -> f32[8] {
  ROOT %dot.2 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(_prefill)/while/body/attention/dot_general"}
}
""")


def test_hlo_scopes_reads_op_names_and_fused_roots():
    mod, m = ph.hlo_scopes(K1, SCOPES)
    assert mod == "jit__many"
    assert m == {"p": None, "convert.1": "attention", "a": None,
                 "fusion.1": "attention", "dot.2": "ffn", "while.3": None}
    assert ph.hlo_scopes(K2, SCOPES)[1]["fusion.1"] == "kv_write"


def scope_trace(extra_run=()):
    op = "%{} = f32[8]{{0}} {}(f32[8]{{0}} %a)".format
    ops = [(op("while.3", "while"), 10 * MS, 20 * MS),     # run of K = 1
           (op("fusion.1", "fusion"), 11 * MS, 14 * MS),
           (op("dot.2", "dot"), 14 * MS, 19 * MS),
           (op("fusion.1", "fusion"), 30 * MS, 32 * MS),   # run of K = 2
           (op("dot.7", "dot"), 32 * MS, 33 * MS),
           (op("dot.2", "dot"), 40 * MS, 45 * MS)]         # prefill
    mods = [("jit__many(11)", 10 * MS, 20 * MS),
            ("jit__many(22)", 30 * MS, 33 * MS),
            ("jit__prefill(3)", 40 * MS, 45 * MS),
            ("jit_admit_slot(4)", 50 * MS, 51 * MS)]
    ops.append((op("copy.5", "copy"), 50 * MS, 51 * MS))
    for name, s, e, op_names in extra_run:
        mods.append((name, s, e))
        ops += [(op(n, "fusion"), s, e) for n in op_names]
    return {"host": [("window", 0, 100 * MS)],
            "chips": {0: {"XLA Modules": mods, "XLA Ops": ops}}}


def test_scope_times_map_each_run_to_the_variant_that_names_its_ops():
    r = ph.scope_times(scope_trace(), [K1, K2, PREFILL],
                       programs=tr.program_names(), scopes=SCOPES)
    assert r["scope_s"]["decode"] == pytest.approx({
        "attention": 0.003, "ffn": 0.005, ph.UNSCOPED: 0.002,
        "kv_write": 0.002, "logits": 0.001})
    assert r["scope_s"]["prefill"] == pytest.approx({"attention": 0.005})
    assert r["scope_runs"] == {"prefill": 1, "decode": 2}
    assert r["scope_unmapped"] == {"prefill": 0, "decode": 0}


def test_a_run_no_variant_maps_makes_the_kind_read_none(capsys):
    # fusion.1 alone is named by both variants, with different scopes
    r = ph.scope_times(scope_trace([("jit__many(33)", 60 * MS, 61 * MS,
                                     ["fusion.1"])]),
                       [K1, K2, PREFILL], programs=tr.program_names(),
                       scopes=SCOPES)
    assert r["scope_s"]["decode"] is None
    assert r["scope_unmapped"]["decode"] == 1
    assert r["scope_s"]["prefill"] == pytest.approx({"attention": 0.005})
    assert "match no HLO text" in capsys.readouterr().err
    # an op no text names at all
    r = ph.scope_times(scope_trace([("jit__many(44)", 60 * MS, 61 * MS,
                                     ["fusion.404"])]),
                       [K1, K2, PREFILL], programs=tr.program_names(),
                       scopes=SCOPES)
    assert r["scope_s"]["decode"] is None


# --------------------------------------------------------------------------
# the recorded profile
# --------------------------------------------------------------------------
HARNESS_SPANS = set(harness.SPANS.values()) | {harness.WINDOW}
ALL_SPANS = HARNESS_SPANS | set(ph.program_spans()) | {ph.RECORD}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("phases") / "v5e.xplane.pb"
    with gzip.open(FIXTURE / "profile.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(FIXTURE / "hlo.json.gz") as f:
        texts = json.load(f)
    return tr.load(str(path), ALL_SPANS), texts


def test_recorded_phase_spans_cover_the_idle_inside_the_step(recorded):
    trace, _ = recorded
    base = tr.reduce(trace, span_names=HARNESS_SPANS)
    r = ph.idle_split(trace, span_names=ALL_SPANS)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    inside = sum(r["idle_in_step"].values())
    assert inside > 0 and r["step_spans"] > 0
    # at most 10% of the idle inside a step lies under no phase span
    assert r["idle_in_step"].get(STEP, 0.0) <= 0.10 * inside
    assert set(r["idle_in_step"]) <= set(ph.program_spans()) | {
        ph.RECORD, "prefill_chunk", "decode_many"}
    # the program's spans leave the harness's own reduction as it was
    assert tr.reduce(trace, span_names=HARNESS_SPANS) == base


def test_recorded_scope_map_finds_the_decode_programs_time(recorded):
    trace, texts = recorded
    base = tr.reduce(trace)
    r = ph.scope_times(trace, texts)
    assert r["scope_unmapped"] == {"prefill": 0, "decode": 0}
    for kind in ("prefill", "decode"):
        sc = r["scope_s"][kind]
        assert set(SCOPES) <= set(sc)
        # at least 95% of the programs' device time is mapped
        assert sum(sc.values()) >= 0.95 * base["program_s"][kind]
        assert sum(sc.values()) <= base["program_s"][kind] * (1 + 1e-9)


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------
def fake_run(device, decode_steps=0):
    return SimpleNamespace(device=device,
                           engine_stats={"decode_steps": decode_steps})


def test_tick_exposed_ms_leaves_out_the_harness_bookkeeping():
    run = fake_run({"idle_in_step": {STEP: 0.003, "tick.admit": 0.004,
                                     ph.RECORD: 0.5}, "step_spans": 7})
    assert ph.tick_exposed_ms(run) == pytest.approx(1e3 * 0.007 / 7)
    assert ph.tick_exposed_ms(fake_run(None)) is None
    assert ph.tick_exposed_ms(fake_run({"idle_in_step": {},
                                        "step_spans": 0})) is None


def test_attention_ms_per_step():
    sc = {"decode": {"attention": 1.5, "ffn": 0.5}, "prefill": None}
    assert ph.attention_ms_per_step(fake_run({"scope_s": sc}, 300)) == \
        pytest.approx(5.0)
    assert ph.attention_ms_per_step(fake_run({"scope_s": sc}, 0)) is None
    assert ph.attention_ms_per_step(
        fake_run({"scope_s": {"decode": None}}, 300)) is None
    assert ph.attention_ms_per_step(fake_run(None, 300)) is None


def test_longest_steps_gather_the_spans_of_their_tick():
    ev = [{"type": "span", "name": STEP, "ts": 1.0, "dur": 0.5, "tick": 1},
          {"type": "span", "name": "tick.admit", "ts": 1.0, "dur": 0.1,
           "tick": 1},
          {"type": "span", "name": "tick.evict", "ts": 1.2, "dur": 0.3,
           "tick": 1},
          {"type": "span", "name": "tick.evict", "ts": 1.4, "dur": 0.05,
           "tick": 1},
          {"type": "req", "ev": "arrive", "rid": 0, "ts": 1.1},
          {"type": "span", "name": STEP, "ts": 2.0, "dur": 0.2, "tick": 2},
          {"type": "span", "name": "tick.admit", "ts": 2.0, "dur": 0.2,
           "tick": 2},
          {"type": "span", "name": STEP, "ts": 9.0, "dur": 5.0, "tick": 3}]
    run = SimpleNamespace(events=ev, in_window=lambda t: 0 <= t < 5)
    (a, b) = ph.longest_steps(run, n=2)
    assert a == {"tick": 1, "s": 0.5,
                 "phases": {"tick.evict": pytest.approx(0.35),
                            "tick.admit": 0.1}}
    assert b["tick"] == 2 and b["phases"] == {"tick.admit": 0.2}


def test_span_names_come_from_one_file():
    cfg = ph.span_config()
    assert ph.program_spans(cfg)[0] == cfg["step"] == STEP
    assert tuple(cfg["scopes"]) == SCOPES
    # no program span shares a name with a span of the harness
    assert not set(ph.program_spans(cfg)) & (set(harness.SPANS.values())
                                             | {harness.WINDOW, ph.RECORD})
