"""The trace reduction, on a small profile recorded on a TPU v5 lite
(one layer of qwen3-0.6b at its widths, two slots, a one-second window
of the sessions mix, mostly idle between requests) and on hand-made events."""
from __future__ import annotations

import gzip
import shutil

import pytest

import harness
import trace_reduce as tr
from conftest import HERE

FIXTURE = HERE / "tests" / "data" / "v5e_window.xplane.pb.gz"
SPANS = set(harness.SPANS.values())


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("profile") / "v5e.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(str(path))


def test_recorded_profile_reduces_to_consistent_numbers(recorded):
    assert list(recorded["chips"]) == [0]
    r = tr.reduce(recorded, span_names=SPANS)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    # both engine programs ran, and no program runs longer than the chip
    # was busy
    assert r["program_runs"]["prefill"] > 0
    assert r["program_runs"]["decode"] > 0
    assert 0 < sum(r["program_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    # self times of the top ops never add up past the busy time
    assert 0 < sum(v for _, v in r["device_ops"]) <= r["busy_s"]
    assert len(r["device_ops"]) <= tr.TOP and len(r["idle_gaps"]) <= tr.TOP
    # gaps are labelled by the harness's own spans
    labels = {k for k, _ in r["idle_gaps"]}
    assert labels <= SPANS | {"no host span"}
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert "tick" in r["host_s"]


def test_program_names_come_from_one_file(recorded):
    r = tr.reduce(recorded, programs={"decode": ["jit__many"]})
    assert set(r["program_s"]) == {"decode"}
    assert tr.program_names()["prefill"] == ["jit__prefill"]


def test_hand_made_events():
    ms = 1_000_000
    trace = {
        "host": [("window", 0, 100 * ms), ("tick", 0, 60 * ms),
                 ("decode_many", 12 * ms, 14 * ms),
                 ("wait_for_arrival", 60 * ms, 100 * ms)],
        "chips": {
            0: {"XLA Modules": [("jit__many(1)", 20 * ms, 50 * ms),
                                ("jit__prefill(2)", 50 * ms, 55 * ms)],
                "XLA Ops": [("%while.1 = (s32[]) while(x)", 20 * ms, 50 * ms),
                            ("%dot.2 = f32[8]{0} dot(a, b)", 25 * ms, 35 * ms),
                            ("%fusion.3 = f32[8]{0} fusion(a)", 50 * ms,
                             55 * ms)]},
            1: {"XLA Modules": [("jit__many(1)", 20 * ms, 40 * ms)],
                "XLA Ops": [("%dot.2 = f32[8]{0} dot(a, b)", 20 * ms,
                             40 * ms)]},
        }}
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((0.035 + 0.020) / 2)
    assert r["program_s"] == pytest.approx({"decode": 0.025,
                                            "prefill": 0.0025})
    assert r["program_runs"] == {"decode": 1, "prefill": 0.5}
    ops = dict(r["device_ops"])
    assert ops["%dot.2 f32[8] dot"] == pytest.approx(0.015)
    assert ops["%while.1 tuple while"] == pytest.approx(0.010)
    gaps = dict(r["idle_gaps"])
    assert gaps["tick"] == pytest.approx((0.020 + 0.020) / 2)
    assert gaps["wait_for_arrival"] == pytest.approx((0.045 + 0.060) / 2)


def test_op_label():
    assert tr.op_label("%fusion.190 = bf16[4096,8]{1,0:T(8,128)} fusion("
                       "bf16[4609]{0} %x), kind=kCustom") == \
        "%fusion.190 bf16[4096,8] fusion"
    assert tr.op_label("%while.4 = (s32[], f32[2]{0}) while((s32[]) %t)") \
        == "%while.4 tuple while"
