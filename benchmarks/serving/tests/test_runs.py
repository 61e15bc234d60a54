"""Whole runs of the harness at tiny widths on the CPU, past its look for
a chip: a sound run is correct; the control (the int8 reference in the
program's place) and each fault the cells can have, planted in the
timed path, come out not correct.  The faults are judged by each cell's
committed limits (``limits/<cell>.json``).  The control is judged by a
tighter limit here: at tiny widths its gaps are smaller than at the
cells' widths, where ``calibrate.py`` judges it by the committed limits
on the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

import harness
from conftest import HERE, ROOT, tiny

SEED = 2 ** 33 + 5
SECONDS = 1.5


def run(cell, control=False):
    return harness.run_cell(cell, SEED, SECONDS, False, time.perf_counter(),
                            control=control)


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    # read every finished request: the more positions, the surer a flip
    # of the control's first choice
    monkeypatch.setattr(harness, "SAMPLE_MAX", 10 ** 4)
    monkeypatch.setattr(harness, "SAMPLE_TOKENS", 10 ** 6)
    res = harness.run_cell(tiny("qwen3-short"), SEED, 4.0, False,
                           time.perf_counter(), control=True)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    cmp = res["comparison"]
    # f32 on the CPU: the served tokens are the reference's own
    assert cmp["program"]["max_logit_gap"] <= 1e-4
    # int8 in the program's place reads a wider gap than the limit
    limit = tiny("qwen3-short").limits["max_logit_gap"]["limit"]
    assert cmp["control"]["max_logit_gap"] > limit
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p90", "tpot_p90", "setup_s"}
    committed = harness.load_cell("qwen3-short").limits
    assert all(c["ok"] for c in harness.judge(committed,
                                                cmp["program"]).values())


def _token_altered(block, mask):
    lane = int(harness.np.argmax(harness.np.asarray(mask)))
    return block.at[0, lane].set(block[0, lane] + 1)


def _half_batch_left_out(block, mask):
    """The second half of the slots in the step gets the first half's
    tokens, as a step that computed half of the batch and spread it over
    the rest."""
    lanes = harness.np.nonzero(harness.np.asarray(mask))[0]
    half = len(lanes) // 2
    return block.at[:, lanes[half:2 * half]].set(block[:, lanes[:half]])


# the half-batch fault needs steps that decode several slots: the closed
# loop keeps every slot busy
@pytest.mark.parametrize("fault,cell", [
    (_token_altered, "qwen3-short"), (_half_batch_left_out, "qwen3-sessions")],
    ids=["token_altered", "half_batch_left_out"])
def test_a_fault_in_the_decode_step_is_not_correct(monkeypatch, fault, cell):
    from repro.serve.engine import PagedEngine
    orig = PagedEngine.decode_many

    def broken(self, tokens, slot_mask, steps_left, k):
        block = orig(self, tokens, slot_mask, steps_left, k)
        out = fault(block, slot_mask)
        # lanes that produced no token (-1) stay so
        return jnp.where((block >= 0) & (out >= 0),
                         out % self.cfg.vocab, block)

    monkeypatch.setattr(PagedEngine, "decode_many", broken)
    assert run(tiny(cell, committed=True))["correct"] is False


def test_a_step_that_leaves_the_kv_state_unchanged_is_not_correct(
        monkeypatch):
    """Every prefill chunk and decode horizon hands back the K and V pools
    it was given: the tokens are computed, their keys and values lost."""
    import dataclasses
    import jax
    from repro.serve.engine import PagedEngine

    def keeping_kv(fast_path):
        def step(self, *args):
            kv = jax.tree.map(jnp.copy, (self.state.k_pages,
                                         self.state.v_pages))
            out = fast_path(self, *args)
            self.state = dataclasses.replace(self.state, k_pages=kv[0],
                                             v_pages=kv[1])
            return out
        return step

    for name in ("prefill_chunk", "decode_many"):
        monkeypatch.setattr(PagedEngine, name,
                            keeping_kv(getattr(PagedEngine, name)))
    assert run(tiny("qwen3-sessions", committed=True))["correct"] is False


MESH_SCRIPT = r"""
import json, sys, time
sys.path[:0] = {paths!r}
import conftest, harness
cell = conftest.tiny_moe(chips=4)
if {broken!r}:
    import repro.distributed.moe_ep as moe_ep
    class NoExchange:
        def __getattr__(self, name):
            return getattr(moe_ep.jax.lax, name)
        @staticmethod
        def all_to_all(x, *a, **k):
            return x
    moe_ep.lax = NoExchange()
res = harness.run_cell(cell, {seed}, {seconds}, False, time.perf_counter())
print(json.dumps({{"correct": res["correct"], "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_expert_parallel_cell_on_four_devices(broken):
    """A sparse-expert cell on four virtual CPU devices (the harness's
    mesh path, expert-parallel MoE): sound, and with the all-to-all
    between chips left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    paths = [str(HERE / "tests"), str(HERE), str(ROOT / "src")]
    code = MESH_SCRIPT.format(paths=paths, broken=broken, seed=SEED,
                              seconds=SECONDS)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res


def test_without_a_tpu_the_command_exits_non_zero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/serving/run.py", "--workload",
         "qwen3-short", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert p.stdout.strip() == ""
