"""The weights both sides are given, and the reference's own arithmetic."""
from __future__ import annotations

import jax
import numpy as np

import harness
from conftest import tiny, tiny_moe


def test_program_layout_holds_the_same_values_as_each_layer():
    cell = tiny_moe(chips=1)
    ref = harness.reference_module(cell.hp)
    p = ref.program_params(cell.hp, 2 ** 40 + 3)
    key = ref.seed_key(2 ** 40 + 3)
    for layer in range(cell.hp["num_hidden_layers"]):
        w = ref.layer_weights(cell.hp, jax.random.fold_in(key, layer))
        lp = jax.tree.map(lambda a: a[layer], p["stages"][0][0])
        # the same draws; the scaling may round apart by an ulp between
        # the vmapped program tree and one jitted layer
        for a, b in ((lp["moe"]["w1"], w["gate"]), (lp["moe"]["w2"], w["down"]),
                     (lp["moe"]["router"], w["router"]),
                     (lp["attn"]["wq"], w["q"]), (lp["ln1"] + 1.0,
                                                  w["input_norm"])):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_program_layout_matches_the_program_tree():
    from repro.models.model import abstract_params
    for cell in (tiny("qwen3-sessions"), tiny_moe(chips=1)):
        ref = harness.reference_module(cell.hp)
        mine = ref.program_shapes(cell.hp)
        theirs = abstract_params(harness.model_config(cell.hp))
        assert jax.tree.structure(mine) == jax.tree.structure(theirs)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            assert a.shape == b.shape and a.dtype == b.dtype


def test_seeds_past_32_bits_give_distinct_weights():
    cell = tiny("qwen3-sessions")
    ref = harness.reference_module(cell.hp)
    a = ref.outer_weights(cell.hp, ref.seed_key(5))["embed"]
    b = ref.outer_weights(cell.hp, ref.seed_key(5 + 2 ** 32))["embed"]
    assert not np.array_equal(a, b)


def test_reference_is_causal_and_the_gap_reads_the_served_token():
    cell = tiny("qwen3-sessions")
    ref = harness.reference_module(cell.hp)
    R = ref.Reference(cell.hp, 11, 32)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 256, 20).astype(np.int32)
    full = R.run([(t, 9)])[0]["served_gap"]
    # the same first 15 tokens alone give the same gaps where both read
    part = R.run([(t[:15], 9)])[0]["served_gap"]
    np.testing.assert_allclose(full[:5], part, rtol=0, atol=1e-5)
    assert (full >= 0).all()
    # teacher-forcing the reference's own first choices reads gaps of 0
    hp, key = cell.hp, R.key
    outer = ref.outer_weights(hp, key)
    x = ref.embed(hp, outer, t)
    for layer in range(hp["num_hidden_layers"]):
        x = ref.layer_forward(hp, ref.layer_weights(
            hp, jax.random.fold_in(key, layer)), x)
    h = ref._rmsnorm(x, outer["final_norm"], float(hp["rms_norm_eps"]))
    best = np.asarray(ref._dot(h, outer["embed"].T, "float32").argmax(-1))
    # position 9 sees t[:10] alone (causal): serve its first choice
    g = R.run([(np.append(t[:10], best[9]).astype(np.int32), 9)])[0][
        "served_gap"]
    assert g.shape == (1,) and g[0] == 0.0
