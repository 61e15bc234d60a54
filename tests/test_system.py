"""End-to-end behaviour tests for the full system: the paper's pipeline from
user-defined operation → synthesized μProgram → execution; the SIMDRAM→LM
integration; launchers; paged serving."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Aoig, aoig_to_mig, pack_np, unpack_np, uprogram_cost
from repro.core.allocator import allocate_cell
from repro.core.bitplane import BitPlaneArray
from repro.core.engine import execute
from repro.core.subarray import d
from repro.core.uprogram import Segment, UProgram, coalesce


def test_user_defined_operation_end_to_end():
    """The framework's headline flexibility claim: a *new* operation
    (3-input majority-vote + mask, not in the library) goes AOIG → MIG →
    allocation → μProgram → engine, bit-exactly."""
    g = Aoig()
    a, b, c, m = (g.input(x) for x in "abcm")
    vote = g.or_(g.or_(g.and_(a, b), g.and_(a, c)), g.and_(b, c))
    out = g.and_(vote, m)
    mig, outs = aoig_to_mig(g, [out], optimize=True)
    uops, _ = allocate_cell(
        mig, {d("OUT", 1, 0): outs[0]},
        {"a": d("A", 1, 0), "b": d("B", 1, 0), "c": d("C", 1, 0),
         "m": d("M", 1, 0)})
    n = 8
    prog = UProgram("votemask", n, [Segment(coalesce(uops), trips=n)])
    rng = np.random.default_rng(0)
    arrs = {k: rng.integers(0, 256, 64) for k in "ABCM"}
    planes = {k: pack_np(v, n).planes for k, v in arrs.items()}
    out_planes = execute(prog, planes, 2, out_bits=n)
    got = unpack_np(BitPlaneArray(out_planes, 64, False))
    ref = ((arrs["A"] & arrs["B"]) | (arrs["A"] & arrs["C"])
           | (arrs["B"] & arrs["C"])) & arrs["M"]
    np.testing.assert_array_equal(got.astype(np.uint64) & np.uint64(0xFF),
                                  ref.astype(np.uint64) & np.uint64(0xFF))
    # and it has a cost the control unit can reason about
    assert uprogram_cost(prog).latency_ns > 0


def test_simdram_quantized_linear_in_model():
    """The paper's technique inside the LM: a bit-plane (vertical layout)
    quantized linear layer swaps in for a dense projection."""
    from repro.kernels import QuantizedLinear
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 64)).astype(np.float32) * 0.1
    x = rng.standard_normal((4, 64)).astype(np.float32)
    ql = QuantizedLinear.from_dense(jnp.asarray(w), n_bits=8)
    y = np.asarray(ql(jnp.asarray(x)))
    rel = np.abs(y - x @ w).max() / (np.abs(x @ w).max() + 1e-9)
    assert rel < 0.03
    assert ql.hbm_bytes < 64 * 64 * 2          # < bf16 dense bytes


def test_train_launcher_end_to_end(tmp_path):
    env = {**os.environ,
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..",
                                      "src")}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-0.6b",
         "--smoke", "--steps", "6", "--batch", "4", "--seq", "64",
         "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "loss" in r.stdout
    # resume path
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-0.6b",
         "--smoke", "--steps", "8", "--batch", "4", "--seq", "64",
         "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr
    assert "resumed" in r2.stdout


def test_paged_serving_matches_dense_decode():
    import dataclasses
    from repro.configs import smoke_config
    from repro.models import forward_train, init_params
    from repro.serve.paged import PagedServer
    cfg = dataclasses.replace(smoke_config("qwen3-0.6b"),
                              param_dtype="float32",
                              compute_dtype="float32", tie_embeddings=False)
    p = init_params(cfg, jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (2, 6))
    full = forward_train(cfg, p, {"tokens": jnp.asarray(toks, jnp.int32),
                                  "labels": jnp.asarray(toks, jnp.int32)})
    srv = PagedServer(cfg, p, n_pages=32, page_size=2, max_seqs=4)
    srv.admit(0)
    srv.admit(1)
    for t in range(6):
        lg = srv.decode(jnp.asarray(toks[:, t:t + 1], jnp.int32), [0, 1])
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   atol=2e-3, rtol=1e-3)
    assert srv.kv.stats["delayed_page_allocs"] > 0


def test_dryrun_artifacts_complete_if_present():
    """If the sweep has run, every (arch × shape × mesh) cell must be
    accounted for (ok or documented skip)."""
    import glob
    import json
    files = glob.glob(os.path.join(os.path.dirname(__file__), "..",
                                   "benchmarks", "results", "dryrun",
                                   "*.json"))
    if len(files) < 80:
        import pytest
        pytest.skip("dry-run sweep artifacts not generated yet")
    bad = []
    for f in files:
        r = json.load(open(f))
        if not r.get("ok"):
            bad.append(os.path.basename(f))
    assert not bad, f"failed cells: {bad}"


def test_compile_cache_dir_is_fixed(monkeypatch):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path in the checkout — never a temp name."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
        assert compile_cache.enable_compile_cache() == "/some/cache"
        assert jax.config.jax_compilation_cache_dir == "/some/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_launcher_smoke_flag_turns_off():
    """--smoke is the default and --no-smoke reaches the published widths
    (the flag used to be store_true with default=True)."""
    from repro.launch.serve import build_parser, serve_config
    ap = build_parser()
    assert ap.parse_args([]).smoke is True
    args = ap.parse_args(["--no-smoke"])
    assert args.smoke is False
    assert serve_config("qwen3-0.6b", args.smoke).d_model == 1024
