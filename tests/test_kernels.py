"""Pallas kernels (interpret mode) vs ref.py oracles — shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pack_np, unpack_np, ORACLES, OPS
from repro.kernels import (QuantizedLinear, from_bitplanes, simdram_op,
                           to_bitplanes)
from repro.kernels.bitserial_matmul.kernel import bsmm_raw
from repro.kernels.bitserial_matmul.ops import (bitserial_matmul,
                                                quantize_activations,
                                                quantize_weights)
from repro.kernels.bitserial_matmul.ref import ref_bsmm_raw
from repro.kernels.paged_attention.kernel import paged_attn_one_seq
from repro.kernels.paged_attention.ref import ref_paged_attention


# -- bitplane_transpose ------------------------------------------------------
@pytest.mark.parametrize("n_bits", [4, 8, 16, 32])
@pytest.mark.parametrize("n_elems", [1, 31, 256, 1000])
def test_transpose_kernel_matches_ref(n_bits, n_elems):
    rng = np.random.default_rng(n_bits * 1000 + n_elems)
    lo = -(1 << (n_bits - 1))
    x = rng.integers(lo, -lo, n_elems).astype(np.int32)
    bp = to_bitplanes(jnp.asarray(x), n_bits, block_words=8)
    ref = pack_np(x, n_bits)
    np.testing.assert_array_equal(np.asarray(bp.planes),
                                  np.asarray(ref.planes))
    back = from_bitplanes(bp, block_words=8)
    np.testing.assert_array_equal(np.asarray(back), x)


# -- simdram_vm --------------------------------------------------------------
@pytest.mark.parametrize("op", ["add", "gt", "relu", "bitcount", "if_else"])
@pytest.mark.parametrize("n", [8, 16])
def test_vm_kernel_matches_oracle(op, n):
    rng = np.random.default_rng(42)
    spec = OPS[op]
    lo = -(1 << (n - 1))
    ins = [rng.integers(lo, -lo, 150) for _ in range(spec.n_inputs)]
    if spec.n_inputs == 3:
        ins[0] = rng.integers(0, 2, 150)
    bps = [pack_np(x, n) for x in ins]
    out = simdram_op(op, *bps, block_words=2)
    m = np.uint64((1 << out.n_bits) - 1)
    got = unpack_np(out).astype(np.uint64) & m
    ref = np.asarray(ORACLES[op](*ins, n), np.uint64) & m
    np.testing.assert_array_equal(got, ref)


def test_vm_kernel_grid_tiling_equivalence():
    """Different VMEM block sizes must give identical results."""
    rng = np.random.default_rng(7)
    a = rng.integers(-128, 128, 500)
    b = rng.integers(-128, 128, 500)
    bpa, bpb = pack_np(a, 8), pack_np(b, 8)
    o1 = simdram_op("add", bpa, bpb, block_words=1)
    o2 = simdram_op("add", bpa, bpb, block_words=16)
    np.testing.assert_array_equal(np.asarray(o1.planes),
                                  np.asarray(o2.planes))


# -- bitserial_matmul --------------------------------------------------------
@pytest.mark.parametrize("n_bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384)])
def test_bsmm_raw_matches_ref(n_bits, shape):
    M, K, N = shape
    rng = np.random.default_rng(n_bits)
    x = rng.integers(-127, 128, (M, K)).astype(np.int8)
    w = rng.integers(0, 2, (n_bits, K, N)).astype(np.int8)
    got = bsmm_raw(jnp.asarray(x), jnp.asarray(w))
    ref = ref_bsmm_raw(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("n_bits", [4, 8])
def test_quantized_linear_accuracy(n_bits):
    rng = np.random.default_rng(0)
    w = rng.standard_normal((200, 120)).astype(np.float32)
    x = rng.standard_normal((17, 200)).astype(np.float32)
    ql = QuantizedLinear.from_dense(jnp.asarray(w), n_bits=n_bits)
    y = np.asarray(ql(jnp.asarray(x)))
    ref = x @ w
    rel = np.abs(y - ref).max() / np.abs(ref).max()
    assert rel < (0.02 if n_bits == 8 else 0.2), rel
    # vertical layout slashes weight bytes (the data-centric win)
    assert ql.hbm_bytes < w.size * 2 * n_bits / 8 / 2 + 4 * 120 + 1


def test_bsmm_padding_path():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, 70)).astype(np.float32)
    w = rng.standard_normal((70, 33)).astype(np.float32)
    xi, xs = quantize_activations(jnp.asarray(x))
    wp, ws = quantize_weights(jnp.asarray(w), 8)
    y = np.asarray(bitserial_matmul(xi, xs, wp, ws))
    assert y.shape == (5, 33)
    rel = np.abs(y - x @ w).max() / np.abs(x @ w).max()
    assert rel < 0.05


# -- paged_attention ---------------------------------------------------------
@pytest.mark.parametrize("seq_len", [1, 5, 16, 31])
@pytest.mark.parametrize("gqa", [(2, 3), (1, 4), (4, 1)])
def test_paged_attention_matches_ref(seq_len, gqa):
    n_kv, g = gqa
    n_pages, ps, dh = 12, 4, 8
    rng = np.random.default_rng(seq_len * 10 + n_kv)
    kp = rng.standard_normal((n_pages, ps, n_kv, dh)).astype(np.float32)
    vp = rng.standard_normal((n_pages, ps, n_kv, dh)).astype(np.float32)
    pt = np.zeros(8, np.int32)
    used = rng.choice(np.arange(1, n_pages), size=8, replace=False)
    pt[:] = used
    q = rng.standard_normal((n_kv, g, dh)).astype(np.float32)
    ln = np.array([seq_len], np.int32)
    args = [jnp.asarray(v) for v in (pt, ln, q, kp, vp)]
    out = paged_attn_one_seq(*args)
    ref = ref_paged_attention(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_attention_ignores_garbage_pages():
    """Entries beyond seq_len (incl. null page 0) must not affect output."""
    n_pages, ps, n_kv, g, dh = 6, 2, 1, 2, 4
    rng = np.random.default_rng(0)
    kp = rng.standard_normal((n_pages, ps, n_kv, dh)).astype(np.float32)
    vp = rng.standard_normal((n_pages, ps, n_kv, dh)).astype(np.float32)
    q = rng.standard_normal((n_kv, g, dh)).astype(np.float32)
    pt1 = np.array([3, 1, 0, 0], np.int32)
    pt2 = np.array([3, 1, 5, 2], np.int32)        # same prefix, junk tail
    ln = np.array([3], np.int32)
    o1 = paged_attn_one_seq(*[jnp.asarray(v) for v in (pt1, ln, q, kp, vp)])
    o2 = paged_attn_one_seq(*[jnp.asarray(v) for v in (pt2, ln, q, kp, vp)])
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# -- run mode ----------------------------------------------------------------
@pytest.mark.parametrize("explicit", [None, True, False])
def test_interpret_mode_follows_backend_unless_given(explicit):
    """One rule for every kernel entry point: interpret off a TPU, lower
    for real on one; an explicit choice wins."""
    from repro.kernels import resolve_interpret
    want = (jax.default_backend() != "tpu") if explicit is None else explicit
    assert resolve_interpret(explicit) is want
