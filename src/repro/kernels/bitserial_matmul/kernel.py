"""Pallas TPU kernel: weight bit-plane (bit-serial) matmul.

The beyond-paper TPU adaptation of SIMDRAM's bit-serial arithmetic
(DESIGN.md §2): weights are stored *vertically* as 1-bit planes, and the
matmul is computed bit-serially over planes but MXU-parallel within each
plane:

    acc[M,N] = Σ_b 2^b · ( X_i8[M,K] @ Wplane_b[K,N] )

Each plane matmul is an int8×int8→int32 MXU contraction (0/1 weights), so an
``n_bits``-bit weight costs ``n_bits`` MXU passes but only ``n_bits/8`` of
the HBM traffic of an int8 weight — exactly the data-movement trade the
paper makes (decode is weight-bandwidth-bound, the MXU has slack).

Grid: (M/bm, N/bn, K/bk), K innermost with an int32 VMEM accumulator; block
shapes default to MXU-aligned 128 multiples.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _bsmm_kernel(x_ref, w_ref, o_ref, *, n_bits: int):
    """x_ref [bm, bk] int8; w_ref [n_bits, bk, bn] int8 ∈ {0,1};
    o_ref [bm, bn] int32 accumulated across the K grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for b in range(n_bits):
        p = jax.lax.dot_general(
            x, w_ref[b],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + (p << b)
    o_ref[...] += acc


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def bsmm_raw(x: jax.Array, w_planes: jax.Array, bm: int = 128, bn: int = 128,
             bk: int = 128, interpret: Optional[bool] = None) -> jax.Array:
    """Σ_b 2^b (x @ w_planes[b]) — raw biased accumulation (int32[M, N])."""
    M, K = x.shape
    n_bits, K2, N = w_planes.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"pad shapes to block multiples ({M}x{K}x{N} vs {bm}/{bk}/{bn})"
    return pl.pallas_call(
        functools.partial(_bsmm_kernel, n_bits=n_bits),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((n_bits, bk, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        interpret=resolve_interpret(interpret),
    )(x, w_planes)
