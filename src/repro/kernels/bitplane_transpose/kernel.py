"""Pallas TPU kernel: the SIMDRAM data transposition unit (Sec. 2.4.1).

Horizontal (element-major) ↔ vertical (bit-plane) layout conversion.  The
hardware unit transposes one cache line per cycle between the LLC and the
memory controller; here each grid step transposes one VMEM tile of
``block_words × 32`` lanes, unrolled over the (static) bit width — bit
extraction and packing are VPU-friendly shifts/masks, and the bit axis is
kept as the major axis so the planes tile ``[n_bits, block_words]`` streams
straight to HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

WORD_BITS = 32


def _pack_kernel(x_ref, o_ref, *, n_bits: int):
    """x_ref: [bw, 32] uint32 lane values; o_ref: [n_bits, bw] packed planes."""
    x = x_ref[...]
    lane_w = (jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32))[None, :]
    for b in range(n_bits):
        bits = (x >> jnp.uint32(b)) & jnp.uint32(1)
        o_ref[b, :] = (bits * lane_w).sum(axis=1).astype(jnp.uint32)


def _unpack_kernel(p_ref, o_ref, *, n_bits: int):
    """p_ref: [n_bits, bw] packed planes; o_ref: [bw, 32] lane values."""
    lanes = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, :]
    acc = jnp.zeros(o_ref.shape, jnp.uint32)
    for b in range(n_bits):
        bits = (p_ref[b, :][:, None] >> lanes) & jnp.uint32(1)
        acc = acc | (bits << jnp.uint32(b))
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("n_bits", "block_words", "interpret"))
def pack_tiles(x_words: jax.Array, n_bits: int, block_words: int = 256,
               interpret: Optional[bool] = None) -> jax.Array:
    """x_words: uint32[n_words, 32] → planes uint32[n_bits, n_words]."""
    n_words = x_words.shape[0]
    assert n_words % block_words == 0
    return pl.pallas_call(
        functools.partial(_pack_kernel, n_bits=n_bits),
        out_shape=jax.ShapeDtypeStruct((n_bits, n_words), jnp.uint32),
        grid=(n_words // block_words,),
        in_specs=[pl.BlockSpec((block_words, WORD_BITS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n_bits, block_words), lambda i: (0, i)),
        interpret=resolve_interpret(interpret),
    )(x_words)


@functools.partial(jax.jit, static_argnames=("n_bits", "block_words", "interpret"))
def unpack_tiles(planes: jax.Array, n_bits: int, block_words: int = 256,
                 interpret: Optional[bool] = None) -> jax.Array:
    """planes uint32[n_bits, n_words] → x uint32[n_words, 32]."""
    n_words = planes.shape[1]
    assert n_words % block_words == 0
    return pl.pallas_call(
        functools.partial(_unpack_kernel, n_bits=n_bits),
        out_shape=jax.ShapeDtypeStruct((n_words, WORD_BITS), jnp.uint32),
        grid=(n_words // block_words,),
        in_specs=[pl.BlockSpec((n_bits, block_words), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_words, WORD_BITS), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
    )(planes)
