"""jit'd public wrappers for the transposition-unit kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.bitplane import BitPlaneArray, WORD_BITS, n_words_for
from .kernel import pack_tiles, unpack_tiles


def to_bitplanes(x: jax.Array, n_bits: int, signed: bool = True,
                 block_words: int = 256, interpret: Optional[bool] = None
                 ) -> BitPlaneArray:
    """Horizontal int array → vertical bit-plane layout (Pallas path)."""
    n_elems = x.shape[0]
    nw = n_words_for(n_elems)
    pad_words = (-nw) % block_words
    total = (nw + pad_words) * WORD_BITS
    xu = jnp.zeros((total,), jnp.uint32).at[:n_elems].set(
        x.astype(jnp.uint32))
    planes = pack_tiles(xu.reshape(-1, WORD_BITS), n_bits,
                        block_words=block_words, interpret=interpret)
    return BitPlaneArray(planes[:, :nw], n_elems, signed)


def from_bitplanes(bp: BitPlaneArray, out_dtype=jnp.int32,
                   block_words: int = 256, interpret: Optional[bool] = None
                   ) -> jax.Array:
    """Vertical bit-plane layout → horizontal ints (sign-extended)."""
    n_bits, nw = bp.planes.shape
    pad_words = (-nw) % block_words
    planes = jnp.pad(bp.planes, ((0, 0), (0, pad_words)))
    lanes = unpack_tiles(planes, n_bits, block_words=block_words,
                         interpret=interpret).reshape(-1)[: bp.n_elems]
    val = lanes.astype(jnp.int32)
    if bp.signed and n_bits < 32:
        sign = (lanes >> jnp.uint32(n_bits - 1)) & jnp.uint32(1)
        val = jnp.where(sign == 1, val - (1 << n_bits), val)
    return val.astype(out_dtype)
