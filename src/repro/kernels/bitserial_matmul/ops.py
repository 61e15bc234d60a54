"""Quantization + public API for the bit-serial (bit-plane) matmul.

``QuantizedLinear`` is the object the LM substrate embeds: weights live as
bit-planes (the vertical layout), activations are dynamically quantized to
int8 per row, and the matmul runs on the Pallas kernel.  ``n_bits`` of 8/4/2
trades accuracy for HBM bytes — the knob used in the §Perf memory-bound
decode hillclimb.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import bsmm_raw


def quantize_weights(w: jax.Array, n_bits: int = 8
                     ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-column quantization → (planes uint8? int8 [n_bits,K,N],
    scale f32 [N]).  Planes store bits of (q + 2^{n-1}) (unsigned offset)."""
    qmax = (1 << (n_bits - 1)) - 1
    scale = jnp.maximum(jnp.abs(w).max(axis=0), 1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax - 1, qmax
                 ).astype(jnp.int32)
    u = (q + (1 << (n_bits - 1))).astype(jnp.uint32)
    planes = jnp.stack([((u >> b) & 1).astype(jnp.int8)
                        for b in range(n_bits)])
    return planes, scale.astype(jnp.float32)


def quantize_activations(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dynamic symmetric per-row int8 quantization."""
    scale = jnp.maximum(jnp.abs(x).max(axis=-1), 1e-8) / 127.0
    xi = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
    return xi, scale.astype(jnp.float32)


def bitserial_matmul(x_i8: jax.Array, x_scale: jax.Array,
                     w_planes: jax.Array, w_scale: jax.Array,
                     interpret: Optional[bool] = None, bm: int = 128,
                     bn: int = 128, bk: int = 128) -> jax.Array:
    """Full quantized matmul: dequantized f32 [M, N]."""
    n_bits = w_planes.shape[0]
    zero = 1 << (n_bits - 1)
    M, K = x_i8.shape
    N = w_planes.shape[2]
    padm, padk, padn = (-M) % bm, (-K) % bk, (-N) % bn
    xp = jnp.pad(x_i8, ((0, padm), (0, padk)))
    wp = jnp.pad(w_planes, ((0, 0), (0, padk), (0, padn)))
    acc = bsmm_raw(xp, wp, bm=bm, bn=bn, bk=bk, interpret=interpret
                   )[:M, :N]
    acc = acc - zero * x_i8.astype(jnp.int32).sum(axis=1, keepdims=True)
    return acc.astype(jnp.float32) * x_scale[:, None] * w_scale[None, :]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedLinear:
    """A linear layer stored in vertical (bit-plane) layout."""
    w_planes: jax.Array      # int8 [n_bits, K, N] ∈ {0,1}
    w_scale: jax.Array       # f32 [N]

    def tree_flatten(self):
        return (self.w_planes, self.w_scale), ()

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)

    @classmethod
    def from_dense(cls, w: jax.Array, n_bits: int = 8) -> "QuantizedLinear":
        return cls(*quantize_weights(w, n_bits))

    def __call__(self, x: jax.Array,
                 interpret: Optional[bool] = None) -> jax.Array:
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        xi, xs = quantize_activations(x2)
        y = bitserial_matmul(xi, xs, self.w_planes, self.w_scale,
                             interpret=interpret)
        return y.reshape(*shape[:-1], -1).astype(x.dtype)

    @property
    def hbm_bytes(self) -> int:
        """1 bit/weight/plane when packed (the data-centric win)."""
        nb, K, N = self.w_planes.shape
        return nb * K * N // 8 + 4 * N
