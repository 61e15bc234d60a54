"""Batched public API for VBI-paged decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.vbi.kvcache import PagedKVState
from .kernel import paged_attn_one_seq


@functools.partial(jax.jit,
                   static_argnames=("n_kv", "interpret", "max_pages"))
def paged_decode_attention(q: jax.Array, state: PagedKVState, layer,
                           n_kv: int, seq_ids=None, max_pages=None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: [batch, n_q_heads, head_dim] (one decode step; sequence ``i`` uses
    page-table row ``seq_ids[i]``, default 0..batch-1); returns
    [batch, n_q_heads, head_dim]."""
    b, n_q, dh = q.shape
    g = n_q // n_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qg = (q.astype(jnp.float32) * scale).reshape(b, n_kv, g, dh)
    k_pages = state.k_pages[layer]
    v_pages = state.v_pages[layer]
    if seq_ids is None:
        seq_ids = jnp.arange(b)
    mp = max_pages or state.page_table.shape[1]
    pts = state.page_table[seq_ids, :mp]
    lens = state.seq_lens[seq_ids]

    def one(pt, ln, qq):
        return paged_attn_one_seq(pt, ln[None], qq, k_pages, v_pages,
                                  interpret=interpret)

    out = jax.vmap(one, in_axes=(0, 0, 0))(pts, lens, qg)
    return out.reshape(b, n_q, dh)
