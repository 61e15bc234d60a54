"""Pallas TPU kernel: VBI-paged decode attention.

The VBI idea made physical: KV pages are the MTL's physical frames, the
per-sequence page table is the VB's translation structure, and the
*BlockSpec index map performs the translation* — the kernel's K/V block for
grid step ``i`` is fetched from physical page ``page_table[i]`` via scalar
prefetch, so translation is resolved by hardware (the DMA engine) with zero
host involvement, off the critical path of compute — the paper's
"translation only where physical memory must be accessed".

One kernel instance serves one sequence (batched by vmap → stacked grid):
grid = (max_pages,), online-softmax accumulation in VMEM scratch, GQA via a
[n_kv, group, d] query layout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG_INF = -1e30


def _paged_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, page_size: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                       # [n_kv, g, d]
    k = k_ref[0]                         # [ps, n_kv, d]  (page pt_ref[i])
    v = v_ref[0]                         # [ps, n_kv, d]
    s = jnp.einsum("hgd,phd->hgp", q, k.astype(q.dtype))   # [n_kv, g, ps]
    pos = i * page_size + jax.lax.iota(jnp.int32, page_size)
    mask = pos < len_ref[0]
    s = jnp.where(mask[None, None, :], s, NEG_INF)

    m_prev = m_scr[...]                  # [n_kv, g]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])    # [n_kv, g, ps]
    p = jnp.where(mask[None, None, :], p, 0.0)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
    acc_scr[...] = (acc_scr[...] * alpha[..., None]
                    + jnp.einsum("hgp,phd->hgd", p, v.astype(q.dtype)))
    m_scr[...] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attn_one_seq(page_table: jax.Array, seq_len: jax.Array,
                       q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       interpret: Optional[bool] = None) -> jax.Array:
    """q [n_kv, g, d]; k/v_pages [n_pages, ps, n_kv, d];
    page_table [max_pages] int32; seq_len [1] int32 → out [n_kv, g, d]."""
    max_pages = page_table.shape[0]
    n_pages, ps, n_kv, dh = k_pages.shape
    g = q.shape[1]
    kv_spec = pl.BlockSpec((1, ps, n_kv, dh),
                           lambda i, pt, ln: (pt[i], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=ps),
        out_shape=jax.ShapeDtypeStruct((n_kv, g, dh), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(max_pages,),
            in_specs=[
                pl.BlockSpec((n_kv, g, dh), lambda i, pt, ln: (0, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((n_kv, g, dh), lambda i, pt, ln: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g, dh), jnp.float32),
            ]),
        interpret=resolve_interpret(interpret),
    )(page_table, seq_len, q, k_pages, v_pages)
