"""VBI-paged serving: batched decode where every sequence's KV stream is a
Virtual Block managed by the MTL (core/vbi/kvcache.py) and attention
resolves page translation in-kernel (kernels/paged_attention).

Per decode step and layer:
  1. ``begin_token`` reserves the next position (delayed page allocation —
     the paper's "allocate on first dirty writeback");
  2. ``write_layer`` stores the new K/V into the sequence's VB;
  3. the Pallas paged-attention kernel attends over the page table.

Sequences are ragged (per-sequence lengths/pages) — the continuous-batching
path the dense serve/step.py cannot express.  Pallas kernels lower for
real only on a TPU; elsewhere this path runs them interpreted
(``kernels.resolve_interpret``).

NOTE: this is the LEGACY reference path.  It does B·L host→device calls and
one host sync per decoded token — kept as the numerical oracle for
serve/engine.py (tests/test_serve_engine.py), which folds the whole step
into a single jitted dispatch.  New code should use
:class:`repro.serve.engine.PagedEngine`.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.vbi.kvcache import PagedKVManager
from ..kernels.paged_attention import paged_decode_attention
from ..models.config import ModelConfig
from ..models.layers import mlp, rms_norm, rope
from ..models.model import _cdt, _logits


class PagedServer:
    """Minimal single-host paged decoder for uniform dense GQA stacks."""

    def __init__(self, cfg: ModelConfig, params, n_pages: int = 256,
                 page_size: int = 16, max_seqs: int = 8):
        assert not cfg.local_global_period and not cfg.rglru_period \
            and cfg.family in ("dense", "vlm"), \
            "paged server supports uniform GQA stacks"
        self.cfg = cfg
        self.params = params
        self.kv = PagedKVManager(
            n_layers=cfg.n_layers, n_pages=n_pages, page_size=page_size,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim, max_seqs=max_seqs,
            dtype=jnp.float32)
        stacked = params["stages"][0][0]
        self._layers = [jax.tree.map(lambda x: x[i], stacked)
                        for i in range(cfg.n_layers)]

    def admit(self, seq_idx: int) -> None:
        self.kv.new_seq(seq_idx)

    def evict(self, seq_idx: int) -> None:
        self.kv.release_seq(seq_idx)

    def decode(self, tokens: jax.Array, seq_ids: List[int]) -> jax.Array:
        """One token for each listed sequence slot → logits [B, 1, V]."""
        cfg = self.cfg
        x = self.params["embed"][tokens].astype(jnp.float32)   # [B,1,d]
        positions = jnp.asarray(
            [self.kv.begin_token(s) for s in seq_ids], jnp.int32)
        for li, lp in enumerate(self._layers):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _qkv_ragged(cfg, lp["attn"], h, positions)
            for bi, sid in enumerate(seq_ids):
                self.kv.write_layer(sid, li, k[bi, :, 0], v[bi, :, 0])
            max_pages = max(1, -(-int(self.kv.state.seq_lens.max())
                                 // self.kv.page_size))
            o = paged_decode_attention(q[:, :, 0], self.kv.state, li,
                                       n_kv=cfg.n_kv,
                                       seq_ids=jnp.asarray(seq_ids),
                                       max_pages=max_pages)
            o = o.reshape(o.shape[0], 1, -1).astype(x.dtype)
            x = x + o @ lp["attn"]["wo"]
            h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + mlp(lp["mlp"], h2, cfg.act)
        return _logits(cfg, self.params, x)


def _qkv_ragged(cfg: ModelConfig, p, x, positions):
    """Like model._qkv but with a per-sequence position vector [B]."""
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = (x @ p["wq"] + (p["bq"] if "bq" in p else 0)
         ).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = (x @ p["wk"] + (p["bk"] if "bk" in p else 0)
         ).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = (x @ p["wv"] + (p["bv"] if "bv" in p else 0)
         ).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = jax.vmap(lambda qq, pp: rope(qq, pp[None], cfg.rope_theta)
                 )(q, positions)
    k = jax.vmap(lambda kk, pp: rope(kk, pp[None], cfg.rope_theta)
                 )(k, positions)
    return q, k, v
