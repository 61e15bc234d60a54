"""Device-resident, fully jitted continuous-batching decode engine.

The legacy :class:`~repro.serve.paged.PagedServer` is the processor-centric
anti-pattern the thesis argues against: every token bounces B·L times
between host ("OS") and device (per-layer, per-sequence ``write_layer``
calls) and ends with a host sync (``int(seq_lens.max())``).  This engine is
the data-centric rewrite (DESIGN.md §5):

  * the MTL's mechanism — page pool, page table, seq_lens, free list —
    lives on device as a pure-functional :class:`PagedServeState`;
  * delayed page allocation ("allocate on first dirty writeback") is
    resolved *inside* the jitted step with one cumsum over the free stack;
  * the whole layer stack folds into a single ``lax.scan``, so
    ``decode_batch(params, state, tokens, slot_mask) -> (logits, state)``
    is ONE jit-compiled dispatch with a static ``max_pages`` bucket —
    no per-token host sync, state donated across steps;
  * the fused decode horizon (DESIGN.md §7): ``decode_many`` scans K such
    token steps inside one dispatch — greedy sampling, token feedback and
    per-slot stopping (steps_left / EOS) on device — so the host syncs a
    ``[K, S]`` token block once per horizon instead of once per token;
  * chunked prefill scans whole prompt chunks inside one dispatch, with
    the next-token argmax inside the jit so only [S] int32 ever crosses;
  * every fast-path entry point is *asynchronous*: ``decode_many`` /
    ``prefill_chunk`` return device arrays without blocking, so a caller
    may defer the horizon-N sync and stage horizon N+1 (admission, span
    reservation, prefix lookup, the next prefill dispatch) while the
    device is still running — the double-buffered scheduler (DESIGN.md
    §9) is built on exactly this contract, with ``block_ready`` as the
    non-blocking probe for whether a deferred sync would stall.

Heterogeneous layer stacks (DESIGN.md §8): the engine partitions
``cfg.layer_kinds()`` into property-typed groups and gives each its own
cache state —

  * **full** attention layers keep the unbounded paged pool + page table;
  * **ring** layers (sliding-window 'local'/SWA) have *bounded liveness*:
    only the last ``window`` tokens are ever read, so they get a static
    per-slot ring of ``window/page_size`` pages, translation ``pos mod
    window`` resolved inside the jitted step — footprint capped, frames
    reused in place, no pool pressure ever;
  * **recurrent** layers (RG-LRU / Mamba-SSD) have *constant size*: a
    fixed per-slot state buffer, zero per-token growth.

The stack is scanned per config stage (``lax.scan`` over each stage's
period, params stacked per period entry), so gemma3's 5-local:1-global
pattern, mixtral's all-SWA MoE stack, recurrentgemma's R,R,A hybrid and
mamba2's attention-free stack all compile to O(period) HLO and serve
through the same jitted dispatch as a uniform GQA stack.

Attention resolves page translation on device either via the batched
gather path (XLA, default on CPU) or the Pallas paged-attention kernel
(``attn_impl="kernel"``, interpret-mode off-TPU); both take (page row,
valid length) so the ring pool rides the exact same two paths with its
static row and ``min(seq_len, window)``.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.vbi.address_space import VBProps
from ..core.vbi.blocks import VBIAllocator
from ..core.vbi.kvcache import (PagedServeState, aux_swap_charge,
                                fused_decode_scan, init_serve_state,
                                make_ring_table, reserve_positions,
                                write_token_kv)
from ..core.vbi.mtl import MTL
from ..kernels.paged_attention.kernel import paged_attn_one_seq
from ..models.config import LayerSpec, ModelConfig
from ..models.layers import mlp, moe, rms_norm
from ..models.model import _logits
from ..models.rglru import rglru_decode_step
from ..models.ssm import mamba_decode_step, ssm_dims
from .paged import _qkv_ragged


# --------------------------------------------------------------------------
# the property-typed stack geometry (static; drives pool shapes + the step)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One config stage, serving view: per period entry its kind, spec and
    the [count] global within-kind layer indices the scan consumes."""
    count: int
    kinds: Tuple[str, ...]
    specs: Tuple[LayerSpec, ...]
    entry_indices: Tuple[Tuple[int, ...], ...]


@dataclasses.dataclass(frozen=True)
class StackGeom:
    """The layer stack partitioned by data property (DESIGN.md §8):
    'full' = unbounded paged KV, 'ring' = bounded liveness (window),
    'rglru'/'mamba' = constant-size recurrent state."""
    kinds: Tuple[str, ...]
    n_full: int
    n_ring: int
    n_rg: int
    n_ssm: int
    window: int                      # shared ring window (0 = no ring)
    ring_pages: int
    stage_plans: Tuple[StagePlan, ...]

    @property
    def has_full(self) -> bool:
        return self.n_full > 0

    @property
    def n_recurrent(self) -> int:
        return self.n_rg + self.n_ssm

    @property
    def uniform_paged(self) -> bool:
        """True iff every layer is full attention — the only shape whose
        KV pages are position-addressed and therefore prefix-shareable."""
        return self.n_ring == 0 and self.n_recurrent == 0

    @property
    def kind_props(self) -> VBProps:
        props = VBProps.NONE
        if self.n_ring:
            props |= VBProps.RING
        if self.n_recurrent:
            props |= VBProps.RECURRENT
        return props


def _entry_kind(spec: LayerSpec) -> str:
    # cfg.stages() stamps the effective window onto every spec (uniform
    # SWA included), so spec.window alone decides — no cfg.window
    # fallback, which would misclassify the global layers of a
    # local/global stack that also sets cfg.window
    if spec.kind in ("attn", "local"):
        return "ring" if spec.window else "full"
    return spec.kind                                 # 'rglru' | 'mamba'


def build_stack_geom(cfg: ModelConfig, page_size: int) -> StackGeom:
    """Classify ``cfg``'s layer stack into property-typed groups and lay
    out per-stage scan plans.  Raises for shapes the serve engine cannot
    express (encoder-decoder; ring windows not page-aligned)."""
    if cfg.is_encdec:
        raise ValueError(f"{cfg.name}: encoder-decoder models are not "
                         f"servable through PagedEngine")
    counts = {"full": 0, "ring": 0, "rglru": 0, "mamba": 0}
    windows = set()
    plans = []
    for st in cfg.stages():
        kinds = tuple(_entry_kind(sp) for sp in st.period)
        per_kind = {k: sum(1 for kk in kinds if kk == k) for k in set(kinds)}
        rank = {k: 0 for k in set(kinds)}
        idx = []
        for sp, k in zip(st.period, kinds):
            idx.append(tuple(counts[k] + per_kind[k] * j + rank[k]
                             for j in range(st.count)))
            rank[k] += 1
            if k == "ring":
                windows.add(sp.window)
        for k, n in per_kind.items():
            counts[k] += n * st.count
        plans.append(StagePlan(st.count, kinds, tuple(st.period),
                               tuple(idx)))
    window = 0
    if windows:
        if len(windows) != 1:
            raise ValueError(f"{cfg.name}: ring layers must share one "
                             f"window, got {sorted(windows)}")
        window = windows.pop()
        if window % page_size:
            raise ValueError(
                f"{cfg.name}: sliding window {window} must be a multiple "
                f"of page_size {page_size} so ring translation stays "
                f"page-exact — pick a page_size dividing the window")
    return StackGeom(
        kinds=tuple(k for p in plans for _ in range(p.count)
                    for k in p.kinds),
        n_full=counts["full"], n_ring=counts["ring"], n_rg=counts["rglru"],
        n_ssm=counts["mamba"], window=window,
        ring_pages=window // page_size if window else 0,
        stage_plans=tuple(plans))


# --------------------------------------------------------------------------
# batched paged attention over the device page pool
# --------------------------------------------------------------------------
def batched_paged_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, layer: jax.Array,
                            page_table: jax.Array, seq_lens: jax.Array,
                            max_pages: int) -> jax.Array:
    """All slots at once, translation via the device page table.

    q [S, n_kv, g, hd] (pre-scaled f32); k/v_pages [n_layers, n_pages, ps,
    n_kv, hd], the stacked pools; ``layer`` the (traced) layer index;
    page_table [S, max_pages_per_seq]; seq_lens [S] → out [S, n_kv, g, hd].
    One gather indexes (layer, page) together, so a slot's rows are read
    straight out of the stacked pool and no layer's pool is ever copied.
    The ring pool uses the same contract with its static page row and
    ``seq_lens`` clipped to the window.
    """
    pts = page_table[:, :max_pages]                       # [S, P]
    S, P = pts.shape
    ps = k_pages.shape[2]
    k = k_pages[layer, pts].reshape(S, P * ps, *k_pages.shape[3:])
    v = v_pages[layer, pts].reshape(S, P * ps, *v_pages.shape[3:])
    s = jnp.einsum("shgd,sphd->shgp", q, k.astype(q.dtype))
    mask = (jnp.arange(P * ps)[None] < seq_lens[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("shgp,sphd->shgd", p, v.astype(q.dtype))
    return out / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)


def _kernel_paged_attention(q, k_pages, v_pages, layer, page_table,
                            seq_lens, max_pages: int) -> jax.Array:
    """Same contract via the Pallas kernel (vmapped over slots); lowers for
    real on TPU, interpret-mode everywhere else
    (``kernels.resolve_interpret``).  The custom call cannot read through
    a slice, so the layer's pool is sliced here."""
    pts = page_table[:, :max_pages]
    k_pages_l, v_pages_l = k_pages[layer], v_pages[layer]

    def one(pt, ln, qq):
        return paged_attn_one_seq(pt, ln[None], qq, k_pages_l, v_pages_l)

    return jax.vmap(one)(pts, seq_lens, q)


# --------------------------------------------------------------------------
# the jitted token step (shared by decode and chunked prefill)
# --------------------------------------------------------------------------
def _token_step(cfg: ModelConfig, geom: StackGeom, max_pages: int,
                attn_impl: str, ring_table: jax.Array, params,
                state: PagedServeState, tokens: jax.Array,
                slot_mask: jax.Array) -> Tuple[jax.Array, PagedServeState]:
    """One token for every masked slot through the *heterogeneous* stack:
    reserve → per-stage scan (each period entry branches by its static
    kind: paged / ring KV scatter + attention, or recurrent update) →
    logits.  Pure; everything stays on device.  ``jax.named_scope`` names
    the parts a profile tells apart (``kv_write``, ``attention``, ``ffn``,
    ``logits``); they reach only the compiled HLO's ``op_name``
    metadata."""
    state, positions = reserve_positions(state, slot_mask,
                                         has_full=geom.has_full)
    x = params["embed"][tokens].astype(jnp.float32)[:, None, :]   # [S,1,d]
    attn_fn = (_kernel_paged_attention if attn_impl == "kernel"
               else batched_paged_attention)
    if geom.n_full or geom.n_ring:
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    if geom.n_ring:
        ring_pos = positions % geom.window
        ring_lens = jnp.minimum(state.seq_lens, geom.window)

    def apply_entry(kind: str, spec: LayerSpec, lp, li, x, pools):
        k_pages, v_pages, k_ring, v_ring, rg_h, rg_conv, ssm_st, ssm_cv = \
            pools
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if kind in ("full", "ring"):
            q, k, v = _qkv_ragged(cfg, lp["attn"], h, positions)
            qg = (q[:, :, 0].astype(jnp.float32) * scale).reshape(
                q.shape[0], cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.head_dim)
            if kind == "full":
                with jax.named_scope("kv_write"):
                    k_pages, v_pages = write_token_kv(
                        k_pages, v_pages, li, state.page_table, positions,
                        slot_mask, k[:, :, 0], v[:, :, 0])
                with jax.named_scope("attention"):
                    o = attn_fn(qg, k_pages, v_pages, li, state.page_table,
                                state.seq_lens, max_pages)
            else:
                # bounded liveness exploited: translation pos mod window
                # into the slot's static ring row; frames reuse in place
                with jax.named_scope("kv_write"):
                    k_ring, v_ring = write_token_kv(
                        k_ring, v_ring, li, ring_table, ring_pos, slot_mask,
                        k[:, :, 0], v[:, :, 0])
                with jax.named_scope("attention"):
                    o = attn_fn(qg, k_ring, v_ring, li, ring_table,
                                ring_lens, geom.ring_pages)
            o = o.reshape(o.shape[0], 1, -1).astype(x.dtype)
            x = x + o @ lp["attn"]["wo"]
        elif kind == "rglru":
            o, hh, cv = rglru_decode_step(lp["rglru"], h, rg_h[li],
                                          rg_conv[li], cfg)
            rg_h = rg_h.at[li].set(
                jnp.where(slot_mask[:, None], hh, rg_h[li]))
            rg_conv = rg_conv.at[li].set(
                jnp.where(slot_mask[:, None, None], cv, rg_conv[li]))
            x = x + o
        else:                                            # mamba
            o, st2, cv = mamba_decode_step(lp["mamba"], h, ssm_st[li],
                                           ssm_cv[li], cfg)
            ssm_st = ssm_st.at[li].set(
                jnp.where(slot_mask[:, None, None, None], st2, ssm_st[li]))
            ssm_cv = ssm_cv.at[li].set(
                jnp.where(slot_mask[:, None, None], cv, ssm_cv[li]))
            x = x + o
        if kind != "mamba":                              # channel mixer
            h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            with jax.named_scope("ffn"):
                y = (moe(lp["moe"], h2, cfg) if spec.moe
                     else mlp(lp["mlp"], h2, cfg.act))
            x = x + y
        return x, (k_pages, v_pages, k_ring, v_ring, rg_h, rg_conv,
                   ssm_st, ssm_cv)

    pools = (state.k_pages, state.v_pages, state.k_ring, state.v_ring,
             state.rg_h, state.rg_conv, state.ssm_state, state.ssm_conv)
    for plan, sp in zip(geom.stage_plans, params["stages"]):
        idxs = tuple(jnp.asarray(ix, jnp.int32) for ix in plan.entry_indices)

        def body(carry, xs, plan=plan):
            x, pools = carry
            entry_params, entry_idx = xs
            for i in range(len(plan.kinds)):
                x, pools = apply_entry(plan.kinds[i], plan.specs[i],
                                       entry_params[i], entry_idx[i],
                                       x, pools)
            return (x, pools), None

        (x, pools), _ = lax.scan(body, (x, pools), (tuple(sp), idxs))
    state = dataclasses.replace(
        state, k_pages=pools[0], v_pages=pools[1], k_ring=pools[2],
        v_ring=pools[3], rg_h=pools[4], rg_conv=pools[5],
        ssm_state=pools[6], ssm_conv=pools[7])
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x)
    return logits, state


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------
class PagedEngine:
    """Continuous-batching serve engine over property-typed cache blocks.

    Any decoder-only stack ``cfg.stages()`` can express is served: uniform
    dense/GQA, local/global (gemma3), all-SWA MoE (mixtral), rglru hybrid
    (recurrentgemma), pure SSM (mamba2).  The engine is *compute only*:
    the per-token fast path is a single donated jit dispatch over the
    device pools.  ALL page lifecycle — allocation, sharing, COW, pinning,
    swap, release — goes through ``self.alloc``
    (:class:`~repro.core.vbi.blocks.VBIAllocator`, the VBI memory API,
    DESIGN.md §6); policy lives in serve/scheduler.py.
    """

    def __init__(self, cfg: ModelConfig, params, n_pages: int = 256,
                 page_size: int = 16, max_seqs: int = 8,
                 max_pages_per_seq: Optional[int] = None,
                 attn_impl: str = "gather", mtl: Optional[MTL] = None,
                 host_swap_pages: int = 0, eos_id: int = -1,
                 mesh: Optional[Mesh] = None, kv_layout: str = "auto"):
        assert attn_impl in ("gather", "kernel")
        assert kv_layout in ("auto", "shard", "replicate")
        if mesh is not None and mesh.devices.size > 1 \
                and attn_impl == "kernel":
            raise ValueError(
                "attn_impl='kernel' is not sharding-aware: the Pallas "
                "paged-attention kernel assumes a single-device page pool "
                "and would crash (or silently gather the whole pool) "
                "inside jit on a sharded mesh. Use attn_impl='gather' on "
                "a >1-device mesh.")
        if mesh is not None and cfg.n_experts > 0:
            # EP serving must never capacity-drop a token the dense
            # reference keeps (it would diverge bit-wise); cap >= T_loc
            # holds iff capacity_factor >= E/K (moe_ep.ep_capacity), and
            # at the dense path's per-token groups the bump leaves cap
            # unchanged — so dense vs EP outputs stay comparable.
            cfg = dataclasses.replace(
                cfg, capacity_factor=max(cfg.capacity_factor,
                                         cfg.n_experts / cfg.top_k))
        geom = build_stack_geom(cfg, page_size)
        self.cfg = cfg
        self.geom = geom
        self.params = params
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_seqs = max_seqs
        self.max_pages = max_pages_per_seq or -(-(n_pages - 1) // max_seqs)
        self.eos_id = eos_id
        # decode_steps counts scan steps *executed* (a lane retired early by
        # EOS still runs masked through the rest of its horizon),
        # decode_dispatches counts jit dispatches: with the fused horizon
        # (DESIGN.md §7) one dispatch covers K steps, so dispatches/steps
        # = 1/K is the tentpole's measurable contract; tokens actually
        # produced are reconciled host-side from the returned block.
        self.stats = {"decode_steps": 0, "decode_dispatches": 0,
                      "prefill_chunks": 0}
        self.metrics = None   # set by attach_metrics (serve/telemetry.py)
        rnn_w = (cfg.rnn_width or cfg.d_model) if geom.n_rg else 0
        ssm_H = ssm_P = ssm_conv_ch = 0
        if geom.n_ssm:
            d_inner, ssm_H, ssm_P = ssm_dims(cfg)
            ssm_conv_ch = d_inner + 2 * cfg.ssm_state
        self.state = init_serve_state(
            n_layers=geom.n_full, n_pages=n_pages, page_size=page_size,
            n_kv=cfg.n_kv, head_dim=cfg.head_dim, max_seqs=max_seqs,
            max_pages_per_seq=self.max_pages, dtype=jnp.float32,
            n_ring_layers=geom.n_ring, ring_pages=geom.ring_pages,
            n_rg=geom.n_rg, rnn_width=rnn_w, conv_width=cfg.conv_width,
            n_ssm=geom.n_ssm, ssm_heads=ssm_H, ssm_proj=ssm_P,
            ssm_state_size=cfg.ssm_state, ssm_conv_ch=ssm_conv_ch)
        # a slot's ring frames are STATIC (kvcache.py::make_ring_table):
        # translation is arithmetic, page 0 stays null for masked-out
        # lanes (mirrors the main pool's null page)
        self.ring_table_np = make_ring_table(max_seqs, geom.ring_pages)
        ring_table = jnp.asarray(self.ring_table_np)
        self.mesh = mesh
        devs = (list(mesh.devices.flat) if mesh is not None
                else jax.devices()[:1])
        # placement is a *data property* of every block carved from this
        # pool (DESIGN.md §13): the device set the block's pages
        # physically live on.  One logical VBI address space (the page
        # table stays host-global), physically distributed pages.
        self.placement = tuple(f"{d.platform}:{d.id}" for d in devs)
        # the engine satisfies the allocator's pool protocol (.state + geom)
        self.alloc = VBIAllocator(self, host_swap_pages=host_swap_pages,
                                  mtl=mtl)
        self._step = partial(_token_step, cfg, geom, self.max_pages,
                             attn_impl, ring_table)
        if mesh is not None:
            from ..distributed.axes import logical_axes
            from ..distributed.sharding import param_specs, shardings_of
            # moe() reads the logical-axes contextvar at trace time to
            # route mixtral through real EP dispatch (moe_ep) inside the
            # scanned stack.
            self._axes = partial(logical_axes, mesh, cfg.n_experts)
            self._param_shardings = shardings_of(
                param_specs(cfg, params, mesh), mesh)
            self.params = jax.device_put(params, self._param_shardings)
        else:
            self._axes = nullcontext
            self._param_shardings = None

        def _decode(params, state, tokens, slot_mask):
            with self._axes():
                return self._step(params, state, tokens, slot_mask)

        def _prefill(params, state, tokens, n_tokens):
            # tokens [S, C]; n_tokens [S] — valid prompt tokens this chunk.
            def tok(st, c):
                mask = (c < n_tokens) & st.slot_active
                logits, st = self._step(params, st, tokens[:, c], mask)
                return st, logits
            with self._axes():
                state, logits_seq = lax.scan(tok, state,
                                             jnp.arange(tokens.shape[1]))
                # last *valid* logits per slot (slots finish at different
                # c); argmax here so only [S] int32 ever needs to cross to
                # the host — and only on chunks where some slot finished
                # its prompt.
                last = jnp.clip(n_tokens - 1, 0)
                logits = logits_seq[last, jnp.arange(tokens.shape[0])]
                return (jnp.argmax(logits[:, 0], -1).astype(jnp.int32),
                        state)

        # mesh layout: pools shard over 'model', translation replicated
        # (sharding.py::serve_state_specs); 'auto' compiles both candidate
        # layouts and keeps the one the HLO cost walker predicts cheaper
        # in collective bytes (DESIGN.md §13).
        self.kv_layout = None
        self.layout_report = None
        self._state_shardings = None
        jit_kw: dict = {}
        if mesh is not None:
            from ..distributed.sharding import shard_serve_state
            if kv_layout == "auto":
                kv_layout = self._pick_layout(mesh, _decode)
            self.kv_layout = kv_layout
            self.state, self._state_shardings = shard_serve_state(
                self.state, mesh, kv_layout)
            self._rep = NamedSharding(mesh, P())
            # out_shardings (not in_shardings) pin the layout across the
            # donated chain; host-side lifecycle ops in between are
            # re-pinned by _pin() on each fast-path entry.
            jit_kw = dict(out_shardings=(self._rep, self._state_shardings))
        self._jit_kw = jit_kw

        # the tentpole contract: ONE jitted dispatch per decode step,
        # KV state donated so the pool is updated in place.
        self._decode = jax.jit(_decode, donate_argnums=(1,), **jit_kw)
        self._prefill = jax.jit(_prefill, donate_argnums=(1,), **jit_kw)
        self._decode_many: Dict[int, object] = {}   # horizon K -> jitted fn

    def _pick_layout(self, mesh: Mesh, decode_fn) -> str:
        """'auto' pool layout: AOT-compile the decode step under both
        candidate layouts (ShapeDtypeStruct probes — no arrays moved) and
        keep the one ``hlo_cost`` predicts cheaper in collective bytes."""
        from ..distributed.hlo_cost import analyze_hlo, comms_share
        from ..distributed.sharding import serve_state_specs
        rep_sh = NamedSharding(mesh, P())
        S = self.max_seqs
        tok = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rep_sh)
        msk = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=rep_sh)
        p_sds = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            self.params, self._param_shardings)
        reports = {}
        for layout in ("shard", "replicate"):
            specs = serve_state_specs(self.state, mesh, layout)
            st_sds = dataclasses.replace(self.state, **{
                k: jax.ShapeDtypeStruct(
                    getattr(self.state, k).shape,
                    getattr(self.state, k).dtype,
                    sharding=NamedSharding(mesh, s))
                for k, s in specs.items()})
            hlo = jax.jit(decode_fn).lower(
                p_sds, st_sds, tok, msk).compile().as_text()
            r = analyze_hlo(hlo)
            reports[layout] = {
                "collective_bytes": r["collectives"]["total"],
                "predicted_comms_share": comms_share(r),
                "flops": r["flops"],
            }
        chosen = ("shard" if reports["shard"]["collective_bytes"]
                  <= reports["replicate"]["collective_bytes"]
                  else "replicate")
        self.layout_report = {"chosen": chosen, "candidates": reports}
        return chosen

    def _pin(self) -> None:
        """Re-pin ``self.state`` to the chosen layout.  Host-side VBI
        lifecycle ops (admit/map/snapshot/restore…) run un-pinned jits
        whose outputs may drift to default placement; ``device_put`` with
        matching shardings is a no-op, so the fast path pays nothing when
        nothing drifted."""
        if self._state_shardings is not None:
            self.state = jax.device_put(self.state, self._state_shardings)

    def attach_metrics(self, metrics) -> None:
        """Move the engine's dispatch counters onto a shared
        MetricsRegistry (serve/telemetry.py, keys ``engine.*``).  The
        ``stats`` face stays dict-compatible and keeps its counts, so
        attaching mid-run loses nothing."""
        from .telemetry import StatsView
        old = dict(self.stats)
        self.metrics = metrics
        self.stats = StatsView(metrics, prefix="engine.", keys=list(old))
        for k, v in old.items():
            self.stats[k] = v

    # -- the property-typed pool protocol (read by allocator + scheduler) ---
    @property
    def has_full(self) -> bool:
        """False for stacks with no full-attention layer: nothing ever
        pops a pool page, so the page budget is identically zero."""
        return self.geom.has_full

    @property
    def supports_prefix_sharing(self) -> bool:
        return self.geom.uniform_paged

    @property
    def kind_props(self) -> VBProps:
        return self.geom.kind_props

    @property
    def aux_swap_pages(self) -> int:
        """Host-tier charge (in pages) of one slot's RING + RECURRENT
        state (kvcache.py::aux_swap_charge)."""
        return aux_swap_charge(self.geom.n_ring, self.geom.ring_pages,
                               self.geom.n_recurrent)

    def ring_row(self, slot: int) -> jax.Array:
        return jnp.asarray(self.ring_table_np[slot])

    # -- the fast paths ------------------------------------------------------
    def decode(self, tokens: jax.Array, slot_mask: jax.Array) -> jax.Array:
        """tokens [max_seqs] int32, slot_mask [max_seqs] bool →
        logits [max_seqs, 1, vocab].  No host transfer happens here."""
        self._pin()
        logits, self.state = self._decode(self.params, self.state, tokens,
                                          slot_mask)
        self.stats["decode_steps"] += 1
        self.stats["decode_dispatches"] += 1
        return logits

    def _horizon_fn(self, k: int):
        """The K-step fused horizon, compiled once per distinct K."""
        if k not in self._decode_many:
            def _many(params, state, tokens, slot_mask, steps_left):
                with self._axes():
                    return fused_decode_scan(
                        partial(self._step, params), state, tokens,
                        slot_mask, steps_left, length=k, eos_id=self.eos_id)
            self._decode_many[k] = jax.jit(_many, donate_argnums=(1,),
                                           **self._jit_kw)
        return self._decode_many[k]

    def decode_many(self, tokens: jax.Array, slot_mask: jax.Array,
                    steps_left: jax.Array, k: int) -> jax.Array:
        """The fused decode horizon (DESIGN.md §7): K token steps — greedy
        sampling, token feedback, per-slot stop masking (steps_left / EOS)
        and delayed page allocation — inside ONE donated-jit dispatch.

        tokens [max_seqs] int32 (each slot's last token), slot_mask
        [max_seqs] bool, steps_left [max_seqs] int32 → token block [k,
        max_seqs] int32 on device (-1 on masked lanes).  The caller syncs
        the block ONCE per horizon instead of once per token; page budget
        for the worst-case span must be reserved through ``self.alloc``
        before dispatch."""
        self._pin()
        block, self.state = self._horizon_fn(k)(
            self.params, self.state, tokens, slot_mask, steps_left)
        self.stats["decode_steps"] += k
        self.stats["decode_dispatches"] += 1
        return block

    @staticmethod
    def block_ready(x: jax.Array) -> bool:
        """Non-blocking probe: has the device finished computing ``x``?
        The overlap scheduler reads this right before a deferred horizon
        sync — a False means the host failed to hide the whole horizon
        behind staging work (counted in ``Scheduler.stats``)."""
        return x.is_ready()

    def prefill_chunk(self, tokens: jax.Array, n_tokens: jax.Array
                      ) -> jax.Array:
        """tokens [max_seqs, C] int32, n_tokens [max_seqs] int32 →
        next greedy token per slot, [max_seqs] int32 *on device* (argmax of
        each slot's last fed position — the caller reads it back only when
        a slot actually finished its prompt this chunk)."""
        self._pin()
        nxt, self.state = self._prefill(self.params, self.state, tokens,
                                        n_tokens)
        self.stats["prefill_chunks"] += 1
        return nxt

    # -- introspection (syncs; never call on the decode fast path) ----------
    @property
    def free_pages(self) -> int:
        return int(self.state.free_top)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - 1 - self.free_pages
