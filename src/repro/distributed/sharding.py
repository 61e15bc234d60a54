"""Sharding rules: DP/FSDP over ``data`` (+``pod``), TP/EP/SP over ``model``.

Conventions (MaxText-style, adapted):
  * batch dims shard over ('pod','data') (multi-pod) or ('data',);
  * params FSDP-shard their *d_model-like* dim over 'data' and their
    heads/ff/vocab/experts dim over 'model' (TP / EP);
  * MoE experts shard over 'model' when divisible (EP), else fall back to
    tensor-parallel expert FFNs;
  * decode KV caches shard batch over data axes and sequence over 'model'
    (SP) — for batch-1 long-context, sequence shards over ('data','model').

Rules are name-based over the pytree path, so they apply uniformly to
params, grads, and optimizer moments.  ``placement_hint`` maps VBI property
bitvectors to sharding preferences (the data-aware hook, DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.vbi.address_space import VBProps
from ..models.config import ModelConfig


def batch_axes_for(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _divisible(n: int, mesh: Mesh, axis: str) -> bool:
    return n % _axis_size(mesh, axis) == 0


# name → (spec for trailing dims); leading (stack) dims padded with None
_RULES = {
    # attention
    "wq": ("data", "model"), "wk": ("data", "model"), "wv": ("data", "model"),
    "wo": ("model", "data"),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    # dense mlp
    "w1": ("data", "model"), "w3": ("data", "model"), "w2": ("model", "data"),
    # ssm / rglru
    "in_proj": ("data", "model"), "out_proj": ("model", "data"),
    "in_x": ("data", "model"), "in_gate": ("data", "model"),
    "w_a": ("data", "model"), "w_i": ("data", "model"),
    "out": ("model", "data"),
    "conv_w": (None, "model"),
    # router
    "router": ("data", None),
}

_MOE_LEAVES = {"w1", "w3", "w2"}


def _leaf_name(path) -> str:
    names = [str(part.key) for part in path if hasattr(part, "key")]
    # quantized leaves ({'q8','s'}) inherit the enclosing matmul's rule
    while names and names[-1] in ("q8", "s"):
        names.pop()
    return names[-1] if names else ""


def _is_scale(path) -> bool:
    names = [str(part.key) for part in path if hasattr(part, "key")]
    return bool(names) and names[-1] == "s"


def _has_moe(path) -> bool:
    return any(getattr(p, "key", None) == "moe" for p in path)


def param_specs(cfg: ModelConfig, params_shape, mesh: Mesh):
    """PartitionSpec pytree for a params-shaped tree (works for grads and
    optimizer moments too)."""
    ep = cfg.n_experts > 0 and _divisible(cfg.n_experts, mesh, "model")
    fsdp: object = "data"
    if getattr(cfg, "fsdp_axes", "data") == "pod_data" \
            and "pod" in mesh.axis_names:
        fsdp = ("pod", "data")

    def _axis_total(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= _axis_size(mesh, a)
        return n

    def spec_for(path, leaf) -> P:
        name = _leaf_name(path)
        nd = len(leaf.shape)
        if _is_scale(path):
            # quantization scale [*, N]: shard like the matmul's output dim
            rule = _RULES.get(name)
            ax = rule[-1] if rule else (
                "model" if name == "lm_head" else None)
            if ax is not None and _divisible(leaf.shape[-1], mesh, ax):
                return P(*((None,) * (nd - 1)), ax)
            return P(*((None,) * nd))
        if name == "embed":
            # vocab TP only: sharding d here would put the contraction dim of
            # the (tied) logits matmul on 'data' → a full-logits all-reduce.
            return P("model", None)
        if name == "lm_head":
            return P(None, "model")
        if name in ("step",):
            return P()
        if _has_moe(path) and name in _MOE_LEAVES:
            # [*, E, a, b]
            lead = (None,) * (nd - 3)
            if ep:
                if name == "w2":
                    return P(*lead, "model", None, fsdp)
                return P(*lead, "model", fsdp, None)
            if name == "w2":
                return P(*lead, None, "model", fsdp)
            return P(*lead, None, fsdp, "model")
        rule = _RULES.get(name)
        if rule is None or nd < len(rule):
            return P(*((None,) * nd))
        # verify divisibility; drop axes that do not divide
        dims = leaf.shape[nd - len(rule):]
        fixed = []
        for ax, dim in zip(rule, dims):
            if ax == "data":
                ax = fsdp
            if ax is not None and dim % _axis_total(ax) == 0:
                fixed.append(ax)
            else:
                fixed.append(None)
        return P(*((None,) * (nd - len(rule))), *fixed)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def state_specs(cfg: ModelConfig, state_shape, mesh: Mesh):
    """Train-state tree: {'params': ..., 'opt': {'m','v','step'}}."""
    return {
        "params": param_specs(cfg, state_shape["params"], mesh),
        "opt": {
            "m": param_specs(cfg, state_shape["opt"]["m"], mesh),
            "v": param_specs(cfg, state_shape["opt"]["v"], mesh),
            "step": P(),
        },
    }


def batch_spec(cfg: ModelConfig, batch_shape, mesh: Mesh):
    baxes = batch_axes_for(mesh)

    def spec_for(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        b = leaf.shape[0]
        n_b = 1
        for a in baxes:
            n_b *= _axis_size(mesh, a)
        first = baxes if (b % n_b == 0 and n_b > 1) else None
        if isinstance(first, tuple) and len(first) == 1:
            first = first[0]
        return P(first, *((None,) * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_shape)


def cache_specs(cfg: ModelConfig, cache_shape, mesh: Mesh, batch: int):
    """Decode caches: [count, B, ...].  KV seq shards over 'model' (SP);
    batch over data axes when divisible, else seq additionally over 'data'.
    """
    baxes = batch_axes_for(mesh)
    n_b = 1
    for a in baxes:
        n_b *= _axis_size(mesh, a)
    shard_batch = batch % n_b == 0 and n_b > 1
    b_ax = (baxes if len(baxes) > 1 else baxes[0]) if shard_batch else None
    seq_ax = "model" if shard_batch else (
        ("data", "model") if "data" in mesh.axis_names else "model")

    def spec_for(path, leaf):
        name = _leaf_name(path)
        nd = len(leaf.shape)
        if name in ("k", "v", "xk", "xv"):
            # [count, B, n_kv, S, hd]
            S = leaf.shape[3]
            ok = True
            sa = seq_ax if isinstance(seq_ax, tuple) else (seq_ax,)
            n_s = 1
            for a in sa:
                n_s *= _axis_size(mesh, a)
            ok = S % n_s == 0
            return P(None, b_ax, None, seq_ax if ok else None, None)
        if name == "state":          # [count, B, H, P, N]
            h = leaf.shape[2]
            ax = "model" if _divisible(h, mesh, "model") else None
            return P(None, b_ax, ax, None, None)
        if name == "h":              # [count, B, w]
            w = leaf.shape[2]
            ax = "model" if _divisible(w, mesh, "model") else None
            return P(None, b_ax, ax)
        if name == "conv":           # [count, B, k, ch]
            ch = leaf.shape[3]
            ax = "model" if _divisible(ch, mesh, "model") else None
            return P(None, b_ax, None, ax)
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def serve_state_specs(state, mesh: Mesh, kv_layout: str = "shard"):
    """PartitionSpec pytree for a ``PagedServeState`` (DESIGN.md §13).

    The page *table* and every other translation leaf stay replicated —
    the VBI address space is one logical space, host-global — while the
    physical pools (KV pages, ring frames, recurrent state) shard over
    the ``model`` axis.  Candidate dims per leaf come from
    ``core/vbi/kvcache.py::SERVE_STATE_SHARD_DIMS`` (next to the state
    definition); the first candidate where ``shape[d]`` is divisible by
    and at least the axis size wins, otherwise the leaf is replicated.
    ``kv_layout='replicate'`` keeps everything replicated (the hlo_cost
    auto-layout probe compares both).
    """
    from ..core.vbi.kvcache import SERVE_STATE_SHARD_DIMS
    n_m = _axis_size(mesh, "model")
    fields = type(state).__dataclass_fields__ \
        if hasattr(type(state), "__dataclass_fields__") else {}
    specs = {}
    for name in fields:
        leaf = getattr(state, name)
        nd = len(getattr(leaf, "shape", ()))
        spec = P(*((None,) * nd))
        if kv_layout == "shard" and n_m > 1:
            for d in SERVE_STATE_SHARD_DIMS.get(name, ()):
                size = leaf.shape[d] if d < nd else 0
                if size >= n_m and size % n_m == 0:
                    axes = [None] * nd
                    axes[d] = "model"
                    spec = P(*axes)
                    break
        specs[name] = spec
    return specs


def shard_serve_state(state, mesh: Mesh, kv_layout: str = "shard"):
    """Place a ``PagedServeState``'s leaves by ``serve_state_specs``.

    Returns ``(state, shardings)`` where ``shardings`` is a state-shaped
    pytree of ``NamedSharding`` suitable for ``jax.device_put`` re-pinning
    and jit ``out_shardings``.
    """
    import dataclasses as _dc

    specs = serve_state_specs(state, mesh, kv_layout)
    shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    placed = _dc.replace(state, **{
        k: jax.device_put(getattr(state, k), sh)
        for k, sh in shardings.items()})
    sharding_tree = _dc.replace(state, **shardings)
    return placed, sharding_tree


def placement_hint(props: VBProps) -> dict:
    """Data-aware mapping hints from VBI property bits (Sec. 3.6.3 analogue):
    latency-sensitive → replicate close; bandwidth-sensitive → shard wide;
    cold → host offload tier."""
    if props & VBProps.LATENCY_SENSITIVE:
        return {"tier": "hbm", "prefer": "replicate"}
    if props & VBProps.BANDWIDTH_SENSITIVE:
        return {"tier": "hbm", "prefer": "shard_wide"}
    if props & VBProps.COLD:
        return {"tier": "host", "prefer": "shard_wide"}
    return {"tier": "hbm", "prefer": "default"}


def shardings_of(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def init_sharded_params(cfg: ModelConfig, key, mesh: Mesh):
    """``init_params`` with every leaf created directly in its
    ``param_specs`` sharding on ``mesh`` (one jitted init with
    ``out_shardings``): no device ever holds a whole sharded leaf, and the
    values do not depend on the mesh."""
    from ..models.model import init_params    # models imports this package
    init = partial(init_params, cfg)
    shardings = shardings_of(
        param_specs(cfg, jax.eval_shape(init, key), mesh), mesh)
    return jax.jit(init, out_shardings=shardings)(key)
