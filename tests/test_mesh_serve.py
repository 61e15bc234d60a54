"""Mesh-sharded paged serving (DESIGN.md §13): sharding is a layout
property of the serve state, never a value change.

  * mesh exactness: the engine on a 1×1 mesh and (when the host platform
    exposes ≥4 devices — CI sets ``XLA_FLAGS=
    --xla_force_host_platform_device_count=4``) a 1×4 mesh serves dense
    (qwen3), MoE-through-real-EP (mixtral) and recurrent-hybrid
    (recurrentgemma) stacks with outputs bit-identical to BOTH the
    unmeshed engine and the ``models/model.py`` prefill+decode_step
    reference, across decode horizons K ∈ {1, 8};
  * preemption + host-swap under pool pressure stay bit-exact on the
    sharded pool (the gather for the swap image crosses the mesh);
  * ``moe_ep`` at T=1 tokens matches the dense MoE path bit-exactly,
    and ``ep_capacity`` under the engine's serve bump keeps cap ≥
    tokens (no token may be capacity-dropped or decode diverges);
  * the Pallas kernel attention path is rejected up front on a
    >1-device mesh (it assumes a single-device page pool);
  * placement is recorded as a data property: every block carries the
    mesh's device set, sharded pools set ``VBProps.SHARDED``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vbi.address_space import VBProps
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import serve_config
from repro.models.model import decode_step, init_params, prefill
from repro.serve.engine import PagedEngine
from repro.serve.scheduler import Scheduler

N_DEV = jax.device_count()
needs4 = pytest.mark.skipif(
    N_DEV < 4, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                      "device_count=4 (CI mesh step)")

MESH_ARCHS = ("qwen3-0.6b", "mixtral-8x7b", "recurrentgemma-9b")


@pytest.fixture(scope="module")
def archs():
    out = {}
    for i, arch in enumerate(MESH_ARCHS):
        cfg = serve_config(arch)
        out[arch] = (cfg, init_params(cfg, jax.random.key(i)))
    return out


def _reference_decode(cfg, params, prompts, max_new, max_len=64):
    """models/model.py oracle: whole-prompt prefill + one-token decode
    steps, greedy, one request at a time (B=1)."""
    outs = {}
    for i, p in enumerate(prompts):
        logits, caches = prefill(cfg, params,
                                 {"tokens": jnp.asarray(p, jnp.int32)[None]},
                                 max_len=max_len)
        out = [int(jnp.argmax(logits[0, -1]))]
        pos = len(p)
        for _ in range(max_new - 1):
            logits, caches = decode_step(
                cfg, params, caches,
                jnp.asarray([[out[-1]]], jnp.int32), jnp.int32(pos))
            out.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        outs[i] = out
    return outs


def _engine_decode(cfg, params, prompts, max_new, k, **eng_kw):
    kw = dict(n_pages=33, page_size=8, max_seqs=2, max_pages_per_seq=8)
    kw.update(eng_kw)
    eng = PagedEngine(cfg, params, **kw)
    sched = Scheduler(eng, prefill_chunk=4, decode_horizon=k)
    for p in prompts:
        sched.add_request(p, max_new=max_new)
    fin = sched.run()
    return {r.rid: r.out for r in fin}, eng, sched


def _prompts(cfg, arch, n=2):
    rng = np.random.default_rng(hash(arch) % 2**31)
    return [rng.integers(0, cfg.vocab, 5).tolist() for _ in range(n)]


# ---------------------------------------------------------------------------
# exactness: 1×1 mesh (always) and 1×4 mesh (CI mesh step)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", MESH_ARCHS)
def test_mesh_1x1_matches_reference(archs, arch):
    """Degenerate mesh: the whole mesh machinery (state sharding tree,
    layout probe, param placement, logical axes) engages with n_model=1
    and must change nothing."""
    cfg, params = archs[arch]
    prompts = _prompts(cfg, arch)
    ref = _reference_decode(cfg, params, prompts, 16)
    mesh = make_host_mesh(data=1, model=1)
    for k in (1, 8):
        plain, _, _ = _engine_decode(cfg, params, prompts, 16, k)
        meshed, eng, _ = _engine_decode(cfg, params, prompts, 16, k,
                                        mesh=mesh)
        assert plain == ref, f"{arch} K={k}: unmeshed engine diverged"
        assert meshed == ref, f"{arch} K={k}: 1x1 mesh diverged"
        assert eng.kv_layout in ("shard", "replicate")
        assert len(eng.placement) == 1


@needs4
@pytest.mark.parametrize("arch", MESH_ARCHS)
@pytest.mark.parametrize("kv_layout", ("auto", "shard", "replicate"))
def test_mesh_4dev_matches_reference(archs, arch, kv_layout):
    """The tentpole acceptance: a 4-way model-sharded engine is bit-exact
    vs the dense reference for dense, EP-MoE and recurrent stacks, for
    every kv layout the probe can choose."""
    cfg, params = archs[arch]
    prompts = _prompts(cfg, arch)
    ref = _reference_decode(cfg, params, prompts, 16)
    mesh = make_host_mesh(data=1, model=4)
    for k in (1, 8):
        out, eng, _ = _engine_decode(cfg, params, prompts, 16, k,
                                     mesh=mesh, kv_layout=kv_layout)
        assert out == ref, f"{arch} K={k} {kv_layout}: mesh diverged"
        assert len(eng.placement) == 4
        assert eng.free_pages == eng.alloc.free_pages


@needs4
def test_mesh_preemption_and_swap_exactness(archs):
    """Pool pressure on the sharded pool: discard + re-prefill and
    host-swap resume (the swap image gathers pages across the mesh) both
    stay bit-identical to the roomy run."""
    cfg, params = archs["qwen3-0.6b"]
    prompts = _prompts(cfg, "qwen3-0.6b")
    mesh = make_host_mesh(data=1, model=4)
    roomy, _, _ = _engine_decode(cfg, params, prompts, 12, 4,
                                 n_pages=33, page_size=4, mesh=mesh)
    tight = dict(n_pages=8, page_size=4, mesh=mesh)
    discard, _, s_d = _engine_decode(cfg, params, prompts, 12, 4, **tight)
    swapped, eng, s_s = _engine_decode(cfg, params, prompts, 12, 4,
                                       host_swap_pages=32, **tight)
    assert s_d.stats["preemptions"] >= 1 and s_s.stats["swap_ins"] >= 1
    assert discard == roomy and swapped == roomy
    assert eng.alloc.swap.used_pages == 0           # tier drained
    assert eng.free_pages == eng.alloc.free_pages == 7


# ---------------------------------------------------------------------------
# EP vs dense MoE (satellite 2)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(N_DEV < 2, reason="EP needs a >1 'model' axis")
def test_moe_ep_T1_matches_dense_bitexact(archs):
    """moe_ep at T=1 tokens — the decode corner where capacity math is
    tightest — returns bit-identical values to the dense local path, and
    the engine's capacity bump guarantees cap >= tokens."""
    import dataclasses

    from repro.distributed.axes import logical_axes
    from repro.distributed.moe_ep import ep_capacity, moe_ep
    from repro.models.layers import moe

    n_m = 4 if N_DEV >= 4 else 2
    mesh = make_host_mesh(data=1, model=n_m)
    cfg, params = archs["mixtral-8x7b"]
    E, K = cfg.n_experts, cfg.top_k
    cfg = dataclasses.replace(cfg, capacity_factor=max(
        cfg.capacity_factor, E / K))                # the engine's bump
    # stage params are layer-stacked; peel layer 0's MoE weights
    moe_params = jax.tree_util.tree_map(
        lambda a: a[0], params["stages"][0][0]["moe"])

    for B, S in ((1, 1), (2, 1), (4, 1)):
        cap, t_loc = ep_capacity(cfg, mesh, B, S)
        assert cap >= t_loc, f"cap {cap} < T_loc {t_loc} at B={B},S={S}"
        x = jax.random.normal(jax.random.key(B), (B, S, cfg.d_model))
        dense = moe(moe_params, x, cfg)             # no ctx: local path
        with logical_axes(mesh, cfg.n_experts):
            ep = moe_ep(moe_params, x, cfg, mesh)
        assert jnp.array_equal(dense, ep), \
            f"EP diverged from dense at B={B},S={S}"


# ---------------------------------------------------------------------------
# guard rails + placement property
# ---------------------------------------------------------------------------

@needs4
def test_kernel_attention_rejected_on_mesh(archs):
    cfg, params = archs["qwen3-0.6b"]
    mesh = make_host_mesh(data=1, model=4)
    with pytest.raises(ValueError, match="kernel"):
        PagedEngine(cfg, params, n_pages=9, page_size=8, max_seqs=1,
                    attn_impl="kernel", mesh=mesh)


@needs4
def test_placement_is_a_block_property(archs):
    """Every allocated block carries the mesh's device set and the
    SHARDED props bit; the degenerate mesh carries a single device and
    no bit."""
    cfg, params = archs["qwen3-0.6b"]
    mesh = make_host_mesh(data=1, model=4)
    eng = PagedEngine(cfg, params, n_pages=17, page_size=8, max_seqs=2,
                      mesh=mesh)
    blk = eng.alloc.alloc(0)
    assert blk.placement == eng.placement and len(blk.placement) == 4
    assert blk.props & VBProps.SHARDED
    eng.alloc.free(blk)

    eng1 = PagedEngine(cfg, params, n_pages=17, page_size=8, max_seqs=2,
                       mesh=make_host_mesh(data=1, model=1))
    blk1 = eng1.alloc.alloc(0)
    assert len(blk1.placement) == 1
    assert not (blk1.props & VBProps.SHARDED)
    eng1.alloc.free(blk1)


@pytest.mark.parametrize("shape,axes", [((1, 1), ("data", "model")),
                                        ((1,), ("pod",))])
def test_meshes_are_built_with_auto_axes(shape, axes):
    """JAX 0.9's make_mesh defaults to Explicit axes, under which the
    vocab-sharded embedding gather raises; every mesh here is Auto."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert all(t == AxisType.Auto for t in mesh.axis_types)
