"""Multi-device behaviour (8 host devices in a subprocess each, since the
main pytest process must keep jax at 1 device): sharded training, EP MoE,
elastic checkpoint resharding, pipeline parallelism, compressed psum."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def run_py(body: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=ENV, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import ModelConfig
        from repro.train.step import init_train_state, make_train_step
        from repro.optim.adamw import AdamWConfig
        from repro.distributed.sharding import state_specs, batch_spec, shardings_of
        from repro.distributed.axes import logical_axes
        from repro.data.pipeline import SyntheticLMData

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv=2, head_dim=16, d_ff=128,
                          vocab=256, remat=False, param_dtype="float32")
        opt = AdamWConfig(lr=1e-3)
        batch = {k: jnp.asarray(v) for k, v in
                 SyntheticLMData(cfg, 8, 32, 0).batch_at(0).items()}
        state = init_train_state(cfg, opt, jax.random.key(0))
        step = make_train_step(cfg, opt)
        # single device reference
        s_ref, m_ref = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)
        # sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, logical_axes(mesh):
            st_sh = shardings_of(state_specs(cfg, jax.eval_shape(lambda: state), mesh), mesh)
            b_sh = shardings_of(batch_spec(cfg, jax.eval_shape(lambda: batch), mesh), mesh)
            st = jax.device_put(state, st_sh)
            bt = jax.device_put(batch, b_sh)
            s_new, m = jax.jit(step, in_shardings=(st_sh, b_sh))(st, bt)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-4, (m, m_ref)
        d = jax.tree.map(lambda a, b: float(jnp.abs(jnp.asarray(a, jnp.float32)
                         - jnp.asarray(b, jnp.float32)).max()),
                         s_new["params"], s_ref["params"])
        assert max(jax.tree.leaves(d)) < 1e-4
        print("SHARDED OK")
        """)


def test_moe_ep_and_decode_on_mesh():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models.config import ModelConfig
        from repro.models.layers import _moe_local
        from repro.distributed.moe_ep import moe_ep
        from repro.models.model import _init_moe
        cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                          n_heads=4, n_kv=2, head_dim=8, d_ff=0,
                          expert_d_ff=48, vocab=64, n_experts=8, top_k=2,
                          capacity_factor=8.0, moe_groups=1,
                          param_dtype="float32", compute_dtype="float32")
        mesh = make_mesh((2, 4), ("data", "model"))
        p = _init_moe(cfg, jax.random.key(1), jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 16, 32)),
                        jnp.float32)
        ref = _moe_local(p, x.reshape(64, 32), cfg).reshape(4, 16, 32)
        with mesh:
            out = jax.jit(lambda pp, xx: moe_ep(pp, xx, cfg, mesh))(p, x)
        err = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert err < 2e-5, err
        print("MOE EP OK")
        """)


def test_elastic_checkpoint_resharding(tmp_path):
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import ModelConfig
        from repro.train.step import init_train_state
        from repro.optim.adamw import AdamWConfig
        from repro.distributed.sharding import state_specs, shardings_of
        from repro.checkpoint import save_pytree, restore_pytree

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv=2, head_dim=16, d_ff=128,
                          vocab=256, param_dtype="float32")
        opt = AdamWConfig()
        state = init_train_state(cfg, opt, jax.random.key(3))
        shape = jax.eval_shape(lambda: state)
        # save while sharded on an 8-chip mesh
        meshA = make_mesh((2, 4), ("data", "model"))
        stA = jax.device_put(state, shardings_of(
            state_specs(cfg, shape, meshA), meshA))
        save_pytree(stA, r"{tmp_path}", 1)
        # restore onto a DIFFERENT (shrunk) mesh — elastic restart
        meshB = make_mesh((2, 2), ("data", "model"))
        shB = shardings_of(state_specs(cfg, shape, meshB), meshB)
        stB = restore_pytree(state, r"{tmp_path}", 1, shB)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(stB)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert len(set(d.device.id if hasattr(d, 'device') else 0
                   for d in jax.tree.leaves(stB)[0].addressable_shards)) > 1
        print("ELASTIC OK")
        """)


def test_pipeline_parallel_matches_sequential():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipeline_apply

        P_st, M, mb, d = 4, 6, 8, 16
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.standard_normal((P_st, d, d)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((M, mb, d)), jnp.float32)

        def stage(w, h):
            return jnp.tanh(h @ w)

        # sequential reference
        ref = x
        for i in range(P_st):
            ref = jax.vmap(lambda h: stage(Ws[i], h))(ref)

        mesh = make_mesh((4, 2), ("pipe", "data"))
        out = pipeline_apply(stage, Ws, x, mesh, axis="pipe")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        print("PIPELINE OK")
        """)


def test_compressed_psum_under_shard_map():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum

        mesh = make_mesh((8,), ("pod",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64))
                        * 0.01, jnp.float32)
        f = shard_map(lambda s: compressed_psum(s[0], "pod")[None],
                      mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                      check_rep=False)
        got = np.asarray(f(x))[0]
        ref = np.asarray(x.sum(0))
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.05, rel
        print("COMPRESSED PSUM OK")
        """)


def test_dryrun_cell_on_small_mesh():
    """Smoke-config dry-run lowering on an 8-device mesh — the in-test
    version of the 512-device sweep (which runs as its own process)."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import smoke_config
        from repro.train.step import make_train_step, abstract_train_state
        from repro.optim.adamw import AdamWConfig
        from repro.launch.specs import batch_specs
        from repro.distributed.sharding import state_specs, batch_spec, shardings_of
        from repro.distributed.axes import logical_axes
        from repro.distributed.hlo_cost import analyze_hlo

        cfg = smoke_config("gemma3-12b")
        opt = AdamWConfig()
        st = abstract_train_state(cfg, opt)
        batch = batch_specs(cfg, 8, 64)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, logical_axes(mesh):
            in_sh = (shardings_of(state_specs(cfg, st, mesh), mesh),
                     shardings_of(batch_spec(cfg, batch, mesh), mesh))
            comp = jax.jit(make_train_step(cfg, opt), in_shardings=in_sh,
                           donate_argnums=(0,)).lower(st, batch).compile()
        mem = comp.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        r = analyze_hlo(comp.as_text())
        assert r["flops"] > 0 and r["collectives"]["total"] > 0
        print("DRYRUN-SMALL OK")
        """)
