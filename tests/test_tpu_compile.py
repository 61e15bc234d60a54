"""The serving path's programs compile for a TPU v5e at published widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, which refuses what the chip would refuse (misaligned kernel
tiles, too much VMEM or HBM).  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only the
worker that runs this file loads the TPU library.

* the Pallas paged-attention kernel at qwen3-0.6b's head shape (8 KV heads,
  head_dim 128, 2 query heads per KV head), pages of 8 and 16;
* qwen3-0.6b's decode step, prefill chunk and K=8 fused decode horizon,
  gather path, from ``jax.eval_shape`` parameters;
* the same three programs copy no layer's KV pool: attention gathers a
  slot's rows straight out of the stacked pool;
* the kernel-path decode step, which must hold the kernel as a
  ``tpu_custom_call`` (the interpret switch is steered in the test).
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import kernel as pa_kernel
from repro.launch.serve import serve_config
from repro.models.model import init_params
from repro.serve.engine import PagedEngine


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a described chip's executables cannot be read back here, so the
        # persistent cache stays off while these programs compile
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            from jax.experimental import topologies
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:     # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _engine(one_chip, attn_impl="gather", n_pages=33):
    """qwen3-0.6b at its published widths over abstract parameters; with
    no mesh the engine never touches them."""
    cfg = serve_config("qwen3-0.6b", smoke=False)
    params = jax.eval_shape(partial(init_params, cfg), jax.random.key(0))
    eng = PagedEngine(cfg, params, page_size=8, max_seqs=4, n_pages=n_pages,
                      attn_impl=attn_impl)
    return eng, _on(one_chip, params), _on(one_chip, eng.state)


def _lower(one_chip, eng, params, state, program):
    S = eng.max_seqs
    i32 = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)
    if program == "decode":
        return eng._decode.lower(params, state, i32, mask)
    if program == "prefill":
        chunk = jax.ShapeDtypeStruct((S, 8), jnp.int32, sharding=one_chip)
        return eng._prefill.lower(params, state, chunk, i32)
    return eng._horizon_fn(8).lower(params, state, i32, mask, i32)


@pytest.mark.parametrize("page_size", (8, 16))
def test_paged_attention_kernel_compiles(one_chip, page_size):
    n_kv, g, hd, n_pages, max_pages = 8, 2, 128, 64, 16
    args = (jax.ShapeDtypeStruct((max_pages,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((n_kv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_pages, page_size, n_kv, hd),
                                 jnp.float32),
            jax.ShapeDtypeStruct((n_pages, page_size, n_kv, hd),
                                 jnp.float32))
    fn = jax.jit(partial(pa_kernel.paged_attn_one_seq, interpret=False))
    text = fn.lower(*_on(one_chip, args)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("program", ("decode", "prefill", "decode_many"))
def test_serve_programs_compile_at_published_widths(one_chip, program):
    eng, params, state = _engine(one_chip)
    mem = _lower(one_chip, eng, params, state,
                 program).compile().memory_analysis()
    # the f32 weights are the arguments: ~2.4 GB at published widths
    assert mem.argument_size_in_bytes > 2e9


@pytest.mark.parametrize("program", ("decode", "prefill", "decode_many"))
def test_serve_programs_copy_no_layer_pool(one_chip, program):
    # 37 pages is no other dimension of the program, so one layer's pool
    # [37, page_size, n_kv, head_dim] shows in the compiled text, in any
    # dtype, only if something copies it out of the stacked pool
    eng, params, state = _engine(one_chip, n_pages=37)
    assert state.k_pages.shape == (28, 37, 8, 8, 128)
    text = _lower(one_chip, eng, params, state, program).compile().as_text()
    assert "[28,37,8,8,128]" in text
    copies = [ln for ln in text.splitlines()
              if re.search(r"[a-z]\d*\[37,8,8,128\]", ln)]
    assert not copies, copies[:3]


def test_kernel_decode_step_holds_the_kernel(one_chip, monkeypatch):
    # the backend here is the CPU; steer the kernel's run mode to the chip's
    monkeypatch.setattr(pa_kernel, "resolve_interpret", lambda i: False)
    eng, params, state = _engine(one_chip, attn_impl="kernel")
    S = eng.max_seqs
    text = eng._decode.lower(
        params, state,
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text
