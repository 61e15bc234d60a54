"""The jitted continuous-batching serve engine (serve/engine.py):

  * numerical equivalence with the legacy per-sequence PagedServer;
  * scheduler admission/eviction reuses freed pages (device free stack);
  * the decode step performs NO host transfers (jax.transfer_guard) and
    donates the KV state;
  * attention gathers a slot's rows straight out of the stacked pool,
    bit for bit what slicing the layer's pool and gathering from it gave;
  * preemption under pool pressure keeps results well-formed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vbi.kvcache import (init_serve_state, make_ring_table,
                                    release_slot)
from repro.launch.serve import serve_config
from repro.models.model import init_params
from repro.serve.engine import PagedEngine, batched_paged_attention
from repro.serve.paged import PagedServer
from repro.serve.scheduler import Scheduler


@pytest.fixture(scope="module")
def setup():
    cfg = serve_config("qwen3-0.6b")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def test_decode_batch_matches_legacy(setup):
    """Jitted batched decode == per-sequence reference, over several steps
    with ragged admission (slots 0 and 2 active, different histories)."""
    cfg, params = setup
    srv = PagedServer(cfg, params, n_pages=64, page_size=4, max_seqs=4)
    eng = PagedEngine(cfg, params, n_pages=64, page_size=4, max_seqs=4,
                      max_pages_per_seq=8)
    for s in (0, 2):
        srv.admit(s)
        eng.alloc.alloc(s)
    mask = jnp.asarray([True, False, True, False])
    rng = np.random.default_rng(1)
    for step in range(7):              # crosses page boundaries (ps=4)
        pair = rng.integers(0, cfg.vocab, 2)
        legacy = srv.decode(jnp.asarray(pair, jnp.int32)[:, None], [0, 2])
        full = jnp.zeros((4,), jnp.int32).at[0].set(int(pair[0])) \
            .at[2].set(int(pair[1]))
        batched = eng.decode(full, mask)
        np.testing.assert_allclose(
            np.asarray(legacy), np.asarray(batched[jnp.asarray([0, 2])]),
            rtol=1e-5, atol=1e-5, err_msg=f"step {step}")


def test_prefill_chunk_matches_tokenwise_decode(setup):
    """A chunked prefill lands the same KV/next-token as feeding the prompt
    one decode step at a time (the argmax now lives inside the jitted
    prefill, so only [S] int32 ever crosses the jit boundary)."""
    cfg, params = setup
    prompt = np.asarray([[3, 1, 4, 1, 5], [9, 2, 6, 5, 3]], np.int32)
    eng_a = PagedEngine(cfg, params, n_pages=32, page_size=4, max_seqs=2,
                        max_pages_per_seq=4)
    eng_b = PagedEngine(cfg, params, n_pages=32, page_size=4, max_seqs=2,
                        max_pages_per_seq=4)
    for s in range(2):
        eng_a.alloc.alloc(s)
        eng_b.alloc.alloc(s)
    nxt_a = eng_a.prefill_chunk(
        jnp.asarray(prompt), jnp.full((2,), prompt.shape[1], jnp.int32))
    assert nxt_a.shape == (2,) and nxt_a.dtype == jnp.int32
    mask = jnp.ones((2,), bool)
    for c in range(prompt.shape[1]):
        logits_b = eng_b.decode(jnp.asarray(prompt[:, c]), mask)
    np.testing.assert_array_equal(
        np.asarray(nxt_a), np.asarray(jnp.argmax(logits_b[:, 0], -1)))
    np.testing.assert_array_equal(np.asarray(eng_a.state.seq_lens),
                                  np.asarray(eng_b.state.seq_lens))
    # the decode KV landed identically: same logits from both engines next
    logits_a2 = eng_a.decode(nxt_a, mask)
    logits_b2 = eng_b.decode(nxt_a, mask)
    np.testing.assert_allclose(np.asarray(logits_a2), np.asarray(logits_b2),
                               rtol=1e-5, atol=1e-5)


def test_scheduler_reuses_freed_pages(setup):
    """Pages released by finished requests are recycled: serving more
    requests than the pool could hold simultaneously succeeds, and the free
    stack returns to its initial level."""
    cfg, params = setup
    # pool: 12 usable pages; each request needs 3 (8-token prompt+gen @ ps=4)
    eng = PagedEngine(cfg, params, n_pages=13, page_size=4, max_seqs=2,
                      max_pages_per_seq=4)
    sched = Scheduler(eng, prefill_chunk=4)
    rng = np.random.default_rng(0)
    n_requests = 8                      # 8 * 3 = 24 pages >> pool of 12
    for _ in range(n_requests):
        sched.add_request(rng.integers(0, cfg.vocab, 5).tolist(), max_new=4)
    finished = sched.run()
    assert len(finished) == n_requests
    assert all(len(r.out) == 4 for r in finished)
    assert eng.free_pages == 12                 # everything returned
    # the allocator's host mirror stayed exact
    assert eng.free_pages == eng.alloc.free_pages
    assert eng.alloc.stats["frees"] == n_requests


def test_release_slot_returns_pages_to_free_stack():
    """Device-side release pushes exactly the owned pages back."""
    state = init_serve_state(n_layers=1, n_pages=9, page_size=2, n_kv=1,
                             head_dim=2, max_seqs=2, max_pages_per_seq=4)
    # hand-craft: slot 0 owns pages 5 and 3, length 3 (2 pages)
    state = dataclasses.replace(
        state,
        page_table=state.page_table.at[0, 0].set(5).at[0, 1].set(3),
        seq_lens=state.seq_lens.at[0].set(3),
        slot_active=state.slot_active.at[0].set(True),
        free_top=jnp.asarray(4, jnp.int32))
    out = release_slot(state, jnp.int32(0))
    assert int(out.free_top) == 6
    np.testing.assert_array_equal(np.asarray(out.free_stack[4:6]), [5, 3])
    assert int(out.seq_lens[0]) == 0
    assert not bool(out.slot_active[0])


def _slice_then_gather(q, k_pages, v_pages, layer, page_table, seq_lens,
                       max_pages):
    """The earlier formulation: copy out the layer's pool, then gather."""
    k_l, v_l = k_pages[layer], v_pages[layer]
    pts = page_table[:, :max_pages]
    S, P = pts.shape
    ps = k_l.shape[1]
    k = k_l[pts].reshape(S, P * ps, *k_l.shape[2:])
    v = v_l[pts].reshape(S, P * ps, *v_l.shape[2:])
    s = jnp.einsum("shgd,sphd->shgp", q, k.astype(q.dtype))
    mask = (jnp.arange(P * ps)[None] < seq_lens[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("shgp,sphd->shgd", p, v.astype(q.dtype))
    return out / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)


@pytest.mark.parametrize("pool", ("full", "ring"))
def test_attention_gathers_through_the_stacked_pool(pool):
    """One (layer, page) gather over the stacked pool reads, bit for bit,
    what slicing out the layer's pool and then gathering read: slots of
    mixed lengths, an empty slot, rows that end in the null page 0."""
    L, ps, n_kv, g, hd, S = 3, 4, 2, 2, 8, 4
    if pool == "full":
        n_pages, max_pages = 13, 3
        table = np.zeros((S, 4), np.int32)
        table[0, :3] = (5, 2, 9)
        table[1, :1] = 7                         # then the null page
        table[3, :3] = (1, 12, 3)
        lens = np.asarray([11, 3, 0, 12], np.int32)
    else:
        max_pages = 2                            # ring pages per slot
        table = make_ring_table(S, max_pages)
        n_pages = 1 + S * max_pages
        lens = np.minimum([11, 3, 0, 6], max_pages * ps).astype(np.int32)
    rng = np.random.default_rng(7)
    k_pages, v_pages = (jnp.asarray(rng.standard_normal(
        (L, n_pages, ps, n_kv, hd)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((S, n_kv, g, hd)), jnp.float32)
    table, lens = jnp.asarray(table), jnp.asarray(lens)
    new = jax.jit(batched_paged_attention, static_argnums=6)
    old = jax.jit(_slice_then_gather, static_argnums=6)
    for li in range(L):
        args = (q, k_pages, v_pages, jnp.int32(li), table, lens, max_pages)
        out = np.asarray(new(*args))
        np.testing.assert_array_equal(out, np.asarray(old(*args)))
        assert not out[2].any()                  # the empty slot reads 0
        assert np.isfinite(out).all()


def test_decode_step_no_host_transfers(setup):
    """The tentpole contract: after warmup, a decode step triggers zero
    implicit device→host transfers (no .max() host sync, no per-layer
    writebacks), and the donated state is consumed."""
    cfg, params = setup
    eng = PagedEngine(cfg, params, n_pages=32, page_size=4, max_seqs=2,
                      max_pages_per_seq=4)
    eng.alloc.alloc(0)
    eng.alloc.alloc(1)
    mask = jax.device_put(jnp.ones((2,), bool))
    toks = jax.device_put(jnp.asarray([1, 2], jnp.int32))
    eng.decode(toks, mask)                       # compile/warmup
    prev_state = eng.state
    with jax.transfer_guard("disallow"):
        logits = eng.decode(toks, mask)
        jax.block_until_ready(logits)
    # state was donated into the step (legacy path can't do this: it reads
    # seq_lens back to the host every token)
    assert prev_state.k_pages.is_deleted()


def test_preemption_under_pool_pressure(setup):
    """When decode would exhaust the pool, the youngest request is
    preempted, requeued with its generated prefix, and finishes later."""
    cfg, params = setup
    # 5 usable pages, 2 slots; both admit with 2 reserved pages each, then
    # each grows to 4 pages (8 tokens @ ps=2) ⇒ 8 > 5: the younger request
    # is preempted mid-decode and finishes after the older one releases.
    eng = PagedEngine(cfg, params, n_pages=6, page_size=2, max_seqs=2,
                      max_pages_per_seq=4)
    sched = Scheduler(eng, prefill_chunk=4)
    rng = np.random.default_rng(0)
    sched.add_request(rng.integers(0, cfg.vocab, 2).tolist(), max_new=6)
    sched.add_request(rng.integers(0, cfg.vocab, 2).tolist(), max_new=6)
    finished = sched.run()
    assert len(finished) == 2
    assert all(len(r.out) == 6 for r in finished)
    assert sched.stats["preemptions"] >= 1
    assert eng.free_pages == 5


def test_scheduler_rejects_oversized_request(setup):
    cfg, params = setup
    eng = PagedEngine(cfg, params, n_pages=4, page_size=2, max_seqs=2,
                      max_pages_per_seq=4)
    sched = Scheduler(eng, prefill_chunk=4)
    # exceeds one slot's page-table row (4 pages × 2 tokens): refused at
    # intake — past the row the device scatter would silently corrupt KV
    with pytest.raises(ValueError, match="per-slot capacity"):
        sched.add_request(list(range(12)), max_new=2)
    # fits a slot (8 ≤ 8 tokens) but its 5-page budget can never fit the
    # 3-page pool: also refused at intake now (used to fail late, in run())
    with pytest.raises(ValueError, match="pool capacity"):
        sched.add_request(list(range(6)), max_new=2)
    # with a prefix cache the prompt pages *could* be shared, so intake
    # accepts — but nothing is cached, so run() detects the impossibility
    from repro.serve.prefix_cache import PrefixCache
    sched = Scheduler(eng, prefill_chunk=4,
                      prefix_cache=PrefixCache(page_size=2))
    sched.add_request(list(range(6)), max_new=2)
    with pytest.raises(RuntimeError, match="pages"):
        sched.run()
