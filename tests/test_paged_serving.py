"""Personalized paged serving: block-paged KV cache + per-user deltas.

The anchors, mirroring tests/test_decode.py's dense-slab suite:

* paged == fixed-slot == solo, greedy, BITWISE — the paged attention
  contracts its (pages, page_size) axes in the same logical order the
  dense kernel reads its (max_len,) axis, so any paging bug (wrong
  physical page, stale page attendable, frontier misallocation) is a
  token mismatch here;
* ONE compiled paged step + ONE pack program per server lifetime,
  across admissions, evictions, page-boundary crossings and prefix
  sharing (the page table crosses as a traced argument);
* prefix sharing is pure HBM bookkeeping: refcounts rise on the second
  sharer, pages free only when the last sharer retires, replies are
  unchanged;
* a personalization delta of all zeros touches NOTHING — the served
  params object is literally the base object, so personalized serving
  with an empty store is bitwise-identical to unpersonalized serving;
* the ``decode_paged`` graft audit passes on the real paged step and
  FAILS on the dense-slab mutation (what makes the pass meaningful).
"""

import jax
import numpy as np
import pytest

from commefficient_tpu.data.tokenizer import ByteTokenizer
from commefficient_tpu.serving import (ContinuousBatchingServer,
                                       PagedKVCache,
                                       PersonalizationIndex)


@pytest.fixture(scope="module")
def tiny(serving_tiny_engine):
    # ONE engine shared with test_speculative (conftest session
    # fixture): every test drives the same jit caches, so
    # prefill/pack/step compile once per shape for the whole suite
    # (the parity test clears the jits it counts before counting)
    return serving_tiny_engine


def _engine_and_prompts(tiny, n=3):
    tok, model, params, engine = tiny
    texts = ["hello there", "do you like fish", "the weather is nice",
             "tell me a story", "what is your name", "where are you from",
             "sing me a song", "how old are you", "good morning friend",
             "what time is it"][:n]
    prompts = []
    for t in texts:
        ids = tok.encode(t)
        prompts.append((ids, [1] * len(ids)))
    return engine, prompts


def test_paged_matches_fixed_and_solo_one_compile(tiny):
    """Greedy token parity, bitwise, at batch 1 and 8: every reply from
    the paged server equals the fixed-slot server's reply AND the solo
    engine's — and the paged step/pack programs each compiled exactly
    ONCE PER SERVER across all the admission/eviction churn (the second
    slot count adds exactly one program, nothing recompiles per
    admission, per budget, or per page-boundary crossing)."""
    n = 10
    engine, prompts = _engine_and_prompts(tiny, n=n)
    budgets = [8, 3, 8, 1, 6, 5, 2, 8, 4, 7][:n]
    # the engine is shared by every serving test file of this worker
    # (conftest.serving_tiny_engine): count from empty caches, whichever
    # file ran first
    engine.paged_step.clear_cache()
    engine.paged_insert.clear_cache()

    def run(kv, slots):
        srv = ContinuousBatchingServer(engine, slots=slots,
                                       prefill_len=32, kv_cache=kv)
        rids = [srv.submit(ids, types, types[-1], budgets[i])
                for i, (ids, types) in enumerate(prompts)]
        replies = srv.run()
        return [replies[r] for r in rids]

    # one solo program (max_new=8) covers every budget: greedy chains
    # are deterministic, so stopping at budget b is the 8-token chain's
    # prefix (eos latches identically on both sides)
    solo8 = [engine.generate([(ids, types)], [types[-1]], max_new=8)[0]
             for ids, types in prompts]
    compiles = []
    for slots in (1, 8):
        paged = run("paged", slots)
        compiles.append((engine.paged_step._cache_size(),
                         engine.paged_insert._cache_size()))
        for i in range(n):
            assert paged[i] == solo8[i][:budgets[i]]
    assert paged == run("fixed", 8)  # the dense slab, same request churn
    assert compiles == [(1, 1), (2, 2)]


def test_prefix_share_refcounts_and_eviction(tiny):
    """Two slots admitted with the same prompt share its full pages:
    the second admission allocates nothing for the shared prefix
    (refcount 2 on the same physical pages), replies stay bitwise
    identical, and the pages return to the free list only when BOTH
    slots have retired."""
    engine, _ = _engine_and_prompts(tiny, n=1)
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged", page_size=8)
    tok = ByteTokenizer()
    ids = tok.encode("the weather is nice")    # >= 2 full 8-token pages
    assert len(ids) >= 16
    full_pages = len(ids) // 8
    types = [1] * len(ids)
    srv.submit(ids, types, 1, 6)
    srv.submit(ids, types, 1, 3)
    srv.step()                                  # both admitted
    pg = srv.pager
    assert pg.shared_hits == full_pages
    assert (pg.table[0, :full_pages] == pg.table[1, :full_pages]).all()
    assert (pg.refcount[pg.table[0, :full_pages]] == 2).all()
    shared_phys = set(int(p) for p in pg.table[0, :full_pages])
    replies = srv.run()
    assert replies[1] == replies[0][:3]         # same greedy chain
    assert pg.pages_in_use == 0                 # last sharer freed them
    assert all(pg.refcount[p] == 0 for p in shared_phys)
    # a fresh admission may reuse the freed physical pages
    srv.submit(ids, types, 1, 2)
    srv.run()
    assert pg.pages_in_use == 0


def test_paged_pool_exhaustion_is_loud(tiny):
    engine, prompts = _engine_and_prompts(tiny, n=2)
    with pytest.raises(ValueError, match="multiple of"):
        PagedKVCache(slots=2, max_len=48, prefill_len=30, page_size=16)
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=16,
                                   kv_cache="paged", page_size=8,
                                   num_pages=3)  # garbage + 2 pages
    srv.submit(prompts[0][0], prompts[0][1], 1, 8)
    srv.submit(prompts[1][0], prompts[1][1], 1, 8)
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        srv.run()


def test_paged_drain_then_fresh_server_matches_solo(tiny):
    """drain() under paging: admitted requests finish (pages all
    returned), leftovers re-submit verbatim on a fresh paged server and
    complete with the exact solo greedy tokens."""
    engine, prompts = _engine_and_prompts(tiny, n=10)
    srv = ContinuousBatchingServer(engine, slots=8, prefill_len=32,
                                   kv_cache="paged")
    rids = [srv.submit(ids, types, types[-1], 8) for ids, types in prompts]
    srv.step()                          # admit 8, leave 2 queued
    replies, leftovers = srv.drain()
    assert len(replies) + len(leftovers) == len(rids)
    assert srv.pager.pages_in_use == 0
    fresh = ContinuousBatchingServer(engine, slots=8, prefill_len=32,
                                     kv_cache="paged")
    new_rids = [fresh.submit(*left) for left in leftovers]
    replies2 = fresh.run()
    got = list(replies.values()) + [replies2[r] for r in new_rids]
    solos = [engine.generate([(ids, types)], [types[-1]], max_new=8)[0]
             for ids, types in prompts]
    assert sorted(map(tuple, got)) == sorted(map(tuple, solos))


def _sparse_store(params):
    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.client_store import (HostArenaStore,
                                                          make_codec)
    flat, _ = ravel_pytree(params)
    cfg = FedConfig(mode="local_topk", error_type="local",
                    client_state="sparse", k=4,
                    num_clients=4).finalize(flat.shape[0])
    return HostArenaStore(cfg, make_codec(cfg)), int(flat.shape[0])


def test_zero_delta_personalized_serving_is_bitwise_base(tiny):
    """A user whose store row is all zeros (the init state of every one
    of the million clients) must serve EXACTLY the base model: the
    served params object is untouched and the greedy reply is bitwise
    the unpersonalized one."""
    tok, model, params, _eng = tiny
    engine, prompts = _engine_and_prompts(tiny, n=2)
    store, _ = _sparse_store(engine.params)
    index = PersonalizationIndex(engine.params, store)
    base_params = engine.params
    srv = ContinuousBatchingServer(engine, slots=8, prefill_len=32,
                                   kv_cache="paged", personalize=index)
    rid0 = srv.submit(*prompts[0], reply_type=1, max_new=8, user_id=0)
    rid1 = srv.submit(*prompts[1], reply_type=1, max_new=8)  # anonymous
    replies = srv.run()
    assert engine.params is base_params         # literally untouched
    assert not index.active
    for (ids, types), rid in zip(prompts, (rid0, rid1)):
        solo = engine.generate([(ids, types)], [types[-1]], max_new=8)[0]
        assert replies[rid] == solo


def test_personalized_delta_applies_and_restores_bitwise(tiny):
    """A real delta perturbs the served weights while the user is
    active; after the last of their slots retires, every param leaf is
    BITWISE back at base (restore scatters base values, it does not
    subtract)."""
    from jax.flatten_util import ravel_pytree
    engine, prompts = _engine_and_prompts(tiny, n=1)
    store, D = _sparse_store(engine.params)
    rng = np.random.RandomState(3)
    row = np.zeros(D, np.float32)
    row[rng.choice(D, 3, replace=False)] = [0.5, -1.25, 2.0]
    store.set_row("errors", 1, store.codec.encode_row_np(row))
    index = PersonalizationIndex(engine.params, store)
    base_flat = np.asarray(ravel_pytree(engine.params)[0])
    srv = ContinuousBatchingServer(engine, slots=8, prefill_len=32,
                                   kv_cache="paged", personalize=index)
    srv.submit(*prompts[0], reply_type=1, max_new=4, user_id=1)
    srv.step()
    served = np.asarray(ravel_pytree(engine.params)[0])
    expect = base_flat.copy()
    expect[row != 0] += row[row != 0]
    np.testing.assert_array_equal(served, expect.astype(np.float32))
    srv.run()
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(engine.params)[0]), base_flat)
    assert not index.active
    # prefix sharing is disabled whenever an index is attached: page
    # content depends on the active deltas, so cross-user sharing would
    # serve one user's pages to another
    assert srv.pager.share_prefix is False


def test_personalization_requires_sparse_codec_and_user_gate(tiny):
    tok, model, params, _eng = tiny
    engine, prompts = _engine_and_prompts(tiny, n=1)

    class _FakeCodec:
        name = "sketched"

    class _FakeStore:
        codec = _FakeCodec()

    with pytest.raises(ValueError, match="sparse"):
        PersonalizationIndex(params, _FakeStore())
    srv = ContinuousBatchingServer(engine, slots=1, prefill_len=32,
                                   kv_cache="paged")
    with pytest.raises(ValueError, match="user_id"):
        srv.submit(*prompts[0], reply_type=1, max_new=2, user_id=7)
    with pytest.raises(ValueError, match="kv_cache"):
        ContinuousBatchingServer(engine, slots=1, prefill_len=32,
                                 kv_cache="ragged")


def test_personalization_from_checkpoint_gate(tiny):
    """Legacy checkpoints (no client_state fingerprint) serve
    unpersonalized with a warning; a non-sparse fingerprint refuses
    loudly; sparse builds the index."""
    from commefficient_tpu.serving import personalization_from_checkpoint
    tok, model, params, _eng = tiny
    store, _ = _sparse_store(params)
    with pytest.warns(UserWarning, match="unpersonalized"):
        assert personalization_from_checkpoint(None, store, params) is None
    with pytest.warns(UserWarning, match="unpersonalized"):
        assert personalization_from_checkpoint({}, store, params) is None
    with pytest.raises(ValueError, match="sparse"):
        personalization_from_checkpoint({"client_state": "sketched"},
                                        store, params)
    idx = personalization_from_checkpoint({"client_state": "sparse"},
                                          store, params)
    assert isinstance(idx, PersonalizationIndex)


@pytest.mark.parametrize("program", ["pack", "step"])
def test_paged_programs_trace_at_gpt2_small(gpt2_small_shapes, paged_shapes,
                                            program):
    """KV-pool and page-table signature gate at published widths, 8
    slots of 128 + 64 tokens in pages of 16: the pack of a prefilled B=1
    row into pool pages and the page-table-traced paged step. The pools
    stay (num_pages, page_size, H, hd) end to end. Nothing compiles."""
    import jax.numpy as jnp
    engine, P, B = gpt2_small_shapes.engine(), gpt2_small_shapes.P, 8
    params, cfg = engine.params, engine.model.config
    pager, pools, pt, vec, done = paged_shapes(engine, B, P)
    if program == "pack":
        ids1 = jax.ShapeDtypeStruct((1, P), jnp.int32)
        _, row_cache = jax.eval_shape(
            engine._prefill_raw, params,
            jax.eval_shape(lambda: engine.init_cache(1)), ids1, ids1,
            jax.ShapeDtypeStruct((1,), jnp.int32))
        new_pools = jax.eval_shape(
            engine._paged_insert_raw, pools, row_cache,
            jax.ShapeDtypeStruct((pager.prefill_pages,), jnp.int32))
    else:
        new_pools, nxt, pos, _, new_done = jax.eval_shape(
            engine._paged_step_raw, params, pools, pt, vec, vec, vec,
            jax.random.PRNGKey(0), done)
        assert (nxt.shape, pos.shape, new_done.shape) == ((B,),) * 3
    page = (pager.num_pages, 16, cfg.n_head, cfg.n_embd // cfg.n_head)
    assert len(new_pools) == cfg.n_layer
    assert {x.shape for x in jax.tree.leaves(new_pools)} == {page}
    assert all(x.dtype == cfg.jnp_dtype for x in jax.tree.leaves(new_pools))


@pytest.mark.audit
def test_decode_paged_audit_passes_at_head():
    from commefficient_tpu.analysis.targets import decode_paged_target
    rep = decode_paged_target().audit(with_retrace=False)
    assert rep.target == "decode_paged/step"
    assert rep.ok, rep


@pytest.mark.audit
def test_decode_paged_audit_fails_on_dense_slab_mutation():
    """Re-introducing the dense (slots, max_len, H, hd) cache slab must
    FAIL the footprint rule — the negative control that keeps the
    decode_paged gate honest."""
    from commefficient_tpu.analysis.targets import decode_paged_target
    rep = decode_paged_target(mutate=True).audit(with_retrace=False)
    assert not rep.ok
    msgs = "\n".join(str(v) for r in rep.rule_reports
                     for v in r.violations)
    assert "dense per-slot KV cache slab" in msgs
    assert "(3, 32, 4, 32)" in msgs
