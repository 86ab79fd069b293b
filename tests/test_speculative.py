"""Speculative decoding over the serving stack (serving/speculative.py).

The anchors:

* speculative greedy == non-speculative greedy, BITWISE, for the fixed
  slab, the paged pools, and the personalized-verify composition —
  every emitted token is a target argmax, so any acceptance-window,
  rollback or catch-up bug is a token mismatch here;
* ONE compiled draft program + ONE compiled verify program per server
  lifetime, across admission churn and every per-slot acceptance length
  (acceptance is masks inside the program, never a shape);
* a self-drafting server (drafter == target) accepts 100% of its
  drafts, and the drafted/accepted/corrected counters account for it;
* mid-stream rejection rollback is pure page-table bookkeeping: after
  every step the table/refcounts/free-list are mutually consistent, and
  every page returns to the pool at the end (no leaks, no double
  frees);
* drain() + fresh-server reuse reproduce the same greedy replies;
* STOCHASTIC acceptance (topk engines): the residual rule's emitted
  marginals measurably equal the non-speculative top-k distribution at
  every window position, a self-drafting stochastic server accepts its
  whole window, and the stochastic programs hold the same one-compile
  contract;
* the ``decode_speculative`` graft audit passes on the real paged
  verify and FAILS on the dense-cache mutation.
"""

import warnings

import jax
import numpy as np
import pytest

from commefficient_tpu.data.tokenizer import ByteTokenizer
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.serving import (ContinuousBatchingServer,
                                       DecodeEngine, PersonalizationIndex,
                                       SpeculativeDecoder,
                                       speculation_from_checkpoint)
from commefficient_tpu.serving.paged_cache import GARBAGE_PAGE


@pytest.fixture(scope="module")
def tiny(serving_tiny_engine):
    # the conftest session engine shared with test_paged_serving: that
    # module collects first, so its prefill/step/pack/solo-generate
    # programs arrive here already compiled
    return serving_tiny_engine


def _micro_drafter(tok):
    """A 1-layer drafter over the same vocab: parity must hold for ANY
    drafter (every emitted token is a target argmax), so tests that
    don't assert acceptance statistics can draft with the cheapest
    model that passes construction validation."""
    cfg = GPT2Config(vocab_size=tok.vocab_size, n_positions=64, n_embd=32,
                     n_layer=1, n_head=2, dropout=0.0)
    model = GPT2DoubleHeads(cfg)
    ids = np.zeros((1, 1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(3), ids, ids,
                        np.zeros((1, 1), np.int32), train=False)["params"]
    return model, params


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


def _solo8(engine, prompts):
    return [engine.generate([(ids, types)], [types[-1]], max_new=8)[0]
            for ids, types in prompts]


def test_speculative_matches_plain_bitwise_one_compile(tiny):
    """Greedy parity, bitwise, for fixed and paged caches at several γ:
    the speculative server's replies equal the non-speculative server's
    AND the solo engine's prefix — and each server compiled exactly ONE
    draft and ONE verify program across all its admission churn and
    per-slot acceptance variation."""
    n = 4
    engine, prompts = _engine_and_prompts(tiny, n=n)
    solo = _solo8(engine, prompts)

    def run(kv, slots, spec_k, budgets, **kw):
        srv = ContinuousBatchingServer(engine, slots=slots,
                                       prefill_len=32, kv_cache=kv,
                                       speculate_k=spec_k, **kw)
        rids = [srv.submit(ids, types, types[-1], budgets[i])
                for i, (ids, types) in enumerate(prompts)]
        replies = srv.run()
        return [replies[r] for r in rids], srv

    # fixed slab, per-slot budget variation including the budget=1 edge
    # (micro drafter: parity is drafter-independent, and the cheap
    # drafter keeps this arm's compile small)
    dmodel, dparams = _micro_drafter(tiny[0])
    budgets = [8, 3, 8, 1]
    got, srv = run("fixed", 3, 2, budgets,
                   drafter_model=dmodel, drafter_params=dparams)
    for i in range(n):
        assert got[i] == solo[i][:budgets[i]], i
    assert srv.spec.draft._cache_size() == 1
    assert srv.spec.verify._cache_size() == 1

    # paged pools — and the default drafter IS the target, so this
    # server is self-drafting: every draft matches the target's argmax,
    # acceptance must be exactly 100% and the counters must account for
    # every draft (uniform budgets, so no window is cut mid-round).
    # slots=1 paged parity rides in the personalized test below — each
    # SpeculativeDecoder carries its own jits, so another server config
    # here would be another full compile for no new coverage.
    got, srv = run("paged", 3, 2, [8] * n)
    assert got == [s[:8] for s in solo]
    assert srv.spec.draft._cache_size() == 1
    assert srv.spec.paged_verify._cache_size() == 1
    assert srv.pager.pages_in_use == 0
    st = srv.stats()
    assert st["speculate_k"] == 2
    assert st["drafted"] == 2 * st["rounds"]
    assert st["accepted"] == st["drafted"]      # self-draft: accept all
    assert st["acceptance_rate"] == 1.0
    assert st["corrected"] == st["rounds"]      # one bonus token per round
    # retired slots keep their last occupancy's rate until re-admission
    assert all(r is None or r == 1.0 for r in st["per_slot_acceptance"])


def test_rejecting_drafter_still_bitwise_and_rollback_consistent(tiny):
    """A drafter with DIFFERENT weights (fresh random init) disagrees
    with the target, forcing real mid-stream rejections — replies must
    STILL be bitwise the plain greedy stream, and after every step the
    page table, refcounts and free list must be mutually consistent
    (each live table entry refcounted, in-use count == live pages, no
    page both free and referenced), with everything freed at the end."""
    tok, model, params, _eng = tiny
    engine, prompts = _engine_and_prompts(tiny, n=5)
    dparams = model.init(jax.random.PRNGKey(7),
                         np.zeros((1, 1, 8), np.int32),
                         np.zeros((1, 1, 8), np.int32),
                         np.zeros((1, 1), np.int32), train=False)["params"]
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged", page_size=8,
                                   speculate_k=3, drafter_model=model,
                                   drafter_params=dparams)
    rids = [srv.submit(ids, types, types[-1], 8) for ids, types in prompts]
    replies = {}
    while srv._queue or any(r is not None for r in srv._slot_req):
        for rid, toks in srv.step():
            replies[rid] = toks
        pg = srv.pager
        live = set(int(p) for p in pg.table.ravel() if p != GARBAGE_PAGE)
        assert all(pg.refcount[p] >= 1 for p in live)
        assert pg.pages_in_use == len(live)     # prompts are distinct
        assert len(pg._free) == len(set(pg._free))          # no dup frees
        assert not live & set(pg._free)         # never free AND referenced
    solo = _solo8(engine, prompts)
    assert [replies[r] for r in rids] == [s[:8] for s in solo]
    st = srv.stats()
    assert 0 < st["accepted"] < st["drafted"]   # rejections really happened
    assert srv.pager.pages_in_use == 0


def test_speculative_drain_then_fresh_server_matches_solo(tiny):
    """drain() on a speculative paged server: admitted requests finish,
    pages all return, and leftovers re-submitted on a FRESH speculative
    server complete with the exact solo greedy tokens."""
    engine, prompts = _engine_and_prompts(tiny, n=6)
    dmodel, dparams = _micro_drafter(tiny[0])   # parity holds for ANY drafter

    def make():
        return ContinuousBatchingServer(engine, slots=3, prefill_len=32,
                                        kv_cache="paged", speculate_k=2,
                                        drafter_model=dmodel,
                                        drafter_params=dparams)

    srv = make()
    rids = [srv.submit(ids, types, types[-1], 8) for ids, types in prompts]
    srv.step()                          # admit 3, leave 3 queued
    replies, leftovers = srv.drain()
    assert len(replies) + len(leftovers) == len(rids)
    assert srv.pager.pages_in_use == 0
    fresh = make()
    new_rids = [fresh.submit(*left) for left in leftovers]
    replies2 = fresh.run()
    got = list(replies.values()) + [replies2[r] for r in new_rids]
    solos = [s[:8] for s in _solo8(engine, prompts)]
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


def test_personalized_verify_speculative_parity(tiny):
    """--speculate_k composed with --serve_personalized: the drafter
    snapshots base params, the verify forward serves base + the active
    user's delta, and replies are bitwise the plain personalized
    server's. Occupancy is serialized (slots=1) because the active
    users' deltas share one params tree — co-residency, not
    speculation, is what changes logits otherwise — and base params
    must come back bitwise once everyone retires."""
    from jax.flatten_util import ravel_pytree
    engine, prompts = _engine_and_prompts(tiny, n=3)
    store, D = _sparse_store(engine.params)
    rng = np.random.RandomState(5)
    for uid in range(1, 3):
        row = np.zeros(D, np.float32)
        row[rng.choice(D, 4, replace=False)] = rng.randn(4)
        store.set_row("errors", uid, store.codec.encode_row_np(row))
    base_flat = np.asarray(ravel_pytree(engine.params)[0])

    def serve(spec_k):
        srv = ContinuousBatchingServer(
            engine, slots=1, prefill_len=32, kv_cache="paged",
            speculate_k=spec_k,
            personalize=PersonalizationIndex(engine.params, store))
        rids = [srv.submit(ids, types, types[-1], 6, user_id=uid)
                for uid, (ids, types) in enumerate(prompts)]
        replies = srv.run()
        return [replies[r] for r in rids]

    assert serve(2) == serve(0)
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(engine.params)[0]), base_flat)


def test_config_and_constructor_validation(tiny):
    from commefficient_tpu.config import FedConfig
    tok, model, params, engine = tiny
    with pytest.raises(ValueError, match="speculate_k must be >= 0"):
        FedConfig(speculate_k=-1).finalize(100)
    # speculation composes with BOTH sampling methods now (stochastic
    # acceptance for topk) — the old config refusal is gone
    FedConfig(speculate_k=4, serve_sample="topk").finalize(100)
    with pytest.raises(ValueError, match="serve_sample"):
        FedConfig(serve_sample="nucleus").finalize(100)
    with pytest.raises(ValueError, match="kv_quant"):
        FedConfig(kv_quant="fp8").finalize(100)
    FedConfig(speculate_k=4).finalize(100)      # greedy default: fine
    FedConfig(kv_quant="int8").finalize(100)

    with pytest.raises(ValueError, match="speculate_k must be >= 1"):
        SpeculativeDecoder(engine, gamma=0, slots=2)
    topk_engine = DecodeEngine(model, params, eos_id=engine.eos_id,
                               max_len=48, method="topk")
    # a topk engine constructs a STOCHASTIC decoder instead of raising
    assert SpeculativeDecoder(topk_engine, gamma=2, slots=2).stochastic
    assert not SpeculativeDecoder(engine, gamma=2, slots=2).stochastic
    short = GPT2DoubleHeads(GPT2Config.tiny(vocab_size=tok.vocab_size))
    short.config.n_positions = 16               # < engine.max_len
    with pytest.raises(ValueError, match="n_positions"):
        SpeculativeDecoder(engine, gamma=2, slots=2, drafter_model=short,
                           drafter_params=params)
    other_vocab = GPT2DoubleHeads(GPT2Config.tiny(vocab_size=64))
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeDecoder(engine, gamma=2, slots=2,
                           drafter_model=other_vocab,
                           drafter_params=params)


def test_stochastic_acceptance_marginals_match_topk(tiny):
    """The residual rule's theorem, measured: with drafts sampled from
    the drafter's distribution p and acceptance w.p. min(1, q/p) plus
    normalized-residual resampling, every emitted token is marginally
    ~ q — the exact distribution the non-speculative top-k step draws
    from (``sample_next``'s marginal is ``_topk_dist``, pinned here at
    the same sample size). One ``_accept_stoch`` call over a large iid
    batch gives the empirical marginals; position 0 is unconditional,
    position 1 conditions on the window surviving position 0 (an event
    independent of position-1 randomness)."""
    from commefficient_tpu.serving.decode import sample_next
    tok, model, params, engine = tiny
    topk_engine = DecodeEngine(model, params, eos_id=engine.eos_id,
                               max_len=48, method="topk")
    spec = SpeculativeDecoder(topk_engine, gamma=2, slots=2)
    assert spec.stochastic
    V, B = 16, 8192
    rs = np.random.RandomState(11)
    qlog = np.asarray(rs.randn(3, V).astype(np.float32) * 2.0)
    # drafter = perturbed target: enough overlap that acceptance is
    # common, enough disagreement that rejections are too
    plog = qlog[:2] + rs.randn(2, V).astype(np.float32) * 0.7
    q = np.asarray(spec._topk_dist(qlog))     # target dist per position
    p = np.asarray(spec._topk_dist(plog))     # drafter dist per draft

    # sample_next's marginal IS _topk_dist — the non-speculative stream
    toks, _ = sample_next(np.broadcast_to(qlog[0], (B, V)),
                          jax.random.PRNGKey(0), method="topk",
                          top_k=topk_engine.top_k,
                          temperature=topk_engine.temperature)
    freq = np.bincount(np.asarray(toks), minlength=V) / B
    assert np.abs(freq - q[0]).max() < 0.03

    # drafts sampled from p, verified window accepted stochastically
    k0, k1, ka = jax.random.split(jax.random.PRNGKey(1), 3)
    d0 = jax.random.categorical(k0, np.log(np.broadcast_to(
        p[0] + 1e-30, (B, V))), axis=-1).astype(np.int32)
    d1 = jax.random.categorical(k1, np.log(np.broadcast_to(
        p[1] + 1e-30, (B, V))), axis=-1).astype(np.int32)
    ids = np.stack([np.full(B, 5, np.int32), np.asarray(d0),
                    np.asarray(d1)], axis=1)
    qdist = np.broadcast_to(q, (B, 3, V))
    dprobs = np.broadcast_to(p, (B, 2, V))
    out = spec._accept_stoch(ids, qdist, dprobs,
                             np.zeros(B, np.int32),
                             np.zeros(B, bool), ka)
    emitted, acc = np.asarray(out[0]), np.asarray(out[1])
    assert len(out) == 7                      # rng threads back out
    # position 0: every row emits, marginal must be q_0
    freq0 = np.bincount(emitted[:, 0], minlength=V)[:V] / B
    assert np.abs(freq0 - q[0]).max() < 0.03
    # position 1: rows whose first draft was accepted; still ~ q_1
    srv1 = emitted[acc >= 2, 1]
    assert len(srv1) > B // 8                 # acceptance really happens
    assert (acc < 3).any()                    # rejections really happen
    freq1 = np.bincount(srv1, minlength=V)[:V] / len(srv1)
    assert np.abs(freq1 - q[1]).max() < 5 * np.sqrt(0.25 / len(srv1))


def test_stochastic_topk_server_end_to_end_self_draft(tiny):
    """--speculate_k + --serve_sample topk over the paged server: the
    composition the config layer used to refuse. Self-drafting, so the
    drafter's top-k distribution equals the target's and the ratio test
    accepts (up to float jitter between the drafter's dense cache and
    the target's paged attention); the stochastic draft + verify
    programs compile once each across the admission churn."""
    engine, prompts = _engine_and_prompts(tiny, n=4)
    tok, model, params, _eng = tiny
    topk_engine = DecodeEngine(model, params, eos_id=engine.eos_id,
                               max_len=48, method="topk")
    srv = ContinuousBatchingServer(topk_engine, slots=2, prefill_len=32,
                                   kv_cache="paged", page_size=8,
                                   speculate_k=2)
    assert srv.spec.stochastic
    budgets = [6, 3, 6, 1]
    rids = [srv.submit(ids, types, types[-1], budgets[i])
            for i, (ids, types) in enumerate(prompts)]
    replies = srv.run()
    for i, r in enumerate(rids):
        assert 0 < len(replies[r]) <= budgets[i]
        assert all(0 <= t < tok.vocab_size for t in replies[r])
    st = srv.stats()
    assert st["drafted"] > 0
    assert st["acceptance_rate"] > 0.99       # self-draft: ratio == 1
    assert srv.spec.draft._cache_size() == 1
    assert srv.spec.paged_verify._cache_size() == 1
    assert srv.pager.pages_in_use == 0


def test_speculation_from_checkpoint_gate():
    """Legacy checkpoints (no drafter record) and mismatched drafter
    fingerprints warn + serve non-speculative (speculate_k -> 0); a
    matching record passes the requested γ through."""
    from commefficient_tpu.serving.speculative import drafter_fingerprint
    dcfg = GPT2Config.tiny(vocab_size=300)
    with pytest.warns(UserWarning, match="non-speculative"):
        assert speculation_from_checkpoint(None, dcfg, speculate_k=4) == 0
    with pytest.warns(UserWarning, match="non-speculative"):
        assert speculation_from_checkpoint({}, dcfg, speculate_k=4) == 0
    wrong = dict(drafter_fingerprint(dcfg), n_layer=12)
    with pytest.warns(UserWarning, match="does not match"):
        assert speculation_from_checkpoint({"drafter": wrong}, dcfg,
                                           speculate_k=4) == 0
    record = {"drafter": drafter_fingerprint(dcfg)}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert speculation_from_checkpoint(record, dcfg,
                                           speculate_k=4) == 4
        assert speculation_from_checkpoint(record, dcfg,
                                           speculate_k=0) == 0


@pytest.mark.parametrize("program", ["draft", "paged_verify"])
@pytest.mark.parametrize("method,B,gamma", [("greedy", 1, 8),
                                            ("topk", 8, 4)])
def test_speculative_programs_trace_at_gpt2_small(
        gpt2_small_shapes, paged_shapes, method, B, gamma, program):
    """Drafter-cache and verify-window signature gate at published
    widths: a GPT2-small target, a ``GPT2Config.tiny``-class drafter on
    its vocabulary, pages of 16. Greedy: the gamma-draft program gives
    (B, gamma) tokens and the paged multi-token verify (B, gamma + 1).
    Under ``method='topk'`` the stochastic twins: the rng-threaded draft
    also returns the drafter's full (B, gamma, V) distributions, which
    the residual-rule verify takes. Nothing compiles."""
    import jax.numpy as jnp
    engine, P, N = (gpt2_small_shapes.engine(method), gpt2_small_shapes.P,
                    gpt2_small_shapes.N)
    V = engine.model.config.vocab_size
    dcfg = GPT2Config.tiny(vocab_size=V)
    dcfg.n_positions = max(dcfg.n_positions, P + N)
    dcfg.dtype = "bfloat16"
    drafter = GPT2DoubleHeads(dcfg)
    dparams = gpt2_small_shapes.abstract_params(drafter)
    spec = SpeculativeDecoder(engine, gamma=gamma, slots=B,
                              drafter_model=drafter, drafter_params=dparams)
    assert spec.stochastic == (method == "topk")
    _, pools, pt, vec, done = paged_shapes(engine, B, P)
    key = jax.random.PRNGKey(0)
    drafts = jax.ShapeDtypeStruct((B, gamma), jnp.int32)
    dprobs = jax.ShapeDtypeStruct((B, gamma, V), jnp.float32)
    if program == "draft":
        raw = spec._draft_stoch_raw if spec.stochastic else spec._draft_raw
        out = jax.eval_shape(raw, dparams, spec.dcache, vec, vec, vec, vec,
                             vec, *([key] if spec.stochastic else []))
        want = (drafts, dprobs) if spec.stochastic else (drafts,)
        assert [(o.shape, o.dtype) for o in out[1:1 + len(want)]] == [
            (w.shape, w.dtype) for w in want]
        return
    if spec.stochastic:
        out = jax.eval_shape(spec._paged_verify_stoch_raw, engine.params,
                             pools, pt, vec, vec, vec, drafts, dprobs, done,
                             key)
    else:
        out = jax.eval_shape(spec._paged_verify_raw, engine.params, pools,
                             pt, vec, vec, vec, drafts, done)
    assert out[1].shape == (B, gamma + 1)          # emitted


@pytest.mark.audit
def test_decode_speculative_audit_passes_at_head():
    from commefficient_tpu.analysis.targets import decode_speculative_target
    rep = decode_speculative_target().audit(with_retrace=False)
    assert rep.target == "decode_speculative/verify"
    assert rep.ok, rep


@pytest.mark.audit
def test_decode_speculative_audit_fails_on_dense_cache_mutation():
    """Verifying through the dense (slots, max_len, H, hd) cache must
    FAIL the footprint rule — the negative control that keeps the
    decode_speculative gate honest."""
    from commefficient_tpu.analysis.targets import decode_speculative_target
    rep = decode_speculative_target(mutate=True).audit(with_retrace=False)
    assert not rep.ok
    msgs = "\n".join(str(v) for r in rep.rule_reports
                     for v in r.violations)
    assert "dense per-slot KV cache slab" in msgs
    assert "(3, 32, 4, 32)" in msgs
