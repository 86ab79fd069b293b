"""KV-cached jitted decode + continuous-batching serving path.

The parity anchor: greedy decoding through the cached engine
(serving/decode.py — one prefill, then a lax.scan of O(T)-per-token
cached steps with sampling inside the jit) must produce the SAME token
sequence as the incumbent ``sample_reply`` loop, which rebuilds and
re-runs the full prompt every token. Both walk argmax chains over the
same logits, so any cache-threading bug (wrong position offsets, stale
rows becoming attendable, dtype drift in the per-layer k/v buffers)
shows up as a token mismatch here before it shows up as garbage text on
a chip.

On top of that anchor: batched == solo generation (per-row independence
of the decode step), served == solo (the continuous-batching server
interleaves admissions/retirements without perturbing any lane), one
compile for the step program across the server's whole lifetime, cache
capacity latching, and the checkpoint -> head-only finetune -> serve
round trip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.data.tokenizer import ByteTokenizer
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.models.gpt2_generate import (sample_reply,
                                                    sample_reply_cached)
from commefficient_tpu.serving import ContinuousBatchingServer, DecodeEngine


@pytest.fixture(scope="module")
def tiny():
    tok = ByteTokenizer()
    cfg = GPT2Config.tiny(vocab_size=tok.vocab_size)
    model = GPT2DoubleHeads(cfg)
    ids = np.zeros((1, 1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids,
                        np.zeros((1, 1), np.int32), train=False)["params"]
    return tok, model, params


def _prompt(tok, persona_txt="i like cats", history_txt="hello there"):
    return [tok.encode(persona_txt)], [tok.encode(history_txt)]


def test_cached_greedy_parity_with_sample_reply(tiny):
    tok, model, params = tiny
    for ptxt, htxt in (("i like cats", "hello there"),
                       ("i am a robot from space", "what do you do")):
        persona, history = _prompt(tok, ptxt, htxt)
        ref = sample_reply(model, params, tok, persona, history,
                           max_seq_len=64, max_reply_len=10)
        got = sample_reply_cached(model, params, tok, persona, history,
                                  max_seq_len=64, max_reply_len=10)
        assert got == ref


def test_cached_topk_deterministic_and_valid(tiny):
    tok, model, params = tiny
    persona, history = _prompt(tok)
    kw = dict(max_seq_len=64, max_reply_len=8, method="topk", top_k=4,
              seed=7)
    r1 = sample_reply_cached(model, params, tok, persona, history, **kw)
    r2 = sample_reply_cached(model, params, tok, persona, history, **kw)
    assert r1 == r2                      # same seed, same chain
    assert len(r1) <= 8
    eos = tok.convert_tokens_to_ids("<eos>")
    assert all(isinstance(t, int) and 0 <= t < tok.vocab_size and t != eos
               for t in r1)
    with pytest.raises(ValueError):
        sample_reply_cached(model, params, tok, persona, history,
                            max_seq_len=64, method="beam")


def test_engine_method_mismatch_raises(tiny):
    tok, model, params = tiny
    persona, history = _prompt(tok)
    eos = tok.convert_tokens_to_ids("<eos>")
    engine = DecodeEngine(model, params, eos_id=eos, max_len=64,
                          method="greedy")
    with pytest.raises(ValueError, match="method"):
        sample_reply_cached(model, params, tok, persona, history,
                            max_seq_len=64, method="topk", engine=engine)


def _engine_and_prompts(tiny, n=3):
    tok, model, params = tiny
    eos = tok.convert_tokens_to_ids("<eos>")
    texts = ["hello there", "do you like fish", "the weather is nice",
             "tell me a story", "what is your name"][:n]
    prompts = []
    for t in texts:
        ids = tok.encode(t)
        prompts.append((ids, [1] * len(ids)))
    engine = DecodeEngine(model, params, eos_id=eos, max_len=48,
                          method="greedy")
    return engine, prompts


def test_batched_generate_matches_solo(tiny):
    """Per-row independence: each row of a batched generate attends only
    its own cache rows, so batch {1, n} produce identical replies."""
    engine, prompts = _engine_and_prompts(tiny)
    reply_types = [p[1][-1] for p in prompts]
    batched = engine.generate(prompts, reply_types, max_new=8)
    for i, p in enumerate(prompts):
        solo = engine.generate([p], [reply_types[i]], max_new=8)[0]
        assert batched[i] == solo


def test_server_matches_solo_engine_one_compile(tiny):
    """5 requests with different budgets through a 2-slot continuous-
    batching server == what the engine produces for each alone, AND the
    decode step stayed ONE compiled program across every admission and
    retirement (slot indices cross into jit as traced values)."""
    engine, prompts = _engine_and_prompts(tiny, n=5)
    server = ContinuousBatchingServer(engine, slots=2, prefill_len=32)
    budgets = [8, 3, 8, 1, 6]
    rids = [server.submit(ids, types, types[-1], budgets[i])
            for i, (ids, types) in enumerate(prompts)]
    replies = server.run()
    assert set(replies) == set(rids)
    for i, (ids, types) in enumerate(prompts):
        solo = engine.generate([(ids, types)], [types[-1]],
                               max_new=budgets[i])[0]
        assert replies[rids[i]] == solo
    assert engine.step._cache_size() == 1


def test_server_drain_then_fresh_server_matches_solo(tiny):
    """Graceful preemption of the serving path: drain() finishes every
    admitted request and hands back the never-admitted queue; a FRESH
    server over the same weights completes the leftovers with the exact
    greedy tokens the original server would have produced."""
    engine, prompts = _engine_and_prompts(tiny, n=5)
    server = ContinuousBatchingServer(engine, slots=2, prefill_len=32)
    rids = [server.submit(ids, types, types[-1], 6)
            for ids, types in prompts]
    server.step()                        # admit 2 into slots, 3 queued
    replies, leftovers = server.drain()
    # drained replies cover exactly the admitted requests, none dropped
    assert set(replies) | {lid for lid, _ in _match_leftovers(
        rids, prompts, leftovers)} == set(rids)
    assert len(leftovers) == len(rids) - len(replies)
    # leftovers come back in submission order, re-submittable verbatim
    replacement = ContinuousBatchingServer(engine, slots=2, prefill_len=32)
    new_rids = [replacement.submit(*left) for left in leftovers]
    replies2 = replacement.run()
    done = dict(replies)
    for (orig_rid, _), nrid in zip(
            _match_leftovers(rids, prompts, leftovers), new_rids):
        done[orig_rid] = replies2[nrid]
    for rid, (ids, types) in zip(rids, prompts):
        solo = engine.generate([(ids, types)], [types[-1]], max_new=6)[0]
        assert done[rid] == solo


def _match_leftovers(rids, prompts, leftovers):
    """Map drained leftovers back to their original rids by content (the
    queue preserves submission order)."""
    out, j = [], 0
    for left in leftovers:
        while j < len(prompts):
            ids, types = prompts[j]
            rid = rids[j]
            j += 1
            if (list(ids), list(types), types[-1]) == (left[0], left[1],
                                                       left[2]):
                out.append((rid, left))
                break
    return out


def test_server_rejects_overlong_prompt(tiny):
    engine, prompts = _engine_and_prompts(tiny, n=1)
    server = ContinuousBatchingServer(engine, slots=2, prefill_len=4)
    with pytest.raises(ValueError, match="prefill_len"):
        server.submit(list(range(10)), [1] * 10, 1, 4)
    with pytest.raises(ValueError, match="capacity"):
        ContinuousBatchingServer(engine, slots=2, prefill_len=1000)


def test_decode_latches_at_cache_capacity(tiny):
    """A reply never writes past the cache: generation latches done once
    the write position would leave [0, max_len), instead of wrapping or
    erroring mid-scan."""
    tok, model, params = tiny
    eos = tok.convert_tokens_to_ids("<eos>")
    ids = tok.encode("hello there friend")
    types = [1] * len(ids)
    cap = len(ids) + 3
    engine = DecodeEngine(model, params, eos_id=eos, max_len=cap,
                          method="greedy")
    r = engine.generate([(ids, types)], [1], max_new=10)[0]
    # prefill ends at len(ids)-1; tokens are emitted for write positions
    # len(ids)-1 .. cap-1, then the done latch holds
    assert len(r) <= cap - len(ids) + 1
    unlimited = DecodeEngine(model, params, eos_id=eos, max_len=64,
                             method="greedy")
    full = unlimited.generate([(ids, types)], [1], max_new=10)[0]
    assert r == full[:len(r)]            # truncation, not divergence


def test_checkpoint_finetune_serve_e2e(tiny, tmp_path):
    """The deployment round trip: train a step, checkpoint, reload into a
    head-only finetune learner (body frozen), finetune a step, then serve
    the finetuned weights through the KV-cached engine."""
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_gpt2_train_loss
    from commefficient_tpu.utils.checkpoint import save_checkpoint
    from commefficient_tpu.utils.finetune import (head_only_mask,
                                                  load_pretrained_for_finetune)

    tok, model, _ = tiny
    # C=2 candidates: with a single candidate the MC loss is a constant
    # (softmax over one class) and the head-only finetune has no gradient
    T, W, B, C = 16, 1, 2, 2
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 200, (W, B, C, T)).astype(np.int32)
    types = rng.randint(0, 3, (W, B, C, T)).astype(np.int32)
    mc = np.full((W, B, C), T - 1, np.int32)
    labels = np.where(rng.rand(W, B, C, T) < 0.5, ids, -1).astype(np.int32)
    mcl = np.zeros((W, B), np.int32)
    batch = (ids, mc, labels, mcl, types)
    mask = np.ones((W, B), np.float32)

    class _Wrap:
        def init(self, rng_, sample_in, train):
            return model.init(rng_, *sample_in, train=train)

        def apply(self, *a, **k):
            return model.apply(*a, **k)

    wrap = _Wrap()
    sample_in = (ids[0][:1], types[0][:1], mc[0][:1])
    loss = make_gpt2_train_loss(model)
    cfg = FedConfig(mode="uncompressed", error_type="none",
                    virtual_momentum=0, local_momentum=0, weight_decay=0,
                    num_workers=W, num_clients=2, lr_scale=0.05,
                    max_seq_len=T)
    pre = FedLearner(wrap, cfg, loss, None, jax.random.PRNGKey(0),
                     sample_in)
    pre.train_round(np.arange(W), batch, mask)
    fn = save_checkpoint(str(tmp_path), pre, "gpt2")

    init_params, ft_mask = load_pretrained_for_finetune(
        wrap, jax.random.PRNGKey(1), sample_in, fn,
        head_substring="mc_head")
    ft = FedLearner(wrap, cfg, loss, None, jax.random.PRNGKey(0),
                    sample_in, init_params=init_params,
                    trainable_mask=ft_mask)
    w0 = np.asarray(ft.state.weights).copy()
    ft.train_round(np.arange(W), batch, mask)
    w1 = np.asarray(ft.state.weights)
    frozen = np.asarray(ft_mask) == 0
    assert not np.any((w1 != w0) & frozen)   # body untouched
    assert np.any((w1 != w0) & ~frozen)      # head moved

    served = ft.unflatten(ft.state.weights)
    persona, history = _prompt(tok)
    reply = sample_reply_cached(model, served, tok, persona, history,
                                max_seq_len=64, max_reply_len=6)
    assert isinstance(reply, list) and len(reply) <= 6
    assert all(isinstance(t, int) for t in reply)


@pytest.mark.parametrize("program", ["prefill", "generate", "uncached"])
def test_decode_programs_trace_at_gpt2_small(gpt2_small_shapes, program):
    """The serving path's signature gate at published widths: the prefill
    that fills the cache, the whole-reply program (prefill + the jitted
    scan of cached steps) and the uncached full-window forward it stands
    in for, each traced at batch 1 with a 128-token prompt and 64 new
    tokens. Nothing compiles."""
    P, N = gpt2_small_shapes.P, gpt2_small_shapes.N
    engine = gpt2_small_shapes.engine()
    model, params = engine.model, engine.params
    B, V = 1, model.config.vocab_size
    ids = jax.ShapeDtypeStruct((B, P), jnp.int32)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    if program == "prefill":
        cache = jax.eval_shape(lambda: engine.init_cache(B))
        logits, filled = jax.eval_shape(engine._prefill_raw, params, cache,
                                        ids, ids, vec)
        assert logits.shape == (B, V)
        assert jax.tree.structure(filled) == jax.tree.structure(cache)
        assert [x.shape for x in jax.tree.leaves(filled)] == [
            x.shape for x in jax.tree.leaves(cache)]
    elif program == "generate":
        toks = jax.eval_shape(
            lambda *a: engine._generate_raw(*a, max_new=N),
            params, ids, ids, vec, vec, jax.random.PRNGKey(0))
        assert (toks.shape, toks.dtype) == ((B, N), jnp.int32)
    else:
        lm, _ = jax.eval_shape(
            lambda p: model.apply(
                {"params": p}, jnp.zeros((B, 1, P + N), jnp.int32),
                jnp.zeros((B, 1, P + N), jnp.int32),
                jnp.zeros((B, 1), jnp.int32), train=False), params)
        assert lm.shape == (B, 1, P + N, V)
