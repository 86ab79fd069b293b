"""--kv_quant: the int8/int4 page codec over the block-paged serving
cache (ops/kv_quant.py).

The anchors:

* the codec's error bound — every dequantized value sits within half a
  quantization step of its source, per (page, head) tile — and the
  all-zero page stores scale 0 and reproduces exact zeros, never NaN;
* ``--kv_quant none`` is the f32 incumbent BITWISE: same replies, and
  the none-mode server adds ZERO compiled programs over a plain paged
  server (the pools are the same pytree, so the trace is the same
  trace);
* int8 serving holds the token-agreement contract against the f32
  stream at tiny scale, and ``stats()`` reports the pool-byte
  accounting (the ≥3x capacity multiplier ROADMAP's users-per-chip
  lever multiplies onto);
* quantization changes no attendability: a poisoned garbage page 0
  (extreme int8 values under an extreme scale) changes no reply;
* copy-on-write prefix sharing shares the quantized page AND its scale
  row — pure host bookkeeping, refcounts identical to f32 paging;
* page reuse after retirement leaves no stale scales: the requant-on-
  write path overwrites page and scale together, so a recycled page
  serves its new occupant exactly as a fresh pool would;
* KV pools are transient serving state: a checkpoint saved while an
  int8 server is live is byte-identical (same digest) to one saved
  before, and serving mutates no param buffer;
* the ``decode_paged_quant`` graft audit passes on the int8 step and
  FAILS on the unquantized-pool mutation (what makes the pass
  meaningful).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.data.tokenizer import ByteTokenizer
from commefficient_tpu.ops import kv_quant as kvq
from commefficient_tpu.serving import ContinuousBatchingServer


@pytest.fixture(scope="module")
def tiny(serving_tiny_engine):
    # the session engine shared with test_paged_serving/test_speculative:
    # same jit caches, so paged programs compile once per shape suite-wide
    return serving_tiny_engine


def _prompts(tok, n=6):
    texts = ["hello there", "do you like fish", "the weather is nice",
             "tell me a story", "what is your name",
             "where are you from"][:n]
    return [(tok.encode(t), [1] * len(tok.encode(t))) for t in texts]


# ---------------------------------------------------------------- codec


def test_codec_roundtrip_error_bound():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 8, 4, 32).astype(np.float32) * 3.0)
    for mode in ("int8", "int4"):
        q, s = kvq.quantize_pages(x, mode)
        assert q.dtype == kvq.pool_dtype(mode)
        assert s.shape == (5, 4)
        y = kvq.dequantize_pages(q, s, mode)
        assert y.shape == x.shape
        # per-(page, head) half-step bound
        err = np.abs(np.asarray(y - x))
        bound = np.asarray(s)[:, None, :, None] * 0.5 + 1e-6
        assert (err <= bound).all(), (mode, err.max())


def test_int4_pack_unpack_exact():
    # every representable nibble value survives the offset-binary pack
    q = jnp.asarray(np.arange(-8, 8, dtype=np.int32).reshape(1, 1, 1, 16))
    assert (np.asarray(kvq._unpack_int4(kvq._pack_int4(q)))
            == np.asarray(q)).all()
    # the quantizer itself clips to the symmetric [-7, 7] range
    x = jnp.asarray(np.linspace(-9, 9, 32, dtype=np.float32)
                    .reshape(1, 1, 1, 32))
    qq, _ = kvq.quantize_pages(x, "int4")
    back = np.asarray(kvq._unpack_int4(qq))
    assert back.min() >= -7 and back.max() <= 7


def test_all_zero_page_scale_zero_no_nan():
    z = jnp.zeros((3, 8, 4, 32), jnp.float32)
    for mode in ("int8", "int4"):
        q, s = kvq.quantize_pages(z, mode)
        assert (np.asarray(s) == 0).all()
        y = np.asarray(kvq.dequantize_pages(q, s, mode))
        assert np.isfinite(y).all() and (y == 0).all()
    # inserting into an all-zero pool (the init state) stays finite
    vals = jnp.asarray(np.random.RandomState(0)
                       .randn(2, 1, 4, 32).astype(np.float32))
    phys = jnp.asarray([[1], [2]], jnp.int32)
    off = jnp.asarray([[0], [3]], jnp.int32)
    qp, sc = kvq.quantize_pages(z, "int8")
    qp2, sc2 = kvq.insert_tokens(qp, sc, vals, phys, off, "int8")
    out = np.asarray(kvq.dequantize_pages(qp2, sc2, "int8"))
    assert np.isfinite(out).all()
    assert np.abs(out[1, 0] - np.asarray(vals[0, 0])).max() < 0.05


def test_mode_validation_and_byte_accounting():
    with pytest.raises(ValueError, match="kv_quant"):
        kvq.validate_mode("fp8")
    with pytest.raises(ValueError, match="even"):
        kvq.packed_head_dim(33, "int4")
    np_, ps, h, hd, nl = 13, 8, 4, 32, 2
    f32 = kvq.pool_bytes(np_, ps, h, hd, nl, "none")
    i8 = kvq.pool_bytes(np_, ps, h, hd, nl, "int8")
    i4 = kvq.pool_bytes(np_, ps, h, hd, nl, "int4")
    assert f32 == 2 * nl * np_ * ps * h * hd * 4
    assert i8 == 2 * nl * (np_ * ps * h * hd + np_ * h * 4)
    assert i4 == 2 * nl * (np_ * ps * h * (hd // 2) + np_ * h * 4)
    assert kvq.capacity_multiplier_vs_f32(np_, ps, h, hd, nl, "none") == 1.0
    assert kvq.capacity_multiplier_vs_f32(np_, ps, h, hd, nl, "int8") > 3.0
    assert kvq.capacity_multiplier_vs_f32(np_, ps, h, hd, nl, "int4") > 7.0


def test_infer_mode_from_pool_statics(tiny):
    tok, model, params, engine = tiny
    hd = model.config.n_embd // model.config.n_head
    for mode in ("int8", "int4"):
        pools = engine.init_paged_pools(7, 8, kv_quant=mode)
        assert kvq.infer_mode(pools[0]["k"], hd) == mode
        assert pools[0]["k_scale"].shape == (7, model.config.n_head)
    # none-mode pools carry no scale arrays (the dispatch key) and no
    # inferable codec — infer_mode is only reached behind that key
    plain = engine.init_paged_pools(7, 8, kv_quant="none")
    assert "k_scale" not in plain[0]
    with pytest.raises(ValueError, match="cannot infer"):
        kvq.infer_mode(plain[0]["k"], hd)


# -------------------------------------------------------------- serving


def test_kv_quant_none_is_bitwise_and_adds_no_program(tiny):
    tok, model, params, engine = tiny
    prompts = _prompts(tok, n=4)

    def run(**kw):
        srv = ContinuousBatchingServer(engine, slots=4, prefill_len=32,
                                       kv_cache="paged", page_size=8, **kw)
        rids = [srv.submit(ids, types, 1, 5) for ids, types in prompts]
        replies = srv.run()
        return [replies[r] for r in rids]

    base = run()
    n_step = engine.paged_step._cache_size()
    n_pack = engine.paged_insert._cache_size()
    assert run(kv_quant="none") == base
    # none-mode pools are the SAME pytree — the explicit flag may not
    # retrace either paged program
    assert engine.paged_step._cache_size() == n_step
    assert engine.paged_insert._cache_size() == n_pack


def test_int8_serving_token_agreement_and_stats(tiny):
    tok, model, params, engine = tiny
    prompts = _prompts(tok, n=6)
    budgets = [8, 3, 6, 5, 2, 7]

    def run(mode):
        srv = ContinuousBatchingServer(engine, slots=4, prefill_len=32,
                                       kv_cache="paged", page_size=8,
                                       kv_quant=mode)
        rids = [srv.submit(ids, types, 1, budgets[i])
                for i, (ids, types) in enumerate(prompts)]
        replies = srv.run()
        return [replies[r] for r in rids], srv.stats()

    f32, _ = run("none")
    for mode in ("int8", "int4"):
        got, st = run(mode)
        same = sum(a == b for r1, r2 in zip(got, f32)
                   for a, b in zip(r1, r2))
        total = sum(len(r) for r in f32)
        # token-agreement contract: the quantized greedy stream tracks
        # the f32 stream at tiny scale (half-lsb per-value error)
        assert same / total >= 0.9, (mode, same, total, got, f32)
        assert st["kv_quant"] == mode
        assert st["kv_pool_bytes"] > 0
        mult = st["kv_capacity_multiplier_vs_f32"]
        assert mult >= (3.0 if mode == "int8" else 7.0)


def test_garbage_page_poisoning_changes_no_reply(tiny):
    """Physical page 0 is the never-attendable garbage page; quantizing
    the pools must not change that. Poison its int8 payload AND its
    scale rows with extreme values — every reply is unchanged."""
    tok, model, params, engine = tiny
    prompts = _prompts(tok, n=4)

    def run(poison):
        srv = ContinuousBatchingServer(engine, slots=4, prefill_len=32,
                                       kv_cache="paged", page_size=8,
                                       kv_quant="int8")
        if poison:
            srv.cache = tuple(
                {"k": c["k"].at[0].set(127), "v": c["v"].at[0].set(-127),
                 "k_scale": c["k_scale"].at[0].set(1e6),
                 "v_scale": c["v_scale"].at[0].set(1e6)}
                for c in srv.cache)
        rids = [srv.submit(ids, types, 1, 6) for ids, types in prompts]
        replies = srv.run()
        return [replies[r] for r in rids]

    assert run(poison=True) == run(poison=False)


def test_cow_shares_quant_page_and_scale_row(tiny):
    tok, model, params, engine = tiny
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged", page_size=8,
                                   kv_quant="int8")
    ids = tok.encode("the weather is nice")       # >= 2 full 8-token pages
    assert len(ids) >= 16
    full_pages = len(ids) // 8
    types = [1] * len(ids)
    srv.submit(ids, types, 1, 6)
    srv.submit(ids, types, 1, 3)
    srv.step()                                    # both admitted
    pg = srv.pager
    assert pg.shared_hits == full_pages
    assert (pg.table[0, :full_pages] == pg.table[1, :full_pages]).all()
    assert (pg.refcount[pg.table[0, :full_pages]] == 2).all()
    # ONE quantized copy: the shared physical page's scale row is the
    # only scale state for both sharers, and the pack wrote it hot
    shared = [int(p) for p in pg.table[0, :full_pages]]
    ks = np.asarray(srv.cache[0]["k_scale"])
    assert (ks[shared] > 0).all()
    replies = srv.run()
    assert replies[1] == replies[0][:3]           # same greedy chain
    assert pg.pages_in_use == 0


def test_page_reuse_leaves_no_stale_scales(tiny):
    """A retired request's pages go back to the free list with their
    old quantized payload and scales still in HBM; the next occupant's
    pack/requant writes must fully overwrite both. The recycled-pool
    reply must equal a fresh server's reply."""
    tok, model, params, engine = tiny
    a = tok.encode("hello there")    # 11 + 5 new = 16 tokens, 2 pages
    b = tok.encode("what time")      # 9 + 5 new = 14 tokens, 2 pages

    def serve(srv, ids, budget=5):
        rid = srv.submit(ids, [1] * len(ids), 1, budget)
        return srv.run()[rid]

    def make():
        # garbage page + 2 usable pages: request B reuses A's pages
        return ContinuousBatchingServer(engine, slots=1, prefill_len=16,
                                        kv_cache="paged", page_size=8,
                                        num_pages=3, kv_quant="int8")

    recycled = make()
    serve(recycled, a)
    assert recycled.pager.pages_in_use == 0
    got = serve(recycled, b)
    assert got == serve(make(), b)


def test_checkpoint_roundtrip_ignores_kv_quant(tiny, tmp_path):
    """KV pools are transient serving state: a checkpoint saved while an
    int8 paged server is live is byte-identical to one saved before it
    existed, the roundtrip restores it, and serving touched no param
    buffer."""
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_regression_loss
    from commefficient_tpu.models import ToyLinear
    from commefficient_tpu.utils.checkpoint import (load_checkpoint,
                                                    save_checkpoint)

    X = np.asarray([[0.0], [1.0], [2.0], [3.0]], np.float32)
    cfg = FedConfig(mode="uncompressed", virtual_momentum=0.9,
                    local_momentum=0, error_type="none", weight_decay=0,
                    num_workers=1, num_clients=2, lr_scale=0.02)
    lmodel = ToyLinear()
    learner = FedLearner(lmodel, cfg, make_regression_loss(lmodel), None,
                         jax.random.PRNGKey(0), X[:1])
    learner.train_round(np.array([0]), (X[None], X[None]),
                        np.ones((1, 4), np.float32))
    fn_before = save_checkpoint(str(tmp_path / "before"), learner, "toy")
    dig_before = str(np.load(fn_before)["digest"])

    tok, model, params, engine = tiny
    leaves_before = [np.asarray(x).copy()
                     for x in jax.tree.leaves(engine.params)]
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged", page_size=8,
                                   kv_quant="int8")
    ids = tok.encode("hello there")
    srv.submit(ids, [1] * len(ids), 1, 5)
    srv.run()

    fn_after = save_checkpoint(str(tmp_path / "after"), learner, "toy")
    assert str(np.load(fn_after)["digest"]) == dig_before
    fresh = FedLearner(lmodel, cfg, make_regression_loss(lmodel), None,
                       jax.random.PRNGKey(0), X[:1])
    load_checkpoint(fn_after, fresh)
    assert fresh.rounds_done == 1
    for a, b in zip(leaves_before, jax.tree.leaves(engine.params)):
        assert (a == np.asarray(b)).all()


@pytest.mark.parametrize("contract", ["footprint", "capacity"])
def test_int8_paged_step_contracts_at_gpt2_small(gpt2_small_shapes,
                                                 paged_shapes, contract):
    """``--kv_quant int8`` at published widths (8 slots of 128 + 64
    tokens, pages of 16). footprint: the footprint rule of the
    decode_paged_quant audit over the traced int8 paged step's jaxpr
    finds no f32 value of the pool's (num_pages, page_size, H, hd) shape:
    dequantization touches gathered pages only. capacity: the same KV
    bytes hold at least three times the pages of an f32 pool, scale rows
    counted. Nothing compiles."""
    from commefficient_tpu.analysis import FootprintRule, ShapePattern, walk
    engine = gpt2_small_shapes.engine()
    cfg = engine.model.config
    hd = cfg.n_embd // cfg.n_head
    pager, pools, pt, vec, done = paged_shapes(
        engine, 8, gpt2_small_shapes.P, kv_quant="int8")
    if contract == "capacity":
        assert kvq.capacity_multiplier_vs_f32(
            pager.num_pages, 16, cfg.n_head, hd, cfg.n_layer,
            "int8") >= 3.0
        return
    sites, stats = walk(jax.make_jaxpr(engine._paged_step_raw)(
        engine.params, pools, pt, vec, vec, vec, jax.random.PRNGKey(0), done))
    f32pool = ShapePattern(
        ("num_pages", "page_size", "H", "hd"),
        label="f32 materialization of the quantized KV pool",
        allow_primitives=frozenset(), dtype="float32")
    rep = FootprintRule((f32pool,)).check(
        sites, stats, {"num_pages": pager.num_pages, "page_size": 16,
                       "H": cfg.n_head, "hd": hd})
    assert rep.ok, [str(v) for v in rep.violations]


# ---------------------------------------------------------------- audit


@pytest.mark.audit
def test_decode_paged_quant_audit_passes_at_head():
    from commefficient_tpu.analysis.targets import decode_paged_quant_target
    rep = decode_paged_quant_target().audit(with_retrace=False)
    assert rep.target == "decode_paged_quant/step"
    assert rep.ok, rep


@pytest.mark.audit
def test_decode_paged_quant_audit_fails_on_f32_pool_mutation():
    """The unquantized paged step's f32 pool-shaped write-back scatters
    must FAIL the dtype-scoped footprint rule — the negative control
    that keeps the decode_paged_quant gate honest."""
    from commefficient_tpu.analysis.targets import decode_paged_quant_target
    rep = decode_paged_quant_target(mutate=True).audit(with_retrace=False)
    assert not rep.ok
    msgs = "\n".join(str(v) for r in rep.rule_reports
                     for v in r.violations)
    assert "f32 materialization of the quantized KV pool" in msgs
    assert "(13, 8, 4, 32)" in msgs
