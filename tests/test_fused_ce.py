"""Fused LM-head CE (ops/fused_ce.py) and hardware-RNG dropout
(ops/dropout.py hw path): equivalence against the materialized-logits
reference path.

The Pallas hw-dropout kernel itself cannot run under the CPU interpreter
(no prng_seed lowering in this JAX build), so its bit-level contracts are
asserted in the TPU-gated test at the bottom; the CPU suite covers the
fallback routing and the fused-CE math (pure jnp, runs everywhere).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from commefficient_tpu.federated.losses import (_lm_nll_sums,
                                                make_gpt2_train_loss,
                                                make_gpt2_val_loss)
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.ops.fused_ce import lm_head_nll, shifted_lm_nll


def _rand_case(seed=0, N=37, V=1000, E=64):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(N, E).astype(np.float32))
    w = jnp.asarray(rng.randn(V, E).astype(np.float32) * 0.1)
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))
    return h, w, lab


def test_lm_head_nll_matches_optax_f32():
    h, w, lab = _rand_case()
    ref = optax.softmax_cross_entropy_with_integer_labels(h @ w.T, lab)
    got = lm_head_nll(h, w, lab, 256, jnp.float32)
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_lm_head_nll_bf16_close():
    h, w, lab = _rand_case(1)
    ref = optax.softmax_cross_entropy_with_integer_labels(h @ w.T, lab)
    got = lm_head_nll(h, w, lab, 256, jnp.bfloat16)
    # bf16 matmul inputs, f32 accumulation: ~2-3 decimal digits
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)


def test_lm_head_nll_grads_match_optax():
    h, w, lab = _rand_case(2)
    scale = jnp.arange(h.shape[0], dtype=jnp.float32)  # nonuniform cotangent

    def loss_ref(h, w):
        nll = optax.softmax_cross_entropy_with_integer_labels(h @ w.T, lab)
        return jnp.sum(nll * scale)

    def loss_fused(h, w):
        return jnp.sum(lm_head_nll(h, w, lab, 256, jnp.float32) * scale)

    gr = jax.grad(loss_ref, argnums=(0, 1))(h, w)
    gf = jax.grad(loss_fused, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gf[0], gr[0], atol=1e-3)
    np.testing.assert_allclose(gf[1], gr[1], atol=1e-2)


def test_lm_head_nll_vocab_not_multiple_of_chunk():
    # V=1000 with chunk 384: two full chunks + a masked pad chunk
    h, w, lab = _rand_case(3)
    ref = optax.softmax_cross_entropy_with_integer_labels(h @ w.T, lab)
    got = lm_head_nll(h, w, lab, 384, jnp.float32)
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_shifted_lm_nll_matches_reference_sums():
    rng = np.random.RandomState(4)
    B, C, T, E, V = 3, 2, 17, 64, 500
    w = jnp.asarray(rng.randn(V, E).astype(np.float32) * 0.1)
    hid = jnp.asarray(rng.randn(B, C, T, E).astype(np.float32))
    labs = jnp.asarray(np.where(rng.rand(B, C, T) < 0.4,
                                rng.randint(0, V, (B, C, T)),
                                -1).astype(np.int32))
    s_ref, c_ref = _lm_nll_sums(hid @ w.T, labs)
    s4, c4 = shifted_lm_nll(hid, w, labs, 128, jnp.float32)
    np.testing.assert_allclose(jnp.sum(s4, -1), s_ref, atol=1e-4)
    assert (jnp.sum(c4, -1) == c_ref).all()


def _tiny_batch(rng, B=3, C=2, T=16, V=300):
    ids = jnp.asarray(rng.randint(0, V, (B, C, T)).astype(np.int32))
    types = jnp.asarray(rng.randint(0, 3, (B, C, T)).astype(np.int32))
    mc = jnp.full((B, C), T - 1, jnp.int32)
    labels = jnp.asarray(np.where(rng.rand(B, C, T) < 0.5,
                                  np.asarray(ids), -1).astype(np.int32))
    mcl = jnp.ones((B,), jnp.int32)
    return (ids, mc, labels, mcl, types)


def test_fused_lm_head_model_loss_parity():
    """GPT2DoubleHeads(fused_lm_head=True) + fused losses == the default
    materialized-logits path: same params tree, same train/val losses."""
    cfg_a, cfg_b = GPT2Config.tiny(), GPT2Config.tiny()
    cfg_b.fused_lm_head = True
    m_a, m_b = GPT2DoubleHeads(cfg_a), GPT2DoubleHeads(cfg_b)
    rng = np.random.RandomState(5)
    batch = _tiny_batch(rng)
    ids, mc, labels, mcl, types = batch
    p_a = m_a.init(jax.random.PRNGKey(0), ids, types, mc,
                   train=False)["params"]
    p_b = m_b.init(jax.random.PRNGKey(0), ids, types, mc,
                   train=False)["params"]
    chex_equal = jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p_a, p_b)
    del chex_equal

    # tiny() is an f32 config, so the fused head runs compute_dtype=f32
    # and must be ~exact against the materialized-logits path
    for make in (make_gpt2_train_loss, make_gpt2_val_loss):
        la, _ = make(m_a)(p_a, batch, jax.random.PRNGKey(1), False)
        lb, _ = make(m_b)(p_b, batch, jax.random.PRNGKey(1), False)
        np.testing.assert_allclose(lb, la, atol=1e-4, rtol=1e-5)

    # grads flow to the tied wte through the fused head
    def total(p):
        loss, _ = make_gpt2_train_loss(m_b)(p, batch,
                                            jax.random.PRNGKey(1), False)
        return jnp.sum(loss)

    g = jax.grad(total)(p_b)
    assert float(jnp.abs(g["wte"]["embedding"]).max()) > 0


def test_fused_lm_head_rejects_ring():
    cfg = GPT2Config.tiny()
    cfg.fused_lm_head = True
    cfg.attn_impl = "ring"
    m = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(6)
    ids, mc, labels, mcl, types = _tiny_batch(rng)
    with pytest.raises(ValueError, match="fused_lm_head"):
        m.init(jax.random.PRNGKey(0), ids, types, mc, train=False)


def _flag_args(**kw):
    from types import SimpleNamespace
    base = dict(fused_ce="auto", fused_lm_head=False, attn_impl="full",
                max_seq_len=256)
    base.update(kw)
    return SimpleNamespace(**base)


def _fake_mesh(**axes):
    from types import SimpleNamespace
    return SimpleNamespace(axis_names=tuple(axes), shape=dict(axes))


def test_fused_ce_auto_dispatches_on_seq_len():
    """--fused_ce auto: off below the T=512 threshold, on at/above it —
    the flip point where the (tokens, vocab) logits tensor starts to
    dominate HBM (docs/ROOFLINE.md)."""
    from commefficient_tpu.training.args import (FUSED_CE_AUTO_T,
                                                 resolve_fused_ce)

    assert not resolve_fused_ce(_flag_args(max_seq_len=256))
    assert not resolve_fused_ce(_flag_args(max_seq_len=FUSED_CE_AUTO_T - 1))
    assert resolve_fused_ce(_flag_args(max_seq_len=FUSED_CE_AUTO_T))
    assert resolve_fused_ce(_flag_args(max_seq_len=1024))


def test_fused_ce_explicit_overrides_auto():
    from commefficient_tpu.training.args import resolve_fused_ce

    assert resolve_fused_ce(_flag_args(fused_ce="on", max_seq_len=64))
    assert not resolve_fused_ce(_flag_args(fused_ce="off",
                                           max_seq_len=2048))
    # legacy --fused_lm_head == --fused_ce on; combining it with an
    # explicit off is a contradiction, not a silent pick
    assert resolve_fused_ce(_flag_args(fused_lm_head=True, max_seq_len=64))
    with pytest.raises(ValueError, match="fused_lm_head"):
        resolve_fused_ce(_flag_args(fused_ce="off", fused_lm_head=True))


def test_fused_ce_auto_stays_off_where_not_plumbed():
    """auto must never resolve to on under ring attention or seq=/stage=
    meshes (the model/pipeline would reject it); explicit 'on' passes
    through so those rejections stay loud."""
    from commefficient_tpu.training.args import resolve_fused_ce

    long = dict(max_seq_len=2048)
    assert not resolve_fused_ce(_flag_args(attn_impl="ring", **long))
    assert not resolve_fused_ce(_flag_args(**long),
                                _fake_mesh(clients=1, seq=2))
    assert not resolve_fused_ce(_flag_args(**long),
                                _fake_mesh(clients=1, stage=2))
    # size-1 inner axes are a plain data mesh: auto still applies
    assert resolve_fused_ce(_flag_args(**long),
                            _fake_mesh(clients=4, seq=1))
    assert resolve_fused_ce(_flag_args(fused_ce="on", attn_impl="ring",
                                       **long))


def test_fused_ce_parser_default_and_legacy_alias():
    from commefficient_tpu.training.args import (build_parser,
                                                 resolve_fused_ce)

    args = build_parser().parse_args([])
    assert args.fused_ce == "auto" and not args.fused_lm_head
    args.max_seq_len, args.attn_impl = 256, "full"
    assert not resolve_fused_ce(args)
    args = build_parser().parse_args(["--fused_lm_head"])
    args.max_seq_len, args.attn_impl = 256, "full"
    assert resolve_fused_ce(args)


def test_tpu_bits_falls_back_to_xla_off_tpu():
    """On CPU the 'tpu_bits' impl must route to masked_dropout and match
    it bit-for-bit (same key, same bits)."""
    from commefficient_tpu.ops.dropout import FusedDropout

    if jax.default_backend() == "tpu":
        pytest.skip("fallback path is the off-TPU behavior")
    x = jnp.ones((4, 256), jnp.float32)
    key = jax.random.PRNGKey(3)
    a = FusedDropout(0.25, "xla").apply({}, x, False,
                                        rngs={"dropout": key})
    b = FusedDropout(0.25, "tpu_bits").apply({}, x, False,
                                             rngs={"dropout": key})
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hw_dropout_on_device_contracts():
    """TPU-only: exact keep rate scaling, forward/backward mask identity,
    and key sensitivity of the Pallas hardware-RNG dropout."""
    from commefficient_tpu.ops.dropout import _seeds_from_key, hw_dropout

    if jax.default_backend() != "tpu":
        pytest.skip("hardware PRNG kernel needs a real TPU")
    seeds = _seeds_from_key(jax.random.PRNGKey(7))
    x = jnp.ones((512, 1024), jnp.float32)
    y = jax.jit(lambda x: hw_dropout(x, seeds, 0.1))(x)
    y = np.asarray(y)
    keep = (y != 0).mean()
    assert abs(keep - 0.9) < 5e-3
    np.testing.assert_allclose(y[y != 0], 1.0 / 0.9, rtol=1e-6)

    g = jax.jit(jax.grad(
        lambda x: jnp.sum(hw_dropout(x, seeds, 0.1))))(x)
    np.testing.assert_array_equal(np.asarray(g), y)

    seeds2 = _seeds_from_key(jax.random.PRNGKey(8))
    y2 = np.asarray(jax.jit(lambda x: hw_dropout(x, seeds2, 0.1))(x))
    assert (y2 != y).mean() > 0.1


def test_rbg_u16_mask_distribution_and_vjp():
    """The xla_rbg path's 16-bit threshold draw: keep fraction within
    statistical tolerance of 1-rate, scaling exact, and the
    recompute-in-backward mask identical between forward and backward
    (two RngBitGenerator draws from the same key are the same bits)."""
    from commefficient_tpu.ops.dropout import _scaled_mask, masked_dropout

    key = jax.random.key(5, impl="rbg")
    m = np.asarray(_scaled_mask(key, 0.1, (512, 512), jnp.float32))
    keep = (m != 0).mean()
    assert abs(keep - 0.9) < 5e-3
    np.testing.assert_allclose(m[m != 0], 1.0 / 0.9, rtol=1e-6)

    x = jnp.ones((512, 512), jnp.float32)
    y = np.asarray(masked_dropout(x, key, 0.1))
    g = np.asarray(jax.grad(
        lambda x: jnp.sum(masked_dropout(x, key, 0.1)))(x))
    np.testing.assert_array_equal(g, y)
