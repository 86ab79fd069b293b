"""Pallas flash-attention kernel vs the reference implementations.

Runs the kernels in Pallas interpreter mode (the CPU test path; on TPU the
same kernels compile via Mosaic — ``blockwise_attention`` auto-dispatches).
Covers: forward equivalence with ``full_attention``, custom-VJP gradients
vs autodiff through ``full_attention``, ragged (non-block-multiple) T,
bf16 inputs, and the NaN regression of the -1e30 sentinel arithmetic
(ops/attention.py fold; observed on TPU with bf16 + >1 kv block).

In-kernel probability dropout: the interpret path draws its keep-bits
from an emulated counter-hash generator whose full mask
``dropout_keep_reference`` reconstructs on the host, so the tests below
check the fused kernel — forward AND its custom VJP — against a dense
reference with that exact mask applied explicitly. Agreement at f32
tolerance is the bit-agreement proof: at rate 0.1 a single keep-bit
differing anywhere between the forward and either backward kernel would
shift whole p/dp entries by O(1), orders of magnitude above the
tolerance. The rate-0 path must be BIT-identical to a call without
dropout arguments (it is statically the unmodified kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops.attention import (blockwise_attention,
                                             full_attention)
from commefficient_tpu.ops.flash_attention import (_NEG,
                                                   dropout_keep_reference,
                                                   flash_attention,
                                                   supported)


def _qkv(B, T, H, D, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)
                             ).astype(dtype)
    return mk(), mk(), mk()


def _masked_reference(q, k, v, keep, rate):
    """Dense causal attention with the GIVEN keep mask applied to the
    normalized probabilities — the semantics the kernel must match."""
    B, T, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    qp = jnp.arange(T)[:, None]
    kp = jnp.arange(T)[None, :]
    s = jnp.where(kp <= qp, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    pd = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", pd, v)


@pytest.mark.parametrize("shape,blocks", [
    ((2, 128, 2, 16), (64, 64)),
    ((1, 200, 3, 8), (64, 32)),     # ragged: T not a block multiple
    ((2, 256, 2, 64), (128, 128)),
    ((1, 96, 1, 16), (256, 256)),   # T smaller than the block
])
def test_forward_matches_full(shape, blocks):
    q, k, v = _qkv(*shape)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=blocks[0],
                          block_k=blocks[1], interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("shape,blocks", [
    ((2, 128, 2, 16), (64, 64)),
    ((1, 200, 2, 8), (64, 32)),
])
def test_custom_vjp_matches_autodiff(shape, blocks):
    q, k, v = _qkv(*shape)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    def loss_fa(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
            interpret=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=2e-4)


def test_bf16_inputs():
    q, k, v = _qkv(1, 128, 2, 16, dtype=jnp.bfloat16)
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), atol=3e-2)


def test_supported_predicate():
    q, k, v = _qkv(1, 64, 2, 16)
    assert supported(q, k, v, causal=True, kv_mask=None)
    assert not supported(q, k, v, causal=False, kv_mask=None)
    assert not supported(q, k, v, causal=True,
                         kv_mask=jnp.ones((1, 64), bool))
    qq = jnp.zeros((1, 64, 2, 12))  # head_dim not a multiple of 8
    assert not supported(qq, qq, qq, causal=True, kv_mask=None)


def test_blockwise_dispatch_equivalence():
    """blockwise_attention(use_kernel=...) must agree between the scan
    path and the kernel (interpret mode stands in for the TPU path)."""
    q, k, v = _qkv(1, 160, 2, 16)
    scan = blockwise_attention(q, k, v, causal=True, block_size=64,
                               use_kernel=False)
    kern = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(scan),
                               atol=2e-5)


def test_dropout_zero_rate_bitwise_identical():
    """dropout_rate=0.0 (key or not) is statically the unmodified kernel:
    outputs AND gradients are bit-identical to a no-dropout-args call."""
    q, k, v = _qkv(2, 128, 2, 16)
    key = jax.random.PRNGKey(3)
    plain = flash_attention(q, k, v, block_q=64, block_k=64,
                            interpret=True)
    zero = flash_attention(q, k, v, block_q=64, block_k=64,
                           dropout_rate=0.0, dropout_key=key,
                           interpret=True)
    assert bool(jnp.array_equal(plain, zero))

    def loss(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    g_plain = loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True))
    g_zero = loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, dropout_rate=0.0,
        dropout_key=key, interpret=True))
    for a, b in zip(g_plain, g_zero):
        assert bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("shape,blocks", [
    ((2, 96, 2, 16), (256, 256)),   # single tile (the T<block clamp)
    ((2, 256, 2, 16), (64, 64)),    # 4x4 tiles: exercises per-tile seeds
    ((1, 200, 2, 8), (64, 32)),     # ragged T + rectangular tiles
])
def test_dropout_forward_matches_masked_reference(shape, blocks):
    B, T, H, D = shape
    q, k, v = _qkv(*shape)
    key = jax.random.PRNGKey(11)
    rate = 0.1
    out = flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1],
                          dropout_rate=rate, dropout_key=key,
                          interpret=True)
    keep = dropout_keep_reference(key, B * H, T, dropout_rate=rate,
                                  block_q=blocks[0], block_k=blocks[1])
    keep = keep[:, :T, :T].reshape(B, H, T, T)
    ref = _masked_reference(q, k, v, keep, rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("shape,blocks", [
    ((2, 96, 2, 16), (256, 256)),
    ((2, 256, 2, 16), (64, 64)),
])
def test_dropout_backward_masks_bit_agree(shape, blocks):
    """The custom VJP regenerates the forward's keep mask in both backward
    kernels: flash gradients must match autodiff through the dense
    reference carrying the host-reconstructed mask. (A single flipped
    keep-bit between forward and backward moves dq/dk/dv entries by O(1)
    — far above the tolerance — so agreement here IS the bit-identity
    check.) Also: two identical calls produce bit-equal gradients."""
    B, T, H, D = shape
    q, k, v = _qkv(*shape, seed=4)
    key = jax.random.PRNGKey(13)
    rate = 0.1
    keep = dropout_keep_reference(key, B * H, T, dropout_rate=rate,
                                  block_q=blocks[0], block_k=blocks[1])
    keep = keep[:, :T, :T].reshape(B, H, T, T)

    def loss_fa(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, block_q=blocks[0], block_k=blocks[1],
            dropout_rate=rate, dropout_key=key, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_masked_reference(q, k, v, keep, rate) ** 2)

    gf = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-4)
    gf2 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gf2):
        assert bool(jnp.array_equal(a, b))


def test_dropout_keep_rate_within_binomial_ci():
    """Realized keep-rate of the tile-seeded generator ~ Binomial(n, 1-r):
    checked on the host reconstruction, which the forward/backward tests
    above pin to the kernel's actual draws bit-for-bit."""
    rate = 0.1
    BH, T = 8, 256
    keep = dropout_keep_reference(jax.random.PRNGKey(17), BH, T,
                                  dropout_rate=rate, block_q=64,
                                  block_k=64)
    n = keep.size
    realized = float(jnp.mean(keep.astype(jnp.float32)))
    sigma = np.sqrt(rate * (1 - rate) / n)
    assert abs(realized - (1 - rate)) < 4 * sigma, \
        f"keep rate {realized} vs {1 - rate} +- {4 * sigma}"
    # and distinct keys draw distinct masks
    keep2 = dropout_keep_reference(jax.random.PRNGKey(18), BH, T,
                                   dropout_rate=rate, block_q=64,
                                   block_k=64)
    assert not bool(jnp.array_equal(keep, keep2))


def test_dropout_rate0_grads_match_scan_reference():
    """Dropout disabled: gradients through the dropout-capable kernel
    entrypoint match the scan-formulation reference at tight tolerance."""
    q, k, v = _qkv(1, 160, 2, 16, seed=2)
    key = jax.random.PRNGKey(0)

    def loss_scan(q, k, v):
        y = blockwise_attention(q, k, v, causal=True, block_size=64,
                                use_kernel=False)
        return jnp.sum(y ** 2)

    def loss_fa(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, block_q=64, block_k=64, dropout_rate=0.0,
            dropout_key=key, interpret=True) ** 2)

    gs = jax.grad(loss_scan, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gf):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=2e-4)


def test_dropout_dispatch():
    """blockwise_attention threads dropout to the kernel; the scan path
    refuses it (it would have to materialize the (T, T) mask)."""
    q, k, v = _qkv(1, 96, 2, 16)
    key = jax.random.PRNGKey(5)
    rate = 0.1
    via_dispatch = blockwise_attention(q, k, v, causal=True,
                                       use_kernel=True, dropout_rate=rate,
                                       dropout_rng=key, block_q=64,
                                       block_k=64, interpret=True)
    direct = flash_attention(q, k, v, block_q=64, block_k=64,
                             dropout_rate=rate, dropout_key=key,
                             interpret=True)
    assert bool(jnp.array_equal(via_dispatch, direct))
    with pytest.raises(ValueError, match="fused kernel"):
        blockwise_attention(q, k, v, causal=True, use_kernel=False,
                            dropout_rate=rate, dropout_rng=key)
    with pytest.raises(ValueError, match="dropout_key"):
        flash_attention(q, k, v, dropout_rate=rate, interpret=True)
    with pytest.raises(ValueError, match="dropout_rate"):
        flash_attention(q, k, v, dropout_rate=1.5, dropout_key=key,
                        interpret=True)


def test_dropout_bf16():
    """bf16 inputs with in-kernel dropout: finite grads, forward close to
    the f32 masked reference (mask application happens in f32)."""
    q, k, v = _qkv(1, 128, 2, 16, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(23)
    rate = 0.1
    out = flash_attention(q, k, v, block_q=64, block_k=64,
                          dropout_rate=rate, dropout_key=key,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    keep = dropout_keep_reference(key, 2, 128, dropout_rate=rate,
                                  block_q=64, block_k=64)
    keep = keep.reshape(1, 2, 128, 128)
    ref = _masked_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), keep, rate)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), atol=5e-2)

    def loss(q, k, v):
        y = flash_attention(q, k, v, block_q=64, block_k=64,
                            dropout_rate=rate, dropout_key=key,
                            interpret=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


def test_bf16_multiblock_grads_finite():
    """Regression: bf16 + multiple kv blocks produced NaN dq/dk on TPU via
    XLA folding the f32 cast of the score einsum into bf16 reductions
    (fixed with preferred_element_type + exponent clamps)."""
    q, k, v = _qkv(1, 128, 2, 16, dtype=jnp.bfloat16)

    def loss(q, k, v):
        y = blockwise_attention(q, k, v, causal=True, block_size=64,
                                use_kernel=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


# the GPT2-small federated round's attention: W*B*C = 64 rows, 12 heads of 64
_ROUND_ROWS, _ROUND_HEADS, _ROUND_HEAD_DIM = 64, 12, 64


@pytest.mark.parametrize("T,variant", [
    (256, (256, 256)), (256, (256, 128)), (256, (128, 256)),
    (256, (128, 128)), (256, "no_dropout"), (256, "xla"),
    (512, (512, 512)), (512, (256, 256)), (512, "no_dropout"),
    (512, "xla")], ids=str)
def test_fwd_bwd_traces_at_the_round_shape(T, variant):
    """Forward and backward of the kernel with in-kernel dropout at each
    (block_q, block_k) a GPT2 round may pick at T = 256 / 512, of the
    kernel without dropout, and of the XLA formulation it replaces
    (materialized scores, causal bias, rbg dropout on the probabilities:
    models/gpt2.py's 'full' branch), traced at the round's bf16 shape.
    Nothing compiles; a block size the kernel refuses, a drifted
    signature or a gate that hands the work to the other formulation
    fails here."""
    from commefficient_tpu.ops.dropout import masked_dropout
    R, H, D = _ROUND_ROWS, _ROUND_HEADS, _ROUND_HEAD_DIM
    key = jax.random.PRNGKey(0)
    rbg_key = jax.random.wrap_key_data(
        jnp.arange(4, dtype=jnp.uint32), impl="rbg")

    def xla_full(q, k, v):
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        causal = jnp.tril(jnp.ones((T, T), bool))
        att = att + jnp.where(causal, 0.0,
                              jnp.finfo(att.dtype).min)[None, None]
        att = masked_dropout(jax.nn.softmax(att, axis=-1), rbg_key, 0.1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    if variant == "xla":
        attn = xla_full
    elif variant == "no_dropout":
        attn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, block_q=256, block_k=256)
    else:
        attn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, block_q=variant[0], block_k=variant[1],
            dropout_rate=0.1, dropout_key=key)
    grad = jax.grad(lambda q, k, v: jnp.sum(
        attn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct((R, T, H, D), jnp.bfloat16)
    closed = jax.make_jaxpr(grad)(x, x, x)
    assert [(a.shape, a.dtype) for a in closed.out_avals] == [
        ((R, T, H, D), jnp.bfloat16)] * 3
    assert ("pallas_call" in str(closed)) == (variant != "xla")
