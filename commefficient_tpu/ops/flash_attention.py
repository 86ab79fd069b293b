"""Pallas TPU flash attention — the fused hot-op behind the long-context
path (and any T where materializing (T, T) scores is wasteful).

The reference materializes full attention scores inside PyTorch/CUDA
(its GPT2 comes from ``pytorch_transformers``; no fused kernel, short
PersonaChat sequences). This framework's scan-based
``ops.attention.blockwise_attention`` already gives O(T*block) memory on
any backend; this module is the TPU-native kernel for the same math:

* one fused kernel per (batch*head, q-block) computes the online softmax
  over k/v blocks entirely in VMEM — no (T, T) score tensor ever touches
  HBM, and XLA cannot fuse across the scan the way a hand-written kernel
  can (the lax.scan formulation re-reads q and re-writes the f32
  accumulators every block).
* a custom VJP recomputes scores blockwise in two more kernels (dq and
  dk/dv), the standard FlashAttention-2 backward: residuals are just the
  output and the per-row logsumexp — O(T) extra memory.
* causal blocks strictly above the diagonal are skipped via
  ``pl.when`` — ~2x fewer score blocks at long T.
* reference-parity Bernoulli dropout ON THE ATTENTION PROBABILITIES
  (``dropout_rate``/``dropout_key``): keep-bits are drawn in-register from
  the TPU core PRNG, seeded deterministically per (batch*head, q-block,
  k-block) tile, so neither the probabilities nor their masks ever touch
  HBM. The backward kernels regenerate bit-identical masks from the same
  per-tile seeds — the recompute-in-backward contract ``ops/dropout.py``
  establishes for the XLA path. The softmax DENOMINATOR accumulates the
  undropped probabilities (normalize-then-drop), exactly matching the
  reference's softmax -> dropout(P) -> P@V order, so the saved logsumexp
  and the whole backward recompute are unchanged; the rank-1 softmax-
  Jacobian fold delta = rowsum(dO*O) survives dropout unchanged because
  rowsum(dO*O) = rowsum((P*M) * (dO V^T)) algebraically.

Numerics: scores, running max and denominator are f32 regardless of the
input dtype (bf16 in the GPT2 round); p and the p@v / ds@k matmuls run in
the input dtype on the MXU with f32 accumulation
(``preferred_element_type``), matching ``ops.attention``'s convention.
The dropout mask/scale is applied to p in f32 before the cast.

Dropout bits: on a real chip ``pltpu.prng_seed``/``prng_random_bits``
(the hardware PRNG — same generator ``ops.dropout.hw_dropout`` measured
at ~8x XLA's bit rate). The Pallas interpreter has no lowering for the
hardware PRNG on CPU, so ``interpret=True`` statically swaps in a pure
jnp counter-based hash generator over the same per-tile seeds;
``dropout_keep_reference`` reconstructs that mask on the host so the CPU
tests can check the kernel against an explicitly-masked reference. Like
the hw/XLA dropout split, the realized mask differs across the two bit
sources but the Bernoulli distribution (and the fwd/bwd bit-agreement
contract) is identical.

Constraints (enforced by ``supported()``): no kv_mask (the GPT2 path
attends padded positions, reference parity — fed_persona.py:360-392 pads
with real tokens and masks the LOSS, not the attention), causal only,
head_dim a multiple of 8. Everything else falls back to the scan
implementation; `ops.attention.blockwise_attention` does the dispatch, so
callers never import this module directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30          # matches ops.attention: exp(_NEG - m) == 0, no NaNs

# Swept on a v5e chip at T=4096, H=12, D=64 bf16 (gpt2-small long-context
# shapes): large q blocks amortize per-grid-step overhead and k/v
# refetch; fwd+bwd 8.3ms vs 25.9ms for the lax.scan formulation (3.1x).
# At short T both clamp to a single (T, T) tile (see tile() below), so
# the federated GPT2 round's T=256 runs one 256x256 score block per
# (b*h); the smaller block sizes of that shape are traced, not timed, by
# tests/test_flash_attention.py::test_fwd_bwd_traces_at_the_round_shape.
DEFAULT_BLOCK_Q = 2048
DEFAULT_BLOCK_K = 512

# Odd 32-bit mixing constants (golden-ratio / murmur3 family) for the
# per-tile seed derivation, written as signed int32 literals (int32
# arithmetic wraps; XLA and the TPU agree on two's complement). The
# first is the same word ops/dropout.py's hw kernel mixes its block
# index with.
_MIX_B = -1640531527       # 0x9E3779B9
_MIX_QB = -2048144777      # 0x85EBCA77
_MIX_KB = -1028477379      # 0xC2B2AE3D
_MIX_B2 = 668265263        # 0x27D4EB2F


def supported(q, k, v, causal: bool, kv_mask) -> bool:
    """Whether the fused kernel handles this call (see module docstring).

    Dtype is part of the gate: Mosaic tiling is only exercised (on a real
    chip: tests/test_flash_attention.py CI runs interpret-mode) for
    f32/bf16; anything else falls back to the scan formulation."""
    B, Tq, H, D = q.shape
    return (causal and kv_mask is None and k.shape == v.shape
            and q.shape[::2] == k.shape[::2] and D % 8 == 0
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and q.dtype == k.dtype == v.dtype
            and Tq == k.shape[1])   # self-attention: q/k share positions


def _pad_t(x, block):
    t = x.shape[1]
    tp = -(-t // block) * block
    if tp == t:
        return x
    return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))


def _effective_blocks(t: int, block_q: int, block_k: int):
    """The (bq, bk) the kernels actually run: clamped to T and rounded up
    to a sublane-tile multiple (16 covers both the f32 sublane of 8 and
    the bf16 sublane of 16) — a ragged T (say 100) must not become the
    literal block shape; Mosaic would reject the unaligned tile on a real
    chip. Shared with ``dropout_keep_reference`` so the host-side mask
    reconstruction tiles exactly like the kernel."""
    from commefficient_tpu.utils.params import round_up
    tile = lambda x: round_up(max(x, 8), 16)
    return tile(min(block_q, t)), tile(min(block_k, t))


def _threshold(rate: float) -> int:
    # keep = (bits >= rate * 2^32): P(keep) = 1 - rate exact to 2^-32 —
    # the same convention (and constant) as ops.dropout.hw_dropout
    return min(int(round(rate * 2.0 ** 32)), 2 ** 32 - 1)


def _hash_bits(s0, s1, shape):
    """Counter-based uint32 stream for the interpreter: position hash
    (murmur3-fmix32 rounds with the two tile-seed words folded in
    between). Pure jnp/VPU ops only — no TPU PRNG — so it lowers
    everywhere; statically selected only when ``interpret=True``.
    ``dropout_keep_reference`` replicates this bit-for-bit on the host."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = r * jnp.uint32(2654435761) + c * jnp.uint32(2246822519)
    x = x ^ s0.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(2246822507)
    x = x ^ s1.astype(jnp.uint32)
    x = (x ^ (x >> 13)) * jnp.uint32(3266489909)
    return x ^ (x >> 16)


def _tile_keep(seed_ref, b, qb, kb, shape, rate: float, hash_bits: bool):
    """The (block_q, block_k) keep mask for tile (b, qb, kb).

    The seed words are a function of the LOGICAL tile coordinates only, so
    the forward, dq and dkv kernels — whose grids order (qb, kb)
    differently — regenerate the identical mask for the same tile, and a
    re-dispatch of the same program draws the same bits (deterministic
    under jit/scan; distinct layers/calls differ via ``seed_ref``, which
    comes from the flax 'dropout' collection's per-module fold_in).
    ``b``/``qb``/``kb`` are program ids evaluated at kernel TOP — the
    interpreter does not resolve program_id inside a pl.when branch."""
    s0 = (seed_ref[0] + b * jnp.int32(_MIX_B) + qb * jnp.int32(_MIX_QB))
    s1 = (seed_ref[1] + kb * jnp.int32(_MIX_KB) + b * jnp.int32(_MIX_B2))
    if hash_bits:
        bits = _hash_bits(s0, s1, shape)
    else:
        pltpu.prng_seed(s0, s1)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(_threshold(rate))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _causal_conditions(qb, kb, block_q, block_k, t_k):
    """(any_valid, fully_valid) for the (qb, kb) score block.

    fully_valid blocks (strictly below the diagonal, no padded keys) skip
    mask materialization entirely — for long T that is ~half of all
    blocks, and the mask is 3 extra VPU passes over (bq, bk)."""
    any_valid = kb * block_k <= (qb + 1) * block_q - 1
    last_k = (kb + 1) * block_k - 1
    fully_valid = (last_k <= qb * block_q) & (last_k < t_k)
    return any_valid, fully_valid


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, block_q, block_k, t_k,
                dropout_rate, hash_bits):
    bh, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def body(masked: bool):
        q = q_ref[0]                                   # (bq, D)
        k = k_ref[0]                                   # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        if masked:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((k_pos <= q_pos) & (k_pos < t_k), s, _NEG)

        m_prev = m_scr[:]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exponent clamped at 0 (true mathematically; defends against
        # rounding slop at sentinel magnitude — see ops.attention)
        p = jnp.exp(jnp.minimum(s - m_new, 0.0))
        if masked:
            # explicit zero: on a fully-masked row m_new == s == _NEG and
            # the exp above is exp(0) == 1. Causal self-attention never
            # produces such a row (key 0 is always valid), but the guard
            # keeps the kernel correct if masking is ever extended; it
            # costs a select on diagonal blocks only
            p = jnp.where(s <= _NEG / 2, 0.0, p)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        # the denominator accumulates the UNDROPPED p: the reference drops
        # the already-normalized probabilities, so l (and the saved lse)
        # must not see the mask
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        if dropout_rate > 0.0:
            keep = _tile_keep(seed_ref, bh, qb, kb, (block_q, block_k),
                              dropout_rate, hash_bits)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, D)
        acc_scr[:] = acc_scr[:] * corr + pv

    any_valid, fully_valid = _causal_conditions(qb, kb, block_q, block_k,
                                                t_k)
    pl.when(any_valid & fully_valid)(lambda: body(masked=False))
    pl.when(any_valid & jnp.logical_not(fully_valid))(
        lambda: body(masked=True))

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # logsumexp residual for the backward recompute; fully-masked rows
        # keep the _NEG sentinel (the backward kernels zero their p
        # explicitly). Stored lane-oriented as ((b, qb)-row, 1, block_q):
        # a trailing dim of 1 would waste 127/128 lanes of every VMEM tile
        # it touches, and Mosaic requires the block's second-to-last dim
        # to match the array's.
        lse_ref[0, 0] = jnp.where(m_scr[:] <= _NEG / 2, _NEG,
                                  m_scr[:] + jnp.log(l))[:, 0]


def _fwd(q3, k3, v3, seeds, scale, block_q, block_k, t_k, dropout_rate,
         interpret):
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    nq, nk = Tq // block_q, Tk // block_k
    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, t_k=t_k,
                               dropout_rate=dropout_rate,
                               hash_bits=interpret)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j: (b * nq + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH * nq, 1, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seeds, q3, k3, v3)
    return o, lse


# --------------------------------------------------------------------------
# backward — FlashAttention-2 style: recompute p blockwise from q/k and the
# saved logsumexp; delta = rowsum(do * o) folds the softmax Jacobian's
# rank-1 term. With dropout: dv sees the dropped p; the softmax backward
# sees dp masked/scaled (dPd = dO V^T flows through the mask before the
# Jacobian); delta is unchanged (see module docstring).
# --------------------------------------------------------------------------

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, scale, block_q, block_k,
                   t_k, dropout_rate, hash_bits):
    bh, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def body(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((k_pos <= q_pos) & (k_pos < t_k), s, _NEG)
        p = jnp.exp(jnp.minimum(s - lse_ref[0, 0][:, None], 0.0))
        if masked:
            # fully-masked rows store lse == _NEG, making the exp above 1,
            # not 0 — zero them explicitly (see _fwd_kernel's comment)
            p = jnp.where(s <= _NEG / 2, 0.0, p)

        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        if dropout_rate > 0.0:
            # regenerate the forward tile's mask bit-for-bit (same seeds,
            # same logical (qb, kb)) and push the cotangent through it
            keep = _tile_keep(seed_ref, bh, qb, kb, (block_q, block_k),
                              dropout_rate, hash_bits)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta_ref[0, 0][:, None])       # (bq, bk) f32
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    any_valid, fully_valid = _causal_conditions(qb, kb, block_q, block_k,
                                                t_k)
    pl.when(any_valid & fully_valid)(lambda: body(masked=False))
    pl.when(any_valid & jnp.logical_not(fully_valid))(
        lambda: body(masked=True))

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, block_q, block_k, t_k, dropout_rate,
                    hash_bits):
    bh, kb, qb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((k_pos <= q_pos) & (k_pos < t_k), s, _NEG)
        p = jnp.exp(jnp.minimum(s - lse_ref[0, 0][:, None], 0.0))
        if masked:
            # fully-masked rows store lse == _NEG, making the exp above 1,
            # not 0 — zero them explicitly (see _fwd_kernel's comment)
            p = jnp.where(s <= _NEG / 2, 0.0, p)

        do = do_ref[0]
        if dropout_rate > 0.0:
            # one draw serves both terms: dv needs the dropped p, ds needs
            # the dropped dp — same tile, same mask
            keep = _tile_keep(seed_ref, bh, qb, kb, (block_q, block_k),
                              dropout_rate, hash_bits)
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
        else:
            keep, inv, p_drop = None, 1.0, p
        dv_scr[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, D)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    any_valid, fully_valid = _causal_conditions(qb, kb, block_q, block_k,
                                                t_k)
    pl.when(any_valid & fully_valid)(lambda: body(masked=False))
    pl.when(any_valid & jnp.logical_not(fully_valid))(
        lambda: body(masked=True))

    @pl.when(qb == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, do3, lse, delta, seeds, scale, block_q, block_k, t_k,
         dropout_rate, interpret):
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    nq, nk = Tq // block_q, Tk // block_k
    s_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    r_spec = pl.BlockSpec((1, 1, block_q),
                          lambda b, i, j: (b * nq + i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, t_k=t_k,
                          dropout_rate=dropout_rate, hash_bits=interpret),
        grid=(BH, nq, nk),
        in_specs=[s_spec, q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seeds, q3, k3, v3, do3, lse, delta)

    # swap grid roles: (bh, kv-block, q-block); q-side operands follow j
    q_spec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    k_spec2 = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    r_spec2 = pl.BlockSpec((1, 1, block_q),
                           lambda b, i, j: (b * nq + j, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, t_k=t_k,
                          dropout_rate=dropout_rate, hash_bits=interpret),
        grid=(BH, nk, nq),
        in_specs=[s_spec, q_spec2, k_spec2, k_spec2, q_spec2, r_spec2,
                  r_spec2],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k3.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seeds, q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q3, k3, v3, seeds, scale, blocks, dropout_rate, interpret):
    o, _ = _fwd(q3, k3, v3, seeds, scale, blocks[0], blocks[1], blocks[2],
                dropout_rate, interpret)
    return o


def _flash_fwd_rule(q3, k3, v3, seeds, scale, blocks, dropout_rate,
                    interpret):
    o, lse = _fwd(q3, k3, v3, seeds, scale, blocks[0], blocks[1],
                  blocks[2], dropout_rate, interpret)
    return o, (q3, k3, v3, seeds, o, lse)


def _flash_bwd_rule(scale, blocks, dropout_rate, interpret, res, do):
    q3, k3, v3, seeds, o, lse = res
    BH, Tq, _ = q3.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                           # (BH, Tq)
    delta = delta.reshape(-1, 1, blocks[0])            # match lse layout
    dq, dk, dv = _bwd(q3, k3, v3, do, lse, delta, seeds, scale,
                      blocks[0], blocks[1], blocks[2], dropout_rate,
                      interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    dropout_rate: float = 0.0,
                    dropout_key=None,
                    interpret: bool = False) -> jax.Array:
    """Fused causal self-attention. q/k/v: (B, T, H, D) -> (B, T, H, D).

    Differentiable (custom VJP). ``dropout_rate > 0`` applies reference-
    parity Bernoulli dropout to the attention PROBABILITIES inside the
    kernel (keep-bits from the TPU core PRNG, never materialized to HBM),
    seeded from ``dropout_key`` (a JAX PRNG key); the backward regenerates
    the identical mask. ``dropout_rate == 0.0`` is statically the
    unmodified kernel — bit-identical to a call without dropout arguments.
    ``interpret=True`` runs the kernels in the Pallas interpreter — the
    CPU test path (dropout bits then come from the emulated hash
    generator; see module docstring). Use
    ``ops.attention.blockwise_attention`` unless you specifically want the
    kernel: it dispatches here when ``supported()`` and the backend is TPU.
    """
    if not causal:
        raise NotImplementedError("flash_attention is causal-only; "
                                  "use ops.attention for non-causal")
    rate = float(dropout_rate)
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {rate}")
    if rate > 0.0 and dropout_key is None:
        raise ValueError("dropout_rate > 0 requires dropout_key")
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    # see _effective_blocks: clamp to T, round up to a sublane tile; an
    # explicit block_q=100 must not reach Mosaic as a 100-row tile any
    # more than a ragged T may. _pad_t then pads T to the block, the
    # kernel masks padded keys via t_k, and padded query rows are sliced
    # off on return.
    bq, bk = _effective_blocks(T, block_q, block_k)
    if rate > 0.0:
        from commefficient_tpu.ops.dropout import _seeds_from_key
        seeds = _seeds_from_key(dropout_key)
    else:
        # dead operand on the rate-0 path (the kernels never read it);
        # kept unconditional so the call structure is static
        seeds = jnp.zeros((2,), jnp.int32)

    def to3(x, block):
        return _pad_t(x.transpose(0, 2, 1, 3).reshape(B * H, T, D), block)

    q3, k3, v3 = to3(q, bq), to3(k, bk), to3(v, bk)
    o3 = _flash(q3, k3, v3, seeds, scale, (bq, bk, T), rate, interpret)
    return (o3[:, :T]
            .reshape(B, H, T, D).transpose(0, 2, 1, 3))


def dropout_keep_reference(dropout_key, batch_heads: int, t: int, *,
                           dropout_rate: float,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Host-side reconstruction of the INTERPRET-mode keep mask.

    Returns the (batch_heads, Tq_pad, Tk_pad) bool mask the interpreter
    kernels realize for these arguments (``batch_heads`` = B*H of the
    flash_attention call; padding per ``_effective_blocks``). Pure jnp —
    it replays ``_tile_keep``'s seed derivation and ``_hash_bits``
    bit-for-bit, which is what lets the CPU tests check the fused forward
    AND backward against an explicitly-masked dense reference. Only valid
    for ``interpret=True`` calls: a real chip draws different (but
    identically-distributed) bits from the hardware PRNG."""
    from commefficient_tpu.ops.dropout import _seeds_from_key
    seeds = _seeds_from_key(dropout_key)
    bq, bk = _effective_blocks(t, block_q, block_k)
    tq = -(-t // bq) * bq
    tk = -(-t // bk) * bk
    b = jnp.arange(batch_heads, dtype=jnp.int32)
    qb = jnp.arange(tq // bq, dtype=jnp.int32)
    kb = jnp.arange(tk // bk, dtype=jnp.int32)
    s0 = (seeds[0] + b[:, None] * jnp.int32(_MIX_B)
          + qb[None, :] * jnp.int32(_MIX_QB))           # (BH, nq)
    s1 = (seeds[1] + kb[None, :] * jnp.int32(_MIX_KB)
          + b[:, None] * jnp.int32(_MIX_B2))            # (BH, nk)
    s0 = jnp.repeat(s0, bq, axis=1).astype(jnp.uint32)  # (BH, tq)
    s1 = jnp.repeat(s1, bk, axis=1).astype(jnp.uint32)  # (BH, tk)
    r = (jnp.arange(tq, dtype=jnp.uint32) % jnp.uint32(bq))
    c = (jnp.arange(tk, dtype=jnp.uint32) % jnp.uint32(bk))
    x = (r[:, None] * jnp.uint32(2654435761)
         + c[None, :] * jnp.uint32(2246822519))[None]   # (1, tq, tk)
    x = x ^ s0[:, :, None]
    x = (x ^ (x >> 16)) * jnp.uint32(2246822507)
    x = x ^ s1[:, None, :]
    x = (x ^ (x >> 13)) * jnp.uint32(3266489909)
    x = x ^ (x >> 16)
    return x >= jnp.uint32(_threshold(float(dropout_rate)))
