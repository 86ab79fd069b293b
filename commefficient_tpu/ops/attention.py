"""Long-context attention: blockwise (flash-style) and ring attention.

The reference has NO sequence parallelism — PersonaChat utterances are
short, padded per batch (reference fed_persona.py:360-392), and attention
materializes the full (T, T) score matrix. For a TPU-first framework,
long-context is a first-class capability:

* ``blockwise_attention`` — single-device flash-style attention: an online
  softmax over key/value blocks via ``lax.scan``, so peak memory is
  O(T * block) instead of O(T^2). f32 running max/denominator for
  stability regardless of compute dtype.

* ``ring_attention`` — sequence-parallel attention over a ``seq`` mesh
  axis. Each device holds a contiguous sequence shard of q/k/v; k/v shards
  rotate around the ring with ``lax.ppermute`` while every device folds
  the visiting block into the same online softmax. After ``seq`` steps
  every query has attended to every key; communication rides the ICI
  neighbor links (the all-to-all-free formulation of Liu et al.'s Ring
  Attention). Call it inside ``shard_map`` with sequence-sharded operands
  — ``ring_attention_sharded`` wraps exactly that.

Both are numerically equivalent (<=1e-5 f32) to full attention — tested
against ``full_attention`` on an 8-device CPU mesh in
tests/test_attention.py. Attention-probability dropout is supported on
the fused-kernel path only (``blockwise_attention(dropout_rate=...,
dropout_rng=...)`` — keep-bits drawn in-register per score tile,
regenerated bit-identically in the backward; ops/flash_attention.py).
The scan and ring formulations still do not compose with prob-dropout
(XLA recomputes nothing, so the mask would have to materialize at
O(T^2)); callers that need dropout off-kernel apply output dropout
instead (models/gpt2.py's fallback).

* ``grouped_query_attention`` — the training path with fewer key/value
  heads than query heads (32 over 2, say): the shared heads repeated, then
  one of the two above.

* ``decode_attention`` — the inference mode: one (or a few) query rows
  against a cached (B, S, H, D) key/value array with per-row global
  positions. O(S) per generated token; the KV-cached serving path
  (models/gpt2.py cache mode, commefficient_tpu/serving/) is built on it.

* ``paged_verify_attention`` / ``paged_decode_attention`` — the same
  decode mode against block-paged KV pools reached through a traced
  page table, masked by logical position; the verify form takes
  Tq = speculate_k + 1 queries per row (the speculative-decoding
  multi-token verify, serving/speculative.py), the decode form is its
  Tq = 1 alias.

Layout: q/k/v are (B, T, H, D); causal masking uses GLOBAL positions, so
shards mask correctly wherever they sit in the ring. ``kv_mask`` (B, T)
marks valid (non-pad) keys.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30  # large-negative instead of -inf: exp(_NEG - m) == 0 without
              # producing NaN on fully-masked score rows


def full_attention(q, k, v, *, causal: bool = True,
                   kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Plain O(T^2)-memory attention; the correctness reference."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # f32 scores via MXU accumulation (NOT a bf16 einsum + cast: XLA may
    # fold the cast into downstream reductions at bf16, corrupting the
    # _NEG sentinel enough that the online-softmax exps blow up — observed
    # as NaN grads on TPU)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    if causal:
        # ADDITIVE bias, not jnp.where(mask, s, _NEG): the select's
        # backward is another (B, H, T, T) select (ds where-zeroed), an
        # add's backward is identity. Measured speed-NEUTRAL on the
        # deterministic device A/B (docs/ROOFLINE.md r5 — XLA already
        # fuses the select into the bandwidth-bound softmax chain); kept
        # as the simpler form. Identical math: |s| << |_NEG|, so s + _NEG
        # is -1e30 in f32 (absorbed) and exp()==0 exactly, and masked
        # positions get p == 0 so no gradient flows to them either way.
        qp = jnp.arange(Tq)[:, None]
        kp = jnp.arange(Tk)[None, :]
        s = s + jnp.where(kp <= qp, 0.0, _NEG)[None, None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask[:, None, None, :], 0.0, _NEG)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if causal and kv_mask is None and Tq == Tk:
        # causal self-attention can have no fully-masked query row
        # (position q always attends to itself), so the any_valid
        # correction below is an identity — skipping it drops a
        # (B,H,T,T) compare-reduce and a (B,T,H,D) select from the
        # trace. (This function is the ops-level correctness reference
        # used by the tests/seq paths; the GPT2 'full' path is the
        # inline attention in models/gpt2.py.)
        return out
    # fully-masked queries emit 0 (softmax of an all-masked row would
    # produce a meaningless uniform average) — the same convention the
    # online-softmax impls use
    any_valid = jnp.any(s > _NEG / 2, axis=-1)            # (B, H, Tq)
    return jnp.where(any_valid.transpose(0, 2, 1)[..., None], out, 0.0)


#: sequences from this length on take the blockwise (flash) path
_BLOCKWISE_FROM = 1024


def grouped_query_attention(q, k, v, *, causal: bool = True) -> jax.Array:
    """Training-path attention with fewer key/value heads than query heads:
    q (B, T, H, D), k/v (B, T, Hkv, D), H a multiple of Hkv; query head h
    reads key/value head h // (H // Hkv). The shared heads are repeated to H
    (autodiff sums their gradients back over the group) and handed to
    ``full_attention`` below ``_BLOCKWISE_FROM`` positions, else to
    ``blockwise_attention`` (the fused kernel on a TPU)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} key/value heads")
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    if q.shape[1] < _BLOCKWISE_FROM:
        return full_attention(q, k, v, causal=causal)
    return blockwise_attention(q, k, v, causal=causal)


def decode_attention(q, k, v, q_pos, *,
                     kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Single-query attention against a KV cache: the decode mode.

    ``q`` is (B, Tq, H, D) with a SMALL static Tq (1 for token-by-token
    decode); ``k``/``v`` are the cache, (B, S, H, D) with S the cache
    capacity. ``q_pos`` (B,) is each row's global position of q's first
    query, so scores are (B, H, Tq, S) — O(S) work and memory per token
    instead of the O(S^2) a full recompute pays — and key position kp is
    attended iff kp <= q_pos[b] + t. Stale cache slots beyond the row's
    position are masked out by construction, so callers may leave
    garbage (pad-derived prefill writes) above the write position.

    Every query attends at least to its own just-written position, so
    no fully-masked rows exist and no zero-emission correction is
    needed. f32 scores via MXU accumulation (see full_attention).

    Tensor-parallel contract (parallel/tp.py): H is a pure batch axis
    of both einsums here, so a cache head-sharded along the 'model'
    mesh axis keeps this whole function shard-local — GSPMD introduces
    NO collective inside it (the block's single psum sits after the
    downstream output projection)."""
    B, Tq, H, D = q.shape
    S = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    kp = jnp.arange(S)
    qp = q_pos[:, None] + jnp.arange(Tq)[None, :]          # (B, Tq)
    mask = kp[None, None, :] <= qp[:, :, None]             # (B, Tq, S)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, :]
    s = s + jnp.where(mask, 0.0, _NEG)[:, None]            # broadcast H
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def paged_verify_attention(q, k_pool, v_pool, page_table, q_pos, *,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Multi-query attention against a block-paged KV cache.

    ``q`` is (B, Tq, H, D) with small static Tq — 1 for token-by-token
    decode, ``speculate_k + 1`` for the speculative verify forward
    (serving/speculative.py), where the target model scores a row's
    pending token plus its drafted continuation in ONE forward;
    ``k_pool``/``v_pool`` are the shared page pools, (num_pages,
    page_size, H, D); ``page_table`` (B, M) int32 maps each row's
    logical page m to a physical pool page (physical page 0 is the
    reserved garbage page — free lanes and unallocated logical pages
    point there); ``q_pos`` (B,) is each row's position of q's first
    query. M * page_size is the logical capacity, so this scores the
    same M*P key positions the dense ``decode_attention`` scores over
    its (B, S, H, D) cache — the mask is by LOGICAL position
    ``m * page_size + p <= q_pos[b] + t``, which covers garbage-page
    reads by construction (an unallocated logical page lies entirely
    above the row's position) and keeps rejected speculative entries
    above a row's accepted frontier unattendable until overwritten.

    With ``k_scale``/``v_scale`` ((num_pages, H) f32) the pools are
    QUANTIZED (ops/kv_quant.py: int8, or nibble-packed int4) and the
    dequantization happens here, on the GATHERED pages only — the
    per-page scales gather through the same page table and multiply
    the (B, M, P, H, D) working set, so no f32 (or compute-dtype)
    array of the pool's own (num_pages, page_size, H, D) shape ever
    exists, which is exactly what the ``decode_paged_quant`` audit
    target forbids.

    The gathered pages stay 5-D (B, M, P, H, D) end to end — they are
    never reshaped to a (B, S, H, D) slab, so the per-step working set
    is the gather plus (B, H, Tq, M, P) scores and the ``decode_paged``
    / ``decode_speculative`` audits' forbidden dense-cache shape cannot
    appear. f32 scores via MXU accumulation (see full_attention); the
    (m, p) contraction runs in logical order, matching the dense path's
    key order.

    Tensor-parallel contract (parallel/tp.py): H is a batch axis of
    the gather AND both einsums, so pools head-sharded along the
    'model' mesh axis — (num_pages, page_size, H/tp, D) per shard,
    scales (num_pages, H/tp) — keep the page gather and the whole
    score/softmax/weighted-sum pipeline shard-local. The page_table
    index is replicated (tiny int32), so GSPMD lowers the gather to a
    local dynamic-gather per shard with NO collective; the block's one
    psum sits after the downstream output projection."""
    B, Tq, H, D = q.shape
    P = k_pool.shape[1]
    M = page_table.shape[1]
    k = k_pool[page_table]                                 # (B, M, P, H, D)
    v = v_pool[page_table]
    if k_scale is not None:
        from commefficient_tpu.ops import kv_quant
        mode = kv_quant.infer_mode(k_pool, D)
        k = kv_quant.dequantize_pages(k, k_scale[page_table],
                                      mode).astype(q.dtype)
        v = kv_quant.dequantize_pages(v, v_scale[page_table],
                                      mode).astype(q.dtype)
    s = jnp.einsum("bqhd,bmphd->bhqmp", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    logical = jnp.arange(M)[:, None] * P + jnp.arange(P)[None, :]  # (M, P)
    qp = q_pos[:, None] + jnp.arange(Tq)[None, :]          # (B, Tq)
    mask = logical[None, None] <= qp[:, :, None, None]     # (B, Tq, M, P)
    s = s + jnp.where(mask, 0.0, _NEG)[:, None]            # broadcast H
    p = jax.nn.softmax(
        s.reshape(B, H, Tq, M * P).astype(jnp.float32), axis=-1)
    p = p.reshape(B, H, Tq, M, P).astype(q.dtype)
    return jnp.einsum("bhqmp,bmphd->bqhd", p, v)


def paged_decode_attention(q, k_pool, v_pool, page_table, q_pos, *,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Single-query (Tq == 1) decode against the paged cache — a pure
    delegation to ``paged_verify_attention``, which is the same math at
    general Tq (identical einsums, so the Tq=1 trace is bitwise the
    pre-speculative program). Kept as the named decode entry point the
    serving step and its docs refer to. ``k_scale``/``v_scale`` select
    the quantized-pool form (in-gather dequant; ops/kv_quant.py).
    Inherits paged_verify_attention's tensor-parallel contract: head-
    sharded pools keep the Tq=1 step shard-local, no collectives."""
    return paged_verify_attention(q, k_pool, v_pool, page_table, q_pos,
                                  k_scale=k_scale, v_scale=v_scale)


def _fold_block(acc, q, kb, vb, q_pos, k_pos, kv_mask_b, causal):
    """Fold one k/v block into the online-softmax accumulator.

    acc = (m (B,H,Tq), l (B,H,Tq), o (B,Tq,H,D)); f32 statistics."""
    m, l, o = acc
    D = q.shape[-1]
    # preferred_element_type, not .astype: see full_attention's comment
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    if causal:
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None], s, _NEG)
    if kv_mask_b is not None:
        s = jnp.where(kv_mask_b[:, None, None, :], s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # explicit zero for masked entries: when every score so far is _NEG,
    # exp(s - m_new) would be exp(0) = 1 and re-enable them.
    # The exponents are clamped at 0: mathematically s <= m_new and
    # m <= m_new always, but XLA fusion may recompute the two sides of the
    # subtraction along different (mixed-precision) paths, and at sentinel
    # magnitude the rounding slop can reach exp-overflow — inf * 0 = NaN in
    # the VJP (observed on TPU bf16 with >1 kv block; the clamp is free)
    p = jnp.where(s <= _NEG / 2, 0.0,
                  jnp.exp(jnp.minimum(s - m_new[..., None], 0.0)))
    corr = jnp.exp(jnp.minimum(m - m_new, 0.0))
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vb)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
    return m_new, l_new, o_new


def _finish(m, l, o, dtype):
    # fully-masked queries (all-pad rows) have l == 0: emit 0, not NaN
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(dtype)


def kernel_prob_dropout_eligible(q, k, v, *, causal: bool = True,
                                 kv_mask: Optional[jax.Array] = None) -> bool:
    """True when ``blockwise_attention`` would auto-dispatch the fused
    kernel for this call — i.e. when in-kernel attention-probability
    dropout is available. The model layer keys its dropout placement off
    this (in-kernel prob dropout when eligible, output dropout otherwise)
    so eligibility logic lives in exactly one place."""
    from commefficient_tpu.ops import flash_attention as _fa
    return (_fa.supported(q, k, v, causal, kv_mask)
            and jax.default_backend() == "tpu")


def blockwise_attention(q, k, v, *, causal: bool = True,
                        kv_mask: Optional[jax.Array] = None,
                        block_size: int = 512,
                        use_kernel: Optional[bool] = None,
                        dropout_rate: float = 0.0,
                        dropout_rng: Optional[jax.Array] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """Flash-style attention: O(T*block) memory on any backend.

    On TPU, calls the fused Pallas kernel (ops/flash_attention.py — 3.1x
    the lax.scan formulation for fwd+bwd at T=4096) whenever the call is
    kernel-supported (causal self-attention, no kv_mask); otherwise scans
    over key/value blocks with the same online softmax. ``use_kernel``
    forces the choice (None = auto); ``block_size`` applies to the scan
    path only — the kernel uses its swept defaults unless
    ``block_q``/``block_k`` override them (tests/test_flash_attention.py).

    ``dropout_rate > 0`` applies reference-parity Bernoulli dropout to
    the attention probabilities INSIDE the kernel, seeded from
    ``dropout_rng`` — kernel path only: the scan formulation raises,
    because supporting it would mean materializing the O(T^2) mask this
    module exists to avoid. ``interpret`` runs the kernel in the Pallas
    interpreter (CPU tests)."""
    from commefficient_tpu.ops import flash_attention as _fa
    if use_kernel is None:
        use_kernel = kernel_prob_dropout_eligible(q, k, v, causal=causal,
                                                  kv_mask=kv_mask)
    if use_kernel:
        if not _fa.supported(q, k, v, causal, kv_mask):
            raise ValueError(
                "use_kernel=True but the call is not kernel-supported "
                "(needs causal self-attention without kv_mask)")
        kw = {}
        if block_q is not None:
            kw["block_q"] = block_q
        if block_k is not None:
            kw["block_k"] = block_k
        return _fa.flash_attention(q, k, v, causal=causal,
                                   dropout_rate=dropout_rate,
                                   dropout_key=dropout_rng,
                                   interpret=interpret, **kw)
    if dropout_rate > 0.0:
        raise ValueError(
            "attention-probability dropout needs the fused kernel path "
            "(the scan formulation would materialize the (T, T) mask); "
            "use output dropout on this backend/shape instead")
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bs = min(block_size, Tk)
    nb = -(-Tk // bs)
    Tp = nb * bs
    pad = [(0, 0), (0, Tp - Tk), (0, 0), (0, 0)]
    kp = jnp.pad(k, pad).reshape(B, nb, bs, H, D).transpose(1, 0, 2, 3, 4)
    vp = jnp.pad(v, pad).reshape(B, nb, bs, H, D).transpose(1, 0, 2, 3, 4)
    # padded keys are masked via kv_mask (padding always produces one)
    km = jnp.ones((B, Tk), bool) if kv_mask is None else kv_mask.astype(bool)
    km = jnp.pad(km, [(0, 0), (0, Tp - Tk)]).reshape(B, nb, bs) \
        .transpose(1, 0, 2)
    q_pos = jnp.arange(Tq)
    k_pos_blocks = jnp.arange(Tp).reshape(nb, bs)

    m0 = jnp.full((B, H, Tq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)

    def step(acc, xs):
        kb, vb, kmb, k_pos = xs
        return _fold_block(acc, q, kb, vb, q_pos, k_pos, kmb, causal), None

    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0),
                                (kp, vp, km, k_pos_blocks))
    return _finish(m, l, o, q.dtype)


def ring_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                   kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Sequence-parallel attention; call INSIDE shard_map.

    Operands are this device's sequence shard: q/k/v (B, T_loc, H, D),
    ``kv_mask`` (B, T_loc). k/v (and the mask) travel the ring; global
    positions derive from each visiting shard's origin, so causal masking
    is exact across shards."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, T, H, D = q.shape
    q_pos = my * T + jnp.arange(T)

    # derive initial accumulators (and the all-valid mask) from q so
    # shard_map types them as varying over axis_name (plain constants
    # would mismatch the ppermute'd loop carry)
    zero = jnp.zeros_like(q, jnp.float32)
    km = (zero[..., 0, 0] == 0) if kv_mask is None else kv_mask.astype(bool)
    m0 = zero[..., 0].transpose(0, 2, 1) + _NEG    # (B, H, T)
    l0 = zero[..., 0].transpose(0, 2, 1)
    o0 = zero
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        m, l, o, kb, vb, kmb = carry
        src = (my - s) % n              # ring owner of the visiting shard
        k_pos = src * T + jnp.arange(T)
        m, l, o = _fold_block((m, l, o), q, kb, vb, q_pos, k_pos, kmb,
                              causal)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        kmb = jax.lax.ppermute(kmb, axis_name, perm)
        return m, l, o, kb, vb, kmb

    m, l, o, _, _, _ = jax.lax.fori_loop(0, n, step,
                                         (m0, l0, o0, k, v, km))
    return _finish(m, l, o, q.dtype)


def ring_attention_sharded(mesh, q, k, v, *, axis_name: str = "seq",
                           causal: bool = True,
                           kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Convenience wrapper: shard q/k/v over ``axis_name`` and run
    ``ring_attention``. Inputs/outputs are global (B, T, H, D) arrays."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    qkv_spec = P(None, axis_name, None, None)
    mask_spec = P(None, axis_name)
    fn = partial(ring_attention, axis_name=axis_name, causal=causal)
    if kv_mask is None:
        return shard_map(lambda a, b, c: fn(a, b, c), mesh=mesh,
                         in_specs=(qkv_spec,) * 3,
                         out_specs=qkv_spec)(q, k, v)
    return shard_map(lambda a, b, c, mm: fn(a, b, c, kv_mask=mm), mesh=mesh,
                     in_specs=(qkv_spec,) * 3 + (mask_spec,),
                     out_specs=qkv_spec)(q, k, v, kv_mask)
