"""Streaming hierarchical top-k Pallas kernels + fused server-update epilogue.

The FetchSGD server recovers each round's update with an exact magnitude
top-k over the full parameter dimension (d = 124.4M at the repo's GPT2
shape). The incumbent chain (federated/server.py + ops/topk.py) runs as
separate XLA ops — estimates, ``vec*vec`` scores, ``jax.lax.top_k``'s
full sort, a dense scatter mask, then the error-feedback masking — each
materializing its own d-sized f32 vector in HBM, and the sort is the
last O(d·log d) stage in the round. This module replaces the whole chain
with two streaming passes over 8,192-element tiles:

* **Pass 1 — exact threshold by radix-select.** Magnitude scores
  ``v*v`` are non-negative f32, so their IEEE bit patterns, read as
  signed int32, order identically to the floats (sign bit 0). Eight
  rounds of 4-bit refinement each run ONE ``pallas_call`` over the
  stream that counts ``bits >= cand`` for the 16 candidate prefixes of
  the current nibble; the largest candidate whose count still reaches k
  extends the prefix. After 8 rounds the prefix IS the k-th largest
  score's bit pattern, exactly. One more counting call at ``[t, t+1]``
  yields ``n_gt`` (strictly-greater survivors), so ``n_take = k - n_gt``
  ties must be accepted. Total work: 9 streaming passes of pure
  compare+sum — O(d) each, no sort, no d-sized intermediate (the only
  HBM traffic is re-reading the operand stream). A counting call walks
  the stream in its own blocks of ``COUNT_ROWS`` rows, not in tiles:
  its counts stay lane-dense in VMEM, one ``int32[8, 128]`` a
  candidate, and fold to 16 SMEM scalars once, at the last step.

* **Pass 2 — fused select/epilogue.** A second sequential-grid kernel
  recomputes each tile's scores, selects ``bits > t`` plus the first
  ``n_take`` ties in flat-index order — a running tie count carried in
  SMEM across grid steps, with the within-tile exclusive rank computed
  by two strict-lower-triangular matmuls (exact: 0/1 operands, counts
  < 2^24) — and writes ONLY the outputs the round keeps. Two source
  modes are baked in statically:

  - ``plain``    — the stream is the vector itself (ops/topk.py, and
    the sketch server's estimate vector, below);
  - ``resid``    — the true_topk server epilogue: the momentum read
    ``v = g + rho*vvel`` / ``err = verr + v`` runs ONCE in the XLA
    wrapper (recomputing a mul-then-add inside the kernel is not
    bit-safe — the compiler may contract it into an FMA, a 1-ulp drift
    vs the incumbent program), then the kernels stream (err, v) and
    fuse everything downstream: the masked update AND both
    error-feedback residuals ``where(support, 0, err)`` /
    ``where(support, 0, v)`` emit tile-by-tile, with no sort, no
    scatter mask and no post-momentum d-vector.

**Unsketch + top-k** (:func:`unsketch_select_pallas`, the sketch
server's update) is the ``plain`` program over ONE d-long buffer: a
single pass of ops/sketch_kernels' estimates kernel writes every
coordinate's estimate in the tiled layout (five hashes, five window
gathers, an XOR lane permutation and a median a coordinate: 4.6 ms at
d = 6.57 M on a v5e with the permutation as a butterfly, sixteen times a
plain count pass), the nine counts stream that
buffer, and the select pass overwrites it in place with the masked
update (``input_output_aliases``: it reads tile i and writes tile i).
Recomputing the estimates per tile inside each pass instead costs ten
estimate passes a round (PERF.md, PR 36).

**Tie-break bit-agreement.** ``jax.lax.top_k`` is stable: equal scores
are taken in ascending index order. Selecting ties in flat-index order
until ``n_take`` are taken reproduces exactly the set ``lax.top_k``
keeps, so the dense masked outputs are BITWISE-identical to the
incumbent (including ``-0.0`` survivors and the ``update != 0`` support
convention — masking uses the value's own nonzeroness, not the
selection mask). Padding lanes get the sentinel bit pattern INT32_MIN,
which no valid non-negative score can reach, so they never count and
never select. ``tests/test_topk_kernels.py`` pins parity under
duplicated magnitudes and sign-differing equal squares.

**Per-row k.** k enters only comparisons — never shapes — so the
batched 2-D grid variant takes a traced per-row ``kk`` vector: the
heterogeneous-client path (``--client_k_dist``) selects each worker's
own k on-kernel in one pass, with static-max-k fallbacks reproducing
the incumbent two-stage masking bitwise.

Dispatch mirrors ops/sketch_kernels: ``force_dispatch`` ("kernel" /
"fallback") overrides the backend gate for audits and parity tests, the
``custom_vmap`` guards dispatch the purpose-built batched kernels under
vmap (never JAX's default grid-prepending rule), and every entry has a
bitwise XLA fallback. The select pass's outputs are dense; a caller
that needs the k-long ``(values, indices)`` form pays
:func:`values_indices_from_mask`'s d-long compaction for it
(``ops.topk.topk_values_indices`` only — the server rules take the dense
outputs as they come). ``approx_recall`` refuses the kernel by contract:
``lax.approx_max_k`` is already TPU-native and intentionally inexact,
so there is nothing to bit-agree with (callers gate on
:func:`topk_kernel_ok`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the SAME dispatch machinery the sketch kernels use, and their one
# estimates pass (unsketch_select_pallas streams what it writes)
from commefficient_tpu.ops.sketch_kernels import (LANES, TILE_BLOCKS,
                                                  _estimates_tiles,
                                                  _interpret,
                                                  force_dispatch,
                                                  forced_dispatch,
                                                  kernel_supported)

__all__ = ["topk_kernel_ok", "topk_select_pallas", "fused_true_topk_pallas",
           "unsketch_select_pallas", "values_indices_from_mask",
           "force_dispatch", "forced_dispatch"]

TILE_N = TILE_BLOCKS * LANES          # elements per grid step (8,192)
#: rows of a count pass's block (512 KB of float32). A pass alone at d =
#: 666 962 944 on a v5e, medians of 10: 29.9 ms in 64-row steps with the
#: counts reduced to SMEM scalars every step; 22.4 ms with lane-dense
#: accumulators at 64 rows; 8.6 ms at 512-4 096 rows; 7.3 ms at 1 024 or
#: 2 048 rows with only the blocks that hold a lane >= n masked (PERF.md
#: §6). The count is bound by the vector unit there, not by the step.
COUNT_ROWS = 1024
_NIBBLES = 16                          # candidates per radix round
_SENTINEL = np.int32(-(2 ** 31))      # below every valid score's bits
_I32_MAX = np.int32(2 ** 31 - 1)
#: every kernel here carries a ``name=``: it is the instruction's name in a
#: device trace (an unnamed call inside the radix ``while`` read
#: ``closed_call.N``). The select pass takes its public caller's name.
COUNT_KERNEL_NAME = "radix_count_pallas"


def topk_kernel_ok(approx_recall=None) -> bool:
    """Trace-time dispatch gate for the streaming top-k kernels.

    ``approx_recall`` refuses the kernel unconditionally — the
    ``lax.approx_max_k`` path is already TPU-native and there is no
    exact selection to bit-agree with. Otherwise
    ``force_dispatch("kernel"/"fallback")`` overrides the backend gate
    (audits trace the kernel program on CPU via the interpreter; the
    parity tests and the audit mutation arm force the incumbent chain)."""
    if approx_recall:
        return False
    forced = forced_dispatch()
    if forced == "fallback":
        return False
    if forced == "kernel":
        return True
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# in-kernel tile helpers
# --------------------------------------------------------------------------

def _bits(x):
    """Score bits: ``x*x`` bitcast to int32 (non-negative f32 orders
    identically as signed int32)."""
    return jax.lax.bitcast_convert_type(x * x, jnp.int32)


def _masked_bits(x, i0, n):
    """Score bits for block ``i0`` of ``x.shape[0]`` rows, with padding
    lanes (flat index >= n) forced to the sentinel so they never count
    toward a threshold and never select."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    idx = (i0 * x.shape[0] + rows) * LANES + lanes
    return jnp.where(idx < n, _bits(x), _SENTINEL)


def _source_tile(refs, *, src, batched):
    """The value stream for one tile, per source mode. Returns
    (selection values, extra outputs-to-mask) — for true_topk the extras
    are (v,) so the epilogue can emit the velocity residual too."""
    if src == "resid":
        # the true_topk epilogue streams (err, v) — computed ONCE by the
        # XLA wrapper with the incumbent's exact multi-use expression
        # structure. Recomputing ``g + rho*vv`` in-kernel is NOT
        # bit-safe: the compiler may contract the mul+add into an FMA
        # (observed 1-ulp drift vs the incumbent program on CPU, and a
        # bitcast round-trip barrier gets stripped before contraction),
        # so no mul-then-add ever appears on a kernel data path —
        # ``x*x`` scores and the 0/1 rank matmuls are contraction-proof
        err_ref, v_ref = refs
        err = err_ref[0] if batched else err_ref[...]
        v = v_ref[0] if batched else v_ref[...]
        return err, (v,)
    (vec_ref,) = refs
    return (vec_ref[0] if batched else vec_ref[...]), ()


# --------------------------------------------------------------------------
# pass 1 — counting kernel (one call per radix round)
# --------------------------------------------------------------------------

def _count_kernel(vec_ref, cand_ref, out_ref, acc_ref, *, n, batched):
    axis = 1 if batched else 0
    i = pl.program_id(axis)
    rows = vec_ref.shape[-2]

    # lane-dense counts in VMEM across the sequential grid: zeroed as each
    # (batch row's) first block comes in, folded to the 16 scalars of the
    # SMEM output at its last. A step is elementwise adds of whole vregs,
    # no cross-lane reduction and no SMEM access; a lane's count stays
    # under the buffer's rows / 8 (651 k at d = 667 M)
    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

    def add(bits):
        b3 = bits.reshape(rows // 8, 8, LANES)
        for j in range(_NIBBLES):
            acc_ref[j] = acc_ref[j] + jnp.sum(
                (b3 >= cand_ref[0, j]).astype(jnp.int32), axis=0)

    # only a block that holds a lane >= n (the padding, or past the buffer
    # where the last block overhangs it) pays for the mask
    whole = (i + 1) * (rows * LANES) <= n

    @pl.when(whole)
    def _():
        add(_bits(vec_ref[0] if batched else vec_ref[...]))

    @pl.when(jnp.logical_not(whole))
    def _():
        add(_masked_bits(vec_ref[0] if batched else vec_ref[...], i, n))

    @pl.when(i == pl.num_programs(axis) - 1)
    def _():
        for j in range(_NIBBLES):
            out_ref[0, j] = jnp.sum(acc_ref[j])


def _count_call(vec, cands, *, n, interp, batched=False):
    """Counts of ``bits >= cand`` for 16 candidates over the tiled ``vec``
    (the vector itself, the true_topk error, the sketch server's
    estimates), in blocks of ``COUNT_ROWS`` rows, capped at ``vec``'s; the
    last block may overhang ``vec`` and is masked by flat index."""
    rows = vec.shape[-2]
    blk = min(COUNT_ROWS, rows)
    n_steps = -(-rows // blk)
    kern = partial(_count_kernel, n=n, batched=batched)
    cand_smem = dict(memory_space=pltpu.SMEM)
    acc = [pltpu.VMEM((_NIBBLES, 8, LANES), jnp.int32)]
    if batched:
        B = cands.shape[0]
        return pl.pallas_call(
            kern, grid=(B, n_steps),
            in_specs=[pl.BlockSpec((1, blk, LANES), lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, _NIBBLES), lambda b, i: (b, 0),
                                   **cand_smem)],
            out_specs=pl.BlockSpec((1, _NIBBLES), lambda b, i: (b, 0),
                                   **cand_smem),
            out_shape=jax.ShapeDtypeStruct((B, _NIBBLES), jnp.int32),
            scratch_shapes=acc,
            interpret=interp, name=COUNT_KERNEL_NAME)(vec, cands)
    out = pl.pallas_call(
        kern, grid=(n_steps,),
        in_specs=[pl.BlockSpec((blk, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, _NIBBLES), lambda i: (0, 0),
                               **cand_smem)],
        out_specs=pl.BlockSpec((1, _NIBBLES), lambda i: (0, 0), **cand_smem),
        out_shape=jax.ShapeDtypeStruct((1, _NIBBLES), jnp.int32),
        scratch_shapes=acc,
        interpret=interp,
        name=COUNT_KERNEL_NAME)(vec, cands.reshape(1, _NIBBLES))
    return out.reshape(_NIBBLES)


# --------------------------------------------------------------------------
# radix-select threshold driver (XLA glue around the counting kernel)
# --------------------------------------------------------------------------

def _radix_threshold(count_fn, kk):
    """Exact k-th-largest score bits via 8 rounds of 4-bit refinement.

    ``count_fn(cands)`` maps 16 int32 candidates to counts of
    ``bits >= cand`` over the stream. Each round extends the prefix by
    the largest nibble whose candidate still has >= kk survivors; the
    ``cands >= prefix`` guard excludes signed-overflow candidates
    (round 0's ``8 << 28`` IS INT32_MIN) — the true threshold itself
    always fits, so the correct nibble is never excluded. Returns
    ``(t, n_take)``: the threshold bits and how many ties at t to
    accept (k minus the strictly-greater count). ``kk`` may be traced
    (per-row k support)."""
    js = jnp.arange(_NIBBLES, dtype=jnp.int32)

    def body(rnd, prefix):
        shift = 28 - 4 * rnd
        cands = prefix + (js << shift)
        counts = count_fn(cands)
        ok = (counts >= kk) & (cands >= prefix)
        nib = jnp.max(jnp.where(ok, js, 0))
        return prefix + (nib << shift)

    t = jax.lax.fori_loop(0, 8, body, jnp.int32(0))
    t_plus = t + (t < _I32_MAX).astype(jnp.int32)
    fin = count_fn(jnp.where(js == 1, t_plus, t))
    return t, kk - fin[1]


def _radix_threshold_batched(count_fn, kk):
    """Per-row twin: ``count_fn`` maps (B, 16) candidates to (B, 16)
    counts; ``kk`` is the (B,) per-row k. One counting kernel per round
    covers every row (the 2-D grid walks rows sequentially)."""
    B = kk.shape[0]
    js = jnp.arange(_NIBBLES, dtype=jnp.int32)

    def body(rnd, prefix):
        shift = 28 - 4 * rnd
        cands = prefix[:, None] + (js[None, :] << shift)
        counts = count_fn(cands)
        ok = (counts >= kk[:, None]) & (cands >= prefix[:, None])
        nib = jnp.max(jnp.where(ok, js[None, :], 0), axis=1)
        return prefix + (nib << shift)

    t = jax.lax.fori_loop(0, 8, body, jnp.zeros((B,), jnp.int32))
    t_plus = t + (t < _I32_MAX).astype(jnp.int32)
    fin = count_fn(jnp.where(js[None, :] == 1, t_plus[:, None], t[:, None]))
    return t, kk - fin[:, 1]


# --------------------------------------------------------------------------
# pass 2 — fused select / epilogue kernel
# --------------------------------------------------------------------------

def _tile_select(bits, t, ntake, carry, i0):
    """Selection mask for one tile: everything above threshold, plus
    ties at the threshold in ascending flat-index order until ``ntake``
    are taken — exactly the set stable ``lax.top_k`` keeps. The running
    tie count crosses grid steps in SMEM; the within-tile exclusive rank
    (row-major) is two strict-lower-triangular matmuls over the 0/1 tie
    indicator — exact in f32 (tile counts < 2^24), with the global
    carry kept int32."""
    @pl.when(i0 == 0)
    def _():
        carry[0, 0] = jnp.int32(0)

    c0 = carry[0, 0]
    eq = bits == t
    gt = bits > t
    eqf = eq.astype(jnp.float32)
    rows = eqf.shape[0]
    lane_lt = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
               ).astype(jnp.float32)
    row_lt = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
              ).astype(jnp.float32)
    lane_pre = jnp.dot(eqf, lane_lt, preferred_element_type=jnp.float32)
    row_pre = jnp.dot(row_lt, jnp.sum(eqf, axis=1, keepdims=True),
                      preferred_element_type=jnp.float32)
    rank = c0 + (lane_pre + row_pre).astype(jnp.int32)
    carry[0, 0] = c0 + jnp.sum(eq.astype(jnp.int32))
    return gt | (eq & (rank < ntake))


def _select_kernel(*refs, n, src, batched, with_mask):
    if src == "resid":
        (err_ref, v_ref, t_ref, take_ref,
         upd_ref, nv_ref, ne_ref, carry) = refs
        srcs = (err_ref, v_ref)
    elif with_mask:
        vec_ref, t_ref, take_ref, out_ref, mask_ref, carry = refs
        srcs = (vec_ref,)
    else:
        vec_ref, t_ref, take_ref, out_ref, carry = refs
        srcs = (vec_ref,)
    i0 = pl.program_id(1) if batched else pl.program_id(0)

    vals, extras = _source_tile(srcs, src=src, batched=batched)
    bits = _masked_bits(vals, i0, n)
    sel = _tile_select(bits, t_ref[0, 0], take_ref[0, 0], carry, i0)

    def store(ref, tile):
        if batched:
            ref[0, :, :] = tile
        else:
            ref[:, :] = tile

    if src == "resid":
        (v,) = extras
        err = vals
        upd = jnp.where(sel, err, 0.0)
        # the incumbent masks state on the UPDATE's nonzeroness, not the
        # selection mask: a selected exact zero (or -0.0) keeps its
        # residual — replicated here bit-for-bit
        supp = sel & (upd != 0)
        store(upd_ref, upd)
        store(nv_ref, jnp.where(supp, 0.0, v))
        store(ne_ref, jnp.where(supp, 0.0, err))
    else:
        store(out_ref, jnp.where(sel, vals, 0.0))
        if with_mask:
            store(mask_ref, sel.astype(jnp.int32))


def _select_call(streams, t, take, *, n, n_tiles, interp, src, name,
                 batched=False, with_mask=False, in_place=False):
    """``in_place`` (unbatched grid only) gives the first stream's buffer
    to the first output — the kernel reads tile i before it writes tile
    i, and no tile is read twice: for a caller whose stream is a
    temporary of its own."""
    kern = partial(_select_kernel, n=n, src=src, batched=batched,
                   with_mask=with_mask)
    rows = n_tiles * TILE_BLOCKS
    n_out = 3 if src == "resid" else (2 if with_mask else 1)
    out_dtypes = ([jnp.float32] * 3 if src == "resid"
                  else [jnp.float32, jnp.int32][:n_out])
    smem = dict(memory_space=pltpu.SMEM)
    if batched:
        assert src == "plain"
        B = t.shape[0]
        tile = pl.BlockSpec((1, TILE_BLOCKS, LANES), lambda b, i: (b, i, 0),
                            memory_space=pltpu.VMEM)
        scalar = pl.BlockSpec((1, 1), lambda b, i: (b, 0), **smem)
        outs = pl.pallas_call(
            kern, grid=(B, n_tiles),
            in_specs=[tile] * len(streams) + [scalar, scalar],
            out_specs=[tile] * n_out,
            out_shape=[jax.ShapeDtypeStruct((B, rows, LANES), dt)
                       for dt in out_dtypes],
            scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)],
            interpret=interp,
            name=name)(*streams, t.reshape(B, 1), take.reshape(B, 1))
        return tuple(o.reshape(B, -1)[:, :n] for o in outs)
    tile = pl.BlockSpec((TILE_BLOCKS, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0), **smem)
    outs = pl.pallas_call(
        kern, grid=(n_tiles,),
        in_specs=[tile] * len(streams) + [scalar, scalar],
        out_specs=[tile] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), dt)
                   for dt in out_dtypes],
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)],
        input_output_aliases={0: 0} if in_place else {},
        interpret=interp,
        name=name)(*streams, t.reshape(1, 1), take.reshape(1, 1))
    return tuple(o.reshape(-1)[:n] for o in outs)


# --------------------------------------------------------------------------
# batch guards (multi-operand twins of sketch_kernels._batch_guard)
# --------------------------------------------------------------------------

def _out_flags(out, flag):
    return jax.tree_util.tree_map(lambda _: flag, out)


def _guard2(kernel_call, xla_fallback, batched_call=None):
    """Batch guard for a (vec, kk) entry. A vmapped call dispatches the
    purpose-built 2-D grid ``batched_call`` (per-row block specs and
    carry resets — NOT the default rule's grid-prepend); an unbatched
    ``kk`` is broadcast to the batch. Nested vmap — the batched entry is
    itself guarded — maps the XLA fallback instead of mis-gridding."""
    run = jax.custom_batching.custom_vmap(kernel_call)

    @run.def_vmap
    def _rule(axis_size, in_batched, x, kk):
        xb, kb = in_batched
        if not xb and not kb:
            out = xla_fallback(x, kk)
            return out, _out_flags(out, False)
        kkb = kk if kb else jnp.broadcast_to(kk, (axis_size,))
        if not xb:
            out = jax.vmap(lambda kk_: xla_fallback(x, kk_))(kkb)
            return out, _out_flags(out, True)
        if batched_call is None:
            out = jax.vmap(xla_fallback)(x, kkb)
            return out, _out_flags(out, True)
        guarded = _guard2(batched_call,
                          lambda xs, ks: jax.vmap(xla_fallback)(xs, ks))
        out = guarded(x, kkb)
        return out, _out_flags(out, True)

    return run


def _guard_fallback_only(kernel_call, xla_fallback):
    """Batch guard for entries with no batched kernel (the fused server
    epilogues run on the unbatched server state): any batching maps the
    bitwise XLA fallback, with unbatched operands broadcast."""
    run = jax.custom_batching.custom_vmap(kernel_call)

    @run.def_vmap
    def _rule(axis_size, in_batched, *args):
        if not any(in_batched):
            out = kernel_call(*args)
            return out, _out_flags(out, False)
        full = [a if b else
                jnp.broadcast_to(a[None], (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = jax.vmap(xla_fallback)(*full)
        return out, _out_flags(out, True)

    return run


# --------------------------------------------------------------------------
# bitwise XLA fallbacks (the incumbent programs, verbatim)
# --------------------------------------------------------------------------

def _mask_fallback(vec, kk, k, with_mask=False):
    """The incumbent masked top-k with a traced valid count: stable
    ``lax.top_k`` over the squares, keep the first ``kk`` of the k
    selected slots. At ``kk == k`` this IS ops/topk._topk_1d bitwise;
    for ``kk < k`` the kept set is the length-kk prefix of the stable
    selection order — the same set the radix kernel takes."""
    sq = vec * vec
    _, idx = jax.lax.top_k(sq, k)
    keep = jnp.arange(k) < kk
    mask = jnp.zeros(vec.shape, dtype=bool).at[idx].set(keep)
    masked = jnp.where(mask, vec, 0)
    if with_mask:
        return masked, mask.astype(jnp.int32)
    return masked


def _fused_true_topk_fallback(g, vvel, verr, *, k, rho):
    """The incumbent federated/server._true_topk chain, verbatim — the
    B side of the A/B and the audit's re-materialized mutation arm."""
    v = g + rho * vvel
    err = verr + v
    update = _mask_fallback(err, jnp.int32(k), k)
    support = update != 0
    return (update, jnp.where(support, 0.0, v),
            jnp.where(support, 0.0, err))


# --------------------------------------------------------------------------
# public entries
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "with_mask", "interpret"))
def topk_select_pallas(vec, kk, *, k, with_mask=False, interpret=False):
    """Dense masked top-``kk`` of a 1-D ``vec`` (2-D under vmap), with
    ``kk`` traced (per-row k) and ``k`` the static selection budget
    (``kk <= k``). ``with_mask`` also returns the int32 selection mask
    (selected zeros included) for the values/indices compaction.
    Bitwise-identical to ``_mask_fallback`` — and, at ``kk == k``, to
    ``ops.topk._topk_1d`` — in both dispatch modes."""
    interp = _interpret(interpret)
    kk = jnp.asarray(kk, jnp.int32)

    def kernel_call(v, kk_):
        n = v.shape[0]
        n_tiles = -(-n // TILE_N)
        vp = jnp.pad(v, (0, n_tiles * TILE_N - n)).reshape(
            n_tiles * TILE_BLOCKS, LANES)
        t, ntake = _radix_threshold(
            lambda cands: _count_call(vp, cands, n=n, interp=interp), kk_)
        outs = _select_call((vp,), t, ntake, n=n, n_tiles=n_tiles,
                            interp=interp, src="plain", with_mask=with_mask,
                            name="topk_select_pallas")
        return outs if with_mask else outs[0]

    def fallback(v, kk_):
        return _mask_fallback(v, kk_, k, with_mask=with_mask)

    def batched_call(vs, kks):
        B, n = vs.shape
        n_tiles = -(-n // TILE_N)
        vp = jnp.pad(vs, ((0, 0), (0, n_tiles * TILE_N - n))).reshape(
            B, n_tiles * TILE_BLOCKS, LANES)
        t, ntake = _radix_threshold_batched(
            lambda cands: _count_call(vp, cands, n=n, interp=interp,
                                      batched=True), kks)
        outs = _select_call((vp,), t, ntake, n=n, n_tiles=n_tiles,
                            interp=interp, src="plain", batched=True,
                            with_mask=with_mask, name="topk_select_pallas")
        return outs if with_mask else outs[0]

    return _guard2(kernel_call, fallback, batched_call)(vec, kk)


@partial(jax.jit, static_argnames=("k", "rho", "interpret"))
def fused_true_topk_pallas(gradient, vvelocity, verror, *, k, rho,
                           interpret=False):
    """The fused true_topk server update: momentum, error accumulation,
    exact top-k selection and BOTH error-feedback residuals in two
    streaming passes — returns ``(update, new_Vvelocity, new_Verror)``
    with no d-sized intermediate between them. Bitwise-identical to the
    incumbent federated/server._true_topk chain (the XLA fallback here,
    also what any vmapped call maps)."""
    interp = _interpret(interpret)
    fb = partial(_fused_true_topk_fallback, k=k, rho=rho)

    def kernel_call(g, vv, ve):
        n = g.shape[0]
        n_tiles = -(-n // TILE_N)
        # the momentum read runs HERE, in XLA, with the incumbent's
        # exact multi-use expression structure (v feeds err AND the
        # kernel; err feeds counting AND the epilogue) — in-kernel
        # recomputation is not bit-safe against FMA contraction (see
        # _source_tile). The kernels stream (err, v) and fuse
        # everything downstream: scores, threshold, mask, update and
        # both error-feedback residuals, with no sort, no scatter and
        # no further d-vector.
        v = g + rho * vv
        err = ve + v

        def pad(x):
            return jnp.pad(x, (0, n_tiles * TILE_N - n)).reshape(
                n_tiles * TILE_BLOCKS, LANES)

        errp, vp = pad(err), pad(v)
        t, ntake = _radix_threshold(
            lambda cands: _count_call(errp, cands, n=n, interp=interp),
            jnp.int32(k))
        return _select_call((errp, vp), t, ntake, n=n, n_tiles=n_tiles,
                            interp=interp, src="resid",
                            name="fused_true_topk_pallas")

    return _guard_fallback_only(kernel_call, fb)(gradient, vvelocity,
                                                 verror)


@partial(jax.jit, static_argnames=("cs", "k", "with_mask", "interpret"))
def unsketch_select_pallas(cs, table, *, k, with_mask=False,
                           interpret=False):
    """Fused unsketch + exact top-k for a tiled CountSketch ``cs``: one
    pass of the estimates kernel writes all d estimates (bit-identical to
    ``cs.estimates``) into one d-long buffer in the tiled layout, the
    radix threshold's nine counts stream it, and the select pass
    overwrites it in place with the masked estimates — the update; one
    d-long buffer lives through the call. ``with_mask`` also returns the
    int32 selection mask, a second d-long output, for a caller that
    compacts to (values, indices); the sketch server does not ask.
    Requires ``sketch_kernels.kernel_supported(cs)`` (callers gate). Any
    vmapped call maps the bitwise XLA chain."""
    assert kernel_supported(cs), "unsketch kernel needs a supported sketch"
    interp = _interpret(interpret)
    n = cs.d
    n_tiles = -(-cs.nblocks // TILE_BLOCKS)

    def kernel_call(tab):
        # padded as the kernel wrote it: no slice to d and no pad between
        # the passes (_masked_bits sends lanes >= d to the sentinel)
        est = _estimates_tiles(cs, tab, interp)
        t, ntake = _radix_threshold(
            lambda cands: _count_call(est, cands, n=n, interp=interp),
            jnp.int32(k))
        outs = _select_call((est,), t, ntake, n=n, n_tiles=n_tiles,
                            interp=interp, src="plain", with_mask=with_mask,
                            in_place=True, name="unsketch_select_pallas")
        return outs if with_mask else outs[0]

    def fallback(tab):
        est = cs.estimates(tab, use_kernel=False)
        return _mask_fallback(est, jnp.int32(k), k, with_mask=with_mask)

    return _guard_fallback_only(kernel_call, fallback)(table)


def values_indices_from_mask(masked, mask, k):
    """(values, indices) in the EXACT ``lax.top_k`` return order from a
    dense masked vector + int32 selection mask: compact the <= k selected
    positions (cumsum ranks; OOB slots drop), then a two-key
    ``lax.sort`` on (-score, index) restores descending-score,
    ascending-index-on-ties — the stable top_k order — so downstream
    float summations (``sketch_sparse`` bucket sums, scatter ``.at[]``)
    see bitwise-identical operand order. Unselected slots (when fewer
    than k entries are selected, impossible for exact k) pad with
    index 0 / value ``masked[0]``-free zeros exactly like the scatter
    default.

    The cost is the compaction, not the k-long sort: the cumsum and the
    scatter are d long (38 ms a round at d=6.5M, k=50k on a v5e; PERF.md,
    PR 34). Not on the sketch server's path (federated/server._sketched
    takes ``unsketch_select_pallas``'s dense output as the update); here
    for ``ops.topk.topk_values_indices`` under a dispatched kernel, whose
    callers want the k-long form."""
    d = masked.shape[0]
    sel = mask != 0
    pos = jnp.cumsum(mask) - 1
    scatter_pos = jnp.where(sel, pos, k)
    idxs = jnp.zeros((k,), jnp.int32).at[scatter_pos].set(
        jnp.arange(d, dtype=jnp.int32), mode="drop")
    vals = masked[idxs]
    neg_score = jnp.negative(vals * vals)
    _, idxs, vals = jax.lax.sort((neg_score, idxs, vals), num_keys=2)
    return vals, idxs
