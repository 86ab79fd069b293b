"""Pallas TPU kernels for the CountSketch hot paths, batch-native.

Round 3 measured the sketched round's remaining cost in the sketch
pipeline, not the model (docs/ROOFLINE.md): at d=6.5M the estimate-all
step (windowed gather + sign + median over rows) costs ~12 ms via the
XLA "permuted-copies" formulation, which materializes all 128 XOR-lane
permutations of each table row (L * c_eff floats per row of HBM traffic)
to avoid scalar gathers. This kernel removes that intermediate entirely:

* the whole (r, c_eff) table is VMEM-resident (10 MB at the reference's
  5x500k config — checked against a budget before selecting the kernel);
* a scalar loop per 64-block tile dynamic-slices each block's 128-float
  window straight out of VMEM (row-granular reads — the design point of
  the tiled scheme, ops/countsketch.py). The loop computes no hash: the
  window bases of every (row, block) are hashed once, vectorised, by
  ``window_bases`` (plain jax.numpy over an iota inside the jitted round;
  they depend on the sketch's coefficients and the block id only — not on
  the data, the round, or which pass asks) and reach the loop as a second
  operand blocked into SMEM. Per window it is read, shift, access, the
  tile's 64 steps unrolled when the kernel is lowered. Hashing inside
  the loop (a multiply-add, the murmur finaliser and a 32-bit remainder
  on a scalar unit with no divider, 320 times a tile) was a third to two
  fifths of a tile: PERF.md §6, PR 38;
* the XOR lane permutation is one lane gather a vreg and row
  (``_lane_xor``: ``tpu.dynamic_gather`` on the chip), where the XLA path
  runs a 7-step butterfly of lane rolls; with the sign multiply and the
  r=3/5 min-max median network it stays in registers;
* the only HBM traffic is the (d,) output write.

Round 8 made both kernels BATCH-NATIVE: under ``vmap`` the custom_vmap
rule (``_batch_guard``) dispatches a 2-D grid ``(batch, n_tiles)``
variant with per-row block specs instead of abandoning the kernel, so
the vmapped call sites — the per-worker transmit (federated/client.py)
and the sketched client-state codec (federated/client_store.py) — run
on the kernel too. Grid steps execute sequentially with the LAST axis
fastest, so all of a batch row's tiles run back-to-back before the next
row's: per row the accumulation order is identical to the unbatched
kernel, and the VMEM budget is per-row (one table block + the tile
temporaries are resident at a time), unchanged by the batch width.

Bit-exactness: gather + multiply + min/max contain no reassociable
summation, and the scatter direction hits each window in ascending
block order in both formulations, so kernel output is BIT-IDENTICAL to
``CountSketch.estimates`` / ``sketch_range`` — per batch row too
(asserted in tests/test_sketch_kernels.py via interpret mode, and cheap
to re-assert on-device).
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the SAME hash finalizer and median networks the XLA paths use — plain
# jnp elementwise code, legal inside the kernel; importing (not copying)
# them is what makes the bit-identity contract drift-proof
from commefficient_tpu.ops.countsketch import _median_small as _median
from commefficient_tpu.ops.countsketch import _mix

LANES = 128
# blocks (= 8,192 coordinates) per grid step: at the reference 5x500k
# config the table alone is 10 MB of the ~16 MB VMEM, and the vectorized
# phase keeps ~r tile-sized temporaries alive — 256-block tiles measured
# 17.8 MB of scoped VMEM (OOM); 64 keeps the stack under the limit
TILE_BLOCKS = 64
VMEM_TABLE_BUDGET = 10 << 20  # leave headroom under ~16 MB VMEM
# grid steps that share one SMEM block of window bases: 16 x r x 64 = r x
# 1024 words, a whole number of the 1024-word tiles a flat int32 array is
# laid out in
BASE_TILES = 16

_U = jnp.uint32

#: trace-time dispatch override — see :func:`force_dispatch`
_FORCED = None


def forced_dispatch():
    """Current dispatch override: "kernel", "fallback", or None."""
    return _FORCED


@contextmanager
def force_dispatch(mode):
    """Force CountSketch kernel dispatch while tracing/driving a program.

    ``mode="kernel"`` makes ``CountSketch._kernel_ok`` ignore the backend
    gate (the entry points below run via the Pallas interpreter off-TPU),
    so the kernel program is traceable and executable on the CPU tier-1 —
    this is how the ``sketch_batched`` graft-audit target traces the
    production kernel dispatch without a chip. ``mode="fallback"`` forces
    the XLA formulation everywhere — the audit's mutation, and the B side
    of the forced-dispatch parity tests. ``mode=None`` restores backend-based
    dispatch.

    Clears the jit caches on entry AND exit: the override changes what a
    call with identical shapes and statics traces to, and the inner
    jitted CountSketch methods key their caches on (shapes, statics)
    only — a cached program from the other mode must not leak across the
    boundary.
    """
    global _FORCED
    if mode not in (None, "kernel", "fallback"):
        raise ValueError(f"mode must be kernel|fallback|None, got {mode!r}")
    prev = _FORCED
    jax.clear_caches()
    _FORCED = mode
    try:
        yield
    finally:
        _FORCED = prev
        jax.clear_caches()


def _block_hash(coeffs_row, blk):
    """(base, lanemask) for block ids ``blk`` — countsketch._block_hashes
    term-for-term (one copy per concept; both kernels share it)."""
    h5, h6 = _U(coeffs_row[4]), _U(coeffs_row[5])
    mb = _mix(h6 * blk + h5)
    return mb, _mix(mb ^ h5) & _U(LANES - 1)


def _signs(coeffs_row, idx):
    """±1 signs for coordinate ids ``idx`` — countsketch._row_signs."""
    h1, h2, h3, h4 = (_U(c) for c in coeffs_row[:4])
    acc = h1 * idx + h2
    acc = acc * idx + h3
    acc = acc * idx + h4
    return (1 - 2 * (_mix(acc) & _U(1)).astype(jnp.int32)
            ).astype(jnp.float32)


def _lane_xor(x, lane, lanemask):
    """y[b, l] = x[b, l ^ lanemask[b, l]] — countsketch._permute_xor's
    permutation as one lane gather (every index lies in [0, 128))."""
    return jnp.take_along_axis(x, (lane ^ lanemask).astype(jnp.int32),
                               axis=1, mode="promise_in_bounds")


@partial(jax.jit, static_argnames=("cs", "n_tiles", "block_offset"))
def window_bases(cs, n_tiles: int, block_offset: int = 0):
    """Window bases of every block of an ``n_tiles`` grid and every row,
    as the kernels' SMEM operand: flat int32, ``[pair of tiles][row][the
    pair's 128 blocks]``, the grid padded to whole SMEM blocks of
    ``BASE_TILES`` tiles. ``CountSketch._block_hashes`` over an iota — the
    one copy of the hash — in one lane-dense elementwise pass (104 MB at
    d = 667 M, 1 MB at 6.57 M). A function of (coefficients, block id)
    alone: every pass of a round over the same grid asks for the same
    array, and XLA's CSE keeps one. ``block_offset`` shifts the block ids
    hashed, as sketch_range's bucket offset does."""
    rows = -(-n_tiles // BASE_TILES) * (BASE_TILES // 2) * cs.r
    m = jax.lax.broadcasted_iota(_U, (rows, LANES), 0)
    blk = (_U(block_offset) + (m // _U(cs.r)) * _U(LANES)
           + jax.lax.broadcasted_iota(_U, (rows, LANES), 1))
    base, _ = cs._block_hashes(m % _U(cs.r), blk)
    return base.astype(jnp.int32).reshape(-1)


def _bases_spec(cs, batched):
    """The bases operand's block: one SMEM block serves ``BASE_TILES``
    consecutive grid steps (the copy is issued when the block index
    changes, double-buffered); every batch row hashes alike, so the 2-D
    grid's index map ignores the batch index."""
    return pl.BlockSpec(
        (BASE_TILES * cs.r * TILE_BLOCKS,),
        (lambda b, i: (i // BASE_TILES,)) if batched
        else (lambda i: (i // BASE_TILES,)),
        memory_space=pltpu.SMEM)


def _for_each_block(bases_ref, i0, r, batched, unrolled, visit):
    """The scalar phase both kernels share: ``visit(i, window)`` for each
    block ``i`` of tile ``i0``, ascending, where ``window(row)`` indexes the
    block's (1, 128) window in row ``row`` of the table ref (behind the
    length-1 batch dim where ``batched``), its base read from the tile's
    part of the SMEM block — no hash, no remainder. On the chip the 64
    steps are unrolled when the kernel is lowered (``unrolled``): with no
    loop around the 320 accesses the scheduler overlaps them with each
    other and with the vector phase (PERF.md §6, PR 38: 3.1 us a tile with
    8 blocks a loop step, 2.5 with none). The Pallas interpreter keeps the
    loop: unrolled, XLA:CPU would compile 64 copies of the body."""
    t = i0 % BASE_TILES
    off = (t // 2) * (r * LANES) + (t % 2) * TILE_BLOCKS

    def block(i, carry):
        def window(row):
            base = bases_ref[off + row * LANES + i]
            at = (pl.ds(row, 1),
                  pl.ds(pl.multiple_of(base * LANES, LANES), LANES))
            return (0, *at) if batched else at

        visit(i, window)
        return carry

    jax.lax.fori_loop(0, TILE_BLOCKS, block, 0, unroll=unrolled)


def _batch_guard(kernel_call, xla_fallback, batched_call=None):
    """Batch-aware dispatch for a single-operand Pallas entry point.

    JAX's default pallas_call batching rule prepends the batch axis to
    the GRID, so under ``vmap`` ``pl.program_id(0)`` becomes the batch
    index: the tiling — and the sketch kernel's step-0 accumulator init —
    would be silently wrong (the review-r4 hazard). This ``custom_vmap``
    overrides that rule: a batched call dispatches ``batched_call``, the
    purpose-built 2-D grid ``(batch, n_tiles)`` kernel whose block specs
    and init gate are batch-row-aware — NOT the default rule's mis-grid.
    The XLA fallback remains for the cases the batched kernel does not
    cover: ``batched_call=None`` (caller decided the shape is
    unsupported/over-budget), and NESTED vmap — the batched entry is
    itself guarded, so a second batching level maps the doubly-vmapped
    XLA formulation instead of mis-gridding the 2-D kernel. Unbatched
    calls are untouched.
    """
    run = jax.custom_batching.custom_vmap(kernel_call)

    @run.def_vmap
    def _rule(axis_size, in_batched, x):
        del axis_size
        (x_batched,) = in_batched
        if not x_batched:
            return xla_fallback(x), False
        if batched_call is None:
            return jax.vmap(xla_fallback)(x), True
        guarded = _batch_guard(batched_call,
                               lambda xs: jax.vmap(xla_fallback)(xs))
        return guarded(x), True

    return run


def _interpret(flag: bool) -> bool:
    """Whether a kernel entry point runs the Pallas interpreter: only on
    the explicit ``interpret=True`` argument, or under
    ``force_dispatch("kernel")`` off-TPU (CPU tests and audits). Never
    chosen on a TPU backend, and never a silent default: an unforced
    kernel call off-TPU fails to lower instead of quietly interpreting."""
    return bool(flag) or (_FORCED == "kernel"
                          and jax.default_backend() != "tpu")


def _batched_params(cs):
    """Compiler params of the 2-D grid (batch, n_tiles) calls. Their
    per-row table block changes with the batch index, so the pipeline
    double-buffers it: 2 x table on top of what the unbatched call fits
    into the default 16 MiB of scoped VMEM (the chip's compiler refused
    the 5x500k sketch at batch 8 for exactly that: 30.74M against the
    16M limit). ``kernel_supported`` caps the table at 10 MiB, so this
    stays under 36 MiB of a v5e's 128 MiB."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=2 * cs.r * cs.c_eff * 4 + (16 << 20))


def _estimates_kernel(table_ref, bases_ref, out_ref, win, *, coeffs, r,
                      batched, unrolled):
    # batched: 2-D grid (batch, n_tiles); program_id(0) is the batch row
    # (blocks carry a leading length-1 batch dim), program_id(1) the tile
    i0 = pl.program_id(1) if batched else pl.program_id(0)

    # phase 1 — scalar window gathers: each block's window base comes from
    # SMEM (window_bases); the 128-float window is one VMEM dynamic slice
    def gather(i, window):
        for row in range(r):
            win[row, pl.ds(i, 1), :] = table_ref[window(row)]

    _for_each_block(bases_ref, i0, r, batched, unrolled, gather)

    # phase 2 — vectorized permute + sign + median over rows
    blk_vec = (_U(i0) * _U(TILE_BLOCKS)
               + jax.lax.broadcasted_iota(_U, (TILE_BLOCKS, LANES), 0))
    lane = jax.lax.broadcasted_iota(_U, (TILE_BLOCKS, LANES), 1)
    idx = blk_vec * _U(LANES) + lane
    per_row = []
    for row in range(r):
        _, lanemask = _block_hash(coeffs[row], blk_vec)
        signs = _signs(coeffs[row], idx)
        per_row.append(_lane_xor(win[row], lane, lanemask) * signs)
    if batched:
        out_ref[0, :, :] = _median(per_row)
    else:
        out_ref[:, :] = _median(per_row)


def _estimates_tiles(cs, table, interp):
    """One pass of the estimates kernel over ``table``: every coordinate's
    estimate in the tiled layout the streaming kernels read,
    ``(n_tiles * TILE_BLOCKS, LANES)`` float32. Rows past ``cs.d`` hold
    the estimates of block ids no coordinate has (finite garbage):
    ``estimates_pallas`` slices them off, and
    ``topk_kernels.unsketch_select_pallas`` streams the array as it is
    (its score bits send lanes ``>= d`` to the sentinel)."""
    n_tiles = -(-cs.nblocks // TILE_BLOCKS)
    return pl.pallas_call(
        partial(_estimates_kernel, coeffs=cs.coeffs, r=cs.r, batched=False,
                unrolled=not interp),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((cs.r, cs.c_eff), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
                  _bases_spec(cs, batched=False)],
        out_specs=pl.BlockSpec((TILE_BLOCKS, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_tiles * TILE_BLOCKS, LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((cs.r, TILE_BLOCKS, LANES),
                                   jnp.float32)],
        interpret=interp, name="estimates_pallas",
    )(table, window_bases(cs, n_tiles))


@partial(jax.jit, static_argnames=("cs", "interpret"))
def estimates_pallas(cs, table, interpret: bool = False):
    """All-coordinate estimates for a tiled-scheme CountSketch ``cs``.

    Drop-in for ``cs.estimates(table)`` when ``kernel_supported(cs)``;
    ``interpret=True`` runs the Pallas interpreter (see ``_interpret``).
    Batch-native (_batch_guard): a vmapped call dispatches the 2-D grid
    (batch, n_tiles) kernel — per-row table blocks, bit-identical per
    row; nested vmap maps the XLA ``cs.estimates`` instead."""
    interp = _interpret(interpret)
    n_tiles = -(-cs.nblocks // TILE_BLOCKS)

    def kernel_call(tab):
        return _estimates_tiles(cs, tab, interp).reshape(-1)[:cs.d]

    def batched_call(tabs):
        B = tabs.shape[0]
        out = pl.pallas_call(
            partial(_estimates_kernel, coeffs=cs.coeffs, r=cs.r,
                    batched=True, unrolled=not interp),
            grid=(B, n_tiles),
            in_specs=[pl.BlockSpec((1, cs.r, cs.c_eff),
                                   lambda b, i: (b, 0, 0),
                                   memory_space=pltpu.VMEM),
                      _bases_spec(cs, batched=True)],
            out_specs=pl.BlockSpec((1, TILE_BLOCKS, LANES),
                                   lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                (B, n_tiles * TILE_BLOCKS, LANES), jnp.float32),
            scratch_shapes=[pltpu.VMEM((cs.r, TILE_BLOCKS, LANES),
                                       jnp.float32)],
            compiler_params=_batched_params(cs),
            interpret=interp, name="estimates_pallas",
        )(tabs, window_bases(cs, n_tiles))
        return out.reshape(B, -1)[:, :cs.d]

    return _batch_guard(kernel_call,
                        lambda tab: cs.estimates(tab, use_kernel=False),
                        batched_call if kernel_supported(cs) else None
                        )(table)


def kernel_supported(cs) -> bool:
    """The kernels handle the tiled scheme with an r=1/3/5 median network
    and a table that fits the VMEM residency budget. The budget is
    PER-ROW and therefore batch-independent: the batched 2-D grid keeps
    one batch row's table block plus the (r, TILE_BLOCKS, LANES) tile
    temporaries resident per grid step, exactly like the unbatched
    grid."""
    return (cs.scheme == "tiled" and cs.r in (1, 3, 5)
            and cs.r * cs.c_eff * 4 <= VMEM_TABLE_BUDGET)


def _sketch_kernel(vec_ref, bases_ref, out_ref, win, *, coeffs, r,
                   block_offset, batched, unrolled):
    """Scatter direction: TPU grid steps run SEQUENTIALLY on a core, and
    the output block's index_map is constant in the tile axis, so
    ``out_ref`` itself is the VMEM-resident accumulator across steps (a
    separate scratch table doubled VMEM and OOM'd at the 5x500k config) —
    the per-window '+=' needs no atomics. Additions hit each window in
    ascending block order — the same order as the XLA paths (segment_sum
    groups by base in block order; the XOR permutation guarantees one
    value per bucket per block), so the result is bit-identical.
    ``batched``: 2-D grid (batch, n_tiles), the LAST axis fastest — a
    row's tiles run back-to-back, so the zero-init is gated on the TILE
    index (``pl.program_id(1) == 0``, once per batch row as its output
    block comes into residency) and per row the accumulation order is
    exactly the unbatched kernel's. ``block_offset`` shifts the GLOBAL
    block ids the hashes key on: the grid covers one transmit bucket's
    blocks (countsketch.sketch_range) while every contribution still
    lands in the cell the monolithic sketch would put it."""
    i0 = pl.program_id(1) if batched else pl.program_id(0)

    @pl.when(i0 == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # vectorized: sign-multiply + XOR-permute the tile (the XOR permutation
    # is an involution: the same permute serves scatter and gather)
    blk_vec = (_U(block_offset) + _U(i0) * _U(TILE_BLOCKS)
               + jax.lax.broadcasted_iota(_U, (TILE_BLOCKS, LANES), 0))
    lane = jax.lax.broadcasted_iota(_U, (TILE_BLOCKS, LANES), 1)
    idx = blk_vec * _U(LANES) + lane
    x = vec_ref[0, :, :] if batched else vec_ref[:, :]
    for row in range(r):
        _, lanemask = _block_hash(coeffs[row], blk_vec)
        win[row, :, :] = _lane_xor(x * _signs(coeffs[row], idx), lane,
                                   lanemask)

    # scalar: accumulate each block's window at its base, read from SMEM
    # (window_bases: hashed there with the same block_offset). A block's r
    # windows lie in r different table rows, so they are loaded together,
    # added, and stored together: only consecutive BLOCKS wait on each
    # other's stores (two blocks of a row may share a window)
    def scatter(i, window):
        at = [window(row) for row in range(r)]
        sums = [out_ref[at[row]] + win[row, pl.ds(i, 1), :]
                for row in range(r)]
        for row in range(r):
            out_ref[at[row]] = sums[row]

    _for_each_block(bases_ref, i0, r, batched, unrolled, scatter)


@partial(jax.jit, static_argnames=("cs", "interpret", "block_offset"))
def sketch_vec_pallas(cs, vec, interpret: bool = False,
                      block_offset: int = 0):
    """Drop-in for ``cs.sketch_vec(vec)`` when ``kernel_supported(cs)``.

    ``vec`` may be a bucket slice shorter than d; ``block_offset`` is its
    first coordinate's block id (countsketch.sketch_range dispatches
    ``offset // 128``). Batch-native (_batch_guard): a vmapped call
    dispatches the 2-D grid (batch, n_tiles) kernel — per-row input and
    accumulator blocks, zero-init on each row's first tile — bit-identical
    per row to the unbatched kernel and to the XLA formulation; nested
    vmap maps the XLA sketch_range instead of mis-gridding."""
    n = vec.shape[0]
    if n == 0:
        # a zero-length slice sketches to the zero table (the XLA paths'
        # empty segment_sum); a 0-tile grid would leave the accumulator
        # uninitialized, so never reach the kernel
        return jnp.zeros((cs.r, cs.c_eff), jnp.float32)
    interp = _interpret(interpret)
    n_blocks = -(-n // LANES)
    n_tiles = -(-n_blocks // TILE_BLOCKS)

    def _padded(v):
        # zero-pad so tail-tile blocks contribute exact zeros to their
        # windows
        return jnp.pad(v, (0, n_tiles * TILE_BLOCKS * LANES - n)
                       ).reshape(n_tiles * TILE_BLOCKS, LANES)

    def kernel_call(v):
        return pl.pallas_call(
            partial(_sketch_kernel, coeffs=cs.coeffs, r=cs.r,
                    block_offset=block_offset, batched=False,
                    unrolled=not interp),
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((TILE_BLOCKS, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
                      _bases_spec(cs, batched=False)],
            out_specs=pl.BlockSpec((cs.r, cs.c_eff), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((cs.r, cs.c_eff), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((cs.r, TILE_BLOCKS, LANES), jnp.float32),
            ],
            interpret=interp, name="sketch_vec_pallas",
        )(_padded(v), window_bases(cs, n_tiles, block_offset))

    def batched_call(vs):
        B = vs.shape[0]
        vp = jax.vmap(_padded)(vs)
        return pl.pallas_call(
            partial(_sketch_kernel, coeffs=cs.coeffs, r=cs.r,
                    block_offset=block_offset, batched=True,
                    unrolled=not interp),
            grid=(B, n_tiles),
            in_specs=[pl.BlockSpec((1, TILE_BLOCKS, LANES),
                                   lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM),
                      _bases_spec(cs, batched=True)],
            out_specs=pl.BlockSpec((1, cs.r, cs.c_eff),
                                   lambda b, i: (b, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, cs.r, cs.c_eff),
                                           jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((cs.r, TILE_BLOCKS, LANES), jnp.float32),
            ],
            compiler_params=_batched_params(cs),
            interpret=interp, name="sketch_vec_pallas",
        )(vp, window_bases(cs, n_tiles, block_offset))

    return _batch_guard(
        kernel_call,
        lambda v: cs.sketch_range(v, block_offset * LANES,
                                  use_kernel=False),
        batched_call if kernel_supported(cs) else None,
    )(vec)
