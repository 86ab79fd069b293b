"""TPU-native CountSketch.

Replaces the external ``csvec`` package the reference depends on (reference
README.md:12; call sites fed_aggregator.py:464-467,583-601 and
fed_worker.py:312-320). API parity:

    csvec.CSVec(d, c, r, numBlocks)   -> CountSketch(d, c, r, seed=...)
    .accumulateVec(vec)               -> table = cs.accumulate_vec(table, vec)
    .accumulateTable(t)               -> table = table + t   (linearity)
    .unSketch(k)                      -> cs.unsketch(table, k)
    .table                            -> the (r, c_eff) array itself
    .zero()                           -> cs.zero_table()
    .l2estimate()                     -> cs.l2estimate(table)

Design differences from csvec (deliberate, TPU-first):

* The sketch is *stateless*: hash coefficients are a small static tuple
  derived from a seed, and every method is a pure function on an
  ``(r, c_eff)`` table. This makes sketches safe to close over in
  jitted/pjitted programs and guarantees every replica of an SPMD program
  uses identical hash functions (the reference gets this via a global
  ``torch.manual_seed(42)`` inside csvec).
* Bucket/sign hashes are computed **on the fly in-trace** with integer
  polynomial hashing mod 2**32 plus murmur-style avalanche mixing, instead
  of materialising (r, d) index tables in memory (csvec's ``numBlocks``
  exists only to shrink those tables; here it is accepted and ignored).
* Two hash schemes:

  - ``scheme='tiled'`` (default) — the TPU-first design. Coordinates are
    grouped into blocks of L=128 (one vector lane tile); block ``b`` hashes
    to a 128-wide *window* of columns, and each coordinate to a lane offset
    within its block's window via a per-(row, block) lane PERMUTATION:

        bucket(i) = base(i // L) * L + (i % L) ^ lanemask(i // L)

    Within-window scatter/gather then become one-hot routing contractions
    over (L, L) tiles — pure vector ops — and the only data-dependent
    memory accesses left are ROW-granular (128 contiguous floats), cutting
    the scalar-bound access count from d to d/128. Measured at d=6.5M,
    c=500k, r=5 on one TPU chip: sketch 196ms -> <10ms, estimate-all
    257ms -> <15ms versus the global scheme below.

    Statistically this is a "blocked" CountSketch with same-block
    separation: the XOR lane permutation makes same-block collisions
    IMPOSSIBLE (for d <= 128 the sketch is lossless), and two coordinates
    of different blocks collide iff their blocks share a window and their
    permuted lanes coincide — probability 1/c_eff, exactly the classic
    per-pair rate. Expected bucket load is unchanged (d/c). Collisions are
    correlated at block-pair granularity (two blocks sharing a window
    collide on all 128 lanes pairwise), which the median over r
    independently-hashed rows absorbs; heavy-hitter recovery and l2
    estimates match the global scheme in the property tests.

  - ``scheme='global'`` — classic CountSketch; every coordinate hashes
    independently into [0, c). One ``segment_sum`` per row (sort-based
    scatter on TPU, scalar-bound); kept for cross-checking and for exact
    column counts.

* ``c_eff``: the tiled scheme pads the column count to a multiple of L
  (500_000 -> 500_096, +0.02%). Communication accounting must charge the
  physical table, so ``FedConfig.upload_floats_per_client`` uses
  ``sketch_cols`` = c_eff.

Hash family: seeded cubic polynomials over uint32 with avalanche mixing
(murmur-style finalizer). uint32 wraparound is well-defined in XLA and int32
units are native on TPU (int64 would be emulated) — so this is both the fast
and the portable choice; determinism across replicas/platforms is what
CountSketch actually needs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128        # TPU vector lane width; tiled window/block size
_CHUNK = 1024      # blocks per routing chunk: bounds the (CHUNK, L, L)
                   # one-hot intermediate at ~67 MB f32 when XLA
                   # materializes it (CPU); fused away on TPU


def pad_cols(c: int) -> int:
    """Physical column count for the tiled scheme: c rounded up to a lane
    tile. The single source of truth for the padding rule (used by both
    CountSketch and FedConfig.sketch_cols)."""
    return -(-int(c) // LANES) * LANES


def _hash_coeffs(seed: int, r: int) -> tuple:
    rng = np.random.RandomState(seed)
    # 6 odd coefficients per row: h1..h4 for the sign polynomial, h5, h6 for
    # the bucket hash. Odd => multiplication is a bijection mod 2**32.
    coeffs = rng.randint(1, 1 << 31, size=(r, 6)).astype(np.uint32) * 2 + 1
    return tuple(tuple(int(x) for x in row) for row in coeffs)


def _mix(x: jax.Array) -> jax.Array:
    """murmur3-style avalanche finalizer over uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _median_small(rows: list) -> jax.Array:
    """Median across a small list of equal-shape arrays.

    ``jnp.median`` sorts, which at (5, 6.5M) measured 258ms on a TPU chip;
    the r=3/r=5 min/max selection networks below are pure VPU elementwise
    ops (~10x faster). Even/other r falls back to the sort."""
    r = len(rows)
    if r == 1:
        return rows[0]
    if r == 3:
        a, b, c = rows
        return jnp.maximum(jnp.minimum(a, b),
                           jnp.minimum(jnp.maximum(a, b), c))
    if r == 5:
        a, b, c, d, e = rows
        f, g = jnp.minimum(a, b), jnp.maximum(a, b)
        h, i = jnp.minimum(c, d), jnp.maximum(c, d)
        j = jnp.maximum(f, h)   # drop the smaller of the two mins
        k = jnp.minimum(g, i)   # drop the larger of the two maxs
        return jnp.maximum(jnp.minimum(j, k),
                           jnp.minimum(jnp.maximum(j, k), e))
    return jnp.median(jnp.stack(rows), axis=0)


def _chunked_route(route, x: jax.Array, off: jax.Array) -> jax.Array:
    """Apply a per-block-tile ``route((n, L) data, (n, L) lanes)`` over B
    blocks, chunked with ``lax.scan`` so the (chunk, L, L) one-hot
    intermediate is bounded where XLA materializes it (CPU); chunking only
    regroups independent per-block tiles, so results are bit-identical for
    any chunk size."""
    B = x.shape[0]
    if B <= _CHUNK:
        return route(x, off)
    nb = -(-B // _CHUNK)
    Bp = nb * _CHUNK
    pad = [(0, Bp - B), (0, 0)]
    xc = jnp.pad(x, pad).reshape(nb, _CHUNK, LANES)
    oc = jnp.pad(off, pad).reshape(nb, _CHUNK, LANES)
    out = jax.lax.scan(lambda c, xs: (c, route(*xs)), 0.0, (xc, oc))[1]
    return out.reshape(Bp, LANES)[:B]


def _permute_xor(x: jax.Array, lanemask: jax.Array) -> jax.Array:
    """y[b, l] = x[b, l ^ lanemask[b]] as a 7-step butterfly of lane rolls.

    XOR by a 7-bit mask decomposes into per-bit swaps of lanes differing in
    that bit; each swap is two cyclic lane rotations blended by the lane's
    own bit, applied only to blocks whose mask has the bit set. 14 rolls +
    14 selects over (B, L) — O(14*d) data movement with NO blowup
    intermediate, unlike one-hot routing whose (chunk, L, L) tensor XLA
    fuses in small programs but materializes inside large ones (observed:
    the fused federated round read/wrote 75 GB more than its components,
    3x the round time). XOR is an involution, so the same function serves
    scatter (values to lanes) and gather (lanes to values)."""
    lanes = jnp.arange(LANES, dtype=jnp.uint32)
    for b in range(7):
        w = 1 << b
        plus = jnp.roll(x, w, axis=1)      # x[l - w]: for lanes with bit b
        minus = jnp.roll(x, -w, axis=1)    # x[l + w]: for lanes without
        swapped = jnp.where(((lanes >> b) & 1).astype(bool)[None, :],
                            plus, minus)
        bit = ((lanemask >> jnp.uint32(b)) & 1).astype(bool)[:, None]
        x = jnp.where(bit, swapped, x)
    return x


def _route_gather(win: jax.Array, off: jax.Array) -> jax.Array:
    """(B, L) windows + (B, L) lane sources -> (B, L) values.

    out[b, l] = win[b, off[b, l]]. One-hot select + reduce, NOT a dot: it
    stays exact f32 (an MXU einsum would round the values to bfloat16 at
    default precision) and fuses on TPU; a take_along_axis would lower to
    a slow general gather there (measured 244ms vs <15ms at B=51319). The
    scatter direction needs no routed twin: ``sketch_vec`` scatters via
    ``_permute_xor`` (the XOR butterfly is an involution, so the same
    permutation serves both directions)."""
    iota = jnp.arange(LANES, dtype=off.dtype)

    def route(w, o):
        onehot = (o[:, :, None] == iota[None, None, :])
        return jnp.sum(jnp.where(onehot, w[:, None, :], 0.0), axis=2)

    return _chunked_route(route, win, off)


class CountSketch:
    """Stateless CountSketch over vectors of length ``d`` into
    ``(r, c_eff)``, where ``c_eff == c`` for the global scheme and c
    rounded up to a multiple of 128 for the tiled scheme."""

    def __init__(self, d: int, c: int, r: int, seed: int = 42,
                 num_blocks: int = 1, scheme: str = "tiled"):
        del num_blocks  # csvec memory knob; hashes here are computed in-trace
        if scheme not in ("tiled", "global"):
            raise ValueError(f"scheme must be 'tiled' or 'global', "
                             f"got {scheme!r}")
        self.d = int(d)
        self.c = int(c)
        self.r = int(r)
        self.seed = int(seed)
        self.scheme = scheme
        self.coeffs = _hash_coeffs(seed, r)
        if scheme == "tiled":
            self.nblocks = -(-self.d // LANES)
            self.d_pad = self.nblocks * LANES
            self.c_eff = pad_cols(self.c)
            self.nwindows = self.c_eff // LANES
        else:
            self.c_eff = self.c

    # hashable/static so instances can be closed over by jitted functions
    def __hash__(self):
        return hash((self.d, self.c, self.r, self.seed, self.scheme))

    def __eq__(self, other):
        return (isinstance(other, CountSketch) and
                (self.d, self.c, self.r, self.seed, self.scheme) ==
                (other.d, other.c, other.r, other.seed, other.scheme))

    # --- hashing ----------------------------------------------------------
    def _row_signs(self, row: int, idx: jax.Array) -> jax.Array:
        """±1 sign per coordinate: mixed cubic polynomial, low bit."""
        h1, h2, h3, h4, _, _ = (jnp.uint32(h) for h in self.coeffs[row])
        i = idx.astype(jnp.uint32)
        acc = h1 * i + h2
        acc = acc * i + h3
        acc = acc * i + h4
        signs = 1 - 2 * (_mix(acc) & jnp.uint32(1)).astype(jnp.int32)
        return signs.astype(jnp.float32)

    def _block_hashes(self, row, blk: jax.Array):
        """(window base, 7-bit lane mask) per block for the tiled scheme.
        Two independent avalanche mixes so base and mask are uncorrelated.
        ``row`` is one row id, or an array of row ids that broadcasts
        against ``blk`` (sketch_kernels.window_bases hashes every row's
        blocks in one elementwise pass): the coefficients are then picked
        by a select a row, not a gather."""
        if isinstance(row, int):
            h5, h6 = (jnp.uint32(h) for h in self.coeffs[row][4:])
        else:
            h5, h6 = (jnp.uint32(h) for h in self.coeffs[0][4:])
            for k in range(1, self.r):
                h5 = jnp.where(row == k, jnp.uint32(self.coeffs[k][4]), h5)
                h6 = jnp.where(row == k, jnp.uint32(self.coeffs[k][5]), h6)
        mb = _mix(h6 * blk + h5)
        base = mb % jnp.uint32(self.nwindows)
        lanemask = _mix(mb ^ h5) & jnp.uint32(LANES - 1)
        return base, lanemask

    def _row_hashes(self, row: int, idx: jax.Array):
        """(signs, buckets) for coordinate indices ``idx`` under row ``row``
        — flat bucket in [0, c_eff) for either scheme."""
        _, _, _, _, h5, h6 = (jnp.uint32(h) for h in self.coeffs[row])
        i = idx.astype(jnp.uint32)
        signs = self._row_signs(row, idx)
        if self.scheme == "global":
            buckets = _mix(h5 * i + h6) % jnp.uint32(self.c)
        else:
            base, lanemask = self._block_hashes(row, i // jnp.uint32(LANES))
            off = (i & jnp.uint32(LANES - 1)) ^ lanemask
            buckets = base * jnp.uint32(LANES) + off
        return signs, buckets.astype(jnp.int32)

    def _row_tiled(self, row: int):
        """Hashes for the dense tiled fast path: per-coordinate signs and
        lane offsets as (nblocks, L), per-block window bases as (nblocks,)."""
        i = jnp.arange(self.d_pad, dtype=jnp.uint32)
        signs = self._row_signs(row, i).reshape(self.nblocks, LANES)
        blk = jnp.arange(self.nblocks, dtype=jnp.uint32)
        base, lanemask = self._block_hashes(row, blk)
        lanes = jnp.arange(LANES, dtype=jnp.uint32)
        off = (lanes[None, :] ^ lanemask[:, None]).astype(jnp.int32)
        return signs, off, base.astype(jnp.int32)

    # --- core ops ---------------------------------------------------------
    def zero_table(self, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros((self.r, self.c_eff), dtype=dtype)

    def _use_routed(self) -> bool:
        """Whether the dense tiled paths should use one-hot lane routing.

        The routed formulation trades a ~128x FLOP increase for eliminating
        element-granular scatter/gather — a huge win on TPU (whose XLA
        scatter/gather is scalar-bound at ~8ns/element; none of the
        XLA-level reformulations — fused single scatter, promise_in_bounds,
        precomputed sorted layout — move it) and a large loss on CPU, where
        scatters are cheap. Because the XOR lane permutation lets each
        block contribute at most ONE value per bucket, both formulations
        sum every bucket in block order: results are BIT-IDENTICAL, so the
        choice is a pure backend performance decision (tested in
        test_countsketch.py)."""
        return jax.default_backend() == "tpu"

    def _kernel_ok(self, use_kernel: bool) -> bool:
        """Pallas-kernel dispatch gate. The kernels are OPT-IN per call
        site (``use_kernel=True``) and BATCH-NATIVE: each public entry is
        wrapped in a ``custom_vmap`` (sketch_kernels._batch_guard) whose
        batching rule dispatches the purpose-built 2-D grid
        ``(batch, n_tiles)`` kernel — per-row block specs, zero-init gated
        on the tile index per batch row — instead of letting JAX's default
        pallas_call batching rule prepend the batch axis to the grid and
        turn ``pl.program_id(0)`` into the batch index (review r4: that
        silently corrupts the tiling and the sketch accumulator's step-0
        init, and is the hazard that kept the per-worker vmap paths off
        the kernel until round 8). So the vmapped call site — the
        per-worker transmit (federated/client.py) — gets the kernel too
        (the sketched client codec keeps the 'global' scheme); the
        XLA fallback remains for NESTED vmap, over-budget shapes, and
        non-TPU backends. ``sketch_kernels.force_dispatch`` overrides the
        backend gate for audits and tests (kernel mode runs the Pallas
        interpreter off-TPU)."""
        if not use_kernel:
            return False
        from commefficient_tpu.ops.sketch_kernels import (
            forced_dispatch, kernel_supported)
        forced = forced_dispatch()
        if forced == "fallback":
            return False
        if not kernel_supported(self):
            return False
        if forced == "kernel":
            return True
        return jax.default_backend() == "tpu"

    @partial(jax.jit, static_argnums=(0, 2))
    def sketch_vec(self, vec: jax.Array,
                   use_kernel: bool = False) -> jax.Array:
        """Sketch a length-d vector into an (r, c_eff) table."""
        return self.sketch_range(vec, 0, use_kernel)

    @partial(jax.jit, static_argnums=(0, 2, 3))
    def sketch_range(self, chunk: jax.Array, offset: int = 0,
                     use_kernel: bool = False) -> jax.Array:
        """Sketch the contiguous slice ``vec[offset : offset+len(chunk)]``
        of a conceptual length-d vector into a full (r, c_eff) table.

        Linearity makes the sketch of a vector the sum of the sketches of
        its slices, so a bucketed transmit (``--grad_buckets``)
        accumulates per-bucket tables into the same table ``sketch_vec``
        builds monolithically. Hashes are keyed by GLOBAL coordinate and
        block ids, so every contribution lands in exactly the cell the
        monolithic path would put it, and within a bucket each window
        still sums in ascending block order (the routed/unrouted
        bit-identity argument, unchanged). Across buckets the per-cell
        sums associate bucket-by-bucket instead of strictly
        block-by-block: equal in exact arithmetic, equal to f32 rounding
        in practice (tests/test_grad_buckets.py pins the tolerance;
        ``offset=0`` with the full vector IS the monolithic path,
        bitwise).

        The tiled scheme requires ``offset`` on a 128-lane block boundary
        — the GradBuckets planner aligns bucket edges for exactly this
        reason.

        Dispatch mirrors ``sketch_vec``: Pallas kernel (offset-aware
        grid; batch-native under vmap — see ``_kernel_ok``) when
        ``use_kernel`` and eligible — measured 16.8 ms vs 24.9 ms for the
        XLA path at d=6.5M, 5x500k (quiet chip) — else the XOR-butterfly
        routed formulation on TPU backends, else the per-coordinate
        segment_sum on CPU/GPU.
        """
        n = chunk.shape[0]
        if offset < 0 or offset + n > self.d:
            raise ValueError(f"slice [{offset}, {offset + n}) outside the "
                             f"sketch's coordinate space [0, {self.d})")
        if self.scheme == "tiled":
            if offset % LANES:
                raise ValueError(
                    f"tiled sketch_range needs a {LANES}-aligned offset, "
                    f"got {offset} (GradBuckets aligns bucket edges)")
            if self._kernel_ok(use_kernel):
                from commefficient_tpu.ops.sketch_kernels import \
                    sketch_vec_pallas
                return sketch_vec_pallas(self, chunk,
                                         block_offset=offset // LANES)
            if self._use_routed():
                nb = -(-n // LANES)
                vp = chunk if n == nb * LANES else \
                    jnp.pad(chunk, (0, nb * LANES - n))
                blk = (jnp.uint32(offset // LANES)
                       + jnp.arange(nb, dtype=jnp.uint32))
                idx = (jnp.uint32(offset)
                       + jnp.arange(nb * LANES, dtype=jnp.uint32))
                rows = []
                for row in range(self.r):
                    signs = self._row_signs(row, idx).reshape(nb, LANES)
                    base, lanemask = self._block_hashes(row, blk)
                    win = _permute_xor(vp.reshape(nb, LANES) * signs,
                                       lanemask)
                    rows.append(jax.ops.segment_sum(
                        win, base.astype(jnp.int32),
                        num_segments=self.nwindows).reshape(-1))
                return jnp.stack(rows)

        idx = offset + jnp.arange(n, dtype=jnp.int32)

        def one_row(row):
            signs, buckets = self._row_hashes(row, idx)
            return jax.ops.segment_sum(signs * chunk, buckets,
                                       num_segments=self.c_eff)

        return jnp.stack([one_row(row) for row in range(self.r)])

    def accumulate_vec(self, table: jax.Array, vec: jax.Array) -> jax.Array:
        return table + self.sketch_vec(vec)

    @partial(jax.jit, static_argnums=0)
    def sketch_sparse(self, values: jax.Array, indices: jax.Array) -> jax.Array:
        """Sketch a k-sparse vector given (values, coordinate indices).

        Equivalent to ``sketch_vec`` of the dense vector (the d-k zeros
        contribute 0.0 to every bucket) up to float32 summation order in
        buckets where several nonzeros collide, at O(r*k) instead of
        O(r*d). The right re-sketch wherever (values, indices) come for
        nothing (``lax.top_k``'s return) and the dense sketch would be
        XLA's: an older tree read 330 ms -> <5 ms for that pair at
        d=6.5M, k=50k on a TPU chip. Where the Pallas kernels dispatch
        the order turns round: the dense kernel pass takes 4.5 ms at that
        shape, and compacting a dense top-k to (values, indices) first
        cost 38 ms, so federated/server._sketched re-sketches the dense
        update there (PERF.md, PR 34). Works for both schemes:
        ``_row_hashes`` yields the same flat buckets the dense paths
        use."""
        idx = indices.astype(jnp.int32)

        def one_row(row):
            signs, buckets = self._row_hashes(row, idx)
            return jax.ops.segment_sum(signs * values, buckets,
                                       num_segments=self.c_eff)

        return jnp.stack([one_row(row) for row in range(self.r)])

    @partial(jax.jit, static_argnums=(0, 2))
    def estimates(self, table: jax.Array,
                  use_kernel: bool = False) -> jax.Array:
        """Median-of-rows unbiased estimates of all d coordinates."""
        if self.scheme == "tiled":
            # Pallas kernel: VMEM-resident table, per-block window slices,
            # in-register permute/sign/median — no permuted-copies
            # intermediate at all. Bit-identical (no reassociable sums;
            # tests/test_sketch_kernels.py); opt-in per call site, and
            # batch-native under vmap (_kernel_ok / _batch_guard). Checked
            # ahead of _use_routed so a forced-kernel audit dispatches it
            # on CPU too (via the Pallas interpreter).
            if self._kernel_ok(use_kernel):
                from commefficient_tpu.ops.sketch_kernels import \
                    estimates_pallas
                return estimates_pallas(self, table)
        if self.scheme == "tiled" and self._use_routed():
            # Permuted-copies gather: materialize all 128 XOR-lane
            # permutations of the row's windows (L * c_eff floats, e.g.
            # 256 MB at c=500k), then each block's estimate is ONE
            # row-gather at index (lanemask, window) — no per-lane routing
            # at all. Work: d + L*c_eff per row instead of the one-hot
            # route's 128*d; measured 433ms -> 51ms (8.5x) for the full
            # 5-row estimate at d=124M on a v5e chip, bit-identical.
            # Guarded by a memory cap: fall back to one-hot routing when
            # the permuted copies would exceed ~1 GB.
            if LANES * self.c_eff <= (1 << 28):
                lanes = jnp.arange(LANES, dtype=jnp.uint32)
                xor_tab = (lanes[None, :] ^ lanes[:, None]).astype(jnp.int32)
                per_row = []
                for row in range(self.r):
                    signs, off, base = self._row_tiled(row)
                    lanemask = off[:, 0]            # off[b, l] = l ^ m_b
                    t3 = table[row].reshape(self.nwindows, LANES)
                    perms = (t3[:, xor_tab]         # (w, m, l) -> (m, w, l)
                             .transpose(1, 0, 2)
                             .reshape(LANES * self.nwindows, LANES))
                    est = perms[lanemask * self.nwindows + base] * signs
                    per_row.append(est.reshape(-1)[:self.d])
                return _median_small(per_row)
            per_row = []
            for row in range(self.r):
                signs, off, base = self._row_tiled(row)
                win = table[row].reshape(self.nwindows, LANES)[base]
                est = _route_gather(win, off) * signs
                per_row.append(est.reshape(-1)[:self.d])
            return _median_small(per_row)

        idx = jnp.arange(self.d, dtype=jnp.int32)
        per_row = []
        for row in range(self.r):
            signs, buckets = self._row_hashes(row, idx)
            per_row.append(table[row, buckets] * signs)
        return _median_small(per_row)

    @partial(jax.jit, static_argnums=(0, 2))
    def sketch_vec_batched(self, vec: jax.Array,
                           use_kernel: bool = False) -> jax.Array:
        """``sketch_vec`` routed through the batch-guard dispatch.

        A singleton vmap over the public entry: under ``use_kernel`` on a
        TPU backend the ``_batch_guard`` custom_vmap batching rule
        dispatches the 2-D grid ``(batch, n_tiles)`` kernel at batch 1
        instead of the 1-D grid kernel — the SAME program the vmapped
        per-worker call sites (federated/client.py, client_store.py)
        compile, so a server/aggregate-side sketch is one program, not a
        second near-identical kernel to keep resident. Off-TPU (and for
        over-budget shapes) the rule maps the XLA fallback, which is
        batch-invariant. Bit-identical to ``sketch_vec`` either way
        (tests/test_sketch_kernels.py pins both arms bitwise)."""
        return jax.vmap(lambda v: self.sketch_vec(v, use_kernel))(
            vec[None])[0]

    @partial(jax.jit, static_argnums=(0, 2))
    def estimates_batched(self, table: jax.Array,
                          use_kernel: bool = False) -> jax.Array:
        """``estimates`` routed through the batch-guard dispatch — the
        singleton-vmap twin of ``sketch_vec_batched`` (same rationale,
        same bitwise contract)."""
        return jax.vmap(lambda t: self.estimates(t, use_kernel))(
            table[None])[0]

    def _fused_unsketch_ok(self, approx_recall, use_kernel: bool) -> bool:
        """Gate for the fused unsketch+top-k kernels (ops/topk_kernels):
        both the sketch kernel (its estimates pass writes the stream) and
        the top-k kernel (exact selection only) must dispatch."""
        from commefficient_tpu.ops.topk_kernels import topk_kernel_ok
        return self._kernel_ok(use_kernel) and topk_kernel_ok(approx_recall)

    @partial(jax.jit, static_argnums=(0, 2, 3, 4))
    def unsketch(self, table: jax.Array, k: int,
                 approx_recall=None, use_kernel: bool = False) -> jax.Array:
        """Recover the top-k coordinates (dense d-vector, zeros elsewhere).

        With the kernels dispatched the estimates are written once, by
        the estimates kernel, into one d-long buffer; the streaming radix
        top-k counts over it and its select pass overwrites it in place
        (ops/topk_kernels.unsketch_select_pallas — bitwise-identical to
        the estimates -> topk chain below, no sort, no selection mask).
        That dense output IS the server's update on the fused arm
        (federated/server._sketched): nothing compacts it to (values,
        indices). ``approx_recall`` selects with ``lax.approx_max_k``
        instead of the exact sort (see ops/topk.py; 5.4x at d=124M,
        k=50k) and refuses the fusion."""
        from commefficient_tpu.ops.topk import topk
        if self._fused_unsketch_ok(approx_recall, use_kernel):
            from commefficient_tpu.ops.topk_kernels import \
                unsketch_select_pallas
            return unsketch_select_pallas(self, table, k=k)
        return topk(self.estimates(table, use_kernel), k, approx_recall)

    @partial(jax.jit, static_argnums=0)
    def l2estimate(self, table: jax.Array) -> jax.Array:
        """Estimate ||vec||_2 as sqrt(median over rows of row sum-of-squares)."""
        return jnp.sqrt(jnp.median(jnp.sum(table * table, axis=1)))
