"""Pallas estimate-all kernel vs the XLA reference path: BIT-IDENTICAL
(gather + multiply + min/max median — no reassociable sums). Runs the
kernel in interpret mode on CPU; on a TPU backend the same function runs
compiled (countsketch.estimates selects it there)."""

import jax
import numpy as np
import pytest

from commefficient_tpu.analysis.walker import iter_eqns
from commefficient_tpu.ops.countsketch import CountSketch, _permute_xor
from commefficient_tpu.ops.sketch_kernels import (BASE_TILES, LANES,
                                                 TILE_BLOCKS, _lane_xor,
                                                 estimates_pallas,
                                                 kernel_supported,
                                                 sketch_vec_pallas,
                                                 window_bases)


# 300 000 coordinates are 37 tiles: three SMEM blocks of window bases
@pytest.mark.parametrize("d,c,r", [(40_000, 3_000, 5), (9_999, 1_111, 3),
                                   (128, 256, 1), (300_000, 3_000, 5)])
def test_kernel_estimates_bit_identical(d, c, r):
    cs = CountSketch(d=d, c=c, r=r, seed=7, scheme="tiled")
    assert kernel_supported(cs)
    rng = np.random.RandomState(0)
    vec = np.zeros(d, np.float32)
    hot = rng.choice(d, 50, replace=False)
    vec[hot] = rng.randn(50).astype(np.float32) * 10
    table = cs.sketch_vec(vec)
    ref = np.asarray(cs.estimates(table))
    ker = np.asarray(estimates_pallas(cs, table, interpret=True))
    np.testing.assert_array_equal(ker, ref)


def test_kernel_recovers_heavy_hitters():
    d, k = 30_000, 20
    cs = CountSketch(d=d, c=4_000, r=5, seed=3, scheme="tiled")
    rng = np.random.RandomState(1)
    vec = np.zeros(d, np.float32)
    hot = rng.choice(d, k, replace=False)
    vec[hot] = (rng.randn(k).astype(np.float32) + 3) * 5
    est = np.asarray(estimates_pallas(cs, cs.sketch_vec(vec),
                                      interpret=True))
    top = np.argsort(-np.abs(est))[:k]
    assert len(set(top) & set(hot)) >= k - 1


@pytest.mark.parametrize("d,c,r", [(40_000, 3_000, 5), (9_999, 1_111, 3),
                                   (300_000, 3_000, 5), (150_000, 900, 1)])
def test_sketch_kernel_bit_identical(d, c, r):
    cs = CountSketch(d=d, c=c, r=r, seed=5, scheme="tiled")
    rng = np.random.RandomState(2)
    vec = rng.randn(d).astype(np.float32)
    ref = np.asarray(cs.sketch_vec(vec))
    ker = np.asarray(sketch_vec_pallas(cs, jax.numpy.asarray(vec),
                                       interpret=True))
    np.testing.assert_array_equal(ker, ref)


def test_kernel_supported_gate():
    assert not kernel_supported(
        CountSketch(d=1000, c=100, r=5, scheme="global"))
    assert not kernel_supported(CountSketch(d=1000, c=100, r=4))
    # a table over the VMEM budget must fall back
    assert not kernel_supported(CountSketch(d=10_000_000, c=2_000_000, r=5))


@pytest.mark.parametrize("d,n_max,offset_blocks", [
    (9_999, 4_000, 0), (9_999, 4_000, 1), (9_999, 4_000, 7),
    # 19 tiles from block 1 000 on: the offset shifts the bases the helper
    # hashes over two SMEM blocks, and the chunk ends inside a tile
    (300_000, 150_001, 1_000)])
def test_sketch_kernel_offset_grid_bit_identical(d, n_max, offset_blocks):
    """Bucketed dispatch: the kernel sketches a chunk at a non-zero block
    offset (countsketch.sketch_range) and must land every contribution
    in exactly the cell the monolithic XLA path would — the hashes key
    on GLOBAL block/coordinate ids, shifted inside the grid."""
    c, r = 1_111, 3
    cs = CountSketch(d=d, c=c, r=r, seed=5, scheme="tiled")
    rng = np.random.RandomState(4)
    off = offset_blocks * 128
    n = min(n_max, d - off)
    chunk = rng.randn(n).astype(np.float32)
    ref = np.asarray(cs.sketch_range(chunk, off))
    ker = np.asarray(sketch_vec_pallas(cs, jax.numpy.asarray(chunk),
                                       interpret=True,
                                       block_offset=offset_blocks))
    np.testing.assert_array_equal(ker, ref)


@pytest.mark.parametrize("r", [1, 3, 5])
@pytest.mark.parametrize("n_tiles,block_offset", [(3, 0), (21, 0),
                                                  (21, 1_000)])
def test_window_bases_are_the_block_hashes(r, n_tiles, block_offset):
    """The kernels' SMEM operand holds, for every row and every block id
    of the grid (tile edges, SMEM block edges and a bucket's offset
    included), ``CountSketch._block_hashes(row, blk)[0]`` — laid out
    [pair of tiles][row][the pair's 128 blocks], the grid padded to whole
    SMEM blocks."""
    cs = CountSketch(d=500_000, c=3_000, r=r, seed=13, scheme="tiled")
    bases = np.asarray(window_bases(cs, n_tiles, block_offset))
    padded = -(-n_tiles // BASE_TILES) * BASE_TILES
    assert bases.dtype == np.int32
    assert bases.shape == (padded * r * TILE_BLOCKS,)
    bases = bases.reshape(padded // 2, r, 2 * TILE_BLOCKS)
    assert 2 * TILE_BLOCKS == LANES
    blk = block_offset + jax.numpy.arange(padded * TILE_BLOCKS,
                                          dtype=jax.numpy.uint32)
    for row in range(r):
        want = np.asarray(cs._block_hashes(row, blk)[0])
        np.testing.assert_array_equal(bases[:, row, :].reshape(-1), want)
    assert 0 <= bases.min() and bases.max() < cs.nwindows


def test_lane_gather_is_the_xor_permutation_for_every_mask():
    """The kernels' lane gather against the XLA path's butterfly: row b of
    a (128, 128) tile of distinct floats permuted by lane mask b, so all
    128 masks are checked, bit for bit."""
    x = jax.numpy.asarray(
        np.random.RandomState(3).permutation(LANES * LANES)
        .astype(np.float32).reshape(LANES, LANES) - 8_000.5)
    masks = jax.numpy.arange(LANES, dtype=jax.numpy.uint32)
    lane = jax.lax.broadcasted_iota(jax.numpy.uint32, x.shape, 1)
    got = jax.jit(_lane_xor)(x, lane, jax.numpy.broadcast_to(
        masks[:, None], x.shape))
    want = jax.jit(_permute_xor)(x, masks)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(got)[5], np.asarray(x)[5][np.arange(LANES) ^ 5])


@pytest.mark.parametrize("r", [1, 3, 5])
@pytest.mark.parametrize("kernel", ["estimates", "sketch_vec"])
@pytest.mark.parametrize("batched", [False, True],
                         ids=["grid_1d", "grid_2d"])
def test_hash_kernel_body_permutes_by_one_gather_a_row(r, kernel, batched):
    """The kernel arm's body, as traced: r lane gathers (one a table row)
    and no lane concatenate — a butterfly of lane rolls (slices joined by
    concatenate) would be back in the vector phase."""
    cs = CountSketch(d=20_000, c=1_111, r=r, seed=5, scheme="tiled")
    fn, arg = {
        "estimates": (lambda t: estimates_pallas(cs, t, interpret=True),
                      np.zeros((r, cs.c_eff), np.float32)),
        "sketch_vec": (lambda v: sketch_vec_pallas(cs, v, interpret=True),
                       np.zeros(cs.d, np.float32)),
    }[kernel]
    if batched:
        fn, arg = jax.vmap(fn), np.stack([arg, arg])
    bodies = [site.eqn.params["jaxpr"]
              for site in iter_eqns(jax.make_jaxpr(fn)(arg))
              if site.primitive == "pallas_call"]
    assert len(bodies) == 1
    prims = [site.primitive for site in iter_eqns(bodies[0])]
    assert prims.count("gather") == r
    assert "concatenate" not in prims


def _jaxpr_has_pallas(fn, *args) -> bool:
    # interpret-mode pallas_call still appears as the pallas_call
    # primitive in jaxprs — dispatch is visible without a TPU
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_sketch_kernel_vmap_dispatches_batched_kernel_bitwise():
    """The review-r4 hazard, closed the other way in round 8: instead of
    abandoning the kernel under vmap, the custom_vmap batch guard now
    dispatches the purpose-built 2-D grid (batch, n_tiles) kernel — whose
    per-row block specs and tile-gated init make it bit-identical per
    batch row to the XLA path (JAX's DEFAULT batching rule would have
    prepended batch to the grid and corrupted program_id(0))."""
    d, c, r = 2_000, 512, 3
    cs = CountSketch(d=d, c=c, r=r, seed=9, scheme="tiled")
    rng = np.random.RandomState(5)
    vecs = jax.numpy.asarray(rng.randn(4, d).astype(np.float32))
    sk = jax.vmap(lambda v: sketch_vec_pallas(cs, v, interpret=True))
    assert _jaxpr_has_pallas(sk, vecs)
    out = sk(vecs)
    ref = jax.vmap(lambda v: cs.sketch_vec(v, use_kernel=False))(vecs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # estimates: same guard, same batched dispatch, same contract
    tables = jax.vmap(lambda v: cs.sketch_vec(v))(vecs)
    est_fn = jax.vmap(lambda t: estimates_pallas(cs, t, interpret=True))
    assert _jaxpr_has_pallas(est_fn, tables)
    est = est_fn(tables)
    est_ref = jax.vmap(lambda t: cs.estimates(t, use_kernel=False))(tables)
    np.testing.assert_array_equal(np.asarray(est), np.asarray(est_ref))


def test_nested_vmap_falls_back_to_xla_bitwise():
    """A second batching level must NOT reach a kernel: the batched entry
    is itself batch-guarded, so nested vmap maps the doubly-vmapped XLA
    formulation (no pallas_call in the jaxpr) and stays bitwise."""
    d, c, r = 1_500, 256, 3
    cs = CountSketch(d=d, c=c, r=r, seed=11, scheme="tiled")
    rng = np.random.RandomState(7)
    vecs = jax.numpy.asarray(rng.randn(2, 3, d).astype(np.float32))
    sk = jax.vmap(jax.vmap(
        lambda v: sketch_vec_pallas(cs, v, interpret=True)))
    assert not _jaxpr_has_pallas(sk, vecs)
    ref = jax.vmap(jax.vmap(
        lambda v: cs.sketch_vec(v, use_kernel=False)))(vecs)
    np.testing.assert_array_equal(np.asarray(sk(vecs)), np.asarray(ref))
    tables = jax.vmap(jax.vmap(lambda v: cs.sketch_vec(v)))(vecs)
    est_fn = jax.vmap(jax.vmap(
        lambda t: estimates_pallas(cs, t, interpret=True)))
    assert not _jaxpr_has_pallas(est_fn, tables)
    est_ref = jax.vmap(jax.vmap(
        lambda t: cs.estimates(t, use_kernel=False)))(tables)
    np.testing.assert_array_equal(np.asarray(est_fn(tables)),
                                  np.asarray(est_ref))


def test_zero_length_chunk_sketches_to_zero_table():
    """A zero-length bucket slice must sketch to the zero table (the XLA
    paths' empty segment_sum) without reaching a 0-tile grid — unbatched
    and under vmap."""
    cs = CountSketch(d=2_000, c=512, r=3, seed=9, scheme="tiled")
    empty = jax.numpy.zeros((0,), jax.numpy.float32)
    zero = np.zeros((cs.r, cs.c_eff), np.float32)
    np.testing.assert_array_equal(
        np.asarray(sketch_vec_pallas(cs, empty, interpret=True)), zero)
    np.testing.assert_array_equal(np.asarray(cs.sketch_range(empty, 0)),
                                  zero)
    batch = jax.numpy.zeros((3, 0), jax.numpy.float32)
    out = jax.vmap(lambda v: sketch_vec_pallas(cs, v, interpret=True))(batch)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros((3, cs.r, cs.c_eff), np.float32))


@pytest.mark.parametrize("r", [1, 3, 5])
@pytest.mark.parametrize("d,batch,n_max,offset_blocks", [
    (9_999, 4, 4_000, 0), (9_999, 4, 4_000, 7),
    # 18 tiles a row: the bases' SMEM block changes inside a row and is
    # fetched again as the next batch row starts
    (200_000, 2, 140_001, 7)])
def test_batched_kernel_offsets_all_r_bit_identical(r, d, batch, n_max,
                                                    offset_blocks):
    """Acceptance sweep: the batched 2-D grid kernel, at offset 0 and a
    bucketed offset, for every supported median width — bit-identical to
    the vmapped XLA formulation in both directions. d is chosen so the
    chunk ends on a TAIL tile (n_blocks not a multiple of TILE_BLOCKS)
    and a partial last block, exercising the zero-pad path per row."""
    c = 1_111
    cs = CountSketch(d=d, c=c, r=r, seed=5, scheme="tiled")
    rng = np.random.RandomState(40 + r)
    off = offset_blocks * 128
    n = min(n_max, d - off)
    chunks = jax.numpy.asarray(rng.randn(batch, n).astype(np.float32))
    out = jax.vmap(lambda v: sketch_vec_pallas(
        cs, v, interpret=True, block_offset=offset_blocks))(chunks)
    ref = jax.vmap(lambda v: cs.sketch_range(v, off))(chunks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # estimate-all over the batch of bucket tables
    est = jax.vmap(lambda t: estimates_pallas(cs, t, interpret=True))(out)
    est_ref = jax.vmap(lambda t: cs.estimates(t, use_kernel=False))(out)
    np.testing.assert_array_equal(np.asarray(est), np.asarray(est_ref))


def test_misaligned_offset_under_vmap_raises():
    """The tiled 128-alignment contract is enforced at trace time, so a
    misaligned bucket offset fails loudly even inside a vmapped transmit
    rather than silently mis-hashing."""
    cs = CountSketch(d=2_000, c=512, r=3, seed=9, scheme="tiled")
    vecs = jax.numpy.ones((2, 256), jax.numpy.float32)
    with pytest.raises(ValueError, match="128-aligned"):
        jax.vmap(lambda v: cs.sketch_range(v, 64, True))(vecs)


def test_sketch_vec_use_kernel_safe_under_round_style_vmap():
    """End-to-end shape of the per-worker DP/clip path: sketch_vec with
    use_kernel=True inside a vmap must produce the exact XLA tables. On
    the CPU tier-1 _kernel_ok is False (backend gate), pinning the
    pure-XLA vmap result; on TPU the same call dispatches the batched
    kernel, bit-identical per row."""
    d = 1_500
    cs = CountSketch(d=d, c=256, r=3, seed=2, scheme="tiled")
    rng = np.random.RandomState(6)
    vecs = jax.numpy.asarray(rng.randn(3, d).astype(np.float32))
    out = jax.vmap(lambda v: cs.sketch_vec(v, use_kernel=True))(vecs)
    ref = jax.numpy.stack([cs.sketch_vec(v) for v in vecs])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_force_dispatch_routes_public_api_to_kernel_on_cpu():
    """force_dispatch('kernel') overrides the backend gate so the public
    CountSketch entry points dispatch the (interpreted) kernels on CPU —
    the mechanism the sketch_batched graft-audit target stands on — and
    'fallback' forces them off everywhere. Both
    bitwise; dispatch asserted via the jaxpr."""
    from commefficient_tpu.ops.sketch_kernels import force_dispatch
    d = 1_500
    cs = CountSketch(d=d, c=256, r=3, seed=2, scheme="tiled")
    rng = np.random.RandomState(8)
    vecs = jax.numpy.asarray(rng.randn(3, d).astype(np.float32))
    ref = jax.vmap(lambda v: cs.sketch_vec(v, use_kernel=False))(vecs)
    with force_dispatch("kernel"):
        fn = jax.vmap(lambda v: cs.sketch_vec(v, use_kernel=True))
        assert _jaxpr_has_pallas(fn, vecs)
        np.testing.assert_array_equal(np.asarray(fn(vecs)), np.asarray(ref))
        tables = fn(vecs)
        est_fn = jax.vmap(lambda t: cs.estimates(t, use_kernel=True))
        assert _jaxpr_has_pallas(est_fn, tables)
        est_ref = jax.vmap(lambda t: cs.estimates(t, use_kernel=False))(
            tables)
        np.testing.assert_array_equal(np.asarray(est_fn(tables)),
                                      np.asarray(est_ref))
    with force_dispatch("fallback"):
        fn = jax.vmap(lambda v: cs.sketch_vec(v, use_kernel=True))
        assert not _jaxpr_has_pallas(fn, vecs)
        np.testing.assert_array_equal(np.asarray(fn(vecs)), np.asarray(ref))


def test_batched_entry_points_bitwise_on_cpu_xla():
    """The aggregate/server-side call sites (federated/server.py,
    buffer.py, round.py) now go through sketch_vec_batched /
    estimates_batched — a singleton vmap over the batch-guarded entry.
    On the CPU tier-1 the backend gate maps the XLA fallback at batch 1,
    which must be bitwise-equal to the unbatched call (lockstep buffered
    == sync hangs on this)."""
    d = 1_500
    cs = CountSketch(d=d, c=256, r=3, seed=2, scheme="tiled")
    rng = np.random.RandomState(12)
    vec = jax.numpy.asarray(rng.randn(d).astype(np.float32))
    table = cs.sketch_vec(vec)
    np.testing.assert_array_equal(
        np.asarray(cs.sketch_vec_batched(vec, use_kernel=True)),
        np.asarray(cs.sketch_vec(vec, use_kernel=True)))
    np.testing.assert_array_equal(
        np.asarray(cs.estimates_batched(table, use_kernel=True)),
        np.asarray(cs.estimates(table, use_kernel=True)))


def test_batched_entry_points_dispatch_batched_kernel_bitwise():
    """Under force_dispatch('kernel') the singleton-vmap entries must
    dispatch a pallas kernel (the 2-D grid batched variant, at batch 1)
    and stay bitwise-equal to both the unbatched kernel and the XLA
    reference — the contract that let the server/aggregate call sites
    drop their 1-D grid twin."""
    from commefficient_tpu.ops.sketch_kernels import force_dispatch
    d = 1_500
    cs = CountSketch(d=d, c=256, r=3, seed=2, scheme="tiled")
    rng = np.random.RandomState(13)
    vec = jax.numpy.asarray(rng.randn(d).astype(np.float32))
    ref_table = np.asarray(cs.sketch_vec(vec, use_kernel=False))
    ref_est = np.asarray(cs.estimates(jax.numpy.asarray(ref_table),
                                      use_kernel=False))
    with force_dispatch("kernel"):
        assert _jaxpr_has_pallas(
            lambda v: cs.sketch_vec_batched(v, use_kernel=True), vec)
        bat = np.asarray(cs.sketch_vec_batched(vec, use_kernel=True))
        unb = np.asarray(cs.sketch_vec(vec, use_kernel=True))
        np.testing.assert_array_equal(bat, unb)
        np.testing.assert_array_equal(bat, ref_table)
        t = jax.numpy.asarray(ref_table)
        assert _jaxpr_has_pallas(
            lambda x: cs.estimates_batched(x, use_kernel=True), t)
        ebat = np.asarray(cs.estimates_batched(t, use_kernel=True))
        eunb = np.asarray(cs.estimates(t, use_kernel=True))
        np.testing.assert_array_equal(ebat, eunb)
        np.testing.assert_array_equal(ebat, ref_est)
