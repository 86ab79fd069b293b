"""Streaming hierarchical top-k Pallas kernels vs the incumbent
``jax.lax.top_k`` chain: BITWISE-identical, including tie-breaking. Runs
the kernels in interpret mode on CPU (force_dispatch overrides the
backend gate); on a TPU backend the same programs run compiled.

The tie-break contract is the load-bearing part: ``lax.top_k`` is stable
(equal scores taken in ascending index order), and the radix kernel
reproduces that exactly by accepting threshold ties in flat-index order
until ``k - n_gt`` are taken — pinned here under duplicated magnitudes
crossing tile boundaries and sign-differing equal squares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import topk_kernels as tk
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.ops.topk import topk, topk_values_indices


def _jaxpr_has_pallas(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _vec_with_ties(d, n_ties, seed, mag=1.5):
    """Random vector with n_ties entries of EXACTLY equal magnitude and
    mixed sign, scattered across the whole index range (so threshold
    ties cross tile boundaries for multi-tile d)."""
    rng = np.random.RandomState(seed)
    x = rng.randn(d).astype(np.float32)
    ties = rng.choice(d, n_ties, replace=False)
    x[ties] = np.where(rng.rand(n_ties) < 0.5, mag, -mag).astype(np.float32)
    return x


@pytest.mark.parametrize("d,k", [(300, 7), (300, 300), (20_000, 50),
                                 (20_000, 1), (8_192, 8_192)])
def test_select_bit_identical_to_lax_topk(d, k):
    rng = np.random.RandomState(d % 97)
    vec = jnp.asarray(rng.randn(d).astype(np.float32))
    ref = np.asarray(topk(vec, k))
    with tk.force_dispatch("kernel"):
        got = np.asarray(tk.topk_select_pallas(vec, k, k=k, interpret=True))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("n_ties,k", [(300, 100), (300, 299), (50, 30)])
def test_tie_break_bit_agrees_across_tiles(n_ties, k):
    """Duplicated magnitudes (mixed sign — equal SQUARES, different
    values) scattered across a multi-tile stream: the kernel must keep
    exactly the ties stable ``lax.top_k`` keeps (ascending index)."""
    d = 20_000
    vec = jnp.asarray(_vec_with_ties(d, n_ties, seed=3, mag=1.5))
    ref = np.asarray(topk(vec, k))
    with tk.force_dispatch("kernel"):
        got = np.asarray(tk.topk_select_pallas(vec, k, k=k, interpret=True))
    np.testing.assert_array_equal(got, ref)
    # the threshold tie really is contested: more candidates than slots
    assert (np.abs(np.asarray(vec)) == 1.5).sum() > k - 1


def test_negative_values_with_equal_squares_keep_sign():
    """-x and +x have identical scores; whichever the stable order keeps
    must come through with its own sign bit (the dense mask copies the
    VALUE, never the magnitude)."""
    vec = jnp.asarray(np.array([0.1, -2.0, 2.0, -0.1, 2.0, -2.0, 0.0],
                               np.float32))
    for k in (1, 2, 3, 5):
        ref = np.asarray(topk(vec, k))
        with tk.force_dispatch("kernel"):
            got = np.asarray(tk.topk_select_pallas(vec, k, k=k,
                                                   interpret=True))
        np.testing.assert_array_equal(got, ref)


def test_all_zero_vector_selects_first_k_like_stable_sort():
    vec = jnp.zeros((9_000,), jnp.float32)
    ref = np.asarray(topk(vec, 12))
    with tk.force_dispatch("kernel"):
        got = np.asarray(tk.topk_select_pallas(vec, 12, k=12,
                                               interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_fused_true_topk_bitwise_vs_incumbent_server_chain():
    """The fused epilogue vs the ACTUAL incumbent program structure
    (federated/server._true_topk verbatim, jitted): update, new
    Vvelocity and new Verror all bitwise, in both dispatch modes."""
    from functools import partial

    d, k, rho = 20_000, 50, 0.9
    rng = np.random.RandomState(7)
    g = jnp.asarray(rng.randn(d).astype(np.float32))
    vv = jnp.asarray(rng.randn(d).astype(np.float32))
    ve = jnp.asarray(rng.randn(d).astype(np.float32))

    @partial(jax.jit, static_argnames=("k", "rho"))
    def incumbent(g, vvel, verr, *, k, rho):
        v = g + rho * vvel
        err = verr + v
        update = topk(err, k)
        support = update != 0
        return (update, jnp.where(support, 0.0, v),
                jnp.where(support, 0.0, err))

    ref = incumbent(g, vv, ve, k=k, rho=rho)
    for mode in ("kernel", "fallback"):
        with tk.force_dispatch(mode):
            got = tk.fused_true_topk_pallas(g, vv, ve, k=k, rho=rho,
                                            interpret=True)
        for a, b, nm in zip(ref, got, ("update", "Vvelocity", "Verror")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{nm} [{mode}]")


def test_fused_true_topk_ties_and_selected_zero_residuals():
    """Ties in the ERROR stream plus exact-zero errors at selected
    positions: the incumbent's support convention is ``update != 0``
    (a selected zero keeps its residual), replicated in-kernel."""
    d, k, rho = 20_000, 120, 0.9
    g = jnp.asarray(_vec_with_ties(d, 200, seed=11, mag=2.5))
    rng = np.random.RandomState(12)
    vv = jnp.asarray(rng.randn(d).astype(np.float32))
    ve = jnp.asarray((-np.asarray(g) * 1.0
                      - rho * np.asarray(vv)).astype(np.float32))
    # verr + g + rho*vv is (mostly) exactly zero -> heavy zero-score ties
    ref = jax.jit(lambda a, b, c: tk._fused_true_topk_fallback(
        a, b, c, k=k, rho=rho))(g, vv, ve)
    with tk.force_dispatch("kernel"):
        got = tk.fused_true_topk_pallas(g, vv, ve, k=k, rho=rho,
                                        interpret=True)
    for a, b, nm in zip(ref, got, ("update", "Vvelocity", "Verror")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=nm)


@pytest.mark.parametrize("with_mask", [False, True],
                         ids=["dense", "with_mask"])
@pytest.mark.parametrize("d", [5_000, 9_000, 16_384, "planted"],
                         ids=["one_tile", "9000", "two_whole_tiles",
                              "planted"])
def test_unsketch_select_bit_identical_to_estimates_then_topk(
        d, with_mask, planted_table):
    """One estimates pass, the counts over what it wrote and the in-place
    select must equal CountSketch.estimates -> masked top-k bitwise (the
    mask too, for a caller that asks): with a tail tile whose lanes past
    d hold estimates of blocks no coordinate has, with d a whole number
    of tiles, and where the top-k fills up with tied and zero estimates,
    -0.0 among them."""
    planted = d == "planted"
    d, c, r, k = (20_000, 2_048, 5, 200) if planted else (d, 512, 3, 40)
    cs = CountSketch(d=d, c=c, r=r, seed=5, scheme="tiled")
    rng = np.random.RandomState(4)
    if planted:
        table = planted_table(cs, rng, n=40)
    else:
        vec = np.zeros(d, np.float32)
        hot = rng.choice(d, 60, replace=False)
        vec[hot] = rng.randn(60).astype(np.float32) * 10
        table = cs.sketch_vec(vec)
    est = cs.estimates(table, use_kernel=False)
    ref_masked, ref_mask = jax.jit(
        lambda e: tk._mask_fallback(e, jnp.int32(k), k, with_mask=True))(est)
    if planted:
        picked = np.asarray(ref_masked)[np.asarray(ref_mask) != 0]
        assert np.signbit(picked[picked == 0]).any()
        assert np.unique(np.abs(picked[picked != 0])).size < 10
    for mode in ("kernel", "fallback"):
        with tk.force_dispatch(mode):
            got = tk.unsketch_select_pallas(cs, table, k=k,
                                            with_mask=with_mask,
                                            interpret=True)
        got_masked, got_mask = got if with_mask else (got, None)
        np.testing.assert_array_equal(
            np.asarray(got_masked).view(np.uint32),
            np.asarray(ref_masked).view(np.uint32), err_msg=mode)
        if with_mask:
            np.testing.assert_array_equal(np.asarray(got_mask),
                                          np.asarray(ref_mask),
                                          err_msg=mode)


def test_values_indices_from_mask_restores_exact_topk_order():
    """Compaction + two-key sort must hand back (values, indices) in the
    EXACT ``lax.top_k`` return order — descending score, ascending index
    on ties — so downstream float summations see identical operand
    order."""
    d, k = 20_000, 200
    vec = jnp.asarray(_vec_with_ties(d, 300, seed=9, mag=1.5))
    ref_vals, ref_idx = topk_values_indices(vec, k)
    with tk.force_dispatch("kernel"):
        masked, mask = tk.topk_select_pallas(vec, k, k=k, with_mask=True,
                                             interpret=True)
    vals, idx = tk.values_indices_from_mask(masked, mask, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_vals))


def test_per_row_k_batched_kernel_matches_legacy_two_stage():
    """Heterogeneous per-client k (PR 19): a vmapped call with a traced
    per-row kk must dispatch the 2-D grid kernel and be bitwise equal to
    the legacy two-stage path — topk at the static max-k, then keep each
    row's first client_k slots in stable selection order."""
    B, d, kmax = 3, 20_000, 40
    rng = np.random.RandomState(21)
    vecs = jnp.asarray(rng.randn(B, d).astype(np.float32))
    kks = jnp.asarray(np.array([40, 17, 1], np.int32))

    # legacy: stable top-k of kmax, then rank mask (client.py PR-19 block)
    def legacy(v, kk):
        dense = topk(v, kmax)
        sq = dense * dense
        _, order = jax.lax.top_k(sq, kmax)
        keep = jnp.zeros(v.shape, bool).at[order].set(
            jnp.arange(kmax) < kk)
        return jnp.where(keep, dense, 0)

    ref = jax.vmap(legacy)(vecs, kks)
    with tk.force_dispatch("kernel"):
        fn = jax.vmap(lambda v, kk: tk.topk_select_pallas(
            v, kk, k=kmax, interpret=True))
        assert _jaxpr_has_pallas(fn, vecs, kks)
        got = fn(vecs, kks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # fallback arm of the public per-row-k entry: same bits, no kernel
    with tk.force_dispatch("fallback"):
        fb = lambda m, kk: topk(m, kmax, row_k=kk)  # noqa: E731
        assert not _jaxpr_has_pallas(fb, vecs, kks)
        np.testing.assert_array_equal(np.asarray(fb(vecs, kks)),
                                      np.asarray(ref))


def test_nested_vmap_falls_back_to_xla_bitwise():
    """A second batching level must NOT reach a kernel: the batched
    entry is itself batch-guarded, so nested vmap maps the doubly-
    vmapped XLA fallback (no pallas_call in the jaxpr) and stays
    bitwise."""
    d, k = 2_000, 9
    rng = np.random.RandomState(23)
    vecs = jnp.asarray(rng.randn(2, 3, d).astype(np.float32))
    kks = jnp.asarray(np.array([[9, 4, 1], [2, 9, 5]], np.int32))
    with tk.force_dispatch("kernel"):
        fn = jax.vmap(jax.vmap(lambda v, kk: tk.topk_select_pallas(
            v, kk, k=k, interpret=True)))
        assert not _jaxpr_has_pallas(fn, vecs, kks)
        got = fn(vecs, kks)
    ref = jax.vmap(jax.vmap(
        lambda v, kk: tk._mask_fallback(v, kk, k)))(vecs, kks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_approx_recall_refuses_the_kernel():
    """``approx_max_k`` is TPU-native and intentionally inexact — there
    is nothing to bit-agree with, so the gate refuses even under forced
    kernel dispatch and the public chain keeps the approx path."""
    assert not tk.topk_kernel_ok(0.95)
    with tk.force_dispatch("kernel"):
        assert not tk.topk_kernel_ok(0.95)
        assert tk.topk_kernel_ok(None)
    with tk.force_dispatch("fallback"):
        assert not tk.topk_kernel_ok(None)


def test_topk_public_api_dispatches_kernel_under_force():
    """ops.topk.topk / topk_values_indices route through the streaming
    kernel when forced (the audits' mechanism) — bitwise, with the
    pallas_call visible in the jaxpr — and approx_recall keeps the
    incumbent approx path even when forced."""
    d, k = 20_000, 50
    rng = np.random.RandomState(31)
    vec = jnp.asarray(rng.randn(d).astype(np.float32))
    ref = np.asarray(topk(vec, k))
    rv, ri = topk_values_indices(vec, k)
    with tk.force_dispatch("kernel"):
        assert _jaxpr_has_pallas(lambda v: topk(v, k), vec)
        np.testing.assert_array_equal(np.asarray(topk(vec, k)), ref)
        assert not _jaxpr_has_pallas(
            lambda v: topk(v, k, approx_recall=0.9), vec)
        assert _jaxpr_has_pallas(lambda v: topk_values_indices(v, k), vec)
        kv, ki = topk_values_indices(vec, k)
        np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    with tk.force_dispatch("fallback"):
        assert not _jaxpr_has_pallas(lambda v: topk(v, k), vec)
        np.testing.assert_array_equal(np.asarray(topk(vec, k)), ref)


def test_topk_2d_and_values_indices_2d_share_batched_selection():
    """Satellite: topk_values_indices now takes 2-D input (per-row), and
    2-D topk dispatches the batched kernel under force — both bitwise
    against the per-row incumbent."""
    B, d, k = 3, 9_000, 16
    rng = np.random.RandomState(37)
    mat = jnp.asarray(rng.randn(B, d).astype(np.float32))
    ref_dense = np.stack([np.asarray(topk(mat[i], k)) for i in range(B)])
    ref_vi = [topk_values_indices(mat[i], k) for i in range(B)]
    with tk.force_dispatch("kernel"):
        assert _jaxpr_has_pallas(lambda m: topk(m, k), mat)
        np.testing.assert_array_equal(np.asarray(topk(mat, k)), ref_dense)
        vals, idx = topk_values_indices(mat, k)
    assert vals.shape == idx.shape == (B, k)
    for i in range(B):
        np.testing.assert_array_equal(np.asarray(vals[i]),
                                      np.asarray(ref_vi[i][0]))
        np.testing.assert_array_equal(np.asarray(idx[i]),
                                      np.asarray(ref_vi[i][1]))
    vals, idx = topk_values_indices(mat, k)  # backend-gated fallback path
    for i in range(B):
        np.testing.assert_array_equal(np.asarray(vals[i]),
                                      np.asarray(ref_vi[i][0]))
        np.testing.assert_array_equal(np.asarray(idx[i]),
                                      np.asarray(ref_vi[i][1]))


def test_topk_row_k_matches_per_row_masking():
    """Satellite: ``topk(mat, k, row_k=...)`` — the public per-row-k
    entry the heterogeneous-client path calls — equals topk + per-row
    stable-rank masking in both dispatch modes."""
    B, d, kmax = 4, 2_000, 12
    rng = np.random.RandomState(41)
    mat = jnp.asarray(rng.randn(B, d).astype(np.float32))
    row_k = jnp.asarray(np.array([12, 5, 1, 12], np.int32))
    ref = np.stack([
        np.asarray(tk._mask_fallback(mat[i], row_k[i], kmax))
        for i in range(B)])
    got = np.asarray(topk(mat, kmax, row_k=row_k))
    np.testing.assert_array_equal(got, ref)
    with tk.force_dispatch("kernel"):
        got_k = np.asarray(topk(mat, kmax, row_k=row_k))
    np.testing.assert_array_equal(got_k, ref)


_BLOCK_N = tk.COUNT_ROWS * 128        # elements of one count block


def _padded(vec, poison=3e30):
    """``vec`` in the tiled layout the kernels stream, its padding lanes
    holding a score above every real one: they count only if the mask
    fails to send them to the sentinel."""
    n = vec.shape[-1]
    width = -(-n // tk.TILE_N) * tk.TILE_N
    out = np.full(vec.shape[:-1] + (width,), poison, np.float32)
    out[..., :n] = vec
    return out.reshape(vec.shape[:-1] + (width // 128, 128))


@pytest.mark.parametrize("case", ["whole_blocks", "overhang", "one_block",
                                  "batched", "all_tied", "padding"])
def test_count_kernel_counts_equal_numpy(case):
    """The count pass's 16 counts of ``bits >= cand`` equal a numpy count:
    over whole count blocks, where the last block overhangs the buffer (the
    interpreter fills what lies past it with NaN, whose bits would count),
    in one block smaller than ``COUNT_ROWS``, per row on the batched grid
    with a different k a row, with every score tied, and with the padding
    lanes at the sentinel (candidates at the sentinel + 1 and at 0 count
    exactly the d real lanes)."""
    rng = np.random.RandomState(43)
    d = {"whole_blocks": 2 * _BLOCK_N, "one_block": 20_000,
         "all_tied": _BLOCK_N + 5_000}.get(case, _BLOCK_N + 18_928)
    B = 3 if case == "batched" else 1
    vec = rng.randn(B, d).astype(np.float32)
    if case == "all_tied":
        vec[:] = -1.5
    bits = (vec * vec).view(np.int32)
    js = np.arange(16, dtype=np.int32)
    if case == "padding":
        cands = np.array([[tk._SENTINEL + 1, 0, 1] + [0x7F000000] * 13],
                         np.int32)
    else:
        # around each row's k-th largest score, for a different k a row
        kth = [np.sort(bits[b])[::-1][k - 1]
               for b, k in zip(range(B), (1, 700, 20_000))]
        cands = np.stack([t - 8 + js for t in kth]).astype(np.int32)
    ref = (bits[:, :, None] >= cands[:, None, :]).sum(axis=1)
    vp = jnp.asarray(_padded(vec))
    if case == "batched":
        got = tk._count_call(vp, jnp.asarray(cands), n=d, interp=True,
                             batched=True)
    else:
        got = tk._count_call(vp[0], jnp.asarray(cands[0]), n=d,
                             interp=True)[None]
    np.testing.assert_array_equal(np.asarray(got), ref)
    if case == "all_tied":
        assert set(ref[0].tolist()) == {0, d}
    if case == "padding":
        assert ref[0, 0] == ref[0, 1] == d
    if case == "overhang":
        assert vp.shape[-2] % tk.COUNT_ROWS != 0


@pytest.mark.parametrize("kk", [700, 640])
def test_threshold_parity_where_the_last_count_block_overhangs(kk):
    """The radix threshold and the select over a stream whose last count
    block overhangs the buffer, with contested ties across tiles and
    count blocks: equal to the incumbent masked top-k, bit for bit."""
    d, k = _BLOCK_N + 18_928, 700
    vec = jnp.asarray(_vec_with_ties(d, 900, seed=47, mag=2.0))
    assert (-(-d // tk.TILE_N) * 64) % tk.COUNT_ROWS != 0
    ref = jax.jit(lambda v: tk._mask_fallback(v, jnp.int32(kk), k))(vec)
    with tk.force_dispatch("kernel"):
        got = tk.topk_select_pallas(vec, kk, k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(ref).view(np.uint32))
    assert (np.abs(np.asarray(vec)) == 2.0).sum() > kk


def _pallas_programs(fn, *args):
    """[(name, grid, block shapes, scratch shapes, output shapes, kernel
    body length)] of every pallas_call in ``fn``'s kernel-arm jaxpr."""
    from commefficient_tpu.analysis.walker import iter_eqns
    with tk.force_dispatch("kernel"):
        jaxpr = jax.make_jaxpr(fn)(*args)
    out = []
    for site in iter_eqns(jaxpr):
        if site.primitive != "pallas_call":
            continue
        p, gm = site.eqn.params, site.eqn.params["grid_mapping"]
        assert not p["input_output_aliases"]
        out.append((
            p["name"], tuple(gm.grid),
            [tuple(b.block_size for b in bm.block_shape)
             for bm in gm.block_mappings],
            [a.shape for a in gm.scratch_avals],
            [o.shape for o in p["out_avals"]], len(p["jaxpr"].eqns)))
    return out


_TILE, _CANDS, _ONE = (64, 128), (1, 16), (1, 1)
#: the count walks the 192 rows of d = 20 000 in one block of its own, with
#: its 16 lane-dense accumulators in VMEM scratch
_COUNT = ("radix_count_pallas", (1,), [(192, 128), _CANDS, _CANDS],
          [(16, 8, 128)], [(1, 16)], 15)
_BCOUNT = ("radix_count_pallas", (3, 1), [(1, 192, 128), _CANDS, _CANDS],
           [(16, 8, 128)], [(3, 16)], 15)
#: the select entries read off the tree before the estimate stream left
#: the kernels these programs share, at d = 20 000, k = 50
SHARED_PROGRAMS = {
    "plain": [_COUNT, _COUNT,
              ("topk_select_pallas", (3,), [_TILE, _ONE, _ONE, _TILE],
               [_ONE], [(192, 128)], 45)],
    "plain_with_mask": [_COUNT, _COUNT,
                        ("topk_select_pallas", (3,),
                         [_TILE, _ONE, _ONE, _TILE, _TILE], [_ONE],
                         [(192, 128)] * 2, 47)],
    "batched": [_BCOUNT, _BCOUNT,
                ("topk_select_pallas", (3, 3),
                 [(1,) + _TILE, _ONE, _ONE, (1,) + _TILE], [_ONE],
                 [(3, 192, 128)], 45)],
    "true_topk": [_COUNT, _COUNT,
                  ("fused_true_topk_pallas", (3,),
                   [_TILE, _TILE, _ONE, _ONE, _TILE, _TILE, _TILE], [_ONE],
                   [(192, 128)] * 3, 52)],
}


@pytest.mark.parametrize("program", sorted(SHARED_PROGRAMS))
def test_programs_that_share_the_kernels_are_as_they_were(program):
    """``topk_select_pallas`` and ``fused_true_topk_pallas`` share
    ``_count_kernel`` / ``_select_kernel`` / ``_tile_select`` with the
    sketch server's unsketch: an edit to that path must leave their
    pallas_calls as they are — grid, blocks, scratch, outputs, name, the
    kernel body's length, and no aliasing."""
    d, k = 20_000, 50
    v = jnp.zeros((d,), jnp.float32)
    fn, args = {
        "plain": (lambda x: tk.topk_select_pallas(x, k, k=k), (v,)),
        "plain_with_mask": (lambda x: tk.topk_select_pallas(
            x, k, k=k, with_mask=True), (v,)),
        "batched": (jax.vmap(lambda x, kk: tk.topk_select_pallas(
            x, kk, k=k)), (jnp.zeros((3, d)), jnp.arange(5, 8))),
        "true_topk": (lambda g, a, b: tk.fused_true_topk_pallas(
            g, a, b, k=k, rho=0.9), (v, v, v)),
    }[program]
    assert _pallas_programs(fn, *args) == SHARED_PROGRAMS[program]
