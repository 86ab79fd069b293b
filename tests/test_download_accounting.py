"""Download accounting: counting by streamed comparison vs the dense (W, d)
matrix and the searchsorted + histogram that came between
(federated/round.py::download_counts).

count_w = #{i : last_changed[i] >= stale_round[w]} was first the row sums
of the full (W, d) boolean comparison matrix (496 MB of pure accounting
overhead per round at gpt2-small W=4), then a searchsorted of all d
coordinates against the W sorted stale rounds plus a (W+1)-bin scatter-add
(O(d) memory, but seven dependent d-long gathers and a d-long scatter: 391
of a 565 ms ResNet-9 round on a TPU v5e, PERF.md PR 27). It is now a loop
over the W participants whose step is one fused ``sum(last_changed >=
stale)`` that streams ``last_changed``. These tests pin what that claims:
(1) the dense formulation's integers bit for bit, for the function alone
over awkward W and d and for download_bytes across modes, padded epoch-tail
rounds and post-abort rounds; (2) no aval of W*d elements or more, whatever
its shape, anywhere in the round's jaxpr; (3) no gather, scatter or sort
with a d-long operand inside the accounting phase.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis import iter_eqns
from commefficient_tpu.analysis.walker import _sub_jaxprs
from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.federated.round import download_counts
from commefficient_tpu.models import TinyMLP
from commefficient_tpu.utils.tracing import PHASE_PREFIX, phase

N_CLIENTS = 6
W = 2


def make_learner(num_workers=W, num_clients=N_CLIENTS, **cfg_kw):
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=num_workers,
                    num_clients=num_clients, lr_scale=0.05, **cfg_kw)
    return FedLearner(model, cfg, make_cv_loss(model), None,
                      jax.random.PRNGKey(1), np.zeros((1, 8), np.float32))


def dense_download_bytes(last_changed, client_last_round, ids, mask):
    """The dense (W, d) formulation, recomputed host-side in exact
    integer arithmetic from the PRE-round state (the reference
    implementation the streamed count must match bit-for-bit)."""
    stale = client_last_round[np.asarray(ids)]                  # (W,)
    changed = last_changed[None, :] >= stale[:, None]           # (W, d)
    valid = np.asarray(mask).any(axis=1)
    return 4.0 * float(np.sum(changed.sum(axis=1, dtype=np.int64) *
                              valid.astype(np.int64)))


def scenario(seed=0):
    """Rounds covering every accounting regime: normal rotation with
    repeat participants, a padded epoch-tail slot, a NaN-abort round,
    and post-abort rounds (which must bill zero bytes)."""
    rng = np.random.RandomState(seed)

    def normal():
        ids = rng.choice(N_CLIENTS, W, replace=False)
        Xb = rng.randn(W, 4, 8).astype(np.float32)
        yb = rng.randint(0, 2, (W, 4)).astype(np.int32)
        return ids, (Xb, yb), np.ones((W, 4), np.float32)

    rounds = [normal() for _ in range(3)]
    ids, batch, mask = normal()                 # padded epoch tail
    mask = mask.copy()
    mask[-1] = 0.0
    rounds.append((ids, batch, mask))
    rounds.append(normal())
    ids, (Xb, yb), mask = normal()              # NaN -> device-guard abort
    Xb = Xb.copy()
    Xb[0, 0, 0] = np.nan
    rounds.append((ids, (Xb, yb), mask))
    rounds += [normal() for _ in range(2)]      # post-abort: frozen, 0 bytes
    return rounds


CFGS = [
    dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
         k=3, num_rows=3, num_cols=20),
    dict(mode="true_topk", error_type="virtual", virtual_momentum=0.9,
         local_momentum=0.9, k=3),
    dict(mode="fedavg", error_type="none", virtual_momentum=0.0,
         local_momentum=0, local_batch_size=-1),
]


@pytest.mark.parametrize("cfg_kw", CFGS,
                         ids=["sketch", "true_topk", "fedavg"])
def test_round_counts_match_dense_matrix_bit_for_bit(cfg_kw):
    ln = make_learner(**cfg_kw)
    saw_nonzero = saw_abort = False
    for ids, batch, mask in scenario():
        # snapshot BEFORE the round: the state buffers are donated
        lc = np.asarray(ln.state.last_changed)
        clr = np.asarray(ln.state.client_last_round)
        expect = dense_download_bytes(lc, clr, ids, mask)
        out = ln.train_round(ids, batch, mask)
        if out["aborted"]:
            # okf gates the metric: the breaching round and everything
            # after it transferred nothing
            expect = 0.0
            saw_abort = True
        saw_nonzero = saw_nonzero or expect > 0
        # both sides are exact integer math * 4.0 — equality is bitwise
        assert out["download_bytes"] == expect
    assert saw_nonzero and saw_abort  # the scenario exercised both regimes


def test_repeat_participant_bills_only_changed_coordinates():
    # a participant is billed exactly the coordinates with
    # last_changed >= its stale round: never-changed weights (init -2)
    # bill nothing even to first-time pullers, and a true_topk round
    # changes <= k coords, so later pulls bill a sparse count, never the
    # dense full-vector d — the property the count must preserve
    ln = make_learner(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, k=3)
    rng = np.random.RandomState(7)

    def mk(ids):
        Xb = rng.randn(W, 4, 8).astype(np.float32)
        yb = rng.randint(0, 2, (W, 4)).astype(np.int32)
        return np.asarray(ids), (Xb, yb), np.ones((W, 4), np.float32)

    d = int(ln.state.last_changed.shape[0])
    bills = []
    for ids in ([0, 1], [2, 3], [0, 4]):        # client 0 returns
        lc = np.asarray(ln.state.last_changed)
        clr = np.asarray(ln.state.client_last_round)
        out = ln.train_round(*mk(ids))
        expect = dense_download_bytes(lc, clr, np.asarray(ids),
                                      np.ones((W, 4), np.float32))
        assert out["download_bytes"] == expect
        bills.append(out["download_bytes"])
    # round 0: nothing has ever changed -> zero bytes billed
    assert bills[0] == 0.0
    # each later round bills the <= k changed coords per participant,
    # nonzero but far below a dense full-vector pull
    k = ln.cfg.k
    for b in bills[1:]:
        assert 0.0 < b <= 4.0 * 2 * 2 * k < 4.0 * 2 * d


def _count_cases():
    """(id, last_changed, stale_round): W in {1, 4, 100, 401} against d
    below one vector register, odd and prime (no block size, and no
    unroll of the participant loop, divides either), then the edges of
    ``>=``."""
    rng = np.random.RandomState(27)
    cases = []
    for w in (1, 4, 100, 401):
        for d in (7, 1009, 40009):
            lc = rng.randint(-2, 60, d).astype(np.int32)
            lc[rng.rand(d) < 0.3] = -2              # never-changed weights
            stale = rng.randint(-1, 60, w).astype(np.int32)
            cases.append((f"W{w}-d{d}", lc, stale))
    lc = rng.randint(-2, 9, 1013).astype(np.int32)
    cases += [
        ("duplicate-stale", lc,
         np.array([3, 3, 7, 3, -1, 7, 0, 0, 3], np.int32)),
        # a fresh cohort (client_last_round init -1) on fresh weights
        # (last_changed init -2) downloads nothing
        ("fresh-cohort-fresh-weights", np.full(1013, -2, np.int32),
         np.full(5, -1, np.int32)),
        ("stale-above-every-entry", lc, np.array([9, 10, 2**31 - 1],
                                                 np.int32)),
        # every entry equal to one stale round: >= counts all of them for
        # it and for every earlier puller, none for a later one
        ("all-entries-equal-one-stale", np.full(1013, 5, np.int32),
         np.array([4, 5, 6, 5, -1], np.int32)),
    ]
    return [pytest.param(lc, stale, id=name) for name, lc, stale in cases]


@pytest.mark.parametrize("lc,stale", _count_cases())
def test_download_counts_match_dense_numpy_count(lc, stale):
    expect = (lc[None, :] >= stale[:, None]).sum(axis=1, dtype=np.int64)
    got = jax.jit(download_counts)(jnp.asarray(lc), jnp.asarray(stale))
    assert got.dtype == jnp.int32 and got.shape == stale.shape
    np.testing.assert_array_equal(np.asarray(got), expect)


def _aval_sizes(eqn):
    """(elements, shape) of every array the eqn reads or writes."""
    shapes = [tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())
              for v in list(eqn.invars) + list(eqn.outvars)]
    return [(int(np.prod(shape)), shape) for shape in shapes if shape]


def _forbidden_hits(closed, min_size):
    """Every eqn (any depth, via the analysis walker — which also
    descends into custom_vjp/remat sub-jaxprs the old test-local copy
    missed) with an input or output aval of ``min_size`` elements or
    more, whatever its shape: a (W, n_blocks, block) array is the dense
    (W, d) matrix again."""
    return [((site.path + "/" if site.path else "") + site.primitive, shape)
            for site in iter_eqns(closed)
            for size, shape in _aval_sizes(site.eqn) if size >= min_size]


def _dense(lc, stale):
    return jnp.sum(lc[None, :] >= stale[:, None], axis=1)


def _dense_in_blocks(lc, stale):
    blocks = lc.reshape(2, -1)                                  # d even
    return jnp.sum(blocks[None] >= stale[:, None, None], axis=(1, 2))


@pytest.mark.parametrize("specimen", [_dense, _dense_in_blocks])
def test_walker_flags_the_dense_formulation(specimen):
    # self-test: the checker must catch the construct it polices, under
    # any shape
    d, w = 46, 3
    closed = jax.make_jaxpr(specimen)(jnp.zeros((d,), jnp.int32),
                                      jnp.zeros((w,), jnp.int32))
    assert _forbidden_hits(closed, w * d)


def _round_jaxpr(w, **cfg_kw):
    ln = make_learner(num_workers=w, num_clients=7, **cfg_kw)
    d = int(ln.state.last_changed.shape[0])
    ids = jnp.zeros((w,), jnp.int32)
    batch = (jnp.zeros((w, 4, 8), jnp.float32),
             jnp.zeros((w, 4), jnp.int32))
    mask = jnp.ones((w, 4), jnp.float32)
    closed = jax.make_jaxpr(ln._round.raw)(
        ln.state, ids, batch, mask, jnp.float32(0.05),
        jax.random.PRNGKey(0))
    return closed, d


def test_round_jaxpr_has_no_dense_changed_matrix():
    # fused uncompressed path: NO legitimate intermediate of W*d elements
    # exists (one backward over the folded (W*B, ...) batch: 96 elements
    # here against 138), so any aval that large in the round program is
    # the accounting matrix leaking back in
    w = 3
    closed, d = _round_jaxpr(w, mode="uncompressed", error_type="none",
                             virtual_momentum=0.0, local_momentum=0)
    hits = _forbidden_hits(closed, w * d)
    assert not hits, f"W*d-element intermediates materialized: {hits}"


def _accounting_eqns(closed):
    """Every eqn traced under ``phase("download_accounting")``, with all
    that its sub-jaxprs hold (a loop body's own name stack starts anew)."""
    for site in iter_eqns(closed):
        if PHASE_PREFIX + "download_accounting" in str(
                site.eqn.source_info.name_stack):
            yield site.eqn
            for sub in _sub_jaxprs(site.eqn.params):
                yield from (inner.eqn for inner in iter_eqns(sub))


def _d_long_indexing_hits(closed, d):
    """Gathers, scatters and sorts of the accounting phase with an operand
    or result of d elements or more: what a TPU has no fast path for (the
    searchsorted of d coordinates was seven dependent gathers, the
    histogram a d-long scatter-add)."""
    return [eqn.primitive.name for eqn in _accounting_eqns(closed)
            if eqn.primitive.name.startswith(("gather", "scatter", "sort"))
            and max((size for size, _ in _aval_sizes(eqn)), default=0) >= d]


def searchsorted_counts(last_changed, stale_round):
    """The replaced formulation, kept as the specimen the check below must
    catch: bucket each coordinate by how many sorted stale rounds are <=
    it, histogram the buckets, read the counts off the cumulative sum."""
    W = stale_round.shape[0]
    order = jnp.argsort(stale_round)
    buckets = jnp.searchsorted(stale_round[order], last_changed,
                               side="right")                    # (d,)
    hist = jnp.zeros((W + 1,), jnp.int32).at[buckets].add(1)
    below_sorted = jnp.cumsum(hist)[:W]
    return jnp.zeros((W,), jnp.int32).at[order].set(
        last_changed.shape[0] - below_sorted)


def test_indexing_check_flags_the_searchsorted_formulation():
    # self-test, and the specimen still counts right
    d, w = 46, 3
    rng = np.random.RandomState(3)
    lc = rng.randint(-2, 5, d).astype(np.int32)
    stale = rng.randint(-1, 5, w).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(searchsorted_counts(jnp.asarray(lc), jnp.asarray(stale))),
        (lc[None, :] >= stale[:, None]).sum(axis=1))

    def scoped(last_changed, stale_round):
        with phase("download_accounting"):
            return searchsorted_counts(last_changed, stale_round)

    hits = _d_long_indexing_hits(jax.make_jaxpr(scoped)(lc, stale), d)
    assert any(h.startswith("gather") for h in hits), hits
    assert any(h.startswith("scatter") for h in hits), hits
    # outside the phase nothing is looked at
    assert not _d_long_indexing_hits(
        jax.make_jaxpr(searchsorted_counts)(lc, stale), d)


@pytest.mark.parametrize("cfg_kw", CFGS,
                         ids=["sketch", "true_topk", "fedavg"])
def test_accounting_phase_has_no_d_long_gather_scatter_or_sort(cfg_kw):
    closed, d = _round_jaxpr(3, **cfg_kw)
    # the phase is there, and it holds the participant loop
    assert any(e.primitive.name == "scan" for e in _accounting_eqns(closed))
    hits = _d_long_indexing_hits(closed, d)
    assert not hits, f"d-long indexing in download accounting: {hits}"
