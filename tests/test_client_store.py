"""ClientStateStore (federated/client_store.py): the placement x
representation matrix for per-client persistent state.

Pins the subsystem's contracts (docs/SCALING.md):

* sparse codec EXACT whenever nnz <= cap, so ``--client_state sparse``
  under local_topk with k >= d/2 is BITWISE trajectory-equivalent to
  dense — identity under host placement holds by construction (the codec
  runs host-side, the compiled round program is shared), and device
  placement matches to tight tolerance (different XLA program).
* sketched codec: bounded roundtrip divergence (heavy-hitter recovery)
  and end-to-end accuracy within eps of the dense run.
* HostArenaStore: block-partitioned shard routing, O(n*k) memory,
  gather/scatter roundtrip on a 2+ shard mesh.
* the ``client_store`` graft-audit target passes, and its mutation
  (dense device arena reintroduced) FAILS — the audit can actually fire.
* checkpoint fingerprint refuses a representation flip on --resume.
* FaultModel at 1M clients: lazy construction, order-independent fates,
  per-round cost O(W) (``fate_draws``), never O(num_clients).
"""

import types

import jax
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.buffer import BufferedFedLearner
from commefficient_tpu.federated.client_store import (DenseCodec,
                                                      HostArenaStore,
                                                      SketchedCodec,
                                                      SparseCodec,
                                                      gather_rows,
                                                      make_codec,
                                                      scatter_rows)
from commefficient_tpu.federated.faults import FaultModel
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.models import TinyMLP

N_CLIENTS = 6
W = 2
D = 46  # TinyMLP(num_classes=2, hidden=4) flat dim
K_EXACT = 24  # >= D/2: local_topk residual nnz <= D - K <= cap


def make_learner(offload, server_mode="sync", **cfg_kw):
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=W, num_clients=N_CLIENTS,
                    lr_scale=0.05, client_state_offload=offload,
                    server_mode=server_mode, **cfg_kw)
    loss = make_cv_loss(model)
    cls = BufferedFedLearner if server_mode == "buffered" else FedLearner
    return cls(model, cfg, loss, None, jax.random.PRNGKey(1),
               np.zeros((1, 8), np.float32))


def rounds_data(n_rounds, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for r in range(n_rounds):
        ids = rng.choice(N_CLIENTS, W, replace=False)
        Xb = rng.randn(W, 4, 8).astype(np.float32)
        yb = rng.randint(0, 2, (W, 4)).astype(np.int32)
        out.append((ids, (Xb, yb), np.ones((W, 4), np.float32)))
    return out


SPARSE_KW = dict(mode="local_topk", error_type="local", local_momentum=0.9,
                 k=K_EXACT)


# ---------------------------------------------------------------------------
# codec units
# ---------------------------------------------------------------------------

def test_sparse_codec_exact_below_capacity():
    codec = SparseCodec(d=16, cap=6)
    rng = np.random.RandomState(0)
    rows = np.zeros((3, 16), np.float32)
    for i in range(3):
        nnz = rng.choice(16, 6, replace=False)
        rows[i, nnz] = rng.randn(6)
    dec = np.asarray(codec.decode_rows(codec.encode_rows(rows)))
    np.testing.assert_array_equal(dec, rows)
    # numpy single-row path (the host arena's wire format) is exact too
    for i in range(3):
        np.testing.assert_array_equal(
            codec.decode_row_np(codec.encode_row_np(rows[i])), rows[i])


def test_sparse_codec_truncates_to_largest_magnitude():
    codec = SparseCodec(d=8, cap=3)
    row = np.array([0.1, -5.0, 0.2, 4.0, -0.3, 3.0, 0.0, 0.0], np.float32)
    want = np.array([0.0, -5.0, 0.0, 4.0, 0.0, 3.0, 0.0, 0.0], np.float32)
    np.testing.assert_array_equal(
        np.asarray(codec.decode_rows(codec.encode_rows(row[None])))[0], want)
    np.testing.assert_array_equal(
        codec.decode_row_np(codec.encode_row_np(row)), want)


def test_sparse_codec_rejects_bad_cap():
    with pytest.raises(ValueError, match="cap >= 1"):
        SparseCodec(d=8, cap=0)


def test_dense_codec_is_identity():
    codec = DenseCodec(d=5)
    rows = np.random.RandomState(0).randn(2, 5).astype(np.float32)
    assert codec.encode_rows(rows) is rows
    assert codec.decode_rows(rows) is rows
    assert codec.row_floats() == 5


def test_sketched_codec_bounded_roundtrip():
    # a k-sparse row through the per-client CountSketch: the heavy
    # hitters come back (c >> nnz so collisions are rare) with bounded
    # L2 divergence — the contract error feedback absorbs
    codec = SketchedCodec(d=46, r=5, c=64, k=4, seed=0)
    row = np.zeros((1, 46), np.float32)
    row[0, [3, 17, 30, 41]] = [4.0, -3.0, 2.5, -2.0]
    dec = np.asarray(codec.decode_rows(codec.encode_rows(row)))
    err = np.linalg.norm(dec - row) / np.linalg.norm(row)
    assert err < 0.5, f"sketch roundtrip diverged: rel L2 {err:.3f}"
    # decode support is the top-k heavy hitters, nothing else
    assert (dec[0] != 0).sum() <= 4


def test_make_codec_dispatch():
    base = dict(weight_decay=0, num_workers=W, num_clients=N_CLIENTS,
                lr_scale=0.05)
    cfg = FedConfig(mode="local_topk", error_type="local", k=3, **base)
    cfg = cfg.finalize(D)
    assert isinstance(make_codec(cfg), DenseCodec)
    cfg_s = FedConfig(mode="local_topk", error_type="local", k=3,
                      client_state="sparse", **base).finalize(D)
    codec = make_codec(cfg_s)
    assert isinstance(codec, SparseCodec) and codec.cap == 3
    cfg_k = FedConfig(mode="local_topk", error_type="local", k=3,
                      client_state="sketched", client_sketch_rows=3,
                      client_sketch_cols=32, **base).finalize(D)
    assert isinstance(make_codec(cfg_k), SketchedCodec)


def test_gather_scatter_roundtrip_device_sparse():
    codec = SparseCodec(d=12, cap=6)
    storage = codec.init_rows(5)
    rng = np.random.RandomState(1)
    rows = np.zeros((2, 12), np.float32)
    rows[0, rng.choice(12, 6, replace=False)] = rng.randn(6)
    rows[1, rng.choice(12, 4, replace=False)] = rng.randn(4)
    ids = np.array([1, 3])
    storage = scatter_rows(storage, ids, rows, codec)
    back = np.asarray(gather_rows(storage, ids, codec))
    np.testing.assert_array_equal(back, rows)
    # untouched rows still decode to zero
    others = np.asarray(gather_rows(storage, np.array([0, 2, 4]), codec))
    np.testing.assert_array_equal(others, np.zeros((3, 12), np.float32))
    # None storage (inactive field) passes through both directions
    assert gather_rows(None, ids, codec) is None
    assert scatter_rows(None, ids, rows, codec) is None


# ---------------------------------------------------------------------------
# trajectory equivalence: the acceptance contract
# ---------------------------------------------------------------------------

def test_sparse_offload_matches_dense_offload_bitwise():
    """Host placement shares ONE compiled round program across dense and
    sparse (the codec runs host-side in the arena), so with k >= d/2 the
    two trajectories are BITWISE identical — not allclose."""
    ln_d = make_learner(True, **SPARSE_KW)
    ln_s = make_learner(True, client_state="sparse", **SPARSE_KW)
    for r, (ids, batch, mask) in enumerate(rounds_data(8)):
        a = ln_d.train_round(ids, batch, mask)
        b = ln_s.train_round(ids, batch, mask)
        np.testing.assert_array_equal(a["loss"], b["loss"],
                                      err_msg=f"round {r}")
        np.testing.assert_array_equal(np.asarray(ln_d.state.weights),
                                      np.asarray(ln_s.state.weights),
                                      err_msg=f"round {r}")
    # the sparse arena really stores (cap,) pairs, not dense rows
    row = ln_s.host_clients["errors"][0]
    assert set(row) == {"idx", "val"} and row["val"].shape == (K_EXACT,)
    # ...and decodes to exactly the dense learner's row
    for i in range(N_CLIENTS):
        np.testing.assert_array_equal(
            np.asarray(ln_d.host_clients["errors"][i]),
            ln_s.codec.decode_row_np(ln_s.host_clients["errors"][i]),
            err_msg=f"errors[{i}]")


def test_sparse_device_matches_dense_device():
    """Device placement keeps the codec in-program (a different XLA
    program than dense), so weights match to tight tolerance while the
    per-round losses stay bitwise for the first rounds."""
    ln_d = make_learner(False, **SPARSE_KW)
    ln_s = make_learner(False, client_state="sparse", **SPARSE_KW)
    for r, (ids, batch, mask) in enumerate(rounds_data(3)):
        a = ln_d.train_round(ids, batch, mask)
        b = ln_s.train_round(ids, batch, mask)
        np.testing.assert_array_equal(a["loss"], b["loss"],
                                      err_msg=f"round {r}")
    np.testing.assert_allclose(np.asarray(ln_d.state.weights),
                               np.asarray(ln_s.state.weights),
                               rtol=0, atol=1e-6)
    # encoded device storage: {"idx": (n, cap), "val": (n, cap)}
    enc = ln_s.state.clients.errors
    assert set(enc) == {"idx", "val"}
    assert enc["val"].shape == (N_CLIENTS, K_EXACT)


def test_sparse_buffered_matches_dense_buffered():
    # the buffered server's cohort/apply programs gather/scatter through
    # the same codec; fault-free lock-step must stay equivalent
    ln_d = make_learner(False, server_mode="buffered", **SPARSE_KW)
    ln_s = make_learner(False, server_mode="buffered",
                        client_state="sparse", **SPARSE_KW)
    for r, (ids, batch, mask) in enumerate(rounds_data(3)):
        a = ln_d.finalize_round_metrics(
            ln_d.train_round_async(ids, batch, mask))
        b = ln_s.finalize_round_metrics(
            ln_s.train_round_async(ids, batch, mask))
        np.testing.assert_array_equal(a["loss"], b["loss"],
                                      err_msg=f"round {r}")
    np.testing.assert_allclose(np.asarray(ln_d.state.weights),
                               np.asarray(ln_s.state.weights),
                               rtol=0, atol=1e-6)


SKETCH_KW = dict(mode="local_topk", error_type="local", local_momentum=0,
                 k=6, client_sketch_rows=5, client_sketch_cols=64)


def test_sketched_e2e_within_eps_of_dense():
    """``--client_state sketched``: per-client error rows live as (r, c)
    CountSketch tables. Divergence from dense is bounded (heavy-hitter
    recovery + error feedback), so losses track within eps."""
    ln_d = make_learner(False, **SKETCH_KW)
    ln_k = make_learner(False, client_state="sketched", **SKETCH_KW)
    losses_d, losses_k = [], []
    for ids, batch, mask in rounds_data(8):
        losses_d.append(float(ln_d.train_round(ids, batch, mask)["loss"]))
        losses_k.append(float(ln_k.train_round(ids, batch, mask)["loss"]))
    assert np.all(np.isfinite(losses_k))
    assert abs(np.mean(losses_k[-3:]) - np.mean(losses_d[-3:])) < 0.25
    # weights stay in a bounded tube around the dense trajectory
    wd = np.asarray(ln_d.state.weights)
    wk = np.asarray(ln_k.state.weights)
    assert np.linalg.norm(wk - wd) < 0.5 * max(np.linalg.norm(wd), 1.0)
    # storage really is the (n, r, c) table
    assert ln_k.state.clients.errors["table"].shape == (N_CLIENTS, 5, 64)


# ---------------------------------------------------------------------------
# host arenas
# ---------------------------------------------------------------------------

def test_host_arena_shard_routing_and_roundtrip():
    base = dict(weight_decay=0, num_workers=W, num_clients=8, lr_scale=0.05)
    cfg = FedConfig(mode="local_topk", error_type="local",
                    local_momentum=0.9, k=4, client_state="sparse",
                    client_state_offload=True, **base).finalize(12)
    codec = make_codec(cfg)
    store = HostArenaStore(cfg, codec, num_shards=2)
    assert store.rows_per_shard == 4
    assert [store.owner(c) for c in range(8)] == [0] * 4 + [1] * 4
    rng = np.random.RandomState(0)
    rows = {}
    for cid in (1, 5, 7):  # both shards
        row = np.zeros(12, np.float32)
        row[rng.choice(12, 4, replace=False)] = rng.randn(4)
        rows[cid] = row
        store.set_row("errors", cid, codec.encode_row_np(row))
    for cid, row in rows.items():
        np.testing.assert_array_equal(
            codec.decode_row_np(store.row("errors", cid)), row)
    # traffic counters attribute reads/writes to the OWNING shard
    np.testing.assert_array_equal(store.shard_writes, [1, 2])
    np.testing.assert_array_equal(store.shard_reads, [1, 2])
    # O(n*k) bytes: idx+val caps at 8 bytes per entry per active field
    n_fields = sum(v is not None for v in store._arenas.values())
    assert store.nbytes() <= 8 * cfg.num_clients * codec.cap * n_fields


def test_host_arena_validation():
    base = dict(weight_decay=0, num_workers=W, num_clients=6, lr_scale=0.05)
    cfg = FedConfig(mode="local_topk", error_type="local", k=3,
                    client_state_offload=True, **base).finalize(12)
    codec = make_codec(cfg)
    with pytest.raises(ValueError, match="divisible"):
        HostArenaStore(cfg, codec, num_shards=4)
    store = HostArenaStore(cfg, codec, num_shards=2)
    with pytest.raises(IndexError, match="out of range"):
        store.row("errors", 6)
    # view quacks like the historical list-of-rows
    view = store.view("errors")
    assert len(view) == 6 and len(list(view)) == 6


# ---------------------------------------------------------------------------
# the graft-audit target (and its mutation) — the audit CAN fail
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_client_store_audit_passes_and_mutation_fails():
    from commefficient_tpu.analysis.targets import client_store_target
    good = client_store_target().audit(with_retrace=False)
    assert good.ok, format(good)
    # mutation: dense representation back on device — the (num_clients,
    # d) arena the footprint rule forbids must actually fire
    bad = client_store_target(mutate=True).audit(with_retrace=False)
    assert not bad.ok


# ---------------------------------------------------------------------------
# checkpoint fingerprint: --resume refuses a representation flip
# ---------------------------------------------------------------------------

def test_resume_refuses_representation_flip(tmp_path):
    from commefficient_tpu.training.preempt import config_fingerprint
    from commefficient_tpu.utils.checkpoint import (load_checkpoint,
                                                    save_checkpoint)
    args_d = types.SimpleNamespace(seed=0, client_state="dense")
    args_s = types.SimpleNamespace(seed=0, client_state="sparse")
    fp_d = config_fingerprint(args_d, "cv")
    fp_s = config_fingerprint(args_s, "cv")
    # dense is the compat default: not emitted, so pre-flag checkpoints
    # (no client_state key at all) keep resuming under dense
    assert "client_state" not in fp_d
    assert fp_s["client_state"] == "sparse"

    ln = make_learner(False, **SPARSE_KW)
    ids, batch, mask = rounds_data(1)[0]
    ln.train_round(ids, batch, mask)
    fn = save_checkpoint(str(tmp_path), ln, "fp", fingerprint=fp_d)
    with pytest.raises(ValueError, match="client_state"):
        load_checkpoint(fn, make_learner(False, **SPARSE_KW),
                        expect_fingerprint=fp_s)
    # matching fingerprint (and the pre-flag None case) load fine
    load_checkpoint(fn, make_learner(False, **SPARSE_KW),
                    expect_fingerprint=fp_d)


def test_sketched_fingerprint_pins_table_dims():
    from commefficient_tpu.training.preempt import config_fingerprint
    a = config_fingerprint(types.SimpleNamespace(
        client_state="sketched", client_sketch_rows=3,
        client_sketch_cols=128), "cv")
    b = config_fingerprint(types.SimpleNamespace(
        client_state="sketched", client_sketch_rows=3,
        client_sketch_cols=256), "cv")
    assert a["client_sketch_cols"] == 128
    assert a != b  # a (r, c) change is a loud resume mismatch


# ---------------------------------------------------------------------------
# fault model at 1M clients: per-round cost scales with W, not n
# ---------------------------------------------------------------------------

def test_fault_model_1m_lazy_and_w_scaled():
    fm = FaultModel(seed=7, num_clients=1_000_000, straggler_frac=0.2,
                    dropout_prob=0.1, crash_prob=0.05)
    # construction draws NOTHING per-client (the historical eager mask
    # was O(num_clients) before round one)
    assert fm._straggler_memo == {} and fm.fate_draws == 0
    R, Wc = 5, 8
    rng = np.random.RandomState(0)
    for r in range(R):
        ids = rng.choice(1_000_000, Wc, replace=False)
        fm.cohort_fates(r, ids)
    assert fm.fate_draws == R * Wc
    # only the sampled clients were ever materialized
    assert len(fm._straggler_memo) <= R * Wc


def test_fault_model_1m_order_independent():
    ids = np.random.RandomState(1).choice(1_000_000, 16, replace=False)
    fm1 = FaultModel(seed=7, num_clients=1_000_000, straggler_frac=0.2,
                     dropout_prob=0.1, crash_prob=0.05)
    fm2 = FaultModel(seed=7, num_clients=1_000_000, straggler_frac=0.2,
                     dropout_prob=0.1, crash_prob=0.05)
    s1, a1, l1 = fm1.cohort_fates(3, ids)
    perm = np.random.RandomState(2).permutation(16)
    s2, a2, l2 = fm2.cohort_fates(3, ids[perm])
    np.testing.assert_array_equal(s1[perm], s2)
    np.testing.assert_array_equal(a1[perm], a2)
    np.testing.assert_array_equal(l1[perm], l2)


@pytest.mark.parametrize("num_clients", [120, 1_000_000])
def test_sparse_arena_is_o_nk_and_round_traces_at_scale(trace_round,
                                                        num_clients):
    """The docs/SCALING.md memory model, O(num_clients * k + W * d): the
    sparse host arena's bytes track n * k (8 bytes an (idx, val) entry a
    field, three fields at most; anything near n * d * 4 means a dense
    arena came back), while the traced offload round's row input stays
    (W, d) whatever n is."""
    Wc, B, F, k = 8, 16, 8, 32
    model = TinyMLP(num_classes=10, hidden=32)          # d = 618
    cfg = FedConfig(mode="local_topk", k=k, error_type="local",
                    local_momentum=0.9, virtual_momentum=0, num_workers=Wc,
                    num_clients=num_clients, lr_scale=0.1,
                    client_state="sparse", client_state_offload=True)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), np.zeros((1, F), np.float32))
    assert ln.host_store.nbytes() <= 24 * num_clients * k
    # scattered ids, the way sampling walks the arena
    ids = np.random.RandomState(0).choice(num_clients, Wc, replace=False)
    _, out_rows, _ = trace_round(
        ln, ids, (np.zeros((Wc, B, F), np.float32),
                  np.zeros((Wc, B), np.int32)), np.ones((Wc, B), np.float32))
    assert {leaf.shape for leaf in jax.tree.leaves(out_rows)} == {
        (Wc, ln.cfg.grad_dim)}
