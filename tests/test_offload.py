"""Host-offloaded client state (config.client_state_offload).

The reference bounds per-client momentum/error state by HOST RAM, not
accelerator memory, by parking it in shared-memory tensors (reference
fed_aggregator.py:116-129, .share_memory_() at :125-128). The TPU-native
analog keeps those rows in pinned_host memory and moves only the sampled
rows to device each round (federated/round.py offload path +
api.HostOffloadPipeline; tests/test_offload_async.py pins the async
pipeline against this sync path). These tests pin the contract:
bit-identical trajectories to device-resident state, inert padded slots,
NaN-guard safety, and checkpoint roundtrip.
"""

import dataclasses

import jax
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.models import TinyMLP

N_CLIENTS = 6
W = 2


def make_learner(offload: bool, **cfg_kw):
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=W, num_clients=N_CLIENTS,
                    lr_scale=0.05, client_state_offload=offload, **cfg_kw)
    rng = np.random.RandomState(0)
    Xs = rng.randn(8, 8).astype(np.float32)
    return FedLearner(model, cfg, make_cv_loss(model), None,
                      jax.random.PRNGKey(1), Xs[:1])


def rounds_data(n_rounds, seed=0):
    """n_rounds of (ids, batch, mask) with rotating client subsets."""
    rng = np.random.RandomState(seed)
    out = []
    for r in range(n_rounds):
        ids = rng.choice(N_CLIENTS, W, replace=False)
        Xb = rng.randn(W, 4, 8).astype(np.float32)
        yb = rng.randint(0, 2, (W, 4)).astype(np.int32)
        mask = np.ones((W, 4), np.float32)
        out.append((ids, (Xb, yb), mask))
    return out


def host_row(ln, field, i):
    return np.asarray(ln.host_clients[field][i])


CFGS = [
    dict(mode="local_topk", error_type="local", local_momentum=0.9, k=3),
    dict(mode="local_topk", error_type="local", k=3, do_topk_down=True),
    dict(mode="true_topk", error_type="virtual", virtual_momentum=0.9,
         local_momentum=0.9, k=3),
]


@pytest.mark.parametrize("cfg_kw", CFGS,
                         ids=["local_topk", "topk_down", "truetopk_vel"])
def test_offload_matches_device_resident(cfg_kw):
    ln_dev = make_learner(False, **cfg_kw)
    ln_off = make_learner(True, **cfg_kw)
    assert ln_off._offload
    # the two builds compile DIFFERENT XLA programs (scatter vs row
    # passthrough), so float reductions may reassociate — equality is
    # tight-tolerance, not bitwise; integers/bytes must match exactly
    for ids, batch, mask in rounds_data(5):
        a = ln_dev.train_round(ids, batch, mask)
        b = ln_off.train_round(ids, batch, mask)
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=0, atol=1e-6)
        assert a["upload_bytes"] == b["upload_bytes"]
        assert a["download_bytes"] == b["download_bytes"]
    np.testing.assert_allclose(np.asarray(ln_dev.state.weights),
                               np.asarray(ln_off.state.weights),
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(ln_dev.state.client_last_round),
        np.asarray(ln_off.state.client_last_round))
    # every host row == the device-resident learner's state row
    for field in ("velocities", "errors", "weights"):
        dev_arr = getattr(ln_dev.state.clients, field)
        host_lst = ln_off.host_clients[field]
        assert (dev_arr is None) == (host_lst is None)
        if dev_arr is None:
            continue
        for i in range(N_CLIENTS):
            np.testing.assert_allclose(np.asarray(dev_arr[i]),
                                       host_row(ln_off, field, i),
                                       rtol=0, atol=1e-6,
                                       err_msg=f"{field}[{i}]")


def test_offload_padded_slot_cannot_clobber_real_update():
    # a padded slot (zero mask) aliases id 0 in the SAME round where
    # client 0 really participates; the host put-back must skip it
    cfg_kw = dict(mode="local_topk", error_type="local",
                  local_momentum=0.9, k=3)
    ln_dev = make_learner(False, **cfg_kw)
    ln_off = make_learner(True, **cfg_kw)
    rng = np.random.RandomState(3)
    Xb = rng.randn(W, 4, 8).astype(np.float32)
    yb = rng.randint(0, 2, (W, 4)).astype(np.int32)
    ids = np.array([0, 0])
    mask = np.stack([np.ones(4, np.float32), np.zeros(4, np.float32)])
    a = ln_dev.train_round(ids, (Xb, yb), mask)
    b = ln_off.train_round(ids, (Xb, yb), mask)
    np.testing.assert_array_equal(a["loss"], b["loss"])
    for i in range(N_CLIENTS):
        np.testing.assert_array_equal(
            np.asarray(ln_dev.state.clients.errors[i]),
            host_row(ln_off, "errors", i))
    # client 0's error row must be the REAL update, not zeros
    assert np.any(host_row(ln_off, "errors", 0) != 0)


def test_offload_abort_keeps_host_rows_frozen():
    cfg_kw = dict(mode="local_topk", error_type="local",
                  local_momentum=0.9, k=3, nan_threshold=1e-9)
    ln = make_learner(True, **cfg_kw)
    (ids, batch, mask), = rounds_data(1)
    before = [host_row(ln, "errors", i) for i in range(N_CLIENTS)]
    out = ln.train_round(ids, batch, mask)
    assert out["aborted"]  # any finite loss breaches the 1e-9 threshold
    for i in range(N_CLIENTS):
        np.testing.assert_array_equal(host_row(ln, "errors", i), before[i])


def test_offload_rejects_scan():
    ln = make_learner(True, mode="local_topk", error_type="local", k=3)
    with pytest.raises(ValueError, match="scan_rounds=1"):
        ln.scan_window(4)
    with pytest.raises(ValueError, match="scan_rounds=1"):
        ln.train_rounds_scan(np.zeros((2, W), np.int32), (), ())


def test_offload_on_mesh_matches_single_host():
    # offload used to hard-raise on any mesh; the mesh-sharded arenas
    # (federated/client_store.HostArenaStore) made it a supported
    # placement — trajectories must match the single-host offload run
    from commefficient_tpu.training.args import parse_mesh
    cfg_kw = dict(mode="local_topk", error_type="local",
                  local_momentum=0.9, k=3)
    ln_one = make_learner(True, **cfg_kw)
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=W, num_clients=N_CLIENTS,
                    lr_scale=0.05, client_state_offload=True, **cfg_kw)
    mesh = parse_mesh("clients=2")
    ln_mesh = FedLearner(model, cfg, make_cv_loss(model), None,
                         jax.random.PRNGKey(1),
                         np.random.RandomState(0).randn(1, 8)
                         .astype(np.float32), mesh=mesh)
    assert ln_mesh._offload
    assert ln_mesh.host_store.num_shards == 2
    for ids, batch, mask in rounds_data(3):
        a = ln_one.train_round(ids, batch, mask)
        b = ln_mesh.train_round(ids, batch, mask)
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=0, atol=1e-6)
        assert a["upload_bytes"] == b["upload_bytes"]
        assert a["download_bytes"] == b["download_bytes"]
    np.testing.assert_allclose(np.asarray(ln_one.state.weights),
                               np.asarray(ln_mesh.state.weights),
                               rtol=0, atol=1e-6)
    for i in range(N_CLIENTS):
        np.testing.assert_allclose(host_row(ln_one, "errors", i),
                                   host_row(ln_mesh, "errors", i),
                                   rtol=0, atol=1e-6)
    # ids were routed to their owning shards, not all to shard 0
    assert ln_mesh.host_store.shard_reads.sum() > 0
    assert ln_mesh.host_store.shard_writes.sum() > 0


def test_offload_noop_without_client_state():
    # uncompressed has no per-client rows: the flag must be a clean no-op
    ln = make_learner(True, mode="uncompressed", error_type="none")
    assert not ln._offload and ln.host_clients is None
    (ids, batch, mask), = rounds_data(1)
    out = ln.train_round(ids, batch, mask)
    assert np.isfinite(out["loss"])


def test_offload_checkpoint_roundtrip(tmp_path):
    from commefficient_tpu.utils.checkpoint import (load_checkpoint,
                                                    save_checkpoint)
    cfg_kw = dict(mode="local_topk", error_type="local",
                  local_momentum=0.9, k=3)
    ln = make_learner(True, **cfg_kw)
    data = rounds_data(4)
    for ids, batch, mask in data[:2]:
        ln.train_round(ids, batch, mask)
    fn = save_checkpoint(str(tmp_path), ln, "off")
    # resumed learner continues identically to the uninterrupted one
    ln2 = make_learner(True, **cfg_kw)
    load_checkpoint(fn, ln2)
    ln2.rng = ln.rng
    for ids, batch, mask in data[2:]:
        a = ln.train_round(ids, batch, mask)
        b = ln2.train_round(ids, batch, mask)
        np.testing.assert_array_equal(a["loss"], b["loss"])
    np.testing.assert_array_equal(np.asarray(ln.state.weights),
                                  np.asarray(ln2.state.weights))
    for i in range(N_CLIENTS):
        np.testing.assert_array_equal(host_row(ln, "errors", i),
                                      host_row(ln2, "errors", i))
    # a device-resident learner must refuse an offloaded checkpoint
    ln3 = make_learner(False, **cfg_kw)
    with pytest.raises(ValueError, match="mismatch"):
        load_checkpoint(fn, ln3)


def test_offload_round_traces_at_resnet9_scale(trace_round):
    """The offload round's signature at a model's size: ResNet-9
    (d = 6.57 M, bf16 compute) under local_topk with local momentum and
    local error, the sampled clients' rows gathered from the host arena
    and handed to the round as its second argument. Traced, not run."""
    from commefficient_tpu.models import ResNet9
    Wr, B, N = 4, 16, 12
    model = ResNet9(num_classes=10, dtype="bfloat16")
    cfg = FedConfig(mode="local_topk", k=50_000, error_type="local",
                    local_momentum=0.9, virtual_momentum=0, num_workers=Wr,
                    num_clients=N, lr_scale=0.1, client_state_offload=True)
    images = np.zeros((Wr, B, 32, 32, 3), np.float32)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), images[0][:1])
    assert ln._offload
    state, out_rows, metrics = trace_round(
        ln, np.arange(Wr), (images, np.zeros((Wr, B), np.int32)),
        np.ones((Wr, B), np.float32))
    assert state.weights.shape == (ln.cfg.grad_dim,)
    d = ln.cfg.grad_dim
    assert {leaf.shape for leaf in jax.tree.leaves(out_rows)} == {(Wr, d)}
