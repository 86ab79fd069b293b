"""Mesh-sharded round == single-device round, bit-for-bit-ish.

The reference's key invariant is that splitting clients across executors
doesn't change the math (sum of transmits / total datapoints, reference
fed_aggregator.py:332). Here the analogous invariant: the same round on an
8-device 'clients' mesh and on one device produces the same trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.models import TinyMLP
from commefficient_tpu.parallel import make_mesh


def make_problem():
    rng = np.random.RandomState(0)
    Xs = rng.randn(8, 16, 8).astype(np.float32)  # 8 workers x 16 items
    ys = (Xs[:, :, 0] > 0).astype(np.int32)
    ids = np.arange(8)
    mask = np.ones((8, 16), np.float32)
    return ids, (Xs, ys), mask


def make_learner(cfg_kw, mesh):
    model = TinyMLP(num_classes=2, hidden=8)
    cfg = FedConfig(num_workers=8, num_clients=8, lr_scale=0.1,
                    weight_decay=0, **cfg_kw)
    return FedLearner(model, cfg, make_cv_loss(model), None,
                      jax.random.PRNGKey(0), make_problem()[1][0][0][:1],
                      mesh=mesh)


def run(cfg_kw, mesh, rounds=3):
    ids, batch, mask = make_problem()
    ln = make_learner(cfg_kw, mesh)
    outs = [ln.train_round(ids, batch, mask) for _ in range(rounds)]
    return np.asarray(ln.state.weights), outs


@pytest.mark.parametrize("cfg_kw", [
    dict(mode="uncompressed", virtual_momentum=0.9, error_type="none"),
    dict(mode="true_topk", error_type="virtual", k=20, virtual_momentum=0.9),
    dict(mode="local_topk", error_type="local", k=20, local_momentum=0.9),
    dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
         k=20, num_rows=3, num_cols=500),
    dict(mode="fedavg", error_type="none", local_batch_size=-1,
         fedavg_batch_size=8),
])
def test_mesh_matches_single_device(cfg_kw):
    assert len(jax.devices()) >= 8
    w_single, outs_single = run(cfg_kw, mesh=None)
    w_mesh, outs_mesh = run(cfg_kw, mesh=make_mesh(8))
    np.testing.assert_allclose(w_mesh, w_single, rtol=2e-4, atol=2e-5)
    for a, b in zip(outs_single, outs_mesh):
        assert a["loss"] == pytest.approx(b["loss"], rel=2e-4)
        assert a["download_bytes"] == b["download_bytes"]
        assert a["upload_bytes"] == b["upload_bytes"]


@pytest.mark.parametrize("cfg_kw", [
    dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
         k=20, num_rows=3, num_cols=500),
    dict(mode="true_topk", error_type="virtual", k=20, virtual_momentum=0.9),
], ids=["sketch", "true_topk"])
def test_mesh_round_runs_kernels_per_replica(cfg_kw):
    """The TPU compiler refuses to partition a Pallas kernel ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map"), so on a mesh the aggregate side of the round — the
    sketch of the reduced gradient and the server update, replicated
    compute on replicated state — runs inside a shard_map
    (parallel/mesh.on_each_replica): every pallas_call of the mesh round
    sits under one, and the trajectory is still the single-device one."""
    from commefficient_tpu.analysis.walker import iter_eqns
    from commefficient_tpu.ops.sketch_kernels import force_dispatch

    with force_dispatch("kernel"):
        w_single, _ = run(cfg_kw, mesh=None, rounds=2)
        ids, batch, mask = make_problem()
        ln = make_learner(cfg_kw, make_mesh(4))
        jaxpr = jax.make_jaxpr(ln._round)(
            ln.state, jnp.asarray(ids, jnp.int32),
            tuple(jnp.asarray(c) for c in batch), jnp.asarray(mask),
            jnp.float32(0.1), jax.random.PRNGKey(1))
        kernel_paths = [site.path for site in iter_eqns(jaxpr)
                        if site.primitive == "pallas_call"]
        assert kernel_paths
        assert all("shard_map" in path for path in kernel_paths)
        for _ in range(2):
            ln.train_round(ids, batch, mask)
    np.testing.assert_allclose(np.asarray(ln.state.weights), w_single,
                               rtol=2e-4, atol=2e-5)


def test_mesh_divisibility_validation():
    model = TinyMLP(num_classes=2, hidden=8)
    cfg = FedConfig(mode="uncompressed", error_type="none", num_workers=6,
                    num_clients=8, lr_scale=0.1)
    with pytest.raises(ValueError, match="divisible"):
        FedLearner(model, cfg, make_cv_loss(model), None,
                   jax.random.PRNGKey(0), np.zeros((1, 8), np.float32),
                   mesh=make_mesh(8))


def test_state_actually_sharded():
    mesh = make_mesh(8)
    model = TinyMLP(num_classes=2, hidden=8)
    cfg = FedConfig(mode="local_topk", error_type="local", k=5,
                    local_momentum=0.9, num_workers=8, num_clients=8,
                    lr_scale=0.1)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), np.zeros((1, 8), np.float32),
                    mesh=mesh)
    sh = ln.state.clients.errors.sharding
    assert sh.spec == jax.sharding.PartitionSpec("clients")
    # each device holds 1/8 of the rows
    shard_shapes = {s.data.shape for s in ln.state.clients.errors.addressable_shards}
    assert shard_shapes == {(1, ln.cfg.grad_size)}


def _gpt2_fed_problem(T=16, W=2, B=2):
    from commefficient_tpu.federated.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    rng = np.random.RandomState(0)
    gcfg = GPT2Config.tiny()
    gcfg.n_positions = T
    model = GPT2DoubleHeads(gcfg)
    ids = rng.randint(0, 200, (W, B, 1, T)).astype(np.int32)
    types = rng.randint(0, 3, (W, B, 1, T)).astype(np.int32)
    mc = np.full((W, B, 1), T - 1, np.int32)
    labels = np.where(rng.rand(W, B, 1, T) < 0.5, ids, -1).astype(np.int32)
    mcl = np.zeros((W, B), np.int32)
    batch = (ids, mc, labels, mcl, types)
    mask = np.ones((W, B), np.float32)

    class _Wrap:
        def init(self, rng_, sample_in, train):
            return model.init(rng_, *sample_in, train=train)

        def apply(self, *a, **k):
            return model.apply(*a, **k)

    sample_in = (ids[0][:1], types[0][:1], mc[0][:1])
    loss = make_gpt2_train_loss(model)
    return _Wrap(), loss, sample_in, batch, mask


@pytest.mark.slow  # ~9s compile on 1-core CPU; the clients x model mesh
# round runs end-to-end in __graft_entry__.dryrun_multichip part 4
def test_clients_x_model_mesh_matches_single_device():
    # 2D federation (round-2 verdict gap #3): the client vmap runs over a
    # model axis carrying the Megatron TP layout; weights/state rows are
    # coordinate-split over 'model' (parallel/mesh.fed_state_shardings),
    # and the trajectory matches the unsharded round.
    from commefficient_tpu.parallel.tp import gpt2_tp_specs

    wrap, loss, sample_in, batch, mask = _gpt2_fed_problem()
    cfg = FedConfig(mode="uncompressed", error_type="none",
                    virtual_momentum=0.9, weight_decay=0,
                    num_workers=2, num_clients=4, lr_scale=0.05,
                    max_seq_len=16)

    def run(mesh, specs):
        ln = FedLearner(wrap, cfg, loss, None, jax.random.PRNGKey(0),
                        sample_in, mesh=mesh, param_specs=specs)
        outs = [ln.train_round(np.arange(2), batch, mask)
                for _ in range(3)]
        return np.asarray(ln.state.weights), outs

    w1, o1 = run(None, None)
    mesh = make_mesh(8, model=4)  # (clients=2, model=4)
    ln_probe = FedLearner(wrap, cfg, loss, None, jax.random.PRNGKey(0),
                          sample_in)
    specs = gpt2_tp_specs(ln_probe.unflatten(ln_probe.state.weights))
    w2, o2 = run(mesh, specs)
    # the 2D mesh pads the flat vector to the model axis; pads must be
    # exactly zero and the logical prefix must match the unsharded run
    d = len(w1)
    assert np.all(w2[d:] == 0.0)
    np.testing.assert_allclose(w2[:d], w1, rtol=2e-4, atol=2e-5)
    for a, b in zip(o1, o2):
        assert a["loss"] == pytest.approx(b["loss"], rel=2e-4)
    # weights really are coordinate-split over the model axis
    ln = FedLearner(wrap, cfg, loss, None, jax.random.PRNGKey(0),
                    sample_in, mesh=mesh, param_specs=specs)
    shard_shapes = {s.data.shape for s in ln.state.weights.addressable_shards}
    d = ln.cfg.grad_size
    assert all(sh[0] < d for sh in shard_shapes), shard_shapes


def test_clients_x_model_sketch_nondivisible_cols():
    # review finding: sketch tables with c not divisible by the model axis
    # must replicate instead of crashing at shard_state
    from commefficient_tpu.models import TinyMLP
    model = TinyMLP(num_classes=2, hidden=8)
    cfg = FedConfig(mode="sketch", error_type="virtual", k=5, num_rows=2,
                    num_cols=100, sketch_scheme="global",
                    virtual_momentum=0.9, weight_decay=0,
                    num_workers=2, num_clients=4, lr_scale=0.05)
    mesh = make_mesh(8, model=4)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), np.zeros((1, 8), np.float32),
                    mesh=mesh)
    rng = np.random.RandomState(0)
    Xs = rng.randn(2, 4, 8).astype(np.float32)
    ys = (Xs[:, :, 0] > 0).astype(np.int32)
    out = ln.train_round(np.arange(2), (Xs, ys),
                         np.ones((2, 4), np.float32))
    assert np.isfinite(out["loss"])


def test_a_state_handed_in_on_one_device_is_placed_on_the_mesh():
    """Weights made on one device and swapped into a mesh learner's state
    (how a harness sets its own initial weights) are put where the round's
    in_shardings want them by the ``state`` setter — explicitly, so the
    guarded evaluate and dispatch do not have to; the round's own outputs
    pass through untouched."""
    ids, batch, mask = make_problem()
    cfg_kw = dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                  k=20, num_rows=3, num_cols=500)
    ln = make_learner(cfg_kw, make_mesh(8))
    want = ln.state.weights.sharding
    w0 = jnp.asarray(np.asarray(ln.state.weights) * 0.5)
    assert w0.sharding != want
    ln.state = ln.state.replace(weights=w0)
    assert ln.state.weights.sharding == want
    np.testing.assert_array_equal(np.asarray(ln.state.weights),
                                  np.asarray(w0))
    ln.evaluate([((batch[0][0], batch[1][0]), np.ones(16, np.float32))])
    out = ln.train_round(ids, batch, mask)        # under the guard
    assert np.isfinite(out["loss"])
    held = ln.state
    ln.state = held
    assert ln.state is held
    # and one device off-mesh stays as it is
    single = make_learner(cfg_kw, None)
    s = single.state.replace(weights=w0)
    single.state = s
    assert single.state is s
