"""Aux-parity tests: checkpoint/resume, worker DP, finetune freezing,
loggers, schedules."""

import io
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.losses import make_cv_loss, make_regression_loss
from commefficient_tpu.models import TinyMLP, ToyLinear
from commefficient_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
from commefficient_tpu.utils.finetune import head_only_mask
from commefficient_tpu.utils.logging import TSVLogger, TableLogger, Timer
from commefficient_tpu.utils.schedules import PiecewiseLinear, cifar_lr_schedule

X = np.asarray([[0.0], [1.0], [2.0], [3.0]], np.float32)


def make_learner(**cfg_kw):
    cfg = FedConfig(mode="uncompressed", virtual_momentum=0.9,
                    local_momentum=0, error_type="none", weight_decay=0,
                    num_workers=1, num_clients=2, lr_scale=0.02, **cfg_kw)
    model = ToyLinear()
    return FedLearner(model, cfg, make_regression_loss(model), None,
                      jax.random.PRNGKey(0), X[:1])


def batch():
    return np.array([0]), (X[None], X[None]), np.ones((1, 4), np.float32)


def test_checkpoint_midtraining_resume(tmp_path):
    # The reference can only save final weights (SURVEY.md §5: 'No
    # mid-training resume'); we checkpoint the whole FedState.
    ids, b, m = batch()
    a = make_learner()
    a.train_round(ids, b, m)
    fn = save_checkpoint(str(tmp_path), a, "toy")
    a.train_round(ids, b, m)
    w_expected = float(a.state.weights[0])

    fresh = make_learner()
    load_checkpoint(fn, fresh)
    assert fresh.rounds_done == 1
    fresh.train_round(ids, b, m)
    # momentum state survived the round trip: same trajectory
    assert float(fresh.state.weights[0]) == pytest.approx(w_expected,
                                                          abs=1e-7)


def test_checkpoint_mode_mismatch_rejected_by_leaf_path(tmp_path):
    # v2 checkpoints carry the pytree key-path list; loading into a learner
    # with DIFFERENT state leaves must fail loudly by name — never shift
    # equal-shaped adjacent leaves into the wrong slots (ADVICE r3)
    ids, b, m = batch()
    a = make_learner()   # uncompressed: no per-client rows
    a.train_round(ids, b, m)
    fn = save_checkpoint(str(tmp_path), a, "toy")
    cfg = FedConfig(mode="local_topk", error_type="local", k=1,
                    virtual_momentum=0.0, local_momentum=0.9, weight_decay=0,
                    num_workers=1, num_clients=2, lr_scale=0.02)
    model = ToyLinear()
    other = FedLearner(model, cfg, make_regression_loss(model), None,
                       jax.random.PRNGKey(0), X[:1])
    with pytest.raises(ValueError, match="missing state leaf"):
        load_checkpoint(fn, other)


def test_checkpoint_v2_backfills_missing_aborted_leaf(tmp_path):
    # a v2 file written before a whitelisted state field existed loads with
    # the documented backfill (checkpoint._BACKFILL), keyed by path — not
    # by array-count inference
    import json as pyjson
    ids, b, m = batch()
    a = make_learner()
    a.train_round(ids, b, m)
    fn = save_checkpoint(str(tmp_path), a, "toy")
    with np.load(fn) as z:
        data = {k: z[k] for k in z.files}
    paths = pyjson.loads(str(data["leaf_paths"]))
    drop = next(i for i, p in enumerate(paths) if p == ".aborted")
    # rewrite the file without the aborted leaf (renumber the tail)
    arrs = [data[f"arr_{i}"] for i in range(len(paths))]
    del arrs[drop], paths[drop]
    # a pre-v3 file has none of the v3 keys (digest/rng/cursor/fingerprint)
    v3_only = ("digest", "learner_rng", "cursor", "fingerprint")
    data = {k: v for k, v in data.items()
            if not k.startswith("arr_") and k not in v3_only}
    data["format_version"] = np.asarray(2)
    data["leaf_paths"] = np.asarray(pyjson.dumps(paths))
    np.savez(fn, **data, **{f"arr_{i}": x for i, x in enumerate(arrs)})
    fresh = make_learner()
    load_checkpoint(fn, fresh)
    assert bool(np.asarray(fresh.state.aborted)) is False
    assert fresh.rounds_done == 1


def test_load_checkpoint_mismatch_leaves_learner_untouched(tmp_path):
    # transactional load: a rejected checkpoint must not half-restore —
    # state, rounds_done, byte totals, and rng all stay exactly as they
    # were (the pre-v3 loader overwrote state BEFORE host-row validation)
    ids, b, m = batch()
    a = make_learner()
    a.train_round(ids, b, m)
    fn = save_checkpoint(str(tmp_path), a, "toy")
    # a learner whose state tree has MORE leaves (local_topk error rows)
    cfg = FedConfig(mode="local_topk", error_type="local", k=1,
                    virtual_momentum=0.0, local_momentum=0.9, weight_decay=0,
                    num_workers=1, num_clients=2, lr_scale=0.02)
    model = ToyLinear()
    other = FedLearner(model, cfg, make_regression_loss(model), None,
                       jax.random.PRNGKey(0), X[:1])
    other.train_round(ids, b, m)
    before = jax.tree_util.tree_map(np.asarray, other.state)
    rounds, down, up = (other.rounds_done, other.total_download_bytes,
                        other.total_upload_bytes)
    rng_before = np.asarray(other.rng)
    with pytest.raises(ValueError, match="missing state leaf"):
        load_checkpoint(fn, other)
    after = jax.tree_util.tree_map(np.asarray, other.state)
    for p, q in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(p, q)
    assert (other.rounds_done, other.total_download_bytes,
            other.total_upload_bytes) == (rounds, down, up)
    np.testing.assert_array_equal(np.asarray(other.rng), rng_before)


def test_worker_dp_noise_and_clip():
    ids, b, m = batch()
    noisy = make_learner(do_dp=True, dp_mode="worker", noise_multiplier=0.5,
                         l2_norm_clip=0.1)
    clean = make_learner()
    noisy.train_round(ids, b, m)
    clean.train_round(ids, b, m)
    w_noisy = float(noisy.state.weights[0])
    w_clean = float(clean.state.weights[0])
    assert w_noisy != pytest.approx(w_clean, abs=1e-9)
    # clip bounds the update magnitude: |mean grad| clipped to 0.1 (+noise)
    assert abs(w_noisy) < abs(w_clean)


def test_finetune_head_only_mask_freezes_body():
    model = TinyMLP(num_classes=2, hidden=4)
    xs = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    ys = (xs[:, 0] > 0).astype(np.int32)
    cfg = FedConfig(mode="uncompressed", virtual_momentum=0, local_momentum=0,
                    error_type="none", weight_decay=0, num_workers=1,
                    num_clients=2, lr_scale=0.1)
    params = model.init(jax.random.PRNGKey(1), xs[:1],
                        train=False)["params"]
    mask = head_only_mask(params)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), xs[:1], init_params=params,
                    trainable_mask=mask)
    w0 = np.asarray(ln.state.weights).copy()
    ln.train_round(np.array([0]), (xs[None], ys[None]),
                   np.ones((1, 8), np.float32))
    w1 = np.asarray(ln.state.weights)
    changed = w1 != w0
    frozen = np.asarray(mask) == 0
    assert not np.any(changed & frozen)      # body untouched
    assert np.any(changed & ~frozen)         # head moved


def test_finetune_mask_applies_before_compression():
    # with local_topk, frozen-body gradients must not consume the k budget
    # (the mask is applied client-side, before top-k — like the reference's
    # requires_grad=False)
    model = TinyMLP(num_classes=2, hidden=4)
    xs = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    ys = (xs[:, 0] > 0).astype(np.int32)
    params = model.init(jax.random.PRNGKey(1), xs[:1],
                        train=False)["params"]
    mask = head_only_mask(params)
    k = int(np.sum(np.asarray(mask) > 0))  # k == head size
    cfg = FedConfig(mode="local_topk", error_type="none", k=k,
                    virtual_momentum=0, local_momentum=0, weight_decay=0,
                    num_workers=1, num_clients=2, lr_scale=0.1)
    ln = FedLearner(model, cfg, make_cv_loss(model), None,
                    jax.random.PRNGKey(0), xs[:1], init_params=params,
                    trainable_mask=mask)
    w0 = np.asarray(ln.state.weights).copy()
    for _ in range(3):
        ln.train_round(np.array([0]), (xs[None], ys[None]),
                       np.ones((1, 8), np.float32))
    w1 = np.asarray(ln.state.weights)
    head = np.asarray(mask) > 0
    # the entire k budget reached the head: it moved substantially
    assert np.sum((w0 != w1) & head) > 0
    assert not np.any((w0 != w1) & ~head)


def test_load_pretrained_for_finetune(tmp_path):
    from commefficient_tpu.utils.finetune import load_pretrained_for_finetune
    from commefficient_tpu.utils.params import flatten_params

    model = TinyMLP(num_classes=2, hidden=4)
    xs = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    ys = (xs[:, 0] > 0).astype(np.int32)
    cfg = FedConfig(mode="uncompressed", virtual_momentum=0, local_momentum=0,
                    error_type="none", weight_decay=0, num_workers=1,
                    num_clients=2, lr_scale=0.1)
    pre = FedLearner(model, cfg, make_cv_loss(model), None,
                     jax.random.PRNGKey(0), xs[:1])
    for _ in range(2):
        pre.train_round(np.array([0]), (xs[None], ys[None]),
                        np.ones((1, 8), np.float32))
    fn = save_checkpoint(str(tmp_path), pre, "TinyMLP")

    init_params, mask = load_pretrained_for_finetune(
        model, jax.random.PRNGKey(7), xs[:1], fn)
    flat, _ = flatten_params(init_params)
    trained = np.asarray(pre.state.weights)
    m = np.asarray(mask)
    # body coordinates come from the checkpoint, head is fresh (not equal to
    # the trained head, which moved away from any fresh init)
    np.testing.assert_array_equal(np.asarray(flat)[m == 0], trained[m == 0])
    assert np.any(np.asarray(flat)[m == 1] != trained[m == 1])
    # directory form resolves to the single .npz inside
    init_params2, _ = load_pretrained_for_finetune(
        model, jax.random.PRNGKey(7), xs[:1], str(tmp_path))
    flat2, _ = flatten_params(init_params2)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(flat2))


def test_finetune_head_swap_across_num_classes(tmp_path):
    # pretrain with 2 classes, finetune with 3: body restored per-leaf from
    # the checkpoint metadata, head fresh + alone trainable (the reference's
    # primary finetune use, cv_train.py:377-384)
    from commefficient_tpu.utils.finetune import load_pretrained_for_finetune
    from commefficient_tpu.utils.params import flatten_params

    xs = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    ys = (xs[:, 0] > 0).astype(np.int32)
    cfg = FedConfig(mode="uncompressed", virtual_momentum=0, local_momentum=0,
                    error_type="none", weight_decay=0, num_workers=1,
                    num_clients=2, lr_scale=0.1)
    pre_model = TinyMLP(num_classes=2)
    pre = FedLearner(pre_model, cfg, make_cv_loss(pre_model), None,
                     jax.random.PRNGKey(0), xs[:1])
    pre.train_round(np.array([0]), (xs[None], ys[None]),
                    np.ones((1, 8), np.float32))
    fn = save_checkpoint(str(tmp_path), pre, "TinyMLP",
                         meta={"model": "TinyMLP", "num_classes": 2})

    new_model = TinyMLP(num_classes=3)
    init_params, mask = load_pretrained_for_finetune(
        new_model, jax.random.PRNGKey(7), xs[:1], fn)
    new_flat, _ = flatten_params(init_params)
    m = np.asarray(mask)
    old_body = np.asarray(pre.state.weights)[
        np.asarray(head_only_mask(pre.unflatten(pre.state.weights))) == 0]
    np.testing.assert_array_equal(np.asarray(new_flat)[m == 0], old_body)
    assert int(m.sum()) > 0


def test_scalar_writer_tsv_roundtrip(tmp_path):
    from commefficient_tpu.utils.logging import ScalarWriter
    w = ScalarWriter(str(tmp_path / "run"))
    w.add_scalar("test_acc", 0.5, 1)
    w.add_scalar("test_acc", 0.75, 2)
    w.close()
    import os
    files = []
    for root, _, fns in os.walk(tmp_path):
        files += [os.path.join(root, f) for f in fns]
    assert files, "writer produced no output files"
    if any(f.endswith("scalars.tsv") for f in files):
        content = open([f for f in files if f.endswith("scalars.tsv")][0]).read()
        assert "1\ttest_acc\t0.5" in content


def test_schedules():
    s = cifar_lr_schedule(0.4, 5, 24)
    assert s(0) == 0
    assert s(5) == pytest.approx(0.4)
    assert s(24) == pytest.approx(0.0)
    assert s(30) == pytest.approx(0.0)       # clamped
    p = PiecewiseLinear([0, 2], [1.0, 3.0])
    assert p(1) == pytest.approx(2.0)


def test_loggers(capsys):
    t = TableLogger()
    t.append({"epoch": 1, "loss": 0.5})
    t.append({"epoch": 2, "loss": 0.25})
    out = capsys.readouterr().out
    assert "epoch" in out and "0.2500" in out
    tsv = TSVLogger()
    tsv.append({"epoch": 1, "total_time": 3600, "test_acc": 0.9})
    assert "1\t1.00000000\t90.00" in str(tsv)
    timer = Timer()
    dt = timer()
    assert dt >= 0 and timer.total_time >= dt


def test_fractional_final_epoch(tmp_path):
    """Fractional --num_epochs truncates the LAST epoch's round count
    (ref cv_train.py:100-106, 194-196), not just the LR schedule."""
    from commefficient_tpu.data import FedBatcher
    from commefficient_tpu.training.args import build_parser
    from commefficient_tpu.training.cv import make_dataset, train

    argv = ["--mode", "uncompressed", "--error_type", "none",
            "--model", "TinyMLP",
            "--dataset_name", "Digits", "--dataset_dir", str(tmp_path),
            "--num_workers", "2", "--local_batch_size", "8",
            "--valid_batch_size", "128", "--lr_scale", "0.01",
            "--num_epochs", "1.5", "--seed", "3"]
    args = build_parser().parse_args(argv)
    train_set = make_dataset(args, train=True)
    spe = FedBatcher(train_set, args.num_workers, args.local_batch_size,
                     seed=args.seed).steps_per_epoch()
    assert spe >= 2  # the truncation must be observable
    learner, row = train(args, log=False)
    assert row["epoch"] == 2
    assert learner.rounds_done == spe + max(1, int(round(spe * 0.5)))


@pytest.mark.parametrize("env_dir", [None, "/some/dir"],
                         ids=["checkout_default", "placed_from_outside"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """The persistent compile cache is placed from OUTSIDE when
    JAX_COMPILATION_CACHE_DIR is set (nothing is set in code); otherwise
    it is the fixed <checkout>/.jax_cache — never a temp name."""
    import os

    from commefficient_tpu.utils import compile_cache
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert compile_cache.place_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.place_compile_cache() == env_dir
        assert updates == []
