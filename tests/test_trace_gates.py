"""Trace gates at published shapes: programs of the package traced with
``jax.eval_shape`` on the CPU at the size a user runs them, so that
signature drift, a shape bug or rot in an example's flags fails in tier-1
and not on the chip. Nothing compiles or runs. The gates of one module
live beside that module's other tests (flash attention, offload, client
store, decode, paged serving, speculation, KV quantization); the one here
is the CV entry point's learner, which no other file builds at full size.
"""

import os
import re
import shlex

import jax
import numpy as np
import pytest

EXAMPLE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "cifar10_fetchsgd.sh")


def example_flags(path):
    """The flags of the script's one ``python -m`` command, up to
    ``"$@"``, with the data flags taken out (the gate runs on Synthetic:
    CIFAR-10's shapes, no files)."""
    with open(path) as f:
        command = re.search(r"python -m commefficient_tpu\.training\.cv(.*?)"
                            r'"\$@"', f.read(), re.S).group(1)
    flags = shlex.split(command.replace("\\\n", " "))
    for name in ("--dataset_name", "--dataset_dir"):
        at = flags.index(name)
        del flags[at:at + 2]
    return flags


@pytest.fixture(scope="module")
def fetchsgd_learner():
    """The learner ``training/cv.py`` builds for the FetchSGD headline
    run (ResNet-9, d = 6.57 M, 5 x 500 000 sketch, k = 50 000, 8 clients
    x 32 images a round), and one round's cohort."""
    from commefficient_tpu.training.args import build_parser
    from commefficient_tpu.training.cv import build_learner
    args = build_parser().parse_args(
        example_flags(EXAMPLE) + ["--dataset_name", "Synthetic"])
    W, B = args.num_workers, args.local_batch_size
    rng = np.random.RandomState(0)
    images = rng.randn(W, B, 32, 32, 3).astype(np.float32)
    targets = rng.randint(0, 10, (W, B)).astype(np.int32)
    learner = build_learner(args, images[0][:1], num_classes=10, channels=3)
    return args, learner, (np.arange(W), (images, targets),
                           np.ones((W, B), np.float32))


@pytest.mark.parametrize("program", ["round", "scan_rounds"])
def test_cv_learner_traces_at_the_example_flags(fetchsgd_learner,
                                                trace_round, program):
    args, learner, (ids, batch, mask) = fetchsgd_learner
    cfg = learner.cfg
    assert (cfg.mode, cfg.k, cfg.num_rows, cfg.num_cols) == (
        "sketch", 50_000, 5, 500_000)
    assert cfg.grad_size > 6_500_000
    if program == "round":
        state, metrics = trace_round(learner, ids, batch, mask)
    else:
        K = args.scan_rounds
        assert K > 1, "the example no longer asks for --scan_rounds"
        _, (state, metrics) = trace_round(learner, ids, batch, mask,
                                          scan_rounds=K)
        assert all(leaf.shape[0] == K for leaf in jax.tree.leaves(metrics))
    assert state.weights.shape == learner.state.weights.shape
    assert jax.tree.structure(state) == jax.tree.structure(learner.state)
