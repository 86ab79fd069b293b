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


def test_sketch_round_kernels_read_one_expression_of_window_bases(
        fetchsgd_learner):
    """The three hash passes of a sketch round at the small cell's sketch
    (d = 6.57 M, 5 x 500 000: the aggregate-side sketch, the server's
    estimate pass, the re-sketch of the update) each take two operands,
    the second a block in SMEM: the window bases. Each call's bases are
    the output of ``sketch_kernels.window_bases`` — a jitted function of
    static arguments only, traced once a call site — and the three
    expressions are EQUAL (same sketch, same grid, offset 0), not one
    shared variable: nothing threads the array through round.py and
    server.py, and XLA's CSE makes them one array in the compiled round
    (tests/test_chip_compile.py holds that on the compiled program)."""
    from commefficient_tpu.analysis.walker import iter_eqns
    from commefficient_tpu.ops import sketch_kernels
    _, learner, (ids, batch, mask) = fetchsgd_learner
    args = (jax.numpy.asarray(ids, jax.numpy.int32),
            tuple(jax.numpy.asarray(t) for t in batch),
            jax.numpy.asarray(mask), jax.numpy.float32(0.1),
            jax.random.PRNGKey(0))
    with sketch_kernels.force_dispatch("kernel"):
        jaxpr = jax.make_jaxpr(learner._round)(learner.state, *args)
    sites = list(iter_eqns(jaxpr))
    made = {id(site.eqn.outvars[0]): site.eqn for site in sites
            if site.primitive in ("pjit", "jit")
            and site.eqn.params["name"] == "window_bases"}
    passes = [site.eqn for site in sites if site.primitive == "pallas_call"
              and site.eqn.params["name"] in ("sketch_vec_pallas",
                                              "estimates_pallas")]
    assert sorted(e.params["name"] for e in passes) == [
        "estimates_pallas", "sketch_vec_pallas", "sketch_vec_pallas"]
    expressions = set()
    for eqn in passes:
        data, bases = eqn.invars
        assert bases.aval.dtype == jax.numpy.int32 and bases.aval.ndim == 1
        spaces = [str(bm.transformed_block_aval.memory_space)
                  for bm in eqn.params["grid_mapping"].block_mappings]
        assert spaces == ["vmem", "smem", "vmem"], spaces
        maker = made[id(bases)]
        assert not maker.invars       # a function of the sketch alone
        expressions.add(str(maker.params["jaxpr"]))
    assert len(expressions) == 1
