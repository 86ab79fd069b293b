"""Entry adapter: ``commefficient_tpu.training.cv.train``, observed from outside.

The window drives the user's entry point exactly as ``training/cv.py::main``
wires it (parser -> ``parse_mesh`` -> ``train``). This adapter only

* provides the inputs: CIFAR-format files (one generated pool per checkout,
  see ``prepare_data``) and the weights of ``--seed``, made by the
  configuration's plain reference and set into the learner's state before
  the first round (so the reference never takes weights the program made);
* wraps ``cv.build_learner`` and, on the learner it returns,
  ``train_round_async``, ``evaluate`` and the ``push``/``flush`` of every
  object ``learner.pipeline()`` returns. The wrappers take host timestamps,
  keep what ``correct`` compares of the first rounds, mark the window's
  start after the warm-up rounds, and end the window by raising
  ``StopWindow`` at the first dispatch at or after ``--seconds``.

So the batcher, ``device_prefetch``, ``RoundPipeline``, the transfer guard
and donation all run as a user runs them.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import sys

import numpy as np

from benchlib.probe import Probe, StopWindow


def prepare_data(bench_dir, data):
    """The dataset directory of this checkout, generated on first use.

    One pool of images per checkout (``pool_seed`` in the configuration), not
    one per run: writing and preparing 3 GB took over a minute of set-up here
    and would be paid, and written, by every run of every check. The run's
    ``--seed`` still decides which rows each round draws, their augmentation
    and the weights. ``stats.json`` (written last by the program's own
    ``prepare_datasets``) marks a finished directory; anything less is wiped
    and made again."""
    from datagen import cifar_pickles
    root = os.path.join(bench_dir, "_data", f"pool-{data['pool_seed']}-"
                        f"{data['train_images']}-{data['test_images']}")
    raw = os.path.join(root, "cifar-10-batches-py")
    if os.path.exists(os.path.join(root, "stats.json")):
        shutil.rmtree(raw, ignore_errors=True)   # only prepare() reads them
        return root
    shutil.rmtree(root, ignore_errors=True)
    cifar_pickles.write(root, data["pool_seed"], data["train_images"],
                        data["test_images"],
                        noise_bits=data.get("noise_bits", 6))
    return root


def build_flags(config, cell, seed, data_dir):
    return (list(config["flags"]) + list(cell["flags"])
            + ["--dataset_dir", data_dir, "--seed", str(seed)])


def reference_spec(args, rounds_per_epoch):
    """What the plain reference needs to know of the run, from the flags."""
    return {"mode": args.mode, "k": args.k, "num_rows": args.num_rows,
            "num_cols": args.num_cols,
            "virtual_momentum": args.virtual_momentum,
            "weight_decay": args.weight_decay,
            "num_workers": args.num_workers,
            "lr_scale": args.lr_scale, "pivot_epoch": args.pivot_epoch,
            "num_epochs": args.num_epochs,
            "rounds_per_epoch": rounds_per_epoch,
            "precision": args.compute_dtype}


@contextlib.contextmanager
def instrumented(cv, probe, reference, seed):
    """``cv.build_learner`` returns learners that carry the reference's
    weights and report to ``probe``."""
    import jax
    build = cv.build_learner

    def build_and_wrap(*a, **kw):
        probe.mark("datasets_loaded")
        learner = build(*a, **kw)
        sizes = [int(n) for n in learner._param_leaf_sizes]
        if sizes != list(reference.SIZES):
            raise RuntimeError(
                f"the program's parameter leaves {sizes} are not the "
                f"reference's layout {list(reference.SIZES)}")
        w0 = reference.make_weights(seed)
        probe.w0 = np.asarray(jax.device_get(w0))
        learner.state = learner.state.replace(weights=w0)
        _wrap(learner, probe)
        probe.mark("learner_built")
        return learner

    cv.build_learner = build_and_wrap
    try:
        yield
    finally:
        cv.build_learner = build


def _wrap(learner, probe):
    probe.learner = learner
    dispatch, evaluate, pipeline = (learner.train_round_async,
                                    learner.evaluate, learner.pipeline)

    def timed_dispatch(ids, cols, mask, **kw):
        probe.before_dispatch(ids, cols, mask)       # may raise StopWindow
        with probe.span("dispatch"):
            return dispatch(ids, cols, mask, **kw)

    def timed_evaluate(batches):
        with probe.span("eval"):
            return evaluate(batches)

    def timed_pipeline():
        pipe = pipeline()
        push, flush = pipe.push, pipe.flush

        def timed_push(raw):
            with probe.span("push_sync"):
                out = push(raw)
            probe.after_push(out)
            return out

        def timed_flush():
            with probe.span("flush_sync"):
                out = flush()
            probe.after_flush(out)
            return out

        pipe.push, pipe.flush = timed_push, timed_flush
        return pipe

    learner.train_round_async = timed_dispatch
    learner.evaluate = timed_evaluate
    learner.pipeline = timed_pipeline


def opt_state_after_first_step(learner):
    return learner.state.opt.Vvelocity


def weights_of(learner):
    return learner.state.weights


def round_program(learner, batch_shapes):
    """What the round program of this learner is, from one trace of it with
    the run's own state and batch shapes: how many ``pallas_call``s it holds
    (so that no dispatch gate can hand the run to the XLA formulation
    unnoticed) and the bytes of temporaries its compiled form needs."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.analysis.walker import iter_eqns
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    state = jax.tree.map(shape, learner.state)
    ids, cols, mask = batch_shapes
    args = (jax.ShapeDtypeStruct(ids[0], jnp.int32),
            tuple(jax.ShapeDtypeStruct(s, d) for s, d in cols),
            jax.ShapeDtypeStruct(mask[0], jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            shape(jax.random.PRNGKey(0)))
    traced = learner._round.trace(state, *args)
    kernels = sum(1 for site in iter_eqns(traced.jaxpr)
                  if site.primitive == "pallas_call")
    memory = traced.lower().compile().memory_analysis()
    return {"pallas_calls": kernels,
            "temp_bytes": getattr(memory, "temp_size_in_bytes", None)}


def run(cell, config, seed, seconds, trace, ctx):
    """Drive ``train`` through set-up and the window. Returns the probe."""
    import jax

    from commefficient_tpu.training import cv
    from commefficient_tpu.training.args import (parse_mesh,
                                                 round_up_workers_for_mesh)
    from commefficient_tpu.utils.compile_cache import place_compile_cache

    data_dir = prepare_data(ctx["bench_dir"], config["data"])
    place_compile_cache()
    # the program's small set-up programs compile in under a second each and
    # would otherwise be compiled again by every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    args = cv.build_parser(default_lr=0.4).parse_args(
        build_flags(config, cell, seed, data_dir))
    mesh = parse_mesh(args.mesh)
    round_up_workers_for_mesh(args, mesh)
    np.random.seed(args.seed)

    samples_per_round = args.num_workers * args.local_batch_size
    if samples_per_round != cell["samples_per_round"]:
        raise ValueError(f"the cell states {cell['samples_per_round']} "
                         f"samples a round, its flags give "
                         f"{samples_per_round}")
    rounds_per_epoch = -(-config["data"]["train_images"]
                         // samples_per_round)
    probe = Probe(t_process=ctx["t_process"], seconds=seconds,
                  warmup_rounds=int(cell["warmup_rounds"]),
                  samples_per_round=samples_per_round,
                  trace=bool(trace), trace_dir=ctx["trace_dir"],
                  trace_rounds=int(cell.get("trace_rounds", 20)),
                  keep_rounds=3, opt_state=opt_state_after_first_step,
                  weights=weights_of)
    probe.spec = reference_spec(args, rounds_per_epoch)
    probe.mark("jax_and_data_dir_ready")
    with instrumented(cv, probe, ctx["reference"], seed), \
            probe.compile_events():
        try:
            _, last = cv.train(args, mesh=mesh)
        except StopWindow:
            probe.close_window()
        else:
            ended_window(probe, last)
    return probe


def ended_window(probe, last):
    """``train`` returned by itself. A training that diverged inside the
    window (the device guard's ``aborted``) closes the window there and goes
    on to ``correct``, which counts it as failed; anything else has no
    window to report and raises."""
    if not last.get("aborted"):
        raise RuntimeError("train() returned before the window closed: "
                           "raise --num_epochs in the configuration")
    what = (f"training diverged: loss {last['loss']} at round "
            f"{probe.dispatched} (train() aborted on --nan_threshold)")
    if probe.t_start is None:
        raise RuntimeError(what + ", before the window opened after "
                           f"{probe.warmup_rounds} warm-up rounds")
    print(f"benchmark: {what}, {probe.window_rounds} rounds into the "
          f"window; the run counts as failed", file=sys.stderr)
    probe.close_window()
