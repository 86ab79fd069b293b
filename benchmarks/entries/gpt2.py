"""Entry adapter: ``commefficient_tpu.training.gpt2.train``, observed from
outside, for a language model on packed token sequences.

As ``entries/cv.py`` does for ``cv.train``: the window drives the user's
entry point exactly as ``training/gpt2.py::main`` wires it (parser ->
``parse_mesh`` -> ``train``). This adapter only

* provides the inputs: token streams in the program's public layout (one
  generated pool per checkout, ``datagen/token_docs.py``) and the weights of
  ``--seed``, made by the configuration's plain reference (told the model's
  sizes first: ``reference.configure(config["model"])``) and set into the
  learner's state before the first round;
* wraps ``gpt2.build_learner`` and, on the learner it returns, ``train_round_async``, ``evaluate`` and the
  ``push``/``flush`` of every object ``learner.pipeline()`` returns — the
  wrappers of ``entries/cv.py``, imported from there.

``cols`` of a round are ``(tokens, labels)``, so ``run.py::host_batches``
takes them as it takes ``(images, labels)``. The reference's own top-k over
d coordinates makes a followed round dear, so ``correct`` follows two rounds
here (``keep_rounds`` = 2).
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import shutil

import numpy as np

from benchlib.probe import Probe, StopWindow


def _cv():
    """``entries/cv.py``: the wrappers and the readers of a learner's state
    are the same for every ``FedLearner``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cv.py")
    spec = importlib.util.spec_from_file_location("bench_entries_cv", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


cv_entry = _cv()
round_program = cv_entry.round_program


def prepare_data(bench_dir, data, vocab_rows):
    """The token pool of this checkout, generated on first use (one per
    checkout, as the image pool: ``--seed`` decides which clients and
    sequences a round draws, and the weights). ``valid.npy`` is written
    last and marks a finished directory."""
    from datagen import token_docs
    root = os.path.join(
        bench_dir, "_data", f"tokens-{data['pool_seed']}-"
        f"{data['num_clients']}-{data['tokens_per_client']}-{vocab_rows}")
    if os.path.exists(os.path.join(root, "valid.npy")):
        return root
    shutil.rmtree(root, ignore_errors=True)
    token_docs.write(root, data["pool_seed"], data["num_clients"],
                     data["tokens_per_client"], data["valid_tokens"],
                     vocab_rows, zipf_a=data["zipf_a"])
    return root


def reference_spec(args, total_steps):
    return {"mode": args.mode, "k": args.k, "num_rows": args.num_rows,
            "num_cols": args.num_cols,
            "virtual_momentum": args.virtual_momentum,
            "weight_decay": args.weight_decay,
            "num_workers": args.num_workers, "lr_scale": args.lr_scale,
            "total_steps": total_steps, "precision": args.compute_dtype}


@contextlib.contextmanager
def instrumented(gpt2, probe, reference, seed):
    """Learners that ``gpt2.train`` builds carry the reference's weights and
    report to ``probe``."""
    import jax
    build = gpt2.build_learner

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
        probe.spec["total_steps"] = int(learner.lr_schedule.knots[1])
        cv_entry._wrap(learner, probe)
        probe.mark("learner_built")
        return learner

    gpt2.build_learner = build_and_wrap
    try:
        yield
    finally:
        gpt2.build_learner = build


def run(cell, config, seed, seconds, trace, ctx):
    """Drive ``train`` through set-up and the window. Returns the probe."""
    import jax

    from commefficient_tpu.training import gpt2
    from commefficient_tpu.training.args import (parse_mesh,
                                                 round_up_workers_for_mesh)
    from commefficient_tpu.utils.compile_cache import place_compile_cache

    reference = ctx["reference"]
    reference.configure(config["model"])
    data_dir = prepare_data(ctx["bench_dir"], config["data"],
                            config["model"]["vocab_rows"])
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    args = gpt2.build_gpt2_parser().parse_args(
        list(config["flags"]) + list(cell["flags"])
        + ["--dataset_dir", data_dir, "--seed", str(seed)])
    mesh = parse_mesh(args.mesh)
    round_up_workers_for_mesh(args, mesh)
    np.random.seed(args.seed)

    samples_per_round = args.num_workers * args.local_batch_size
    if samples_per_round != cell["samples_per_round"]:
        raise ValueError(f"the cell states {cell['samples_per_round']} "
                         f"samples a round, its flags give "
                         f"{samples_per_round}")
    probe = Probe(t_process=ctx["t_process"], seconds=seconds,
                  warmup_rounds=int(cell["warmup_rounds"]),
                  samples_per_round=samples_per_round,
                  trace=bool(trace), trace_dir=ctx["trace_dir"],
                  trace_rounds=int(cell.get("trace_rounds", 2)),
                  keep_rounds=2,
                  opt_state=cv_entry.opt_state_after_first_step,
                  weights=cv_entry.weights_of,
                  trace_skip=int(cell.get("trace_skip", 5)))
    probe.spec = reference_spec(args, None)
    probe.mark("jax_and_data_dir_ready")
    with instrumented(gpt2, probe, reference, seed), probe.compile_events():
        try:
            _, last = gpt2.train(args, mesh=mesh, log=False)
        except StopWindow:
            probe.close_window()
        else:
            cv_entry.ended_window(probe, last)
    return probe
