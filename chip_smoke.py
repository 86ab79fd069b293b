#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip  (what the driver runs)
    python chip_smoke.py --chips 4   # the --mesh clients=4 path, nothing else

Default: the FetchSGD headline configuration (examples/cifar10_fetchsgd.sh:
ResNet-9 in bf16 compute, d = 6.57 M, 5 x 500 000 sketch, k = 50 000, 8 of
100 clients a round) on the generated ``Synthetic`` dataset, through the CV
entry point's own parser and ``train`` exactly as ``training/cv.py::main``
wires them, for a handful of rounds, twice in this process: the first call
pays the cold compile, the second builds a new learner, so the persistent
compile cache has to serve the round program. The weights are random, made
from ``--seed``. It then checks what came out (finite loss, no abort, the
state on the chip, Pallas kernels in the round that ran, weights that
moved) and fails if any check fails.

``--chips 4`` runs ONLY the mesh path and what it is compared with: the
same configuration in f32 compute for three rounds on device 0, then under
``--mesh clients=4``, and requires the two to agree, the per-client state
and the worker batch to sit on four distinct devices, and the kernels to be
in the mesh round too.

One process, no child that needs the chip, no CPU fallback: where JAX finds
no TPU the script says so and exits non-zero. Its last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every check passed. Earlier lines are smoke readings (wall seconds
that include compiles), NOT benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import math
import os
import sys
import time
from typing import Any, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))

#: examples/cifar10_fetchsgd.sh, minus what the sealed machine cannot have
#: (the CIFAR pickles: ``Synthetic`` is generated, CIFAR-shaped) and minus
#: ``--scan_rounds`` (the per-round dispatch is the path to bring up first).
#: ``--valid_batch_size`` only sizes the one validation pass that
#: ``max_rounds`` forces at the end of ``train``.
FLAGS = [
    "--dataset_name", "Synthetic",
    "--dataset_dir", os.path.join(HERE, "dataset", "synthetic"),
    "--model", "ResNet9", "--mode", "sketch", "--error_type", "virtual",
    "--virtual_momentum", "0.9", "--num_clients", "100",
    "--num_workers", "8", "--local_batch_size", "32",
    "--k", "50000", "--num_rows", "5", "--num_cols", "500000",
    "--num_epochs", "24", "--pivot_epoch", "5", "--lr_scale", "0.4",
    "--valid_batch_size", "512",
]
ROUNDS_ONE_CHIP = 6     # >= 4: several rounds run on a warm program
ROUNDS_FOUR_CHIPS = 3
# mesh vs one device: what rounding can explain — reduction order, and the
# odd coordinate it tips across the top-k threshold. A wrong reduction (a
# lost shard, a sum counted four times) moves both by order one.
LOSS_RTOL = 1e-2
UPDATE_RTOL = 5e-2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(reading: str, **fields) -> None:
    print(json.dumps({"smoke": reading, **fields}), flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def initial_weights_recorded(cv):
    """Host copy of the weights every learner ``train`` builds starts from.
    Taken before the first round: the round DONATES its state, so on the
    chip (unlike the CPU backend, which ignores donation) the initial
    buffers are deleted by the first dispatch."""
    import numpy as np
    build, seen = cv.build_learner, []

    def recording_build(*a, **kw):
        learner = build(*a, **kw)
        seen.append(np.asarray(learner.state.weights))
        return learner

    cv.build_learner = recording_build
    try:
        yield seen
    finally:
        cv.build_learner = build


class Run(NamedTuple):
    """One ``train`` call and what the checks need of it."""
    args: Any
    learner: Any
    loss: float          # the train loss ``train`` returned
    w0: Any              # initial weights, on the host
    seconds: float       # wall clock of the ``train`` call, compiles included
    cache_hits: int      # persistent compile-cache hits during it

    @property
    def weights(self):
        import numpy as np
        return np.asarray(self.learner.state.weights)


def run_train(flags, rounds) -> Run:
    """``training/cv.py::main`` between the parse and the final print, with
    the round count bounded."""
    import jax
    import numpy as np

    from commefficient_tpu.training import cv
    from commefficient_tpu.training.args import (parse_mesh,
                                                 round_up_workers_for_mesh)
    from commefficient_tpu.utils.logging import profile_ctx

    args = cv.build_parser(default_lr=0.4).parse_args(flags)
    mesh = parse_mesh(args.mesh)
    round_up_workers_for_mesh(args, mesh)
    np.random.seed(args.seed)
    hits = []

    def count_hit(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(count_hit)
    try:
        with initial_weights_recorded(cv) as init, \
                profile_ctx(args.profile):
            t0 = time.perf_counter()
            learner, row = cv.train(args, mesh=mesh, max_rounds=rounds)
            jax.block_until_ready(learner.state)
            seconds = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_listener(count_hit)
    require(not row.get("aborted"), f"train aborted: {row}")
    require(math.isfinite(row["train_loss"]),
            f"train loss is not finite: {row['train_loss']}")
    require(not bool(learner.state.aborted),
            "learner.state.aborted is set")
    return Run(args, learner, row["train_loss"], init[0], seconds, len(hits))


def first_batch(args, learner):
    """One round's arguments, drawn and placed as ``train`` draws and
    places them (same dataset, batcher, prefetch and shardings)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.data import FedBatcher
    from commefficient_tpu.data.prefetch import device_prefetch
    from commefficient_tpu.training.cv import make_dataset

    batcher = FedBatcher(make_dataset(args, train=True), args.num_workers,
                         args.local_batch_size, seed=args.seed)
    ids, cols, mask = next(device_prefetch(
        batcher.epoch(), size=1, shardings=learner.batch_shardings))
    return (jnp.asarray(ids, jnp.int32), tuple(cols),
            jnp.asarray(mask, jnp.float32),
            jnp.float32(learner.lr_at(0.0)), jax.random.PRNGKey(0))


def kernels_in_round(learner, batch):
    """Grid and outputs of every ``pallas_call`` in the round program this
    learner runs — traced with the run's own state and a batch of its
    shapes, so that no dispatch gate (``_kernel_ok``, ``kernel_supported``,
    ``_batch_guard``, ``topk_kernel_ok``) can hand the run to the XLA
    formulation unnoticed."""
    import jax

    from commefficient_tpu.analysis.walker import iter_eqns

    jaxpr = jax.make_jaxpr(learner._round)(learner.state, *batch)
    calls = [{"grid": list(site.eqn.params["grid_mapping"].grid),
              "out": [a.str_short() for a in site.eqn.params["out_avals"]]}
             for site in iter_eqns(jaxpr) if site.primitive == "pallas_call"]
    require(calls, "no pallas_call in the round program: the run took the "
                   "XLA formulation")
    return calls


def one_chip(flags) -> None:
    import jax
    import numpy as np

    from commefficient_tpu import native

    dev = jax.devices()[0]
    cold = run_train(flags, ROUNDS_ONE_CHIP)
    d = cold.learner.cfg.grad_size
    say("train_cold", wall_seconds=cold.seconds, rounds=ROUNDS_ONE_CHIP, d=d,
        train_loss=cold.loss, compile_cache_hits=cold.cache_hits,
        note="wall clock of train(): compiles, data and one validation "
             "pass included; a smoke reading, not a benchmark number")
    on = cold.learner.state.weights.devices()
    require(on == {dev}, f"weights live on {on}, not on {dev}")
    w_cold = cold.weights
    require(w_cold.shape == (d,) and np.isfinite(w_cold).all(),
            "final weights are not finite")
    moved = int(np.count_nonzero(w_cold != cold.w0))
    require(moved > 0, "the final weights equal the initial ones")
    kernels = kernels_in_round(cold.learner,
                               first_batch(cold.args, cold.learner))
    say("round_program", pallas_calls=kernels, weights_moved=moved,
        sketch=[cold.args.num_rows, cold.args.num_cols], k=cold.args.k)

    # the same call again: a NEW learner, so jit's in-memory cache misses
    # and only the persistent compile cache can spare the compile
    warm = run_train(flags, ROUNDS_ONE_CHIP)
    say("train_warm", wall_seconds=warm.seconds, rounds=ROUNDS_ONE_CHIP,
        train_loss=warm.loss, compile_cache_hits=warm.cache_hits,
        note="second identical train() in this process, new learner: "
             "the persistent compile cache serves the round program; a "
             "smoke reading, not a benchmark number")
    require(warm.cache_hits > 0, "the second train() call hit the "
                                 "persistent compile cache 0 times")
    require(np.array_equal(warm.weights, w_cold),
            "two identical train() calls gave different weights")
    stats = dev.memory_stats() or {}
    say("device_memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))
    say("host_data_plane",
        fedio="native" if native.lib() is not None else "numpy fallback")


def four_chips(flags) -> None:
    import numpy as np

    flags = flags + ["--compute_dtype", "float32"]
    one = run_train(flags, ROUNDS_FOUR_CHIPS)
    say("train_one_device", wall_seconds=one.seconds, train_loss=one.loss)
    mesh = run_train(flags + ["--mesh", "clients=4"], ROUNDS_FOUR_CHIPS)
    say("train_mesh_clients4", wall_seconds=mesh.seconds,
        train_loss=mesh.loss)

    # (a) the mesh run is the one-device run up to rounding
    w0, w1, w4 = one.w0, one.weights, mesh.weights
    require(np.array_equal(w0, mesh.w0), "the two runs start from "
                                         "different weights")
    update_norm = float(np.linalg.norm(w1 - w0))
    require(update_norm > 0, "the weights never moved")
    loss_rel = abs(mesh.loss - one.loss) / abs(one.loss)
    update_rel = float(np.linalg.norm(w4 - w1)) / update_norm
    say("mesh_vs_one_device", loss_one=one.loss, loss_mesh=mesh.loss,
        loss_rel_diff=loss_rel,
        max_abs_weight_diff=float(np.max(np.abs(w4 - w1))),
        max_abs_update=float(np.max(np.abs(w1 - w0))),
        update_rel_l2_diff=update_rel)
    require(loss_rel <= LOSS_RTOL,
            f"train loss differs by {loss_rel:.3g} relative")
    require(update_rel <= UPDATE_RTOL,
            f"weight update differs by {update_rel:.3g} relative (L2)")

    # (b) the sharded axis really is spread over the four chips
    state = mesh.learner.state
    batch = first_batch(mesh.args, mesh.learner)
    ids, cols, mask = batch[:3]
    placed = {"state.client_last_round": state.client_last_round,
              "state.quarantine": state.quarantine,
              "batch.client_ids": ids, "batch.images": cols[0],
              "batch.targets": cols[1], "batch.mask": mask}
    spread = {name: sorted(d.id for d in x.sharding.device_set)
              for name, x in placed.items()}
    say("residency", devices=spread,
        weights_on=sorted(d.id for d in state.weights.devices()))
    for name, ids_on in spread.items():
        require(len(ids_on) == 4, f"{name} is resident on devices "
                                  f"{ids_on}, not on 4 distinct ones")
        shard_rows = {s.data.shape[0] for s in
                      placed[name].addressable_shards}
        require(shard_rows == {placed[name].shape[0] // 4},
                f"{name} is not split four ways: shard rows {shard_rows}")

    # (c) the kernels survive in the mesh round
    say("round_program",
        pallas_calls=kernels_in_round(mesh.learner, batch))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh clients=4 path and the "
                         "one-device run it is compared with")
    ap.add_argument("--seed", type=int, default=21,
                    help="seed of the random weights and the data")
    opts = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
              f"{len(devices)} device(s)); this script measures nothing "
              f"off the chip", file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from commefficient_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    say("versions", **{pkg: importlib.metadata.version(pkg)
                       for pkg in ("jax", "jaxlib", "libtpu", "flax")})
    say("compile_cache", dir=cache_dir,
        placed_by=("JAX_COMPILATION_CACHE_DIR"
                   if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                   else "utils/compile_cache.py"))

    flags = FLAGS + ["--seed", str(opts.seed)]
    try:
        if opts.chips == 4:
            four_chips(flags)
        else:
            one_chip(flags + ["--compute_dtype", "bfloat16"])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
