#!/usr/bin/env python3
"""Proof on the chip that no round's array is written again under a
transfer in flight (data/batching.py keeps a few arrays and writes a later
round into one once nothing else refers to it).

    python examples/check_batch_reuse.py --dataset_dir <prepared CIFAR-10>

Trains ``--rounds`` consecutive rounds with the flags of the benchmark's
``resnet9-cifar10.uncompressed`` cell, through ``device_prefetch`` and
``RoundPipeline`` as ``training/cv.py`` wires them, i.e. at the device's
pace. Of every round's image column ON THE DEVICE it takes the sum and the
sum of squares (one jitted reduction, fetched after the last round), and
compares them with the same reduction over the rounds of a second batcher,
same seed, that makes a new array for every round. Then the same again with
no training round between the transfers: the host as fast as it can go, so
that a transfer is always in flight while the next round is written.

The two sums of a round are equal bit for bit where the device saw the
pixels the never-re-using batcher made. The last line is one JSON object;
the exit code is 1 where any round differs. Needs the TPU: on the CPU
backend a device array can alias the host memory, which
tests/test_data.py::test_rounds_alive_on_the_device_never_change covers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: benchmarks/configs/resnet9-cifar10.json + workloads/...uncompressed.json
FLAGS = ["--dataset_name", "CIFAR10", "--model", "ResNet9",
         "--compute_dtype", "bfloat16", "--virtual_momentum", "0.9",
         "--lr_scale", "0.025", "--pivot_epoch", "5", "--num_epochs", "24",
         "--mode", "uncompressed", "--error_type", "none",
         "--num_clients", "10000", "--num_workers", "100",
         "--local_batch_size", "50"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dataset_dir", required=True)
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--seed", type=int, default=2147488001)
    opts = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from commefficient_tpu.data import FedBatcher, batching
    from commefficient_tpu.data.prefetch import device_prefetch
    from commefficient_tpu.training import cv
    from commefficient_tpu.utils import tracing
    from commefficient_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("check_batch_reuse: needs the TPU", file=sys.stderr)
        return 2
    place_compile_cache()
    args = cv.build_parser(default_lr=0.4).parse_args(
        FLAGS + ["--dataset_dir", opts.dataset_dir, "--seed", str(opts.seed)])
    sums = jax.jit(lambda x: jnp.stack([jnp.sum(x), jnp.sum(x * x)]))

    def rounds_of(train_set):
        """The first ``opts.rounds`` rounds, over as many epochs as it
        takes, as ``cv.train`` asks for them."""
        batcher = FedBatcher(train_set, args.num_workers,
                             args.local_batch_size, seed=args.seed)
        left = opts.rounds
        while left > 0:
            for item in batcher.epoch():
                yield item
                left -= 1
                if left == 0:
                    break

    def device_sums(train_set, learner=None):
        """(rounds, 2) sums of the image column as the device got it, and
        the seconds the loop took."""
        pipe = learner.pipeline() if learner is not None else None
        seen = []
        t0 = time.perf_counter()
        for n, (ids, cols, mask) in enumerate(device_prefetch(
                rounds_of(train_set))):
            seen.append(sums(cols[0]))
            if learner is not None:
                pipe.push(learner.train_round_async(
                    ids, cols, mask, epoch_frac=n / 100))
        if pipe is not None:
            pipe.flush()
        out = np.asarray(jax.device_get(jnp.stack(seen)))
        return out, time.perf_counter() - t0

    def counters():
        return {k: v[0] for k, v in tracing.snapshot()["counters"].items()
                if k.startswith("data.")}

    make = lambda: cv.make_dataset(args, train=True)
    train_set = make()
    args.num_clients = train_set.num_clients
    if train_set.round_builder() is None:
        print("check_batch_reuse: this dataset builds no round in one pass",
              file=sys.stderr)
        return 2
    _, cols0, _ = next(iter(FedBatcher(train_set, args.num_workers,
                                       args.local_batch_size).epoch()))
    learner = cv.build_learner(args, cols0[0][0][:1], 10, 3)
    del cols0

    # warm the round and the reduction, then count from zero
    result = {"rounds": opts.rounds, "seed": opts.seed,
              "device": jax.devices()[0].device_kind,
              "warm_up_s": device_sums(make(), learner)[1]}
    for name, with_rounds in (("training", True), ("transfers_only", False)):
        tracing.reset()
        got, seconds = device_sums(make(), learner if with_rounds else None)
        reused = counters()
        own, batching._OWN_REFS = batching._OWN_REFS, 0   # never free
        try:
            tracing.reset()
            want, _ = device_sums(make())
            fresh = counters()
        finally:
            batching._OWN_REFS = own
        differ = np.nonzero((got != want).any(axis=1))[0]
        result[name] = {
            "rounds_that_differ": differ.tolist(),
            "ms_a_round": 1e3 * seconds / opts.rounds,
            "arrays_new": reused.get("data.arrays_new", 0),
            "arrays_reused": reused.get("data.arrays_reused", 0),
            "rounds_one_pass": reused.get("data.rounds_one_pass", 0),
            "rounds_per_client": reused.get("data.rounds_per_client", 0),
            "never_reusing_arrays_new": fresh.get("data.arrays_new", 0),
            "first_round_sums": got[0].tolist(),
            "last_round_sums": got[-1].tolist()}
    result["ok"] = not any(result[n]["rounds_that_differ"]
                           or not result[n]["arrays_reused"]
                           for n in ("training", "transfers_only"))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
