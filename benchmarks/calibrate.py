#!/usr/bin/env python3
"""Readings that ``correct``'s limits are set from, for a training cell.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 --controls 3

For every seed, in this one process: the cell's own set-up and a short
window through the timed path (``run.py``'s), then the program against the
plain reference (a *lower* reading). For the first ``--controls`` seeds also
the readings that have to fail: the reference computed in the next precision
below the configuration's (``fp8``), and the reference with a planted fault
(``half_batch``), each put in the program's place. Not part of a benchmark
run; PERF.md records what it read on the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--rehearsal")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    opts = ap.parse_args(argv)
    cell, config = harness.load_cell(opts.workload, opts.rehearsal)
    import jax

    from benchlib import compare
    if not opts.rehearsal and jax.devices()[0].platform != "tpu":
        print("calibrate: the readings are the chip's", file=sys.stderr)
        return 2
    reference = harness.load_module("reference", config["reference"])
    entry = harness.load_module("entries", config["entry"])
    slices = reference.leaf_slices()
    ctx = {"bench_dir": harness.BENCH_DIR, "t_process": harness.T_PROCESS,
           "trace_dir": None, "reference": reference}
    for i, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        probe = entry.run(cell, config, seed, opts.seconds, False, ctx)
        program = harness.program_side(probe)
        batches, w0, spec = harness.host_batches(probe), probe.w0, probe.spec
        probe.learner = None
        gc.collect()
        ref = reference.steps(w0, batches, spec, spec["precision"])
        out = {"seed": seed, "cell": cell["name"],
               "program": compare.training_numbers(program, ref, w0, slices),
               "ref_loss": ref["loss"], "program_loss": program["loss"][:3]}
        if i < opts.controls:
            for name, precision, fault in (
                    ("control_fp8", "fp8", None),
                    ("fault_half_batch", spec["precision"], "half_batch"),
                    ("reference_float32", "float32", None)):
                other = reference.steps(w0, batches, spec, precision,
                                        fault=fault)
                out[name] = compare.training_numbers(other, ref, w0, slices)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
