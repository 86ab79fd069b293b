#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, cell, metric, reader, entry
point or reference is a file of its own under this directory, found by the
name ``BENCHMARK.json`` gives (see README.md); this file names none of them.
The last line of standard output is the result's JSON object; without a TPU
(or with fewer chips than the cell asks for) there is no result and the exit
code is not 0. ``--rehearsal <name>`` runs a file of ``rehearsal/`` on the
CPU instead: control flow only, no device metric in its line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
for _p in (REPO_DIR, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_cell(workload=None, rehearsal=None):
    """(cell, configuration) of a benchmark cell, or of a CPU rehearsal."""
    where = "rehearsal" if rehearsal else "workloads"
    cell = load_json(where, (rehearsal or workload) + ".json")
    config = load_json("rehearsal" if rehearsal else "configs",
                       cell["config"] + ".json")
    return cell, config


def load_module(kind, name):
    """``<kind>/<name>.py`` of this directory, as a module."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def device_record(devices, probe=None):
    dev = devices[0]
    rec = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devices)}
    if probe is not None:
        rec["memory_peak_bytes"] = probe.peak_bytes
    return rec


def read_metric(name, obs):
    """(value, unit) of one metric by its file, or None where its reader
    finds nothing to read."""
    spec = load_json("metrics", name + ".json")
    value = load_module("readers", spec["reader"]).read(
        obs, spec.get("params", {}))
    if value is None or not math.isfinite(value):
        return None
    return {"value": value, "unit": spec["unit"]}


def program_side(probe):
    return {"loss": [m["loss"] for m in probe.finalized],
            "opt_after_1": probe.opt_after_1, "w": probe.w_after}


def host_batches(probe):
    """The first rounds' cohorts as the reference takes them: rows."""
    out = []
    for ids, cols, mask in probe.batches:
        images, labels = cols
        out.append((images.reshape((-1,) + images.shape[2:]),
                    labels.reshape(-1), mask.reshape(-1)))
    return out


def decide_correct(cell, probe, reference, entry, fault=None):
    """Every number compared, beside its limit: [(name, value, limit, ok)].
    Runs the plain reference over the first rounds, so call it once the
    window has closed and the peak memory has been read."""
    from benchlib import compare
    rows = []
    rows.append(("compiles_in_window", probe.compiles_in_window(), 0,
                 probe.compiles_in_window() == 0))
    bad = sum(1 for m in probe.finalized
              if m["aborted"] or not math.isfinite(m["loss"]))
    bad += int(bool(probe.aborted))
    rows.append(("rounds_aborted_or_not_finite", bad, 0, bad == 0))
    lo, hi = cell.get("pallas_calls", [0, None])
    t0 = time.perf_counter()
    program = entry.round_program(probe.learner, probe.batch_shapes)
    n_kernels = program["pallas_calls"]
    t1 = time.perf_counter()
    rows.append(("pallas_calls", n_kernels, [lo, hi],
                 n_kernels >= lo and (hi is None or n_kernels <= hi)))
    probe.learner = None          # the program's state leaves the device
    gc.collect()
    ref = reference.steps(probe.w0, host_batches(probe), probe.spec,
                          probe.spec["precision"], fault=fault)
    numbers = compare.training_numbers(
        program_side(probe), ref, probe.w0, reference.leaf_slices())
    rows += compare.judge(numbers, cell["limits"])
    print(f"after the window: round program traced in {t1 - t0:.1f} s, "
          f"reference followed in {time.perf_counter() - t1:.1f} s",
          file=sys.stderr)
    return rows, len(probe.finalized), bad, program


def print_compared(rows):
    for name, value, limit, ok in rows:
        print(f"compared {name} = {value!r} limit {limit!r} "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()


def run_cell(cell, config, seed, seconds, trace, devices, fault=None):
    """Set-up, window, metrics and ``correct`` of one run; returns the
    result's dict. ``fault`` is for the tests that break the comparison."""
    reference = load_module("reference", config["reference"])
    entry = load_module("entries", config["entry"])
    trace_dir = os.path.join(BENCH_DIR, "_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"bench_dir": BENCH_DIR, "t_process": T_PROCESS,
           "trace_dir": trace_dir, "reference": reference}
    probe = entry.run(cell, config, seed, seconds, trace, ctx)

    obs = {"probe": probe, "cell": cell, "config": config, "trace": None,
           "reference": reference, "device": devices[0],
           "peaks": load_json("peaks.json")}
    device = device_record(devices, probe)
    breakdown = {}
    if trace:
        from benchlib import trace as tr
        path = tr.find_xplane(trace_dir)
        if path is None:
            raise RuntimeError("the traced run wrote no trace")
        obs["trace"] = tr.read_xplane(path)
        seen = tr.busy(obs["trace"])
        if seen is None:
            raise RuntimeError("no operation ran on the device inside the "
                               "traced window")
        device["busy_s"], device["window_s"] = seen
        breakdown = {"breakdown": {
            "device_ops": tr.top_ops(obs["trace"], 10),
            "idle_gaps": tr.idle_gaps(obs["trace"], 10)}}
        if os.environ.get("BENCH_KEEP_TRACE"):   # to cut a test's fixture
            with open(os.path.join(BENCH_DIR, "_trace", "trace.json"),
                      "w") as f:
                json.dump(obs["trace"], f)
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for name in names:
        got = read_metric(name, obs)
        if got is not None:
            metrics[name] = got
    obs["trace"] = None

    rows, attempted, failed, program = decide_correct(
        cell, probe, reference, entry, fault=fault)
    program["memory_stats"] = probe.memory_stats
    program["setup_phases_s"] = probe.setup_phases()
    print_compared(rows)
    return {"correct": all(ok for *_, ok in rows),
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **breakdown,
            "program": program,
            "compared": {name: {"value": value, "limit": limit}
                         for name, value, limit, _ in rows}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearsal", help="a file of rehearsal/, run on the "
                                        "CPU; prints no device metric")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    if bool(opts.workload) == bool(opts.rehearsal):
        ap.error("give --workload or --rehearsal")

    if opts.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell, config = load_cell(opts.workload, opts.rehearsal)

    try:
        import jax
        devices = jax.devices()
        import commefficient_tpu  # noqa: F401  (the system under test)
    except (ImportError, RuntimeError) as e:
        print(f"benchmark: cannot start: {e}", file=sys.stderr)
        return 3
    platform = devices[0].platform
    if opts.rehearsal:
        if platform != "cpu":
            print("benchmark: a rehearsal runs on the CPU", file=sys.stderr)
            return 2
    elif platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"benchmark: {cell['chips']} TPU chip(s) needed, JAX found "
              f"{len(devices)} {platform} device(s); nothing is measured "
              f"off the chip", file=sys.stderr)
        return 2

    result = run_cell(cell, config, opts.seed, opts.seconds,
                      bool(opts.trace) and not opts.rehearsal,
                      devices[:int(cell["chips"])])
    if opts.rehearsal:
        # a CPU's times are no device metrics: the line carries none
        result["metrics"] = {}
        result["device"] = device_record(devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
