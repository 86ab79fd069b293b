"""Accuracy-vs-communication results harness — the reference's raison d'etre.

The reference exists to produce accuracy-vs-communication curves: per-client
upload/download byte accounting (reference fed_aggregator.py:239-299) is the
x-axis, final accuracy the y-axis, across the five aggregation modes
(fed_aggregator.py:483-613). This harness runs REAL end-to-end federated
training through the CV entrypoint (commefficient_tpu/training/cv.py — the
same code path a user runs) for every mode and emits ``RESULTS.json`` +
``RESULTS.md``.

What is run (exactly — this environment has no network egress, so the
canonical CIFAR-10 pickles cannot be placed on disk; BASELINE.md's
accuracy target is re-measured on the closest real-pixel proxies
available offline, see data/offline.py):

* **patches32** (headline): FedPatches32 — 32x32x3 patches of scikit-learn's
  two bundled real photographs, 10 balanced (photo, band) classes, 5,500
  train / 1,500 val. The splits are SPATIALLY DISJOINT (val = a held-out
  column strip with a 32px guard band, data/offline.py) — round-3 numbers
  used an interleaved split with 75% train/val pixel overlap and are not
  comparable. ResNet9 at its full CIFAR size (d = 6,568,640), 100
  clients non-iid (class-per-client, the reference's CIFAR recipe,
  fed_cifar.py:45-58), 10 clients sampled per round, the reference's LR
  recipe (PiecewiseLinear 0 -> 0.4 @ epoch 5 -> 0 @ epoch 24,
  utils.py:153,162) and sketch config (5x500k, k=50k, utils.py:142-145).
  Upload ratios are therefore the paper's own: uncompressed/true_topk/fedavg
  26.3 MB per client per round, sketch 10.0 MB, local_topk 0.2 MB.

* **digits** (secondary): FedDigits — 1,797 real 8x8 digit scans, 10
  classes, 100 clients non-iid, TinyMLP (d=2,410) with compression budgets
  scaled to d: sketch 3x600 (1.34x upload compression), k=120 (20x for
  local_topk). The small d makes byte totals modest; this task is about
  the ACCURACY each mode reaches under compression on real data — the
  full-scale byte story lives in patches32.

* **persona** (NLP): the reference's second benchmark shape
  (gpt2_train.py: GPT2 double-heads on PersonaChat). The PERSONA raw
  corpus cannot be fetched offline, so SyntheticPersona generates
  word-soup dialogs through the SAME tokenize + build_input_from_segments
  pipeline (50 personas = natural clients, 8 dialogs each, T=64,
  gpt2-tiny). The LM's token-weighted validation nll/ppl is the learnable
  target — the synthetic MC candidates are random, so mc_acc carries no
  signal and is not reported.

* **persona_small** (NLP at the real scale): gpt2-small with the vocab
  table padded to the HF row count (measured d = 124,051,201 — the
  473.2 MiB dense upload of the reference experiment); modes uncompressed/sketch/
  local_topk at the paper's 5x500k / k=50k budgets. local_topk's
  per-client state (2 x 50 x 124M floats, ~50 GB) exceeds one chip's HBM,
  so that row runs with --client_state_offload: rows live in TPU-host
  pinned memory (the reference's host-shm capacity model,
  fed_aggregator.py:116-129) and the sampled rows stream to device per
  round; on a mesh the same state shards over the `clients` axis instead.

Usage:
    python results.py                 # all 4 tasks (TPU, ~1.5h)
    python results.py --task patches32 --modes sketch,uncompressed
    python results.py --grid          # patches32 LR x seed tuning grid +
                                      # local_topk diagnostics (resumable)
    python results.py --sweep         # byte-budget curve on patches32
    python results.py --quick         # tiny smoke (CI): 8 rounds per mode
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import numpy as np

MODES = ("uncompressed", "sketch", "true_topk", "local_topk", "fedavg")


def mode_flags(mode: str, task: str, quick: bool = False) -> list:
    """Per-mode optimizer/compression flags (reference recipes:
    virtual momentum 0.9 with virtual error for the server-side modes,
    local momentum+error for local_topk (fed_worker.py:193-216), no
    momentum/error for fedavg (fed_aggregator.py:484-486))."""
    common = {
        "uncompressed": ["--virtual_momentum", "0.9", "--error_type", "none"],
        "sketch": ["--virtual_momentum", "0.9", "--error_type", "virtual"],
        "true_topk": ["--virtual_momentum", "0.9", "--error_type", "virtual"],
        "local_topk": ["--local_momentum", "0.9", "--error_type", "local"],
        "fedavg": ["--error_type", "none", "--local_batch_size", "-1"],
    }[mode]
    if task == "patches32":
        # the paper's CIFAR sketch/topk budget (utils.py:142-145)
        sizes = ["--k", "50000", "--num_rows", "5", "--num_cols", "500000"]
        if quick:  # CI smoke: tiny sketch so CPU compiles fast
            sizes = ["--k", "500", "--num_rows", "3", "--num_cols", "5000"]
    elif task == "persona_small":
        # gpt2-small at the REFERENCE's exact compression config
        # (utils.py:142-145 applied to the NLP benchmark): d=124M,
        # sketch 5x500k (473 MiB grad -> 9.5 MiB upload), k=50k local_topk
        sizes = ["--k", "50000", "--num_rows", "5", "--num_cols", "500000"]
        if quick:  # CI smoke: tiny everything (see task_flags)
            sizes = ["--k", "50", "--num_rows", "3", "--num_cols", "500"]
    elif task == "persona":
        # gpt2-tiny d ~ 450k -> sketch 3x40k (3.7x), k=4k (~110x local)
        sizes = ["--k", "4000", "--num_rows", "3", "--num_cols", "40000"]
    else:  # digits: TinyMLP d=2,410 -> sketch 3x600 (1.3x), k=120 (20x)
        sizes = ["--k", "120", "--num_rows", "3", "--num_cols", "600"]
    return ["--mode", mode] + common + sizes


def task_flags(task: str, quick: bool) -> list:
    if task == "persona":
        # the reference's NLP benchmark shape (gpt2_train.py): double-heads
        # GPT2 on PersonaChat-layout dialogs. PERSONA raw files cannot be
        # fetched offline, so SyntheticPersona generates word-soup dialogs
        # through the SAME tokenize + build_input_from_segments pipeline —
        # the LM's nll/ppl is the learnable target (the MC candidates are
        # random, so mc_acc has no signal here; state that in the table).
        return ["--dataset_name", "SyntheticPersona", "--model", "gpt2-tiny",
                "--dataset_dir", "./dataset/results_persona",
                "--synthetic_personas", "50", "--synthetic_dialogs", "8",
                "--max_seq_len", "64", "--num_workers", "4",
                "--local_batch_size", "4", "--valid_batch_size", "16",
                "--lr_scale", "0.04", "--num_epochs", "2" if quick else "8",
                "--weight_decay", "0", "--seed", "21"]
    if task == "persona_small":
        # VERDICT r3 #7: the NLP accuracy-vs-bytes evidence at the real
        # model scale. gpt2-small with the vocab table padded to the HF
        # row count (measured d = 124,051,201, a 473.2 MiB dense upload)
        # so the byte ratios are the reference experiment's
        # (--vocab_pad_to docstring);
        # reduced epochs — the deliverable is the mode ORDERING at real
        # compression ratios, not a converged model
        # quick = plumbing smoke only: a full d=124M model with a 5x500k
        # sketch would turn the CPU smoke into hours (review r4) — shrink
        # to gpt2-tiny with a small vocab pad so the flag PATH is what's
        # smoked, not the scale
        model = ["--model", "gpt2-tiny", "--vocab_pad_to", "600"] if quick \
            else ["--model", "gpt2", "--vocab_pad_to", "50262",
                  "--compute_dtype", "bfloat16"]
        return (["--dataset_name", "SyntheticPersona"] + model +
                ["--dataset_dir", "./dataset/results_persona",
                 "--synthetic_personas", "50", "--synthetic_dialogs", "8",
                 "--max_seq_len", "64", "--num_workers", "4",
                 "--local_batch_size", "4", "--valid_batch_size", "16",
                 "--lr_scale", "0.04", "--num_epochs", "1" if quick else "4",
                 "--weight_decay", "0", "--seed", "21"])
    if task == "patches32":
        return ["--dataset_name", "Patches32", "--model", "ResNet9",
                "--dataset_dir", "./dataset/patches32",
                "--num_clients", "100", "--num_workers", "10",
                "--local_batch_size", "16", "--valid_batch_size", "256",
                # 0.4 is the reference's CIFAR peak (utils.py:162) but
                # diverges on this dataset/batch (measured: NaN at the
                # lr~0.27 point of the ramp; 0.15 diverges too, 0.08
                # trains stably) — the SHAPE of the schedule is the
                # reference's, the peak is tuned to the task
                "--lr_scale", "0.08", "--pivot_epoch", "5",
                "--num_epochs", "2" if quick else "24",
                "--weight_decay", "5e-4", "--seed", "21"]
    return ["--dataset_name", "Digits", "--model", "TinyMLP",
            "--dataset_dir", "./dataset/digits",
            "--num_clients", "100", "--num_workers", "10",
            "--local_batch_size", "8", "--valid_batch_size", "304",
            "--lr_scale", "0.1", "--pivot_epoch", "5",
            "--num_epochs", "3" if quick else "60",
            "--weight_decay", "1e-4", "--seed", "21"]


# --- the round-4 tuning grid (VERDICT r3 #1) --------------------------------
# Per-mode LR ranges STRADDLE each mode's round-3 operating point so the
# tuned-best is an interior point, not an endpoint; every mode's headline
# number becomes "best LR over this probe, mean over GRID_SEEDS".
GRID_LRS = {
    "uncompressed": ["0.02", "0.04", "0.08", "0.15"],
    "sketch": ["0.04", "0.08", "0.2", "0.4"],
    "true_topk": ["0.04", "0.08", "0.2", "0.4"],
    "local_topk": ["0.01", "0.02", "0.05", "0.1"],
    "fedavg": ["0.02", "0.05", "0.1", "0.2"],
}
GRID_SEEDS = ("21", "42", "77", "91", "17")

# local_topk mechanism diagnostics (VERDICT r3 Missing #3): the paper's own
# thesis is that local error accumulation degrades under client subsampling
# (error memory goes stale between a client's participations). If that — and
# not an implementation bug (ruled out by the hand-computed trace test,
# tests/test_round.py) — explains the gap, accuracy must climb when k grows
# (less error held back), when data is iid (client updates agree), and when
# participation rises 10% -> 50% (fresher error memory).
LOCAL_TOPK_DIAG = [
    ("k200k", ["--k", "200000"]),
    ("k500k", ["--k", "500000"]),
    ("iid", ["--iid"]),
    ("participation50", ["--num_workers", "50"]),
]


def _grid_label(mode: str, lr: str, seed: str) -> str:
    return f"{mode}_lr{lr}_s{seed}"


def run_grid(out: str = "RESULTS_grid", quick: bool = False) -> list:
    """Resumable patches32 (mode x lr x seed) grid + local_topk diagnostics.

    Incremental: rows are keyed by label and written to ``{out}.json`` after
    every run, so an interrupted grid continues where it stopped.
    """
    if quick:
        out = out + "_smoke"   # never mix smoke rows into the real artifact
    path = f"{out}.json"
    rows = []
    if os.path.exists(path) and not quick:
        with open(path) as f:
            rows = json.load(f)["results"]
    done = {r["mode"] for r in rows}
    grid_lrs = GRID_LRS
    seeds = GRID_SEEDS
    diags = LOCAL_TOPK_DIAG
    if quick:  # plumbing smoke: 2 LRs x 2 seeds x 1 diag
        grid_lrs = {m: lrs[:2] for m, lrs in GRID_LRS.items()}
        seeds = GRID_SEEDS[:2]
        diags = LOCAL_TOPK_DIAG[:1]

    def launch(mode, lr, seed, label, extra=()):
        if label in done:
            return
        r = run_one("patches32", mode, quick,
                    variant=(label, ["--lr_scale", lr, "--seed", seed,
                                     *extra]))
        r.update(base_mode=mode, lr=float(lr), seed=int(seed))
        rows.append(r)
        done.add(label)
        with open(path, "w") as f:
            json.dump({"results": rows}, f, indent=1)

    # stage A: LR probe at the base seed
    for mode, lrs in grid_lrs.items():
        for lr in lrs:
            launch(mode, lr, seeds[0], _grid_label(mode, lr, seeds[0]))

    # stage B: remaining seeds at each mode's tuned-best LR
    for mode in grid_lrs:
        lr = best_lr(rows, mode)
        for seed in seeds[1:]:
            launch(mode, lr, seed, _grid_label(mode, lr, seed))

    # stage C: local_topk mechanism diagnostics at its tuned-best LR
    lt_lr = best_lr(rows, "local_topk")
    for dlabel, extra in diags:
        launch("local_topk", lt_lr, seeds[0],
               f"local_topk_diag_{dlabel}_lr{lt_lr}", extra)

    # stage D (VERDICT r4 Missing #3): the accuracy license for the fast
    # approx selector. --topk_approx_recall 0.95 selects top-k with
    # approx_max_k (recall 0.95); these rows run the SAME tuned recipes
    # with --topk_approx_recall 0.95 so the fast configuration and the
    # validated configuration are no longer disjoint. base_mode gets an
    # _approx95 suffix so tuned_rows/best_lr never mix them with the exact
    # rows.
    n_approx_seeds = 1 if quick else 3
    for mode in ("sketch", "true_topk"):
        if mode not in grid_lrs:
            continue
        lr = best_lr(rows, mode)
        for seed in seeds[:n_approx_seeds]:
            label = f"{mode}_approx95_lr{lr}_s{seed}"
            if label in done:
                continue
            r = run_one("patches32", mode, quick,
                        variant=(label, ["--lr_scale", lr, "--seed", seed,
                                         "--topk_approx_recall", "0.95"]))
            r.update(base_mode=f"{mode}_approx95", lr=float(lr),
                     seed=int(seed))
            rows.append(r)
            done.add(label)
            with open(path, "w") as f:
                json.dump({"results": rows}, f, indent=1)
    return rows


# --- the round-5 persona_small tuning grid (VERDICT r4 Weak #1) -------------
# The d=124M headline previously pinned uncompressed to lr=0.01 — the LR
# tuned on gpt2-tiny (d~450k), never probed at this scale — with 2 seeds.
# Probe each headline mode at LRs STRADDLING its inherited point, then give
# the tuned-best 3 seeds, so the "sketch beats dense at 49.6x less upload"
# claim meets the same tuned-grid standard patches32 does.
GRID_SMALL_LRS = {
    "uncompressed": ["0.005", "0.01", "0.02"],
    "sketch": ["0.02", "0.04", "0.08"],
}
# 5 seeds at tuned-best — the same standard the patches32 grid meets
GRID_SMALL_SEEDS = ("21", "42", "77", "91", "17")


def run_grid_small(out: str = "RESULTS_grid_small",
                   quick: bool = False) -> list:
    """Resumable persona_small (mode x lr x seed) tuning grid.

    Incremental like ``run_grid``; existing RESULTS.json persona_small rows
    at matching (mode, lr, seed) are imported instead of re-run (each run
    costs 2-7 min of TPU)."""
    if quick:
        out = out + "_smoke"
    path = f"{out}.json"
    rows = []
    if os.path.exists(path) and not quick:
        with open(path) as f:
            rows = json.load(f)["results"]
    if not rows and os.path.exists("RESULTS.json") and not quick:
        # seed the grid with the already-run persona_small evidence
        with open("RESULTS.json") as f:
            prior = json.load(f)["results"]
        for r in prior:
            if r["task"] != "persona_small" or r["aborted"]:
                continue
            base = r["mode"].split("_s")[0].split("_lr")[0]
            if base not in GRID_SMALL_LRS:
                continue
            imported = dict(r)
            imported.update(
                mode=_grid_label(base, f"{r['lr']:g}", str(r["seed"])),
                base_mode=base)
            rows.append(imported)
    done = {r["mode"] for r in rows}
    grid_lrs = GRID_SMALL_LRS
    seeds = GRID_SMALL_SEEDS
    if quick:
        grid_lrs = {m: lrs[:2] for m, lrs in GRID_SMALL_LRS.items()}
        seeds = GRID_SMALL_SEEDS[:2]

    def launch(mode, lr, seed, label):
        if label in done:
            return
        r = run_one("persona_small", mode, quick,
                    variant=(label, ["--lr_scale", lr, "--seed", seed]))
        r.update(base_mode=mode, lr=float(lr), seed=int(seed))
        rows.append(r)
        done.add(label)
        with open(path, "w") as f:
            json.dump({"results": rows}, f, indent=1)

    # stage A: LR probe at the base seed
    for mode, lrs in grid_lrs.items():
        for lr in lrs:
            launch(mode, lr, seeds[0], _grid_label(mode, lr, seeds[0]))
    # stage B: remaining seeds at each mode's tuned-best LR
    for mode in grid_lrs:
        lr = best_lr_small(rows, mode)
        for seed in seeds[1:]:
            launch(mode, lr, seed, _grid_label(mode, lr, seed))
    return rows


def best_lr_small(rows: list, mode: str) -> str:
    """Tuned-best persona_small LR: lowest base-seed val nll, diverged
    runs excluded."""
    base_seed = int(GRID_SMALL_SEEDS[0])
    cand = [(r["final_nll"], r["lr"]) for r in rows
            if r.get("base_mode") == mode and r.get("seed") == base_seed
            and not r["aborted"] and r.get("final_nll") is not None]
    if not cand:
        raise RuntimeError(f"no surviving grid_small rows for {mode}")
    return f"{min(cand)[1]:g}"


def tuned_rows_small(grid: list) -> list:
    """One representative persona_small row per mode: the base-seed run at
    the tuned-best LR, annotated with nll seed statistics."""
    out = []
    for mode in GRID_SMALL_LRS:
        lr = float(best_lr_small(grid, mode))
        seed_rows = [r for r in grid
                     if r.get("base_mode") == mode and r.get("lr") == lr
                     and not r["aborted"]]
        nlls = [r["final_nll"] for r in seed_rows]
        rep = dict(next(r for r in seed_rows
                        if r["seed"] == int(GRID_SMALL_SEEDS[0])))
        rep.update(mode=mode, nll_mean=float(np.mean(nlls)),
                   nll_min=min(nlls), nll_max=max(nlls),
                   n_seeds=len(seed_rows),
                   n_diverged=len([r for r in grid
                                   if r.get("base_mode") == mode
                                   and r.get("lr") == lr and r["aborted"]]))
        out.append(rep)
    return out


def write_grid_small_markdown(grid: list,
                              path: str = "RESULTS_grid_small.md") -> None:
    lines = [
        "# Tuning grid — persona_small (gpt2-small, d=124,051,201)",
        "",
        "Every cell is a full 4-epoch federated run through the GPT2 "
        "entrypoint at the reference's compression config (sketch 5x500k, "
        "473.2 MiB dense upload). Stage A probes each mode's LR range at "
        "seed 21 (straddling the LR previously inherited untuned from the "
        "275x-smaller gpt2-tiny grid); stage B re-runs the tuned-best LR "
        "on the remaining seeds. Lower nll is better.",
        "",
        "| mode | lr | seed | final val nll | ppl |",
        "|---|---|---|---|---|",
    ]
    for r in sorted(grid, key=lambda r: (r["base_mode"], r["lr"],
                                         r["seed"])):
        cell = ("DIVERGED | —" if r["aborted"]
                else f"{r['final_nll']:.4f} | {r['final_ppl']:.2f}")
        lines.append(f"| {r['base_mode']} | {r['lr']:g} | {r['seed']} | "
                     f"{cell} |")
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


# --- the round-5 FedAvg-regime grid (VERDICT r4 Weak #2/#3) -----------------
# fedavg quietly tops the fixed-epoch patches32 table; the FetchSGD paper's
# claim is that it degrades where sketch holds: low participation and
# multi-epoch client drift. This grid holds the ROUND budget fixed (240
# communication rounds — the fedavg headline row's count; the earlier
# participation50 diagnostic was confounded by running 4x fewer rounds at
# fixed epochs) and varies participation {10%, 2%} x fedavg local epochs
# {1, 5}, vs sketch at the same round budget. num_epochs is set per cell so
# the LR schedule completes exactly at the budget (fractional final epochs
# truncate, training/cv.py).
REGIME_ROUNDS = 240
REGIME_SEEDS = ("21", "42", "77", "91", "17")
REGIME_LRS = {"fedavg": ["0.2", "0.05"], "sketch": ["0.2", "0.08"]}


def _regime_cells():
    cells = [("fedavg", W, le) for W in (10, 2) for le in (1, 5)]
    cells += [("sketch", W, None) for W in (10, 2)]
    return cells


_REGIME_DS = {}


def _regime_schedule(mode: str, W: int) -> tuple:
    """(num_epochs, pivot_epoch) such that schedule-rounds ==
    REGIME_ROUNDS and the LR peak stays at the same FRACTION of the run
    as the headline recipe. spe comes from the SAME batcher the run will
    use (FedBatcher over the real patches32 recipe), and the pivot ratio
    from the recipe's own parsed --pivot_epoch/--num_epochs, so neither
    the budget nor the schedule shape can silently drift from the
    recipe's constants (ADVICE: no re-hardcoded constants)."""
    from commefficient_tpu.data import FedBatcher
    from commefficient_tpu.training.args import build_parser
    from commefficient_tpu.training.cv import make_dataset
    argv = (task_flags("patches32", False)
            + mode_flags(mode, "patches32")
            + ["--num_workers", str(W)])
    args = build_parser().parse_args(argv)
    if "train" not in _REGIME_DS:
        _REGIME_DS["train"] = make_dataset(args, train=True)
    spe = FedBatcher(_REGIME_DS["train"], args.num_workers,
                     args.local_batch_size,
                     seed=args.seed).steps_per_epoch()
    epochs = REGIME_ROUNDS / spe
    return epochs, epochs * args.pivot_epoch / args.num_epochs


def run_regime(out: str = "RESULTS_regime", quick: bool = False) -> list:
    """Resumable fixed-round-budget grid: probe 2 LRs per cell at the base
    seed, then give the better one the remaining seeds."""
    if quick:
        out = out + "_smoke"
    path = f"{out}.json"
    rows = []
    if os.path.exists(path) and not quick:
        with open(path) as f:
            rows = json.load(f)["results"]
    done = {r["mode"] for r in rows}
    cells = _regime_cells()
    seeds = REGIME_SEEDS
    max_rounds = REGIME_ROUNDS
    if quick:
        cells = cells[:1] + cells[-1:]
        seeds = REGIME_SEEDS[:2]
        max_rounds = 6

    def cell_name(mode, W, le):
        # W workers of 100 clients == W% participation
        return f"{mode}_p{W}" + (f"_le{le}" if le else "")

    def launch(mode, W, le, lr, seed):
        name = cell_name(mode, W, le)
        label = f"{name}_lr{lr}_s{seed}"
        if label in done:
            return
        # keep the SCHEDULE SHAPE constant in round space: the headline
        # recipe peaks at pivot_epoch/num_epochs of the run (~21%); a
        # shorter num_epochs must scale the pivot with it, or
        # PiecewiseLinear gets non-monotonic knots (pivot 5 > num_epochs
        # 4.8) and np.interp returns garbage (code review r5)
        epochs, pivot = _regime_schedule(mode, W)
        extra = ["--lr_scale", lr, "--seed", seed,
                 "--num_workers", str(W),
                 "--num_epochs", f"{epochs:g}",
                 "--pivot_epoch", f"{pivot:g}"]
        if le:
            extra += ["--num_fedavg_epochs", str(le)]
        r = run_one("patches32", mode, quick, variant=(label, extra),
                    max_rounds=max_rounds)
        r.update(cell=name, lr=float(lr), seed=int(seed),
                 participation=W / 100.0, fedavg_epochs=le or 0)
        rows.append(r)
        done.add(label)
        with open(path, "w") as f:
            json.dump({"results": rows}, f, indent=1)

    # stage A: 2-LR probe per cell at the base seed
    for mode, W, le in cells:
        for lr in REGIME_LRS[mode]:
            launch(mode, W, le, lr, seeds[0])
    # stage B: remaining seeds at each cell's better LR
    for mode, W, le in cells:
        name = cell_name(mode, W, le)
        cand = [(r["final_test_acc"], r["lr"]) for r in rows
                if r.get("cell") == name and r["seed"] == int(seeds[0])
                and not r["aborted"] and r["final_test_acc"] is not None]
        if not cand:
            continue   # every probe LR diverged: recorded honestly
        lr = f"{max(cand)[1]:g}"
        for seed in seeds[1:]:
            launch(mode, W, le, lr, seed)
    return rows


def write_regime_markdown(rows: list,
                          path: str = "RESULTS_regime.md") -> None:
    lines = [
        "# FedAvg-breaking regime — patches32 at a FIXED round budget",
        "",
        f"Every run stops at {REGIME_ROUNDS} communication rounds with its "
        "LR schedule scaled to complete there (fractional final epochs), "
        "so cells differ ONLY in participation (workers of 100 clients) "
        "and fedavg local epochs — the axes the FetchSGD paper says break "
        "FedAvg. Each cell: 2-LR probe at seed 21, better LR re-run on "
        "the remaining seeds (5 per cell; the 2% cells were extended "
        "first when 3 seeds proved too few to order them). Note the "
        "modes see different amounts of data per "
        "round by definition (fedavg consumes whole clients per round; "
        "sketch consumes one 16-image minibatch per sampled client): the "
        "budget held fixed is COMMUNICATION, the federated constraint.",
        "",
        "| cell | participation | local epochs | lr | seed | final val acc |",
        "|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: (r["cell"], r["lr"], r["seed"])):
        acc = "DIVERGED" if r["aborted"] else f"{r['final_test_acc']:.4f}"
        lines.append(
            f"| {r['cell']} | {int(r['participation'] * 100)}% | "
            f"{r['fedavg_epochs'] or '—'} | {r['lr']:g} | {r['seed']} | "
            f"{acc} |")
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


# --- the straggler study (buffered async vs sync under faults) --------------
# Both arms run the SAME digits/local_topk recipe, the SAME seeded
# FaultModel parameters, and stop at the SAME simulated wall-clock budget;
# the only difference is the aggregation policy. The sync server pays the
# barrier: each round costs the slowest present client (or the full
# sync_timeout whenever any sampled client never reports — it cannot
# distinguish a dropout from a straggler until it has out-waited the
# chronic tail). The buffered server dispatches a cohort every
# dispatch_interval of simulated time and applies whenever M contributions
# have arrived, so stragglers overlap instead of serializing.
#
# Concurrency accounting (stated, not hidden): with dispatch_interval =
# base_latency the buffered server keeps ~W * E[latency]/base clients in
# flight (~2x sync's W at straggler_frac 0.25 x mult 5). That matches
# FedBuff's operating model — the async server exists to keep more
# clients productively in flight — but it means the comparison is
# "policy at its natural concurrency", not "identical client-hours".
STRAGGLER_SEEDS = (21, 42, 77)
STRAGGLER_ALPHAS = (0.0, 0.3, 0.6)
STRAGGLER_FAULTS = dict(straggler_frac=0.25, straggler_mult=5.0,
                        dropout_prob=0.10, crash_prob=0.02,
                        base_latency=1.0, latency_sigma=0.25)
#: the deeper-staleness regime (the ``deep_*`` arms): the M = W / 5x-tail
#: grid above measured a FLAT alpha sweep — contributions barely age before
#: they are applied, so the staleness discount has nothing to discount.
#: Here the apply threshold is raised to M = 2W slots (a contribution waits
#: across more cohorts before an apply) and the latency tail is heavy
#: enough (25x stragglers, sigma 0.75) that late arrivals carry REAL
#: staleness — the configuration where 1/(1+tau)^alpha can actually matter.
STRAGGLER_DEEP = dict(straggler_frac=0.25, straggler_mult=25.0,
                      dropout_prob=0.10, crash_prob=0.02,
                      base_latency=1.0, latency_sigma=0.75)
STRAGGLER_BUDGET = 600.0   # sim-seconds; ~60 data epochs for buffered


def _straggler_run(arm: str, alpha: float, seed: int, quick: bool,
                   deep: bool = False) -> dict:
    from commefficient_tpu.data.batching import FedBatcher, val_batches
    from commefficient_tpu.federated.faults import FaultModel
    from commefficient_tpu.training.cv import (build_learner, build_parser,
                                               make_dataset)

    argv = task_flags("digits", quick=False) + mode_flags("local_topk",
                                                          "digits")
    faults = STRAGGLER_DEEP if deep else STRAGGLER_FAULTS
    args = build_parser().parse_args(argv)
    args.lr_scale = 0.05          # the digits/local_topk tuned point
    args.seed = int(seed)
    if arm == "buffered":
        args.server_mode = "buffered"
        args.staleness_alpha = float(alpha)
        args.fault_seed = 1000 + int(seed)
        args.dispatch_interval = faults["base_latency"]
        for k in ("straggler_frac", "straggler_mult", "base_latency",
                  "latency_sigma"):
            setattr(args, k, faults[k])
        args.fault_dropout_prob = faults["dropout_prob"]
        args.fault_crash_prob = faults["crash_prob"]
        if deep:
            # M > W: an apply waits for 2 cohorts' worth of arrivals, so
            # every contribution ages in the buffer instead of being
            # applied the cohort it lands
            args.buffer_m = 2 * args.num_workers

    train_set = make_dataset(args, train=True)
    val_set = make_dataset(args, train=False)
    args.num_clients = train_set.num_clients
    batcher = FedBatcher(train_set, args.num_workers, args.local_batch_size,
                         seed=args.seed)
    ids0, cols0, _ = next(iter(batcher.epoch()))
    learner = build_learner(args, cols0[0][0][:1], train_set.num_classes, 1)

    T = 40.0 if quick else STRAGGLER_BUDGET
    np.random.seed(args.seed)
    t0 = time.time()

    def endless_rounds():
        while True:
            yield from batcher.epoch()

    rounds = applies = 0
    sim = 0.0
    if arm == "sync":
        # the sync arm drives the SAME fault schedule host-side: absent
        # clients' mask rows zero out (round.py treats an all-zero mask
        # row as a non-participant — no bytes, no contribution) and the
        # barrier bills the straggler tail / timeout to the sim clock
        fm = FaultModel(1000 + int(seed), args.num_clients, **faults)
        for ids, cols, mask in endless_rounds():
            if sim >= T:
                break
            present, _, dt = fm.sync_round(rounds, ids,
                                           valid=mask.sum(axis=1) > 0)
            sim += dt
            m = mask * present[:, None].astype(np.float32)
            # LR schedule indexed by SIM-CLOCK fraction on both arms, so
            # neither arm's anneal depends on how many rounds it fit
            learner.train_round(ids, cols, m,
                                epoch_frac=min(sim / T, 1.0)
                                * args.num_epochs)
            rounds += 1
        applies = rounds
        sim_final = sim
    else:
        for ids, cols, mask in endless_rounds():
            clock = learner.cohorts_done * learner.dispatch_interval
            if clock >= T:
                break
            # finalize every cohort: byte totals accumulate there, and a
            # TinyMLP metric sync costs ~nothing
            learner.finalize_round_metrics(learner.train_round_async(
                ids, cols, mask,
                epoch_frac=min(clock / T, 1.0) * args.num_epochs))
        learner.flush_faults()
        rounds = learner.cohorts_done
        applies = learner.applies_done
        sim_final = max(learner.sim_time,
                        learner.cohorts_done * learner.dispatch_interval)

    val = learner.evaluate(val_batches(val_set, args.valid_batch_size))
    label = arm if arm == "sync" else f"buffered_a{alpha:g}"
    if deep:
        label = f"deep_{label}"
    row = {
        "arm": label, "alpha": (None if arm == "sync" else float(alpha)),
        "seed": int(seed), "sim_budget": T, "deep": bool(deep),
        "buffer_m": (2 * args.num_workers
                     if deep and arm == "buffered" else None),
        "rounds": int(rounds), "applies": int(applies),
        "sim_time": round(float(sim_final), 1),
        "aborted": bool(np.asarray(learner.state.aborted)),
        "final_test_acc": float(val["metrics"][0]),
        "upload_mib": round(learner.total_upload_bytes / 2**20, 2),
        "download_mib": round(learner.total_download_bytes / 2**20, 2),
        "fault_stats": (dict(learner.fault_stats)
                        if hasattr(learner, "fault_stats") else None),
        "wall_seconds": round(time.time() - t0, 1),
    }
    print(f"[straggler/{label} s{seed}] acc={row['final_test_acc']:.4f} "
          f"rounds={rounds} applies={applies} "
          f"up={row['upload_mib']:.1f}MiB ({row['wall_seconds']:.0f}s)",
          flush=True)
    return row


#: persona-arm sim budget: ~STRAGGLER_PERSONA_BUDGET buffered cohorts of
#: gpt2-tiny (50 personas, W=4) ~ 5 data epochs, while the sync barrier
#: fits ~1 epoch under the same 5x tail — enough dispatch asymmetry for
#: the mechanism to separate in nll without digits' 600-unit budget
#: (each persona round is ~100x a TinyMLP round).
STRAGGLER_PERSONA_BUDGET = 60.0


def _straggler_run_persona(arm: str, alpha: float, seed: int,
                           quick: bool) -> dict:
    """The straggler protocol on the NLP benchmark shape (results.py
    'persona' task: gpt2-tiny double-heads on SyntheticPersona through
    the real tokenize + build_input_from_segments pipeline) — the
    mechanism measured beyond CIFAR-shaped CV. Same seeded FaultModel,
    same fixed simulated wall-clock budget, same resumable protocol;
    the learnable target is the token-weighted validation nll (lower is
    better). Constant LR on both arms: a round-indexed anneal would
    hand the faster-dispatching arm a different schedule."""
    import jax

    from commefficient_tpu.data.batching import FedBatcher, val_batches
    from commefficient_tpu.data.tokenizer import get_tokenizer
    from commefficient_tpu.federated.faults import FaultModel
    from commefficient_tpu.federated.losses import (make_gpt2_train_loss,
                                                    make_gpt2_val_loss)
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.training.args import (args_to_config,
                                                 learner_factory)
    from commefficient_tpu.training.gpt2 import (build_gpt2_parser,
                                                 make_persona)

    argv = task_flags("persona", quick=False) + mode_flags("local_topk",
                                                           "persona")
    faults = STRAGGLER_FAULTS
    args = build_gpt2_parser().parse_args(argv)
    args.lr_scale = 0.01          # the persona/local_topk tuned point
    args.seed = int(seed)
    if arm == "buffered":
        args.server_mode = "buffered"
        args.staleness_alpha = float(alpha)
        args.fault_seed = 1000 + int(seed)
        args.dispatch_interval = faults["base_latency"]
        for k in ("straggler_frac", "straggler_mult", "base_latency",
                  "latency_sigma"):
            setattr(args, k, faults[k])
        args.fault_dropout_prob = faults["dropout_prob"]
        args.fault_crash_prob = faults["crash_prob"]

    tokenizer = get_tokenizer(args.model_checkpoint)
    train_set = make_persona(args, tokenizer, train=True)
    val_set = make_persona(args, tokenizer, train=False)
    args.num_clients = train_set.num_clients
    gcfg = GPT2Config.tiny(vocab_size=tokenizer.vocab_size)
    gcfg.n_positions = max(gcfg.n_positions, args.max_seq_len)
    model = GPT2DoubleHeads(gcfg)
    loss_tr = make_gpt2_train_loss(model, args.lm_coef, args.mc_coef)
    loss_val = make_gpt2_val_loss(model)
    batcher = FedBatcher(train_set, args.num_workers, args.local_batch_size,
                         seed=args.seed)
    sample = tuple(c[:1] for c in train_set.get_flat_batch(np.arange(1)))
    sample_in = (sample[0], sample[4], sample[1])

    class _Wrap:
        def init(self, rng, s, train):
            return model.init(rng, *s, train=train)

        def apply(self, *a, **k):
            return model.apply(*a, **k)

    cfg = args_to_config(args, num_clients=args.num_clients,
                         max_seq_len=args.max_seq_len)
    learner_cls, learner_extra = learner_factory(args, cfg.num_clients)
    learner = learner_cls(_Wrap(), cfg, loss_tr, loss_val,
                          jax.random.PRNGKey(args.seed), sample_in,
                          lr_schedule=None, mesh=None, **learner_extra)

    T = 8.0 if quick else STRAGGLER_PERSONA_BUDGET
    np.random.seed(args.seed)
    t0 = time.time()

    def endless_rounds():
        while True:
            yield from batcher.epoch()

    rounds = applies = 0
    sim = 0.0
    if arm == "sync":
        # the sync arm drives the SAME fault schedule host-side (see
        # _straggler_run: absent clients' mask rows zero out, the barrier
        # bills the straggler tail / timeout to the sim clock)
        fm = FaultModel(1000 + int(seed), args.num_clients, **faults)
        for ids, cols, mask in endless_rounds():
            if sim >= T:
                break
            present, _, dt = fm.sync_round(rounds, ids,
                                           valid=mask.sum(axis=1) > 0)
            sim += dt
            m = mask * present[:, None].astype(np.float32)
            learner.train_round(ids, cols, m)
            rounds += 1
        applies = rounds
        sim_final = sim
    else:
        for ids, cols, mask in endless_rounds():
            clock = learner.cohorts_done * learner.dispatch_interval
            if clock >= T:
                break
            learner.finalize_round_metrics(
                learner.train_round_async(ids, cols, mask))
        learner.flush_faults()
        rounds = learner.cohorts_done
        applies = learner.applies_done
        sim_final = max(learner.sim_time,
                        learner.cohorts_done * learner.dispatch_interval)

    val = learner.evaluate(val_batches(val_set, args.valid_batch_size))
    m = np.asarray(val["metrics"], np.float64)
    nll = float(m[1]) / max(float(m[2]), 1e-9)
    label = ("persona_sync" if arm == "sync"
             else f"persona_buffered_a{alpha:g}")
    row = {
        "arm": label, "task": "persona",
        "alpha": (None if arm == "sync" else float(alpha)),
        "seed": int(seed), "sim_budget": T, "deep": False,
        "buffer_m": None,
        "rounds": int(rounds), "applies": int(applies),
        "sim_time": round(float(sim_final), 1),
        "aborted": bool(np.asarray(learner.state.aborted)),
        "final_nll": round(nll, 4),
        "final_ppl": round(float(np.exp(min(nll, 20.0))), 2),
        "upload_mib": round(learner.total_upload_bytes / 2**20, 2),
        "download_mib": round(learner.total_download_bytes / 2**20, 2),
        "fault_stats": (dict(learner.fault_stats)
                        if hasattr(learner, "fault_stats") else None),
        "wall_seconds": round(time.time() - t0, 1),
    }
    print(f"[straggler/{label} s{seed}] nll={nll:.4f} "
          f"rounds={rounds} applies={applies} "
          f"up={row['upload_mib']:.1f}MiB ({row['wall_seconds']:.0f}s)",
          flush=True)
    return row


def run_straggler(out: str = "RESULTS_straggler",
                  quick: bool = False) -> list:
    """Resumable sync-vs-buffered grid at a fixed simulated wall-clock
    budget: seeds x (sync, buffered at each staleness alpha)."""
    if quick:
        out = out + "_smoke"
    path = f"{out}.json"
    rows = []
    if os.path.exists(path) and not quick:
        with open(path) as f:
            rows = json.load(f)["results"]
    done = {(r["arm"], r["seed"]) for r in rows}
    seeds = STRAGGLER_SEEDS[:1] if quick else STRAGGLER_SEEDS
    alphas = STRAGGLER_ALPHAS[1:2] if quick else STRAGGLER_ALPHAS
    jobs = [("sync", 0.0, s, False) for s in seeds]
    jobs += [("buffered", a, s, False) for a in alphas for s in seeds]
    if not quick:
        # the deeper-staleness regime (M = 2W, 25x tail): same resumable
        # protocol, labels prefixed deep_
        jobs += [("sync", 0.0, s, True) for s in seeds]
        jobs += [("buffered", a, s, True)
                 for a in STRAGGLER_ALPHAS for s in seeds]
    # the persona arms (gpt2-tiny NLP — the mechanism beyond CIFAR-shaped
    # CV): same resumable protocol, labels prefixed persona_
    persona_jobs = [("sync", 0.0, s) for s in seeds]
    persona_jobs += [("buffered", a, s) for a in alphas for s in seeds]
    for arm, alpha, seed, deep in jobs:
        label = arm if arm == "sync" else f"buffered_a{alpha:g}"
        if deep:
            label = f"deep_{label}"
        if (label, seed) in done:
            continue
        rows.append(_straggler_run(arm, alpha, seed, quick, deep=deep))
        with open(path, "w") as f:
            json.dump({"results": rows, "faults": STRAGGLER_FAULTS,
                       "deep_faults": STRAGGLER_DEEP,
                       "budget": STRAGGLER_BUDGET if not quick else 40.0,
                       "seeds": list(seeds)}, f, indent=1)
    for arm, alpha, seed in persona_jobs:
        label = ("persona_sync" if arm == "sync"
                 else f"persona_buffered_a{alpha:g}")
        if (label, seed) in done:
            continue
        rows.append(_straggler_run_persona(arm, alpha, seed, quick))
        with open(path, "w") as f:
            json.dump({"results": rows, "faults": STRAGGLER_FAULTS,
                       "deep_faults": STRAGGLER_DEEP,
                       "budget": STRAGGLER_BUDGET if not quick else 40.0,
                       "persona_budget": (STRAGGLER_PERSONA_BUDGET
                                          if not quick else 8.0),
                       "seeds": list(seeds)}, f, indent=1)
    return rows


def write_straggler_markdown(rows: list,
                             path: str = "RESULTS_straggler.md") -> None:
    persona = [r for r in rows if r.get("task") == "persona"]
    rows = [r for r in rows if r.get("task") != "persona"]
    lines = [
        "# Stragglers and dropouts — buffered async vs the sync barrier",
        "",
        "digits/local_topk (TinyMLP d=2,410, 100 clients non-iid, 10 "
        "sampled per round, k=120), both arms under the SAME seeded fault "
        f"model ({STRAGGLER_FAULTS['straggler_frac']:.0%} chronic "
        f"stragglers at {STRAGGLER_FAULTS['straggler_mult']:g}x latency, "
        f"{STRAGGLER_FAULTS['dropout_prob']:.0%} dropout + "
        f"{STRAGGLER_FAULTS['crash_prob']:.0%} crash per client-round) and "
        "the SAME simulated wall-clock budget. The sync server pays the "
        "barrier — a round costs the slowest present client, or the full "
        "timeout whenever anyone sampled never reports; the buffered "
        "server (FedBuff-style, staleness weight 1/(1+tau)^alpha) keeps "
        "dispatching cohorts and applies every M arrivals, so stragglers "
        "overlap. Its natural concurrency is ~2x sync's in-flight clients "
        "at these fault rates (see results.py for the accounting).",
        "",
        "The `deep_*` arms rerun the grid in a deeper-staleness regime: "
        f"the apply threshold is raised to M = 2W buffer slots (a "
        f"contribution waits across more cohorts before an apply) and the "
        f"latency tail is heavier ({STRAGGLER_DEEP['straggler_mult']:g}x "
        f"stragglers, sigma {STRAGGLER_DEEP['latency_sigma']:g}), so late "
        "arrivals carry real staleness — the configuration where the "
        "1/(1+tau)^alpha discount has actual work to do. The shallow grid "
        "measured a flat alpha sweep; this is the arm that tests whether "
        "that was a property of the discount or of the regime.",
        "",
        "| arm | seed | rounds | applies | final val acc | up (MiB) |",
        "|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: (r["arm"], r["seed"])):
        acc = "DIVERGED" if r["aborted"] else f"{r['final_test_acc']:.4f}"
        lines.append(f"| {r['arm']} | {r['seed']} | {r['rounds']} | "
                     f"{r['applies']} | {acc} | {r['upload_mib']:.1f} |")
    arms = sorted({r["arm"] for r in rows})
    lines.append("")
    lines.append("| arm | mean acc | min..max | mean applies |")
    lines.append("|---|---|---|---|")
    means = {}
    for arm in arms:
        sub = [r for r in rows if r["arm"] == arm and not r["aborted"]]
        if not sub:
            lines.append(f"| {arm} | DIVERGED | — | — |")
            continue
        accs = [r["final_test_acc"] for r in sub]
        means[arm] = float(np.mean(accs))
        lines.append(f"| {arm} | {np.mean(accs):.4f} | "
                     f"{min(accs):.4f}..{max(accs):.4f} | "
                     f"{np.mean([r['applies'] for r in sub]):.0f} |")
    for regime, prefix in (("shallow (M = W, 5x tail)", ""),
                           ("deep (M = 2W, 25x tail)", "deep_")):
        sync_arm = prefix + "sync"
        bufs = {a: m for a, m in means.items()
                if a.startswith(prefix + "buffered")}
        if not prefix:
            bufs = {a: m for a, m in bufs.items()
                    if not a.startswith("deep_")}
        if sync_arm not in means or not bufs:
            continue
        best_buf = max(bufs, key=lambda a: bufs[a])
        delta = bufs[best_buf] - means[sync_arm]
        verdict = ("confirms" if delta > 0 else "REFUTES")
        lines.append("")
        lines.append(
            f"In the {regime} regime the best buffered arm ({best_buf}) "
            f"lands {delta:+.4f} accuracy vs {sync_arm} — this {verdict} "
            "the claim that buffered aggregation dominates under a "
            "straggler/dropout regime at fixed wall-clock. The alpha "
            "sweep for this regime reads directly off the summary table "
            "above.")
    deep_alpha = {a: m for a, m in means.items()
                  if a.startswith("deep_buffered")}
    if len(deep_alpha) > 1:
        spread = max(deep_alpha.values()) - min(deep_alpha.values())
        per_seed = [r["final_test_acc"] for r in rows
                    if r["arm"] in deep_alpha and not r["aborted"]]
        noise = max(per_seed) - min(per_seed) if per_seed else 0.0
        sweep = ", ".join(
            f"alpha={a.split('_a')[-1]}: {deep_alpha[a]:.4f}"
            for a in sorted(deep_alpha))
        lines.append("")
        lines.append(
            f"Staleness-discount verdict (the honest part): the deep "
            f"alpha sweep spans {spread:.4f} accuracy ({sweep}) against a "
            f"{noise:.4f} per-seed spread within the deep buffered arms. "
            + ("The discount separates from noise in this regime."
               if spread > noise else
               "Even with M = 2W forcing every contribution to age and a "
               "25x tail, the 1/(1+tau)^alpha discount stays within seed "
               "noise — the flat shallow-regime sweep was a property of "
               "the discount (uniform cohort staleness under FIFO "
               "dispatch), not of insufficient staleness depth."))
    if persona:
        lines += [
            "",
            "## The mechanism beyond CIFAR-shaped CV — persona (GPT2)",
            "",
            "Same protocol on the NLP benchmark shape (gpt2-tiny "
            "double-heads on SyntheticPersona, 50 personas = natural "
            "clients, local_topk k=4k, constant LR on both arms), same "
            "seeded fault model, fixed simulated budget of "
            f"{STRAGGLER_PERSONA_BUDGET:g} units. The learnable target "
            "is the token-weighted validation nll — LOWER is better.",
            "",
            "| arm | seed | rounds | applies | final val nll (ppl) | "
            "up (MiB) |",
            "|---|---|---|---|---|---|",
        ]
        for r in sorted(persona, key=lambda r: (r["arm"], r["seed"])):
            nll = ("DIVERGED" if r["aborted"]
                   else f"{r['final_nll']:.4f} ({r['final_ppl']:.2f})")
            lines.append(f"| {r['arm']} | {r['seed']} | {r['rounds']} | "
                         f"{r['applies']} | {nll} | "
                         f"{r['upload_mib']:.1f} |")
        pmeans = {}
        for arm in sorted({r["arm"] for r in persona}):
            sub = [r for r in persona
                   if r["arm"] == arm and not r["aborted"]]
            if sub:
                pmeans[arm] = float(np.mean([r["final_nll"] for r in sub]))
        lines += ["", "| arm | mean nll | mean applies |", "|---|---|---|"]
        for arm in sorted(pmeans):
            sub = [r for r in persona
                   if r["arm"] == arm and not r["aborted"]]
            lines.append(f"| {arm} | {pmeans[arm]:.4f} | "
                         f"{np.mean([r['applies'] for r in sub]):.0f} |")
        bufs = {a: m for a, m in pmeans.items()
                if a.startswith("persona_buffered")}
        if "persona_sync" in pmeans and bufs:
            best = min(bufs, key=lambda a: bufs[a])
            delta = pmeans["persona_sync"] - bufs[best]
            verdict = "confirms" if delta > 0 else "REFUTES"
            lines += ["",
                      f"Best buffered arm ({best}) lands {delta:+.4f} nll "
                      f"below persona_sync at the same simulated budget — "
                      f"this {verdict} that the buffered mechanism "
                      "transfers beyond CIFAR-shaped CV to the GPT2 "
                      "persona shape."]
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


# --- the train-while-serve study (--online, ROADMAP item 2 scenario) --------
# The --serve_online loop end to end, measured: persona traffic is served
# by the paged personalized server, every (prompt, served-reply,
# gold-label) interaction becomes a federated example for its user,
# buffered cohorts train on the live store, and HotSwapCoordinator
# promotes the refreshed base weights through drain -> swap -> resubmit.
# Held-out per-user nll (ODD dialog positions — never served, never
# trained) is evaluated at EVERY swap boundary, both personalized
# (base + that user's sparse delta) and base-only; the gap is what the
# per-user deltas buy, the base trajectory is what the shared weights
# learned from traffic. The recipe is the tiny-gpt2 local_topk point the
# --serve_online e2e smoke (tests/test_online.py) proves out, scaled up
# to more users/dialogs and more swaps.
ONLINE_SEEDS = (3, 21, 42)
ONLINE_SWAPS = 4
# lr 0.5 (the e2e smoke's setting) is stable over 2 swaps but diverges
# by round 3-4 at this scale (momentum 0.9, 8-interaction rounds);
# 0.1 with 4 interactions per round improves on every seed.
ONLINE_LR = 0.1


def _online_argv() -> list:
    return [
        "--dataset_name", "SyntheticPersona", "--model", "gpt2-tiny",
        "--dataset_dir", "./dataset/results_online",
        "--synthetic_personas", "16", "--synthetic_dialogs", "4",
        "--max_seq_len", "64", "--num_workers", "4",
        "--local_batch_size", "4", "--valid_batch_size", "16",
        "--num_epochs", "1", "--weight_decay", "0",
        "--mode", "local_topk", "--local_momentum", "0.9",
        "--error_type", "local", "--client_state", "sparse", "--k", "16",
        "--server_mode", "buffered", "--serve_personalized",
        "--serve_online", "--serve_slots", "8",
        "--online_train_every", "4", "--online_swap_every", "1",
        "--lr_scale", str(ONLINE_LR), "--seed", "3",
    ]


def _online_run(seed: int, quick: bool) -> dict:
    from commefficient_tpu.online import run_online
    from commefficient_tpu.training.gpt2 import build_gpt2_parser

    args = build_gpt2_parser().parse_args(_online_argv())
    args.seed = int(seed)
    target = 2 if quick else ONLINE_SWAPS
    t0 = time.time()
    _, _, res = run_online(args, log=False, target_swaps=target)
    row = {
        "arm": "online", "seed": int(seed), "lr": float(args.lr_scale),
        "k": int(args.k), "target_swaps": target,
        "swaps": int(res["swaps"]),
        "dirty_swaps": int(res["dirty_swaps"]),
        "refused_swaps": int(res["refused_swaps"]),
        "rounds": int(res["rounds"]),
        "interactions": int(res["interactions"]),
        "collected": int(res["collected"]),
        "trajectory": res["heldout_trajectory"],
        "nll_first": float(res["heldout_nll_first"]),
        "nll_last": float(res["heldout_nll_last"]),
        "improved": bool(res["heldout_improved"]),
        "wall_seconds": round(time.time() - t0, 1),
    }
    print(f"[online s{seed}] heldout nll {row['nll_first']:.4f} -> "
          f"{row['nll_last']:.4f} over {row['swaps']} swaps, "
          f"{row['interactions']} interactions "
          f"({'improved' if row['improved'] else 'NOT improved'}; "
          f"{row['wall_seconds']:.0f}s)", flush=True)
    return row


def run_online_study(out: str = "RESULTS_online",
                     quick: bool = False) -> list:
    """Resumable per-seed train-while-serve runs (same incremental
    protocol as ``run_straggler``: one JSON row per completed run,
    rerunning skips what exists)."""
    if quick:
        out = out + "_smoke"
    path = f"{out}.json"
    rows = []
    if os.path.exists(path) and not quick:
        with open(path) as f:
            rows = json.load(f)["results"]
    done = {(r["arm"], r["seed"]) for r in rows}
    seeds = ONLINE_SEEDS[:1] if quick else ONLINE_SEEDS
    for seed in seeds:
        if ("online", seed) in done:
            continue
        rows.append(_online_run(seed, quick))
        with open(path, "w") as f:
            json.dump({"results": rows, "lr": ONLINE_LR,
                       "target_swaps": 2 if quick else ONLINE_SWAPS,
                       "seeds": list(seeds)}, f, indent=1)
    return rows


def write_online_markdown(rows: list,
                          path: str = "RESULTS_online.md") -> None:
    lines = [
        "# Train-while-serve — held-out per-user perplexity across hot "
        "swaps",
        "",
        "The --serve_online loop (online/loop.py) end to end: persona "
        "traffic served by the paged personalized server, every served "
        "interaction trained as a federated example for its user through "
        "buffered cohorts over the LIVE client store, and the refreshed "
        "base weights hot-swapped into the running server "
        "(drain -> fingerprint gate -> swap -> resubmit) every apply. "
        "gpt2-tiny / local_topk (k=16 sparse per-user rows), 16 synthetic "
        "personas x 4 dialogs, T=64. Held-out = each user's ODD dialog "
        "positions — never served, never trained. Both trajectories are "
        "evaluated at every swap boundary: `personalized` is base + that "
        "user's current sparse delta (what an admitted user decodes "
        "under), `base` is the shared weights alone; the gap is what the "
        "per-user deltas buy on top of what the base learned from "
        "everyone's traffic.",
        "",
        "| seed | swaps | rounds | interactions | nll swap-0 | nll final "
        "| delta | base delta | dirty |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: r["seed"]):
        t0, tN = r["trajectory"][0], r["trajectory"][-1]
        bdelta = ((tN.get("mean_nll_base") or tN["mean_nll"])
                  - (t0.get("mean_nll_base") or t0["mean_nll"]))
        lines.append(
            f"| {r['seed']} | {r['swaps']} | {r['rounds']} | "
            f"{r['interactions']} | {r['nll_first']:.4f} | "
            f"{r['nll_last']:.4f} | {r['nll_last'] - r['nll_first']:+.4f} "
            f"| {bdelta:+.4f} | {r['dirty_swaps']} |")
    lines += [
        "",
        "## Trajectories (mean held-out nll at each swap boundary)",
        "",
        "| seed | swaps landed | personalized nll | base nll | "
        "personalization gap |",
        "|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: r["seed"]):
        for t in r["trajectory"]:
            b = t.get("mean_nll_base")
            gap = (f"{t['mean_nll'] - b:+.4f}" if b is not None else "—")
            lines.append(
                f"| {r['seed']} | {t['swaps']} | {t['mean_nll']:.4f} | "
                f"{(f'{b:.4f}' if b is not None else '—')} | {gap} |")
    deltas = [r["nll_last"] - r["nll_first"] for r in rows]
    dirty = sum(r["dirty_swaps"] for r in rows)
    refused = sum(r["refused_swaps"] for r in rows)
    if deltas:
        n_imp = sum(d < 0 for d in deltas)
        spread = max(deltas) - min(deltas) if len(deltas) > 1 else 0.0
        mean_d = float(np.mean(deltas))
        verdict = ("confirms" if n_imp == len(deltas) and mean_d < 0
                   else "REFUTES")
        lines += [
            "",
            f"Verdict: held-out per-user nll moved {mean_d:+.4f} on "
            f"average across {len(deltas)} seed(s) "
            f"({n_imp}/{len(deltas)} improved; cross-seed delta spread "
            f"{spread:.4f}) while the server stayed up — this {verdict} "
            "the ROADMAP item 2 scenario (personalization quality "
            "improves from live traffic across hot swaps). "
            f"{dirty} dirty swap(s) and {refused} fingerprint "
            "refusal(s) across every run: each swap drained its "
            "in-flight slots before weights moved (the online_loop "
            "audit target enforces the same contract in CI).",
        ]
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


def best_lr(rows: list, mode: str) -> str:
    """Tuned-best LR for a mode: highest base-seed accuracy, diverged runs
    excluded (a diverging LR is outside the feasible set, not a 0-acc run)."""
    base_seed = int(GRID_SEEDS[0])
    cand = [(r["final_test_acc"], r["lr"]) for r in rows
            if r.get("base_mode") == mode and r.get("seed") == base_seed
            and not r["aborted"] and r["final_test_acc"] is not None
            and "diag" not in r["mode"]]
    if not cand:
        raise RuntimeError(f"no surviving grid rows for {mode}")
    return f"{max(cand)[1]:g}"


SWEEP = [
    # the paper's actual deliverable is a CURVE: accuracy at several byte
    # budgets per mode. Variants override the compression size flags on
    # the patches32 recipe; labels name the upload budget per client/round.
    ("sketch", "sketch_5x200k_k20k",
     ["--num_rows", "5", "--num_cols", "200000", "--k", "20000"]),
    ("sketch", "sketch_5x100k_k10k",
     ["--num_rows", "5", "--num_cols", "100000", "--k", "10000"]),
    ("sketch", "sketch_5x50k_k5k",
     ["--num_rows", "5", "--num_cols", "50000", "--k", "5000"]),
    ("true_topk", "true_topk_k10k", ["--k", "10000"]),
    ("local_topk", "local_topk_k200k", ["--k", "200000"]),
]


def run_one(task: str, mode: str, quick: bool, variant=None,
            max_rounds=None) -> dict:
    if task.startswith("persona"):
        from commefficient_tpu.training.gpt2 import (
            build_gpt2_parser as build_parser, train)
    else:
        from commefficient_tpu.training.cv import build_parser, train
    argv = task_flags(task, quick) + mode_flags(mode, task, quick)
    # per-mode LR: fedavg applies lr worker-side over whole-client local
    # epochs; local_topk's local momentum (0.9) + error feedback compound
    # the effective step ~1/(1-m)x (measured: NaN at the base LR's ramp)
    lr_override = {
        ("patches32", "fedavg"): "0.05",
        ("patches32", "local_topk"): "0.02",
        ("digits", "fedavg"): "0.05",
        ("digits", "local_topk"): "0.05",
        # dense persona updates need the gentler LR (measured: 0.04 and
        # even 0.02 plateau at nll ~2.8; 0.01 reaches ~0.69)
        ("persona", "uncompressed"): "0.01",
        ("persona", "true_topk"): "0.01",
        ("persona", "fedavg"): "0.02",   # 0.01 measured worse (3.08 vs 2.29)
        ("persona", "local_topk"): "0.01",
        # gpt2-small starts from the tiny-scale tuned points; dense modes
        # use the gentler LR there too
        ("persona_small", "uncompressed"): "0.01",
        ("persona_small", "local_topk"): "0.01",
    }.get((task, mode))
    if lr_override is not None:
        i = argv.index("--lr_scale")
        argv[i + 1] = lr_override
    label = mode
    if variant is not None:
        label, extra = variant
        argv = argv + extra
    args = build_parser().parse_args(argv)
    np.random.seed(args.seed)
    t0 = time.time()
    if max_rounds is None and quick:
        max_rounds = 8
    learner, row = train(args, max_rounds=max_rounds, log=False)
    wall = time.time() - t0
    aborted = bool(row.get("aborted", False))
    d = learner.cfg.grad_size
    up_per_client_round = 4.0 * learner.cfg.upload_floats_per_client
    out = {
        "task": task, "mode": label, "aborted": aborted,
        "grad_size": d,
        "lr": float(args.lr_scale),
        "seed": int(args.seed),
        "final_test_acc": (None if aborted or "test_acc" not in row
                           else float(row["test_acc"])),
        "final_nll": (float(row["nll"]) if not aborted and "nll" in row
                      else None),
        "final_ppl": (float(row["ppl"]) if not aborted and "ppl" in row
                      else None),
        "final_train_loss": (None if aborted or "train_loss" not in row
                             else float(row["train_loss"])),
        "epochs": None if aborted or "epoch" not in row
        else int(row["epoch"]),
        "rounds": int(learner.rounds_done),
        "upload_bytes_total": float(learner.total_upload_bytes),
        "download_bytes_total": float(learner.total_download_bytes),
        "upload_bytes_per_client_round": up_per_client_round,
        "wall_seconds": round(wall, 1),
    }
    headline = (f"nll={out['final_nll']}" if task.startswith("persona")
                else f"acc={out['final_test_acc']}")
    print(f"[{task}/{label}] {headline} "
          f"up={out['upload_bytes_total']/2**20:.1f}MiB "
          f"down={out['download_bytes_total']/2**20:.1f}MiB "
          f"rounds={out['rounds']} ({wall:.0f}s)", flush=True)
    return out


def tuned_rows(grid: list) -> list:
    """One representative patches32 row per mode from the grid: the seed-21
    run at the tuned-best LR, annotated with the seed statistics (acc mean /
    min / max over GRID_SEEDS) so RESULTS.md reports tuned-best vs
    tuned-best with error bars, never a single untuned run."""
    out = []
    for mode in GRID_LRS:
        lr = float(best_lr(grid, mode))
        seed_rows = [r for r in grid
                     if r.get("base_mode") == mode and r.get("lr") == lr
                     and "diag" not in r["mode"] and not r["aborted"]]
        accs = [r["final_test_acc"] for r in seed_rows]
        rep = dict(next(r for r in seed_rows
                        if r["seed"] == int(GRID_SEEDS[0])))
        rep.update(mode=mode, acc_mean=float(np.mean(accs)),
                   acc_min=min(accs), acc_max=max(accs),
                   n_seeds=len(accs),
                   final_test_acc=float(np.mean(accs)))
        out.append(rep)
    return out


def write_grid_markdown(grid: list, path: str = "RESULTS_grid.md") -> None:
    lines = [
        "# Tuning grid — patches32, per-mode LR x seed",
        "",
        "Every cell is a full 24-epoch federated run on the spatially "
        "disjoint Patches32 split (data/offline.py). Stage A probes "
        "each mode's LR range at seed 21; stage B re-runs the tuned-best "
        "LR on the remaining seeds; stage C probes local_topk's failure "
        "mechanism (see results.py LOCAL_TOPK_DIAG).",
        "",
        "## Stage A+B: accuracy by (mode, lr, seed)",
        "",
        "| mode | lr | seed | final val acc |",
        "|---|---|---|---|",
    ]
    main_rows = [r for r in grid if "diag" not in r["mode"]
                 and "approx95" not in r["mode"]]
    for r in sorted(main_rows, key=lambda r: (r["base_mode"], r["lr"],
                                              r["seed"])):
        acc = "DIVERGED" if r["aborted"] else f"{r['final_test_acc']:.4f}"
        lines.append(f"| {r['base_mode']} | {r['lr']:g} | {r['seed']} | "
                     f"{acc} |")
    diag = [r for r in grid if "diag" in r["mode"]]
    if diag:
        base = next((r for r in main_rows
                     if r["base_mode"] == "local_topk"
                     and f"{r['lr']:g}" == best_lr(grid, "local_topk")
                     and r["seed"] == int(GRID_SEEDS[0])), None)
        lines += ["", "## Stage C: local_topk mechanism diagnostics", "",
                  "Baseline = tuned local_topk (k=50k, non-iid, 10% "
                  "participation"
                  + (f", acc {base['final_test_acc']:.4f}" if base else "")
                  + "). Round 3 reported local_topk ~2x below the other "
                  "modes; that gap was an artifact of the leaky "
                  "interleaved split (ADVICE r3) — at its tuned LR on the "
                  "disjoint split, local_topk sits in the pack (stage A), "
                  "and the implementation is verified against a "
                  "hand-computed two-round trace (tests/test_round.py). "
                  "The knobs below probe the residual mechanism: k and "
                  "iid move accuracy within ordinary seed noise "
                  "(stage B spread is ~±0.04), i.e. no pathological "
                  "k-sensitivity or heterogeneity failure. The "
                  "participation run is NOT directly comparable: 50 "
                  "clients/round at fixed epochs means 4x fewer rounds "
                  "and LR-schedule updates (rounds column in the JSON), "
                  "so its low score measures an undertrained schedule, "
                  "not participation itself — the fixed-ROUND-budget "
                  "participation comparison lives in RESULTS_regime.md "
                  "(results.py --regime), which isolates the axis "
                  "properly.", "",
                  "| variant | final val acc | upload/client/round |",
                  "|---|---|---|"]
        for r in diag:
            acc = "DIVERGED" if r["aborted"] else f"{r['final_test_acc']:.4f}"
            lines.append(
                f"| {r['mode']} | {acc} | "
                f"{r['upload_bytes_per_client_round']/2**20:.2f} MiB |")
    approx = [r for r in grid if "approx95" in r["mode"]]
    if approx:
        lines += ["", "## Stage D: approx-top-k accuracy license", "",
                  "Same tuned recipes with `--topk_approx_recall 0.95` — "
                  "the TPU-native approximate selector "
                  "(jax.lax.approx_max_k; coordinates the approximate "
                  "selector misses stay in the error-feedback accumulator "
                  "and are recovered in later rounds). Compare each row "
                  "against the same (mode, lr, seed) exact row in the "
                  "stage A+B table.", "",
                  "| mode | lr | seed | approx acc | exact acc (same "
                  "recipe) |", "|---|---|---|---|---|"]
        exact = {(r["base_mode"], r["lr"], r["seed"]): r for r in main_rows}
        for r in sorted(approx, key=lambda r: (r["base_mode"], r["seed"])):
            base = r["base_mode"].replace("_approx95", "")
            e = exact.get((base, r["lr"], r["seed"]))
            acc = "DIVERGED" if r["aborted"] else f"{r['final_test_acc']:.4f}"
            eacc = ("—" if e is None else "DIVERGED" if e["aborted"]
                    else f"{e['final_test_acc']:.4f}")
            lines.append(f"| {base} | {r['lr']:g} | {r['seed']} | {acc} | "
                         f"{eacc} |")
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


def fold_into_results(tuned: list, replaced) -> None:
    """Replace the RESULTS.{json,md} rows matching ``replaced(row)`` with
    tuned-grid rows and rewrite both artifacts together (shared by the
    --grid and --grid_small folds)."""
    results = []
    if os.path.exists("RESULTS.json"):
        with open("RESULTS.json") as f:
            results = [r for r in json.load(f)["results"]
                       if not replaced(r)]
    results = results + tuned
    task_idx = {"patches32": 0, "digits": 1, "persona": 2}
    results.sort(key=lambda r: (task_idx.get(r["task"], 3), r["mode"]))
    with open("RESULTS.json", "w") as f:
        json.dump({"quick": False, "results": results}, f, indent=1)
    write_markdown(results)


def write_markdown(results: list, path: str = "RESULTS.md") -> None:
    lines = [
        "# RESULTS — accuracy vs. communication (real data, real runs)",
        "",
        "Every row is a full federated training run through "
        "`commefficient_tpu.training.cv.train` (the user-facing entrypoint) "
        "on one real TPU chip; no synthetic gradients, no smoke shortcuts. "
        "The datasets are real pixels available offline "
        "(`commefficient_tpu/data/offline.py`): the canonical CIFAR-10 "
        "pickles cannot be fetched in this zero-egress environment, so the "
        "run recipe (100 clients non-iid class-per-client, 10 sampled per "
        "round, PiecewiseLinear LR 0->0.4@5->0@24, sketch 5x500k k=50k at "
        "d=6.57M) — the reference's own CIFAR recipe — is applied to the "
        "closest real-statistics proxies. See results.py docstring for the "
        "exact definition of each task.",
        "",
        "Upload/download byte semantics are the reference's "
        "(fed_aggregator.py:239-299): upload = 4 bytes x mode-dependent "
        "count x clients per round; download = 4 bytes x weights changed "
        "since the client last participated.",
        "",
    ]
    for task in dict.fromkeys(r["task"] for r in results):
        rows = [r for r in results if r["task"] == task]
        base = next((r for r in rows if r["mode"] == "uncompressed"), None)
        persona = task.startswith("persona")
        metric_hdr = ("final val nll | ppl" if persona
                      else "final val acc")
        lines += [f"## {task}", ""]
        if persona:
            lines += ["(lower nll is better; the synthetic MC candidates "
                      "carry no signal, so nll/ppl is the learnable "
                      "target — results.py docstring)", ""]
        seed_rows = [r for r in rows if "_s" in r["mode"]
                     and r["mode"].rsplit("_s", 1)[-1].isdigit()]
        if seed_rows:
            lines += ["`mode_sNN` rows re-run that mode at seed NN with "
                      "an otherwise identical recipe (base rows are "
                      "seed 21) — the seed-robustness evidence for this "
                      "task.", ""]
        lines += [f"| mode | lr | {metric_hdr} | upload/client/round | "
                  "upload total | upload vs uncompressed | download total | "
                  "rounds | wall |",
                  "|---|---|---|" + "---|" * (7 if persona else 6)]
        for r in rows:
            lr_cell = f"{r['lr']:g}" if r.get("lr") is not None else "—"
            if r["aborted"]:
                div = "DIVERGED | —" if persona else "DIVERGED"
                lines.append(f"| {r['mode']} | {lr_cell} | {div} | — | — | "
                             f"— | — | {r['rounds']} | {r['wall_seconds']}s |")
                continue
            if persona and "nll_mean" in r:
                # tuned-grid row: seed mean with min-max spread
                metric_cell = (f"{r['nll_mean']:.4f} "
                               f"[{r['nll_min']:.4f}-{r['nll_max']:.4f}, "
                               f"{r['n_seeds']} seeds] | "
                               f"{math.exp(r['nll_mean']):.2f}")
            elif persona:
                metric_cell = f"{r['final_nll']:.4f} | {r['final_ppl']:.2f}"
            elif "acc_mean" in r:
                # tuned-grid row: seed mean with min-max spread
                metric_cell = (f"{r['acc_mean']:.4f} "
                               f"[{r['acc_min']:.4f}-{r['acc_max']:.4f}, "
                               f"{r['n_seeds']} seeds]")
            else:
                metric_cell = f"{r['final_test_acc']:.4f}"
            upx = (base["upload_bytes_total"] / r["upload_bytes_total"]
                   if base and r["upload_bytes_total"] else None)
            up_cell = f"{upx:.1f}x less" if upx is not None else "—"
            lines.append(
                f"| {r['mode']} | {lr_cell} | {metric_cell} | "
                f"{r['upload_bytes_per_client_round']/2**20:.2f} MiB | "
                f"{r['upload_bytes_total']/2**30:.2f} GiB | "
                f"{up_cell} | "
                f"{r['download_bytes_total']/2**30:.2f} GiB | "
                f"{r['rounds']} | {r['wall_seconds']:.0f}s |")
        lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="both",
                    choices=("patches32", "digits", "persona",
                             "persona_small", "both"))
    ap.add_argument("--modes", default=None,
                    help="comma list; default = all five modes (the three "
                         "supported ones for --task persona_small)")
    ap.add_argument("--quick", action="store_true",
                    help="8 rounds per mode — plumbing smoke, not results")
    ap.add_argument("--sweep", action="store_true",
                    help="run the byte-budget sweep variants (SWEEP) on "
                         "patches32 instead of the base modes")
    ap.add_argument("--grid", action="store_true",
                    help="run the patches32 LR x seed tuning grid + "
                         "local_topk diagnostics (resumable), then fold "
                         "tuned-best rows into RESULTS.{json,md}")
    ap.add_argument("--grid_small", action="store_true",
                    help="run the persona_small LR x seed tuning grid "
                         "(resumable), then fold tuned-best rows into "
                         "RESULTS.{json,md}")
    ap.add_argument("--regime", action="store_true",
                    help="run the fixed-round-budget FedAvg-regime grid "
                         "(participation x local epochs vs sketch) on "
                         "patches32 (resumable)")
    ap.add_argument("--straggler", action="store_true",
                    help="run the sync-vs-buffered straggler/dropout grid "
                         "(fixed simulated wall-clock budget, staleness "
                         "alpha sweep) on digits (resumable)")
    ap.add_argument("--online", action="store_true",
                    help="run the train-while-serve study (--serve_online "
                         "per-seed runs; held-out per-user perplexity "
                         "trajectory across hot swaps, resumable)")
    ap.add_argument("--out", default=None,
                    help="artifact basename (default RESULTS, or "
                         "RESULTS_smoke under --quick so a smoke run can "
                         "never clobber or leak into the real artifact)")
    args = ap.parse_args()
    if args.online:
        rows = run_online_study(quick=args.quick)
        if args.quick:
            write_online_markdown(rows, "RESULTS_online_smoke.md")
            print(f"quick online smoke done ({len(rows)} rows; real "
                  "artifacts untouched)")
            return
        write_online_markdown(rows)
        print("wrote RESULTS_online.{json,md}")
        return
    if args.straggler:
        rows = run_straggler(quick=args.quick)
        if args.quick:
            write_straggler_markdown(rows, "RESULTS_straggler_smoke.md")
            print(f"quick straggler smoke done ({len(rows)} rows; real "
                  "artifacts untouched)")
            return
        write_straggler_markdown(rows)
        print("wrote RESULTS_straggler.{json,md}")
        return
    if args.regime:
        rows = run_regime(quick=args.quick)
        if args.quick:
            write_regime_markdown(rows, "RESULTS_regime_smoke.md")
            print(f"quick regime smoke done ({len(rows)} rows; real "
                  "artifacts untouched)")
            return
        write_regime_markdown(rows)
        print("wrote RESULTS_regime.{json,md}")
        return
    if args.grid_small:
        grid = run_grid_small(quick=args.quick)
        if args.quick:
            write_grid_small_markdown(grid, "RESULTS_grid_small_smoke.md")
            print(f"quick grid_small smoke done ({len(tuned_rows_small(grid))}"
                  " tuned rows; real artifacts untouched)")
            return
        write_grid_small_markdown(grid)
        # replace the persona_small headline rows in RESULTS with tuned rows
        fold_into_results(
            tuned_rows_small(grid),
            lambda r: (r["task"] == "persona_small"
                       and (r["mode"] in GRID_SMALL_LRS
                            or r["mode"].split("_s")[0].split("_lr")[0]
                            in GRID_SMALL_LRS)))
        print("wrote RESULTS_grid_small.{json,md} and folded tuned rows "
              "into RESULTS.{json,md}")
        return
    if args.grid:
        grid = run_grid(quick=args.quick)
        if args.quick:
            # exercise the whole reporting path against smoke filenames so
            # a reporting bug can't survive to the end of the real grid
            write_grid_markdown(grid, "RESULTS_grid_smoke.md")
            print(f"quick grid smoke done ({len(tuned_rows(grid))} tuned "
                  "rows; real artifacts untouched)")
            return
        write_grid_markdown(grid)
        # replace the patches32 base-mode rows in RESULTS with tuned rows
        fold_into_results(tuned_rows(grid),
                          lambda r: (r["task"] == "patches32"
                                     and r["mode"] in MODES))
        print("wrote RESULTS_grid.{json,md} and folded tuned rows into "
              "RESULTS.{json,md}")
        return
    if args.out is None:
        args.out = "RESULTS_smoke" if args.quick else "RESULTS"
    elif args.quick and args.out == "RESULTS":
        raise SystemExit("--quick may not write the real RESULTS artifact")

    tasks = (["patches32", "digits", "persona", "persona_small"]
             if args.task == "both" else [args.task])
    # persona_small is the d=124M evidence run: only the three modes the
    # verdict asks for (fedavg/true_topk add ~20 min of TPU each for no
    # new ordering information at this scale). Defaulted mode lists trim
    # to the supported trio automatically; an EXPLICIT --modes request
    # with an unsupported mode must error, not produce zero jobs.
    ps_modes = {"uncompressed", "sketch", "local_topk"}
    if args.modes is None:
        modes = list(m for m in MODES
                     if args.task != "persona_small" or m in ps_modes)
    else:
        modes = [m.strip() for m in args.modes.split(",") if m.strip()]
        bad = set(modes) - set(MODES)
        if bad:
            raise SystemExit(f"unknown modes: {sorted(bad)}")
        if args.task == "persona_small":
            unsupported = set(modes) - ps_modes
            if unsupported:
                raise SystemExit(
                    f"persona_small only runs {sorted(ps_modes)} "
                    f"(got {sorted(unsupported)})")
    # persona_small/local_topk at the full 50 clients needs 2 x 50 x 124M
    # floats of per-client state — over one chip's HBM, but NOT over host
    # RAM: --client_state_offload parks the rows in TPU-host pinned memory
    # (the reference's shm capacity model, fed_aggregator.py:116-129) and
    # streams the 4 sampled rows per round. Replaces the round-4
    # reduced-client (4-client) artifact row.
    ps_lt_variant = ("local_topk", ["--client_state_offload"])
    jobs = [(t, m, ps_lt_variant
             if (t == "persona_small" and m == "local_topk") else None)
            for t in tasks for m in modes
            if not (t == "persona_small" and m not in ps_modes)]
    if args.sweep:
        if args.task != "both" or args.modes is not None:
            raise SystemExit("--sweep runs its own fixed job list; "
                             "--task/--modes would be silently ignored")
        if args.quick:
            raise SystemExit("--sweep is a real-budget curve; it has no "
                             "quick mode (variant sizes would override "
                             "the smoke sizes)")
        jobs = [("patches32", mode, (label, extra))
                for mode, label, extra in SWEEP]

    # incremental: merge into an existing artifact so one (task, mode) can
    # be rerun (e.g. after an LR adjustment) without repeating the suite
    results = []
    labels = {(t, v[0] if v else m) for t, m, v in jobs}
    if os.path.exists(args.out + ".json") and not args.quick:
        with open(args.out + ".json") as f:
            results = [r for r in json.load(f)["results"]
                       if (r["task"], r["mode"]) not in labels]

    task_idx = {"patches32": 0, "digits": 1, "persona": 2,
                "persona_small": 3}
    order = {(t, m): (ti, mi) for t, ti in task_idx.items()
             for mi, m in enumerate(MODES)}
    sort_key = lambda r: (*order.get((r["task"], r["mode"]),  # noqa: E731
                                     (task_idx.get(r["task"], 3), 9)),
                          r["mode"])
    for task, mode, variant in jobs:
        results.append(run_one(task, mode, args.quick, variant=variant))
        results.sort(key=sort_key)
        # JSON and markdown regenerate together after EVERY job, so an
        # interrupted run never leaves the artifact pair inconsistent
        with open(args.out + ".json", "w") as f:
            json.dump({"quick": args.quick, "results": results}, f,
                      indent=1)
        if not args.quick:
            write_markdown(results, args.out + ".md")
    print(f"wrote {args.out}.json" + ("" if args.quick
                                      else f" and {args.out}.md"))


if __name__ == "__main__":
    main()
