"""The comparison that decides ``correct`` for a training cell.

Numbers, each against a limit of its own (set in the cell's file from
readings on the chip, see PERF.md):

``loss_gap``       worst of the followed steps: |program - reference| over
                   |reference|.
``grad_norm_gap``  the first gradient as the optimizer got it, read from the
                   optimizer state after step one, by the worst leaf.
``dw_norm_gap``    the parameters' change over the followed steps, by the
                   worst leaf.

"By the worst leaf": the gap between the program's norm of a leaf and the
reference's (not the norm of their difference), over the reference's norm of
that leaf or of the median leaf, whichever is larger. Leaves whose reference
gradient is under a thousandth of the median leaf's are left out of the
change: they move by round-off alone.
"""

from __future__ import annotations

import numpy as np


def _norms(leaves):
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in leaves])


def worst_leaf_gap(program_leaves, reference_leaves, keep=None):
    p, r = _norms(program_leaves), _norms(reference_leaves)
    gaps = np.abs(p - r) / np.maximum(np.maximum(r, np.median(r)), 1e-30)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    return float(np.max(gaps))


def split(flat, slices):
    return [flat[a:b] for _, a, b in slices]


def opt_leaves(opt, slices):
    """Leaves of the optimizer state: the parameter tensors of a dense
    state, the rows of a sketch table."""
    opt = np.asarray(opt)
    return list(opt) if opt.ndim == 2 else split(opt, slices)


def training_numbers(program, reference, w0, slices):
    """``program`` and ``reference``: {"loss": [...], "opt_after_1": array,
    "w": array}; the reference also gives ``grad1_leaf_norms``."""
    ref_loss = np.asarray(reference["loss"], np.float64)
    prog_loss = np.asarray(program["loss"][:len(ref_loss)], np.float64)
    g = np.asarray(reference["grad1_leaf_norms"], np.float64)
    moved = g >= 1e-3 * np.median(g)
    return {
        "loss_gap": float(np.max(np.abs(prog_loss - ref_loss)
                                 / np.abs(ref_loss))),
        "grad_norm_gap": worst_leaf_gap(
            opt_leaves(program["opt_after_1"], slices),
            opt_leaves(reference["opt_after_1"], slices)),
        "dw_norm_gap": worst_leaf_gap(
            split(np.asarray(program["w"]) - w0, slices),
            split(np.asarray(reference["w"]) - w0, slices), keep=moved),
    }


def judge(numbers, limits):
    """[(name, value, limit, ok)] for every number that has a limit; a
    number that is not finite is not ok."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = (value is not None and np.isfinite(value)
              and value <= limit)
        rows.append((name, value, limit, bool(ok)))
    return rows
