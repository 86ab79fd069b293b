"""``correct`` of the looped model's cell, on its CPU rehearsal
(``rehearsal/ouro-tiny.json``, through ``entries/gpt2.py``): a sound run
comes out correct; the reference computed one precision below the
configuration's (fp8 operands for bfloat16), and the reference with the loop
left out (``single_pass``: the stack run once, one exit), each put in the
program's place, break a limit; and so does a sound program judged against
the faulty reference under the harness. The rehearsal's limits were read
from this rehearsal (program at most 2.2e-5 / 5.1e-4 / 0.009 over four
seeds, the fp8 control at least 4.4e-4 / 3.3e-3 / 0.11; the cell's own are
read on the chip, see PERF.md)."""

import numpy as np
import pytest

import run as harness


@pytest.fixture(scope="module")
def ouro_tiny():
    return harness.load_cell(rehearsal="ouro-tiny")


def devices():
    import jax
    return jax.devices()[:1]


def test_sound_run_is_correct(ouro_tiny):
    cell, config = ouro_tiny
    result = harness.run_cell(cell, config, 2147489011, 1.0, False,
                              devices())
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_planted_single_pass_is_not_correct(ouro_tiny):
    cell, config = ouro_tiny
    result = harness.run_cell(cell, config, 2147489012, 1.0, False,
                              devices(), fault="single_pass")
    assert not result["correct"], result["compared"]
    assert result["failed"] == 0          # the rounds themselves were sound


@pytest.mark.parametrize("precision,fault", [("fp8", None),
                                             ("bfloat16", "single_pass"),
                                             ("bfloat16", "half_batch")])
def test_control_and_faults_in_the_programs_place(ouro_tiny, precision,
                                                  fault):
    from benchlib import compare
    cell, config = ouro_tiny
    reference = harness.load_module("reference", config["reference"])
    reference.configure(config["model"])
    rng = np.random.default_rng(3)
    w0 = np.asarray(reference.make_weights(3))
    T = config["model"]["seq_len"]
    batches = []
    for _ in range(2):
        tokens = rng.integers(0, 256, (8, T)).astype(np.int32)
        labels = np.concatenate([tokens[:, 1:], -np.ones((8, 1), np.int32)],
                                axis=1)
        batches.append((tokens, labels.reshape(-1), np.ones(8, np.float32)))
    spec = {"mode": "sketch", "k": 4000, "num_rows": 5, "num_cols": 200000,
            "virtual_momentum": 0.9, "weight_decay": 0.0, "num_workers": 8,
            "lr_scale": 0.04, "total_steps": 48}
    ref = reference.steps(w0, batches, spec, "bfloat16")
    again = reference.steps(w0, batches, spec, "bfloat16")
    other = reference.steps(w0, batches, spec, precision, fault=fault)
    slices = reference.leaf_slices()
    same = compare.training_numbers(again, ref, w0, slices)
    wrong = compare.training_numbers(other, ref, w0, slices)
    assert all(ok for *_, ok in compare.judge(same, cell["limits"]))
    assert not all(ok for *_, ok in compare.judge(wrong, cell["limits"])), \
        wrong
