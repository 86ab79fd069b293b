"""``correct`` has to come out false where it must.

* The control: the plain reference computed one precision below the
  configuration's (fp8 operands for bfloat16), put in the program's place,
  has to break a limit — here at a size a test run can hold, with limits
  scaled from the chip's (the cell's own limits are read on the chip, see
  PERF.md).
* The faults a training cell can have, planted under the harness: a step
  that returns its state unchanged, and half of the batch left out with the
  mean taken over the rest. The test skips the harness's look for a chip
  and drives the rest of a run (``run.run_cell``) on the rehearsal cell.
"""

import numpy as np
import pytest

import run as harness


def devices():
    import jax
    return jax.devices()[:1]


def test_sound_run_is_correct(tiny):
    cell, config = tiny
    result = harness.run_cell(cell, config, 11, 1.0, False, devices())
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_control_one_precision_down_is_not_correct(tiny):
    from benchlib import compare
    cell, config = tiny
    reference = harness.load_module("reference", config["reference"])
    rng = np.random.default_rng(3)
    w0 = np.asarray(reference.make_weights(3))
    batches = [(rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
                rng.integers(0, 10, 16).astype(np.int32),
                np.ones(16, np.float32)) for _ in range(3)]
    spec = {"mode": "sketch", "k": 50000, "num_rows": 5, "num_cols": 500000,
            "virtual_momentum": 0.9, "weight_decay": 5e-4, "num_workers": 4,
            "lr_scale": 0.4, "pivot_epoch": 5, "num_epochs": 24,
            "rounds_per_epoch": 20}
    ref = reference.steps(w0, batches, spec, "bfloat16")
    again = reference.steps(w0, batches, spec, "bfloat16")
    control = reference.steps(w0, batches, spec, "fp8")
    slices = reference.leaf_slices()
    same = compare.training_numbers(again, ref, w0, slices)
    lower = compare.training_numbers(control, ref, w0, slices)
    assert all(ok for *_, ok in compare.judge(same, cell["limits"]))
    assert not all(ok for *_, ok in compare.judge(lower, cell["limits"])), \
        lower


def _break(monkeypatch, how):
    """Break the timed path underneath the harness's wrappers."""
    from commefficient_tpu.federated.api import FedLearner
    dispatch = FedLearner.train_round_async

    def state_unchanged(self, ids, cols, mask, **kw):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, self.state)   # the step donates it
        out = dispatch(self, ids, cols, mask, **kw)
        self.state = keep
        return out

    def half_batch(self, ids, cols, mask, **kw):
        import jax.numpy as jnp
        mask = jnp.asarray(mask)
        w = mask.shape[0]
        mask = mask.at[w // 2:].set(0.0)
        return dispatch(self, ids, cols, mask, **kw)

    monkeypatch.setattr(FedLearner, "train_round_async",
                        {"state_unchanged": state_unchanged,
                         "half_batch": half_batch}[how])


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, how):
    cell, config = tiny
    _break(monkeypatch, how)
    result = harness.run_cell(cell, config, 12, 1.0, False, devices())
    assert not result["correct"], result["compared"]
