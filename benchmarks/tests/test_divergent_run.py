"""A training that diverges says so in the result, not by an exit code.

The configuration guarantees a finite loss for the whole window. Where the
program's device guard trips inside the window (``train()`` aborts and
returns), the run still ends with a result: ``correct`` false, ``failed``
at least 1, the row ``rounds_aborted_or_not_finite`` over its limit. Where
it trips before the window opens there is no window to report, and the
error names the divergence. Planted under the harness's wrappers, as
``test_correct_fails.py`` plants its faults: one round is fed images that
are not finite.
"""

import pytest

import run as harness


def _poison_round(monkeypatch, index):
    """The dispatch numbered ``index`` (from 0) gets NaN images, so its loss
    is not finite and the round's guard latches ``aborted``."""
    from commefficient_tpu.federated.api import FedLearner
    dispatch = FedLearner.train_round_async
    seen = {"n": 0}

    def poisoned(self, ids, cols, mask, **kw):
        import jax.numpy as jnp
        if seen["n"] == index:
            images, labels = cols
            cols = (jnp.full_like(images, jnp.nan), labels)
        seen["n"] += 1
        return dispatch(self, ids, cols, mask, **kw)

    monkeypatch.setattr(FedLearner, "train_round_async", poisoned)


def test_abort_inside_the_window_is_a_failed_result(tiny, monkeypatch, capfd):
    import jax
    cell, config = tiny
    _poison_round(monkeypatch, cell["warmup_rounds"] + 1)
    # a window no CPU run reaches the end of: the abort has to close it
    result = harness.run_cell(cell, config, 13, 3600.0, False,
                              jax.devices()[:1])
    assert result["correct"] is False
    assert result["failed"] >= 1 and result["attempted"] > result["failed"]
    row = result["compared"]["rounds_aborted_or_not_finite"]
    assert row["value"] > row["limit"] == 0
    assert set(result["metrics"]) == set(cell["end_to_end"])
    assert "training diverged: loss nan at round" in capfd.readouterr().err


def test_abort_before_the_window_raises_and_names_the_divergence(
        tiny, monkeypatch):
    import jax
    cell, config = tiny
    _poison_round(monkeypatch, 0)
    with pytest.raises(RuntimeError, match="training diverged") as err:
        harness.run_cell(cell, config, 14, 1.0, False, jax.devices()[:1])
    assert "before the window opened" in str(err.value)
    assert "--num_epochs" not in str(err.value)
