"""Device time of one phase of the round program (``params["phase"]``: a
``phase:`` scope of ``commefficient_tpu/utils/tracing.py``) in the traced
window, in ms a round: own time of the first device's operations, each put
in the phase its instruction carries in the compiled round
(``benchlib.program.phase_times``). Nothing is returned unless 99 % of the
traced device time was found there by instruction: a split that guesses is
worse than none."""

from benchlib import program


def read(obs, params):
    p = obs["probe"]
    times = program.phase_times(obs)
    rounds = (p.trace_round1 or 0) - (p.trace_round0 or 0)
    if times is None or rounds <= 0:
        return None
    return times.get(params["phase"], 0) / rounds / 1e6
