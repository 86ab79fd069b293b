"""One of the program's own host spans (``commefficient_tpu/utils/
tracing.py``; ``params["span"]``): the median, in ms, over the rounds of
the window of what the round spent inside it, all its occurrences together
(a round fetches once a client). Rounds that hold a validation pass are
left out. A round is what lies between two dispatches, so it holds the
batch build that the dispatch after it waits for."""

import statistics

from benchlib import program


def read(obs, params):
    rounds = program.window_rounds(obs)
    if not rounds or not any(params["span"] in r["spans"] for r in rounds):
        return None
    return statistics.median(
        r["spans"].get(params["span"], [0])[0] for r in rounds) / 1e6
