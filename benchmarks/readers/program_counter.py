"""Counters the program keeps itself (``commefficient_tpu/utils/
tracing.py``), summed over ``params["counters"]``: with ``params["at"] ==
"window_start"`` their value when the window opened (set-up's total), else
their growth over the window's rounds per round."""

from benchlib import program


def read(obs, params):
    if program.snapshot(obs) is None:
        return None
    if params.get("at") == "window_start":
        return float(sum(program.counter_at_window_start(obs, name)
                         for name in params["counters"]))
    rounds = program.window_rounds(obs)
    if not rounds:
        return None
    return sum(r["counts"].get(name, 0) for r in rounds
               for name in params["counters"]) / len(rounds)
