"""Device busy time in the traced window (the union of the intervals in
which an operation ran, from the profiler's trace): ``as: ms_per_round``
divides it by the rounds dispatched in that window, ``as: idle_pct`` gives
1 - busy / window."""

from benchlib import trace as tr


def read(obs, params):
    p = obs["probe"]
    seen = tr.busy(obs["trace"]) if obs["trace"] else None
    if seen is None:
        return None
    busy_s, window_s = seen
    if params["as"] == "idle_pct":
        return 100.0 * (1.0 - busy_s / window_s)
    rounds = p.trace_round1 - p.trace_round0
    return busy_s / rounds * 1e3 if rounds else None
