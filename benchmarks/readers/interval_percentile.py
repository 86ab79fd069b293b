"""A percentile, in ms, of the intervals between successive returns of the
pipeline's ``push`` over every round of the window (the host seeing a round
complete), the first counted from the window's start."""

import numpy as np


def read(obs, params):
    p = obs["probe"]
    marks = [p.t_start] + [t for t in p.push_returns
                           if p.t_start <= t <= p.t_end]
    if len(marks) < 3:
        return None
    return float(np.percentile(np.diff(marks), params["percentile"]) * 1e3)
