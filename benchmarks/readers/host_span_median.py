"""Median duration, in ms, of one of the host spans the benchmark's wrappers
record (``params["span"]``), over the window."""

import statistics


def read(obs, params):
    spans = obs["probe"].in_window(params["span"])
    if not spans:
        return None
    return statistics.median(b - a for a, b in spans) * 1e3
