"""The device's timeline over the window's rounds, from the program's own
stamps of each round's enqueue and device completion
(``benchlib/timeline.py`` has the definitions): ``params["as"]`` is
``device_ms`` (median device time a round), ``idle_pct`` (idle share of
the wall time between completions), ``gap_max_ms`` (the longest idle gap),
``boundary_ms`` (median epoch boundary) or ``stamp_skew_us`` (the stamps
against the device trace, traced run). ``None`` for a program without the
stamps, or with fewer than two rounds to count."""

from benchlib import timeline

READ = {"device_ms": timeline.device_ms, "idle_pct": timeline.idle_pct,
        "gap_max_ms": timeline.gap_max_ms,
        "boundary_ms": timeline.boundary_ms,
        "stamp_skew_us": timeline.stamp_skew_us}


def read(obs, params):
    return READ[params["as"]](obs)
