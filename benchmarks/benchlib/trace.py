"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the idle gaps by what the host was doing.

A trace is held as plain data, so that the reduction can be checked against
a small recorded one (``benchmarks/tests``)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``read_xplane`` makes that from the ``.xplane.pb`` the JAX profiler writes.
Host spans come from the ``bench:*`` annotations the benchmark's wrappers
put on the host plane, on the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench:"
TRACED_WINDOW = ANNOTATION_PREFIX + "traced_window"
#: where a gap is covered by several spans, the first of these names it
SPAN_ORDER = ("eval", "flush_sync", "push_sync", "dispatch", "data_wait")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path):
    """The trace at ``path`` as plain data, cut to what the readers here
    use: the device planes' operations, and from the host planes the
    benchmark's own annotations (whatever thread they are on)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(ANNOTATION_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace):
    """{device plane name: [(name, start, end)] sorted by start}."""
    out = {}
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = sorted(
                    (n, s, s + d) for n, s, d in line["events"] if d > 0)
    return out


def annotations(trace):
    """{span name without the prefix: [(start, end)]} from the host planes."""
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if n.startswith(ANNOTATION_PREFIX):
                    out.setdefault(n[len(ANNOTATION_PREFIX):], []).append(
                        (s, s + d))
    for spans in out.values():
        spans.sort()
    return out


def traced_window(trace):
    """(start, end) of the ``bench:traced_window`` annotation, or None."""
    spans = annotations(trace).get("traced_window")
    return spans[0] if spans else None


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union(intervals):
    """Merged, sorted, non-overlapping copy of ``intervals``."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(trace):
    """(busy seconds averaged over the devices, window seconds) inside the
    traced window; None where the trace holds no device operation or no
    window annotation."""
    window = traced_window(trace)
    ops = device_ops(trace)
    if window is None or not ops:
        return None
    lo, hi = window
    per_device = [sum(b - a for a, b in union(_clip(
        [(s, e) for _, s, e in events], lo, hi)))
        for events in ops.values()]
    if not any(per_device):
        return None
    return (sum(per_device) / len(per_device) / 1e9, (hi - lo) / 1e9)


def op_time(trace, pattern):
    """(seconds, events) of the device operations whose name matches the
    regular expression, inside the traced window, on the first device."""
    window = traced_window(trace)
    ops = device_ops(trace)
    if window is None or not ops:
        return None
    lo, hi = window
    rx = re.compile(pattern)
    events = ops[sorted(ops)[0]]
    hit = [(s, e) for n, s, e in events
           if rx.search(n) and s >= lo and e <= hi]
    if not hit:
        return None
    return sum(e - s for s, e in hit) / 1e9, len(hit)


def short(name, width=120):
    """An operation's name as the breakdown gives it: the trace's own text
    (on a TPU the whole HLO instruction), cut to ``width`` characters."""
    return name if len(name) <= width else name[:width - 3] + "..."


def self_times(events):
    """{name: ns} of each operation's own time: its duration less that of
    the operations nested inside it (a ``while`` holds its body's)."""
    total, stack = {}, []      # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0) + own

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return total


def top_ops(trace, n=10):
    """The n device operations with most own time in the traced window:
    [[name, seconds], ...] on the first device."""
    window = traced_window(trace)
    ops = device_ops(trace)
    if window is None or not ops:
        return []
    lo, hi = window
    inside = [(name, max(s, lo), min(e, hi))
              for name, s, e in ops[sorted(ops)[0]] if e > lo and s < hi]
    ranked = sorted(self_times(inside).items(), key=lambda kv: -kv[1])[:n]
    return [[short(name), t / 1e9] for name, t in ranked]


def idle_gaps(trace, n=5):
    """The n longest gaps between device operations in the traced window,
    each named by the host span that covers most of it:
    [[span name, seconds], ...]."""
    window = traced_window(trace)
    ops = device_ops(trace)
    if window is None or not ops:
        return []
    lo, hi = window
    events = ops[sorted(ops)[0]]
    merged = union(_clip([(s, e) for _, s, e in events], lo, hi))
    edges = [lo] + [t for ab in merged for t in ab] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:n]
    spans = annotations(trace)
    out = []
    for length, a, b in gaps:
        cover = {name: sum(y - x for x, y in union(_clip(
            spans.get(name, []), a, b))) for name in SPAN_ORDER}
        # a wait between rounds holds the epoch's flush and validation pass
        cover["data_wait"] -= cover["eval"] + cover["flush_sync"]
        name = max(SPAN_ORDER, key=lambda k: cover[k])
        if cover[name] * 2 < length:
            name = "other"
        out.append([name, length / 1e9])
    return out


def cut(trace, rounds=3):
    """A small copy of ``trace`` for a test fixture: everything inside the
    first ``rounds`` dispatch spans of the traced window."""
    window = traced_window(trace)
    dispatches = [s for s in annotations(trace).get("dispatch", [])
                  if s[0] >= window[0]]
    lo, hi = window[0], dispatches[rounds][0]
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [[n, s, d] for n, s, d in line["events"]
                      if n == TRACED_WINDOW or (s >= lo and s + d <= hi)]
            events = [[n, lo, hi - lo] if n == TRACED_WINDOW else [n, s, d]
                      for n, s, d in events]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}
