"""The window's device timeline, from the stamps the program keeps of each
round (``commefficient_tpu/utils/tracing.py``): the dispatch ``t_ns``, the
enqueue ``t_enq_ns`` (the jitted call returned) and the device's completion
``t_done_ns`` (the recorder's waiter saw the round's output ready), with the
main thread's top-level host spans as ``intervals``. All of them are on
``time.perf_counter``, the probe's clock. The program only stamps; the
arithmetic is the benchmark's, here.

Counted are the rounds whose completion lies inside the probe's window,
less every round and gap that overlaps the profiler's session (from
``probe.trace_t0`` to the first round mark after ``probe.trace_t1``, which
holds the profiler's stop), and less the rounds that hold a validation pass
(``eval``, as ``program.window_rounds``): the gap after such a round is an
epoch boundary, read on its own. For round r:

* device time: ``t_done(r) - max(t_done(r-1), t_enq(r))``;
* the idle gap after it: ``max(0, t_enq(r+1) - t_done(r))``, over the wall
  time ``t_done(r+1) - t_done(r)``;
* an epoch boundary, where r holds ``eval``: ``t_enq(r+1) - t_done(r)``.

Everything returns ``None`` for a program without the stamps (a commit from
before them) and where fewer than ``MIN_ROUNDS`` rounds are counted.
"""

from __future__ import annotations

import bisect
import math
import statistics

from benchlib import program
from benchlib import trace as tr

MIN_ROUNDS = 2
#: where an idle gap is covered by several of the program's spans, the
#: first of these names it (``data`` is every ``data.*`` span); a gap that
#: no one of them covers the most of is ``host``
GAP_ORDER = ("eval", "round.sync", "data", "round.dispatch")
UNNAMED = "host"


def stamped(snap):
    """The marked rounds of a program snapshot, in order (the open one
    last); None where the program keeps no completion stamps."""
    if snap is None:
        return None
    rounds = [r for r in snap["rounds"] + [snap["open"]]
              if r["round"] is not None]
    if not rounds or "t_done_ns" not in rounds[0]:
        return None
    return rounds


def profiler_session(rounds, probe):
    """(start, end) ns of the profiler's session, or None untraced."""
    t0 = getattr(probe, "trace_t0", None)
    if t0 is None:
        return None
    t1 = getattr(probe, "trace_t1", None)
    if t1 is None:
        return (t0 * 1e9, math.inf)
    after = [r["t_ns"] for r in rounds if r["t_ns"] >= t1 * 1e9]
    return (t0 * 1e9, after[0] if after else math.inf)


def build(rounds, t_start_ns, t_end_ns, session=None):
    """{"device": [ns a counted round], "gaps": [(t_done(r), t_enq(r+1),
    wall ns, r, r+1)], "boundaries": [(t_done(r), t_enq(r+1), r, r+1)]}
    over ``rounds`` (snapshot records, in order); see the module
    docstring."""
    def overlaps(a, b):
        return session is not None and a <= session[1] and b >= session[0]

    def inside(r):
        return (r["t_done_ns"] is not None
                and t_start_ns <= r["t_done_ns"] <= t_end_ns)

    out = {"device": [], "gaps": [], "boundaries": []}
    pairs = [(a, b) for a, b in zip(rounds, rounds[1:])
             if b["round"] == a["round"] + 1]
    for prev, r in pairs:
        if (inside(r) and "eval" not in r["spans"]
                and None not in (prev["t_done_ns"], r["t_enq_ns"])
                and not overlaps(r["t_ns"], r["t_done_ns"])):
            out["device"].append(
                r["t_done_ns"] - max(prev["t_done_ns"], r["t_enq_ns"]))
    for r, nxt in pairs:
        if not (inside(r) and inside(nxt)) or nxt["t_enq_ns"] is None:
            continue
        done, enq = r["t_done_ns"], nxt["t_enq_ns"]
        if "eval" in r["spans"]:
            if not overlaps(done, enq):
                out["boundaries"].append((done, enq, r, nxt))
        elif not overlaps(done, nxt["t_done_ns"]):
            out["gaps"].append((done, max(done, enq),
                                nxt["t_done_ns"] - done, r, nxt))
    return out


def timeline(obs):
    """``build`` over the run's window, computed once a run; None where the
    program keeps no stamps."""
    if "timeline" not in obs:
        p = obs["probe"]
        rounds = stamped(program.snapshot(obs))
        obs["timeline"] = None if rounds is None else build(
            rounds, p.t_start * 1e9, p.t_end * 1e9,
            profiler_session(rounds, p))
    return obs["timeline"]


def _counted(obs):
    line = timeline(obs)
    if line is None or len(line["device"]) < MIN_ROUNDS:
        return None
    return line


def device_ms(obs):
    line = _counted(obs)
    return statistics.median(line["device"]) / 1e6 if line else None


def idle_pct(obs):
    line = _counted(obs)
    if not line or not line["gaps"]:
        return None
    idle = sum(b - a for a, b, *_ in line["gaps"])
    return 100.0 * idle / sum(wall for _, _, wall, *_ in line["gaps"])


def gap_max_ms(obs):
    line = _counted(obs)
    if not line or not line["gaps"]:
        return None
    return max(b - a for a, b, *_ in line["gaps"]) / 1e6


def boundary_ms(obs):
    line = timeline(obs)
    if not line or not line["boundaries"]:
        return None
    return statistics.median(b - a for a, b, *_ in line["boundaries"]) / 1e6


def name_gap(a, b, intervals):
    """The program span of ``intervals`` ([name, t0, t1]) that covers the
    most of [a, b] if it covers more than half, by ``GAP_ORDER`` (the first
    listed where several cover as much), else ``UNNAMED``."""
    cover = dict.fromkeys(GAP_ORDER, 0)
    for name, t0, t1 in intervals:
        key = "data" if name.startswith("data.") else name
        if key in cover:
            cover[key] += max(0, min(b, t1) - max(a, t0))
    best = max(GAP_ORDER, key=cover.get)
    return best if 2 * cover[best] > b - a else UNNAMED


def idle_gaps(obs, n=5):
    """The n longest idle gaps counted in ``idle_pct``, each named by what
    the host was doing (``name_gap`` over the intervals of the rounds on
    both sides): [[name, seconds], ...], as ``trace.idle_gaps`` gives the
    traced slice's."""
    line = _counted(obs)
    if not line:
        return []
    gaps = sorted(((b - a, a, b, r, nxt) for a, b, _, r, nxt in line["gaps"]
                   if b > a), key=lambda g: -g[0])[:n]
    return [[name_gap(a, b, r["intervals"] + nxt["intervals"]), ns / 1e9]
            for ns, a, b, r, nxt in gaps]


def stamp_skew_us(obs):
    """Median over the rounds that completed inside the traced window of
    (the program's ``t_done_ns`` on the trace's clock) - (the end of the
    last device operation before it), in us. One anchor maps the clocks:
    the ``bench:traced_window`` annotation's start on the trace is
    ``probe.trace_t0``, both taken on the same host within microseconds."""
    trace, p = obs["trace"], obs["probe"]
    rounds = stamped(program.snapshot(obs))
    if trace is None or rounds is None or getattr(
            p, "trace_t0", None) is None:
        return None
    window, ops = tr.traced_window(trace), tr.device_ops(trace)
    if window is None or not ops:
        return None
    lo, hi = window
    offset = lo - p.trace_t0 * 1e9
    ends = sorted(e for _, _, e in ops[sorted(ops)[0]])
    skews = []
    for r in rounds:
        if r["t_done_ns"] is None:
            continue
        at = r["t_done_ns"] + offset
        i = bisect.bisect_right(ends, at)
        if lo <= at <= hi and i:
            skews.append(at - ends[i - 1])
    return statistics.median(skews) / 1e3 if skews else None
