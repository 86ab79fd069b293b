"""The device timeline read from the program's own stamps
(``benchlib/timeline.py``, ``readers/program_timeline.py``), against a
hand-made snapshot and probe whose answers can be worked out on paper, and
against the recorded chip trace with stamps placed at known offsets."""

import glob
import gzip
import json
import os
import types

import pytest

import run as harness
from benchlib import program, timeline
from benchlib import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NEW = ("timeline.device_ms", "timeline.idle_pct", "timeline.gap_max_ms",
       "timeline.boundary_ms", "timeline.stamp_skew_us")


def ns(s):
    return int(round(s * 1e9))


def rnd(index, mark, enq, done, spans=(), intervals=()):
    return {"round": index, "t_ns": ns(mark),
            "t_enq_ns": None if enq is None else ns(enq),
            "t_done_ns": None if done is None else ns(done),
            "spans": {k: [1, 1, 1] for k in spans}, "counts": {},
            "intervals": [[k, ns(a), ns(b)] for k, a, b in intervals],
            "intervals_dropped": 0}


def hand_made_snapshot():
    # a round takes 100 ms on the device. Window 10 s .. 30 s; the profiler
    # runs from 13.0 s, is stopped at 13.5 s and the next mark is at 27.0 s
    return {"rounds": [
        rnd(None, 0.0, None, None, ["setup.data"]),
        rnd(5, 9.80, 9.802, 9.95),              # done before the window
        rnd(6, 9.90, 9.902, 10.05),             # 10.05 - 9.95
        rnd(7, 10.00, 10.002, 10.15),
        # the sync of round 8 stalls 100 ms after the device is done
        rnd(8, 10.10, 10.102, 10.25, ["round.sync"],
            [("round.sync", 10.15, 10.35)]),
        rnd(9, 10.36, 10.362, 10.46, ["round.dispatch"],
            [("round.dispatch", 10.36, 10.362)]),  # 10.46 - 10.362
        rnd(10, 10.37, 10.372, 10.56),
        # an epoch end: flush and validation pass, then round 12
        rnd(11, 10.47, 10.472, 10.66, ["eval"], [("eval", 10.7, 11.1)]),
        rnd(12, 11.20, 11.202, 11.30),          # 11.30 - 11.202
        rnd(13, 11.25, 11.252, 11.40),
        # a gap into the profiler's session: left out with its rounds
        rnd(14, 12.90, 12.902, 13.05),
        rnd(15, 13.10, 13.102, 13.20),
        rnd(16, 27.00, 27.002, 27.10),          # its mark ends the session
        rnd(17, 27.05, 27.052, 27.20, ["data.sample"],
            [("data.sample", 27.16, 27.29)]),
        rnd(18, 27.15, 27.30, 27.40),           # waited 100 ms for data
    ], "open": rnd(19, 27.35, 27.352, None),    # not done yet
        "counters": {}}


def hand_made_obs(t_start=10.0, t_end=30.0):
    probe = types.SimpleNamespace(t_start=t_start, t_end=t_end,
                                  trace_t0=13.0, trace_t1=13.5)
    return {"probe": probe, "program_snapshot": hand_made_snapshot(),
            "trace": None}


def read(obs, what):
    return harness.load_module("readers", "program_timeline").read(
        obs, {"as": what})


@pytest.mark.parametrize("what, want", [
    # rounds 6-10, 12, 13, 17, 18: 100, 100, 100, 98, 100, 98, 100, 100, 100
    ("device_ms", 100.0),
    # gaps 112 (8 -> 9) and 100 (17 -> 18) over the wall time of the eight
    # pairs counted: 100 x 6 + 210 + 200 = 1 010 ms
    ("idle_pct", 100.0 * 212 / 1010),
    ("gap_max_ms", 112.0),
    ("boundary_ms", 542.0),                     # 11.202 - 10.66
    ("stamp_skew_us", None),                    # untraced
])
def test_every_reading_on_a_hand_made_window(what, want):
    got = read(hand_made_obs(), what)
    assert got == (None if want is None else pytest.approx(want))


def test_what_is_counted_and_what_is_left_out():
    line = timeline.timeline(hand_made_obs())
    counted = [r["round"] for *_, r, nxt in line["gaps"]]
    assert counted == [6, 7, 8, 9, 10, 12, 16, 17]   # 13 -> 14 meets the
    assert [(r["round"], nxt["round"])               # profiler, 11 is an
            for *_, r, nxt in line["boundaries"]] == [(11, 12)]  # epoch end
    assert len(line["device"]) == 9


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    got = timeline.idle_gaps(hand_made_obs())
    assert [name for name, _ in got] == ["round.sync", "data"]
    assert [s for _, s in got] == pytest.approx([0.112, 0.100])


@pytest.mark.parametrize("intervals, want", [
    ([["round.sync", 0, 60], ["eval", 0, 60]], "eval"),     # a tie
    ([["round.sync", 0, 40], ["data.fetch", 40, 100]], "data"),
    ([["data.fetch", 0, 30], ["data.h2d", 30, 60]], "data"),  # data.* pool
    ([["round.dispatch", 0, 40], ["offload.flush", 40, 100]], "host"),
    ([], "host"),
])
def test_name_gap(intervals, want):
    assert timeline.name_gap(0, 100, intervals) == want


def test_fewer_than_two_rounds_give_nothing():
    obs = hand_made_obs(t_start=27.0, t_end=27.25)   # round 17 alone
    assert len(timeline.timeline(obs)["device"]) == 1
    for what in ("device_ms", "idle_pct", "gap_max_ms"):
        assert read(obs, what) is None
    assert timeline.idle_gaps(obs) == []


def test_a_program_without_the_stamps_gives_nothing(monkeypatch):
    obs = hand_made_obs()
    for r in obs["program_snapshot"]["rounds"] + [
            obs["program_snapshot"]["open"]]:
        for key in ("t_enq_ns", "t_done_ns", "intervals",
                    "intervals_dropped"):
            del r[key]
    obs["trace"] = recorded()
    for what in ("device_ms", "idle_pct", "gap_max_ms", "boundary_ms",
                 "stamp_skew_us"):
        assert read(obs, what) is None
    monkeypatch.setattr(program, "tracing_module", lambda: None)
    obs = hand_made_obs()
    del obs["program_snapshot"]
    assert read(obs, "device_ms") is None


def recorded():
    with gzip.open(os.path.join(HERE, "trace_slice.json.gz"), "rt") as f:
        return json.load(f)["trace"]


def test_stamp_skew_on_the_recorded_trace():
    trace = recorded()
    lo, hi = tr.traced_window(trace)
    ops = tr.device_ops(trace)
    merged = tr.union([(s, e) for _, s, e in ops[sorted(ops)[0]]])
    # stamps after the ends of three busy stretches, each before the next
    # operation begins: 2 us and 9 us into the two longest pauses between
    # operations (13.5 us each), and 250 us after the last operation
    pauses = sorted(range(len(merged) - 1),
                    key=lambda i: merged[i][1] - merged[i + 1][0])[:2]
    assert all(merged[i + 1][0] - merged[i][1] > 9_000 for i in pauses)
    at = [merged[pauses[0]][1] + 2_000, merged[pauses[1]][1] + 9_000,
          merged[-1][1] + 250_000]
    t0 = 5_000.0                     # probe.trace_t0, on the program's clock
    to_program = lambda x: int(x - lo + t0 * 1e9)
    rounds = [rnd(i, 0, 0, 0) for i in range(6)]
    for r, x in zip(rounds[1:], at):
        r["t_done_ns"] = to_program(x)
    rounds[4]["t_done_ns"] = to_program(hi + 1_000)   # after the window
    rounds[5]["t_done_ns"] = None                      # not stamped
    obs = {"probe": types.SimpleNamespace(trace_t0=t0),
           "program_snapshot": {"rounds": rounds[:-1], "open": rounds[-1],
                                "counters": {}},
           "trace": trace}
    assert read(obs, "stamp_skew_us") == pytest.approx(9.0, abs=1e-3)


def manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_each_metric_file_is_ready(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = harness.load_module("readers", spec["reader"])
    assert spec["params"]["as"] in reader.READ
    have = manifest()
    assert spec["layer"] in {m["layer"] for m in have["per_layer"]}
    assert spec["moves"] == "samples_per_s"
    assert spec["source"] == ("device_trace" if name.endswith("skew_us")
                              else "program_span")
    # the cells that list it are the manifest entry's, once it has one
    listing = []
    for path in sorted(glob.glob(os.path.join(BENCH, "workloads", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if name in cell["per_layer"]:
            listing.append(cell["name"])
    entry = [m for m in have["per_layer"] if m["name"] == name]
    assert listing == (sorted(entry[0]["workloads"]) if entry else [])
