"""The reduction from a trace to numbers, on a hand-made trace whose answers
can be worked out on paper and on a slice cut from a real chip trace."""

import gzip
import json
import os

import pytest

from benchlib import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    # window 0..1000; device busy 100..400 (two ops, one nested), 600..700
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100, 300], ["convolution.2", 150, 100],
                ["sketch_kernel", 600, 100], ["before", -50, 20]]},
            {"name": "Steps", "events": [["0", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench:traced_window", 0, 1000],
            ["bench:dispatch", 0, 90], ["bench:data_wait", 400, 190],
            ["bench:push_sync", 700, 300], ["other_thing", 0, 1000]]}]}]}


def test_busy_is_the_union_inside_the_window():
    busy_s, window_s = tr.busy(hand_made())
    assert busy_s == pytest.approx(400e-9)
    assert window_s == pytest.approx(1000e-9)


def test_top_ops_and_op_time():
    ops = dict(tr.top_ops(hand_made(), 10))
    assert ops["fusion.1"] == pytest.approx(200e-9)     # less its child
    assert ops["convolution.2"] == pytest.approx(100e-9)
    assert "before" not in ops
    seconds, count = tr.op_time(hand_made(), r"sketch")
    assert (seconds, count) == (pytest.approx(100e-9), 1)
    assert tr.op_time(hand_made(), r"no_such_kernel") is None


def test_gaps_are_named_by_the_host_span_that_covers_them():
    gaps = tr.idle_gaps(hand_made(), 5)
    assert gaps[0] == ["push_sync", pytest.approx(300e-9)]
    assert gaps[1] == ["data_wait", pytest.approx(200e-9)]
    assert gaps[2] == ["dispatch", pytest.approx(100e-9)]


def test_a_trace_without_device_operations_gives_nothing():
    t = hand_made()
    t["planes"] = t["planes"][1:]
    assert tr.busy(t) is None and tr.top_ops(t) == [] and tr.idle_gaps(t) == []


RECORDED = os.path.join(HERE, "trace_slice.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded slice committed")
def test_recorded_slice_of_a_chip_trace():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    trace, want = rec["trace"], rec["expected"]
    busy_s, window_s = tr.busy(trace)
    assert busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < busy_s <= window_s
    assert [n for n, _ in tr.top_ops(trace, 3)] == want["top3"]
    for pattern, seconds in want["kernels"].items():
        assert tr.op_time(trace, pattern)[0] == pytest.approx(seconds,
                                                              rel=1e-9)
