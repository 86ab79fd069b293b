"""The readers of what the program records about itself (its spans, its
counters, the phases of its round), against hand-made observations whose
answers can be worked out on paper; and the server top-k's roofline share
by the kernels' names."""

import json
import os
import types

import pytest

import run as harness
from benchlib import program

HERE = os.path.dirname(os.path.abspath(__file__))
S = 1_000_000_000      # the recorder keeps ns, the probe seconds


def reader(name):
    return harness.load_module("readers", name)


def metric(name):
    with open(os.path.join(os.path.dirname(HERE), "metrics",
                           name + ".json")) as f:
        return json.load(f)


def rnd(index, t_s, spans=None, counts=None):
    return {"round": index, "t_ns": int(t_s * S),
            "spans": {k: [int(ms * 1e6), n, int(ms * 1e6)]
                      for k, (ms, n) in (spans or {}).items()},
            "counts": counts or {}}


def hand_made_obs():
    # window 10 s .. 20 s; rounds marked at 9.5 (warm-up), 10.0, 12, 14 (an
    # epoch end: holds the validation pass), 16, and 18 still open
    snap = {
        "rounds": [
            rnd(None, 0.0, {"setup.data": (900, 1)},
                {"compile.backend_s": 4.0, "compile.cache_misses": 3}),
            rnd(5, 9.5, {"data.fetch": (50, 100), "round.sync": (400, 1)},
                {"compile.backend_s": 1.0}),
            rnd(6, 10.0, {"data.fetch": (100, 100), "round.sync": (500, 1)},
                {"data.rows": 5000}),
            rnd(7, 12.0, {"data.fetch": (140, 100), "round.sync": (520, 1)},
                {"data.rows": 5000, "compile.backend_s": 0.5}),
            rnd(8, 14.0, {"data.fetch": (999, 100), "round.sync": (10, 1),
                          "eval": (800, 1)}, {"data.rows": 5000}),
            rnd(9, 16.0, {"data.fetch": (120, 100), "round.sync": (480, 1)},
                {"data.rows": 5000}),
        ],
        "open": rnd(10, 18.0, {"data.fetch": (1, 1)},
                    {"compile.backend_s": 0.25}),
        "counters": {"compile.backend_s": [5.75, int(18.5 * S)],
                     "compile.cache_misses": [3, int(3.0 * S)],
                     "data.rows": [20000, int(17.0 * S)]},
    }
    probe = types.SimpleNamespace(t_start=10.0, t_end=20.0,
                                  trace_round0=11, trace_round1=13)
    return {"probe": probe, "program_snapshot": snap, "trace": None}


def test_a_program_without_a_recorder_gives_nothing(monkeypatch):
    monkeypatch.setattr(program, "tracing_module", lambda: None)
    obs = hand_made_obs()
    del obs["program_snapshot"]
    obs["trace"] = hand_made_trace()
    assert reader("program_span").read(obs, {"span": "data.fetch"}) is None
    assert reader("program_counter").read(
        obs, {"counters": ["compile.backend_s"],
              "at": "window_start"}) is None
    assert reader("phase_time").read(obs, {"phase": "reduce"}) is None


@pytest.mark.parametrize("span, want", [
    ("data.fetch", 120.0),       # median of 100, 140, 120: round 8 is out
    ("round.sync", 500.0),
    ("data.augment", None),      # no round of the window holds it
])
def test_program_span_is_the_median_over_the_windows_rounds(span, want):
    got = reader("program_span").read(hand_made_obs(), {"span": span})
    assert got == (pytest.approx(want) if want is not None else None)


def test_program_span_counts_a_round_without_the_span_as_zero():
    obs = hand_made_obs()
    for r in obs["program_snapshot"]["rounds"][2:4]:
        del r["spans"]["data.fetch"]
    assert reader("program_span").read(obs, {"span": "data.fetch"}) == 0.0


@pytest.mark.parametrize("params, want", [
    # last changed before the window opened: the value itself
    ({"counters": ["compile.cache_misses"], "at": "window_start"}, 3.0),
    # changed since: less what the rounds marked since the start added
    ({"counters": ["compile.backend_s"], "at": "window_start"}, 5.0),
    ({"counters": ["compile.backend_s", "compile.cache_misses"],
      "at": "window_start"}, 8.0),
    ({"counters": ["never.counted"], "at": "window_start"}, 0.0),
    # growth a round over the window's rounds (round 8 is out)
    ({"counters": ["data.rows"]}, 5000.0),
    ({"counters": ["compile.backend_s"]}, 0.5 / 3),
])
def test_program_counter(params, want):
    got = reader("program_counter").read(hand_made_obs(), params)
    assert got == pytest.approx(want)


def hand_made_trace(stray_ns=5):
    # window 0..20000 ns, two rounds. A while (server) holds a kernel; an
    # operation of another program (``stray``) is not in the round's map
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%conv.1 = f32[8]{0} convolution(f32[8]{0} %a)", 0, 4000],
            ["%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %conv.1), "
             "to_apply=%add", 4000, 1000],
            ["%while.3 = (s32[]) while((s32[]) %t), body=%b", 5000, 3000],
            ["%radix_count_pallas.9 = s32[1,16]{1,0} custom-call(f32[5,8]"
             "{1,0} %g)", 5500, 2000],
            ["%fusion.300 = s32[8]{0} fusion(s32[8]{0} %lc)", 8000, 1990],
            ["%stray = u32[2]{0} fusion(u32[2]{0} %key.1)", 9990, stray_ns],
        ]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["%conv.1 = f32[8]{0} convolution(f32[8]{0} %a)", 0, 9000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:traced_window", 0, 20000]]}]}]}


OP_PHASES = {
    "%conv.1 = f32[8]{0}(%a)": "client_grad",
    "%all-reduce.2 = f32[8]{0}(%conv.1)": "reduce",
    "%while.3 = (s32[])(%t)": "server_update",
    "%radix_count_pallas.9 = s32[1,16]{1,0}(%g)": "server_update",
    "%fusion.300 = s32[8]{0}(%lc)": "download_accounting",
}


@pytest.mark.parametrize("phase, want_ns", [
    ("client_grad", 4000), ("reduce", 1000),
    ("server_update", 1000 + 2000),       # the while's own time + the kernel
    ("download_accounting", 1990), ("compress", 0)])
def test_phase_time_sums_own_time_by_phase(phase, want_ns):
    pytest.importorskip("commefficient_tpu.utils.tracing")
    obs = hand_made_obs()
    obs["trace"], obs["op_phases"] = hand_made_trace(), dict(OP_PHASES)
    got = reader("phase_time").read(obs, {"phase": phase})
    assert got == pytest.approx(want_ns / 2 / 1e6)      # ms a round


def test_phase_time_refuses_when_under_99_percent_is_found():
    pytest.importorskip("commefficient_tpu.utils.tracing")
    obs = hand_made_obs()
    obs["trace"], obs["op_phases"] = hand_made_trace(), dict(OP_PHASES)
    del obs["op_phases"]["%fusion.300 = s32[8]{0}(%lc)"]      # 20 % unfound
    assert reader("phase_time").read(obs, {"phase": "client_grad"}) is None
    # ... and just inside the limit it answers: 9990 of 10090 ns found
    obs = hand_made_obs()
    obs["trace"] = hand_made_trace(stray_ns=100)
    obs["op_phases"] = dict(OP_PHASES)
    assert reader("phase_time").read(obs, {"phase": "reduce"}) == \
        pytest.approx(1000 / 2 / 1e6)
    obs = hand_made_obs()
    obs["trace"] = hand_made_trace(stray_ns=102)          # 99.0 % less a hair
    obs["op_phases"] = dict(OP_PHASES)
    assert reader("phase_time").read(obs, {"phase": "reduce"}) is None


def test_phase_time_needs_a_traced_run():
    obs = hand_made_obs()
    obs["op_phases"] = dict(OP_PHASES)
    assert reader("phase_time").read(obs, {"phase": "reduce"}) is None


def test_server_topk_roofline_reads_the_kernels_by_their_names():
    spec = metric("server_topk_roofline")
    _, config = harness.load_cell(rehearsal="tiny")
    reference = harness.load_module("reference", config["reference"])
    trace = hand_made_trace()
    events = trace["planes"][0]["lines"][0]["events"]
    events += [["%unsketch_select_pallas.1 = (f32[8]{0}, s32[8]{0}) "
                "custom-call(f32[5,8]{1,0} %g)", 7600, 300],
               ["%closed_call.23 = s32[1,16]{1,0} custom-call(f32[5,8]{1,0}"
                " %g)", 7900, 50],          # a name no kernel has any more
               ["%sketch_vec_pallas.1 = f32[1,5,8]{2,1,0} custom-call("
                "f32[8]{0} %v)", 4000, 700]]
    probe = types.SimpleNamespace(
        trace_round0=11, trace_round1=13,
        spec={"num_rows": 5, "num_cols": 500000, "k": 50000})
    obs = {"probe": probe, "trace": trace, "reference": reference,
           "device": types.SimpleNamespace(device_kind="TPU v5 lite"),
           "peaks": harness.load_json("peaks.json")}
    got = reader(spec["reader"]).read(obs, spec["params"])
    need = 2 * reference.kernel_bytes("server_topk", probe.spec)
    assert got == pytest.approx(100 * (need / 819e9) / 2300e-9)
    assert spec["unit"] == "%" and spec["moves"] == "samples_per_s"


NEW_METRICS = ["data.fetch_ms", "data.augment_ms", "data.assemble_ms",
               "data.h2d_ms", "round.sync_ms", "round.client_grad_ms",
               "round.compress_ms", "round.reduce_ms",
               "round.server_update_ms", "round.download_accounting_ms",
               "server_topk_roofline", "setup.compile_s",
               "setup.cache_misses"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_is_ready_for_a_cell_to_list_it(name):
    """No cell lists these yet (PERF.md section 7 says why the mesh cell was
    left out): each file names a reader that is there, an end-to-end metric
    of the manifest to move, and a layer the manifest or PERF.md has."""
    spec = metric(name)
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert spec["moves"] in {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert spec["layer"] in layers | {"entry point / loop"}
    assert spec["source"] in ("program_span", "program_counter",
                              "device_trace")
    assert spec["kind"] == "per_layer" and spec["what"]
    assert os.path.exists(os.path.join(os.path.dirname(HERE), "readers",
                                       spec["reader"] + ".py"))
    assert name not in {m["name"] for m in manifest["per_layer"]}
