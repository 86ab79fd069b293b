"""The program's tracing module (utils/tracing.py) and where it is used:
spans and counters on the host path, phase scopes and kernel names in the
round program."""

import contextlib
import json
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis.walker import _sub_jaxprs, iter_eqns
from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.api import FedLearner
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.models import TinyMLP
from commefficient_tpu.ops import sketch_kernels
from commefficient_tpu.utils import tracing

KERNEL_NAMES = {"sketch_vec_pallas", "estimates_pallas",
                "radix_count_pallas", "unsketch_select_pallas",
                "topk_select_pallas", "fused_true_topk_pallas"}

MODES = {
    "sketch": dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                   k=3, num_rows=3, num_cols=20),
    "uncompressed": dict(mode="uncompressed", error_type="none",
                         virtual_momentum=0.9),
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, k=3),
    "local_topk": dict(mode="local_topk", error_type="local",
                       local_momentum=0.9, k=3),
}
#: the phases a mode's round has something in
EXPECTED = {
    "sketch": set(tracing.PHASES),
    "uncompressed": set(tracing.PHASES) - {"compress"},
    "true_topk": set(tracing.PHASES) - {"compress"},
    "local_topk": set(tracing.PHASES),
}
W, B = 2, 4


@pytest.fixture(autouse=True)
def fresh_recorder():
    tracing.reset()
    yield
    tracing.reset()


def make_learner(**kw):
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=5e-4, num_workers=W, num_clients=6,
                    lr_scale=0.05, **kw)
    return FedLearner(model, cfg, make_cv_loss(model), None,
                      jax.random.PRNGKey(1), np.zeros((1, 8), np.float32))


def round_args():
    return (jnp.zeros((W,), jnp.int32),
            (jnp.zeros((W, B, 8)), jnp.zeros((W, B), jnp.int32)),
            jnp.ones((W, B)), jnp.float32(0.1), jax.random.PRNGKey(0))


def host_batch(r):
    rng = np.random.RandomState(r)
    return (rng.choice(6, W, replace=False),
            (rng.randn(W, B, 8).astype(np.float32),
             rng.randint(0, 2, (W, B)).astype(np.int32)),
            np.ones((W, B), np.float32))


# ------------------------------------------------------------ the recorder

def test_spans_nest_and_self_time_adds_up():
    with tracing.span("outer"):
        time.sleep(0.002)
        for _ in range(2):
            with tracing.span("inner"):
                time.sleep(0.002)
    spans = tracing.snapshot()["open"]["spans"]
    outer, inner = spans["outer"], spans["inner"]
    assert (outer[1], inner[1]) == (1, 2)
    assert inner[0] == inner[2] >= 2 * 2_000_000      # a leaf: all its own
    assert outer[2] == outer[0] - inner[0]            # self = total - kids
    assert outer[2] >= 2_000_000


def test_a_span_closes_when_its_body_raises():
    with pytest.raises(KeyError):
        with tracing.span("outer"):
            with tracing.span("inner"):
                raise KeyError("x")
    with tracing.span("after"):
        pass
    spans = tracing.snapshot()["open"]["spans"]
    assert set(spans) == {"outer", "inner", "after"}
    assert spans["after"][0] == spans["after"][2]     # no stale parent


def test_round_mark_files_totals_under_the_round_and_bounds_the_ring():
    for i in range(tracing.RING_ROUNDS + 10):
        tracing.round_mark(i)
        with tracing.span("work"):
            pass
        tracing.count("things", 2)
    snap = tracing.snapshot()
    assert len(snap["rounds"]) == tracing.RING_ROUNDS
    assert snap["rounds"][-1]["round"] == tracing.RING_ROUNDS + 8
    assert snap["open"]["round"] == tracing.RING_ROUNDS + 9
    assert all(r["spans"]["work"][1] == 1 and r["counts"] == {"things": 2}
               for r in snap["rounds"][-100:])
    value, changed = snap["counters"]["things"]
    assert value == 2 * (tracing.RING_ROUNDS + 10)
    assert changed >= snap["open"]["t_ns"]
    assert sum(r["spans"]["work"][0]
               for r in (*snap["rounds"], snap["open"])) > 0


def test_write_and_profile_ctx_leave_spans_json(tmp_path, monkeypatch):
    from commefficient_tpu.utils.logging import profile_ctx
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda d: contextlib.nullcontext())
    with profile_ctx(str(tmp_path)):
        tracing.round_mark(0)
        with tracing.span("round.dispatch"):
            pass
    with open(tmp_path / "spans.json") as f:
        snap = json.load(f)
    assert snap["open"]["spans"]["round.dispatch"][1] == 1
    # the stamps and intervals travel too, and the session's anchor lies
    # before everything stamped inside it
    assert snap["open"]["intervals"][0][0] == "round.dispatch"
    assert snap["open"]["intervals_dropped"] == 0
    assert {"t_enq_ns", "t_done_ns"} <= set(snap["open"])
    assert snap["anchors"]["fed:profile"] <= snap["open"]["t_ns"]
    with profile_ctx(None):         # falsy: no trace, nothing written
        pass


def test_compile_counters_count_a_program_once():
    tracing.compile_counters()
    tracing.compile_counters()      # registers once

    @jax.jit
    def fresh(x):
        return jnp.sin(x) * 3 + jax.jit(jnp.cos)(x)

    def programs():
        return tracing.snapshot()["counters"].get("compile.programs",
                                                  [0])[0]

    x = jnp.arange(4.0)
    before = programs()
    fresh(x).block_until_ready()
    first = programs()
    fresh(x).block_until_ready()
    assert (first - before, programs() - first) == (1, 0)
    counters = tracing.snapshot()["counters"]
    for name in ("compile.trace_s", "compile.lower_s", "compile.backend_s"):
        assert counters[name][0] > 0
    # the inner jit is traced inside the outer one: counted once, so the
    # traced seconds cannot exceed the wall time of this test by much
    assert counters["compile.trace_s"][0] < 60


# ------------------------------------------------- phases in the round

def eqn_phases(jaxpr, inherited=""):
    """[(innermost phase or None, primitive)] of every leaf equation; an
    equation inside a call-like one sits under the caller's scopes too."""
    out = []
    for eqn in jaxpr.eqns:
        stack = inherited + "/" + str(eqn.source_info.name_stack)
        subs = [] if eqn.primitive.name == "pallas_call" else list(
            _sub_jaxprs(eqn.params))
        for sub in subs:
            out += eqn_phases(sub, stack)
        if not subs:
            found = re.findall(r"phase:([a-z_]+)", stack)
            prim = eqn.primitive.name
            if prim == "pallas_call":
                prim += ":" + eqn.params["name"]
            out.append((found[-1] if found else None, prim))
    return out


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_equation_of_the_round_lies_in_one_phase(mode):
    learner = make_learner(**MODES[mode])
    with sketch_kernels.force_dispatch("kernel"):
        jaxpr = jax.make_jaxpr(learner._round)(learner.state, *round_args())
    phases = eqn_phases(jaxpr.jaxpr)
    assert len(phases) > 50
    outside = [prim for phase, prim in phases if phase is None]
    assert outside == []
    assert {phase for phase, _ in phases} == EXPECTED[mode]


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_lowered_round_carries_the_phases(mode):
    learner = make_learner(**MODES[mode])
    text = learner._round.lower(learner.state, *round_args()).as_text(
        debug_info=True)
    for phase in tracing.PHASES:
        assert (f"phase:{phase}" in text) == (phase in EXPECTED[mode])


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_op_phases_places_the_compiled_round(mode):
    learner = make_learner(**MODES[mode])
    compiled = learner._round.lower(learner.state, *round_args()).compile()
    phases = tracing.op_phases(compiled)
    assert len(phases) > 50
    placed = sum(1 for p in phases.values() if p != tracing.OTHER)
    assert placed >= 0.99 * len(phases)
    assert set(phases.values()) - {tracing.OTHER} <= set(tracing.PHASES)
    assert EXPECTED[mode] - {"reduce"} <= set(phases.values())
    # by its text too, and every key is an instruction_key of itself
    assert tracing.op_phases(compiled.as_text()) == phases
    assert all(tracing.instruction_key(k) == k for k in phases)


HLO = """HloModule toy

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/phase:client_grad/mul"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %g = f32[8]{0} get-tuple-element(%t), index=1
  %k = f32[8]{0} custom-call(%g), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/phase:server_update/while/body/radix_count_pallas/pallas_call"}, backend_config={"x": {"y": 1}}
  ROOT %r = (s32[], f32[8]{0}) tuple(%g, %k)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0:T(8)S(1)} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[8]{0} copy(%fusion.1)
  %all-reduce.1 = f32[8]{0} all-reduce(%copy.1), replica_groups={}, to_apply=%add, metadata={op_name="jit(f)/phase:client_grad/transpose(jvp(m))/dot"}
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/phase:server_update/while"}
  %lonely = f32[8]{0} iota(), iota_dimension=0
  ROOT %out = f32[8]{0} add(%all-reduce.1, %lonely), metadata={op_name="jit(f)/phase:server_update/phase:download_accounting/add"}
}
"""

HLO_WANT = {
    "%m = f32[8]{0}(%p, %p)": "client_grad",
    "%fusion.1 = f32[8]{0}(%a)": "client_grad",      # what it calls
    "%copy.1 = f32[8]{0}(%fusion.1)": "client_grad",  # what it reads
    "%all-reduce.1 = f32[8]{0}(%copy.1)": "reduce",   # the cohort's psum
    "%k = f32[8]{0}(%g)": "server_update",
    "%g = f32[8]{0}(%t)": "server_update",            # the while holds it
    "%out = f32[8]{0}(%all-reduce.1, %lonely)": "download_accounting",
    "%lonely = f32[8]{0}()": "download_accounting",   # what reads it
}


@pytest.mark.parametrize("key", sorted(HLO_WANT))
def test_op_phases_rules_on_a_hand_made_module(key):
    assert tracing.op_phases(HLO)[key] == HLO_WANT[key]


HLO_LAYERS = """HloModule toy

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %e = f32[8]{0} exponential(%p), metadata={op_name="jit(f)/phase:client_grad/checkpoint/layer:ssm_scan/exp"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %copy.2 = f32[8]{0} copy(%fusion.2)
  %d = f32[8]{0} dot(%copy.2, %a), metadata={op_name="jit(f)/phase:client_grad/transpose(jvp(layer:moe_route/layer:moe_experts))/dot"}
  ROOT %s = f32[8]{0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/phase:compress/sketch_vec_pallas"}
}
"""

HLO_LAYERS_WANT = {
    "%e = f32[8]{0}(%p)": "ssm_scan",
    "%fusion.2 = f32[8]{0}(%a)": "ssm_scan",          # what it calls
    "%copy.2 = f32[8]{0}(%fusion.2)": "other",        # no neighbours' rule
    "%d = f32[8]{0}(%copy.2, %a)": "moe_experts",     # the innermost scope
    "%s = f32[8]{0}(%d)": "other",                    # outside the model
}


@pytest.mark.parametrize("key", sorted(HLO_LAYERS_WANT))
def test_op_layers_rules_on_a_hand_made_module(key):
    assert tracing.op_layers(HLO_LAYERS)[key] == HLO_LAYERS_WANT[key]
    # the phases of the same module are as before, layers or not
    assert tracing.op_phases(HLO_LAYERS)[key] in ("client_grad", "compress")


def test_op_layers_finds_every_part_of_the_hybrid_model():
    from commefficient_tpu.federated.losses import make_lm_loss
    from commefficient_tpu.models.nemotron_h import (NemotronH,
                                                     NemotronHConfig)
    model = NemotronH(NemotronHConfig.tiny())
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = make_lm_loss(model, train=True)

    def total(p):
        return jnp.sum(loss(p, (ids, ids), None, True)[0])

    with tracing.phase("client_grad"):
        compiled = jax.jit(jax.grad(total)).lower(params).compile()
    layers = set(tracing.op_layers(compiled).values())
    assert {"ssm_scan", "ssm_proj", "moe_route", "moe_experts", "moe_shared",
            "attn", "lm_head"} <= layers


@pytest.mark.parametrize("printed, key", [
    # the profiler's print of an instruction and as_text's give one key
    ("%copy.21 = f32[100,50]{1,0:T(8,128)S(1)} copy(f32[100,50]"
     "{0,1:T(8,128)} %mask.1)",
     "%copy.21 = f32[100,50]{1,0:T(8,128)S(1)}(%mask.1)"),
    ("  ROOT %copy.21 = f32[100,50]{1,0:T(8,128)S(1)} copy(%mask.1), "
     "sharding={replicated}, metadata={op_name=\"a(b)\"}",
     "%copy.21 = f32[100,50]{1,0:T(8,128)S(1)}(%mask.1)"),
    ("%s.1 = ((f32[5]{0}), f32[2]{0:S(1)}, s32[]{:S(2)}) async-start("
     "f32[5]{0:T(8)} %v.1), calls=%async_computation.28",
     "%s.1 = ((f32[5]{0}), f32[2]{0:S(1)}, s32[]{:S(2)})(%v.1)"),
    ("%s.1 = ((f32[5]{0}), f32[2]{0:S(1)}, s32[]{:S(2)}) slice-start(%v.1),"
     " slice={[0:2]}",
     "%s.1 = ((f32[5]{0}), f32[2]{0:S(1)}, s32[]{:S(2)})(%v.1)"),
    ("%w.2 = (s32[]{:T(128)}, f32[9]{0}) while((s32[]{:T(128)}, /*index=1*/"
     "f32[9]{0}) %tuple.8), condition=%c, body=%b",
     "%w.2 = (s32[]{:T(128)}, f32[9]{0})(%tuple.8)"),
])
def test_instruction_key_is_the_same_for_both_printers(printed, key):
    assert tracing.instruction_key(printed) == key


@pytest.mark.parametrize("mode", ["sketch", "true_topk", "local_topk"])
def test_every_pallas_call_of_the_round_is_named(mode):
    learner = make_learner(**MODES[mode])
    with sketch_kernels.force_dispatch("kernel"):
        jaxpr = jax.make_jaxpr(learner._round)(learner.state, *round_args())
    names = [site.eqn.params["name"] for site in iter_eqns(jaxpr)
             if site.primitive == "pallas_call"]
    assert names and set(names) <= KERNEL_NAMES
    if mode == "sketch":
        assert set(names) == {"sketch_vec_pallas", "estimates_pallas",
                              "radix_count_pallas",
                              "unsketch_select_pallas"}
        # the server's estimate pass lies in its phase (the compiled
        # call, by op_phases: tests/test_chip_compile.py)
        assert ("server_update", "pallas_call:estimates_pallas") in (
            eqn_phases(jaxpr.jaxpr))


# ------------------------------------------------------ spans on the path

def test_rounds_leave_dispatch_and_sync_spans_under_their_index():
    learner = make_learner(**MODES["sketch"])
    pipe = learner.pipeline()
    for r in range(3):
        pipe.push(learner.train_round_async(*host_batch(r)))
    pipe.flush()
    learner.evaluate([])
    snap = tracing.snapshot()
    rounds = snap["rounds"][1:] + [snap["open"]]     # [0] is set-up
    assert [r["round"] for r in rounds] == [0, 1, 2]
    assert all(r["spans"]["round.dispatch"][1] == 1 for r in rounds)
    assert [r["spans"].get("round.sync", [0, 0])[1] for r in rounds] == [
        0, 1, 2]                                     # one round behind
    assert snap["open"]["round"] + 1 == learner.rounds_done == 3
    assert "rounds" not in snap["counters"]          # the ring's index is it
    assert "eval" in snap["open"]["spans"]


def test_offload_pipeline_times_itself_in_spans_not_in_stats():
    learner = make_learner(client_state_offload=True, **MODES["local_topk"])
    for r in range(2):
        learner.train_round(*host_batch(r))
    spans = tracing.snapshot()["open"]["spans"]
    for r in tracing.snapshot()["rounds"]:
        for name, tot in r["spans"].items():
            have = spans.setdefault(name, [0, 0, 0])
            spans[name] = [a + b for a, b in zip(have, tot)]
    stats = learner._offload_pipe.stats
    assert set(stats) == {"gathers", "prefetch_hits", "rows_from_pending",
                          "flushed_rounds"}
    assert spans["offload.gather"][1] == stats["gathers"] == 2
    assert spans["offload.scatter"][1] == stats["flushed_rounds"] == 2
    assert spans["offload.flush"][1] == 2
    assert spans["offload.scatter"][0] <= spans["offload.flush"][0]


def test_two_round_train_leaves_the_data_and_round_totals(monkeypatch):
    from commefficient_tpu.training import cv
    monkeypatch.setattr(cv, "get_transforms",
                        lambda name, train: (lambda cols, rng: cols))
    args = cv.build_parser(default_lr=0.4).parse_args(
        "--dataset_name Synthetic --model TinyMLP --mode sketch "
        "--error_type virtual --k 10 --num_rows 3 --num_cols 100 "
        "--num_workers 4 --local_batch_size 8 --eval_before_start "
        "--valid_batch_size 64".split())
    np.random.seed(0)
    cv.train(args, max_rounds=2, log=False)
    snap = tracing.snapshot()
    setup, first = snap["rounds"][0], snap["rounds"][1]
    assert setup["round"] is None
    for name in ("setup.data", "setup.learner", "setup.eval", "eval"):
        assert setup["spans"][name][1] == 1
    assert setup["spans"]["setup.eval"][2] < setup["spans"]["setup.eval"][0]
    assert [r["round"] for r in snap["rounds"][1:]] + [
        snap["open"]["round"]] == [0, 1]
    for name in ("data.sample", "data.fetch", "data.augment",
                 "data.assemble", "data.h2d", "round.dispatch"):
        assert first["spans"][name][1] >= 1, name
    assert first["spans"]["data.fetch"][1] == 4 * first["spans"][
        "data.h2d"][1]                               # once a client
    assert first["counts"]["data.rows"] == 32 * first["spans"]["data.h2d"][1]
    assert first["counts"]["data.h2d_bytes"] > 0
    last = snap["open"]["spans"]
    assert last["round.dispatch"][1] == 1 and last["round.sync"][1] == 2
    assert snap["counters"]["compile.programs"][0] >= 1


@pytest.mark.parametrize("one_pass", [True, False])
def test_batcher_counts_its_paths_and_its_arrays(uint8_pool, one_pass):
    """12 rounds of W = 2 clients x B = 5 images, the consumer holding one
    round at a time: which path built each round, how often an array was
    written again, and the spans of a round built in one pass."""
    from commefficient_tpu import native
    from commefficient_tpu.data import FedBatcher
    from commefficient_tpu.data.transforms import cifar10_train_transforms
    if one_pass and native.lib() is None:
        pytest.skip("native fedio library unavailable")
    ds = uint8_pool((40, 40, 40), num_clients=12, seed=3,
                    transform=cifar10_train_transforms)
    if not one_pass:
        ds.round_builder = lambda: None
    batcher = FedBatcher(ds, 2, 5, seed=1)
    for _ in batcher.epoch():
        pass
    snap = tracing.snapshot()
    counters = {k: v[0] for k, v in snap["counters"].items()}
    ran, other = (("data.rounds_one_pass", "data.rounds_per_client")
                  if one_pass else
                  ("data.rounds_per_client", "data.rounds_one_pass"))
    assert counters[ran] == 12 and other not in counters
    # the round being built and the one the consumer holds: two arrays
    assert counters["data.arrays_new"] == 2
    assert counters["data.arrays_reused"] == 10
    assert counters["data.rows"] == 120
    spans = snap["open"]["spans"]
    assert spans["data.sample"][1] == 13             # the step that ends it
    per_round = 1 if one_pass else 2                 # once a round / client
    assert spans["data.fetch"][1] == 12 * per_round
    assert spans["data.augment"][1] == 24            # draws + pass / clients
    # skipped rounds draw and build nothing
    for _ in batcher.epoch(skip=3):
        pass
    counters = {k: v[0] for k, v in tracing.snapshot()["counters"].items()}
    assert counters[ran] == 12 + 9
    assert counters["data.arrays_new"] == 2


# ------------------------------------------------------ the round's stamps

def stamped_rounds(timeout=30.0):
    """The marked rounds, once the waiter has stamped every one it was
    handed (it stamps on its own thread, so give it a moment)."""
    deadline = time.monotonic() + timeout
    while True:
        snap = tracing.snapshot()
        rounds = [r for r in snap["rounds"] + [snap["open"]]
                  if r["round"] is not None]
        if all(r["t_done_ns"] for r in rounds if r["t_enq_ns"]) or (
                time.monotonic() > deadline):
            return rounds
        time.sleep(0.01)


def check_stamps(rounds):
    assert rounds
    for r in rounds:
        assert r["t_done_ns"] >= r["t_enq_ns"] >= r["t_ns"], r["round"]
    done = [r["t_done_ns"] for r in rounds]
    assert done == sorted(done)                      # the device's order
    marks = [r["t_ns"] for r in rounds]
    assert marks == sorted(marks)


def waiters():
    return [t for t in threading.enumerate() if t.name == tracing.WAITER]


def quiet():
    """A recorder with no waiter alive (a former test's ends on reset)."""
    tracing.reset()
    for t in waiters():
        t.join(10)
    assert not waiters()


def buffered_learner(fault_model):
    from commefficient_tpu.federated.buffer import BufferedFedLearner
    from commefficient_tpu.federated.faults import FaultModel
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=W, num_clients=6,
                    lr_scale=0.05, server_mode="buffered", **MODES[
                        "local_topk"])
    fm = FaultModel(0, 6, dropout_prob=0.0, latency_sigma=0.1) if (
        fault_model) else None
    return BufferedFedLearner(model, cfg, make_cv_loss(model), None,
                              jax.random.PRNGKey(1),
                              np.zeros((1, 8), np.float32), fault_model=fm)


@pytest.mark.parametrize("site", ["sync", "buffered_lockstep",
                                  "buffered_faults"])
def test_every_dispatch_site_stamps_enqueue_and_completion(site):
    learner = (make_learner(**MODES["sketch"]) if site == "sync"
               else buffered_learner(site == "buffered_faults"))
    pipe = learner.pipeline()
    for r in range(3):
        pipe.push(learner.train_round_async(*host_batch(r)))
    pipe.flush()
    rounds = stamped_rounds()
    assert [r["round"] for r in rounds] == [0, 1, 2]
    check_stamps(rounds)
    for r in rounds:                 # the enqueue lies inside the dispatch
        (t0, t1), = [(a, b) for name, a, b in r["intervals"]
                     if name == "round.dispatch"]
        assert t0 <= r["t_enq_ns"] <= t1


def test_the_waiter_stamps_every_round_of_a_short_train(monkeypatch):
    from commefficient_tpu.training import cv
    quiet()
    seen = []                        # is a waiter alive at each mark?
    mark = tracing._REC.mark

    def watched(index):
        seen.append(bool(waiters()))
        mark(index)

    monkeypatch.setattr(tracing._REC, "mark", watched)
    monkeypatch.setattr(cv, "get_transforms",
                        lambda name, train: (lambda cols, rng: cols))
    args = cv.build_parser(default_lr=0.4).parse_args(
        "--dataset_name Synthetic --model TinyMLP --mode sketch "
        "--error_type virtual --k 10 --num_rows 3 --num_cols 100 "
        "--num_workers 4 --local_batch_size 8 --eval_before_start "
        "--valid_batch_size 64".split())
    np.random.seed(0)
    cv.train(args, max_rounds=4, log=False)
    # set-up (data, learner, compile, the validation pass) started no
    # thread: the first mark found none and left one behind
    assert seen[0] is False and all(seen[1:]) and len(waiters()) == 1
    rounds = stamped_rounds()
    assert [r["round"] for r in rounds] == [0, 1, 2, 3]
    check_stamps(rounds)
    setup = tracing.snapshot()["rounds"][0]
    assert setup["t_enq_ns"] is None and setup["t_done_ns"] is None
    names = [i[0] for i in setup["intervals"]]
    assert {"setup.data", "setup.learner", "setup.eval"} <= set(names)


class Gate:
    """A round output the test makes ready by hand (or never)."""

    def __init__(self, fails=False):
        self.open = threading.Event()
        self.fails = fails

    def block_until_ready(self):
        self.open.wait()
        if self.fails:
            raise RuntimeError("the round failed")
        return self


def test_a_round_not_ready_is_unstamped_and_blocks_no_dispatch():
    tracing.round_mark(0)
    first, failing, last = Gate(), Gate(fails=True), Gate()
    t0 = time.perf_counter()
    tracing.round_enqueued(first)
    tracing.round_mark(1)
    tracing.round_enqueued(failing)
    tracing.round_mark(2)
    tracing.round_enqueued(last)
    assert time.perf_counter() - t0 < 0.5           # nothing waited here
    time.sleep(0.05)
    snap = tracing.snapshot()
    rounds = snap["rounds"][1:] + [snap["open"]]
    assert all(r["t_enq_ns"] and r["t_done_ns"] is None for r in rounds)
    # in order: the later rounds wait behind the first; a failed output
    # leaves its round unstamped and the waiter goes on
    last.open.set()
    failing.open.set()
    time.sleep(0.05)
    assert tracing.snapshot()["open"]["t_done_ns"] is None
    first.open.set()
    deadline = time.monotonic() + 10
    while tracing.snapshot()["open"]["t_done_ns"] is None:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    snap = tracing.snapshot()
    done = [r["t_done_ns"] for r in snap["rounds"][1:] + [snap["open"]]]
    assert done[0] and done[1] is None and done[2] >= done[0]


def test_intervals_are_the_main_threads_top_level_spans_and_capped():
    tracing.round_mark(0)
    with tracing.span("outer"):
        with tracing.span("inner"):
            time.sleep(0.001)
    worker = threading.Thread(target=lambda: tracing.span("elsewhere")
                              .__enter__().__exit__(None, None, None))
    worker.start()
    worker.join()
    for _ in range(tracing.MAX_INTERVALS + 5):
        with tracing.span("leaf"):
            pass
    rnd = tracing.snapshot()["open"]
    names = [name for name, _, _ in rnd["intervals"]]
    assert names[0] == "outer" and "inner" not in names
    assert "elsewhere" not in names and "elsewhere" in rnd["spans"]
    assert len(names) == tracing.MAX_INTERVALS
    assert rnd["intervals_dropped"] == 1 + 5          # 32 kept of 1 + 37
    (_, t0, t1) = rnd["intervals"][0]
    assert t1 - t0 == rnd["spans"]["outer"][0] >= rnd["spans"]["inner"][0]
    edges = [t for _, a, b in rnd["intervals"] for t in (a, b)]
    assert edges == sorted(edges)                    # in order, disjoint
    tracing.round_mark(1)
    assert tracing.snapshot()["open"]["intervals"] == []


def monitoring_listeners():
    from jax._src import monitoring
    return [len(get()) for get in (monitoring.get_event_listeners,
                                   monitoring.get_event_duration_listeners,
                                   monitoring.get_event_time_span_listeners,
                                   monitoring.get_scalar_listeners)]


def test_the_hand_off_adds_nothing_to_the_round():
    learner = make_learner(**MODES["sketch"])
    before = learner._round.lower(learner.state, *round_args()).as_text()
    listening = monitoring_listeners()
    pipe = learner.pipeline()
    for r in range(3):
        pipe.push(learner.train_round_async(*host_batch(r)))
    pipe.flush()
    check_stamps(stamped_rounds())
    after = learner._round.lower(learner.state, *round_args()).as_text()
    assert after == before
    assert learner._round._cache_size() == 1         # nothing recompiled
    assert monitoring_listeners() == listening       # and nothing listens


def test_the_ring_and_the_waiter_stay_bounded():
    """Rounds as fast as the host can mark them, the interpreter switching
    threads every microsecond: the waiter's stamps and the main thread's
    spans and intervals land in the same records, none lost."""
    outs = [jnp.float32(i) for i in range(tracing.RING_ROUNDS + 10)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for i, out in enumerate(outs):
            tracing.round_mark(i)
            with tracing.span("round.dispatch"):
                tracing.round_enqueued(out)
        del outs
        rounds = stamped_rounds()
    finally:
        sys.setswitchinterval(interval)
    assert all(r["spans"]["round.dispatch"][1] == 1
               and len(r["intervals"]) == 1 for r in rounds)
    assert len(tracing.snapshot()["rounds"]) == tracing.RING_ROUNDS
    assert len(rounds) == tracing.RING_ROUNDS + 1     # and the open one
    check_stamps(rounds)
    assert tracing._REC.waiting.empty()              # it holds no output
    assert len(waiters()) == 1
