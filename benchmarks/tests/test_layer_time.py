"""``readers/layer_time.py`` on the hand-made trace of
``test_program_readers.py``: own device time summed by the ``layer:`` scope
of each operation's instruction, nothing for a program without such scopes,
nothing unless 99 % of the traced device time was found."""

import pytest

from test_program_readers import hand_made_obs, hand_made_trace, reader

OP_LAYERS = {
    "%conv.1 = f32[8]{0}(%a)": "ssm_scan",
    "%all-reduce.2 = f32[8]{0}(%conv.1)": "other",
    "%while.3 = (s32[])(%t)": "moe_experts",
    "%radix_count_pallas.9 = s32[1,16]{1,0}(%g)": "moe_route",
    "%fusion.300 = s32[8]{0}(%lc)": "other",
}


def obs_with(layers, **trace_kw):
    obs = hand_made_obs()
    obs["trace"], obs["op_layers"] = hand_made_trace(**trace_kw), layers
    return obs


@pytest.mark.parametrize("layers, want_ns", [
    (["ssm_scan"], 4000), (["moe_experts", "moe_route"], 1000 + 2000),
    (["moe_route"], 2000)])
def test_layer_time_sums_own_time_by_layer(layers, want_ns):
    pytest.importorskip("commefficient_tpu.utils.tracing")
    got = reader("layer_time").read(obs_with(dict(OP_LAYERS)),
                                    {"layers": layers})
    assert got == pytest.approx(want_ns / 2 / 1e6)      # ms a round


def test_layer_time_is_silent_where_there_is_nothing_to_read():
    pytest.importorskip("commefficient_tpu.utils.tracing")
    read = reader("layer_time").read
    # a program whose rounds carry no such layer (ResNet-9: all "other")
    assert read(obs_with({k: "other" for k in OP_LAYERS}),
                {"layers": ["ssm_scan"]}) is None
    # under 99 % of the device time found by instruction
    few = dict(OP_LAYERS)
    del few["%fusion.300 = s32[8]{0}(%lc)"]
    assert read(obs_with(few), {"layers": ["ssm_scan"]}) is None
    # an untraced run
    obs = hand_made_obs()
    obs["op_layers"] = dict(OP_LAYERS)
    assert read(obs, {"layers": ["ssm_scan"]}) is None


def test_layer_time_is_silent_for_a_program_without_op_layers(monkeypatch):
    tracing = pytest.importorskip("commefficient_tpu.utils.tracing")
    monkeypatch.delattr(tracing, "op_layers")
    assert reader("layer_time").read(obs_with(dict(OP_LAYERS)),
                                     {"layers": ["ssm_scan"]}) is None
