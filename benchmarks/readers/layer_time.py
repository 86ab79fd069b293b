"""Device time of parts of the model (``params["layers"]``: ``layer:``
scopes of ``commefficient_tpu/utils/tracing.py``, summed) in the traced
window, in ms a round: own time of the first device's operations, each put
in the layer its instruction carries in the compiled round
(``tracing.op_layers``; forward, rematerialised forward and backward alike).
Nothing is returned for a program without such scopes (``op_layers``
missing, or no operation of these layers in the trace), nor unless 99 % of
the traced device time was found in the compiled round by instruction."""

from benchlib import program
from benchlib import trace as tr


def layer_times(obs):
    """{layer: ns}, computed once a run; also leaves ``op_phases`` of the
    same compiled round for ``phase_time`` to find."""
    if "layer_times" in obs:
        return obs["layer_times"]
    obs["layer_times"] = None
    tracing, trace = program.tracing_module(), obs["trace"]
    window = tr.traced_window(trace) if trace else None
    ops = tr.device_ops(trace) if trace else None
    if (tracing is None or not hasattr(tracing, "op_layers")
            or window is None or not ops):
        return None
    layers = obs.get("op_layers")
    if layers is None:
        text = program.compiled_round(obs).as_text()
        obs.setdefault("op_phases", tracing.op_phases(text))
        layers = obs["op_layers"] = tracing.op_layers(text)
    lo, hi = window
    inside = [(name, max(s, lo), min(e, hi))
              for name, s, e in ops[sorted(ops)[0]] if e > lo and s < hi]
    out, found, total = {}, 0, 0
    for name, ns in tr.self_times(inside).items():
        total += ns
        layer = layers.get(tracing.instruction_key(name))
        if layer is not None:
            found += ns
            out[layer] = out.get(layer, 0) + ns
    if total and found >= program.FOUND_SHARE * total:
        obs["layer_times"] = out
    return obs["layer_times"]


def read(obs, params):
    p = obs["probe"]
    times = layer_times(obs)
    rounds = (p.trace_round1 or 0) - (p.trace_round0 or 0)
    if times is None or rounds <= 0:
        return None
    ns = sum(times.get(name, 0) for name in params["layers"])
    return ns / rounds / 1e6 if ns else None
