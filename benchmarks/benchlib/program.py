"""What the program records about itself, as the readers take it: the spans
and counters of ``commefficient_tpu/utils/tracing.py`` cut to the window, and
the traced device time split by the round program's phases.

Everything here returns ``None`` for a program that has no such recorder (a
commit from before it), so a reader built on it leaves its metric out.
"""

from __future__ import annotations

from benchlib import trace as tr

#: at least this share of the traced device time has to be found in the
#: compiled round by instruction, or no phase time is given at all
FOUND_SHARE = 0.99


def tracing_module():
    try:
        from commefficient_tpu.utils import tracing
    except ImportError:
        return None
    return tracing


def snapshot(obs):
    """The program's ``tracing.snapshot()``, taken once a run."""
    if "program_snapshot" not in obs:
        tracing = tracing_module()
        obs["program_snapshot"] = tracing.snapshot() if tracing else None
    return obs["program_snapshot"]


def window_rounds(obs):
    """The closed rounds whose mark lies inside the window and that hold no
    validation pass (``data.wait_ms`` leaves epoch ends out too)."""
    snap, p = snapshot(obs), obs["probe"]
    if snap is None:
        return None
    return [r for r in snap["rounds"]
            if p.t_start <= r["t_ns"] / 1e9 <= p.t_end
            and "eval" not in r["spans"]]


def counter_at_window_start(obs, name):
    """A counter's value when the window opened: its value now if it last
    changed before that, else less its growth in the rounds marked since."""
    snap, p = snapshot(obs), obs["probe"]
    if snap is None:
        return None
    value, changed_ns = snap["counters"].get(name, (0, 0))
    if changed_ns / 1e9 <= p.t_start:
        return value
    since = [r for r in snap["rounds"] + [snap["open"]]
             if r["t_ns"] / 1e9 >= p.t_start]
    return value - sum(r["counts"].get(name, 0) for r in since)


def compiled_round(obs):
    """The round program compiled from the learner at the run's own state
    and batch shapes, as ``entries/cv.py::round_program`` builds it."""
    import jax
    import jax.numpy as jnp
    p = obs["probe"]
    learner = p.learner
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), learner.state)
    ids, cols, mask = p.batch_shapes
    args = (jax.ShapeDtypeStruct(ids[0], jnp.int32),
            tuple(jax.ShapeDtypeStruct(s, d) for s, d in cols),
            jax.ShapeDtypeStruct(mask[0], jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return learner._round.lower(state, *args).compile()


def phase_times(obs):
    """{phase: ns} of the first device's operations inside the traced
    window, own time (a ``while``'s less its body's), by the phase
    ``tracing.op_phases`` gives each operation's instruction in the compiled
    round; ``None`` unless the operations found there hold ``FOUND_SHARE``
    of the traced device time. Computed once a run."""
    if "phase_times" in obs:
        return obs["phase_times"]
    obs["phase_times"] = None
    tracing, trace = tracing_module(), obs["trace"]
    window = tr.traced_window(trace) if trace else None
    ops = tr.device_ops(trace) if trace else None
    if tracing is None or window is None or not ops:
        return None
    phases = obs.get("op_phases")
    if phases is None:
        phases = obs["op_phases"] = tracing.op_phases(compiled_round(obs))
    lo, hi = window
    inside = [(name, max(s, lo), min(e, hi))
              for name, s, e in ops[sorted(ops)[0]] if e > lo and s < hi]
    out, found, total = {}, 0, 0
    for name, ns in tr.self_times(inside).items():
        total += ns
        phase = phases.get(tracing.instruction_key(name))
        if phase is not None:
            found += ns
            out[phase] = out.get(phase, 0) + ns
    if total and found >= FOUND_SHARE * total:
        obs["phase_times"] = out
    return obs["phase_times"]
