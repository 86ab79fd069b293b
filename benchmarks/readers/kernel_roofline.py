"""A kernel's share of its memory roofline: the bytes the algorithm has to
move per round (the reference's ``kernel_bytes``, from d, r, c, k only) times
the rounds of the traced window, over the chip's memory bandwidth, over the
summed device time of the operations whose name matches ``params["ops"]``.
Nothing matched, nothing returned."""

from benchlib import trace as tr


def read(obs, params):
    p = obs["probe"]
    if not obs["trace"]:
        return None
    hit = tr.op_time(obs["trace"], params["ops"])
    if hit is None:
        return None
    seconds, _ = hit
    rounds = p.trace_round1 - p.trace_round0
    peak = obs["peaks"][obs["device"].device_kind]["hbm_bytes_per_s"]
    need = obs["reference"].kernel_bytes(params["bytes"], p.spec) * rounds
    return 100.0 * (need / peak) / seconds
