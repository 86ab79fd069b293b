"""The whole step's share of the chip's peak: the operations the plain
reference's forward and backward need for the samples of the rounds
dispatched in the traced window (counted from shapes by the reference's
``flops_per_sample``; sketch, top-k and server update add nothing), over
that window's wall time times the chips' bf16 peak."""


def read(obs, params):
    p = obs["probe"]
    if p.trace_t0 is None or p.trace_t1 is None:
        return None
    rounds = p.trace_round1 - p.trace_round0
    peak = obs["peaks"][obs["device"].device_kind]["bf16_flops_per_s"]
    flops = (obs["reference"].flops_per_sample()
             * p.samples_per_round * rounds)
    chips = int(obs["cell"]["chips"])
    return 100.0 * flops / ((p.trace_t1 - p.trace_t0) * peak * chips)
