"""Work completed per second over the whole window: every sample of every
round dispatched in it, over its whole wall time (start mark to the state
ready after the last round)."""


def read(obs, params):
    p = obs["probe"]
    return p.window_rounds * p.samples_per_round / (p.t_end - p.t_start)
