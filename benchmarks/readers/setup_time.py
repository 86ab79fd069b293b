"""Seconds from the start of the process to the window's start: imports,
data, dataset preparation, learner, compile or cache hit, warm-up rounds."""


def read(obs, params):
    p = obs["probe"]
    return p.t_start - p.t_process
