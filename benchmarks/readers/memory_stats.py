"""Peak device memory after the window, in MB (10**6 bytes), as the
backend's ``memory_stats()`` reports it (live buffers plus the reservation
for programs' temporaries: ``Probe.peak_bytes``)."""


def read(obs, params):
    peak = obs["probe"].peak_bytes
    return None if peak is None else peak / 1e6
