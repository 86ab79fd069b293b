"""Device prefetch: keep upcoming batches in flight on the accelerator.

The reference's data path blocks per round: batches cross the process
boundary through shm queues right when a worker needs them (reference
fed_aggregator.py:303-307). Here host->device transfer is asynchronous
(``jax.device_put`` returns immediately), so a training loop that puts
the NEXT round's batch on device while the current round computes hides
the transfer entirely. Composes with the one-round metric pipeline
(federated/api.RoundPipeline): together they keep the device busy
end-to-end.
"""

from __future__ import annotations

from collections import deque
from typing import Iterable, Iterator

import jax

from commefficient_tpu.utils.tracing import count, span


def device_prefetch(batches: Iterable, size: int = 2,
                    shardings=None) -> Iterator:
    """Yield items from ``batches`` with up to ``size`` of them already
    transferred to the device (arrays only; pytree structure and order
    preserved).

    ``shardings``: optional sharding pytree (or prefix) for each item —
    REQUIRED for mesh training to deliver the overlap: without it the
    batch lands whole on the default device and the learner reshards it
    device-to-device per round (an extra full-batch hop)."""
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    buf = deque()
    if shardings is None:
        put = lambda item: jax.tree_util.tree_map(jax.device_put, item)
    else:
        put = lambda item: jax.device_put(item, shardings)
    for item in batches:
        with span("data.h2d"):
            buf.append(put(item))
        count("data.h2d_bytes", sum(
            getattr(x, "nbytes", 0) for x in jax.tree_util.tree_leaves(item)))
        if len(buf) > size:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def with_lookahead(items: Iterable) -> Iterator:
    """Yield ``(item, next_item_or_None)`` pairs — one-item lookahead.

    The offload pipeline's gather-ahead (api.HostOffloadPipeline) needs
    the NEXT round's pre-sampled client ids while the current round
    dispatches; wrapping the (already device-prefetched) batch iterator
    exposes them without touching the sampler. The final item pairs with
    ``None`` (no prefetch for a round that never runs)."""
    it = iter(items)
    try:
        cur = next(it)
    except StopIteration:
        return
    for nxt in it:
        yield cur, nxt
        cur = nxt
    yield cur, None
