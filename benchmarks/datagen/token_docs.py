"""Token streams in the layout ``--dataset_name TOKENS`` reads
(``commefficient_tpu/data/tokens.py``): ``client<c>.npy`` and ``valid.npy``,
int32 and 1-D, a client's documents concatenated.

Ids follow a Zipf law with exponent ``zipf_a`` over ``vocab_rows`` ranks
(P(rank r) ~ r**-a, truncated and renormalised): a few ids carry most of the
text, as words do, so the tokens an expert sees a round are uneven. The
frequent ranks are scattered over the id range by a permutation of the seed,
and every client draws from its own stream of the seed. Drawn in bulk: one
inverse-CDF lookup a client.
"""

from __future__ import annotations

import os

import numpy as np


def write(root, seed, num_clients, tokens_per_client, valid_tokens,
          vocab_rows, zipf_a=1.1):
    """Write the files under ``root``; returns the bytes written."""
    os.makedirs(root, exist_ok=True)
    cdf = np.cumsum(np.arange(1, vocab_rows + 1, dtype=np.float64) ** -zipf_a)
    cdf /= cdf[-1]
    ids = np.random.Generator(np.random.SFC64(np.random.SeedSequence(
        [seed, 0x70C]))).permutation(vocab_rows).astype(np.int32)
    written = 0
    jobs = [(f"client{c}.npy", tokens_per_client, c + 1)
            for c in range(num_clients)] + [("valid.npy", valid_tokens, 0)]
    for name, n, tag in jobs:
        rng = np.random.Generator(np.random.SFC64(
            np.random.SeedSequence([seed, tag])))
        ranks = np.searchsorted(cdf, rng.random(n), side="right")
        path = os.path.join(root, name)
        np.save(path, ids[np.minimum(ranks, vocab_rows - 1)])
        written += os.path.getsize(path)
    return written
