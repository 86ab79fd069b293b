"""Fixed-shape device batches from ragged per-client samples.

XLA wants static shapes, so ragged client batches (especially the
``local_batch_size == -1`` whole-client regime, SURVEY.md §7 hard parts)
become (num_workers, pad_size, ...) arrays plus a validity mask. The round
function weights every sum by the mask, so padding never changes the math
(tested by test_padding_invariance).
"""

from __future__ import annotations

import sys
from collections import deque
from typing import Iterator, Optional, Tuple

import numpy as np

from commefficient_tpu.data.sampler import FedSampler
from commefficient_tpu.utils.tracing import count, span, spanned

#: rounds' column arrays a batcher keeps to write again. The first touch of
#: a fresh array costs ten times the write (PERF.md, PR 32), and a round in
#: flight (being built, in a transfer, two in the prefetch queue) holds few
KEPT_ROUNDS = 4


def _max_refs(arrays) -> int:
    return max(sys.getrefcount(a) for a in arrays)


#: what ``_max_refs`` reads of arrays that only their list refers to
_OWN_REFS = _max_refs([object()])


class FedBatcher:
    """Iterates federated rounds as (client_ids, batch_arrays, mask).

    The column arrays of a round are written again by a later round once
    nothing but the batcher refers to them: whoever still holds a round --
    a list of ``epoch()``'s items, a ``jax.device_put`` in flight, a device
    array or a view that aliases the memory -- holds a reference, and never
    sees it change. ``ids`` and ``mask`` are new every round."""

    def __init__(self, dataset, num_workers: int, local_batch_size: int,
                 seed: int = 0, pad_size: Optional[int] = None):
        self.dataset = dataset
        self.num_workers = num_workers
        self.sampler = FedSampler(dataset, num_workers, local_batch_size,
                                  seed=seed)
        if pad_size is None:
            if local_batch_size == -1:
                pad_size = int(np.max(dataset.data_per_client))
            else:
                pad_size = local_batch_size
        self.pad_size = pad_size
        # (column arrays, the (W, B) rows written in them): the oldest
        # leaves when a fifth is made
        self._kept = deque(maxlen=KEPT_ROUNDS)

    def epoch(self, skip: int = 0
              ) -> Iterator[Tuple[np.ndarray, tuple, np.ndarray]]:
        """One epoch of device-shaped rounds. ``skip`` replays the first
        ``skip`` rounds without yielding them — the sampler AND the
        dataset's augmentation RNG (stochastic train transforms draw from
        ``dataset.rng`` per fetched batch) advance exactly as if those
        rounds had been trained, so a preempted run resumes on the
        uninterrupted run's bitwise round sequence (docs/ROBUSTNESS.md).

        Where the dataset offers a ``round_builder`` a round's image
        column is one native pass; otherwise the round is built client by
        client through ``get_flat_batch``. Same batches, same draws."""
        self._epoch_start_aug = self._aug_state()
        builder = self.dataset.round_builder()
        for round_batches in spanned(self.sampler.epoch(), "data.sample"):
            if skip > 0:
                skip -= 1
                self._skip_round(round_batches, builder)
            elif builder is not None:
                # yielded as built: a local of this frame that held the
                # round would count as a reference when the next one
                # looks for arrays to write again
                yield self._round_in_one_pass(round_batches, builder)
            else:
                yield self._round_per_client(round_batches)

    def _skip_round(self, round_batches, builder) -> None:
        """Advance the augmentation RNG as building the round would."""
        if builder is not None:
            builder.draw([len(flat_idxs) for _, flat_idxs in round_batches])
        else:
            for _, flat_idxs in round_batches:
                self.dataset.get_flat_batch(flat_idxs)

    def _round_per_client(self, round_batches):
        # rounds can have fewer than W clients at epoch end (the reference
        # drops the tail instead, fed_aggregator.py:230-237 — a quirk
        # SURVEY.md says not to replicate); padded workers have all-zero
        # masks and contribute nothing
        W, B = self.num_workers, self.pad_size
        ids = np.zeros(W, np.int32)
        mask = np.zeros((W, B), np.float32)
        for w, (client_id, flat_idxs) in enumerate(round_batches):
            data = self.dataset.get_flat_batch(flat_idxs)
            with span("data.assemble"):
                if w == 0:
                    cols, written = self._arrays(
                        [(d.shape[1:], d.dtype) for d in data])
                n = min(len(flat_idxs), B)
                ids[w] = client_id
                mask[w, :n] = 1.0
                for c, d in zip(cols, data):
                    c[w, :n] = d[:n]
        with span("data.assemble"):
            self._zero_stale(cols, written, mask)
        count("data.rounds_per_client")
        return ids, tuple(cols), mask

    def _round_in_one_pass(self, round_batches, builder):
        W, B = self.num_workers, self.pad_size
        counts = np.array([len(flat_idxs) for _, flat_idxs in round_batches])
        params = builder.draw(counts)
        with span("data.assemble"):
            cols, written = self._arrays(builder.specs)
            ids = np.zeros(W, np.int32)
            ids[:len(counts)] = [client_id for client_id, _ in round_batches]
            # a client's rows past B are drawn for and dropped, as the
            # per-client path does
            worker = np.repeat(np.arange(len(counts)), counts)
            row = np.arange(len(worker)) - (np.cumsum(counts) - counts)[worker]
            keep = row < B
            slots = (worker * B + row)[keep]
            mask = np.zeros((W, B), np.float32)
            mask.reshape(-1)[slots] = 1.0
        flat_idxs = np.concatenate([f for _, f in round_batches])[keep]
        rest = builder.write(flat_idxs, params[keep], slots, cols[0])
        with span("data.assemble"):
            for c, rows in zip(cols[1:], rest):
                c.reshape((W * B,) + c.shape[2:])[slots] = rows
            self._zero_stale(cols, written, mask)
        count("data.rounds_one_pass")
        return ids, tuple(cols), mask

    def _arrays(self, specs):
        """``(cols, written)``: a round's column arrays, (W, B) + shape of
        dtype for each of ``specs``, zero wherever ``written`` (W, B) is
        False. Kept ones that nothing else refers to any more, else new."""
        for cols, written in self._kept:
            if ([(c.shape[2:], c.dtype) for c in cols] == list(specs)
                    and _max_refs(cols) == _OWN_REFS):
                count("data.arrays_reused")
                return cols, written
        shape = (self.num_workers, self.pad_size)
        cols = [np.zeros(shape + tuple(s), d) for s, d in specs]
        self._kept.append((cols, np.zeros(shape, bool)))
        count("data.arrays_new")
        return self._kept[-1]

    @staticmethod
    def _zero_stale(cols, written, mask) -> None:
        """Rows an earlier round wrote and this one did not read zero
        again, as in a new array."""
        now = mask > 0
        stale = written & ~now
        if stale.any():
            for c in cols:
                c[stale] = 0
        written[...] = now

    # -- preemption cursor (training/preempt.py) -------------------------

    def _aug_state(self):
        rng = getattr(self.dataset, "rng", None)
        return rng.get_state() if rng is not None else None

    def cursor(self, in_epoch: bool) -> dict:
        """Composes the sampler's RNG cursor with the dataset's
        augmentation RNG (epoch-start state mid-epoch — the resumed epoch
        replays its fetches — live state at a boundary)."""
        cur = {"sampler": self.sampler.cursor(in_epoch)}
        aug = (getattr(self, "_epoch_start_aug", None) if in_epoch
               else self._aug_state())
        if aug is not None:
            kind, keys, pos, has_gauss, cached = aug
            cur["aug"] = [kind, [int(x) for x in keys], int(pos),
                          int(has_gauss), float(cached)]
        return cur

    def restore_cursor(self, cur: dict, in_epoch: bool) -> None:
        self.sampler.restore_cursor(cur["sampler"], in_epoch)
        if cur.get("aug") is not None:
            kind, keys, pos, has_gauss, cached = cur["aug"]
            self.dataset.rng.set_state(
                (kind, np.asarray(keys, np.uint32), pos, has_gauss, cached))

    def steps_per_epoch(self) -> int:
        return self.sampler.steps_per_epoch()


def val_batches(dataset, batch_size: int):
    """Centralized validation batches: ((inputs...,), mask) pairs, padded to
    a fixed batch size so eval jits once."""
    n = len(dataset)
    for start in range(0, n, batch_size):
        idxs = np.arange(start, min(start + batch_size, n))
        data = dataset.get_val_batch(idxs)
        b = len(idxs)
        mask = np.zeros(batch_size, np.float32)
        mask[:b] = 1.0
        cols = []
        for d in data:
            pad = np.zeros((batch_size,) + d.shape[1:], d.dtype)
            pad[:b] = d
            cols.append(pad)
        yield tuple(cols), mask
