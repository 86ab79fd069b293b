"""Federated packed token sequences (``--dataset_name TOKENS``).

The on-disk layout is public and small: under ``--dataset_dir``

    client<c>.npy   int32, 1-D: client c's documents, tokenized and
                    concatenated (c = 0, 1, ... without a gap)
    valid.npy       int32, 1-D: the held-out stream

A client owns its documents. Its stream is cut into fixed packed sequences
of ``--max_seq_len`` tokens (the tail that does not fill one is left out);
a training example is one sequence, ``(tokens (T,), labels (T,))`` with
``labels[t] = tokens[t + 1]`` and -1 (ignored by the loss) at the end.
``benchmarks/datagen/token_docs.py`` writes such a directory from a seed;
any tokenizer's output in this layout trains the same way.
"""

from __future__ import annotations

import os

import numpy as np

from commefficient_tpu.data.fed_dataset import FedDataset


def next_token_labels(tokens):
    """labels[..., t] = tokens[..., t + 1]; -1 at the last position."""
    return np.concatenate(
        [tokens[..., 1:], np.full_like(tokens[..., :1], -1)], axis=-1)


class FedTokens(FedDataset):
    def __init__(self, *args, max_seq_len: int = 256, **kw):
        self.max_seq_len = int(max_seq_len)
        super().__init__(*args, **kw)

    def client_fn(self, client_id: int) -> str:
        return os.path.join(self.dataset_dir, f"client{client_id}.npy")

    def stats_fn(self) -> str:
        # what marks a directory as holding the layout: its first client
        return self.client_fn(0)

    def prepare_datasets(self):
        raise FileNotFoundError(
            f"no token streams under {self.dataset_dir}: --dataset_name "
            f"TOKENS reads client<c>.npy and valid.npy (int32, 1-D; see "
            f"commefficient_tpu/data/tokens.py)")

    def _sequences(self, path):
        stream = np.load(path, mmap_mode="r")
        n = len(stream) // self.max_seq_len
        return np.asarray(stream[:n * self.max_seq_len], np.int32).reshape(
            n, self.max_seq_len)

    def _load_meta(self):
        T = self.max_seq_len
        self.client_datasets = []
        c = 0
        while os.path.exists(self.client_fn(c)):
            self.client_datasets.append(self._sequences(self.client_fn(c)))
            c += 1
        self.valid = self._sequences(
            os.path.join(self.dataset_dir, "valid.npy"))
        self.images_per_client = np.array(
            [len(a) for a in self.client_datasets])
        if not self.images_per_client.all():
            raise ValueError(f"a client under {self.dataset_dir} holds "
                             f"fewer than --max_seq_len {T} tokens")
        self.num_val_images = len(self.valid)
        self._client_ends = np.cumsum(self.images_per_client)
        self._client_starts = self._client_ends - self.images_per_client

    def _get_train_batch(self, client_id, idxs):
        tokens = self.client_datasets[client_id][idxs]
        return tokens, next_token_labels(tokens)

    def _get_val_batch(self, idxs):
        tokens = self.valid[idxs]
        return tokens, next_token_labels(tokens)
