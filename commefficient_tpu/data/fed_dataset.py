"""Client-partitioned dataset base (reference data_utils/fed_dataset.py:9-98).

Contract preserved from the reference:
* the train set is a list of per-client numpy arrays; ``images_per_client``
  gives the natural (non-iid) partition sizes
* ``do_iid`` overlays a global permutation so each client sees an iid slice
  (ref :29, :68-78)
* metadata is cached in ``stats.json`` in the dataset dir; first use calls
  ``prepare_datasets`` (ref :23-24)
* validation data is centralized (client_id == -1 downstream)

Difference: instead of per-item ``__getitem__`` through a torch DataLoader,
batches are fetched as whole per-client index arrays (``get_flat_batch``) —
the host side stays numpy and hands fixed-shape arrays to the device. A
dataset whose rows and transform allow it also offers a ``round_builder``:
a whole round's images in one native pass (``FedBatcher`` asks for it).
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Tuple

import numpy as np

from commefficient_tpu import native
from commefficient_tpu.data.transforms import PadCropTrain
from commefficient_tpu.utils.tracing import count, span


class FedDataset:
    def __init__(self, dataset_dir: str = "./dataset", do_iid: bool = False,
                 num_clients: Optional[int] = None, train: bool = True,
                 transform=None, seed: int = 0):
        self.dataset_dir = dataset_dir
        self.do_iid = do_iid
        self._num_clients = num_clients
        self.train = train
        self.transform = transform
        self.rng = np.random.RandomState(seed)

        if not do_iid and num_clients == 1:
            raise ValueError("can't have 1 client when non-iid")

        if not os.path.exists(self.stats_fn()):
            self.prepare_datasets()
        self._load_meta()

        if self.do_iid and self.train:
            self.iid_shuffle = self.rng.permutation(len(self))

    # --- to implement per dataset ----------------------------------------
    def prepare_datasets(self):
        raise NotImplementedError

    def _get_train_batch(self, client_id: int,
                         idxs: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Return (inputs..., targets) arrays for rows of a *natural* client."""
        raise NotImplementedError

    def _get_val_batch(self, idxs: np.ndarray) -> Tuple[np.ndarray, ...]:
        raise NotImplementedError

    # --- shared machinery -------------------------------------------------
    def stats_fn(self) -> str:
        return os.path.join(self.dataset_dir, "stats.json")

    def _load_meta(self):
        with open(self.stats_fn()) as f:
            stats = json.load(f)
        self.images_per_client = np.array(stats["images_per_client"])
        self.num_val_images = stats["num_val_images"]
        # each natural client's [start, end) of the flat index space
        self._client_ends = np.cumsum(self.images_per_client)
        self._client_starts = self._client_ends - self.images_per_client

    @property
    def num_clients(self) -> int:
        return (self._num_clients if self._num_clients is not None
                else len(self.images_per_client))

    @property
    def data_per_client(self) -> np.ndarray:
        """Partition sizes after iid/num_clients overlay (ref :31-48)."""
        if self.do_iid:
            n = len(self)
            per = np.full(self.num_clients, n // self.num_clients, dtype=int)
            per[self.num_clients - (n % self.num_clients):] += 1 \
                if n % self.num_clients else 0
            return per
        n_nat = len(self.images_per_client)
        if self.num_clients % n_nat != 0:
            raise ValueError(
                f"num_clients ({self.num_clients}) must be a multiple of the "
                f"natural partition count ({n_nat}) for non-iid splits")
        per_class = self.num_clients // n_nat
        out = []
        for num_images in self.images_per_client:
            sizes = [num_images // per_class] * per_class
            sizes[-1] += num_images % per_class
            out.extend(sizes)
        return np.array(out)

    def __len__(self) -> int:
        if self.train:
            return int(np.sum(self.images_per_client))
        return self.num_val_images

    def _flat_to_natural(self, flat_idxs: np.ndarray):
        """Map global flat indices to (natural_client, idx_within) pairs."""
        if self.do_iid:
            flat_idxs = self.iid_shuffle[flat_idxs]
        client = np.searchsorted(self._client_ends, flat_idxs, side="right")
        return client, flat_idxs - self._client_starts[client]

    def get_flat_batch(self, flat_idxs: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Fetch arbitrary flat train indices (crossing natural clients)."""
        with span("data.fetch"):
            clients, within = self._flat_to_natural(np.asarray(flat_idxs))
            parts = []
            order = np.argsort(clients, kind="stable")
            inv = np.empty_like(order)
            inv[order] = np.arange(len(order))
            for c in np.unique(clients):
                rows = within[clients == c]
                parts.append(self._get_train_batch(int(c), rows))
            cols = [np.concatenate([p[i] for p in parts])
                    for i in range(len(parts[0]))]
            cols = [c[inv] for c in cols]  # restore request order
        count("data.rows", len(cols[0]))
        if self.transform is not None:
            with span("data.augment"):
                cols = self.transform(cols, self.rng)
        return tuple(cols)

    def round_builder(self):
        """What builds a whole round's columns at once (see
        ``PadCropRound`` for the interface), or None where this dataset
        has no such thing: the batcher then builds the round client by
        client through ``get_flat_batch``. Either way the same batches and
        the same draws from ``self.rng``."""
        return None

    def get_val_batch(self, idxs: np.ndarray) -> Tuple[np.ndarray, ...]:
        cols = list(self._get_val_batch(np.asarray(idxs)))
        if self.transform is not None:
            cols = self.transform(cols, self.rng)
        return tuple(cols)

    def client_slices(self) -> List[Tuple[int, int]]:
        """[start, end) flat range of each (overlay) client."""
        cumsum = np.cumsum(self.data_per_client)
        starts = np.hstack([[0], cumsum[:-1]])
        return list(zip(starts.tolist(), cumsum.tolist()))


class PreparedArrayDataset(FedDataset):
    """Shared materialized layout: one .npy of images per natural client
    (class-split, ref fed_cifar.py:45-58) + a centralized ``test.npz``.
    Subclasses implement ``_make_xy`` returning the raw arrays; everything
    else — caching, per-client files, batch fetch — is common (used by
    CIFAR10/100 and the offline real-data sets)."""

    name = "prepared"
    #: bump in a subclass whenever its ``_make_xy`` changes what it returns;
    #: a cached split written by an older version is deleted and rebuilt
    #: (caches without the key are grandfathered as version 1)
    version = 1

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if self.train:
            self.client_datasets = [
                np.load(self.client_fn(c))
                for c in range(len(self.images_per_client))]
        else:
            with np.load(self.test_fn()) as t:
                self.test_images = t["test_images"]
                self.test_targets = t["test_targets"]

    def client_fn(self, client_id: int) -> str:
        return os.path.join(self.dataset_dir, f"client{client_id}.npy")

    def test_fn(self) -> str:
        return os.path.join(self.dataset_dir, "test.npz")

    def _make_xy(self):
        """-> (train_x, train_y, test_x, test_y, num_classes)"""
        raise NotImplementedError

    def _load_meta(self):
        with open(self.stats_fn()) as f:
            stats = json.load(f)
        if stats.get("version", 1) != self.version:
            # stale cache from an older _make_xy (e.g. the pre-round-4
            # leaky Patches32 split): drop and rebuild deterministically
            for c in range(len(stats["images_per_client"])):
                if os.path.exists(self.client_fn(c)):
                    os.remove(self.client_fn(c))
            for fn in (self.test_fn(), self.stats_fn()):
                if os.path.exists(fn):
                    os.remove(fn)
            self.prepare_datasets()
        super()._load_meta()

    def prepare_datasets(self):
        os.makedirs(self.dataset_dir, exist_ok=True)
        train_x, train_y, test_x, test_y, n_cls = self._make_xy()
        images_per_client = []
        # overwriting is allowed: stats.json is written LAST and is the
        # cache-validity marker, so an interrupted build (partial client
        # files, no stats.json) is simply rebuilt on the next construction
        # instead of wedging the dir (review r4)
        for c in range(n_cls):
            rows = train_x[train_y == c]
            images_per_client.append(len(rows))
            np.save(self.client_fn(c), rows)
        np.savez(self.test_fn(), test_images=test_x, test_targets=test_y)
        with open(self.stats_fn(), "w") as f:
            json.dump({"images_per_client": images_per_client,
                       "num_val_images": len(test_y),
                       "version": self.version}, f)

    def round_builder(self):
        t = self.transform
        if not (self.train and isinstance(t, PadCropTrain)
                and native.lib() is not None):
            return None
        image = (t.size, t.size, len(t.mean))
        if not all(a.dtype == np.uint8 and a.shape == (n,) + image
                   and a.flags.c_contiguous for a, n in
                   zip(self.client_datasets, self.images_per_client)):
            return None
        return PadCropRound(self)

    def _get_train_batch(self, client_id: int, idxs: np.ndarray):
        imgs = self.client_datasets[client_id][idxs]
        # target == natural client id == the class (ref fed_cifar.py:79-81)
        return imgs, np.full(len(idxs), client_id, np.int32)

    def _get_val_batch(self, idxs: np.ndarray):
        return (self.test_images[idxs],
                self.test_targets[idxs].astype(np.int32))


class PadCropRound:
    """A round of a ``PreparedArrayDataset`` under a ``PadCropTrain``
    transform: every image read as uint8 from its client's array, where it
    lies, and written once, normalized, cropped and flipped, at its place
    in the round's image column. What ``get_flat_batch`` gives client by
    client, bit for bit, with the same draws from ``dataset.rng``."""

    def __init__(self, dataset):
        self.dataset = dataset
        t = dataset.transform
        image = (t.size, t.size, len(t.mean))
        #: (row shape, dtype) of the columns: the images, then the targets
        self.specs = [(image, np.dtype(np.float32)), ((), np.dtype(np.int32))]
        self._table = t.table()
        self._row_bytes = int(np.prod(image))
        self._base = np.array([a.ctypes.data for a in dataset.client_datasets],
                              np.int64)

    def draw(self, counts) -> np.ndarray:
        """The (y, x, flip) rows of a round whose clients fetch ``counts``
        images, drawn client by client as ``get_flat_batch`` draws them."""
        ds = self.dataset
        with span("data.augment"):
            return np.concatenate(
                [ds.transform.draw(ds.rng, int(n)) for n in counts])

    def write(self, flat_idxs, params, slots, images):
        """Image ``flat_idxs[i]`` under ``params[i]`` into image slot
        ``slots[i]`` of ``images`` ((W, B, H, W, C) float32). Returns the
        rows of the other columns: the targets."""
        ds, t = self.dataset, self.dataset.transform
        with span("data.fetch"):
            clients, within = ds._flat_to_natural(np.asarray(flat_idxs))
            # the kernel reads raw addresses, where fancy indexing raises
            if len(clients) and (within.min() < 0
                                 or clients.max() >= len(self._base)):
                raise IndexError(f"flat index out of range for {len(ds)} "
                                 f"train images")
            src = self._base[clients] + within * self._row_bytes
        count("data.rows", len(src))
        with span("data.augment"):
            native.pad_crop_round(src, slots, params, self._table, images,
                                  t.padding, t.mode == "reflect", t.fill)
        # target == natural client id == the class (ref fed_cifar.py:79-81)
        return (clients.astype(np.int32),)
