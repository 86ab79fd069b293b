"""Host-side augmentation pipelines (reference
data_utils/transforms.py:3-75, torchvision-based there).

Images flow as NHWC float32. Each transform is
``fn(cols, rng) -> cols`` over the batch's column list (first column is the
image batch), so pipelines compose with plain function composition.

Two implementations per train pipeline:

* pure numpy (always available; the reference semantics, documented here)
* a fused native path through ``commefficient_tpu.native`` (C++ threaded
  crop+resize+flip+normalize kernels) used automatically when the native
  library builds. Both paths draw the SAME random sequence from the same
  ``RandomState`` — randomness is sampled in Python and only deterministic
  pixel math moves to C++ — so they produce identical augmentations
  (cross-checked in tests/test_native.py).

The augmentation stream is per fetched batch, i.e. per client of a round:
each call draws its images' parameters, in order, from the dataset's one
generator. ``PadCropTrain.draw`` takes a batch's draws as arrays (the same
stream as the numpy stages' scalar draws), and a whole round built in one
native pass (``data/fed_dataset.py::PadCropRound``) still draws client by
client, so which path builds a batch never changes the stream, and a
preempted run replays it (``FedBatcher.epoch(skip)``, docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import numpy as np

from commefficient_tpu import native

CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2471, 0.2435, 0.2616], np.float32)
CIFAR100_MEAN = np.array([0.5071, 0.4867, 0.4408], np.float32)
CIFAR100_STD = np.array([0.2675, 0.2565, 0.2761], np.float32)
FEMNIST_MEAN = np.array([0.9637], np.float32)
FEMNIST_STD = np.array([0.1597], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def normalize(mean, std):
    def fn(cols, rng):
        was_uint8 = cols[0].dtype == np.uint8
        img = cols[0].astype(np.float32)
        if was_uint8:
            img = img / 255.0
        cols[0] = (img - mean) / std
        return cols
    return fn


def random_crop(size: int, padding: int, mode: str = "reflect",
                fill: float = 0.0):
    def fn(cols, rng):
        img = cols[0]
        if mode == "reflect":
            padded = np.pad(img, ((0, 0), (padding, padding),
                                  (padding, padding), (0, 0)), mode="reflect")
        else:
            padded = np.pad(img, ((0, 0), (padding, padding),
                                  (padding, padding), (0, 0)),
                            mode="constant", constant_values=fill)
        out = np.empty_like(img)
        for i in range(img.shape[0]):
            y = rng.randint(0, 2 * padding + 1)
            x = rng.randint(0, 2 * padding + 1)
            out[i] = padded[i, y:y + size, x:x + size]
        cols[0] = out
        return cols
    return fn


def random_hflip(p: float = 0.5):
    def fn(cols, rng):
        img = cols[0]
        flips = rng.rand(img.shape[0]) < p
        img = img.copy()
        img[flips] = img[flips, :, ::-1]
        cols[0] = img
        return cols
    return fn


def _bilinear_resize(img: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """Vectorized bilinear resize of one HWC image (any dtype -> float32)."""
    h, w = img.shape[:2]
    if (h, w) == (out_h, out_w):
        return img.astype(np.float32)
    y = (np.arange(out_h) + 0.5) * h / out_h - 0.5
    x = (np.arange(out_w) + 0.5) * w / out_w - 0.5
    y0 = np.clip(np.floor(y).astype(np.int64), 0, h - 1)
    x0 = np.clip(np.floor(x).astype(np.int64), 0, w - 1)
    y1 = np.minimum(y0 + 1, h - 1)
    x1 = np.minimum(x0 + 1, w - 1)
    wy = np.clip(y - y0, 0.0, 1.0).astype(np.float32)[:, None, None]
    wx = np.clip(x - x0, 0.0, 1.0).astype(np.float32)[None, :, None]
    img = img.astype(np.float32)
    top = img[y0][:, x0] * (1 - wx) + img[y0][:, x1] * wx
    bot = img[y1][:, x0] * (1 - wx) + img[y1][:, x1] * wx
    return top * (1 - wy) + bot * wy


def rrc_crop_params(h, w, rng, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """Sample one RandomResizedCrop window (torchvision semantics, ref
    transforms.py:68): 10 area/aspect attempts, center fallback. Shared by
    the numpy and native pipelines so both consume the same rng sequence."""
    log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
    area = h * w
    for _ in range(10):
        target_area = area * rng.uniform(scale[0], scale[1])
        aspect = np.exp(rng.uniform(log_ratio[0], log_ratio[1]))
        cw = int(round(np.sqrt(target_area * aspect)))
        ch = int(round(np.sqrt(target_area / aspect)))
        if 0 < cw <= w and 0 < ch <= h:
            top = rng.randint(0, h - ch + 1)
            left = rng.randint(0, w - cw + 1)
            return top, left, ch, cw
    # fallback: largest center crop within the ratio bounds
    in_ratio = w / h
    if in_ratio < ratio[0]:
        cw, ch = w, int(round(w / ratio[0]))
    elif in_ratio > ratio[1]:
        ch, cw = h, int(round(h * ratio[1]))
    else:
        cw, ch = w, h
    return (h - ch) // 2, (w - cw) // 2, ch, cw


def random_resized_crop(size: int, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """torchvision RandomResizedCrop semantics (ref transforms.py:68): sample
    an area/aspect crop (10 attempts, center fallback), resize to ``size``."""

    def crop_params(h, w, rng):
        return rrc_crop_params(h, w, rng, scale, ratio)

    def fn(cols, rng):
        img = cols[0]
        was_uint8 = img.dtype == np.uint8
        B, h, w = img.shape[:3]
        out = np.empty((B, size, size, img.shape[3]), np.float32)
        for i in range(B):
            top, left, ch, cw = crop_params(h, w, rng)
            out[i] = _bilinear_resize(img[i, top:top + ch, left:left + cw],
                                      size, size)
        cols[0] = out / 255.0 if was_uint8 else out
        return cols
    return fn


def resize_center_crop(size: int, resize_to: int):
    """Resize shorter side to ``resize_to`` then center-crop ``size``
    (ref transforms.py:72-75: Resize(int(sz*1.14)) + CenterCrop(sz))."""

    def fn(cols, rng):
        img = cols[0]
        was_uint8 = img.dtype == np.uint8
        B, h, w = img.shape[:3]
        s = resize_to / min(h, w)
        rh, rw = max(resize_to, round(h * s)), max(resize_to, round(w * s))
        top, left = (rh - size) // 2, (rw - size) // 2
        out = np.empty((B, size, size, img.shape[3]), np.float32)
        for i in range(B):
            r = (_bilinear_resize(img[i], rh, rw)
                 if (rh, rw) != (h, w) else img[i].astype(np.float32))
            out[i] = r[top:top + size, left:left + size]
        cols[0] = out / 255.0 if was_uint8 else out
        return cols
    return fn


def compose(*fns):
    def fn(cols, rng):
        for f in fns:
            cols = f(list(cols), rng)
        return cols
    return fn


def fused_rrc_train(mean, std, size: int, hflip_p: float = 0.5,
                    scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """RandomResizedCrop + hflip + normalize as ONE native pass when the
    C++ library is available (crop windows and flips still sampled here, in
    the exact order the numpy stages would), numpy stages otherwise."""
    numpy_fn = compose(random_resized_crop(size, scale, ratio),
                       random_hflip(hflip_p), normalize(mean, std))
    # affine on raw uint8: v/255 -> (v - mean)/std  ==  v*kscale + kbias
    kscale = (1.0 / (255.0 * std)).astype(np.float32)
    kbias = (-mean / std).astype(np.float32)

    def fn(cols, rng):
        img = cols[0]
        if (native.lib() is None or img.dtype != np.uint8
                or img.shape[3] != len(kscale)):
            return numpy_fn(cols, rng)
        B, h, w = img.shape[:3]
        params = np.empty((B, 5), np.int32)
        for i in range(B):
            params[i, :4] = rrc_crop_params(h, w, rng, scale, ratio)
        params[:, 4] = rng.rand(B) < hflip_p
        cols[0] = native.rrc_batch(img, params, size, kscale, kbias)
        return cols
    return fn


class PadCropTrain:
    """normalize + random_crop + hflip, split into a draw and an apply.

    ``draw(rng, n)`` takes a batch's (y, x, flip) from ``rng`` as arrays;
    the stream is the numpy stages' own (two scalar ``randint``s an image,
    then one ``rand(n)``), so the generator ends in the same state. Called
    on a batch, the geometric part runs as one native pass over the
    normalized floats (pure copies: bit-identical to the numpy stages),
    or through the numpy stages where the library is absent. A dataset
    that holds uint8 rows in memory can instead hand the draws, ``table()``
    and the geometry to ``native.pad_crop_round``, which writes a whole
    round's pixels once (``PreparedArrayDataset.round_builder``)."""

    def __init__(self, mean, std, size: int, padding: int,
                 mode: str = "reflect", fill: float = 0.0,
                 hflip_p: float = 0.5):
        if not 0 <= padding < size:
            raise ValueError(f"padding {padding} must lie in [0, {size})")
        self.mean, self.std = mean, std
        self.size, self.padding = size, padding
        self.mode, self.fill, self.hflip_p = mode, fill, hflip_p
        aug = ([random_crop(size, padding, mode, fill)] +
               ([random_hflip(hflip_p)] if hflip_p > 0 else []))
        # NOTE: normalize runs first (matching the reference
        # transforms.py:47), so a constant ``fill`` lands in the output
        # verbatim, post-normalization -- e.g. EMNIST's fill=1.0 means
        # "1.0 in normalized space", not raw white
        self._normalize = normalize(mean, std)
        self._numpy_fn = compose(self._normalize, *aug)

    def draw(self, rng, n: int) -> np.ndarray:
        """(n, 3) int32 rows of (y, x, flip) for one batch of ``n``
        images. Per batch, never across batches: one draw over several
        clients' images would interleave offsets and flips differently
        and change the stream."""
        params = np.empty((n, 3), np.int32)
        params[:, :2] = rng.randint(0, 2 * self.padding + 1, size=(n, 2))
        params[:, 2] = (rng.rand(n) < self.hflip_p) if self.hflip_p > 0 else 0
        return params

    def table(self) -> np.ndarray:
        """(256, C) float32: what ``normalize`` makes of each uint8 value
        in each channel, computed by ``normalize`` itself."""
        values = np.arange(256, dtype=np.uint8)[:, None]
        return self._normalize(
            [np.broadcast_to(values, (256, len(self.mean)))], None)[0]

    def __call__(self, cols, rng):
        img = cols[0]
        # the kernel (like the numpy stage, which writes into
        # empty_like(img)) only supports size == H == W; anything else
        # goes to the numpy path, which fails loudly on the mismatch
        if (native.lib() is None or img.shape[1] != self.size
                or img.shape[2] != self.size):
            return self._numpy_fn(cols, rng)
        cols = self._normalize(cols, rng)
        cols[0] = native.pad_crop_batch(
            cols[0], self.draw(rng, img.shape[0]), self.padding,
            self.mode == "reflect", self.fill)
        return cols



cifar10_train_transforms = PadCropTrain(
    CIFAR10_MEAN, CIFAR10_STD, 32, 4, "reflect")
cifar10_test_transforms = normalize(CIFAR10_MEAN, CIFAR10_STD)
cifar100_train_transforms = PadCropTrain(
    CIFAR100_MEAN, CIFAR100_STD, 32, 4, "reflect")
cifar100_test_transforms = normalize(CIFAR100_MEAN, CIFAR100_STD)
femnist_train_transforms = PadCropTrain(
    FEMNIST_MEAN, FEMNIST_STD, 28, 2, "constant", fill=1.0, hflip_p=0.0)
femnist_test_transforms = normalize(FEMNIST_MEAN, FEMNIST_STD)
# stored uint8 @ 256 -> RandomResizedCrop(224)+flip (train) /
# resize(256)+center-crop(224) (val) -> normalize (ref transforms.py:62-75)
imagenet_train_transforms = fused_rrc_train(
    IMAGENET_MEAN, IMAGENET_STD, 224)
imagenet_val_transforms = compose(
    resize_center_crop(224, resize_to=256),
    normalize(IMAGENET_MEAN, IMAGENET_STD))


def get_transforms(dataset_name: str, train: bool):
    table = {
        "CIFAR10": (cifar10_train_transforms, cifar10_test_transforms),
        "CIFAR100": (cifar100_train_transforms, cifar100_test_transforms),
        "EMNIST": (femnist_train_transforms, femnist_test_transforms),
        "ImageNet": (imagenet_train_transforms, imagenet_val_transforms),
        "Synthetic": (None, None),
    }
    tr, te = table.get(dataset_name, (None, None))
    return tr if train else te
