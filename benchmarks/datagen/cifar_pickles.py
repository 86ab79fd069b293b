"""CIFAR-10's public on-disk format, filled with generated images.

``cifar-10-batches-py/data_batch_1..5`` and ``test_batch``: pickled dicts
with ``data`` (uint8 rows of 3 072: 1 024 red, green, blue bytes) and
``labels``. Every image is its class's template plus uniform noise (64 grey levels wide), so the
classes can be learnt and a wrong update shows in the loss; labels are
exactly balanced. Everything is drawn from the seed, in bulk: one vectorised
pass per class and file, a few threads wide.
"""

from __future__ import annotations

import os
import pickle
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROW = 3072


def _rows(rng, template, n, noise_bits):
    """n uint8 rows: template + uniform noise in [0, 2**noise_bits), drawn
    as raw 64-bit words (the uint8 path of ``integers`` is ten times
    slower). The template leaves the noise room, so nothing wraps."""
    words = rng.bit_generator.random_raw(n * ROW // 8)
    out = words.view(np.uint8).reshape(n, ROW)
    out >>= 8 - noise_bits
    out += template
    return out


def _file(path, seed, tag, templates, per_class, noise_bits):
    rng = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([seed, tag])))
    data = np.concatenate([_rows(rng, t, per_class, noise_bits)
                           for t in templates])
    labels = np.repeat(np.arange(len(templates)), per_class)
    with open(path, "wb") as f:
        pickle.dump({"data": data, "labels": labels.tolist()}, f,
                    protocol=4)


def write(root, seed, train_images, test_images, num_classes=10,
          template_std=48, noise_bits=6, threads=6):
    """Write the six files under ``root/cifar-10-batches-py`` and return the
    number of bytes written."""
    d = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(d, exist_ok=True)
    if train_images % (5 * num_classes) or test_images % num_classes:
        raise ValueError("images must divide evenly over files and classes")
    trng = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([seed, 0xC1FA])))
    room = 2 ** noise_bits
    templates = np.clip(128 - room // 2 + template_std * trng.standard_normal(
        (num_classes, ROW)), 0, 256 - room).astype(np.uint8)
    jobs = [(os.path.join(d, f"data_batch_{i + 1}"), seed, i + 1, templates,
             train_images // (5 * num_classes), noise_bits)
            for i in range(5)]
    jobs.append((os.path.join(d, "test_batch"), seed, 6, templates,
                 test_images // num_classes, noise_bits))
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for fut in [pool.submit(_file, *job) for job in jobs]:
            fut.result()
    return sum(os.path.getsize(j[0]) for j in jobs)
