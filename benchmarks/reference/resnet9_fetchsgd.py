"""Plain reference: ResNet-9 federated rounds under FetchSGD or plain momentum.

Written from the published descriptions, in straightforward ``jax.numpy``;
it imports nothing of ``commefficient_tpu`` and takes nothing the program
made (no weights, hashes or tables). What it follows:

* ResNet-9 (cifar10-fast, as FetchSGD's CIFAR-10 task uses it): conv 3->64,
  conv 64->128 + pool 2, residual(128), conv 128->256 + pool 2,
  conv 256->512 + pool 2, residual(512), max-pool 4, bias-free linear head,
  logits scaled by 0.125; 3x3 bias-free convolutions, ReLU, no batch-norm
  (the reference implementation's default). NHWC images, HWIO kernels.
* One federated round: the mean gradient of the cross-entropy over every
  image of the cohort, plus the weight decay the reference implementation
  folds in (``weight_decay / num_workers * w``).
* ``uncompressed``: v <- g + rho v;  w <- w - lr v.
* ``sketch`` (FetchSGD, Rothchild et al. 2020, Alg. 1 with the reference
  implementation's momentum masking): S <- CountSketch(g);
  v <- S + rho v;  e <- e + v;  u <- top-k by magnitude of unsketch(e);
  zero v and e wherever sketch(u) is non-zero;  w <- w - lr u.
* The CountSketch this repository documents (ops/countsketch.py docstring):
  blocked ("tiled") buckets of 128 lanes, seeded cubic sign polynomial and
  murmur-style mixing over uint32, coefficients from
  ``numpy.random.RandomState(42)``. A sketch is only comparable under the
  same hash family, so the family is part of the configuration; it is
  re-derived here from that description, as scatter-adds and gathers.
* The learning rate: linear 0 -> lr_scale at ``pivot_epoch`` -> 0 at
  ``num_epochs``, read at round / rounds_per_epoch.

Departures, each deliberate: ``compute_dtype`` is the configuration's
(bfloat16 convolutions and head on float32 parameters, float32 loss), not
float32 throughout, because ``correct``'s control is the next precision
*below* the stated one; gradients are accumulated over blocks of rows so
the cohort fits beside nothing else; ``fp8`` (the control) rounds every
convolution's two operands to float8_e4m3 under a per-tensor scale.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128

#: parameter tensors in the order the flat weight vector holds them
#: (name, shape); convolutions are HWIO, the head is (in, out)
LAYOUT = (
    ("prep", (3, 3, 3, 64)),
    ("layer1", (3, 3, 64, 128)),
    ("layer2", (3, 3, 128, 256)),
    ("layer3", (3, 3, 256, 512)),
    ("head", (512, 10)),
    ("res1a", (3, 3, 128, 128)),
    ("res1b", (3, 3, 128, 128)),
    ("res3a", (3, 3, 512, 512)),
    ("res3b", (3, 3, 512, 512)),
)
SIZES = tuple(int(np.prod(s)) for _, s in LAYOUT)
D = sum(SIZES)


def leaf_slices():
    out, at = [], 0
    for (name, _), n in zip(LAYOUT, SIZES):
        out.append((name, at, at + n))
        at += n
    return out


def unflatten(flat):
    return {name: flat[a:b].reshape(shape)
            for (name, shape), (_, a, b) in zip(LAYOUT, leaf_slices())}


# ---------------------------------------------------------------- weights

@partial(jax.jit, static_argnums=1)
def _make_weights(key, _d):
    parts = []
    for i, (name, shape) in enumerate(LAYOUT):
        fan_in = int(np.prod(shape[:-1]))
        # He-normal convolutions, LeCun-normal head (variance 2/fan_in, 1/fan_in)
        var = (1.0 if name == "head" else 2.0) / fan_in
        k = jax.random.fold_in(key, i)
        parts.append((jax.random.normal(k, shape, jnp.float32)
                      * math.sqrt(var)).reshape(-1))
    return jnp.concatenate(parts)


def make_weights(seed: int):
    """The flat float32 weight vector of ``seed``, made on the device in one
    jitted call."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return _make_weights(key, D)


# ------------------------------------------------------------------ model

def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale; the gradient passes
    straight through (it is not itself rounded to eight bits)."""
    x32 = jax.lax.stop_gradient(x).astype(jnp.float32)
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12)
    q = (x32 * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + (q - x32).astype(x.dtype)


def _operands(x, k, precision):
    if precision == "float32":
        return x.astype(jnp.float32), k.astype(jnp.float32)
    x, k = x.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    if precision == "fp8":
        x, k = _fp8(x), _fp8(k)
    return x, k


def _conv(x, k, precision):
    x, k = _operands(x, k, precision)
    return jax.nn.relu(jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision="highest" if precision == "float32" else None))


def _pool(x, n):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, n, n, 1),
                                 (1, n, n, 1), "VALID")


def logits_fn(p, images, precision):
    x = _conv(images, p["prep"], precision)
    x = _pool(_conv(x, p["layer1"], precision), 2)
    x = x + _conv(_conv(x, p["res1a"], precision), p["res1b"], precision)
    x = _pool(_conv(x, p["layer2"], precision), 2)
    x = _pool(_conv(x, p["layer3"], precision), 2)
    x = x + _conv(_conv(x, p["res3a"], precision), p["res3b"], precision)
    x = _pool(x, 4).reshape(x.shape[0], -1)
    x, k = _operands(x, p["head"], precision)
    out = jnp.dot(x, k, precision="highest" if precision == "float32"
                  else None)
    return out.astype(jnp.float32) * 0.125


def _loss_sum(flat, images, labels, mask, precision):
    logits = logits_fn(unflatten(flat), images, precision)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return jnp.sum(nll * mask)


@partial(jax.jit, static_argnums=4)
def _block_grad(flat, images, labels, mask, precision):
    return jax.value_and_grad(_loss_sum)(flat, images, labels, mask,
                                         precision)


def mean_loss_and_grad(flat, images, labels, mask, precision, block=500):
    """Mean loss and mean gradient over the rows with mask 1, accumulated
    over blocks of ``block`` rows (float32 sums)."""
    n = images.shape[0]
    loss = jnp.zeros((), jnp.float32)
    grad = jnp.zeros_like(flat)
    for a in range(0, n, block):
        l, g = _block_grad(flat, jnp.asarray(images[a:a + block]),
                           jnp.asarray(labels[a:a + block]),
                           jnp.asarray(mask[a:a + block]), precision)
        loss, grad = loss + l, grad + g
    total = jnp.maximum(jnp.sum(jnp.asarray(mask)), 1.0)
    return loss / total, grad / total


# ------------------------------------------------------------ CountSketch

def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


class Sketch:
    """(r, c_eff) CountSketch of length-d vectors; c_eff is c rounded up to
    a multiple of 128."""

    def __init__(self, d, c, r, seed=42):
        self.d, self.r = int(d), int(r)
        self.c_eff = -(-int(c) // LANES) * LANES
        self.nwindows = self.c_eff // LANES
        rng = np.random.RandomState(seed)
        self.coeffs = (rng.randint(1, 1 << 31, size=(r, 6))
                       .astype(np.uint32) * 2 + 1)
        self._key = (self.d, self.c_eff, self.r, int(seed))

    # equal sizes and seed, equal sketch: one compiled update per process
    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Sketch) and self._key == other._key

    def hashes(self, row, idx):
        """(signs f32, buckets i32) of coordinates ``idx`` in ``row``."""
        h1, h2, h3, h4, h5, h6 = (jnp.uint32(int(h))
                                  for h in self.coeffs[row])
        i = idx.astype(jnp.uint32)
        acc = h1 * i + h2
        acc = acc * i + h3
        acc = acc * i + h4
        signs = (1 - 2 * (_mix(acc) & jnp.uint32(1)).astype(jnp.int32)
                 ).astype(jnp.float32)
        blk = i // jnp.uint32(LANES)
        mb = _mix(h6 * blk + h5)
        base = mb % jnp.uint32(self.nwindows)
        lanemask = _mix(mb ^ h5) & jnp.uint32(LANES - 1)
        off = (i & jnp.uint32(LANES - 1)) ^ lanemask
        return signs, (base * jnp.uint32(LANES) + off).astype(jnp.int32)

    def sketch(self, values, idx):
        rows = []
        for row in range(self.r):
            signs, buckets = self.hashes(row, idx)
            rows.append(jnp.zeros((self.c_eff,), jnp.float32)
                        .at[buckets].add(signs * values))
        return jnp.stack(rows)

    def estimates(self, table):
        idx = jnp.arange(self.d, dtype=jnp.int32)
        per_row = []
        for row in range(self.r):
            signs, buckets = self.hashes(row, idx)
            per_row.append(table[row, buckets] * signs)
        return jnp.median(jnp.stack(per_row), axis=0)


# ---------------------------------------------------------- server update

@partial(jax.jit, static_argnums=(0, 1, 2))
def _sketch_update(sk, k, rho, g, v, e, w, lr):
    table = sk.sketch(g, jnp.arange(sk.d, dtype=jnp.int32))
    v = table + rho * v
    e = e + v
    est = sk.estimates(e)
    _, idx = jax.lax.top_k(est * est, k)
    vals = est[idx]
    support = sk.sketch(vals, idx) != 0
    e = jnp.where(support, 0.0, e)
    v = jnp.where(support, 0.0, v)
    update = jnp.zeros((sk.d,), jnp.float32).at[idx].set(vals)
    return v, e, w - lr * update


@partial(jax.jit, static_argnums=0)
def _momentum_update(rho, g, v, w, lr):
    v = g + rho * v
    return v, w - lr * v


def lr_at(round_idx, spec):
    t = round_idx / spec["rounds_per_epoch"]
    return float(np.interp(t, [0, spec["pivot_epoch"], spec["num_epochs"]],
                           [0, spec["lr_scale"], 0]))


def steps(w0, batches, spec, precision, fault=None):
    """Follow the first ``len(batches)`` rounds from ``w0``.

    ``batches``: per round ``(images (N,32,32,3) f32, labels (N,), mask
    (N,))`` on the host. ``spec``: mode, k, num_rows, num_cols,
    virtual_momentum, weight_decay, num_workers and the schedule's numbers.
    ``fault`` plants one of the faults ``correct`` must catch, for the
    calibration and the tests: ``half_batch`` (half of the rows left out, the
    mean over the rest), ``state_unchanged`` (the step returns its state).

    Returns ``{"loss": [..], "opt_after_1": array, "w": final weights,
    "grad1_leaf_norms": [..]}``;
    ``opt_after_1`` is the momentum state after the first step — the first
    gradient as the optimizer got it (sketch mode: its masked table)."""
    w = jnp.asarray(w0, jnp.float32)
    sketched = spec["mode"] == "sketch"
    if sketched:
        sk = Sketch(D, spec["num_cols"], spec["num_rows"])
        v = jnp.zeros((sk.r, sk.c_eff), jnp.float32)
        e = jnp.zeros_like(v)
    else:
        v = jnp.zeros((D,), jnp.float32)
    rho = float(spec["virtual_momentum"])
    losses, opt1 = [], None
    for i, (images, labels, mask) in enumerate(batches):
        if fault == "half_batch":
            mask = np.array(mask, np.float32)
            mask[len(mask) // 2:] = 0.0
        loss, g = mean_loss_and_grad(w, images, labels, mask, precision)
        g = g + (spec["weight_decay"] / spec["num_workers"]) * w
        losses.append(float(loss))
        if i == 0:
            gn = [float(jnp.linalg.norm(g[a:b])) for _, a, b in leaf_slices()]
        lr = jnp.float32(lr_at(i, spec))
        if fault != "state_unchanged":
            if sketched:
                v, e, w = _sketch_update(sk, int(spec["k"]), rho, g, v, e,
                                         w, lr)
            else:
                v, w = _momentum_update(rho, g, v, w, lr)
        if i == 0:
            opt1 = np.asarray(v)
    return {"loss": losses, "opt_after_1": opt1, "w": np.asarray(w),
            "grad1_leaf_norms": gn}


# ----------------------------------------------- operation and byte counts

def flops_per_sample(image_hw=32):
    """Multiply-adds x 2 that one image's forward and backward pass need:
    every 3x3 tap that falls inside the image (zero padding is no work),
    forward, gradient to the kernel, and gradient to the input (which the
    first convolution does not need). Pooling, ReLU and the loss are left
    out; so are the sketch, the top-k and the server update."""
    def taps(h):            # in-image taps along one axis, summed over h
        return 3 * h - 2

    convs = [("prep", 32), ("layer1", 32), ("res1a", 16), ("res1b", 16),
             ("layer2", 16), ("layer3", 8), ("res3a", 4), ("res3b", 4)]
    shapes = dict(LAYOUT)
    total = 0
    for name, h in convs:
        h = h * image_hw // 32
        _, _, cin, cout = shapes[name]
        fwd = 2 * taps(h) * taps(h) * cin * cout
        total += fwd * (2 if name == "prep" else 3)
    total += 3 * 2 * 512 * 10
    return total


def kernel_bytes(kind, spec):
    """Bytes the algorithm has to move for one call, from d, r, c, k only.

    ``sketch``: read the d-long float32 gradient, write the r x c_eff table.
    ``server_topk``: read the table, stream the d-long estimate once (it is
    produced and consumed, 4 bytes a coordinate, not stored), write k values
    and k indices."""
    c_eff = -(-int(spec["num_cols"]) // LANES) * LANES
    table = 4 * int(spec["num_rows"]) * c_eff
    if kind == "sketch":
        return 4 * D + table
    if kind == "server_topk":
        return table + 4 * D + 8 * int(spec["k"])
    raise KeyError(kind)
