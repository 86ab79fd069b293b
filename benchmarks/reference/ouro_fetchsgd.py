"""Plain reference: a looped language model's federated rounds under FetchSGD.

Written from the published description (``model_type: ouro``; Ouro-2.6B's
``config.json``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), in straightforward ``jax.numpy``; it imports nothing of
``commefficient_tpu`` and takes nothing the program made. What it follows:

* A layer, "sandwich" norms (four RMSNorms, each ``x * rsqrt(mean(x^2) +
  eps) * g``):  ``a = Attn(N1(x))``, ``x <- x + N2(a)``;
  ``m = W_down(SiLU(W_gate u) * W_up u)`` with ``u = N3(x)``,
  ``x <- x + N4(m)``.
  ``Attn(u)``: ``q, k, v = W_q u, W_k u, W_v u`` in heads; rotary positions
  over the whole head on ``q`` and ``k`` (``inv_freq_i = theta^(-2i/D)``, the
  half-split form ``x cos + rotate_half(x) sin``, ``rotate_half(x) = [-x2,
  x1]``); the full causal masked softmax of ``q k^T / sqrt(D)``; ``W_o``. No
  bias anywhere.
* The model, the loop written as a Python loop: ``h = Embed(ids)``; for
  ``t`` = 1..``total_ut_steps``: ``h <- L_N(... L_1(h))`` through the same N
  layers with the same weights, ``h^t = N_f(h)`` (one final norm, shared),
  and ``h^t`` goes into step ``t + 1``; ``logits_t = W_head h^t`` (one
  untied head, shared); exit gate ``lambda_t = sigmoid(w_g . h^t + b_g)`` a
  token.
* The loss of a labelled token: ``p_1 = lambda_1``, ``p_t = lambda_t
  prod_{j<t} (1 - lambda_j)``, the last exit takes what is left;
  ``l = sum_t p_t CE(logits_t, y) - beta H(p)``, ``H(p) = -sum_t p_t log
  p_t``; a sequence's loss is the mean over its labelled positions, a
  round's the mean over its sequences.
* One federated round, ``sketch`` mode (FetchSGD), and the learning rate of
  ``training/gpt2.py``: the CountSketch, ``sketch_update`` and ``lr_at`` of
  ``nemotron_h_fetchsgd.py``, loaded from that file by its path, as are its
  roundings of a matrix product's operands (``precision`` ``float32``:
  every product at ``highest``; ``bfloat16``: operands rounded, float32
  accumulation, as the configuration computes; ``fp8``: the control).

The model's sizes are module state: ``configure(model)`` takes the ``model``
group of a benchmark configuration. Gradients are accumulated a sequence at
a time, each layer application and each exit's head rematerialised, so that
the followed rounds fit on the chip beside the d-long vectors.
"""

from __future__ import annotations

import importlib.util
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"ouro_ref_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_fetchsgd = _sibling("nemotron_h_fetchsgd")
Sketch, sketch_update, lr_at = (_fetchsgd.Sketch, _fetchsgd.sketch_update,
                                _fetchsgd.lr_at)
_mm = _fetchsgd._mm
LANES = _fetchsgd.LANES

SEQ_BLOCK = 1          # sequences a gradient block

#: the benchmark's cut of Ouro-2.6B (see configs/ouro-2.6b.json, which
#: carries the same numbers)
DEFAULT_MODEL = {
    "layers_held": 8, "hidden_size": 2048, "vocab_rows": 49152,
    "rms_norm_eps": 1e-6, "num_attention_heads": 16,
    "num_key_value_heads": 16, "head_dim": 128, "intermediate_size": 5632,
    "rope_theta": 1000000.0, "total_ut_steps": 4, "entropy_beta": 0.05,
    "seq_len": 2048,
}

MODEL = {}
LAYOUT = ()      # ((name, shape), ...) in the order the flat vector holds them
SIZES = ()
D = 0
_SLICES = []     # one list object for the module's life: see leaf_slices


def configure(model=None):
    """Set the model's sizes (module state) from a configuration's ``model``
    group; the flat vector holds the leaves sorted by path, as a pytree of
    nested dicts flattens."""
    global MODEL, LAYOUT, SIZES, D
    MODEL = dict(DEFAULT_MODEL if model is None else
                 {k: model[k] for k in DEFAULT_MODEL})
    m = MODEL
    C, V, F = m["hidden_size"], m["vocab_rows"], m["intermediate_size"]
    Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    leaves = [(("embed", "embedding"), (V, C)),
              (("exit_gate", "bias"), (1,)),
              (("exit_gate", "kernel"), (C, 1)),
              (("lm_head_embedding",), (V, C)),
              (("loop", "final_norm", "scale"), (C,))]
    for i in range(m["layers_held"]):
        at = lambda *path: ("loop", f"layers_{i:02d}") + path   # noqa: E731
        leaves += [(at("attn", "k_proj", "kernel"), (C, Hkv, Dh)),
                   (at("attn", "o_proj", "kernel"), (Hq * Dh, C)),
                   (at("attn", "q_proj", "kernel"), (C, Hq, Dh)),
                   (at("attn", "v_proj", "kernel"), (C, Hkv, Dh)),
                   (at("mlp", "down_proj", "kernel"), (F, C)),
                   (at("mlp", "gate_proj", "kernel"), (C, F)),
                   (at("mlp", "up_proj", "kernel"), (C, F))]
        leaves += [(at(f"norm{n}", "scale"), (C,)) for n in (1, 2, 3, 4)]
    leaves.sort(key=lambda leaf: leaf[0])
    LAYOUT = tuple(("/".join(path), shape) for path, shape in leaves)
    SIZES = tuple(int(np.prod(s)) for _, s in LAYOUT)
    D = sum(SIZES)
    at, slices = 0, []
    for (name, _), n in zip(LAYOUT, SIZES):
        slices.append((name, at, at + n))
        at += n
    _SLICES[:] = slices


def leaf_slices():
    """[(name, start, end)]: the same list object whatever ``configure`` is
    called with later, refilled in place."""
    return _SLICES


def unflatten(flat):
    return {name: flat[a:b].reshape(shape)
            for (name, shape), (_, a, b) in zip(LAYOUT, _SLICES)}


# ---------------------------------------------------------------- weights

def _init_leaf(key, name, shape):
    """Matrices, embeddings and the gate's weight normal(0.02); norm scales
    one; the gate's bias zero (a gate at zero gives the exit distribution
    (1/2, 1/4, 1/8, 1/8))."""
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return jnp.ones(shape, jnp.float32)
    if last == "bias":
        return jnp.zeros(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * 0.02


@partial(jax.jit, static_argnums=1)
def _make_weights(key, layout):
    return jnp.concatenate([
        _init_leaf(jax.random.fold_in(key, i), name, shape).reshape(-1)
        for i, (name, shape) in enumerate(layout)])


def make_weights(seed: int):
    """The flat float32 weight vector of ``seed``, made on the device in one
    jitted call."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return _make_weights(key, LAYOUT)


# ------------------------------------------------------------------ model

def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + MODEL["rms_norm_eps"]) * scale


def rotary(x, positions):
    """Rotary positions over the whole head of x (b, T, H, D), half-split."""
    half = x.shape[-1] // 2
    inv_freq = MODEL["rope_theta"] ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return (x * jnp.cos(angle)
            + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angle))


def _attention(p, pre, u, precision):
    m = MODEL
    Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    b, T, _ = u.shape
    q = _mm("btc,chd->bthd", u, p[pre + "q_proj/kernel"], precision)
    k = _mm("btc,chd->bthd", u, p[pre + "k_proj/kernel"], precision)
    v = _mm("btc,chd->bthd", u, p[pre + "v_proj/kernel"], precision)
    q, k = rotary(q, jnp.arange(T)), rotary(k, jnp.arange(T))
    k, v = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (k, v))
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", a, v, precision)
    return _mm("btf,fc->btc", o.reshape(b, T, Hq * Dh),
               p[pre + "o_proj/kernel"], precision)


def _mlp(p, pre, u, precision):
    gate = _mm("btc,cf->btf", u, p[pre + "gate_proj/kernel"], precision)
    up = _mm("btc,cf->btf", u, p[pre + "up_proj/kernel"], precision)
    return _mm("btf,fc->btc", jax.nn.silu(gate) * up,
               p[pre + "down_proj/kernel"], precision)


@partial(jax.checkpoint, static_argnums=(2, 3))
def layer(p, x, i, precision):
    """One application of layer ``i``: x (b, T, C) -> (b, T, C)."""
    pre = f"loop/layers_{i:02d}/"
    a = _attention(p, pre + "attn/", _rms(x, p[pre + "norm1/scale"]),
                   precision)
    x = x + _rms(a, p[pre + "norm2/scale"])
    mlp = _mlp(p, pre + "mlp/", _rms(x, p[pre + "norm3/scale"]), precision)
    return x + _rms(mlp, p[pre + "norm4/scale"])


def exit_states(p, tokens, precision, steps=None):
    """[h^1, .., h^steps]: the normed hidden state (b, T, C) of every exit."""
    h = p["embed/embedding"][tokens]
    out = []
    for _ in range(MODEL["total_ut_steps"] if steps is None else steps):
        for i in range(MODEL["layers_held"]):
            h = layer(p, h, i, precision)
        h = _rms(h, p["loop/final_norm/scale"])
        out.append(h)
    return out


def exit_gates(p, states):
    """lambda_t (b, T) of every exit, float32."""
    return [jax.nn.sigmoid(
        jnp.einsum("btc,co->bto", h, p["exit_gate/kernel"],
                   precision="highest")[..., 0] + p["exit_gate/bias"][0])
        for h in states]


def exit_distribution(gates):
    """(steps, ...) p_t of lambda_t: what is left after the last gate goes
    to the last exit, so that the p_t sum to 1."""
    left, out = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(out + [left])


def logits_fn(head, h, precision):
    """Logits (b, T, V) of an exit's hidden state under the head (V, C)."""
    return _mm("btc,vc->btv", h, head, precision)


@partial(jax.checkpoint, static_argnums=(4,))
def _exit_nll(head, h, labels, valid, precision):
    logits = logits_fn(head, h, precision)
    return (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0])


def token_losses(flat, tokens, labels, precision, fault=None):
    """(loss of every token (b, T), exit distribution (steps, b, T))."""
    p = unflatten(flat)
    valid = labels >= 0
    steps = 1 if fault == "single_pass" else None
    states = exit_states(p, tokens, precision, steps)
    nll = jnp.stack([_exit_nll(p["lm_head_embedding"], h, labels, valid,
                               precision) for h in states])
    if fault == "single_pass":
        return nll[0], jnp.ones_like(nll)
    dist = exit_distribution(exit_gates(p, states))
    entropy = -jnp.sum(dist * jnp.log(jnp.maximum(dist, 1e-30)), axis=0)
    return (jnp.sum(dist * nll, axis=0) - MODEL["entropy_beta"] * entropy,
            dist)


def sequence_losses(flat, tokens, labels, precision, fault=None):
    """Mean loss of each sequence over its labelled positions (label -1:
    none)."""
    loss, _ = token_losses(flat, tokens, labels, precision, fault)
    valid = labels >= 0
    return (jnp.sum(jnp.where(valid, loss, 0.0), axis=-1)
            / jnp.maximum(jnp.sum(valid, axis=-1), 1))


def _loss_sum(flat, tokens, labels, mask, precision, fault):
    return jnp.sum(sequence_losses(flat, tokens, labels, precision, fault)
                   * mask)


@partial(jax.jit, static_argnums=(4, 5))
def _block_grad(flat, tokens, labels, mask, precision, fault):
    return jax.value_and_grad(_loss_sum)(flat, tokens, labels, mask,
                                         precision, fault)


def mean_loss_and_grad(flat, tokens, labels, mask, precision, fault=None,
                       block=SEQ_BLOCK):
    """Mean loss and mean gradient over the sequences with mask 1,
    accumulated over blocks of ``block`` sequences (float32 sums)."""
    loss = jnp.zeros((), jnp.float32)
    grad = jnp.zeros_like(flat)
    for a in range(0, tokens.shape[0], block):
        l, g = _block_grad(flat, jnp.asarray(tokens[a:a + block]),
                           jnp.asarray(labels[a:a + block]),
                           jnp.asarray(mask[a:a + block]), precision, fault)
        loss, grad = loss + l, grad + g
    total = jnp.maximum(jnp.sum(jnp.asarray(mask)), 1.0)
    return loss / total, grad / total


def steps(w0, batches, spec, precision, fault=None):
    """Follow the first ``len(batches)`` rounds from ``w0``.

    ``batches``: per round ``(tokens (n, T) int32, labels (n * T,) or (n, T)
    int32 — the next token, -1 where there is none —, mask (n,))`` on the
    host. ``spec``: mode (``sketch``), k, num_rows, num_cols,
    virtual_momentum, weight_decay, num_workers, lr_scale, total_steps.
    ``fault`` plants one of the faults ``correct`` must catch:
    ``half_batch`` (half of the sequences left out, the mean over the rest),
    ``state_unchanged`` (the step returns its state), ``single_pass`` (the
    stack run once and one exit: the loop left out).

    Returns ``{"loss": [..], "opt_after_1": the masked momentum table after
    the first step, "w": final weights, "grad1_leaf_norms": [..]}``."""
    if spec["mode"] != "sketch":
        raise ValueError("this reference follows --mode sketch")
    w = jnp.asarray(w0, jnp.float32)
    sk = Sketch(D, spec["num_cols"], spec["num_rows"])
    v = jnp.zeros((sk.r, sk.c_eff), jnp.float32)
    e = jnp.zeros_like(v)
    rho = float(spec["virtual_momentum"])
    model_fault = fault if fault == "single_pass" else None
    losses, opt1 = [], None
    for i, (tokens, labels, mask) in enumerate(batches):
        tokens = np.asarray(tokens)
        labels = np.asarray(labels).reshape(tokens.shape)
        if fault == "half_batch":
            mask = np.array(mask, np.float32)
            mask[len(mask) // 2:] = 0.0
        loss, g = mean_loss_and_grad(w, tokens, labels, mask, precision,
                                     model_fault)
        g = g + (spec["weight_decay"] / spec["num_workers"]) * w
        losses.append(float(loss))
        if i == 0:
            gn = [float(jnp.linalg.norm(g[a:b])) for _, a, b in _SLICES]
        lr = jnp.float32(lr_at(i, spec))
        if fault != "state_unchanged":
            v, e, w = sketch_update(sk, int(spec["k"]), rho, g, v, e, w, lr)
        del g
        if i == 0:
            opt1 = np.asarray(v)
    return {"loss": losses, "opt_after_1": opt1, "w": np.asarray(w),
            "grad1_leaf_norms": gn}


# ----------------------------------------------- operation and byte counts

def flops_per_sample():
    """Multiply-adds x 2 that one sequence of ``seq_len`` tokens needs,
    forward and backward (3 x forward): every loop step through the held
    layers (the four attention projections, the gated MLP's three matrices,
    causal attention: a query reads half the positions), every exit's head
    and gate. Norms, rotary, activations, the loss, the sketch, the top-k
    and the server update are left out; so is recomputation."""
    m = MODEL
    C, T, V, F = (m["hidden_size"], m["seq_len"], m["vocab_rows"],
                  m["intermediate_size"])
    Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    a_layer = (2 * C * Dh * (Hq + 2 * Hkv) + 2 * Hq * Dh * C
               + 2 * Hq * Dh * T + 6 * C * F)
    forward = m["total_ut_steps"] * (m["layers_held"] * a_layer
                                     + 2 * C * V + 2 * C)
    return 3 * forward * T


def kernel_bytes(kind, spec):
    """Bytes the algorithm has to move for one call, from d, r, c, k only.

    ``sketch``: read the d-long float32 gradient, write the r x c_eff table.
    ``server_topk``: read the table, stream the d-long estimate once, write
    k values and k indices."""
    c_eff = -(-int(spec["num_cols"]) // LANES) * LANES
    table = 4 * int(spec["num_rows"]) * c_eff
    if kind == "sketch":
        return 4 * D + table
    if kind == "server_topk":
        return table + 4 * D + 8 * int(spec["k"])
    raise KeyError(kind)


configure()
