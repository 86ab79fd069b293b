"""Plain reference: NemotronH federated rounds under FetchSGD.

Written from the published descriptions, in straightforward ``jax.numpy``;
it imports nothing of ``commefficient_tpu`` and takes nothing the program
made (no weights, hashes or tables). What it follows:

* The model (``model_type: nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's
  ``config.json``). Every layer is one mixer behind a pre-norm residual,
  ``x <- x + mixer(RMSNorm(x))``, by a pattern string; then a final RMSNorm
  and an untied head.
  ``M`` Mamba-2: ``[z | xBC | dt] = in_proj(u)``, ``xBC <- SiLU(causal
  depthwise conv1d(xBC))`` split into ``x`` (heads x head_dim), ``B``, ``C``
  (groups x state; a group serves heads / groups heads), ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, and the recurrence *as the recurrence*, a
  ``lax.scan`` over time:  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = h_t C_t + D x_t``;  ``y <- RMSNorm_grouped(y * SiLU(z))``,
  ``out_proj``.
  ``E`` sparse experts: ``s = sigmoid(W_r u)`` over all routed experts, the
  ``top_k`` largest of ``s + b`` chosen (``b`` chooses, ``s`` weighs), the
  chosen ``s`` renormalised to sum 1 and scaled by ``routed_scaling_factor``;
  an expert is ``W_down relu(W_up u)^2``; one shared expert of the same form
  is added for every token. Only the experts in ``experts_held`` are
  computed here — a loop over them, each over every token under a mask —
  and what the absent experts would add is left out, as on the chip that
  holds this share.
  ``*`` attention: query heads over fewer key/value heads, causal softmax,
  no bias, no rotary.
* Loss: mean next-token cross-entropy over the held rows of the vocabulary,
  a sequence at a time; a round's loss is the mean over its sequences.
* One federated round, ``sketch`` mode (FetchSGD, Rothchild et al. 2020,
  Alg. 1 with the reference implementation's momentum masking), exactly as
  ``resnet9_fetchsgd.py`` follows it, with the same documented CountSketch
  (copied from there), here a block of coordinates at a time so that d of
  several 1e8 fits beside the weights: S <- CountSketch(g); v <- S + rho v;
  e <- e + v; u <- top-k by magnitude of unsketch(e); zero v and e wherever
  sketch(u) is non-zero; w <- w - lr u.
* The learning rate of ``training/gpt2.py``: linear from ``lr_scale`` to 0
  over ``total_steps``.

The model's sizes are module state: ``configure(model)`` takes the ``model``
group of a benchmark configuration (the entry adapter calls it before
anything else); the default is the benchmark's cut of the published model.

Departures, each deliberate: ``precision`` ``bfloat16`` rounds the operands
of every matrix product (and what enters the recurrence: ``dt x``, ``B``,
``C``) to bfloat16 and accumulates in float32, as the configuration
computes, because ``correct``'s control is the next precision *below* the
stated one; ``float32`` runs every product at ``highest``; ``fp8`` (the
control) rounds the same operands to float8_e4m3 under a per-tensor scale.
Gradients are accumulated over blocks of sequences, each layer
rematerialised and the scan checkpointed every ``SEGMENT`` steps, so that
the followed rounds fit on the chip.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
SEGMENT = 128          # steps of the recurrence between checkpoints
COORD_BLOCK = 1 << 25  # coordinates sketched / estimated at a time
SEQ_BLOCK = 2          # sequences a gradient block

#: the benchmark's cut of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (see
#: configs/nemotron3-nano-30b-a3b.json, which carries the same numbers)
DEFAULT_MODEL = {
    "pattern": "EMEMEMEM*", "hidden_size": 2688, "vocab_rows": 16384,
    "norm_eps": 1e-5, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 128, "experts_held": [0, 1, 2, 3, 4, 5, 6, 7],
    "num_experts_per_tok": 6, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "seq_len": 2048,
}

MODEL = {}
LAYOUT = ()      # ((name, shape), ...) in the order the flat vector holds them
SIZES = ()
D = 0
_SLICES = []     # one list object for the module's life: see leaf_slices


def _layer_leaves(i, kind, m):
    C = m["hidden_size"]
    pre = f"layers_{i:02d}"
    out = [((pre, "norm", "scale"), (C,))]
    mix = lambda *path: (pre, "mixer") + path            # noqa: E731
    if kind == "M":
        H, G, N = m["mamba_num_heads"], m["n_groups"], m["ssm_state_size"]
        di = H * m["mamba_head_dim"]
        conv = di + 2 * G * N
        out += [(mix("A_log"), (H,)), (mix("D"), (H,)),
                (mix("conv_bias"), (conv,)),
                (mix("conv_kernel"), (m["conv_kernel"], conv)),
                (mix("dt_bias"), (H,)),
                (mix("in_proj", "kernel"), (C, 2 * di + 2 * G * N + H)),
                (mix("norm", "scale"), (di,)),
                (mix("out_proj", "kernel"), (di, C))]
    elif kind == "E":
        held, F = len(m["experts_held"]), m["moe_intermediate_size"]
        Fs = m["moe_shared_expert_intermediate_size"]
        out += [(mix("moe_w1"), (held, C, F)), (mix("moe_w2"), (held, F, C)),
                (mix("router", "kernel"), (C, m["n_routed_experts"])),
                (mix("shared_down", "kernel"), (Fs, C)),
                (mix("shared_up", "kernel"), (C, Fs))]
    elif kind == "*":
        Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                       m["head_dim"])
        out += [(mix("k_proj", "kernel"), (C, Hkv, Dh)),
                (mix("o_proj", "kernel"), (Hq * Dh, C)),
                (mix("q_proj", "kernel"), (C, Hq, Dh)),
                (mix("v_proj", "kernel"), (C, Hkv, Dh))]
    else:
        raise ValueError(f"layer kind {kind!r}: M, E or *")
    return out


def configure(model=None):
    """Set the model's sizes (module state) from a configuration's ``model``
    group; the flat vector holds the leaves sorted by path, as a pytree of
    nested dicts flattens."""
    global MODEL, LAYOUT, SIZES, D
    MODEL = dict(DEFAULT_MODEL if model is None else
                 {k: model[k] for k in DEFAULT_MODEL})
    m = MODEL
    leaves = [(("embed", "embedding"), (m["vocab_rows"], m["hidden_size"])),
              (("final_norm", "scale"), (m["hidden_size"],)),
              (("lm_head_embedding",), (m["vocab_rows"], m["hidden_size"]))]
    for i, kind in enumerate(m["pattern"]):
        leaves += _layer_leaves(i, kind, m)
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
    """Matrices and embeddings normal(0.02); norm scales and ``D`` one;
    ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly in [time_step_min, time_step_max], floored;
    convolution kernel normal(0.5), bias zero."""
    m, last = MODEL, name.rsplit("/", 1)[-1]
    if last == "scale" or last == "D":
        return jnp.ones(shape, jnp.float32)
    if last == "conv_bias":
        return jnp.zeros(shape, jnp.float32)
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if last == "dt_bias":
        lo, hi = math.log(m["time_step_min"]), math.log(m["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo)
        dt = jnp.maximum(dt, m["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    std = 0.5 if last == "conv_kernel" else 0.02
    return jax.random.normal(key, shape, jnp.float32) * std


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

def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale; the gradient passes
    straight through (it is not itself rounded to eight bits)."""
    x32 = jax.lax.stop_gradient(x).astype(jnp.float32)
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12)
    q = (x32 * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + (q - x32).astype(x.dtype)


def _operand(x, precision):
    """An operand of a matrix product, as ``precision`` holds it."""
    if precision == "float32":
        return x.astype(jnp.float32)
    x = x.astype(jnp.bfloat16)
    return _fp8(x) if precision == "fp8" else x


def _mm(eq, a, b, precision):
    return jnp.einsum(
        eq, _operand(a, precision), _operand(b, precision),
        preferred_element_type=jnp.float32,
        precision="highest" if precision == "float32" else None)


def _rms(x, scale, groups=1):
    g = x.reshape(x.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + MODEL["norm_eps"])
    return g.reshape(x.shape) * scale


def selective_scan(x, dt, A, B, C, D, precision="float32"):
    """The recurrence over time. x (b, T, H, P); dt (b, T, H) after
    softplus; A, D (H,); B, C (b, T, G, N). Float32 state; what enters it
    (dt x, B, C) rounded as ``precision`` holds operands. Checkpointed every
    ``SEGMENT`` steps where T divides by it."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    f32 = jnp.float32
    per_head = lambda m: jnp.repeat(                      # noqa: E731
        _operand(m, precision).astype(f32), H // G, axis=2)
    xs = (_operand(x * dt[..., None], precision).astype(f32),
          jnp.exp(dt * A), per_head(B), per_head(C))

    def step(h, s):
        dtx_t, decay_t, b_t, c_t = s
        h = (h * decay_t[..., None, None]
             + dtx_t[..., None] * b_t[:, :, None, :])
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(h, seg):
        return jax.lax.scan(step, h, seg)

    seg = SEGMENT if T % SEGMENT == 0 else T
    xs = tuple(m.swapaxes(0, 1).reshape((T // seg, seg) + (b,) + m.shape[2:])
               for m in xs)
    _, y = jax.lax.scan(segment, jnp.zeros((b, H, P, N), f32), xs)
    return y.reshape((T, b, H, P)).swapaxes(0, 1) + x * D[:, None]


def _mamba(p, pre, u, precision):
    m = MODEL
    H, P, G, N = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state_size"])
    di, K = H * P, m["conv_kernel"]
    b, T, _ = u.shape
    zxbcdt = _mm("btc,cf->btf", u, p[pre + "in_proj/kernel"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    past = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    w = p[pre + "conv_kernel"]
    xbc = jax.nn.silu(p[pre + "conv_bias"]
                      + sum(past[:, i:i + T] * w[i] for i in range(K)))
    x, B, C = jnp.split(xbc, [di, di + G * N], axis=-1)
    y = selective_scan(
        x.reshape(b, T, H, P), jax.nn.softplus(dt + p[pre + "dt_bias"]),
        -jnp.exp(p[pre + "A_log"]), B.reshape(b, T, G, N),
        C.reshape(b, T, G, N), p[pre + "D"], precision)
    y = _rms(y.reshape(b, T, di) * jax.nn.silu(z), p[pre + "norm/scale"], G)
    return _mm("btf,fc->btc", y, p[pre + "out_proj/kernel"], precision)


def route(scores, bias):
    """(expert ids (n, k), gates (n, k)) of router scores (n, E)."""
    m = MODEL
    _, idx = jax.lax.top_k(scores + bias, m["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if m["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * m["routed_scaling_factor"]


def _expert(u, up, down, precision):
    h = jnp.square(jax.nn.relu(_mm("nc,cf->nf", u, up, precision)))
    return _mm("nf,fc->nc", h, down, precision)


def _experts(p, pre, u, precision, fault=None, score_bias=None,
             held=None, shared=True):
    """The part of the layer's result that the experts in ``held`` (ids; the
    configuration's by default) give, plus the shared expert."""
    m = MODEL
    held = m["experts_held"] if held is None else held
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(jnp.dot(u, p[pre + "router/kernel"],
                                    precision="highest"))
    bias = (jnp.zeros((m["n_routed_experts"],), jnp.float32)
            if score_bias is None else score_bias)
    idx, gates = route(scores, bias)
    out = jnp.zeros_like(u)
    slots = list(enumerate(held))
    if fault == "missing_expert":
        slots = slots[:-1]
    for slot, e in slots:
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _expert(
            u, p[pre + "moe_w1"][slot], p[pre + "moe_w2"][slot], precision)
    if shared:
        out = out + _expert(u, p[pre + "shared_up/kernel"],
                            p[pre + "shared_down/kernel"], precision)
    return out.reshape(shape)


def _attention(p, pre, u, precision):
    m = MODEL
    Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    b, T, _ = u.shape
    q = _mm("btc,chd->bthd", u, p[pre + "q_proj/kernel"], precision)
    k = _mm("btc,chd->bthd", u, p[pre + "k_proj/kernel"], precision)
    v = _mm("btc,chd->bthd", u, p[pre + "v_proj/kernel"], precision)
    k, v = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (k, v))
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", a, v, precision)
    return _mm("btf,fc->btc", o.reshape(b, T, Hq * Dh),
               p[pre + "o_proj/kernel"], precision)


def hidden_states(p, tokens, precision, fault=None):
    """Final hidden states (b, T, C) of token ids (b, T)."""
    x = p["embed/embedding"][tokens]
    for i, kind in enumerate(MODEL["pattern"]):
        pre = f"layers_{i:02d}/mixer/"

        @jax.checkpoint
        def block(x, p=p, pre=pre, kind=kind, i=i):
            u = _rms(x, p[f"layers_{i:02d}/norm/scale"])
            if kind == "M":
                return x + _mamba(p, pre, u, precision)
            if kind == "E":
                return x + _experts(p, pre, u, precision, fault)
            return x + _attention(p, pre, u, precision)

        x = block(x)
    return _rms(x, p["final_norm/scale"])


def logits_fn(p, tokens, precision, fault=None):
    h = hidden_states(p, tokens, precision, fault)
    return _mm("btc,vc->btv", h, p["lm_head_embedding"], precision)


def sequence_losses(flat, tokens, labels, precision, fault=None):
    """Mean cross-entropy of each sequence over its labelled positions
    (label -1: none)."""
    logits = logits_fn(unflatten(flat), tokens, precision, fault)
    valid = labels >= 0
    nll = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0])
    return (jnp.sum(jnp.where(valid, nll, 0.0), axis=-1)
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


# ------------------------------------------------------------ CountSketch
# (the hash family of resnet9_fetchsgd.py, copied; there one call covers all
# of d, here a block of coordinates at a time)

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

    def estimates(self, table, idx):
        per_row = []
        for row in range(self.r):
            signs, buckets = self.hashes(row, idx)
            per_row.append(table[row, buckets] * signs)
        return jnp.median(jnp.stack(per_row), axis=0)


@partial(jax.jit, static_argnums=0)
def _sketch_block(sk, table, values, start):
    idx = start + jnp.arange(values.shape[0], dtype=jnp.int32)
    return table + sk.sketch(values, idx)


@partial(jax.jit, static_argnums=(0, 3, 4))
def _top_of_block(sk, table, start, n, k):
    """Estimates of coordinates [start, start + n) and the k largest of
    them by magnitude: (estimates, their squares' top k, global indices)."""
    idx = start + jnp.arange(n, dtype=jnp.int32)
    est = sk.estimates(table, idx)
    top, at = jax.lax.top_k(est * est, min(k, n))
    return est, top, at + start


@partial(jax.jit, static_argnums=(0, 1))
def _apply_update(sk, k, tops, ats, est, v, e, w, lr):
    _, which = jax.lax.top_k(tops, k)
    idx = ats[which]
    vals = est[idx]
    support = sk.sketch(vals, idx) != 0
    return (jnp.where(support, 0.0, v), jnp.where(support, 0.0, e),
            w.at[idx].add(-lr * vals))


def sketch_update(sk, k, rho, g, v, e, w, lr):
    """One FetchSGD server step; returns (v, e, w)."""
    table = jnp.zeros((sk.r, sk.c_eff), jnp.float32)
    for a in range(0, sk.d, COORD_BLOCK):
        table = _sketch_block(sk, table, g[a:a + COORD_BLOCK], jnp.int32(a))
    v = table + rho * v
    e = e + v
    ests, tops, ats = [], [], []
    for a in range(0, sk.d, COORD_BLOCK):
        est, top, at = _top_of_block(sk, e, jnp.int32(a),
                                     min(COORD_BLOCK, sk.d - a), k)
        ests.append(est), tops.append(top), ats.append(at)
    return _apply_update(sk, k, jnp.concatenate(tops), jnp.concatenate(ats),
                         jnp.concatenate(ests), v, e, w, lr)


def lr_at(round_idx, spec):
    return float(np.interp(round_idx, [0, spec["total_steps"]],
                           [spec["lr_scale"], 0]))


def steps(w0, batches, spec, precision, fault=None):
    """Follow the first ``len(batches)`` rounds from ``w0``.

    ``batches``: per round ``(tokens (n, T) int32, labels (n * T,) or (n, T)
    int32 — the next token, -1 where there is none —, mask (n,))`` on the
    host. ``spec``: mode (``sketch``), k, num_rows, num_cols,
    virtual_momentum, weight_decay, num_workers, lr_scale, total_steps.
    ``fault`` plants one of the faults ``correct`` must catch:
    ``half_batch`` (half of the sequences left out, the mean over the rest),
    ``state_unchanged`` (the step returns its state), ``missing_expert``
    (the last held expert of every ``E`` layer left out).

    Returns ``{"loss": [..], "opt_after_1": the masked momentum table after
    the first step, "w": final weights, "grad1_leaf_norms": [..]}``."""
    if spec["mode"] != "sketch":
        raise ValueError("this reference follows --mode sketch")
    w = jnp.asarray(w0, jnp.float32)
    sk = Sketch(D, spec["num_cols"], spec["num_rows"])
    v = jnp.zeros((sk.r, sk.c_eff), jnp.float32)
    e = jnp.zeros_like(v)
    rho = float(spec["virtual_momentum"])
    model_fault = fault if fault == "missing_expert" else None
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
    forward and backward (3 x forward), of what this chip computes: the
    projections, the depthwise convolution, the recurrence in its recurrent
    form (a token and head: decay, dt x (x) B and the add over P x N, then
    the product with C: 5 P N), the router, the held experts at an even
    routing's share (top_k x held / routed assignments a token) and the
    shared expert, causal attention (a query reads half the positions), the
    head. Norms, activations, the loss, the sketch, the top-k and the server
    update are left out; so is recomputation."""
    m = MODEL
    C, T, V = m["hidden_size"], m["seq_len"], m["vocab_rows"]
    H, P, G, N = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state_size"])
    di = H * P
    mamba = (2 * C * (2 * di + 2 * G * N + H)
             + 2 * m["conv_kernel"] * (di + 2 * G * N)
             + 5 * H * P * N + 2 * di * C)
    share = (m["num_experts_per_tok"] * len(m["experts_held"])
             / m["n_routed_experts"])
    experts = (2 * C * m["n_routed_experts"]
               + share * 4 * C * m["moe_intermediate_size"]
               + 4 * C * m["moe_shared_expert_intermediate_size"])
    Hq, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    attention = (2 * C * Dh * (Hq + 2 * Hkv) + 2 * Hq * Dh * C
                 + 2 * Hq * Dh * T)
    per_token = {"M": mamba, "E": experts, "*": attention}
    forward = sum(per_token[kind] for kind in m["pattern"]) + 2 * C * V
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
