"""Ouro: a looped language model (``model_type: ouro``; Ouro-2.6B is the
published instance, https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/
config.json; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

ONE stack of decoder layers, applied ``total_ut_steps`` times with the same
weights; after every pass an exit: the shared final norm, the shared untied
head, and a learned gate that says how much of a token's loss is taken there.

A layer, "sandwich" norms (four RMSNorms)::

    a = Attn(N1(x));  x <- x + N2(a)
    m = W_down(SiLU(W_gate u) * W_up u), u = N3(x);  x <- x + N4(m)

``Attn``: full causal attention over ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads, rotary positions over the whole
head (``ops/rotary.py``), no bias. The model::

    h = Embed(ids)
    for t in 1..total_ut_steps:  h <- L_N(.. L_1(h));  h^t = N_f(h);  h <- h^t
    logits_t = W_head h^t;   lambda_t = sigmoid(w_g . h^t + b_g)

and the training loss of a labelled token (``Ouro.exit_loss``)::

    p_1 = lambda_1,  p_t = lambda_t prod_{j<t} (1 - lambda_j),  p_last = what is left
    l = sum_t p_t CE(logits_t, y) - entropy_beta * H(p)

The loop is a ``scan`` over the steps with the parameters broadcast: d
counts every leaf once, the compiled program holds the stack once, and a
leaf's gradient is the sum over the steps. ``layers_held`` is the one cut key
(the layers of the stack held here: a pipeline stage); every width is the
configuration's. Parameters are float32; matrix products take
``compute_dtype`` operands and accumulate in float32; norms, rotary angles,
the gate, the exit distribution and the loss are float32.

Rematerialisation has two levels to choose from (``remat``): ``"layer"``
saves every layer application's input (steps x layers of them); ``"step"``
saves the steps' inputs, and inside the step being differentiated the layers'
inputs (steps + layers of them), at one more forward pass. At the
benchmark's cut (8 layers, 16 384 tokens) both fit one v5e chip, ``"layer"``
in 16.6 GB of its 16.9 and a tenth faster, ``"step"`` in 12.6 GB (PERF.md
section 4): ``"layer"`` is the default, ``"step"`` is for a longer batch or a
deeper stage.

``__call__(ids)`` returns ``(h (steps, B, T, C), lambda (steps, B, T))``;
the head is ``params['lm_head_embedding']`` (V, C), applied by the loss
(``federated/losses.py::make_lm_loss``) an exit at a time through the
vocabulary-chunked ``ops/fused_ce.py``, or by ``logits``.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from commefficient_tpu.models.nemotron_h import (RMSNorm, _dense,  # noqa: F401
                                                 logits)
from commefficient_tpu.ops.attention import grouped_query_attention
from commefficient_tpu.ops.rotary import apply_rotary, rotary_angles
from commefficient_tpu.utils.tracing import layer


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Keys as the published ``config.json`` names them; ``layers_held`` is
    its ``num_hidden_layers``, the one key a cut changes. ``entropy_beta``
    is the weight of the exit distribution's entropy in the training loss
    (not in the config: the benchmark configuration lists it as assumed)."""
    layers_held: int = 48
    hidden_size: int = 2048
    vocab_size: int = 49152
    rms_norm_eps: float = 1e-6
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    entropy_beta: float = 0.05
    compute_dtype: str = "float32"
    remat: str = "layer"         # "layer", or "step" (both levels)

    def __post_init__(self):
        if self.remat not in ("step", "layer"):
            raise ValueError(f"remat {self.remat!r}: step or layer")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def vocab_rows(self):
        return self.vocab_size

    @classmethod
    def tiny(cls, **kw):
        """The CPU tests' size; nothing published."""
        base = dict(layers_held=3, hidden_size=64, vocab_size=256,
                    num_attention_heads=2, num_key_value_heads=2,
                    head_dim=32, intermediate_size=176)
        base.update(kw)
        return cls(**base)


class Attention(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, u, cos, sin):
        cfg, cd = self.cfg, self.cfg.jnp_dtype
        Hq, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        q, k, v = (_dense((h, Dh), cd, name)(u)
                   for h, name in ((Hq, "q_proj"), (Hkv, "k_proj"),
                                   (Hkv, "v_proj")))
        o = grouped_query_attention(
            apply_rotary(q, cos, sin).astype(cd),
            apply_rotary(k, cos, sin).astype(cd), v.astype(cd), causal=True)
        return _dense(cfg.hidden_size, cd, "o_proj")(
            o.reshape(u.shape[:2] + (Hq * Dh,)))


class GatedMLP(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, u):
        cfg, cd = self.cfg, self.cfg.jnp_dtype
        gate = _dense(cfg.intermediate_size, cd, "gate_proj")(u)
        up = _dense(cfg.intermediate_size, cd, "up_proj")(u)
        return _dense(cfg.hidden_size, cd, "down_proj")(
            jax.nn.silu(gate) * up)


class Layer(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.cfg

        def norm(n):
            return RMSNorm(cfg.rms_norm_eps, name=f"norm{n}")

        with layer("attn"):
            a = Attention(cfg, name="attn")(norm(1)(x), cos, sin)
            x = x + norm(2)(a)
        with layer("mlp"):
            m = GatedMLP(cfg, name="mlp")(norm(3)(x))
            return x + norm(4)(m)


class LoopStep(nn.Module):
    """One pass through the held layers and the shared final norm: the body
    of the scan, ``h -> (h^t, h^t)``."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, h, cos, sin):
        cfg = self.cfg
        for i in range(cfg.layers_held):
            h = nn.remat(Layer)(cfg, name=f"layers_{i:02d}")(h, cos, sin)
        with layer("exit_gate"):
            h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(h)
        return h, h


class Ouro(nn.Module):
    config: OuroConfig

    #: the metric rows of the training loss, {counter of training/gpt2.py:
    #: key of what ``exit_loss`` returns}
    train_counters = {"loop.expected_steps": "loop_expected_steps",
                      "loop.tokens": "loop_tokens"}

    @nn.compact
    def __call__(self, ids):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed",
                     embedding_init=nn.initializers.normal(0.02))(ids)
        cos, sin = rotary_angles(jnp.arange(ids.shape[1]), cfg.head_dim,
                                 cfg.rope_theta)
        step = nn.remat(LoopStep) if cfg.remat == "step" else LoopStep
        loop = nn.scan(step, variable_broadcast="params",
                       split_rngs={"params": False},
                       in_axes=(nn.broadcast, nn.broadcast),
                       length=cfg.total_ut_steps)
        _, exits = loop(cfg, name="loop")(x, cos, sin)
        with layer("exit_gate"):
            gate = jax.nn.sigmoid(nn.Dense(
                1, name="exit_gate", precision=jax.lax.Precision.HIGHEST,
                kernel_init=nn.initializers.normal(0.02))(exits)[..., 0])
        # the untied head: declared here, applied by the loss (fused, an
        # exit at a time) or by ``logits`` (``models/nemotron_h.py``'s)
        self.param("lm_head_embedding", nn.initializers.normal(0.02),
                   (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        return exits, gate

    def exit_loss(self, nll, gate, valid):
        """The loss of every token from its cross-entropy at every exit
        ``nll`` (steps, B, T) and the gates ``gate`` (steps, B, T), and the
        metric rows of ``train_counters`` by sequence over the labelled
        positions ``valid`` (B, T): the expected exit step under the gate's
        distribution, summed, and the labelled tokens."""
        with layer("exit_gate"):
            p = exit_distribution(gate)
            entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
            loss = (jnp.sum(p * nll, axis=0)
                    - self.config.entropy_beta * entropy)
            depth = sum((t + 1.0) * p[t] for t in range(p.shape[0]))
            return loss, {
                "loop_expected_steps": jnp.sum(jnp.where(valid, depth, 0.0),
                                               axis=-1),
                "loop_tokens": jnp.sum(valid, axis=-1).astype(jnp.float32)}


def exit_distribution(gate):
    """p (steps, ...) of the gates lambda (steps, ...): ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)``, and the last exit takes what the gates
    before it left, so that the p_t sum to 1."""
    left = jnp.cumprod(1.0 - gate[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gate[:1]), left[:-1]], axis=0)
    return jnp.concatenate([gate[:-1] * before, left[-1:]], axis=0)
