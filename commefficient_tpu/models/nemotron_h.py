"""NemotronH: a hybrid of Mamba-2, sparse-expert and grouped-query attention
layers (``model_type: nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B is the
published instance, https://huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json).

Depth is a pattern string with one character a layer; every layer is ONE
mixer behind a pre-norm residual, ``x <- x + mixer(RMSNorm(x))``:

``M``  Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC`` through a causal
       depthwise convolution and SiLU, split into ``x`` (heads x head_dim),
       ``B``, ``C`` (groups x state); ``dt = softplus(dt + dt_bias)``,
       ``A = -exp(A_log)``; the selective scan (``ops/ssd.py``, chunked);
       ``RMSNorm_grouped(y * SiLU(z))``; ``out_proj``.
``E``  sparse experts (``ops/moe.py``): sigmoid router over
       ``n_routed_experts``, top-k of score + correction bias, gates
       renormalised and scaled, squared-ReLU experts, one shared expert;
       this chip computes the experts in ``experts_held``.
``*``  attention: ``num_attention_heads`` query heads over
       ``num_key_value_heads`` key/value heads, causal, no bias, no rotary
       (the Mamba layers carry order).

Then a final RMSNorm and an untied head over ``vocab_rows`` rows of the
vocabulary (ids, logits and loss are over that slice). The cut keys are
``pattern``, ``experts_held`` and ``vocab_rows``; every width is the
configuration's. Parameters are float32; matrix products take
``compute_dtype`` operands; the router, ``dt``, ``A``, the scan's decays and
every norm are float32. Each layer is rematerialised (``nn.remat``), and its
parameters are cast inside it, so the low-precision copy lives a layer at a
time.

``__call__(ids)`` returns the final hidden states (B, T, C); the head is
``params['lm_head']['embedding']`` (V, C), applied by the loss through the
vocabulary-chunked ``ops/fused_ce.py`` or by ``logits``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from commefficient_tpu.ops.attention import grouped_query_attention
from commefficient_tpu.ops.moe import MoEFFN
from commefficient_tpu.ops.ssd import ssd_chunked
from commefficient_tpu.utils.tracing import layer

#: the published pattern (52 layers: 23 M, 23 E, 6 *)
NANO_30B_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Keys as the published ``config.json`` names them, plus the three that
    cut the model to a chip's share: ``pattern`` (the layers held),
    ``experts_held`` (ids of the routed experts held) and ``vocab_rows``."""
    pattern: str = NANO_30B_PATTERN
    hidden_size: int = 2688
    vocab_rows: int = 131072
    norm_eps: float = 1e-5
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # E
    n_routed_experts: int = 128
    experts_held: Optional[Tuple[int, ...]] = None
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    compute_dtype: str = "float32"
    remat: bool = True

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @classmethod
    def tiny(cls, **kw):
        """The CPU tests' size: every kind of layer, nothing published."""
        base = dict(pattern="EM*", hidden_size=64, vocab_rows=256,
                    mamba_num_heads=2, mamba_head_dim=16, n_groups=2,
                    ssm_state_size=16, chunk_size=8, n_routed_experts=8,
                    experts_held=(0, 1), num_experts_per_tok=6,
                    moe_intermediate_size=48,
                    moe_shared_expert_intermediate_size=96,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16)
        base.update(kw)
        return cls(**base)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        g = x32.reshape(x.shape[:-1] + (self.groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.eps)
        return g.reshape(x.shape) * scale


class Projection(nn.Module):
    """Bias-free projection of the last axis onto ``features`` (an int or a
    tuple of axes): ``dtype`` operands, float32 parameters and result."""
    features: Tuple[int, ...]
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (x.shape[-1],) + tuple(self.features),
                            jnp.float32)
        return jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dense(features, dtype, name):
    if isinstance(features, int):
        features = (features,)
    return Projection(tuple(features), dtype, name=name)


def _dt_bias_init(cfg):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    return init


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg, f32 = self.cfg, jnp.float32
        H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        di, K = cfg.d_inner, cfg.conv_kernel
        b, T, _ = u.shape
        with layer("ssm_proj"):
            zxbcdt = _dense(2 * di + 2 * G * N + H, cfg.jnp_dtype,
                            "in_proj")(u)
            z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
            w = self.param("conv_kernel", nn.initializers.normal(0.5),
                           (K, xbc.shape[-1]), f32)
            cb = self.param("conv_bias", nn.initializers.zeros,
                            (xbc.shape[-1],), f32)
            past = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            xbc = jax.nn.silu(cb + sum(past[:, i:i + T] * w[i]
                                       for i in range(K)))
            x, B, C = jnp.split(xbc, [di, di + G * N], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (H,), f32)
        A_log = self.param(
            "A_log", lambda k, s, d=f32: jnp.log(
                jax.random.uniform(k, s, d, 1.0, 16.0)), (H,), f32)
        D = self.param("D", nn.initializers.ones, (H,), f32)
        with layer("ssm_scan"):
            y = ssd_chunked(x.reshape(b, T, H, P),
                            jax.nn.softplus(dt + dt_bias), -jnp.exp(A_log),
                            B.reshape(b, T, G, N), C.reshape(b, T, G, N), D,
                            chunk=cfg.chunk_size,
                            compute_dtype=cfg.jnp_dtype)
        with layer("ssm_proj"):
            y = RMSNorm(cfg.norm_eps, groups=G, name="norm")(
                y.reshape(b, T, di) * jax.nn.silu(z))
            return _dense(cfg.hidden_size, cfg.jnp_dtype, "out_proj")(y)


class Attention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg, cd = self.cfg, self.cfg.jnp_dtype
        Hq, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        with layer("attn"):
            q, k, v = (_dense((h, Dh), cd, name)(u).astype(cd)
                       for h, name in ((Hq, "q_proj"), (Hkv, "k_proj"),
                                       (Hkv, "v_proj")))
            o = grouped_query_attention(q, k, v, causal=True)
            return _dense(cfg.hidden_size, cd, "o_proj")(
                o.reshape(u.shape[:2] + (Hq * Dh,)))


class Block(nn.Module):
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        u = RMSNorm(cfg.norm_eps, name="norm")(x)
        if self.kind == "M":
            y = Mamba2Mixer(cfg, name="mixer")(u)
        elif self.kind == "*":
            y = Attention(cfg, name="mixer")(u)
        elif self.kind == "E":
            y = MoEFFN(cfg.n_routed_experts, cfg.moe_intermediate_size,
                       experts_held=cfg.experts_held,
                       top_k=cfg.num_experts_per_tok, scoring="sigmoid",
                       norm_topk=cfg.norm_topk_prob,
                       routed_scale=cfg.routed_scaling_factor,
                       activation="relu2", use_bias=False,
                       shared_d_ff=cfg.moe_shared_expert_intermediate_size,
                       aux_loss=False, dtype=cfg.jnp_dtype,
                       name="mixer")(u)
        else:
            raise ValueError(f"layer kind {self.kind!r} in pattern "
                             f"{cfg.pattern!r}: M, E or *")
        return x + y.astype(x.dtype)


class NemotronH(nn.Module):
    config: NemotronHConfig

    #: the metric rows of the training loss, {counter of training/gpt2.py:
    #: key the expert layers sow a token under (``ops/moe.py``)}
    train_counters = {"moe.assignments_held": "moe_held",
                      "moe.assignments_fullest": "moe_fullest",
                      "moe.dropped": "moe_dropped"}

    @nn.compact
    def __call__(self, ids, train: bool = False):
        cfg = self.config
        block = nn.remat(Block) if cfg.remat else Block
        x = nn.Embed(cfg.vocab_rows, cfg.hidden_size, name="embed",
                     embedding_init=nn.initializers.normal(0.02))(ids)
        for i, kind in enumerate(cfg.pattern):
            x = block(cfg, kind, name=f"layers_{i:02d}")(x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        # the untied head: declared here, applied by the loss (fused) or by
        # ``logits``
        self.param("lm_head_embedding", nn.initializers.normal(0.02),
                   (cfg.vocab_rows, cfg.hidden_size), jnp.float32)
        return x


def logits(params, hidden, compute_dtype=jnp.float32):
    """(B, T, V) float32 logits of final hidden states."""
    with layer("lm_head"):
        return jnp.einsum("btc,vc->btv", hidden.astype(compute_dtype),
                          params["lm_head_embedding"].astype(compute_dtype),
                          preferred_element_type=jnp.float32)
