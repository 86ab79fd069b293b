"""Recompute-in-backward dropout — the HBM-traffic-free formulation.

The reference inherits torch's dropout, whose backward reads a saved
mask tensor. Under XLA the same pattern emerges from ``nn.Dropout``: the
keep-mask is a forward intermediate reused by the backward pass, so it is
materialized to HBM and read back — and the elementwise multiply around it
breaks producer/consumer fusions on both sides. Round 3 measured the
resulting tax on the federated GPT2 round at ~45 ms (docs/ROOFLINE.md:
PRNG choice and flash-vs-full attention were both ruled out as the cost).

``masked_dropout`` is a ``jax.custom_vjp`` whose only backward residual is
the PRNG key (32 bytes): the backward REGENERATES the keep-bits from the
key instead of loading a saved mask; both passes draw from the same key,
so forward and backward masks agree exactly. The forward becomes a pure
elementwise op XLA can fuse into the surrounding matmul epilogues.

What the round-4 on-chip probes established about the BIT-GENERATION cost
(the dominant term at the federated GPT2 round's shape, where the attention
probability masks alone are 604M draws per forward pass):

* threefry bernoulli ~16 ms/pass on-chip; rbg (hardware RngBitGenerator)
  bernoulli ~11 ms; rbg 16-bit threshold draws ~8 ms. The round pays two
  passes (forward + recompute backward), so switching the dropout
  collection to rbg+u16 (``FusedDropout(impl='xla_rbg')``) took the
  federated round 208 -> 185 ms. Saved-mask (no recompute) measured
  NEUTRAL vs recompute under rbg — the mask store/load round-trip costs
  what the regeneration does.
* a per-tensor Pallas kernel drawing bits with the TPU core PRNG
  (``hw_dropout`` below) generates ~8x faster than XLA standalone
  (0.9 vs 7.5 ms per attention-mask volume) but made the round 56 ms
  SLOWER in context: ~76 kernel launches per step, each an XLA fusion
  break. Kept for its on-device bit-exactness contracts and as the
  measured record of why the fusable-XLA path wins (docs/ROOFLINE.md).

Distributionally identical to ``flax.linen.Dropout`` (iid Bernoulli keep
with 1/keep_prob scaling); the realized mask differs only if flax changes
its bit-derivation. ``FusedDropout`` is the drop-in module replacement
(same ``deterministic`` semantics, same ``'dropout'`` rng collection).
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def _scaled_mask(key, rate: float, shape, dtype):
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) and \
            jax.random.key_impl(key) is not None and \
            "rbg" in str(jax.random.key_impl(key)):
        # rbg path (FusedDropout impl='xla_rbg'): threshold 16-bit draws
        # instead of bernoulli's 32-bit->f32 uniform compare — half the
        # generated bits, measured -14 ms on the federated GPT2 round.
        # Keep probability is quantized to 1/65536: round((1-rate)*2^16)
        # /2^16, e.g. 0.89999390 for rate 0.1 (|err| <= 7.7e-6) vs
        # bernoulli's own f32 granularity of 2^-24. The threshold draw is
        # cheaper precisely because it never converts bits to floats.
        thresh = int(round((1.0 - rate) * 65536.0))
        if 0 < thresh < 65536:
            keep = jax.random.bits(key, shape, dtype=jnp.uint16) \
                < jnp.uint16(thresh)
            return keep.astype(dtype) / (1.0 - rate)
    keep = jax.random.bernoulli(key, 1.0 - rate, shape)
    return keep.astype(dtype) / (1.0 - rate)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def masked_dropout(x, key, rate: float):
    """x * Bernoulli(1-rate)/(1-rate); backward stores only ``key``."""
    return x * _scaled_mask(key, rate, x.shape, x.dtype)


def _fwd(x, key, rate: float):
    return masked_dropout(x, key, rate), key


def _bwd(rate: float, key, g):
    # same key -> same bits -> the exact forward mask, regenerated
    # (g has the output's shape/dtype, which is x's)
    return g * _scaled_mask(key, rate, g.shape, g.dtype), None


masked_dropout.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------
# Hardware-RNG Pallas path
#
# Even with the recompute formulation the XLA cost of dropout is dominated
# by BIT GENERATION, not HBM traffic: at the federated GPT2 round's shape the
# attention-probability masks alone are 604M draws per forward pass, and
# jax.random generation measures 22-31 ms per pass on-chip for every
# generator/width combination (threefry/rbg x f32/u8/u16 — round-4 probe;
# the recompute backward pays it again). The TPU's per-core hardware PRNG
# (pltpu.prng_random_bits) generates bits at vector-unit rate inside a
# kernel, so this path fuses generate+threshold+multiply into one
# elementwise Pallas op whose cost is just the HBM stream of x itself.
#
# Semantics: keep = (bits >= rate * 2^32), i.e. P(keep) = 1 - rate exact to
# 2^-32 — *tighter* than jax.random.bernoulli's f32-uniform granularity of
# 2^-24. Forward and backward seed the PRNG identically (same seed scalars,
# same grid), so the regenerated backward mask is bit-identical to the
# forward mask — the same contract as masked_dropout above, asserted
# on-device in tests/test_dropout.py (the interpreter used by the CPU suite
# has no prng_seed lowering, so the kernel tests are TPU-gated).
#
# The realized mask differs from the XLA path's (different generator), but
# the distribution is identical; convergence/distribution tests cover both.
# Not vmap-safe (scalar-prefetch grid); call sites opt in the same way the
# CountSketch kernels do (countsketch._kernel_ok).
# --------------------------------------------------------------------------

_LANES = 1024          # flattened minor dim of the kernel view
_BLOCK_ROWS = 256      # (256, 1024) f32 block = 1 MiB of VMEM per buffer


def _hw_kernel(seed_ref, x_ref, o_ref, *, threshold: int, inv_keep: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # distinct stream per grid block: same (seeds, block) pair in forward
    # and backward -> identical bits; distinct call sites differ in seeds.
    # (prng_seed takes at most two words, so the block index is mixed into
    # the first with an odd multiplicative constant)
    pid = pl.program_id(0)
    pltpu.prng_seed(seed_ref[0] + pid * jnp.int32(-1640531527),
                    seed_ref[1])
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    keep = bits >= jnp.uint32(threshold)
    scaled = x_ref[:].astype(jnp.float32) * inv_keep
    o_ref[:] = jnp.where(keep, scaled, 0.0).astype(o_ref.dtype)


def hw_dropout_supported(shape) -> bool:
    """The Pallas path handles any tensor whose element count folds into
    (rows, 1024) lanes; anything else falls back to masked_dropout."""
    n = int(np.prod(shape))
    return n >= _LANES and n % _LANES == 0


def _seeds_from_key(key) -> jax.Array:
    """Two int32 seed words from a JAX PRNG key (typed or raw uint32[2])."""
    data = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    flat = jnp.ravel(data).astype(jnp.uint32)
    # keys are >= 1 word; fold everything into two words so both threefry
    # (2 words) and rbg (4 words) keys map injectively enough
    w0 = flat[0]
    w1 = flat[-1] ^ jnp.uint32(0x9e3779b9) if flat.shape[0] > 1 \
        else jnp.uint32(0x9e3779b9)
    return jnp.stack([w0, w1]).astype(jnp.int32)


def _hw_apply(x, seeds, rate: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape, dtype = x.shape, x.dtype
    x2 = x.reshape(-1, _LANES)
    rows = x2.shape[0]
    grid = pl.cdiv(rows, _BLOCK_ROWS)
    threshold = min(int(round(rate * 2.0 ** 32)), 2 ** 32 - 1)
    out = pl.pallas_call(
        partial(_hw_kernel, threshold=threshold,
                inv_keep=1.0 / (1.0 - rate)),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, dtype),
    )(seeds, x2)
    return out.reshape(shape)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def hw_dropout(x, seeds, rate: float):
    """Hardware-RNG dropout: x * Bernoulli(1-rate)/(1-rate) with bits drawn
    by the TPU core PRNG inside a fused Pallas kernel. ``seeds`` is the
    (2,) int32 vector from ``_seeds_from_key``. Backward regenerates the
    identical mask (dropout is elementwise-linear in x, so applying the
    same masked scaling to the cotangent IS the VJP)."""
    return _hw_apply(x, seeds, rate)


def _hw_fwd(x, seeds, rate: float):
    return _hw_apply(x, seeds, rate), seeds


def _hw_bwd(rate: float, seeds, g):
    return _hw_apply(g, seeds, rate), None


hw_dropout.defvjp(_hw_fwd, _hw_bwd)


class FusedDropout(nn.Module):
    """Drop-in for ``nn.Dropout(rate)(x, deterministic=...)`` using the
    recompute-in-backward formulation above.

    ``impl='tpu_bits'`` swaps in the hardware-RNG Pallas kernel (same
    distribution, different realized bits; not vmap-safe — the GPT2 config
    plumbs this only into fused-round paths)."""

    rate: float
    impl: str = "xla"

    @nn.compact
    def __call__(self, x, deterministic: bool):
        if self.rate == 0.0 or deterministic:
            return x
        if self.rate == 1.0:
            # nn.Dropout's documented edge case: everything dropped, and
            # 0/(1-rate) would be 0/0 = NaN
            return jnp.zeros_like(x)
        key = self.make_rng("dropout")
        on_tpu = jax.default_backend() == "tpu"
        if self.impl == "tpu_bits" and hw_dropout_supported(x.shape) \
                and on_tpu:
            return hw_dropout(x, _seeds_from_key(key), self.rate)
        if self.impl == "xla_rbg" and on_tpu:
            # same recompute-in-backward masked_dropout, but drawing bits
            # with XLA's RngBitGenerator (TPU hardware RNG) instead of
            # threefry: ~2x cheaper generation at identical fusion
            # behavior (the threefry hash is pure VPU arithmetic and
            # dominates the dropout tax — round-4 probes). The threefry
            # key's words seed the rbg key, so the flax rng-collection
            # fold_in structure still decorrelates call sites.
            data = jnp.ravel(jax.random.key_data(key) if jnp.issubdtype(
                key.dtype, jax.dtypes.prng_key) else key).astype(jnp.uint32)
            k4 = jnp.concatenate([data, data ^ jnp.uint32(0x9e3779b9)])[:4]
            key = jax.random.wrap_key_data(k4, impl="rbg")
        return masked_dropout(x, key, self.rate)
