"""Rotary position embedding on the training path, in float32.

The half-split form (``rotate_half``): the head's D channels are two halves
``[x1 | x2]``; pair ``i`` (channels ``i`` and ``i + D/2``) turns by the angle
``position * theta^(-2i/D)``:

    rotary(x) = x * cos + [-x2 | x1] * sin

which is the complex product ``(x1 + i x2) * exp(i * angle)`` laid out as
``[real | imaginary]`` (``tests/test_ouro.py`` checks it against that form up
to position 4096). Applied to queries and keys before the scores, so a score
depends on the positions' difference alone.
"""

from __future__ import annotations

import jax.numpy as jnp


def rotary_angles(positions, head_dim: int, theta: float):
    """(cos, sin), each (T, head_dim) float32, of integer ``positions`` (T,):
    the D/2 angles of a position, repeated over both halves."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def apply_rotary(x, cos, sin):
    """x (B, T, H, D) turned by the angles of ``rotary_angles``; float32 in
    and out whatever x's dtype."""
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]
