"""State-space duality (Mamba-2): the selective scan in its chunked block form.

The layer's recurrence, a head at a time (state ``h`` of shape (P, N)):

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t @ C_t + D * x_t

is linear in ``h``, so a chunk of Q steps is three matrix products and the
state crosses chunk boundaries only (Dao & Gu 2024, "Transformers are SSMs",
section 6): inside a chunk ``y = (L * (C B^T)) (dt x)`` with the lower-
triangular decay ``L[i, j] = exp(sum_{j < s <= i} dt_s A)``; every chunk
leaves ``sum_j exp(sum_{s > j} dt_s A) dt_j x_j (outer) B_j``; a scan over the
chunks carries the one state between them; and ``C`` reads the state a
chunk started from. Backward is autodiff of this form (no custom VJP).

The decays (cumulative sums, ``exp``) and the carried state are float32;
the matrix products take ``compute_dtype`` operands and accumulate in
float32. The recurrence itself, which the tests hold this to, is the plain
reference's (``benchmarks/reference/nemotron_h_fetchsgd.py::selective_scan``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 128,
                compute_dtype=jnp.float32):
    """x (b, T, H, P); dt (b, T, H) after softplus; A (H,) negative;
    B, C (b, T, G, N) with H a multiple of G (a group serves H // G heads);
    D (H,). Returns y (b, T, H, P) float32. T need not divide by ``chunk``:
    padded steps have dt = 0, which neither decays nor feeds the state."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // chunk
    cd, f32 = compute_dtype, jnp.float32
    R = H // G
    dt = dt.astype(f32)
    # heads before time inside a chunk, so the (chunk, chunk) and
    # (chunk, P) faces are the minor dimensions of every large array
    per_head = lambda m: m.reshape(                   # noqa: E731
        (b, nc, chunk, G, R) + m.shape[3:]).transpose(
        (0, 1, 3, 4, 2) + tuple(range(5, 2 + m.ndim)))
    per_group = lambda m: m.reshape(b, nc, chunk, G, N).transpose(  # noqa: E731
        0, 1, 3, 2, 4).astype(cd)
    xdt = per_head(x.astype(f32) * dt[..., None])     # (b, nc, G, R, Q, P)
    Bc, Cc = per_group(B), per_group(C)               # (b, nc, G, Q, N)
    cum = jnp.cumsum(per_head(dt * A.astype(f32)), axis=-1)   # <= 0
    total = cum[..., -1]                              # (b, nc, G, R)

    # inside a chunk
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    seg = cum[..., :, None] - cum[..., None, :]       # (b, nc, G, R, i, j)
    L = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcgin,bcgjn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip",
                   (L * cb[:, :, :, None]).astype(cd), xdt.astype(cd),
                   preferred_element_type=f32)

    # what each chunk leaves, and the one state carried between chunks
    to_end = jnp.exp(total[..., None] - cum)          # (b, nc, G, R, j)
    left = jnp.einsum("bcgrjp,bcgjn->bcgrpn",
                      (xdt * to_end[..., None]).astype(cd), Bc,
                      preferred_element_type=f32)

    def carry(h, xs):
        decay, s = xs
        return h * decay[..., None, None] + s, h      # emits the state before

    _, before = jax.lax.scan(
        carry, jnp.zeros((b, G, R, P, N), f32),
        (jnp.exp(total).swapaxes(0, 1), left.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                    # (b, nc, G, R, P, N)
    y = y + jnp.einsum("bcgin,bcgrpn->bcgrip", Cc, before.astype(cd),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(b, nc * chunk, H, P)[:, :T]
    return y + x[:, :T].astype(f32) * D.astype(f32)[:, None]
