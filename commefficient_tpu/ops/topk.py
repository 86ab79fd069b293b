"""Magnitude top-k as a dense masked vector.

Semantics of the reference ``_topk`` (reference utils.py:232-252): return a
vector of the same shape as ``vec`` holding the k largest-magnitude entries
and zero elsewhere; 2-D inputs take k per row. The reference needs CUDA for
this to be fast ("topk is impossibly slow on CPU, very fast on GPU",
reference fed_worker.py:206); on TPU there are now THREE fast paths, picked
per call:

* exact, streaming (default on TPU): the two-pass radix-select Pallas
  kernel in ``ops/topk_kernels.py`` — 9 counting passes + 1 select pass,
  O(d) work, no sort and no d-sized intermediates, bitwise-identical to
  the ``jax.lax.top_k`` formulation below (tie-breaking included);
* exact, sort-unit: ``jax.lax.top_k`` on the hardware sort unit — the
  incumbent O(d·log d) chain, kept as the bitwise fallback and the
  non-TPU path;
* approximate: ``jax.lax.approx_max_k`` when ``approx_recall`` is set
  (0 < r <= 1) — the TPU-native partial reduction. At FetchSGD's NLP
  scale (d=124M, k=50k) this is 5.4x faster than the exact sort (95ms vs
  514ms on a v5e chip) at 0.988 measured recall; the swapped-out
  coordinates stay in the error-feedback accumulators and transmit in a
  later round, exactly how FetchSGD already absorbs sketch-recovery
  noise. approx_recall REFUSES the streaming kernel by contract (nothing
  exact to bit-agree with). Exact (None) is the default everywhere for
  reference parity; opt in via ``FedConfig.topk_approx_recall``.

``row_k``: 2-D calls may pass a per-row valid count (traced, <= static k)
— each row keeps only its first ``row_k`` slots of the stable selection
order, which is how heterogeneous-k clients (``--client_k_dist``) select
on-kernel in one pass instead of the legacy topk-then-re-rank two-stage.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _select(sq: jax.Array, k: int, approx_recall: Optional[float]):
    """Indices of the k largest entries of a 1-D score vector."""
    if approx_recall:
        _, idx = jax.lax.approx_max_k(sq, k, recall_target=approx_recall)
        return idx
    _, idx = jax.lax.top_k(sq, k)
    return idx


def _topk_1d(vec, k, approx_recall=None):
    idx = _select(vec * vec, k, approx_recall)
    mask = jnp.zeros(vec.shape, dtype=bool).at[idx].set(True)
    return jnp.where(mask, vec, 0)


def _kernels():
    # function-local: topk_kernels imports countsketch which imports topk
    from commefficient_tpu.ops import topk_kernels
    return topk_kernels


@partial(jax.jit, static_argnames=("k", "approx_recall", "use_kernel"))
def topk(vec: jax.Array, k: int, approx_recall: Optional[float] = None,
         row_k: Optional[jax.Array] = None,
         use_kernel: Optional[bool] = None) -> jax.Array:
    """Zero all but the k largest-magnitude entries (per row if 2-D).

    ``row_k``: a traced valid count <= k (scalar for 1-D, per-row vector
    for 2-D); each row keeps the first ``row_k`` entries of its stable
    selection order — the on-kernel heterogeneous-client path.
    ``use_kernel=False`` pins the incumbent ``lax.top_k`` formulation
    (``--server_fused off``); None/True is the auto backend gate."""
    tk = _kernels()
    kernel = use_kernel is not False and tk.topk_kernel_ok(approx_recall)
    if row_k is not None and approx_recall:
        raise ValueError("row_k requires exact selection "
                         "(approx_recall must be unset)")
    if vec.ndim == 1:
        if kernel:
            return tk.topk_select_pallas(
                vec, k if row_k is None else row_k, k=k)
        if row_k is None:
            return _topk_1d(vec, k, approx_recall)
        return tk._mask_fallback(vec, jnp.asarray(row_k, jnp.int32), k)
    if vec.ndim == 2:
        if kernel:
            kk = (jnp.full((vec.shape[0],), k, jnp.int32)
                  if row_k is None else jnp.asarray(row_k, jnp.int32))
            return jax.vmap(lambda v, c: tk.topk_select_pallas(
                v, c, k=k))(vec, kk)
        if row_k is None:
            return jax.vmap(lambda v: _topk_1d(v, k, approx_recall))(vec)
        return jax.vmap(lambda v, c: tk._mask_fallback(v, c, k))(
            vec, jnp.asarray(row_k, jnp.int32))
    raise ValueError(f"topk supports 1-D/2-D inputs, got ndim={vec.ndim}")


def _values_indices_1d(tk, vec, k, approx_recall, use_kernel):
    if use_kernel:
        masked, mask = tk.topk_select_pallas(vec, k, k=k, with_mask=True)
        return tk.values_indices_from_mask(masked, mask, k)
    idx = _select(vec * vec, k, approx_recall)
    return vec[idx], idx


@partial(jax.jit, static_argnames=("k", "approx_recall", "use_kernel"))
def topk_values_indices(vec: jax.Array, k: int,
                        approx_recall: Optional[float] = None,
                        use_kernel: Optional[bool] = None):
    """(values, indices) of the k largest-magnitude entries, per row if 2-D.

    The sparse twin of ``topk``: same support, same selection (one
    implementation, both dispatch modes), but handing back the k-sized
    arrays lets callers re-sketch or transmit the update at O(k) instead
    of O(d) (server._sketched, where the fused unsketch does not
    dispatch). Under a dispatched kernel the k-sized form costs a d-long
    compaction (topk_kernels.values_indices_from_mask); ``lax.top_k``
    hands it over for nothing."""
    tk = _kernels()
    kernel = use_kernel is not False and tk.topk_kernel_ok(approx_recall)
    if vec.ndim == 1:
        return _values_indices_1d(tk, vec, k, approx_recall, kernel)
    if vec.ndim == 2:
        return jax.vmap(lambda v: _values_indices_1d(
            tk, v, k, approx_recall, kernel))(vec)
    raise ValueError("topk_values_indices supports 1-D/2-D inputs, "
                     f"got ndim={vec.ndim}")
