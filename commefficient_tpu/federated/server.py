"""The five server update rules — the scientific core of FetchSGD.

Pure-functional ports of the reference's ``_server_helper_*`` functions
(reference fed_aggregator.py:483-613). Each rule maps

    (gradient, state, lr) -> (weight_update, new_state)

where ``gradient`` is the round's aggregated (possibly compressed) gradient —
dense ``(d,)`` for uncompressed/true_topk/local_topk/fedavg, an ``(r, c)``
sketch table for sketch mode — and ``state`` holds the virtual momentum and
virtual error vectors. ``weight_update`` is always dense ``(d,)`` and already
scaled by ``lr`` (which may be a scalar or a per-parameter vector, for
Fixup-style per-group learning rates, ref fed_aggregator.py:411-427).

Deviations from the reference (deliberate):
* ``sketch`` mode with ``error_type='none'`` unsketches the momentum table
  directly. The reference would unsketch an all-zero ``Verror``
  (fed_aggregator.py:579-590 only assigns Verror for local/virtual), i.e.
  produce a zero update — clearly dead configuration, not semantics worth
  preserving.
* true_topk's momentum factor masking of *participating client* velocities
  (fed_aggregator.py:528-533, which crashes upstream due to the missing
  ``global g_participating_clients`` at :219) is done correctly in the round
  step (client.py), using the update's support.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.state import ServerOptState
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.ops.topk import topk, topk_values_indices


def init_server_opt_state(cfg: FedConfig) -> ServerOptState:
    """Zero virtual momentum/error of the mode's shape (ref :400-409)."""
    shape = cfg.transmit_shape
    return ServerOptState(Vvelocity=jnp.zeros(shape), Verror=jnp.zeros(shape))


def make_sketch(cfg: FedConfig) -> CountSketch:
    """Sketch with hashes shared by clients and server (ref args2sketch :464)."""
    return CountSketch(d=cfg.grad_dim, c=cfg.num_cols, r=cfg.num_rows,
                       seed=42, num_blocks=cfg.num_blocks,
                       scheme=cfg.sketch_scheme)


def _momentum(gradient, velocity, rho):
    """v <- gradient + rho * v (ref torch.add(..., alpha=rho) :502-506)."""
    return gradient + rho * velocity


def _fused_ok(cfg: FedConfig) -> bool:
    """Gate for the fused server-update path (ops/topk_kernels.py):
    exact selection only (approx_recall refuses by contract), opt-out
    via --server_fused off, and the kernel backend/force gate."""
    from commefficient_tpu.ops.topk_kernels import topk_kernel_ok
    return (cfg.server_fused != "off"
            and topk_kernel_ok(cfg.topk_approx_recall or None))


def _fedavg(avg_update, state, cfg, lr):
    # lr is applied worker-side during local SGD; server applies momentum
    # only (ref :483-495, lr forced to 1 at :451).
    v = _momentum(avg_update, state.Vvelocity, cfg.virtual_momentum)
    return v, ServerOptState(Vvelocity=v, Verror=state.Verror)


def _uncompressed(gradient, state, cfg, lr, noise_rng):
    v = _momentum(gradient, state.Vvelocity, cfg.virtual_momentum)
    update = v
    if cfg.do_dp and cfg.dp_mode == "server":
        if noise_rng is None:
            raise ValueError("server DP requires a fresh noise_rng per round")
        noise = cfg.noise_multiplier * jax.random.normal(
            noise_rng, update.shape, update.dtype)
        update = update + noise
    return update * lr, ServerOptState(Vvelocity=v, Verror=state.Verror)


def _true_topk(gradient, state, cfg, lr):
    if _fused_ok(cfg):
        # one fused pass (ops/topk_kernels.fused_true_topk_pallas):
        # momentum, error accumulation, streaming radix top-k and BOTH
        # error-feedback residuals emit tile-by-tile — no sort, no
        # scatter mask, no d-sized intermediate between the stages.
        # Bitwise-identical to the chain below (tests/test_server_fused)
        from commefficient_tpu.ops.topk_kernels import fused_true_topk_pallas
        update, v, err = fused_true_topk_pallas(
            gradient, state.Vvelocity, state.Verror, k=cfg.k,
            rho=cfg.virtual_momentum)
        return update * lr, ServerOptState(Vvelocity=v, Verror=err)
    v = _momentum(gradient, state.Vvelocity, cfg.virtual_momentum)
    err = state.Verror + v
    update = topk(err, cfg.k, cfg.topk_approx_recall or None,
                  use_kernel=None if cfg.server_fused != "off" else False)
    support = update != 0
    # error feedback + momentum factor masking on the global top-k support
    err = jnp.where(support, 0.0, err)
    v = jnp.where(support, 0.0, v)
    return update * lr, ServerOptState(Vvelocity=v, Verror=err)


def _local_topk(summed_local_topk, state, cfg, lr):
    # momentum on the already-sparse sum of worker top-ks; no virtual error,
    # and no factor masking (it would zero the whole velocity every round,
    # ref :544-566).
    v = _momentum(summed_local_topk, state.Vvelocity, cfg.virtual_momentum)
    return v * lr, ServerOptState(Vvelocity=v, Verror=state.Verror)


def _sketched(sketched_grad, state, cfg, lr, sketch: CountSketch):
    v = _momentum(sketched_grad, state.Vvelocity, cfg.virtual_momentum)
    # 'virtual' accumulates; 'none' recovers straight from the momentum table
    # (sketch+'local' is rejected by FedConfig.validate)
    err = state.Verror + v if cfg.error_type == "virtual" else v
    # One algorithm (recover the top-k, re-sketch the update, mask the state
    # on the re-sketch's nonzero buckets), two ways to make the re-sketch,
    # chosen by what is known at trace time.
    approx = cfg.topk_approx_recall or None
    if cfg.server_fused != "off" and sketch._fused_unsketch_ok(approx, True):
        # The kernels dispatch: unsketch_select_pallas writes the DENSE
        # masked update over the buffer its one estimates pass filled
        # (ops/topk_kernels), so that output is the update, and its
        # re-sketch is one more pass of the dense sketch kernel (the
        # program round.py's aggregate-side sketch already compiles).
        # Compacting it to (vals, idxs) for sketch_sparse would cost a
        # d-long cumsum + scatter: 38 ms a round at d=6.5M/k=50k on a v5e
        # against 4.5 ms for the kernel's dense pass (PERF.md, PR 34).
        update = sketch.unsketch(err, cfg.k, None, use_kernel=True)
        sketched_update = sketch.sketch_vec_batched(update, use_kernel=True)
    else:
        # lax.top_k hands over (vals, idxs) for nothing, so re-sketching
        # only the k nonzeros (O(r*k), CountSketch.sketch_sparse) beats a
        # dense XLA sketch of the update (O(r*d)). estimate-all is routed
        # through the batch-guard dispatch at batch 1 so it compiles the
        # SAME 2-D grid kernel the vmapped client.py/client_store.py paths
        # run — one resident estimate program instead of a 1-D grid twin.
        vals, idxs = topk_values_indices(
            sketch.estimates_batched(err, use_kernel=True), cfg.k, approx,
            use_kernel=None if cfg.server_fused != "off" else False)
        update = jnp.zeros((cfg.grad_dim,)).at[idxs].set(vals)
        sketched_update = sketch.sketch_sparse(vals, idxs)
    # the update's footprint *in sketch space*: only `!= 0` is read, so
    # the two re-sketches agree wherever their bucket sums differ by float
    # summation order (tests/test_server_fused.py pins update, Verror and
    # Vvelocity bitwise across the arms)
    support = sketched_update != 0
    if cfg.error_type == "virtual":
        err = jnp.where(support, 0.0, err)
    # momentum factor masking, approximated in sketch space (ref :603-611)
    v = jnp.where(support, 0.0, v)
    return update * lr, ServerOptState(Vvelocity=v, Verror=err)


def server_update(
    gradient: jax.Array,
    state: ServerOptState,
    cfg: FedConfig,
    lr,
    sketch: Optional[CountSketch] = None,
    noise_rng: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[jax.Array, ServerOptState]:
    """Dispatch to the mode's update rule (ref get_server_update :469-481).

    Pure and jit-safe: ``cfg``/``sketch`` are static, everything else traced.
    Inside a program partitioned over ``mesh`` the rule — replicated compute
    on replicated state, and the home of the top-k/unsketch kernels — runs
    on each chip's own replica (``parallel.mesh.on_each_replica``).
    """
    if mesh is not None:
        from commefficient_tpu.parallel.mesh import on_each_replica
        return on_each_replica(
            mesh, lambda g, st, lr_, key: server_update(
                g, st, cfg, lr_, sketch=sketch, noise_rng=key)
        )(gradient, state, lr, noise_rng)
    if cfg.mode == "fedavg":
        return _fedavg(gradient, state, cfg, lr)
    if cfg.mode == "uncompressed":
        return _uncompressed(gradient, state, cfg, lr, noise_rng)
    if cfg.mode == "true_topk":
        return _true_topk(gradient, state, cfg, lr)
    if cfg.mode == "local_topk":
        return _local_topk(gradient, state, cfg, lr)
    if cfg.mode == "sketch":
        if sketch is None:
            sketch = make_sketch(cfg)
        return _sketched(gradient, state, cfg, lr, sketch)
    raise ValueError(f"unknown mode {cfg.mode!r}")
