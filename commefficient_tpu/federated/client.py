"""Client-side local computation: one simulated federated client's step.

Functional port of the reference worker math (reference fed_worker.py:140-335)
— local SGD gradients with weight decay, gradient clipping, worker-side DP,
local momentum, local error feedback, local top-k masking, sketching, and the
FedAvg multi-epoch inner loop — with two structural changes:

* No processes, no queues: one client's step is a pure function; the round
  vmaps it over sampled clients and XLA shards the vmap across the mesh.
* Ragged client batches become fixed-shape padded batches with a validity
  mask (XLA needs static shapes); all sums weight by true counts, matching
  the reference's weighting by datapoints (fed_worker.py:281-283).

The loss callable contract (set by the entrypoints, like compute_loss_train
at reference cv_train.py:67-83):

    apply_loss(params_pytree, batch_tuple, rng, train) ->
        (per_example_loss (B,), per_example_metrics (M, B))
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.config import FedConfig
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.ops.topk import topk
from commefficient_tpu.utils.tracing import phase


class ClientStepOut(NamedTuple):
    transmit: jax.Array          # (d,) or (r, c): sum-of-grads scaled
    velocity: Optional[jax.Array]
    error: Optional[jax.Array]
    client_weights: Optional[jax.Array]
    loss_sum: jax.Array
    metric_sums: jax.Array
    num_datapoints: jax.Array


def _masked_loss_and_grad(apply_loss, unflatten, w_flat, batch, mask, rng,
                          microbatch_size: int = -1):
    """Gradient of the *summed* loss over valid examples + summed metrics.

    ``microbatch_size > 0`` splits the batch into chunks and accumulates the
    gradient over a ``lax.scan`` — the reference's microbatch loop
    (fed_worker.py:265-287), which bounds peak activation memory to one
    microbatch (the enabler for GPT2 whole-client batches on one chip).
    Because the gradient is of a *sum*, chunked accumulation is numerically
    the same computation as the one-shot path (same adds, scan order).
    """

    def chunk_grad(flat, chunk_batch, chunk_mask, chunk_rng):
        def loss_sum_fn(f):
            params = unflatten(f)
            per_ex_loss, per_ex_metrics = apply_loss(
                params, chunk_batch, chunk_rng, True)
            loss_sum = jnp.sum(per_ex_loss * chunk_mask)
            metric_sums = jnp.sum(per_ex_metrics * chunk_mask[None, :],
                                  axis=-1)
            return loss_sum, (loss_sum, metric_sums)

        return jax.grad(loss_sum_fn, has_aux=True)(flat)

    B = mask.shape[0]
    if microbatch_size <= 0 or microbatch_size >= B:
        grads, (loss_sum, metric_sums) = chunk_grad(w_flat, batch, mask, rng)
        return grads, loss_sum, metric_sums

    mb = microbatch_size
    n_chunks = -(-B // mb)  # ceil
    pad_to = n_chunks * mb

    def pad_and_split(x):
        pad_width = [(0, pad_to - B)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, pad_width).reshape((n_chunks, mb) + x.shape[1:])

    batch_r = tuple(pad_and_split(t) for t in batch)
    mask_r = pad_and_split(mask)
    # per-chunk rng in its own fold_in domain: folding the raw rng by chunk
    # index would make chunk 1's key bitwise-equal to the DP noise key
    # (fold_in(rng, 1) in compute_gradient). Only observable through
    # stochastic pieces of the loss (dropout); deterministic losses match
    # the one-shot path exactly.
    mb_rng = jax.random.fold_in(rng, 0x4d42)
    chunk_rngs = jax.vmap(lambda i: jax.random.fold_in(mb_rng, i))(
        jnp.arange(n_chunks))

    _, (l_shape, m_shape) = jax.eval_shape(
        chunk_grad, w_flat, tuple(t[0] for t in batch_r), mask_r[0],
        chunk_rngs[0])

    def body(carry, xs):
        g_acc, l_acc, m_acc = carry
        cb, cm, crng = xs
        grads, (ls, ms) = chunk_grad(w_flat, cb, cm, crng)
        return (g_acc + grads, l_acc + ls, m_acc + ms), None

    init = (jnp.zeros_like(w_flat), jnp.zeros(l_shape.shape, l_shape.dtype),
            jnp.zeros(m_shape.shape, m_shape.dtype))
    (grads, loss_sum, metric_sums), _ = jax.lax.scan(
        body, init, (batch_r, mask_r, chunk_rngs))
    return grads, loss_sum, metric_sums


def _clip_to_norm(vec, max_norm):
    """Scale down to max_norm if the norm exceeds it (ref utils.py:305-313)."""
    norm = jnp.linalg.norm(vec)
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return vec * scale


def reconstruct_worker_weights(ps_weights, stale_weights, cfg: FedConfig):
    """topk_down: stale client weights + top-k of the diff
    (ref get_new_worker_weights, fed_worker.py:232-247)."""
    diff = ps_weights - stale_weights
    return stale_weights + topk(diff, cfg.k, cfg.topk_approx_recall or None)


def compute_gradient(apply_loss, unflatten, forward_weights, batch, mask,
                     rng, cfg: FedConfig, sketch: Optional[CountSketch],
                     trainable_mask=None):
    """The forward_grad equivalent (ref fed_worker.py:249-335): returns the
    (possibly sketched) *mean* gradient and summed loss/metrics.

    ``trainable_mask`` zeros frozen coordinates BEFORE momentum/error/
    compression — the analog of the reference's requires_grad=False
    (frozen params never enter the gradient vector there), so top-k budgets
    and sketch capacity are spent only on trainable weights."""
    with phase("client_grad"):
        n = jnp.sum(mask)
        safe_n = jnp.maximum(n, 1.0)
        grad_sum, loss_sum, metric_sums = _masked_loss_and_grad(
            apply_loss, unflatten, forward_weights, batch, mask, rng,
            microbatch_size=cfg.microbatch_size)
        grad = grad_sum / safe_n
        if trainable_mask is not None:
            grad = grad * trainable_mask

        # gradient clipping on the raw gradient, before weight decay —
        # matches clip_grad_norm_ placement at ref fed_worker.py:290-292
        # (non-sketch)
        if cfg.max_grad_norm is not None and cfg.mode != "sketch":
            grad = _clip_to_norm(grad, cfg.max_grad_norm)

        # weight decay folded into the gradient (ref utils.py:254-259); divided
        # by num_workers because every worker adds it and the server sums;
        # frozen coordinates get no decay (they're not trainable params)
        if cfg.weight_decay != 0:
            wd = (cfg.weight_decay / cfg.num_workers) * forward_weights
            if trainable_mask is not None:
                wd = wd * trainable_mask
            grad = grad + wd

        # worker-side differential privacy (ref fed_worker.py:304-309)
        if cfg.do_dp:
            grad = _clip_to_norm(grad, cfg.l2_norm_clip)
            if cfg.dp_mode == "worker":
                noise_rng = jax.random.fold_in(rng, 1)
                grad = grad + (cfg.noise_multiplier *
                               jnp.sqrt(float(cfg.num_workers)) *
                               jax.random.normal(noise_rng, grad.shape))

    with phase("compress"):
        # sketch is None in sketch mode when the round uses the
        # sketch-after-aggregate fast path (see round.build_round_step):
        # with no per-worker nonlinearity the sum of sketches equals the
        # sketch of the sum, so the round sketches once after aggregation
        if cfg.mode == "sketch" and sketch is not None:
            # this call runs under the round's per-worker vmap, and on TPU
            # backends it DISPATCHES the batched Pallas sketch kernel: the
            # batch guard's custom_vmap rule (ops/sketch_kernels._batch_guard)
            # selects the 2-D grid (W, n_tiles) variant, bit-identical per
            # worker row to the XLA formulation, so all W sketches run on the
            # kernel in one pallas_call. CPU, nested vmap, and over-budget
            # shapes still fall back to the bit-identical XLA path — asserted
            # by the sketch_batched graft-audit target (analysis/targets.py)
            g = sketch.sketch_vec(grad, use_kernel=True)
            if cfg.max_grad_norm is not None:
                # sketch-space clip via l2 estimate (ref fed_worker.py:317-319)
                est = sketch.l2estimate(g)
                scale = jnp.where(est > cfg.max_grad_norm,
                                  cfg.max_grad_norm
                                  / jnp.maximum(est, 1e-12), 1.0)
                g = g * scale
        else:
            g = grad

    return g, loss_sum, metric_sums, n


def client_step(apply_loss, unflatten, ps_weights, batch, mask, velocity,
                error, stale_weights, rng, cfg: FedConfig,
                sketch: Optional[CountSketch],
                trainable_mask=None, client_k=None) -> ClientStepOut:
    """One non-fedavg client's local step (ref local_step fed_worker.py:184-230).

    ``client_k`` (traced scalar, only under cfg.client_k_dist) is this
    client's own transmit budget k_i <= cfg.k: the provisioned top-k
    selection is masked down to the k_i largest-magnitude survivors
    (federated dropout-style partial participation). Coordinates masked
    out by the budget keep their error-feedback mass — they are simply
    not transmitted this round."""
    with phase("client_grad"):
        if cfg.do_topk_down:
            forward_weights = reconstruct_worker_weights(
                ps_weights, stale_weights, cfg)
            new_stale = forward_weights
        else:
            forward_weights = ps_weights
            new_stale = None

    g, loss_sum, metric_sums, n = compute_gradient(
        apply_loss, unflatten, forward_weights, batch, mask, rng, cfg, sketch,
        trainable_mask=trainable_mask)

    with phase("compress"):
        # sum-of-gradients semantics: scale the mean grad back up by the true
        # batch size so the server can divide by total datapoints (ref :190)
        g = g * n

        if cfg.local_momentum > 0:
            velocity = g + cfg.local_momentum * velocity
            carrier = velocity
        else:
            carrier = g

        if cfg.error_type == "local":
            error = error + carrier
            to_transmit = error
        else:
            to_transmit = carrier

        if cfg.mode == "local_topk":
            if client_k is not None and not cfg.topk_approx_recall:
                # per-client budget, selected in ONE pass: keep the first
                # client_k slots of the stable selection order (the length-
                # k_i prefix of the magnitude order — the same set the
                # legacy topk-then-re-rank two-stage kept). Under the round
                # vmap this is the batched per-row-k kernel path; masked
                # coordinates keep their error-feedback mass below.
                to_transmit = topk(to_transmit, cfg.k, row_k=client_k)
            else:
                to_transmit = topk(to_transmit, cfg.k,
                                   cfg.topk_approx_recall or None)
                if client_k is not None:
                    # approx selection has no stable prefix to cut, so the
                    # budget still ranks the provisioned selection and keeps
                    # the client_k largest. Slots that point at zero
                    # coordinates (selection narrower than cfg.k) are
                    # harmless: where() writes 0.0 over 0.0.
                    _, sel = jax.lax.top_k(jnp.abs(to_transmit), cfg.k)
                    keep = jnp.zeros(to_transmit.shape, bool).at[sel].set(
                        jnp.arange(cfg.k) < client_k)
                    to_transmit = jnp.where(keep, to_transmit, 0.0)
            support = to_transmit != 0
            if cfg.error_type == "local":
                error = jnp.where(support, 0.0, error)   # error feedback
            if cfg.local_momentum > 0:
                velocity = jnp.where(support, 0.0, velocity)  # factor masking

    return ClientStepOut(transmit=to_transmit, velocity=velocity, error=error,
                         client_weights=new_stale, loss_sum=loss_sum,
                         metric_sums=metric_sums, num_datapoints=n)


def fedavg_client_step(apply_loss, unflatten, ps_weights, batch, mask, lr,
                       rng, cfg: FedConfig,
                       trainable_mask=None) -> ClientStepOut:
    """FedAvg: multi-epoch local SGD on this client's whole (padded) dataset,
    transmitting the weight delta scaled by the client's datapoint count
    (ref fed_worker.py:61-113) — as a lax.scan over static-shaped chunks.

    The reference's per-step lr-decay exponent counts the client's ACTUAL
    local steps across epochs (fed_worker.py:98-101). Padded ghost chunks
    (all-zero mask tails) are skipped in that count: the exponent is
    ``epoch * n_real_chunks + chunk_idx``, which matches the reference
    exactly for tail-padded ragged clients (tested against a host-side
    reference simulation in tests/test_round.py).
    """
    max_b = mask.shape[0]
    if cfg.fedavg_batch_size == -1:
        chunk = max_b
    else:
        chunk = min(cfg.fedavg_batch_size, max_b)
    n_chunks = -(-max_b // chunk)  # ceil
    pad_to = n_chunks * chunk

    def pad(x):
        pad_width = [(0, pad_to - max_b)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, pad_width)

    batch = tuple(pad(t) for t in batch)
    mask_p = pad(mask)
    n_steps = n_chunks * cfg.num_fedavg_epochs
    # chunks containing at least one real row (client data is tail-padded)
    n_real_chunks = jnp.sum(
        jnp.sum(mask_p.reshape(n_chunks, chunk), axis=1) > 0).astype(
            jnp.float32)

    def body(w, step):
        b_idx = step % n_chunks
        start = b_idx * chunk
        mb = tuple(jax.lax.dynamic_slice_in_dim(t, start, chunk) for t in batch)
        mmask = jax.lax.dynamic_slice_in_dim(mask_p, start, chunk)
        g, loss_sum, metric_sums, n = compute_gradient(
            apply_loss, unflatten, w, mb, mmask,
            jax.random.fold_in(rng, step), cfg, None,
            trainable_mask=trainable_mask)
        # exponent counts real steps only (ref fed_worker.py:98-101)
        eff_step = (step // n_chunks).astype(jnp.float32) * n_real_chunks \
            + (step % n_chunks).astype(jnp.float32)
        decay = cfg.fedavg_lr_decay ** eff_step
        # g is already the mean grad over the chunk (ref :98-101 divides)
        w = w - g * lr * decay * jnp.where(n > 0, 1.0, 0.0)
        return w, (loss_sum, metric_sums, n)

    final_w, (loss_sums, metric_sums, ns) = jax.lax.scan(
        body, ps_weights, jnp.arange(n_steps))

    client_n = jnp.sum(mask)
    transmit = (ps_weights - final_w) * client_n
    return ClientStepOut(
        transmit=transmit, velocity=None, error=None, client_weights=None,
        # metrics summed over all local steps; one epoch over the client's
        # data contributes each datapoint once per epoch
        loss_sum=jnp.sum(loss_sums) / cfg.num_fedavg_epochs,
        metric_sums=jnp.sum(metric_sums, axis=0) / cfg.num_fedavg_epochs,
        num_datapoints=client_n)


def eval_step(apply_loss, unflatten, ps_weights, batch, mask, rng):
    """Validation forward pass (ref _call_val / compute_grad=False path)."""
    params = unflatten(ps_weights)
    per_ex_loss, per_ex_metrics = apply_loss(params, batch, rng, False)
    return (jnp.sum(per_ex_loss * mask),
            jnp.sum(per_ex_metrics * mask[None, :], axis=-1),
            jnp.sum(mask))
