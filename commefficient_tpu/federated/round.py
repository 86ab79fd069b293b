"""The jitted federated round — the whole PS/worker dance as one SPMD program.

Reference equivalent: FedModel._call_train + worker_loop + FedOptimizer.step
(reference fed_aggregator.py:213-335, 429-461; fed_worker.py:14-138). What
was there a multiprocess choreography — queue.put per worker process, shm
reads of ps_weights, NCCL reduce of summed gradients, host-side server update
— is here ONE pure jitted function:

    round_step(state, batch, lr, rng) -> (new_state, metrics)

* sampled clients' local steps run as a ``vmap`` over the worker axis; with a
  mesh, that axis is sharded over devices ("clients" mesh axis), so each chip
  simulates W/n_chips clients — the TPU analog of each GPU process simulating
  num_workers/n_gpus clients sequentially (ref fed_aggregator.py:230-237)
* the NCCL reduce (ref fed_worker.py:138) becomes the implicit XLA
  all-reduce generated by summing the vmapped transmits across the sharded
  axis
* upload/download byte accounting is exact and fully in-program: a
  per-weight last-changed-round vector and per-client last-participation
  rounds replace the reference's deque-of-past-weight-vectors host machinery
  (fed_aggregator.py:186-194, 239-299)
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated import client as client_lib
from commefficient_tpu.federated.client_store import (gather_rows,
                                                      init_client_storage,
                                                      make_codec,
                                                      scatter_rows,
                                                      select_rows)
from commefficient_tpu.federated.server import make_sketch, server_update
from commefficient_tpu.federated.state import (BufferState, ClientState,
                                               GradBuckets, ServerOptState)
from commefficient_tpu.utils.tracing import phase


@struct.dataclass
class FedState:
    """Everything that persists across rounds, as one donatable pytree."""
    weights: jax.Array                 # (d,) replicated
    opt: ServerOptState                # virtual momentum / error
    clients: ClientState               # (num_clients, ...) sharded rows
    round_idx: jax.Array               # () int32
    last_changed: jax.Array            # (d,) int32: round each weight changed
    client_last_round: jax.Array       # (num_clients,) int32
    aborted: jax.Array                 # () bool: NaN guard tripped (sticky)
    # () int32: how many server applies have mutated ``weights``. In sync
    # mode it tracks round_idx exactly; in buffered mode it is the version
    # counter client contributions are stamped with, and tau =
    # weights_version - start_version is the staleness the apply scales by.
    weights_version: jax.Array
    # (num_clients,) int32: rounds of bench time left per client
    # (cfg.client_quarantine). A client with quarantine > 0 is not
    # sampled into the aggregate: it neither pulls weights nor uploads.
    quarantine: jax.Array
    # server_mode='buffered' only: the M-slot contribution buffer
    # (federated/buffer.py). None in sync mode — an empty pytree subtree,
    # so sync checkpoints/shardings are unchanged.
    buffer: Optional[BufferState] = None


def init_fed_state(cfg: FedConfig, flat_weights: jax.Array) -> FedState:
    d = cfg.grad_dim   # physical length (>= logical grad_size on a 2D
    n = cfg.num_clients  # clients x model mesh; pads stay zero forever)
    assert flat_weights.shape == (d,), (flat_weights.shape, d)
    if cfg.client_state_offload and cfg.has_client_state:
        # rows live host-side (api.FedLearner's HostArenaStore); the device
        # state carries none of them
        clients = ClientState()
    else:
        # device placement: encoded per-client storage (client_store.py) —
        # the dense codec reproduces the historical (n, d) arrays exactly
        clients = init_client_storage(cfg, make_codec(cfg), flat_weights)
    return FedState(
        weights=flat_weights.astype(jnp.float32),
        opt=ServerOptState(Vvelocity=jnp.zeros(cfg.transmit_shape),
                           Verror=jnp.zeros(cfg.transmit_shape)),
        clients=clients,
        round_idx=jnp.zeros((), jnp.int32),
        # -2 = "never changed": strictly below the -1 "never participated"
        # sentinel so the >= download-accounting comparison starts at zero
        last_changed=jnp.full((d,), -2, jnp.int32),
        client_last_round=jnp.full((n,), -1, jnp.int32),
        aborted=jnp.zeros((), bool),
        weights_version=jnp.zeros((), jnp.int32),
        quarantine=jnp.zeros((n,), jnp.int32),
        buffer=None,
    )


def _gather_rows(arr: Optional[jax.Array], ids: jax.Array):
    return None if arr is None else arr[ids]


def _scatter_rows(arr: Optional[jax.Array], ids: jax.Array,
                  rows: Optional[jax.Array]):
    if arr is None or rows is None:
        return arr
    # ids may contain out-of-bounds sentinels for padded workers -> dropped
    return arr.at[ids].set(rows, mode="drop")


def fused_clients_eligible(cfg: FedConfig) -> bool:
    """Whether the round may take the fused-gradient fast path: no
    per-worker state or nonlinearity, so the sum of per-client gradients
    equals the gradient of the summed loss (see build_round_step). The
    SINGLE definition — the gpt2 entrypoint's ``--mesh seq=`` gate must
    agree with the path the round actually takes (a seq-parallel loss can
    only nest its shard_map under the fused path's one loss call, never
    under the per-worker vmap). Per-client quarantine needs per-client
    transmits to judge finiteness per contribution, so it forces the
    per-worker path."""
    return (cfg.mode in ("uncompressed", "sketch", "true_topk")
            and not cfg.do_dp and cfg.max_grad_norm is None
            and not cfg.do_topk_down
            and not cfg.needs_velocity_state
            and cfg.error_type != "local"
            and cfg.microbatch_size == -1
            and not cfg.client_quarantine)


# participants counted in one pass over ``last_changed``: the compiler fuses
# an unrolled step's sibling reductions into one multi-output fusion, so the
# d-long operand is read once for eight stale rounds (v5e, d = 6.57 M,
# W = 100: 3.9 ms at one participant a pass, 0.66 ms at eight; PERF.md, PR 27)
_COUNTS_PER_PASS = 8


def download_counts(last_changed: jax.Array,
                    stale_round: jax.Array) -> jax.Array:
    """Per-participant count of weights changed since it last pulled:
    ``counts[w] = #{i : last_changed[i] >= stale_round[w]}``, as a loop
    over the W participants whose step is one fused compare-and-sum that
    streams ``last_changed``. No gather, scatter or sort with a d-long
    operand (a TPU has no fast path for them: the searchsorted + histogram
    this replaces was 391 of a 565 ms ResNet-9 round) and no (W, d)
    comparison matrix (496 MB at gpt2-small W=4): O(d) reads a pass, O(W)
    memory, the dense formulation's integers bit for bit
    (tests/test_download_accounting.py). Shared by the sync round and the
    buffered cohort (federated/buffer.py)."""
    def count(_, stale):
        return None, jnp.sum(last_changed >= stale, dtype=jnp.int32)

    return jax.lax.scan(count, None, stale_round,
                        unroll=_COUNTS_PER_PASS)[1]                # (W,)


def build_round_step(apply_loss: Callable, unflatten: Callable,
                     cfg: FedConfig, mesh=None,
                     trainable_mask: Optional[jax.Array] = None,
                     force_per_worker: bool = False,
                     buckets: Optional[GradBuckets] = None) -> Callable:
    """Build the jitted training-round function for this config.

    ``apply_loss(params, batch_tuple, rng, train) -> (loss (B,), metrics (M, B))``.

    The returned program's invariants — no dense (W, d) changed-matrix in
    the fused modes, (num_clients, d) state touched only through scatter,
    no host callbacks, zero post-warmup retraces — are machine-checked by
    ``commefficient_tpu.analysis`` (``python -m commefficient_tpu.analysis
    --target round``; tests/test_analysis_audits.py), via the un-donated
    ``.raw`` attribute attached below.

    ``trainable_mask``: optional (d,) 0/1 vector; zero entries freeze those
    weights (the finetune path — reference freezes all but a fresh head,
    cv_train.py:377-384, resnet9.py:105-113).

    ``buckets`` (``--grad_buckets``, plan built by api.FedLearner): split
    the round's transmit into independent per-bucket compress/reduce ops
    so XLA's latency-hiding scheduler can overlap bucket k's sketch and
    cross-chip psum with bucket k+1's backward compute instead of running
    the whole compression tail serially after the backward (ROADMAP 4(b);
    docs/ROOFLINE.md Round 7). ``None`` (or a 1-bucket plan) leaves every
    code path literally unchanged. Bucketing restructures the DENSE
    transmit reduce (one (W, s_k) -> (s_k,) sum — one psum on a mesh — per
    bucket) and the aggregate-side sketch (one ``sketch_range`` per
    bucket, tables accumulated by linearity); per-worker-sketched
    transmits are already (r, c) tables with nothing left to slice
    (FedConfig.validate rejects that combination).

    The returned function:
        round_step(state, client_ids (W,), batch (W,B,...), mask (W,B),
                   lr, rng) -> (FedState, dict)
    """
    cfg.validate()
    sketch = make_sketch(cfg) if cfg.mode == "sketch" else None
    is_fedavg = cfg.mode == "fedavg"
    # Linearity fast path: sum of sketches == sketch of the sum, so when no
    # per-worker nonlinearity exists (no DP clip/noise, no sketch-space grad
    # clip) the round sketches ONCE after aggregation instead of once per
    # worker — identical math (cs is linear), ~W x less sketch work. The
    # simulated upload cost is unchanged: the metric charges 4*r*c per
    # client per round regardless of how the simulation computes it.
    sketch_after_aggregate = (cfg.mode == "sketch" and not cfg.do_dp
                              and cfg.max_grad_norm is None)
    client_sketch = None if sketch_after_aggregate else sketch
    # Fused-gradient fast path: when clients carry NO per-worker state or
    # nonlinearity (no DP clip/noise, no grad clip, no local momentum/error,
    # no topk_down, no per-client microbatching), the sum of per-client
    # gradients equals the gradient of the summed loss. One backward over
    # the whole (W*B, ...) batch replaces W separate backwards: the worker
    # axis folds into the matmul batch dimension (bigger MXU tiles) and the
    # (W, d) per-worker transmit buffer — 2 GB at GPT2 scale W=4 — is never
    # materialized. Identical math modulo f32 summation order; dropout
    # draws one key for the fused batch instead of one per worker (same
    # distribution, batch rows still decorrelated by position).
    # (force_per_worker is a test hook: the equivalence of the two paths
    # is asserted directly in tests/test_round.py)
    fused_clients = not force_per_worker and fused_clients_eligible(cfg)
    # Host-offloaded client state (cfg.client_state_offload): the sampled
    # clients' ENCODED rows arrive as an explicit W-leading ClientState
    # argument (gathered from the mesh-sharded host arenas by
    # api.FedLearner's HostArenaStore/HostOffloadPipeline, which route
    # each id to its owning row shard) and leave as an output the host
    # scatters back — the round never touches a (num_clients, d) device
    # array. Invalid/aborted slots return their INPUT encoding, so the
    # host put-back is unconditional and value-correct.
    offload = cfg.client_state_offload and cfg.has_client_state
    # the representation axis (--client_state): rows cross the round
    # boundary through this codec; dense is the identity, so every
    # pre-codec jaxpr is reproduced bitwise. Under offload the
    # exactness-preserving codecs (dense/sparse) run HOST-side in the
    # arena instead — the program then takes dense (W, d) rows for every
    # such representation and is compiled ONCE, which is what makes
    # dense- and sparse-offload trajectories bitwise-identical
    # (client_store.DenseCodec.host_side_offload).
    codec = make_codec(cfg)
    host_codec = offload and codec.host_side_offload
    if trainable_mask is not None:
        trainable_mask = jnp.asarray(trainable_mask, jnp.float32)
    # Bucketing is active only when the quantity being reduced/compressed
    # is the dense flat gradient; a plan degenerates to inactive rather
    # than erroring so one FedLearner-built plan serves every path.
    bucketed = (buckets is not None and buckets.num_buckets > 1
                and (cfg.mode != "sketch" or sketch_after_aggregate))
    if bucketed and sum(buckets.sizes) != cfg.grad_dim:
        raise ValueError(f"GradBuckets plan covers {sum(buckets.sizes)} "
                         f"coordinates, round has {cfg.grad_dim}")
    # the aggregate-side sketch is replicated compute that holds a Pallas
    # kernel: on a mesh each chip runs it on its own replica, outside the
    # auto-partitioner (server_update(mesh=) does the same for its kernels)
    from commefficient_tpu.parallel.mesh import on_each_replica

    def bucketed_compress(chunk_of):
        """Assemble the round's aggregate from K independent per-bucket
        ops. ``chunk_of(offset, size)`` produces bucket k's aggregated
        (size,) chunk — each chunk an independent reduce depending only on
        its slice of the per-worker transmits (or of the fused gradient),
        which is what lets the scheduler start bucket k's reduce/psum and
        sketch while later buckets are still in the backward. Dense modes
        reassemble by concatenation (per coordinate the worker sum is
        untouched: bitwise identical to the monolithic reduce); sketch
        mode accumulates per-bucket ``sketch_range`` tables by linearity
        (equal to the monolithic table up to f32 bucket-boundary
        association; tests/test_grad_buckets.py)."""
        chunks = [chunk_of(o, s)
                  for o, s in zip(buckets.offsets, buckets.sizes)]
        if sketch_after_aggregate:
            def sketch_bucket(off, ch):
                return on_each_replica(
                    mesh, lambda c: sketch.sketch_range(c, off, True))(ch)

            with phase("compress"):
                table = sketch_bucket(buckets.offsets[0], chunks[0])
                for off, ch in zip(buckets.offsets[1:], chunks[1:]):
                    table = table + sketch_bucket(off, ch)
            return table
        return jnp.concatenate(chunks)

    # --client_k_dist: per-client transmit budgets k_i <= k enter as an
    # optional trailing (W,) int32 argument (host-drawn from the seeded
    # Philox stream, faults.cohort_client_ks) — static per-config, so the
    # het-k and homogeneous programs never share a cache entry. Only the
    # per-worker local_topk path spends it (config.validate pins the mode).
    het_k = cfg.client_k_active

    def one_client(ps_w, batch, mask, vel, err, stale, lr, rng, ck=None):
        if is_fedavg:
            return client_lib.fedavg_client_step(
                apply_loss, unflatten, ps_w, batch, mask, lr, rng, cfg,
                trainable_mask=trainable_mask)
        return client_lib.client_step(
            apply_loss, unflatten, ps_w, batch, mask, vel, err, stale,
            rng, cfg, client_sketch, trainable_mask=trainable_mask,
            client_k=ck)

    def round_core(state: FedState, rows, client_ids, batch, mask, lr, rng,
                   client_ks=None):
        w = state.weights
        ids = client_ids
        with phase("client_grad"):
            # epoch-tail rounds can carry fewer than num_workers real clients;
            # padded slots have all-zero masks and must neither transmit, nor
            # write state rows, nor count in the byte accounting
            valid_w = jnp.any(mask > 0, axis=1)                         # (W,)
            num_clients = (state.client_last_round.shape[0])
            if cfg.client_quarantine:
                # benched clients sit the round out entirely: no weight pull,
                # no compute counted, no upload (their vmapped slot still
                # computes — dead lanes — but every consumer masks it)
                benched_w = state.quarantine[ids] > 0                   # (W,)
                pull_w = jnp.logical_and(valid_w, ~benched_w)
            else:
                pull_w = valid_w

        with phase("download_accounting"):
            # --- download accounting (before this round's update; exact count
            # of weights changed since each participant last pulled). ``>=``
            # because a weight updated at the END of the round a client last
            # pulled in was not seen by that client (last_changed init is -2 so
            # a never-changed weight never counts). Streamed comparison, one
            # participant a step: see download_counts above. ---
            stale_round = state.client_last_round[ids]                  # (W,)
            counts = download_counts(state.last_changed, stale_round)   # (W,)
            download_floats = jnp.sum(
                counts * pull_w.astype(jnp.int32)).astype(jnp.float32)

        if fused_clients:
            with phase("client_grad"):
                flat_cols = tuple(c.reshape((-1,) + c.shape[2:])
                                  for c in batch)
                flat_mask = mask.reshape(-1)
                grad_sum, loss_total, metric_totals = \
                    client_lib._masked_loss_and_grad(
                        apply_loss, unflatten, w, flat_cols, flat_mask, rng)
                if trainable_mask is not None:
                    grad_sum = grad_sum * trainable_mask
                total_n = jnp.sum(flat_mask)
                if cfg.weight_decay != 0:
                    # each valid worker adds (wd/W)*w scaled by its datapoint
                    # count (client.compute_gradient); summed: (wd/W)*w*total_n
                    wd = (cfg.weight_decay / cfg.num_workers) * w * total_n
                    if trainable_mask is not None:
                        wd = wd * trainable_mask
                    grad_sum = grad_sum + wd
            with phase("reduce"):
                if bucketed:
                    # slicing the fused gradient keeps each bucket's
                    # normalize+sketch an independent consumer of its slice
                    # (elementwise divide commutes with slicing: dense
                    # reassembly is bitwise identical)
                    agg = bucketed_compress(
                        lambda o, s: grad_sum[o:o + s]
                        / jnp.maximum(total_n, 1.0))
                else:
                    agg = grad_sum / jnp.maximum(total_n, 1.0)
            new_vels = out_errors = out_client_weights = None
            contrib_w = valid_w   # no per-worker transmits to judge
        else:
            with phase("client_grad"):
                if offload:
                    def _dec(enc):
                        if enc is None or host_codec:   # already dense rows
                            return enc
                        return codec.decode_rows(enc)
                    vels, errs, stales = (_dec(rows.velocities),
                                          _dec(rows.errors),
                                          _dec(rows.weights))
                else:
                    vels = gather_rows(state.clients.velocities, ids, codec)
                    errs = gather_rows(state.clients.errors, ids, codec)
                    stales = gather_rows(state.clients.weights, ids, codec)
                rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(ids)

                axes = (None, 0, 0,
                        None if vels is None else 0,
                        None if errs is None else 0,
                        None if stales is None else 0,
                        None, 0)
                if client_ks is not None:
                    out = jax.vmap(one_client, in_axes=axes + (0,))(
                        w, batch, mask, vels, errs, stales, lr, rngs,
                        client_ks)
                else:
                    out = jax.vmap(one_client, in_axes=axes)(
                        w, batch, mask, vels, errs, stales, lr, rngs)

            with phase("reduce"):
                if cfg.client_quarantine:
                    # graceful degradation: judge each contribution's
                    # finiteness individually; a non-finite slot is EXCLUDED
                    # (jnp.where, not a multiply — NaN * 0 is NaN) instead of
                    # poisoning the aggregate and tripping the global abort
                    finite_w = jnp.logical_and(
                        jnp.isfinite(out.loss_sum),
                        jnp.all(jnp.isfinite(
                            out.transmit.reshape((out.transmit.shape[0], -1))),
                            axis=1))                                    # (W,)
                    contrib_w = jnp.logical_and(pull_w, finite_w)
                    cb = contrib_w.reshape(
                        (-1,) + (1,) * (out.transmit.ndim - 1))
                    total_n = jnp.sum(
                        jnp.where(contrib_w, out.num_datapoints, 0.0))
                    if bucketed:
                        agg = bucketed_compress(
                            lambda o, s: jnp.sum(
                                jnp.where(cb, out.transmit[:, o:o + s], 0.0),
                                axis=0) / jnp.maximum(total_n, 1.0))
                    else:
                        agg = (jnp.sum(jnp.where(cb, out.transmit, 0.0),
                                       axis=0) / jnp.maximum(total_n, 1.0))
                    loss_total = jnp.sum(
                        jnp.where(contrib_w, out.loss_sum, 0.0))
                    metric_totals = jnp.sum(
                        jnp.where(contrib_w[:, None], out.metric_sums, 0.0),
                        axis=0)
                else:
                    contrib_w = valid_w
                    total_n = jnp.sum(out.num_datapoints)
                    # the reduce across the worker/mesh axis (ref
                    # fed_worker.py:138 + fed_aggregator.py:329-332): sum
                    # transmits, divide by datapoints. Padded workers are
                    # zeroed: with local error feedback their transmit would
                    # otherwise leak the aliased client's error row.
                    valid_bcast = valid_w.reshape(
                        (-1,) + (1,) * (out.transmit.ndim - 1))
                    if bucketed:
                        # one independent (W, s_k) -> (s_k,) reduce per bucket
                        # — on a mesh, one psum per bucket instead of one
                        # monolithic (d,)-sized all-reduce
                        agg = bucketed_compress(
                            lambda o, s: jnp.sum(
                                out.transmit[:, o:o + s] * valid_bcast,
                                axis=0) / jnp.maximum(total_n, 1.0))
                    else:
                        agg = (jnp.sum(out.transmit * valid_bcast, axis=0) /
                               jnp.maximum(total_n, 1.0))
                    loss_total = jnp.sum(out.loss_sum)
                    metric_totals = jnp.sum(out.metric_sums, axis=0)
            new_vels = out.velocity
            out_errors = out.error
            out_client_weights = out.client_weights
        with phase("compress"):
            if sketch_after_aggregate and not bucketed:
                # aggregate-side sketch via the batch-guard dispatch at batch
                # 1 (bucketed runs already sketched per bucket above): same
                # 2-D grid kernel as the per-worker vmapped paths, and the
                # same call buffer.py makes — buffered lockstep stays bitwise
                # equal to sync
                agg = on_each_replica(
                    mesh, lambda a: sketch.sketch_vec_batched(a, True))(agg)

        with phase("server_update"):
            # --- in-round NaN guard (ref cv_train.py:221-229: the reference
            # checks the round's loss BEFORE opt.step, so a breaching round's
            # update is never applied). The host pipeline detects divergence
            # one round late (RoundPipeline), but this device-side guard makes
            # the breaching round — and every round after it — a state
            # no-op, so abort-lag never pollutes weights/state/accounting. ---
            loss_mean = loss_total / jnp.maximum(total_n, 1.0)
            # under client_quarantine, loss_mean is post-exclusion — a single
            # bad client no longer trips this; only a SERVER-side breach
            # (post-exclusion divergence past the threshold) aborts the run
            breach = jnp.logical_or(~jnp.isfinite(loss_mean),
                                    loss_mean > cfg.nan_threshold)
            ok = jnp.logical_and(~breach, ~state.aborted)
            okf = ok.astype(jnp.float32)
            scatter_ids = jnp.where(jnp.logical_and(contrib_w, ok), ids,
                                    num_clients)            # OOB => dropped

            server_lr = 1.0 if is_fedavg else lr
            noise_rng = jax.random.fold_in(rng, 0x5e77e7)
            # on TPU (or forced dispatch) the exact-mode true_topk/sketch
            # recovery inside runs the fused streaming top-k path
            # (ops/topk_kernels, gated by cfg.server_fused) — bitwise-
            # identical to the lax.top_k chain, audited by
            # `graft-audit --target server_update_fused`
            update, new_opt = server_update(agg, state.opt, cfg, server_lr,
                                            sketch=sketch, noise_rng=noise_rng,
                                            mesh=mesh)
            if trainable_mask is not None:
                update = update * trainable_mask
            # aborted => no weight/last_changed movement. A select, not a
            # multiply: a non-finite breach makes the update itself NaN, and
            # NaN * 0 = NaN would poison the weights on the breach round
            # instead of freezing them
            update = jnp.where(ok, update, 0.0)
            if cfg.grad_dim != cfg.grad_size:
                # model-axis pad coordinates must NEVER move: sketch recovery
                # (hash-collision noise puts pad indices in the top-k like any
                # true-zero coordinate) and server DP noise both produce
                # nonzero pad updates; letting them through would drift the
                # pads and bill them to every client's download accounting
                update = update.at[cfg.grad_size:].set(0.0)
            new_opt = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                                   new_opt, state.opt)
            new_w = w - update

            if (cfg.mode == "true_topk" and cfg.local_momentum > 0
                    and new_vels is not None):
                # momentum factor masking of participating clients'
                # velocities at the *global* top-k support — the reference
                # intends this but crashes on a missing global
                # (fed_aggregator.py:528-533 vs :219)
                support = (update != 0)[None, :]
                new_vels = jnp.where(support, 0.0, new_vels)

            if offload:
                keep = jnp.logical_and(valid_w, ok)               # (W,)

                def _keep(new_dense, old_enc):
                    # slot-freeze on the stored rows: a frozen slot returns
                    # its input (dense row under a host-side codec, encoding
                    # otherwise) verbatim — bitwise, never a re-encode of
                    # its decode
                    if old_enc is None:
                        return None
                    if new_dense is None:
                        return old_enc
                    if host_codec:   # rows travel dense; arena encodes
                        return jnp.where(keep[:, None], new_dense, old_enc)
                    return select_rows(keep, codec.encode_rows(new_dense),
                                       old_enc)

                out_rows = ClientState(velocities=_keep(new_vels,
                                                        rows.velocities),
                                       errors=_keep(out_errors, rows.errors),
                                       weights=_keep(out_client_weights,
                                                     rows.weights))
                new_clients = state.clients
            else:
                out_rows = None
                new_clients = ClientState(
                    velocities=scatter_rows(state.clients.velocities,
                                            scatter_ids, new_vels, codec),
                    errors=scatter_rows(state.clients.errors, scatter_ids,
                                        out_errors, codec),
                    weights=scatter_rows(state.clients.weights, scatter_ids,
                                         out_client_weights, codec),
                )

        with phase("download_accounting"):
            new_last_changed = jnp.where(update != 0, state.round_idx,
                                         state.last_changed)
            if cfg.client_quarantine:
                # every client that PULLED this round re-syncs its download
                # baseline, even if its contribution was later dropped as
                # non-finite (the bytes moved regardless)
                pull_ids = jnp.where(jnp.logical_and(pull_w, ok), ids,
                                     num_clients)
                new_client_last = state.client_last_round.at[pull_ids].set(
                    state.round_idx, mode="drop")
                # bench clients whose update came back non-finite for
                # quarantine_rounds rounds; everyone else's bench clock ticks
                # down one per applied round
                bad_ids = jnp.where(
                    jnp.logical_and(jnp.logical_and(pull_w, ~finite_w), ok),
                    ids, num_clients)
                new_quarantine = jnp.maximum(
                    state.quarantine - ok.astype(jnp.int32), 0
                ).at[bad_ids].set(jnp.int32(cfg.quarantine_rounds),
                                  mode="drop")
            else:
                new_client_last = state.client_last_round.at[scatter_ids].set(
                    state.round_idx, mode="drop")
                new_quarantine = state.quarantine
            # a guarded round transferred nothing (the reference returns
            # before accumulating the breaching round's bytes,
            # cv_train.py:222-226)
            download_bytes = 4.0 * download_floats * okf

        with phase("server_update"):
            new_state = FedState(
                weights=new_w, opt=new_opt, clients=new_clients,
                round_idx=state.round_idx + ok.astype(jnp.int32),
                last_changed=new_last_changed,
                client_last_round=new_client_last,
                aborted=jnp.logical_or(state.aborted, breach),
                weights_version=state.weights_version + ok.astype(jnp.int32),
                quarantine=new_quarantine,
                buffer=state.buffer,
            )
            metrics = {
                "loss_sum": loss_total,
                "metric_sums": metric_totals,
                "num_datapoints": total_n,
                # host-visible guard state: the ONLY reliable abort signal for
                # a training loop (a later pipelined round's loss can look
                # healthy again because the guard froze the weights)
                "aborted": jnp.logical_or(state.aborted, breach),
                "download_bytes": download_bytes,
                # (a guarded round uploaded nothing either)
                "upload_bytes": (4.0 * cfg.upload_floats_per_client *
                                 jnp.sum(pull_w.astype(jnp.float32)) * okf),
                "update_l2": jnp.linalg.norm(update),
            }
            if cfg.client_quarantine:
                # observability for the degraded path (gated so the legacy
                # metric pytree — and every trajectory test built on it —
                # is unchanged when the flag is off)
                metrics["dropped_contributions"] = jnp.sum(
                    jnp.logical_and(pull_w, ~finite_w).astype(jnp.float32)
                ) * okf
                metrics["num_quarantined"] = jnp.sum(
                    (new_quarantine > 0).astype(jnp.int32))
        if offload:
            return new_state, out_rows, metrics
        return new_state, metrics

    def _check_mesh(mesh):
        n_shards = mesh.shape["clients"]
        if cfg.num_workers % n_shards:
            raise ValueError(
                f"num_workers ({cfg.num_workers}) must be divisible by "
                f"the mesh 'clients' axis size ({n_shards})")
        if cfg.num_clients % n_shards:
            raise ValueError(
                f"num_clients ({cfg.num_clients}) must be divisible by "
                f"the mesh 'clients' axis size ({n_shards})")

    if offload:
        if mesh is None:
            # rows (argnum 1) donated too: the W-leading encoded input
            # buffer — 2 GB at gpt2-small W=4 dense — is reused for the
            # output rows
            jitted = jax.jit(round_core, donate_argnums=(0, 1))
        else:
            # mesh + offload: the host arenas are block-partitioned along
            # the 'clients' axis (client_store.HostArenaStore matches
            # jax's leading-dim block sharding), and the gathered rows
            # enter the program sharded over the worker axis like the
            # batch — each shard's devices consume the rows its own host
            # arena produced
            from commefficient_tpu.parallel.mesh import (
                batch_shardings, client_rows_shardings,
                fed_state_shardings)
            _check_mesh(mesh)
            state_sh = fed_state_shardings(cfg, mesh)
            rows_sh = client_rows_shardings(cfg, mesh)
            ids_sh, cols_sh, mask_sh = batch_shardings(mesh)
            jitted = jax.jit(
                round_core, donate_argnums=(0, 1),
                in_shardings=(state_sh, rows_sh, ids_sh, cols_sh,
                              mask_sh, None, None)
                + ((ids_sh,) if het_k else ()),
                out_shardings=(state_sh, rows_sh, None))
        jitted.offload = True
        jitted.raw = round_core   # traceable without donation (analysis/)
        return jitted

    def round_step(state, client_ids, batch, mask, lr, rng, *ks):
        return round_core(state, None, client_ids, batch, mask, lr, rng,
                          *ks)

    if mesh is None:
        jitted = jax.jit(round_step, donate_argnums=0)
        jitted.raw = round_step   # for lax.scan composition (api.py
        return jitted             # train_rounds_scan)

    from commefficient_tpu.parallel.mesh import (batch_shardings,
                                                 fed_state_shardings)
    _check_mesh(mesh)
    state_sh = fed_state_shardings(cfg, mesh)
    ids_sh, cols_sh, mask_sh = batch_shardings(mesh)
    jitted = jax.jit(
        round_step, donate_argnums=0,
        in_shardings=(state_sh, ids_sh, cols_sh, mask_sh, None, None)
        + ((ids_sh,) if het_k else ()),
        out_shardings=(state_sh, None))
    jitted.raw = round_step
    return jitted


def build_eval_step(apply_loss: Callable, unflatten: Callable) -> Callable:
    """Jitted centralized validation step (ref _call_val
    fed_aggregator.py:337-364; no client grouping, client_id == -1)."""

    @jax.jit
    def eval_step(weights, batch, mask, rng):
        loss_sum, metric_sums, n = client_lib.eval_step(
            apply_loss, unflatten, weights, batch, mask, rng)
        return {"loss_sum": loss_sum, "metric_sums": metric_sums,
                "num_datapoints": n}

    return eval_step
