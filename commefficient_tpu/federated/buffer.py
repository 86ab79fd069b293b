"""Buffered asynchronous aggregation (FedBuff) under the seeded fault model.

The sync round (federated/round.py) is a lock-step barrier: the server
waits for every sampled client before applying, so ONE straggler or
dropout stalls the whole cohort. FedBuff (Nguyen et al., AISTATS 2022;
deployed at scale as Papaya, Huba et al. MLSys 2022) removes the barrier:
contributions land in an M-slot buffer as they arrive, and the server
applies whenever M have accumulated, scaling each by its staleness
``s(tau) = 1 / (1 + tau)^alpha`` where ``tau = weights_version -
start_version`` is how many server applies happened since that client
pulled.

The sync round's one-jitted-program shape survives the split into three
programs over the same client step:

* ``cohort``  — vmap the W sampled clients' local steps against the
  CURRENT weights and emit their contributions as a W-slot
  ``BufferState`` (plus cohort-level loss/metric sums for reporting).
  Pure w.r.t. server state: nothing is donated, nothing applied.
* ``deposit`` — scatter an arrived subset of a cohort's slots into the
  server's M-slot buffer (donated). WHICH slots arrive, and when, is the
  host event loop's business (``BufferedFedLearner``), driven by the
  seeded ``FaultModel`` — the device program only ever sees a boolean
  take-mask, so a fault schedule replays bit-identically from its seed.
* ``apply``   — staleness-weighted aggregate of the filled slots, server
  update, deferred client-row writeback, byte accounting, buffer reset
  (donated, like the sync round).

Bit-identity contract (tests/test_buffered.py): with no fault model and
alpha = 0, the fused lock-step program (cohort -> apply in ONE jit, see
``lockstep_core``) IS the sync round — same vmap, same rng chain
(fold_in(rng, id) per client; fold_in(rng, 0x5e77e7) for server noise),
same reduction ops over slots in worker order, client rows written at
apply with the same ok-gating — so the trajectory matches the sync
learner bit-for-bit, including through padded epoch tails and a NaN
abort. (Fused, not split: XLA's fusion decisions shift at jit boundaries
and cost ~1 ulp in the loss reduction otherwise.)

Per-client NaN quarantine (cfg.client_quarantine) composes: a non-finite
contribution is excluded at apply (jnp.where — NaN * 0 is NaN) and its
client benched for quarantine_rounds applies; only a post-exclusion
server-side breach trips the sticky global abort.

Mesh-native: with a ``--mesh``, all four programs are pjit programs over
the ``clients`` axis — cohort compute shards the W sampled clients across
data-parallel devices exactly as the sync round does, contributions
deposit into a SHARDED buffer (every slot-leading leaf splits its slot
dim over the axis, so each shard owns its own slot rows and no ``(W, d)``
or ``(M, d)`` aval is ever replicated — the ``buffered_mesh`` graft-audit
target enforces this), and the staleness-weighted apply's slot reduction
is the same implicit psum the sync round's worker reduce lowers to. The
HOST event loop stays exactly where it was: heap order, fate draws, and
take-masks are device-count-independent, which is what keeps the event
cursor SIGKILL-resumable on a mesh (docs/ROBUSTNESS.md). The loop itself
is NOT training-only: it is externally steppable (``pump_events``
delivers due arrivals without dispatching a cohort), which is how the
train-while-serve driver (commefficient_tpu/online/loop.py) interleaves
buffered cohorts with the continuous-batching server's decode steps on
one host loop — two program families sharing a process, never a jit
program.

Host-offloaded client state (cfg.client_state_offload) composes too:
cohorts gather the sampled rows from the per-shard host arenas through
the owner-routing offload pipeline (exactly like the sync round's
offload path), updated rows ride the contribution slots, and the host
writes them back into the arenas at APPLY time — deferred writeback,
the same visibility semantics as device-resident buffered state, where
rows also land in client state only when the buffer applies.
"""

from __future__ import annotations

import heapq
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated import client as client_lib
from commefficient_tpu.federated.api import FedLearner, _dispatch_guard
from commefficient_tpu.federated.client_store import (gather_rows,
                                                      make_codec,
                                                      scatter_rows)
from commefficient_tpu.federated.faults import FaultModel
from commefficient_tpu.federated.round import FedState, download_counts
from commefficient_tpu.federated.server import make_sketch, server_update
from commefficient_tpu.federated.state import BufferState, ClientState
from commefficient_tpu.utils.tracing import (phase, round_enqueued,
                                             round_mark, span)


def build_buffer_programs(apply_loss: Callable, unflatten: Callable,
                          cfg: FedConfig,
                          trainable_mask: Optional[jax.Array] = None,
                          mesh=None):
    """Build the (cohort, deposit, apply) jitted programs for this config.

    Returns ``(cohort_fn, deposit_fn, apply_fn, lockstep_fn)``:

        cohort_fn(state, [rows,] ids (W,), batch (W,B,...), mask (W,B),
                  lr, rng[, client_ks (W,)])
            -> (BufferState with W slots, cohort metric dict)
        deposit_fn(buffer (M slots), contrib (W slots), take (W,) bool)
            -> new buffer     [buffer donated]
        apply_fn(state, lr, rng) -> (new state, apply metric dict)
                                  [state donated]
        lockstep_fn(state, [rows,] ids, batch, mask, lr, rng[, client_ks])
            -> (new state, merged metric dict)   [state donated]

    The optional arguments are static per-config: ``rows`` (a W-leading
    encoded ClientState) appears iff client state is host-offloaded —
    apply/lockstep then additionally return a ``(writeback_ids (M,),
    writeback rows)`` element between state and metrics, the deferred
    arena writeback the host pushes through its offload pipeline — and
    ``client_ks`` appears iff cfg.client_k_dist is set.

    With a ``mesh``, all four are pjit programs: state/buffer per
    ``fed_state_shardings``/``buffer_state_shardings``, batch and
    take-mask worker-sharded over the ``clients`` axis, lr/rng
    replicated. The caller must pass the SAME mesh the learner's state is
    sharded on; num_workers, num_clients AND buffer_m must divide the
    axis (each shard owns its own slot rows).

    Each carries an un-donated ``.raw`` for analysis/ tracing.
    """
    cfg.validate()
    if cfg.server_mode != "buffered":
        raise ValueError("build_buffer_programs needs server_mode="
                         f"'buffered', got {cfg.server_mode!r}")
    M = cfg.effective_buffer_m
    # client rows live in codec-encoded storage (client_store.make_codec);
    # buffer SLOTS stay dense — M is small — and rows encode only on the
    # scatter back into client state at apply (or, under offload, on the
    # writeback rows handed to the host at apply)
    codec = make_codec(cfg)
    sketch = make_sketch(cfg) if cfg.mode == "sketch" else None
    is_fedavg = cfg.mode == "fedavg"
    offload = cfg.client_state_offload and cfg.has_client_state
    host_codec = offload and codec.host_side_offload
    het_k = cfg.client_k_active
    # same linearity fast path as the sync round: sketch once per APPLY
    # instead of once per client when no per-worker nonlinearity exists
    sketch_after_aggregate = (cfg.mode == "sketch" and not cfg.do_dp
                              and cfg.max_grad_norm is None)
    client_sketch = None if sketch_after_aggregate else sketch
    if trainable_mask is not None:
        trainable_mask = jnp.asarray(trainable_mask, jnp.float32)

    from commefficient_tpu.parallel.mesh import on_each_replica
    if mesh is not None:
        from commefficient_tpu.parallel.mesh import (
            batch_shardings, buffer_state_shardings,
            client_rows_shardings, fed_state_shardings)
        n_shards = mesh.shape["clients"]
        for name, val in (("num_workers", cfg.num_workers),
                          ("num_clients", cfg.num_clients),
                          ("buffer_m", M)):
            if val % n_shards:
                raise ValueError(
                    f"{name} ({val}) must be divisible by the mesh "
                    f"'clients' axis size ({n_shards}) — buffered slot "
                    f"rows shard over that axis (each shard owns its "
                    f"own slots)")
        state_sh = fed_state_shardings(cfg, mesh)
        buf_sh = buffer_state_shardings(cfg, mesh)
        state_buf_sh = state_sh.replace(buffer=buf_sh)
        ids_sh, cols_sh, mask_sh = batch_shardings(mesh)

        def _pin(buf: BufferState) -> BufferState:
            # in-program slot-sharding pins: the deposit chain is where a
            # replicated (M, d)/(W, d) buffer aval would sneak in, and
            # these constraints are what the buffered_mesh graft-audit
            # rule keys on (analysis/rules.ShardedBufferRule). Deposit
            # only — the fused lockstep stays constraint-free so XLA's
            # fusion decisions match the sync round's (the bitwise
            # lock-step contract).
            return jax.tree.map(jax.lax.with_sharding_constraint,
                                buf, buf_sh)
    else:
        def _pin(buf: BufferState) -> BufferState:
            return buf

    def one_client(ps_w, batch, mask, vel, err, stale, lr, rng, ck=None):
        if is_fedavg:
            return client_lib.fedavg_client_step(
                apply_loss, unflatten, ps_w, batch, mask, lr, rng, cfg,
                trainable_mask=trainable_mask)
        return client_lib.client_step(
            apply_loss, unflatten, ps_w, batch, mask, vel, err, stale,
            rng, cfg, client_sketch, trainable_mask=trainable_mask,
            client_k=ck)

    def cohort_core(state: FedState, rows, client_ids, batch, mask, lr,
                    rng, client_ks=None):
        w = state.weights
        ids = client_ids
        W = ids.shape[0]
        valid_w = jnp.any(mask > 0, axis=1)                         # (W,)
        num_clients = state.client_last_round.shape[0]
        if cfg.client_quarantine:
            benched_w = state.quarantine[ids] > 0
            alive_w = jnp.logical_and(valid_w, ~benched_w)
        else:
            alive_w = valid_w

        # download accounting snapshot: counts vs the weights the client
        # pulls NOW; billed at apply time (gated by that apply's ok)
        stale_round = state.client_last_round[ids]
        with phase("download_accounting"):
            counts = download_counts(state.last_changed, stale_round)

        if offload:
            # sampled rows arrive host-gathered (owner-routed through the
            # per-shard arenas), dense under a host-side codec — the same
            # wire contract as round.round_core's offload branch
            def _dec(enc):
                if enc is None or host_codec:
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
        with phase("client_grad"):
            if client_ks is not None:
                out = jax.vmap(one_client, in_axes=axes + (0,))(
                    w, batch, mask, vels, errs, stales, lr, rngs,
                    client_ks)
            else:
                out = jax.vmap(one_client, in_axes=axes)(
                    w, batch, mask, vels, errs, stales, lr, rngs)

        contrib = BufferState(
            transmit=out.transmit,
            loss_sum=out.loss_sum,
            metric_sums=out.metric_sums,
            num_datapoints=out.num_datapoints,
            download_floats=(counts * alive_w.astype(jnp.int32)
                             ).astype(jnp.float32),
            cid=jnp.where(alive_w, ids.astype(jnp.int32),
                          jnp.int32(num_clients)),     # OOB => dropped
            start_version=jnp.broadcast_to(state.weights_version, (W,)),
            valid=alive_w,
            count=jnp.zeros((), jnp.int32),
            velocities=out.velocity,
            errors=out.error,
            weights=out.client_weights,
        )
        # cohort-level reporting sums, masked the same way the sync round
        # reports them: with quarantine ON, excluded slots are where-masked
        # out; OFF, the sums are the sync round's EXACT ops — plain sums
        # over all slots (padded slots are exact zeros, a NaN slot flows
        # through to the global guard). The op-for-op match matters: a
        # where between the per-batch and per-cohort reduction stages
        # blocks the reduction fusion XLA applies to the sync program, and
        # costs the lock-step loss metric its bitwise equality (1 ulp).
        if cfg.client_quarantine:
            finite_w = jnp.logical_and(
                jnp.isfinite(out.loss_sum),
                jnp.all(jnp.isfinite(
                    out.transmit.reshape((W, -1))), axis=1))
            report_w = jnp.logical_and(alive_w, finite_w)
            cmetrics = {
                "loss_sum": jnp.sum(
                    jnp.where(report_w, out.loss_sum, 0.0)),
                "metric_sums": jnp.sum(
                    jnp.where(report_w[:, None], out.metric_sums, 0.0),
                    axis=0),
                "num_datapoints": jnp.sum(
                    jnp.where(report_w, out.num_datapoints, 0.0)),
            }
        else:
            cmetrics = {
                "loss_sum": jnp.sum(out.loss_sum),
                "metric_sums": jnp.sum(out.metric_sums, axis=0),
                "num_datapoints": jnp.sum(out.num_datapoints),
            }
        return contrib, cmetrics

    def deposit_core(buf: BufferState, contrib: BufferState, take):
        """Scatter taken cohort slots into the next free buffer slots, in
        worker order. ``take`` is the host's arrival mask; invalid slots
        (padded tails, benched clients — device knowledge the host lacks)
        drop out here, so the host's count mirror must re-read
        ``buf.count``. The caller guarantees popcount(take) <= M - count;
        overflow slots would silently OOB-drop."""
        contrib = _pin(contrib)
        take_eff = jnp.logical_and(take, contrib.valid)
        ti = take_eff.astype(jnp.int32)
        slots = jnp.where(take_eff, buf.count + jnp.cumsum(ti) - 1,
                          jnp.int32(M))                 # OOB => dropped

        def put(dst, src):
            if dst is None or src is None:
                return dst
            return dst.at[slots].set(src, mode="drop")

        return _pin(BufferState(
            transmit=put(buf.transmit, contrib.transmit),
            loss_sum=put(buf.loss_sum, contrib.loss_sum),
            metric_sums=put(buf.metric_sums, contrib.metric_sums),
            num_datapoints=put(buf.num_datapoints, contrib.num_datapoints),
            download_floats=put(buf.download_floats,
                                contrib.download_floats),
            cid=put(buf.cid, contrib.cid),
            start_version=put(buf.start_version, contrib.start_version),
            valid=buf.valid.at[slots].set(True, mode="drop"),
            count=buf.count + jnp.sum(ti),
            velocities=put(buf.velocities, contrib.velocities),
            errors=put(buf.errors, contrib.errors),
            weights=put(buf.weights, contrib.weights),
        ))

    def apply_core(state: FedState, lr, rng):
        buf = state.buffer
        w = state.weights
        num_clients = state.client_last_round.shape[0]
        Mv = buf.valid.shape[0]
        vmask = jnp.logical_and(
            buf.valid, jnp.arange(Mv, dtype=jnp.int32) < buf.count)
        if cfg.client_quarantine:
            # per-contribution exclusion (jnp.where, never a multiply:
            # NaN * 0 is NaN) — one poisoned client degrades the apply,
            # it doesn't abort the run
            finite_b = jnp.logical_and(
                jnp.isfinite(buf.loss_sum),
                jnp.all(jnp.isfinite(
                    buf.transmit.reshape((Mv, -1))), axis=1))
            contrib_b = jnp.logical_and(vmask, finite_b)
        else:
            contrib_b = vmask

        tau = jnp.maximum(state.weights_version - buf.start_version, 0)
        if cfg.staleness_alpha == 0.0:
            # static branch: no 1.0-multiplies between the buffered and
            # sync dataflow, so the lock-step equivalence is bitwise
            wt_t, wt_n = buf.transmit, buf.num_datapoints
        else:
            s = jnp.power(1.0 + tau.astype(jnp.float32),
                          -cfg.staleness_alpha)                     # (M,)
            wt_t = s.reshape((-1,) + (1,) * (buf.transmit.ndim - 1)
                             ) * buf.transmit
            wt_n = s * buf.num_datapoints
        cb = contrib_b.reshape((-1,) + (1,) * (buf.transmit.ndim - 1))
        total_n = jnp.sum(jnp.where(contrib_b, wt_n, 0.0))
        agg = (jnp.sum(jnp.where(cb, wt_t, 0.0), axis=0) /
               jnp.maximum(total_n, 1.0))
        # server-side breach check on the UNWEIGHTED post-exclusion loss
        # (staleness scaling is an aggregation rule, not a health metric)
        loss_total = jnp.sum(jnp.where(contrib_b, buf.loss_sum, 0.0))
        n_raw = jnp.sum(jnp.where(contrib_b, buf.num_datapoints, 0.0))
        loss_mean = loss_total / jnp.maximum(n_raw, 1.0)
        if sketch_after_aggregate:
            # aggregate-side sketch via the batch-guard dispatch at batch
            # 1: same 2-D grid kernel as the per-worker vmapped paths,
            # bitwise-identical to the unbatched call — and identical to
            # round.py's sync-path call site, which keeps the buffered
            # lockstep trajectory pinned bit-equal to sync
            with phase("compress"):
                agg = on_each_replica(
                    mesh,
                    lambda a: sketch.sketch_vec_batched(a, True))(agg)

        breach = jnp.logical_or(~jnp.isfinite(loss_mean),
                                loss_mean > cfg.nan_threshold)
        ok = jnp.logical_and(~breach, ~state.aborted)
        okf = ok.astype(jnp.float32)

        server_lr = 1.0 if is_fedavg else lr
        noise_rng = jax.random.fold_in(rng, 0x5e77e7)
        with phase("server_update"):
            update, new_opt = server_update(
                agg, state.opt, cfg, server_lr, sketch=sketch,
                noise_rng=noise_rng, mesh=mesh)
        if trainable_mask is not None:
            update = update * trainable_mask
        # select, not multiply: NaN * 0 = NaN (mirrors round.round_core)
        update = jnp.where(ok, update, 0.0)
        if cfg.grad_dim != cfg.grad_size:
            update = update.at[cfg.grad_size:].set(0.0)
        new_opt = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                               new_opt, state.opt)
        new_w = w - update

        # deferred client-row writeback: rows computed at cohort time land
        # in client state only when their contribution is applied, with
        # the same contrib & ok gating as the sync scatter
        new_vels = buf.velocities
        if (cfg.mode == "true_topk" and cfg.local_momentum > 0
                and new_vels is not None):
            support = (update != 0)[None, :]
            new_vels = jnp.where(support, 0.0, new_vels)
        scatter_ids = jnp.where(jnp.logical_and(contrib_b, ok), buf.cid,
                                jnp.int32(num_clients))
        if offload:
            # deferred arena writeback: rows ride the buffer slots dense
            # and leave the program here, gated by the same contrib & ok
            # mask as the device scatter (dropped slots carry the
            # num_clients OOB sentinel id, which the host pipeline
            # skips). Non-host codecs (sketched) re-encode in-program,
            # the host writes the encoding verbatim.
            def _enc(dense):
                if dense is None:
                    return None
                return dense if host_codec else codec.encode_rows(dense)
            writeback = (scatter_ids,
                         ClientState(velocities=_enc(new_vels),
                                     errors=_enc(buf.errors),
                                     weights=_enc(buf.weights)))
            new_clients = state.clients
        else:
            writeback = None
            new_clients = ClientState(
                velocities=scatter_rows(state.clients.velocities,
                                        scatter_ids, new_vels, codec),
                errors=scatter_rows(state.clients.errors, scatter_ids,
                                    buf.errors, codec),
                weights=scatter_rows(state.clients.weights, scatter_ids,
                                     buf.weights, codec),
            )

        # stamps are in APPLY (version) units, same axis the download
        # comparison runs on: a weight changed at version u was unseen by
        # a client that pulled at version v iff u >= v — the sync round's
        # invariant with round_idx replaced by weights_version (they are
        # the same counter in lock-step)
        new_last_changed = jnp.where(update != 0, state.weights_version,
                                     state.last_changed)
        if cfg.client_quarantine:
            pull_ids = jnp.where(jnp.logical_and(vmask, ok), buf.cid,
                                 jnp.int32(num_clients))
            new_client_last = state.client_last_round.at[pull_ids].set(
                buf.start_version, mode="drop")
            bad_ids = jnp.where(
                jnp.logical_and(jnp.logical_and(vmask, ~finite_b), ok),
                buf.cid, jnp.int32(num_clients))
            new_quarantine = jnp.maximum(
                state.quarantine - ok.astype(jnp.int32), 0
            ).at[bad_ids].set(jnp.int32(cfg.quarantine_rounds),
                              mode="drop")
        else:
            new_client_last = state.client_last_round.at[scatter_ids].set(
                buf.start_version, mode="drop")
            new_quarantine = state.quarantine

        reset = BufferState(
            transmit=jnp.zeros_like(buf.transmit),
            loss_sum=jnp.zeros_like(buf.loss_sum),
            metric_sums=jnp.zeros_like(buf.metric_sums),
            num_datapoints=jnp.zeros_like(buf.num_datapoints),
            download_floats=jnp.zeros_like(buf.download_floats),
            cid=jnp.full_like(buf.cid, num_clients),
            start_version=jnp.zeros_like(buf.start_version),
            valid=jnp.zeros_like(buf.valid),
            count=jnp.zeros_like(buf.count),
            velocities=(None if buf.velocities is None
                        else jnp.zeros_like(buf.velocities)),
            errors=(None if buf.errors is None
                    else jnp.zeros_like(buf.errors)),
            weights=(None if buf.weights is None
                     else jnp.zeros_like(buf.weights)),
        )
        new_state = FedState(
            weights=new_w, opt=new_opt, clients=new_clients,
            round_idx=state.round_idx + ok.astype(jnp.int32),
            last_changed=new_last_changed,
            client_last_round=new_client_last,
            aborted=jnp.logical_or(state.aborted, breach),
            weights_version=state.weights_version + ok.astype(jnp.int32),
            quarantine=new_quarantine,
            buffer=reset,
        )
        download_floats = jnp.sum(
            jnp.where(vmask, buf.download_floats, 0.0))
        nf = jnp.float32
        ametrics = {
            "aborted": jnp.logical_or(state.aborted, breach),
            "download_bytes": 4.0 * download_floats * okf,
            "upload_bytes": (4.0 * cfg.upload_floats_per_client *
                             jnp.sum(vmask.astype(nf)) * okf),
            "update_l2": jnp.linalg.norm(update),
            "applied": okf,
            "buffer_fill": buf.count.astype(nf),
            "staleness_mean": (jnp.sum(jnp.where(
                contrib_b, tau.astype(nf), 0.0)) /
                jnp.maximum(jnp.sum(contrib_b.astype(nf)), 1.0)),
        }
        if cfg.client_quarantine:
            ametrics["dropped_contributions"] = jnp.sum(
                jnp.logical_and(vmask, ~finite_b).astype(nf)) * okf
            ametrics["num_quarantined"] = jnp.sum(
                (new_quarantine > 0).astype(jnp.int32))
        if offload:
            return new_state, writeback, ametrics
        return new_state, ametrics

    def lockstep_core(state: FedState, rows, client_ids, batch, mask, lr,
                      rng, client_ks=None):
        """cohort -> apply fused in ONE program, the no-fault-model path:
        every contribution arrives instantly and the server applies each
        cohort, so the transient W-slot buffer never leaves the jit
        (state.buffer stays None). Fusing matters beyond dispatch count:
        compiled as one program, XLA makes the same fusion decisions it
        makes for the sync round, which is what turns the M=W, alpha=0
        equivalence from allclose into assert_array_equal — on a mesh as
        much as single-chip (same shardings, same op structure, one jit;
        no sharding constraints are pinned inside this path)."""
        contrib, cm = cohort_core(state, rows, client_ids, batch, mask,
                                  lr, rng, client_ks)
        W = client_ids.shape[0]
        st = state.replace(buffer=contrib.replace(count=jnp.int32(W)))
        if offload:
            new_state, wb, am = apply_core(st, lr, rng)
            return new_state.replace(buffer=None), wb, {**cm, **am}
        new_state, am = apply_core(st, lr, rng)
        return new_state.replace(buffer=None), {**cm, **am}

    # public signatures: rows / client_ks appear iff their feature is on
    # (static per-config — ONE pytree structure per program, so each
    # program compiles exactly once across the event loop)
    if offload:
        def cohort_pub(state, rows, ids, batch, mask, lr, rng, *ks):
            return cohort_core(state, rows, ids, batch, mask, lr, rng,
                               *ks)

        def lockstep_pub(state, rows, ids, batch, mask, lr, rng, *ks):
            return lockstep_core(state, rows, ids, batch, mask, lr, rng,
                                 *ks)
    else:
        def cohort_pub(state, ids, batch, mask, lr, rng, *ks):
            return cohort_core(state, None, ids, batch, mask, lr, rng,
                               *ks)

        def lockstep_pub(state, ids, batch, mask, lr, rng, *ks):
            return lockstep_core(state, None, ids, batch, mask, lr, rng,
                                 *ks)

    if mesh is None:
        # cohort is NOT donated: its inputs (state) stay live for
        # deposit/apply
        cohort_fn = jax.jit(cohort_pub)
        deposit_fn = jax.jit(deposit_core, donate_argnums=0)
        apply_fn = jax.jit(apply_core, donate_argnums=0)
        lockstep_fn = jax.jit(lockstep_pub, donate_argnums=0)
    else:
        batch_in = (ids_sh, cols_sh, mask_sh)
        rows_in = (client_rows_shardings(cfg, mesh),) if offload else ()
        ks_in = (ids_sh,) if het_k else ()
        slot_sh = buf_sh.cid   # any (M,)/(W,)-leading slot sharding
        wb_out = (((slot_sh, client_rows_shardings(cfg, mesh)),)
                  if offload else ())
        cohort_fn = jax.jit(
            cohort_pub,
            in_shardings=(state_sh,) + rows_in + batch_in
            + (None, None) + ks_in,
            out_shardings=(buf_sh, None))
        deposit_fn = jax.jit(
            deposit_core, donate_argnums=0,
            in_shardings=(buf_sh, buf_sh, ids_sh),
            out_shardings=buf_sh)
        apply_fn = jax.jit(
            apply_core, donate_argnums=0,
            in_shardings=(state_buf_sh, None, None),
            out_shardings=(state_buf_sh,) + wb_out + (None,))
        lockstep_fn = jax.jit(
            lockstep_pub, donate_argnums=0,
            in_shardings=(state_sh,) + rows_in + batch_in
            + (None, None) + ks_in,
            out_shardings=(state_sh,) + wb_out + (None,))
    cohort_fn.raw = cohort_pub
    deposit_fn.raw = deposit_core
    apply_fn.raw = apply_core
    lockstep_fn.raw = lockstep_pub
    return cohort_fn, deposit_fn, apply_fn, lockstep_fn


def init_buffer(contrib: BufferState, m: int,
                num_clients: int) -> BufferState:
    """An empty M-slot buffer shaped off a cohort's concrete contribution
    (slot 0 of each array gives the per-slot shape/dtype)."""

    def grow(x):
        return (None if x is None
                else jnp.zeros((m,) + x.shape[1:], x.dtype))

    return BufferState(
        transmit=grow(contrib.transmit),
        loss_sum=grow(contrib.loss_sum),
        metric_sums=grow(contrib.metric_sums),
        num_datapoints=grow(contrib.num_datapoints),
        download_floats=grow(contrib.download_floats),
        cid=jnp.full((m,), num_clients, jnp.int32),
        start_version=jnp.zeros((m,), jnp.int32),
        valid=jnp.zeros((m,), bool),
        count=jnp.zeros((), jnp.int32),
        velocities=grow(contrib.velocities),
        errors=grow(contrib.errors),
        weights=grow(contrib.weights),
    )


def _merge_apply(a: Optional[dict], b: Optional[dict]) -> Optional[dict]:
    """Roll up apply metrics when one host call triggers several applies:
    bytes/counts sum, point-in-time values (aborted, update_l2, staleness)
    take the latest. A single apply passes through untouched — no
    arithmetic on the device scalars, preserving lock-step bit-identity."""
    if a is None:
        return b
    if b is None:
        return a
    out = dict(b)
    for k in ("download_bytes", "upload_bytes", "applied",
              "dropped_contributions"):
        if k in a and k in b:
            out[k] = a[k] + b[k]
    return out


class BufferedFedLearner(FedLearner):
    """FedLearner whose server runs FedBuff-style buffered aggregation.

    The host side is a deterministic event loop over simulated time:

    * cohort k is dispatched at ``D_k = k * dispatch_interval``
    * each sampled client's fate (dropout / crash / arrival latency) comes
      from the seeded ``FaultModel`` — or, with ``fault_model=None``, every
      valid client arrives instantly and each call runs the fused
      cohort->apply lock-step program (the sync-equivalent mode the
      trajectory test pins down bitwise)
    * arrivals scheduled in a heap are delivered IN ARRIVAL-TIME ORDER
      before dispatching any later cohort, so the buffer fills exactly as
      it would in wall-clock reality; the server applies whenever
      ``buffer_m`` contributions have landed
    * ``sim_time`` advances to each apply's trigger arrival — the
      simulated wall-clock results.py budgets against

    Determinism: fates are pure functions of (seed, cohort, client) and
    deposits happen in heap order with a monotone tiebreak, so the same
    seed replays the same buffer schedule bit-for-bit — and because none
    of (heap order, fate draws, take-masks) depends on the device count,
    the schedule is the SAME on a mesh: sharding the cohort compute and
    the buffer slots over the 'clients' axis changes where slot rows
    live, never which slot an arrival lands in. The event cursor
    therefore stays SIGKILL-resumable at any dp (tests/test_preemption).
    """

    def __init__(self, module, cfg: FedConfig, loss_train,
                 loss_val, rng, sample_input, lr_schedule=None,
                 mesh=None, init_params=None, trainable_mask=None,
                 lr_scale_vec=None, param_specs=None,
                 fault_model: Optional[FaultModel] = None,
                 dispatch_interval: Optional[float] = None):
        if cfg.server_mode != "buffered":
            raise ValueError("BufferedFedLearner needs cfg.server_mode="
                             f"'buffered', got {cfg.server_mode!r}")
        super().__init__(module, cfg, loss_train, loss_val, rng,
                         sample_input, lr_schedule=lr_schedule, mesh=mesh,
                         init_params=init_params,
                         trainable_mask=trainable_mask,
                         lr_scale_vec=lr_scale_vec,
                         param_specs=param_specs)
        self.M = self.cfg.effective_buffer_m
        (self._cohort, self._deposit, self._apply,
         self._lockstep) = build_buffer_programs(
            self._loss_train, self._round_unflatten, self.cfg,
            trainable_mask=self._trainable_mask, mesh=mesh)
        if mesh is not None:
            from commefficient_tpu.parallel.mesh import (
                batch_shardings, buffer_state_shardings)
            self._buf_sh = buffer_state_shardings(self.cfg, mesh)
            self._take_sh = batch_shardings(mesh)[0]
        else:
            self._buf_sh = self._take_sh = None
        # the apply program marks dropped writeback slots with the OOB
        # client-count sentinel; host-side masking needs the same count
        self._sentinel_clients = int(self.state.client_last_round.shape[0])
        self.fault_model = fault_model
        self.dispatch_interval = float(
            dispatch_interval if dispatch_interval is not None
            else (fault_model.base_latency if fault_model else 1.0))
        self._events = []       # heap of (arrival_t, seq, contrib, worker)
        self._seq = 0           # monotone heap tiebreak (determinism)
        self._buf_count = 0     # host mirror, re-read after each deposit
        self._last_lr_in = None
        self._apply_rng = None
        self.cohorts_done = 0
        self.applies_done = 0
        self.sim_time = 0.0
        self.fault_stats = {"dispatched": 0, "dropouts": 0, "crashes": 0,
                            "arrivals": 0, "applies": 0,
                            "partial_applies": 0}

    # -- event loop ------------------------------------------------------

    def _push_writeback(self, wb):
        """Deferred host-arena writeback (offload only): the apply hands
        back (ids (M,), encoded rows); dropped/quarantined slots carry
        the OOB client-count sentinel id, masked out here. Routing each
        id to its owning shard's arena is the pipeline's job."""
        ids, rows = wb
        ids_np = np.asarray(jax.device_get(ids)).astype(np.int64)
        self._offload_pipe.push(ids_np, ids_np < self._sentinel_clients,
                                rows)

    def _do_apply(self, t: float) -> dict:
        with _dispatch_guard():
            if self._offload:
                self.state, wb, am = self._apply(
                    self.state, self._last_lr_in, self._apply_rng)
            else:
                self.state, am = self._apply(self.state, self._last_lr_in,
                                             self._apply_rng)
        if self._offload:
            self._push_writeback(wb)
        self._buf_count = 0
        self.applies_done += 1
        self.fault_stats["applies"] += 1
        self.sim_time = max(self.sim_time, float(t))
        return am

    def _deliver(self, contrib: BufferState, workers, t: float):
        """Deposit ``workers`` (cohort slot indices, in order) at sim time
        ``t``, applying whenever the buffer fills. Chunked pessimistically
        so a deposit can never overflow even if every candidate slot is
        valid; the count mirror re-reads the device count because invalid
        slots (padding, benched clients) are dropped device-side."""
        W = contrib.valid.shape[0]
        merged = None
        i = 0
        while i < len(workers):
            space = self.M - self._buf_count
            if space <= 0:
                merged = _merge_apply(merged, self._do_apply(t))
                continue
            chunk = workers[i:i + space]
            take = np.zeros(W, bool)
            take[chunk] = True
            # explicit placement BEFORE the guarded dispatch (mesh: the
            # take mask shards over 'clients' like the cohort ids)
            take_dev = (jnp.asarray(take) if self.mesh is None
                        else jax.device_put(take, self._take_sh))
            with _dispatch_guard():
                new_buf = self._deposit(self.state.buffer, contrib,
                                        take_dev)
            self.state = self.state.replace(buffer=new_buf)
            self._buf_count = int(new_buf.count)
            i += len(chunk)
            if self._buf_count >= self.M:
                merged = _merge_apply(merged, self._do_apply(t))
        return merged

    def _drain(self, upto: float):
        """Deliver every heaped arrival with t <= upto, in arrival order —
        contributions that land before a later cohort dispatches must be
        applied first (their applies advance weights_version, which is the
        staleness those later cohorts are judged against)."""
        merged = None
        while self._events and self._events[0][0] <= upto:
            t, _seq, contrib, worker = heapq.heappop(self._events)
            self.fault_stats["arrivals"] += 1
            merged = _merge_apply(merged, self._deliver(contrib, [worker],
                                                        t))
        return merged

    def _ensure_buffer(self, contrib: BufferState):
        if self.state.buffer is None:
            buf = init_buffer(contrib, self.M, self.cfg.num_clients)
            if self.mesh is not None:
                # committed slot-sharded placement up front: the deposit
                # donates the buffer, so every later buffer already sits
                # in this layout — placing the first one identically
                # keeps the deposit/apply compile caches at one entry
                buf = jax.device_put(buf, self._buf_sh)
            self.state = self.state.replace(buffer=buf)

    # -- FedLearner surface ----------------------------------------------

    def train_round_async(self, client_ids, batch, mask, epoch_frac=None,
                          next_client_ids=None):
        round_mark(self.rounds_done)
        with span("round.dispatch"):
            return self._dispatch_cohort(client_ids, batch, mask,
                                         epoch_frac, next_client_ids)

    def _dispatch_cohort(self, client_ids, batch, mask, epoch_frac,
                         next_client_ids):
        """Dispatch one COHORT (not one apply): local steps run against
        the current weights; whether/when contributions reach the buffer
        is the fault model's call. Returned metrics merge the cohort's
        loss/metric sums with whatever applies fired during this call
        (zeros when none did — e.g. every client straggling past the next
        dispatch)."""
        lr = self.lr_at(self.rounds_done if epoch_frac is None
                        else epoch_frac)
        self.rng, cohort_rng = jax.random.split(self.rng)
        ids = jnp.asarray(client_ids, jnp.int32)
        cols = tuple(jnp.asarray(t) for t in batch)
        m = jnp.asarray(mask, jnp.float32)
        if self.mesh is not None:
            ids_sh, cols_sh, mask_sh = self._batch_sh
            ids = jax.device_put(ids, ids_sh)
            cols = jax.device_put(cols, cols_sh)
            m = jax.device_put(m, mask_sh)
        lr_in = (jnp.float32(lr) if self.lr_scale_vec is None
                 else lr * self.lr_scale_vec)
        if self.mesh is not None:
            lr_in, cohort_rng = self._replicate(lr_in, cohort_rng)
        # applies triggered from here on use this cohort's rng/lr — in
        # lock-step mode that reproduces the sync round's noise chain
        self._last_lr_in = lr_in
        self._apply_rng = cohort_rng
        ks = ((self._client_ks(client_ids),) if self.cfg.client_k_active
              else ())

        def _gather_rows_arg():
            # host-gathered encoded rows, routed from each id's owning
            # shard arena — the sync offload round's wire contract; the
            # writeback is DEFERRED to whichever apply consumes the
            # slots. Must run AFTER any drain whose applies this cohort
            # should observe: an apply pushes fresher rows.
            if not self._offload:
                return ()
            return (self._offload_pipe.gather(
                np.asarray(client_ids).astype(np.int64)),)

        fm = self.fault_model
        self.fault_stats["dispatched"] += 1
        if fm is None:
            # lock-step: every contribution arrives instantly and the
            # server applies each cohort (padded tails included — sync
            # applies every round). One fused program, state donated like
            # the sync round; state.buffer stays None. Cross-cohort buffer
            # accumulation requires a fault model (a zero-fault FaultModel
            # works: every client arrives after one latency unit).
            rows_arg = _gather_rows_arg()
            with _dispatch_guard():
                out = self._lockstep(self.state, *rows_arg, ids, cols, m,
                                     lr_in, cohort_rng, *ks)
            round_enqueued(out[-1]["loss_sum"])
            if self._offload:
                self.state, wb, raw = out
                self._push_writeback(wb)
            else:
                self.state, raw = out
            raw = dict(raw)
            self.applies_done += 1
            self.fault_stats["applies"] += 1
        else:
            d_k = self.cohorts_done * self.dispatch_interval
            # causal order: arrivals due before this dispatch apply first
            # (their applies advance weights_version — the staleness this
            # cohort will eventually be judged against)
            am = self._drain(d_k)
            rows_arg = _gather_rows_arg()
            # buffer stripped from the cohort's input: the cohort never
            # reads it and is not donated, and ONE pytree structure
            # (buffer=None, first dispatch and every later one) keeps its
            # compile cache at a single entry
            with _dispatch_guard():
                contrib, cmetrics = self._cohort(
                    self.state.replace(buffer=None), *rows_arg, ids,
                    cols, m, lr_in, cohort_rng, *ks)
            round_enqueued(cmetrics["loss_sum"])
            self._ensure_buffer(contrib)
            valid_np = np.asarray(mask).any(axis=1)
            started, arrives, latency = fm.cohort_fates(
                self.cohorts_done, np.asarray(client_ids), valid_np)
            self.fault_stats["dropouts"] += int(
                (valid_np & ~started).sum())
            self.fault_stats["crashes"] += int((started & ~arrives).sum())
            for wk in np.nonzero(arrives)[0]:
                heapq.heappush(self._events,
                               (d_k + float(latency[wk]), self._seq,
                                contrib, int(wk)))
                self._seq += 1
            raw = dict(cmetrics)
            if am is None:
                zero = jnp.zeros((), jnp.float32)
                # COPY the abort flag: raw outlives this round inside
                # RoundPipeline, and a later drain's apply donates the
                # state buffer this leaf lives in — aliasing it here is a
                # deleted-array crash one round later
                raw.update({"aborted": jnp.copy(self.state.aborted),
                            "download_bytes": zero, "upload_bytes": zero,
                            "update_l2": zero})
            else:
                raw.update(am)

        if self._offload and next_client_ids is not None:
            self._offload_pipe.prefetch(
                np.asarray(next_client_ids).astype(np.int64))
        self.cohorts_done += 1
        self.rounds_done += 1
        raw["lr"] = lr
        return raw

    def pump_events(self, upto: Optional[float] = None):
        """Externally-driven event-loop stepping: deliver every arrival
        due by ``upto`` (default: the current dispatch clock,
        ``cohorts_done * dispatch_interval``) WITHOUT dispatching a
        cohort. This is the hook the train-while-serve driver
        (online/loop.py) calls between server decode steps, so buffered
        applies land at their scheduled sim times even while the host
        loop is busy serving. Byte totals from pumped applies accumulate
        directly (like flush_faults, they bypass
        finalize_round_metrics). Returns the merged apply metrics
        (host-side), or None when nothing was due."""
        if upto is None:
            upto = self.cohorts_done * self.dispatch_interval
        am = self._drain(float(upto))
        if am is None:
            return None
        out = jax.device_get(am)
        self.total_download_bytes += float(out["download_bytes"])
        self.total_upload_bytes += float(out["upload_bytes"])
        return out

    def event_cursor(self) -> dict:
        """Host event-loop position for checkpointing — the cursor the
        online serving loop rides into its mid-run checkpoints
        (training/preempt.py) as well as the training CLI's. In-flight
        heap entries and any partial buffer are deliberately transient
        (see utils/checkpoint.py: contributions are never saved) — the
        cursor is the dispatch clock the fault model's pure-function
        schedule replays from."""
        return {"cohorts_done": self.cohorts_done,
                "applies_done": self.applies_done,
                "sim_time": float(self.sim_time),
                "seq": self._seq}

    def restore_event_cursor(self, cur: dict) -> None:
        self.cohorts_done = int(cur["cohorts_done"])
        self.applies_done = int(cur["applies_done"])
        self.sim_time = float(cur["sim_time"])
        self._seq = int(cur["seq"])
        # a resume starts with an empty buffer and no in-flight arrivals
        # (checkpoint saves happen after flush points in the training
        # loop; anything still heaped at a hard kill is lost by contract)
        self._events = []
        self._buf_count = 0
        self._last_lr_in = None
        self._apply_rng = None

    def flush_faults(self, apply_partial: bool = True):
        """Drain every in-flight arrival and (optionally) apply whatever
        partial buffer remains — end-of-training barrier, the one place
        the buffered server waits. Byte totals from flush-triggered
        applies accumulate directly (they bypass finalize_round_metrics).
        Returns the merged host-side apply metrics, or None."""
        am = self._drain(np.inf)
        if apply_partial and self._buf_count > 0:
            self.fault_stats["partial_applies"] += 1
            am = _merge_apply(am, self._do_apply(self.sim_time))
        # offloaded rows: make the host arenas current too (pending
        # writebacks from the drained applies land now)
        self.flush_offload()
        if am is None:
            return None
        out = jax.device_get(am)
        self.total_download_bytes += float(out["download_bytes"])
        self.total_upload_bytes += float(out["upload_bytes"])
        return out

    def train_rounds_scan(self, *a, **k):
        raise NotImplementedError(
            "buffered mode dispatches cohorts through a host event loop; "
            "K-round scan windows are a sync-mode optimization")

    def scan_window(self, k: int):
        raise NotImplementedError(
            "buffered mode has no scan window (see train_rounds_scan)")
