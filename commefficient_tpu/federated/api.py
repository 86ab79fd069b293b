"""High-level federated training API.

The reference's user contract (reference cv_train.py:389-390):

    model = FedModel(model, compute_loss_train, args, compute_loss_val)
    opt   = FedOptimizer(opt, args)
    ...
    loss, acc, down, up = model(batch);  opt.step()

Here both wrappers collapse into one object, because there are no processes
to coordinate — state is explicit and the round is one jitted function:

    learner = FedLearner(module, cfg, loss_train, loss_val, rng, sample_input)
    metrics = learner.train_round(client_ids, batch, mask)   # one fed round
    metrics = learner.evaluate(batches)                      # centralized val
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.client_store import (HostArenaStore,
                                                      make_codec)
from commefficient_tpu.federated.round import (
    FedState, build_eval_step, build_round_step, init_fed_state)
from commefficient_tpu.federated.state import (CLIENT_STATE_FIELDS,
                                               ClientState,
                                               make_grad_buckets)
from commefficient_tpu.ops.countsketch import LANES
from commefficient_tpu.utils.params import flatten_params
from commefficient_tpu.utils.schedules import PiecewiseLinear
from commefficient_tpu.utils.tracing import (round_enqueued, round_mark,
                                             span)

# --------------------------------------------------------------------------
# Transfer guard around the round dispatch.
#
# The jitted round must never trigger an implicit host<->device transfer
# at call time: a python scalar or numpy array slipping into the dispatch
# serializes the async pipeline (and usually means a retrace is next).
# All conversions (jnp.asarray / device_put / the lr scalar) happen
# BEFORE the guarded region, so under "disallow" the dispatch itself is
# proven transfer-free.  conftest.py turns this on for the whole test
# suite; training entrypoints expose it as --transfer_guard (default
# disallow).  A module switch rather than a global jax.transfer_guard
# because a process-wide "disallow" would (correctly) reject ordinary
# host-side setup like jnp.zeros or device_get.
# --------------------------------------------------------------------------

_TRANSFER_GUARD_MODE = "allow"


def set_transfer_guard(mode: str) -> None:
    """Set the guard mode ('allow' | 'log' | 'disallow') applied around
    every jitted round dispatch (train_round_async / train_rounds_scan /
    evaluate)."""
    if mode not in ("allow", "log", "disallow"):
        raise ValueError(f"transfer_guard must be allow|log|disallow, "
                         f"got {mode!r}")
    global _TRANSFER_GUARD_MODE
    _TRANSFER_GUARD_MODE = mode


def transfer_guard_mode() -> str:
    return _TRANSFER_GUARD_MODE


def _dispatch_guard():
    return jax.transfer_guard(_TRANSFER_GUARD_MODE)


class FedLearner:
    def __init__(self, module, cfg: FedConfig, loss_train: Callable,
                 loss_val: Optional[Callable], rng: jax.Array,
                 sample_input, lr_schedule: Optional[Callable] = None,
                 mesh=None, init_params=None, trainable_mask=None,
                 lr_scale_vec=None, param_specs=None):
        self.module = module
        init_rng, self.rng = jax.random.split(rng)
        if init_params is None:
            variables = module.init(init_rng, sample_input, train=False)
            init_params = variables["params"]
        if callable(lr_scale_vec):
            # structure-derived multipliers (e.g. scalar_lr_multipliers)
            # need the param pytree, which may only exist here
            lr_scale_vec = lr_scale_vec(init_params)
        flat, unflatten = flatten_params(init_params)
        flat = flat.astype(jnp.float32)
        d_logical = flat.shape[0]
        pad_to = 1
        if mesh is not None and "model" in mesh.axis_names:
            # the flat vector is coordinate-split over the model axis, so
            # its physical length must divide evenly; pad coordinates are
            # invisible (unflatten slices them off, so they get no grads,
            # no decay, no updates) and never charged to byte accounting
            pad_to = mesh.shape["model"]
        self.cfg = cfg.finalize(d_logical, pad_to=pad_to)
        if self.cfg.grad_dim != d_logical:
            flat = jnp.pad(flat, (0, self.cfg.grad_dim - d_logical))
            base_unflatten = unflatten
            unflatten = lambda fp: base_unflatten(fp[:d_logical])  # noqa: E731
        self.unflatten = unflatten
        self.mesh = mesh
        self._weights_sh = None
        if mesh is not None:
            from commefficient_tpu.parallel.mesh import fed_state_shardings
            self._weights_sh = fed_state_shardings(self.cfg, mesh).weights
        self.state = init_fed_state(self.cfg, flat)   # placed by the setter
        # Host-offloaded client state (cfg.client_state_offload): the
        # momentum/error/weight rows live in mesh-sharded host arenas
        # (client_store.HostArenaStore) — the row space block-partitioned
        # along the mesh's 'clients' axis, each host shard owning its own
        # contiguous arena — stored in the run's --client_state encoding
        # (O(k) per row for sparse/sketched), and only the W sampled rows
        # move to device each round (round.build_round_step offload path).
        # Row movement runs through a double-buffered async pipeline
        # (HostOffloadPipeline): next-round gathers and last-round
        # writebacks overlap the current round's compute, with each id
        # routed to its owning shard's arena.
        self._offload = (self.cfg.client_state_offload
                         and self.cfg.has_client_state)
        self.codec = make_codec(self.cfg)
        self.host_clients = None
        self.host_store = None
        self._offload_pipe = None
        if self._offload:
            self._init_host_rows(flat)
            self._offload_pipe = HostOffloadPipeline(
                self, depth=self.cfg.offload_pipeline_depth)
            if mesh is None:
                # the pipeline hands the round COMMITTED row stacks; with
                # an uncommitted initial state the first round's outputs
                # (donated back as the next state) would flip to committed
                # and force a one-time recompile — commit up front so the
                # round compiles exactly once (analysis/ retrace guard)
                self.state = jax.device_put(self.state, self._s_dev)
        if mesh is not None:
            from commefficient_tpu.parallel.mesh import batch_shardings
            self._batch_sh = batch_shardings(mesh)
        round_unflatten = unflatten
        if mesh is not None and param_specs is not None:
            # Inner-axis model layouts: the flat weight vector is STORED
            # per fed_state_shardings (coordinate-split over a 'model'
            # axis; replicated otherwise), but the model should COMPUTE
            # in its parallel layout — parallel/tp.py's Megatron specs on
            # a 'model' axis, ops/moe.moe_ep_specs on an 'expert' axis.
            # Re-constrain each unflattened leaf so GSPMD resharding
            # happens once per round, then the matmuls run in layout.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            def round_unflatten(flat):
                tree = unflatten(flat)
                return jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, s)),
                    tree, param_specs,
                    is_leaf=lambda x: isinstance(x, _P))
        if (trainable_mask is not None
                and self.cfg.grad_dim != d_logical):
            trainable_mask = jnp.pad(
                jnp.asarray(trainable_mask, jnp.float32),
                (0, self.cfg.grad_dim - d_logical))  # pads stay frozen
        # --grad_buckets: partition the flat gradient at param-leaf
        # boundaries (tree_leaves order == flatten_params ravel order) so
        # each bucket's compress/reduce is an independent op the scheduler
        # can overlap with the rest of the backward (round.build_round_step
        # docstring; docs/ROOFLINE.md Round 7). Sketch mode needs bucket
        # edges on the tiled scheme's 128-lane blocks for sketch_range
        # bit-compatibility; dense modes split at raw leaf boundaries.
        self.grad_buckets = make_grad_buckets(
            [leaf.size for leaf in jax.tree_util.tree_leaves(init_params)],
            self.cfg.grad_dim, self.cfg.grad_buckets,
            align=LANES if (self.cfg.mode == "sketch"
                            and self.cfg.sketch_scheme == "tiled") else 1)
        self._round = build_round_step(loss_train, round_unflatten, self.cfg,
                                       mesh=mesh,
                                       trainable_mask=trainable_mask,
                                       buckets=self.grad_buckets)
        self._eval = build_eval_step(loss_val or loss_train, unflatten)
        # stashed (post-padding) for subclasses that build additional
        # jitted programs over the same loss/parameterization
        # (federated/buffer.BufferedFedLearner)
        self._loss_train = loss_train
        self._round_unflatten = round_unflatten
        self._trainable_mask = trainable_mask
        self._param_leaf_sizes = [
            leaf.size for leaf in jax.tree_util.tree_leaves(init_params)]
        self.lr_schedule = lr_schedule or (lambda t: cfg.lr_scale)
        # optional (d,) per-coordinate LR multipliers (the reference's
        # per-param-group LR vector, fed_aggregator.py:411-427; built from
        # param structure by utils.params.scalar_lr_multipliers). The round
        # receives lr * vec — server rules already broadcast a vector lr
        # over the dense update (federated/server.py docstring).
        if lr_scale_vec is not None:
            lr_scale_vec = jnp.asarray(lr_scale_vec, jnp.float32)
            if lr_scale_vec.shape != (self.cfg.grad_size,):
                raise ValueError(
                    f"lr_scale_vec must have shape ({self.cfg.grad_size},), "
                    f"got {lr_scale_vec.shape}")
            if self.cfg.grad_dim != d_logical:
                lr_scale_vec = jnp.pad(
                    lr_scale_vec, (0, self.cfg.grad_dim - d_logical),
                    constant_values=1.0)
        self.lr_scale_vec = lr_scale_vec
        # --client_k_dist: chronic per-client budget draws, memoized so a
        # client costs one Philox draw per run (faults.cohort_client_ks)
        self._client_k_memo = {}
        self.rounds_done = 0
        self.total_download_bytes = 0.0
        self.total_upload_bytes = 0.0

    @property
    def state(self) -> FedState:
        return self._state

    @state.setter
    def state(self, new: FedState):
        """Under a mesh, a state that does not sit where the round's
        ``in_shardings`` want it (fresh from ``init_fed_state``, or handed
        in with weights made on one device) is placed here, explicitly —
        the guarded dispatch may not move it. The round's own outputs
        already match and pass through on one comparison."""
        if (self._weights_sh is not None
                and new.weights.sharding != self._weights_sh):
            from commefficient_tpu.parallel.mesh import shard_state
            new = shard_state(new, self.cfg, self.mesh)
        self._state = new

    def _init_host_rows(self, flat):
        """Allocate the host-side client state: one ``HostArenaStore`` of
        per-shard numpy arenas, block-partitioned along the mesh's
        'clients' axis (num_shards = that axis size; 1 off-mesh), each
        row stored in the run's codec encoding.  Arenas live in plain
        host RAM — contiguous per-shard blocks, so gathers are slices,
        not per-row buffer hops (the old per-row pinned_host buffers
        traded that locality away; docs/SCALING.md discusses when a
        pinned staging buffer would still pay).  ``host_clients`` keeps
        the historical per-field row-list interface as ``_ArenaView``s."""
        from jax.sharding import SingleDeviceSharding
        self._s_dev = SingleDeviceSharding(jax.devices()[0])
        self._s_host = None
        n_shards = (self.mesh.shape["clients"] if self.mesh is not None
                    else 1)
        fill = (np.asarray(flat) if self.cfg.needs_client_weights
                else None)   # topk_down stale weights start at init weights
        self.host_store = HostArenaStore(self.cfg, self.codec,
                                         flat_weights=fill,
                                         num_shards=n_shards)
        self.host_clients = {f: self.host_store.view(f)
                             for f in CLIENT_STATE_FIELDS}
        if self.mesh is not None:
            from commefficient_tpu.parallel.mesh import \
                client_rows_shardings
            self._rows_sh = client_rows_shardings(self.cfg, self.mesh)
        else:
            self._rows_sh = None

    def _to_host(self, x):
        # rows may be encoded pytrees (dicts of leaves); map per leaf
        if self._s_host is not None:
            return jax.tree.map(lambda a: jax.device_put(a, self._s_host),
                                x)
        return jax.tree.map(np.asarray, x)

    def flush_offload(self):
        """Drain the offload pipeline: apply every pending host writeback
        and drop any gather-ahead buffer. No-op off the offload path.
        ``train_round`` (the blocking wrapper) calls this so synchronous
        callers — and everything that reads ``host_clients`` directly:
        tests, checkpointing — always see current rows; async loops defer
        it to epoch boundaries."""
        if self._offload_pipe is not None:
            with span("offload.flush"):
                self._offload_pipe.flush_all()

    @property
    def batch_shardings(self):
        """Per-round batch shardings on the mesh (None off-mesh) — for
        sharding-aware prefetch (data.prefetch.device_prefetch)."""
        return self._batch_sh if self.mesh is not None else None

    @property
    def params(self):
        """Current global model as a pytree (for checkpoint/eval exports)."""
        return self.unflatten(self.state.weights)

    def lr_at(self, t: float) -> float:
        return float(self.lr_schedule(t))

    def _client_ks(self, client_ids):
        """Device (W,) int32 per-client transmit budgets under
        ``--client_k_dist`` — drawn host-side from the seeded keyed-Philox
        stream (pure function of (cfg.seed, client): order-independent
        and resumable), placed like the ids so the guarded dispatch stays
        transfer-free."""
        from commefficient_tpu.federated.faults import cohort_client_ks
        ks = jnp.asarray(cohort_client_ks(
            self.cfg.seed, np.asarray(client_ids), self.cfg.k,
            self.cfg.client_k_dist, memo=self._client_k_memo))
        if self.mesh is not None:
            ks = jax.device_put(ks, self._batch_sh[0])
        return ks

    def _replicate(self, *xs):
        """Explicitly replicate per-call args (lr scalar, round rng, eval
        batch) across the mesh. Under the dispatch transfer guard the jit
        may not implicitly broadcast a single-device array to all mesh
        devices — device_put is the sanctioned, explicit transfer."""
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self.mesh, PartitionSpec())
        out = tuple(jax.device_put(x, repl) for x in xs)
        return out if len(out) > 1 else out[0]

    def train_round_async(self, client_ids, batch, mask, epoch_frac=None,
                          next_client_ids=None):
        """Dispatch one federated round WITHOUT blocking on the result.

        Returns the round's raw metrics as device arrays; pass them to
        ``finalize_round_metrics`` when (if) host values are needed. Rounds
        dispatched back-to-back pipeline on the device: batch upload and the
        next round's dispatch overlap the current round's compute, so a
        training loop that only finalizes metrics at logging points runs at
        device throughput instead of round latency (the reference pays the
        equivalent cost as blocking queue round-trips per round,
        fed_aggregator.py:303-318).

        ``next_client_ids``: the NEXT round's pre-sampled client ids
        (offload path only; ignored otherwise). When given, round t+1's
        host rows are gathered while round t computes and round t-1's
        output rows write back lazily (HostOffloadPipeline), so the
        host<->device row traffic overlaps compute instead of serializing
        the round."""
        round_mark(self.rounds_done)
        with span("round.dispatch"):
            lr = self.lr_at(self.rounds_done if epoch_frac is None
                            else epoch_frac)
            self.rng, round_rng = jax.random.split(self.rng)
            ids = jnp.asarray(client_ids, jnp.int32)
            cols = tuple(jnp.asarray(t) for t in batch)
            m = jnp.asarray(mask, jnp.float32)
            if self.mesh is not None:
                ids_sh, cols_sh, mask_sh = self._batch_sh
                ids = jax.device_put(ids, ids_sh)
                cols = jax.device_put(cols, cols_sh)
                m = jax.device_put(m, mask_sh)
            # device scalar, not a python float: the guarded dispatch below
            # must not trigger an implicit h2d, and a weak-typed scalar is
            # one dtype-promotion away from a retrace
            lr_in = (jnp.float32(lr) if self.lr_scale_vec is None
                     else lr * self.lr_scale_vec)
            if self.mesh is not None:
                lr_in, round_rng = self._replicate(lr_in, round_rng)
            ks = ((self._client_ks(client_ids),) if self.cfg.client_k_active
                  else ())
            if self._offload:
                ids_np = np.asarray(client_ids).astype(np.int64)
                valid = np.asarray(mask).any(axis=1)
                rows = self._offload_pipe.gather(ids_np)
                with _dispatch_guard():
                    self.state, out_rows, metrics = self._round(
                        self.state, rows, ids, cols, m, lr_in, round_rng, *ks)
                round_enqueued(metrics["loss_sum"])
                self._offload_pipe.push(ids_np, valid, out_rows)
                if next_client_ids is not None:
                    self._offload_pipe.prefetch(
                        np.asarray(next_client_ids).astype(np.int64))
            else:
                with _dispatch_guard():
                    self.state, metrics = self._round(self.state, ids, cols, m,
                                                      lr_in, round_rng, *ks)
                round_enqueued(metrics["loss_sum"])
            self.rounds_done += 1
            metrics["lr"] = lr
            return metrics

    def finalize_round_metrics(self, raw):
        """Block on one round's device metrics and roll them up host-side
        (mirrors run_batches, reference cv_train.py:171-252). Byte totals
        accumulate here, so a loop must finalize every round's metrics
        (in any order) for ``total_{down,up}load_bytes`` to be complete."""
        if "lr" not in raw:
            raise ValueError("round metrics were already finalized "
                             "(finalize_* consumes its input)")
        if isinstance(raw["lr"], list):
            raise TypeError("this is a train_rounds_scan result; use "
                            "finalize_scan_metrics")
        lr = raw.pop("lr")
        with span("round.sync"):
            out = jax.device_get(raw)
        n = max(float(out["num_datapoints"]), 1.0)
        self.total_download_bytes += float(out["download_bytes"])
        self.total_upload_bytes += float(out["upload_bytes"])
        return {
            "loss": float(out["loss_sum"]) / n,
            "metrics": np.asarray(out["metric_sums"]) / n,
            "num_datapoints": n,
            "download_bytes": float(out["download_bytes"]),
            "upload_bytes": float(out["upload_bytes"]),
            "update_l2": float(out["update_l2"]),
            "aborted": bool(out["aborted"]),
            "lr": lr,
        }

    def train_round(self, client_ids, batch, mask, epoch_frac=None):
        """Run one federated round and block for its metrics (offloaded
        host rows are flushed too, so ``host_clients`` is always current
        after a synchronous round)."""
        out = self.finalize_round_metrics(
            self.train_round_async(client_ids, batch, mask,
                                   epoch_frac=epoch_frac))
        self.flush_offload()
        return out

    def _rounds_scan_fn(self):
        """Lazily-built jitted K-round scan (see train_rounds_scan)."""
        if getattr(self, "_rounds_scan", None) is None:
            raw = self._round.raw
            scale_vec = self.lr_scale_vec
            het_k = self.cfg.client_k_active

            def scan_rounds(state, ids_k, cols_k, mask_k, lrs, rngs,
                            *ks_k):
                def body(st, per_round):
                    ids, cols, m, lr, rng = per_round[:5]
                    lr_in = lr if scale_vec is None else lr * scale_vec
                    return raw(st, ids, cols, m, lr_in, rng,
                               *per_round[5:])

                return jax.lax.scan(
                    body, state, (ids_k, cols_k, mask_k, lrs, rngs)
                    + ks_k)

            if self.mesh is None:
                self._rounds_scan = jax.jit(scan_rounds, donate_argnums=0)
            else:
                # same sharding contract as the per-round jit
                # (round.build_round_step), with the scan axis replicated
                from commefficient_tpu.parallel.mesh import (
                    fed_state_shardings, stacked_batch_shardings)
                state_sh = fed_state_shardings(self.cfg, self.mesh)
                ids_sh, cols_sh, mask_sh = stacked_batch_shardings(self.mesh)
                self._rounds_scan = jax.jit(
                    scan_rounds, donate_argnums=0,
                    in_shardings=(state_sh, ids_sh, cols_sh, mask_sh,
                                  None, None)
                    + ((ids_sh,) if het_k else ()),
                    out_shardings=(state_sh, None))
        return self._rounds_scan

    def train_rounds_scan(self, client_ids, batches, masks,
                          epoch_fracs=None):
        """Dispatch K federated rounds as ONE traced ``lax.scan``.

        ``client_ids`` (K, W), each column of ``batches`` stacked to
        (K, W, B, ...), ``masks`` (K, W, B). Identical math to K
        ``train_round_async`` calls — the round rngs follow the same
        host-side split chain, so trajectories match bit-for-bit
        (asserted in tests/test_round.py) — but the host dispatches once
        per K rounds instead of once per round: the per-dispatch host
        cost otherwise bounds round throughput no matter how fast the
        chip is; a scanned window runs back-to-back at device speed
        (neither cost is measured on the v5e yet — PERF.md). LR comes
        from the same
        schedule, evaluated at ``rounds_done + k`` (or ``epoch_fracs``
        (K,)). Returns raw stacked metrics for
        ``finalize_scan_metrics``."""
        if self._offload:
            raise ValueError(
                "train_rounds_scan needs device-resident client state "
                "(offloaded rows are host-gathered per round); run with "
                "scan_rounds=1 under client_state_offload")
        ids = jnp.asarray(client_ids, jnp.int32)
        K = ids.shape[0]
        ts = (np.asarray(epoch_fracs, np.float64) if epoch_fracs is not None
              else np.arange(self.rounds_done, self.rounds_done + K))
        lrs_host = [self.lr_at(float(t)) for t in ts]
        lrs = jnp.asarray(lrs_host, jnp.float32)
        round_rngs = []
        for _ in range(K):   # the exact split chain train_round_async uses
            self.rng, r = jax.random.split(self.rng)
            round_rngs.append(r)
        rngs = jnp.stack(round_rngs)
        cols = tuple(jnp.asarray(t) for t in batches)
        m = jnp.asarray(masks, jnp.float32)
        ks = ()
        if self.cfg.client_k_active:
            # stacked (K, W) budgets, one row per scanned round — the same
            # chronic per-client draws train_round_async would make, so
            # scanned and per-round trajectories stay bit-identical
            from commefficient_tpu.federated.faults import cohort_client_ks
            ks = (jnp.asarray(np.stack([
                cohort_client_ks(self.cfg.seed, row, self.cfg.k,
                                 self.cfg.client_k_dist,
                                 memo=self._client_k_memo)
                for row in np.asarray(client_ids)])),)
        if self.mesh is not None:
            from commefficient_tpu.parallel.mesh import \
                stacked_batch_shardings
            ids_sh, cols_sh, mask_sh = stacked_batch_shardings(self.mesh)
            ids = jax.device_put(ids, ids_sh)
            cols = jax.device_put(cols, cols_sh)
            m = jax.device_put(m, mask_sh)
            if ks:
                ks = (jax.device_put(ks[0], ids_sh),)
            lrs, rngs = self._replicate(lrs, rngs)
        scan_fn = self._rounds_scan_fn()
        with _dispatch_guard():
            self.state, metrics = scan_fn(self.state, ids, cols, m, lrs,
                                          rngs, *ks)
        self.rounds_done += K
        metrics["lr"] = lrs_host   # host-known; keeps the dispatch async
        return metrics

    def finalize_scan_metrics(self, raw):
        """Block on a train_rounds_scan result: returns a list of K
        per-round dicts (same schema as finalize_round_metrics) and
        accumulates the byte totals."""
        if "lr" not in raw:
            raise ValueError("scan metrics were already finalized "
                             "(finalize_* consumes its input)")
        if not isinstance(raw["lr"], list):
            raise TypeError("this is a single-round result; use "
                            "finalize_round_metrics")
        lrs = raw.pop("lr")
        out = jax.device_get(raw)
        K = len(lrs)
        results = []
        for k in range(K):
            n = max(float(out["num_datapoints"][k]), 1.0)
            self.total_download_bytes += float(out["download_bytes"][k])
            self.total_upload_bytes += float(out["upload_bytes"][k])
            results.append({
                "loss": float(out["loss_sum"][k]) / n,
                "metrics": np.asarray(out["metric_sums"][k]) / n,
                "num_datapoints": n,
                "download_bytes": float(out["download_bytes"][k]),
                "upload_bytes": float(out["upload_bytes"][k]),
                "update_l2": float(out["update_l2"][k]),
                "aborted": bool(out["aborted"][k]),
                "lr": float(lrs[k]),
            })
        return results

    def pipeline(self) -> "RoundPipeline":
        """A one-round software pipeline over this learner (see
        ``RoundPipeline``)."""
        return RoundPipeline(self)

    def scan_window(self, k: int) -> "ScanWindow":
        """A K-round scan buffer over this learner (see ``ScanWindow``)."""
        if self._offload:
            raise ValueError(
                "--scan_rounds K>1 is incompatible with "
                "--client_state_offload (rows are host-gathered per "
                "round); use scan_rounds=1")
        return ScanWindow(self, k)

    def evaluate(self, batches: Iterable):
        """Centralized validation over an iterable of (batch_tuple, mask)."""
        loss_sum, metric_sums, n_total = 0.0, None, 0.0
        with span("eval"):
            for batch, mask in batches:
                self.rng, eval_rng = jax.random.split(self.rng)
                cols = tuple(jnp.asarray(t) for t in batch)
                m = jnp.asarray(mask, jnp.float32)
                if self.mesh is not None:
                    cols, m, eval_rng = self._replicate(cols, m, eval_rng)
                with _dispatch_guard():
                    out_dev = self._eval(self.state.weights, cols, m,
                                         eval_rng)
                out = jax.device_get(out_dev)
                loss_sum += float(out["loss_sum"])
                ms = np.asarray(out["metric_sums"])
                metric_sums = ms if metric_sums is None else metric_sums + ms
                n_total += float(out["num_datapoints"])
        n = max(n_total, 1.0)
        return {"loss": loss_sum / n,
                "metrics": (metric_sums if metric_sums is not None
                            else np.zeros(1)) / n,
                "num_datapoints": n}


class HostOffloadPipeline:
    """Double-buffered async gather/scatter of host-offloaded client rows.

    Rows live in the learner's ``HostArenaStore`` — per-shard arenas
    block-partitioned over the mesh's 'clients' axis, in the run's
    ``--client_state`` encoding — and every gather/writeback here routes
    each client id to its owning shard (``_ArenaView`` indexing goes
    through ``HostArenaStore.owner``). The synchronous offload path
    serialized three stages per round: host-gather the sampled W encoded
    rows, run the jitted round, scatter the output rows back — a
    device<->host transfer of up to 2 GB at GPT2 scale (dense encoding)
    blocking every round. This pipeline takes both transfers off the
    critical path:

    * **gather-ahead**: with the next round's pre-sampled client ids
      (``prefetch``), round t+1's input rows are stacked and put on
      device while round t computes; the jitted round still donates the
      (W, d) buffer, so at most ``depth`` input/output row buffers are
      alive at once (depth 2 = classic double buffering).
    * **lazy scatter**: a finished round's output rows sit in a bounded
      ``pending`` queue as device arrays and write back to the host rows
      when the queue overflows or ``flush_all`` runs (epoch boundaries,
      ``train_round``, checkpointing).

    Correctness under overlap (the read-after-write hazard when round
    t+1 samples a client round t also touched): ``gather`` resolves each
    requested id against the pending queue newest-first before falling
    back to the host row, so a round always sees the latest value of
    every client row no matter when the writeback lands — and because
    the round returns the INPUT row for aborted/invalid slots, pending
    entries are value-correct even across NaN-guard rounds. Padded
    (invalid) slots are skipped on writeback exactly like the
    synchronous path, so a padded id-0 slot can never clobber a real
    client-0 update. Equivalence with the synchronous path — weights,
    rows, and byte accounting, including abort and padded-tail rounds —
    is pinned in tests/test_offload_async.py.

    ``stats`` counts gathers/prefetch hits/pending-row hits; the host
    time spent building gathers and flushing writebacks is in the
    ``offload.gather`` / ``offload.scatter`` spans (utils/tracing.py)."""

    def __init__(self, learner: "FedLearner", depth: int = 2):
        self.learner = learner
        self.depth = max(1, int(depth))
        # wire format of the rows crossing the round boundary: host-side
        # codecs (dense/sparse) decode arena rows to dense (d,) on gather
        # and encode on writeback — the jitted round sees dense rows and
        # is representation-blind (the bitwise-equivalence contract);
        # in-program codecs (sketched) ship the encoding itself
        if learner.codec.host_side_offload:
            self._arena_decode = learner.codec.decode_row_np
            self._arena_encode = learner.codec.encode_row_np
        else:
            self._arena_decode = lambda row: row
            self._arena_encode = lambda row: row
        # a lossy codec (truncating sparse) must see pending-queue rows
        # through the same encode/decode roundtrip an arena writeback
        # applies — otherwise a gather's value would depend on whether a
        # flush (e.g. a checkpoint drain) happened first, and
        # checkpointing would silently perturb the trajectory
        if learner.codec.wire_lossless:
            self._wire_normalize = lambda row: row
        else:
            self._wire_normalize = lambda row: self._arena_decode(
                self._arena_encode(jax.tree.map(np.asarray, row)))
        self._pending = deque()     # (ids_np, valid_np, out_rows) FIFO
        self._prefetched = None     # (key tuple, rows ClientState)
        self._pushes = 0            # pending-queue generation counter
        self._prefetch_gen = -1
        self.stats = {"gathers": 0, "prefetch_hits": 0,
                      "rows_from_pending": 0, "flushed_rounds": 0}

    # --- gather side -----------------------------------------------------
    def _resolve_row(self, field, cid, lst):
        """Latest value of client ``cid``'s ``field`` row (an encoded
        pytree): the newest pending (not yet written back) output row if
        one exists, else the arena row. Within a round the last valid
        slot wins, matching the ascending-w host writeback order."""
        for ids_np, valid, out in reversed(self._pending):
            new = getattr(out, field)
            if new is None:
                continue
            for w in range(len(ids_np) - 1, -1, -1):
                if valid[w] and ids_np[w] == cid:
                    self.stats["rows_from_pending"] += 1
                    # pending rows are already in wire format; a lossy
                    # codec still roundtrips them (flush-timing neutrality)
                    return self._wire_normalize(
                        jax.tree.map(lambda a: a[w], new)), True
        return self._arena_decode(lst[cid]), False

    def _build_gather(self, ids_np):
        """Stack the sampled clients' encoded rows into W-leading device
        arrays (per encoded leaf). Out-of-range ids (padded epoch-tail
        slots) clamp like the device gather would; their rows are inert
        (zero mask). On a mesh the stacked rows are placed per
        ``client_rows_shardings`` — worker-dim sharded like the batch, so
        each shard's devices receive the rows its own arena owns."""
        ln = self.learner
        with span("offload.gather"):
            fields = {}
            for field in CLIENT_STATE_FIELDS:
                lst = ln.host_clients[field]
                if lst is None:
                    fields[field] = None
                    continue
                n = len(lst)
                picked, any_pending = [], False
                for i in ids_np:
                    row, from_pending = self._resolve_row(
                        field, int(np.clip(i, 0, n - 1)), lst)
                    any_pending = any_pending or from_pending
                    picked.append(row)
                if ln._s_host is None and not any_pending:
                    # numpy arena rows, nothing in flight: ONE stacked
                    # host->device transfer per leaf instead of W row puts.
                    # Committed placement (device_put, not jnp.asarray) so the
                    # round sees the SAME input sharding as the pending-row
                    # path below — mixing committed and uncommitted rows
                    # would recompile the round on every path flip
                    stacked = jax.tree.map(
                        lambda *rs: jax.device_put(np.stack(rs), ln._s_dev),
                        *picked)
                else:
                    # device_put is a no-op for rows already on device
                    # (pending-queue slices)
                    picked = [jax.tree.map(
                        lambda r: jax.device_put(r, ln._s_dev), row)
                        for row in picked]
                    stacked = jax.tree.map(lambda *rs: jnp.stack(rs), *picked)
                if ln.mesh is not None:
                    stacked = jax.device_put(stacked,
                                             getattr(ln._rows_sh, field))
                fields[field] = stacked
        self.stats["gathers"] += 1
        return ClientState(**fields)

    def gather(self, ids_np):
        """Rows for a round about to dispatch: the gather-ahead buffer if
        it matches (same ids, no round pushed since it was built), else a
        fresh stack."""
        if self._prefetched is not None:
            key, rows = self._prefetched
            self._prefetched = None
            if (key == tuple(int(i) for i in ids_np)
                    and self._prefetch_gen == self._pushes):
                self.stats["prefetch_hits"] += 1
                return rows
        return self._build_gather(ids_np)

    def prefetch(self, ids_np):
        """Start the NEXT round's gather now (its host->device transfers
        overlap the current round's device compute)."""
        self._prefetched = (tuple(int(i) for i in ids_np),
                            self._build_gather(ids_np))
        self._prefetch_gen = self._pushes

    # --- scatter side ----------------------------------------------------
    def push(self, ids_np, valid, out_rows):
        """Queue a finished round's output rows for lazy writeback."""
        self._pending.append((np.asarray(ids_np), np.asarray(valid),
                              out_rows))
        self._pushes += 1
        while len(self._pending) > self.depth:
            self._flush_one()

    def _flush_one(self):
        ln = self.learner
        with span("offload.scatter"):
            ids_np, valid, out = self._pending.popleft()
            for field in CLIENT_STATE_FIELDS:
                lst = ln.host_clients[field]
                new = getattr(out, field)
                if lst is None or new is None:
                    continue
                # one device->host transfer per leaf, then per-row numpy
                # slices encoded into the owning shard's arena
                new_np = jax.tree.map(np.asarray, new)
                for w, cid in enumerate(ids_np):
                    if valid[w] and 0 <= cid < len(lst):
                        lst[int(cid)] = self._arena_encode(
                            jax.tree.map(lambda a: a[w], new_np))
        self.stats["flushed_rounds"] += 1

    def flush_all(self):
        """Apply every pending writeback and drop the gather-ahead buffer
        (host rows may be replaced right after, e.g. checkpoint load)."""
        while self._pending:
            self._flush_one()
        self._prefetched = None


class RoundPipeline:
    """One-round software pipeline over a ``FedLearner``.

    Feed each dispatched round's raw (device) metrics with ``push``; it
    returns the PREVIOUS round's finalized metrics (or None for the first
    round), so the host-side sync always overlaps the current round's
    device compute. Call ``flush`` after the loop for the final round.
    Training loops get device throughput instead of blocking latency while
    keeping per-round metric visibility one round behind (which is why a
    NaN abort driven by these metrics lags one round)."""

    def __init__(self, learner: FedLearner):
        self.learner = learner
        self._pending = None

    def push(self, raw):
        out = None
        if self._pending is not None:
            out = self.learner.finalize_round_metrics(self._pending)
        self._pending = raw
        return out

    def flush(self):
        out = None
        if self._pending is not None:
            out = self.learner.finalize_round_metrics(self._pending)
            self._pending = None
        return out


class ScanWindow:
    """Buffers per-round inputs and flushes every K of them as ONE
    ``train_rounds_scan`` dispatch — the scan-mode counterpart of
    ``RoundPipeline`` for training loops (``--scan_rounds K``).

    ``push`` returns the window's finalized per-round metrics when it
    flushed (a list), else None; call ``flush`` after the loop for the
    tail (a shorter window — one extra compile for that K)."""

    def __init__(self, learner: FedLearner, k: int):
        self.learner = learner
        self.k = max(1, int(k))
        self._buf = []

    def push(self, client_ids, cols, mask, epoch_frac):
        self._buf.append((np.asarray(client_ids), tuple(cols), mask,
                          epoch_frac))
        if len(self._buf) >= self.k:
            return self.flush()
        return None

    def flush(self):
        if not self._buf:
            return []
        ids_k = np.stack([b[0] for b in self._buf])
        cols_k = tuple(jnp.stack([b[1][i] for b in self._buf])
                       for i in range(len(self._buf[0][1])))
        mask_k = jnp.stack([jnp.asarray(b[2], jnp.float32)
                            for b in self._buf])
        fracs = [b[3] for b in self._buf]
        self._buf.clear()
        return self.learner.finalize_scan_metrics(
            self.learner.train_rounds_scan(ids_k, cols_k, mask_k,
                                           epoch_fracs=fracs))
