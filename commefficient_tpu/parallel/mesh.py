"""Device mesh + sharding layout for the federated round.

This replaces the reference's process topology (1 PS process + N worker GPU
processes wired by shm queues and a localhost NCCL group, reference
fed_aggregator.py:131-164) with a ``jax.sharding.Mesh`` carrying a single
``clients`` axis:

* sampled-client batches and per-client state rows are sharded along
  ``clients`` — each chip simulates W/n_chips clients per round, the analog
  of each worker GPU sequentially simulating num_workers/n_gpus clients
  (ref fed_aggregator.py:230-237)
* global weights and server optimizer state are replicated
* the cross-device reduce of transmitted gradients is whatever XLA inserts
  for ``sum`` over the sharded axis — psum over ICI, the NCCL-reduce analog
  (ref fed_worker.py:138)

Multi-host: build the mesh over ``jax.devices()`` after
``jax.distributed.initialize()``; the layout is unchanged (DCN slips in
between hosts automatically).

A ``seq`` axis for sequence/context parallelism (ring attention) composes
with this: mesh ("clients", "seq"), batches sharded on both axes. The CV
path leaves seq=1.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.state import ClientState, ServerOptState


from commefficient_tpu.utils.params import round_up  # noqa: F401  (re-export:
# the padding rule is shared with config.finalize and kernel tiling)


def padded_num_clients(num_clients: int, mesh: Optional[Mesh],
                       axis: str = "clients") -> int:
    """Client state rows must divide the mesh axis; pad with inert rows
    (samplers only emit real dataset client ids, so padded rows are never
    gathered or written — memory only)."""
    if mesh is None:
        return num_clients
    return round_up(num_clients, mesh.shape[axis])


def make_mesh(n_devices: Optional[int] = None, axis: str = "clients",
              seq: int = 1, model: int = 1, stage: int = 1,
              expert: int = 1) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    if sum(s > 1 for s in (seq, model, stage, expert)) > 1:
        raise ValueError("choose ONE inner axis: seq (ring attention), "
                         "model (tensor parallelism), stage (GPipe "
                         "pipeline), or expert (MoE expert parallelism)")
    for name, size in (("seq", seq), ("model", model), ("stage", stage),
                       ("expert", expert)):
        if size > 1:
            if n % size:
                raise ValueError(f"n_devices must be divisible by {name}")
            arr = np.array(devs[:n]).reshape(n // size, size)
            return Mesh(arr, (axis, name))
    return Mesh(np.array(devs[:n]), (axis,))


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def fed_state_shardings(cfg: FedConfig, mesh: Mesh, axis: str = "clients"):
    """Sharding pytree matching FedState (see round.FedState).

    With a ``model`` axis in the mesh (2D clients x model federation), the
    flat weight-vector quantities shard their coordinate dimension over it:
    weights/last_changed (d,), the server opt state, and the SECOND dim of
    per-client rows (n, d) — so a model too big for one chip can still be
    federated (the capability the reference approximates by giving each
    client a whole GPU, fed_worker.py:18-20). The flat-coordinate split is
    a storage layout, not the compute layout: the round's ``unflatten``
    re-constrains params to the Megatron TP specs (parallel/tp.py), and
    GSPMD inserts the reshard."""
    from commefficient_tpu.federated.round import FedState
    m = "model" if "model" in mesh.axis_names else None
    rep = _ns(mesh)
    vec = _ns(mesh, m) if m else rep           # (d,)-shaped quantities
    row = _ns(mesh, axis, m) if m else _ns(mesh, axis)  # (num_clients, d)
    if cfg.mode == "sketch":
        # (r, c) sketch tables: shard columns over the model axis only
        # when c divides evenly (the tiled scheme's 128-multiple covers
        # power-of-two axes; anything else replicates — tables are small)
        cols_divide = m and cfg.sketch_cols % mesh.shape["model"] == 0
        opt_sh = _ns(mesh, None, m) if cols_divide else rep
    else:
        opt_sh = vec
    if cfg.client_state_offload and cfg.has_client_state:
        # host placement: rows live in the HostArenaStore's per-shard
        # arenas (federated/client_store.py), so the device FedState
        # carries no client rows at all
        clients = ClientState()
    else:
        # the sharding tree must mirror the ENCODED storage structure
        # (client_store.make_codec): the dense codec keeps (n, d) arrays
        # — leading dim over the clients axis, coordinate dim over the
        # model axis — while sparse/sketched leaves are O(k)-wide per
        # row and shard their leading dim only
        from commefficient_tpu.federated.client_store import make_codec
        codec = make_codec(cfg)
        enc_row = row if cfg.client_state == "dense" \
            else codec.structure(_ns(mesh, axis))
        clients = ClientState(
            velocities=enc_row if cfg.needs_velocity_state else None,
            errors=enc_row if cfg.needs_error_state else None,
            weights=enc_row if cfg.needs_client_weights else None,
        )
    return FedState(
        weights=vec,
        opt=ServerOptState(Vvelocity=opt_sh, Verror=opt_sh),
        clients=clients,
        round_idx=rep,
        last_changed=vec,
        client_last_round=_ns(mesh, axis),
        aborted=rep,
        weights_version=rep,
        quarantine=_ns(mesh, axis),
        # buffer=None even for server_mode='buffered': the buffer subtree
        # only exists between the first cohort and the reset-on-apply, so
        # the canonical state tree (what shard_state / checkpoints / the
        # sync round see) stays buffer-less. Programs that carry a live
        # buffer extend this tree with buffer_state_shardings below.
        buffer=None,
    )


def buffer_state_shardings(cfg: FedConfig, mesh: Mesh,
                           axis: str = "clients"):
    """Sharding pytree matching a live BufferState (federated/state.py) —
    used both for the M-slot server buffer and the W-slot cohort
    contribution (NamedSharding is size-agnostic; only the leading slot
    dim's axis assignment matters).

    Every slot-leading leaf shards its slot dim over the ``clients`` axis:
    each shard owns its slot rows, so no ``(M, d)`` or ``(W, d)`` aval is
    ever replicated (the buffered_mesh graft-audit target enforces this).
    Dense client rows and dense transmits additionally shard their
    coordinate dim over a ``model`` axis when present, matching the
    fed_state_shardings row layout; sketch-mode (M, r, c) transmits shard
    the slot dim only (tables are small). The scalar fill count is
    replicated — every shard needs it for the slot-assignment cumsum."""
    from commefficient_tpu.federated.state import BufferState
    m = "model" if "model" in mesh.axis_names else None
    slot = _ns(mesh, axis)
    if cfg.mode == "sketch":
        transmit = _ns(mesh, axis, None, None)
    else:
        transmit = _ns(mesh, axis, m) if m else slot
    row = _ns(mesh, axis, m) if m else slot
    return BufferState(
        transmit=transmit,
        loss_sum=slot,
        metric_sums=slot,
        num_datapoints=slot,
        download_floats=slot,
        cid=slot,
        start_version=slot,
        valid=slot,
        count=_ns(mesh),
        velocities=row if cfg.needs_velocity_state else None,
        errors=row if cfg.needs_error_state else None,
        weights=row if cfg.needs_client_weights else None,
    )


def client_rows_shardings(cfg: FedConfig, mesh: Mesh,
                          axis: str = "clients"):
    """Shardings for the offload round's W-leading encoded rows argument
    (round.build_round_step, offload + mesh): rows travel with the batch —
    leading worker dim over the ``clients`` axis, so each shard's devices
    consume exactly the rows its own host arena gathered
    (client_store.HostArenaStore block partition). Dense rows additionally
    shard their coordinate dim over a ``model`` axis, matching
    ``fed_state_shardings``'s row layout."""
    from commefficient_tpu.federated.client_store import make_codec
    codec = make_codec(cfg)
    m = "model" if "model" in mesh.axis_names else None
    dense_row = _ns(mesh, axis, m) if m else _ns(mesh, axis)
    # host-side codecs (dense/sparse) hand the round dense (W, d) rows —
    # the arena holds the encoding; only in-program codecs (sketched)
    # ship their encoded structure across the boundary
    enc_row = dense_row if codec.host_side_offload \
        else codec.structure(_ns(mesh, axis))
    return ClientState(
        velocities=enc_row if cfg.needs_velocity_state else None,
        errors=enc_row if cfg.needs_error_state else None,
        weights=enc_row if cfg.needs_client_weights else None,
    )


def batch_shardings(mesh: Mesh, axis: str = "clients"):
    """(ids, cols-prefix, mask) shardings: worker axis over the mesh."""
    worker0 = _ns(mesh, axis)
    return worker0, worker0, worker0


def stacked_batch_shardings(mesh: Mesh, axis: str = "clients"):
    """Batch shardings for a K-round stacked window
    (api.FedLearner.train_rounds_scan): the leading scan axis is
    replicated (lax.scan consumes it sequentially), the worker axis
    shards as in ``batch_shardings``."""
    worker1 = _ns(mesh, None, axis)
    return worker1, worker1, worker1


def on_each_replica(mesh: Optional[Mesh], fn):
    """``fn`` as every chip of ``mesh`` runs it on its OWN replica of
    replicated operands: a ``shard_map`` over all mesh axes with
    replicated in/out specs. Values are what the bare call gives; what
    changes is that the compiler is told not to partition the body.

    The aggregate side of a round (sketch of the all-reduced gradient,
    server update on the replicated optimizer state) is such a
    computation, and it holds the Pallas kernels — which the TPU compiler
    refuses to partition automatically ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map").
    Identity off-mesh, and on a mesh with a ``model`` axis, whose flat
    vectors are coordinate-split rather than replicated."""
    if mesh is None or mesh.shape.get("model", 1) > 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)


def shard_state(state, cfg: FedConfig, mesh: Mesh):
    return jax.device_put(state, fed_state_shardings(cfg, mesh))
