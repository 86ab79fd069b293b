"""ClientStateStore: placement x representation for per-client state.

Every stateful mode keeps persistent per-client rows (local momentum,
error feedback, topk_down stale weights).  Stored dense and
device-resident they are ``(num_clients, d)`` arrays — ~1 GB *per row*
at gpt2-small, which caps the simulator near ~50 clients, four orders of
magnitude short of the million-client north star (ROADMAP item 1).  This
module closes the gap along two composable axes:

* **Representation** (``--client_state dense|sparse|sketched``, a
  ``RowCodec``): how one client's ``(d,)`` row is stored.

  - ``dense``  — the row verbatim (today's behavior, bitwise unchanged).
  - ``sparse`` — ``(cap,)`` index/value pairs, ``cap = cfg.k``.  A
    local_topk residual row is sparse *by construction* (error feedback
    and momentum are zeroed on the transmitted top-k support, so a row
    carries at most ``d - k`` nonzeros); whenever ``nnz(row) <= cap``
    the codec is EXACT — decode(encode(x)) == x bitwise — which makes
    ``--client_state sparse`` trajectory-equivalent to dense
    (tests/test_client_store.py pins this at k >= d/2).  Beyond capacity
    it keeps the ``cap`` largest-magnitude coordinates: "sparsified
    memory", the same bounded-divergence contract error feedback already
    gives top-k itself.
  - ``sketched`` — a per-client ``(r, c)`` CountSketch of the error row
    (Charikar et al., the same ``ops/countsketch.py`` used server-side,
    'global' scheme so the table is exactly ``(r, c)``).  Decode
    recovers the top-k heavy hitters; divergence is bounded by the
    sketch's heavy-hitter guarantee and absorbed by error feedback.

  The round encodes/decodes rows AT THE ROUND BOUNDARY
  (``gather_rows``/``scatter_rows``), so the jitted round math is
  representation-blind.

* **Placement**: ``device`` (encoded storage leaves live in ``FedState``
  — sharded over the mesh ``clients`` axis like dense rows always were)
  or ``host`` (``--client_state_offload``): a ``HostArenaStore`` of
  per-shard numpy arenas.  On a mesh the row space is block-partitioned
  along the ``clients`` axis — shard s owns rows
  ``[s*rows_per_shard, (s+1)*rows_per_shard)``, matching jax's
  leading-dim block sharding, so each host's arena holds exactly the
  rows its devices consume and the offload pipeline routes every
  sampled id to its owning shard (``HostArenaStore.owner``).  Buffered
  cohorts (``server_mode='buffered'``) compose with both placements:
  the cohort gathers rows after the pipeline drains, defers writeback
  to apply time, and ``flush_faults`` drains the offload queue so a
  checkpoint sees the arenas settled (docs/SCALING.md, "Owner routing
  into buffered cohorts").

Peak state memory for a W-worker round over n clients is
``O(n * row_bytes(codec) + W * d)``: only the sampled rows ever exist
densely, and only on device.  The ``client_store`` graft-audit target
(analysis/targets.py) proves the jitted round materializes no
``(num_clients, d)`` array under host placement.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import FedConfig
from commefficient_tpu.federated.state import CLIENT_STATE_FIELDS, ClientState


# --------------------------------------------------------------------------
# Row codecs (the representation axis)
# --------------------------------------------------------------------------

class DenseCodec:
    """Identity codec: a row is stored as itself.  encode/decode are the
    identity function, so every jaxpr built through this codec is
    literally the pre-codec program (bitwise-compatibility anchor)."""

    name = "dense"
    # Host placement runs this codec HOST-side (in the arena), not inside
    # the jitted round: the round then receives dense (W, d) rows whatever
    # the representation, so dense- and sparse-offload runs execute the
    # IDENTICAL compiled program and their trajectories match bitwise by
    # construction. (An in-program codec — even an exact one — perturbs
    # XLA's fusion choices and drifts weights at the ulp level; see
    # docs/SCALING.md. Sketched keeps its codec in-program: its contract
    # is bounded divergence, and its encode must run on device anyway.)
    host_side_offload = True
    #: decode(encode(x)) == x for every row the run can produce — when
    #: False, the offload pipeline normalizes pending wire-format rows
    #: through the codec roundtrip so gather results never depend on
    #: flush timing (a checkpoint drain must be trajectory-neutral)
    wire_lossless = True

    def __init__(self, d: int):
        self.d = int(d)

    def encode_rows(self, rows: jax.Array) -> jax.Array:
        return rows

    def decode_rows(self, enc: jax.Array) -> jax.Array:
        return enc

    def init_rows(self, n: int, fill: Optional[jax.Array] = None):
        if fill is None:
            return jnp.zeros((n, self.d), jnp.float32)
        return jnp.broadcast_to(fill, (n, self.d)).copy()

    def init_host_rows(self, n: int, fill=None):
        if fill is None:
            return np.zeros((n, self.d), np.float32)
        return np.broadcast_to(np.asarray(fill, np.float32),
                               (n, self.d)).copy()

    def structure(self, leaf):
        """The encoded pytree with every leaf replaced by ``leaf`` —
        used to build sharding trees matching the storage structure."""
        return leaf

    # numpy single-row codec for the host-side arena path
    def encode_row_np(self, row):
        return np.asarray(row)

    def decode_row_np(self, enc):
        return np.asarray(enc)

    def row_floats(self) -> int:
        return self.d

    def __hash__(self):
        return hash((type(self).__name__, self.d))

    def __eq__(self, other):
        return type(other) is type(self) and other.d == self.d


class SparseCodec:
    """``(cap,)`` index/value pairs per row, largest-|value| truncation.

    Exact (decode(encode(x)) == x, bitwise) whenever ``nnz(x) <= cap``;
    under local_topk the residual support is the complement of the
    transmitted top-k, so ``cap = cfg.k`` is exact iff ``k >= d/2`` and
    a documented largest-magnitude truncation below that."""

    name = "sparse"
    host_side_offload = True   # see DenseCodec: exactness-preserving
    # representations run host-side under offload so every representation
    # shares ONE compiled round program (bitwise trajectory equivalence)

    def __init__(self, d: int, cap: int):
        self.d = int(d)
        self.cap = int(min(cap, d))
        if self.cap < 1:
            raise ValueError(f"sparse codec needs cap >= 1, got {cap}")
        # local_topk residual/velocity rows carry at most d - k nonzeros
        # (cap == cfg.k), so the codec is exact for every storable row
        # iff k >= d/2; below that it truncates, and the pipeline must
        # roundtrip pending rows so flush timing can't change a gather
        self.wire_lossless = 2 * self.cap >= self.d

    def encode_rows(self, rows: jax.Array) -> dict:
        # lax.top_k on |row| is deterministic (ties break to the lower
        # index), so encode is a pure function of the row
        _, idx = jax.lax.top_k(jnp.abs(rows), self.cap)       # (W, cap)
        val = jnp.take_along_axis(rows, idx, axis=-1)         # (W, cap)
        return {"idx": idx.astype(jnp.int32), "val": val}

    def decode_rows(self, enc: dict) -> jax.Array:
        idx, val = enc["idx"], enc["val"]
        w = idx.shape[0]
        out = jnp.zeros((w, self.d), val.dtype)
        # top_k indices are distinct per row; init-time storage carries
        # duplicate zeros at index 0, whose scattered value is 0.0 either
        # way — decode stays deterministic
        return out.at[jnp.arange(w)[:, None], idx].set(val)

    def init_rows(self, n: int, fill=None):
        assert fill is None, "sparse codec cannot seed non-zero rows"
        return {"idx": jnp.zeros((n, self.cap), jnp.int32),
                "val": jnp.zeros((n, self.cap), jnp.float32)}

    def init_host_rows(self, n: int, fill=None):
        assert fill is None, "sparse codec cannot seed non-zero rows"
        return {"idx": np.zeros((n, self.cap), np.int32),
                "val": np.zeros((n, self.cap), np.float32)}

    def structure(self, leaf):
        return {"idx": leaf, "val": leaf}

    def encode_row_np(self, row):
        """numpy single-row encode for the host arena: largest-|value|
        cap coordinates, stable ties by index.  Exact (decode == row,
        bitwise) whenever nnz(row) <= cap — the values are copied, never
        recomputed."""
        row = np.asarray(row)
        idx = np.argsort(-np.abs(row), kind="stable")[:self.cap]
        return {"idx": idx.astype(np.int32),
                "val": row[idx].astype(np.float32, copy=False)}

    def decode_row_np(self, enc):
        out = np.zeros((self.d,), np.float32)
        out[enc["idx"]] = enc["val"]
        return out

    def row_floats(self) -> int:
        return 2 * self.cap

    def __hash__(self):
        return hash((type(self).__name__, self.d, self.cap))

    def __eq__(self, other):
        return (type(other) is type(self) and other.d == self.d
                and other.cap == self.cap)


class SketchedCodec:
    """Per-client ``(r, c)`` CountSketch of the error row.

    encode = ``sketch_vec``; decode = ``unsketch`` top-k heavy hitters
    (k = the run's top-k budget — the coordinates error feedback can act
    on next round).  Divergence from the dense trajectory is bounded by
    the sketch's heavy-hitter guarantee and re-absorbed by error
    feedback, the identical mechanism that absorbs server-side sketch
    recovery noise (tests/test_client_store.py pins a roundtrip bound
    and end-to-end accuracy-within-eps)."""

    name = "sketched"
    host_side_offload = False  # encode IS the sketch: runs in-program on
    # device (the contract is bounded divergence, not bitwise identity)
    wire_lossless = True  # the wire format IS the arena format (tables)

    def __init__(self, d: int, r: int, c: int, k: int, seed: int):
        from commefficient_tpu.ops.countsketch import CountSketch
        # 'global' scheme (trajectory-preserving): classic per-coordinate
        # hashing, table exactly (r, c) with no lane-tile padding. The
        # lane-tiled layout, whose W vmapped sketches could dispatch the
        # batched Pallas kernels, was never measured at the codec's
        # small-c operating point (per-client tables are small and
        # gathered W at a time); it comes back with the cell that
        # measures it.
        self.cs = CountSketch(d=int(d), c=int(c), r=int(r),
                              seed=int(seed) ^ 0xC11E57, scheme="global")
        self.d = int(d)
        self.k = int(min(k, d))

    def encode_rows(self, rows: jax.Array) -> dict:
        # (W, r, c_eff); use_kernel opts into the batched Pallas sketch
        # kernel where eligible (tiled scheme on TPU) — a no-op for the
        # codec's 'global' scheme
        return {"table": jax.vmap(
            lambda v: self.cs.sketch_vec(v, use_kernel=True))(rows)}

    def decode_rows(self, enc: dict) -> jax.Array:
        # positional: unsketch's statics (k, approx_recall, use_kernel)
        # are static_argnums, which jit requires positionally
        return jax.vmap(lambda t: self.cs.unsketch(
            t, self.k, None, True))(enc["table"])

    def init_rows(self, n: int, fill=None):
        assert fill is None, "sketched codec cannot seed non-zero rows"
        return {"table": jnp.zeros((n, self.cs.r, self.cs.c_eff),
                                   jnp.float32)}

    def init_host_rows(self, n: int, fill=None):
        assert fill is None, "sketched codec cannot seed non-zero rows"
        return {"table": np.zeros((n, self.cs.r, self.cs.c_eff),
                                  np.float32)}

    def structure(self, leaf):
        return {"table": leaf}

    def row_floats(self) -> int:
        return self.cs.r * self.cs.c_eff

    def __hash__(self):
        return hash((type(self).__name__, self.d, self.k, self.cs))

    def __eq__(self, other):
        return (type(other) is type(self) and other.d == self.d
                and other.k == self.k and other.cs == self.cs)


def make_codec(cfg: FedConfig):
    """The run's RowCodec (``--client_state``). cfg must be finalized
    (grad_dim known)."""
    d = cfg.grad_dim
    if cfg.client_state == "dense":
        return DenseCodec(d)
    if cfg.client_state == "sparse":
        return SparseCodec(d, cap=cfg.k)
    if cfg.client_state == "sketched":
        return SketchedCodec(d, r=cfg.client_sketch_rows,
                             c=cfg.client_sketch_cols, k=cfg.k,
                             seed=cfg.seed)
    raise ValueError(f"unknown client_state {cfg.client_state!r}")


# --------------------------------------------------------------------------
# The gather/scatter contract (device placement)
# --------------------------------------------------------------------------

def gather_rows(storage, ids: jax.Array, codec):
    """Encoded storage (n-leading leaves) + sampled ids -> dense (W, d)
    rows.  For the dense codec this is literally ``storage[ids]``."""
    if storage is None:
        return None
    enc = jax.tree.map(lambda a: a[ids], storage)
    return codec.decode_rows(enc)


def scatter_rows(storage, ids: jax.Array, dense_rows, codec):
    """Dense (W, d) output rows -> encoded, written back at ``ids``
    (out-of-bounds ids — padded/invalid slots — are dropped, matching
    the historical dense scatter)."""
    if storage is None or dense_rows is None:
        return storage
    enc = codec.encode_rows(dense_rows)
    return jax.tree.map(lambda s, e: s.at[ids].set(e, mode="drop"),
                        storage, enc)


def select_rows(keep: jax.Array, new_enc, old_enc):
    """Leaf-wise slot freeze on ENCODED rows: slot w keeps its input
    encoding when ``keep[w]`` is False.  Selecting on the encoded pytree
    (rather than re-encoding a decoded input) is what keeps frozen slots
    bitwise-stable across abort/padded rounds."""
    def sel(n, o):
        k = keep.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(k, n, o)
    return jax.tree.map(sel, new_enc, old_enc)


def init_client_storage(cfg: FedConfig, codec, flat_weights) -> ClientState:
    """Device-resident encoded storage for every active field."""
    n = cfg.num_clients
    return ClientState(
        velocities=codec.init_rows(n) if cfg.needs_velocity_state else None,
        errors=codec.init_rows(n) if cfg.needs_error_state else None,
        weights=codec.init_rows(n, fill=flat_weights)
        if cfg.needs_client_weights else None,
    )


# --------------------------------------------------------------------------
# Host arenas (the placement axis, --client_state_offload)
# --------------------------------------------------------------------------

class _ArenaView:
    """Per-client row view over one field's sharded arenas.

    Quacks like the historical list-of-rows (``host_clients[field][i]``,
    ``lst[i] = row``, ``len``, iteration) so tests and checkpointing
    keep working, while storage stays contiguous per-shard blocks."""

    def __init__(self, store: "HostArenaStore", field: str):
        self._store = store
        self._field = field

    def __len__(self):
        return self._store.num_rows

    def __getitem__(self, i):
        return self._store.row(self._field, i)

    def __setitem__(self, i, row):
        self._store.set_row(self._field, i, row)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


class HostArenaStore:
    """Mesh-sharded host arenas of ENCODED per-client rows.

    The row space [0, num_rows) is block-partitioned into ``num_shards``
    contiguous shards — ``owner(cid) = cid // rows_per_shard`` — the
    same leading-dim block layout jax uses to shard a device array over
    the mesh ``clients`` axis, so shard s's arena holds exactly the rows
    shard s's devices would own device-resident.  Each shard's arena is
    one contiguous numpy block per encoded leaf (for multi-host runs,
    each host allocates only its own shard's block; this in-process
    store simulates that partitioning and counts per-shard row traffic
    in ``shard_reads``/``shard_writes`` so routing is testable).

    Memory: ``num_rows * codec.row_floats() * 4`` bytes total across
    shards — O(n*k) for sparse/sketched codecs, which is what makes a
    million-client arena fit in host RAM (docs/SCALING.md)."""

    def __init__(self, cfg: FedConfig, codec, flat_weights=None,
                 num_shards: int = 1):
        n = int(cfg.num_clients)
        if num_shards < 1 or n % num_shards:
            raise ValueError(
                f"num_clients ({n}) must be divisible by num_shards "
                f"({num_shards})")
        self.codec = codec
        self.num_rows = n
        self.num_shards = int(num_shards)
        self.rows_per_shard = n // self.num_shards
        self.shard_reads = np.zeros(self.num_shards, np.int64)
        self.shard_writes = np.zeros(self.num_shards, np.int64)

        def alloc(fill=None):
            return [codec.init_host_rows(self.rows_per_shard, fill=fill)
                    for _ in range(self.num_shards)]

        self._arenas = {
            "velocities": alloc() if cfg.needs_velocity_state else None,
            "errors": alloc() if cfg.needs_error_state else None,
            "weights": alloc(fill=flat_weights)
            if cfg.needs_client_weights else None,
        }
        assert set(self._arenas) == set(CLIENT_STATE_FIELDS)

    def owner(self, cid: int) -> int:
        """The shard (host) owning client ``cid``'s row."""
        return int(cid) // self.rows_per_shard

    def _locate(self, cid: int):
        cid = int(cid)
        if not 0 <= cid < self.num_rows:
            raise IndexError(f"client id {cid} out of range "
                             f"[0, {self.num_rows})")
        s = cid // self.rows_per_shard
        return s, cid - s * self.rows_per_shard

    def view(self, field: str) -> Optional[_ArenaView]:
        return None if self._arenas[field] is None \
            else _ArenaView(self, field)

    def row(self, field: str, cid: int):
        s, local = self._locate(cid)
        self.shard_reads[s] += 1
        arena = self._arenas[field][s]
        return jax.tree.map(lambda a: a[local], arena)

    def set_row(self, field: str, cid: int, row) -> None:
        s, local = self._locate(cid)
        self.shard_writes[s] += 1
        arena = self._arenas[field][s]

        def assign(a, r):
            a[local] = np.asarray(r)
            return a
        jax.tree.map(assign, arena, row)

    def nbytes(self) -> int:
        total = 0
        for arenas in self._arenas.values():
            if arenas is None:
                continue
            for shard in arenas:
                total += sum(a.nbytes for a in jax.tree.leaves(shard))
        return total
