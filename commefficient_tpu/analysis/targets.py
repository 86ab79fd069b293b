"""The repo's auditable programs, built at CPU-friendly scale.

Each target constructs the real production code path — the federated
round via ``FedLearner``/``build_round_step``, the GPT2 train step with
``remat=True``, the flash-attention custom VJP, the CountSketch ops —
at toy dimensions chosen so the forbidden shapes are distinctive (no
accidental collisions with legitimate intermediates), traces it to a
jaxpr, and binds the symbolic footprint dims.  The CLI and the tier-1
``audit``-marked tests both run these.

Dims are deliberately small: tracing is shape-polymorphic in spirit —
a (W, d) changed-matrix materializes at W=3, d=46 exactly as it would
at gpt2-small scale, and the audit is about *structure*, not size.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .report import AuditReport
from .retrace import check_retrace
from .rules import (DEFAULT_PATTERNS, BatchedSketchRule,
                    BucketedTransmitRule, FootprintRule,
                    FusedServerUpdateRule, RuleReport, ShapePattern,
                    ShardedBufferRule, ShardedPoolRule, TransferRule,
                    Violation)
from .walker import walk


@dataclass
class AuditTarget:
    name: str
    description: str
    trace: Callable[[], object]          # () -> ClosedJaxpr
    dims: dict = field(default_factory=dict)
    rules: tuple = ()
    retrace: Optional[Callable[[], RuleReport]] = None

    def audit(self, with_retrace: bool = True) -> AuditReport:
        closed = self.trace()
        sites, stats = walk(closed)
        report = AuditReport(target=self.name, stats=stats)
        for rule in self.rules:
            report.rule_reports.append(rule.check(sites, stats, self.dims))
        if with_retrace and self.retrace is not None:
            report.rule_reports.append(self.retrace())
        return report


# --------------------------------------------------------------------------
# federated round
# --------------------------------------------------------------------------

ROUND_CFGS = {
    "sketch": dict(mode="sketch", error_type="virtual",
                   virtual_momentum=0.9, k=3, num_rows=3, num_cols=20),
    "local_topk": dict(mode="local_topk", error_type="local",
                       local_momentum=0.9, k=3),
    "uncompressed": dict(mode="uncompressed", error_type="none",
                         virtual_momentum=0.0, local_momentum=0),
}

#: Modes that run the fused fold-the-batch path, where NO legitimate
#: (W, d) stack exists and any such aval is the O(W·d) accounting
#: changed-matrix leaking back (the PR 2 contract).  local_topk, by
#: contrast, *owns* per-sampled-client (W, d) rows — local momentum and
#: error feedback are per-client state — so only the (num_clients, d)
#: ban binds there.
FUSED_ROUND_MODES = ("sketch", "uncompressed")


def _make_learner(num_workers=3, num_clients=7, hidden=4, **cfg_kw):
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import TinyMLP

    model = TinyMLP(num_classes=2, hidden=hidden)
    cfg = FedConfig(weight_decay=0, num_workers=num_workers,
                    num_clients=num_clients, lr_scale=0.05, **cfg_kw)
    return FedLearner(model, cfg, make_cv_loss(model), None,
                      jax.random.PRNGKey(1), np.zeros((1, 8), np.float32))


def _round_batch(w=3, rng=None):
    rng = rng or np.random.RandomState(0)
    Xb = jnp.asarray(rng.randn(w, 4, 8).astype(np.float32))
    yb = jnp.asarray(rng.randint(0, 2, (w, 4)).astype(np.int32))
    return (Xb, yb), jnp.ones((w, 4), jnp.float32)


def round_target(mode: str = "sketch") -> AuditTarget:
    w, n_clients = 3, 7
    ln = _make_learner(num_workers=w, num_clients=n_clients,
                       **ROUND_CFGS[mode])
    d = int(ln.state.last_changed.shape[0])
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)

    def trace():
        return jax.make_jaxpr(ln._round.raw)(
            ln.state, ids, batch, mask, jnp.float32(0.05),
            jax.random.PRNGKey(0))

    def retrace():
        rng = np.random.RandomState(3)

        def drive(i):
            ids_i = rng.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rng)
            ln.train_round_async(ids_i, b, m)

        return check_retrace(ln._round, None, repeats=3, warmup=1,
                             drive=drive)

    dims = {"num_clients": n_clients, "d": d}
    if mode in FUSED_ROUND_MODES:
        dims["W"] = w
    return AuditTarget(
        name=f"round/{mode}",
        description=f"federated round, mode={mode} (TinyMLP scale)",
        trace=trace,
        dims=dims,
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# bucketed federated round (--grad_buckets)
# --------------------------------------------------------------------------

def round_bucketed_target(variant: str = "local_topk",
                          mutate: bool = False) -> AuditTarget:
    """The bucketed transmit path (``--grad_buckets``, federated/round.py
    ``bucketed_compress``) — the program whose *structure* is the point:
    one independent compress/reduce eqn per bucket, so XLA's
    latency-hiding scheduler can overlap bucket-k aggregation with
    bucket-(k+1) backward and a mesh issues one psum per bucket.

    Two variants, covering both transmit shapes:

    * ``local_topk`` — per-worker dense transmits; the worker-axis
      ``reduce_sum`` must appear once per bucket and never over the full
      (W, d) stack.  TinyMLP hidden=4 (d=46) with a dense (align=1)
      plan.
    * ``sketch`` — fused path with sketch-after-aggregate; each bucket
      feeds its own ``sketch_range`` and no full-(d,) ``sketch_vec``
      remains.  TinyMLP hidden=64 (d=706) so the 128-aligned plan has a
      real interior cut, num_cols=256 so c_eff collides with no bucket
      size.

    ``mutate=True`` builds the SAME config with ``grad_buckets=1`` — the
    monolithic program a re-concatenation refactor would produce — while
    keeping the K>1 plan in the rule.  The audit must FAIL on it
    (tests/test_grad_buckets.py pins this), which is what makes a PASS
    on the real program meaningful.
    """
    from commefficient_tpu.federated.state import make_grad_buckets
    from commefficient_tpu.ops.countsketch import LANES, pad_cols

    w, n_clients, K = 3, 7, 4
    if variant == "sketch":
        hidden, align = 64, LANES
        cfg_kw = dict(ROUND_CFGS["sketch"], num_cols=256)
    elif variant == "local_topk":
        hidden, align = 4, 1
        cfg_kw = dict(ROUND_CFGS["local_topk"])
    else:
        raise ValueError(f"variant must be local_topk|sketch, "
                         f"got {variant!r}")
    ln = _make_learner(num_workers=w, num_clients=n_clients, hidden=hidden,
                       grad_buckets=1 if mutate else K, **cfg_kw)
    d = int(ln.state.last_changed.shape[0])
    plan = ln.grad_buckets or make_grad_buckets(
        ln._param_leaf_sizes, ln.cfg.grad_dim, K, align=align)
    assert plan is not None and plan.num_buckets >= 2, \
        f"bucketed audit needs a >=2-bucket plan at d={d}"
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)

    def trace():
        return jax.make_jaxpr(ln._round.raw)(
            ln.state, ids, batch, mask, jnp.float32(0.05),
            jax.random.PRNGKey(0))

    def retrace():
        rng = np.random.RandomState(3)

        def drive(i):
            ids_i = rng.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rng)
            ln.train_round_async(ids_i, b, m)

        return check_retrace(ln._round, None, repeats=3, warmup=1,
                             drive=drive)

    # W is bound as a footprint dim only where the fused path makes any
    # (W, d) aval illegal; the bucketed rule gets W separately so it can
    # police the worker reduce without arming the footprint ban for
    # local modes that own (W, d) state rows.
    dims = {"num_clients": n_clients, "d": d}
    if variant in FUSED_ROUND_MODES:
        dims["W"] = w
    kind = "sketch" if variant == "sketch" else "worker_reduce"
    return AuditTarget(
        name=f"round_bucketed/{variant}" + ("(mutated)" if mutate else ""),
        description=f"bucketed transmit, mode={variant}, "
                    f"plan sizes {plan.sizes} (TinyMLP hidden={hidden})",
        trace=trace,
        dims=dims,
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule(),
               BucketedTransmitRule(
                   plan.sizes, kind=kind, W=w,
                   c_eff=pad_cols(cfg_kw["num_cols"])
                   if kind == "sketch" else None)),
        retrace=retrace)


# --------------------------------------------------------------------------
# batched per-worker sketch kernel dispatch (round 8)
# --------------------------------------------------------------------------

def sketch_batched_target(mutate: bool = False) -> AuditTarget:
    """The per-worker transmit runs the BATCHED Pallas sketch kernel.

    Traces a sketch round with ``max_grad_norm`` set — the sketch-space
    clip is a per-worker nonlinearity, so ``round.build_round_step``
    takes the NON-fused path and each worker sketches its own grad under
    the round's worker vmap (federated/client.py) — and asserts via
    :class:`BatchedSketchRule` that a ``pallas_call`` producing the
    batched ``(W, r, c_eff)`` table appears INSIDE the vmapped transmit,
    with no ``(W, ·)`` segment-sum routing contraction left.

    Dispatch is forced with ``sketch_kernels.force_dispatch``: "kernel"
    overrides the backend gate so the tier-1 CPU trace walks the real
    kernel program (the Pallas interpreter executes it in the retrace
    drives); ``mutate=True`` forces "fallback" — the pre-round-8 program
    a guard revert would produce — and the audit must FAIL on it
    (tests/test_analysis_audits.py pins this). The context manager
    clears jit caches at both edges so neither mode's trace can be
    served from the other's cache; within one mode the compile cache
    must still stay at 1 (the retrace guard runs INSIDE the context).

    W=4 (not the usual 3) so the checked ``(W, r, c_eff)=(4, 3, 256)``
    and ``(W, c_eff)`` shapes cannot collide with the server's own
    ``(r, c_eff)=(3, 256)`` sketch-table eqns. W is NOT bound in dims —
    the per-worker path legitimately owns (W, d) grads.
    """
    from commefficient_tpu.ops import sketch_kernels
    from commefficient_tpu.ops.countsketch import pad_cols

    w, n_clients, hidden = 4, 7, 64
    cfg_kw = dict(ROUND_CFGS["sketch"], num_cols=256, max_grad_norm=1.0)
    mode = "fallback" if mutate else "kernel"
    ln = _make_learner(num_workers=w, num_clients=n_clients, hidden=hidden,
                       **cfg_kw)
    d = int(ln.state.last_changed.shape[0])
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)

    def trace():
        with sketch_kernels.force_dispatch(mode):
            return jax.make_jaxpr(ln._round.raw)(
                ln.state, ids, batch, mask, jnp.float32(0.05),
                jax.random.PRNGKey(0))

    def retrace():
        rng = np.random.RandomState(3)

        def drive(i):
            ids_i = rng.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rng)
            ln.train_round_async(ids_i, b, m)

        # one context around warmup + every drive: force_dispatch clears
        # jit caches at its edges, so entering per-drive would make the
        # cache-stays-at-1 guard vacuous
        with sketch_kernels.force_dispatch(mode):
            return check_retrace(ln._round, None, repeats=3, warmup=1,
                                 drive=drive)

    return AuditTarget(
        name="sketch_batched/per-worker" + ("(mutated)" if mutate else ""),
        description=f"per-worker vmapped sketch on the batched kernel, "
                    f"W={w}, d={d}, forced dispatch={mode}",
        trace=trace,
        dims={"num_clients": n_clients, "d": d},
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule(),
               BatchedSketchRule(W=w, r=cfg_kw["num_rows"],
                                 c_eff=pad_cols(cfg_kw["num_cols"]))),
        retrace=retrace)


# --------------------------------------------------------------------------
# fused server update (streaming top-k kernel path, round 9)
# --------------------------------------------------------------------------

#: max_live_d budgets per mode, measured on the fused program at HEAD —
#: zero slack, so re-materializing even one stage of the incumbent
#: d-vector chain fails. The mutated arms' counts sit strictly above
#: (18 and 190 vs these 13 and 5 at d=1000, k=5). The sketch arm's 5
#: holds no compaction of the select kernel's dense output to
#: (vals, idxs), no scatter back (that output is the update) and, since
#: the select pass writes over the one buffer of estimates, no
#: selection mask.
_FUSED_SERVER_BUDGETS = {"true_topk": 13, "sketch": 5}


def server_update_fused_target(mode: str = "true_topk",
                               mutate: bool = False) -> AuditTarget:
    """The server update runs the FUSED streaming top-k path.

    Traces the jitted ``server_update`` alone — the program the round
    step embeds — for the exact-mode true_topk and sketch configs, and
    asserts via :class:`FusedServerUpdateRule` that (1) the streaming
    radix/select ``pallas_call``s are present, (2) no sort-unit
    selection (``top_k``/``sort``) runs over the d-stream, and (3) the
    count of live d-shaped eqn outputs stays at the fused path's own
    measured budget — the ISSUE-20 contract that the round writes only
    the outputs it must keep (update / Vvelocity / Verror) and never
    re-materializes the estimates -> scores -> sort -> mask -> where
    chain.

    Dispatch is forced with ``force_dispatch`` exactly like
    :func:`sketch_batched_target`: "kernel" walks the real kernel
    program on CPU (the Pallas interpreter executes it in the retrace
    drives); ``mutate=True`` forces "fallback" — the incumbent chain a
    dispatch revert would produce — and the audit must FAIL on it
    (tests/test_analysis_audits.py pins all three violation classes).
    """
    from functools import partial

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.server import (init_server_opt_state,
                                                    make_sketch,
                                                    server_update)
    from commefficient_tpu.ops import sketch_kernels

    if mode not in ("true_topk", "sketch"):
        raise ValueError(f"mode must be true_topk|sketch, got {mode!r}")
    d, k = 1_000, 5
    cfg_kw = dict(mode=mode, k=k, error_type="virtual",
                  virtual_momentum=0.9)
    if mode == "sketch":
        cfg_kw.update(num_rows=3, num_cols=256)
    cfg = FedConfig(**cfg_kw).finalize(d)
    sketch = make_sketch(cfg) if mode == "sketch" else None
    state = init_server_opt_state(cfg)
    force = "fallback" if mutate else "kernel"

    def fn(g, st, lr):
        return server_update(g, st, cfg, lr, sketch=sketch)

    jitted = jax.jit(fn)
    g_shape = cfg.transmit_shape

    def trace():
        with sketch_kernels.force_dispatch(force):
            return jax.make_jaxpr(fn)(
                jnp.zeros(g_shape, jnp.float32), state, jnp.float32(0.05))

    def retrace():
        rng = np.random.RandomState(17)

        def make_args(i):
            return (jnp.asarray(rng.randn(*g_shape).astype(np.float32)),
                    state, jnp.float32(0.05))

        # one context around warmup + drives (force_dispatch clears jit
        # caches at its edges; the cache-stays-at-1 guard runs inside)
        with sketch_kernels.force_dispatch(force):
            return check_retrace(jitted, make_args, repeats=3, warmup=1)

    return AuditTarget(
        name=f"server_update_fused/{mode}" + ("(mutated)" if mutate else ""),
        description=f"fused server update, mode={mode}, d={d}, k={k}, "
                    f"forced dispatch={force}",
        trace=trace,
        dims={"d": d},
        rules=(FusedServerUpdateRule(
            max_live_d=_FUSED_SERVER_BUDGETS[mode], min_pallas=2),),
        retrace=retrace)


# --------------------------------------------------------------------------
# buffered asynchronous round (FedBuff-style server)
# --------------------------------------------------------------------------

def buffered_target() -> AuditTarget:
    """The fused lock-step program of the buffered server: cohort +
    staleness-weighted apply in ONE jit (the fault-free production path,
    and the program whose bit-identity with the sync round tier-1
    pins).  Built with quarantine ON and staleness_alpha != 0 so the
    audit walks the richest dataflow: the per-contribution exclusion
    masks and the (1+tau)^-alpha reweighting are both in the jaxpr.

    Same memory contract as round/local_topk: per-sampled-client (W, d)
    rows are owned state here, so only the (num_clients, d) ban binds.
    """
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.buffer import BufferedFedLearner
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import TinyMLP

    w, n_clients = 3, 7
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=w, num_clients=n_clients,
                    lr_scale=0.05, server_mode="buffered",
                    staleness_alpha=0.5, client_quarantine=True,
                    quarantine_rounds=3, **ROUND_CFGS["local_topk"])
    ln = BufferedFedLearner(model, cfg, make_cv_loss(model), None,
                            jax.random.PRNGKey(1),
                            np.zeros((1, 8), np.float32))
    d = int(ln.state.last_changed.shape[0])
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)

    def trace():
        return jax.make_jaxpr(ln._lockstep.raw)(
            ln.state, ids, batch, mask, jnp.float32(0.05),
            jax.random.PRNGKey(0))

    def retrace():
        rng = np.random.RandomState(3)

        def drive(i):
            ids_i = rng.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rng)
            ln.train_round_async(ids_i, b, m)

        return check_retrace(ln._lockstep, None, repeats=3, warmup=1,
                             drive=drive)

    return AuditTarget(
        name="buffered/lockstep",
        description="buffered async round, fused cohort+apply "
                    "(quarantine + staleness, TinyMLP scale)",
        trace=trace,
        dims={"num_clients": n_clients, "d": d},
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
        retrace=retrace)


def buffered_mesh_target(mutate: bool = False) -> AuditTarget:
    """The mesh-native buffered server: the split cohort -> deposit ->
    apply chain as pjit programs over a dp=2 ``clients`` mesh
    (federated/buffer.py with ``mesh=``).

    The multi-chip contract is that every slot-leading buffer aval is
    SHARDED along the clients axis — each shard owns its own rows of
    the W-slot cohort contribution and the M-slot server buffer
    (parallel/mesh.buffer_state_shardings), so no ``(W, d)`` or
    ``(M, d)`` aval is ever replicated. Inside the traced chain that
    contract is visible as the deposit path's ``sharding_constraint``
    eqns (buffer.py ``_pin``) pinning every slot-leading aval to a
    spec with the clients axis at the slot index; a REPLICATED
    constraint is the all-gather GSPMD would materialize on every
    shard (dp x the buffer HBM plus a per-deposit collective over all
    slot rows), and ZERO row pins means the layout is unpinned and
    GSPMD is free to pick exactly that. The transfer rule proves the
    event loop stays host-side: no callback crosses into the jitted
    chain. The retrace guard drives a REAL dp=2 event loop —
    seeded FaultModel stragglers/dropouts, heap-ordered deposits,
    buffer-full and flush-partial applies, plus a fault-free lockstep
    learner — and asserts all four programs' compile caches sit at
    ONE entry (the ``buffer=None`` cohort input and the committed
    slot-sharded buffer placement are what keep them there).

    ``mutate=True`` re-pins every deposited buffer leaf to the
    replicated spec ``P()`` between deposit and apply — the layout a
    replicated-buffer reintroduction would produce — and the audit
    must FAIL on it (tests/test_buffered_mesh.py pins this).

    Needs ``jax.device_count() >= 2`` (the CLI forces 8 virtual CPU
    devices; tests/conftest.py does the same).
    """
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.buffer import (BufferedFedLearner,
                                                    init_buffer)
    from commefficient_tpu.federated.faults import FaultModel
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import TinyMLP

    if jax.device_count() < 2:
        raise RuntimeError(
            "buffered_mesh needs >= 2 devices for the dp=2 mesh — on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "BEFORE jax is imported")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
    w, n_clients, m_slots = 2, 8, 4
    model = TinyMLP(num_classes=2, hidden=4)
    cfg = FedConfig(weight_decay=0, num_workers=w, num_clients=n_clients,
                    lr_scale=0.05, server_mode="buffered",
                    buffer_m=m_slots, staleness_alpha=0.5,
                    client_quarantine=True, quarantine_rounds=3,
                    **ROUND_CFGS["local_topk"])

    def make_learner(fault_model=None):
        return BufferedFedLearner(
            model, cfg, make_cv_loss(model), None, jax.random.PRNGKey(1),
            np.zeros((1, 8), np.float32), mesh=mesh,
            fault_model=fault_model)

    ln = make_learner()
    d = int(ln.state.last_changed.shape[0])
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)
    take = jnp.ones((w,), bool)

    def chain(state, ids, batch, mask, lr, rng, take):
        # the fault path's real program sequence: cohort against the
        # current weights, deposit of the arrival take-mask into an
        # empty M-slot buffer, staleness-weighted apply
        contrib, cm = ln._cohort.raw(state.replace(buffer=None), ids,
                                     batch, mask, lr, rng)
        buf = ln._deposit.raw(init_buffer(contrib, m_slots,
                                          cfg.num_clients), contrib, take)
        if mutate:
            rep = NamedSharding(mesh, PSpec())
            buf = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, rep), buf)
        new_state, am = ln._apply.raw(state.replace(buffer=buf), lr, rng)
        return new_state, cm, am

    def trace():
        return jax.make_jaxpr(chain)(
            ln.state, ids, batch, mask, jnp.float32(0.05),
            jax.random.PRNGKey(0), take)

    def retrace():
        report = RuleReport(rule="retrace", ok=True)

        def flag(msg):
            report.ok = False
            report.violations.append(Violation(
                rule="retrace", path="", primitive="jit", message=msg))

        fm = FaultModel(7, n_clients, straggler_frac=0.25,
                        dropout_prob=0.1)
        ln_f = make_learner(fault_model=fm)
        ln_l = make_learner()            # fault-free: fused lockstep
        rs = np.random.RandomState(3)
        for _ in range(6):
            ids_i = rs.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rs)
            ln_f.train_round_async(ids_i, b, m)
            ln_l.train_round_async(ids_i, b, m)
        ln_f.flush_faults()
        stats = ln_f.fault_stats
        if stats["applies"] < 1 or stats["arrivals"] < 1:
            flag(f"fault-model drive exercised no deposit/apply "
                 f"({stats}) — the cache assertions would be vacuous")
        for name, fn in (("cohort", ln_f._cohort),
                         ("deposit", ln_f._deposit),
                         ("apply", ln_f._apply),
                         ("lockstep", ln_l._lockstep)):
            n = fn._cache_size()
            if n != 1:
                flag(f"{name} compile cache at {n} entries (want "
                     f"exactly 1) after the driven dp=2 event loop")
        report.checked_eqns = 12
        report.notes = (f"6 fault-model cohorts + flush and 6 lockstep "
                        f"cohorts on the dp=2 mesh; fault_stats {stats}")
        return report

    return AuditTarget(
        name="buffered_mesh/chain" + ("(mutated)" if mutate else ""),
        description="mesh-native buffered cohort->deposit->apply chain "
                    "(dp=2); every slot-leading buffer aval must be "
                    "pinned slot-sharded along 'clients' — replicated "
                    "slot rows (the all-gather layout) are banned"
                    + (" [replicated-buffer mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"num_clients": n_clients, "d": d},
        rules=(FootprintRule(DEFAULT_PATTERNS),
               ShardedBufferRule("clients", W=w, M=m_slots),
               TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# client state store (placement x representation)
# --------------------------------------------------------------------------

def client_store_target(mutate: bool = False) -> AuditTarget:
    """The million-client round: host-arena placement + sparse O(k) rows
    (federated/client_store.py). The audited program is the OFFLOAD round
    — client rows live in per-shard host arenas, the jit receives only
    the W sampled rows — so a ``(num_clients, d)`` aval anywhere in the
    jaxpr is a dense device arena leaking back in. The rule is STRICT:
    unlike ``round/local_topk``'s footprint ban, no scatter-writeback
    allowlist applies, because the offload program has no legitimate
    n-leading eqn at all.

    ``mutate=True`` builds the same config with device-resident dense
    state — the program a dense-arena reintroduction would produce — and
    the audit must FAIL on it (tests/test_client_store.py pins this),
    which is what makes a PASS on the real program meaningful.
    """
    w, n_clients = 3, 9
    # k=24 >= d/2=23: the local_topk residual has nnz <= d - k <= k, so
    # the sparse codec is exact (the bitwise dense<->sparse contract)
    cfg_kw = dict(mode="local_topk", error_type="local",
                  local_momentum=0.9, k=24, client_state="sparse",
                  client_state_offload=True)
    if mutate:
        cfg_kw.update(client_state="dense", client_state_offload=False)
    ln = _make_learner(num_workers=w, num_clients=n_clients, **cfg_kw)
    d = int(ln.state.last_changed.shape[0])
    batch, mask = _round_batch(w)
    ids = jnp.arange(w, dtype=jnp.int32)

    if mutate:
        def trace():
            return jax.make_jaxpr(ln._round.raw)(
                ln.state, ids, batch, mask, jnp.float32(0.05),
                jax.random.PRNGKey(0))
    else:
        rows = ln._offload_pipe.gather(np.arange(w))

        def trace():
            return jax.make_jaxpr(ln._round.raw)(
                ln.state, rows, ids, batch, mask, jnp.float32(0.05),
                jax.random.PRNGKey(0))

    def retrace():
        rng = np.random.RandomState(3)

        def drive(i):
            ids_i = rng.choice(n_clients, w, replace=False)
            b, m = _round_batch(w, rng)
            ln.train_round_async(ids_i, b, m)

        return check_retrace(ln._round, None, repeats=3, warmup=1,
                             drive=drive)

    strict = ShapePattern(("num_clients", "d"),
                          label="dense client arena",
                          allow_primitives=frozenset())
    return AuditTarget(
        name="client_store/offload-sparse" + ("(mutated)" if mutate else ""),
        description="offload round with sparse O(k) client rows; strict "
                    "no-(num_clients, d) ban"
                    + (" [device-dense mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"num_clients": n_clients, "d": d},
        rules=(FootprintRule((strict,) + DEFAULT_PATTERNS[1:]),
               TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# GPT2 train step (remat=True)
# --------------------------------------------------------------------------

def gpt2_target() -> AuditTarget:
    from commefficient_tpu.federated.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    B, C, T, V = 3, 2, 16, 300
    cfg = GPT2Config.tiny(vocab_size=V)
    cfg.remat = True
    cfg.dropout = 0.1
    # the audited contract is the production attention path: blockwise
    # keeps scores in (block, block) tiles, never a full (B*C, H, T, T)
    cfg.attn_impl = "blockwise"
    cfg.attn_block_size = 8
    model = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, V, (B, C, T)).astype(np.int32))
    types = jnp.asarray(rng.randint(0, 3, (B, C, T)).astype(np.int32))
    mc = jnp.full((B, C), T - 1, jnp.int32)
    labels = jnp.asarray(np.where(rng.rand(B, C, T) < 0.5,
                                  np.asarray(ids), -1).astype(np.int32))
    mcl = jnp.ones((B,), jnp.int32)
    batch = (ids, mc, labels, mcl, types)
    params = model.init(jax.random.PRNGKey(0), ids, types, mc,
                        train=False)["params"]
    apply_loss = make_gpt2_train_loss(model)

    def step(p, bt, key):
        def total(q):
            loss, _ = apply_loss(q, bt, key, True)
            return jnp.sum(loss)

        grads = jax.grad(total)(p)
        return jax.tree.map(lambda x, g: x - 0.1 * g, p, grads)

    def trace():
        return jax.make_jaxpr(step)(params, batch, jax.random.PRNGKey(1))

    def retrace():
        jitted = jax.jit(step)
        rs = np.random.RandomState(11)

        def make_args(i):
            ids_i = jnp.asarray(rs.randint(0, V, (B, C, T)).astype(np.int32))
            bt = (ids_i, mc, labels, mcl, types)
            return (params, bt, jax.random.PRNGKey(i))

        return check_retrace(jitted, make_args, repeats=3, warmup=1)

    return AuditTarget(
        name="gpt2/train-step",
        description="GPT2 tiny train step, remat=True, blockwise attention",
        trace=trace,
        # attention folds choices into the batch: scores would be
        # (B*C, H, T, T) if materialized
        dims={"B": B * C, "H": cfg.n_head, "T": T},
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# flash attention custom VJP
# --------------------------------------------------------------------------

def attention_target(bwd: bool = True) -> AuditTarget:
    from commefficient_tpu.ops.flash_attention import flash_attention

    B, T, H, D = 2, 64, 2, 8
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
               for _ in range(3))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               interpret=True)

    if bwd:
        fn = jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v)),
                      argnums=(0, 1, 2))
        name = "attention/flash-bwd"
        desc = "flash attention backward (custom-VJP bwd, inlined by grad)"
    else:
        fn = fwd
        name = "attention/flash-fwd"
        desc = "flash attention forward (custom_vjp_call descent)"

    def trace():
        return jax.make_jaxpr(fn)(q, k, v)

    def retrace():
        jitted = jax.jit(fn)
        rs = np.random.RandomState(13)

        def make_args(i):
            return tuple(jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
                         for _ in range(3))

        return check_retrace(jitted, make_args, repeats=3, warmup=1)

    return AuditTarget(
        name=name, description=desc, trace=trace,
        dims={"B": B, "H": H, "T": T},
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
        # interpret-mode pallas compiles per call on CPU are still
        # cached by jit; the retrace check holds
        retrace=retrace)


# --------------------------------------------------------------------------
# KV-cached decode (serving path)
# --------------------------------------------------------------------------

def _decode_engine(batch=3, mesh=None):
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import DecodeEngine

    S, V = 32, 300
    cfg = GPT2Config.tiny(vocab_size=V)
    model = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(17)
    ids = jnp.asarray(rng.randint(0, V, (1, 1, 8)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), ids, ids,
                        jnp.zeros((1, 1), jnp.int32),
                        train=False)["params"]
    return DecodeEngine(model, params, eos_id=V - 1, max_len=S,
                        mesh=mesh), S


def decode_target(program: str = "step") -> AuditTarget:
    """The serving path's decode programs (serving/decode.py).

    ``step`` — one token for every row, sampling inside the program.
    The retrace guard drives the jitted step with fresh token/position
    VALUES each call and asserts the compile cache stays flat: token
    generation never retraces.  ``generate`` — the whole-reply program
    (prefill + lax.scan of the step), walked through the scan body.

    Both bind T to the CACHE capacity S, so the footprint rule bans a
    materialized (B, H, S, S) score tensor anywhere in the program —
    the single-query decode attention is (B, H, 1, S), O(S) per token —
    and the transfer rule proves no host callback hides inside the
    token loop."""
    engine, S = _decode_engine()
    B = 3
    cfg = engine.model.config
    tok = jnp.asarray(np.full((B,), 5, np.int32))
    typ = jnp.asarray(np.full((B,), 7, np.int32))
    pos = jnp.asarray(np.array([3, 9, 1], np.int32))
    rng0 = jax.random.PRNGKey(2)
    done = jnp.zeros((B,), bool)

    if program == "step":
        def trace():
            return jax.make_jaxpr(engine._step_raw)(
                engine.params, engine.init_cache(B), tok, typ, pos,
                rng0, done)

        def retrace():
            cache = engine.init_cache(B)
            rs = np.random.RandomState(23)
            state = {"cache": cache, "tok": tok, "pos": pos,
                     "rng": rng0, "done": done}

            def drive(i):
                # fresh token/position values every call — the across-
                # tokens axis the gate is about
                out = engine.step(engine.params, state["cache"],
                                  state["tok"], typ, state["pos"],
                                  state["rng"], state["done"])
                state["cache"], state["tok"], state["pos"], \
                    state["rng"], state["done"] = out

            return check_retrace(engine.step, None, repeats=3, warmup=1,
                                 drive=drive)

        return AuditTarget(
            name="decode/step",
            description="KV-cached decode step, sampling in-program "
                        "(GPT2 tiny, cache S=32)",
            trace=trace,
            dims={"B": B, "H": cfg.n_head, "T": S},
            rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
            retrace=retrace)

    P, max_new = 8, 6
    rs = np.random.RandomState(19)

    def _prompts(i):
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size - 1,
                                     (B, P)).astype(np.int32))
        types = jnp.asarray(np.full((B, P), 7, np.int32))
        lengths = jnp.asarray(np.array([8, 5, 3], np.int32))
        return (engine.params, ids, types, lengths,
                jnp.asarray(np.full((B,), 7, np.int32)),
                jax.random.PRNGKey(i))

    def trace():
        args = _prompts(0)
        return jax.make_jaxpr(
            lambda *a: engine._generate_raw(*a, max_new=max_new))(*args)

    def retrace():
        def drive(i):
            engine.generate_tokens(*_prompts(i), max_new=max_new)

        return check_retrace(engine.generate_tokens, None, repeats=3,
                             warmup=1, drive=drive)

    return AuditTarget(
        name="decode/generate",
        description="prefill + scanned decode loop, one dispatch per "
                    "reply (GPT2 tiny, cache S=32)",
        trace=trace,
        dims={"B": B, "H": cfg.n_head, "T": S},
        rules=(FootprintRule(DEFAULT_PATTERNS), TransferRule()),
        retrace=retrace)


def decode_paged_target(mutate: bool = False) -> AuditTarget:
    """The block-paged serving step (serving/paged_cache.py + the
    ``paged_step`` program in serving/decode.py).

    The paged contract is that per-slot KV state lives ONLY in the page
    pools — ``(num_pages, page_size, H, hd)`` per layer — reached
    through the traced page table, so a ``(slots, max_len, H, hd)`` aval
    anywhere in the step is the dense per-slot cache slab leaking back
    in (the exact HBM reservation paging exists to remove), and a
    ``(slots, max_len)`` aval is its one-hot position-write mask.  The
    rule is STRICT (no allowlist): the paged program's gathered pages
    stay 5-D end to end (ops/attention.paged_decode_attention), so no
    legitimate eqn carries either shape.  The transfer rule proves the
    host bookkeeping (free lists, refcounts, prefix sharing) stays
    between steps, and the retrace guard drives the step through a REAL
    paged server — admissions, evictions, page-boundary crossings and
    shared prompt pages — asserting the compile cache stays flat.

    ``mutate=True`` traces the dense fixed-slot step at the same dims —
    the program a dense-slab reintroduction would produce — and the
    audit must FAIL on it (tests/test_paged_serving.py pins this).
    """
    engine, S = _decode_engine()
    B = 3
    cfg = engine.model.config
    page_size = 8
    tok = jnp.asarray(np.full((B,), 5, np.int32))
    typ = jnp.asarray(np.full((B,), 7, np.int32))
    pos = jnp.asarray(np.array([3, 9, 1], np.int32))
    rng0 = jax.random.PRNGKey(2)
    done = jnp.zeros((B,), bool)
    max_pages = S // page_size
    num_pages = 1 + B * max_pages

    if mutate:
        def trace():
            return jax.make_jaxpr(engine._step_raw)(
                engine.params, engine.init_cache(B), tok, typ, pos,
                rng0, done)
    else:
        def trace():
            pools = engine.init_paged_pools(num_pages, page_size)
            pt = jnp.zeros((B, max_pages), jnp.int32)
            return jax.make_jaxpr(engine._paged_step_raw)(
                engine.params, pools, pt, tok, typ, pos, rng0, done)

    def retrace():
        from commefficient_tpu.serving import ContinuousBatchingServer
        srv = ContinuousBatchingServer(engine, slots=B, prefill_len=16,
                                       kv_cache="paged",
                                       page_size=page_size)
        rs = np.random.RandomState(31)
        V = cfg.vocab_size
        shared = [int(t) for t in rs.randint(0, V - 1, 16)]

        def drive(i):
            if len(srv._queue) < 2:
                # two sharers of the same 2-page prompt + a private one:
                # every step sees a fresh page table (admission churn,
                # refcounted shared pages, frontier allocations)
                srv.submit(shared, [7] * 16, 7, 5)
                srv.submit(shared, [7] * 16, 7, 3)
                pl = int(rs.randint(3, 12))
                srv.submit([int(t) for t in rs.randint(0, V - 1, pl)],
                           [7] * pl, 7, 4)
            srv.step()

        return check_retrace(engine.paged_step, None, repeats=3,
                             warmup=1, drive=drive)

    slab = ShapePattern(("slots", "max_len", "H", "hd"),
                        label="dense per-slot KV cache slab",
                        allow_primitives=frozenset())
    posmask = ShapePattern(("slots", "max_len"),
                           label="dense per-slot position mask",
                           allow_primitives=frozenset())
    return AuditTarget(
        name="decode_paged/step" + ("(mutated)" if mutate else ""),
        description="block-paged decode step against page pools + traced "
                    "page table; strict no-(slots, max_len, H, hd) ban"
                    + (" [dense-slab mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"slots": B, "max_len": S, "H": cfg.n_head,
              "hd": cfg.n_embd // cfg.n_head},
        rules=(FootprintRule((slab, posmask)), TransferRule()),
        retrace=retrace)


def decode_speculative_target(mutate: bool = False) -> AuditTarget:
    """The speculative verify step over the paged pools
    (serving/speculative.py ``_paged_verify_raw``).

    Same contract as ``decode_paged``, extended to the multi-token
    verify window: per-slot KV state lives ONLY in the page pools
    reached through the traced page table, so a
    ``(slots, max_len, H, hd)`` aval anywhere in the verify program is
    the dense per-slot slab leaking back in, and a ``(slots, max_len)``
    aval is its position-write mask.  Strict (no allowlist): the paged
    verify's gathered pages stay 5-D end to end
    (ops/attention.paged_verify_attention) and its γ+1 writes route
    through the page table, so no legitimate eqn carries either shape.
    The retrace guard drives a REAL speculative paged server —
    admission churn, variable per-slot acceptance, mid-stream
    rollbacks, page-boundary crossings — and asserts BOTH the verify
    and the draft compile caches stay at one program (the per-slot-
    variable-acceptance-via-masks invariant: acceptance length never
    becomes a shape).

    ``mutate=True`` traces the DENSE-cache verify (``_verify_raw``) at
    the same dims — the program a dense-slab verify would produce — and
    the audit must FAIL on it (tests/test_speculative.py pins this)."""
    from commefficient_tpu.serving.speculative import SpeculativeDecoder

    engine, S = _decode_engine()
    B, gamma, page_size = 3, 3, 8
    cfg = engine.model.config
    spec = SpeculativeDecoder(engine, gamma=gamma, slots=B)
    tok = jnp.asarray(np.full((B,), 5, np.int32))
    typ = jnp.asarray(np.full((B,), 7, np.int32))
    pos = jnp.asarray(np.array([3, 9, 1], np.int32))
    drafts = jnp.asarray(np.full((B, gamma), 6, np.int32))
    done = jnp.zeros((B,), bool)
    max_pages = S // page_size
    num_pages = 1 + B * max_pages

    if mutate:
        def trace():
            return jax.make_jaxpr(spec._verify_raw)(
                engine.params, engine.init_cache(B), tok, typ, pos,
                drafts, done)
    else:
        def trace():
            pools = engine.init_paged_pools(num_pages, page_size)
            pt = jnp.zeros((B, max_pages), jnp.int32)
            return jax.make_jaxpr(spec._paged_verify_raw)(
                engine.params, pools, pt, tok, typ, pos, drafts, done)

    def retrace():
        from commefficient_tpu.serving import ContinuousBatchingServer
        srv = ContinuousBatchingServer(engine, slots=B, prefill_len=16,
                                       kv_cache="paged",
                                       page_size=page_size,
                                       speculate_k=gamma)
        rs = np.random.RandomState(37)
        V = cfg.vocab_size

        def drive(i):
            if len(srv._queue) < 2:
                # fresh prompts/budgets every round: variable per-slot
                # acceptance and mid-stream rollback must reuse the same
                # two compiled programs
                for _ in range(3):
                    pl = int(rs.randint(3, 12))
                    srv.submit([int(t) for t in rs.randint(0, V - 1, pl)],
                               [7] * pl, 7, int(rs.randint(2, 8)))
            srv.step()

        report = check_retrace(srv.spec.paged_verify, None, repeats=3,
                               warmup=1, drive=drive)
        dsize = srv.spec.draft._cache_size()
        if dsize > 1:
            from .rules import Violation
            report.ok = False
            report.violations.append(Violation(
                rule="retrace", path="", primitive="jit",
                message=f"draft program compiled {dsize} variants — "
                        f"acceptance length leaked into a shape"))
        report.notes += f"; draft cache size {dsize}"
        return report

    slab = ShapePattern(("slots", "max_len", "H", "hd"),
                        label="dense per-slot KV cache slab",
                        allow_primitives=frozenset())
    posmask = ShapePattern(("slots", "max_len"),
                           label="dense per-slot position mask",
                           allow_primitives=frozenset())
    return AuditTarget(
        name="decode_speculative/verify" + ("(mutated)" if mutate else ""),
        description="speculative multi-token verify against page pools + "
                    "traced page table; strict no-(slots, max_len, H, hd) "
                    "ban; draft + verify caches must stay at one program"
                    + (" [dense-cache verify mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"slots": B, "max_len": S, "H": cfg.n_head,
              "hd": cfg.n_embd // cfg.n_head},
        rules=(FootprintRule((slab, posmask)), TransferRule()),
        retrace=retrace)


def decode_paged_quant_target(mutate: bool = False) -> AuditTarget:
    """The quantized paged decode step (ops/kv_quant.py codec +
    ``paged_step`` over int8 pools).

    The quantization contract is that the KV pools live in HBM at the
    CODEC dtype — ``(num_pages, page_size, H, hd)`` int8 plus
    ``(num_pages, H)`` f32 scale rows — and dequantization happens only
    on GATHERED pages inside the attention kernel (the 5-D
    ``(B, M, P, H, D)`` working set), never on the pool itself.  So a
    FLOAT32 aval of the pool's shape anywhere in the step is the codec
    silently round-tripping the whole pool through f32 — the exact HBM
    reservation quantization exists to remove.  The ban is dtype-scoped
    because the pool shape itself is legal at int8: the requant-on-write
    scatters produce pool-shaped int8 outputs by design.  The retrace
    guard drives the step through a REAL int8 paged server (admissions,
    requant writes, page-boundary crossings) and asserts the compile
    cache stays flat.

    ``mutate=True`` traces the UNQUANTIZED paged step at the same dims —
    whose f32 pool-shaped write-back scatters are exactly the aval the
    rule bans — proving the dtype-scoped gate is live
    (tests/test_serving_kv_quant.py pins this).
    """
    engine, S = _decode_engine()
    B = 3
    cfg = engine.model.config
    page_size = 8
    tok = jnp.asarray(np.full((B,), 5, np.int32))
    typ = jnp.asarray(np.full((B,), 7, np.int32))
    pos = jnp.asarray(np.array([3, 9, 1], np.int32))
    rng0 = jax.random.PRNGKey(2)
    done = jnp.zeros((B,), bool)
    max_pages = S // page_size
    num_pages = 1 + B * max_pages

    def trace():
        mode = "none" if mutate else "int8"
        pools = engine.init_paged_pools(num_pages, page_size,
                                        kv_quant=mode)
        pt = jnp.zeros((B, max_pages), jnp.int32)
        return jax.make_jaxpr(engine._paged_step_raw)(
            engine.params, pools, pt, tok, typ, pos, rng0, done)

    def retrace():
        from commefficient_tpu.serving import ContinuousBatchingServer
        srv = ContinuousBatchingServer(engine, slots=B, prefill_len=16,
                                       kv_cache="paged",
                                       page_size=page_size,
                                       kv_quant="int8")
        rs = np.random.RandomState(41)
        V = cfg.vocab_size
        shared = [int(t) for t in rs.randint(0, V - 1, 16)]

        def drive(i):
            if len(srv._queue) < 2:
                # same churn as decode_paged — shared-prefix sharers +
                # a private prompt — but every write requantizes pages
                srv.submit(shared, [7] * 16, 7, 5)
                srv.submit(shared, [7] * 16, 7, 3)
                pl = int(rs.randint(3, 12))
                srv.submit([int(t) for t in rs.randint(0, V - 1, pl)],
                           [7] * pl, 7, 4)
            srv.step()

        return check_retrace(engine.paged_step, None, repeats=3,
                             warmup=1, drive=drive)

    f32pool = ShapePattern(("num_pages", "page_size", "H", "hd"),
                           label="f32 materialization of the quantized "
                                 "KV pool",
                           allow_primitives=frozenset(),
                           dtype="float32")
    return AuditTarget(
        name="decode_paged_quant/step" + ("(mutated)" if mutate else ""),
        description="int8-paged decode step; pool stays codec-dtype, "
                    "dequant only on gathered pages — strict ban on any "
                    "f32 aval of the pool shape"
                    + (" [unquantized-pool mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"num_pages": num_pages, "page_size": page_size,
              "H": cfg.n_head, "hd": cfg.n_embd // cfg.n_head},
        rules=(FootprintRule((f32pool,)), TransferRule()),
        retrace=retrace)


def serve_multihost_target(mutate: bool = False) -> AuditTarget:
    """The tensor-parallel paged decode step (serving/decode.py with a
    ``mesh`` + parallel/tp.py ``constrain_kv_cache_tp``).

    The multi-host contract is that the page pools are SHARDED along
    the KV head axis — each shard holds ``(num_pages, page_size,
    H/tp, hd)`` and the paged gathers stay shard-local, because heads
    are a batch dimension in every attention einsum.  Inside the traced
    step that contract is visible as ``sharding_constraint`` eqns
    pinning every pool-shaped aval to a spec with the model axis at the
    head index; a REPLICATED pool constraint is the all-gather GSPMD
    would materialize on every shard (tp× the pool HBM plus a per-step
    collective over the whole KV state), and ZERO pool constraints
    means the layout is unpinned and GSPMD is free to pick exactly
    that.  The transfer rule proves the page-table bookkeeping stays a
    host-side allocator: no per-step host gather of the sharded pools.
    The retrace guard drives a REAL tp=2 paged server — admissions,
    evictions, shared prompt pages, page-boundary crossings — and
    asserts the compile cache stays at ONE program (per-shard pool
    shapes never leak into trace-time Python).

    ``mutate=True`` re-pins every pool leaf to the replicated spec
    ``P()`` before the step — the layout an all-gather reintroduction
    would produce — and the audit must FAIL on it
    (tests/test_serving_multihost.py pins this).

    Needs ``jax.device_count() >= 2`` (the CLI forces 8 virtual CPU
    devices; tests/conftest.py does the same).
    """
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    if jax.device_count() < 2:
        raise RuntimeError(
            "serve_multihost needs >= 2 devices for the tp=2 mesh — on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "BEFORE jax is imported")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    engine, S = _decode_engine(mesh=mesh)
    B = 3
    cfg = engine.model.config
    page_size = 8
    tok = jnp.asarray(np.full((B,), 5, np.int32))
    typ = jnp.asarray(np.full((B,), 7, np.int32))
    pos = jnp.asarray(np.array([3, 9, 1], np.int32))
    rng0 = jax.random.PRNGKey(2)
    done = jnp.zeros((B,), bool)
    max_pages = S // page_size
    num_pages = 1 + B * max_pages

    def trace():
        pools = engine.init_paged_pools(num_pages, page_size)
        pt = jnp.zeros((B, max_pages), jnp.int32)
        if mutate:
            rep = NamedSharding(mesh, PSpec())

            def step_replicated(params, pools, pt, tok, typ, pos, rng,
                                done):
                pools = tuple(
                    {k: jax.lax.with_sharding_constraint(v, rep)
                     for k, v in layer.items()} for layer in pools)
                return engine._paged_step_raw(params, pools, pt, tok,
                                              typ, pos, rng, done)

            return jax.make_jaxpr(step_replicated)(
                engine.params, pools, pt, tok, typ, pos, rng0, done)
        return jax.make_jaxpr(engine._paged_step_raw)(
            engine.params, pools, pt, tok, typ, pos, rng0, done)

    def retrace():
        from commefficient_tpu.serving import ContinuousBatchingServer
        srv = ContinuousBatchingServer(engine, slots=B, prefill_len=16,
                                       kv_cache="paged",
                                       page_size=page_size)
        rs = np.random.RandomState(43)
        V = cfg.vocab_size
        shared = [int(t) for t in rs.randint(0, V - 1, 16)]

        def drive(i):
            if len(srv._queue) < 2:
                # same churn as decode_paged, but every step runs the
                # head-sharded program: per-shard pool shapes must not
                # leak into trace-time Python
                srv.submit(shared, [7] * 16, 7, 5)
                srv.submit(shared, [7] * 16, 7, 3)
                pl = int(rs.randint(3, 12))
                srv.submit([int(t) for t in rs.randint(0, V - 1, pl)],
                           [7] * pl, 7, 4)
            srv.step()

        return check_retrace(engine.paged_step, None, repeats=3,
                             warmup=1, drive=drive)

    return AuditTarget(
        name="serve_multihost/step" + ("(mutated)" if mutate else ""),
        description="tensor-parallel (tp=2) paged decode step; every "
                    "pool-shaped aval must be pinned head-sharded along "
                    "'model' — replicated pools (the all-gather layout) "
                    "are banned"
                    + (" [replicated-pool mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"num_pages": num_pages, "page_size": page_size,
              "H": cfg.n_head, "hd": cfg.n_embd // cfg.n_head},
        rules=(ShardedPoolRule("model"), TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# train-while-serve online loop
# --------------------------------------------------------------------------

def online_loop_target(mutate: bool = False) -> AuditTarget:
    """The train-while-serve cycle (commefficient_tpu/online/).

    The audited PROGRAM is the buffered lock-step cohort over
    collector-built batches — the exact jit ``OnlineLoop`` dispatches
    between decode steps — under the STRICT ``(num_clients, d)`` ban:
    online client state is sparse-encoded ``(num_clients, O(k))``
    arenas read through ``LearnerClientStore``, so a dense client
    matrix anywhere in the cohort program is the densification the
    subsystem exists to avoid (no writeback allowlist applies; the
    sparse round has no legitimate n-leading eqn at all).

    The retrace guard drives the REAL cycle end to end: synthetic
    per-user traffic through a paged personalized server, finished
    replies collected into cohorts, lock-step applies, and >= 2 hot
    swaps through ``HotSwapCoordinator`` — asserting that

    * the paged step AND pack programs never grow past ONE compiled
      variant across every swap (swap_base_params re-places leaves
      onto the old shardings/commitment; params cross every serving
      jit as traced arguments, with personalization admit/evict churn
      in between), and
    * every swap was CLEAN (``server.dirty_swaps == 0`` — the drain
      ran first, so every reply finished under its admission-time
      weights; tests/test_online.py pins that parity bitwise).

    ``mutate=True`` keeps the same build but fires one
    ``coordinator.swap(..., force=True)`` while a slot is verifiably
    mid-decode — the skip-the-drain bug — and the audit must FAIL on
    it (tests/test_online.py pins this): the forced swap surfaces as
    ``dirty_swaps > 0``.
    """
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.buffer import BufferedFedLearner
    from commefficient_tpu.federated.losses import (make_gpt2_train_loss,
                                                    make_gpt2_val_loss)
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.online import (HotSwapCoordinator,
                                          InteractionCollector,
                                          LearnerClientStore, OnlineLoop)
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)
    from commefficient_tpu.serving.personalize import PersonalizationIndex

    n_clients, W, B, S, V = 6, 2, 2, 32, 300
    eos = V - 1
    model = GPT2DoubleHeads(GPT2Config.tiny(vocab_size=V))

    class _Wrap:
        def init(self, rng, s, train):
            return model.init(rng, *s, train=train)

        def apply(self, *a, **k):
            return model.apply(*a, **k)

    # lr small enough that training does NOT collapse replies to an
    # immediate eos (every collected example carries an eos-labeled
    # tail): probes must keep decoding across swap boundaries for the
    # parity/dirty checks to have anything to straddle
    cfg = FedConfig(mode="local_topk", error_type="local",
                    local_momentum=0.9, k=16, client_state="sparse",
                    weight_decay=0, num_workers=W, num_clients=n_clients,
                    lr_scale=0.05, server_mode="buffered")
    collector = InteractionCollector(n_clients, S, num_candidates=1,
                                     eos_id=eos)
    sample = collector.sample_batch()
    ln = BufferedFedLearner(_Wrap(), cfg, make_gpt2_train_loss(model, 1., 1.),
                            make_gpt2_val_loss(model), jax.random.PRNGKey(5),
                            (sample[0], sample[4], sample[1]))
    d = int(ln.state.last_changed.shape[0])
    # all-padding cohort at the collector's exact shapes (shape source
    # only, like the learner's init sample)
    ids0, cols0, mask0 = InteractionCollector(
        n_clients, S, num_candidates=1, eos_id=eos).sample_round(W, B)

    def trace():
        return jax.make_jaxpr(ln._lockstep.raw)(
            ln.state, jnp.asarray(ids0),
            tuple(jnp.asarray(c) for c in cols0), jnp.asarray(mask0),
            jnp.float32(0.05), jax.random.PRNGKey(0))

    def retrace():
        from .rules import Violation
        engine = DecodeEngine(model, ln.params, eos_id=eos, max_len=S,
                              method="greedy")
        store = LearnerClientStore(ln)
        collector.store = store
        srv = ContinuousBatchingServer(
            engine, slots=4, prefill_len=S, kv_cache="paged",
            personalize=PersonalizationIndex(engine.params, store))
        coord = HotSwapCoordinator(srv, ln, resubmit=False)
        loop = OnlineLoop(srv, collector, ln, coord, train_every=2,
                          swap_every=1, num_workers=W, local_batch_size=B,
                          max_new=4)
        rs = np.random.RandomState(41)
        forced = [0]

        def feed():
            while loop.inflight() < srv.slots:
                pl = int(rs.randint(3, 8))
                gold = [int(t) for t in
                        rs.randint(0, V - 1, int(rs.randint(3, 6)))]
                loop.submit([int(t) for t in rs.randint(0, V - 1, pl)],
                            [7] * pl, 7, max_new=len(gold),
                            user_id=int(rs.randint(0, n_clients)),
                            label_ids=gold)

        def drive(i):
            # each call lands (at least) one more CLEAN swap: traffic in,
            # replies collected, cohorts trained, coordinator swap
            target = loop.swaps + 1
            for _ in range(80):
                feed()
                loop.step()
                if loop.swaps >= target:
                    break
            if mutate and i == 2 and not forced[0]:
                # the deliberate bug: swap under ACTIVE slots. Pump the
                # server directly (srv.step never swaps, unlike
                # loop.step) until a slot is verifiably mid-decode, then
                # skip the drain.
                feed()
                for _ in range(20):
                    loop._record_finished(srv.step())
                    if any(r is not None for r in srv._slot_req):
                        break
                coord.swap(jax.tree.map(
                    lambda x: x + 0.1 * jnp.sin(
                        jnp.arange(x.size, dtype=jnp.float32)
                    ).reshape(x.shape).astype(x.dtype), ln.params),
                    force=True)
                forced[0] = 1

        report = check_retrace(engine.paged_step, None, repeats=3,
                               warmup=1, drive=drive)

        def flag(msg):
            report.ok = False
            report.violations.append(Violation(
                rule="retrace", path="", primitive="jit", message=msg))

        pack = engine.paged_insert._cache_size()
        dirty = int(srv.dirty_swaps)
        if pack > 1:
            flag(f"paged pack program compiled {pack} variants across "
                 f"{loop.swaps} swaps — the swap leaked a new call "
                 f"signature (sharding/commitment drift)")
        if loop.swaps < 2:
            flag(f"drive landed only {loop.swaps} clean swaps — the "
                 f"audit never exercised the swap boundary")
        if dirty:
            flag(f"{dirty} dirty swap(s): weights moved under active "
                 f"slots — the drain-before-swap contract was skipped")
        report.notes += (f"; {loop.swaps} clean swaps, {dirty} dirty, "
                         f"{loop.rounds_done} cohorts over "
                         f"{collector.collected} collected interactions, "
                         f"pack cache {pack}")
        return report

    strict = ShapePattern(("num_clients", "d"),
                          label="dense client matrix",
                          allow_primitives=frozenset())
    return AuditTarget(
        name="online_loop/cycle" + ("(mutated)" if mutate else ""),
        description="train-while-serve cohort over collector batches; "
                    "strict no-(num_clients, d) ban; retrace drives the "
                    "real serve->collect->train->swap cycle, caches at "
                    "one program, every swap drained-before-swapped"
                    + (" [forced dirty-swap mutation — must fail]"
                       if mutate else ""),
        trace=trace,
        dims={"num_clients": n_clients, "d": d},
        rules=(FootprintRule((strict,) + DEFAULT_PATTERNS[1:]),
               TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# sketch ops
# --------------------------------------------------------------------------

def sketch_target() -> AuditTarget:
    from commefficient_tpu.ops.countsketch import CountSketch

    d, c, r, k = 1000, 128, 3, 10
    cs = CountSketch(d=d, c=c, r=r, seed=7)
    rng = np.random.RandomState(9)
    vec = jnp.asarray(rng.randn(d).astype(np.float32))

    def roundtrip(v):
        table = cs.sketch_vec(v)
        return cs.unsketch(table, k)

    def trace():
        return jax.make_jaxpr(roundtrip)(vec)

    def retrace():
        jitted = jax.jit(roundtrip)

        def make_args(i):
            return (jnp.asarray(rng.randn(d).astype(np.float32)),)

        return check_retrace(jitted, make_args, repeats=3, warmup=1)

    return AuditTarget(
        name="sketch/roundtrip",
        description="CountSketch sketch_vec + unsketch top-k",
        trace=trace,
        dims={},
        # no symbolic patterns bind here; the contract is the byte
        # budget: nothing in the sketch pipeline may materialize more
        # than a handful of d-length temporaries (the one-hot scatter
        # path would blow this budget at (d, c) scale)
        rules=(FootprintRule(DEFAULT_PATTERNS,
                             max_eqn_bytes=64 * d * 4),
               TransferRule()),
        retrace=retrace)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def build_targets(name: str) -> list:
    """Targets for a CLI/gate name: round|gpt2|attention|sketch|all."""
    if name == "round":
        return [round_target("sketch"), round_target("local_topk"),
                round_target("uncompressed")]
    if name == "gpt2":
        return [gpt2_target()]
    if name == "attention":
        return [attention_target(bwd=False), attention_target(bwd=True)]
    if name == "sketch":
        return [sketch_target()]
    if name == "buffered":
        return [buffered_target()]
    if name == "buffered_mesh":
        return [buffered_mesh_target()]
    if name == "round_bucketed":
        return [round_bucketed_target("local_topk"),
                round_bucketed_target("sketch")]
    if name == "sketch_batched":
        return [sketch_batched_target()]
    if name == "server_update_fused":
        return [server_update_fused_target("true_topk"),
                server_update_fused_target("sketch")]
    if name == "decode":
        return [decode_target("step"), decode_target("generate")]
    if name == "decode_paged":
        return [decode_paged_target()]
    if name == "decode_speculative":
        return [decode_speculative_target()]
    if name == "decode_paged_quant":
        return [decode_paged_quant_target()]
    if name == "serve_multihost":
        return [serve_multihost_target()]
    if name == "client_store":
        return [client_store_target()]
    if name == "online_loop":
        return [online_loop_target()]
    if name == "all":
        return (build_targets("round") + build_targets("round_bucketed")
                + build_targets("sketch_batched")
                + build_targets("server_update_fused")
                + build_targets("buffered")
                + build_targets("buffered_mesh")
                + build_targets("client_store")
                + build_targets("gpt2") + build_targets("attention")
                + build_targets("sketch") + build_targets("decode")
                + build_targets("decode_paged")
                + build_targets("decode_speculative")
                + build_targets("decode_paged_quant")
                + build_targets("serve_multihost")
                + build_targets("online_loop"))
    raise ValueError(f"unknown audit target {name!r} (round|round_bucketed|"
                     f"sketch_batched|server_update_fused|buffered|"
                     f"buffered_mesh|client_store|"
                     f"gpt2|attention|sketch|decode|decode_paged|"
                     f"decode_speculative|decode_paged_quant|"
                     f"serve_multihost|online_loop|all)")
