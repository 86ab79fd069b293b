"""Benchmarks for the two north-star metrics (BASELINE.md):

1. CIFAR10 ResNet-9 federated rounds/sec — full sketched FetchSGD pipeline
   (8 clients x 32 images, default 5x500k sketch, k=50k: reference
   utils.py:142-145), on the attached TPU chip.
2. GPT2 PersonaChat tokens/sec/chip — gpt2-small double-heads federated
   round on PersonaChat shapes (4 clients x 4 dialogs x 2 candidates x 256
   tokens), bfloat16 compute, uncompressed mode (model-bound).

Prints ONE JSON line: the primary metric fields plus ``extra_metrics`` and
a per-component ``breakdown_ms`` of the sketch round (where the time goes:
sketching the aggregate, unsketching, per-client grads) and of the
host-offload pipeline (gather/scatter overlap). Each metric runs ISOLATED:
one that raises reports None and an ``errors`` entry instead of zeroing
the whole artifact, and the process then exits non-zero.

``--profile DIR`` wraps the timed rounds in ``jax.profiler.trace`` for
TensorBoard inspection. The reference publishes no numbers (BASELINE.md),
so vs_baseline is 1.0 by convention.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# the decode_tp row builds a tp=2 mesh; a fresh CPU process exposes ONE
# device unless this flag lands before jax's first import (all jax
# imports in this module are function-local, so module import is early
# enough)
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

#: --dry-run: every bench row builds its real setup (model, learner,
#: device batch) and TRACES its jitted programs via jax.eval_shape, then
#: returns without compiling or timing. Signature drift, shape bugs and
#: config rot — the class of failure that silently zeroed the round-5
#: bench artifact — surface at trace time, so tier-1 catches them
#: (tests/test_bench_dry_run.py) instead of the next capture session.
DRY_RUN = False


def _dry_trace_round(learner, ids_fn, batch, mask, scan_rounds=None):
    """Trace the learner's jitted round — and, when ``scan_rounds`` is
    given, the K-round scan dispatch — without compiling. Exercises the
    exact argument plumbing the timed path uses (offload rows included),
    so a drifted signature or dtype fails here like it would on-chip."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids_fn(0), jnp.int32)
    cols = tuple(jnp.asarray(t) for t in batch)
    m = jnp.asarray(mask, jnp.float32)
    lr = jnp.float32(learner.lr_at(0.0))
    rng = jax.random.PRNGKey(0)
    if learner._offload:
        rows = learner._offload_pipe.gather(
            np.asarray(ids_fn(0)).astype(np.int64))
        out = jax.eval_shape(learner._round, learner.state, rows, ids,
                             cols, m, lr, rng)
    else:
        out = jax.eval_shape(learner._round, learner.state, ids, cols, m,
                             lr, rng)
    if scan_rounds:
        K = scan_rounds
        ids_k = jnp.broadcast_to(ids, (K,) + ids.shape)
        cols_k = tuple(jnp.broadcast_to(c, (K,) + c.shape) for c in cols)
        mask_k = jnp.broadcast_to(m, (K,) + m.shape)
        jax.eval_shape(learner._rounds_scan_fn(), learner.state, ids_k,
                       cols_k, mask_k, jnp.zeros((K,), jnp.float32),
                       jnp.stack([rng] * K))
    return {"dry_run": "ok", "out_leaves": len(jax.tree.leaves(out))}


def _sync(x):
    """Force completion of everything ``x`` depends on."""
    import jax
    jax.block_until_ready(x)


def _time(fn, *args, n=10):
    _sync(fn(*args))  # compile + warm
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_cifar_sketch(approx_recall=0.95):
    """Sketched CIFAR federated round (ResNet9 d=6.57M, 5x500k, k=50k).

    ``approx_recall=0.95`` selects with approx_max_k (ops/topk.py) — the
    headline config since round 4, mirroring the GPT2 sketch bench: the
    coordinates the approximate selector misses stay in the server's
    virtual-error accumulator and are recovered in later rounds (the
    same error-feedback mechanism that absorbs sketch noise; convergence
    under approx selection is asserted in
    tests/test_round.py::test_sketch_with_approx_topk_learns). The bench
    JSON reports BOTH this and the exact-sort variant so numbers stay
    comparable to the reference's exact selector and to rounds 1-3."""
    import jax

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import ResNet9

    W, B = 8, 32
    # bf16 convs/matmuls at full MXU rate; params and logits stay f32
    # (models/resnet9.py) — the same flag the CV entrypoint exposes as
    # --compute_dtype, convergence-tested in tests/test_models.py
    model = ResNet9(num_classes=10, dtype="bfloat16")
    cfg = FedConfig(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                    local_momentum=0, k=50_000, num_rows=5, num_cols=500_000,
                    num_workers=W, num_clients=100, lr_scale=0.4,
                    weight_decay=5e-4, topk_approx_recall=approx_recall)
    rng = np.random.RandomState(0)
    images = rng.randn(W, B, 32, 32, 3).astype(np.float32)
    targets = rng.randint(0, 10, (W, B)).astype(np.int32)
    mask = np.ones((W, B), np.float32)

    learner = FedLearner(model, cfg, make_cv_loss(model), None,
                         jax.random.PRNGKey(0), images[0][:1])

    import jax.numpy as jnp
    imgs_d = jax.device_put(jnp.asarray(images))
    tgts_d = jax.device_put(jnp.asarray(targets))
    mask_d = jax.device_put(jnp.asarray(mask, jnp.float32))

    def ids_fn(r):
        return (np.arange(W) + r * W) % cfg.num_clients

    def one_round(r):
        return learner.train_round_async(ids_fn(r), (imgs_d, tgts_d), mask_d)

    if DRY_RUN:
        # trace the sketch component ops too — the breakdown section
        # dispatches them standalone with use_kernel=True
        from commefficient_tpu.federated.server import make_sketch
        cs = make_sketch(learner.cfg)
        vec = jax.ShapeDtypeStruct((learner.cfg.grad_size,), jnp.float32)
        table = jax.eval_shape(lambda v: cs.sketch_vec(v, True), vec)
        jax.eval_shape(lambda t: cs.unsketch(t, cfg.k, approx_recall or None,
                                             True), table)
        return _dry_trace_round(learner, ids_fn, (imgs_d, tgts_d), mask_d,
                                scan_rounds=12), {}

    # Headline metric = steady-state THROUGHPUT: 12-round windows, one
    # metric sync per window, each window dispatched as ONE traced
    # lax.scan (train_rounds_scan). A round costs the device round plus
    # (a) per-round host dispatch and (b) a device->host metric sync. A
    # real training loop pays (b) once per logging point (or hides it
    # with RoundPipeline), so the window convention amortizes it; the
    # per-round-dispatch variant is reported alongside. Median of 3
    # windows.
    per_dispatch_time = _timed_windows(learner, one_round)
    round_time = _timed_scan_windows(learner, ids_fn, (imgs_d, tgts_d),
                                     mask_d)

    # blocking per-round latency (sync every round), median of 6
    lat = []
    for r in range(6):
        t0 = time.perf_counter()
        learner.finalize_round_metrics(one_round(100 + r))
        lat.append(time.perf_counter() - t0)
    latency = float(np.median(lat))

    # component breakdown of where the round's time goes. Blocking sub-op
    # timings include the per-dispatch host round-trip; subtract a
    # measured null dispatch so components compare against the pipelined
    # round time.
    from commefficient_tpu.federated.server import make_sketch
    d = learner.cfg.grad_size  # finalized config carries the derived size
    cs = make_sketch(learner.cfg)
    vec = jax.numpy.asarray(rng.randn(d).astype(np.float32))
    table = cs.sketch_vec(vec)
    t_null = _time(jax.jit(lambda x: x + 1.0), jax.numpy.zeros(8))
    # use_kernel=True: measure the same Pallas paths the round dispatches
    t_sketch = max(_time(cs.sketch_vec, vec, True) - t_null, 0.0)
    t_unsketch = max(_time(cs.unsketch, table, cfg.k,
                           approx_recall or None, True) - t_null, 0.0)
    breakdown = {
        "topk_approx_recall": approx_recall,
        "round_throughput_ms": round(round_time * 1e3, 1),
        "round_throughput_per_dispatch_ms": round(
            per_dispatch_time * 1e3, 1),
        "round_blocking_latency_ms": round(latency * 1e3, 1),
        "sketch_aggregate_ms": round(t_sketch * 1e3, 1),
        "unsketch_topk_ms": round(t_unsketch * 1e3, 1),
        "grads_and_rest_ms": round(
            max(round_time - t_sketch - t_unsketch, 0.0) * 1e3, 1),
    }
    return 1.0 / round_time, breakdown


def _gpt2_fed_setup(B=8, attn_impl="full", dropout_impl="xla_rbg",
                    fused_lm_head=False, T=256, attn_dropout="auto",
                    attn_block_size=None, **cfg_kw):
    """Shared gpt2-small federated-bench setup: model, learner, and a
    device-resident synthetic PersonaChat batch (W=4, B dialogs, C=2,
    T tokens — 16k tokens/round at the default B=8/T=256, a realistic
    device batch; round 2 ran 8k). ``attn_impl='blockwise'`` swaps in
    the flash kernel; ``attn_dropout='kernel'`` additionally REQUIRES
    reference-parity dropout on the attention probabilities inside that
    kernel (ops/flash_attention.py — keep-bits in-register, no (T,T)
    masks in HBM) and raises if the kernel is ineligible, so an A/B row
    can never silently fall back to output dropout."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    W, C = 4, 2
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, T)
    gcfg.dropout = 0.1
    gcfg.dtype = "bfloat16"  # MXU-native compute; params stay f32
    gcfg.attn_impl = attn_impl
    # default block pick: 256 tiles. The T=512 federated row keeps 256
    # explicitly — flash_attn_t512_parity_dropout_kernel_ab sweeps the
    # candidates (up to 512x512 single-tile) and the pick below should
    # track whatever that row crowns on-chip.
    gcfg.attn_block_size = attn_block_size or min(256, T)
    gcfg.attn_dropout = attn_dropout
    if DRY_RUN and attn_dropout == "kernel" \
            and jax.default_backend() != "tpu":
        # --dry-run validates shapes/signatures on whatever host runs it;
        # the in-kernel dropout path is TPU-only and 'kernel' rightly
        # raises off-TPU. 'auto' traces the same blockwise program with
        # output dropout; timed runs (and TPU dry-runs) stay strict.
        gcfg.attn_dropout = "auto"
    # 'xla_rbg' dropout: reference-parity Bernoulli masks (attn_pdrop on
    # the probabilities) with bits drawn by the TPU hardware RngBitGenerator
    # instead of threefry — ~2x cheaper generation, same fusion behavior
    # (ops/dropout.py; the Pallas per-tensor kernel measured SLOWER
    # in-round from launch/fusion breaks, docs/ROOFLINE.md r4).
    gcfg.dropout_impl = dropout_impl
    # fused LM head+CE (ops/fused_ce.py) is OFF here: measured ~12 ms
    # slower than XLA's materialized-logits CE at this shape (it is a
    # memory lever for long T, not a speed lever — docs/ROOFLINE.md)
    gcfg.fused_lm_head = fused_lm_head
    model = GPT2DoubleHeads(gcfg)
    cfg = FedConfig(virtual_momentum=0.9, local_momentum=0, weight_decay=0,
                    num_workers=W, num_clients=16, lr_scale=4e-2, **cfg_kw)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 50000, (W, B, C, T)).astype(np.int32)
    types = rng.randint(0, 3, (W, B, C, T)).astype(np.int32)
    mc = np.full((W, B, C), T - 1, np.int32)
    labels = np.where(rng.rand(W, B, C, T) < 0.3, ids, -1).astype(np.int32)
    mcl = np.ones((W, B), np.int32)
    batch = tuple(jax.device_put(jnp.asarray(t))
                  for t in (ids, mc, labels, mcl, types))
    mask = jax.device_put(jnp.ones((W, B), jnp.float32))

    class _Wrap:
        def init(self, rng_, sample_in, train):
            return model.init(rng_, *sample_in, train=train)

        def apply(self, *a, **k):
            return model.apply(*a, **k)

    learner = FedLearner(
        _Wrap(), cfg, make_gpt2_train_loss(model), None,
        jax.random.PRNGKey(0), (batch[0][0][:1], batch[4][0][:1],
                                batch[1][0][:1]))

    def ids_fn(r):
        return (np.arange(W) + r * W) % cfg.num_clients

    def one_round(r):
        return learner.train_round_async(ids_fn(r), batch, mask)

    return learner, one_round, W * B * C * T, (batch, mask, ids_fn)


def _timed_windows(learner, one_round, n_windows=3, n_rounds=12):
    """Compile + warm, then median steady-state seconds/round over
    ``n_windows`` back-to-back async windows (one sync per window)."""
    learner.finalize_round_metrics(one_round(0))  # compile
    learner.finalize_round_metrics(one_round(1))  # warm
    window_times = []
    for w in range(n_windows):
        t0 = time.perf_counter()
        raw = None
        for r in range(n_rounds):
            raw = one_round(2 + w * n_rounds + r)
        learner.finalize_round_metrics(raw)
        window_times.append((time.perf_counter() - t0) / n_rounds)
    return float(np.median(window_times))


def _timed_scan_windows(learner, ids_fn, batch, mask, n_windows=3,
                        n_rounds=12):
    """Median seconds/round with each window dispatched as ONE
    train_rounds_scan(K=n_rounds) — K rounds per host dispatch, so the
    per-dispatch host cost drops out and the window runs at device
    speed. The scan is
    trajectory-identical to per-round dispatch
    (tests/test_round.py::test_rounds_scan_matches_sequential)."""
    import jax.numpy as jnp

    def stacked(r0):
        ids_k = np.stack([ids_fn(r0 + k) for k in range(n_rounds)])
        cols_k = tuple(jnp.broadcast_to(c, (n_rounds,) + c.shape)
                       for c in batch)
        mask_k = jnp.broadcast_to(mask, (n_rounds,) + mask.shape)
        return ids_k, cols_k, mask_k

    ids_k, cols_k, mask_k = stacked(0)
    learner.finalize_scan_metrics(
        learner.train_rounds_scan(ids_k, cols_k, mask_k))  # compile
    learner.finalize_scan_metrics(
        learner.train_rounds_scan(*stacked(n_rounds)))     # warm
    window_times = []
    for w in range(n_windows):
        args = stacked((2 + w) * n_rounds)
        t0 = time.perf_counter()
        learner.finalize_scan_metrics(learner.train_rounds_scan(*args))
        window_times.append((time.perf_counter() - t0) / n_rounds)
    return float(np.median(window_times))


def bench_gpt2_tokens(attn_impl="full", B=8, T=256, attn_dropout="auto",
                      per_dispatch=True):
    """Returns (scan-mode tokens/s, per-round-dispatch tokens/s). The
    scan number is the headline: per-round host dispatch adds a cost
    that no amount of on-chip work removes — train_rounds_scan is the
    framework's answer, and the per-dispatch figure is reported
    alongside.
    ``per_dispatch=False`` skips the second compile + timed windows (the
    long-context row only needs the headline convention)."""
    learner, one_round, tokens_per_round, (batch, mask, ids_fn) = \
        _gpt2_fed_setup(attn_impl=attn_impl, B=B, T=T,
                        attn_dropout=attn_dropout, mode="uncompressed",
                        error_type="none")
    if DRY_RUN:
        return _dry_trace_round(learner, ids_fn, batch, mask,
                                scan_rounds=12), None
    pd = (tokens_per_round / _timed_windows(learner, one_round)
          if per_dispatch else None)
    scanned = tokens_per_round / _timed_scan_windows(
        learner, ids_fn, batch, mask)
    return scanned, pd


def bench_flash_dropout_kernel_ab(T=256, rate=0.1, blocks=None):
    """Kernel-level A/B at the federated bench's attention shape: fused
    flash attention WITH in-kernel parity dropout (block-size sweep — the
    kernel's DEFAULT_BLOCK_Q=2048 was tuned at T=4096 and clamps to one
    (T, T) tile here, so the sweep covers the short-T candidates) vs the
    incumbent XLA path (materialized scores + additive causal bias + f32
    softmax + rbg prob dropout — exactly models/gpt2.py's 'full' branch).
    Both time fwd+bwd through jax.grad with the window convention (10
    dispatches per sync). This adjudicates the tentpole at the op level
    even if the round-level number moves for unrelated reasons, and is
    the measured basis for docs/ROOFLINE.md's dropout-kernel section.

    ``blocks`` overrides the (block_q, block_k) sweep; the T=512 row
    passes candidates up to the single-tile 512x512 so the federated
    T=512 flash row's ``attn_block_size`` pick (_gpt2_fed_setup) is
    re-tuned from measurements rather than inherited from the T=256
    sweep.

    Returns (xla_ms / best_flash_ms speedup, per-config ms dict)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.ops.flash_attention import flash_attention
    from commefficient_tpu.ops.dropout import masked_dropout

    R, H, D = 64, 12, 64        # W*B*C = 64 rows: the bench round's shape
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(R, T, H, D).astype(np.float32)
                             ).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    key = jax.random.PRNGKey(0)
    # the incumbent draws its mask bits through the rbg key exactly as
    # FusedDropout(impl='xla_rbg') builds it (ops/dropout.py)
    data = jnp.ravel(jax.random.key_data(key)).astype(jnp.uint32)
    k4 = jnp.concatenate([data, data ^ jnp.uint32(0x9e3779b9)])[:4]
    rbg_key = jax.random.wrap_key_data(k4, impl="rbg")

    def timed_fwd_bwd(attn_fn, n_windows=3, n_steps=10):
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                attn_fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        if DRY_RUN:
            jax.eval_shape(g, q, k, v)
            return float("nan")
        _sync(g(q, k, v)[0])  # compile
        _sync(g(q, k, v)[0])  # warm
        times = []
        for _ in range(n_windows):
            t0 = time.perf_counter()
            out = None
            for _ in range(n_steps):
                out = g(q, k, v)
            _sync(out[0])
            times.append((time.perf_counter() - t0) / n_steps)
        return float(np.median(times))

    def xla_full(q, k, v):
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        causal = jnp.tril(jnp.ones((T, T), bool))
        att = att + jnp.where(causal, 0.0,
                              jnp.finfo(att.dtype).min)[None, None]
        att = jax.nn.softmax(att, axis=-1)
        att = masked_dropout(att, rbg_key, rate)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    results = {}
    for bq, bk in blocks or ((256, 256), (256, 128), (128, 256),
                             (128, 128)):
        t = timed_fwd_bwd(
            lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, block_q=bq, block_k=bk, dropout_rate=rate,
                dropout_key=key))
        results[f"flash_dropout_bq{bq}_bk{bk}_ms"] = round(t * 1e3, 3)
    results["flash_nodropout_bq256_bk256_ms"] = round(
        timed_fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, block_q=256, block_k=256)) * 1e3, 3)
    results["xla_full_prob_dropout_ms"] = round(
        timed_fwd_bwd(xla_full) * 1e3, 3)
    if DRY_RUN:   # every config traced (values are NaN placeholders)
        return {"dry_run": "ok", "configs": len(results)}, results
    best = min(val for name, val in results.items()
               if name.startswith("flash_dropout"))
    results["best_flash_dropout_ms"] = best
    return round(results["xla_full_prob_dropout_ms"] / best, 4), results


def bench_gpt2_sketch_rounds(approx_recall=0.95, per_dispatch=True):
    """FetchSGD on gpt2-small itself (d~124M) — the paper's NLP headline:
    5x500k sketch compresses the 474MB gradient to 9.5MB per client per
    round. One full federated sketch round on PersonaChat shapes.

    ``approx_recall=0.95`` uses the TPU-native approx_max_k selector (5.4x
    faster than the exact sort at this d/k; missed coordinates ride the
    error-feedback accumulator — config.py/ops/topk.py docstrings); the
    bench JSON reports BOTH this and the exact-top-k variant so numbers
    stay comparable to the reference's exact selector and to pre-approx
    history (round-2 advisor note)."""
    learner, one_round, _, (batch, mask, ids_fn) = _gpt2_fed_setup(
        B=4, mode="sketch", error_type="virtual", k=50_000, num_rows=5,
        num_cols=500_000, topk_approx_recall=approx_recall)
    if DRY_RUN:
        return _dry_trace_round(learner, ids_fn, batch, mask,
                                scan_rounds=6), None
    # BOTH measurement conventions (ADVICE r4): rounds 1-3 reported
    # per-round dispatch; round 4 switched the headline to scan windows —
    # emitting the per-dispatch companion keeps history comparable.
    scanned = 1.0 / _timed_scan_windows(learner, ids_fn, batch, mask,
                                        n_rounds=6)
    if not per_dispatch:   # skip the extra compile + 3x6 timed rounds
        return scanned, None
    return scanned, 1.0 / _timed_windows(learner, one_round, n_rounds=6)


def bench_gpt2_bucketed_rounds(T=256, Ks=(1, 4, 16)):
    """Bucketed transmit A/B (``--grad_buckets``, docs/ROOFLINE.md
    Round 7): the gpt2-small FetchSGD sketch round with the transmit
    split into K layer-grouped, 128-lane-aligned buckets — each bucket's
    sketch (and, on a mesh, its psum) is an independent op XLA's
    latency-hiding scheduler can overlap with the rest of the backward —
    priced against the K=1 monolithic incumbent.

    ONE model/learner setup per row; only the round program is rebuilt
    per K from the learner's stashed loss/unflatten/mask (the exact
    production constructor path: ``dataclasses.replace(cfg,
    grad_buckets=K)`` + ``make_grad_buckets`` + ``build_round_step``),
    so the A/B isolates the transmit restructuring. Every K is timed
    with the same window convention; K=1 is trajectory-identical to the
    pre-bucketing round (tests/test_grad_buckets.py), so its number IS
    the incumbent's. A K whose realized plan collapses (num_buckets <
    requested) is still reported, labeled with the realized count.

    Returns (K=1 ms / best-K ms speedup — may be < 1, the refutation
    outcome ROOFLINE.md Round 7 budgets for — and the per-K ms dict)."""
    import dataclasses

    from commefficient_tpu.federated.round import build_round_step
    from commefficient_tpu.federated.state import make_grad_buckets
    from commefficient_tpu.ops.countsketch import LANES

    learner, one_round, _, (batch, mask, ids_fn) = _gpt2_fed_setup(
        B=4, T=T, attn_impl="blockwise", attn_dropout="kernel",
        mode="sketch", error_type="virtual", k=50_000, num_rows=5,
        num_cols=500_000, topk_approx_recall=0.95)

    results = {}
    try:
        for K in Ks:
            cfg_k = dataclasses.replace(learner.cfg, grad_buckets=K)
            plan = make_grad_buckets(learner._param_leaf_sizes,
                                     cfg_k.grad_dim, K, align=LANES)
            learner._round = build_round_step(
                learner._loss_train, learner._round_unflatten, cfg_k,
                mesh=learner.mesh,
                trainable_mask=learner._trainable_mask, buckets=plan)
            realized = plan.num_buckets if plan is not None else 1
            name = f"bucketed_K{K}_ms"
            if realized != K:
                name = f"bucketed_K{K}_realized{realized}_ms"
            if DRY_RUN:
                _dry_trace_round(learner, ids_fn, batch, mask)
                results[name] = float("nan")
                continue
            results[name] = round(
                _timed_windows(learner, one_round, n_rounds=6) * 1e3, 1)
    finally:
        # the learner dies with this row, but keep the invariant anyway:
        # _round always matches learner.cfg/grad_buckets on exit
        learner._round = build_round_step(
            learner._loss_train, learner._round_unflatten, learner.cfg,
            mesh=learner.mesh, trainable_mask=learner._trainable_mask,
            buckets=learner.grad_buckets)
    if DRY_RUN:
        return {"dry_run": "ok", "configs": len(results)}, results
    base = results["bucketed_K1_ms"]
    best = min(v for k, v in results.items() if not k.startswith(
        "bucketed_K1"))
    return round(base / best, 4), results


def bench_gpt2_fused_ce_ab(T=512):
    """--fused_ce A/B at T=512 (ROADMAP 4c): the double-heads LM loss
    with the head matmul + cross-entropy fused (ops/fused_ce.py — logits
    never materialized, O(B*T*block) memory) vs the incumbent
    materialized-(B,C,T,V)-logits CE, both inside the full federated
    round at the long-context shape where the (B,C,T,V) f32 logits cost
    real HBM (B=4, C=2, T=512, V=50262: ~825 MB). At T=256 the fused
    path measured ~12 ms SLOWER (it is a memory lever, not a speed
    lever — _gpt2_fed_setup note); this row prices the T=512 crossover
    so ``--fused_ce auto`` has a measured basis.

    Returns (fused tokens/s / materialized tokens/s — > 1 means fused
    wins at this shape — and the per-variant tokens/s dict)."""
    results = {}
    for label, fused in (("materialized_logits", False), ("fused_ce", True)):
        learner, one_round, tokens_per_round, (batch, mask, ids_fn) = \
            _gpt2_fed_setup(B=4, T=T, attn_impl="blockwise",
                            attn_dropout="kernel", fused_lm_head=fused,
                            mode="uncompressed", error_type="none")
        if DRY_RUN:
            _dry_trace_round(learner, ids_fn, batch, mask)
            results[f"{label}_tokens_per_sec"] = float("nan")
            continue
        results[f"{label}_tokens_per_sec"] = round(
            tokens_per_round / _timed_scan_windows(learner, ids_fn, batch,
                                                   mask), 1)
    if DRY_RUN:
        return {"dry_run": "ok", "configs": len(results)}, results
    ratio = (results["fused_ce_tokens_per_sec"]
             / results["materialized_logits_tokens_per_sec"])
    return round(ratio, 4), results


def bench_longcontext_tokens():
    """Long-context LM step: gpt2-small fwd+bwd at T=4096 with blockwise
    (flash-style) attention, bf16. Full attention would materialize
    12 x 4096^2 score matrices per layer; blockwise keeps O(T*block)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    # B=4 measured +37% tokens/s over B=1 (48.8k vs 35.6k same-session)
    # and still fits HBM with remat + the flash kernel; B=8 saturates
    B, T = 4, 4096
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = T
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    gcfg.attn_impl = "blockwise"
    gcfg.attn_block_size = 512
    # per-block rematerialization: fits T=4096 in HBM (33G -> <16G)
    gcfg.remat = True
    model = GPT2DoubleHeads(gcfg)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 50000, (B, 1, T)).astype(np.int32))
    types = jnp.asarray(rng.randint(0, 3, (B, 1, T)).astype(np.int32))
    mc = jnp.full((B, 1), T - 1, jnp.int32)
    labels = jnp.asarray(rng.randint(0, 50000, (B, 1, T)).astype(np.int32))
    if DRY_RUN:
        # even the init is traced, not run — gpt2-small at T=4096 has no
        # business executing a forward pass during a smoke check
        params = jax.eval_shape(
            lambda r: model.init(r, ids, types, mc, train=False),
            jax.random.PRNGKey(0))["params"]
    else:
        params = model.init(jax.random.PRNGKey(0), ids, types, mc,
                            train=False)["params"]

    # labels shifted instead of slicing logits[:-1]: the sliced logits'
    # backward would materialize a (B, T, V) 3.3 GB pad (losses.py note)
    tgt = jnp.concatenate([labels[:, 0, 1:], labels[:, 0, :1]], axis=-1)

    @jax.jit
    def step(p):
        def loss_fn(p):
            lm, _ = model.apply({"params": p}, ids, types, mc, train=False)
            lp = jax.nn.log_softmax(lm[:, 0].astype(jnp.float32))
            picked = jnp.take_along_axis(lp, tgt[..., None], axis=-1)
            return -jnp.mean(picked[:, :-1])
        return jax.grad(loss_fn)(p)

    if DRY_RUN:
        out = jax.eval_shape(step, params)
        return {"dry_run": "ok",
                "grad_leaves": len(jax.tree.leaves(out))}

    # steady-state throughput, same convention as the federated metrics:
    # dispatch a window of steps back-to-back, sync once, so the
    # per-dispatch host round-trip does not swamp the step
    _sync(step(params)["wte"]["embedding"])  # compile
    _sync(step(params)["wte"]["embedding"])  # warm
    n_windows, n_steps = 3, 5
    times = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        out = None
        for _ in range(n_steps):
            out = step(params)
        _sync(out["wte"]["embedding"])
        times.append((time.perf_counter() - t0) / n_steps)
    return B * T / float(np.median(times))


def bench_offload_overlap(n_rounds=8):
    """Host-offloaded client rows: the SYNC round pays gather + compute +
    scatter serially on the critical path, while the async pipeline
    (api.HostOffloadPipeline) gathers round t+1's rows and lazily writes
    back round t-1's outputs while round t computes. ResNet9 local_topk
    with local momentum + local error — the same two-field client state
    the offloaded persona_small runs carry. Returns breakdown timings
    including how much of the gather+scatter host time the pipeline hid
    (round-5 VERDICT: offload rounds ran ~4.5 s with neither stacked
    transfers nor prefetch; this measures the recovery)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import ResNet9

    W, B, N = 4, 16, 12
    model = ResNet9(num_classes=10, dtype="bfloat16")
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(W, B, 32, 32, 3).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, 10, (W, B)).astype(np.int32))
    mask = jax.device_put(jnp.ones((W, B), jnp.float32))
    batch = (jax.device_put(images), jax.device_put(targets))

    def make_learner():
        cfg = FedConfig(mode="local_topk", k=50_000, error_type="local",
                        local_momentum=0.9, virtual_momentum=0,
                        num_workers=W, num_clients=N, lr_scale=0.1,
                        client_state_offload=True)
        return FedLearner(model, cfg, make_cv_loss(model), None,
                          jax.random.PRNGKey(0), np.asarray(images[0][:1]))

    def ids_fn(r):
        return (np.arange(W) + r * W) % N

    if DRY_RUN:
        return _dry_trace_round(make_learner(), ids_fn, batch, mask)

    # sync convention: train_round flushes the pipeline every round, so
    # gather/compute/scatter serialize — the pre-pipeline critical path
    ln = make_learner()
    ln.train_round(ids_fn(0), batch, mask)  # compile
    ln.train_round(ids_fn(1), batch, mask)  # warm
    t0 = time.perf_counter()
    for r in range(n_rounds):
        ln.train_round(ids_fn(2 + r), batch, mask)
    sync_t = (time.perf_counter() - t0) / n_rounds

    # async convention: gather-ahead + lazy writeback, one metric sync and
    # one flush per window (the training-loop steady state)
    ln = make_learner()
    ln.train_round(ids_fn(0), batch, mask)  # compile
    ln.train_round(ids_fn(1), batch, mask)  # warm
    from commefficient_tpu.utils.tracing import span_seconds
    gather0 = span_seconds("offload.gather")
    scatter0 = span_seconds("offload.scatter")
    t0 = time.perf_counter()
    raw = None
    for r in range(n_rounds):
        nxt = ids_fn(3 + r) if r + 1 < n_rounds else None
        raw = ln.train_round_async(ids_fn(2 + r), batch, mask,
                                   next_client_ids=nxt)
    ln.finalize_round_metrics(raw)
    ln.flush_offload()
    async_t = (time.perf_counter() - t0) / n_rounds

    return {
        "offload_round_sync_ms": round(sync_t * 1e3, 1),
        "offload_round_async_ms": round(async_t * 1e3, 1),
        # host time spent inside gather/scatter during the async window
        "offload_gather_ms": round(
            (span_seconds("offload.gather") - gather0) / n_rounds * 1e3, 1),
        "offload_scatter_ms": round(
            (span_seconds("offload.scatter") - scatter0) / n_rounds * 1e3,
            1),
        # fixed cost the pipeline actually took off the critical path
        "offload_gather_scatter_overlap_ms": round(
            max(sync_t - async_t, 0.0) * 1e3, 1),
    }


def bench_client_store_gather_scatter(scales=(10_000, 1_000_000),
                                      n_rounds=8):
    """Million-client host arenas (federated/client_store.HostArenaStore):
    per-client state lives host-side as O(k) sparse rows, so the arena is
    num_clients * k floats/ints — not num_clients * d — and the device
    only ever sees the W sampled rows' dense decodes per round. This row
    runs the same TinyMLP local_topk round at num_clients = 1e4 and 1e6
    and reports per-round gather/scatter host time plus the arena's
    actual bytes at each scale: gather/scatter cost must track the cohort
    width W (flat across scales), while arena bytes track n * k — the
    docs/SCALING.md memory model, O(num_clients*k + W*d)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import TinyMLP
    from commefficient_tpu.utils.tracing import span_seconds

    W, B, F = 8, 16, 8
    model = TinyMLP(num_classes=10, hidden=32)  # d = 618
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.randn(W, B, F).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, 10, (W, B)).astype(np.int32))
    mask = jax.device_put(jnp.ones((W, B), jnp.float32))
    batch = (jax.device_put(feats), jax.device_put(targets))

    def make_learner(n):
        cfg = FedConfig(mode="local_topk", k=32, error_type="local",
                        local_momentum=0.9, virtual_momentum=0,
                        num_workers=W, num_clients=n, lr_scale=0.1,
                        client_state="sparse", client_state_offload=True)
        return FedLearner(model, cfg, make_cv_loss(model), None,
                          jax.random.PRNGKey(0), np.asarray(feats[0][:1]))

    def make_ids_fn(n):
        # scattered ids (not a contiguous window) so the gather walks the
        # arena the way production sampling does
        def ids_fn(r):
            return np.random.RandomState(r).choice(n, size=W,
                                                   replace=False)
        return ids_fn

    def tag(n):
        return f"{n // 1_000_000}m" if n >= 1_000_000 else f"{n // 1000}k"

    if DRY_RUN:
        # both scales must build + trace: the 1M arena is host numpy and
        # the traced round's row input stays (W, d) regardless of n
        status = None
        for n in scales:
            ln = make_learner(n)
            status = _dry_trace_round(ln, make_ids_fn(n), batch, mask)
            arena = ln.host_store.nbytes()
            # 8 bytes per (idx, val) entry per field; 3 fields is the
            # ceiling — anything near n*d*4 means a dense arena snuck in
            assert arena <= 24 * n * ln.cfg.k, \
                f"arena not O(n*k): {arena} bytes at n={n}"
        return status

    out = {}
    for n in scales:
        ln = make_learner(n)
        ids_fn = make_ids_fn(n)
        ln.train_round(ids_fn(0), batch, mask)  # compile
        ln.train_round(ids_fn(1), batch, mask)  # warm
        gather0 = span_seconds("offload.gather")
        scatter0 = span_seconds("offload.scatter")
        t0 = time.perf_counter()
        for r in range(n_rounds):
            ln.train_round(ids_fn(2 + r), batch, mask)
        t = tag(n)
        out[f"round_ms_{t}"] = round(
            (time.perf_counter() - t0) / n_rounds * 1e3, 2)
        out[f"gather_ms_{t}"] = round(
            (span_seconds("offload.gather") - gather0) / n_rounds * 1e3, 2)
        out[f"scatter_ms_{t}"] = round(
            (span_seconds("offload.scatter") - scatter0) / n_rounds * 1e3,
            2)
        out[f"arena_mb_{t}"] = round(ln.host_store.nbytes() / 2**20, 1)
    return out


def bench_buffered_rounds(n_rounds=8):
    """Buffered async server (federated/buffer.py) vs the sync round at
    the same config — ResNet9 local_topk, the offload row's scale.

    Two claims worth a number: (1) the fault-free lock-step path (fused
    cohort+apply, bit-identical to sync by tests/test_buffered.py) costs
    ~nothing over the sync round — same program shape, one dispatch;
    (2) with a fault model the event loop adds only host-side
    bookkeeping per cohort (heap + deposit dispatches), reported as the
    delta over the lock-step time alongside the simulated-clock stats
    the --straggler results grid is built on."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.federated.buffer import (BufferedFedLearner,
                                                    init_buffer)
    from commefficient_tpu.federated.faults import FaultModel
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import ResNet9

    W, B, N = 4, 16, 12
    model = ResNet9(num_classes=10, dtype="bfloat16")
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(W, B, 32, 32, 3).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, 10, (W, B)).astype(np.int32))
    mask = jax.device_put(jnp.ones((W, B), jnp.float32))
    batch = (jax.device_put(images), jax.device_put(targets))

    def make_learner(server_mode, fault_model=None):
        cfg = FedConfig(mode="local_topk", k=50_000, error_type="local",
                        local_momentum=0.9, virtual_momentum=0,
                        num_workers=W, num_clients=N, lr_scale=0.1,
                        server_mode=server_mode,
                        staleness_alpha=0.5 if fault_model else 0.0)
        cls = (BufferedFedLearner if server_mode == "buffered"
               else FedLearner)
        kw = {"fault_model": fault_model} if fault_model else {}
        return cls(model, cfg, make_cv_loss(model), None,
                   jax.random.PRNGKey(0), np.asarray(images[0][:1]), **kw)

    def ids_fn(r):
        return (np.arange(W) + r * W) % N

    if DRY_RUN:
        ln = make_learner("buffered")
        ids = jnp.asarray(ids_fn(0), jnp.int32)
        lr, key = jnp.float32(0.1), jax.random.PRNGKey(0)
        # the fused lock-step program (fault-free path)
        out = jax.eval_shape(ln._lockstep, ln.state, ids, batch, mask,
                             lr, key)
        # the split cohort -> deposit -> apply chain (event-loop path),
        # composed in one trace so every signature is exercised
        M = ln.cfg.effective_buffer_m

        def full(state, ids_, cols, m, lr_, rng_):
            contrib, _ = ln._cohort.raw(state, ids_, cols, m, lr_, rng_)
            buf = init_buffer(contrib, M, ln.cfg.num_clients)
            buf = ln._deposit.raw(buf, contrib,
                                  jnp.ones((W,), jnp.bool_))
            return ln._apply.raw(state.replace(buffer=buf), lr_, rng_)

        jax.eval_shape(full, ln.state, ids, batch, mask, lr, key)
        return {"dry_run": "ok",
                "out_leaves": len(jax.tree.leaves(out))}

    def timed_rounds(ln):
        ln.finalize_round_metrics(
            ln.train_round_async(ids_fn(0), batch, mask))  # compile
        ln.train_round_async(ids_fn(1), batch, mask)       # warm
        t0 = time.perf_counter()
        raw = None
        for r in range(n_rounds):
            raw = ln.train_round_async(ids_fn(2 + r), batch, mask)
        ln.finalize_round_metrics(raw)
        return (time.perf_counter() - t0) / n_rounds

    sync_t = timed_rounds(make_learner("sync"))
    lockstep_t = timed_rounds(make_learner("buffered"))

    fm = FaultModel(1, N, straggler_frac=0.25, straggler_mult=5.0,
                    dropout_prob=0.1, crash_prob=0.05)
    ln_f = make_learner("buffered", fault_model=fm)
    faulted_t = timed_rounds(ln_f)
    ln_f.flush_faults()

    return {
        "round_sync_ms": round(sync_t * 1e3, 1),
        "round_buffered_lockstep_ms": round(lockstep_t * 1e3, 1),
        # host event loop + split cohort/deposit/apply dispatches
        "cohort_buffered_faulted_ms": round(faulted_t * 1e3, 1),
        "event_loop_overhead_ms": round((faulted_t - lockstep_t) * 1e3,
                                        1),
        "faulted_sim_time": round(ln_f.sim_time, 2),
        "faulted_applies_per_cohort": round(
            ln_f.applies_done / max(ln_f.cohorts_done, 1), 3),
        **{f"faulted_{k}": v for k, v in ln_f.fault_stats.items()},
    }


def bench_buffered_mesh_rounds(n_rounds=8, dp=2):
    """Mesh-native buffered aggregation A/B (federated/buffer.py over
    the 'clients' mesh axis): the fault-free lock-step program and the
    split cohort -> sharded-deposit -> staleness-apply chain run dp-way
    data-parallel vs the same config single-chip. The deposit's slot
    rows are pinned sharded over 'clients' (buffered_mesh audit), so
    the buffer never materializes a replicated (M, d) slab — the
    capacity win; on one host the time ratio should be ~flat, which is
    the number this row pins. The faulted arm adds the host event loop
    (heap + per-arrival deposit dispatches) with heterogeneous
    per-client k, reported as the delta over the dp lock-step time.

    Dry-run traces the dp-sharded programs via eval_shape — the
    sharding_constraint annotations land in the jaxpr (the
    buffered_mesh audit's subject). Degrades to mesh=None when the
    process has a single device."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.buffer import (BufferedFedLearner,
                                                    init_buffer)
    from commefficient_tpu.federated.faults import FaultModel
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.models import ResNet9
    from commefficient_tpu.parallel.mesh import make_mesh

    W, B, N = 4, 16, 12
    model = ResNet9(num_classes=10, dtype="bfloat16")
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(W, B, 32, 32, 3).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, 10, (W, B)).astype(np.int32))
    mask = jax.device_put(jnp.ones((W, B), jnp.float32))
    batch = (jax.device_put(images), jax.device_put(targets))
    mesh = make_mesh(dp) if jax.device_count() >= dp else None

    def make_learner(mesh_, fault_model=None, k_dist=None):
        cfg = FedConfig(mode="local_topk", k=50_000, error_type="local",
                        local_momentum=0.9, virtual_momentum=0,
                        num_workers=W, num_clients=N, lr_scale=0.1,
                        server_mode="buffered",
                        staleness_alpha=0.5 if fault_model else 0.0,
                        client_k_dist=k_dist or "")
        kw = {"fault_model": fault_model} if fault_model else {}
        return BufferedFedLearner(model, cfg, make_cv_loss(model), None,
                                  jax.random.PRNGKey(0),
                                  np.asarray(images[0][:1]),
                                  mesh=mesh_, **kw)

    def ids_fn(r):
        return (np.arange(W) + r * W) % N

    if DRY_RUN:
        ln = make_learner(mesh)
        ids = jnp.asarray(ids_fn(0), jnp.int32)
        lr, key = jnp.float32(0.1), jax.random.PRNGKey(0)
        out = jax.eval_shape(ln._lockstep, ln.state, ids, batch, mask,
                             lr, key)
        M = ln.cfg.effective_buffer_m

        def full(state, ids_, cols, m, lr_, rng_):
            contrib, _ = ln._cohort.raw(state, ids_, cols, m, lr_, rng_)
            buf = init_buffer(contrib, M, ln.cfg.num_clients)
            buf = ln._deposit.raw(buf, contrib,
                                  jnp.ones((W,), jnp.bool_))
            return ln._apply.raw(state.replace(buffer=buf), lr_, rng_)

        jax.eval_shape(full, ln.state, ids, batch, mask, lr, key)
        return {"dry_run": "ok", "dp": 1 if mesh is None else dp,
                "out_leaves": len(jax.tree.leaves(out))}, {}

    if mesh is None:
        return None     # single-device process: nothing to A/B

    def timed_rounds(ln):
        ln.finalize_round_metrics(
            ln.train_round_async(ids_fn(0), batch, mask))  # compile
        ln.train_round_async(ids_fn(1), batch, mask)       # warm
        t0 = time.perf_counter()
        raw = None
        for r in range(n_rounds):
            raw = ln.train_round_async(ids_fn(2 + r), batch, mask)
        ln.finalize_round_metrics(raw)
        return (time.perf_counter() - t0) / n_rounds

    single_t = timed_rounds(make_learner(None))
    dp_t = timed_rounds(make_learner(mesh))

    fm = FaultModel(1, N, straggler_frac=0.25, straggler_mult=5.0,
                    dropout_prob=0.1, crash_prob=0.05)
    ln_f = make_learner(mesh, fault_model=fm, k_dist="uniform:0.5,1.0")
    faulted_t = timed_rounds(ln_f)
    ln_f.flush_faults()

    breakdown = {
        "round_lockstep_single_ms": round(single_t * 1e3, 1),
        f"round_lockstep_dp{dp}_ms": round(dp_t * 1e3, 1),
        f"cohort_faulted_hetk_dp{dp}_ms": round(faulted_t * 1e3, 1),
        "event_loop_overhead_ms": round((faulted_t - dp_t) * 1e3, 1),
        "faulted_sim_time": round(ln_f.sim_time, 2),
        **{f"faulted_{k}": v for k, v in ln_f.fault_stats.items()},
    }
    return round(dp_t / single_t, 4), breakdown


def bench_checkpoint_overhead(every_rounds=100):
    """Crash-consistent checkpoint round trip (utils/checkpoint.py v3):
    atomic save (temp file + fsync + rename + digest), digest verify,
    and transactional load of the gpt2-small federated learner — the
    state a preempted PersonaChat run writes every
    ``--checkpoint_every_rounds``. Reports the absolute costs plus the
    per-round amortization at the default cadence, the number that says
    whether periodic checkpointing is visible in the headline
    tokens/sec rows (docs/ROBUSTNESS.md 'Preemption')."""
    import os
    import shutil
    import tempfile

    from commefficient_tpu.utils.checkpoint import (load_checkpoint,
                                                    save_checkpoint,
                                                    verify_checkpoint)

    def roundtrip(learner, d, n=1):
        """Median save/verify/load seconds + file size for ``learner``."""
        def med(f):
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                f()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        cursor = {"entry": "bench", "epoch": 0, "rounds_in_epoch": 1,
                  "total_rounds": 1, "in_epoch": True}
        fp = {"seed": 0, "mode": "uncompressed"}
        box = {}

        def save():
            box["fn"] = save_checkpoint(d, learner, "bench", step=1,
                                        cursor=cursor, fingerprint=fp)
        save_t = med(save)
        verify_t = med(lambda: verify_checkpoint(box["fn"]))
        load_t = med(lambda: load_checkpoint(box["fn"], learner))
        return save_t, verify_t, load_t, os.path.getsize(box["fn"])

    if DRY_RUN:
        # the checkpoint path is host-side numpy + file I/O — nothing to
        # eval_shape — so the dry run exercises the REAL save/verify/load
        # round trip at toy scale: signature drift or a broken digest
        # fails here, not in the next capture session
        import jax

        from commefficient_tpu.config import FedConfig
        from commefficient_tpu.federated.api import FedLearner
        from commefficient_tpu.federated.losses import make_regression_loss
        from commefficient_tpu.models import ToyLinear
        X = np.asarray([[0.0], [1.0]], np.float32)
        cfg = FedConfig(mode="uncompressed", virtual_momentum=0.9,
                        local_momentum=0, error_type="none",
                        weight_decay=0, num_workers=1, num_clients=2,
                        lr_scale=0.02)
        model = ToyLinear()
        ln = FedLearner(model, cfg, make_regression_loss(model), None,
                        jax.random.PRNGKey(0), X[:1])
        d = tempfile.mkdtemp()
        try:
            save_t, verify_t, load_t, nbytes = roundtrip(ln, d)
            return {"dry_run": "ok", "bytes": nbytes}
        finally:
            shutil.rmtree(d, ignore_errors=True)

    learner, one_round, _, _ = _gpt2_fed_setup()
    learner.finalize_round_metrics(one_round(0))  # materialize state
    round_t = _timed_windows(learner, one_round, n_windows=1, n_rounds=4)
    d = tempfile.mkdtemp()
    try:
        save_t, verify_t, load_t, nbytes = roundtrip(learner, d, n=3)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {
        "save_ms": round(save_t * 1e3, 1),
        "verify_ms": round(verify_t * 1e3, 1),
        "load_ms": round(load_t * 1e3, 1),
        "bytes": nbytes,
        "round_ms": round(round_t * 1e3, 1),
        # what --checkpoint_every_rounds=100 adds to every round
        "amortized_per_round_ms": round(save_t / every_rounds * 1e3, 3),
        "amortized_overhead_pct": round(
            save_t / every_rounds / round_t * 100, 3),
        "checkpoint_every_rounds": every_rounds,
    }


def bench_generate(batch=8, prompt_len=128, new_tokens=64,
                   ab_uncached=False):
    """KV-cached decode throughput: gpt2-small bf16, tokens/s/chip.

    One DecodeEngine generate dispatch = prefill (fills the cache from
    the padded prompts, O(P^2) once) + a jitted lax.scan of single-query
    decode steps (ops/attention.decode_attention, O(S) per token,
    sampling in-program — zero host syncs between tokens). The
    prefill-vs-decode split comes from timing the prefill program
    standalone and subtracting it from the whole generate dispatch.

    Flat-in-prefix assertion: the decode program is one compile whose
    cost depends on the CACHE CAPACITY, not on how many tokens are
    already in context — decoding after a full-length prompt must cost
    the same per token as after a quarter-length one. Both runs reuse
    the identical compiled program (only the length VALUES differ), and
    the breakdown reports the measured ratio, asserted ~1. The
    incumbent recompute-everything loop is the opposite: every token
    pays a full window forward.

    ``ab_uncached`` times that incumbent (models/gpt2_generate.py's
    structure: one full-window jitted forward + a host round-trip per
    token) for a few tokens and reports the measured per-token speedup.
    Batch 1 only: the uncached forward materializes (B, S, V) logits —
    2.5 GB at batch 64, which is itself part of why it cannot serve.

    Returns (decode tokens/s/chip, breakdown dict)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import DecodeEngine

    B, P, N = batch, prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 50000, (B, P)).astype(np.int32))
    types = jnp.asarray(rng.randint(0, 3, (B, P)).astype(np.int32))
    reply_type = jnp.asarray(np.full((B,), 1, np.int32))
    len_full = jnp.asarray(np.full((B,), P, np.int32))
    len_short = jnp.asarray(np.full((B,), max(8, P // 4), np.int32))
    key = jax.random.PRNGKey(0)
    sample_in = (ids[:1, None, :8], types[:1, None, :8],
                 jnp.zeros((1, 1), jnp.int32))

    if DRY_RUN:
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False),
            key)["params"]
        engine = DecodeEngine(model, params, eos_id=50261, max_len=S)
        cache = jax.eval_shape(lambda: engine.init_cache(B))
        jax.eval_shape(engine._prefill_raw, params, cache, ids, types,
                       len_full - 1)
        out = jax.eval_shape(
            lambda *a: engine._generate_raw(*a, max_new=N),
            params, ids, types, len_full, reply_type, key)
        if ab_uncached:
            jax.eval_shape(
                lambda p: model.apply({"params": p}, ids[:, None, :],
                                      types[:, None, :],
                                      jnp.zeros((B, 1), jnp.int32),
                                      train=False), params)
        return {"dry_run": "ok", "tokens_shape": list(out.shape)}, {}

    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=50261, max_len=S)

    cache0 = engine.init_cache(B)
    prefill_t = _time(lambda: engine.prefill(params, cache0, ids, types,
                                             len_full - 1)[0])
    gen_full_t = _time(lambda: engine.generate_tokens(
        params, ids, types, len_full, reply_type, key, max_new=N))
    gen_short_t = _time(lambda: engine.generate_tokens(
        params, ids, types, len_short, reply_type, key, max_new=N))

    decode_full = max(gen_full_t - prefill_t, 1e-9)
    decode_short = max(gen_short_t - prefill_t, 1e-9)
    per_tok_full = decode_full / N
    per_tok_short = decode_short / N
    flat_ratio = per_tok_full / per_tok_short

    breakdown = {
        "batch": B, "prompt_len": P, "new_tokens": N,
        "cache_capacity": S,
        "prefill_ms": round(prefill_t * 1e3, 3),
        "generate_total_ms": round(gen_full_t * 1e3, 3),
        "decode_ms": round(decode_full * 1e3, 3),
        "decode_per_token_ms": round(per_tok_full * 1e3, 4),
        "decode_per_token_ms_quarter_prefix": round(per_tok_short * 1e3,
                                                    4),
        "decode_flat_in_prefix_ratio": round(flat_ratio, 3),
        "e2e_tokens_per_sec": round(B * N / gen_full_t, 1),
    }

    if ab_uncached:
        # the incumbent's cost structure: full-window forward + host
        # round-trip per token (sample_reply's loop, batched)
        @jax.jit
        def uncached_step(p, buf_ids, buf_types, idx):
            lm, _ = model.apply({"params": p}, buf_ids[:, None, :],
                                buf_types[:, None, :],
                                jnp.zeros((B, 1), jnp.int32), train=False)
            row = jnp.take_along_axis(lm[:, 0], idx[:, None, None],
                                      axis=1)[:, 0]
            return jnp.argmax(row, axis=-1).astype(jnp.int32)

        buf_ids = np.zeros((B, S), np.int32)
        buf_types = np.ones((B, S), np.int32)
        buf_ids[:, :P] = np.asarray(ids)
        buf_types[:, :P] = np.asarray(types)
        n_ab = min(N, 8)

        def uncached_tokens():
            bi, bt = buf_ids.copy(), buf_types.copy()
            last = None
            for t in range(n_ab):
                nxt = np.asarray(uncached_step(
                    params, jnp.asarray(bi), jnp.asarray(bt),
                    jnp.full((B,), P + t - 1, jnp.int32)))
                bi[:, P + t] = nxt
                last = nxt
            return jnp.asarray(last)

        uncached_t = _time(uncached_tokens, n=3) / n_ab
        breakdown["uncached_per_token_ms"] = round(uncached_t * 1e3, 3)
        breakdown["uncached_speedup_x"] = round(uncached_t / per_tok_full,
                                                2)

    # flat-in-prefix contract, asserted from the measured breakdown
    # (lenient bounds: the shared chip can swing individual windows)
    assert 0.5 < flat_ratio < 2.0, (
        f"decode cost not flat in prefix length: {breakdown}")
    return B * N / decode_full, breakdown


def bench_decode_paged_ab(batches=(8, 64), prompt_len=128, new_tokens=64,
                          page_size=16, requests_per_slot=3):
    """Paged-vs-fixed serving A/B: the continuous-batching server run
    over the same request stream (random prompts in [P/2, P], budget N)
    with ``kv_cache='paged'`` (block-paged pools + traced page table,
    serving/paged_cache.py) and ``kv_cache='fixed'`` (the dense
    (slots, max_len, H, hd) slab). Throughput should be ~flat — the
    paged step does the same attention math through a page gather — so
    the number that matters is the DERIVED capacity multiplier: the
    dense slab reserves slots * max_pages pages of HBM up front, while
    the paged pool's measured peak occupancy is what the stream actually
    needed, and their ratio is how many more concurrent users the same
    KV HBM holds under paging (ROADMAP item 1's users-per-chip lever).
    Greedy decode; replies are not compared here (bitwise parity is
    tests/test_paged_serving.py's job, the decode_paged audit pins the
    no-dense-slab invariant).

    Dry-run traces the paged pack + step programs via eval_shape — the
    pools stay (num_pages, page_size, H, hd) end to end.

    Returns (paged/fixed tokens/s ratio at the largest batch, breakdown
    with both arms' tokens/s and the capacity multiplier per batch)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)
    from commefficient_tpu.serving.paged_cache import PagedKVCache

    P, N = prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))

    if DRY_RUN:
        B = batches[0]
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False), key)["params"]
        engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                              method="greedy")
        pager = PagedKVCache(slots=B, max_len=S, prefill_len=P,
                             page_size=page_size)
        pools = jax.eval_shape(
            lambda: engine.init_paged_pools(pager.num_pages, page_size))
        ids1 = jax.ShapeDtypeStruct((1, P), jnp.int32)
        cache1 = jax.eval_shape(lambda: engine.init_cache(1))
        _, row_cache = jax.eval_shape(
            engine._prefill_raw, params, cache1, ids1, ids1,
            jax.ShapeDtypeStruct((1,), jnp.int32))
        pools = jax.eval_shape(
            engine._paged_insert_raw, pools, row_cache,
            jax.ShapeDtypeStruct((pager.prefill_pages,), jnp.int32))
        vec = jax.ShapeDtypeStruct((B,), jnp.int32)
        out = jax.eval_shape(
            engine._paged_step_raw, params, pools,
            jax.ShapeDtypeStruct((B, pager.max_pages), jnp.int32),
            vec, vec, vec, key, jax.ShapeDtypeStruct((B,), jnp.bool_))
        return {"dry_run": "ok", "out_leaves": len(jax.tree.leaves(out))}, {}

    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                          method="greedy")
    breakdown = {"prompt_len": P, "new_tokens": N, "page_size": page_size,
                 "requests_per_slot": requests_per_slot}
    ratio = None
    for B in batches:
        reqs = []
        for _ in range(requests_per_slot * B):
            L = int(rng.randint(P // 2, P + 1))
            reqs.append((rng.randint(0, 50000, L).astype(np.int32).tolist(),
                         [1] * L))
        for kv in ("paged", "fixed"):
            kw = {"page_size": page_size} if kv == "paged" else {}

            def make():
                return ContinuousBatchingServer(engine, slots=B,
                                                prefill_len=P,
                                                kv_cache=kv, **kw)

            warm = make()                       # compile all programs
            warm.submit(reqs[0][0], reqs[0][1], 1, 2)
            warm.run()
            srv = make()
            for ids, types in reqs:
                srv.submit(ids, types, 1, N)
            got, peak = 0, 0
            t0 = time.perf_counter()
            while srv._queue or any(r is not None for r in srv._slot_req):
                for _, toks in srv.step():
                    got += len(toks)
                if srv.pager is not None:
                    peak = max(peak, srv.pager.pages_in_use)
            dt = time.perf_counter() - t0
            breakdown[f"{kv}_tokens_per_sec_b{B}"] = round(got / dt, 1)
            if srv.pager is not None:
                # pages the dense slab would have RESERVED for the same
                # B slots vs what the paged pool's peak actually held
                breakdown[f"paged_peak_pages_b{B}"] = int(peak)
                breakdown[f"users_per_chip_at_fixed_hbm_x_b{B}"] = round(
                    B * srv.pager.max_pages / max(peak, 1), 2)
        ratio = (breakdown[f"paged_tokens_per_sec_b{B}"]
                 / breakdown[f"fixed_tokens_per_sec_b{B}"])
    return round(ratio, 4), breakdown


def bench_decode_paged_quant_ab(batches=(8, 64), prompt_len=128,
                                new_tokens=64, page_size=16,
                                requests_per_slot=3, kv_quant="int8"):
    """Quantized-vs-f32 paged pool A/B: the continuous-batching server
    run over the same request stream with ``--kv_quant int8`` (int8
    pools + per-page-per-head f32 scales, ops/kv_quant.py) and
    ``--kv_quant none`` (the f32 incumbent). Throughput should be ~flat
    — the dequant runs only on GATHERED pages inside the attention
    kernel, never on the pool — so the number that matters is the
    CAPACITY multiplier: the same KV HBM holds ~3.97x the pages at int8
    (pool bytes + scale bytes vs f32 pool bytes), which multiplies
    straight onto the paged users-per-chip lever. Replies are not
    compared here (the int8 logit-tolerance/token-agreement contract is
    tests/test_serving_kv_quant.py's job; the decode_paged_quant audit pins the
    no-f32-pool invariant).

    Dry-run traces the int8 paged step and runs the REAL audit rule
    over its jaxpr — no f32 aval of the pool's (num_pages, page_size,
    H, hd) shape anywhere — and asserts the byte-accounted capacity
    multiplier clears 3x.

    Returns (int8/f32 tokens/s ratio at the largest batch, breakdown
    with both arms' tokens/s and the capacity multiplier)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.ops import kv_quant as kvq
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)
    from commefficient_tpu.serving.paged_cache import PagedKVCache

    P, N = prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    hd = gcfg.n_embd // gcfg.n_head
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))

    if DRY_RUN:
        from commefficient_tpu.analysis import (FootprintRule, ShapePattern,
                                                walk)
        B = batches[0]
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False), key)["params"]
        engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                              method="greedy")
        pager = PagedKVCache(slots=B, max_len=S, prefill_len=P,
                             page_size=page_size)
        pools = jax.eval_shape(
            lambda: engine.init_paged_pools(pager.num_pages, page_size,
                                            kv_quant=kv_quant))
        vec = jax.ShapeDtypeStruct((B,), jnp.int32)
        closed = jax.make_jaxpr(engine._paged_step_raw)(
            params, pools,
            jax.ShapeDtypeStruct((B, pager.max_pages), jnp.int32),
            vec, vec, vec, key, jax.ShapeDtypeStruct((B,), jnp.bool_))
        sites, stats = walk(closed)
        pat = ShapePattern(("num_pages", "page_size", "H", "hd"),
                           label="f32 materialization of the quantized "
                                 "KV pool",
                           allow_primitives=frozenset(), dtype="float32")
        rep = FootprintRule((pat,)).check(
            sites, stats, {"num_pages": pager.num_pages,
                           "page_size": page_size,
                           "H": gcfg.n_head, "hd": hd})
        assert rep.ok, [str(v) for v in rep.violations]
        mult = kvq.capacity_multiplier_vs_f32(pager.num_pages, page_size,
                                              gcfg.n_head, hd,
                                              gcfg.n_layer, kv_quant)
        assert mult >= 3.0, f"capacity multiplier {mult} < 3x"
        return {"dry_run": "ok",
                "users_per_chip_at_fixed_hbm_x": round(mult, 4)}, {}

    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                          method="greedy")
    breakdown = {"prompt_len": P, "new_tokens": N, "page_size": page_size,
                 "kv_quant": kv_quant,
                 "requests_per_slot": requests_per_slot}
    ratio = None
    for B in batches:
        reqs = []
        for _ in range(requests_per_slot * B):
            L = int(rng.randint(P // 2, P + 1))
            reqs.append((rng.randint(0, 50000, L).astype(np.int32).tolist(),
                         [1] * L))
        for mode in ("none", kv_quant):
            tag = "f32" if mode == "none" else mode

            def make():
                return ContinuousBatchingServer(engine, slots=B,
                                                prefill_len=P,
                                                kv_cache="paged",
                                                page_size=page_size,
                                                kv_quant=mode)

            warm = make()                       # compile all programs
            warm.submit(reqs[0][0], reqs[0][1], 1, 2)
            warm.run()
            srv = make()
            for ids, types in reqs:
                srv.submit(ids, types, 1, N)
            got, peak = 0, 0
            t0 = time.perf_counter()
            while srv._queue or any(r is not None for r in srv._slot_req):
                for _, toks in srv.step():
                    got += len(toks)
                peak = max(peak, srv.pager.pages_in_use)
            dt = time.perf_counter() - t0
            breakdown[f"{tag}_tokens_per_sec_b{B}"] = round(got / dt, 1)
            st = srv.stats()
            breakdown[f"{tag}_pool_bytes"] = st["kv_pool_bytes"]
            if mode != "none":
                # pool-byte capacity multiplier composed onto the paged
                # peak-vs-reserved ratio: users the same KV HBM holds
                mult = st["kv_capacity_multiplier_vs_f32"]
                breakdown["kv_capacity_multiplier_vs_f32"] = round(mult, 4)
                breakdown[f"users_per_chip_at_fixed_hbm_x_b{B}"] = round(
                    mult * B * srv.pager.max_pages / max(peak, 1), 2)
        ratio = (breakdown[f"{kv_quant}_tokens_per_sec_b{B}"]
                 / breakdown[f"f32_tokens_per_sec_b{B}"])
    return round(ratio, 4), breakdown


def bench_personalized_admission(n_users=16, k=256, prompt_len=128):
    """--serve_personalized admission overhead: applying a user's O(k)
    sparse weight delta at slot admission (PersonalizationIndex.admit)
    and restoring base at retirement (evict), priced against the B=1
    prefill every admission already pays. gpt2-small params, a sparse
    client store with ``k`` nonzero coordinates per user row — the
    store rows are built directly as idx/val pairs, so nothing dense in
    d=124M is ever materialized (the serving deployment's exact shape).

    Dry-run exercises the REAL exactness contract at tiny scale (like
    the checkpoint row): a zero-delta admit returns the params object
    untouched, and an admit/evict cycle restores every leaf bitwise.

    Returns the breakdown dict; the headline is the per-admission delta
    apply time in ms."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.client_store import (HostArenaStore,
                                                          make_codec)
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import DecodeEngine, PersonalizationIndex

    def sparse_store(d, n, cap):
        cfg = FedConfig(mode="local_topk", error_type="local",
                        client_state="sparse", k=cap,
                        num_clients=n).finalize(d)
        return HostArenaStore(cfg, make_codec(cfg))

    if DRY_RUN:
        # host-side bookkeeping + two tiny jitted scatters: run the real
        # contract instead of eval_shape (nothing here is worth tracing
        # abstractly — the exactness IS the row's correctness surface)
        gcfg = GPT2Config.tiny(vocab_size=256)
        model = GPT2DoubleHeads(gcfg)
        z = np.zeros((1, 1, 8), np.int32)
        params = model.init(jax.random.PRNGKey(0), z, z,
                            np.zeros((1, 1), np.int32),
                            train=False)["params"]
        d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        store = sparse_store(d, 4, 4)
        index = PersonalizationIndex(params, store)
        assert index.admit(params, 0) is params     # zero delta: no-op
        rng = np.random.RandomState(0)
        idx = rng.choice(d, 4, replace=False).astype(np.int64)
        store.set_row("errors", 1, {"idx": idx,
                                    "val": np.full(4, 0.5, np.float32)})
        served = index.admit(params, 1)
        restored = index.evict(served, 1)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return {"dry_run": "ok", "d": d}

    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, prompt_len)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    key = jax.random.PRNGKey(0)
    z = jnp.zeros((1, 1, 8), jnp.int32)
    params = model.init(key, z, z, jnp.zeros((1, 1), jnp.int32),
                        train=False)["params"]
    d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    store = sparse_store(d, n_users, k)
    rng = np.random.RandomState(0)
    for uid in range(n_users):
        # distinct coordinates without a d-sized permutation: oversample
        # with replacement, dedup, top up from a disjoint tail
        cand = np.unique(rng.randint(0, d - k, 2 * k))[:k]
        idx = np.concatenate([cand, np.arange(d - k, d - k + k -
                                              cand.shape[0])])
        val = rng.randn(k).astype(np.float32)
        val[val == 0.0] = 1.0
        store.set_row("errors", uid,
                      {"idx": idx.astype(np.int64), "val": val})
    index = PersonalizationIndex(params, store)

    first = index.admit(params, 0)              # compile the leaf scatters
    _sync(jax.tree.leaves(first)[0])
    index.evict(first, 0)

    admits, evicts = [], []
    for uid in range(1, n_users):
        t0 = time.perf_counter()
        served = index.admit(params, uid)
        _sync(jax.tree.leaves(served)[0])
        admits.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        back = index.evict(served, uid)
        _sync(jax.tree.leaves(back)[0])
        evicts.append(time.perf_counter() - t0)

    # the cost admission already pays, for scale: one B=1 prefill
    engine = DecodeEngine(model, params, eos_id=50261, max_len=prompt_len,
                          method="greedy")
    ids = jnp.asarray(rng.randint(0, 50000, (1, prompt_len)), jnp.int32)
    cache = engine.init_cache(1)
    last = jnp.asarray([prompt_len - 1], jnp.int32)
    prefill_t = _time(lambda: engine.prefill(params, cache, ids, ids,
                                             last)[0])

    apply_ms = float(np.median(admits)) * 1e3
    return {
        "admission_delta_apply_ms": round(apply_ms, 3),
        "eviction_restore_ms": round(float(np.median(evicts)) * 1e3, 3),
        "prefill_ms": round(prefill_t * 1e3, 3),
        "overhead_vs_prefill_pct": round(
            apply_ms / (prefill_t * 1e3) * 100, 2),
        "k": k, "d": d, "n_users": n_users,
    }


def bench_decode_speculative_ab(gammas=(0, 2, 4, 8), batches=(1, 8),
                                prompt_len=128, new_tokens=64,
                                page_size=16, method="greedy"):
    """Speculative decoding A/B over the paged serving stack: the
    continuous-batching server run over the same greedy request stream
    with ``speculate_k`` swept over γ ∈ ``gammas`` (γ=0 is the
    non-speculative incumbent) at each batch size. The drafter is a
    randomly-initialized ``GPT2Config.tiny()``-class model sharing the
    target's vocab, which prices the MECHANISM honestly: a random
    drafter's acceptance is near-floor, so a loss at every γ is the
    budgeted, publishable answer for an untrained drafter, and the
    acceptance-rate breakdown says how much a distilled drafter would
    have to accept for the γ-round arithmetic (γ drafter forwards + one
    γ+1-token target forward per up-to-γ+1 tokens) to win. A
    self-drafting ceiling arm (drafter == target, acceptance 1.0) bounds
    the mechanism's best case at the largest batch. Emitted tokens are
    bitwise the non-speculative stream by construction
    (tests/test_speculative.py asserts it; this row only times).

    ``method='topk'`` runs the same sweep with STOCHASTIC acceptance
    (the Leviathan/Chen residual rule, serving/speculative.py): drafts
    sampled from the drafter's top-k distribution, accept with prob
    min(1, q/p), resample rejections from the normalized residual — the
    emitted marginals match the non-speculative top-k stream
    (tests/test_speculative.py's distribution-equivalence row) rather
    than being bitwise.

    Dry-run traces the draft and paged-verify programs via eval_shape —
    the verify stays paged end to end (the decode_speculative audit pins
    the no-dense-slab invariant); at ``method='topk'`` it traces the
    stochastic twins (rng-threaded draft + residual-rule verify).

    Returns (best speculative tokens/s over the γ=0 arm at the largest
    batch, breakdown with per-γ tokens/s + acceptance rates)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)

    P, N = prompt_len, new_tokens
    S = P + N
    V = 50262
    gcfg = GPT2Config.small(vocab_size=V)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    dcfg = GPT2Config.tiny(vocab_size=V)
    dcfg.n_positions = max(dcfg.n_positions, S)
    dcfg.dtype = "bfloat16"
    drafter = GPT2DoubleHeads(dcfg)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))

    if DRY_RUN:
        from commefficient_tpu.serving.paged_cache import PagedKVCache
        from commefficient_tpu.serving.speculative import SpeculativeDecoder

        B, gamma = batches[0], gammas[-1] or 4
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False), key)["params"]
        dparams = jax.eval_shape(
            lambda r: drafter.init(r, *sample_in, train=False),
            key)["params"]
        engine = DecodeEngine(model, params, eos_id=V - 1, max_len=S,
                              method=method)
        spec = SpeculativeDecoder(engine, gamma=gamma, slots=B,
                                  drafter_model=drafter,
                                  drafter_params=dparams)
        pager = PagedKVCache(slots=B, max_len=S, prefill_len=P,
                             page_size=page_size)
        pools = jax.eval_shape(
            lambda: engine.init_paged_pools(pager.num_pages, page_size))
        vec = jax.ShapeDtypeStruct((B,), jnp.int32)
        done = jax.ShapeDtypeStruct((B,), jnp.bool_)
        pt = jax.ShapeDtypeStruct((B, pager.max_pages), jnp.int32)
        if method == "topk":
            assert spec.stochastic
            _, drafts, dprobs, _ = jax.eval_shape(
                spec._draft_stoch_raw, dparams, spec.dcache,
                vec, vec, vec, vec, vec, key)
            assert drafts.shape == (B, gamma), drafts.shape
            assert dprobs.shape == (B, gamma, V), dprobs.shape
            out = jax.eval_shape(
                spec._paged_verify_stoch_raw, params, pools, pt,
                vec, vec, vec, drafts, dprobs, done, key)
        else:
            _, drafts = jax.eval_shape(spec._draft_raw, dparams,
                                       spec.dcache, vec, vec, vec, vec,
                                       vec)
            assert drafts.shape == (B, gamma), drafts.shape
            out = jax.eval_shape(
                spec._paged_verify_raw, params, pools, pt,
                vec, vec, vec, drafts, done)
        assert out[1].shape == (B, gamma + 1), out[1].shape  # emitted
        return {"dry_run": "ok",
                "out_leaves": len(jax.tree.leaves(out))}, {}

    params = model.init(key, *sample_in, train=False)["params"]
    dparams = drafter.init(jax.random.PRNGKey(1), *sample_in,
                           train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=V - 1, max_len=S,
                          method=method)
    breakdown = {"prompt_len": P, "new_tokens": N, "page_size": page_size,
                 "drafter": "tiny-random", "method": method,
                 "gammas": list(gammas), "batches": list(batches)}
    ratio = None
    for B in batches:
        reqs = []
        for _ in range(2 * B):
            L = int(rng.randint(P // 2, P + 1))
            reqs.append((rng.randint(0, 50000, L).astype(np.int32).tolist(),
                         [1] * L))

        def run_arm(g, dm=None, dp=None, tag=""):
            kw = {}
            if g:
                kw = {"speculate_k": g, "drafter_model": dm or drafter,
                      "drafter_params": dp if dp is not None else dparams}
            warm = ContinuousBatchingServer(engine, slots=B, prefill_len=P,
                                            kv_cache="paged",
                                            page_size=page_size, **kw)
            warm.submit(reqs[0][0], reqs[0][1], 1, 2)
            warm.run()
            srv = ContinuousBatchingServer(engine, slots=B, prefill_len=P,
                                           kv_cache="paged",
                                           page_size=page_size, **kw)
            for ids, types in reqs:
                srv.submit(ids, types, 1, N)
            got = 0
            t0 = time.perf_counter()
            while srv._queue or any(r is not None for r in srv._slot_req):
                for _, toks in srv.step():
                    got += len(toks)
            dt = time.perf_counter() - t0
            breakdown[f"spec{tag}_g{g}_b{B}_tokens_per_sec"] = round(
                got / dt, 1)
            if g:
                st = srv.stats()
                breakdown[f"acceptance_rate{tag}_g{g}_b{B}"] = round(
                    st["acceptance_rate"] or 0.0, 4)
            return got / dt

        base = run_arm(0)
        best = max(run_arm(g) for g in gammas if g)
        ratio = best / base
        if B == max(batches):
            # self-drafting ceiling: acceptance 1.0 by construction, so
            # this is the best any drafter of the TARGET's cost could do
            run_arm(max(g for g in gammas if g), dm=model, dp=params,
                    tag="_selfdraft")
    return round(ratio, 4), breakdown


def bench_decode_speculative_personalized(gamma=4, batch=8,
                                          prompt_len=128, new_tokens=64,
                                          page_size=16, k=256):
    """The free personalized drafter: ``--speculate_k`` composed with
    ``--serve_personalized`` on the paged server. The drafter snapshots
    BASE params at server construction (personalization's admit returns
    a new tree, so the snapshot never sees a user delta) while the
    verify forward serves base + each admitted user's O(k) sparse
    delta — the drafter costs nothing extra per user, and output is
    still exactly the personalized target's greedy stream. Reports the
    speculative-vs-plain throughput ratio on a personalized request
    stream plus the base-drafter acceptance rate (how far k nonzeros of
    delta move gpt2-small's argmax stream — a measured, publishable
    number either way).

    Dry-run runs the REAL composition contract at tiny scale: a
    self-drafting speculative personalized server must reply bitwise
    with the non-speculative personalized server over the same users.

    Returns (speculative/plain tokens/s ratio, breakdown)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.client_store import (HostArenaStore,
                                                          make_codec)
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine,
                                           PersonalizationIndex)

    def sparse_store(d, n, cap):
        cfg = FedConfig(mode="local_topk", error_type="local",
                        client_state="sparse", k=cap,
                        num_clients=n).finalize(d)
        return HostArenaStore(cfg, make_codec(cfg))

    if DRY_RUN:
        gcfg = GPT2Config.tiny(vocab_size=256)
        model = GPT2DoubleHeads(gcfg)
        z = np.zeros((1, 1, 8), np.int32)
        params = model.init(jax.random.PRNGKey(0), z, z,
                            np.zeros((1, 1), np.int32),
                            train=False)["params"]
        d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        rng = np.random.RandomState(3)
        store = sparse_store(d, 4, 8)
        for uid in range(4):
            store.set_row("errors", uid, {
                "idx": rng.choice(d, 8, replace=False).astype(np.int64),
                "val": rng.randn(8).astype(np.float32)})
        reqs = [([int(t) for t in rng.randint(1, 255, 6)], [1] * 6, uid)
                for uid in range(4)]

        def serve(spec_k):
            # slots=1 serializes occupancy: active users' deltas share
            # one params tree, so WHICH users are co-resident shifts
            # logits, and speculation retires rows on a different
            # schedule — the per-request contract is parity under the
            # same co-residency, which one slot pins
            eng = DecodeEngine(model, params, eos_id=255, max_len=32)
            srv = ContinuousBatchingServer(
                eng, slots=1, prefill_len=8, kv_cache="paged",
                page_size=8, speculate_k=spec_k,
                personalize=PersonalizationIndex(params, store))
            for ids, types, uid in reqs:
                srv.submit(ids, types, 2, 8, user_id=uid)
            return srv.run()

        assert serve(gamma) == serve(0), \
            "personalized speculative replies diverged from plain"
        return {"dry_run": "ok", "d": d}, {}

    P, N = prompt_len, new_tokens
    S = P + N
    V = 50262
    gcfg = GPT2Config.small(vocab_size=V)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    key = jax.random.PRNGKey(0)
    z = jnp.zeros((1, 1, 8), jnp.int32)
    params = model.init(key, z, z, jnp.zeros((1, 1), jnp.int32),
                        train=False)["params"]
    d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    rng = np.random.RandomState(0)
    n_users = 2 * batch
    store = sparse_store(d, n_users, k)
    for uid in range(n_users):
        cand = np.unique(rng.randint(0, d - k, 2 * k))[:k]
        idx = np.concatenate([cand, np.arange(d - k, d - k + k -
                                              cand.shape[0])])
        val = (0.02 * rng.randn(k)).astype(np.float32)
        val[val == 0.0] = 0.01
        store.set_row("errors", uid,
                      {"idx": idx.astype(np.int64), "val": val})
    engine = DecodeEngine(model, params, eos_id=V - 1, max_len=S,
                          method="greedy")
    reqs = []
    for uid in range(n_users):
        L = int(rng.randint(P // 2, P + 1))
        reqs.append((rng.randint(0, 50000, L).astype(np.int32).tolist(),
                     [1] * L, uid))

    breakdown = {"gamma": gamma, "batch": batch, "k": k,
                 "prompt_len": P, "new_tokens": N}
    tps = {}
    for g in (0, gamma):
        def make():
            return ContinuousBatchingServer(
                engine, slots=batch, prefill_len=P, kv_cache="paged",
                page_size=page_size, speculate_k=g,
                personalize=PersonalizationIndex(params, store))

        warm = make()
        warm.submit(reqs[0][0], reqs[0][1], 1, 2, user_id=reqs[0][2])
        warm.run()
        srv = make()
        for ids, types, uid in reqs:
            srv.submit(ids, types, 1, N, user_id=uid)
        got = 0
        t0 = time.perf_counter()
        while srv._queue or any(r is not None for r in srv._slot_req):
            for _, toks in srv.step():
                got += len(toks)
        dt = time.perf_counter() - t0
        tps[g] = got / dt
        breakdown[f"personalized_g{g}_tokens_per_sec"] = round(got / dt, 1)
        if g:
            st = srv.stats()
            breakdown["base_drafter_acceptance_rate"] = round(
                st["acceptance_rate"] or 0.0, 4)
    return round(tps[gamma] / tps[0], 4), breakdown


def bench_per_worker_sketch_ab(d=6_570_240, W=8, r=5, c=500_000):
    """BENCH_r08 A/B: the per-worker vmapped sketch — exactly the
    federated/client.py transmit shape, W workers' grads sketched under
    one vmap with ``use_kernel=True`` — on the batched 2-D grid Pallas
    kernel (forced 'kernel' dispatch; the natural choice on a TPU
    backend) vs the vmapped XLA formulation (forced 'fallback' — the
    pre-round-8 program). Deterministic device-cycle discipline: each arm
    compiles and times inside its own ``force_dispatch`` context
    back-to-back on the same chip, and the (W, r, c_eff) tables are
    checked BITWISE-equal between arms before the ratio is reported.
    Refutation is budgeted: a ratio below 1 is recorded as the measured
    answer, not suppressed.

    Dry-run: traces BOTH arms' programs on CPU and asserts the kernel
    arm's jaxpr contains the pallas_call while the fallback arm's does
    not — so a dispatch regression fails CI's trace, not just the
    on-chip capture."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.ops import sketch_kernels
    from commefficient_tpu.ops.countsketch import CountSketch

    cs = CountSketch(d=d, c=c, r=r, seed=8, scheme="tiled")
    assert sketch_kernels.kernel_supported(cs), (d, c, r)

    def transmit(vs):
        return jax.vmap(lambda v: cs.sketch_vec(v, True))(vs)

    if DRY_RUN:
        vecs = jax.ShapeDtypeStruct((W, d), jnp.float32)
        for mode, want_kernel in (("kernel", True), ("fallback", False)):
            with sketch_kernels.force_dispatch(mode):
                out = jax.eval_shape(transmit, vecs)
                assert out.shape == (W, cs.r, cs.c_eff), out.shape
                has = "pallas_call" in str(jax.make_jaxpr(transmit)(vecs))
                assert has == want_kernel, (mode, has)
        return None, {"d": d, "W": W, "r": r, "c": c}

    vecs = jnp.asarray(np.random.default_rng(0).standard_normal(
        (W, d), dtype=np.float32))
    ms, tables = {}, {}
    for mode in ("kernel", "fallback"):
        with sketch_kernels.force_dispatch(mode):
            # compile AND time inside the context: force_dispatch clears
            # jit caches at its edges, so each arm's program is fresh
            fn = jax.jit(transmit)
            out = fn(vecs)
            _sync(out)
            ms[mode] = _time(fn, vecs, n=5) * 1e3
            tables[mode] = np.asarray(out)  # (W, r, c_eff): small
    bitwise = bool(np.array_equal(tables["kernel"], tables["fallback"]))
    assert bitwise, "batched kernel diverged from the XLA formulation"
    return ms["fallback"] / ms["kernel"], {
        "kernel_ms": round(ms["kernel"], 3),
        "xla_ms": round(ms["fallback"], 3),
        "bitwise_equal": bitwise, "d": d, "W": W, "r": r, "c": c}


def bench_server_update_fused_ab(d=124_440_576, k=50_000, r=5, c=500_000):
    """BENCH_r09 A/B: the fused server-update path (--server_fused auto,
    ops/topk_kernels.py) vs the incumbent chain, at gpt2-small scale
    (d=124.4M, k=50k) for BOTH modes that select server-side:

    * true_topk — one streaming pass fusing momentum, error
      accumulation, the exact radix top-k and both error-feedback
      residuals (forced 'kernel') vs momentum -> err -> lax.top_k ->
      scatter -> two jnp.where sweeps (forced 'fallback', the program
      ``--server_fused off`` pins).
    * sketch — fused unsketch+select (estimates computed per tile in
      VMEM, the (d,) estimate vector never materialized) vs
      estimate-all -> topk_values_indices.

    Same chip, back-to-back, each arm compiled inside its own
    force_dispatch context; updates AND new (Vvelocity, Verror) state
    checked BITWISE-equal between arms before any ratio is reported
    (the contract tests/test_server_fused.py pins at toy scale).
    Refutation is budgeted: a ratio below 1 is recorded as the measured
    answer, not suppressed — adjudication in docs/ROOFLINE.md Round 9.

    Dry-run: traces both arms' programs on CPU and asserts the kernel
    arm's jaxpr contains pallas_call while the fallback arm's does not,
    so a dispatch regression fails CI's trace, not just the on-chip
    capture."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.server import (init_server_opt_state,
                                                    make_sketch,
                                                    server_update)
    from commefficient_tpu.ops import sketch_kernels

    cfgs = {
        "true_topk": FedConfig(mode="true_topk", error_type="virtual",
                               k=k, virtual_momentum=0.9).finalize(d),
        "sketch": FedConfig(mode="sketch", error_type="virtual", k=k,
                            num_rows=r, num_cols=c,
                            virtual_momentum=0.9).finalize(d),
    }
    breakdown = {"d": d, "k": k, "r": r, "c": c}
    ratios = {}
    for mode, cfg in cfgs.items():
        sketch = make_sketch(cfg) if mode == "sketch" else None

        def fn(g, st, _cfg=cfg, _sk=sketch):
            return server_update(g, st, _cfg, 0.1, sketch=_sk)

        if DRY_RUN:
            g_shape = ((sketch.r, sketch.c_eff) if mode == "sketch"
                       else (cfg.grad_dim,))
            g = jax.ShapeDtypeStruct(g_shape, jnp.float32)
            st = jax.eval_shape(lambda _cfg=cfg: init_server_opt_state(_cfg))
            for force, want_kernel in (("kernel", True),
                                       ("fallback", False)):
                with sketch_kernels.force_dispatch(force):
                    has = "pallas_call" in str(jax.make_jaxpr(fn)(g, st))
                    assert has == want_kernel, (mode, force, has)
            continue
        if mode == "sketch":
            vec = jax.random.normal(jax.random.PRNGKey(0),
                                    (cfg.grad_dim,), jnp.float32)
            g = jax.jit(sketch.sketch_vec)(vec)
            del vec
        else:
            g = jax.random.normal(jax.random.PRNGKey(0),
                                  (cfg.grad_dim,), jnp.float32)
        ms, outs = {}, {}
        for force in ("kernel", "fallback"):
            with sketch_kernels.force_dispatch(force):
                jitted = jax.jit(fn)
                st = init_server_opt_state(cfg)
                upd, new_st = jitted(g, st)
                _sync(upd)
                ms[force] = _time(jitted, g, st, n=5) * 1e3
                outs[force] = (upd, new_st)
        for a, b in zip(jax.tree_util.tree_leaves(outs["kernel"]),
                        jax.tree_util.tree_leaves(outs["fallback"])):
            assert bool(jnp.all(a == b)), \
                f"{mode}: fused server update diverged from incumbent"
        del outs, g
        ratios[mode] = ms["fallback"] / ms["kernel"]
        breakdown[f"{mode}_fused_ms"] = round(ms["kernel"], 3)
        breakdown[f"{mode}_incumbent_ms"] = round(ms["fallback"], 3)
        breakdown[f"{mode}_speedup_x"] = round(ratios[mode], 4)
        breakdown[f"{mode}_bitwise_equal"] = True
    if DRY_RUN:
        return None, breakdown
    return ratios["sketch"], breakdown


def bench_topk_hierarchical_ab(d=124_440_576, ks=(5_000, 50_000, 500_000)):
    """BENCH_r09 A/B: the streaming two-pass radix top-k kernel vs the
    sort-unit incumbent (jax.lax.top_k via ops/topk's masking path) on a
    dense (d,) vector at gpt2-small d, swept over k spanning two orders
    of magnitude around the paper's operating point (k = 50k at
    compression d/k ~ 2500x). Both arms run the PUBLIC ``topk`` entry
    under forced dispatch, so the row measures exactly what a dispatch
    flip changes and nothing else; masked outputs are checked
    BITWISE-equal per k (ties, signs and all — the lowest-index
    tie-break contract of tests/test_topk_kernels.py). Headline ratio is
    the k=50k point; the sweep rides in the breakdown.

    Dry-run: traces both arms per k on CPU, asserting pallas_call
    presence/absence in the jaxprs."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.ops import sketch_kernels
    from commefficient_tpu.ops.topk import topk

    breakdown = {"d": d, "ks": list(ks)}
    ratios = {}
    for k in ks:
        def fn(v, _k=k):
            return topk(v, _k)

        if DRY_RUN:
            v = jax.ShapeDtypeStruct((d,), jnp.float32)
            for force, want_kernel in (("kernel", True),
                                       ("fallback", False)):
                with sketch_kernels.force_dispatch(force):
                    has = "pallas_call" in str(jax.make_jaxpr(fn)(v))
                    assert has == want_kernel, (k, force, has)
            continue
        v = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
        ms, outs = {}, {}
        for force in ("kernel", "fallback"):
            with sketch_kernels.force_dispatch(force):
                jitted = jax.jit(fn)
                out = jitted(v)
                _sync(out)
                ms[force] = _time(jitted, v, n=5) * 1e3
                outs[force] = out
        assert bool(jnp.all(outs["kernel"] == outs["fallback"])), \
            f"k={k}: kernel top-k diverged from lax.top_k masking"
        del outs
        ratios[k] = ms["fallback"] / ms["kernel"]
        breakdown[f"k{k}_kernel_ms"] = round(ms["kernel"], 3)
        breakdown[f"k{k}_sort_unit_ms"] = round(ms["fallback"], 3)
        breakdown[f"k{k}_speedup_x"] = round(ratios[k], 4)
    if DRY_RUN:
        return None, breakdown
    return ratios[50_000] if 50_000 in ratios else \
        ratios[max(ratios)], breakdown


def bench_client_store_sketched_codec(d=6_570_240, W=8, r=3, c=128,
                                      k=50_000):
    """BENCH_r08: encode/decode cost of the sketched client-state codec
    (client_store.SketchedCodec) under its two schemes — the incumbent
    'global' per-coordinate layout vs 'tiled', whose W-row vmapped
    encode/decode can dispatch the batched Pallas kernels. PR 11 chose
    'global' on the ASSERTED claim that the tiled layout buys nothing at
    the codec's small-c operating point; this row turns that into a
    measurement (refutation budgeted — if tiled doesn't pay here,
    'global' stays the default and the ratio lands in ROOFLINE.md as the
    answer). Dry-run traces both schemes' encode+decode and asserts the
    tiled encode reaches the batched kernel under forced dispatch."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.federated.client_store import SketchedCodec
    from commefficient_tpu.ops import sketch_kernels

    codecs = {s: SketchedCodec(d, r=r, c=c, k=k, seed=1, scheme=s)
              for s in ("global", "tiled")}

    if DRY_RUN:
        rows = jax.ShapeDtypeStruct((W, d), jnp.float32)
        for s, codec in codecs.items():
            enc = jax.eval_shape(codec.encode_rows, rows)
            assert enc["table"].shape == (W, codec.cs.r, codec.cs.c_eff)
            dec = jax.eval_shape(codec.decode_rows, enc)
            assert dec.shape == (W, d), dec.shape
        with sketch_kernels.force_dispatch("kernel"):
            jaxpr = str(jax.make_jaxpr(codecs["tiled"].encode_rows)(rows))
        assert "pallas_call" in jaxpr, \
            "tiled codec encode did not reach the batched kernel"
        return None, {"d": d, "W": W, "r": r, "c": c, "k": k}

    rows = jnp.asarray(np.random.default_rng(1).standard_normal(
        (W, d), dtype=np.float32))
    breakdown = {"d": d, "W": W, "r": r, "c": c, "k": k}
    totals = {}
    for s, codec in codecs.items():
        enc_fn = jax.jit(codec.encode_rows)
        enc = enc_fn(rows)
        _sync(enc["table"])
        t_enc = _time(enc_fn, rows, n=5) * 1e3
        dec_fn = jax.jit(codec.decode_rows)
        _sync(dec_fn(enc))
        t_dec = _time(dec_fn, enc, n=5) * 1e3
        breakdown[f"{s}_encode_ms"] = round(t_enc, 3)
        breakdown[f"{s}_decode_ms"] = round(t_dec, 3)
        totals[s] = t_enc + t_dec
    return totals["global"] / totals["tiled"], breakdown


def _run_metric(name, fn, errors):
    """Run one bench in isolation: a failure in metric A must not cost
    metrics B..Z their numbers. The failure is recorded in ``errors``
    (``main`` then exits non-zero) and the metric reports None."""
    try:
        return fn()
    except Exception as exc:  # noqa: BLE001 — isolation is the point
        errors.append({"metric": name,
                       "error": f"{type(exc).__name__}: {exc}"[:500]})
        return None


def bench_decode_tp_ab(batches=(8, 64), prompt_len=128, new_tokens=64,
                       page_size=16, tp=2, requests_per_slot=2):
    """Tensor-parallel serving A/B: the paged continuous-batching server
    run over the same greedy request stream with a replicated engine
    (tp=1) and a head-sharded one (tp=2: Megatron params via
    parallel/tp.py, page pools sharded (num_pages, page_size, H/tp, hd)
    per shard, host page table unsharded). Tokens/s should be ~flat on
    one host — the win is CAPACITY: each shard holds 1/tp of the pool
    HBM, so at fixed per-chip KV HBM a tp-chip fleet serves tp x the
    concurrent users; the ``users_per_fleet_at_fixed_hbm_x`` entries
    price that against the measured peak page occupancy. Replies are
    not compared here (tp greedy parity is pinned token-identical by
    __graft_entry__.dryrun_multichip and tests/test_serving_multihost).

    Dry-run traces the tp-sharded paged step via eval_shape — the
    sharding_constraint annotations land in the jaxpr (the
    serve_multihost audit's subject). Degrades to mesh=None when the
    process has a single device.

    Returns (tp tokens/s / tp=1 tokens/s at the largest batch,
    breakdown with both arms' tokens/s + fleet-capacity multipliers)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)
    from commefficient_tpu.serving.paged_cache import PagedKVCache

    P, N = prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))
    mesh = (Mesh(np.asarray(jax.devices()[:tp]), ("model",))
            if jax.device_count() >= tp else None)

    if DRY_RUN:
        B = batches[0]
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False), key)["params"]
        engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                              method="greedy", mesh=mesh)
        pager = PagedKVCache(slots=B, max_len=S, prefill_len=P,
                             page_size=page_size)
        pools = jax.eval_shape(
            lambda: engine.init_paged_pools(pager.num_pages, page_size))
        vec = jax.ShapeDtypeStruct((B,), jnp.int32)
        out = jax.eval_shape(
            engine._paged_step_raw, params, pools,
            jax.ShapeDtypeStruct((B, pager.max_pages), jnp.int32),
            vec, vec, vec, key, jax.ShapeDtypeStruct((B,), jnp.bool_))
        return {"dry_run": "ok", "tp": engine.tp,
                "out_leaves": len(jax.tree.leaves(out))}, {}

    if mesh is None:
        return None     # single-device process: nothing to A/B

    params = model.init(key, *sample_in, train=False)["params"]
    engines = {
        1: DecodeEngine(model, params, eos_id=50261, max_len=S,
                        method="greedy"),
        tp: DecodeEngine(model, params, eos_id=50261, max_len=S,
                         method="greedy", mesh=mesh),
    }
    breakdown = {"prompt_len": P, "new_tokens": N, "page_size": page_size,
                 "tp": tp, "requests_per_slot": requests_per_slot}
    ratio = None
    for B in batches:
        reqs = []
        for _ in range(requests_per_slot * B):
            L = int(rng.randint(P // 2, P + 1))
            reqs.append((rng.randint(0, 50000, L).astype(np.int32).tolist(),
                         [1] * L))
        for arm, eng in engines.items():
            def make(eng=eng):
                return ContinuousBatchingServer(eng, slots=B,
                                                prefill_len=P,
                                                kv_cache="paged",
                                                page_size=page_size)

            warm = make()                       # compile all programs
            warm.submit(reqs[0][0], reqs[0][1], 1, 2)
            warm.run()
            srv = make()
            for ids, types in reqs:
                srv.submit(ids, types, 1, N)
            got, peak = 0, 0
            t0 = time.perf_counter()
            while srv._queue or any(r is not None for r in srv._slot_req):
                for _, toks in srv.step():
                    got += len(toks)
                peak = max(peak, srv.pager.pages_in_use)
            dt = time.perf_counter() - t0
            breakdown[f"tp{arm}_tokens_per_sec_b{B}"] = round(got / dt, 1)
            # each shard physically holds peak/arm pages' worth of KV
            # bytes, so a fleet of ``arm`` chips at the same per-chip KV
            # HBM budget as the dense single-chip slab holds arm x the
            # users the slab reserved for
            breakdown[f"users_per_fleet_at_fixed_hbm_x_b{B}_tp{arm}"] = \
                round(arm * B * srv.pager.max_pages / max(peak, 1), 2)
        ratio = (breakdown[f"tp{tp}_tokens_per_sec_b{B}"]
                 / breakdown[f"tp1_tokens_per_sec_b{B}"])
    return round(ratio, 4), breakdown


def bench_serve_disagg_latency(B=8, prompt_len=128, new_tokens=64,
                               page_size=16, burst=24):
    """Decode-latency-under-prefill-burst A/B: the paged server with a
    full decode pool gets ``burst`` queued requests dumped on it, and
    every ``step()``'s wall time is recorded until the stream drains.
    Unified admission runs EVERY fitting prefill before the decode
    step, so in-flight decodes hiccup by a full B=1 prefill per retired
    slot; disaggregation (--serve_disagg) steps the decode pool first
    and budgets admissions at ``prefill_slots`` per step, so the decode
    cadence stays flat. The p50 should roughly match across arms (most
    steps admit nothing) — the p99, the number a latency SLO is written
    against, is where the burst shows up.

    Dry-run traces the shared paged programs and constructs the
    disaggregated server (the split slot pools + budget validation are
    host-side wiring this exercises).

    Returns (unified p99 / disagg p99 — >1 means disaggregation
    flattened the tail, breakdown with both arms' p50/p99 ms)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)
    from commefficient_tpu.serving.paged_cache import PagedKVCache

    P, N = prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))

    if DRY_RUN:
        params = jax.eval_shape(
            lambda r: model.init(r, *sample_in, train=False), key)["params"]
        engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                              method="greedy")
        srv = ContinuousBatchingServer(engine, slots=B, prefill_len=P,
                                       kv_cache="paged",
                                       page_size=page_size,
                                       disaggregate=True)
        pager = srv.pager
        ids1 = jax.ShapeDtypeStruct((1, P), jnp.int32)
        cache1 = jax.eval_shape(lambda: engine.init_cache(1))
        _, row_cache = jax.eval_shape(
            engine._prefill_raw, params, cache1, ids1, ids1,
            jax.ShapeDtypeStruct((1,), jnp.int32))
        pools = jax.eval_shape(
            lambda: engine.init_paged_pools(pager.num_pages, page_size))
        pools = jax.eval_shape(
            engine._paged_insert_raw, pools, row_cache,
            jax.ShapeDtypeStruct((pager.prefill_pages,), jnp.int32))
        vec = jax.ShapeDtypeStruct((B,), jnp.int32)
        out = jax.eval_shape(
            engine._paged_step_raw, params, pools,
            jax.ShapeDtypeStruct((B, pager.max_pages), jnp.int32),
            vec, vec, vec, key, jax.ShapeDtypeStruct((B,), jnp.bool_))
        return {"dry_run": "ok", "prefill_slots": srv.prefill_slots,
                "out_leaves": len(jax.tree.leaves(out))}, {}

    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                          method="greedy")
    breakdown = {"slots": B, "prompt_len": P, "new_tokens": N,
                 "page_size": page_size, "burst": burst}
    p99s = {}
    for arm, disagg in (("unified", False), ("disagg", True)):
        def make(disagg=disagg):
            return ContinuousBatchingServer(engine, slots=B,
                                            prefill_len=P,
                                            kv_cache="paged",
                                            page_size=page_size,
                                            disaggregate=disagg)

        def prompt():
            L = int(rng.randint(P // 2, P + 1))
            return (rng.randint(0, 50000, L).astype(np.int32).tolist(),
                    [1] * L)

        warm = make()                           # compile all programs
        warm.submit(*prompt(), 1, 2)
        warm.run()
        srv = make()
        for _ in range(B):                      # fill the decode pool
            srv.submit(*prompt(), 1, N)
        srv.step()
        for _ in range(burst):                  # then the prefill burst
            srv.submit(*prompt(), 1, N)
        lat = []
        while srv._queue or any(r is not None for r in srv._slot_req):
            t0 = time.perf_counter()
            srv.step()
            lat.append((time.perf_counter() - t0) * 1e3)
        p50, p99 = np.percentile(np.asarray(lat), [50, 99])
        breakdown[f"{arm}_decode_step_p50_ms"] = round(float(p50), 2)
        breakdown[f"{arm}_decode_step_p99_ms"] = round(float(p99), 2)
        p99s[arm] = float(p99)
        if disagg:
            breakdown["prefill_slots"] = srv.prefill_slots
    return round(p99s["unified"] / max(p99s["disagg"], 1e-9), 4), breakdown


def _perturbed_params(params, eps, seed):
    """Deterministic shape/dtype-preserving weight perturbation — the
    stand-in for 'the learner trained for a while' in the online rows
    (a per-leaf sinusoid so no PRNG threading is needed)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: (x + eps * jnp.sin(
            jnp.arange(x.size, dtype=jnp.float32) + float(seed)
        ).reshape(x.shape).astype(x.dtype)).astype(x.dtype), params)


def bench_online_swap_latency(n_swaps=6, B=8, prompt_len=128,
                              new_tokens=64, page_size=16, queued=8):
    """--serve_online hot-swap latency: the wall time a running paged
    server spends promoting fresh base weights through
    HotSwapCoordinator — drain the in-flight slots to completion, place
    the new gpt2-small leaves onto the old leaves' shardings, resubmit
    the never-admitted queue verbatim, take the first post-swap step.
    ``n_swaps`` back-to-back swaps of pre-built perturbed weights with
    the request stream kept flowing between them. The compile-cache
    assertion is the row's hard contract: the paged step AND pack
    caches must sit at exactly their pre-swap sizes after every swap
    (params are per-call arguments everywhere, so a growing cache means
    a recompile leaked into the swap path — the online_loop audit pins
    the same invariant at audit scale).

    Dry-run runs the REAL contract at tiny scale (like the
    personalization row): a live tiny server mid-decode, two coordinator
    swaps of perturbed weights through drain -> swap -> resubmit, the
    caches asserted flat, zero dirty swaps, and the admitted work's
    replies delivered by the drain rather than thrown away.

    Returns (median swap-to-serving ms, breakdown with p50/p99,
    drained/resubmitted counts and the pinned cache sizes)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.online import HotSwapCoordinator
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)

    rng = np.random.RandomState(0)

    if DRY_RUN:
        V = 256
        model = GPT2DoubleHeads(GPT2Config.tiny(vocab_size=V))
        z = np.zeros((1, 1, 8), np.int32)
        params = model.init(jax.random.PRNGKey(0), z, z,
                            np.zeros((1, 1), np.int32),
                            train=False)["params"]
        engine = DecodeEngine(model, params, eos_id=V - 1, max_len=32,
                              method="greedy")
        srv = ContinuousBatchingServer(engine, slots=2, prefill_len=16,
                                       kv_cache="paged", page_size=8)
        coord = HotSwapCoordinator(srv, resubmit=True)
        for i in range(6):                      # 2 admitted + 4 queued
            ids = rng.randint(0, V - 1, 6 + i).astype(np.int32).tolist()
            srv.submit(ids, [1] * len(ids), 1, 8)
        srv.step()
        caches = (engine.paged_step._cache_size(),
                  engine.paged_insert._cache_size())
        drained = 0
        for k in range(2):
            # a swap must find slots mid-decode or it prices nothing
            while not any(r is not None for r in srv._slot_req):
                srv.step()
            replies, _ = coord.swap(_perturbed_params(params, 0.01, k))
            drained += len(replies)
            srv.step()                          # serve on the new weights
        after = (engine.paged_step._cache_size(),
                 engine.paged_insert._cache_size())
        assert after == caches, \
            f"compile cache grew across hot swaps: {caches} -> {after}"
        assert srv.dirty_swaps == 0 and coord.swaps_done == 2
        assert drained >= 2, "drain delivered no in-flight replies"
        srv.run()
        return {"dry_run": "ok", "caches": list(caches),
                "drained": drained}, {}

    P, N = prompt_len, new_tokens
    S = P + N
    gcfg = GPT2Config.small(vocab_size=50262)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))
    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=50261, max_len=S,
                          method="greedy")
    srv = ContinuousBatchingServer(engine, slots=B, prefill_len=P,
                                   kv_cache="paged", page_size=page_size)
    coord = HotSwapCoordinator(srv, resubmit=True)

    def prompt():
        L = int(rng.randint(P // 2, P + 1))
        return (rng.randint(0, 50000, L).astype(np.int32).tolist(),
                [1] * L)

    for _ in range(B):                          # compile every program
        srv.submit(*prompt(), 1, 4)
    srv.run()
    swaps = [_perturbed_params(params, 0.01, k) for k in range(n_swaps)]
    for s in swaps:                             # build OUTSIDE the clock
        _sync(jax.tree.leaves(s)[0])
    caches = (engine.paged_step._cache_size(),
              engine.paged_insert._cache_size())

    lat, drained, resubmitted = [], 0, 0
    for k in range(n_swaps):
        for _ in range(B + queued):             # in-flight + queued load
            srv.submit(*prompt(), 1, N)
        for _ in range(4):                      # slots mid-decode
            srv.step()
        t0 = time.perf_counter()
        replies, leftovers = coord.swap(swaps[k])
        srv.step()                              # first post-swap step
        lat.append((time.perf_counter() - t0) * 1e3)
        drained += len(replies)
        resubmitted += len(leftovers)
        srv.run()                               # clear between swaps
    after = (engine.paged_step._cache_size(),
             engine.paged_insert._cache_size())
    assert after == caches, \
        f"compile cache grew across hot swaps: {caches} -> {after}"
    assert srv.dirty_swaps == 0
    p50, p99 = np.percentile(np.asarray(lat), [50, 99])
    return round(float(p50), 2), {
        "swap_to_serving_p50_ms": round(float(p50), 2),
        "swap_to_serving_p99_ms": round(float(p99), 2),
        "n_swaps": n_swaps, "slots": B, "queued_per_swap": queued,
        "drained_total": drained, "resubmitted_total": resubmitted,
        "dirty_swaps": srv.dirty_swaps,
        "paged_step_cache": after[0], "paged_insert_cache": after[1],
    }


def bench_online_acceptance_drift_ab(gamma=4, B=8, prompt_len=64,
                                     new_tokens=48, page_size=16,
                                     eps=(0.005, 0.02, 0.08)):
    """--serve_online x --speculate_k: how fast online training strands
    a pinned drafter. The server self-drafts (drafter snapshot == the
    target at t=0, so greedy acceptance is 1.0 by construction), then
    the coordinator hot-swaps progressively perturbed target weights
    while the drafter keeps its pre-swap snapshot — the online loop's
    deployment shape, where the drafter is NOT retrained every swap.
    ``stats()['acceptance_rate_since_swap']`` (the window
    swap_base_params resets) is the drift signal: post-swap over
    pre-swap acceptance is the fraction of the speculative win each
    swap keeps before the drafter is refreshed.

    Dry-run runs the REAL counter-reset contract at tiny scale: a live
    self-drafting speculative server accumulates drafted_since_swap, a
    drained coordinator swap must zero the window (rate None, counts 0)
    while the lifetime totals survive, and post-swap traffic must
    re-accumulate into the fresh window.

    Returns (post-swap acceptance at the largest perturbation /
    pre-swap acceptance, breakdown with the per-eps trajectory)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.online import HotSwapCoordinator
    from commefficient_tpu.serving import (ContinuousBatchingServer,
                                           DecodeEngine)

    rng = np.random.RandomState(0)

    if DRY_RUN:
        V = 256
        model = GPT2DoubleHeads(GPT2Config.tiny(vocab_size=V))
        z = np.zeros((1, 1, 8), np.int32)
        params = model.init(jax.random.PRNGKey(0), z, z,
                            np.zeros((1, 1), np.int32),
                            train=False)["params"]
        engine = DecodeEngine(model, params, eos_id=V - 1, max_len=32,
                              method="greedy")
        srv = ContinuousBatchingServer(engine, slots=2, prefill_len=16,
                                       kv_cache="paged", page_size=8,
                                       speculate_k=2, drafter_model=model,
                                       drafter_params=params)
        coord = HotSwapCoordinator(srv, resubmit=True)

        def pump(n):
            for i in range(n):
                ids = rng.randint(0, V - 1, 6 + i).astype(
                    np.int32).tolist()
                srv.submit(ids, [1] * len(ids), 1, 8)
            srv.run()

        pump(3)
        st = srv.stats()
        assert st["drafted_since_swap"] > 0
        lifetime = st["drafted"]
        coord.swap(_perturbed_params(params, 0.05, 0))
        st = srv.stats()                        # the mark reset itself
        assert st["drafted_since_swap"] == 0
        assert st["accepted_since_swap"] == 0
        assert st["acceptance_rate_since_swap"] is None
        assert st["drafted"] == lifetime        # totals survive the swap
        pump(3)
        st = srv.stats()
        assert st["drafted_since_swap"] > 0     # fresh window fills
        return {"dry_run": "ok",
                "drafted_since_swap": st["drafted_since_swap"]}, {}

    P, N = prompt_len, new_tokens
    S = P + N
    V = 50262
    gcfg = GPT2Config.small(vocab_size=V)
    gcfg.n_positions = max(gcfg.n_positions, S)
    gcfg.dropout = 0.0
    gcfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(gcfg)
    key = jax.random.PRNGKey(0)
    sample_in = (jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32))
    params = model.init(key, *sample_in, train=False)["params"]
    engine = DecodeEngine(model, params, eos_id=V - 1, max_len=S,
                          method="greedy")
    srv = ContinuousBatchingServer(engine, slots=B, prefill_len=P,
                                   kv_cache="paged", page_size=page_size,
                                   speculate_k=gamma, drafter_model=model,
                                   drafter_params=params)
    coord = HotSwapCoordinator(srv, resubmit=True)

    def pump():
        for _ in range(2 * B):
            L = int(rng.randint(P // 2, P + 1))
            srv.submit(rng.randint(0, 50000, L).astype(np.int32).tolist(),
                       [1] * L, 1, N)
        srv.run()

    pump()
    acc0 = srv.stats()["acceptance_rate_since_swap"]
    breakdown = {"gamma": gamma, "slots": B, "eps": list(eps),
                 "acceptance_pre_swap": round(acc0, 4)}
    acc = acc0
    for k, e in enumerate(eps):
        # each arm perturbs the ORIGINAL snapshot by eps, so the
        # trajectory is drift-vs-distance, not compounding noise
        coord.swap(_perturbed_params(params, e, k))
        pump()
        acc = srv.stats()["acceptance_rate_since_swap"]
        breakdown[f"acceptance_since_swap_eps{e}"] = round(acc, 4)
    return round(acc / max(acc0, 1e-9), 4), breakdown


def _bench_rows():
    """Every bench row, as (name, zero-arg closure) pairs — the single
    registry both the timed JSON path and ``--dry-run`` iterate, so a row
    can't exist in one mode and silently be skipped by the other.
    Late-bound so monkeypatched bench_* fns (tests) are picked up."""
    return [
        ("cifar10_resnet9_fed_rounds_per_sec",
         lambda: bench_cifar_sketch()),
        ("cifar10_resnet9_fed_rounds_per_sec_exact_topk",
         lambda: bench_cifar_sketch(approx_recall=0.0)),
        ("gpt2_personachat_tokens_per_sec_chip",
         lambda: bench_gpt2_tokens()),
        ("gpt2_personachat_tokens_per_sec_chip_flash_attn",
         lambda: bench_gpt2_tokens(attn_impl="blockwise",
                                   attn_dropout="kernel")),
        ("gpt2_personachat_tokens_per_sec_chip_T512_flash_attn",
         lambda: bench_gpt2_tokens(attn_impl="blockwise", B=4, T=512,
                                   attn_dropout="kernel",
                                   per_dispatch=False)),
        ("flash_attn_t256_parity_dropout_kernel_ab",
         lambda: bench_flash_dropout_kernel_ab()),
        ("flash_attn_t512_parity_dropout_kernel_ab",
         lambda: bench_flash_dropout_kernel_ab(
             T=512, blocks=((512, 512), (512, 256), (256, 512),
                            (256, 256), (256, 128), (128, 128)))),
        ("gpt2_fused_ce_t512_ab",
         lambda: bench_gpt2_fused_ce_ab(T=512)),
        ("gpt2_fetchsgd_sketch_rounds_per_sec",
         lambda: bench_gpt2_sketch_rounds()),
        ("gpt2_fetchsgd_bucketed_rounds_t256_ab",
         lambda: bench_gpt2_bucketed_rounds(T=256)),
        ("gpt2_fetchsgd_bucketed_rounds_t512_ab",
         lambda: bench_gpt2_bucketed_rounds(T=512)),
        ("gpt2_fetchsgd_sketch_rounds_per_sec_exact_topk",
         lambda: bench_gpt2_sketch_rounds(approx_recall=0.0,
                                          per_dispatch=False)),
        ("gpt2_longcontext_4k_blockwise_tokens_per_sec_chip",
         lambda: bench_longcontext_tokens()),
        ("offload_gather_scatter_overlap",
         lambda: bench_offload_overlap()),
        ("client_store_gather_scatter_1m",
         lambda: bench_client_store_gather_scatter()),
        ("cifar10_resnet9_per_worker_sketch_ab",
         lambda: bench_per_worker_sketch_ab(d=6_570_240, W=8, r=5,
                                            c=500_000)),
        ("gpt2_fetchsgd_per_worker_sketch_ab",
         lambda: bench_per_worker_sketch_ab(d=124_440_576, W=4, r=5,
                                            c=500_000)),
        ("gpt2_server_update_fused_ab",
         lambda: bench_server_update_fused_ab()),
        ("topk_hierarchical_ab",
         lambda: bench_topk_hierarchical_ab()),
        ("client_store_sketched_codec",
         lambda: bench_client_store_sketched_codec()),
        ("buffered_fedbuff_round_overhead",
         lambda: bench_buffered_rounds()),
        ("buffered_mesh_round_overhead_ab",
         lambda: bench_buffered_mesh_rounds()),
        ("checkpoint_save_restore_overhead",
         lambda: bench_checkpoint_overhead()),
        ("gpt2_decode_tokens_per_sec_chip_b1",
         lambda: bench_generate(batch=1, ab_uncached=True)),
        ("gpt2_decode_tokens_per_sec_chip_b8",
         lambda: bench_generate(batch=8)),
        ("gpt2_decode_tokens_per_sec_chip_b64",
         lambda: bench_generate(batch=64)),
        ("gpt2_decode_paged_tokens_per_sec_ab",
         lambda: bench_decode_paged_ab()),
        ("gpt2_decode_paged_quant_ab",
         lambda: bench_decode_paged_quant_ab()),
        ("gpt2_decode_speculative_tokens_per_sec_ab",
         lambda: bench_decode_speculative_ab()),
        ("gpt2_decode_speculative_topk_stochastic_ab",
         lambda: bench_decode_speculative_ab(gammas=(0, 4), batches=(8,),
                                             method="topk")),
        ("gpt2_decode_speculative_personalized_ab",
         lambda: bench_decode_speculative_personalized()),
        ("serve_personalized_admission_overhead",
         lambda: bench_personalized_admission()),
        ("gpt2_decode_tp_tokens_per_sec_ab",
         lambda: bench_decode_tp_ab()),
        ("serve_disagg_decode_latency_ab",
         lambda: bench_serve_disagg_latency()),
        ("gpt2_online_swap_latency",
         lambda: bench_online_swap_latency()),
        ("gpt2_online_acceptance_drift_ab",
         lambda: bench_online_acceptance_drift_ab()),
    ]


#: ``--rows`` preset aliases: one name that expands to a curated
#: selector set. ``serving_column`` is the whole serving-stack column —
#: paged, quantized-paged, speculative (greedy + stochastic),
#: personalized — the rows docs/ROOFLINE.md's serving table reads from.
ROW_PRESETS = {
    "serving_column": ("gpt2_decode_tokens_per_sec_chip_*",
                       "*decode_paged*", "*speculative*",
                       "*personalized*", "*decode_tp*", "*disagg*",
                       "*online*"),
}


def _dry_run_main(row_filter=""):
    """``--dry-run``: build every (selected) row's real setup and trace
    its jitted programs without compiling or timing. Prints one status
    line per row; returns the number of rows that failed to trace."""
    import fnmatch
    global DRY_RUN
    DRY_RUN = True
    sel = [x for s in row_filter.split(",") if s
           for x in (ROW_PRESETS.get(s, (s,)))]

    def matches(name, s):
        # glob selectors ('*bucket*') when the pattern asks for them,
        # plain substring match otherwise — so both CI's quoted globs
        # and bare 'decode' keep working
        if any(ch in s for ch in "*?["):
            return fnmatch.fnmatch(name, s)
        return s in name

    failed = 0
    try:
        for name, fn in _bench_rows():
            if sel and not any(matches(name, s) for s in sel):
                continue
            t0 = time.perf_counter()
            try:
                fn()
                print(f"dry-run ok   {name} "
                      f"({time.perf_counter() - t0:.1f}s)")
            except Exception as exc:  # noqa: BLE001 — report every row
                failed += 1
                print(f"dry-run FAIL {name}: "
                      f"{type(exc).__name__}: {exc}")
    finally:
        DRY_RUN = False
    return failed


def main():
    from commefficient_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None,
                    help="directory for a jax.profiler trace of the bench")
    ap.add_argument("--dry-run", action="store_true",
                    help="build every row's setup and trace its jitted "
                         "programs (jax.eval_shape) without compiling or "
                         "timing; exits nonzero if any row fails to trace")
    ap.add_argument("--rows", action="append", default=None,
                    help="row selector (substring, glob, or a preset "
                         "alias like 'serving_column'); repeatable "
                         "and/or comma-separated (--dry-run only)")
    args = ap.parse_args()

    if args.dry_run:
        row_filter = ",".join(args.rows) if args.rows else ""
        raise SystemExit(1 if _dry_run_main(row_filter) else 0)

    from commefficient_tpu.utils.logging import profile_ctx

    errors = []

    with profile_ctx(args.profile):
        res = {name: _run_metric(name, fn, errors)
               for name, fn in _bench_rows()}
    cifar = res["cifar10_resnet9_fed_rounds_per_sec"]
    cifar_exact = res["cifar10_resnet9_fed_rounds_per_sec_exact_topk"]
    gpt2 = res["gpt2_personachat_tokens_per_sec_chip"]
    gpt2_flash = res["gpt2_personachat_tokens_per_sec_chip_flash_attn"]
    gpt2_flash_512 = res["gpt2_personachat_tokens_per_sec_chip_T512_flash_attn"]
    flash_ab = res["flash_attn_t256_parity_dropout_kernel_ab"]
    flash_ab_512 = res["flash_attn_t512_parity_dropout_kernel_ab"]
    fused_ce_ab = res["gpt2_fused_ce_t512_ab"]
    sketch = res["gpt2_fetchsgd_sketch_rounds_per_sec"]
    bucketed_256 = res["gpt2_fetchsgd_bucketed_rounds_t256_ab"]
    bucketed_512 = res["gpt2_fetchsgd_bucketed_rounds_t512_ab"]
    sketch_exact = res["gpt2_fetchsgd_sketch_rounds_per_sec_exact_topk"]
    longctx = res["gpt2_longcontext_4k_blockwise_tokens_per_sec_chip"]
    offload = res["offload_gather_scatter_overlap"]

    rounds_per_sec, breakdown = cifar if cifar is not None else (None, {})
    config = {"topk_approx_recall": breakdown.pop("topk_approx_recall")} \
        if "topk_approx_recall" in breakdown else {}
    if offload is not None:
        breakdown.update(offload)

    extras = []

    def add(metric, value, unit, config=None):
        if value is None:
            return
        entry = {"metric": metric, "value": value, "unit": unit}
        if config:
            entry["config"] = config
        extras.append(entry)

    add("cifar10_resnet9_fed_rounds_per_sec_exact_topk",
        round(cifar_exact[0], 4) if cifar_exact is not None else None,
        "rounds/sec", {"topk_approx_recall": 0.0})
    add("gpt2_personachat_tokens_per_sec_chip",
        round(gpt2[0], 1) if gpt2 is not None else None, "tokens/sec",
        {"note": "train_rounds_scan windows (K=12 rounds per dispatch, "
                 "one metric sync per window); reference-parity dropout "
                 "semantics (attn_pdrop on probabilities)"})
    add("gpt2_personachat_tokens_per_sec_chip_per_round_dispatch",
        round(gpt2[1], 1) if gpt2 is not None else None, "tokens/sec",
        {"note": "one host dispatch per round (rounds 1-3 measurement "
                 "mode)"})
    add("gpt2_personachat_tokens_per_sec_chip_flash_attn",
        round(gpt2_flash[0], 1) if gpt2_flash is not None else None,
        "tokens/sec",
        {"attn_impl": "blockwise", "attn_dropout": "kernel",
         "note": "in-kernel parity dropout (keep-bits from the core PRNG, "
                 "regenerated in backward) — no (T,T) scores or masks in "
                 "HBM; attn_dropout='kernel' raises rather than silently "
                 "falling back, so this row IS the fused path"})
    add("gpt2_personachat_tokens_per_sec_chip_T512_flash_attn",
        round(gpt2_flash_512[0], 1) if gpt2_flash_512 is not None else None,
        "tokens/sec",
        {"attn_impl": "blockwise", "attn_dropout": "kernel",
         "B": 4, "T": 512,
         "note": "long-context federated row (16384 tokens/round, same as "
                 "headline) at the T=512 crossover where ROOFLINE.md's "
                 "sweep shows blockwise beating full (79.9k vs 66.9k)"})
    add("flash_attn_t256_parity_dropout_kernel_ab",
        round(flash_ab[0], 4) if flash_ab is not None else None,
        "speedup_x",
        dict(flash_ab[1], **{
            "note": "fwd+bwd at R=64,H=12,D=64,T=256 bf16 rate=0.1: best "
                    "flash block config vs XLA full attention with rbg "
                    "prob dropout (the incumbent's exact math)"})
        if flash_ab is not None else None)
    add("flash_attn_t512_parity_dropout_kernel_ab",
        round(flash_ab_512[0], 4) if flash_ab_512 is not None else None,
        "speedup_x",
        dict(flash_ab_512[1], **{
            "note": "T=512 block-size re-tune sweep (up to the single-tile "
                    "512x512); the winner sets _gpt2_fed_setup's "
                    "attn_block_size pick for the T=512 federated rows"})
        if flash_ab_512 is not None else None)
    add("gpt2_fused_ce_t512_ab",
        round(fused_ce_ab[0], 4) if fused_ce_ab is not None else None,
        "speedup_x",
        dict(fused_ce_ab[1], **{
            "note": "fused head+CE vs materialized (B,C,T,V) logits inside "
                    "the federated round at B=4 T=512 — the measured basis "
                    "for --fused_ce auto"}) if fused_ce_ab is not None
        else None)
    add("gpt2_fetchsgd_sketch_rounds_per_sec",
        round(sketch[0], 4) if sketch is not None else None, "rounds/sec",
        {"topk_approx_recall": 0.95,
         "note": "train_rounds_scan windows (K=6)"})
    for label, bucketed in (("t256", bucketed_256), ("t512", bucketed_512)):
        add(f"gpt2_fetchsgd_bucketed_rounds_{label}_ab",
            round(bucketed[0], 4) if bucketed is not None else None,
            "speedup_x",
            dict(bucketed[1], **{
                "note": "sketch round with --grad_buckets K in {1,4,16} "
                        "(128-lane-aligned layer-grouped buckets, one "
                        "sketch/psum op per bucket); K=1 is the "
                        "trajectory-identical monolithic incumbent — "
                        "docs/ROOFLINE.md Round 7"})
            if bucketed is not None else None)
    add("gpt2_fetchsgd_sketch_rounds_per_sec_per_round_dispatch",
        round(sketch[1], 4) if sketch is not None else None, "rounds/sec",
        {"topk_approx_recall": 0.95,
         "note": "one host dispatch per round (rounds 1-3 measurement "
                 "mode)"})
    add("gpt2_fetchsgd_sketch_rounds_per_sec_exact_topk",
        round(sketch_exact[0], 4) if sketch_exact is not None else None,
        "rounds/sec", {"topk_approx_recall": 0.0})
    add("gpt2_longcontext_4k_blockwise_tokens_per_sec_chip",
        round(longctx, 1) if longctx is not None else None, "tokens/sec")
    cstore = res["client_store_gather_scatter_1m"]
    add("client_store_gather_scatter_1m",
        cstore.get("gather_ms_1m") if cstore is not None else None, "ms",
        dict(cstore, **{
            "note": "per-round host gather time at num_clients=1e6 with "
                    "sparse O(k) host arenas (client_store.py); "
                    "gather/scatter cost tracks cohort width W, arena "
                    "bytes track n*k — full breakdown at both 1e4 and "
                    "1e6 in config"}) if cstore is not None else None)
    for label, dims in (("cifar10_resnet9", "d=6.57M W=8 r=5 c=500k"),
                        ("gpt2_fetchsgd", "d=124.4M W=4 r=5 c=500k")):
        pw = res[f"{label}_per_worker_sketch_ab"]
        add(f"{label}_per_worker_sketch_ab",
            round(pw[0], 4) if pw is not None else None, "speedup_x",
            dict(pw[1], **{
                "note": f"BENCH_r08: W vmapped per-worker sketches "
                        f"({dims}) on the batched 2-D grid Pallas kernel "
                        f"vs the forced XLA fallback — same chip, "
                        f"back-to-back, tables checked bitwise-equal; "
                        f"refutation budgeted (a ratio < 1 is the "
                        f"measured answer)"}) if pw is not None else None)
    srv_fused_ab = res["gpt2_server_update_fused_ab"]
    add("gpt2_server_update_fused_ab",
        round(srv_fused_ab[0], 4) if srv_fused_ab is not None else None,
        "speedup_x",
        dict(srv_fused_ab[1], **{
            "note": "BENCH_r09: fused server update (--server_fused "
                    "auto — streaming radix top-k + unsketch/momentum/"
                    "error-feedback epilogue) vs the incumbent chain at "
                    "gpt2 scale, true_topk AND sketch modes, updates and "
                    "state bitwise-checked between arms; headline is the "
                    "sketch-mode ratio, refutation budgeted (ratio < 1 "
                    "is the measured answer) — docs/ROOFLINE.md Round 9"})
        if srv_fused_ab is not None else None)
    topk_ab = res["topk_hierarchical_ab"]
    add("topk_hierarchical_ab",
        round(topk_ab[0], 4) if topk_ab is not None else None,
        "speedup_x",
        dict(topk_ab[1], **{
            "note": "BENCH_r09: streaming two-pass radix top-k kernel vs "
                    "jax.lax.top_k masking through the public dispatch, "
                    "d=124.4M, k swept {5k, 50k, 500k}, outputs bitwise-"
                    "checked per k; headline is the paper operating "
                    "point k=50k"}) if topk_ab is not None else None)
    codec_ab = res["client_store_sketched_codec"]
    add("client_store_sketched_codec",
        round(codec_ab[0], 4) if codec_ab is not None else None,
        "speedup_x",
        dict(codec_ab[1], **{
            "note": "BENCH_r08: sketched client-state codec encode+decode, "
                    "'global' (incumbent) vs 'tiled' (batched-kernel-"
                    "eligible) scheme — PR 11's 'tiled buys nothing' claim "
                    "measured; refutation budgeted, 'global' stays default "
                    "unless tiled wins"}) if codec_ab is not None else None)
    ckpt = res["checkpoint_save_restore_overhead"]
    add("checkpoint_save_restore_overhead",
        ckpt["save_ms"] if ckpt is not None else None, "ms",
        dict(ckpt, **{
            "note": "crash-consistent v3 checkpoint of the gpt2-small "
                    "federated learner: atomic save / digest verify / "
                    "transactional load, with the per-round amortization "
                    "at --checkpoint_every_rounds=100"})
        if ckpt is not None else None)
    bmesh_ab = res["buffered_mesh_round_overhead_ab"]
    add("buffered_mesh_round_overhead_ab",
        round(bmesh_ab[0], 4) if bmesh_ab is not None else None,
        "time_ratio_x",
        dict(bmesh_ab[1], **{
            "note": "buffered lock-step round on the dp-way 'clients' "
                    "mesh vs single-chip, same config (bitwise at α=0 — "
                    "tests/test_buffered_mesh.py); ~flat by design, the "
                    "win is the sharded slot buffer (no replicated (M, d) "
                    "slab — buffered_mesh audit); the faulted arm prices "
                    "the event loop + heterogeneous per-client k"})
        if bmesh_ab is not None else None)
    for bsz in (1, 8, 64):
        dec = res[f"gpt2_decode_tokens_per_sec_chip_b{bsz}"]
        add(f"gpt2_decode_tokens_per_sec_chip_b{bsz}",
            round(dec[0], 1) if dec is not None else None, "tokens/sec",
            dict(dec[1], **{
                "note": "KV-cached jitted decode (prefill + scanned "
                        "single-query steps, sampling in-program); "
                        "decode-phase throughput, prefill reported in "
                        "the breakdown"}) if dec is not None else None)

    paged_ab = res["gpt2_decode_paged_tokens_per_sec_ab"]
    add("gpt2_decode_paged_tokens_per_sec_ab",
        round(paged_ab[0], 4) if paged_ab is not None else None,
        "speedup_x",
        dict(paged_ab[1], **{
            "note": "continuous-batching server, block-paged KV pools + "
                    "traced page table vs the dense (slots, max_len) "
                    "slab, same request stream; throughput is ~flat by "
                    "design — the users_per_chip_at_fixed_hbm_x entries "
                    "are the capacity win (ROADMAP item 1)"})
        if paged_ab is not None else None)
    quant_ab = res["gpt2_decode_paged_quant_ab"]
    add("gpt2_decode_paged_quant_ab",
        round(quant_ab[0], 4) if quant_ab is not None else None,
        "speedup_x",
        dict(quant_ab[1], **{
            "note": "--kv_quant int8 vs none on the paged server, same "
                    "request stream; throughput ~flat by design (dequant "
                    "only on gathered pages, the pool stays int8 — the "
                    "decode_paged_quant audit pins it), the "
                    "kv_capacity_multiplier_vs_f32 and "
                    "users_per_chip_at_fixed_hbm_x entries are the win"})
        if quant_ab is not None else None)
    spec_ab = res["gpt2_decode_speculative_tokens_per_sec_ab"]
    add("gpt2_decode_speculative_tokens_per_sec_ab",
        round(spec_ab[0], 4) if spec_ab is not None else None,
        "speedup_x",
        dict(spec_ab[1], **{
            "note": "--speculate_k over the paged server: γ tiny-drafter "
                    "tokens + one multi-token verify vs γ=0, same greedy "
                    "stream (bitwise — tests/test_speculative.py); the "
                    "random drafter prices the mechanism, acceptance "
                    "rates say what a distilled drafter must hit, the "
                    "selfdraft arm is the ceiling; refutation at any γ "
                    "is the measured answer"})
        if spec_ab is not None else None)
    spec_topk = res["gpt2_decode_speculative_topk_stochastic_ab"]
    add("gpt2_decode_speculative_topk_stochastic_ab",
        round(spec_topk[0], 4) if spec_topk is not None else None,
        "speedup_x",
        dict(spec_topk[1], **{
            "note": "--speculate_k + --serve_sample topk: stochastic "
                    "acceptance (accept w.p. min(1, q/p), residual "
                    "resample) over the paged server vs the "
                    "non-speculative topk stream — marginals match by "
                    "the residual-rule theorem "
                    "(tests/test_speculative.py), this row only times"})
        if spec_topk is not None else None)
    spec_pers = res["gpt2_decode_speculative_personalized_ab"]
    add("gpt2_decode_speculative_personalized_ab",
        round(spec_pers[0], 4) if spec_pers is not None else None,
        "speedup_x",
        dict(spec_pers[1], **{
            "note": "--speculate_k + --serve_personalized: base-weights "
                    "drafter (free — the per-user delta is O(k) and "
                    "admit never mutates the snapshot) vs plain "
                    "personalized serving; base_drafter_acceptance_rate "
                    "measures how far k-sparse deltas move the argmax "
                    "stream"})
        if spec_pers is not None else None)
    tp_ab = res["gpt2_decode_tp_tokens_per_sec_ab"]
    add("gpt2_decode_tp_tokens_per_sec_ab",
        round(tp_ab[0], 4) if tp_ab is not None else None,
        "speedup_x",
        dict(tp_ab[1], **{
            "note": "--serve_tp 2: head-sharded Megatron engine + "
                    "per-shard page pools vs the replicated engine, same "
                    "greedy stream; tokens/s ~flat on one host by design "
                    "— the users_per_fleet_at_fixed_hbm_x entries are "
                    "the capacity win (each shard holds 1/tp of the "
                    "pool HBM; greedy parity pinned token-identical by "
                    "dryrun_multichip)"})
        if tp_ab is not None else None)
    disagg_ab = res["serve_disagg_decode_latency_ab"]
    add("serve_disagg_decode_latency_ab",
        round(disagg_ab[0], 4) if disagg_ab is not None else None,
        "speedup_x",
        dict(disagg_ab[1], **{
            "note": "--serve_disagg: decode pool steps first, admissions "
                    "budgeted at prefill_slots per step vs unified "
                    "admit-everything-then-step, same stream + prefill "
                    "burst; the ratio is unified p99 step latency over "
                    "disagg p99 (>1 = the burst no longer stalls "
                    "in-flight decodes)"})
        if disagg_ab is not None else None)
    pers = res["serve_personalized_admission_overhead"]
    add("serve_personalized_admission_overhead",
        pers["admission_delta_apply_ms"] if pers is not None else None,
        "ms",
        dict(pers, **{
            "note": "--serve_personalized: O(k) sparse weight delta "
                    "applied at slot admission from the client state "
                    "store's row (k nonzeros over gpt2-small's d=124M), "
                    "priced against the B=1 prefill admission already "
                    "pays; eviction restores base bitwise"})
        if pers is not None else None)
    oswap = res["gpt2_online_swap_latency"]
    add("gpt2_online_swap_latency",
        round(oswap[0], 2) if oswap is not None else None, "ms",
        dict(oswap[1], **{
            "note": "--serve_online hot swap: drain the in-flight slots "
                    "to completion, place fresh gpt2-small weights onto "
                    "the old leaves' shardings, resubmit the queue "
                    "verbatim, first post-swap step — median "
                    "swap-to-serving wall time; the paged step/pack "
                    "compile caches are asserted flat across every swap "
                    "(the online_loop audit pins the same invariant)"})
        if oswap is not None else None)
    odrift = res["gpt2_online_acceptance_drift_ab"]
    add("gpt2_online_acceptance_drift_ab",
        round(odrift[0], 4) if odrift is not None else None, "ratio",
        dict(odrift[1], **{
            "note": "--serve_online x --speculate_k: the self-drafting "
                    "acceptance window (acceptance_rate_since_swap, "
                    "reset by swap_base_params) before vs after "
                    "hot-swapping perturbed target weights over a "
                    "pinned drafter snapshot — the per-swap cost of NOT "
                    "retraining the drafter, the signal the online loop "
                    "would key a drafter refresh on"})
        if odrift is not None else None)

    # always ONE JSON line — partial numbers beat no artifact — but a
    # metric that raised makes the run a failure (non-zero exit below)
    print(json.dumps({
        "metric": "cifar10_resnet9_fed_rounds_per_sec",
        "value": round(rounds_per_sec, 4) if rounds_per_sec is not None
        else None,
        "unit": "rounds/sec",
        "vs_baseline": 1.0,
        "config": config,
        "extra_metrics": extras,
        "breakdown_ms": breakdown,
        "errors": errors,
    }))
    if errors:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
