"""Sequence-parallel (ring attention) application of GPT2DoubleHeads.

The reference has no sequence parallelism (SURVEY.md §2: absent); here
long-context is first-class: a GPT2 configured with ``attn_impl='ring'``
runs its whole transformer trunk inside ``shard_map`` with the sequence
dimension sharded over the mesh's ``seq`` axis. Attention keys/values
rotate the ring via ``ppermute`` (ops/attention.py), positions and the
MC-head pick use global offsets (models/gpt2.py), so the result matches
the unsharded model to float tolerance — tested on an 8-device CPU mesh
in tests/test_attention.py.

Scaling story: per-device activation memory falls as T/n_seq, enabling
contexts n_seq times longer than one chip's HBM allows; ring traffic rides
ICI neighbor links and overlaps with per-block attention compute.

Note on dropout: each shard folds its mesh position into the dropout rng
(``_shard_rngs``), so masks are independent across sequence and
data-parallel shards — the same distribution the unsharded model draws
(every position's keep-bit is iid Bernoulli; only the realization
differs). Without the fold, all shards would reuse one mask pattern —
correlated regularization noise across shard boundaries.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _shard_rngs(rngs, *axis_names):
    """Fold this device's mesh position into every rng so stochastic ops
    (dropout) decorrelate across shards; call INSIDE shard_map."""
    if rngs is None:
        return None
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return {k: jax.random.fold_in(v, idx) for k, v in rngs.items()}


def seq_parallel_apply(mesh, model, params, input_ids, token_type_ids,
                       mc_token_ids, *, train: bool = False, rngs=None,
                       axis_name: str = "seq"):
    """Apply a ring-attention GPT2DoubleHeads with T sharded on ``axis_name``.

    Args are global: input_ids/token_type_ids (B, C, T) with T divisible by
    the mesh's seq-axis size; mc_token_ids (B, C) hold GLOBAL token
    positions. Returns (lm_logits (B, C, T, V) sharded on T, mc_logits
    (B, C) replicated).
    """
    if model.config.attn_impl != "ring":
        raise ValueError("seq_parallel_apply requires attn_impl='ring' "
                         f"(got {model.config.attn_impl!r})")
    n_seq = mesh.shape[axis_name]
    T = input_ids.shape[-1]
    if T % n_seq:
        raise ValueError(f"sequence length {T} not divisible by seq axis "
                         f"size {n_seq}")

    ids_spec = P(None, None, axis_name)
    rep = P()

    @partial(shard_map, mesh=mesh,
             in_specs=(ids_spec, ids_spec, rep),
             out_specs=(P(None, None, axis_name, None), rep),
             check_vma=False)
    def run(ids, types, mc_ids):
        return model.apply({"params": params}, ids, types, mc_ids,
                           train=train, rngs=_shard_rngs(rngs, axis_name))

    return run(input_ids, token_type_ids, mc_token_ids)


def _shift_labels(lm_labels):
    """Pre-shift next-token labels at GLOBAL shape so the shard-local CE
    never pairs a logit with a label owned by the next sequence shard:
    the shared ``losses.shift_labels`` convention (which the dense
    ``_lm_nll_sums`` also applies — both paths pair logits 0..T-1 with
    shifted labels)."""
    from commefficient_tpu.federated.losses import shift_labels
    return shift_labels(lm_labels)


def _shift_labels_halo(labs, axis_name: str):
    """``losses.shift_labels`` applied INSIDE shard_map on a (.., T_loc)
    sequence shard: shifted[t] = labels[t+1] at GLOBAL position, so each
    shard's final column is the NEXT shard's first column (one-hop
    ppermute halo) and the last shard pads -1 (ppermute leaves
    non-receiving shards zero-filled, so the -1 is written explicitly)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    head = labs[..., :1]
    nxt = jax.lax.ppermute(head, axis_name,
                           [(i, i - 1) for i in range(1, n)])
    nxt = jnp.where(my == n - 1, jnp.full_like(nxt, -1), nxt)
    return jnp.concatenate([labs[..., 1:], nxt], axis=-1)


def make_gpt2_train_loss_seq(mesh, model, lm_coef: float = 1.0,
                             mc_coef: float = 1.0, dp_axis: str = "clients",
                             axis_name: str = "seq"):
    """Sequence-parallel GPT2 LM+MC federated loss (same contract as
    losses.make_gpt2_train_loss): batch rows shard over ``dp_axis``, the
    sequence over ``axis_name`` with ring attention inside, per-example
    sums psum over the seq axis. This is how ``--mesh clients=N,seq=M``
    composes with the federated round: the round's fused-clients path calls
    this loss ONCE on the flattened (W*B, C, T) batch (round.py
    fused_clients), so the shard_map nests under jit, not under vmap —
    modes needing per-worker state are rejected at the entrypoint.

    Gradients flow through shard_map's transpose: the replicated params
    input (P()) makes the backward psum over both axes automatic —
    equivalence with the unsharded trajectory is asserted in
    tests/test_cli_mesh.py.
    """
    if model.config.attn_impl != "ring":
        raise ValueError("seq federated loss requires attn_impl='ring'")

    def apply_loss(params, batch, rng, train):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        shifted = _shift_labels(lm_labels)
        data_spec = P(dp_axis, None, axis_name)
        row_spec = P(dp_axis)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), data_spec, data_spec, data_spec,
                           P(dp_axis, None), row_spec, P()),
                 out_specs=(row_spec, P(None, dp_axis)),
                 check_vma=False)
        def run(p, ids, types, slabs, mc_ids, mc_labs, key):
            rngs = (_shard_rngs({"dropout": key}, dp_axis, axis_name)
                    if train else None)
            lm, mc = model.apply({"params": p}, ids, types, mc_ids,
                                 train=train, rngs=rngs)
            import optax
            valid = slabs != -1
            safe = jnp.where(valid, slabs, 0)
            nll = optax.softmax_cross_entropy_with_integer_labels(
                lm.astype(jnp.float32), safe)
            nll = jnp.where(valid, nll, 0.0)
            nll_sum = jax.lax.psum(jnp.sum(nll, axis=(-2, -1)), axis_name)
            tokens = jax.lax.psum(
                jnp.sum(valid, axis=(-2, -1)).astype(jnp.float32), axis_name)
            lm_loss = nll_sum / jnp.maximum(tokens, 1.0)
            # mc logits are already replicated over seq (the model psums
            # the picked hidden state, models/gpt2.py)
            mc_loss = optax.softmax_cross_entropy_with_integer_labels(
                mc, mc_labs)
            loss = lm_coef * lm_loss + mc_coef * mc_loss
            return loss, jnp.zeros((1, loss.shape[0]))

        return run(params, input_ids, token_type_ids, shifted,
                   mc_token_ids, mc_labels, rng)

    return apply_loss


def make_gpt2_val_loss_seq(mesh, model, axis_name: str = "seq"):
    """Sequence-parallel twin of losses.make_gpt2_val_loss: only T shards
    (eval batches are arbitrary-sized, so rows replicate); metric rows stay
    [mc acc, nll token-sum, token count] for the exact token-weighted
    rollup."""
    if model.config.attn_impl != "ring":
        raise ValueError("seq federated loss requires attn_impl='ring'")

    def apply_loss(params, batch, rng, train):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        data_spec = P(None, None, axis_name)

        # The labels enter RAW and shift inside the shard_map (ppermute
        # halo) instead of pre-shifting at global shape like the train
        # loss: here the batch dim replicates over the dp axis, and on
        # jax<0.5 a value COMPUTED in-trace that must replicate over an
        # unused mesh axis on entry to shard_map is mis-lowered as a
        # partial sum — each device's copy gets added, labels land out of
        # vocab range, and the CE goes NaN. Raw jit inputs reshard
        # correctly; the halo keeps the shift convention exact across
        # shard boundaries.
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), data_spec, data_spec, data_spec, P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, ids, types, labs, mc_ids, mc_labs):
            import optax
            lm, mc = model.apply({"params": p}, ids, types, mc_ids,
                                 train=False)
            slabs = _shift_labels_halo(labs, axis_name)
            valid = slabs != -1
            safe = jnp.where(valid, slabs, 0)
            nll = optax.softmax_cross_entropy_with_integer_labels(
                lm.astype(jnp.float32), safe)
            nll = jnp.where(valid, nll, 0.0)
            nll_sum = jax.lax.psum(jnp.sum(nll, axis=(-2, -1)), axis_name)
            tokens = jax.lax.psum(
                jnp.sum(valid, axis=(-2, -1)).astype(jnp.float32), axis_name)
            acc = (jnp.argmax(mc, -1) == mc_labs).astype(jnp.float32)
            return (nll_sum / jnp.maximum(tokens, 1.0),
                    jnp.stack([acc, nll_sum, tokens]))

        return run(params, input_ids, token_type_ids, lm_labels,
                   mc_token_ids, mc_labels)

    return apply_loss


def seq_dp_lm_train_step(mesh, model, params, input_ids, token_type_ids,
                         labels, *, dp_axis: str = "clients",
                         axis_name: str = "seq", train: bool = False,
                         rngs=None):
    """One data+sequence-parallel LM training step on a 2D mesh.

    The composition the round engine uses for federated CV scaled to
    long-context NLP: batch rows shard over ``dp_axis``, the sequence
    shards over ``axis_name`` (ring attention inside the model), and
    parameter gradients psum over BOTH axes — dp and sp in one SPMD
    program, no pipeline stages or parameter servers.

    Args are global: input_ids/token_type_ids/labels (B, C, T); B must
    divide by the dp axis, T by the seq axis. ``labels`` use -1 for
    positions that don't contribute (the caller pre-shifts next-token
    targets so shard boundaries are correct: labels[t] = ids[t+1]).
    Returns (mean nll over labeled tokens, grads pytree) — both
    replicated.

    ``train=True`` enables dropout (pass ``rngs={'dropout': key}``); each
    shard folds its (dp, seq) mesh position into the key (``_shard_rngs``),
    so masks are independent across both axes — the distribution the
    unsharded model draws. Default is eval-mode gradients (exact,
    dropout-free).
    """
    if model.config.attn_impl != "ring":
        raise ValueError("seq_dp_lm_train_step requires attn_impl='ring'")
    B, C, T = input_ids.shape
    if B % mesh.shape[dp_axis] or T % mesh.shape[axis_name]:
        raise ValueError(
            f"batch {B} / seq {T} not divisible by mesh axes "
            f"({mesh.shape[dp_axis]}, {mesh.shape[axis_name]})")
    data_spec = P(dp_axis, None, axis_name)
    mc_dummy = jnp.zeros((B, C), jnp.int32)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), data_spec, data_spec, data_spec,
                       P(dp_axis, None)),
             out_specs=(P(), P()), check_vma=False)
    def step(p, ids, types, labs, mc):
        local_rngs = _shard_rngs(rngs, dp_axis, axis_name)

        def local_loss(p):
            lm, _ = model.apply({"params": p}, ids, types, mc,
                                train=train, rngs=local_rngs)
            lp = jax.nn.log_softmax(lm.astype(jnp.float32), axis=-1)
            valid = labs >= 0
            tgt = jnp.where(valid, labs, 0)
            nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
            return jnp.sum(nll * valid), jnp.sum(valid.astype(jnp.float32))

        (loss_sum, n), grads = jax.value_and_grad(
            local_loss, has_aux=True)(p)
        total = jnp.maximum(jax.lax.psum(n, (dp_axis, axis_name)), 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, (dp_axis, axis_name)) / total, grads)
        loss = jax.lax.psum(loss_sum, (dp_axis, axis_name)) / total
        return loss, grads

    return step(params, input_ids, token_type_ids, labels, mc_dummy)
