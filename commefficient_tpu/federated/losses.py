"""Standard loss callables matching the entrypoints' losses.

Contract (see client.py): apply_loss(params, batch_tuple, rng, train)
-> (per_example_loss (B,), per_example_metrics (M, B)).

Reference equivalents: compute_loss_ce / Correct metric
(reference cv_train.py:32-83) and the GPT2 LM+MC loss
(reference gpt2_train.py:77-99).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def make_cv_loss(model):
    """Cross-entropy + top-1 correctness for image classifiers."""

    def apply_loss(params, batch, rng, train):
        images, targets = batch
        logits = model.apply({"params": params}, images, train=train,
                             rngs={"dropout": rng} if train else None)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
        correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        return loss, correct[None, :]

    return apply_loss


def shift_labels(lm_labels):
    """Next-token targets: shifted[t] = labels[t+1], final position -1
    (ignored). The ONE shift convention shared by the dense losses here
    and the sequence-parallel losses (parallel/seq.py)."""
    return jnp.concatenate(
        [lm_labels[..., 1:], jnp.full_like(lm_labels[..., :1], -1)],
        axis=-1)


def _lm_nll_sums(lm_logits, lm_labels):
    """(nll token-sum, labeled-token count) per dialog over shifted
    positions with label != -1 (ref CrossEntropyLoss(ignore_index=-1),
    gpt2_train.py:77-87).

    The shift is applied to the LABELS (``shift_labels``) rather than
    slicing ``lm_logits[..., :-1, :]``: slicing the (.., T, V) logits
    costs a full-tensor copy forward and — worse — XLA materializes the
    sliced gradient back to (.., T, V) with a 3.3 GB `pad` in the
    backward (round-4 HLO audit). Shifting the tiny int32 labels instead
    is mathematically identical: position T-1 gets label -1 and is
    masked like any other ignored position, so its dlogits row is
    exactly zero.
    """
    labels = shift_labels(lm_labels)
    valid = labels != -1
    safe = jnp.where(valid, labels, 0)
    nll = optax.softmax_cross_entropy_with_integer_labels(lm_logits, safe)
    nll = jnp.where(valid, nll, 0.0)
    return (jnp.sum(nll, axis=(-2, -1)),
            jnp.sum(valid, axis=(-2, -1)).astype(jnp.float32))


def _lm_nll_per_example(lm_logits, lm_labels):
    """Mean shifted cross-entropy over labeled positions, per dialog.

    Per-example averaging makes the loss a (B,) vector for the masked
    federated round, with each dialog weighted equally (documented
    divergence: the reference's global mean weights dialogs by their token
    counts; the val path recovers that exactly from _lm_nll_sums).
    """
    nll_sum, tokens = _lm_nll_sums(lm_logits, lm_labels)
    return nll_sum / jnp.maximum(tokens, 1.0)


def _fused_lm_head(model) -> bool:
    return bool(getattr(getattr(model, "config", None),
                        "fused_lm_head", False))


def _fused_nll_sums(model, hidden, params, lm_labels):
    """(nll token-sum, labeled-token count) per dialog from HIDDEN states
    via the vocab-chunked fused head+CE (ops/fused_ce.py) — used when the
    model was built with ``fused_lm_head=True`` and returns hidden states
    instead of logits. Sums over the candidate axis to match
    ``_lm_nll_sums``'s (B,) contract. The head matmul runs in the model's
    configured compute dtype (f32 config => 1e-6-exact vs the
    materialized-logits path, bf16 config => the same bf16-input matmuls
    the rest of the model runs)."""
    from commefficient_tpu.ops.fused_ce import shifted_lm_nll
    wte = params["wte"]["embedding"]
    nll_sum, tokens = shifted_lm_nll(hidden, wte, lm_labels,
                                     compute_dtype=model.config.jnp_dtype)
    return jnp.sum(nll_sum, axis=-1), jnp.sum(tokens, axis=-1)


def make_gpt2_train_loss(model, lm_coef: float = 1.0, mc_coef: float = 1.0,
                         moe_aux_weight: float = 1e-2):
    """LM + multiple-choice loss (reference compute_loss_train,
    gpt2_train.py:88-99). With an MoE-configured model
    (config.moe_experts > 0) the Switch load-balancing auxiliary loss —
    sown per block (ops/moe.py) — is averaged over layers and added at
    ``moe_aux_weight``; without it, routing collapses onto one expert."""
    fused = _fused_lm_head(model)
    moe = getattr(getattr(model, "config", None), "moe_experts", 0) > 0

    def apply_loss(params, batch, rng, train):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        rngs = {"dropout": rng} if train else None
        if moe:
            (lm_out, mc_logits), inter = model.apply(
                {"params": params}, input_ids, token_type_ids,
                mc_token_ids, train=train, rngs=rngs,
                mutable=["intermediates"])
            # select ONLY the moe_aux_loss sows by key path: any other
            # sown intermediate (a metric, a debug stat) must not leak
            # into the objective (code review r5)
            aux_leaves = [
                leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(
                    inter["intermediates"])[0]
                if any("moe_aux_loss" in getattr(p, "key", str(p))
                       for p in path)]
            aux = sum(aux_leaves) / max(len(aux_leaves), 1)
        else:
            lm_out, mc_logits = model.apply(
                {"params": params}, input_ids, token_type_ids,
                mc_token_ids, train=train, rngs=rngs)
        if fused:
            nll_sum, tokens = _fused_nll_sums(model, lm_out, params,
                                              lm_labels)
            lm_loss = nll_sum / jnp.maximum(tokens, 1.0)
        else:
            lm_loss = _lm_nll_per_example(lm_out, lm_labels)
        mc_loss = optax.softmax_cross_entropy_with_integer_labels(
            mc_logits, mc_labels)
        loss = lm_coef * lm_loss + mc_coef * mc_loss
        if moe:
            # scalar aux added to every per-example entry: the masked
            # round's datapoint-weighted mean then recovers exactly
            # moe_aux_weight * aux
            loss = loss + moe_aux_weight * aux
        return loss, jnp.zeros((1, loss.shape[0]))

    return apply_loss


def make_gpt2_val_loss(model):
    """NLL + multiple-choice accuracy (reference compute_loss_val,
    gpt2_train.py:77-87); perplexity = exp(mean nll) at rollup
    (ref test_gpt2 :149-167).

    Metric rows: [mc accuracy, nll token-sum, labeled-token count]. The
    last two let the rollup recover the reference's exact token-weighted
    nll (CrossEntropyLoss(ignore_index=-1) over the flat batch) as
    sum(nll_sums)/sum(token_counts) — the per-example loss channel remains
    dialog-weighted for the masked federated plumbing."""

    fused = _fused_lm_head(model)

    def apply_loss(params, batch, rng, train):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        lm_out, mc_logits = model.apply(
            {"params": params}, input_ids, token_type_ids, mc_token_ids,
            train=False)
        if fused:
            nll_sum, tokens = _fused_nll_sums(model, lm_out, params,
                                              lm_labels)
        else:
            nll_sum, tokens = _lm_nll_sums(lm_out, lm_labels)
        acc = (jnp.argmax(mc_logits, -1) == mc_labels).astype(jnp.float32)
        return (nll_sum / jnp.maximum(tokens, 1.0),
                jnp.stack([acc, nll_sum, tokens]))

    return apply_loss


def make_lm_loss(model, train: bool, chunk: int = 8192):
    """Next-token loss of a language model with no other head
    (``models/nemotron_h.py``, ``models/ouro.py``): the untied head is
    ``params['lm_head_embedding']`` (V, C) and is applied vocabulary-chunk
    by chunk (``ops/fused_ce.py``; the logits are never whole). Batch:
    ``(tokens (B, T), labels (B, T))``, labels already the next token, -1
    ignored. Per-example loss: the mean over a sequence's labelled positions.

    ``model.apply`` returns the final hidden states (B, T, C), and a token's
    loss is its cross-entropy; or, for a model with ``exit_loss`` (more than
    one exit), ``(hidden states (exits, B, T, C), gates (exits, B, T))``:
    the head runs once an exit and ``model.exit_loss`` combines the
    cross-entropies. Validation reads the last exit alone.

    Metric rows. Training: what the model declares, ``model.train_counters``
    ({counter name: key}), each summed by sequence from what the layers sow
    a token under that key (``ops/moe.py``) or from what ``exit_loss``
    returns under it, so they ride to the host with the loss;
    ``apply_loss.counters`` names them for ``training/gpt2.py``. Validation:
    the rows ``make_gpt2_val_loss`` gives ([0, nll token-sum, labelled
    tokens])."""
    from commefficient_tpu.ops.fused_ce import lm_head_nll
    from commefficient_tpu.utils.tracing import layer
    cd = model.config.jnp_dtype
    counters = dict(getattr(model, "train_counters", {}))
    exit_loss = getattr(model, "exit_loss", None)

    def sown(inter, key, B):
        leaves = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(inter)[0]
                  if any(getattr(p, "key", None) == key for p in path)]
        return sum((leaf.reshape(B, -1).sum(axis=-1) for leaf in leaves),
                   jnp.zeros((B,), jnp.float32))

    def apply_loss(params, batch, rng, train_flag):
        tokens, labels = batch
        B = tokens.shape[0]
        out, inter = model.apply({"params": params}, tokens,
                                 mutable=["intermediates"])
        valid = labels >= 0
        wte = params["lm_head_embedding"]
        targets = jnp.where(valid, labels, 0).reshape(-1)

        def head(h):
            with layer("lm_head"):
                return lm_head_nll(h.reshape(-1, h.shape[-1]), wte, targets,
                                   min(chunk, wte.shape[0]),
                                   cd).reshape(labels.shape)

        rows = {}
        if exit_loss is None:
            token_loss = head(out)
        elif train:
            hiddens, gates = out
            token_loss, rows = exit_loss(
                jnp.stack([head(h) for h in hiddens]), gates, valid)
        else:
            token_loss = head(out[0][-1])
        loss_sum = jnp.sum(jnp.where(valid, token_loss, 0.0), axis=-1)
        count = jnp.sum(valid, axis=-1).astype(jnp.float32)
        loss = loss_sum / jnp.maximum(count, 1.0)
        if not train:
            return loss, jnp.stack([jnp.zeros_like(loss), loss_sum, count])
        inter = inter.get("intermediates", {})
        return loss, jnp.stack([rows[key] if key in rows
                                else sown(inter, key, B)
                                for key in counters.values()])

    if train:
        apply_loss.counters = tuple(counters)
    return apply_loss


def make_regression_loss(model):
    """Squared error, for the golden-value toy problems."""

    def apply_loss(params, batch, rng, train):
        x, y = batch
        pred = model.apply({"params": params}, x, train=train)
        loss = jnp.sum((pred - y) ** 2, axis=-1)
        return loss, jnp.zeros((1, loss.shape[0]))

    return apply_loss
