"""Sparse expert feed-forward layer: a router over all experts, the product
over the experts held here, nothing dropped.

One layer serves both users:

* GPT2's ``--moe_experts`` blocks (``models/gpt2.py``): Switch routing —
  softmax scores, the single best expert, its score as the gate, GELU
  experts with biases, every expert held, and the load-balancing auxiliary
  loss sown as ``moe_aux_loss`` (``federated/losses.py`` adds it).
* the hybrid model's ``E`` layers (``models/nemotron_h.py``): sigmoid scores
  over ``num_experts``, the ``top_k`` largest of score + a correction bias
  (the bias chooses, the plain score weighs), gates renormalised to sum 1
  and scaled, squared-ReLU experts without biases, a shared expert every
  token passes through, and only ``experts_held`` of the experts on this
  chip: the layer returns the part of the result its own experts give
  (what expert parallelism asks of a chip; the exchange is not here).

How it computes. Every (token, chosen expert) pair whose expert is held is
an *assignment*. Assignments are sorted by expert, the tokens gathered in
that order, and each expert multiplies its own contiguous run of rows
(``lax.ragged_dot``: a grouped product; on a TPU one Mosaic kernel). No
capacity: a run is as long as the routing made it. The static bound on the
rows is N * top_k, far above what a chip that holds 8 of 128 experts
expects, so the sorted rows are worked through in blocks of
``rows_per_block`` and a block past the last assignment is skipped
(``lax.cond``): the cost follows the load, the worst case still fits.
Each later block is rematerialised on its own, so the backward pass holds
one block's rows at a time.

What the layer reports (``sow('intermediates', ...)``, per token, so a
loss can sum them by example): ``moe_held`` assignments on held experts,
``moe_fullest`` those on the fullest held expert, ``moe_dropped``
assignments that no block computed (0 by construction; counted, not
assumed).

Expert parallelism: the stacked weights (leading axis = experts held) are
the expert axis; ``moe_ep_specs`` shards them over an ``expert`` mesh axis
and GSPMD partitions or gathers around the grouped product.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from commefficient_tpu.utils.tracing import layer


def relu2(x):
    return jnp.square(jax.nn.relu(x))


_ACTIVATIONS = {"gelu": nn.gelu, "relu2": relu2}


def route(scores, bias, top_k: int, norm_topk: bool, routed_scale: float):
    """(expert ids (N, k), gates (N, k)): the ``top_k`` largest of
    ``scores + bias`` a token (``lax.top_k``: of equal values the lower
    index first), weighed by the plain scores."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * routed_scale


def default_rows_per_block(n_tokens, top_k, held, num_experts):
    """Twice the rows an even routing would give the held experts, in
    multiples of 512; all rows where every expert is held."""
    rows = n_tokens * top_k
    even = -(-rows * held // num_experts)
    return min(rows, -(-2 * even // 512) * 512)


class MoEFFN(nn.Module):
    """Drop-in replacement for a transformer MLP: (N..., C) -> (N..., C)."""
    num_experts: int                      # the router's width
    d_ff: int
    experts_held: Optional[Tuple[int, ...]] = None   # ids; None = all
    top_k: int = 1
    scoring: str = "softmax"              # or "sigmoid"
    norm_topk: bool = False
    routed_scale: float = 1.0
    activation: str = "gelu"              # or "relu2"
    use_bias: bool = True
    shared_d_ff: int = 0                  # width of the shared expert, 0 = none
    aux_loss: bool = True                 # sow the Switch balancing loss
    rows_per_block: Optional[int] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        orig_shape = x.shape
        C = orig_shape[-1]
        xt = x.reshape(-1, C)
        N, E, k = xt.shape[0], self.num_experts, self.top_k
        held_ids = (tuple(range(E)) if self.experts_held is None
                    else tuple(self.experts_held))
        H = len(held_ids)
        dt, f32 = self.dtype, jnp.float32
        act = _ACTIVATIONS[self.activation]
        init = nn.initializers.normal(0.02)

        with layer("moe_route"):
            # float32 in earnest: a TPU's default precision would round the
            # operands to bfloat16 and move near-ties across the top-k edge
            logits = nn.Dense(E, use_bias=self.use_bias, dtype=f32,
                              name="router", kernel_init=init,
                              precision=jax.lax.Precision.HIGHEST)(
                                  xt.astype(f32))
            scores = (jax.nn.softmax(logits, axis=-1)
                      if self.scoring == "softmax"
                      else jax.nn.sigmoid(logits))
            # the score-correction bias: a buffer the optimizer never sees
            # (this model balances by it, not by a loss); absent = zeros
            bias = (self.get_variable("buffers", "score_bias")
                    if self.has_variable("buffers", "score_bias")
                    else jnp.zeros((E,), f32))
            idx, gates = route(scores, bias, k, self.norm_topk,
                               self.routed_scale)
            slot_of = jnp.full((E,), H, jnp.int32).at[
                jnp.asarray(held_ids)].set(jnp.arange(H, dtype=jnp.int32))
            slot = slot_of[idx].reshape(-1)           # (N k,), H = not held
            order = jnp.argsort(slot, stable=True)    # held first, by expert
            sizes = jnp.bincount(slot, length=H + 1)[:H].astype(jnp.int32)
            ends = jnp.cumsum(sizes)
            n_held = ends[-1]
            token_of = (order // k).astype(jnp.int32)
            gate_of = gates.reshape(-1)[order]

        # distinctive names: moe_ep_specs shards by param name alone, so
        # the specs work on any tree containing an MoEFFN at any depth
        w1 = self.param("moe_w1", init, (H, C, self.d_ff), f32)
        w2 = self.param("moe_w2", init, (H, self.d_ff, C), f32)
        b1 = b2 = None
        if self.use_bias:
            b1 = self.param("moe_b1", nn.initializers.zeros,
                            (H, self.d_ff), f32)
            b2 = self.param("moe_b2", nn.initializers.zeros, (H, C), f32)

        rows = N * k
        block = min(rows, self.rows_per_block
                    or default_rows_per_block(N, k, H, E))
        n_blocks = -(-rows // block)
        fill = n_blocks * block - rows                # never live: >= n_held
        token_of = jnp.pad(token_of, (0, fill))
        gate_of = jnp.pad(gate_of, (0, fill))

        xc, w1c, w2c = xt.astype(dt), w1.astype(dt), w2.astype(dt)

        def block_rows(start, xc, w1c, w2c, gate_of):
            """Rows [start, start + block) of the sorted assignments: the
            tokens they belong to, what their experts give, how many."""
            tok = jax.lax.dynamic_slice(token_of, (start,), (block,))
            gate = jax.lax.dynamic_slice(gate_of, (start,), (block,))
            at = start + jnp.arange(block, dtype=jnp.int32)
            live = at < n_held
            sz = (jnp.clip(ends - start, 0, block)
                  - jnp.clip(ends - sizes - start, 0, block))
            if self.use_bias:                         # a row's expert
                own = jnp.minimum(
                    jnp.searchsorted(ends, at, side="right"), H - 1)
            # A row past the last assignment belongs to no expert, and what
            # the grouped product leaves in such a row is not defined — in
            # its result or in the cotangent it hands back (on a TPU:
            # whatever the memory held). So every crossing is a select, in
            # both directions: such rows enter as zeros, and nothing of
            # them leaves toward the tokens, the biases or the result.
            alive = live[:, None]
            h = jax.lax.ragged_dot(jnp.where(alive, xc[tok], 0), w1c, sz,
                                   preferred_element_type=f32)
            if b1 is not None:
                h = h + b1[own]
            h = jnp.where(alive, h, 0.0)
            y = jax.lax.ragged_dot(act(h).astype(dt), w2c, sz,
                                   preferred_element_type=f32)
            if b2 is not None:
                y = y + b2[own]
            y = jnp.where(alive, y * gate[:, None], 0.0)
            return tok, y, jnp.sum(live.astype(jnp.int32))

        # A block past the last assignment is skipped. The skip sits INSIDE
        # the rematerialised function and what the backward pass needs of a
        # block is its arguments, which every block shares: a ``cond`` on
        # the outside would hand each block its own copy of them.
        @jax.checkpoint
        def block_or_nothing(start, xc, w1c, w2c, gate_of):
            return jax.lax.cond(
                n_held > start, block_rows,
                lambda *_: (jnp.zeros((block,), jnp.int32),
                            jnp.zeros((block, C), f32),
                            jnp.zeros((), jnp.int32)),
                start, xc, w1c, w2c, gate_of)

        with layer("moe_experts"):
            out, done = jnp.zeros((N, C), f32), jnp.zeros((), jnp.int32)
            for i in range(n_blocks):
                start = jnp.int32(i * block)
                if i == 0:
                    tok, y, n = block_rows(start, xc, w1c, w2c, gate_of)
                    out = out.at[tok].add(y)
                else:
                    tok, y, n = block_or_nothing(start, xc, w1c, w2c,
                                                 gate_of)
                    out = jax.lax.cond(n > 0, lambda o: o.at[tok].add(y),
                                       lambda o: o, out)
                done = done + n

        if self.shared_d_ff:
            with layer("moe_shared"):
                hs = nn.Dense(self.shared_d_ff, use_bias=False, dtype=dt,
                              name="shared_up", kernel_init=init)(
                                  xt.astype(dt))
                out = out + nn.Dense(C, use_bias=False, dtype=dt,
                                     name="shared_down", kernel_init=init)(
                                         act(hs)).astype(f32)

        if self.aux_loss:
            # Switch load-balancing loss: E * sum_e f_e * p_e, f_e the
            # share of assignments on e and p_e the mean router score
            share = jnp.mean(jax.nn.one_hot(idx, E, dtype=f32), axis=(0, 1))
            self.sow("intermediates", "moe_aux_loss",
                     E * jnp.sum(share * jnp.mean(scores, axis=0)))
        # a token's assignments by held expert (slot H, "not held", is past
        # the one-hot's width and counts nowhere)
        on_held = jnp.sum(jax.nn.one_hot(slot.reshape(N, k), H, dtype=f32),
                          axis=1)                                # (N, H)
        fullest = jnp.argmax(sizes)
        per_token = jnp.sum(on_held, axis=-1)
        self.sow("intermediates", "moe_held", per_token)
        self.sow("intermediates", "moe_fullest", on_held[:, fullest])
        # every token's share of what no block computed (0 unless the
        # block walk above is wrong): the total is n_held - done
        self.sow("intermediates", "moe_dropped",
                 per_token * ((n_held - done).astype(f32)
                              / jnp.maximum(n_held, 1).astype(f32)))
        return out.astype(x.dtype).reshape(orig_shape)


def moe_ep_specs(params, axis: str = "expert"):
    """PartitionSpec pytree sharding every stacked-expert weight (leading
    dim == experts held) on ``axis``; everything else replicated. Apply to
    a param tree that contains MoEFFN submodules."""

    def spec(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if any(n in ("moe_w1", "moe_b1", "moe_w2", "moe_b2")
               for n in names):
            return P(axis) if leaf.ndim >= 1 else P()
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_params_ep(params, mesh: Mesh, axis: str = "expert"):
    """Place params on the mesh with expert weights sharded over ``axis``."""
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), moe_ep_specs(params, axis),
        is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, shardings)
