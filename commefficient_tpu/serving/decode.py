"""KV-cached jitted decode for GPT2DoubleHeads.

The incumbent ``models/gpt2_generate.sample_reply`` re-runs a full
``max_seq_len`` forward per generated token — O(T^2) attention recompute
and a host round-trip per token. ``DecodeEngine`` replaces that with
three programs, each compiled exactly once per batch shape:

* ``prefill``  — one causal forward over the padded prompt window that
  fills the KV cache and returns logits at each row's last real token
  (never the (B, T, V) tensor);
* ``step``     — ONE token for every row: single-query attention against
  the cache (ops/attention.decode_attention, O(S) per token) with
  greedy/top-k sampling INSIDE the program;
* ``generate_tokens`` — prefill + ``lax.scan`` of ``step``: the whole
  reply in one dispatch, zero host syncs between tokens.

Rows are independent: each carries its own write ``pos``, its own
``done`` latch (eos seen, or cache capacity reached), and under the
continuous-batching server a different request entirely. Done rows keep
riding the batch (their lanes emit ``eos_id``) so the program never
changes shape — batch {1, 8, 64} and any active-slot mix all reuse the
same compiled step. The ``decode`` graft-audit target
(analysis/targets.py) proves the step stays retrace-free across tokens,
makes no host transfers, and materializes no (B, H, S, S) scores.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models.gpt2 import init_decode_cache


def sample_next(logits, rng, *, method: str, top_k: int, temperature: float):
    """Sample next-token ids (B,) from (B, V) logits, inside the program.

    Greedy consumes no randomness (rng passes through untouched) so a
    greedy decode is bit-deterministic; top-k splits the carried key once
    per token, mirroring sample_reply's per-token split chain."""
    if method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
    rng, sub = jax.random.split(rng)
    vals, idxs = jax.lax.top_k(logits.astype(jnp.float32) / temperature,
                               top_k)
    choice = jax.random.categorical(sub, vals)              # (B,)
    nxt = jnp.take_along_axis(idxs, choice[:, None], axis=1)[:, 0]
    return nxt.astype(jnp.int32), rng


class DecodeEngine:
    """Compiled decode programs for one (model, params) pair.

    ``max_len`` is the cache capacity (prompt + generated tokens),
    bounded by the model's position table. All public jitted entry
    points take ``params`` explicitly so a caller can serve updated
    weights (e.g. after a finetune step) without recompiling.
    """

    def __init__(self, model, params, *, eos_id: int,
                 max_len: Optional[int] = None, pad_id: int = 0,
                 method: str = "greedy", top_k: int = 8,
                 temperature: float = 0.7, mesh=None,
                 tp_axis: str = "model"):
        if method not in ("greedy", "topk"):
            raise ValueError(f"method must be 'greedy' or 'topk', "
                             f"got {method!r}")
        cfg = model.config
        self.model = model
        # tensor-parallel serving (parallel/tp.py): params take the
        # Megatron column/row layout and every KV cache / page pool
        # shards its HEAD axis along ``tp_axis``, so the decode
        # attention einsums (heads are a batch dim throughout,
        # ops/attention.py) and the paged page gathers stay shard-local
        # and GSPMD closes each block with one psum. The host page
        # table stays the single global allocator — it is replicated
        # (tiny int32), only pool CONTENT shards.
        self.mesh = None
        self.tp_axis = tp_axis
        self.tp = 1
        if mesh is not None and tp_axis in mesh.shape \
                and mesh.shape[tp_axis] > 1:
            tp = int(mesh.shape[tp_axis])
            if cfg.n_head % tp:
                raise ValueError(
                    f"tensor-parallel serving shards the KV head axis: "
                    f"n_head {cfg.n_head} must be divisible by the "
                    f"'{tp_axis}' mesh axis size {tp}")
            self.mesh = mesh
            self.tp = tp
            leaves = jax.tree_util.tree_leaves(params)
            if leaves and isinstance(leaves[0], jax.Array):
                from commefficient_tpu.parallel.tp import shard_params_tp
                params = shard_params_tp(params, mesh, tp_axis)
            # else: abstract params (an eval_shape trace of the engine) —
            # placement is moot, the _constrain annotations still trace
        self.params = params
        self.max_len = int(max_len) if max_len else int(cfg.n_positions)
        if self.max_len > cfg.n_positions:
            raise ValueError(f"max_len {self.max_len} exceeds n_positions "
                             f"{cfg.n_positions}")
        self.eos_id = int(eos_id)
        self.pad_id = int(pad_id)
        self.method = method
        self.top_k = int(top_k)
        self.temperature = float(temperature)
        # one compile per batch shape; sampling params are baked in
        self.prefill = jax.jit(self._prefill_raw)
        self.step = jax.jit(self._step_raw)
        self.paged_step = jax.jit(self._paged_step_raw)
        self.paged_insert = jax.jit(self._paged_insert_raw)
        self.generate_tokens = jax.jit(self._generate_raw,
                                       static_argnames=("max_new",))
        self.sample = jax.jit(lambda logits, rng: sample_next(
            logits, rng, method=self.method, top_k=self.top_k,
            temperature=self.temperature))

    # ---- programs (raw = untraced, for eval_shape / make_jaxpr) -------

    def init_cache(self, batch_size: int):
        return self._constrain(init_decode_cache(self.model.config,
                                                 batch_size, self.max_len))

    def _constrain(self, cache):
        """Pin the head-sharded TP layout on a cache/pool pytree (no-op
        for single-device engines, so their traces are unchanged).
        Works eagerly at allocation and under tracing inside the step
        programs, where it lands as the ``sharding_constraint`` eqns
        the ``serve_multihost`` audit target keys on."""
        if self.mesh is None:
            return cache
        from commefficient_tpu.parallel.tp import constrain_kv_cache_tp
        return constrain_kv_cache_tp(cache, self.mesh, self.tp_axis)

    def commit_replicated(self, *arrays):
        """Place host-built per-row state (tok/pos/done/rng) on the TP
        mesh, replicated and COMMITTED, so every step-program input
        keeps one sharding signature from the first call — host-fresh
        uncommitted buffers becoming device-resident outputs would
        otherwise recompile the step once per transition. No-op without
        a mesh."""
        if self.mesh is None:
            return arrays if len(arrays) > 1 else arrays[0]
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(self.mesh, PartitionSpec())
        out = tuple(jax.device_put(a, sh) for a in arrays)
        return out if len(out) > 1 else out[0]

    def _apply(self, params, ids2d, types2d, cache, pos, logits_at):
        B = ids2d.shape[0]
        logits, _, cache = self.model.apply(
            {"params": params}, ids2d[:, None, :], types2d[:, None, :],
            jnp.zeros((B, 1), jnp.int32), train=False,
            cache=cache, position=pos, logits_at=logits_at)
        return logits, cache

    def _prefill_raw(self, params, cache, ids, types, last_idx):
        """Fill the cache from padded prompts ids/types (B, P); return
        (logits (B, V) at each row's last_idx, cache)."""
        pos0 = jnp.zeros((ids.shape[0],), jnp.int32)
        logits, cache = self._apply(params, ids, types,
                                    self._constrain(cache), pos0, last_idx)
        return logits, self._constrain(cache)

    def _step_raw(self, params, cache, tok, type_tok, pos, rng, done):
        """Advance every row one token.

        ``tok`` (B,) is the previous token (written to the cache at
        ``pos``), ``done`` latches on eos or capacity. Returns
        (cache, next_tok, next_pos, rng, next_done); done rows emit
        ``eos_id`` so hosts can truncate without per-row bookkeeping."""
        zero = jnp.zeros_like(tok)
        logits, cache = self._apply(params, tok[:, None], type_tok[:, None],
                                    self._constrain(cache), pos, zero)
        nxt, rng = sample_next(logits, rng, method=self.method,
                               top_k=self.top_k,
                               temperature=self.temperature)
        new_done = done | (nxt == self.eos_id) | (pos + 1 >= self.max_len)
        nxt = jnp.where(done, jnp.int32(self.eos_id), nxt)
        new_pos = jnp.minimum(pos + 1, self.max_len - 1)
        return self._constrain(cache), nxt, new_pos, rng, new_done

    def init_paged_pools(self, num_pages: int, page_size: int,
                         kv_quant: str = "none"):
        """Zero per-layer KV page pools for the block-paged server
        (serving/paged_cache.py): a tuple with one ``{"k", "v"}`` dict
        per layer, each (num_pages, page_size, n_head, head_dim) in the
        compute dtype. Physical page 0 is the reserved garbage page.

        ``kv_quant`` in ("int8", "int4") stores the pools quantized
        (ops/kv_quant.py): the pool dtype becomes int8/packed-uint8 and
        each layer dict gains per-page-per-head f32 ``k_scale`` /
        ``v_scale`` arrays ((num_pages, n_head)). Every downstream
        program (pack, step, verify) dispatches on the presence of the
        scale keys, so mode 'none' traces byte-identical programs to a
        build without the codec."""
        from commefficient_tpu.ops import kv_quant as kvq
        kvq.validate_mode(kv_quant)
        cfg = self.model.config
        hd = cfg.n_embd // cfg.n_head
        if kv_quant == "none":
            shape = (int(num_pages), int(page_size), cfg.n_head, hd)
            return self._constrain(
                tuple({"k": jnp.zeros(shape, cfg.jnp_dtype),
                       "v": jnp.zeros(shape, cfg.jnp_dtype)}
                      for _ in range(cfg.n_layer)))
        shape = (int(num_pages), int(page_size), cfg.n_head,
                 kvq.packed_head_dim(hd, kv_quant))
        sshape = (int(num_pages), cfg.n_head)
        dt = kvq.pool_dtype(kv_quant)
        return self._constrain(
            tuple({"k": jnp.zeros(shape, dt),
                   "v": jnp.zeros(shape, dt),
                   "k_scale": jnp.zeros(sshape, jnp.float32),
                   "v_scale": jnp.zeros(sshape, jnp.float32)}
                  for _ in range(cfg.n_layer)))

    def _paged_step_raw(self, params, pools, pt, tok, type_tok, pos, rng,
                        done):
        """The paged twin of ``_step_raw``: pools + page table instead of
        the dense (B, max_len, H, hd) slab. ``pt`` (B, max_pages) int32
        is traced — the host rebuilds it between steps (admission,
        eviction, frontier allocation, prefix sharing) without ever
        retracing this program. Token/done/pos semantics are identical
        to the dense step, so greedy parity is bitwise. Quantized pools
        (init_paged_pools(kv_quant=...)) carry their scale arrays in the
        same dicts; the merge is key-generic so both layouts share this
        one program body (distinct compiles — the pytree differs)."""
        cache = tuple({**p, "pt": pt} for p in self._constrain(pools))
        zero = jnp.zeros_like(tok)
        logits, cache = self._apply(params, tok[:, None], type_tok[:, None],
                                    cache, pos, zero)
        new_pools = self._constrain(
            tuple({k: v for k, v in c.items() if k != "pt"}
                  for c in cache))
        nxt, rng = sample_next(logits, rng, method=self.method,
                               top_k=self.top_k,
                               temperature=self.temperature)
        new_done = done | (nxt == self.eos_id) | (pos + 1 >= self.max_len)
        nxt = jnp.where(done, jnp.int32(self.eos_id), nxt)
        new_pos = jnp.minimum(pos + 1, self.max_len - 1)
        return new_pools, nxt, new_pos, rng, new_done

    def _paged_insert_raw(self, pools, row_cache, dst):
        """Pack a B=1 dense prefilled cache row into pool pages.

        ``dst`` ((prefill_len // page_size,) int32, TRACED) maps the
        prompt's logical pages to physical pool pages; entries for
        prefill-window pages beyond the prompt point at the garbage
        page. One compiled program regardless of prompt length or share
        pattern — shared pages are rewritten with bitwise-identical
        content (causal k/v at position i depend only on tokens <= i).

        Quantized pools quantize at pack time (ops/kv_quant.py): pages
        and their per-page-per-head scales scatter together, so a
        copy-on-write shared page shares its scale row too. The shared
        rewrite stays idempotent — identical prompt pages quantize to
        identical (page, scale) pairs."""
        from commefficient_tpu.ops import kv_quant as kvq
        n = dst.shape[0]
        out = []
        for pool, row in zip(self._constrain(pools), row_cache):
            P = pool["k"].shape[1]

            def pages_of(r):
                return r[0, :n * P].reshape((n, P) + r.shape[2:])

            if "k_scale" in pool:
                mode = kvq.infer_mode(pool["k"], row["k"].shape[-1])
                qk, sk = kvq.quantize_pages(pages_of(row["k"]), mode)
                qv, sv = kvq.quantize_pages(pages_of(row["v"]), mode)
                out.append({"k": pool["k"].at[dst].set(qk),
                            "v": pool["v"].at[dst].set(qv),
                            "k_scale": pool["k_scale"].at[dst].set(sk),
                            "v_scale": pool["v_scale"].at[dst].set(sv)})
            else:
                def put(pl, r):
                    pages = pages_of(r)
                    return pl.at[dst].set(pages.astype(pl.dtype))
                out.append({"k": put(pool["k"], row["k"]),
                            "v": put(pool["v"], row["v"])})
        return self._constrain(tuple(out))

    def _generate_raw(self, params, ids, types, lengths, reply_type, rng,
                      *, max_new):
        """Whole-reply program: prefill + scan of the decode step.

        ids/types (B, P) padded prompts, lengths (B,) real lengths,
        reply_type (B,) the token_type for generated tokens. Returns
        (B, max_new) tokens; positions >= the first eos are eos."""
        B = ids.shape[0]
        cache = self.init_cache(B)
        logits, cache = self._prefill_raw(params, cache, ids, types,
                                          lengths - 1)
        first, rng = sample_next(logits, rng, method=self.method,
                                 top_k=self.top_k,
                                 temperature=self.temperature)
        pos = lengths.astype(jnp.int32)            # next write position
        full = pos >= self.max_len                 # prompt filled the cache
        done = (first == self.eos_id) | full
        first = jnp.where(full, jnp.int32(self.eos_id), first)
        pos = jnp.minimum(pos, self.max_len - 1)

        def body(carry, _):
            cache, tok, pos, rng, done = carry
            cache, nxt, pos, rng, done = self._step_raw(
                params, cache, tok, reply_type, pos, rng, done)
            return (cache, nxt, pos, rng, done), nxt

        if max_new <= 1:
            return first[:, None]
        _, rest = jax.lax.scan(body, (cache, first, pos, rng, done),
                               None, length=max_new - 1)
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    # ---- host-side convenience ---------------------------------------

    def generate(self, prompts: Sequence[Tuple[Sequence[int],
                                               Sequence[int]]],
                 reply_types: Sequence[int], *, max_new: int,
                 seed: int = 0,
                 prefill_len: Optional[int] = None) -> List[List[int]]:
        """Decode replies for a batch of (ids, types) prompts.

        Pads prompts to a common window, runs the single-dispatch
        generate program, and truncates each row at its first eos (the
        one device->host transfer of the whole decode)."""
        B = len(prompts)
        longest = max(len(ids) for ids, _ in prompts)
        P = int(prefill_len or longest)
        if longest > P:
            raise ValueError(f"prompt length {longest} exceeds prefill "
                             f"window {P}")
        if P > self.max_len:
            raise ValueError(f"prefill window {P} exceeds cache capacity "
                             f"{self.max_len}")
        ids = np.full((B, P), self.pad_id, np.int32)
        types = np.full((B, P), self.pad_id, np.int32)
        lengths = np.zeros((B,), np.int32)
        for i, (row_ids, row_types) in enumerate(prompts):
            L = len(row_ids)
            ids[i, :L] = row_ids
            types[i, :L] = row_types
            lengths[i] = L
        toks = np.asarray(self.generate_tokens(
            self.params, jnp.asarray(ids), jnp.asarray(types),
            jnp.asarray(lengths), jnp.asarray(reply_types, jnp.int32),
            jax.random.PRNGKey(seed), max_new=int(max_new)))
        return [self.truncate(row) for row in toks]

    def truncate(self, row) -> List[int]:
        """Tokens before the first eos (eos excluded), as python ints."""
        out: List[int] = []
        for t in row:
            if int(t) == self.eos_id:
                break
            out.append(int(t))
        return out
