"""GPT-2 with double heads (LM + multiple-choice), flax/TPU-native.

Reference uses ``pytorch_transformers`` GPT2DoubleHeadsModel
(reference gpt2_train.py:262-273): LM head tied to the token embedding and a
scalar multiple-choice head read at each candidate's last token
(``mc_token_ids``). Input layout follows the PersonaChat convention
(reference fed_persona.py:330-358): ``input_ids``/``token_type_ids`` are
(batch, num_candidates, seq_len); ``token_type_ids`` index the same
embedding table as tokens; padded positions are attended (the reference
passes no attention mask) and excluded from the loss via ``lm_labels == -1``.

TPU-first details: bf16-friendly matmuls (dtype parameter), static causal
mask via jnp.tril, everything shape-static so pjit/ring-attention can shard
the sequence axis later.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.ops.dropout import FusedDropout


class GPT2Config:
    def __init__(self, vocab_size=50262, n_positions=512, n_embd=768,
                 n_layer=12, n_head=12, dropout=0.1, dtype="float32",
                 attn_impl="full", attn_block_size=512, seq_axis="seq",
                 remat=False, arch="gpt2"):
        # arch: 'gpt2' (pre-LN blocks + final LN) or 'openai-gpt'
        # (GPT-1: post-LN blocks, no final LN) — the reference accepts
        # both checkpoint families (gpt2_train.py:262-273)
        if arch not in ("gpt2", "openai-gpt"):
            raise ValueError(f"unknown arch {arch!r}")
        self.arch = arch
        self.vocab_size = vocab_size
        self.n_positions = n_positions
        self.n_embd = n_embd
        self.n_layer = n_layer
        self.n_head = n_head
        self.dropout = dropout
        self.dtype = dtype  # "float32" | "bfloat16" compute dtype
        # 'full' = materialized (T,T) scores; 'blockwise' = flash-style
        # online softmax (O(T*block) memory, long-context single chip);
        # 'ring' = sequence-parallel over ``seq_axis`` — the model must
        # then be applied inside shard_map with T sharded on that axis
        # (see ops/attention.py)
        if attn_impl not in ("full", "blockwise", "ring"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        self.attn_impl = attn_impl
        self.attn_block_size = attn_block_size
        self.seq_axis = seq_axis
        # rematerialize each transformer block on backward (jax.checkpoint):
        # trades ~1/3 more FLOPs for O(n_layer) less activation memory —
        # the standard TPU lever for long-context training
        self.remat = remat
        # >0 replaces every block's MLP with a Switch-routed (top-1, no
        # capacity, nothing dropped) MoE of this many experts
        # (ops/moe.py); stacked expert weights are the
        # expert-parallel axis. 0 = dense MLP (reference parity).
        self.moe_experts = 0
        # 'xla' (portable recompute-in-backward masked_dropout) or
        # 'tpu_bits' (hardware-RNG Pallas kernel, ops/dropout.py — same
        # Bernoulli distribution, ~8x cheaper bit generation on-chip; not
        # vmap-safe, so entrypoints only enable it on the fused round path)
        self.dropout_impl = "xla"
        # Where attn_impl='blockwise' puts attention dropout:
        #   'auto'   — reference-parity dropout on the attention
        #              PROBABILITIES inside the fused kernel when the call
        #              is kernel-eligible (TPU, causal self-attn), output
        #              dropout otherwise (the pre-kernel fallback);
        #   'output' — always output dropout (the old blockwise behavior);
        #   'kernel' — require the in-kernel path; raises when training
        #              with dropout>0 on an ineligible backend/shape
        #              (so that an A/B can't silently mislabel an arm).
        # Irrelevant for attn_impl='full' (XLA prob dropout) and 'ring'
        # (output dropout, documented divergence).
        self.attn_dropout = "auto"
        # True: __call__ returns the final HIDDEN states (B, C, T, E)
        # instead of lm_logits, and the loss computes CE with the
        # vocab-chunked fused LM head (ops/fused_ce.py) — the (N, V)
        # logits tensor never materializes. Same loss values (bf16-input
        # matmul accuracy); the losses module branches on this flag.
        # Not supported with attn_impl='ring' (the seq-parallel losses
        # own their logits handling).
        self.fused_lm_head = False

    @property
    def jnp_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @classmethod
    def small(cls, vocab_size=50262):
        return cls(vocab_size=vocab_size)

    @classmethod
    def tiny(cls, vocab_size=300):
        """For tests and offline byte-tokenizer runs."""
        return cls(vocab_size=vocab_size, n_positions=256, n_embd=128,
                   n_layer=2, n_head=4, dropout=0.0)

    @classmethod
    def openai_gpt(cls, vocab_size=40478 + 5):
        """GPT-1 double-heads (ref gpt2_train.py:262-273 'openai-gpt'
        branch): 12-layer post-LN transformer, 512 positions; default
        vocab = GPT-1's 40,478 BPE merges + the 5 PersonaChat special
        tokens the reference adds (gpt2_train.py:101-112)."""
        return cls(vocab_size=vocab_size, n_positions=512, n_embd=768,
                   n_layer=12, n_head=12, arch="openai-gpt")


class CausalSelfAttention(nn.Module):
    n_head: int
    dropout: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "full"       # 'full' | 'blockwise' | 'ring'
    attn_block_size: int = 512
    seq_axis: str = "seq"
    dropout_impl: str = "xla"
    attn_dropout: str = "auto"    # 'auto' | 'output' | 'kernel'

    @nn.compact
    def __call__(self, x, train: bool, cache=None, position=None,
                 verify: bool = False):
        from commefficient_tpu.ops.attention import (
            blockwise_attention, decode_attention, full_attention,
            kernel_prob_dropout_eligible, paged_decode_attention,
            paged_verify_attention, ring_attention)
        B, T, C = x.shape
        qkv = nn.Dense(3 * C, dtype=self.dtype,
                       kernel_init=nn.initializers.normal(0.02))(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = lambda t: t.reshape(B, T, self.n_head, C // self.n_head)
        q, k, v = heads(q), heads(k), heads(v)
        if self.attn_impl not in ("full", "blockwise", "ring"):
            # post-construction assignment can bypass GPT2Config's check;
            # never silently fall through to full attention
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        new_cache = None
        if cache is not None:
            # KV-cached inference (docs/SERVING.md). Static programs,
            # keyed on (T, verify) so each gets its own compile:
            #   T == 1  decode — write this token's k/v at the row's
            #           position (one-hot select: positions differ per
            #           row under continuous batching) and run one query
            #           against the whole cache, O(S) not O(S^2);
            #   T  > 1, verify — speculative multi-token verify
            #           (serving/speculative.py): T consecutive tokens
            #           written at each row's OWN positions
            #           position..position+T-1, attended with the decode
            #           mask, so one forward scores a row's pending token
            #           plus its drafted continuation;
            #   T  > 1  prefill from position 0 — causal self-attention
            #           within the prompt window (cache slots beyond it
            #           hold pad-derived garbage, masked/overwritten
            #           before they ever become attendable), k/v written
            #           with one dynamic_update_slice.
            if self.attn_impl == "ring":
                raise ValueError("KV-cache decoding does not compose with "
                                 "attn_impl='ring' (no shard_map at serve "
                                 "time); serve with 'full' or 'blockwise'")
            if "pt" in cache:
                # Block-paged decode (serving/paged_cache.py): the layer
                # cache is {"k": (num_pages, page_size, H, hd) pool, "v":
                # likewise, "pt": (B, M) int32 page table}. Each token's
                # k/v scatter into the row's frontier pages (host-allocated
                # before the step; free/done lanes point at the reserved
                # garbage page 0, which is never attendable — the mask is
                # by logical position). Prefill stays dense (B=1) and is
                # packed into pages by DecodeEngine.paged_insert.
                if T != 1 and not verify:
                    raise ValueError(
                        "paged KV cache decodes one token per step "
                        "(or a verify=True multi-token window); "
                        "prefill runs dense and is packed host-side")
                Pg = cache["k"].shape[1]
                M = cache["pt"].shape[1]
                b = jnp.arange(B)[:, None]
                p = position[:, None] + jnp.arange(T)[None, :]  # (B, T)
                # out-of-capacity writes route to the garbage page
                # (physical page 0) INSTEAD of clipping: a clipped
                # position would collide with the last real entry's
                # scatter index, and duplicate-index scatter order is
                # undefined. The garbage page absorbs them unattended.
                in_range = p < M * Pg
                pc = jnp.minimum(p, M * Pg - 1)
                phys = jnp.where(in_range, cache["pt"][b, pc // Pg], 0)
                off = pc % Pg
                if "k_scale" in cache:
                    # quantized pools (ops/kv_quant.py): requant-on-write
                    # into the frontier pages, scales riding the cache;
                    # attention dequantizes in-gather so no f32 array of
                    # the pool's shape appears (decode_paged_quant audit)
                    from commefficient_tpu.ops import kv_quant
                    mode = kv_quant.infer_mode(cache["k"],
                                               C // self.n_head)
                    ck, ks = kv_quant.insert_tokens(
                        cache["k"], cache["k_scale"], k, phys, off, mode)
                    cv, vs = kv_quant.insert_tokens(
                        cache["v"], cache["v_scale"], v, phys, off, mode)
                    y = paged_verify_attention(
                        q, ck, cv, cache["pt"],
                        jnp.minimum(position, M * Pg - 1),
                        k_scale=ks, v_scale=vs)
                    new_cache = {"k": ck, "v": cv, "k_scale": ks,
                                 "v_scale": vs, "pt": cache["pt"]}
                else:
                    ck = cache["k"].at[phys, off].set(
                        k.astype(cache["k"].dtype))
                    cv = cache["v"].at[phys, off].set(
                        v.astype(cache["v"].dtype))
                    y = paged_verify_attention(q, ck, cv, cache["pt"],
                                               jnp.minimum(position,
                                                           M * Pg - 1))
                    new_cache = {"k": ck, "v": cv, "pt": cache["pt"]}
            elif verify and T > 1:
                # dense-slab verify twin: scatter T rows at per-row
                # positions with mode="drop" (out-of-capacity writes
                # vanish rather than clip-collide), then the multi-query
                # decode attention
                S = cache["k"].shape[1]
                b = jnp.arange(B)[:, None]
                p = position[:, None] + jnp.arange(T)[None, :]  # (B, T)
                ck = cache["k"].at[b, p].set(
                    k.astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[b, p].set(
                    v.astype(cache["v"].dtype), mode="drop")
                y = decode_attention(q, ck, cv,
                                     jnp.minimum(position, S - 1))
                new_cache = {"k": ck, "v": cv}
            elif T == 1:
                S = cache["k"].shape[1]
                p = jnp.minimum(position, S - 1)
                hit = (jnp.arange(S)[None, :] == p[:, None])[..., None, None]
                ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
                cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
                y = decode_attention(q, ck, cv, p)
                new_cache = {"k": ck, "v": cv}
            else:
                S = cache["k"].shape[1]
                if T > S:
                    raise ValueError(
                        f"prefill length {T} exceeds cache capacity {S}")
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
                if self.attn_impl == "blockwise":
                    y = blockwise_attention(q, k, v, causal=True,
                                            block_size=self.attn_block_size)
                else:
                    y = full_attention(q, k, v, causal=True)
                new_cache = {"k": ck, "v": cv}
        elif self.attn_impl == "blockwise":
            if self.attn_dropout not in ("auto", "output", "kernel"):
                raise ValueError(
                    f"unknown attn_dropout {self.attn_dropout!r}")
            rate = self.dropout if train else 0.0
            in_kernel = (rate > 0.0 and self.attn_dropout != "output"
                         and kernel_prob_dropout_eligible(q, k, v))
            if self.attn_dropout == "kernel" and rate > 0.0 \
                    and not in_kernel:
                raise ValueError(
                    "attn_dropout='kernel' but the fused kernel is not "
                    "eligible for this backend/shape — use 'auto' to "
                    "fall back to output dropout")
            if in_kernel:
                # reference-parity dropout on the attention PROBABILITIES,
                # inside the fused kernel (ops/flash_attention.py): the
                # keep-bits are drawn in-register per score tile and
                # regenerated in the backward — no (T, T) mask in HBM.
                # Flax's make_rng folds in the module path, so each layer
                # draws an independent mask from the round's dropout rng.
                y = blockwise_attention(
                    q, k, v, causal=True,
                    block_size=self.attn_block_size,
                    dropout_rate=rate,
                    dropout_rng=self.make_rng("dropout"))
            else:
                y = blockwise_attention(q, k, v, causal=True,
                                        block_size=self.attn_block_size)
                # off-kernel fallback: dropout on the attention OUTPUT
                # (documented divergence, ops/attention.py module
                # docstring — the scan path can't drop probabilities
                # without materializing the mask)
                y = FusedDropout(self.dropout, self.dropout_impl)(
                    y, deterministic=not train)
        elif self.attn_impl == "ring":
            # requires tracing inside shard_map with T sharded on seq_axis
            y = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
            y = FusedDropout(self.dropout, self.dropout_impl)(
                y, deterministic=not train)
        else:
            att = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                   / np.sqrt(C // self.n_head))
            # ADDITIVE causal bias, not jnp.where(mask, att, min): an
            # add's backward is identity where a select's is another
            # (B,H,T,T) select. Measured speed-NEUTRAL (deterministic
            # device A/B, docs/ROOFLINE.md r5 — XLA already fused the
            # select); kept for the simpler backward. Identical math:
            # |att| << |finfo.min|, so the sum rounds to exactly
            # finfo.min and softmax still zeroes the masked positions
            # (HF logit parity tested).
            causal = jnp.tril(jnp.ones((T, T), bool))
            att = att + jnp.where(causal, 0.0,
                                  jnp.finfo(att.dtype).min)[None, None]
            att = jax.nn.softmax(att, axis=-1)
            att = FusedDropout(self.dropout, self.dropout_impl)(
                att, deterministic=not train)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        y = y.reshape(B, T, C)
        y = nn.Dense(C, dtype=self.dtype,
                     kernel_init=nn.initializers.normal(0.02))(y)
        y = FusedDropout(self.dropout, self.dropout_impl)(
            y, deterministic=not train)
        return y if cache is None else (y, new_cache)


class Block(nn.Module):
    n_head: int
    dropout: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "full"
    attn_block_size: int = 512
    seq_axis: str = "seq"
    moe_experts: int = 0
    post_ln: bool = False    # GPT-1 places LN after the residual add
    dropout_impl: str = "xla"
    attn_dropout: str = "auto"

    def _mlp(self, h, train: bool):
        if self.moe_experts > 0:
            from commefficient_tpu.ops.moe import MoEFFN
            return MoEFFN(self.moe_experts, 4 * h.shape[-1],
                          dtype=self.dtype, name="moe")(h)
        m = nn.Dense(4 * h.shape[-1], dtype=self.dtype,
                     kernel_init=nn.initializers.normal(0.02))(h)
        m = nn.gelu(m)
        return nn.Dense(h.shape[-1], dtype=self.dtype,
                        kernel_init=nn.initializers.normal(0.02))(m)

    @nn.compact
    def __call__(self, x, train: bool, cache=None, position=None,
                 verify: bool = False):
        # epsilon matches HF GPT-2 (1e-5) so imported pretrained weights
        # reproduce reference logits (models/gpt2_import.py)
        ln = lambda t: nn.LayerNorm(dtype=self.dtype, epsilon=1e-5)(t)
        attn = CausalSelfAttention(self.n_head, self.dropout,
                                   self.dtype, self.attn_impl,
                                   self.attn_block_size, self.seq_axis,
                                   self.dropout_impl,
                                   attn_dropout=self.attn_dropout)
        new_cache = None

        def _attn(h):
            # same submodule either way, so the params tree is identical
            # between training and cache-mode serving
            nonlocal new_cache
            if cache is None:
                return attn(h, train)
            out, new_cache = attn(h, train, cache=cache, position=position,
                                  verify=verify)
            return out

        drop = lambda t: FusedDropout(self.dropout, self.dropout_impl,
                                      name="mlp_drop")(
            t, deterministic=not train)
        if self.post_ln:
            # GPT-1 (ref 'openai-gpt'): LN AFTER each residual add
            x = ln(x + _attn(x))
            out = ln(x + drop(self._mlp(x, train)))
        else:
            h = ln(x)
            x = x + _attn(h)
            h = ln(x)
            out = x + drop(self._mlp(h, train))
        return out if cache is None else (out, new_cache)


class GPT2DoubleHeads(nn.Module):
    """Returns (lm_logits (B,C,T,V), mc_logits (B,C)) — or, with
    ``config.fused_lm_head``, (hidden (B,C,T,E), mc_logits (B,C)) for the
    vocab-chunked fused head+CE in the losses module.

    KV-cached inference: pass ``cache`` (init_decode_cache pytree),
    ``position`` and optionally ``logits_at`` with ``train=False`` to get
    (lm_logits (B*C, V), mc_logits, new_cache) — T>1 prefills the cache,
    T==1 decodes one token per row against it (docs/SERVING.md).
    ``verify=True`` with T>1 is the speculative multi-token verify
    instead of prefill: the T tokens are a row's pending token plus its
    drafted continuation, written at positions position..position+T-1
    and attended with the decode mask; ``logits_all=True`` then returns
    lm logits at ALL T positions, (B*C, T, V) with small static T =
    speculate_k + 1 (serving/speculative.py). Cache mode always
    materializes the per-position logits it returns, so
    ``fused_lm_head`` is irrelevant to it."""
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, token_type_ids, mc_token_ids,
                 train: bool = True, cache=None, position=None,
                 logits_at=None, verify: bool = False,
                 logits_all: bool = False):
        cfg = self.config
        if cfg.fused_lm_head and cfg.attn_impl == "ring":
            raise ValueError("fused_lm_head is not supported with "
                             "attn_impl='ring' (the seq-parallel losses "
                             "own their logits handling)")
        if cache is not None:
            # KV-cached inference: ``cache`` is the pytree from
            # init_decode_cache, ``position`` (B*C,) each row's write
            # offset (0 for prefill), ``logits_at`` (B*C,) the per-row
            # index to read LM logits at (default T-1). Returns
            # (lm_logits (B*C, V), mc_logits, new_cache) — logits ONLY
            # at the sampled position, so the (B, T, V) tensor never
            # materializes on the serving path.
            if train:
                raise ValueError("cache decoding is inference-only; "
                                 "call with train=False")
            if cfg.moe_experts > 0:
                raise ValueError("KV-cache decoding does not support MoE "
                                 "blocks yet")
        B, C, T = input_ids.shape
        ids = input_ids.reshape(B * C, T)
        types = token_type_ids.reshape(B * C, T)

        wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       embedding_init=nn.initializers.normal(0.02),
                       name="wte")
        wpe = nn.Embed(cfg.n_positions, cfg.n_embd,
                       embedding_init=nn.initializers.normal(0.01),
                       name="wpe")
        ring = cfg.attn_impl == "ring"
        pos = jnp.arange(T)[None, :]
        if ring:
            # inside shard_map T is the LOCAL sequence shard; positions
            # (and the MC-head pick below) must be global
            pos = pos + jax.lax.axis_index(cfg.seq_axis) * T
        elif cache is not None:
            pos = position[:, None] + pos      # per-row decode offsets
            if verify:
                # near-capacity rows may index past the position table
                # (their emissions are capacity-masked by the verify
                # program); clamp explicitly rather than relying on
                # gather-clip semantics
                pos = jnp.minimum(pos, cfg.n_positions - 1)
        x = wte(ids) + wpe(pos) + wte(types)
        x = FusedDropout(cfg.dropout, cfg.dropout_impl)(
            x, deterministic=not train)
        # static_argnums counts the flax scope as arg 0: train is arg 2.
        # Cache mode always uses the plain Block (remat buys nothing at
        # inference); lifted transforms preserve param names, so the same
        # checkpoint serves either way.
        block_cls = (nn.remat(Block, static_argnums=(2,))
                     if cfg.remat and cache is None else Block)
        post_ln = cfg.arch == "openai-gpt"
        new_cache = []
        for i in range(cfg.n_layer):
            blk = block_cls(cfg.n_head, cfg.dropout, cfg.jnp_dtype,
                            cfg.attn_impl, cfg.attn_block_size,
                            cfg.seq_axis, cfg.moe_experts, post_ln,
                            cfg.dropout_impl,
                            getattr(cfg, "attn_dropout", "auto"))
            if cache is None:
                x = blk(x, train)
            else:
                x, layer_cache = blk(x, train, cache=cache[i],
                                     position=position, verify=verify)
                new_cache.append(layer_cache)
        x = x.astype(jnp.float32)
        if not post_ln:
            x = nn.LayerNorm(epsilon=1e-5)(x)   # GPT-1 has no final LN

        if cache is not None and logits_all:
            # speculative verify: logits at ALL T positions, (B*C, T, V).
            # T here is speculate_k + 1 — a handful — so this never
            # approaches the (B, max_len, V) tensor the serving path
            # exists to avoid.
            lm_out = wte.attend(x)
        elif cache is not None:
            # LM logits only at the sampled positions (tied wte head,
            # f32): (B*C, V), never (B*C, T, V)
            idx = (jnp.full((B * C,), T - 1, jnp.int32)
                   if logits_at is None else logits_at)
            lm_out = wte.attend(x[jnp.arange(B * C), idx])
        elif cfg.fused_lm_head:
            # the loss applies the vocab-chunked fused head+CE
            # (ops/fused_ce.py) to these hidden states with the tied wte
            # weight it reads from params — the (N, V) logits tensor is
            # never materialized
            lm_out = x.reshape(B, C, T, cfg.n_embd)
        else:
            # LM head tied to wte (GPT-2 weight tying); logits in f32
            lm_logits = wte.attend(x)
            lm_out = lm_logits.reshape(B, C, T, cfg.vocab_size)

        # multiple-choice head: hidden state at each candidate's last token
        mc_ids = mc_token_ids.reshape(B * C)
        if ring:
            # mc_token_ids are GLOBAL: the owning shard contributes its
            # hidden state, psum replicates it everywhere. The mc-head
            # dropout is applied to the owner's contribution BEFORE the
            # psum: under seq sharding each shard's dropout rng is folded
            # with its mesh position (parallel/seq._shard_rngs), so a
            # post-psum dropout would draw a DIFFERENT mask per shard on
            # this replicated tensor — mc_logits would silently diverge
            # across the seq axis (review r4). Dropping the owner's value
            # pre-psum gives every shard the owner's realization.
            off = jax.lax.axis_index(cfg.seq_axis) * T
            local = jnp.clip(mc_ids - off, 0, T - 1)
            val = x[jnp.arange(B * C), local]
            mine = (mc_ids >= off) & (mc_ids < off + T)
            contrib = jnp.where(mine[:, None], val, 0.0)
            contrib = FusedDropout(cfg.dropout, cfg.dropout_impl)(
                contrib, deterministic=not train)
            picked = jax.lax.psum(contrib, cfg.seq_axis)
        else:
            picked = x[jnp.arange(B * C), mc_ids]      # (B*C, n_embd)
            picked = FusedDropout(cfg.dropout, cfg.dropout_impl)(
                picked, deterministic=not train)
        mc = nn.Dense(1, kernel_init=nn.initializers.normal(0.02),
                      name="mc_head")(picked)
        mc_logits = mc.reshape(B, C)
        if cache is not None:
            return lm_out, mc_logits, tuple(new_cache)
        return lm_out, mc_logits


def init_decode_cache(config: GPT2Config, batch_size: int, max_len: int):
    """Zero KV cache for ``GPT2DoubleHeads`` cache-mode inference: a tuple
    with one ``{"k", "v"}`` dict per layer, each (batch, max_len, n_head,
    head_dim) in the model's compute dtype. ``max_len`` is the cache
    capacity — prompt plus generated tokens — and is bounded by the
    position-embedding table."""
    if max_len > config.n_positions:
        raise ValueError(f"cache capacity {max_len} exceeds n_positions "
                         f"{config.n_positions}")
    head_dim = config.n_embd // config.n_head
    shape = (batch_size, max_len, config.n_head, head_dim)
    return tuple({"k": jnp.zeros(shape, config.jnp_dtype),
                  "v": jnp.zeros(shape, config.jnp_dtype)}
                 for _ in range(config.n_layer))
