"""Continuous-batching micro-server over a DecodeEngine.

A fixed slot array (the decode batch) serves a stream of requests:

* ``submit`` queues a request (prompt ids/types, reply token_type, a
  token budget);
* each ``step`` first ADMITS queued requests into free slots — a B=1
  prefill program fills a one-row cache, a jitted ``dynamic_update_slice``
  insert grafts it into the slot axis, and the first token is sampled —
  then runs the engine's single jitted decode step over the WHOLE slot
  array, and finally RETIRES finished slots (eos sampled, or budget
  exhausted) host-side;
* ``run`` steps until queue and slots drain.

Invariant: the decode step is one program for the lifetime of the
server, regardless of how many slots are active or how requests are
interleaved — free/finished lanes ride along with their ``done`` latch
set. Host work (admission, retirement, reading each step's tokens)
happens strictly BETWEEN jitted steps: one device->host pull per step,
never one per token per request. Slot indices cross into jitted code as
traced int32 scalars, so admitting to slot 7 reuses the same compile as
admitting to slot 0.

Per-row independence of the decode step (each row attends only its own
cache rows) makes the served reply for a request identical to what
``DecodeEngine.generate`` would produce for it alone — asserted in
tests/test_decode.py.

``kv_cache="paged"`` swaps the dense per-slot cache slab for the
block-paged pools of serving/paged_cache.py: admission packs the B=1
prefilled row into pool pages (one jitted pack program), the step runs
``engine.paged_step`` against the pools + traced page table, and
retirement returns pages to the free list — same one-program-per-
lifetime invariant, greedy-bitwise-identical tokens (the ``decode_paged``
audit target and tests/test_paged_serving.py hold both). Passing
``personalize=`` (a serving.personalize.PersonalizationIndex) applies a
per-user sparse weight delta at admission and subtracts it at
retirement, so requests carrying ``user_id`` decode under base + that
user's delta while base params stay shared.

``speculate_k=γ`` turns each step into a speculative round
(serving/speculative.py): one jitted DRAFT program proposes γ tokens
per slot from a small drafter's own dense cache, one jitted VERIFY
program runs the target over all γ+1 positions (through the paged
pools when ``kv_cache="paged"``) and accepts the longest matching
prefix plus one corrected token in-program — up to γ+1 tokens per
target forward, emitted stream bitwise-identical to the non-speculative
greedy stream. Rejected paged entries roll back host-side
(``PagedKVCache.truncate``). Still exactly one draft + one verify
program for the server's lifetime, and still ONE host pull per step.

Train-while-serve (commefficient_tpu/online/): the buffered federated
event loop and this server interleave on ONE host loop — the
interaction collector turns finished replies into per-client examples,
``BufferedFedLearner`` cohorts write the same sparse client rows the
personalization index reads as per-user deltas, and
``swap_base_params`` promotes refreshed base weights into the live
server. The safe sequence (drain → fingerprint gate → swap → resubmit
leftovers) lives in online/swap.py; every jitted program takes params
per call, so a swap re-uses every compile (cache stays at 1). The
speculative drafter deliberately keeps its pre-swap snapshot, so its
acceptance rate against the advancing target doubles as a live
personalization-drift metric
(``stats()['acceptance_rate_since_swap']``).

Multi-host serving (docs/SERVING.md "Multi-host") composes three
orthogonal pieces on top:

* TENSOR-PARALLEL DECODE — an engine built with ``mesh=`` shards params
  (Megatron column/row, parallel/tp.py) and every KV pool's HEAD axis
  along the 'model' mesh axis; the server is layout-blind (the same
  step calls run GSPMD-sharded), initial slot-row state is committed
  replicated at construction (``engine.commit_replicated``) so every
  step program keeps ONE sharding signature — the compile-cache-at-1
  invariant survives tp>1.
* OWNER-AFFINITY ROUTING — with a SHARDED personalization store
  (HostArenaStore num_shards>1) slots split into contiguous per-shard
  pools and ``submit(user_id=...)`` routes to the pool of
  ``store.owner(user_id)``, so a user's O(k) row reads/writes stay on
  the shard holding the row; a full owner pool makes the request WAIT
  (rows never cross shards) while anonymous requests spill into any
  free slot (counted in ``stats()['spilled_per_shard']``).
* PREFILL/DECODE DISAGGREGATION — ``disaggregate=True`` runs decode
  FIRST each step and caps admissions at ``prefill_slots``, so a
  prefill burst can never stall the resident decode rows; the handoff
  between pools is one paged page-table row write (see the constructor
  comment), which is why it requires ``kv_cache="paged"``.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class _Request:
    rid: int
    ids: Sequence[int]
    types: Sequence[int]
    reply_type: int
    max_new: int
    user_id: object = None
    out: List[int] = field(default_factory=list)


class ContinuousBatchingServer:
    def __init__(self, engine, *, slots: int = 8, prefill_len: int = 64,
                 seed: int = 0, kv_cache: str = "fixed",
                 page_size: int = 16, num_pages: int = None,
                 share_prefix: bool = True, personalize=None,
                 speculate_k: int = 0, drafter_model=None,
                 drafter_params=None, kv_quant: str = "none",
                 disaggregate: bool = False, prefill_slots: int = None):
        from commefficient_tpu.ops import kv_quant as kvq
        if prefill_len > engine.max_len:
            raise ValueError(f"prefill_len {prefill_len} exceeds cache "
                             f"capacity {engine.max_len}")
        if kv_cache not in ("fixed", "paged"):
            raise ValueError(f"kv_cache must be 'fixed' or 'paged', "
                             f"got {kv_cache!r}")
        kvq.validate_mode(kv_quant)
        if kv_quant != "none" and kv_cache != "paged":
            raise ValueError("kv_quant is a property of the paged pools "
                             "(ops/kv_quant.py) — serve with "
                             "kv_cache='paged' or kv_quant='none'")
        if kv_quant != "none" and engine.tp > 1 \
                and engine.model.config.n_head % engine.tp:
            raise ValueError(
                f"kv_quant scale rows are (num_pages, n_head) and shard "
                f"per head: n_head {engine.model.config.n_head} must "
                f"divide by tp {engine.tp}")
        self.engine = engine
        self.slots = int(slots)
        self.prefill_len = int(prefill_len)
        self.kv_cache = kv_cache
        self.kv_quant = kv_quant
        self.personalize = personalize
        # ---- prefill/decode disaggregation ---------------------------
        # With ``disaggregate=True`` admission (the compute-bound B=1
        # prefill program) and decode (the bandwidth-bound step program)
        # run as separate pools inside each ``step()``: the decode pool
        # steps FIRST, every step, and at most ``prefill_slots``
        # admissions follow it — so a prefill burst (a deep queue) can
        # never insert more than prefill_slots prefill dispatches
        # between consecutive decode steps, and admitted decode slots
        # see flat latency. The handoff between the pools is the paged
        # KV page table: the prefill pool packs its B=1 row into pool
        # pages (pager.admit -> paged_insert) and writes one page-table
        # row + slot row, after which the decode pool's unchanged step
        # program serves the request — which is why disaggregation
        # requires kv_cache='paged'.
        self.disaggregate = bool(disaggregate)
        if self.disaggregate:
            if kv_cache != "paged":
                raise ValueError(
                    "disaggregated prefill hands off KV state through "
                    "the paged page table — serve with kv_cache='paged'")
            if self.slots < 2:
                raise ValueError(
                    f"disaggregation splits prefill and decode into two "
                    f"pools; slots {self.slots} < 2 cannot hold both")
            self.prefill_slots = int(prefill_slots) if prefill_slots \
                else max(1, self.slots // 4)
            if not 1 <= self.prefill_slots < self.slots:
                raise ValueError(
                    f"prefill_slots {self.prefill_slots} must be in "
                    f"[1, slots) so the decode pool is never empty")
        else:
            self.prefill_slots = None
        B = self.slots
        if kv_cache == "paged":
            from commefficient_tpu.serving.paged_cache import PagedKVCache

            # per-user weight deltas make page content user-dependent, so
            # cross-user prefix sharing is off whenever a personalization
            # index is attached (docs/SERVING.md "sharing semantics")
            self.pager = PagedKVCache(
                slots=B, max_len=engine.max_len, prefill_len=prefill_len,
                page_size=page_size, num_pages=num_pages,
                share_prefix=share_prefix and personalize is None)
            self.cache = engine.init_paged_pools(self.pager.num_pages,
                                                 page_size,
                                                 kv_quant=kv_quant)
        else:
            self.pager = None
            self.cache = engine.init_cache(B)
        self.tok, self.typ, self.pos, self.done, self.rng = \
            engine.commit_replicated(
                jnp.full((B,), engine.pad_id, jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), bool),           # free lanes stay latched
                jax.random.PRNGKey(seed))
        # ---- owner-affinity routing ----------------------------------
        # The personalization store is sharded (HostArenaStore
        # num_shards): user cid's row lives on shard owner(cid) =
        # cid // rows_per_shard. Slots partition into the same number of
        # contiguous per-shard pools, and a personalized request is only
        # ever admitted into its OWNER's pool — its O(k) row read/write
        # and its weight-delta residency stay on one shard. Anonymous
        # requests queue on the shared ``_queue`` and SPILL (work-steal)
        # into whichever shard has a free slot, so affinity never idles
        # capacity.
        self.num_shards = int(getattr(getattr(personalize, "store", None),
                                      "num_shards", 1) or 1)
        if B % self.num_shards:
            raise ValueError(
                f"slots {B} must divide evenly across the store's "
                f"{self.num_shards} shards (contiguous per-shard slot "
                f"pools)")
        self.slots_per_shard = B // self.num_shards
        self._queue: deque = deque()            # anonymous / shared
        self._shard_queue = [deque() for _ in range(self.num_shards)]
        self._free_slots = [
            list(range(s * self.slots_per_shard,
                       (s + 1) * self.slots_per_shard))
            for s in range(self.num_shards)]
        self._admitted_per_shard = np.zeros((self.num_shards,), np.int64)
        self._spilled_per_shard = np.zeros((self.num_shards,), np.int64)
        self._slot_req: List[_Request] = [None] * B
        self._next_rid = 0
        self.swaps_done = 0
        self.dirty_swaps = 0
        self._insert = jax.jit(self._insert_raw)
        self._set_row = jax.jit(self._set_row_raw)
        self._release = jax.jit(self._release_raw)
        self.spec = None
        if speculate_k:
            from commefficient_tpu.serving.speculative import \
                SpeculativeDecoder

            # constructed BEFORE any personalized admission, so the
            # default (self-drafting) drafter snapshots pristine base
            # params — the free personalized drafter. The snapshot is
            # also deliberately NOT refreshed by swap_base_params: as
            # online training advances the target, the stale drafter's
            # acceptance rate becomes the live drift metric.
            self.spec = SpeculativeDecoder(
                engine, gamma=speculate_k, slots=B,
                drafter_model=drafter_model, drafter_params=drafter_params)
            self.prev_tok, self.prev_typ = engine.commit_replicated(
                jnp.full((B,), engine.pad_id, jnp.int32),
                jnp.zeros((B,), jnp.int32))
            self._set_prev = jax.jit(self._set_prev_raw)
            self._drafted = np.zeros((B,), np.int64)
            self._accepted = np.zeros((B,), np.int64)
            self._spec_totals = {"drafted": 0, "accepted": 0,
                                 "corrected": 0, "rounds": 0}
            self._spec_swap_mark = dict(self._spec_totals)

    # ---- jitted slot surgery (slot index is TRACED: no per-slot
    # recompiles, which the decode audit target's retrace guard relies
    # on holding for the step program these feed) ----------------------

    @staticmethod
    def _insert_raw(cache, row_cache, slot):
        def put(c, r):
            idx = (slot,) + (0,) * (c.ndim - 1)
            return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), idx)
        return jax.tree_util.tree_map(put, cache, row_cache)

    @staticmethod
    def _set_row_raw(tok, typ, pos, done, slot, t, ty, p):
        return (tok.at[slot].set(t), typ.at[slot].set(ty),
                pos.at[slot].set(p), done.at[slot].set(False))

    @staticmethod
    def _release_raw(done, slot):
        return done.at[slot].set(True)

    @staticmethod
    def _set_prev_raw(prev_tok, prev_typ, slot, t, ty):
        return prev_tok.at[slot].set(t), prev_typ.at[slot].set(ty)

    # ---- request lifecycle -------------------------------------------

    def submit(self, ids: Sequence[int], types: Sequence[int],
               reply_type: int, max_new: int, user_id=None) -> int:
        """Queue a request. A ``user_id`` routes it to the slot pool of
        the shard OWNING that user's personalization row
        (HostArenaStore.owner); anonymous requests join the shared queue
        and spill into any free slot."""
        if len(ids) > self.prefill_len:
            raise ValueError(f"prompt length {len(ids)} exceeds "
                             f"prefill_len {self.prefill_len}")
        if user_id is not None and self.personalize is None:
            raise ValueError("submit got a user_id but the server has no "
                             "personalization index attached")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, list(ids), list(types), int(reply_type),
                       int(max_new), user_id)
        if user_id is not None:
            self._shard_queue[self._owner_shard(user_id)].append(req)
        else:
            self._queue.append(req)
        return rid

    def _owner_shard(self, user_id) -> int:
        return int(self.personalize.store.owner(int(user_id)))

    def _shard_of_slot(self, slot: int) -> int:
        return int(slot) // self.slots_per_shard

    def _queued(self) -> bool:
        return bool(self._queue) or any(bool(q) for q in self._shard_queue)

    def _params_for(self, req: _Request):
        """Admission-time served params: base, or base + the user's
        sparse delta applied in place on device (O(k) per admission).
        The delta stays applied until _retire evicts it, so the shared
        decode step serves every active user's personalized weights at
        once — rows are independent only because each user's touched
        coordinates compose additively (serving/personalize.py)."""
        if self.personalize is not None and req.user_id is not None:
            self.engine.params = self.personalize.admit(
                self.engine.params, req.user_id)
        return self.engine.params

    def _evict_user(self, req: _Request) -> None:
        if self.personalize is not None and req.user_id is not None:
            self.engine.params = self.personalize.evict(
                self.engine.params, req.user_id)

    def _admit(self, budget: int = None) -> List[Tuple[int, List[int]]]:
        """Admit queued requests into free slots, owner-affine: shard
        s's slot pool serves shard s's queue first, then steals from the
        shared anonymous queue. A personalized request whose owner pool
        is full WAITS (its row never crosses shards) — the next release
        in that pool admits it before any anonymous spill. ``budget``
        (disaggregated servers) caps admissions — i.e. prefill
        dispatches — per call."""
        finished = []
        admitted, progress = 0, True
        while progress and (budget is None or admitted < budget):
            progress = False
            for s in range(self.num_shards):
                if budget is not None and admitted >= budget:
                    break
                if not self._free_slots[s]:
                    continue
                if self._shard_queue[s]:
                    req, spilled = self._shard_queue[s].popleft(), False
                elif self._queue:
                    req, spilled = self._queue.popleft(), \
                        self.num_shards > 1
                else:
                    continue
                slot = self._free_slots[s].pop()
                self._admitted_per_shard[s] += 1
                if spilled:
                    self._spilled_per_shard[s] += 1
                self._admit_one(req, slot, finished)
                admitted += 1
                progress = True
        return finished

    def _admit_one(self, req: _Request, slot: int, finished) -> None:
        """Prefill ``req`` and graft it into ``slot`` (the B=1 prefill
        program + page-table/slot-row handoff)."""
        eng = self.engine
        P, L = self.prefill_len, len(req.ids)
        ids = np.full((1, P), eng.pad_id, np.int32)
        typ = np.full((1, P), eng.pad_id, np.int32)
        ids[0, :L] = req.ids
        typ[0, :L] = req.types
        params = self._params_for(req)
        logits, row_cache = eng.prefill(
            params, eng.init_cache(1), jnp.asarray(ids),
            jnp.asarray(typ), jnp.asarray([L - 1], jnp.int32))
        first, self.rng = eng.sample(logits, self.rng)
        t = int(np.asarray(first)[0])       # admission-time sync
        if t == eng.eos_id or req.max_new <= 0:
            finished.append((req.rid, []))
            self._free_slots[self._shard_of_slot(slot)].append(slot)
            self._evict_user(req)
            return
        req.out.append(t)
        if req.max_new == 1 or L >= eng.max_len:
            finished.append((req.rid, list(req.out)))
            self._free_slots[self._shard_of_slot(slot)].append(slot)
            self._evict_user(req)
            return
        if self.pager is not None:
            dst = self.pager.admit(slot, req.ids, req.types,
                                   shareable=req.user_id is None)
            self.cache = eng.paged_insert(self.cache, row_cache,
                                          jnp.asarray(dst))
        else:
            self.cache = self._insert(self.cache, row_cache,
                                      jnp.int32(slot))
        self.tok, self.typ, self.pos, self.done = self._set_row(
            self.tok, self.typ, self.pos, self.done, jnp.int32(slot),
            jnp.int32(t), jnp.int32(req.reply_type), jnp.int32(L))
        if self.spec is not None:
            # drafter twin of the target prefill — always BASE
            # params, so a personalized admission drafts for free
            drow = self.spec.dprefill(
                self.spec.dparams, self.spec.init_drafter_row(),
                jnp.asarray(ids), jnp.asarray(typ),
                jnp.asarray([L - 1], jnp.int32))
            self.spec.dcache = self._insert(self.spec.dcache, drow,
                                            jnp.int32(slot))
            # next catch-up rewrites the last PROMPT token at L-1
            self.prev_tok, self.prev_typ = self._set_prev(
                self.prev_tok, self.prev_typ, jnp.int32(slot),
                jnp.int32(int(req.ids[-1])),
                jnp.int32(int(req.types[-1])))
            self._drafted[slot] = 0
            self._accepted[slot] = 0
        self._slot_req[slot] = req

    def _retire(self, slot: int, finished) -> None:
        req = self._slot_req[slot]
        finished.append((req.rid, list(req.out)))
        self._slot_req[slot] = None
        self._free_slots[self._shard_of_slot(slot)].append(slot)
        self.done = self._release(self.done, jnp.int32(slot))
        if self.pager is not None:
            self.pager.release(slot)
        self._evict_user(req)

    def step(self) -> List[Tuple[int, List[int]]]:
        """Advance the server one step; returns the requests finished
        this step as (rid, reply_tokens).

        Unified (default): admit everything that fits, then advance
        every slot one token and retire. Disaggregated: the DECODE pool
        steps first — its cadence never waits on the queue — then at
        most ``prefill_slots`` admissions run their prefills (the
        handoff into the decode pool is a page-table row write)."""
        if self.disaggregate:
            finished = self._decode_round([])
            finished.extend(self._admit(budget=self.prefill_slots))
            return finished
        return self._decode_round(self._admit())

    def _decode_round(self, finished) -> List[Tuple[int, List[int]]]:
        """One decode step over the active slots (+ retirement)."""
        active = [s for s, r in enumerate(self._slot_req) if r is not None]
        if not active:
            return finished
        if self.spec is not None:
            return self._speculative_round(active, finished)
        if self.pager is not None:
            for slot in active:
                self.pager.ensure_frontier(slot)
            pt = self.pager.device_table()
            (self.cache, self.tok, self.pos, self.rng,
             self.done) = self.engine.paged_step(
                self.engine.params, self.cache, pt, self.tok, self.typ,
                self.pos, self.rng, self.done)
            for slot in active:
                self.pager.advance(slot)
        else:
            (self.cache, self.tok, self.pos, self.rng,
             self.done) = self.engine.step(self.engine.params, self.cache,
                                           self.tok, self.typ, self.pos,
                                           self.rng, self.done)
        toks = np.asarray(self.tok)             # ONE host pull per step
        for slot in active:
            req = self._slot_req[slot]
            t = int(toks[slot])
            if t == self.engine.eos_id:
                self._retire(slot, finished)
                continue
            req.out.append(t)
            if len(req.out) >= req.max_new:
                self._retire(slot, finished)
        return finished

    def _speculative_round(self, active, finished):
        """One draft + verify round over the whole slot array: up to
        γ+1 tokens per active slot, same two programs every round."""
        spec, eng = self.spec, self.engine
        if spec.stochastic:
            # the stochastic draft/verify programs thread the server's
            # rng (drafter sampling, acceptance uniforms, residual and
            # bonus draws all come from the one carried key chain)
            spec.dcache, drafts, dprobs, self.rng = spec.draft(
                spec.dparams, spec.dcache, self.prev_tok, self.prev_typ,
                self.tok, self.typ, self.pos, self.rng)
        else:
            spec.dcache, drafts = spec.draft(
                spec.dparams, spec.dcache, self.prev_tok, self.prev_typ,
                self.tok, self.typ, self.pos)
        if self.pager is not None:
            for slot in active:
                # pages covering the whole verify window [pos, pos+γ];
                # writes past logical capacity route to the garbage page
                self.pager.ensure_range(
                    slot, int(self.pager.pos[slot]) + spec.gamma)
            pt = self.pager.device_table()
            if spec.stochastic:
                (self.cache, emitted, acc, self.tok, self.prev_tok,
                 self.pos, self.done, self.rng) = spec.paged_verify(
                    eng.params, self.cache, pt, self.tok, self.typ,
                    self.pos, drafts, dprobs, self.done, self.rng)
            else:
                (self.cache, emitted, acc, self.tok, self.prev_tok,
                 self.pos, self.done) = spec.paged_verify(
                    eng.params, self.cache, pt, self.tok, self.typ,
                    self.pos, drafts, self.done)
        elif spec.stochastic:
            (self.cache, emitted, acc, self.tok, self.prev_tok,
             self.pos, self.done, self.rng) = spec.verify(
                eng.params, self.cache, self.tok, self.typ, self.pos,
                drafts, dprobs, self.done, self.rng)
        else:
            (self.cache, emitted, acc, self.tok, self.prev_tok,
             self.pos, self.done) = spec.verify(
                eng.params, self.cache, self.tok, self.typ, self.pos,
                drafts, self.done)
        # every verified token came out of the TARGET's argmax stream,
        # so the verify round leaves prev pointing at a reply-typed token
        self.prev_typ = self.typ
        em, ac, ph = jax.device_get((emitted, acc, self.pos))  # ONE pull
        for slot in active:
            req = self._slot_req[slot]
            a = int(ac[slot])
            self._spec_totals["rounds"] += 1
            self._spec_totals["drafted"] += spec.gamma
            self._spec_totals["accepted"] += max(a - 1, 0)
            self._spec_totals["corrected"] += min(a, 1)
            self._drafted[slot] += spec.gamma
            self._accepted[slot] += max(a - 1, 0)
            if a == 0:
                # the row latched done in an EARLIER round (capacity):
                # the non-speculative server would emit eos now — retire
                self._retire(slot, finished)
                continue
            retired = False
            for t in em[slot, :a]:
                t = int(t)
                if t == eng.eos_id:
                    self._retire(slot, finished)
                    retired = True
                    break
                req.out.append(t)
                if len(req.out) >= req.max_new:
                    self._retire(slot, finished)
                    retired = True
                    break
            if not retired and self.pager is not None:
                # roll rejected speculative pages back to the accepted
                # frontier — host bookkeeping only
                self.pager.truncate(slot, int(ph[slot]))
        return finished

    def swap_base_params(self, new_params, *, force: bool = False):
        """Promote refreshed BASE weights into the live server (the
        train-while-serve hot swap, online/swap.py).

        Contract (docs/SERVING.md "Online personalization"): call with
        NO active slots — ``drain()`` first — so every per-user delta
        has already been evicted through the bitwise base-restore path
        and every in-flight greedy reply finished under the weights it
        was admitted with. Every jitted program (prefill, step,
        paged_step, draft, verify) takes params per call, and the new
        leaves are placed onto each old leaf's sharding and dtype, so
        the swap re-uses every compile: caches stay at 1 through it.
        The attached personalization index is rebased to the new
        weights so post-swap admissions scatter deltas over (and
        evictions restore) the NEW base.

        ``force=True`` swaps under active slots anyway (counted in
        ``dirty_swaps``): in-flight requests continue under the NEW
        weights and any resident per-user delta is dropped, so greedy
        parity across the boundary is knowingly broken — only the
        ``online_loop`` audit target's mutation arm should do this.
        """
        old = self.personalize.base if self.personalize is not None \
            else self.engine.params
        old_leaves, old_def = jax.tree_util.tree_flatten(old)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if new_def != old_def:
            raise ValueError(
                "swap_base_params: incoming params tree does not match "
                "the serving tree — wrong model/config")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            if tuple(np.shape(o)) != tuple(np.shape(n)):
                raise ValueError(
                    f"swap_base_params: leaf {i} has shape {np.shape(n)},"
                    f" serving expects {np.shape(o)} — wrong model/config")
        active = [s for s, r in enumerate(self._slot_req)
                  if r is not None]
        if active and not force:
            raise RuntimeError(
                f"swap_base_params with {len(active)} active slot(s) — "
                f"drain() first so per-user deltas evict (bitwise base "
                f"restore) and in-flight replies finish under their "
                f"admission-time weights, or pass force=True to break "
                f"parity knowingly")
        # placement preserves each old leaf's jit CALL SIGNATURE, not
        # just its sharding: jit caches key on whether an argument is
        # committed to its device, so an uncommitted serving leaf (the
        # common single-chip case — model.init output) must be replaced
        # by an uncommitted array (host-roundtripped jnp.asarray), while
        # a committed leaf (TP-sharded serving) takes an explicit
        # device_put onto the old sharding. Mixing them grows a second
        # cache entry per program on the first swap.
        def _place(o, n):
            if isinstance(o, jax.Array) and getattr(o, "_committed",
                                                    False):
                return jax.device_put(jnp.asarray(n, dtype=o.dtype),
                                      o.sharding)
            return jnp.asarray(np.asarray(n), dtype=o.dtype)

        placed = jax.tree_util.tree_unflatten(old_def, [
            _place(o, n) for o, n in zip(old_leaves, new_leaves)])
        self.engine.params = placed
        if self.personalize is not None:
            self.personalize.rebase(placed, force=force)
        self.swaps_done += 1
        if active:
            self.dirty_swaps += 1
        if self.spec is not None:
            # reset the since-swap window; spec.dparams stays on its
            # pre-swap snapshot (see the constructor comment)
            self._spec_swap_mark = dict(self._spec_totals)
        return placed

    def stats(self) -> Dict[str, object]:
        """Speculation counters: drafted/accepted/corrected totals, the
        aggregate acceptance rate (accepted drafts / drafted), and the
        per-slot acceptance rate over each slot's CURRENT occupancy
        (None for slots that have not drafted since admission). Paged
        servers additionally report the KV pool's HBM accounting:
        ``kv_quant`` mode, total pool bytes (k + v + scale arrays, all
        layers), and the capacity multiplier vs f32 pools at the same
        page count — the ``users_per_chip_at_fixed_hbm_x`` lever
        (ops/kv_quant.py). KV state is TRANSIENT: none of this enters
        checkpoint fingerprints (tests/test_serving_kv_quant.py pins that a
        checkpoint roundtrip is kv_quant-agnostic)."""
        if self.spec is None:
            s: Dict[str, object] = {"speculate_k": 0}
        else:
            s = dict(self._spec_totals)
            s["speculate_k"] = self.spec.gamma
            s["acceptance_rate"] = (s["accepted"] / s["drafted"]
                                    if s["drafted"] else None)
            s["per_slot_acceptance"] = [
                (float(self._accepted[i] / self._drafted[i])
                 if self._drafted[i] else None)
                for i in range(self.slots)]
            # windowed on the last swap_base_params: with the drafter
            # pinned to its pre-swap snapshot, a falling value here IS
            # the personalization-drift signal (how far online training
            # has moved the target since the drafter last saw it)
            dsw = s["drafted"] - self._spec_swap_mark["drafted"]
            asw = s["accepted"] - self._spec_swap_mark["accepted"]
            s["drafted_since_swap"] = dsw
            s["accepted_since_swap"] = asw
            s["acceptance_rate_since_swap"] = (asw / dsw) if dsw else None
        if self.pager is not None:
            from commefficient_tpu.ops import kv_quant as kvq
            cfg = self.engine.model.config
            hd = cfg.n_embd // cfg.n_head
            args = (self.pager.num_pages, self.pager.page_size,
                    cfg.n_head, hd, cfg.n_layer)
            s["kv_quant"] = self.kv_quant
            s["kv_pool_bytes"] = kvq.pool_bytes(
                *args, self.kv_quant,
                base_dtype=np.dtype(cfg.jnp_dtype))
            s["kv_capacity_multiplier_vs_f32"] = \
                kvq.capacity_multiplier_vs_f32(*args, self.kv_quant)
        # multi-host axes: TP degree, prefill/decode split, and per-shard
        # routing — admitted/spilled per slot pool, plus the store's own
        # shard read/write counters when a personalization index is
        # attached, so a caller can read routing skew directly
        s["swaps_done"] = self.swaps_done
        s["dirty_swaps"] = self.dirty_swaps
        s["tp"] = self.engine.tp
        s["disaggregated"] = self.disaggregate
        if self.disaggregate:
            s["prefill_slots"] = self.prefill_slots
        s["num_shards"] = self.num_shards
        s["slots_per_shard"] = self.slots_per_shard
        s["admitted_per_shard"] = [int(x) for x in
                                   self._admitted_per_shard]
        s["spilled_per_shard"] = [int(x) for x in self._spilled_per_shard]
        total_admitted = int(self._admitted_per_shard.sum())
        s["routing_skew"] = (
            float(self._admitted_per_shard.max()
                  / (total_admitted / self.num_shards))
            if total_admitted else None)
        if self.personalize is not None:
            store = self.personalize.store
            s["store_shard_reads"] = [int(x) for x in store.shard_reads]
            s["store_shard_writes"] = [int(x) for x in store.shard_writes]
        return s

    def run(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Step until every submitted request has a reply."""
        replies: Dict[int, List[int]] = {}
        while self._queued() or any(r is not None for r in self._slot_req):
            for rid, toks in self.step():
                replies[rid] = toks
            max_steps -= 1
            if max_steps <= 0:
                raise RuntimeError("serving loop exceeded max_steps")
        return replies

    def drain(self, max_steps: int = 100_000):
        """Graceful preemption shutdown: stop admissions, finish the
        in-flight slots, and hand back what never started.

        Returns ``(replies, leftovers)``: ``replies`` maps rid ->
        reply tokens for every request that had already been admitted
        (their decode completes here — admitted work is never thrown
        away); ``leftovers`` is the undispatched queue — owner-shard and
        anonymous queues merged back into submission order — as
        ``(ids, types, reply_type, max_new)`` tuples (plus a trailing
        ``user_id`` for personalized requests, so re-submission routes
        to the same owner shard) a replacement server can re-``submit``
        verbatim. Because slot rows
        decode independently and greedy sampling is deterministic,
        resubmitting a leftover on a fresh server over the same
        checkpoint yields the reply this server would have produced
        (tests/test_decode.py)."""
        queued = sorted([r for q in [self._queue] + self._shard_queue
                         for r in q], key=lambda r: r.rid)
        leftovers = [(list(r.ids), list(r.types), r.reply_type, r.max_new)
                     + ((r.user_id,) if r.user_id is not None else ())
                     for r in queued]
        self._queue.clear()
        for q in self._shard_queue:
            q.clear()
        replies: Dict[int, List[int]] = {}
        while any(r is not None for r in self._slot_req):
            for rid, toks in self.step():
                replies[rid] = toks
            max_steps -= 1
            if max_steps <= 0:
                raise RuntimeError("drain exceeded max_steps")
        return replies, leftovers
