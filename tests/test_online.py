"""Train-while-serve (commefficient_tpu/online/): the hot-swap and
collection contracts at tiny scale.

The anchors:

* SWAP PARITY — across a drain->swap, every request admitted BEFORE the
  swap finishes with the exact greedy tokens of the old weights, every
  leftover resubmitted AFTER it serves the exact greedy tokens of the
  new weights, and the server's compiled step/pack programs do NOT grow
  (the swap re-places leaves onto the old shardings; params cross every
  serving jit as traced arguments);
* the FINGERPRINT GATE refuses foreign weights BEFORE anything is
  drained — the server keeps serving its old weights, untouched;
* the collector's shard routing IS the client store's ``owner`` (an
  interaction is collected where its user's state row lives);
* drained leftovers come back VERBATIM (the coordinator resubmits the
  exact queue entries);
* SIGKILL landing mid-swap-boundary-save (inside ``save_checkpoint``,
  via COMMEFF_CRASH_POINT) leaves the previous checkpoint live and
  ``--resume auto`` finishes the online run (in-flight requests lost by
  contract, collected-but-untrained interactions restored).

This module builds its OWN tiny engine (unlike test_paged_serving /
test_speculative, which share the session engine): swaps mutate
``engine.params``, and a shared engine would leak the mutation into the
other suites' bitwise asserts.
"""

import os
import signal
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from commefficient_tpu.data.tokenizer import ByteTokenizer
from commefficient_tpu.online import (HotSwapCoordinator,
                                      InteractionCollector)
from commefficient_tpu.serving import (ContinuousBatchingServer,
                                       DecodeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def own_engine():
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    tok = ByteTokenizer()
    cfg = GPT2Config.tiny(vocab_size=tok.vocab_size)
    model = GPT2DoubleHeads(cfg)
    ids = np.zeros((1, 1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids,
                        np.zeros((1, 1), np.int32), train=False)["params"]
    eos = tok.convert_tokens_to_ids("<eos>")
    engine = DecodeEngine(model, params, eos_id=eos, max_len=48,
                          method="greedy")
    return tok, engine


def _prompts(tok, n):
    texts = ["hello there", "do you like fish", "the weather is nice",
             "tell me a story", "what is your name", "where are you from",
             "sing me a song", "how old are you"][:n]
    out = []
    for t in texts:
        ids = tok.encode(t)
        out.append((ids, [1] * len(ids)))
    return out


def _perturb(params):
    """A deterministic, decisively token-flipping weight change."""
    def f(x):
        x = np.asarray(x)
        bump = 0.1 * np.sin(np.arange(x.size, dtype=np.float32))
        return (x + bump.reshape(x.shape)).astype(x.dtype)
    return jax.tree.map(f, params)


def _solo(engine, prompts, max_new=8):
    return [engine.generate([(ids, types)], [types[-1]],
                            max_new=max_new)[0]
            for ids, types in prompts]


def test_swap_parity_and_compile_cache_stays_at_one(own_engine):
    """Pre-swap admissions finish on OLD weights, resubmitted leftovers
    serve NEW weights, and neither the paged step nor the pack program
    recompiles across the swap."""
    tok, engine = own_engine
    prompts = _prompts(tok, 6)
    old_params = engine.params
    solo_old = _solo(engine, prompts)

    srv = ContinuousBatchingServer(engine, slots=4, prefill_len=32,
                                   kv_cache="paged")
    rids = [srv.submit(ids, types, types[-1], 8) for ids, types in prompts]
    srv.step()                                  # 4 admitted, 2 queued
    step_c = engine.paged_step._cache_size()
    pack_c = engine.paged_insert._cache_size()

    coord = HotSwapCoordinator(srv)             # resubmits leftovers itself
    new_params = _perturb(old_params)
    replies, leftovers = coord.swap(new_params)
    assert coord.swaps_done == 1 and srv.swaps_done == 1
    assert len(replies) == 4 and len(leftovers) == 2
    for i, rid in enumerate(rids[:4]):          # old-weight parity, bitwise
        assert replies[rid] == solo_old[i]

    late = srv.run()                            # the resubmitted leftovers
    solo_new = _solo(engine, prompts)           # engine now serves new
    assert solo_new != solo_old                 # the perturbation is real
    assert sorted(map(tuple, late.values())) \
        == sorted(map(tuple, solo_new[4:]))
    # ONE compiled step + pack program through the whole swap
    assert engine.paged_step._cache_size() == step_c == 1
    assert engine.paged_insert._cache_size() == pack_c == 1
    # restore the module engine for later tests
    srv.drain()
    srv.swap_base_params(old_params)


def test_swap_under_active_slots_refused_without_force(own_engine):
    tok, engine = own_engine
    prompts = _prompts(tok, 1)
    old_params = engine.params
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged")
    srv.submit(*prompts[0], reply_type=1, max_new=8)
    srv.step()                                  # slot active
    with pytest.raises(RuntimeError, match="active"):
        srv.swap_base_params(_perturb(old_params))
    assert engine.params is old_params          # untouched
    srv.run()


def test_fingerprint_mismatch_refuses_and_server_keeps_serving(own_engine):
    """The gate runs BEFORE the drain: a refused swap leaves the server
    mid-decode with its old weights, and the in-flight request still
    finishes with the old greedy tokens."""
    tok, engine = own_engine
    prompts = _prompts(tok, 1)
    old_params = engine.params
    solo_old = _solo(engine, prompts)
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged")
    coord = HotSwapCoordinator(
        srv, expect_fingerprint={"entry": "gpt2_online", "k": 5})
    rid = srv.submit(*prompts[0], reply_type=1, max_new=8)
    srv.step()
    with pytest.raises(ValueError, match="hot swap refused") as ei:
        coord.swap(_perturb(old_params),
                   fingerprint={"entry": "gpt2_online", "k": 9})
    assert "k: incoming=9 serving=5" in str(ei.value)
    assert coord.refused == 1 and coord.swaps_done == 0
    assert srv.swaps_done == 0
    assert engine.params is old_params          # never touched
    replies = srv.run()                         # still serving, old weights
    assert replies[rid] == solo_old[0]


def test_collector_shard_routing_matches_host_store(own_engine):
    """collector.owner IS the store's owner: interactions land on the
    shard that owns the user's state row (HostArenaStore block layout)."""
    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.federated.client_store import (HostArenaStore,
                                                          make_codec)
    tok, engine = own_engine
    flat, _ = ravel_pytree(engine.params)
    cfg = FedConfig(mode="local_topk", error_type="local",
                    client_state="sparse", k=4,
                    num_clients=8).finalize(flat.shape[0])
    store = HostArenaStore(cfg, make_codec(cfg), num_shards=4)
    col = InteractionCollector(8, 32, store=store, eos_id=2)
    assert col.num_shards == 4
    for cid in range(8):
        assert col.owner(cid) == store.owner(cid)
    for cid, n in ((0, 2), (3, 1), (6, 3)):
        for _ in range(n):
            col.record(cid, [5, 6], [1, 1], [7, 8], 1)
    # owners: 0 -> shard 0, 3 -> shard 1, 6 -> shard 3
    assert col.pending_per_shard() == [2, 1, 0, 3]
    assert col.num_pending() == 6


def test_drain_leftovers_resubmitted_verbatim(own_engine):
    """The coordinator re-queues the exact queue entries the drain
    returned — same ids, types, reply type, budget, user routing."""
    tok, engine = own_engine
    prompts = _prompts(tok, 4)
    old_params = engine.params
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged")
    subs = [(ids, types, types[-1], 3 + i)
            for i, (ids, types) in enumerate(prompts)]
    for s in subs:
        srv.submit(*s)
    srv.step()                                  # 2 admitted, 2 queued
    coord = HotSwapCoordinator(srv)
    _, leftovers = coord.swap(_perturb(old_params))
    assert [tuple(lv[:4]) for lv in leftovers] \
        == [(list(s[0]), list(s[1]), s[2], s[3]) for s in subs[2:]]
    srv.run()
    srv.swap_base_params(old_params)


def test_swap_opens_a_new_acceptance_window(own_engine):
    """A hot swap moves the target and not the drafter, so acceptance
    drifts with the size of the move: the ``*_since_swap`` counters of
    ``stats()`` start again at every swap (what OnlineLoop logs as the
    drift), while the lifetime totals run on."""
    tok, engine = own_engine
    old_params = engine.params
    srv = ContinuousBatchingServer(engine, slots=2, prefill_len=32,
                                   kv_cache="paged", speculate_k=2)
    coord = HotSwapCoordinator(srv)

    def serve():
        for ids, types in _prompts(tok, 3):
            srv.submit(ids, types, types[-1], 8)
        srv.run()
        return srv.stats()

    st = serve()
    assert st["drafted_since_swap"] == st["drafted"] > 0
    assert st["acceptance_rate_since_swap"] == 1.0     # self-drafting
    coord.swap(_perturb(old_params))
    st = srv.stats()
    assert st["drafted_since_swap"] == st["accepted_since_swap"] == 0
    assert st["acceptance_rate_since_swap"] is None
    lifetime = st["drafted"]
    assert lifetime > 0
    st = serve()
    assert 0 < st["drafted_since_swap"] == st["drafted"] - lifetime
    # the drafter kept the old weights: the new target refuses some drafts
    assert st["acceptance_rate_since_swap"] < 1.0
    srv.drain()
    srv.swap_base_params(old_params)


# ---------------------------------------------------------------------------
# graft audit: the online_loop target (pass at head, fail on mutation)
# ---------------------------------------------------------------------------


@pytest.mark.audit
def test_online_loop_audit_passes_at_head():
    """The train-while-serve audit drives a real serve->collect->train->
    swap cycle: >= 2 clean swaps, compile caches at one program, strict
    no-(num_clients, d) footprint."""
    from commefficient_tpu.analysis.targets import online_loop_target
    rep = online_loop_target().audit(with_retrace=True)
    assert rep.target == "online_loop/cycle"
    assert rep.ok, rep


@pytest.mark.audit
def test_online_loop_audit_fails_on_forced_dirty_swap():
    """Skipping the drain (coordinator.swap(force=True) under active
    slots) must FAIL the audit — the negative control that keeps the
    online_loop gate honest. The failure is behavioral, so the retrace
    arm must run."""
    from commefficient_tpu.analysis.targets import online_loop_target
    rep = online_loop_target(mutate=True).audit(with_retrace=True)
    assert not rep.ok
    msgs = "\n".join(str(v) for r in rep.rule_reports
                     for v in r.violations)
    assert "dirty swap" in msgs
    assert "drain-before-swap" in msgs


# ---------------------------------------------------------------------------
# subprocess: SIGKILL mid-swap-boundary save, --resume auto
# ---------------------------------------------------------------------------

CHILD = textwrap.dedent("""
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from commefficient_tpu.training.gpt2 import main
    sys.exit(main(sys.argv[1:]))
""")

_ONLINE_ARGV = [
    "--mode", "local_topk", "--error_type", "local",
    "--client_state", "sparse", "--k", "16",
    "--server_mode", "buffered", "--serve_personalized", "--serve_online",
    "--serve_slots", "4", "--online_train_every", "2",
    "--online_swap_every", "1", "--max_seq_len", "64",
    "--lr_scale", "0.5", "--num_epochs", "1", "--seed", "3",
]


def _run_child(workdir, argv, env_extra=None, timeout=300):
    script = os.path.join(str(workdir), "child.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(CHILD)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("COMMEFF_CRASH_POINT", None)
    env.pop("COMMEFF_CRASH_AT_SAVE", None)
    if env_extra:
        env.update(env_extra)
    p = subprocess.Popen([sys.executable, script] + argv, env=env,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=timeout)
    return p.returncode, out


def test_online_sigkill_mid_swap_resume(tmp_path):
    """The online resume contract end-to-end: SIGKILL lands INSIDE the
    swap-boundary checkpoint save (after the temp-file fsync, before the
    atomic rename), so the run dies mid-swap with a torn second save on
    disk. ``--resume auto`` falls back to the swap-1 checkpoint,
    restores the collector pools + traffic cursor (in-flight requests
    lost by contract), and the online run still reaches its target
    swaps with the held-out trajectory intact."""
    ckpt = os.path.join(str(tmp_path), "ckpt")
    argv = _ONLINE_ARGV + [
        "--dataset_dir", os.path.join(str(tmp_path), "ds"),
        "--checkpoint_path", ckpt, "--checkpoint_every_rounds", "1"]
    rc, out = _run_child(
        tmp_path, argv,
        env_extra={"COMMEFF_CRASH_POINT": "ckpt_before_replace",
                   "COMMEFF_CRASH_AT_SAVE": "2"})
    assert rc == -signal.SIGKILL, out
    files = os.listdir(ckpt)
    assert any(f.endswith(".tmp") for f in files), files   # the torn save
    assert any(f.endswith(".npz") for f in files), files   # swap-1 survives
    rc, out = _run_child(tmp_path, argv + ["--resume", "auto"])
    assert rc == 0, out
    assert "resumed from" in out, out
    assert "online done: swaps=2" in out, out
    assert "'swaps': 2" in out and "'dirty_swaps': 0" in out, out
