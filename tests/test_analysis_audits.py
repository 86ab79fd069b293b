"""Audit-at-HEAD: the repo's production programs pass the graft-audit
invariant rules on CPU.

These are the machine-checked versions of claims that previously lived
in comments and docs: the federated round materializes no dense client
or changed matrices, the flash kernels keep (B, H, T, T) out of HBM
(verified *inside* the custom_vjp/remat sub-jaxprs for the first time),
nothing in a jitted region calls back to the host, and the round's
compile cache stays flat after warmup.  The ``audit`` marker lets the
gate run standalone (``pytest -m audit``); the CLI equivalent is
``python -m commefficient_tpu.analysis --target all``.
"""

import pytest

from commefficient_tpu import analysis as A

pytestmark = pytest.mark.audit


@pytest.fixture(scope="module")
def audited():
    """One audit per target, traced once and shared across asserts."""
    cache = {}

    def get(kind, idx=0, with_retrace=False):
        key = (kind, idx, with_retrace)
        if key not in cache:
            cache[key] = A.build_targets(kind)[idx].audit(
                with_retrace=with_retrace)
        return cache[key]

    return get


@pytest.mark.parametrize("mode_idx,mode", [(0, "sketch"), (1, "local_topk")])
def test_round_audit_passes(audited, mode_idx, mode):
    rep = audited("round", mode_idx)
    assert rep.target == f"round/{mode}"
    assert rep.ok, rep.format()


def test_round_retrace_guard_zero_recompiles(audited):
    """The jitted round does not retrace after warmup across 3 further
    rounds with fresh client samples and batches (driven through the
    real train_round_async dispatch, under the conftest-wide
    transfer_guard)."""
    rep = audited("round", 0, with_retrace=True)
    assert rep.ok, rep.format()
    rt = rep.rule("retrace")
    assert rt.checked_eqns == 4  # 1 warmup + 3 measured calls


@pytest.mark.parametrize("idx,variant", [(0, "local_topk"), (1, "sketch")])
def test_round_bucketed_audit_passes_with_retrace(audited, idx, variant):
    """The K=4 bucketed round passes the transmit-structure rules (no
    monolithic (W, d) reduce or (d,) sketch scatter, >=2 independent
    per-bucket transmit ops) AND stays retrace-flat when driven through
    train_round_async.  The negative direction — the audit FAILS when
    buckets are re-concatenated before compression — is pinned by the
    mutation test in tests/test_grad_buckets.py."""
    rep = audited("round_bucketed", idx, with_retrace=True)
    assert rep.target == f"round_bucketed/{variant}"
    assert rep.ok, rep.format()
    assert rep.rule("bucketed").ok


def test_sketch_batched_audit_passes_with_retrace(audited):
    """The per-worker sketch round (max_grad_norm forces the non-fused
    path) runs the BATCHED Pallas sketch kernel inside the worker vmap:
    a pallas_call producing the (W, r, c_eff) table, no (W, ·) routing
    scatter — and the compile cache stays at 1 across drives under
    force_dispatch('kernel') (one context around warmup + drives, so the
    guard is not vacuous)."""
    rep = audited("sketch_batched", 0, with_retrace=True)
    assert rep.target == "sketch_batched/per-worker"
    assert rep.ok, rep.format()
    bs = rep.rule("batched_sketch")
    assert bs.ok and "pallas_calls seen: 1" in bs.notes
    assert rep.stats.visited("pallas_call"), rep.stats.descended_into


def test_sketch_batched_audit_fails_under_forced_fallback():
    """Mutation: the SAME round traced with force_dispatch('fallback') —
    the program a batch-guard revert would produce — must FAIL the
    batched_sketch rule, with the vmapped (W, c_eff) routing scatter
    named in the violations.  This is what makes the PASS at HEAD
    meaningful."""
    from commefficient_tpu.analysis.targets import sketch_batched_target

    rep = sketch_batched_target(mutate=True).audit(with_retrace=False)
    assert rep.target == "sketch_batched/per-worker(mutated)"
    assert not rep.ok
    bs = rep.rule("batched_sketch")
    assert not bs.ok
    msgs = " ".join(v.message for v in bs.violations)
    assert "vmapped XLA sketch routing" in msgs
    assert "no pallas_call" in msgs


@pytest.mark.parametrize("idx,mode,kernels", [(0, "true_topk", 3),
                                              (1, "sketch", 5)])
def test_server_update_fused_audit_passes_with_retrace(audited, idx, mode,
                                                       kernels):
    """The ISSUE-20 fused server update: the streaming radix/select
    pallas_calls are in the traced program, no top_k/sort runs over the
    d-stream, the live-(d,) output count sits at the fused budget, and
    the compile cache stays at 1 across drives under
    force_dispatch('kernel'). Sketch mode holds two kernels more: the
    one estimates pass the counts and the select stream
    (estimates_pallas), and the dense re-sketch of the update
    (sketch_vec_pallas)."""
    rep = audited("server_update_fused", idx, with_retrace=True)
    assert rep.target == f"server_update_fused/{mode}"
    assert rep.ok, rep.format()
    fr = rep.rule("fused_server_update")
    assert fr.ok and f"pallas_calls seen: {kernels}" in fr.notes
    assert rep.stats.visited("pallas_call"), rep.stats.descended_into


@pytest.mark.parametrize("mode", ["true_topk", "sketch"])
def test_server_update_fused_audit_fails_on_rematerialized_chain(mode):
    """Mutation: the SAME server update traced with
    force_dispatch('fallback') — the re-materialized estimates ->
    scores -> sort -> mask -> where chain a dispatch revert would
    produce — must FAIL all three claims: missing pallas_calls,
    a sort-unit selection over the d-stream, and a live-(d,) count
    above the fused budget."""
    from commefficient_tpu.analysis.targets import server_update_fused_target

    rep = server_update_fused_target(mode, mutate=True).audit(
        with_retrace=False)
    assert rep.target == f"server_update_fused/{mode}(mutated)"
    assert not rep.ok
    fr = rep.rule("fused_server_update")
    assert not fr.ok
    msgs = " ".join(v.message for v in fr.violations)
    assert "sort-unit selection over the d-stream" in msgs
    assert "expected >= 2 pallas_call" in msgs
    assert "exceed the fused-path budget" in msgs


def test_gpt2_train_step_audit_passes_and_visits_remat(audited):
    rep = audited("gpt2")
    assert rep.ok, rep.format()
    assert rep.stats.visited("remat2"), rep.stats.descended_into


def test_flash_attention_fwd_audit_visits_custom_vjp(audited):
    rep = audited("attention", 0)
    assert rep.ok, rep.format()
    assert rep.stats.visited("custom_vjp_call"), \
        rep.stats.descended_into
    assert rep.stats.visited("pallas_call"), rep.stats.descended_into


def test_flash_attention_bwd_audit_passes(audited):
    """grad() inlines the custom-VJP bwd, so this trace contains the
    dq/dkv pallas kernels — and still no (B, H, T, T) aval anywhere."""
    rep = audited("attention", 1)
    assert rep.ok, rep.format()
    assert rep.stats.visited("pallas_call"), rep.stats.descended_into


def test_sketch_audit_passes(audited):
    rep = audited("sketch")
    assert rep.ok, rep.format()


def test_decode_step_audit_passes_with_zero_retrace(audited):
    """The serving decode step: no (B, H, T, T) aval (single-query
    attention is (B, H, 1, S)), no host callbacks inside the jit, and the
    compile cache stays at one entry while the step is driven with
    evolving cache/position/done state — the continuous-batching server's
    core invariant."""
    rep = audited("decode", 0, with_retrace=True)
    assert rep.target == "decode/step"
    assert rep.ok, rep.format()


def test_decode_generate_audit_passes_and_visits_scan(audited):
    """The fully-jitted generate program (prefill + lax.scan of decode
    steps with in-loop sampling): the audit descends into the scan body
    and finds no quadratic aval, no transfer, no retrace across prompts
    of different content (same shapes)."""
    rep = audited("decode", 1, with_retrace=True)
    assert rep.target == "decode/generate"
    assert rep.ok, rep.format()
    assert rep.stats.visited("scan"), rep.stats.descended_into


def test_transfer_guard_active_in_suite():
    """conftest.py arms jax.transfer_guard('disallow') around every
    round dispatch for the whole test session."""
    from commefficient_tpu.federated import api

    assert api.transfer_guard_mode() == "disallow"


def test_gate_cli_exits_zero_at_head(capsys):
    """The graft-audit gate (console script / python -m) passes at HEAD
    and prints a structured per-rule report."""
    from commefficient_tpu.analysis.__main__ import main

    rc = main(["--target", "round", "--no-retrace", "--prng-lint"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "footprint" in out and "transfer" in out and "prng" in out
    assert "audit: round/sketch" in out
