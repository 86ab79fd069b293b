"""bench.py per-metric isolation.

One metric that raises must not cost the other metrics their numbers: it
reports None plus an ``errors`` entry, the ONE JSON line is still
printed — and the process then exits non-zero, so a failed metric is
never mistaken for a complete run. Host-side only, no accelerator.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_error_is_recorded_and_the_metric_runs_once():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("shapes (4, 46) and (8,) are incompatible")

    errors = []
    assert bench._run_metric("m", broken, errors) is None
    assert len(calls) == 1  # no retry: a failure is a failure
    assert errors[0]["metric"] == "m"
    assert "incompatible" in errors[0]["error"]


def test_isolation_one_bad_metric_does_not_poison_the_next():
    errors = []
    a = bench._run_metric("a", lambda: 1.0, errors)
    b = bench._run_metric(
        "b", lambda: (_ for _ in ()).throw(RuntimeError("DEADLINE_EXCEEDED")),
        errors)
    c = bench._run_metric("c", lambda: 3.0, errors)
    assert (a, b, c) == (1.0, None, 3.0)
    assert [e["metric"] for e in errors] == ["b"]


def test_main_emits_json_and_exits_nonzero_on_failed_metrics(
        monkeypatch, capsys):
    """The acceptance contract: when metrics die bench.py still produces
    its ONE JSON line — the survivors' numbers intact, the casualties
    listed under ``errors``, the offload gather/scatter overlap merged
    into breakdown_ms — and then exits non-zero."""
    import contextlib
    import json

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        "commefficient_tpu.utils.logging.profile_ctx",
        lambda _: contextlib.nullcontext())
    monkeypatch.setattr(bench, "bench_cifar_sketch",
                        lambda approx_recall=0.95:
                        (2.5, {"topk_approx_recall": approx_recall,
                               "round_throughput_ms": 400.0}))
    monkeypatch.setattr(
        bench, "bench_gpt2_tokens",
        lambda attn_impl="full", B=8, T=256, attn_dropout="auto",
        per_dispatch=True: (1000.0, 900.0 if per_dispatch else None))
    monkeypatch.setattr(
        bench, "bench_flash_dropout_kernel_ab",
        lambda T=256, rate=0.1, blocks=None:
        (1.3, {f"flash_dropout_bq{T}_bk{T}_ms": 8.0,
               "xla_full_prob_dropout_ms": 10.4,
               "best_flash_dropout_ms": 8.0}))
    monkeypatch.setattr(
        bench, "bench_gpt2_fused_ce_ab",
        lambda T=512: (1.1, {"materialized_logits_tok_s": 60_000.0,
                             "fused_ce_tok_s": 66_000.0}))
    monkeypatch.setattr(
        bench, "bench_gpt2_bucketed_rounds",
        lambda T=256, Ks=(1, 4, 16):
        (1.2, {f"bucketed_K{K}_ms": 100.0 / (1.0 + 0.1 * i)
               for i, K in enumerate(Ks)}))

    monkeypatch.setattr(
        bench, "bench_generate",
        lambda batch=8, prompt_len=128, new_tokens=64, ab_uncached=False:
        (5000.0 * batch, {"batch": batch, "prefill_ms": 3.0,
                          "decode_per_token_ms": 0.2,
                          "decode_flat_in_prefix_ratio": 1.0}))

    monkeypatch.setattr(
        bench, "bench_checkpoint_overhead",
        lambda every_rounds=100: {
            "save_ms": 12.0, "verify_ms": 3.0, "load_ms": 9.0,
            "bytes": 1 << 20, "round_ms": 800.0,
            "amortized_per_round_ms": 0.12,
            "amortized_overhead_pct": 0.015,
            "checkpoint_every_rounds": every_rounds})

    monkeypatch.setattr(
        bench, "bench_per_worker_sketch_ab",
        lambda d, W, r, c: (1.4, {"kernel_ms": 5.0, "xla_ms": 7.0,
                                  "bitwise_equal": True,
                                  "d": d, "W": W, "r": r, "c": c}))
    monkeypatch.setattr(
        bench, "bench_client_store_sketched_codec",
        lambda: (1.05, {"global_total_ms": 10.0, "tiled_total_ms": 9.5}))
    monkeypatch.setattr(
        bench, "bench_server_update_fused_ab",
        lambda **kw: (1.6, {"true_topk_speedup_x": 2.1,
                            "sketch_speedup_x": 1.6,
                            "true_topk_bitwise_equal": True,
                            "sketch_bitwise_equal": True}))
    monkeypatch.setattr(
        bench, "bench_topk_hierarchical_ab",
        lambda **kw: (1.8, {"k50000_kernel_ms": 4.0,
                            "k50000_sort_unit_ms": 7.2}))

    monkeypatch.setattr(
        bench, "bench_client_store_gather_scatter",
        lambda **kw: {"gather_ms_1m": 5.0, "scatter_ms_1m": 4.0,
                      "arena_bytes_1m": 512 << 20,
                      "gather_ms_10k": 4.0, "scatter_ms_10k": 3.5})
    monkeypatch.setattr(
        bench, "bench_buffered_rounds",
        lambda **kw: {"round_sync_ms": 50.0,
                      "round_buffered_lockstep_ms": 52.0,
                      "cohort_buffered_faulted_ms": 60.0,
                      "event_loop_overhead_ms": 8.0,
                      "faulted_sim_time": 12.0,
                      "faulted_applies_per_cohort": 0.9})
    monkeypatch.setattr(
        bench, "bench_buffered_mesh_rounds",
        lambda **kw: (1.01, {"round_lockstep_single_ms": 52.0,
                             "round_lockstep_dp2_ms": 52.5,
                             "cohort_faulted_hetk_dp2_ms": 61.0,
                             "event_loop_overhead_ms": 8.5,
                             "faulted_sim_time": 12.0}))
    monkeypatch.setattr(
        bench, "bench_decode_paged_ab",
        lambda **kw: (1.02, {"paged_tokens_per_sec_b64": 50_000.0,
                             "fixed_tokens_per_sec_b64": 49_000.0,
                             "users_per_chip_at_fixed_hbm_x_b64": 2.1}))
    monkeypatch.setattr(
        bench, "bench_decode_paged_quant_ab",
        lambda **kw: (0.98, {"int8_tokens_per_sec_b64": 49_000.0,
                             "f32_tokens_per_sec_b64": 50_000.0,
                             "kv_capacity_multiplier_vs_f32": 3.9689,
                             "users_per_chip_at_fixed_hbm_x_b64": 8.3}))
    monkeypatch.setattr(
        bench, "bench_decode_speculative_ab",
        lambda **kw: (1.15, {"method": kw.get("method", "greedy"),
                             "spec_g0_b8_tokens_per_sec": 50_000.0,
                             "spec_g4_b8_tokens_per_sec": 57_500.0,
                             "acceptance_rate_g4_b8": 0.31,
                             "spec_selfdraft_g8_b8_tokens_per_sec":
                                 120_000.0}))
    monkeypatch.setattr(
        bench, "bench_decode_speculative_personalized",
        lambda **kw: (0.9, {"personalized_g0_tokens_per_sec": 48_000.0,
                            "personalized_g4_tokens_per_sec": 43_200.0,
                            "base_drafter_acceptance_rate": 0.55}))
    monkeypatch.setattr(
        bench, "bench_personalized_admission",
        lambda **kw: {"admission_delta_apply_ms": 1.5,
                      "eviction_restore_ms": 1.7, "prefill_ms": 30.0,
                      "overhead_vs_prefill_pct": 5.0,
                      "k": 256, "d": 124_000_000, "n_users": 16})
    monkeypatch.setattr(
        bench, "bench_decode_tp_ab",
        lambda **kw: (0.99, {"tp1_tokens_per_sec_b64": 50_000.0,
                             "tp2_tokens_per_sec_b64": 49_500.0,
                             "users_per_fleet_at_fixed_hbm_x_b64_tp2":
                                 4.2}))
    monkeypatch.setattr(
        bench, "bench_serve_disagg_latency",
        lambda **kw: (3.5, {"unified_decode_step_p99_ms": 70.0,
                            "disagg_decode_step_p99_ms": 20.0,
                            "unified_decode_step_p50_ms": 5.0,
                            "disagg_decode_step_p50_ms": 5.2,
                            "prefill_slots": 2}))
    monkeypatch.setattr(
        bench, "bench_online_swap_latency",
        lambda **kw: (45.0, {"swap_to_serving_p50_ms": 45.0,
                             "swap_to_serving_p99_ms": 80.0,
                             "n_swaps": 6, "drained_total": 48,
                             "resubmitted_total": 48, "dirty_swaps": 0,
                             "paged_step_cache": 1,
                             "paged_insert_cache": 1}))
    monkeypatch.setattr(
        bench, "bench_online_acceptance_drift_ab",
        lambda **kw: (0.62, {"gamma": 4, "slots": 8,
                             "acceptance_pre_swap": 1.0,
                             "acceptance_since_swap_eps0.08": 0.62}))

    def dead(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    monkeypatch.setattr(bench, "bench_gpt2_sketch_rounds", dead)
    monkeypatch.setattr(bench, "bench_longcontext_tokens", dead)
    monkeypatch.setattr(bench, "bench_offload_overlap",
                        lambda: {"offload_round_sync_ms": 50.0,
                                 "offload_round_async_ms": 30.0,
                                 "offload_gather_ms": 10.0,
                                 "offload_scatter_ms": 8.0,
                                 "offload_gather_scatter_overlap_ms": 20.0})
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 2.5
    assert out["breakdown_ms"]["offload_gather_scatter_overlap_ms"] == 20.0
    metrics = {e["metric"] for e in out["extra_metrics"]}
    assert "gpt2_personachat_tokens_per_sec_chip" in metrics
    assert "gpt2_decode_tokens_per_sec_chip_b64" in metrics
    assert "gpt2_fetchsgd_bucketed_rounds_t512_ab" in metrics
    assert "gpt2_fused_ce_t512_ab" in metrics
    assert "checkpoint_save_restore_overhead" in metrics
    assert "cifar10_resnet9_per_worker_sketch_ab" in metrics
    assert "gpt2_fetchsgd_per_worker_sketch_ab" in metrics
    assert "client_store_sketched_codec" in metrics
    assert "gpt2_server_update_fused_ab" in metrics
    assert "topk_hierarchical_ab" in metrics
    assert "buffered_mesh_round_overhead_ab" in metrics
    assert "gpt2_decode_paged_tokens_per_sec_ab" in metrics
    assert "gpt2_decode_paged_quant_ab" in metrics
    assert "gpt2_decode_speculative_tokens_per_sec_ab" in metrics
    assert "gpt2_decode_speculative_topk_stochastic_ab" in metrics
    assert "gpt2_decode_speculative_personalized_ab" in metrics
    assert "serve_personalized_admission_overhead" in metrics
    assert "gpt2_decode_tp_tokens_per_sec_ab" in metrics
    assert "serve_disagg_decode_latency_ab" in metrics
    assert "gpt2_online_swap_latency" in metrics
    assert "gpt2_online_acceptance_drift_ab" in metrics
    # the dead metrics are absent from the numbers but present in errors
    assert "gpt2_fetchsgd_sketch_rounds_per_sec" not in metrics
    failed = {e["metric"] for e in out["errors"]}
    assert "gpt2_fetchsgd_sketch_rounds_per_sec" in failed
    assert all("RESOURCE_EXHAUSTED" in e["error"] for e in out["errors"])
