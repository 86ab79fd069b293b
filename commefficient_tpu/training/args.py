"""CLI flag surface (reference utils.py:102-230 parse_args).

Flag-name parity with the reference where the concept survives; flags tied
to the process/NCCL machinery (--port, --num_devices, --share_ps_gpu,
--*_dataloader_workers) are gone — the mesh replaces them (--mesh).
"""

from __future__ import annotations

import argparse

from commefficient_tpu.config import DP_MODES, ERROR_TYPES, MODES, FedConfig
from commefficient_tpu.models import MODEL_REGISTRY

# --fused_ce auto threshold: at T >= this the (B*C*T, vocab) logits tensor
# is the batch's dominant activation and the chunked fused head wins on
# both HBM and (slightly) time; below it the materialized XLA path is
# faster (docs/ROOFLINE.md A/B at T=256 vs T=512)
FUSED_CE_AUTO_T = 512


def build_parser(default_lr: float = 0.4) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    # meta
    p.add_argument("--test", action="store_true", dest="do_test")
    p.add_argument("--mode", choices=MODES, default="sketch")
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--tensorboard", dest="use_tensorboard",
                   action="store_true")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the training loop "
                        "to DIR (the TPU analog of the reference's "
                        "cProfile hooks, SURVEY.md §5)")
    # model/data
    p.add_argument("--model", default="ResNet9",
                   choices=sorted(MODEL_REGISTRY))
    p.add_argument("--dataset_name", default="Synthetic",
                   choices=["CIFAR10", "CIFAR100", "EMNIST", "ImageNet",
                            "Synthetic", "PERSONA", "Digits", "Patches32"])
    p.add_argument("--dataset_dir", default="./dataset")
    p.add_argument("--batchnorm", action="store_true", dest="do_batchnorm")
    p.add_argument("--nan_threshold", type=float, default=999)
    p.add_argument("--eval_before_start", action="store_true",
                   help="run a validation pass before training "
                        "(ref cv_train.py:91)")
    p.add_argument("--checkpoint", action="store_true", dest="do_checkpoint")
    p.add_argument("--checkpoint_path", default="./checkpoint")
    p.add_argument("--checkpoint_every_rounds", type=int, default=0,
                   help="write a crash-consistent step checkpoint every N "
                        "rounds (0 = off) under --checkpoint_path, with a "
                        ".latest pointer and bounded retention; also arms "
                        "the SIGTERM/SIGINT finish-round-save-exit handler "
                        "(docs/ROBUSTNESS.md 'Preemption')")
    p.add_argument("--resume", default=None, metavar="auto|PATH",
                   help="resume training from a checkpoint: 'auto' picks "
                        "the newest valid checkpoint under "
                        "--checkpoint_path (fresh start if none), a path "
                        "names a file or directory. Restores learner "
                        "state, data-order cursor, and LR-schedule step; "
                        "a config-fingerprint mismatch fails loudly")
    p.add_argument("--finetune", action="store_true", dest="do_finetune")
    p.add_argument("--finetune_path", default="./finetune")
    # compression
    p.add_argument("--k", type=int, default=50000)
    p.add_argument("--num_cols", type=int, default=500000)
    p.add_argument("--num_rows", type=int, default=5)
    p.add_argument("--num_blocks", type=int, default=20)
    p.add_argument("--compute_dtype", choices=("float32", "bfloat16"),
                   default="float32",
                   help="model compute dtype (params stay float32)")
    p.add_argument("--sketch_scheme", choices=("tiled", "global"),
                   default="tiled",
                   help="tiled = TPU lane-tile windowed hashing (fast); "
                        "global = classic per-coordinate hashing")
    p.add_argument("--grad_buckets", type=int, default=1,
                   help="transmit buckets K (1 = monolithic): slice the "
                        "flat gradient into K layer-grouped chunks and "
                        "compress/reduce each as an independent op so XLA "
                        "overlaps bucket-k compression/psum with bucket-"
                        "(k+1) backward compute (docs/ROOFLINE.md Round 7)."
                        " Trajectory-equivalent to K=1 "
                        "(tests/test_grad_buckets.py)")
    p.add_argument("--topk_down", action="store_true", dest="do_topk_down")
    p.add_argument("--topk_approx_recall", type=float, default=0.0,
                   help="0 = exact top-k; in (0,1] = TPU approx_max_k with "
                        "this recall target (5.4x faster at d=124M)")
    p.add_argument("--server_fused", choices=("auto", "off"),
                   default="auto",
                   help="'auto' = exact server top-k recovery runs as the "
                        "fused streaming radix kernel where it dispatches "
                        "(bitwise-identical to the lax.top_k chain); "
                        "'off' = always the incumbent chain")
    # optimization
    p.add_argument("--local_momentum", type=float, default=0.0)
    p.add_argument("--virtual_momentum", type=float, default=0.0)
    p.add_argument("--weight_decay", type=float, default=5e-4)
    p.add_argument("--num_epochs", type=float, default=24)
    p.add_argument("--num_fedavg_epochs", type=int, default=1)
    p.add_argument("--fedavg_batch_size", type=int, default=-1)
    p.add_argument("--fedavg_lr_decay", type=float, default=1.0)
    p.add_argument("--error_type", choices=ERROR_TYPES, default="none")
    p.add_argument("--lr_scale", type=float, default=default_lr)
    p.add_argument("--scalar_lr_factor", type=float, default=None,
                   help="LR multiplier for scalar (size-1) params — the "
                        "Fixup recipe trains bias/scale scalars at 0.1x "
                        "(ref fed_aggregator.py:411-427 per-group LR "
                        "vector). Default: 0.1 for Fixup* models, 1.0 "
                        "otherwise")
    p.add_argument("--pivot_epoch", type=float, default=5)
    p.add_argument("--max_grad_norm", type=float, default=None)
    # federated dimensions + mesh
    p.add_argument("--num_clients", type=int, default=None,
                   help="None = the dataset's natural partition count")
    p.add_argument("--num_workers", type=int, default=1)
    p.add_argument("--local_batch_size", type=int, default=8)
    p.add_argument("--valid_batch_size", type=int, default=8)
    p.add_argument("--microbatch_size", type=int, default=-1)
    p.add_argument("--iid", action="store_true", dest="do_iid")
    p.add_argument("--client_state_offload", action="store_true",
                   help="keep per-client momentum/error/weight rows in "
                        "host arenas sharded across the mesh's 'clients' "
                        "axis (bounded by aggregate host RAM, not HBM — "
                        "the reference's shm design done TPU-natively); "
                        "each host owns its row shard and only the W "
                        "sampled rows move to device each round. "
                        "Trajectory-identical; needed for local_topk at "
                        "gpt2-small scale")
    p.add_argument("--client_state", choices=("dense", "sparse", "sketched"),
                   default="dense",
                   help="per-client row REPRESENTATION (composes with "
                        "--client_state_offload placement; federated/"
                        "client_store.py): 'dense' stores full (d,) rows; "
                        "'sparse' stores local_topk residuals as k "
                        "(index, value) pairs — exact by construction, "
                        "bitwise-identical trajectories under offload "
                        "(tests/test_client_store.py); 'sketched' stores "
                        "a per-client (rows, cols) CountSketch with "
                        "bounded divergence. O(k)/O(r*c) per client "
                        "instead of O(d) — the difference between 1M "
                        "clients fitting in host RAM or not "
                        "(docs/SCALING.md)")
    p.add_argument("--client_sketch_rows", type=int, default=3,
                   help="CountSketch rows r for --client_state sketched")
    p.add_argument("--client_sketch_cols", type=int, default=128,
                   help="CountSketch cols c for --client_state sketched")
    p.add_argument("--serve_personalized", action="store_true",
                   help="serve per-user weight deltas from the client "
                        "state store (serving/personalize.py): each "
                        "admitted request's O(k) idx/val row is applied "
                        "to the served params at admission and removed "
                        "at eviction. Requires --client_state sparse "
                        "(the only representation storing flat "
                        "coordinate rows); checkpoint fingerprints "
                        "record the representation and loading refuses "
                        "a mismatch")
    p.add_argument("--serve_sample", choices=("greedy", "topk"),
                   default="greedy",
                   help="serving-time sampling method for the decode "
                        "engine; both compose with --speculate_k "
                        "(greedy-prefix or stochastic acceptance)")
    p.add_argument("--speculate_k", type=int, default=0,
                   help="speculative decoding draft length γ "
                        "(serving/speculative.py): a small drafter "
                        "proposes γ tokens per slot and one multi-token "
                        "target forward verifies all γ+1 positions. "
                        "Under --serve_sample greedy, acceptance keeps "
                        "the longest argmax-matching prefix plus one "
                        "corrected token — output bitwise-identical to "
                        "non-speculative greedy decode; under topk, the "
                        "stochastic residual rule keeps the emitted "
                        "marginals exactly the non-speculative topk "
                        "distribution. 0 disables. Composes with paged "
                        "KV caches and --serve_personalized "
                        "(base-weights drafter is free). Checkpoint "
                        "fingerprints record the drafter; a mismatch "
                        "warns and serves non-speculative")
    p.add_argument("--kv_quant", choices=("none", "int8", "int4"),
                   default="none",
                   help="KV page-pool codec for paged serving "
                        "(ops/kv_quant.py): int8 stores pages with "
                        "per-page-per-head f32 scales, quantized at "
                        "write time and dequantized inside the paged "
                        "attention gather — ~4x pool HBM, so ~4x "
                        "users_per_chip_at_fixed_hbm_x, with replies "
                        "under a pinned tolerance contract instead of "
                        "bitwise parity; int4 is the nibble-packed "
                        "stretch mode (~8x). 'none' keeps full-precision "
                        "pools and bitwise greedy parity")
    p.add_argument("--serve_tp", type=int, default=1,
                   help="tensor-parallel serving degree (parallel/tp.py "
                        "+ serving/decode.py): served params take the "
                        "Megatron column/row layout along the mesh's "
                        "'model' axis and every KV cache / page pool "
                        "shards its head axis, so decode attention and "
                        "paged gathers stay shard-local while the host "
                        "page table stays the single global allocator. "
                        "Requires --mesh with model=<this value> and a "
                        "head count divisible by it; greedy replies stay "
                        "token-identical to tp=1. 1 = single-chip")
    p.add_argument("--serve_slots", type=int, default=8,
                   help="continuous-batching slot count (the decode "
                        "batch width, serving/server.py)")
    p.add_argument("--serve_disagg", action="store_true",
                   help="disaggregate prefill from decode "
                        "(serving/server.py): the decode pool steps "
                        "first every server step and admissions (the "
                        "compute-bound B=1 prefill program) run under a "
                        "per-step budget after it, handing KV state to "
                        "the decode pool through a paged page-table row "
                        "write — a prefill burst cannot stall admitted "
                        "decode slots. Requires the paged KV cache and "
                        ">= 2 slots")
    p.add_argument("--serve_online", action="store_true",
                   help="train-while-serve (commefficient_tpu/online/): "
                        "run the continuous-batching server and buffered "
                        "federated cohorts on ONE host loop — served "
                        "interactions become per-client training "
                        "examples, cohorts write the same sparse client "
                        "rows serving reads as per-user deltas, and "
                        "refreshed base weights hot-swap into the live "
                        "server (drain -> fingerprint gate -> swap -> "
                        "resubmit leftovers; greedy replies stay "
                        "token-identical across each swap for requests "
                        "served on one side of it). Requires "
                        "--server_mode buffered and --serve_personalized")
    p.add_argument("--online_train_every", type=int, default=4,
                   help="--serve_online: dispatch one buffered cohort "
                        "every this many served interactions")
    p.add_argument("--online_swap_every", type=int, default=2,
                   help="--serve_online: attempt a base-weight hot swap "
                        "every this many buffered applies")
    p.add_argument("--offload_pipeline_depth", type=int, default=2,
                   help="rounds of offloaded output rows that may queue "
                        "for lazy host writeback (api.HostOffloadPipeline)"
                        ": 2 = double-buffered gather-ahead/scatter-behind"
                        " around the computing round, 1 = one round in "
                        "flight. Trajectory-identical at any depth")
    p.add_argument("--mesh", type=str, default="",
                   help="mesh shape as 'clients=N[,seq=M]' or 'clients=all';"
                        " empty = single-device (no mesh). See parse_mesh")
    p.add_argument("--scan_rounds", type=int, default=1,
                   help="dispatch K rounds per host call as one traced "
                        "lax.scan (api.train_rounds_scan): identical "
                        "trajectory, K-fold fewer dispatches — the host "
                        "per-dispatch cost otherwise bounds throughput "
                        "when the device round is short. NaN abort is "
                        "detected at "
                        "window granularity (the device guard still freezes "
                        "state at the breaching round)")
    # GPT2 / PersonaChat (ref utils.py:185-208)
    p.add_argument("--model_checkpoint", type=str, default="gpt2")
    p.add_argument("--num_candidates", type=int, default=2)
    p.add_argument("--max_history", type=int, default=2)
    p.add_argument("--lm_coef", type=float, default=1.0)
    p.add_argument("--mc_coef", type=float, default=1.0)
    p.add_argument("--personality_permutations", type=int, default=1)
    p.add_argument("--dropout_impl", choices=("xla", "xla_rbg"),
                   default="xla",
                   help="dropout bit source (ops/dropout.py): 'xla_rbg' "
                        "draws mask bits from the TPU hardware "
                        "RngBitGenerator (~12 ms/round faster on the "
                        "federated GPT2 bench, same Bernoulli "
                        "distribution); 'xla' is the portable threefry "
                        "path")
    p.add_argument("--attn_dropout", choices=("auto", "output", "kernel"),
                   default="auto",
                   help="attention-dropout placement for --attn_impl "
                        "blockwise: 'auto' uses reference-parity in-kernel "
                        "dropout on the attention probabilities when the "
                        "fused flash kernel is eligible (TPU, causal "
                        "self-attn; ops/flash_attention.py) and falls back "
                        "to output dropout otherwise; 'output' forces the "
                        "pre-kernel output-dropout behavior; 'kernel' "
                        "requires the in-kernel path and errors when "
                        "ineligible (A/B use)")
    p.add_argument("--fused_ce", choices=("auto", "on", "off"),
                   default="auto",
                   help="vocab-chunked fused LM-head CE (ops/fused_ce.py): "
                        "the (tokens, vocab) logits tensor never "
                        "materializes. 'auto' (default) turns it on at "
                        f"--max_seq_len >= {FUSED_CE_AUTO_T} — where that "
                        "tensor starts to dominate HBM and the chunked "
                        "path wins — and leaves it off below (measured "
                        "slightly SLOWER than XLA's fused materialized "
                        "path at T=256, docs/ROOFLINE.md); auto also "
                        "stays off under ring attention and seq=/stage= "
                        "meshes, where the fused path is not plumbed. "
                        "'on'/'off' force the choice ('on' under those "
                        "meshes still fails loudly downstream)")
    p.add_argument("--fused_lm_head", action="store_true",
                   help="legacy alias for --fused_ce on")
    p.add_argument("--transfer_guard", choices=("allow", "log", "disallow"),
                   default="disallow",
                   help="jax.transfer_guard mode applied around every "
                        "jitted round dispatch (federated/api.py): "
                        "'disallow' (default) makes any implicit "
                        "host<->device transfer at dispatch time an "
                        "error, proving the round stays async")
    # buffered async server + fault model (federated/{buffer,faults}.py)
    p.add_argument("--server_mode", choices=("sync", "buffered"),
                   default="sync",
                   help="'buffered' = FedBuff-style asynchronous server: "
                        "contributions land in a --buffer_m slot buffer "
                        "as they arrive (per --fault_* schedule) and the "
                        "server applies whenever it fills, scaling each "
                        "by staleness 1/(1+tau)^alpha. With no --fault_"
                        "seed it runs lock-step and matches sync "
                        "bit-for-bit at alpha=0 (tests/test_buffered.py)")
    p.add_argument("--buffer_m", type=int, default=0,
                   help="buffered server's apply threshold M; 0 = "
                        "num_workers")
    p.add_argument("--staleness_alpha", type=float, default=0.0,
                   help="staleness-discount exponent alpha in "
                        "s(tau)=1/(1+tau)^alpha (0 = no discounting)")
    p.add_argument("--client_quarantine", action="store_true",
                   help="per-client NaN quarantine: a non-finite client "
                        "contribution is excluded from the aggregate "
                        "(instead of aborting the run) and its client "
                        "benched for --quarantine_rounds applied rounds; "
                        "only a post-exclusion server-side breach trips "
                        "the sticky abort")
    p.add_argument("--quarantine_rounds", type=int, default=5,
                   help="bench duration for a client whose update came "
                        "back non-finite")
    p.add_argument("--fault_seed", type=int, default=None,
                   help="enable the seeded client fault model "
                        "(federated/faults.py): per-(round, client) "
                        "dropout/crash/latency draws, replayable from "
                        "this seed. None = no faults (lock-step)")
    p.add_argument("--fault_dropout_prob", type=float, default=0.0,
                   help="per-(round, client) probability the client never "
                        "starts")
    p.add_argument("--fault_crash_prob", type=float, default=0.0,
                   help="probability a started client crashes mid-round "
                        "(pulls weights, never uploads)")
    p.add_argument("--straggler_frac", type=float, default=0.0,
                   help="fraction of clients that are CHRONIC stragglers "
                        "under this fault seed (a per-client property)")
    p.add_argument("--straggler_mult", type=float, default=10.0,
                   help="latency multiplier for chronic stragglers")
    p.add_argument("--base_latency", type=float, default=1.0,
                   help="median client round-trip in simulated time units")
    p.add_argument("--latency_sigma", type=float, default=0.25,
                   help="log-normal spread of client latency")
    p.add_argument("--dispatch_interval", type=float, default=None,
                   help="simulated time between cohort dispatches "
                        "(buffered server); None = base_latency")
    p.add_argument("--client_k_dist", type=str, default="",
                   help="heterogeneous per-client transmit budgets for "
                        "mode=local_topk, as 'uniform:lo,hi' fractions of "
                        "--k (federated-dropout-style partial "
                        "participation): each client i gets a CHRONIC "
                        "budget k_i = round(U_i * k), U_i ~ Uniform[lo, "
                        "hi] keyed on (--seed, i) via the fault model's "
                        "Philox scheme — order-independent and resumable. "
                        "The device keeps the provisioned top-k selection "
                        "and masks it down to k_i largest-magnitude "
                        "coordinates; masked coordinates stay in the "
                        "error-feedback row. Byte accounting still "
                        "charges the provisioned k (the wire format is "
                        "provisioned). Empty = homogeneous k")
    # DP
    p.add_argument("--dp", action="store_true", dest="do_dp")
    p.add_argument("--dp_mode", choices=DP_MODES, default="worker")
    p.add_argument("--l2_norm_clip", type=float, default=1.0)
    p.add_argument("--noise_multiplier", type=float, default=0.0)
    return p


def args_to_config(args, **overrides) -> FedConfig:
    fields = set(FedConfig.__dataclass_fields__)
    kwargs = {k: v for k, v in vars(args).items() if k in fields}
    kwargs.update(overrides)
    return FedConfig(**kwargs)


def resolve_fused_ce(args, mesh=None) -> bool:
    """``--fused_ce`` (+ legacy ``--fused_lm_head``) -> fused_lm_head bool.

    'on'/'off' are explicit. 'auto' enables the vocab-chunked fused
    head+CE exactly when it pays: ``max_seq_len >= FUSED_CE_AUTO_T`` on a
    plain forward. Under ring attention or a seq=/stage= mesh, auto
    resolves to off — the fused path is not plumbed there (models/gpt2.py
    rejects ring; the GPipe loss materializes its own head einsum) — while
    an explicit 'on' is passed through so those paths keep failing loudly
    instead of silently downgrading an explicit request."""
    choice = getattr(args, "fused_ce", "auto")
    if getattr(args, "fused_lm_head", False):
        if choice == "off":
            raise ValueError("--fused_lm_head (legacy alias for "
                             "--fused_ce on) conflicts with --fused_ce off")
        choice = "on"
    if choice != "auto":
        return choice == "on"
    if getattr(args, "attn_impl", "full") == "ring":
        return False
    if mesh is not None:
        for axis in ("seq", "stage"):
            if axis in mesh.axis_names and mesh.shape[axis] > 1:
                return False
    return int(getattr(args, "max_seq_len", 0)) >= FUSED_CE_AUTO_T


def make_fault_model(args, num_clients: int):
    """``--fault_*`` flags -> a seeded FaultModel, or None without
    --fault_seed (lock-step)."""
    if getattr(args, "fault_seed", None) is None:
        return None
    from commefficient_tpu.federated.faults import FaultModel
    return FaultModel(
        args.fault_seed, num_clients,
        base_latency=args.base_latency,
        latency_sigma=args.latency_sigma,
        straggler_frac=args.straggler_frac,
        straggler_mult=args.straggler_mult,
        dropout_prob=args.fault_dropout_prob,
        crash_prob=args.fault_crash_prob)


def learner_factory(args, num_clients: int):
    """(learner class, extra ctor kwargs) for ``--server_mode``.

    The buffered server consumes the fault flags host-side
    (BufferedFedLearner's event loop); sync training has no fault
    adapter here — the sync-under-faults baseline lives in results.py's
    straggler grid — so --fault_seed with sync mode fails loudly instead
    of silently no-opping."""
    if getattr(args, "server_mode", "sync") != "buffered":
        if getattr(args, "fault_seed", None) is not None:
            raise ValueError(
                "--fault_seed needs --server_mode buffered (the sync "
                "fault baseline is driven by results.py --straggler)")
        from commefficient_tpu.federated.api import FedLearner
        return FedLearner, {}
    from commefficient_tpu.federated.buffer import BufferedFedLearner
    return BufferedFedLearner, {
        "fault_model": make_fault_model(args, num_clients),
        "dispatch_interval": getattr(args, "dispatch_interval", None),
    }


def parse_mesh(spec: str):
    """``--mesh`` string -> ``jax.sharding.Mesh`` (or None for no mesh).

    Grammar: ``clients=N[,seq=M | ,model=M | ,stage=S | ,expert=E]`` —
    the TPU analog of the reference's process-topology flags
    (num_devices/share_ps_gpu, ref utils.py:175). ``seq`` shards the
    sequence (ring attention, gpt2 entrypoint); ``model``
    coordinate-splits weights and client state for 2D clients x model
    federation (the capability the reference buys with a whole GPU per
    client, fed_worker.py:18-20); ``stage`` runs the client loss through
    the GPipe pipeline (parallel/pp.py, gpt2 entrypoint, LM-only);
    ``expert`` shards stacked MoE expert weights (ops/moe.py, requires
    --moe_experts). The inner axes are mutually exclusive (make_mesh).
    ``clients=all`` (or ``auto``) uses every visible device. The mesh is
    built over the first N*M of ``jax.devices()``.
    """
    if not spec:
        return None
    from commefficient_tpu.parallel.mesh import make_mesh
    kv = {}
    for part in spec.split(","):
        key, sep, val = part.partition("=")
        if not sep:
            raise ValueError(f"--mesh: expected key=value, got {part!r}")
        kv[key.strip()] = val.strip()
    unknown = set(kv) - {"clients", "seq", "model", "stage", "expert"}
    if unknown:
        raise ValueError(f"--mesh: unknown axes {sorted(unknown)} "
                         f"(supported: clients=N[,seq=M | ,model=M | "
                         f",stage=S | ,expert=E])")
    inner = {}
    for name in ("seq", "model", "stage", "expert"):
        inner[name] = int(kv.get(name, 1))
        if inner[name] <= 0:
            raise ValueError(f"--mesh: {name} must be positive, "
                             f"got {inner[name]}")
    inner_total = (inner["seq"] * inner["model"] * inner["stage"]
                   * inner["expert"])
    clients = kv.get("clients", "all")
    if clients in ("all", "auto"):
        return make_mesh(None, **inner)
    n = int(clients)
    if n <= 0:
        raise ValueError(f"--mesh: clients must be positive, got {n}")
    return make_mesh(n * inner_total, **inner)


def round_up_workers_for_mesh(args, mesh) -> int:
    """Number of mesh shards along ``clients``; loudly rounds
    ``args.num_workers`` up to a multiple of it (the batch worker axis is
    sharded over that mesh axis, so its width must divide evenly — the
    reference instead silently DROPS the tail chunk when procs don't divide
    clients, fed_aggregator.py:230-237, a quirk SURVEY.md says not to keep)."""
    if mesh is None:
        return 1
    from commefficient_tpu.parallel.mesh import round_up
    n_shards = mesh.shape["clients"]
    if args.num_workers % n_shards:
        padded = round_up(args.num_workers, n_shards)
        print(f"--mesh: rounding num_workers {args.num_workers} -> {padded} "
              f"(must be a multiple of the {n_shards}-way 'clients' axis)")
        args.num_workers = padded
    return n_shards
