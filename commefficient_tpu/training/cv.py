"""CV training entrypoint (reference cv_train.py:85-421).

    python -m commefficient_tpu.training.cv --mode sketch \
        --dataset_name CIFAR10 --model ResNet9 ...

Structure parity: epoch loop over federated rounds, piecewise-linear LR
through a pivot epoch, NaN abort, TableLogger console rows, communication
byte rollup, end-of-training checkpoint. Smoke mode (``--test``) runs one
round + one val batch on a shrunken model, the plumbing test the reference
implements with fake gradients (ref fed_worker.py:117-122, cv_train.py:329-336).
"""

from __future__ import annotations

import math
import sys

import jax
import numpy as np

from commefficient_tpu.data import FedBatcher, fed_datasets, val_batches
from commefficient_tpu.data.transforms import get_transforms
from commefficient_tpu.federated.losses import make_cv_loss
from commefficient_tpu.models import get_model
from commefficient_tpu.training.args import args_to_config, build_parser
from commefficient_tpu.utils.logging import TableLogger, Timer
from commefficient_tpu.utils.schedules import cifar_lr_schedule
from commefficient_tpu.utils.tracing import compile_counters, span

DATASET_CLASSES = {"CIFAR10": 10, "CIFAR100": 100, "EMNIST": 62,
                   "ImageNet": 1000, "Synthetic": 10, "Digits": 10,
                   "Patches32": 10}
DATASET_CHANNELS = {"EMNIST": 1, "Digits": 1}


def make_dataset(args, train: bool):
    cls = fed_datasets[args.dataset_name]
    # num_clients None => the dataset's natural partition (ref utils.py:173
    # has no default; FedModel falls back to dataset client counts)
    kw = dict(dataset_dir=args.dataset_dir, do_iid=args.do_iid,
              num_clients=args.num_clients, train=train,
              transform=get_transforms(args.dataset_name, train),
              seed=args.seed)
    if args.dataset_name == "Synthetic":
        kw.update(per_class=64 if args.do_test else 512)
    return cls(**kw)


def build_learner(args, sample_input, num_classes, channels, mesh=None):
    from commefficient_tpu.parallel.mesh import padded_num_clients
    num_clients = padded_num_clients(args.num_clients, mesh)
    cfg = args_to_config(args, num_classes=num_classes,
                         num_channels=channels,
                         num_clients=num_clients)
    model_kw = dict(num_classes=num_classes)
    compute_dtype = getattr(args, "compute_dtype", "float32")
    if args.model in ("ResNet9",):
        model_kw["do_batchnorm"] = args.do_batchnorm
        # bf16 convs at full MXU rate; params/logits stay f32 (the
        # reference trains f32 — that stays the default)
        model_kw["dtype"] = compute_dtype
    elif compute_dtype != "float32":
        # never let the flag silently no-op
        raise ValueError(f"--compute_dtype {compute_dtype} is only "
                         f"supported for ResNet9 (got {args.model})")
    # input channel count is inferred by flax from the sample input; no
    # per-model stem flag needed (1-channel EMNIST just works)
    model = get_model(args.model, **model_kw)
    loss = make_cv_loss(model)
    sched = cifar_lr_schedule(args.lr_scale, args.pivot_epoch,
                              args.num_epochs)
    init_params, trainable_mask = None, None
    if args.do_finetune:
        # pretrained backbone + fresh trainable head (ref cv_train.py:377-384)
        from commefficient_tpu.utils.finetune import \
            load_pretrained_for_finetune
        init_params, trainable_mask = load_pretrained_for_finetune(
            model, jax.random.PRNGKey(args.seed), sample_input,
            args.finetune_path)
    # per-coordinate LR: Fixup scalars train at a reduced LR (the
    # reference's per-param-group LR vector, fed_aggregator.py:411-427)
    factor = args.scalar_lr_factor
    if factor is None:
        factor = 0.1 if args.model.startswith("Fixup") else 1.0
    lr_vec = None
    if factor != 1.0:
        from functools import partial

        from commefficient_tpu.utils.params import scalar_lr_multipliers
        lr_vec = partial(scalar_lr_multipliers, scalar_factor=factor)
    # --server_mode buffered swaps in the FedBuff event-loop learner
    # (federated/buffer.py) with the --fault_* schedule; sync stays the
    # plain FedLearner
    from commefficient_tpu.training.args import learner_factory
    cls, extra = learner_factory(args, num_clients)
    return cls(model, cfg, loss, loss, jax.random.PRNGKey(args.seed),
               sample_input, lr_schedule=sched, mesh=mesh,
               init_params=init_params, trainable_mask=trainable_mask,
               lr_scale_vec=lr_vec, **extra)


def train(args, mesh=None, max_rounds=None, log=True):
    from commefficient_tpu.federated.api import set_transfer_guard
    set_transfer_guard(getattr(args, "transfer_guard", "disallow"))
    compile_counters()
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        # CV models have no sequence dimension; a seq axis here would
        # silently replicate and waste chips (the dead-flag defect class,
        # VERDICT r2/r3) — fail loudly instead
        raise ValueError("--mesh seq=N applies to the gpt2 entrypoint "
                         "(sequence-parallel ring attention); CV models "
                         "have no sequence axis")
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        # the tensor-parallel specs are wired for GPT2 (parallel/tp.py);
        # letting a CV run accept the axis would silently replicate
        raise ValueError("--mesh model=M (2D clients x model federation) "
                         "is wired for the gpt2 entrypoint; CV models "
                         "have no TP layout")
    if mesh is not None and mesh.shape.get("stage", 1) > 1:
        # the GPipe pipeline stacks homogeneous transformer blocks
        # (parallel/pp.py); CV models have no such trunk
        raise ValueError("--mesh stage=S (GPipe pipeline) is wired for "
                         "the gpt2 entrypoint; CV models have no stacked "
                         "block trunk")
    if mesh is not None and mesh.shape.get("expert", 1) > 1:
        raise ValueError("--mesh expert=E (MoE expert parallelism) is "
                         "wired for the gpt2 entrypoint; CV models have "
                         "no MoE blocks")
    with span("setup.data"):
        train_set = make_dataset(args, train=True)
        val_set = make_dataset(args, train=False)
    args.num_clients = train_set.num_clients
    num_classes = (train_set.num_classes
                   if hasattr(train_set, "num_classes")
                   else DATASET_CLASSES[args.dataset_name])
    channels = DATASET_CHANNELS.get(args.dataset_name, 3)

    batcher = FedBatcher(train_set, args.num_workers, args.local_batch_size,
                         seed=args.seed)
    ids0, cols0, mask0 = next(iter(batcher.epoch()))
    with span("setup.learner"):
        learner = build_learner(args, cols0[0][0][:1], num_classes,
                                channels, mesh=mesh)

    # periodic crash-consistent checkpoints + resume (the probe round
    # above runs before resume() so its sampler/aug draws — identical in
    # every launch — are overwritten by the restored cursor)
    from commefficient_tpu.training.preempt import (PreemptionGuard,
                                                    TrainCheckpointer)
    ckpt = TrainCheckpointer(
        args, learner, batcher, entry="cv", log=log,
        meta={"model": args.model, "num_classes": num_classes,
              "do_batchnorm": args.do_batchnorm})
    cursor = ckpt.resume()
    start_epoch = cursor["epoch"] if cursor else 0
    skip0 = cursor["rounds_in_epoch"] if cursor else 0

    table = TableLogger() if log else None
    writer = None
    if getattr(args, "use_tensorboard", False):
        from commefficient_tpu.utils.logging import ScalarWriter, make_logdir
        writer = ScalarWriter(make_logdir(args))
    timer = Timer()
    spe = batcher.steps_per_epoch()
    total_rounds = cursor["total_rounds"] if cursor else 0
    if getattr(args, "eval_before_start", False):
        # baseline validation at init (ref cv_train.py:91-103). Snapshot
        # the learner rng: evaluate() splits the shared stream, and a
        # logging-only flag must not perturb the training trajectory
        rng_before = learner.rng
        with span("setup.eval"):
            val0 = learner.evaluate(val_batches(val_set,
                                                args.valid_batch_size))
        learner.rng = rng_before
        if log:
            print(f"eval before start: loss={val0['loss']:.4f} "
                  f"acc={float(val0['metrics'][0]):.4f}")
        if writer:
            writer.add_scalar("test_loss", val0["loss"], 0)
            writer.add_scalar("test_acc", float(val0["metrics"][0]), 0)
    guard = PreemptionGuard(enabled=ckpt.active, log=log)
    try:
        guard.__enter__()
        n_epochs = int(math.ceil(args.num_epochs))
        for epoch in range(start_epoch, n_epochs):
            # fractional num_epochs truncates the LAST epoch's round count
            # (ref cv_train.py:100-106, 194-196: only epoch_fraction of the
            # final epoch's batches run); whole epochs run the full spe
            epoch_fraction = (args.num_epochs - epoch
                              if epoch == n_epochs - 1 else 1.0)
            rounds_cap = (spe if epoch_fraction >= 1
                          else max(1, int(round(spe * epoch_fraction))))
            # a resumed mid-epoch run replays the first `skip` rounds'
            # RNG/data draws without training them (batcher.epoch(skip))
            skip = skip0 if epoch == start_epoch else 0
            rounds_in_epoch = skip
            pending_boundary_save = False
            epoch_metrics = []
            # one-round software pipeline (RoundPipeline): metric sync
            # overlaps the next round's device compute, so the loop runs
            # at device throughput (PERF.md section 5: samples_per_s). The
            # host notices a NaN (ref cv_train.py:110-112) one round late,
            # but the in-round device guard (round.py) makes the breaching
            # round and everything after it a state no-op, so the lag
            # never pollutes weights/state/byte accounting.
            pipe = learner.pipeline()

            def check(out):
                if out is None:
                    return None
                epoch_metrics.append(out)
                # the device guard's verdict, not a host loss recompute: a
                # pipelined round AFTER the breach can report a healthy
                # loss again (the guard froze the weights), so the latched
                # flag is the only reliable signal
                return out if out["aborted"] else None

            def abort(bad):
                print(f"NaN/divergent loss ({bad['loss']}); aborting "
                      f"(threshold {args.nan_threshold})")
                learner.flush_offload()  # settle host rows before handing
                return learner, {"aborted": True, "loss": bad["loss"]}

            # next round's batch transfers while this one computes
            # (sharding-aware on a mesh: lands directly on the shards);
            # the one-item lookahead feeds the offload pipeline's
            # gather-ahead (next round's client rows transfer during this
            # round's compute — no-op off the offload path)
            from commefficient_tpu.data.prefetch import (device_prefetch,
                                                         with_lookahead)
            batch_sh = learner.batch_shardings
            # --scan_rounds K>1: K rounds per host dispatch as one traced
            # lax.scan (api.ScanWindow / train_rounds_scan) — identical
            # trajectory, but dispatch and metric-sync costs are paid per
            # window instead of per round. The epoch tail flushes a
            # shorter window (one extra compile for that K).
            scan_k = max(1, int(getattr(args, "scan_rounds", 1) or 1))
            if scan_k > 1 and getattr(args, "server_mode", "sync") != "sync":
                raise ValueError("--scan_rounds > 1 is a sync-mode "
                                 "optimization; the buffered server "
                                 "dispatches cohorts through a host event "
                                 "loop")
            window = learner.scan_window(scan_k) if scan_k > 1 else None

            def check_all(outs):
                # record EVERY finalized round's metrics, but report the
                # FIRST aborted one — post-breach rounds are frozen
                # no-ops that can print a healthy-looking loss
                bad = None
                for out in outs or []:
                    bad = bad or check(out)
                return bad

            for (ids, cols, mask), nxt in with_lookahead(
                    device_prefetch(batcher.epoch(skip=skip),
                                    shardings=batch_sh)):
                frac = total_rounds / max(spe, 1)
                if window is not None:
                    total_rounds += 1
                    rounds_in_epoch += 1
                    if bad := check_all(window.push(ids, cols, mask, frac)):
                        return abort(bad)
                else:
                    raw = learner.train_round_async(
                        ids, cols, mask, epoch_frac=frac,
                        next_client_ids=nxt[0] if nxt is not None else None)
                    total_rounds += 1
                    rounds_in_epoch += 1
                    if bad := check(pipe.push(raw)):
                        return abort(bad)
                # nxt is None == the sampler just exhausted: this round is
                # the epoch's last even if the spe-derived cap disagrees
                # (steps_per_epoch is an estimate; the loop runs the data
                # out), so the save must defer to the boundary path too
                at_boundary = (args.do_test or rounds_in_epoch >= rounds_cap
                               or (max_rounds and total_rounds >= max_rounds)
                               or nxt is None)
                if guard.triggered or ckpt.due(total_rounds):
                    if at_boundary:
                        # defer to after the epoch's flush + eval below: a
                        # save here would record a sampler cursor the
                        # resumed epoch could never finish consuming (the
                        # prefetch lookahead's draws would be lost) and the
                        # eval rng splits would be drawn twice on resume
                        pending_boundary_save = True
                    else:
                        # settle the in-flight round first — rounds_done
                        # and the byte totals only advance in
                        # finalize_round_metrics (the RoundPipeline's
                        # one-round metric lag)
                        if bad := (check_all(window.flush())
                                   if window is not None
                                   else check(pipe.flush())):
                            return abort(bad)
                        learner.flush_offload()
                        ckpt.save(epoch, rounds_in_epoch, total_rounds,
                                  in_epoch=True)
                        if guard.triggered:
                            return learner, {"preempted": True,
                                             "epoch": epoch + 1,
                                             "rounds": total_rounds}
                if at_boundary:
                    break
            # epoch boundary: settle offloaded host rows (pending lazy
            # writebacks + any gather-ahead for a round that never ran)
            learner.flush_offload()
            if bad := (check_all(window.flush()) if window is not None
                       else check(pipe.flush())):
                return abort(bad)
            train_time = timer()
            val = learner.evaluate(val_batches(val_set,
                                               args.valid_batch_size))
            val_time = timer()
            mean = lambda k: float(np.mean([m[k] for m in epoch_metrics]))
            row = {
                "epoch": epoch + 1,
                "lr": epoch_metrics[-1]["lr"],
                "train_loss": mean("loss"),
                "train_acc": float(np.mean(
                    [m["metrics"][0] for m in epoch_metrics])),
                "train_time": train_time,
                "test_loss": val["loss"],
                "test_acc": float(val["metrics"][0]),
                "test_time": val_time,
                "down (MiB)": learner.total_download_bytes / 2**20,
                "up (MiB)": learner.total_upload_bytes / 2**20,
                "total_time": timer.total_time,
            }
            if table:
                table.append(row)
            if writer:
                # the scalars the reference exports (cv_train.py:150-158)
                for tag in ("train_loss", "train_acc", "train_time",
                            "test_loss", "test_acc", "test_time", "lr"):
                    writer.add_scalar(tag, row[tag], epoch + 1)
            if pending_boundary_save or guard.triggered:
                last = (epoch + 1 >= n_epochs or args.do_test
                        or (max_rounds and total_rounds >= max_rounds))
                if not last:
                    # boundary save: cursor points at the NEXT epoch's
                    # start, with the sampler/aug/learner rng all past
                    # this epoch's tail draws and eval splits
                    ckpt.save(epoch + 1, 0, total_rounds, in_epoch=False)
                    if guard.triggered:
                        return learner, dict(row, preempted=True)
            if args.do_test or (max_rounds and total_rounds >= max_rounds):
                break
    finally:
        guard.__exit__()
        if writer:
            writer.close()

    if hasattr(learner, "flush_faults"):
        # buffered server end-of-training barrier: deliver every in-flight
        # contribution and apply whatever partial buffer remains, so the
        # final weights/byte totals account for all dispatched work
        learner.flush_faults()
        row["sim_time"] = learner.sim_time
        # flush-triggered applies moved bytes after the last epoch row
        row["down (MiB)"] = learner.total_download_bytes / 2**20
        row["up (MiB)"] = learner.total_upload_bytes / 2**20
        if log:
            print(f"buffered server: {learner.applies_done} applies over "
                  f"{learner.cohorts_done} cohorts, sim_time="
                  f"{learner.sim_time:.1f} units, faults="
                  f"{learner.fault_stats}")

    if args.do_checkpoint:
        from commefficient_tpu.utils.checkpoint import save_checkpoint
        save_checkpoint(args.checkpoint_path, learner, args.model,
                        meta={"model": args.model,
                              "num_classes": num_classes,
                              "do_batchnorm": args.do_batchnorm})
    return learner, row


def main(argv=None):
    from commefficient_tpu.training.args import (parse_mesh,
                                                 round_up_workers_for_mesh)
    from commefficient_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    parser = build_parser(default_lr=0.4)
    args = parser.parse_args(argv)
    if args.do_test:
        # shrink everything (ref cv_train.py:329-336): tiny sketch, 1 round
        args.k = min(args.k, 10)
        args.num_cols = min(args.num_cols, 100)
        args.num_rows = min(args.num_rows, 1)
        args.num_epochs = 1
    mesh = parse_mesh(args.mesh)
    round_up_workers_for_mesh(args, mesh)
    np.random.seed(args.seed)
    from commefficient_tpu.utils.logging import profile_ctx
    with profile_ctx(args.profile):
        _, final = train(args, mesh=mesh)
    print("final:", {k: round(v, 4) if isinstance(v, float) else v
                     for k, v in final.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
