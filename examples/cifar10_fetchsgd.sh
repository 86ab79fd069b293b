#!/usr/bin/env bash
# FetchSGD headline configuration: CIFAR10 ResNet-9, 5x500k sketch, k=50k
# (reference utils.py:142-145 defaults), 100 clients non-iid (one class
# pair per client), 8 sampled per round. Place the CIFAR-10 python pickle
# batches under $DATASET_DIR first.
set -euo pipefail

DATASET_DIR="${DATASET_DIR:-./dataset/cifar10}"

python -m commefficient_tpu.training.cv \
    --dataset_name CIFAR10 \
    --model ResNet9 \
    --mode sketch \
    --error_type virtual \
    --virtual_momentum 0.9 \
    --num_clients 100 \
    --num_workers 8 \
    --local_batch_size 32 \
    --k 50000 --num_rows 5 --num_cols 500000 \
    --num_epochs 24 \
    --pivot_epoch 5 \
    --lr_scale 0.4 \
    --scan_rounds 8 \
    --dataset_dir "$DATASET_DIR" \
    "$@"

# --scan_rounds 8 dispatches 8 rounds per host call as one traced
# lax.scan (trajectory-identical; api.train_rounds_scan), so per-round
# host dispatch costs do not bound throughput.
# Add --mesh clients=8 to shard client state/batches over 8 chips, and
# --topk_approx_recall 0.95 for the approx-top-k selector.
