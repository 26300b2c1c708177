"""ResNet ImageNet training driver — the BASELINE north-star recipe
(reference models/resnet/TrainImageNet.scala:33 + README.md:131-149:
90 epochs, GLOBAL batch 8192, warmup 5 epochs to maxLr 3.2, poly decay,
LARS, zero-gamma residual BN init; published top-1 0.76114).

    python -m bigdl_tpu.models.resnet_train -f /data/imagenet-tfrecords \\
        -b 8192 --maxEpoch 90 --maxLr 3.2 --warmupEpoch 5 --optim lars

Data layout under --folder: ``train-*`` / ``validation-*`` TFRecord
shards (bigdl_tpu.dataset.sharded); synthetic ImageNet stands in without
it (the DistriOptimizerPerf-style perf/e2e path).  Runs the DP+ZeRO-1
engine over the full mesh via Optimizer.apply.
"""
from __future__ import annotations

import logging
from typing import Optional

import jax.numpy as jnp

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.models.resnet import ResNet
from bigdl_tpu.models.train_utils import (
    base_parser,
    configure,
    init_logging,
    report_validation,
    synthetic_imagenet,
)

logger = logging.getLogger("bigdl_tpu.train")


def make_recipe_optim(args, iters_per_epoch: int):
    """warmup(0 -> maxLr over warmupEpoch) then poly(2) to maxEpoch —
    exactly TrainImageNet.scala's SequentialSchedule; LARS per --optim."""
    warm_iters = args.warmupEpoch * iters_per_epoch
    total_iters = args.maxEpoch * iters_per_epoch
    base_lr = args.learningRate
    sched = optim.SequentialSchedule(iters_per_epoch)
    if warm_iters > 0:
        delta = (args.maxLr - base_lr) / warm_iters
        sched.add(optim.Warmup(delta), warm_iters)
    # after warmup the effective base is maxLr: Poly decays from there
    poly = optim.Poly(2.0, max(total_iters - warm_iters, 1))
    sched.add(_ScaledSchedule(poly, args.maxLr / base_lr if base_lr else 1.0),
              max(total_iters - warm_iters, 1))
    if args.optim == "lars":
        return optim.LarsSGD(base_lr, momentum=args.momentum,
                             weight_decay=args.weightDecay, schedule=sched)
    return optim.SGD(base_lr, momentum=args.momentum,
                     weight_decay=args.weightDecay, schedule=sched)


class _ScaledSchedule(optim.LearningRateSchedule):
    """Multiply an inner schedule by a constant (post-warmup maxLr)."""

    def __init__(self, inner, scale: float):
        self.inner = inner
        self.scale = scale

    def bind(self, base_lr: float):
        self.inner.bind(base_lr)

    def rate(self, step, epoch=0):
        return self.scale * self.inner.rate(step, epoch)


def build(argv: Optional[list] = None):
    """Parse ``argv`` and build the run exactly as :func:`main` trains
    it; returns ``(configured Optimizer, validation DataSet)``."""
    p = base_parser("resnet_train", batch_size=8192, max_epoch=90, lr=0.1)
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--classNum", type=int, default=1000)
    p.add_argument("--imageSize", type=int, default=224)
    p.add_argument("--maxLr", type=float, default=3.2)
    p.add_argument("--warmupEpoch", type=int, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weightDecay", type=float, default=1e-4)
    p.add_argument("--optim", default="lars", choices=["lars", "sgd"])
    p.add_argument("--dataset", default="imagenet",
                   choices=["imagenet", "cifar10"])
    p.add_argument("--fused", action="store_true",
                   help="Pallas conv+BN fusion pipeline (bottleneck "
                        "imagenet depths; nn/fused_block.py)")
    p.add_argument("--streaming", action="store_true",
                   help="stream shards instead of caching records in "
                        "host RAM (full-ImageNet scale)")
    args = p.parse_args(argv)

    if args.folder and args.dataset == "cifar10":
        from bigdl_tpu.models.train_utils import cifar10_datasets

        train_ds, val_ds = cifar10_datasets(args.folder, args.batchSize)
    elif args.folder:
        from bigdl_tpu.dataset.sharded import imagenet_tfrecord_dataset

        train_ds = imagenet_tfrecord_dataset(
            args.folder, "train", args.batchSize, args.imageSize,
            cache=not args.streaming)
        val_ds = imagenet_tfrecord_dataset(
            args.folder, "validation", args.batchSize, args.imageSize,
            cache=not args.streaming)
    else:
        n = args.syntheticSize or 1024
        res = args.imageSize if args.dataset == "imagenet" else 32
        x, y = synthetic_imagenet(n, res, args.classNum)
        xv, yv = synthetic_imagenet(n // 4, res, args.classNum, 1)
        train_ds = DataSet.from_arrays(x, y, batch_size=args.batchSize)
        val_ds = DataSet.from_arrays(xv, yv, batch_size=args.batchSize)

    # zero-gamma on the last BN of each residual block is part of the
    # recipe (ResNet.scala's optnet init; models/resnet.py implements it)
    model = ResNet(class_num=args.classNum, depth=args.depth,
                   dataset=args.dataset, fused=args.fused)

    opt = optim.Optimizer.apply(
        model, train_ds, nn.ClassNLLCriterion(logits=True),
        end_trigger=optim.Trigger.max_epoch(args.maxEpoch),
    )
    method = make_recipe_optim(args, train_ds.batches_per_epoch())
    opt.set_optim_method(method)
    opt.set_compute_dtype(jnp.bfloat16)  # bf16 hot loop (north star)
    opt.set_validation(optim.Trigger.every_epoch(), val_ds,
                       [optim.Top1Accuracy(), optim.Top5Accuracy()])
    return configure(opt, args), val_ds


def main(argv: Optional[list] = None) -> dict:
    init_logging()
    opt, val_ds = build(argv)
    trained = opt.optimize()
    return report_validation(
        opt, trained, val_ds, [optim.Top1Accuracy(), optim.Top5Accuracy()])


if __name__ == "__main__":
    main()
