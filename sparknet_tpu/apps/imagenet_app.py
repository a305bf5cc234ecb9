"""ImageNetApp — AlexNet/CaffeNet on ImageNet-style data (reference:
src/main/scala/apps/ImageNetApp.scala).

Phase parity with the reference: tar → JPEG → force-resize 256 (:84-95 via
ScaleAndConvert) → distributed mean image (:84, ComputeMean) → τ=50 rounds
(:144) with train-time random-crop-227+mirror+mean-subtract closures
(:155-169) and center-crop test preprocessing (:117-131), eval every 10
rounds aggregated across workers (:106-141).  The crop/mirror/mean hot loop
runs in the native C++ pipeline; ``--synthetic`` fabricates resized images
so the app smoke-runs with no dataset.

Run:  python -m sparknet_tpu.apps.imagenet_app --workers 8 --rounds 3 \
          --synthetic --batch 16
"""

from __future__ import annotations

import argparse
import functools
import os
import time

import numpy as np

from typing import Any

from ..data.imagenet import load_imagenet
from ..data.partition import PartitionedDataset
from ..data.transforms import center_crop, random_crop_mirror
from ..models import alexnet, caffenet, googlenet, vgg16
from ..parallel import (
    DistributedTrainer,
    TrainerConfig,
    device_crop_mirror_mean,
    make_mesh,
)
from ..proto import load_solver_prototxt_with_net
from ..utils.timing import PhaseLogger
from ..parallel.cluster import global_max
from .common import RoundFeed, eval_feed, run_training

SOLVER = """
base_lr: 0.01
momentum: 0.9
weight_decay: 0.0005
lr_policy: "step"
gamma: 0.1
stepsize: 100000
"""

MODELS = {"alexnet": alexnet, "caffenet": caffenet, "googlenet": googlenet,
          "vgg16": vgg16}


def synthetic_imagenet(n: int, size: int, classes: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=n)
    x = rng.normal(scale=30.0, size=(n, 3, size, size)).astype(np.float32) + 120
    for i in range(n):
        k = labels[i]
        x[i, k % 3, (7 * k) % size, :] += 80.0
    return np.clip(x, 0, 255), labels.astype(np.int32)


def main(argv=None) -> dict[str, Any]:
    ap = argparse.ArgumentParser(description="ImageNet parameter-averaging app")
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--tar-dir", default=None,
                    help="directory of .tar archives of JPEGs")
    ap.add_argument("--label-file", default=None, help="train.txt label map")
    ap.add_argument("--test-tar-dir", default=None)
    ap.add_argument("--test-label-file", default=None)
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--model", choices=sorted(MODELS), default="caffenet")
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--batch", type=int, default=32,
                    help="per-worker minibatch size")
    ap.add_argument("--tau", type=int, default=50,
                    help="local steps per round (ImageNetApp.scala:144)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--test-interval", type=int, default=10)
    ap.add_argument("--strategy", choices=["local_sgd", "sync"],
                    default="local_sgd")
    ap.add_argument("--resize", type=int, default=256)
    ap.add_argument("--crop", type=int, default=None,
                    help="default 227 (AlexNet-class) / 224 (GoogLeNet, VGG)")
    ap.add_argument("--base-lr", type=float, default=None)
    ap.add_argument("--device-preprocess", action="store_true",
                    help="random crop/mirror/mean INSIDE the compiled "
                         "round (host ships raw full-size images — for "
                         "hosts whose CPUs can't keep up with the chips)")
    ap.add_argument("--snapshot", default=None)
    ap.add_argument("--log-dir", default=".")
    args = ap.parse_args(argv)

    from ..utils.compile_cache import use_compile_cache
    use_compile_cache()
    crop = args.crop or (227 if args.model in ("alexnet", "caffenet") else 224)

    log = PhaseLogger(os.path.join(
        args.log_dir, f"training_log_{int(time.time())}.txt"))
    mesh = make_mesh(args.workers)
    workers = mesh.shape["data"]

    if args.synthetic or args.tar_dir is None:
        log.log("using synthetic ImageNet-like data")
        need = args.batch * workers * (args.tau + 2)
        train_x, train_y = synthetic_imagenet(need, args.resize, args.classes, 1)
        test_x, test_y = synthetic_imagenet(
            max(args.batch * workers * 2, 64), args.resize, args.classes, 2)
        train_ds = PartitionedDataset.from_items(
            list(zip(train_x, train_y)), workers)
        test_ds = PartitionedDataset.from_items(
            list(zip(test_x, test_y)), workers)
    else:
        log.log(f"loading tars from {args.tar_dir}")
        train_ds = load_imagenet(args.tar_dir, args.label_file, workers,
                                 size=args.resize)
        test_ds = load_imagenet(args.test_tar_dir or args.tar_dir,
                                args.test_label_file or args.label_file,
                                workers, size=args.resize)
    log.log(f"train/test partitions: {train_ds.partition_sizes()} / "
            f"{test_ds.partition_sizes()}")

    # distributed mean image over train partitions (ComputeMean analog; the
    # per-partition sums run in the native pipeline)
    from .. import native
    acc = np.zeros((3, args.resize, args.resize), np.float64)
    count = 0
    for p in train_ds.partitions:
        # chunked so the accumulation never copies a whole partition
        for i in range(0, len(p), 64):
            imgs = np.stack([x for x, _ in p[i:i + 64]]).astype(np.float32)
            native.accumulate_mean(imgs, acc)
        count += len(p)
    mean = (acc / max(count, 1)).astype(np.float32)
    log.log("computed mean image")

    test_pre = functools.partial(center_crop, crop=crop, mean=mean)
    if args.device_preprocess:
        train_pre = None  # host ships raw images; crop runs on-device
        device_pre = device_crop_mirror_mean(crop, mirror=True, mean=mean)
    else:
        train_pre = functools.partial(random_crop_mirror, crop=crop,
                                      rng=np.random.default_rng(7),
                                      mean=mean)
        device_pre = None

    net = MODELS[args.model](args.batch * workers, args.batch * workers,
                             crop=crop)
    sp = load_solver_prototxt_with_net(SOLVER, net)
    if args.base_lr is not None:
        sp.base_lr = args.base_lr
    trainer = DistributedTrainer(
        sp, mesh, TrainerConfig(strategy=args.strategy, tau=args.tau,
                                device_preprocess=device_pre), seed=0)
    log.log(f"built {args.model} on {workers}-worker mesh "
            f"({args.strategy}, tau={args.tau}, crop={crop}, "
            f"{'device' if device_pre else 'host'} preprocess)")

    feed = RoundFeed(train_ds, args.batch, trainer.batches_per_round,
                     preprocess=train_pre, seed=3)
    test_factory, test_steps = eval_feed(test_ds, args.batch,
                                         preprocess=lambda x: test_pre(x))
    test_steps = global_max(test_steps)  # lockstep step count across hosts
    scores = run_training(trainer, feed, test_factory, test_steps,
                          rounds=args.rounds,
                          test_interval=args.test_interval, logger=log)
    if args.snapshot:
        trainer.snapshot(args.snapshot)
        log.log(f"snapshot -> {args.snapshot}")
    return scores


if __name__ == "__main__":
    main()
