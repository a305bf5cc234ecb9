"""CifarApp — end-to-end CIFAR-10 training (reference:
src/main/scala/apps/CifarApp.scala).

Phases match the reference: load CIFAR binaries (shuffled train set,
CifarLoader.scala:34) → shard into one partition per worker → τ=10 rounds
of parameter-averaging local SGD (CifarApp.scala:111) with eval every 10
rounds (:93) — but the round itself is one compiled TPU program instead of
a Spark broadcast/collect cycle, and ``--synthetic`` fabricates
format-exact data so the app smoke-runs with no dataset present.

Run:  python -m sparknet_tpu.apps.cifar_app --workers 8 --rounds 20 --synthetic
"""

from __future__ import annotations

import argparse
import glob
import os
import time

import numpy as np

from typing import Any

from ..data import compute_mean_image, load_cifar10_binary
from ..data.partition import PartitionedDataset
from ..models import cifar10_full, cifar10_quick
from ..parallel import DistributedTrainer, TrainerConfig, make_mesh
from ..proto import load_solver_prototxt_with_net
from ..utils.timing import PhaseLogger
from ..parallel.cluster import global_max
from .common import RoundFeed, eval_feed, run_training

SOLVER = """
base_lr: 0.001
momentum: 0.9
weight_decay: 0.004
lr_policy: "fixed"
"""


def synthetic_cifar(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n)
    x = rng.normal(scale=20.0, size=(n, 3, 32, 32)).astype(np.float32) + 120
    for k in range(10):
        x[labels == k, k % 3, k:k + 3, :] += 60.0
    return np.clip(x, 0, 255), labels.astype(np.int32)


def main(argv=None) -> dict[str, Any]:
    ap = argparse.ArgumentParser(description="CIFAR-10 parameter-averaging app")
    ap.add_argument("--workers", type=int, default=None,
                    help="mesh size (default: all devices)")
    ap.add_argument("--data-dir", default=None,
                    help="dir with data_batch_*.bin/test_batch.bin")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--model", choices=["quick", "full"], default="quick")
    ap.add_argument("--batch", type=int, default=100,
                    help="per-worker minibatch size")
    ap.add_argument("--tau", type=int, default=10,
                    help="local steps per round (CifarApp.scala:111)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--test-interval", type=int, default=10)
    ap.add_argument("--strategy", choices=["local_sgd", "sync"],
                    default="local_sgd")
    ap.add_argument("--base-lr", type=float, default=None)
    ap.add_argument("--snapshot", default=None)
    ap.add_argument("--log-dir", default=".")
    args = ap.parse_args(argv)

    from ..utils.compile_cache import use_compile_cache
    use_compile_cache()

    log = PhaseLogger(os.path.join(
        args.log_dir, f"training_log_{int(time.time())}.txt"))

    if args.synthetic or args.data_dir is None:
        log.log("using synthetic CIFAR data")
        train_x, train_y = synthetic_cifar(4000, seed=1)
        test_x, test_y = synthetic_cifar(1000, seed=2)
    else:
        train_files = sorted(glob.glob(
            os.path.join(args.data_dir, "data_batch_*.bin")))
        train_x, train_y = load_cifar10_binary(train_files, shuffle=True)
        test_x, test_y = load_cifar10_binary(
            os.path.join(args.data_dir, "test_batch.bin"))
    log.log(f"loaded {len(train_y)} train / {len(test_y)} test images")

    mean = compute_mean_image(train_x)
    train_x = train_x - mean
    test_x = test_x - mean
    log.log("computed and subtracted mean image")

    mesh = make_mesh(args.workers)
    workers = mesh.shape["data"]
    model_fn = cifar10_quick if args.model == "quick" else cifar10_full
    net = model_fn(args.batch * workers, args.batch * workers)
    sp = load_solver_prototxt_with_net(SOLVER, net)
    if args.base_lr is not None:
        sp.base_lr = args.base_lr
    trainer = DistributedTrainer(
        sp, mesh, TrainerConfig(strategy=args.strategy, tau=args.tau), seed=0)
    log.log(f"built {args.model} net on {workers}-worker mesh "
            f"({args.strategy}, tau={args.tau})")

    train_ds = PartitionedDataset.from_items(
        list(zip(train_x, train_y)), workers)
    test_ds = PartitionedDataset.from_items(
        list(zip(test_x, test_y)), workers)
    feed = RoundFeed(train_ds, args.batch, trainer.batches_per_round, seed=3)
    test_factory, test_steps = eval_feed(test_ds, args.batch)
    test_steps = global_max(test_steps)  # lockstep across hosts

    scores = run_training(trainer, feed, test_factory, test_steps,
                          rounds=args.rounds,
                          test_interval=args.test_interval, logger=log)
    if args.snapshot:
        trainer.snapshot(args.snapshot)
        log.log(f"snapshot -> {args.snapshot}")
    return scores


if __name__ == "__main__":
    main()
