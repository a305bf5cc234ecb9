"""The SparkNet distributed workflow, end to end on a device mesh.

Mirrors what the reference's apps drive through Spark (ImageNetApp.scala
/ CifarApp.scala: shard data -> broadcast weights -> per-worker local
steps -> collect & average -> distributed eval), as the three trainer
strategies this framework compiles into single mesh programs:

  sync          per-step gradient averaging   (P2PSync, parallel.cpp)
  local_sgd     tau-step weight averaging     (the SparkNet algorithm)
  hierarchical  both composed on a (host, chip) pod mesh

Run:  python examples/distributed_workflow.py    (8 virtual CPU devices
      via XLA_FLAGS=--xla_force_host_platform_device_count=8, or a real
      multi-chip platform)
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from sparknet_tpu.models import lenet  # noqa: E402
from sparknet_tpu.parallel import (  # noqa: E402
    DistributedTrainer, TrainerConfig, make_mesh, make_pod_mesh,
)
from sparknet_tpu.proto import load_solver_prototxt_with_net  # noqa: E402

# lr 0.01: each local_sgd worker sees batch 4 here — 0.05 genuinely
# diverges in that regime (same setting the distributed tests use)
SOLVER = 'base_lr: 0.01\nmomentum: 0.9\nlr_policy: "fixed"\n'


def make_data(rng, tau, global_batch):
    """[tau, global_batch, ...] round feeds — a worker's rows are its
    partition slice (the zipPartitions placement)."""
    n = tau * global_batch
    y = rng.integers(0, 10, size=n)
    x = rng.normal(scale=0.3, size=(n, 1, 28, 28)).astype(np.float32)
    for k in range(10):
        x[y == k, :, k % 28, :] += 2.0
    return {"data": x.reshape(tau, global_batch, 1, 28, 28),
            "label": y.reshape(tau, global_batch).astype(np.float32)}


def main() -> None:
    n_dev = len(jax.devices())
    assert n_dev >= 8, f"want 8 devices for the demo, have {n_dev}"
    rng = np.random.default_rng(0)
    sp = load_solver_prototxt_with_net(SOLVER, lenet(32, 32))
    tau, global_batch = 5, 32

    # -- SparkNet rounds: tau local steps then weight averaging ----------
    tr = DistributedTrainer(sp, make_mesh(8),
                            TrainerConfig(strategy="local_sgd", tau=tau),
                            seed=0)
    losses = [tr.train_round(make_data(rng, tau, global_batch))
              for _ in range(6)]
    print(f"local_sgd: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"over {tr.iter} iters on {tr.n_workers} workers")
    assert losses[-1] < 0.5 * losses[0]

    # -- distributed eval: per-worker scores masked + psum'd -------------
    eval_data = make_data(rng, 1, global_batch)
    feed = iter([{"data": eval_data["data"][0],
                  "label": eval_data["label"][0]}] * 4)
    scores = tr.test(feed, num_steps=4)
    acc = scores["accuracy"] / scores["__test_batches__"]
    print(f"eval: accuracy {acc:.3f} over "
          f"{int(scores['__test_batches__'])} worker-batches")

    # -- snapshot / restore (momentum history included) ------------------
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "round.npz")
        tr.snapshot(path)
        tr2 = DistributedTrainer(
            sp, make_mesh(8), TrainerConfig(strategy="local_sgd", tau=tau),
            seed=1)
        tr2.restore(path)
        assert tr2.iter == tr.iter
        print(f"restored at iter {tr2.iter}; next round loss "
              f"{tr2.train_round(make_data(rng, tau, global_batch)):.3f}")

    # -- the composed pod: chip psum x host weight averaging -------------
    pod = make_pod_mesh(2, 4)
    hier = DistributedTrainer(sp, pod,
                              TrainerConfig(strategy="hierarchical",
                                            tau=tau), seed=0)
    hloss = [hier.train_round(make_data(rng, tau, global_batch))
             for _ in range(6)]
    print(f"hierarchical 2x4: loss {hloss[0]:.3f} -> {hloss[-1]:.3f} "
          f"(chip-axis psum per step, host-axis average per tau)")
    assert hloss[-1] < 0.5 * hloss[0]
    print("OK: distributed workflow complete")


if __name__ == "__main__":
    main()
