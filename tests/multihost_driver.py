"""Driver run by the two-process jax.distributed test (and reusable by
hand): trains a fixed lenet workload over the global mesh and dumps final
params.  Each process feeds only ITS rows of the deterministic global batch
(the per-host partition placement of ImageNetApp.scala:145).

Resilience rig: ``--ckpt-dir`` turns on round-granular checkpointing
(params + per-worker solver state + round counter + RNG, manifest with
checksum), and a relaunched driver auto-resumes from the newest valid
manifest.  Every round start passes through the fault-injection hook
(``SPARKNET_FAULT=crash@round:N@rank:R`` etc., utils/faults.py), so the
chaos tests can kill a rank deterministically and assert the restarted
job converges to the fault-free result.  Per-round data is derived from
the ROUND INDEX alone (not a running RNG stream), so a resumed round
refeeds exactly the batch the killed round would have seen.

Elastic rig: ``--elastic`` lets the trainer resume a checkpoint written
by a DIFFERENT worker count (the re-formed survivor set), ``--guard``
arms the numerical-integrity guard (NaN/Inf → rollback), and the round
loop is driven by ``tr.round`` so a guard rollback naturally replays the
dropped round.  Heartbeats are published whenever the launcher sets
SPARKNET_HEARTBEAT_DIR.  SIGTERM/SIGINT trigger one final round
checkpoint before a clean exit (preemption contract, utils/signals.py).

Invoked by sparknet_tpu.tools.launch (env contract) or standalone
single-process with --local-devices N.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def round_batch(r, tau, global_batch):
    """Deterministic per-round lenet batch, a pure function of the round
    index — the property that makes round-granular resume exact."""
    import numpy as np
    rng = np.random.default_rng(1000 + r)
    y = rng.integers(0, 10, size=(tau, global_batch))
    x = rng.normal(scale=0.3, size=(tau, global_batch, 1, 28, 28)
                   ).astype(np.float32)
    for t in range(tau):
        for i, k in enumerate(y[t]):
            x[t, i, :, int(k) % 28, :] += 2.0
    return x, y


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="sync")
    ap.add_argument("--out", required=True)
    ap.add_argument("--local-devices", type=int, default=None,
                    help="single-process mode: virtual CPU device count")
    ap.add_argument("--expect-devices", type=int, default=4,
                    help="global device count the mesh must have "
                         "(0 = don't check — elastic worlds vary)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None,
                    help="round-granular checkpoint/auto-resume directory")
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--elastic", action="store_true",
                    help="allow resuming a checkpoint from a different "
                         "worker count (degraded-mode re-form)")
    ap.add_argument("--guard", action="store_true",
                    help="arm the numerical-integrity guard (needs "
                         "--ckpt-dir)")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="cross-replica parameter audit cadence in rounds "
                         "(0 = off; needs --ckpt-dir)")
    ap.add_argument("--harvest-lag", type=int, default=0,
                    help="zero-stall outer loop: keep up to K rounds in "
                         "flight, harvesting loss/guard/audit verdicts "
                         "up to K rounds late (0 = synchronous)")
    ap.add_argument("--fail-rank", type=int, default=None,
                    help="failure-path mode: this rank dies (exit 3) after "
                         "the first round")
    args = ap.parse_args()

    if args.local_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.local_devices}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from sparknet_tpu.models import lenet
    from sparknet_tpu.parallel import DistributedTrainer, TrainerConfig, make_mesh
    from sparknet_tpu.parallel.cluster import (
        init_cluster_from_env, local_batch_slice,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.utils import faults
    from sparknet_tpu.utils.signals import SolverAction, preemption_guard

    distributed = init_cluster_from_env()
    if args.strategy == "hierarchical":
        # host axis = real processes when distributed (so the weight
        # averaging crosses the process boundary like DCN would); a
        # single-process run folds the same 2-host topology virtually
        from sparknet_tpu.parallel import make_pod_mesh
        n_hosts = jax.process_count() if jax.process_count() > 1 else 2
        mesh = make_pod_mesh(n_hosts)
        n_devices = mesh.shape["host"] * mesh.shape["chip"]
    else:
        mesh = make_mesh()
        n_devices = mesh.shape["data"]
    if args.expect_devices:
        assert n_devices == args.expect_devices, (
            f"expected {args.expect_devices} global devices, got {n_devices}")

    GLOBAL_BATCH, TAU = args.global_batch, 2
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.05\nmomentum: 0.9\nlr_policy: "fixed"\n',
        lenet(GLOBAL_BATCH, GLOBAL_BATCH))
    tr = DistributedTrainer(
        sp, mesh,
        TrainerConfig(strategy=args.strategy, tau=TAU,
                      checkpoint_dir=args.ckpt_dir,
                      checkpoint_every=args.ckpt_every,
                      elastic=args.elastic,
                      guard_numerics=args.guard,
                      audit_every=args.audit_every,
                      harvest_lag=args.harvest_lag),
        seed=0)
    rows = local_batch_slice(GLOBAL_BATCH)
    injector = faults.get_injector()
    rank = jax.process_index()
    if tr.resumed:
        print(f"driver: resumed at round {tr.round} (attempt "
              f"{injector.attempt})", flush=True)

    losses = []
    preempted = False
    with preemption_guard() as guard:
        # driven by tr.round, not a range(): a guard rollback rewinds
        # tr.round and the loop replays the dropped round.  The OUTER
        # loop covers the pipelined case: a deferred verdict can trip
        # during drain() — after the inner loop already exited — which
        # rewinds tr.round again, and the dropped rounds must replay.
        while True:
            while tr.round < args.rounds:
                action = guard.check()
                if action in (SolverAction.SNAPSHOT,
                              SolverAction.SNAPSHOT_STOP):
                    if args.ckpt_dir:
                        print(f"driver: signal checkpoint at round "
                              f"{tr.round}", flush=True)
                        tr.drain()   # settle in-flight rounds first
                        tr.save_round_checkpoint()
                        tr.flush_checkpoints()   # durable BEFORE the exit
                if action in (SolverAction.STOP,
                              SolverAction.SNAPSHOT_STOP):
                    print(f"driver: preempted; stopped cleanly at round "
                          f"boundary {tr.round}", flush=True)
                    preempted = True
                    break
                r = tr.round
                injector.on_round(r, rank=rank)
                x, y = round_batch(r, TAU, GLOBAL_BATCH)
                loss = tr.train_round(
                    {"data": x[:, rows],
                     "label": y[:, rows].astype(np.float32)})
                losses.append(loss)
                print(f"driver: round {r} done loss={loss:.4f}",
                      flush=True)
                if r == 0 and args.fail_rank is not None \
                        and jax.process_index() == args.fail_rank:
                    print(f"driver: rank {args.fail_rank} dying "
                          f"(failure-path test)", flush=True)
                    os._exit(3)
            if preempted:
                break
            # settle every in-flight verdict + async checkpoint write; a
            # trip here rewinds tr.round and the outer loop replays
            tr.drain()
            if tr.round >= args.rounds:
                break

    if preempted:
        return  # clean exit: the relaunch resumes from the checkpoint

    # pipelined mode: exact per-round losses live in tr.round_losses
    if args.harvest_lag:
        losses = [tr.round_losses[r] for r in range(args.rounds)]

    erng = np.random.default_rng(2000)
    eval_y = erng.integers(0, 10, size=(GLOBAL_BATCH,))
    eval_x = erng.normal(scale=0.3, size=(GLOBAL_BATCH, 1, 28, 28)
                         ).astype(np.float32)
    feed = iter([{"data": eval_x[rows],
                  "label": eval_y[rows].astype(np.float32)}] * 2)
    scores = tr.test(feed, num_steps=2)

    if jax.process_index() == 0:
        flat = {}
        for lname, blobs in tr.params.items():
            for i, b in enumerate(blobs):
                flat[f"{lname}/{i}"] = np.asarray(b)
        flat["__losses__"] = np.asarray(losses)
        flat["__guard_trips__"] = np.asarray(tr.guard_trips)
        flat["__audit_trips__"] = np.asarray(tr.audit_trips)
        flat["__scores__"] = np.asarray(
            [scores.get("loss", 0.0), scores.get("accuracy", 0.0)])
        np.savez(args.out, **flat)
        print(f"driver ok: distributed={distributed} "
              f"procs={jax.process_count()} losses={losses}")


if __name__ == "__main__":
    sys.exit(main())
