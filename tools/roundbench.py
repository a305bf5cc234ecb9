"""Sync-vs-async outer-loop parity microbench (the round_overhead gate).

The zero-stall outer loop (TrainerConfig.harvest_lag round pipelining +
the AsyncCheckpointWriter) must be a pure LATENCY optimization: with
checkpointing + numerics guard + cross-replica audit all enabled, the
async loop has to produce exactly the same round losses, bit-identical
final parameters, and byte-identical newest checkpoint content as the
synchronous loop.  This tool runs both loops on a small CPU mesh
(~seconds), FAILS on any divergence, and reports the per-component host
stall seconds (loss_fetch / finite_check / audit_fetch / checkpoint)
for each mode — the same accounting bench.py's ``round_overhead`` leg
captures on the real chip.

Wired into tools/run_tier1.sh behind SPARKNET_ROUNDBENCH=1 (or
``--roundbench``); also exercised in-process by tests/test_resilience.py.

Usage:
    python tools/roundbench.py [--rounds 6] [--lag 2] [--devices 4]
        [--out FILE]

Prints one JSON line on stdout; rc 0 = parity holds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--lag", type=int, default=2,
                    help="harvest_lag / pipeline depth of the async loop")
    ap.add_argument("--devices", type=int, default=4,
                    help="CPU mesh width (virtual devices)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from sparknet_tpu.models import lenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, make_mesh,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.utils.checkpoint import load_checkpoint

    tau = 2
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.005\nmomentum: 0.9\nlr_policy: "fixed"\n',
        lenet(args.batch, args.batch))

    def batch(r):
        rng = np.random.default_rng(4200 + r)
        return {"data": rng.normal(size=(tau, args.batch, 1, 28, 28)
                                   ).astype(np.float32),
                "label": rng.integers(0, 10, size=(tau, args.batch)
                                      ).astype(np.float32)}

    def run(mode: str, ckdir: str) -> dict:
        from sparknet_tpu.utils import knobs
        saved = knobs.raw("SPARKNET_ASYNC_CKPT")
        os.environ["SPARKNET_ASYNC_CKPT"] = "1" if mode == "async" else "0"
        try:
            cfg = TrainerConfig(
                strategy="local_sgd", tau=tau, checkpoint_dir=ckdir,
                checkpoint_keep=4, guard_numerics=True, audit_every=1,
                harvest_lag=args.lag if mode == "async" else 0)
            tr = DistributedTrainer(sp, make_mesh(args.devices), cfg,
                                    seed=0)
            t0 = time.perf_counter()
            while tr.round < args.rounds:
                tr.train_round(batch(tr.round))
            losses = tr.drain()
            dt = time.perf_counter() - t0
        finally:
            if saved is None:
                os.environ.pop("SPARKNET_ASYNC_CKPT", None)
            else:
                os.environ["SPARKNET_ASYNC_CKPT"] = saved
        newest = sorted(f for f in os.listdir(ckdir)
                        if f.endswith(".npz"))[-1]
        return {
            "losses": [losses[r] for r in range(args.rounds)],
            "params": {k: [np.asarray(b) for b in v]
                       for k, v in tr.params.items()},
            "newest_ckpt": newest,
            "ckpt_blob": load_checkpoint(os.path.join(ckdir, newest)),
            "wall_s": round(dt, 3),
            "stall_s": {k: round(v, 4) for k, v in tr.stall_s.items()},
        }

    failures: list[str] = []
    with tempfile.TemporaryDirectory() as d_sync, \
            tempfile.TemporaryDirectory() as d_async:
        sync = run("sync", d_sync)
        async_ = run("async", d_async)

    if sync["losses"] != async_["losses"]:
        failures.append(f"round losses diverge: sync {sync['losses']} "
                        f"vs async {async_['losses']}")
    for name, blobs in sync["params"].items():
        for i, b in enumerate(blobs):
            if not np.array_equal(b, async_["params"][name][i]):
                failures.append(f"param {name}[{i}] not bit-identical")
    if sync["newest_ckpt"] != async_["newest_ckpt"]:
        failures.append(f"newest checkpoint differs: "
                        f"{sync['newest_ckpt']} vs {async_['newest_ckpt']}")
    else:
        for key in ("params", "state", "iter", "round", "rng"):
            a = jax.tree_util.tree_leaves(sync["ckpt_blob"][key])
            b = jax.tree_util.tree_leaves(async_["ckpt_blob"][key])
            if len(a) != len(b) or any(
                    not np.array_equal(x, y) for x, y in zip(a, b)):
                failures.append(f"checkpoint field {key!r} not "
                                f"bit-identical")

    result = {
        "ok": not failures,
        "failures": failures,
        "backend": jax.default_backend(),
        "rounds": args.rounds,
        "harvest_lag": args.lag,
        "devices": args.devices,
        "sync": {k: sync[k] for k in ("wall_s", "stall_s", "losses")},
        "async": {k: async_[k] for k in ("wall_s", "stall_s")},
        "stall_total_sync_s": round(sum(sync["stall_s"].values()), 4),
        "stall_total_async_s": round(sum(async_["stall_s"].values()), 4),
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[roundbench] PARITY FAILURE: {failures}", file=sys.stderr,
              flush=True)
        return 1
    print(f"[roundbench] parity holds over {args.rounds} rounds; host "
          f"stall {result['stall_total_sync_s']}s sync -> "
          f"{result['stall_total_async_s']}s async", file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    # standalone: a CPU parity gate — hold the process to the CPU with a
    # virtual mesh BEFORE jax initializes (as tests/conftest.py does); the
    # result names the backend it ran on
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    raise SystemExit(main())
