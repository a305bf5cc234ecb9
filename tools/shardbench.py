"""Hybrid-sharding parity gate (the tensor-sharded analog of commbench).

Six verdicts on a small CPU mesh (~seconds), any failure = rc 1:

1. **three-strategy bit parity** — a trainer with ``shard="auto"``
   (parallel/partition.py's rule table sharding FC weights across chips)
   must produce bit-identical losses AND bit-identical gathered params
   to the replicated (``shard="off"``) trainer, same seed, codec none,
   for every strategy: local_sgd and sync on the flat mesh,
   hierarchical on a (host, chip) pod mesh.  The reduce-scatter/pmean
   identity is asserted, not assumed.
2. **codec composition** — the int8 compressed exchange composed with
   sharding stays bit-identical to the int8 dp run (decode lands the
   params sharded; the wire arithmetic is untouched).
3. **per-shard checkpoint roundtrip** — ``shard_checkpoint=True`` writes
   one common npz + one npz per shard tile under a checksummed manifest;
   a fresh trainer resumes from them with bit-identical params and an
   identical continuation loss.
4. **elastic re-tile** — a checkpoint written under the world-N shard
   plan restores into a world-M trainer (different plan, different tile
   shapes) with gathered params bit-identical to the consensus that was
   checkpointed, and training continues finite.
5. **audit under sharding** — the [n_pos, 2] shard-aware fingerprint
   passes on a healthy mesh, a planted one-bit flip on replica 2 is
   caught with exactly [2] as the culprit set, and the audit trip's
   checkpoint rollback restores a state that re-passes the audit.
6. **boundary-byte shrink** — analytic per-chip τ-boundary bytes under
   the plan must shrink vs pure DP on BOTH the gate model and
   caffenet-class shapes (where FC dominates: the shrink the paper's
   cheap-interconnect regime actually buys; asserted ≥ 2× at 8 shards).

Wired into tools/run_tier1.sh behind SPARKNET_SHARDSMOKE=1 (or
``--shardsmoke``); the JSON doc ingests into the perf ledger via
``perfwatch regress --ingest`` (entries_from_shardbench).

Usage:
    python tools/shardbench.py [--rounds 3] [--devices 8] [--out FILE]

Prints one JSON line on stdout; rc 0 = all gates hold.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CAFFENET_MIN_SHRINK_X = 2.0   # at 8 shards the analytic value is ~5.6x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--devices", type=int, default=4,
                    help="CPU mesh width (virtual devices); 4 keeps "
                    "lenet's 500-unit ip1 divisible")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.models import lenet
    from sparknet_tpu.models.alexnet import caffenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, comms, make_mesh,
        make_pod_mesh, partition,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.proto.caffe_pb import NetState, Phase

    tau = args.tau
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.005\nmomentum: 0.9\nlr_policy: "fixed"\n',
        lenet(args.batch, args.batch))
    mesh = make_mesh(args.devices)

    def batch(r):
        rng = np.random.default_rng(4200 + r)
        return {"data": rng.normal(size=(tau, args.batch, 1, 28, 28)
                                   ).astype(np.float32),
                "label": rng.integers(0, 10, size=(tau, args.batch)
                                      ).astype(np.float32)}

    def run(cfg: TrainerConfig, use_mesh=None, rounds=None) -> dict:
        tr = DistributedTrainer(sp, use_mesh or mesh, cfg, seed=0)
        losses = []
        t0 = time.perf_counter()
        for r in range(rounds or args.rounds):
            losses.append(tr.train_round(batch(r)))
        tr.drain()
        jax.block_until_ready(tr.params)
        dt = time.perf_counter() - t0
        # sharded leaves are still GLOBAL arrays with full logical
        # shape; np.asarray fetches the assembled value either way
        return {
            "trainer": tr,
            "losses": losses,
            "params": {k: [np.asarray(b) for b in v]
                       for k, v in tr.params.items()},
            "round_s": round(dt / (rounds or args.rounds), 4),
        }

    def bit_identical(a: dict, b: dict) -> list[str]:
        out = []
        if a["losses"] != b["losses"]:
            out.append(f"losses diverge: {a['losses']} vs {b['losses']}")
        for name, blobs in a["params"].items():
            for i, x in enumerate(blobs):
                if not np.array_equal(x, b["params"][name][i]):
                    out.append(f"param {name}[{i}] not bit-identical")
        return out

    failures: list[str] = []
    pod = make_pod_mesh(2, args.devices // 2)

    # -- 1. dp vs sharded bit parity, all three strategies ----------------
    parity: dict[str, bool] = {}
    legs: dict[str, dict] = {}
    for strat, m in (("local_sgd", mesh), ("sync", mesh),
                     ("hierarchical", pod)):
        dp = run(TrainerConfig(strategy=strat, tau=tau, shard="off"),
                 use_mesh=m)
        sh = run(TrainerConfig(strategy=strat, tau=tau, shard="auto"),
                 use_mesh=m)
        if sh["trainer"].shard_plan is None:
            failures.append(f"[plan] {strat}: shard='auto' resolved to "
                            f"no plan — nothing was sharded")
        mismatch = bit_identical(dp, sh)
        parity[strat] = not mismatch
        failures += [f"[parity-{strat}] {m2}" for m2 in mismatch]
        legs[strat] = {"dp": dp, "sharded": sh}
    plan = legs["local_sgd"]["sharded"]["trainer"].shard_plan
    plan_id = legs["local_sgd"]["sharded"]["trainer"].shard_plan_id

    # -- 2. int8 compressed exchange composed with sharding ---------------
    int8_dp = run(TrainerConfig(strategy="local_sgd", tau=tau,
                                comm_codec="int8", shard="off"))
    int8_sh = run(TrainerConfig(strategy="local_sgd", tau=tau,
                                comm_codec="int8", shard="auto"))
    codec_mismatch = bit_identical(int8_dp, int8_sh)
    failures += [f"[codec-int8] {m2}" for m2 in codec_mismatch]

    # -- 3 + 4 + 5. the sharded safety plane ------------------------------
    ckpt_ok = elastic_ok = audit_ok = False
    with tempfile.TemporaryDirectory() as ck:
        cfg = TrainerConfig(strategy="local_sgd", tau=tau, shard="auto",
                            shard_checkpoint=True, checkpoint_dir=ck,
                            checkpoint_every=1, checkpoint_keep=8,
                            audit_every=1, elastic=True)
        tr = DistributedTrainer(sp, mesh, cfg, seed=0)
        for r in range(2):
            tr.train_round(batch(r))
        tr.drain()
        consensus = {k: [np.asarray(b) for b in v]
                     for k, v in tr.params.items()}
        shard_files = glob.glob(os.path.join(ck, "*.shard*.npz"))
        if not shard_files:
            failures.append("[ckpt] shard_checkpoint=True wrote no "
                            "per-shard npz tiles")
        # 3: fresh same-world trainer resumes the tiles bit-exactly;
        # checkpoint_every bumped so only tr keeps writing into ck
        cfg2 = TrainerConfig(strategy="local_sgd", tau=tau, shard="auto",
                             shard_checkpoint=True, checkpoint_dir=ck,
                             checkpoint_every=64, checkpoint_keep=8,
                             audit_every=1, elastic=True)
        tr2 = DistributedTrainer(sp, mesh, cfg2, seed=0)
        got = {k: [np.asarray(b) for b in v]
               for k, v in tr2.params.items()}
        ckpt_mismatch = bit_identical({"losses": [], "params": consensus},
                                      {"losses": [], "params": got})
        cont_a = tr.train_round(batch(2))
        cont_b = tr2.train_round(batch(2))
        if np.float32(cont_a).tobytes() != np.float32(cont_b).tobytes():
            ckpt_mismatch.append(
                f"continuation loss diverges: {cont_a} vs {cont_b}")
        tr.drain()
        tr2.drain()
        ckpt_ok = not ckpt_mismatch
        failures += [f"[ckpt] {m2}" for m2 in ckpt_mismatch]
        # 4: restore the world-N tiles on a world-M mesh (new plan)
        half = make_mesh(args.devices // 2)
        tr_half = DistributedTrainer(sp, half, cfg2, seed=0)
        got_half = {k: [np.asarray(b) for b in v]
                    for k, v in tr_half.params.items()}
        # tr_half resumed the round-2 checkpoint tr wrote after its
        # continuation round — compare against tr's current params
        now = {k: [np.asarray(b) for b in v]
               for k, v in tr.params.items()}
        elastic_mismatch = bit_identical(
            {"losses": [], "params": now},
            {"losses": [], "params": got_half})
        cont = tr_half.train_round(batch(3))
        tr_half.drain()
        if not np.isfinite(list(tr_half.round_losses.values())[-1]
                           if tr_half.round_losses else cont):
            elastic_mismatch.append("re-tiled continuation non-finite")
        elastic_ok = not elastic_mismatch
        failures += [f"[elastic] {m2}" for m2 in elastic_mismatch]
        # 5: audit — healthy pass, planted flip caught, rollback re-passes
        fps = tr.audit_params()
        audit_msgs = []
        if np.asarray(fps).shape != (args.devices, 2):
            audit_msgs.append(f"sharded fingerprint shape "
                              f"{np.asarray(fps).shape} != "
                              f"({args.devices}, 2)")
        if not tr._audit_ok(fps):
            audit_msgs.append(f"healthy mesh failed the audit: {fps}")
        tr._inject_bitflip(2)
        fps2 = tr.audit_params()
        culprits = tr._audit_culprits(fps2)
        if culprits != [2]:
            audit_msgs.append(f"planted flip on replica 2 blamed "
                              f"{culprits}")
        nan = tr.train_round(batch(4))     # trips, rolls back
        if not np.isnan(nan):
            audit_msgs.append("tripped round did not report nan")
        if not tr._audit_ok(tr.audit_params()):
            audit_msgs.append("audit still failing after rollback")
        audit_ok = not audit_msgs
        failures += [f"[audit] {m2}" for m2 in audit_msgs]
        # a round that was NOT rejected has scheduled an asynchronous
        # checkpoint into ck: settle it before the directory goes, so a
        # missed audit is reported as the failure it is and not as a
        # clean-up race
        tr.drain()

    # -- 6. analytic boundary/exchange bytes ------------------------------
    probe = legs["local_sgd"]["sharded"]["trainer"]
    bytes_dp = partition.boundary_bytes_per_chip(probe.params, None)
    bytes_sh = partition.boundary_bytes_per_chip(probe.params, plan)
    shrink = round(bytes_dp / max(bytes_sh, 1), 3)
    none = comms.get_codec("none")
    ex_dp = comms.exchange_bytes(none, probe.params, args.devices)
    ex_sh = comms.sharded_exchange_bytes(none, probe.params,
                                         args.devices, plan)
    if not bytes_sh < bytes_dp:
        failures.append(f"[bytes] plan did not shrink the boundary: "
                        f"{bytes_sh} vs {bytes_dp}")
    # caffenet-class shapes: FC-dominated, the regime the rule table
    # targets.  eval_shape only — no 200 MB of params on the CPU rig.
    cnet_sp = load_solver_prototxt_with_net(
        'base_lr: 0.01\nlr_policy: "fixed"\n', caffenet(8, 8))
    cnet = Net(cnet_sp.net_param or cnet_sp.train_net_param,
               NetState(Phase.TRAIN))
    cnet_shapes = jax.eval_shape(cnet.init, jax.random.PRNGKey(0))
    cnet_plan = partition.resolve_plan("auto", cnet_shapes, axis="data",
                                       n_shards=8)
    cnet_dp = partition.boundary_bytes_per_chip(cnet_shapes, None)
    cnet_sh = partition.boundary_bytes_per_chip(cnet_shapes, cnet_plan)
    cnet_shrink = round(cnet_dp / max(cnet_sh, 1), 3)
    if cnet_plan is None or cnet_shrink < CAFFENET_MIN_SHRINK_X:
        failures.append(f"[bytes] caffenet-class shrink {cnet_shrink}x "
                        f"< {CAFFENET_MIN_SHRINK_X}x at 8 shards")

    result = {
        "shardbench": True,  # ingest sniff key (perfledger.entries_from_any)
        "ok": not failures,
        "failures": failures,
        "backend": jax.default_backend(),
        "model": "lenet",
        "rounds": args.rounds,
        "tau": tau,
        "batch": args.batch,
        "devices": args.devices,
        "plan": plan_id,
        "plan_dims": plan.dims_dict() if plan else {},
        "parity": parity,
        "codec_int8_parity": not codec_mismatch,
        "ckpt_roundtrip_ok": ckpt_ok,
        "elastic_ok": elastic_ok,
        "audit_ok": audit_ok,
        "dp": {"round_s": legs["local_sgd"]["dp"]["round_s"],
               "boundary_bytes_per_chip": bytes_dp,
               "exchange_bytes": ex_dp},
        "sharded": {"round_s": legs["local_sgd"]["sharded"]["round_s"],
                    "boundary_bytes_per_chip": bytes_sh,
                    "exchange_bytes": ex_sh},
        "shard_bytes_shrink_x": shrink,
        "caffenet": {"plan": partition.shard_plan_id(cnet_plan),
                     "boundary_bytes_dp": cnet_dp,
                     "boundary_bytes_sharded": cnet_sh,
                     "shrink_x": cnet_shrink},
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[shardbench] GATE FAILURE: {failures}", file=sys.stderr,
              flush=True)
        return 1
    print(f"[shardbench] all gates hold: 3-strategy bit parity, int8 "
          f"composition, per-shard ckpt roundtrip, elastic re-tile, "
          f"shard-aware audit; boundary bytes {shrink}x smaller "
          f"(caffenet-class {cnet_shrink}x at 8 shards)",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    # standalone: a CPU parity gate — hold the process to the CPU with a
    # virtual mesh BEFORE jax initializes (as tests/conftest.py does); the
    # result names the backend it ran on
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    raise SystemExit(main())
