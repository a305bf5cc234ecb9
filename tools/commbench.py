"""Comm-codec parity gate (the compressed-exchange analog of roundbench).

Five verdicts on a small CPU mesh (~seconds), any failure = rc 1:

1. **codec-none bit-identity** — a trainer with ``comm_codec="none"``
   (overlap on OR off) must produce bit-identical losses and parameters
   to a trainer built with a pre-codec TrainerConfig: the codec machinery
   may not perturb the default path by even one ulp.
2. **error-feedback invariant** — for every real codec,
   ``decode(encode(delta)) + residual == delta`` exactly in f32 (the
   residual IS the deferred compression error).  A planted codec that
   drops residuals (``keep_residual=False``) MUST fail this gate — that
   failure is asserted, so the gate is proven able to catch the bug class
   it exists for.
3. **loss-band convergence** — int8/bf16 delta exchange with error
   feedback must land within a declared band of the full-precision
   trainer's loss after the same rounds (compression defers error, it
   must not change where training goes).
4. **overlap parity + stall** — ``comm_overlap=True`` must be
   bit-identical to False under a lossy codec, with strictly less
   steady-state host stall charged to the comm components (measured
   after a warm-up round so compile time is not the story).
5. **wire-byte shrink** — the int8 codec's per-round exchanged bytes
   must be ≥ 3× smaller than full precision (analytic, from the real
   encode via ``comms.exchange_bytes``).

Wired into tools/run_tier1.sh behind SPARKNET_COMMBENCH=1 (or
``--commbench``); the JSON doc ingests into the perf ledger via
``perfwatch regress --ingest`` (entries_from_commbench).

Usage:
    python tools/commbench.py [--rounds 8] [--devices 4] [--out FILE]

Prints one JSON line on stdout; rc 0 = all gates hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOSS_BAND = 0.05   # |final_loss_codec - final_loss_none| tolerance
REAL_CODECS = ("bf16", "int8", "int8_channel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--devices", type=int, default=4,
                    help="CPU mesh width (virtual devices)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.models import lenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, comms, make_mesh,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net

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

    def run(cfg: TrainerConfig, measure_stall: bool = False) -> dict:
        tr = DistributedTrainer(sp, mesh, cfg, seed=0)
        t0 = time.perf_counter()
        warm = 1 if measure_stall else 0
        losses = []
        for r in range(args.rounds):
            loss = tr.train_round(batch(r))
            if r + 1 == warm:
                # compile + first dispatch settled: zero the comm
                # components so the reported stall is steady-state
                jax.block_until_ready(tr.params)
                for k in ("comm_encode", "comm_allreduce", "comm_decode"):
                    tr.stall_s[k] = 0.0
                t0 = time.perf_counter()
            losses.append(loss)
        tr.drain()
        jax.block_until_ready(tr.params)
        dt = time.perf_counter() - t0
        return {
            "losses": losses,
            "params": {k: [np.asarray(b) for b in v]
                       for k, v in tr.params.items()},
            "wall_s": round(dt, 3),
            "stall_s": {k: round(v, 4) for k, v in tr.stall_s.items()},
            "comm_stall_s": round(sum(
                v for k, v in tr.stall_s.items()
                if k.startswith("comm_")), 4),
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

    # -- 1. codec none == the pre-codec trainer, overlap inert ------------
    base = run(TrainerConfig(strategy="local_sgd", tau=tau))
    none_off = run(TrainerConfig(strategy="local_sgd", tau=tau,
                                 comm_codec="none", comm_overlap=False))
    none_on = run(TrainerConfig(strategy="local_sgd", tau=tau,
                                comm_codec="none", comm_overlap=True))
    failures += [f"[none-vs-base] {m}" for m in bit_identical(base, none_off)]
    failures += [f"[none-overlap] {m}" for m in bit_identical(base, none_on)]

    # -- 2. error-feedback invariant; the planted residual-dropper FAILS --
    dropres = comms.Codec("int8_dropres",
                          encode=comms.get_codec("int8").encode,
                          decode=comms.get_codec("int8").decode,
                          keep_residual=False)
    rng = np.random.default_rng(7)
    delta = {
        "conv": [jnp.asarray(rng.normal(scale=1e-3, size=(4, 8, 1, 5, 5)),
                             jnp.float32)],
        "bias": [jnp.asarray(rng.normal(scale=1e-4, size=(4, 8)),
                             jnp.float32)],
    }

    def ef_invariant_holds(codec) -> bool:
        _, decoded, residual = comms.roundtrip_tree(codec, delta)
        recon = jax.tree_util.tree_map(lambda d, r: d + r, decoded, residual)
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree_util.tree_leaves(recon),
                                   jax.tree_util.tree_leaves(delta)))

    ef = {name: ef_invariant_holds(comms.get_codec(name))
          for name in REAL_CODECS}
    ef["int8_dropres"] = ef_invariant_holds(dropres)
    for name in REAL_CODECS:
        if not ef[name]:
            failures.append(f"[ef] codec {name} violates the "
                            f"error-feedback invariant")
    if ef["int8_dropres"]:
        failures.append("[ef] planted residual-dropping codec PASSED the "
                        "invariant gate — the gate is broken, not the codec")

    # -- 3 + 4. lossy codecs: loss band, overlap parity, stall ------------
    codec_runs: dict[str, dict] = {}
    for name in REAL_CODECS:
        r = run(TrainerConfig(strategy="local_sgd", tau=tau,
                              comm_codec=name), measure_stall=True)
        codec_runs[name] = r
        drift = abs(r["losses"][-1] - base["losses"][-1])
        if not np.isfinite(r["losses"][-1]) or drift > LOSS_BAND:
            failures.append(
                f"[band] codec {name} final loss {r['losses'][-1]:.4f} "
                f"vs none {base['losses'][-1]:.4f} (|Δ|={drift:.4f} > "
                f"{LOSS_BAND})")
    int8_overlap = run(TrainerConfig(strategy="local_sgd", tau=tau,
                                     comm_codec="int8", comm_overlap=True),
                       measure_stall=True)
    failures += [f"[overlap-int8] {m}"
                 for m in bit_identical(codec_runs["int8"], int8_overlap)]
    stall_sync = codec_runs["int8"]["comm_stall_s"]
    stall_overlap = int8_overlap["comm_stall_s"]
    if not stall_overlap < stall_sync:
        failures.append(
            f"[stall] overlap did not reduce comm stall: "
            f"{stall_overlap}s overlapped vs {stall_sync}s synchronous")

    # -- 5. wire bytes ----------------------------------------------------
    tr_probe = DistributedTrainer(
        sp, mesh, TrainerConfig(strategy="local_sgd", tau=tau), seed=0)
    n_tier = args.devices
    bytes_none = comms.exchange_bytes(comms.get_codec("none"),
                                      tr_probe.params, n_tier)
    bytes_by_codec = {
        name: comms.exchange_bytes(comms.get_codec(name), tr_probe.params,
                                   n_tier)
        for name in REAL_CODECS}
    shrink = round(bytes_none / bytes_by_codec["int8"], 3)
    if shrink < 3.0:
        failures.append(f"[bytes] int8 shrink {shrink}x < 3x")

    result = {
        "commbench": True,   # ingest sniff key (perfledger.entries_from_any)
        "ok": not failures,
        "failures": failures,
        "backend": jax.default_backend(),
        "rounds": args.rounds,
        "tau": tau,
        "batch": args.batch,
        "devices": args.devices,
        "ef_invariant": ef,
        "final_loss_none": base["losses"][-1],
        "none": {k: base[k] for k in ("wall_s", "stall_s")},
        "codecs": {
            name: {"wall_s": r["wall_s"], "stall_s": r["stall_s"],
                   "comm_stall_s": r["comm_stall_s"],
                   "final_loss": r["losses"][-1],
                   "exchange_bytes": bytes_by_codec[name]}
            for name, r in codec_runs.items()},
        "overlap_int8": {"wall_s": int8_overlap["wall_s"],
                         "stall_s": int8_overlap["stall_s"],
                         "comm_stall_s": stall_overlap},
        "exchange_bytes_none": bytes_none,
        "comm_stall_sync_s": stall_sync,
        "comm_stall_overlap_s": stall_overlap,
        "comm_bytes_shrink_x": shrink,
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[commbench] GATE FAILURE: {failures}", file=sys.stderr,
              flush=True)
        return 1
    print(f"[commbench] all gates hold: codec none bit-identical, EF "
          f"invariant green (planted dropper caught), int8 shrink "
          f"{shrink}x, comm stall {stall_sync}s sync -> {stall_overlap}s "
          f"overlapped", file=sys.stderr, flush=True)
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
