#!/usr/bin/env bash
# The tier-1 gate, exactly as ROADMAP.md specifies it, plus the chaos
# (fault-injection) subset — one entry point so CI and humans always run
# the same command.  Usage:
#   tools/run_tier1.sh            # tier-1 (everything not marked slow)
#   tools/run_tier1.sh --chaos    # only the chaos marker subset
#   tools/run_tier1.sh --all      # tier-1, then the chaos subset again
set -o pipefail
cd "$(dirname "$0")/.."

run_tier1() {
  rm -f /tmp/_t1.log
  timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
  rc=${PIPESTATUS[0]}
  echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
  return "$rc"
}

# ~1-second sparklint gate (tools/lint.py run) — DEFAULT ON, pure-AST +
# stdlib (no JAX, no devices): the tree must be clean modulo the
# committed tools/lint_baseline.json, and KNOBS.md must match the knob
# registry.  SPARKNET_LINT=0 is the opt-out for rigs that only want the
# pytest surface.
maybe_lint() {
  if [ "${SPARKNET_LINT:-1}" != "0" ]; then
    timeout -k 10 120 python tools/lint.py run       && timeout -k 10 60 python tools/lint.py knobs --check
  fi
}

run_chaos() {
  timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'chaos and not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
}

# 2-run chaos soak smoke (tools/soak.py) — opt-in via SPARKNET_SOAK=1 so
# the default tier-1 wall time is untouched; CI rigs that can afford it
# get randomized-but-seeded fault schedules checked for exact recovery.
maybe_soak() {
  if [ "${SPARKNET_SOAK:-}" = "1" ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python tools/soak.py --runs 2 --seed "${SPARKNET_SOAK_SEED:-0}" \
      --out /tmp/_soak.json
  fi
}

# ~2-second serial-vs-parallel feed microbench (tools/feedbench.py) —
# opt-in via SPARKNET_FEEDBENCH=1.  Fails the gate on any parity
# mismatch: the parallel pipeline must be bit-identical to the serial
# reference, including quarantine accounting under corrupt_record
# faults.  (A fast in-tree smoke of the same parity contract always
# runs inside tier-1: tests/test_pipeline.py.)
maybe_feedbench() {
  if [ "${SPARKNET_FEEDBENCH:-}" = "1" ]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
      python tools/feedbench.py --seconds 2 --out /tmp/_feedbench.json \
      && timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python tools/feedbench.py --seconds 2 --corrupt \
          --out /tmp/_feedbench_corrupt.json
  fi
}

# ~3-second record-shard parity gate (tools/feedbench.py --records-leg)
# — opt-in via SPARKNET_RECORDBENCH=1.  Converts a tiny synthetic LMDB
# to pre-decoded record shards and replays the SAME batches from local
# shards, from a VerifyingStore through the tiered ShardCache (RAM +
# disk spill), and warm — all must be bit-identical to the serial
# decode reference (pixels, labels, quarantine admissions), clean and
# under corrupt_record injection, with cold/warm cache-tier hits
# asserted and a planted corrupt shard block quarantined with source
# attribution.
maybe_recordbench() {
  if [ "${SPARKNET_RECORDBENCH:-}" = "1" ]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python tools/feedbench.py --seconds 2 --records-leg \
      --out /tmp/_recordbench.json \
      && timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/feedbench.py --seconds 2 --records-leg --corrupt \
          --out /tmp/_recordbench_corrupt.json
  fi
}

# ~60-second two-job fleet chaos smoke (tools/soak.py --fleet 2) — opt-in
# via SPARKNET_FLEETSOAK=1.  Two concurrent jobs under one FleetScheduler
# with pinned crash + preempt schedules, plus a late whole-budget
# high-priority preemptor: every job must finish bit-identical to its
# fault-free baseline, with preempt/resume exercised and zero orphaned
# worker processes.  (The full acceptance run is
# `python tools/soak.py --fleet 4 --fleet-kill`, which additionally
# SIGKILLs the scheduler mid-run and resumes it from its journal.)
maybe_fleetsoak() {
  if [ "${SPARKNET_FLEETSOAK:-}" = "1" ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python tools/soak.py --fleet 2 --seed "${SPARKNET_SOAK_SEED:-0}" \
      --out /tmp/_fleetsoak.json
  fi
}

# ~60-second simulated 3-host pod burn-in slice (tools/soak.py --pod 3
# --pod-slice) — opt-in via SPARKNET_PODSOAK=1.  Two training tenants +
# one replicated serving tenant on a 3-host simulated pod under the
# seeded traffic model, with one host-kill fired mid-leg through the
# host-control channel and one flash crowd: the episode must end with
# both trainings bit-identical to the fault-free baseline, zero
# client-visible serving errors, the serving tier healed, the
# corrupt-upload quarantine burst absorbed-and-typed, and zero orphaned
# workers.  (The full acceptance run adds the host-drain and
# serving-host-loss legs: `python tools/soak.py --pod 3`.)
maybe_podsoak() {
  if [ "${SPARKNET_PODSOAK:-}" = "1" ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python tools/soak.py --pod 3 --pod-slice \
      --seed "${SPARKNET_SOAK_SEED:-0}" --out /tmp/_podsoak.json
  fi
}

# ~60-second network chaos slice (tools/soak.py --net --net-slice) —
# opt-in via SPARKNET_NETSOAK=1.  Two legs over the production ssh wire
# format (SshTransport through a local fake-ssh shim) wrapped in
# ChaosTransport: a symmetric partition mid-round must SUSPEND the gang
# (suspect, not straggler-killed, no restart-budget burn), heal, and
# finish bit-identical to the fault-free baseline; and a fenced
# checkpoint ship — torn first transfer resumed crc-verified onto a
# checkpoint-less host, bit-identical resume, zombie writer refused at
# the fence with a typed error.  (The full acceptance run adds the
# slow-link-attribution leg: `python tools/soak.py --net`.)
maybe_netsoak() {
  if [ "${SPARKNET_NETSOAK:-}" = "1" ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python tools/soak.py --net --net-slice \
      --seed "${SPARKNET_SOAK_SEED:-0}" --out /tmp/_netsoak.json
  fi
}

# ~30-second rollout smoke (tools/soak.py --rollout) — opt-in via
# SPARKNET_ROLLSMOKE=1.  Three deployment-plane legs over a real model
# registry + router + per-version engines: a healthy canary must earn
# promotion (green per-version SLO verdicts over the request floor,
# old stable drained, pinned answers bit-identical across the pointer
# flip), a planted bad_canary fault (NaN-emitting head, failed TYPED
# by the engine) must auto-roll back within the judge window with zero
# stable-pinned errors and a flight dump on disk, and a controller
# killed mid-rollout must resume to exactly one of {fully stable,
# fully promoted} with no orphan replicas.
maybe_rollsmoke() {
  if [ "${SPARKNET_ROLLSMOKE:-}" = "1" ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
      python tools/soak.py --rollout --seed "${SPARKNET_SOAK_SEED:-0}" \
      --out /tmp/_rollsmoke.json
  fi
}

# ~2-second serving smoke (tools/serveload.py --smoke) — opt-in via
# SPARKNET_SERVESMOKE=1.  In-process engine + closed-loop clients;
# fails the gate unless results are bit-identical to solo references,
# p99 under 2x overload stays inside the admission bound, and the
# overload produces typed rejections (admission control engaged).
maybe_servesmoke() {
  if [ "${SPARKNET_SERVESMOKE:-}" = "1" ]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python tools/serveload.py --smoke --out /tmp/_servesmoke.json \
      > /dev/null
  fi
}

# Serving-fleet smoke (tools/serveload.py --fleet 2 --smoke) — opt-in
# via SPARKNET_FLEETSERVESMOKE=1.  Two replica subprocesses placed as
# serve-kind fleet tenants behind the request router: paced load must
# stay error-free and bit-identical to local solo references, a
# SIGKILLed replica must fail over typed-only (zero request errors,
# zero hangs) and heal back to N, and a mid-load scale-down must drain
# losslessly to COMPLETED.  (~10 s on a multicore rig; single-core CI
# boxes pay replica startup serially, hence the generous timeout.)
maybe_fleetservesmoke() {
  if [ "${SPARKNET_FLEETSERVESMOKE:-}" = "1" ]; then
    timeout -k 10 480 env JAX_PLATFORMS=cpu \
      python tools/serveload.py --fleet 2 --smoke \
      --out /tmp/_fleetservesmoke.json > /dev/null
  fi
}

# ~10-second observability smoke (tools/obs.py smoke) — opt-in via
# SPARKNET_OBSSMOKE=1.  Runs a 2-round training per rank (two driver
# runs sharing one SPARKNET_RUN_ID) plus a live tools/serve.py driven
# over HTTP, all with tracing on; fails the gate unless
# `tools/obs.py merge --check` yields a valid merged trace (spans from
# both ranks, correlation IDs on every span, aligned monotonic
# timestamps) and `GET /metrics` parses as Prometheus text.
maybe_obssmoke() {
  if [ "${SPARKNET_OBSSMOKE:-}" = "1" ]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
      python tools/obs.py smoke --out /tmp/_obssmoke.json > /dev/null
  fi
}

# ~10-second sync-vs-async outer-loop parity smoke (tools/roundbench.py)
# — opt-in via SPARKNET_ROUNDBENCH=1.  Fails the gate unless the
# pipelined loop (harvest_lag + AsyncCheckpointWriter) reproduces the
# synchronous loop's round losses, final params, and newest checkpoint
# bit for bit, with ckpt+guard+audit all enabled.  (A fast in-tree smoke
# of the same contract always runs inside tier-1: tests/test_resilience.py.)
maybe_roundbench() {
  if [ "${SPARKNET_ROUNDBENCH:-}" = "1" ]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python tools/roundbench.py --rounds 6 --out /tmp/_roundbench.json
  fi
}

# ~15-second comm-codec parity gate (tools/commbench.py) — opt-in via
# SPARKNET_COMMBENCH=1.  Fails the gate unless codec "none" (overlap on
# or off) is bit-identical to the pre-codec trainer, every real codec
# satisfies the error-feedback invariant while a planted
# residual-dropping codec is caught, int8/bf16 delta exchange converges
# inside the declared loss band, overlapped dispatch is bit-identical
# with less measured comm stall, and the int8 wire shrink is >= 3x.  (A
# fast in-tree smoke of the same contracts runs inside tier-1:
# tests/test_comms.py.)
maybe_commbench() {
  if [ "${SPARKNET_COMMBENCH:-}" = "1" ]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
      python tools/commbench.py --out /tmp/_commbench.json
  fi
}

# ~15-second hybrid-sharding parity gate (tools/shardbench.py) — opt-in
# via SPARKNET_SHARDSMOKE=1.  Runs a 2x2-able CPU mesh dryrun and fails
# the gate unless shard="auto" is bit-identical to the replicated
# trainer for all three strategies (codec none) AND composed with the
# int8 exchange, the per-shard checkpoint tiles roundtrip bit-exactly,
# a world-N checkpoint re-tiles onto world-M, the shard-aware audit
# catches a planted one-bit flip with the right culprit and rolls back,
# and the analytic τ-boundary bytes shrink (>= 2x on caffenet-class
# shapes at 8 shards).  (A fast in-tree smoke of the same contracts
# runs inside tier-1: tests/test_partition.py.)
maybe_shardsmoke() {
  if [ "${SPARKNET_SHARDSMOKE:-}" = "1" ]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
      python tools/shardbench.py --out /tmp/_shardbench.json
  fi
}

# ~10-second performance gate (tools/perfwatch.py perfgate) — opt-in
# via SPARKNET_PERFGATE=1.  Runs a ~2s-leg CPU bench smoke through the
# regression sentinel against the committed perf/LEDGER.jsonl (CPU
# fingerprints never gate against the TPU history — wide CPU bands via
# --min-band-pct for rigs that HAVE CPU history), then a sentinel
# self-test: a planted slow feed leg (BENCH_FEED_DELAY_S) must exit
# non-zero with stage attribution naming the decode stage.
maybe_perfgate() {
  if [ "${SPARKNET_PERFGATE:-}" = "1" ]; then
    timeout -k 10 480 env JAX_PLATFORMS=cpu \
      python tools/perfwatch.py perfgate --json /tmp/_perfgate.json
  fi
}

case "${1:-}" in
  --chaos) run_chaos ;;
  --lint)  SPARKNET_LINT=1 maybe_lint ;;
  --soak)  SPARKNET_SOAK=1 maybe_soak ;;
  --fleetsoak) SPARKNET_FLEETSOAK=1 maybe_fleetsoak ;;
  --podsoak) SPARKNET_PODSOAK=1 maybe_podsoak ;;
  --netsoak) SPARKNET_NETSOAK=1 maybe_netsoak ;;
  --rollsmoke) SPARKNET_ROLLSMOKE=1 maybe_rollsmoke ;;
  --feedbench) SPARKNET_FEEDBENCH=1 maybe_feedbench ;;
  --recordbench) SPARKNET_RECORDBENCH=1 maybe_recordbench ;;
  --roundbench) SPARKNET_ROUNDBENCH=1 maybe_roundbench ;;
  --commbench) SPARKNET_COMMBENCH=1 maybe_commbench ;;
  --shardsmoke) SPARKNET_SHARDSMOKE=1 maybe_shardsmoke ;;
  --servesmoke) SPARKNET_SERVESMOKE=1 maybe_servesmoke ;;
  --fleetservesmoke) SPARKNET_FLEETSERVESMOKE=1 maybe_fleetservesmoke ;;
  --obssmoke) SPARKNET_OBSSMOKE=1 maybe_obssmoke ;;
  --perfgate) SPARKNET_PERFGATE=1 maybe_perfgate ;;
  --all)   maybe_lint && run_tier1 && run_chaos && maybe_soak \
             && maybe_fleetsoak && maybe_podsoak && maybe_netsoak \
             && maybe_rollsmoke \
             && maybe_feedbench && maybe_recordbench && maybe_servesmoke \
             && maybe_fleetservesmoke && maybe_roundbench \
             && maybe_commbench && maybe_shardsmoke \
             && maybe_obssmoke && maybe_perfgate ;;
  "")      maybe_lint && run_tier1 && maybe_soak && maybe_fleetsoak \
             && maybe_podsoak && maybe_netsoak && maybe_rollsmoke \
             && maybe_feedbench && maybe_recordbench \
             && maybe_servesmoke && maybe_fleetservesmoke \
             && maybe_roundbench && maybe_commbench && maybe_shardsmoke \
             && maybe_obssmoke && maybe_perfgate ;;
  *) echo "usage: $0 [--chaos|--lint|--soak|--fleetsoak|--podsoak|--netsoak|--rollsmoke|--feedbench|--recordbench|--roundbench|--commbench|--shardsmoke|--servesmoke|--fleetservesmoke|--obssmoke|--perfgate|--all]" >&2
     exit 2 ;;
esac
