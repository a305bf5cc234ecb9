"""Typed registry for every ``SPARKNET_*`` configuration knob.

The env surface grew one knob at a time across twelve PRs; this module
makes it a declared contract instead of folklore.  Every knob has a
name, type, default, one-line doc, and an owner module; the registry is
the single source of truth for

- **runtime reads** — production code reads knobs through :func:`raw` /
  :func:`get_int` / :func:`get_float` / :func:`get_bool` (or a helper
  that delegates here).  Reading a name that was never registered
  raises :class:`UnknownKnob` — a typo'd knob fails loudly instead of
  silently meaning "default".
- **static enforcement** — ``sparknet_tpu/analysis`` (rule family KR)
  flags env reads that bypass the registry, reads of unregistered
  names, and registered-but-never-read knobs (dead registrations).
- **docs** — ``KNOBS.md`` is emitted from this table
  (``tools/lint.py knobs --emit``) and drift-gated in CI
  (``knobs --check``).
- **deprecation** — a knob marked ``deprecated`` lints as a warning
  (DP001) for one release; once ``removed`` it stays registered as a
  tombstone so any surviving mention fails lint (DP002) and a runtime
  read raises :class:`RemovedKnob` naming the replacement.

Design constraints: imports nothing from the rest of ``sparknet_tpu``
(safe to import from anywhere, including ``utils`` leaves), and never
caches values — every accessor reads ``os.environ`` live, so tests
that monkeypatch the env keep working and the existing latch-at-trace/
latch-at-construction semantics stay where they are implemented today
(fusion, Net), not here.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Iterable

__all__ = [
    "Knob", "KnobError", "UnknownKnob", "RemovedKnob", "InvalidKnobValue",
    "get", "all_knobs", "raw", "is_set", "get_str", "get_int", "get_float",
    "get_bool", "knobs_md", "DEPRECATED_SYMBOLS",
]


class KnobError(Exception):
    """Base for knob-registry errors."""


class UnknownKnob(KnobError, KeyError):
    """An env read of a SPARKNET_* name that was never registered."""


class RemovedKnob(KnobError, KeyError):
    """An env read of a knob whose deprecation window has closed."""


class InvalidKnobValue(KnobError, ValueError):
    """A set knob whose value does not parse as the registered type."""


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered env knob (see module docstring for the contract)."""

    name: str
    type: str                  # bool | int | float | str | enum | path | spec
    default: str               # unset-behavior, in env spelling ("" = unset)
    doc: str                   # one line, imperative, shows up in KNOBS.md
    owner: str                 # repo-relative path of the owning module
    choices: tuple[str, ...] = ()          # for type == "enum"
    validator: Callable[[str], object] | None = None
    deprecated: str = ""       # window OPEN:  "r<N>: use X instead"
    removed: str = ""          # window CLOSED: "r<N>: use X instead"


_REGISTRY: dict[str, Knob] = {}


def _register(*knobs: Knob) -> None:
    for k in knobs:
        if k.name in _REGISTRY:
            raise ValueError(f"duplicate knob registration: {k.name}")
        _REGISTRY[k.name] = k


def get(name: str) -> Knob:
    """The registry entry for ``name``; raises :class:`UnknownKnob` /
    :class:`RemovedKnob` — the same check every accessor runs first."""
    try:
        k = _REGISTRY[name]
    except KeyError:
        raise UnknownKnob(
            f"{name} is not a registered knob — add it to "
            f"sparknet_tpu/utils/knobs.py (and KNOBS.md via "
            f"`python tools/lint.py knobs --emit`)") from None
    if k.removed:
        raise RemovedKnob(f"{name} was removed ({k.removed})")
    return k


def all_knobs() -> list[Knob]:
    """Every registered knob (tombstones included), sorted by name."""
    return sorted(_REGISTRY.values(), key=lambda k: k.name)


def raw(name: str, default: str | None = None) -> str | None:
    """Registry-checked ``os.environ.get``.  The one primitive every
    other accessor (and the module-local ``_env_*`` helpers that
    delegate here) bottoms out in."""
    get(name)
    return os.environ.get(name, default)


def is_set(name: str) -> bool:
    """True when the knob is present AND non-empty."""
    return bool(raw(name))


def get_str(name: str, default: str = "") -> str:
    val = raw(name)
    return default if val is None or val == "" else val


def get_int(name: str, default: int) -> int:
    val = raw(name)
    if val is None or val == "":
        return default
    try:
        return int(val)
    except ValueError:
        raise InvalidKnobValue(
            f"{name} must be an integer, got {val!r}") from None


def get_float(name: str, default: float) -> float:
    val = raw(name)
    if val is None or val == "":
        return default
    try:
        return float(val)
    except ValueError:
        raise InvalidKnobValue(
            f"{name} must be a number, got {val!r}") from None


def get_bool(name: str, default: bool) -> bool:
    """Tri-state env bool: ``"0"`` -> False, ``"1"`` -> True, unset or
    anything else -> ``default``.  Sites with historical one-sided
    parses (``== "1"`` opt-ins, ``!= "1"`` opt-outs) compare
    :func:`raw` directly to keep their exact semantics."""
    val = raw(name)
    if val == "0":
        return False
    if val == "1":
        return True
    return default


# ---------------------------------------------------------------------------
# The registry.  Grouped by owner; keep each doc to one line — it becomes
# the KNOBS.md table.  default "" means "unset", with the unset behavior
# stated in the doc line.
# ---------------------------------------------------------------------------

_register(
    # --- graph: fusion + structure toggles ---
    Knob("SPARKNET_FUSE", "enum", "",
         "Set to off for per-layer execution; unset, the graph's legal "
         "conv..LRN chains run their LRN in the fused epilogue (latched "
         "at Net construction).",
         "sparknet_tpu/graph/fusion.py", choices=("off",)),
    Knob("SPARKNET_NO_HFUSE", "bool", "",
         "Set to 1 to disable horizontal inception-branch fusion "
         "(latched at Net construction).",
         "sparknet_tpu/graph/net.py"),
    Knob("SPARKNET_NO_S2D", "bool", "",
         "Set to 1 to disable the space-to-depth stem conv rewrite.",
         "sparknet_tpu/ops/vision.py"),
    Knob("SPARKNET_PALLAS_MAXPOOL", "bool", "",
         "Set to 1 to opt in to the Pallas maxpool backward kernel on TPU.",
         "sparknet_tpu/ops/vision.py"),
    # --- chaos / fault injection ---
    Knob("SPARKNET_FAULT", "spec", "",
         "Comma-separated fault specs (e.g. crash_after:3,slow_feed:200ms) "
         "injected by utils.faults; empty = no chaos.",
         "sparknet_tpu/utils/faults.py"),
    Knob("SPARKNET_FAULT_ATTEMPT", "int", "0",
         "Relaunch attempt index; faults can gate on it so a fault fires "
         "once, not on every restart.",
         "sparknet_tpu/utils/faults.py"),
    # --- cluster bring-up / launcher contract ---
    Knob("SPARKNET_COORDINATOR", "str", "",
         "Coordinator address for jax.distributed; set with NUM_PROCS and "
         "PROC_ID together (launcher env contract).",
         "sparknet_tpu/parallel/cluster.py"),
    Knob("SPARKNET_NUM_PROCS", "int", "",
         "World size under the launcher env contract.",
         "sparknet_tpu/parallel/cluster.py"),
    Knob("SPARKNET_PROC_ID", "int", "0",
         "This process's rank under the launcher env contract; also the "
         "telemetry/heartbeat shard rank.",
         "sparknet_tpu/parallel/cluster.py"),
    Knob("SPARKNET_CONNECT_RETRIES", "int", "3",
         "Coordinator connect attempts (TIME_WAIT races on relaunch).",
         "sparknet_tpu/parallel/cluster.py"),
    Knob("SPARKNET_CONNECT_BACKOFF", "float", "0.5",
         "Base seconds for exponential connect backoff.",
         "sparknet_tpu/parallel/cluster.py"),
    Knob("SPARKNET_CONNECT_JITTER", "float", "0.25",
         "Jitter fraction on connect backoff (de-lockstep relaunched "
         "ranks).",
         "sparknet_tpu/parallel/cluster.py"),
    # --- resilience / supervision ---
    Knob("SPARKNET_RESTART_COUNT", "int", "0",
         "Exported by the supervisor to relaunched children: restarts so "
         "far.",
         "sparknet_tpu/parallel/resilience.py"),
    Knob("SPARKNET_INCARNATION", "int", "0",
         "Elastic re-form incarnation, exported to children and stamped "
         "on telemetry.",
         "sparknet_tpu/parallel/resilience.py"),
    Knob("SPARKNET_HEARTBEAT_DIR", "path", "",
         "Directory for liveness beat files; empty disables the health "
         "plane.",
         "sparknet_tpu/parallel/health.py"),
    Knob("SPARKNET_LEASE_S", "float", "2",
         "Heartbeat lease duration: a host whose relayed beats are older "
         "than LEASE_S * LEASE_MISSES is SUSPECT (suspended, never "
         "killed) until it heals or a down-probe confirms death.",
         "sparknet_tpu/parallel/health.py"),
    Knob("SPARKNET_LEASE_MISSES", "int", "3",
         "Consecutive missed leases before a host turns SUSPECT.",
         "sparknet_tpu/parallel/health.py"),
    # --- host transport (the remote half of the pod fleet) ---
    Knob("SPARKNET_SSH_CMD", "str", "",
         "ssh binary for the SshTransport wire path (default 'ssh'); "
         "point it at a local fake-ssh script to drive the real remote "
         "argv/env/stdio plumbing in CI without an sshd.  Setting it "
         "also makes named-but-loopback addresses (127.0.0.1, "
         "localhost) take the ssh path.",
         "sparknet_tpu/parallel/transport.py"),
    Knob("SPARKNET_SHIP_CHUNK_MB", "float", "4",
         "Chunk size (MB) for crc-verified artifact/checkpoint shipping "
         "ranged reads.",
         "sparknet_tpu/parallel/transport.py"),
    Knob("SPARKNET_SHIP_RETRIES", "int", "4",
         "Attempts for one artifact ship (resumable: each retry keeps "
         "the destination's valid prefix).",
         "sparknet_tpu/parallel/transport.py"),
    Knob("SPARKNET_FENCE_BASE", "int", "0",
         "Fleet-stamped incarnation fence base (episode * 1e5); the "
         "runner adds its attempt number to mint SPARKNET_FENCE_TOKEN. "
         "0/unset = fencing off.",
         "sparknet_tpu/parallel/resilience.py"),
    Knob("SPARKNET_FENCE_TOKEN", "int", "0",
         "This writer's incarnation fence token: checkpoint dirs refuse "
         "publishes from tokens below the dir's claimed fence (the "
         "zombie-writer guard).  Minted by the launch stack, not set by "
         "hand.",
         "sparknet_tpu/utils/checkpoint.py"),
    # --- checkpointing / IO ---
    Knob("SPARKNET_ASYNC_CKPT", "bool", "1",
         "Set to 0 to force synchronous checkpoint writes (default "
         "async).",
         "sparknet_tpu/utils/checkpoint.py"),
    Knob("SPARKNET_IO_RETRIES", "int", "3",
         "Attempts for retryable storage IO (io_retry policy).",
         "sparknet_tpu/utils/retry.py"),
    Knob("SPARKNET_IO_BACKOFF", "float", "0.05",
         "Base seconds for storage IO retry backoff.",
         "sparknet_tpu/utils/retry.py"),
    # --- telemetry plane ---
    Knob("SPARKNET_TELEMETRY", "bool", "1",
         "Set to 0 to no-op the whole telemetry plane (metrics, spans, "
         "flight recorder).",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_TELEMETRY_RANK", "int", "",
         "Telemetry shard rank for processes outside the launcher "
         "contract; wins over PROC_ID.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_TRACE_DIR", "path", "",
         "Write Chrome-trace JSONL shards and flight dumps here; empty "
         "disables tracing.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_METRICS_SNAP", "path", "",
         "Write metrics_rank*.json/.prom snapshots here; empty disables.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_METRICS_SNAP_S", "float", "2",
         "Minimum seconds between metrics snapshots.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_FLIGHT_EVENTS", "int", "256",
         "Flight-recorder ring size.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_RUN_ID", "str", "",
         "Correlation run id stamped on all telemetry; derived per "
         "process when unset.",
         "sparknet_tpu/utils/telemetry.py"),
    Knob("SPARKNET_FLEET_JOB", "str", "",
         "Fleet job tag exported to tenant processes; joins their "
         "telemetry to the scheduler's story.",
         "sparknet_tpu/parallel/fleet.py"),
    Knob("SPARKNET_FLEET_HOSTS", "spec", "",
         "Host inventory for multi-host placement: "
         "'name=devices[@addr],...' inline or a path to a JSON list of "
         "{name, devices, addr}; unset = single-host device budget.",
         "sparknet_tpu/parallel/fleet.py"),
    Knob("SPARKNET_FLEET_HOST", "str", "",
         "Host label the launcher stamps on each worker (the gang's "
         "primary host for fleet tenants); joins per-host telemetry "
         "and heartbeats to the placement story.",
         "sparknet_tpu/tools/launch.py"),
    Knob("SPARKNET_FLEET_HOSTVEC", "str", "",
         "Comma-separated per-slot host labels of the gang's placement, "
         "exported to fleet tenant processes.",
         "sparknet_tpu/parallel/fleet.py"),
    # --- data plane ---
    Knob("SPARKNET_QUARANTINE_FRACTION", "float", "0",
         "Max fraction of an epoch the decode quarantine may swallow.",
         "sparknet_tpu/data/integrity.py"),
    Knob("SPARKNET_QUARANTINE_RECORDS", "int", "0",
         "Absolute quarantined-record budget added to the fraction.",
         "sparknet_tpu/data/integrity.py"),
    Knob("SPARKNET_FEED_WORKERS", "int", "",
         "Decode-pool width; 0 = serial reference path; unset = cpu "
         "count capped at 8.",
         "sparknet_tpu/data/pipeline.py"),
    Knob("SPARKNET_FEED_DEPTH", "int", "4",
         "Prefetch queue depth (batches).",
         "sparknet_tpu/data/pipeline.py"),
    Knob("SPARKNET_FEED_PUTTERS", "int", "2",
         "Device-put staging threads in DeviceFeeder.",
         "sparknet_tpu/data/prefetch.py"),
    Knob("SPARKNET_FEED_STALL_S", "float", "",
         "Feeder stall detector timeout in seconds; unset disables.",
         "sparknet_tpu/data/prefetch.py"),
    Knob("SPARKNET_RECORD_READERS", "int", "",
         "Reader threads of a record-shard feed, each reading runs of "
         "records straight into their rows of the batch; 0 = serial "
         "reference path; unset = SPARKNET_FEED_WORKERS.",
         "sparknet_tpu/data/records.py"),
    Knob("SPARKNET_RECORD_SHARD_MB", "int", "64",
         "Shard roll size in MiB for the record-shard converter.",
         "sparknet_tpu/data/records.py"),
    Knob("SPARKNET_CACHE_SHARDS", "int", "4",
         "RAM tier of the ShardCache: resident shard count before LRU "
         "eviction (evictees spill to disk when spill is enabled).",
         "sparknet_tpu/data/pipeline.py"),
    Knob("SPARKNET_CACHE_SPILL_DIR", "path", "",
         "Disk spill tier directory for ShardCache evictees; unset "
         "disables the spill tier (evict = drop).",
         "sparknet_tpu/data/pipeline.py"),
    Knob("SPARKNET_CACHE_SPILL_SHARDS", "int", "16",
         "Max shards held in the ShardCache disk spill tier (oldest "
         "spill files deleted beyond it).",
         "sparknet_tpu/data/pipeline.py"),
    Knob("SPARKNET_AUG_DEVICE", "bool", "1",
         "Run crop/mirror/mean/scale augmentation inside the compiled "
         "train step (host ships raw uint8); 0 = host-side numpy path "
         "(bit-identical at the same seed).",
         "sparknet_tpu/solvers/solver.py"),
    # --- serving engine ---
    Knob("SPARKNET_SERVE_SHAPES", "spec", "1,4,16,64",
         "Padded batch shapes the engine pre-compiles "
         "(comma-separated ints).",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_MAX_DELAY_MS", "float", "5.0",
         "Micro-batching window: max milliseconds a request waits for "
         "batchmates.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_QUEUE", "int", "256",
         "Admission queue depth; beyond it requests get typed "
         "rejections.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_INFLIGHT", "int", "2",
         "Dispatched-but-not-demuxed batch window (async dispatch "
         "pipelining).",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_HBM_MB", "float", "2048",
         "HBM budget for resident models (LRU eviction above it).",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_DTYPE", "str", "bf16",
         "Serving activation dtype.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_QUOTAS", "spec", "",
         "Per-tenant offered-QPS caps, tenant=qps comma-separated; "
         "* = every unlisted tenant.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SERVE_FORCE_ADMIT", "bool", "",
         "Set to 1 to bypass admission control (load-test harness only).",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SLO_P99_MS", "float", "",
         "Declared p99 latency SLO in ms; unset/0 = latency SLO "
         "undeclared.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SLO_REJECT_BUDGET", "float", "0.02",
         "Rejection-rate error budget for SLO burn accounting.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SLO_WINDOW_S", "float", "60",
         "Slow burn-rate window seconds.",
         "sparknet_tpu/parallel/serving.py"),
    Knob("SPARKNET_SLO_FAST_S", "float", "5",
         "Fast burn-rate window seconds.",
         "sparknet_tpu/parallel/serving.py"),
    # --- router / autoscaler ---
    Knob("SPARKNET_ROUTER_SPILL_DEPTH", "int", "16",
         "Queue depth at the home replica beyond which the router "
         "spills to the next ring member.",
         "sparknet_tpu/parallel/router.py"),
    Knob("SPARKNET_ROUTER_FAILOVERS", "int", "3",
         "Max alternate replicas tried before a typed routing failure.",
         "sparknet_tpu/parallel/router.py"),
    Knob("SPARKNET_ROUTER_DRAIN_S", "float", "30",
         "Seconds a draining replica keeps answering in-flight work.",
         "sparknet_tpu/parallel/router.py"),
    Knob("SPARKNET_AUTOSCALE_MIN", "int", "1",
         "Replica floor.",
         "sparknet_tpu/parallel/autoscale.py"),
    Knob("SPARKNET_AUTOSCALE_MAX", "int", "4",
         "Replica ceiling (device budget).",
         "sparknet_tpu/parallel/autoscale.py"),
    Knob("SPARKNET_AUTOSCALE_UP_QUEUE", "float", "8.0",
         "Mean queue depth per replica that triggers scale-up.",
         "sparknet_tpu/parallel/autoscale.py"),
    Knob("SPARKNET_AUTOSCALE_DOWN_IDLE_S", "float", "10.0",
         "Idle seconds before a replica is eligible for scale-down.",
         "sparknet_tpu/parallel/autoscale.py"),
    Knob("SPARKNET_AUTOSCALE_COOLDOWN_S", "float", "5.0",
         "Minimum seconds between scaling decisions.",
         "sparknet_tpu/parallel/autoscale.py"),
    Knob("SPARKNET_AUTOSCALE_EVAL_S", "float", "1.0",
         "Policy evaluation period seconds.",
         "sparknet_tpu/parallel/autoscale.py"),
    # --- deployment plane (model registry + canary rollout) ---
    Knob("SPARKNET_REGISTRY_DIR", "path", "",
         "Root of the immutable model registry (version bundles + "
         "per-model channel files). Unset = deployment plane off, "
         "plain by-name serving.",
         "sparknet_tpu/parallel/registry.py"),
    Knob("SPARKNET_ROLLOUT_CANARY_FRACTION", "float", "0.1",
         "Traffic share a newly started canary takes (0, 1].",
         "sparknet_tpu/parallel/rollout.py"),
    Knob("SPARKNET_ROLLOUT_JUDGE_S", "float", "8.0",
         "Sustained-health seconds before the judge promotes a canary.",
         "sparknet_tpu/parallel/rollout.py"),
    Knob("SPARKNET_ROLLOUT_POLL_S", "float", "0.5",
         "Judge poll interval seconds.",
         "sparknet_tpu/parallel/rollout.py"),
    Knob("SPARKNET_ROLLOUT_MIN_REQUESTS", "int", "20",
         "Observed-request floor before a canary is promotable (blips "
         "over tiny samples never decide a rollout).",
         "sparknet_tpu/parallel/rollout.py"),
    Knob("SPARKNET_ROLLOUT_BREACH_POLLS", "int", "2",
         "Consecutive breach verdicts that trigger auto-rollback "
         "(multi-window burn discipline: one blip never pages).",
         "sparknet_tpu/parallel/rollout.py"),
    # --- communication-efficient rounds (trainer τ / codec / overlap) ---
    Knob("SPARKNET_TAU", "int", "",
         "Steps per round for driver-built trainers (comm_config_from_env; "
         "the paper's swept τ knob — unset keeps the config's tau).",
         "sparknet_tpu/parallel/trainer.py"),
    Knob("SPARKNET_COMM_CODEC", "str", "",
         "Weight-delta exchange codec for driver-built trainers: none / "
         "bf16 / int8 / int8_channel (or any comms.register_codec name).",
         "sparknet_tpu/parallel/trainer.py"),
    Knob("SPARKNET_COMM_OVERLAP", "bool", "",
         "Set to 1 to dispatch the encode/exchange/decode tail without "
         "host blocking (overlapped averaging; bit-identical results).",
         "sparknet_tpu/parallel/trainer.py"),
    # --- hybrid model+data sharding (partition rule tables) ---
    Knob("SPARKNET_SHARD", "str", "",
         "Partition rule table for driver-built trainers: off (pure data "
         "parallelism, the default), auto (zoo defaults: FC/inner-product "
         "weights shard across chips, convs replicate), or the path of a "
         "versioned JSON rule table (parallel/partition.py).",
         "sparknet_tpu/parallel/trainer.py"),
    Knob("SPARKNET_SHARD_CKPT", "bool", "",
         "Set to 1 to write round checkpoints in the per-shard layout "
         "(one npz tile per shard + the common npz, every file sha256-"
         "pinned in the manifest); only meaningful with a live shard "
         "plan.",
         "sparknet_tpu/parallel/trainer.py"),
    # --- CI gates (read by the tier-1 runner, not by library code) ---
    Knob("SPARKNET_LINT", "bool", "1",
         "Set to 0 to skip the sparklint gate in tools/run_tier1.sh "
         "(default on).",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_SOAK", "bool", "",
         "Set to 1 to run the 2-run chaos soak smoke in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_SOAK_SEED", "int", "",
         "Seed override for the chaos soak smoke.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_FLEETSOAK", "bool", "",
         "Set to 1 to run the 2-job fleet soak smoke in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_PODSOAK", "bool", "",
         "Set to 1 to run the simulated 3-host pod burn-in slice in "
         "run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_NETSOAK", "bool", "",
         "Set to 1 to run the network chaos burn-in (partition-suspend-"
         "heal + fenced-zombie episodes over the fake-ssh transport) in "
         "run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_SOAK_QPS", "float", "4.0",
         "Pod burn-in base offered QPS (the diurnal curve's mean).",
         "tools/soak.py"),
    Knob("SPARKNET_SOAK_FLASH_X", "float", "2.5",
         "Pod burn-in flash-crowd multiplier over the base QPS.",
         "tools/soak.py"),
    Knob("SPARKNET_SOAK_LEG_S", "float", "4.0",
         "Pod burn-in seconds per traffic leg.",
         "tools/soak.py"),
    Knob("SPARKNET_FEEDBENCH", "bool", "",
         "Set to 1 to run the input-pipeline bench gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_RECORDBENCH", "bool", "",
         "Set to 1 to run the record-shard parity gate (feedbench "
         "--records-leg, clean + corrupt) in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_ROUNDBENCH", "bool", "",
         "Set to 1 to run the round-overhead bench gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_SERVESMOKE", "bool", "",
         "Set to 1 to run the serving smoke gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_FLEETSERVESMOKE", "bool", "",
         "Set to 1 to run the fleet-serving smoke gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_OBSSMOKE", "bool", "",
         "Set to 1 to run the observability smoke gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_PERFGATE", "bool", "",
         "Set to 1 to run the perf regression gate in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_ROLLSMOKE", "bool", "",
         "Set to 1 to run the rollout chaos leg (canary promote + "
         "planted-bad-canary rollback + controller-kill resume) in "
         "run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_COMMBENCH", "bool", "",
         "Set to 1 to run the comm-codec parity gate (codec-none "
         "bit-identity, EF invariant, overlap stall) in run_tier1.sh.",
         "tools/run_tier1.sh"),
    Knob("SPARKNET_SHARDSMOKE", "bool", "",
         "Set to 1 to run the hybrid-sharding parity gate (sharded-vs-"
         "replicated bit-parity, per-shard checkpoint roundtrip, elastic "
         "re-tile, boundary-bytes shrink) in run_tier1.sh.",
         "tools/run_tier1.sh"),
    # --- tombstones: window closed, any surviving mention fails lint ---
    Knob("SPARKNET_LRN_CUMSUM", "bool", "",
         "REMOVED: ops.vision.lrn_use_cumsum picks the LRN window-sum form "
         "from backend and width.",
         "sparknet_tpu/ops/vision.py",
         removed="r14: pass use_cumsum= to ops.vision.lrn_window_sum"),
    Knob("SPARKNET_FUSE_PALLAS", "bool", "",
         "REMOVED: ops.vision.lrn_chain_epilogue picks the epilogue from "
         "backend, rank and dtype.",
         "sparknet_tpu/ops/vision.py",
         removed="r14: SPARKNET_FUSE=off is the per-layer side of the A/B"),
)

# Symbols (not knobs) past their deprecation window: any surviving
# reference in scanned code fails lint (DP002).  Seeded with the PR-12
# shims this release deletes — the rule that would have flagged them.
DEPRECATED_SYMBOLS: dict[str, str] = {
    "deprecated_lrn_cumsum_pin":
        "r14: removed with SPARKNET_LRN_CUMSUM; pass use_cumsum=",
    "_shim_pin":
        "r14: removed with the PR-12 env shims",
}


# ---------------------------------------------------------------------------
# KNOBS.md emission
# ---------------------------------------------------------------------------

_MD_HEADER = """\
# SPARKNET_* knob reference

Auto-generated from `sparknet_tpu/utils/knobs.py` by
`python tools/lint.py knobs --emit` — do not edit by hand;
`tools/lint.py knobs --check` gates drift in CI.

Conventions: bool knobs take `0`/`1` (the doc line states which side is
the default); `default` is the unset behavior; removed knobs are listed
last as tombstones (mentioning them fails lint).
"""


def _md_table(rows: Iterable[Knob]) -> list[str]:
    out = ["| Knob | Type | Default | Owner | Doc |",
           "| --- | --- | --- | --- | --- |"]
    for k in rows:
        default = k.default if k.default != "" else "*(unset)*"
        out.append(f"| `{k.name}` | {k.type} | {default} | `{k.owner}` | "
                   f"{k.doc} |")
    return out


def knobs_md() -> str:
    """The full KNOBS.md text."""
    live = [k for k in all_knobs() if not k.removed]
    dead = [k for k in all_knobs() if k.removed]
    lines = [_MD_HEADER]
    by_owner: dict[str, list[Knob]] = {}
    for k in live:
        by_owner.setdefault(k.owner, []).append(k)
    for owner in sorted(by_owner):
        lines.append(f"\n## `{owner}`\n")
        lines.extend(_md_table(by_owner[owner]))
    if dead:
        lines.append("\n## Removed (tombstones)\n")
        lines.append("| Knob | Removed | Replacement |")
        lines.append("| --- | --- | --- |")
        for k in dead:
            since, _, repl = k.removed.partition(": ")
            lines.append(f"| `{k.name}` | {since} | {repl or k.doc} |")
    lines.append("")
    return "\n".join(lines)
