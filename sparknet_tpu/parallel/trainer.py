"""Distributed training strategies as single compiled programs.

The two data-parallel forms of the reference (SURVEY.md §2.4), re-built as
XLA collectives inside one ``shard_map``-compiled round:

1. **"local_sgd"** — SparkNet's contribution: every worker runs τ local SGD
   steps on its own data partition, then weights are averaged.  The
   reference implements this as a Spark driver loop — broadcast weights →
   per-worker ``net.train(τ)`` → collect and average ≈249 MB of weights
   through one driver JVM (reference: src/main/scala/apps/ImageNetApp.scala:
   100-182, WeightCollection.add at src/main/scala/libs/Net.scala:27-46) —
   costing two cross-machine barriers and a driver bottleneck per round.
   Here the whole round is ONE jitted op: ``lax.scan`` over τ compute steps,
   then ``lax.pmean`` over the mesh — the averaging rides ICI at full
   bisection bandwidth and no weight ever visits a host.  Per-worker solver
   state (momentum history) stays device-resident between rounds, exactly
   like the reference's per-worker embedded solvers.

2. **"sync"** — Caffe's P2PSync semantics: per-step gradient reduction then
   a single update (reference: caffe/src/caffe/parallel.cpp:271-360
   tree-reduce over CUDA P2P; ``on_gradients_ready`` hook at solver.cpp:260).
   Here the tree is ``lax.pmean`` on the gradients inside the step.

3. **"hierarchical"** — the two tiers COMPOSED on a (host, chip) mesh,
   the way a real TPU pod would deploy SparkNet's semantics: per-step
   gradient pmean over the ``chip`` axis (ICI within a host — P2PSync's
   role) and τ-step weight averaging over the ``host`` axis (DCN across
   hosts — the Spark driver round's role).  The reference never composed
   its two tiers (SparkNet pinned one GPU per worker, Net.scala:95);
   this is the completion of that design.  Optimizer state is per-HOST
   (all chips of a host apply identical chip-mean updates, so the state
   is replicated within the host and distinct across hosts between
   averaging boundaries).  Collapses to flat "sync" at n_hosts=1 and to
   flat "local_sgd" at chips_per_host=1 (tested equivalences).

τ=1 local_sgd and sync differ exactly as in the reference: sync averages
gradients before the momentum update (one shared optimizer state), local_sgd
averages weights after it (per-worker optimizer states).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import hashlib
import json
import os
import sys
import time
from typing import Any, Callable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.net import Net, WeightCollection
from ..ops import augment
from ..proto.caffe_pb import NetState, Phase, SolverParameter
from ..utils import telemetry
from ..solvers.step import INPUT_SCOPE, apply_update, make_step_fns
from ..solvers.update_rules import make_update_rule
from .mesh import (
    CHIP_AXIS, DATA_AXIS, HOST_AXIS, make_mesh, make_pod_mesh,
    put_global_tree, replicated, stage_local,
)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    strategy: str = "local_sgd"   # "local_sgd" | "sync" | "hierarchical"
    tau: int = 1                  # steps per round (local steps for local_sgd)
    donate: bool = True
    # Optional pure-JAX augmentation applied to each micro-batch INSIDE the
    # compiled round, (micro_batch_dict, rng) -> micro_batch_dict — the
    # TPU-native fix for host-bound preprocessing (the reference crops on
    # the host because GPU Caffe did; on TPU the crop is ~free next to the
    # matmuls, and the host then only ships raw images).  Build one with
    # ``device_crop_mirror_mean``.
    device_preprocess: Any | None = None
    # jax.checkpoint the forward: backward recomputes activations instead
    # of storing them (HBM for FLOPs; big-batch / VGG-class configs)
    remat: bool = False
    # Round-granular fault tolerance: with ``checkpoint_dir`` set, process
    # 0 writes params + per-worker solver state + round counter + RNG +
    # data-cursor every ``checkpoint_every`` completed rounds, each under
    # a checksummed manifest, and a fresh trainer auto-resumes from the
    # newest manifest whose checksum validates (corrupt/partial snapshots
    # are skipped).  ``checkpoint_keep`` bounds disk: older round
    # checkpoints beyond the newest N are pruned.  This is the recovery
    # half of the reference's Spark story — a relaunched job (see
    # ``parallel.resilience.ResilientRunner``) loses at most
    # ``checkpoint_every`` rounds.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    checkpoint_keep: int = 3
    # Elastic degraded mode: allow resuming a checkpoint written by a
    # DIFFERENT worker count — the SparkNet average over k-1 workers is
    # still a valid consensus, so a job that lost a host permanently can
    # re-form on the survivors (params are replicated and restore as-is;
    # stacked per-worker/per-host optimizer state is re-tiered: surviving
    # worker i inherits saved row i mod saved_n).  Strategy mismatches
    # still raise — that is a config error, not membership change.
    elastic: bool = False
    # Numerical-integrity guard: after each averaging step validate the
    # round (finite loss, finite params, optional loss-spike threshold);
    # a poisoned round is DROPPED — the trainer rolls back to the newest
    # valid round checkpoint instead of letting a NaN/Inf be averaged
    # into the master weights and persisted forever.  Requires
    # ``checkpoint_dir`` (a baseline round-0 checkpoint is written at
    # init so rollback is always possible).
    guard_numerics: bool = False
    # > 0: additionally trip when loss exceeds ``loss_spike_factor`` ×
    # the trailing-mean loss (catches divergence before it reaches Inf)
    loss_spike_factor: float = 0.0
    # multiply the effective LR by this on every guard trip (< 1.0 backs
    # off a diverging step size; 1.0 = rollback only).  The scale is a
    # traced input of the compiled round — changing it never recompiles.
    guard_lr_backoff: float = 1.0
    guard_max_trips: int = 3
    # Cross-replica parameter audit: every ``audit_every`` rounds (0 =
    # off), BEFORE the round runs, each replica computes a cheap
    # fingerprint of its resident parameter copy (uint32 bitcast
    # tree-sum — one fused pass, one all_gather) and the mesh compares.
    # Replicated params are an *invariant the hardware can silently
    # break* (a flipped HBM bit, a diverged host): a mismatch means some
    # replica's copy rotted since the last audit, and the next averaging
    # collective would fold it into the master weights forever.  On
    # mismatch the trainer takes the guard's rollback path to the newest
    # checkpoint at or before the last PASSED audit (params/state/iter/
    # RNG restored — the replay is exact and, with one-shot faults,
    # clean), so a bit flip costs at most one audit interval.  Requires
    # ``checkpoint_dir``; shares ``guard_max_trips``.  Note: local_sgd
    # re-averages params every round boundary, which folds (hides) a
    # flip at the next boundary — audit_every=1 is the right cadence
    # there; sync/hierarchical keep per-replica divergence resident, so
    # a coarser cadence still detects.
    audit_every: int = 0
    # Zero-stall outer loop: with ``harvest_lag`` K > 0 the loss, the
    # guard's finite-check verdict, and the audit fingerprints stay
    # ON-DEVICE as futures and are harvested up to K rounds late, so the
    # host never synchronizes with the steady-state round — up to K
    # compiled rounds stay in flight (round pipelining) while host
    # bookkeeping overlaps device compute.  Safety semantics are
    # unchanged, only deferred: a guard/audit trip detected while
    # harvesting round r rolls back to a checkpoint at round <= r (the
    # same exact-RNG replay path), and every in-flight round after r is
    # discarded and replayed.  Checkpoint retention must therefore cover
    # the lag (validated at init: K more rounds may complete before a
    # poison is detected, so the pre-poison checkpoint must outlive
    # them).  0 = today's fully synchronous behavior, bit-identical.
    harvest_lag: int = 0
    # Async checkpointing: round checkpoints snapshot with a
    # NON-BLOCKING device→host copy and serialize/checksum/rename on a
    # background writer thread (utils.checkpoint.AsyncCheckpointWriter),
    # preserving the tmp+rename crash-safety, manifest checksums,
    # pruning, and orphan-tmp sweep byte-for-byte.  Rollback, resume,
    # preemption and fault-injection windows flush the writer first, so
    # recovery semantics are exact.  ``SPARKNET_ASYNC_CKPT=0`` overrides
    # to the synchronous path regardless of this field.
    async_checkpoint: bool = True
    # Compressed τ-boundary weight exchange (parallel/comms.py; ROADMAP
    # item 5b).  "none" keeps the pre-existing fused single-program
    # round — bit-identical to the trainer before codecs existed, BY
    # CONSTRUCTION (no delta arithmetic runs at all).  Any other
    # registered codec ("bf16" / "int8" / "int8_channel" / test-planted
    # ones) splits the round: the compiled local-steps program returns
    # per-tier weights WITHOUT the boundary pmean, an encode program
    # quantizes each tier's delta against the last broadcast state (plus
    # the error-feedback residual, which persists in trainer state and
    # rides checkpoints), the gathered payload is decoded and averaged
    # identically on every replica — so params stay replicated and the
    # cross-replica audit holds under every codec.  Only the strategies
    # that exchange weights at the τ boundary can compress them:
    # local_sgd and hierarchical.  "sync" exchanges per-step GRADIENTS
    # inside the scan and raises at init with any codec but "none".
    comm_codec: str = "none"
    # Overlap the encode→exchange→decode tail with subsequent host work
    # (the harvest-lag discipline of PR 5 applied to the exchange): the
    # three comm programs are DISPATCHED without host blocking, so the
    # next round's feed staging / bookkeeping — and with harvest_lag > 0
    # the next round itself — proceed while the bytes move.  Program
    # order and results are bit-identical to comm_overlap=False; only
    # the host-blocking policy (and therefore the measured
    # stall_s["comm_*"]) changes.  Inert at comm_codec="none", where the
    # exchange already rides inside the one compiled round with zero
    # host stall to hide.
    comm_overlap: bool = False
    # Hybrid model+data sharding (parallel/partition.py; ROADMAP item 2).
    # "off" keeps pure data parallelism — the pre-plan code path byte for
    # byte.  "auto" resolves the zoo default rule table (FC/inner-product
    # weights shard across the mesh's fast axis — chips on a pod mesh,
    # the data axis on a flat mesh — convs and biases stay replicated);
    # anything else is the path of a versioned JSON rule table.  Params
    # then LIVE sharded between rounds (HBM / shard factor), the round
    # bodies gather shards on entry (tiled all_gather — exact) and
    # reduce-scatter at the τ boundary (each position receives only its
    # own shard's bytes), so losses and logical params stay bit-identical
    # to the replicated baseline at codec "none" — by construction:
    # psum_scatter(tiled)/n is bitwise pmean-then-slice, and slicing is
    # not arithmetic.
    shard: str = "off"
    # Per-shard round checkpoints: with a live shard plan, write the
    # sharded leaves as one npz tile per shard (common leaves + manifest
    # unchanged), all fanned through the same (async) writer and each
    # sha256-pinned in the manifest.  Restore joins tiles back to full
    # logical leaves, so a checkpoint written at world N re-tiles onto
    # world M bit-exactly (the elastic contract survives sharding).
    shard_checkpoint: bool = False


# The round's own phases in a profiler trace, beside the layers'
# ``L[<layer>]`` (graph/net.py) and the step's (solvers/step.py): the
# boundary average of the weights and the loss, and the ``sync`` strategy's
# exchange of gradients at every step.
AVERAGE_SCOPE = "L[round.average]"
SYNC_SCOPE = "L[round.sync]"


class TrainingDivergedError(RuntimeError):
    """The numerical-integrity guard tripped and could not recover:
    no checkpoint to roll back to, or ``guard_max_trips`` exceeded
    (the fault is deterministic — rollback alone cannot outrun it)."""


def device_crop_mirror_mean(crop: int, mirror: bool = True,
                            mean=None, field: str = "data"):
    """Build a ``TrainerConfig.device_preprocess``: random crop to
    (crop, crop) + horizontal mirror + mean subtraction, fused into the
    compiled round.  Caffe-window semantics: a full-size mean is
    subtracted before cropping (== subtracting at each sample's window,
    data_transformer.cpp).  The host then ships raw full-size images and
    does no per-pixel work at all — the TPU-native resolution of the
    reference's measured feed bottleneck (java_data_layer.cpp:36-44)."""
    @jax.named_scope(augment.SCOPE)
    def pre(micro, rng):
        data = micro[field]
        lead = data.shape[:-3]
        c, h, w = data.shape[-3:]
        flat = data.reshape((-1, c, h, w))
        n = flat.shape[0]
        ky, kx, kf = jax.random.split(rng, 3)
        ys = jax.random.randint(ky, (n,), 0, h - crop + 1)
        xs = jax.random.randint(kx, (n,), 0, w - crop + 1)
        flips = jax.random.bernoulli(kf, 0.5, (n,)) if mirror else None
        out = augment.crop_mirror(flat, ys, xs, flips, crop)
        if mean is not None:
            # full-size: each sample's window; crop-sized (the pycaffe
            # mean-file shape): mirrored with its sample, since
            # data_transformer.cpp mirrors the subtracted result
            out = out - augment.mean_window(mean, (c, h, w), ys, xs, flips,
                                            crop)
        return {**micro, field: out.reshape(lead + (c, crop, crop))}

    return pre


def comm_config_from_env(base: TrainerConfig | None = None) -> TrainerConfig:
    """``base`` (or a default TrainerConfig) with the communication
    round shape taken from the registered knobs where they are set:
    ``SPARKNET_TAU`` (steps per round — the paper's swept frontier knob),
    ``SPARKNET_COMM_CODEC``, ``SPARKNET_COMM_OVERLAP``, ``SPARKNET_SHARD``
    (partition rule table: off | auto | path) and
    ``SPARKNET_SHARD_CKPT`` (per-shard round checkpoints).  Unset knobs
    leave ``base``'s fields untouched, so an explicitly-constructed
    config still wins; drivers (tools/train, commbench, sweep harnesses)
    call this so one env var re-shapes a whole launched grid without
    code changes."""
    from ..utils import knobs
    cfg = base or TrainerConfig()
    tau = knobs.get_int("SPARKNET_TAU", 0)
    if tau > 0:
        cfg = dataclasses.replace(cfg, tau=tau)
    codec = knobs.get_str("SPARKNET_COMM_CODEC", "")
    if codec:
        cfg = dataclasses.replace(cfg, comm_codec=codec)
    if knobs.is_set("SPARKNET_COMM_OVERLAP"):
        cfg = dataclasses.replace(
            cfg, comm_overlap=knobs.get_bool("SPARKNET_COMM_OVERLAP", False))
    shard = knobs.get_str("SPARKNET_SHARD", "")
    if shard:
        cfg = dataclasses.replace(cfg, shard=shard)
    if knobs.is_set("SPARKNET_SHARD_CKPT"):
        cfg = dataclasses.replace(
            cfg, shard_checkpoint=knobs.get_bool("SPARKNET_SHARD_CKPT",
                                                 False))
    return cfg


class DistributedTrainer:
    """Owns params (replicated, or per-leaf sharded under a partition
    rule table — ``TrainerConfig.shard``) + (per-device or shared) solver
    state and a compiled per-round train step over a device mesh."""

    def __init__(self, sp: SolverParameter, mesh=None,
                 config: TrainerConfig | None = None, *, seed: int = 0):
        self.sp = sp
        self.config = config or TrainerConfig()
        if self.config.strategy not in ("local_sgd", "sync", "hierarchical"):
            raise ValueError(f"unknown strategy {self.config.strategy!r}")
        from . import comms
        # "none" stays structurally OFF this machinery (comms.py module
        # doc): _codec None routes the round through the pre-codec fused
        # program verbatim
        self._codec = (None if self.config.comm_codec == "none"
                       else comms.get_codec(self.config.comm_codec))
        if self._codec is not None and self.config.strategy == "sync":
            raise ValueError(
                f"comm_codec={self.config.comm_codec!r} needs a τ-boundary "
                f"weight exchange to compress; strategy 'sync' exchanges "
                f"per-step gradients inside the scan (use local_sgd or "
                f"hierarchical, or comm_codec='none')")
        if self.config.strategy == "hierarchical":
            self.mesh = mesh if mesh is not None else make_pod_mesh()
            if (HOST_AXIS not in self.mesh.shape
                    or CHIP_AXIS not in self.mesh.shape):
                raise ValueError(
                    "hierarchical strategy needs a (host, chip) mesh — "
                    "build it with make_pod_mesh()")
            self.n_hosts = self.mesh.shape[HOST_AXIS]
            self.n_chips = self.mesh.shape[CHIP_AXIS]
            self.n_workers = self.n_hosts * self.n_chips
            # batch rows shard over BOTH tiers; weights average over host
            self._batch_axes: tuple[str, ...] = (HOST_AXIS, CHIP_AXIS)
        else:
            self.mesh = mesh if mesh is not None else make_mesh()
            self.n_workers = self.mesh.shape[DATA_AXIS]
            self._batch_axes = (DATA_AXIS,)
        net_param = sp.net_param or sp.train_net_param
        if net_param is None:
            raise ValueError("SolverParameter carries no net definition")
        self.train_net = Net(net_param, NetState(Phase.TRAIN))
        self.test_net = Net(net_param, NetState(Phase.TEST))
        self.rule = make_update_rule(sp)
        self.iter = 0

        rng = jax.random.PRNGKey(seed if seed >= 0 else 0)
        self._rng, init_rng = jax.random.split(rng)
        rep = replicated(self.mesh)
        # same-seed host-side init staged onto the (possibly multi-host)
        # mesh — explicit per-host replication (SURVEY.md §7.3)
        host_params = self.train_net.init(init_rng)
        # hybrid model+data sharding: resolve the partition rule table
        # against this net's shapes at init (parallel/partition.py).
        # None = pure DP — every code path below is then the pre-plan
        # trainer byte for byte.  Shards live on the fast axis: chips on
        # a pod mesh, the one data axis on a flat mesh.
        from . import partition
        if self.config.strategy == "hierarchical":
            shard_axis, n_shards = CHIP_AXIS, self.n_chips
        else:
            shard_axis, n_shards = DATA_AXIS, self.n_workers
        self.shard_plan = partition.resolve_plan(
            self.config.shard, host_params, axis=shard_axis,
            n_shards=n_shards)
        self.shard_plan_id = partition.shard_plan_id(self.shard_plan)
        # per-leaf resident placement: a params-shaped pytree of
        # NamedShardings under a plan, one replicated sharding without
        self._params_sharding = (
            self.shard_plan.sharding_tree(self.mesh, host_params)
            if self.shard_plan is not None else rep)
        self.params: WeightCollection = put_global_tree(
            host_params, self._params_sharding)
        state0 = self.rule.init(host_params)
        if self.config.strategy == "sync":
            self.state = put_global_tree(state0, rep)
        else:
            # per-worker (local_sgd) / per-host (hierarchical) optimizer
            # state: leading axis sharded over that tier, so each update
            # domain keeps its own momentum history between averages
            n, spec = self._state_tier()
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), state0)
            self.state = put_global_tree(
                stacked, NamedSharding(self.mesh, spec))
        self._lr_mults = put_global_tree(
            self.train_net.lr_mult_tree(self.params), rep)
        self._decay_mults = put_global_tree(
            self.train_net.decay_mult_tree(self.params), rep)

        self._round = self._build_round()
        self._test_fwd = None

        # -- compressed-exchange state (comm_codec != "none"): per-tier
        # error-feedback residuals (trainer state: checkpointed, rolled
        # back, re-tiered like stacked optimizer state) and the three
        # compiled comm programs (encode / exchange / decode)
        self.comm_residual = None
        self._comm = None
        if self._codec is not None:
            n, spec = self._state_tier()
            self.comm_residual = put_global_tree(
                jax.tree_util.tree_map(
                    lambda x: np.zeros((n,) + tuple(x.shape), np.float32),
                    self.params),
                NamedSharding(self.mesh, spec))
            self._comm = self._build_comm_programs()

        # -- resilience state: completed-round counter, caller-maintained
        # feed cursor (any JSON value), and the manifest we resumed from
        self.round = 0
        self.data_cursor: Any = None
        self.resumed: dict[str, Any] | None = None
        # -- numerical-integrity guard state: effective-LR scale (backed
        # off on trips; checkpointed so a relaunch keeps it), trip count,
        # and a short trailing window of accepted losses for spike checks
        self.lr_scale = 1.0
        self.guard_trips = 0
        self._loss_history: list[float] = []
        self._finite_check = None
        # -- cross-replica audit state: compiled fingerprint fn, trip
        # count, and the newest round whose audit PASSED (the rollback
        # horizon — checkpoints at or before it are divergence-free)
        self.audit_trips = 0
        self._audit_fn = None
        self._last_audit_ok = 0
        # -- zero-stall outer loop state: in-flight rounds awaiting
        # harvest (device futures: loss, finite verdict, audit
        # fingerprints), per-round harvested losses, the async checkpoint
        # writer (lazy), and per-component host-stall accounting that
        # bench.py's round_overhead leg reads
        self._pending: collections.deque = collections.deque()
        self.round_losses: dict[int, float] = {}
        self._ckpt_writer = None
        self.stall_s = {"loss_fetch": 0.0, "finite_check": 0.0,
                        "audit_fetch": 0.0, "checkpoint": 0.0,
                        "comm_encode": 0.0, "comm_allreduce": 0.0,
                        "comm_decode": 0.0}
        # the FeedStats of the newest input_feed() (if any) — published on
        # round_end heartbeats so fleet-level supervisors can see the data
        # plane's health without any extra channel
        self.feed_stats = None
        # telemetry handles (no-op singletons under SPARKNET_TELEMETRY=0)
        reg = telemetry.get_registry()
        self._m_rounds = reg.counter(
            "trainer_rounds_total", "training rounds run (replays included)")
        self._m_guard = reg.counter(
            "trainer_guard_trips_total", "numerical-guard rollbacks")
        self._m_audit = reg.counter(
            "trainer_audit_trips_total", "cross-replica audit rollbacks")
        self._m_stall = reg.gauge(
            "trainer_stall_seconds", "cumulative host stall by component")
        self._m_pending = reg.gauge(
            "trainer_pending_rounds", "in-flight rounds awaiting harvest")
        if self.config.harvest_lag < 0:
            raise ValueError(
                f"harvest_lag must be >= 0, got {self.config.harvest_lag}")
        if self.config.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got "
                f"{self.config.checkpoint_every}")
        if self.config.guard_numerics and not self.config.checkpoint_dir:
            raise ValueError(
                "guard_numerics needs checkpoint_dir — rollback is the "
                "guard's only recovery action")
        if self.config.audit_every < 0:
            raise ValueError(
                f"audit_every must be >= 0, got {self.config.audit_every}")
        if self.config.audit_every:
            if not self.config.checkpoint_dir:
                raise ValueError(
                    "audit_every needs checkpoint_dir — rollback is the "
                    "audit's only recovery action")
            horizon = (self.config.checkpoint_every
                       * max(self.config.checkpoint_keep - 1, 0))
            if horizon < self.config.audit_every:
                raise ValueError(
                    f"audit_every={self.config.audit_every} outruns the "
                    f"checkpoint retention (checkpoint_every="
                    f"{self.config.checkpoint_every} x (checkpoint_keep="
                    f"{self.config.checkpoint_keep} - 1) = {horizon} "
                    f"rounds): by the time a mismatch is detected, every "
                    f"pre-divergence checkpoint may be pruned")
        if self.config.harvest_lag and (self.config.guard_numerics
                                        or self.config.audit_every):
            # retention-vs-lag: a poison at round r surfaces up to
            # harvest_lag rounds later (plus up to audit_every rounds of
            # audit cadence), during which fresh checkpoints keep landing
            # and pruning keeps trimming — the newest pre-poison
            # checkpoint (within checkpoint_every-1 rounds of r) must
            # still be on disk when the trip finally asks for it
            horizon = (self.config.checkpoint_every
                       * max(self.config.checkpoint_keep - 1, 0))
            need = (self.config.harvest_lag + self.config.audit_every
                    + self.config.checkpoint_every - 1)
            if horizon < need:
                raise ValueError(
                    f"harvest_lag={self.config.harvest_lag} outruns the "
                    f"checkpoint retention (checkpoint_every="
                    f"{self.config.checkpoint_every} x (checkpoint_keep="
                    f"{self.config.checkpoint_keep} - 1) = {horizon} < "
                    f"{need} rounds of detection latency): by the time a "
                    f"deferred guard/audit verdict trips, every "
                    f"pre-poison checkpoint may be pruned — raise "
                    f"checkpoint_keep or lower harvest_lag")
        if self.config.checkpoint_dir:
            self.resumed = self.resume_latest(self.config.checkpoint_dir)
            if ((self.config.guard_numerics or self.config.audit_every)
                    and self.resumed is None):
                # baseline snapshot: the guard/audit can always roll
                # back, even when the very first round is the poisoned one
                self.save_round_checkpoint()
        from . import health
        health.maybe_beat(self.round, "init")

    def _state_tier(self) -> tuple[int, P]:
        """(leading-axis length, PartitionSpec) of the stacked optimizer
        state for the strategies that keep one state per update domain."""
        if self.config.strategy == "hierarchical":
            return self.n_hosts, P(HOST_AXIS)
        return self.n_workers, P(DATA_AXIS)

    # -- compiled round ---------------------------------------------------
    def _build_round(self):
        sp = self.sp
        net = self.train_net
        rule = self.rule
        tau = self.config.tau
        strategy = self.config.strategy
        lr_mults = self._lr_mults
        decay_mults = self._decay_mults

        iter_size = sp.iter_size
        _, local_update, accum_grads = make_step_fns(
            sp, net, rule, lr_mults, decay_mults,
            remat=self.config.remat, in_scan=True)

        # params owned by forward-state layers (BatchNorm running stats):
        # the only blobs that drift per-shard under sync DP and need
        # re-averaging — pmean'ing the full weight set every step would be
        # a needless full-model collective (VERDICT r1 weak #7)
        state_keys = frozenset(
            key for n in net.nodes if getattr(n.impl, "has_state", False)
            for key in n.owner_keys())

        def split_micro(batches):
            """[tau*iter_size, local_batch, ...] -> [tau, iter_size, ...]
            (the per-step micro-batch runs of solver.cpp:221-224)."""
            return jax.tree_util.tree_map(
                lambda x: x.reshape((tau, iter_size) + x.shape[1:]), batches)

        device_pre = self.config.device_preprocess

        def maybe_preprocess(micro, rng):
            if device_pre is None:
                return micro
            return device_pre(micro, rng)

        def make_psum_step(axis, lr_scale):
            """One per-step-gradient-averaged update over ``axis`` — the
            P2PSync step, shared verbatim by "sync" (over the flat data
            axis) and "hierarchical" (over the chip axis within a host)."""
            def step(carry, micro):
                params, state, it, rng = carry
                with jax.named_scope(INPUT_SCOPE):
                    rng, sub, pre_rng = jax.random.split(rng, 3)
                    ai = lax.axis_index(axis)
                    sub = jax.random.fold_in(sub, ai)
                    micro = maybe_preprocess(
                        micro, jax.random.fold_in(pre_rng, ai))
                loss, params, grads = accum_grads(params, micro, sub)
                with jax.named_scope(SYNC_SCOPE):
                    grads = lax.pmean(grads, axis)
                    loss = lax.pmean(loss, axis)
                    if state_keys:
                        # BN running stats diverge per shard; re-average
                        # those blobs (and only those) so the replication
                        # the out_spec claims over ``axis`` stays truthful
                        params = {
                            k: (lax.pmean(v, axis) if k in state_keys else v)
                            for k, v in params.items()}
                params, state = apply_update(sp, rule, params, grads, state,
                                             it, lr_scale, lr_mults,
                                             decay_mults)
                with jax.named_scope(INPUT_SCOPE):
                    it = it + 1
                return (params, state, it, rng), loss
            return step

        def sync_body(params, state, it, batches, rng, lr_scale):
            """Per-step grad pmean (P2PSync semantics)."""
            with jax.named_scope(INPUT_SCOPE):
                params = maybe_gather(params)
                micro = split_micro(batches)
            (params, state, it, _), losses = lax.scan(
                make_psum_step(DATA_AXIS, lr_scale),
                (params, state, it, rng), micro)
            with jax.named_scope(SYNC_SCOPE):
                if plan is not None:
                    # every position computed the same full update
                    # (per-step grad pmean); each keeps only its resident
                    # shard — a slice, zero communication, exact
                    params = plan.take_shard(params, DATA_AXIS)
                return params, state, jnp.mean(losses)

        # compressed exchange (comm_codec != "none"): the τ-boundary
        # weight pmean LEAVES the compiled round — the body returns each
        # tier member's local weights stacked on the tier axis (exactly
        # like the optimizer state), and the encode→exchange→decode
        # programs built by _build_comm_programs do the averaging outside
        compressed = self._codec is not None

        # hybrid sharding: params enter the round in their resident
        # (per-leaf sharded) layout, are widened to full leaves by a
        # tiled all_gather (pure data movement — exact), and leave the
        # round shard-local again at the τ boundary.  plan=None keeps
        # the replicated P() contract untouched.
        plan = self.shard_plan

        def maybe_gather(params):
            return params if plan is None else plan.gather(params)

        def shard_boundary_mean(params, axis):
            """τ-boundary average under a plan: sharded leaves reduce-
            scatter (each position RECEIVES only its own shard's bytes
            — the broadcast shrink this refactor exists for), replicated
            leaves pmean as before.  psum_scatter(tiled)/n is bitwise
            identical to pmean-then-slice, so the parity contract
            holds."""
            out = {}
            for name, blobs in params.items():
                row = []
                for i, b in enumerate(blobs):
                    dim = plan.dim_of(f"{name}/{i}")
                    if dim is None:
                        row.append(lax.pmean(b, axis))
                    else:
                        row.append(lax.psum_scatter(
                            b, axis, scatter_dimension=dim, tiled=True)
                            / plan.n_shards)
                out[name] = row
            return out

        def local_sgd_body(params, state, it, batches, rng, lr_scale):
            """τ local steps, then weight averaging (SparkNet semantics)."""
            def step(carry, micro):
                params, state, it, rng = carry
                with jax.named_scope(INPUT_SCOPE):
                    rng, sub, pre_rng = jax.random.split(rng, 3)
                    micro = maybe_preprocess(micro, pre_rng)
                params, state, loss = local_update(params, state, it, micro,
                                                   sub, lr_scale)
                with jax.named_scope(INPUT_SCOPE):
                    it = it + 1
                return (params, state, it, rng), loss

            # what enters the scan reads as the steps' input.  The scan
            # itself stays under no scope: the compiler names what it makes
            # inside a loop after the loop, and that is not the program's
            with jax.named_scope(INPUT_SCOPE):
                params = maybe_gather(params)
                state = jax.tree_util.tree_map(lambda x: x[0], state)
                rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
                micro = split_micro(batches)
            (params, state, it, _), losses = lax.scan(
                step, (params, state, it, rng), micro)
            with jax.named_scope(AVERAGE_SCOPE):
                # the scalar loss is not part of the compressed exchange (3
                # bytes saved would not buy the lost logging fidelity), so
                # it is pmean'd here on either path
                loss = lax.pmean(jnp.mean(losses), DATA_AXIS)
                if not compressed:
                    # the broadcast → reduce → scalarDivide of the
                    # reference's outer loop (ImageNetApp.scala:102,
                    # 178-179), as one ICI collective:
                    if plan is None:
                        params = lax.pmean(params, DATA_AXIS)
                    else:
                        params = shard_boundary_mean(params, DATA_AXIS)
                else:
                    params = jax.tree_util.tree_map(lambda x: x[None],
                                                    params)
                state = jax.tree_util.tree_map(lambda x: x[None], state)
                return params, state, loss

        def hierarchical_body(params, state, it, batches, rng, lr_scale):
            """Per-step grad pmean over chips (the P2PSync step over the
            fast tier), τ-boundary weight pmean over hosts (the Spark
            round) — the two reference tiers composed on the
            (host, chip) mesh.  BN running stats follow both tiers'
            semantics: re-averaged per step over chips inside the psum
            step, averaged with the weights at the τ boundary over
            hosts."""
            with jax.named_scope(INPUT_SCOPE):
                params = maybe_gather(params)
                state = jax.tree_util.tree_map(lambda x: x[0], state)
                rng = jax.random.fold_in(rng, lax.axis_index(HOST_AXIS))
                micro = split_micro(batches)
            (params, state, it, _), losses = lax.scan(
                make_psum_step(CHIP_AXIS, lr_scale),
                (params, state, it, rng), micro)
            with jax.named_scope(AVERAGE_SCOPE):
                loss = lax.pmean(jnp.mean(losses), HOST_AXIS)
                if not compressed:
                    # the cross-host averaging rides DCN once per τ steps
                    # — the broadcast → reduce → scalarDivide of the
                    # reference's outer loop (ImageNetApp.scala:102,
                    # 178-179)
                    if plan is None:
                        params = lax.pmean(params, HOST_AXIS)
                    else:
                        # slice the resident chip shard FIRST, then
                        # average over hosts: the DCN collective moves
                        # only shard bytes, and slice-then-mean ==
                        # mean-then-slice elementwise, so parity holds
                        params = plan.take_shard(params, CHIP_AXIS)
                        params = lax.pmean(params, HOST_AXIS)
                else:
                    # chips within a host already agree (per-step chip
                    # psum); stack one copy per HOST for the compressed
                    # DCN exchange
                    params = jax.tree_util.tree_map(lambda x: x[None],
                                                    params)
                state = jax.tree_util.tree_map(lambda x: x[None], state)
                return params, state, loss

        bodies = {"local_sgd": local_sgd_body, "sync": sync_body,
                  "hierarchical": hierarchical_body}
        body = bodies[strategy]
        state_spec = (P() if strategy == "sync"
                      else self._state_tier()[1])
        # params in/out specs derive from the partition rule table: a
        # per-leaf pytree of PartitionSpecs under a plan, P() without
        params_in_spec = (P() if plan is None
                          else plan.spec_tree(self.params))
        params_out_spec = (self._state_tier()[1] if compressed
                           else params_in_spec)
        # batches: [tau, global_batch, ...] sharded on the batch axis
        batch_spec = P(None, self._batch_axes)

        mapped = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(params_in_spec, state_spec, P(), batch_spec, P(), P()),
            out_specs=(params_out_spec, state_spec, P()),
            check_vma=False,
        )
        # compressed path: the replicated input params stay live as the
        # delta reference for encode/decode — only the state may donate
        donate: tuple[int, ...] = ()
        if self.config.donate:
            donate = (1,) if compressed else (0, 1)
        return jax.jit(mapped, donate_argnums=donate)

    def _build_comm_programs(self):
        """The three programs of the compressed exchange.  All replicas
        run identical programs over replicated inputs for decode, so the
        new params are replicated bit-identically by construction — the
        audit invariant holds under every codec with zero tolerance.

        * **encode** (per-tier): ``delta_i = local_i - ref + residual_i``
          then the codec's wire format; the new residual is the exact
          f32 quantization error (error feedback — compression error is
          deferred to round r+1, never dropped).
        * **exchange**: reshard the stacked payload tier→replicated (one
          all-gather).  This is the collective that moves the wire
          bytes — the only traffic the codec is shrinking.
        * **decode**: every replica decodes the same gathered payload,
          means the deltas over the tier axis, and adds the same
          replicated reference back.
        """
        from . import comms
        codec = self._codec

        def enc(local, ref, residual):
            delta = jax.tree_util.tree_map(
                lambda l, r, e: l - r[None] + e, local, ref, residual)
            payload, _, new_res = comms.roundtrip_tree(codec, delta)
            return payload, new_res

        def dec(payload, ref):
            deltas = comms.decode_tree(codec, payload, ref_stacked_like(ref))
            return jax.tree_util.tree_map(
                lambda r, d: r + jnp.mean(d, axis=0), ref, deltas)

        n_tier = self._state_tier()[0]

        def ref_stacked_like(ref):
            # structural template only (decode_tree re-anchors the tree
            # structure from it; values are never read)
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n_tier,) + x.shape),
                ref)

        rep = replicated(self.mesh)
        # local + residual are consumed; ref params must survive (decode
        # still needs them after encode ran)
        encode = jax.jit(enc, donate_argnums=(0, 2))
        exchange = jax.jit(lambda t: t, out_shardings=rep)
        # every replica decodes the same gathered payload, so the full
        # logical result is identical everywhere; under a shard plan the
        # output lands straight in the per-leaf resident placement (each
        # position stores only its shard of the identical value — the
        # audit's shard invariant holds under every codec)
        decode = jax.jit(dec, out_shardings=self._params_sharding)
        return encode, exchange, decode

    def _run_comm_round(self, batches, rng):
        """One compressed round: local-steps program, then the
        encode→exchange→decode tail.  ``comm_overlap`` is purely a
        host-blocking policy — False inserts a ``block_until_ready``
        after each stage so ``stall_s`` charges the true device time to
        the right component (the roundbench discipline); True dispatches
        all three and returns, letting the tail overlap whatever the
        host does next (feed staging, bookkeeping, or — with
        harvest_lag > 0 — the next round's dispatch).  Same programs,
        same order, bit-identical results either way."""
        overlap = self.config.comm_overlap
        local, self.state, loss = self._round(
            self.params, self.state, jnp.asarray(self.iter), batches, rng,
            jnp.asarray(self.lr_scale, jnp.float32))
        encode, exchange, decode = self._comm
        t0 = time.perf_counter()
        payload, self.comm_residual = encode(
            local, self.params, self.comm_residual)
        if not overlap:
            jax.block_until_ready(payload)
        self.stall_s["comm_encode"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        gathered = exchange(payload)
        if not overlap:
            jax.block_until_ready(gathered)
        self.stall_s["comm_allreduce"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        self.params = decode(gathered, self.params)
        if not overlap:
            jax.block_until_ready(self.params)
        self.stall_s["comm_decode"] += time.perf_counter() - t0
        return loss

    # -- driver API -------------------------------------------------------
    @property
    def input_sharding(self) -> NamedSharding:
        """Sharding for [τ, global_batch, ...] round feeds — batch axis over
        the mesh.  Feeds staged with this (e.g. via ``data.prefetch.
        device_feed``) make ``train_round``'s own device_put a no-op."""
        return NamedSharding(self.mesh, P(None, self._batch_axes))

    @property
    def batches_per_round(self) -> int:
        """Minibatches consumed per round: τ steps × iter_size micro-batches
        (gradient accumulation, reference: solver.cpp:221-224)."""
        return self.config.tau * self.sp.iter_size

    def input_feed(self, rounds: Iterator[Mapping[str, Any]],
                   depth: int | None = None, stats=None,
                   stall_timeout: float | None = None, restarts: int = 1,
                   device_cast: Mapping[str, Any] | None = None):
        """Stage a host round stream for this trainer through the
        parallel feed pipeline (``data.prefetch.device_feed``) with the
        trainer's ``input_sharding`` — decode/transform/transfer overlap
        the compiled round, and ``train_round``'s own device_put becomes
        a no-op.  ``depth`` defaults to ``SPARKNET_FEED_DEPTH`` when set,
        else ``harvest_lag + 1``: a [τ, global_batch, ...] round is large
        in HBM, so the deep default that suits per-step feeds is opt-in
        here — but a pipelined loop (``harvest_lag`` K > 0) keeps K
        compiled rounds in flight and needs that many staged feeds to
        never be the bottleneck.  ``device_cast`` (blob -> dtype) stages
        the host's array as-is and casts AFTER transfer — the raw-uint8
        feed path (records + device-side augmentation) ships 1/4 the
        PCIe bytes of an f32 round.  Close the returned feed (context
        manager) after the loop."""
        from ..data.pipeline import FeedStats, feed_depth
        from ..data.prefetch import device_feed
        if depth is None:
            depth = feed_depth(max(1, self.config.harvest_lag + 1))
        if stats is None:
            stats = FeedStats()
        self.feed_stats = stats
        return device_feed(rounds, depth=depth,
                           sharding=self.input_sharding, stats=stats,
                           stall_timeout=stall_timeout, restarts=restarts,
                           device_cast=device_cast)

    def train_round(self, batches: Mapping[str, Any]) -> float:
        """Run one round (τ steps, each accumulating iter_size
        micro-batches).  ``batches`` maps input blob names to arrays with a
        leading τ·iter_size axis and a batch axis:
        [tau * iter_size, batch, ...].  Single-host, the batch axis is the
        global batch; multi-host, each process passes only ITS rows of the
        global batch (its partitions — the zipPartitions placement,
        reference: ImageNetApp.scala:145) and the global array is assembled
        without any host seeing the whole batch.

        With ``guard_numerics`` the finished round is validated before it
        counts: a non-finite loss, non-finite params, or a loss spike
        rolls the trainer back to the newest valid checkpoint and the
        round is DROPPED — ``self.round`` does not advance, so a
        ``while trainer.round < rounds`` driver naturally replays it.
        The (poisoned) loss is still returned for logging.

        With ``harvest_lag`` K > 0 this call is free of host
        synchronization in the steady state: the loss/guard/audit
        results stay on-device and are harvested once K rounds are in
        flight, so the return value is the loss of a round up to K
        behind (``float('nan')`` until the first harvest; exact
        per-round losses accumulate in ``self.round_losses``).  A trip
        detected at harvest rolls back exactly as the synchronous path
        does — same checkpoint chain, same RNG replay — and discards
        every in-flight round after the poisoned one.  Call ``drain()``
        before reading final params/scores."""
        with telemetry.span("trainer.round", cat="trainer",
                            round=self.round):
            loss_val = self._train_round_impl(batches)
        self._m_rounds.inc()
        self._m_pending.set(len(self._pending))
        for k, v in self.stall_s.items():
            self._m_stall.set(v, comp=k)
        telemetry.get_registry().maybe_snapshot()
        return loss_val

    def _train_round_impl(self, batches: Mapping[str, Any]) -> float:
        from . import health
        from ..utils import faults
        expect = self.batches_per_round
        procs = jax.process_count()
        local_workers = max(self.n_workers // procs, 1)
        for k, v in batches.items():
            if v.shape[0] != expect:
                raise ValueError(
                    f"{k}: leading dim {v.shape[0]} != tau*iter_size "
                    f"{expect}")
            if v.shape[1] % local_workers:
                raise ValueError(
                    f"{k}: batch {v.shape[1]} not divisible by "
                    f"{local_workers} local workers")
        round_idx = self.round
        lag = self.config.harvest_lag
        health.maybe_beat(round_idx, "round_start")
        # deterministic chaos hook: rot one replica's resident param copy
        # (a flipped HBM bit between rounds — the event the audit exists
        # to catch before the next averaging folds it in)
        flip = faults.get_injector().bitflip_rank(round_idx)
        if flip is not None:
            print(f"FAULT: bitflip_params corrupting replica {flip}'s "
                  f"params at round {round_idx}", file=sys.stderr,
                  flush=True)
            self._inject_bitflip(flip)
        audit_fps = None
        if (self.config.audit_every
                and round_idx % self.config.audit_every == 0):
            if lag:
                # fingerprints are computed over the PRE-round params (the
                # invariant the audit checks) but stay on-device; the
                # verdict is harvested with the round's loss
                if self._audit_fn is None:
                    self._audit_fn = self._build_audit()
                audit_fps = self._audit_fn(self.params)
            else:
                t0 = time.perf_counter()
                fps = self.audit_params()
                self.stall_s["audit_fetch"] += time.perf_counter() - t0
                if not self._audit_ok(fps):
                    # round dropped BEFORE it runs; self.round rewinds to
                    # the rollback point, so a while-trainer.round driver
                    # replays
                    self._audit_trip(round_idx, fps)
                    return float("nan")
                self._last_audit_ok = round_idx
        # deterministic chaos hook: poison THIS rank's feed with NaNs (the
        # guard must catch the poison after averaging, no matter which
        # rank produced it — exactly a flaky-HBM / bad-DMA event)
        if faults.get_injector().nan_inject(round_idx):
            print(f"FAULT: nan_inject poisoning round {round_idx} feed",
                  file=sys.stderr, flush=True)
            batches = {
                k: (np.full_like(v, np.nan)
                    if np.issubdtype(np.asarray(v).dtype, np.floating)
                    else v)
                for k, v in batches.items()}
        # pre-shard the feed so each device receives only its slice — no
        # single-device staging (the reference's driver bottleneck); a no-op
        # for feeds already staged via device_feed(input_sharding)
        with telemetry.span("trainer.stage", cat="trainer",
                            round=round_idx):
            batches = {k: stage_local(v, self.input_sharding)
                       for k, v in batches.items()}
        self._rng, rng = jax.random.split(self._rng)
        with telemetry.span("trainer.dispatch", cat="trainer",
                            round=round_idx):
            if self._comm is not None:
                loss = self._run_comm_round(batches, rng)
            else:
                self.params, self.state, loss = self._round(
                    self.params, self.state, jnp.asarray(self.iter),
                    batches, rng, jnp.asarray(self.lr_scale, jnp.float32))
        if lag:
            # zero-stall path: loss + finite verdict stay on-device; the
            # dispatch returns immediately and the verdicts are harvested
            # up to ``lag`` rounds later (below)
            finite = (self._finite_fn()(self.params)
                      if self.config.guard_numerics else None)
            self._pending.append({"round": round_idx, "loss": loss,
                                  "finite": finite, "fps": audit_fps})
            loss_val = float("nan")
        else:
            t0 = time.perf_counter()
            with telemetry.span("trainer.loss_fetch", cat="trainer",
                                round=round_idx):
                loss_val = float(loss)
            self.stall_s["loss_fetch"] += time.perf_counter() - t0
            if self.config.guard_numerics:
                reason = self._poison_reason(loss_val)
                if reason:
                    self._guard_trip(round_idx, reason)
                    return loss_val   # round dropped; self.round unchanged
                self._loss_history = (self._loss_history + [loss_val])[-8:]
            self.round_losses[round_idx] = loss_val
        prev = self.iter
        self.iter += self.config.tau
        # snapshot-on-schedule at round granularity (Solver::Step checks per
        # iter, reference: solver.cpp:270-277; a compiled round cannot stop
        # mid-scan, so the schedule fires when a boundary was crossed)
        if (self.sp.snapshot and self.sp.snapshot_prefix
                and prev // self.sp.snapshot != self.iter // self.sp.snapshot):
            self.snapshot(f"{self.sp.snapshot_prefix}_iter_{self.iter}.npz")
        self.round += 1
        if (self.config.checkpoint_dir
                and self.round % self.config.checkpoint_every == 0):
            self.save_round_checkpoint()
        health.maybe_beat(round_idx, "round_end", extras=self._beat_extras())
        if lag:
            # keep at most ``lag`` rounds in flight: harvesting the
            # overflow is the ONLY place the steady-state loop can block,
            # and with a healthy device it blocks on a round dispatched
            # K rounds ago — long since finished
            while len(self._pending) > lag:
                h = self._harvest_one()
                if h is not None:
                    loss_val = h
        return loss_val

    def _beat_extras(self) -> dict:
        """Telemetry riding the round_end heartbeat: per-component host
        stalls, trip counters, and the feed pipeline's stats — the fleet
        status view's only window into a running job."""
        extras = {
            "stall_s": {k: round(v, 4) for k, v in self.stall_s.items()},
            "guard_trips": self.guard_trips,
            "audit_trips": self.audit_trips,
        }
        if self.feed_stats is not None:
            extras["feed"] = self.feed_stats.snapshot()
        return extras

    # -- numerical-integrity guard (see TrainerConfig.guard_numerics) -----
    def _finite_fn(self):
        """The jitted all-leaves-finite reduction over the float leaves
        of a (replicated) pytree — one fused pass producing one device
        scalar (fetched immediately on the sync path, harvested late on
        the deferred path)."""
        if self._finite_check is None:
            def check(t):
                leaves = [jnp.all(jnp.isfinite(x))
                          for x in jax.tree_util.tree_leaves(t)
                          if jnp.issubdtype(x.dtype, jnp.floating)]
                return (jnp.all(jnp.stack(leaves)) if leaves
                        else jnp.asarray(True))
            self._finite_check = jax.jit(check)
        return self._finite_check

    def _all_finite(self, tree) -> bool:
        t0 = time.perf_counter()
        out = bool(self._finite_fn()(tree))
        self.stall_s["finite_check"] += time.perf_counter() - t0
        return out

    def _loss_poison_reason(self, loss_val: float) -> str | None:
        """The host-only half of the verdict: non-finite or spiking
        loss.  Shared by the synchronous check and the deferred harvest
        (where the params verdict arrives separately, as the round's own
        pre-computed finite flag)."""
        if not np.isfinite(loss_val):
            return f"non-finite loss {loss_val}"
        factor = self.config.loss_spike_factor
        if factor > 0 and len(self._loss_history) >= 3:
            mean = sum(self._loss_history) / len(self._loss_history)
            if loss_val > factor * mean:
                return (f"loss spike {loss_val:.4g} > {factor:g} x "
                        f"trailing mean {mean:.4g}")
        return None

    def _poison_reason(self, loss_val: float) -> str | None:
        """Why the just-finished round should be rejected, or None."""
        reason = self._loss_poison_reason(loss_val)
        if reason:
            return reason
        if not self._all_finite(self.params):
            return "non-finite parameters after averaging"
        return None

    def _guard_trip(self, round_idx: int, reason: str) -> None:
        """Reject round ``round_idx``: roll back to the newest valid
        checkpoint at or before it (params/state/iter/round/RNG all
        restored, so the replay is exact), optionally back off the LR,
        and count the trip.  The ``max_round`` bound is what keeps the
        deferred-harvest path safe: under a harvest lag, checkpoints for
        rounds AFTER the poisoned one may already exist (and carry the
        poison) — they must not be rollback targets.  On the synchronous
        path no newer checkpoint can exist yet, so the bound is inert.
        All processes take this path together — the decision derives
        from replicated values, so no collective can diverge."""
        self.guard_trips += 1
        self._m_guard.inc()
        rec = telemetry.get_recorder()
        rec.record("guard_trip", round=round_idx, reason=reason,
                   trips=self.guard_trips)
        rec.dump("guard_trip")
        print(f"guard: round {round_idx} REJECTED ({reason}); rolling "
              f"back to last valid checkpoint at round <= {round_idx} "
              f"(trip {self.guard_trips}/{self.config.guard_max_trips})",
              file=sys.stderr, flush=True)
        if self.guard_trips > self.config.guard_max_trips:
            raise TrainingDivergedError(
                f"numerical guard tripped {self.guard_trips} times "
                f"(> guard_max_trips={self.config.guard_max_trips}); "
                f"last reason: {reason}")
        manifest = self.resume_latest(self.config.checkpoint_dir,
                                      max_round=round_idx)
        if manifest is None:
            raise TrainingDivergedError(
                f"round {round_idx} poisoned ({reason}) and no valid "
                f"checkpoint at round <= {round_idx} to roll back to in "
                f"{self.config.checkpoint_dir!r}")
        if self.config.guard_lr_backoff != 1.0:
            self.lr_scale *= self.config.guard_lr_backoff
            print(f"guard: LR scale backed off to {self.lr_scale:g}",
                  file=sys.stderr, flush=True)

    # -- deferred harvesting (see TrainerConfig.harvest_lag) --------------
    def _harvest_one(self) -> float | None:
        with telemetry.span("trainer.harvest", cat="trainer",
                            round=int(self._pending[0]["round"])):
            return self._harvest_one_impl()

    def _harvest_one_impl(self) -> float | None:
        """Resolve the OLDEST in-flight round: fetch its audit verdict,
        loss, and finite-check (in that order — the audit inspected the
        params the round STARTED from, so its verdict comes first, as on
        the synchronous path).  A trip discards every younger in-flight
        round (their inputs descend from the poisoned state), flushes
        the checkpoint writer so the rollback scan sees a settled disk,
        rolls back, and prunes now-invalid newer checkpoints.  Returns
        the harvested loss (poisoned losses included, for logging), or
        None when the round was dropped by the audit before it counted."""
        e = self._pending.popleft()
        round_idx = int(e["round"])
        if e["fps"] is not None:
            t0 = time.perf_counter()
            fps = np.asarray(e["fps"])
            self.stall_s["audit_fetch"] += time.perf_counter() - t0
            if not self._audit_ok(fps):
                self._pending.clear()
                self.flush_checkpoints()
                self._audit_trip(round_idx, fps)
                self._drop_checkpoints_after(self.round)
                return None
            self._last_audit_ok = round_idx
        t0 = time.perf_counter()
        loss_val = float(e["loss"])
        self.stall_s["loss_fetch"] += time.perf_counter() - t0
        if self.config.guard_numerics:
            reason = self._loss_poison_reason(loss_val)
            if reason is None and e["finite"] is not None:
                t0 = time.perf_counter()
                finite = bool(e["finite"])
                self.stall_s["finite_check"] += time.perf_counter() - t0
                if not finite:
                    reason = "non-finite parameters after averaging"
            if reason:
                self._pending.clear()
                self.flush_checkpoints()
                self._guard_trip(round_idx, reason)
                self._drop_checkpoints_after(self.round)
                return loss_val
            self._loss_history = (self._loss_history + [loss_val])[-8:]
        self.round_losses[round_idx] = loss_val
        return loss_val

    def drain(self) -> dict[int, float]:
        """Harvest every in-flight round verdict and flush the async
        checkpoint writer — the end-of-loop (and pre-eval) barrier for
        pipelined training.  After this, ``self.params`` is a validated
        state and every scheduled checkpoint is durable.  Returns the
        per-round harvested losses (``self.round_losses``).

        NOTE a deferred verdict can TRIP here, after the driver's round
        loop already exited: the rollback rewinds ``self.round``, so a
        driver that wants the dropped rounds replayed must re-enter its
        ``while trainer.round < rounds`` loop until the target holds
        after drain (see tests/multihost_driver.py)."""
        while self._pending:
            self._harvest_one()
        self.flush_checkpoints()
        return dict(self.round_losses)

    def flush_checkpoints(self) -> None:
        """Durability barrier over this trainer's async checkpoint
        writes; re-raises any background write failure.  A no-op on the
        synchronous path."""
        if self._ckpt_writer is not None:
            t0 = time.perf_counter()
            try:
                self._ckpt_writer.flush()
            finally:
                self.stall_s["checkpoint"] += time.perf_counter() - t0

    def _drop_checkpoints_after(self, round_idx: int) -> None:
        """Remove checkpoints NEWER than ``round_idx`` — after a deferred
        trip rolled back, snapshots taken during the detection lag
        descend from the poisoned state and must not survive as future
        rollback targets.  (The replay re-writes those round boundaries
        with clean state.)  Process 0 only; inert on the synchronous
        path, where no newer checkpoint can exist at trip time."""
        directory = self.config.checkpoint_dir
        if not directory or jax.process_index() != 0:
            return
        for mpath in glob.glob(os.path.join(directory, "manifest_*.json")):
            r = _manifest_round(mpath)
            if r > round_idx:
                # the glob sweeps per-shard tiles along with the main npz
                for p in (mpath, *glob.glob(os.path.join(
                        directory, f"ckpt_round_{r:08d}*.npz"))):
                    try:
                        os.remove(p)
                    except OSError:
                        pass

    # -- cross-replica parameter audit (see TrainerConfig.audit_every) ----
    def _build_audit(self):
        """Compile the fingerprint collective: each replica bit-casts its
        float param leaves to uint32 and tree-sums them (mod 2**32 — any
        single flipped bit changes the sum), then one all_gather over the
        batch axes returns every replica's fingerprint, replicated, so
        all processes reach the same verdict without extra traffic.

        Under a shard plan each position holds full copies of the
        replicated leaves but only ITS shard of the sharded ones, so one
        scalar per position can no longer be compared mesh-wide.  The
        fingerprint becomes a [n_pos, 2] matrix — column 0 sums the
        replicated leaves (must be unanimous mesh-wide, as before),
        column 1 sums the resident shard content (one uint32 per shard,
        gathered in the same single all_gather; compared within the
        groups of positions that hold the same shard — see
        ``_audit_culprits``)."""
        axes = self._batch_axes
        plan = self.shard_plan

        def leaf_sum(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                f32 = (leaf if leaf.dtype == jnp.float32
                       else leaf.astype(jnp.float32))
                bits = lax.bitcast_convert_type(f32, jnp.uint32)
            elif jnp.issubdtype(leaf.dtype, jnp.integer):
                bits = leaf.astype(jnp.uint32)
            else:
                return None
            return jnp.sum(bits, dtype=jnp.uint32)

        def fingerprint(params):
            if plan is None:
                total = jnp.zeros((), jnp.uint32)
                for leaf in jax.tree_util.tree_leaves(params):
                    s = leaf_sum(leaf)
                    if s is not None:
                        total = total + s
                return lax.all_gather(total, axes).reshape(-1)
            total_rep = jnp.zeros((), jnp.uint32)
            total_shard = jnp.zeros((), jnp.uint32)
            for name, blobs in params.items():
                for i, leaf in enumerate(blobs):
                    s = leaf_sum(leaf)
                    if s is None:
                        continue
                    if plan.dim_of(f"{name}/{i}") is None:
                        total_rep = total_rep + s
                    else:
                        total_shard = total_shard + s
            pair = jnp.stack([total_rep, total_shard])
            return lax.all_gather(pair, axes).reshape(-1, 2)

        params_spec = (P() if plan is None
                       else plan.spec_tree(self.params))
        mapped = jax.shard_map(fingerprint, mesh=self.mesh,
                               in_specs=(params_spec,),
                               out_specs=P(), check_vma=False)
        return jax.jit(mapped)

    def _audit_groups(self) -> list[list[int]]:
        """Mesh positions (flattened in batch-axes order) that hold
        identical shard content: on the pod mesh every host replicates
        each chip's shard (group = one chip column across hosts); on a
        flat mesh every position owns a distinct shard (singleton
        groups — the shard column is then self-consistent by definition
        and only the replicated column can trip)."""
        if self.config.strategy == "hierarchical":
            return [[h * self.n_chips + c for h in range(self.n_hosts)]
                    for c in range(self.n_chips)]
        return [[i] for i in range(self.n_workers)]

    def _audit_culprits(self, fps: np.ndarray) -> list[int]:
        """Positions whose fingerprints disagree with their comparison
        group's majority.  1-D fps = the replicated-params legacy shape
        (one scalar per position, one mesh-wide group); 2-D fps = the
        sharded shape (column 0 mesh-wide, column 1 per shard group)."""
        fps = np.asarray(fps)
        if fps.ndim == 1:
            checks = [(list(range(fps.shape[0])), fps)]
        else:
            checks = [(list(range(fps.shape[0])), fps[:, 0])]
            checks += [(g, fps[:, 1]) for g in self._audit_groups()]
        culprits: set[int] = set()
        for group, col in checks:
            sel = col[group]
            vals, counts = np.unique(sel, return_counts=True)
            if vals.size <= 1:
                continue
            majority = vals[int(np.argmax(counts))]
            culprits.update(g for g, f in zip(group, sel) if f != majority)
        return sorted(culprits)

    def _audit_ok(self, fps) -> bool:
        return not self._audit_culprits(np.asarray(fps))

    def audit_params(self) -> np.ndarray:
        """Per-replica parameter fingerprints, one uint32 per mesh
        position (replicas of a healthy mesh all return the same value —
        the replication invariant, made checkable)."""
        if self._audit_fn is None:
            self._audit_fn = self._build_audit()
        return np.asarray(self._audit_fn(self.params))

    def _audit_trip(self, round_idx: int, fps: np.ndarray) -> None:
        """A replica's params diverged: roll back to the newest
        checkpoint at or before the last PASSED audit (that state was
        verified consistent; anything newer may carry the rot) — the
        guard's rollback path, RNG replay and all."""
        self.audit_trips += 1
        self.guard_trips += 1
        fps = np.asarray(fps)
        culprits = self._audit_culprits(fps)
        fps_hex = [hex(int(f)) for f in fps.reshape(-1)]
        self._m_audit.inc()
        rec = telemetry.get_recorder()
        rec.record("audit_mismatch", round=round_idx, culprits=culprits,
                   fingerprints=fps_hex,
                   last_ok=self._last_audit_ok)
        rec.dump("audit_mismatch")
        print(f"audit: round {round_idx} REJECTED — cross-replica param "
              f"fingerprints diverge (replicas {culprits} vs the "
              f"majority: {fps_hex}); rolling back to "
              f"a round <= {self._last_audit_ok} checkpoint "
              f"(trip {self.guard_trips}/{self.config.guard_max_trips})",
              file=sys.stderr, flush=True)
        if self.guard_trips > self.config.guard_max_trips:
            raise TrainingDivergedError(
                f"audit tripped at round {round_idx} and the trip budget "
                f"is spent ({self.guard_trips} > guard_max_trips="
                f"{self.config.guard_max_trips}); replicas {culprits} "
                f"keep diverging")
        manifest = self.resume_latest(self.config.checkpoint_dir,
                                      max_round=self._last_audit_ok)
        if manifest is None:
            raise TrainingDivergedError(
                f"round {round_idx}: replicas {culprits} diverged and no "
                f"checkpoint at round <= {self._last_audit_ok} remains "
                f"in {self.config.checkpoint_dir!r}")

    def _inject_bitflip(self, replica: int) -> None:
        """Chaos hook (``bitflip_params@rank:R@round:N``): flip one
        mantissa bit in replica ``replica``'s resident copy of the first
        non-empty param leaf — the replicas now disagree by one bit,
        exactly what a flaky HBM cell produces.  The flipped value stays
        finite, so the numerical guard can NOT catch it; only the audit
        can.  Multi-host: each process flips only the shard it owns."""
        target = tuple(self.mesh.devices.flat)[replica % self.n_workers]
        leaf = None
        for name in sorted(self.params):
            blobs = self.params[name]
            if blobs and blobs[0].size and blobs[0].dtype == jnp.float32:
                leaf = blobs[0]
                break
        if leaf is None:
            return
        def words(x):
            # a writable copy in C order: what a TPU hands back keeps the
            # device's dimension order in its strides, and reshape(-1) of
            # that is a copy the flip would be lost in
            return np.array(x, order="C").reshape(-1).view(np.uint32)

        arrays = []
        for shard in leaf.addressable_shards:
            data = np.asarray(shard.data)
            if shard.device == target:
                flat = words(data)
                flat[0] ^= np.uint32(1 << 22)
                data = flat.view(data.dtype).reshape(data.shape)
                on_target = jax.device_put(data, shard.device)
                if words(on_target)[0] != flat[0]:
                    raise RuntimeError(
                        f"bitflip_params: the flip did not land on {target}")
                arrays.append(on_target)
            else:
                arrays.append(jax.device_put(data, shard.device))
        self.params[name][0] = jax.make_array_from_single_device_arrays(
            leaf.shape, leaf.sharding, arrays)

    def test(self, feed: Iterator[Mapping[str, Any]], num_steps: int,
             ) -> dict[str, Any]:
        """Distributed eval, the zipPartitions contract made SPMD
        (reference: ImageNetApp.scala:108-141): every worker scores ITS
        batch rows independently (net.test() per partition), the
        per-worker scores are masked by a validity flag and psum'd.

        Feed batches may carry ``"__valid__"`` — a float (local_workers,)
        0/1 mask — so partitions of UNEQUAL size eval with reference
        semantics: exhausted workers feed padding rows with valid=0 and
        contribute nothing, exactly like a zipPartitions worker whose
        ``len`` ran out.  Returned totals are RAW sums over worker-batches
        (the reference's accumulated ``v``); ``totals["__test_batches__"]``
        counts the valid worker-batches, so ``score = totals[k] /
        totals["__test_batches__"]`` is the reference's ``100F·v /
        numTestMinibatches`` normalization (ImageNetApp.scala:139-140)."""
        if self._test_fwd is None:
            net = self.test_net
            # per-blob batch-axis decision from producing-layer metadata
            # (LayerImpl.top_has_batch_axis) — NOT from a runtime shape
            # coincidence: a per-class accuracy vector whose length equals
            # the batch must stay element-wise
            has_batch_axis: dict[str, bool] = {}
            for node in net.nodes:
                for i, t in enumerate(node.tops):
                    has_batch_axis[t] = node.impl.top_has_batch_axis(
                        node.lp, i)

            plan = self.shard_plan

            def worker(params, batch, valid):
                # one zipPartitions worker: score the local rows, zero out
                # invalid (padding) batches, sum across the mesh — the
                # result is replicated so every host can fetch it
                if plan is not None:
                    # widen resident shards to full leaves for the
                    # forward (tiled all_gather — exact)
                    params = plan.gather(params)
                out = net.apply(params, batch, train=False)
                v = valid[0]

                def reduce(k, val):
                    if val.ndim and has_batch_axis.get(k, True):
                        val = jnp.sum(val, axis=0)
                    return val * v
                scores = {k: reduce(k, val) for k, val in out.blobs.items()}
                scores["__test_batches__"] = v
                return jax.tree_util.tree_map(
                    lambda t: lax.psum(t, self._batch_axes), scores)

            params_spec = (P() if plan is None
                           else plan.spec_tree(self.params))
            self._test_fwd = jax.jit(jax.shard_map(
                worker, mesh=self.mesh,
                in_specs=(params_spec, P(self._batch_axes),
                          P(self._batch_axes)),
                out_specs=P(), check_vma=False))
        sharding = NamedSharding(self.mesh, P(self._batch_axes))
        local_workers = max(self.n_workers // jax.process_count(), 1)
        totals: dict[str, Any] = {}
        last_raw: dict[str, Any] | None = None
        for _ in range(num_steps):
            batch = {}
            try:
                raw = dict(next(feed))
            except StopIteration:
                # every step is a collective, so all hosts must take the
                # same num_steps (pass the global max — cluster.global_max);
                # a host whose local feed ran out keeps participating with
                # fully-invalid padding steps
                if last_raw is None:
                    raise ValueError(
                        "eval feed yielded no batches but num_steps > 0")
                raw = dict(last_raw)
                raw["__valid__"] = np.zeros(local_workers, np.float32)
            valid = np.asarray(raw.pop("__valid__",
                                       np.ones(local_workers)), np.float32)
            last_raw = dict(raw)
            if valid.shape != (local_workers,):
                raise ValueError(
                    f"__valid__ must have shape ({local_workers},) — one "
                    f"flag per local worker — got {valid.shape}")
            for k, v in raw.items():
                if v.shape[0] % local_workers:
                    raise ValueError(
                        f"{k}: eval batch {v.shape[0]} not divisible by "
                        f"{local_workers} local workers")
                batch[k] = stage_local(v, sharding)
            scores = self._test_fwd(self.params, batch,
                                    stage_local(valid, sharding))
            for k, v in scores.items():
                val = float(v) if np.ndim(v) == 0 else np.asarray(v)
                totals[k] = val if k not in totals else totals[k] + val
        return totals

    # -- checkpoint (driver-side averaged weights + per-worker state;
    #    parity target per SURVEY.md §5 checkpoint/resume) ----------------
    def _host_blob(self) -> dict[str, Any]:
        """The full training state as a host-fetchable pytree.  Multi-host
        this is a COLLECTIVE (the sharded per-worker optimizer state is
        all-gathered to replicated) — every process must call it."""
        state = self.state
        if jax.process_count() > 1 and self.config.strategy != "sync":
            state = jax.jit(lambda t: t,
                            out_shardings=replicated(self.mesh))(state)
        params = self.params
        if self.shard_plan is not None:
            # blobs always carry FULL logical leaves: a restore at ANY
            # world size just re-slices per the new plan, which is what
            # keeps the elastic re-tile contract bit-exact.  (The
            # per-shard npz layout is a WRITE-side split of this same
            # full blob — see _save_round_checkpoint_impl.)
            params = jax.jit(lambda t: t,
                             out_shardings=replicated(self.mesh))(params)
        blob: dict[str, Any] = {
            "params": params,
            "state": state,
            "iter": self.iter,
            "round": self.round,
            "rng": np.asarray(self._rng),
            "strategy": self.config.strategy,
            "n_workers": self.n_workers,
            "lr_scale": np.float64(self.lr_scale),
        }
        if self.shard_plan is not None:
            blob["shard_plan"] = self.shard_plan_id  # provenance stamp
        if self.config.strategy == "hierarchical":
            blob["n_hosts"] = self.n_hosts  # state is per-host
        if self.comm_residual is not None:
            # error-feedback residuals are trainer state: a rollback (or
            # relaunch) that replayed params but dropped the residual
            # would silently discard deferred quantization error and
            # break the bit-exact-replay contract under lossy codecs
            res = self.comm_residual
            if jax.process_count() > 1:
                res = jax.jit(lambda t: t,
                              out_shardings=replicated(self.mesh))(res)
            blob["comm_residual"] = res
            blob["comm_codec"] = self.config.comm_codec
        return blob

    @staticmethod
    def _retier_state(state, new_n: int):
        """Re-tile stacked per-worker/per-host optimizer state saved with
        a DIFFERENT tier count: new row i inherits saved row i mod
        saved_n.  Shrinking drops the dead workers' rows; growing seeds a
        rejoined worker from an existing one — both keep the elastic
        continuation deterministic, which is what the bit-for-bit re-form
        contract needs (any fixed rule works; this one is stable under
        repeated shrink/grow)."""
        def fix(x):
            x = np.asarray(x)
            return x[np.arange(new_n) % x.shape[0]]
        return jax.tree_util.tree_map(fix, state)

    def _apply_blob(self, blob: Mapping[str, Any]) -> None:
        saved_strategy = str(np.asarray(blob.get("strategy", "")))
        saved_workers = int(blob["n_workers"]) if "n_workers" in blob else None
        if saved_strategy and saved_strategy != self.config.strategy:
            raise ValueError(
                f"checkpoint strategy {saved_strategy!r} != trainer "
                f"{self.config.strategy!r} (per-worker optimizer state is "
                f"not convertible)")
        elastic = self.config.elastic
        state = blob["state"]
        if saved_workers is not None and saved_workers != self.n_workers:
            if not elastic:
                raise ValueError(
                    f"checkpoint has {saved_workers} workers, mesh has "
                    f"{self.n_workers} (set TrainerConfig.elastic=True to "
                    f"re-form on a different worker set)")
            print(f"elastic: re-forming {saved_workers} -> "
                  f"{self.n_workers} workers (params are the consensus "
                  f"average; stacked optimizer state re-tiled)",
                  file=sys.stderr, flush=True)
            if self.config.strategy == "local_sgd":
                state = self._retier_state(state, self.n_workers)
        if self.config.strategy == "hierarchical" and "n_hosts" in blob:
            saved_hosts = int(blob["n_hosts"])
            if saved_hosts != self.n_hosts:
                if not elastic:
                    raise ValueError(
                        f"checkpoint has {saved_hosts} hosts, mesh has "
                        f"{self.n_hosts} (per-host optimizer state does "
                        f"not re-tile; set TrainerConfig.elastic=True)")
                state = self._retier_state(state, self.n_hosts)
        rep = replicated(self.mesh)
        # full logical params land in this trainer's resident placement:
        # under a shard plan each leaf is sliced per-device by its
        # NamedSharding (put_global's callback), which IS the elastic
        # re-tile — deterministic, arithmetic-free, world-size agnostic
        self.params = put_global_tree(blob["params"], self._params_sharding)
        if self.config.strategy == "sync":
            self.state = put_global_tree(state, rep)
        else:
            self.state = put_global_tree(
                state,
                NamedSharding(self.mesh, self._state_tier()[1]))
        if self.comm_residual is not None:
            n_tier, tier_spec = self._state_tier()
            saved_codec = str(np.asarray(blob.get("comm_codec", "")))
            if "comm_residual" in blob and (
                    saved_codec == self.config.comm_codec):
                res = blob["comm_residual"]
                saved_n = len(jax.tree_util.tree_leaves(res)) and int(
                    jax.tree_util.tree_leaves(res)[0].shape[0])
                if saved_n != n_tier:
                    # same elastic contract as stacked optimizer state:
                    # surviving tier row i inherits saved row i mod saved_n
                    res = self._retier_state(res, n_tier)
                self.comm_residual = put_global_tree(
                    res, NamedSharding(self.mesh, tier_spec))
            else:
                # pre-codec checkpoint (or codec changed): the saved
                # residual is meaningless on this wire format — start
                # error feedback fresh (safe: EF state is an optimization
                # of future rounds, never a correctness input)
                if saved_codec and saved_codec != self.config.comm_codec:
                    print(f"resume: checkpoint residuals are for codec "
                          f"{saved_codec!r}, trainer runs "
                          f"{self.config.comm_codec!r} — resetting error "
                          f"feedback", file=sys.stderr, flush=True)
                self.comm_residual = put_global_tree(
                    jax.tree_util.tree_map(
                        lambda x: np.zeros((n_tier,) + tuple(x.shape),
                                           np.float32), blob["params"]),
                    NamedSharding(self.mesh, tier_spec))
        self.iter = int(blob["iter"])
        if "round" in blob:
            self.round = int(blob["round"])
        if "rng" in blob:
            self._rng = jnp.asarray(blob["rng"])
        if "lr_scale" in blob:
            self.lr_scale = float(np.asarray(blob["lr_scale"]))

    def snapshot(self, path: str) -> None:
        from ..utils.checkpoint import save_checkpoint
        save_checkpoint(path, self._host_blob())

    def restore(self, path: str) -> None:
        from ..utils.checkpoint import load_checkpoint
        self._apply_blob(load_checkpoint(path))

    # -- round-granular checkpoint/resume (the recovery half of the
    #    reference's Spark fault-tolerance story; see TrainerConfig) ------
    def _async_ckpt_enabled(self) -> bool:
        from ..utils.checkpoint import async_checkpoints_enabled
        return self.config.async_checkpoint and async_checkpoints_enabled()

    def save_round_checkpoint(self, directory: str | None = None) -> str | None:
        """Write checkpoint + manifest for the current round.  All
        processes must call (the state fetch is a collective); only
        process 0 touches disk.  Returns the checkpoint path on process 0,
        None elsewhere.

        With async checkpointing on (the default; see
        ``TrainerConfig.async_checkpoint``) the durable write — npz
        serialize, sha256, manifest tmp+rename, prune — runs on a
        background writer thread: this call only starts a non-blocking
        device→host snapshot and enqueues the job, so the next round can
        dispatch immediately.  The fault-injection hooks
        (``crash_in_ckpt``/``corrupt_ckpt``) fire inside the job at the
        same points in the write sequence, and ``flush_checkpoints()``
        is the barrier that restores strict durability where callers
        need it (rollback, preemption, end of run)."""
        with telemetry.span("trainer.ckpt_submit", cat="ckpt",
                            round=self.round):
            return self._save_round_checkpoint_impl(directory)

    def _save_round_checkpoint_impl(
            self, directory: str | None = None) -> str | None:
        from ..utils import faults, knobs
        from ..utils.checkpoint import (
            AsyncCheckpointWriter, CheckpointFencedError, advance_fence,
            check_fence, save_checkpoint, snapshot_tree,
        )
        directory = directory or self.config.checkpoint_dir
        if not directory:
            raise ValueError("no checkpoint directory configured")
        # pin the injector INSTANCE now: the write may run later on the
        # writer thread, and the fault decision belongs to the round that
        # scheduled it, not to whatever the env says at write time
        injector = faults.get_injector()
        t0 = time.perf_counter()
        blob = self._host_blob()
        if jax.process_index() != 0:
            return None
        os.makedirs(directory, exist_ok=True)
        # incarnation fencing: claim the dir with our launch-stamped
        # token (0 = unmanaged, fencing inert).  A zombie writer from a
        # fenced-off incarnation is refused HERE, before any bytes move
        fence_token = knobs.get_int("SPARKNET_FENCE_TOKEN", 0)
        if fence_token:
            advance_fence(directory, fence_token)
        # capture the round-scoped fields NOW — on the async path the
        # trainer's counters will have moved on by write time
        round_now, iter_now = self.round, self.iter
        name = f"ckpt_round_{round_now:08d}.npz"
        path = os.path.join(directory, name)
        manifest = {
            "round": round_now,
            "iter": iter_now,
            "file": name,
            "sha256": None,   # filled in after the npz lands
            "mesh_shape": {k: int(v) for k, v in self.mesh.shape.items()},
            "strategy": self.config.strategy,
            "n_workers": self.n_workers,
            "tau": self.config.tau,
            "data_cursor": self.data_cursor,
        }
        # per-shard checkpoint layout (TrainerConfig.shard_checkpoint):
        # sharded param leaves split into one npz tile per shard, the
        # main npz keeps everything else; the manifest pins every tile's
        # sha256 and the split dims, and appears LAST — so a torn multi-
        # file write is indistinguishable from no checkpoint at all
        plan = self.shard_plan
        shard_ckpt = plan is not None and self.config.shard_checkpoint
        shard_dims = plan.dims_dict() if shard_ckpt else None
        n_shards = plan.n_shards if shard_ckpt else 0
        if plan is not None:
            manifest["shard_plan"] = self.shard_plan_id

        def job() -> None:
            from ..utils.checkpoint import split_sharded_tree
            check_fence(directory, fence_token)
            shard_paths: list[str] = []
            if shard_ckpt:
                common, parts = split_sharded_tree(
                    jax.tree_util.tree_map(np.asarray, blob["params"]),
                    shard_dims, n_shards)
                save_checkpoint(path, {**blob, "params": common})
                shard_entries = []
                for k, part in enumerate(parts):
                    sname = f"ckpt_round_{round_now:08d}.shard{k:02d}.npz"
                    spath = os.path.join(directory, sname)
                    save_checkpoint(spath, part)
                    shard_paths.append(spath)
                    shard_entries.append(
                        {"file": sname, "sha256": _sha256_file(spath)})
                manifest["shards"] = shard_entries
                manifest["shard_dims"] = shard_dims
            else:
                save_checkpoint(path, blob)
            # torn-write chaos window: the npz is durable, the manifest is
            # not yet — crash_in_ckpt kills HERE; resume must treat the
            # orphan npz as if the checkpoint never happened
            injector.on_checkpoint_write(round_now)
            # deterministic chaos hook: scribble the snapshot AFTER it
            # exists (and before/after the manifest — both orders must be
            # survivable; we corrupt after so the manifest's checksum
            # catches it)
            corrupt = injector.corrupt_checkpoint(round_now)
            manifest["sha256"] = _sha256_file(path)
            manifest["fence_token"] = fence_token
            mpath = os.path.join(directory,
                                 f"manifest_{round_now:08d}.json")
            # unique temp name (pid-stamped): a crashed writer's leftover
            # can never collide with — or be half-overwritten into — a
            # live write
            tmp = f"{mpath}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1)
            # rename-time fence: the LAST gate before the checkpoint
            # becomes visible.  A successor may have claimed the dir
            # while our npz was in flight (the zombie-writer window) —
            # refuse, and leave zero new state behind
            try:
                check_fence(directory, fence_token)
            except CheckpointFencedError:
                for p in (tmp, path, *shard_paths):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                raise
            os.replace(tmp, mpath)  # manifest appears atomically, last
            if corrupt:
                print(f"FAULT: corrupt_ckpt scribbling {path}",
                      file=sys.stderr, flush=True)
                faults.scribble(path)
            self._prune_checkpoints(directory)

        if self._async_ckpt_enabled():
            # alias-free device copy + async d2h start; the job's
            # np.asarray then lands on a transfer already in flight
            blob = snapshot_tree(blob)
            if self._ckpt_writer is None:
                self._ckpt_writer = AsyncCheckpointWriter()
            self._ckpt_writer.submit(job)
        else:
            job()
        self.stall_s["checkpoint"] += time.perf_counter() - t0
        return path

    def _prune_checkpoints(self, directory: str) -> None:
        keep = max(int(self.config.checkpoint_keep), 1)
        rounds = sorted(
            (_manifest_round(m) for m in
             glob.glob(os.path.join(directory, "manifest_*.json"))),
            reverse=True)
        for r in rounds[keep:]:
            # the glob sweeps per-shard tiles along with the main npz
            for p in (os.path.join(directory, f"manifest_{r:08d}.json"),
                      *glob.glob(os.path.join(
                          directory, f"ckpt_round_{r:08d}*.npz"))):
                try:
                    os.remove(p)
                except OSError:
                    pass
        # sweep temp droppings from writers killed mid-write (ours are
        # already renamed away by now, so anything *.tmp.* is an orphan)
        for p in glob.glob(os.path.join(directory, "*.tmp.*")):
            try:
                os.remove(p)
            except OSError:
                pass

    def resume_latest(self, directory: str,
                      max_round: int | None = None) -> dict[str, Any] | None:
        """Restore from the newest manifest whose checkpoint validates
        (file sha256 against the manifest, then the in-file content
        checksum).  Corrupt or partial snapshots are skipped with a
        warning, falling back to the next-older manifest; a checkpoint
        from an INCOMPATIBLE config (strategy/mesh mismatch) raises — that
        is a config error, not corruption.  ``max_round`` bounds the
        search (the audit's rollback horizon: newer checkpoints may carry
        an unverified divergence).  Returns the manifest resumed from, or
        None when no valid checkpoint exists."""
        from ..utils import knobs
        from ..utils.checkpoint import (
            CheckpointError, advance_fence, flush_all_writers,
            load_checkpoint,
        )
        # async tier: settle every in-flight background write (this
        # trainer's AND any other live instance writing the same
        # directory) before scanning — the newest manifest must not be
        # sitting in a writer queue when we look for it
        flush_all_writers()
        # claim the dir for OUR incarnation before reading: from here a
        # zombie writer from a fenced-off predecessor refuses at its
        # next fence check instead of clobbering what we resume from
        fence_token = knobs.get_int("SPARKNET_FENCE_TOKEN", 0)
        if fence_token and os.path.isdir(directory):
            advance_fence(directory, fence_token)
        for mpath in sorted(
                glob.glob(os.path.join(directory, "manifest_*.json")),
                key=_manifest_round, reverse=True):
            if max_round is not None and _manifest_round(mpath) > max_round:
                continue
            try:
                with open(mpath) as f:
                    manifest = json.load(f)
                path = os.path.join(directory, manifest["file"])
                got = _sha256_file(path)
                if got != manifest["sha256"]:
                    raise CheckpointError(
                        f"manifest sha256 mismatch (manifest "
                        f"{manifest['sha256'][:12]}…, file {got[:12]}…)",
                        path)
                blob = load_checkpoint(path)
                shard_entries = manifest.get("shards") or []
                if shard_entries:
                    # per-shard layout: verify every tile against the
                    # manifest, then join back to full logical leaves (a
                    # corrupt/missing tile fails the WHOLE checkpoint —
                    # fall through to the next-older manifest)
                    from ..utils.checkpoint import join_sharded_tree
                    parts = []
                    for s in shard_entries:
                        spath = os.path.join(directory, s["file"])
                        sgot = _sha256_file(spath)
                        if sgot != s["sha256"]:
                            raise CheckpointError(
                                f"shard sha256 mismatch (manifest "
                                f"{s['sha256'][:12]}…, file "
                                f"{sgot[:12]}…)", spath)
                        parts.append(load_checkpoint(spath))
                    blob["params"] = join_sharded_tree(
                        blob["params"], parts,
                        manifest.get("shard_dims") or {})
            except (OSError, json.JSONDecodeError, KeyError,
                    CheckpointError) as e:
                print(f"resume: skipping {os.path.basename(mpath)}: {e}",
                      file=sys.stderr, flush=True)
                continue
            mesh_shape = manifest.get("mesh_shape")
            if mesh_shape and mesh_shape != {
                    k: int(v) for k, v in self.mesh.shape.items()}:
                if not self.config.elastic:
                    raise ValueError(
                        f"checkpoint mesh shape {mesh_shape} != trainer "
                        f"mesh {dict(self.mesh.shape)} (set TrainerConfig."
                        f"elastic=True to re-form on a different mesh)")
                print(f"elastic: resuming checkpoint of mesh {mesh_shape} "
                      f"on mesh {dict(self.mesh.shape)}",
                      file=sys.stderr, flush=True)
            self._apply_blob(blob)
            self.round = int(manifest.get("round", self.round))
            self.data_cursor = manifest.get("data_cursor")
            # the restore re-broadcasts params to every replica, so the
            # mesh is consistent by construction from here
            self._last_audit_ok = self.round
            telemetry.get_recorder().record(
                "resume", round=self.round, iter=self.iter,
                file=os.path.basename(manifest["file"]))
            print(f"resume: restored round {self.round} "
                  f"(iter {self.iter}) from "
                  f"{os.path.basename(manifest['file'])}",
                  file=sys.stderr, flush=True)
            return manifest
        return None


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _manifest_round(path: str) -> int:
    stem = os.path.basename(path)
    try:
        return int(stem[len("manifest_"):-len(".json")])
    except ValueError:
        return -1
