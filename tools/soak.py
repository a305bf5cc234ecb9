"""Chaos soak runner: N short supervised training runs under randomized —
but seeded — fault schedules, each checked for exact recovery, with a
JSON verdict.

The per-fault chaos tests (tests/test_resilience.py, marker ``chaos``)
pin one failure mode each; this runner is the composition check the
ROADMAP's production posture needs: pick a fault *schedule* at random
(crash, torn checkpoint write, NaN poison, replica bit flip, straggle ...
each with a random round/rank), run the standard 4-round driver workload
under ResilientRunner supervision, and assert the finished params are
bit-for-bit the fault-free baseline of the same configuration.  The
randomness is fully derived from ``--seed``, so any red verdict is
replayable with the same command line.

Fleet mode (``--fleet N``) is the FLEET-WIDE composition check: N
seeded jobs (each with its own injected crash/straggle/preempt/nan
schedule) run CONCURRENTLY under one ``FleetScheduler``, plus a
late-arriving high-priority job sized to the whole device budget that
forces a fleet-level preemption of everything running.  With
``--fleet-kill`` the scheduler itself is SIGKILLed mid-run and resumed
from its journal.  The verdict requires every job to reach its target
round with final params bit-identical to its fault-free baseline, the
resumed queue to never double-launch, and ZERO orphaned worker
processes at the end.

Pod mode (``--pod N``) is the POD-SCALE burn-in: a simulated N-host rig
(every host a local slice of the device budget, but placed over the
REAL ssh wire format — ``SshTransport`` through a fake-ssh shim, or a
caller-supplied ``SPARKNET_SSH_CMD`` for a live inventory) runs mixed
tenants — two training gangs plus a
replicated serving tenant behind the request router — under a seeded
production-shaped :class:`TrafficModel`: a diurnal paced-load curve, a
flash crowd, corrupt-upload bursts through the data quarantine plane,
and host-kill / host-drain chaos events injected through the
host-control channel mid-leg.  Every episode must end with the training
params bit-identical to the fault-free baseline, zero client-visible
serving errors (typed rejections allowed), the serving tier healed back
to N replicas, and ZERO orphans; any breach writes a postmortem.json +
flight-recorder dump and fails the run.  ``--forever`` keeps scheduling
episodes until one fails (the standing burn-in posture); ``--pod-slice``
is the ~60 s CI shape (one host-kill + one flash crowd).

Net mode (``--net``) is the NETWORK chaos burn-in — the partition-vs-
death legs the pod burn-in grows in PR 17, runnable standalone so CI
can gate on them.  Every leg drives the production ssh wire format
(``SshTransport`` through a local fake-ssh shim) wrapped in a
``ChaosTransport``: (1) *partition-suspend-heal* — sever the beat relay
to a mid-round gang; the lease must mark the host SUSPECT (not kill it,
not burn restart budget), the heal must lift the suspension, and the
finished params must be bit-identical to the fault-free baseline;
(2) *fenced-zombie-ship* — an incarnation checkpoints on one host, its
requeue lands on a checkpoint-less host that pulls the newest valid
round over a link that TEARS the first transfer (the retry resumes the
torn prefix, crc-verified), resumes bit-identically, and the fenced-off
zombie returning from behind the partition is refused at the fence with
a typed error and zero corruption; (3, full runs only) *slow-link
attribution* — a delayed relay is NOT silence: no suspect, no straggler
kill, bit-identical finish.  A full ``--pod`` episode set appends the
same legs, so the pod burn-in exercises them too; ``--net-slice`` keeps
the ~60 s CI shape (legs 1 + 2).

Rollout mode (``--rollout``) is the DEPLOYMENT-PLANE burn-in (PR 18):
three legs over a real registry + router + per-version engines in one
process.  (1) *canary-promote* — a healthy canary at 50 % traffic must
earn promotion through sustained green per-version SLO verdicts over
the request floor, with the old stable drained through the router
fences and pinned-canary answers bit-identical across the pointer
flip; (2) *bad-canary-rollback* — a canary poisoned with the planted
``bad_canary`` fault (its head emits NaNs; the engine fails those
requests TYPED, never serves them) must be auto-rolled back by the
judge within the breach window, with zero errors on stable-pinned
traffic, zero non-finite rows served, the channel pointer reverted,
the canary drained, and a flight dump on disk; (3) *controller-kill-
resume* — a controller killed after ``canary_live`` must resume to
fully-stable (an unjudged canary takes no traffic) and one killed
between ``promote_begin`` and its ``done`` must resume to
fully-promoted, both idempotently with no orphan replicas.

Usage:
  python tools/soak.py --runs 8 --seed 0 --out soak.json
  python tools/soak.py --fleet 4 --fleet-kill --seed 0   # fleet chaos
  python tools/soak.py --pod 3 --seed 0 --out SOAK_pod.json
  python tools/soak.py --pod 3 --forever   # standing burn-in
  python tools/soak.py --net --seed 0 --out SOAK_net.json
  python tools/soak.py --rollout --seed 0 --out SOAK_rollout.json
  SPARKNET_SOAK=1 tools/run_tier1.sh       # the 2-run CI smoke
  SPARKNET_FLEETSOAK=1 tools/run_tier1.sh  # the 2-job fleet smoke
  SPARKNET_PODSOAK=1 tools/run_tier1.sh    # the 3-host pod slice
  SPARKNET_NETSOAK=1 tools/run_tier1.sh    # the 2-leg net slice
  SPARKNET_ROLLSMOKE=1 tools/run_tier1.sh  # the 3-leg rollout smoke

Exit code 0 iff every run recovered exactly; the JSON verdict names each
run's schedule, exit code, attempt count, and whether the params matched.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
DRIVER = os.path.join(REPO, "tests", "multihost_driver.py")


def _schedules(rng):
    """One randomized-but-seeded fault schedule: (name, SPARKNET_FAULT
    value, extra driver flags).  Rounds land in [1, 3) so the 4-round
    workload always has a checkpoint before and rounds after the fault."""
    r = int(rng.integers(1, 3))
    return [
        ("crash", f"crash@round:{r}", []),
        ("crash_in_ckpt", f"crash_in_ckpt@round:{r}", []),
        ("corrupt_ckpt", f"corrupt_ckpt@round:{r}", []),
        ("nan_inject", f"nan_inject@round:{r}", ["--guard"]),
        ("bitflip_params",
         f"bitflip_params@rank:{int(rng.integers(0, 4))}@round:{r}",
         ["--audit-every", "1"]),
        ("straggle+crash",
         f"straggle:0.5s@round:{r},crash@round:{r}@attempt:0", []),
    ]


# telemetry env survives the scrub so a traced soak (SPARKNET_TRACE_DIR
# set, then `tools/obs.py merge` over the dir) yields the one-timeline
# chaos story: fault injection, restarts, rollbacks, recovered rounds,
# correlated across every rank and attempt
_KEEP_ENV = ("SPARKNET_SOAK", "SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
             "SPARKNET_METRICS_SNAP", "SPARKNET_METRICS_SNAP_S",
             "SPARKNET_RUN_ID", "SPARKNET_FLIGHT_EVENTS",
             # a caller-supplied ssh shim (or real ssh wrapper) survives
             # the scrub: the pod/net modes ride the wire it names
             "SPARKNET_SSH_CMD")


def _clean_env():
    os.environ.pop("XLA_FLAGS", None)
    for k in list(os.environ):
        if k.startswith("SPARKNET_") and k not in _KEEP_ENV:
            os.environ.pop(k)


def _run_driver(out, ckpt, flags, fault=None, max_restarts=2,
                local_devices=4, rounds=4):
    from sparknet_tpu.parallel.resilience import ResilientRunner, RestartPolicy
    cmd = [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
           "--local-devices", str(local_devices),
           "--expect-devices", str(local_devices),
           "--rounds", str(rounds)] + flags
    if ckpt:
        cmd += ["--ckpt-dir", ckpt]
    runner = ResilientRunner(
        cmd, nprocs=1, platform="cpu", timeout=300,
        policy=RestartPolicy(max_restarts=max_restarts, backoff_base=0.2),
        extra_env={"SPARKNET_FAULT": fault} if fault else None)
    rc = runner.run()
    return rc, len(runner.attempts)


def _params_match(base_npz, out_npz):
    import numpy as np
    a, b = np.load(base_npz), np.load(out_npz)
    for k in a.files:
        if k.startswith("__"):
            continue
        if not np.array_equal(a[k], b[k]):
            return False, k
    return True, None


# ---------------------------------------------------------------------------
# Net chaos legs (--net; full --pod runs append the same set): partition
# vs death, fenced checkpoint shipping, and slow-link attribution over
# the REAL ssh wire format (SshTransport through a fake-ssh shim) with
# ChaosTransport injecting the network faults mid-episode
# ---------------------------------------------------------------------------

def _fake_ssh_shim(workdir: str) -> str:
    """Write the fake-ssh shim: executes the remote command string
    locally with the exact argv ssh receives (``$4`` is the remote
    string after ``-o BatchMode=yes <host>``), so the wire format, env
    contract, and stdio plumbing are the production path — no sshd.
    ``exec`` keeps the worker pid == the Popen pid (signalling and
    pid-identity checks work unchanged)."""
    path = os.path.join(workdir, "fake-ssh")
    with open(path, "w") as f:
        f.write('#!/bin/bash\nexec bash -c "$4"\n')
    os.chmod(path, 0o755)
    return path


class _TornOnceInjector:
    """Minimal injector for ChaosTransport: tear the first ``torn``
    ship attempts (each leaves a half-written temp the retry must
    resume past), then run clean.  Duck-typed to the faults-injector
    surface the transport consumes."""

    def __init__(self, torn: int = 1):
        self.torn = torn
        self.specs = ()

    def net_specs(self):
        return []

    def drop_ship(self, seq):
        return False

    def torn_ship(self):
        if self.torn > 0:
            self.torn -= 1
            return True
        return False


def _net_knobs(workdir: str) -> None:
    """The net-leg env: the fake-ssh wire (unless the caller supplied a
    real SPARKNET_SSH_CMD), a tight lease so a partition is suspected
    within ~1 s, and small ship chunks so torn-transfer resume moves a
    real whole-chunk prefix."""
    os.environ.setdefault("SPARKNET_SSH_CMD", _fake_ssh_shim(workdir))
    os.environ.setdefault("SPARKNET_LEASE_S", "0.5")
    os.environ.setdefault("SPARKNET_LEASE_MISSES", "2")
    os.environ.setdefault("SPARKNET_SHIP_CHUNK_MB", "0.0625")
    # the ssh-spawned workers inherit this process's env through the
    # shim (the remote branch applies no platform/device carving)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _wire_driver(out, rounds, *, host, ckpt=None, extra_env=None,
                 transport=None, heartbeat_dir=None, round_deadline=None,
                 report=None) -> int:
    """One driver run over the ssh wire: a single rank with 4 virtual
    devices on the fake 'remote' host (SPARKNET_NUM_PROCS=1 — the gang
    shape the pod fleet places).  ``host`` is the host LABEL
    (beat-staging + lease identity); the transport address stays
    127.0.0.1 so the coordinator resolves, exactly the name-vs-addr
    split a HostPool inventory makes."""
    from sparknet_tpu.tools.launch import free_port, launch_ssh
    cmd = [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
           "--local-devices", "4", "--expect-devices", "4",
           "--rounds", str(rounds)]
    if ckpt:
        cmd += ["--ckpt-dir", ckpt]
    return launch_ssh(cmd, hosts=["127.0.0.1"], host_map=[host],
                      coordinator_port=free_port(),
                      cwd=REPO, timeout=300, extra_env=extra_env,
                      transport=transport, heartbeat_dir=heartbeat_dir,
                      round_deadline=round_deadline, report=report)


def _net_partition_episode(workdir, baseline, rounds, *,
                           slow_ms: float | None = None) -> dict:
    """Symmetric partition mid-round (or, with ``slow_ms``, a degraded
    link): sever the beat relay to a healthy mid-round gang.  The lease
    must mark the host SUSPECT and *suspend* its ranks — no straggler
    kill, no restart-budget burn — then lift the suspension on heal,
    and the finished params must be bit-identical to the fault-free
    baseline.  The slow-link variant asserts the opposite discipline:
    delay is NOT silence — beats arrive late but fresh, so no suspect,
    no kill (straggler attribution stays with the per-rank beats)."""
    import threading

    from sparknet_tpu.parallel import health
    from sparknet_tpu.parallel.transport import (ChaosTransport,
                                                 SshTransport)

    name = "slow_link_attribution" if slow_ms else "partition_suspend_heal"
    epdir = os.path.join(workdir, name)
    os.makedirs(epdir, exist_ok=True)
    out = os.path.join(epdir, "out.npz")
    hb = os.path.join(epdir, "hb")
    host = "hostb"
    chaos = ChaosTransport(SshTransport(), injector=_TornOnceInjector(0))
    flap: dict = {}

    def flapper():
        # wait until the first beat has been RELAYED (the monitor has
        # host liveness on file — a partition before any relayed beat
        # is startup grace, not a lease event), then flap the link
        hdir = health.host_dir(hb, host)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if health._read_flat(hdir):
                break
            time.sleep(0.05)
        else:
            flap["error"] = "no beat ever relayed"
            return
        if slow_ms:
            chaos.set_slow(host, slow_ms)
            flap["slow_ms"] = slow_ms
            time.sleep(3.0)
            chaos.set_slow(host, 0)
            flap["restored"] = True
        else:
            chaos.partition(host)
            flap["partitioned"] = True
            time.sleep(4.0)   # 4x the 1 s lease window, > round deadline
            chaos.heal(host)
            flap["healed"] = True

    th = threading.Thread(target=flapper, daemon=True)
    th.start()
    report: dict = {}
    t0 = time.monotonic()
    rc = _wire_driver(out, rounds, host=host, transport=chaos,
                      heartbeat_dir=hb, round_deadline=3.0, report=report)
    th.join(timeout=15.0)
    match, bad = False, None
    if rc == 0:
        match, bad = _params_match(baseline, out)
    row = {"episode": name, "rc": rc, "cause": report.get("cause"),
           "transport": report.get("transport"),
           "suspects": report.get("suspect_hosts"),
           "confirmed_down": report.get("confirmed_down"),
           "stragglers": report.get("stragglers"), "flap": flap,
           "match": match, "elapsed_s": round(time.monotonic() - t0, 1)}
    if bad:
        row["diverged_at"] = bad
    if slow_ms:
        row["ok"] = bool(rc == 0 and match
                         and report.get("cause") == "clean"
                         and not report.get("suspect_hosts")
                         and not report.get("stragglers")
                         and flap.get("restored"))
    else:
        row["ok"] = bool(rc == 0 and match
                         and report.get("cause") == "clean"
                         and report.get("suspect_hosts") == [host]
                         and not report.get("confirmed_down")
                         and not report.get("stragglers")
                         and flap.get("healed"))
    return row


def _net_fenced_ship_episode(workdir, baseline, rounds) -> dict:
    """Fenced, resumable checkpoint shipping end-to-end: incarnation 1
    (fence token 100001) trains the first half of the rounds
    checkpointing on hosta; its requeue lands on checkpoint-less hostb,
    which pulls the newest valid round over a link that TEARS the first
    transfer — the retry must resume the torn whole-chunk prefix and
    land crc-verified.  Incarnation 2 (token 200002) resumes from the
    shipped artifacts and must finish bit-identical to the
    uninterrupted baseline.  Then the fenced-off incarnation returns
    from behind the partition and tries to reclaim the dir: typed
    refusal at the fence, zero state touched."""
    import glob

    from sparknet_tpu.parallel.transport import (
        ChaosTransport, SshTransport, newest_valid_round,
        ship_latest_checkpoint,
    )
    from sparknet_tpu.utils.checkpoint import (
        CheckpointFencedError, advance_fence, read_fence,
    )

    epdir = os.path.join(workdir, "fenced_zombie_ship")
    os.makedirs(epdir, exist_ok=True)
    ck_a = os.path.join(epdir, "ckpt_host_hosta")
    ck_b = os.path.join(epdir, "ckpt_host_hostb")
    out = os.path.join(epdir, "out.npz")
    t0 = time.monotonic()
    row: dict = {"episode": "fenced_zombie_ship"}

    rc1 = _wire_driver(os.path.join(epdir, "half.npz"), rounds // 2,
                       host="hosta", ckpt=ck_a,
                       extra_env={"SPARKNET_FENCE_TOKEN": "100001"})
    row["rc_first"] = rc1

    chaos = ChaosTransport(SshTransport(), injector=_TornOnceInjector())
    try:
        rec = ship_latest_checkpoint(chaos, "hostb", ck_a, ck_b)
    except (OSError, RuntimeError, ValueError) as e:  # ShipError is OSError
        rec = None
        row["ship_error"] = f"{type(e).__name__}: {e}"
    row["ship"] = rec

    rc2 = _wire_driver(out, rounds, host="hostb", ckpt=ck_b,
                       extra_env={"SPARKNET_FENCE_TOKEN": "200002"})
    row["rc_resume"] = rc2
    match, bad = False, None
    if rc2 == 0:
        match, bad = _params_match(baseline, out)
    if bad:
        row["diverged_at"] = bad

    zombie: dict = {"refused": False}
    try:
        advance_fence(ck_b, 100002)
    except CheckpointFencedError as e:
        zombie = {"refused": True, "error": type(e).__name__,
                  "token": e.token, "fence": e.fence}
    torn_left = glob.glob(os.path.join(ck_b, "*.tmp*"))
    row.update(
        zombie=zombie, fence=read_fence(ck_b),
        newest_round=newest_valid_round(ck_b), match=match,
        elapsed_s=round(time.monotonic() - t0, 1),
        ok=bool(rc1 == 0 and rc2 == 0 and match and rec
                and rec.get("round") == rounds // 2
                and rec.get("resumed_bytes", 0) > 0
                and zombie.get("refused")
                and zombie.get("fence") == read_fence(ck_b)
                and not torn_left))
    if torn_left:
        row["torn_leftovers"] = torn_left
    return row


def _net_episodes(workdir, baseline, rounds, *, net_slice: bool) -> list:
    """The net chaos leg set (shared by --net and full --pod runs)."""
    episodes = [
        _net_partition_episode(workdir, baseline, rounds),
        _net_fenced_ship_episode(workdir, baseline, rounds),
    ]
    if not net_slice:
        episodes.append(_net_partition_episode(workdir, baseline, rounds,
                                               slow_ms=250.0))
    for e in episodes:
        print(f"net-soak: {e['episode']} -> "
              f"{'OK' if e['ok'] else 'FAIL'} ({e['elapsed_s']}s)",
              flush=True)
    return episodes


def net_soak(args) -> int:
    from sparknet_tpu.parallel.health import lease_window_s

    _clean_env()
    own_tmp = args.workdir is None
    workdir = args.workdir or tempfile.mkdtemp(prefix="sparknet_net_")
    os.makedirs(workdir, exist_ok=True)
    _net_knobs(workdir)
    t0 = time.monotonic()
    rounds = 8
    base = os.path.join(workdir, "base.npz")
    rc, _ = _run_driver(base, None, [], rounds=rounds)
    if rc != 0:
        raise RuntimeError(f"fault-free baseline failed rc={rc}")
    episodes = _net_episodes(workdir, base, rounds,
                             net_slice=args.net_slice)
    passed = sum(1 for e in episodes if e["ok"])
    report = {"mode": "net", "seed": args.seed,
              "backend": os.environ["JAX_PLATFORMS"],
              "slice": bool(args.net_slice), "rounds": rounds,
              "lease_window_s": lease_window_s(), "episodes": episodes,
              "passed": passed, "failed": len(episodes) - passed,
              "elapsed_s": round(time.monotonic() - t0, 1),
              "ok": bool(episodes) and passed == len(episodes)}
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"net-soak: verdict written to {args.out} "
              f"({passed}/{len(episodes)} episode(s) passed)")
    else:
        print(text)
    if own_tmp and report["ok"]:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    elif not report["ok"]:
        print(f"net-soak: scratch kept at {workdir} for post-mortem",
              file=sys.stderr)
    return 0 if report["ok"] else 1


# ---------------------------------------------------------------------------
# Fleet chaos soak (--fleet N): concurrent jobs, one scheduler, injected
# crash/straggle/preempt/nan schedules + fleet-level priority preemption
# (+ optional scheduler kill/resume), verified bit-identical and orphan-free
# ---------------------------------------------------------------------------

def _fleet_schedules(rng, i):
    """Seeded fault schedule for fleet job ``i``.  The first FOUR jobs
    are pinned to the crash / preempt / nan / straggle families in that
    order, so the 2-job CI smoke (SPARKNET_FLEETSOAK=1) always covers
    the preempt/resume/crash triangle and any >= 4-job acceptance run
    covers all four; later jobs draw seeded from the full menu (the
    round numbers stay seeded for every job)."""
    r = int(rng.integers(1, 3))
    menu = [
        ("crash", f"crash@round:{r}", False),
        ("preempt", f"preempt@round:{r}", False),
        ("nan_inject", f"nan_inject@round:{r}", True),
        ("straggle+crash",
         f"straggle:0.5s@round:{r},crash@round:{r}@attempt:0", False),
        ("crash_in_ckpt", f"crash_in_ckpt@round:{r}", False),
        ("corrupt_ckpt", f"corrupt_ckpt@round:{r}", False),
    ]
    if i < 4:
        return menu[i]
    return menu[int(rng.integers(0, len(menu)))]


def _journal_pids(workdir):
    """Every worker pid the fleet journal ever recorded."""
    from sparknet_tpu.parallel.fleet import FleetJournal
    pids = {}
    path = os.path.join(workdir, "fleet_journal.jsonl")
    for ev in FleetJournal.read(path):
        if ev.get("ev") == "pids":
            pids.setdefault(ev["job"], set()).update(ev.get("pids", []))
    return pids


def fleet_soak(args) -> int:
    import numpy as np

    from sparknet_tpu.parallel.fleet import (
        FleetScheduler, JobSpec, _pid_is_fleet_job, format_status,
    )

    _clean_env()
    rng = np.random.default_rng(args.seed)
    own_tmp = args.workdir is None
    workdir = args.workdir or tempfile.mkdtemp(prefix="sparknet_fleet_")
    os.makedirs(workdir, exist_ok=True)
    fleet_dir = os.path.join(workdir, "fleet")
    devices = args.fleet_devices
    t0 = time.monotonic()

    # -- job set: N faulted jobs + the late high-priority preemptor ------
    specs, meta = [], {}
    for i in range(args.fleet):
        name, fault, guard = _fleet_schedules(rng, i)
        spec = JobSpec(
            name=f"job{i}", tenant=("acme", "beta")[i % 2],
            priority=i % 2, world=4, rounds=4, guard=guard, fault=fault,
            max_restarts=2, timeout_s=300.0)
        specs.append(spec)
        meta[spec.name] = {"schedule": name, "fault": fault}
    preemptor = JobSpec(
        name="preemptor", tenant="ops", priority=99, world=devices,
        rounds=3, not_before_s=args.fleet_preempt_after,
        preemptible=False, timeout_s=300.0)
    specs.append(preemptor)
    meta[preemptor.name] = {"schedule": "clean-high-priority", "fault": None}

    # -- fault-free baselines, one per distinct job shape ----------------
    baselines: dict[tuple, str] = {}

    def baseline_for(spec):
        key = (spec.world, spec.rounds, spec.guard)
        if key not in baselines:
            path = os.path.join(workdir, f"base_{len(baselines)}.npz")
            ck = os.path.join(workdir, f"base_ck_{len(baselines)}")
            flags = ["--guard"] if spec.guard else []
            rc, _ = _run_driver(path, ck if flags else None, flags,
                                local_devices=spec.world,
                                rounds=spec.rounds)
            if rc != 0:
                raise RuntimeError(f"fault-free baseline failed rc={rc} "
                                   f"(shape={key})")
            baselines[key] = path
        return baselines[key]

    for spec in specs:
        baseline_for(spec)

    # -- run the fleet (optionally killing the scheduler mid-run) --------
    killed = False
    if args.fleet_kill:
        jobs_json = os.path.join(workdir, "jobs.json")
        with open(jobs_json, "w") as f:
            json.dump([s.to_json() for s in specs], f)
        import signal
        import subprocess
        proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools", "fleet.py"),
             "--workdir", fleet_dir, "--devices", str(devices),
             "--jobs", jobs_json, "--status-every", "0"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        time.sleep(args.fleet_kill_after)
        proc.send_signal(signal.SIGKILL)   # no grace: the worst case
        proc.wait()
        killed = True
        print(f"fleet-soak: scheduler SIGKILLed after "
              f"{args.fleet_kill_after}s; resuming from the journal",
              flush=True)
        fleet = FleetScheduler.resume(fleet_dir)
    else:
        fleet = FleetScheduler(fleet_dir, devices,
                               tenants={"acme": devices, "beta": devices})
        for spec in specs:
            fleet.submit(spec)
    rc = fleet.run(tick_s=0.1, timeout_s=args.fleet_timeout)

    # -- verdict ---------------------------------------------------------
    jobs = []
    for spec in specs:
        job = fleet.jobs[spec.name]
        verdict = dict(meta[spec.name], job=spec.name, state=job.state,
                       episodes=job.episodes, attempts=job.restarts_used,
                       preempts=job.preempt_count)
        if job.state == "COMPLETED":
            match, bad = _params_match(baseline_for(spec), job.out_path)
            verdict.update(match=match,
                           **({"diverged_at": bad} if not match else {}))
        else:
            verdict.update(match=False)
        verdict["ok"] = job.state == "COMPLETED" and verdict["match"]
        jobs.append(verdict)

    # zero-orphans: every pid the journal ever recorded must be dead (or
    # provably not ours anymore)
    orphans = {name: sorted(p for p in pids
                            if _pid_is_fleet_job(p, name))
               for name, pids in _journal_pids(fleet_dir).items()}
    orphans = {k: v for k, v in orphans.items() if v}
    preempt_seen = any(j["preempts"] > 0 for j in jobs)

    passed = sum(1 for j in jobs if j["ok"])
    report = {"mode": "fleet", "seed": args.seed, "devices": devices,
              "killed_scheduler": killed, "jobs": jobs,
              "passed": passed, "failed": len(jobs) - passed,
              "orphans": orphans, "preemption_exercised": preempt_seen,
              "elapsed_s": round(time.monotonic() - t0, 1),
              "ok": (rc == 0 and passed == len(jobs) and not orphans
                     and preempt_seen)}
    print(format_status(fleet.status()), flush=True)
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"fleet-soak: verdict written to {args.out} "
              f"({passed}/{len(jobs)} passed"
              f"{', orphans!' if orphans else ''})")
    else:
        print(text)
    if own_tmp and report["ok"]:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    elif not report["ok"]:
        print(f"fleet-soak: scratch kept at {workdir} for post-mortem",
              file=sys.stderr)
    return 0 if report["ok"] else 1


# ---------------------------------------------------------------------------
# Pod burn-in (--pod N): simulated multi-host fleet under production-shaped
# traffic — diurnal paced load, a flash crowd, corrupt-upload bursts through
# the quarantine plane, host-kill / host-drain chaos — every recovery
# bit-identical, every leg error-free, zero orphans
# ---------------------------------------------------------------------------

class TrafficModel:
    """Seeded synthesized production traffic for the pod burn-in.

    One instance is one "day": ``next_qps()`` walks a diurnal sine curve
    (seeded phase, so two runs with the same ``--seed`` replay the same
    day), ``flash_qps()`` is the flash-crowd step over the base, and
    ``corrupt_burst(budget)`` sizes the corrupt-upload bursts the
    quarantine plane must absorb (one within budget) and reject (one
    past it).  All magnitudes come from the SPARKNET_SOAK_* knobs unless
    the CLI overrides them."""

    def __init__(self, rng, *, base_qps=None, flash_x=None, leg_s=None,
                 day_legs: int = 12):
        from sparknet_tpu.utils import knobs
        self.rng = rng
        self.base_qps = (base_qps if base_qps is not None
                         else knobs.get_float("SPARKNET_SOAK_QPS", 4.0))
        self.flash_x = (flash_x if flash_x is not None
                        else knobs.get_float("SPARKNET_SOAK_FLASH_X", 2.5))
        self.leg_s = (leg_s if leg_s is not None
                      else knobs.get_float("SPARKNET_SOAK_LEG_S", 4.0))
        self.day_legs = day_legs
        self.phase = float(rng.uniform(0.0, 1.0))
        self.step = 0

    def next_qps(self) -> float:
        import math
        f = self.step / self.day_legs + self.phase
        self.step += 1
        qps = self.base_qps * (0.7 + 0.3 * math.sin(2 * math.pi * f))
        return round(max(qps, 0.5), 3)

    def flash_qps(self) -> float:
        return round(max(self.base_qps * self.flash_x, 1.0), 3)

    def corrupt_burst(self, budget: int) -> tuple[int, int]:
        """(records in the within-budget burst, records attempted in the
        past-budget flood)."""
        within = int(self.rng.integers(2, max(budget, 3)))
        return min(within, budget), budget + 2


def _corrupt_upload_burst(tm: "TrafficModel") -> dict:
    """One corrupt-upload episode through the data quarantine plane: a
    within-budget burst must be absorbed as typed skip accounting
    (attributed per source), and the first record past the budget must
    raise QuarantineExceeded carrying the report — silent swallowing or
    an untyped crash are both red."""
    from sparknet_tpu.data.integrity import (
        DataCorruptionError, Quarantine, QuarantineExceeded,
        QuarantinePolicy,
    )
    epoch = 200
    q = Quarantine(QuarantinePolicy(max_fraction=0.05), epoch_size=epoch,
                   source="pod-upload")
    within, flood = tm.corrupt_burst(q.budget)
    for i in range(within):
        q.admit(DataCorruptionError(
            "synthetic upload corruption", source="pod-upload",
            key=f"upload/{i}", offset=int(tm.rng.integers(0, 1 << 20))))
    absorbed = q.report()
    typed_report = None
    try:
        for i in range(flood):
            q.admit(DataCorruptionError(
                "synthetic upload corruption", source="pod-upload-flood",
                key=f"flood/{i}"))
    except QuarantineExceeded as e:
        typed_report = e.report
    return {"budget": q.budget, "absorbed": within,
            "typed_overflow": typed_report is not None,
            "by_source": absorbed["by_source"],
            "ok": bool(typed_report is not None
                       and absorbed["epoch_bad"] == within)}


def _wait_for(cond, timeout_s: float, tick_s: float = 0.15) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick_s)
    return bool(cond())


def _pod_episode(args, rng, workdir, baseline, ep: int,
                 rounds: int) -> dict:
    """One burn-in episode on a fresh simulated pod: schedule the mixed
    tenants, replay the traffic model (serveload's paced closed loops),
    fire the chaos events mid-leg through the host-control channel, and
    return the verdict row.  ``--pod-slice`` keeps the CI shape (one
    host-kill + one flash crowd); the full episode adds a host drain
    mid-training and a serving-host loss."""
    import numpy as np

    from sparknet_tpu.parallel.autoscale import (
        Autoscaler, AutoscaleConfig, fleet_stats_fn,
    )
    from sparknet_tpu.parallel.fleet import (
        COMPLETED, TERMINAL, FleetScheduler, HostPool, JobSpec,
        _pid_is_fleet_job, format_status, request_mark_host,
    )
    from sparknet_tpu.parallel.router import RouterConfig, ServingFleet
    from sparknet_tpu.parallel.serving import (
        ModelHouse, ServeConfig, solo_references,
    )
    from sparknet_tpu.utils.telemetry import get_recorder

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serveload

    t0 = time.monotonic()
    full = not args.pod_slice
    model, replicas, world = "lenet", 2, 3
    tm = TrafficModel(rng, base_qps=args.pod_qps,
                      flash_x=args.pod_flash_x, leg_s=args.pod_leg_s)
    rec = get_recorder()
    fleet_dir = os.path.join(workdir, f"ep{ep}")
    pool = HostPool.parse(",".join(f"h{i}={args.pod_devices}"
                                   for i in range(args.pod)))

    cfg = ServeConfig(batch_shapes=(1, 4, 8), seed=0)
    serve_env = {
        "SPARKNET_SERVE_SHAPES": ",".join(str(s)
                                          for s in cfg.batch_shapes),
        "SPARKNET_SERVE_MAX_DELAY_MS": str(cfg.max_delay_ms),
        "SPARKNET_SERVE_QUEUE": str(cfg.max_queue),
        "SPARKNET_SERVE_DTYPE": cfg.dtype,
    }
    sched = FleetScheduler(fleet_dir, None, hosts=pool,
                           preempt_grace_s=15.0)
    fleet = ServingFleet(fleet_dir, pool.total_devices, scheduler=sched,
                         serve_env=serve_env,
                         router_cfg=RouterConfig(spill_depth=8),
                         replica_timeout_s=20.0)
    scaler = Autoscaler(
        fleet_stats_fn(fleet), fleet.scale_up, fleet.scale_down,
        cfg=AutoscaleConfig(min_replicas=replicas,
                            max_replicas=replicas + 1, up_queue=64.0,
                            cooldown_s=2.0, down_idle_s=3600.0,
                            sample_every_s=0.25),
        state_path=os.path.join(fleet_dir, "autoscale.json"))

    trains = [JobSpec(name=f"train{i}", tenant=("acme", "beta")[i],
                      world=world, rounds=rounds, global_batch=4 * world,
                      max_restarts=3, timeout_s=300.0)
              for i in range(2)]
    if not full:
        # slice: trainings warm up alongside the replicas so the single
        # host-kill lands mid-round within the ~60s budget
        for spec in trains:
            sched.submit(spec)

    report: dict = {"episode": ep, "hosts": pool.to_json(),
                    "base_qps": tm.base_qps, "leg_s": tm.leg_s,
                    "slice": not full}
    legs: list[dict] = []
    chaos: dict = {}

    def mark(host, state):
        rec.record("pod_soak_chaos", host=host, state=state, episode=ep)
        request_mark_host(fleet_dir, host, state, by=f"pod-soak-ep{ep}")
        return {"host": host, "state": state}

    def serve_hosts():
        return {h for j in sched.jobs.values()
                if j.spec.kind == "serve" and j.state not in TERMINAL
                for h in j.hosts}

    def leg(name, qps, midpoint=None, clients=4):
        rep, mid = serveload._paced_with_midpoint(
            fleet.router, model, inputs, refs, clients=clients, window=1,
            seconds=tm.leg_s, qps=qps, midpoint=midpoint or (lambda: None),
            tenant="podsoak")
        row = {"leg": name, "offered_qps": qps,
               "achieved_qps": rep.get("achieved_qps"),
               "errors": rep.get("errors"),
               "mismatches": rep.get("exact_mismatches"),
               "rejected": rep.get("rejected"),
               "p99_ms": rep.get("p99_ms")}
        if mid.get("error"):
            row["chaos_error"] = mid["error"]
        elif mid.get("value") is not None:
            row["chaos"] = mid["value"]
        legs.append(row)
        print(f"pod-soak: ep{ep} leg {name}: offered {qps} qps -> "
              f"{row['achieved_qps']} qps, errors {row['errors']}, "
              f"mismatches {row['mismatches']}"
              + (f", chaos {row.get('chaos')}" if midpoint else ""),
              flush=True)
        return row

    healed = drained = True
    try:
        # in-process references: replicas share config + seed, so the
        # pod must answer bit-identically to this solo house
        lm = ModelHouse(cfg).load(model)
        inputs = [rng.normal(size=lm.in_shape).astype(np.float32)
                  for _ in range(12)]
        refs = solo_references(lm, inputs)

        fleet.ensure(model, replicas)
        fleet.attach_autoscaler(scaler)
        fleet.run_background()
        fleet.wait_ready(model, replicas, timeout_s=240.0)
        if full:
            # full episode: the trainings start only now, so the drain
            # leg below still catches a gang mid-round
            for spec in trains:
                sched.submit(spec)
        if not _wait_for(lambda: all(sched.jobs[s.name].hosts
                                     for s in trains), 60.0):
            raise RuntimeError("training gangs never placed: "
                               + format_status(sched.status()))

        # -- chaos 1: kill a training host mid-leg ---------------------
        sh = serve_hosts()
        kill_victim = next(
            (h for s in trains for h in sched.jobs[s.name].hosts
             if h not in sh),
            sched.jobs[trains[0].name].hosts[0])
        chaos["host_kill"] = kill_victim
        leg("diurnal_kill", tm.next_qps(),
            midpoint=lambda: mark(kill_victim, "lost"))

        # -- corrupt-upload burst through the quarantine plane ---------
        report["quarantine"] = _corrupt_upload_burst(tm)

        # -- flash crowd; the lost host recovers mid-crowd -------------
        leg("flash_crowd", tm.flash_qps(), clients=6,
            midpoint=lambda: mark(kill_victim, "live"))

        if full:
            # -- chaos 2: drain a host carrying a live training gang ---
            sh = serve_hosts()
            cands = [h for s in trains
                     if sched.jobs[s.name].state not in TERMINAL
                     for h in sched.jobs[s.name].hosts]
            cands = [h for h in cands if h not in sh or len(sh) > 1]
            if cands:
                drain_victim = cands[0]
                chaos["host_drain"] = drain_victim
                leg("diurnal_drain", tm.next_qps(),
                    midpoint=lambda: mark(drain_victim, "draining"))
                drained = _wait_for(
                    lambda: not sched.jobs_on_host(drain_victim), 120.0)
                mark(drain_victim, "live")
            else:
                # the full acceptance must exercise the drain path; a
                # missed window (trainings already done) is red
                chaos["host_drain"] = None
                drained = False

        # -- trainings must finish (kills/drains notwithstanding) ------
        if not _wait_for(lambda: all(sched.jobs[s.name].state in TERMINAL
                                     for s in trains), args.pod_timeout):
            raise RuntimeError("trainings not terminal within "
                               f"{args.pod_timeout}s: "
                               + format_status(sched.status()))

        if full:
            # -- chaos 3: serving host loss = bulk replica death -------
            sh = sorted(serve_hosts())
            if len(sh) >= 2:
                victim2 = sh[0]
                chaos["serve_host_loss"] = victim2
                leg("diurnal_serve_loss", tm.next_qps(),
                    midpoint=lambda: mark(victim2, "lost"))
                try:
                    fleet.wait_ready(model, replicas, timeout_s=180.0)
                except TimeoutError:
                    healed = False
                mark(victim2, "live")
            else:
                chaos["serve_host_loss"] = None
                healed = False   # replicas were never spread: red

        # -- final heal check ------------------------------------------
        try:
            fleet.wait_ready(model, replicas, timeout_s=120.0)
        except TimeoutError:
            healed = False
    finally:
        fleet.stop(grace_s=5.0)

    # -- verdict ---------------------------------------------------------
    tverd = []
    for s in trains:
        job = sched.jobs[s.name]
        v = {"job": s.name, "state": job.state, "episodes": job.episodes,
             "preempts": job.preempt_count}
        if job.state == COMPLETED:
            m, bad = _params_match(baseline, job.out_path)
            v.update(match=m, **({"diverged_at": bad} if not m else {}))
        else:
            v["match"] = False
        v["ok"] = job.state == COMPLETED and v["match"]
        tverd.append(v)

    orphans = {name: sorted(p for p in pids
                            if _pid_is_fleet_job(p, name))
               for name, pids in _journal_pids(fleet_dir).items()}
    orphans = {k: v for k, v in orphans.items() if v}
    slo_ok = all(l["errors"] == 0 and l["mismatches"] == 0 for l in legs)
    perf_ok = all((l["achieved_qps"] or 0) > 0 for l in legs)
    chaos_errs = [l["chaos_error"] for l in legs if "chaos_error" in l]

    report.update(
        chaos=chaos, legs=legs, trainings=tverd, healed=healed,
        drained=drained, slo_ok=slo_ok, perf_band_ok=perf_ok,
        orphans=orphans, elapsed_s=round(time.monotonic() - t0, 1),
        ok=(all(v["ok"] for v in tverd) and slo_ok and perf_ok
            and healed and drained and not orphans and not chaos_errs
            and report.get("quarantine", {}).get("ok", False)))
    if chaos_errs:
        report["chaos_errors"] = chaos_errs

    if not report["ok"]:
        # artifact-producing failure: black box + postmortem in the
        # episode dir (which pod_soak then keeps)
        rec.dump(f"pod-soak-ep{ep}", directory=fleet_dir)
        try:
            with open(os.path.join(fleet_dir, "postmortem.json"),
                      "w") as f:
                json.dump({"report": report,
                           "status": sched.status()}, f, indent=1,
                          default=str)
        except OSError:
            pass
    return report


def pod_soak(args) -> int:
    import numpy as np

    _clean_env()
    rng = np.random.default_rng(args.seed)
    own_tmp = args.workdir is None
    workdir = args.workdir or tempfile.mkdtemp(prefix="sparknet_pod_")
    os.makedirs(workdir, exist_ok=True)
    # the pod's host lifecycle rides the REAL ssh wire format: with
    # SPARKNET_SSH_CMD set, every placement/exec/ship goes through
    # SshTransport (the fake-ssh shim by default; a live inventory
    # supplies its own wrapper and keeps it through _KEEP_ENV)
    _net_knobs(workdir)
    t0 = time.monotonic()

    # one fault-free baseline for the training shape all tenants share
    # (world=3 gangs; batch 12 keeps the shard math exact; the full
    # episode trains longer so the drain leg catches a gang mid-round)
    rounds = 4 if args.pod_slice else 12
    base = os.path.join(workdir, "base.npz")
    rc, _ = _run_driver(base, None, ["--global-batch", "12"],
                        local_devices=3, rounds=rounds)
    if rc != 0:
        raise RuntimeError(f"fault-free baseline failed rc={rc}")

    episodes = []
    ok = True
    try:
        ep = 0
        while True:
            episodes.append(_pod_episode(args, rng, workdir, base, ep,
                                         rounds))
            ok = episodes[-1]["ok"]
            print(f"pod-soak: episode {ep} -> "
                  f"{'OK' if ok else 'FAIL'} "
                  f"({episodes[-1]['elapsed_s']}s)", flush=True)
            ep += 1
            if not ok or not args.forever:
                break
    except KeyboardInterrupt:
        print("pod-soak: interrupted — closing out the verdict",
              file=sys.stderr, flush=True)

    if not args.pod_slice and ok and not args.forever:
        # the full burn-in grows the network chaos legs (partition
        # suspend/heal, fenced zombie shipping, slow-link attribution)
        # on its own fault-free baseline shape
        net_base = os.path.join(workdir, "net_base.npz")
        rc, _ = _run_driver(net_base, None, [], rounds=8)
        if rc != 0:
            raise RuntimeError(f"net-leg baseline failed rc={rc}")
        episodes.extend(_net_episodes(os.path.join(workdir, "net"),
                                      net_base, 8, net_slice=False))

    passed = sum(1 for e in episodes if e["ok"])
    report = {"mode": "pod", "seed": args.seed, "pod_hosts": args.pod,
              "backend": os.environ["JAX_PLATFORMS"],
              "devices_per_host": args.pod_devices,
              "transport": "ssh",
              "slice": bool(args.pod_slice), "episodes": episodes,
              "passed": passed, "failed": len(episodes) - passed,
              "elapsed_s": round(time.monotonic() - t0, 1),
              "ok": bool(episodes) and passed == len(episodes)}
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"pod-soak: verdict written to {args.out} "
              f"({passed}/{len(episodes)} episode(s) passed)")
    else:
        print(text)
    if own_tmp and report["ok"]:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    elif not report["ok"]:
        print(f"pod-soak: scratch kept at {workdir} for post-mortem "
              "(postmortem.json + flight dump in the failing episode "
              "dir)", file=sys.stderr)
    return 0 if report["ok"] else 1


# ---------------------------------------------------------------------------
# Rollout chaos legs (--rollout): the deployment plane end to end — a
# healthy canary must promote, a poisoned canary must auto-roll back
# with zero client-visible damage on stable traffic, and a controller
# killed mid-rollout must resume to a consistent fleet.


class _RolloutFleet:
    """In-process serving tier keyed by VERSIONED name: the rollout
    controller's ensure/retire/verdict wiring.  Every versioned name
    gets its own house + engine (whose built-in per-version SLOMonitor
    is the judge's verdict source) behind one real Router — the same
    shape ``tools/serve.py --fleet`` runs, minus the HTTP hop."""

    def __init__(self, registry, cfg, router):
        self.registry = registry
        self.cfg = cfg
        self.router = router
        self.live: dict = {}      # versioned name -> (rid, engine)

    def ensure(self, name: str) -> None:
        if name in self.live:
            return
        from sparknet_tpu.parallel.router import InProcessReplica
        from sparknet_tpu.parallel.serving import InferenceEngine, ModelHouse
        model, _, version = name.partition("@")
        house = ModelHouse(self.cfg)
        house.load_version(model, version, registry=self.registry)
        eng = InferenceEngine(house, self.cfg)
        rid = f"r-{version}"
        self.router.add_replica(rid, InProcessReplica(rid, eng))
        self.live[name] = (rid, eng)

    def retire(self, name: str) -> None:
        ent = self.live.pop(name, None)
        if ent is None:
            return
        rid, eng = ent
        self.router.drain(rid, timeout_s=30.0)
        eng.stop()

    def verdict(self, name: str):
        ent = self.live.get(name)
        if ent is None:
            return None
        return ent[1].slo.evaluate()

    def close(self) -> None:
        for name in list(self.live):
            self.retire(name)


def _rollout_promote_episode(ctl, fleet, reg, router, inputs, refs,
                             v1, v2) -> dict:
    """A HEALTHY canary must earn promotion: sustained green verdicts
    over the request floor, old stable drained, pinned-canary answers
    bit-identical across the pointer flip."""
    import numpy as np
    from sparknet_tpu.parallel.registry import versioned
    from sparknet_tpu.parallel.serving import ServingError

    t0 = time.monotonic()
    reg.set_channels("lenet", stable=v1)
    fleet.ensure(versioned("lenet", v1))
    ctl.start_canary("lenet", v2, weight=0.5)
    pins = inputs[:4]
    pre = [router.classify("lenet", x, version=v2, timeout=60).probs
           for x in pins]

    errors = mism = iters = 0
    decision = "canary"
    deadline = time.monotonic() + 120.0
    while decision == "canary" and time.monotonic() < deadline:
        for i, x in enumerate(inputs):
            try:
                res = router.classify("lenet", x, tenant="rollsoak",
                                      timeout=60)
            except ServingError:
                errors += 1      # untyped errors crash the episode: bug
            else:
                if not np.array_equal(res.probs, refs[res.padded_to][i]):
                    mism += 1
        iters += 1
        decision = ctl.judge("lenet")
        time.sleep(0.05)

    promoted = decision == "promote"
    if promoted:
        ctl.promote("lenet")
    post = [router.classify("lenet", x, version=v2, timeout=60).probs
            for x in pins]
    ch = reg.channels("lenet")
    pin_ok = all(np.array_equal(a, b) for a, b in zip(pre, post))
    old_gone = (versioned("lenet", v1) not in fleet.live
                and f"r-{v1}" not in router.replica_ids())
    return {"episode": "canary_promote", "stable": v1, "canary": v2,
            "promoted": promoted, "iters": iters,
            "stable_errors": errors, "mismatches": mism,
            "pin_identical": pin_ok, "old_stable_drained": old_gone,
            "channels": ch,
            "elapsed_s": round(time.monotonic() - t0, 1),
            "ok": bool(promoted and errors == 0 and mism == 0
                       and pin_ok and old_gone
                       and ch["stable"] == v2 and ch["canary"] is None)}


def _rollout_bad_canary_episode(ctl, fleet, reg, router, inputs, refs,
                                stable, trace_dir) -> dict:
    """A POISONED canary (planted ``bad_canary`` fault: the model head
    emits NaNs) must be caught by the judge and auto-rolled back: zero
    errors on stable-pinned traffic, zero non-finite rows ever served,
    channel reverted, canary drained, flight dump on disk, and the
    journal resuming as consistent with pinned answers bit-identical
    across the recovery."""
    import glob

    import numpy as np

    from sparknet_tpu.parallel.registry import versioned
    from sparknet_tpu.parallel.rollout import RolloutController
    from sparknet_tpu.parallel.serving import ServingError

    t0 = time.monotonic()
    v3 = reg.publish("lenet", notes="rollout soak v3 (to be poisoned)")
    pins = inputs[:4]
    pre = [router.classify("lenet", x, version=stable, timeout=60).probs
           for x in pins]
    dumps_before = len(glob.glob(os.path.join(
        trace_dir, "*rollout_rollback*")))

    # the canary is born bad: every batch of the poisoned version
    # produces NaNs (the engine must fail them TYPED, never serve them)
    os.environ["SPARKNET_FAULT"] = f"bad_canary:{v3}"
    stable_errors = typed = untyped = served_bad = mism = 0
    try:
        ctl.start_canary("lenet", v3, weight=0.5)
        t_live = time.monotonic()
        decision = "canary"
        deadline = time.monotonic() + 120.0
        while decision == "canary" and time.monotonic() < deadline:
            for i, x in enumerate(inputs):
                try:
                    res = router.classify("lenet", x, tenant="rollsoak",
                                          timeout=60)
                except ServingError:
                    typed += 1       # the canary failing loudly is fine
                # measuring untyped leakage IS this episode's job: the
                # soak asserts this counter stays zero
                except Exception:  # sparklint: disable=CD003
                    untyped += 1     # anything untyped is not
                else:
                    if not np.isfinite(res.probs).all():
                        served_bad += 1   # NaN reached a client: red
                    elif not np.array_equal(res.probs,
                                            refs[res.padded_to][i]):
                        mism += 1
            # stable-PINNED traffic must never feel the canary at all
            try:
                router.classify("lenet", inputs[0], version=stable,
                                timeout=60)
            except ServingError:
                stable_errors += 1   # untyped here crashes the episode
            decision = ctl.judge("lenet")
            time.sleep(0.05)
        rolled_back = decision == "rollback"
        detect_s = round(time.monotonic() - t_live, 2)
        if rolled_back:
            ctl.rollback("lenet", reason="sustained SLO breach "
                                         "(bad canary)")
    finally:
        os.environ.pop("SPARKNET_FAULT", None)

    ch = reg.channels("lenet")
    ro = router.rollout("lenet")
    drained = (versioned("lenet", v3) not in fleet.live
               and f"r-{v3}" not in router.replica_ids())
    dumped = len(glob.glob(os.path.join(
        trace_dir, "*rollout_rollback*"))) > dumps_before
    post = [router.classify("lenet", x, version=stable, timeout=60).probs
            for x in pins]
    pin_ok = all(np.array_equal(a, b) for a, b in zip(pre, post))
    # a fresh controller over the same journal must find nothing to fix
    resumed = RolloutController(
        reg, ctl.workdir, ensure=fleet.ensure, retire=fleet.retire,
        verdict=fleet.verdict, router=router, cfg=ctl.cfg).resume()
    post2 = [router.classify("lenet", x, version=stable,
                             timeout=60).probs for x in pins]
    pin_ok = pin_ok and all(np.array_equal(a, b)
                            for a, b in zip(pre, post2))
    return {"episode": "bad_canary_rollback", "stable": stable,
            "canary": v3, "rolled_back": rolled_back,
            "detect_s": detect_s, "stable_errors": stable_errors,
            "canary_typed_failures": typed, "untyped_errors": untyped,
            "served_bad": served_bad, "mismatches": mism,
            "drained": drained, "flight_dump": dumped,
            "pin_identical": pin_ok,
            "resume": resumed.get("lenet", "consistent"),
            "channels": ch,
            "elapsed_s": round(time.monotonic() - t0, 1),
            "ok": bool(rolled_back and stable_errors == 0 and typed > 0
                       and untyped == 0 and served_bad == 0
                       and mism == 0 and drained and dumped and pin_ok
                       and ch["stable"] == stable
                       and ch["canary"] is None and ch["weight"] == 0.0
                       and ro is not None and ro.canary is None
                       and resumed.get("lenet",
                                       "consistent") == "consistent")}


def _rollout_resume_episode(workdir) -> dict:
    """Kill the controller at BOTH dangerous points — after the canary
    went live (before any judgment) and between ``promote_begin`` and
    its ``done`` — and prove resume lands on exactly one of {fully
    stable, fully promoted}, idempotently, with no orphan replicas."""
    from sparknet_tpu.parallel.registry import ModelRegistry, versioned
    from sparknet_tpu.parallel.rollout import RolloutConfig, RolloutController

    t0 = time.monotonic()
    cfg = RolloutConfig(fraction=0.25, judge_s=0.5, poll_s=0.05,
                        min_requests=1, breach_polls=1)

    class _Killed(Exception):
        pass

    def rig(tag):
        d = os.path.join(workdir, tag)
        reg = ModelRegistry(os.path.join(d, "registry"))
        up: set = set()
        retired: list = []

        def retire(name):
            retired.append(name)
            up.discard(name)

        a = reg.publish("demo", notes="a")
        b = reg.publish("demo", notes="b")
        reg.set_channels("demo", stable=a)
        kw = dict(ensure=up.add, retire=retire,
                  verdict=lambda name: None, cfg=cfg)
        return d, reg, up, retired, a, b, kw

    # -- kill after canary_live: nobody is judging -> must roll back ---
    d, reg, up, retired, a, b, kw = rig("mid_canary")
    RolloutController(reg, d, **kw).start_canary("demo", b)
    res1 = RolloutController(reg, d, **kw).resume()
    ch = reg.channels("demo")
    mid_canary_ok = (res1 == {"demo": "rolled_back"}
                     and ch["stable"] == a and ch["canary"] is None
                     and versioned("demo", b) in retired
                     and up == {versioned("demo", a)})
    res1b = RolloutController(reg, d, **kw).resume()
    idem1 = res1b == {"demo": "consistent"}

    # -- kill between promote_begin and done: the decision is durable
    # -> resume must FINISH the promote, not un-decide it --------------
    class _DiesApplying(RolloutController):
        def _apply_promote(self, *args, **kwargs):
            raise _Killed()

    d, reg, up, retired, a, b, kw = rig("mid_promote")
    ctl = _DiesApplying(reg, d, **kw)
    ctl.start_canary("demo", b)
    try:
        ctl.promote("demo")
    except _Killed:
        pass
    res2 = RolloutController(reg, d, **kw).resume()
    ch = reg.channels("demo")
    mid_promote_ok = (res2 == {"demo": "promoted"}
                      and ch["stable"] == b and ch["canary"] is None
                      and versioned("demo", a) in retired
                      and up == {versioned("demo", b)})
    res2b = RolloutController(reg, d, **kw).resume()
    idem2 = res2b == {"demo": "consistent"}

    return {"episode": "controller_kill_resume",
            "mid_canary": res1.get("demo"),
            "mid_promote": res2.get("demo"),
            "idempotent": bool(idem1 and idem2),
            "elapsed_s": round(time.monotonic() - t0, 1),
            "ok": bool(mid_canary_ok and mid_promote_ok
                       and idem1 and idem2)}


def rollout_soak(args) -> int:
    import numpy as np

    from sparknet_tpu.parallel.registry import ModelRegistry, versioned
    from sparknet_tpu.parallel.rollout import RolloutConfig, RolloutController
    from sparknet_tpu.parallel.router import Router, RouterConfig
    from sparknet_tpu.parallel.serving import (
        ModelHouse, ServeConfig, solo_references,
    )

    _clean_env()
    rng = np.random.default_rng(args.seed)
    own_tmp = args.workdir is None
    workdir = args.workdir or tempfile.mkdtemp(prefix="sparknet_rollout_")
    os.makedirs(workdir, exist_ok=True)
    trace_dir = os.environ.setdefault(
        "SPARKNET_TRACE_DIR", os.path.join(workdir, "trace"))
    os.makedirs(trace_dir, exist_ok=True)
    regdir = os.path.join(workdir, "registry")
    os.environ["SPARKNET_REGISTRY_DIR"] = regdir
    t0 = time.monotonic()

    reg = ModelRegistry(regdir)
    # small fast SLO windows so a ~30 s leg sees real multi-window
    # burn-rate judgments, not just the defaults' opening blur
    cfg = ServeConfig(batch_shapes=(1, 4), seed=0,
                      slo_fast_window_s=1.5, slo_window_s=6.0,
                      slo_min_requests=4, slo_reject_budget=0.05,
                      slo_sample_every_s=0.1)
    router = Router(RouterConfig(spill_depth=8))
    fleet = _RolloutFleet(reg, cfg, router)
    ctl = RolloutController(
        reg, workdir, ensure=fleet.ensure, retire=fleet.retire,
        verdict=fleet.verdict, router=router,
        cfg=RolloutConfig(fraction=0.5, judge_s=1.5, poll_s=0.05,
                          min_requests=10, breach_polls=2))

    v1 = reg.publish("lenet", slo={"p99_ms": 2000.0},
                     notes="rollout soak v1")
    v2 = reg.publish("lenet", slo={"p99_ms": 2000.0},
                     notes="rollout soak v2")
    # zoo-init versions share seed 0, so one solo house is the
    # bit-identity oracle for BOTH sides of the split
    lm = ModelHouse(cfg).load("lenet")
    inputs = [rng.normal(size=lm.in_shape).astype(np.float32)
              for _ in range(16)]
    refs = solo_references(lm, inputs)

    episodes = []
    try:
        episodes.append(_rollout_promote_episode(
            ctl, fleet, reg, router, inputs, refs, v1, v2))
        if episodes[-1]["ok"]:
            episodes.append(_rollout_bad_canary_episode(
                ctl, fleet, reg, router, inputs, refs, v2, trace_dir))
        episodes.append(_rollout_resume_episode(
            os.path.join(workdir, "resume")))
    finally:
        fleet.close()

    for e in episodes:
        print(f"rollout-soak: {e['episode']} -> "
              f"{'OK' if e['ok'] else 'FAIL'} ({e['elapsed_s']}s)",
              flush=True)
    passed = sum(1 for e in episodes if e["ok"])
    report = {"mode": "rollout", "seed": args.seed,
              "episodes": episodes, "passed": passed,
              "failed": len(episodes) - passed,
              "elapsed_s": round(time.monotonic() - t0, 1),
              "ok": len(episodes) == 3 and passed == len(episodes)}
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"rollout-soak: verdict written to {args.out} "
              f"({passed}/{len(episodes)} episode(s) passed)")
    else:
        print(text)
    if own_tmp and report["ok"]:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    elif not report["ok"]:
        print(f"rollout-soak: scratch kept at {workdir} for post-mortem "
              "(rollout.jsonl + flight dumps)", file=sys.stderr)
    return 0 if report["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="chaos soak runner")
    ap.add_argument("--runs", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the JSON verdict here (default: stdout)")
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a TemporaryDirectory)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet mode: N concurrent seeded chaos jobs + a "
                         "late whole-budget preemptor under one "
                         "FleetScheduler (0 = classic per-run soak)")
    ap.add_argument("--fleet-devices", type=int, default=8)
    ap.add_argument("--fleet-kill", action="store_true",
                    help="SIGKILL the scheduler mid-run and resume it "
                         "from its journal")
    ap.add_argument("--fleet-kill-after", type=float, default=6.0)
    ap.add_argument("--fleet-preempt-after", type=float, default=5.0,
                    help="delay before the high-priority preemptor "
                         "arrives")
    ap.add_argument("--fleet-timeout", type=float, default=420.0)
    ap.add_argument("--pod", type=int, default=0, metavar="N",
                    help="pod mode: burn in a simulated N-host fleet "
                         "(mixed training + serving tenants) under the "
                         "seeded traffic model")
    ap.add_argument("--pod-devices", type=int, default=4,
                    help="device slices per simulated host")
    ap.add_argument("--pod-slice", action="store_true",
                    help="the ~60s CI shape: one host-kill + one flash "
                         "crowd (skips the drain and serving-host-loss "
                         "legs)")
    ap.add_argument("--forever", action="store_true",
                    help="standing burn-in: keep scheduling episodes "
                         "until one fails (or Ctrl-C)")
    ap.add_argument("--pod-timeout", type=float, default=420.0,
                    help="bound on the training tenants of one episode")
    ap.add_argument("--pod-qps", type=float, default=None,
                    help="base offered QPS (default SPARKNET_SOAK_QPS)")
    ap.add_argument("--pod-flash-x", type=float, default=None,
                    help="flash-crowd multiplier "
                         "(default SPARKNET_SOAK_FLASH_X)")
    ap.add_argument("--pod-leg-s", type=float, default=None,
                    help="seconds per traffic leg "
                         "(default SPARKNET_SOAK_LEG_S)")
    ap.add_argument("--net", action="store_true",
                    help="net mode: the partition/fenced-ship/slow-link "
                         "chaos legs over the fake-ssh ChaosTransport")
    ap.add_argument("--net-slice", action="store_true",
                    help="the ~60s CI shape: partition-suspend-heal + "
                         "fenced-zombie legs only (skips slow-link)")
    ap.add_argument("--rollout", action="store_true",
                    help="rollout mode: canary-promote, bad-canary "
                         "auto-rollback, and controller-kill-resume "
                         "legs over the registry + rollout controller")
    args = ap.parse_args(argv)

    if args.rollout:
        return rollout_soak(args)
    if args.net:
        return net_soak(args)
    if args.pod:
        return pod_soak(args)
    if args.fleet:
        return fleet_soak(args)

    import numpy as np
    _clean_env()
    rng = np.random.default_rng(args.seed)

    own_tmp = args.workdir is None
    workdir = args.workdir or tempfile.mkdtemp(prefix="sparknet_soak_")
    os.makedirs(workdir, exist_ok=True)

    baselines: dict[tuple[str, ...], str] = {}

    def baseline_for(flags):
        """Fault-free reference run per flag set (cached — the guard and
        audit change checkpoint traffic but not the training math, so
        matching flags keeps the comparison honest)."""
        key = tuple(flags)
        if key not in baselines:
            path = os.path.join(workdir, f"base_{len(baselines)}.npz")
            ck = os.path.join(workdir, f"base_ck_{len(baselines)}")
            rc, _ = _run_driver(path, ck if flags else None, list(flags))
            if rc != 0:
                raise RuntimeError(f"fault-free baseline failed rc={rc} "
                                   f"(flags={flags})")
            baselines[key] = path
        return baselines[key]

    runs = []
    t0 = time.monotonic()
    for i in range(args.runs):
        options = _schedules(rng)
        name, fault, flags = options[int(rng.integers(0, len(options)))]
        out = os.path.join(workdir, f"run_{i}.npz")
        ck = os.path.join(workdir, f"ck_{i}")
        verdict = {"run": i, "schedule": name, "fault": fault,
                   "flags": flags}
        try:
            base = baseline_for(flags)
            rc, attempts = _run_driver(out, ck, list(flags), fault=fault)
            verdict.update(rc=rc, attempts=attempts)
            if rc == 0:
                match, bad_key = _params_match(base, out)
                verdict.update(match=match,
                               **({"diverged_at": bad_key}
                                  if not match else {}))
            else:
                verdict.update(match=False)
        except Exception as e:   # a broken run is a red verdict, not a crash
            verdict.update(rc=-1, attempts=0, match=False, error=str(e))
        verdict["ok"] = bool(verdict.get("rc") == 0 and verdict["match"])
        runs.append(verdict)
        print(f"soak: run {i} [{fault}] -> "
              f"{'OK' if verdict['ok'] else 'FAIL'} "
              f"(rc={verdict.get('rc')}, attempts="
              f"{verdict.get('attempts')})", flush=True)

    passed = sum(1 for r in runs if r["ok"])
    report = {"seed": args.seed, "runs": runs, "passed": passed,
              "failed": len(runs) - passed,
              "elapsed_s": round(time.monotonic() - t0, 1),
              "ok": passed == len(runs)}
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"soak: verdict written to {args.out} "
              f"({passed}/{len(runs)} passed)")
    else:
        print(text)
    if own_tmp and report["ok"]:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    elif not report["ok"]:
        print(f"soak: scratch kept at {workdir} for post-mortem",
              file=sys.stderr)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
