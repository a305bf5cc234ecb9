"""Multi-process launcher — the spark-submit analog.

The reference launches one driver + N executor JVMs via spark-submit
(reference: SETUP.md:45, README.md:60; worker-handle RDD at
ImageNetApp.scala:97).  Here the launcher only does *process placement* —
it carries no tensor traffic (that rides ICI/DCN via the JAX distributed
runtime).  Every spawned process gets the SPARKNET_COORDINATOR /
SPARKNET_NUM_PROCS / SPARKNET_PROC_ID env contract consumed by
``parallel.cluster.init_cluster_from_env``.

Modes:
  local  — spawn N processes on this machine (the CPU multi-process test
           rig; the analog of Spark local mode).  ``--devices-per-proc``
           carves virtual CPU devices per process.
  ssh    — run the command on each host of ``--hosts`` via ssh, process i
           on host i (plain SSH pod bring-up for TPU-VM workers, where
           each host sees its local chips natively).

Health plane: with ``heartbeat_dir`` set the children get
SPARKNET_HEARTBEAT_DIR (workers publish per-round beats via
``parallel.health.maybe_beat``), and with ``round_deadline`` the
supervisor additionally runs a ``StragglerMonitor`` over those beats — a
rank that beat once and then went silent past the deadline is declared
hung, killed, and the job torn down with exit code ``EXIT_STRAGGLER``
(125) so the resilience layer relaunches from checkpoint instead of
stalling until the global timeout.  ``log_dir`` tees every rank's output
to ``rank_<i>.log`` (the post-mortem ResilientRunner quotes), and a
caller-provided ``report`` dict receives per-rank exit codes, the first
failing rank, and any straggler kills.

Usage:
  python -m sparknet_tpu.tools.launch --nprocs 2 --devices-per-proc 2 \
      --platform cpu -- python -m sparknet_tpu.apps.cifar_app --synthetic ...
  python -m sparknet_tpu.tools.launch --hosts tpu-w0,tpu-w1 -- \
      python -m sparknet_tpu.apps.imagenet_app ...
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import threading
import time


EXIT_STRAGGLER = 125   # a rank was killed for missing the round deadline

# ssh-mode addresses that mean "spawn here, not over ssh" — the
# simulated N-host pod rig runs every 'host' on one CPU box with these
LOCAL_ADDRS = ("local", "localhost", "127.0.0.1")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _proc_env(base: dict, coordinator: str, nprocs: int, pid: int,
              platform: str | None, devices_per_proc: int | None,
              extra_env: dict | None = None) -> dict:
    env = dict(base)
    env["SPARKNET_COORDINATOR"] = coordinator
    env["SPARKNET_NUM_PROCS"] = str(nprocs)
    env["SPARKNET_PROC_ID"] = str(pid)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if devices_per_proc:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{devices_per_proc}").strip()
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    return env


def _wait_all(procs: list, timeout: float | None,
              poll_interval: float = 0.05, monitor=None,
              report: dict | None = None) -> int:
    """Supervise the worker set: returns 0 when every process exits clean.
    The FIRST nonzero exit tears the whole round down — remaining workers
    are killed immediately rather than left hanging on a dead collective
    until the timeout (the stage-abort half of Spark's task supervision;
    the reschedule half lives in ``parallel.resilience``).  A timeout
    kills everything and returns 124.

    ``monitor`` (a ``parallel.health.StragglerMonitor``) is polled with
    the still-live rank set; any rank it flags is killed and the job
    torn down with EXIT_STRAGGLER — a hung rank costs one round-deadline,
    not the whole timeout.  ``report`` (if given) is filled with the
    post-mortem: per-rank exit codes, the first failing rank, straggler
    kills, and the failure cause."""
    deadline = time.monotonic() + timeout if timeout else None
    rc = 0
    rcs: dict[int, int | None] = {i: None for i in range(len(procs))}
    first_failure: int | None = None
    stragglers: list[int] = []
    cause = ""
    pending = dict(enumerate(procs))
    while pending and rc == 0:
        for rank, p in list(pending.items()):
            r = p.poll()
            if r is None:
                continue
            del pending[rank]
            rcs[rank] = r
            if r != 0:
                rc, first_failure, cause = r, rank, "exit"
                break
        if rc == 0 and pending:
            if monitor is not None:
                hung = monitor.check(sorted(pending))
                if hung:
                    rc, first_failure, cause = (
                        EXIT_STRAGGLER, hung[0], "straggler")
                    stragglers = hung
                    for rank in hung:
                        print(f"launch: rank {rank} missed the round "
                              f"deadline ({monitor.deadline_s:.3g}s); "
                              f"killing as hung", file=sys.stderr,
                              flush=True)
                        pending[rank].kill()
                    break
            if deadline is not None and time.monotonic() > deadline:
                rc, cause = 124, "timeout"
                break
            time.sleep(poll_interval)
    for p in procs:
        if p.poll() is None:
            p.kill()
    for rank, p in enumerate(procs):
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover
            pass
        if rcs.get(rank) is None:
            rcs[rank] = p.poll()
    if rc == 0:
        cause = "clean"
    if report is not None:
        report.update(rcs=rcs, first_failure=first_failure,
                      stragglers=stragglers, cause=cause)
    return rc


def _stream(prefix: str, pipe, log_path: str | None = None) -> None:
    log = open(log_path, "ab") if log_path else None
    try:
        for line in iter(pipe.readline, b""):
            sys.stderr.write(f"[{prefix}] {line.decode(errors='replace')}")
            sys.stderr.flush()
            if log is not None:
                log.write(line)
                log.flush()
    finally:
        if log is not None:
            log.close()


def _make_monitor(heartbeat_dir: str | None, round_deadline: float | None,
                  *, host_map: list | None = None, transport=None,
                  host_suspect_probe=None, host_down_probe=None):
    if not (heartbeat_dir and round_deadline):
        return None
    # lazy import: the health plane is optional and the launcher should
    # stay importable without it on minimal rigs
    from ..parallel.health import GangHealth, StragglerMonitor
    os.makedirs(heartbeat_dir, exist_ok=True)
    # lease-aware gang monitor when beats ride a remote transport (the
    # relay is part of the tick) or the fleet can mark hosts suspect —
    # either way partition-vs-death discipline applies
    if host_map is not None and (
            (transport is not None and not transport.local)
            or host_suspect_probe is not None):
        return GangHealth(heartbeat_dir, round_deadline, host_map=host_map,
                          transport=transport,
                          suspect_probe=host_suspect_probe,
                          down_probe=host_down_probe)
    return StragglerMonitor(heartbeat_dir, round_deadline)


def _rank_hb_dir(heartbeat_dir: str | None,
                 host_map: list | None, rank: int) -> str | None:
    """Rank ``rank``'s beacon dir: the per-host ``host_<name>/`` subdir
    when a host placement is given (so supervisors can roll liveness up
    per host — health.read_hosts), else the flat root."""
    if not heartbeat_dir:
        return None
    if not host_map:
        return heartbeat_dir
    from ..parallel.health import host_dir
    return host_dir(heartbeat_dir, str(host_map[rank]))


def _check_host_map(host_map: list | None, n: int) -> None:
    if host_map is not None and len(host_map) != n:
        raise ValueError(f"host_map has {len(host_map)} entries for "
                         f"{n} ranks — one host label per rank required")


def launch_local(cmd: list[str], nprocs: int, *, platform: str | None = None,
                 devices_per_proc: int | None = None,
                 coordinator: str | None = None,
                 timeout: float | None = None,
                 extra_env: dict | None = None,
                 heartbeat_dir: str | None = None,
                 round_deadline: float | None = None,
                 log_dir: str | None = None,
                 report: dict | None = None,
                 host_map: list | None = None,
                 on_spawn=None) -> int:
    """Spawn ``nprocs`` copies of ``cmd`` locally; returns the first
    non-zero exit code, else 0.  Output is streamed with [p<i>] prefixes.
    The first worker death kills the remaining workers immediately
    (see ``_wait_all``).  ``extra_env`` adds per-job vars to every child
    (the ResilientRunner's attempt-stamping channel); ``heartbeat_dir`` /
    ``round_deadline`` / ``log_dir`` / ``report`` are the health plane
    (module docstring).  ``host_map`` (one host label per rank) stamps
    SPARKNET_FLEET_HOST on each child and routes its beacons into the
    per-host ``host_<name>/`` subdir — the simulated-pod rig's placement
    channel.  ``on_spawn`` (if given) receives the list of
    ``subprocess.Popen`` handles once the full gang is up — an external
    supervisor's only safe channel to the worker pids (for preemption
    signals and orphan accounting; see ``parallel.fleet``)."""
    _check_host_map(host_map, nprocs)
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    monitor = _make_monitor(heartbeat_dir, round_deadline)
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    procs = []
    threads = []
    for pid in range(nprocs):
        env = _proc_env(os.environ, coordinator, nprocs, pid, platform,
                        devices_per_proc, extra_env)
        hb = _rank_hb_dir(heartbeat_dir, host_map, pid)
        if hb:
            env["SPARKNET_HEARTBEAT_DIR"] = hb
        if host_map:
            env["SPARKNET_FLEET_HOST"] = str(host_map[pid])
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        log = os.path.join(log_dir, f"rank_{pid}.log") if log_dir else None
        t = threading.Thread(target=_stream, args=(f"p{pid}", p.stdout, log),
                             daemon=True)
        t.start()
        procs.append(p)
        threads.append(t)
    if on_spawn is not None:
        on_spawn(list(procs))
    rc = _wait_all(procs, timeout, monitor=monitor, report=report)
    for t in threads:
        t.join(timeout=5)
    return rc


def launch_ssh(cmd: list[str], hosts: list[str], *,
               coordinator_port: int | None = None,
               cwd: str | None = None,
               timeout: float | None = None,
               extra_env: dict | None = None,
               heartbeat_dir: str | None = None,
               round_deadline: float | None = None,
               log_dir: str | None = None,
               report: dict | None = None,
               platform: str | None = None,
               devices_per_proc: int | None = None,
               host_map: list | None = None,
               on_spawn=None,
               transport=None,
               host_suspect_probe=None,
               host_down_probe=None) -> int:
    """Run ``cmd`` on every host via the host transport; host 0 doubles
    as coordinator.  ``transport`` (a ``parallel.transport.HostTransport``)
    is the exec/ship/beat seam; when omitted it is chosen from the env —
    ssh when ``SPARKNET_SSH_CMD`` is set or any address is remote, local
    otherwise, chaos-wrapped when network faults are active.  Addresses
    in ``LOCAL_ADDRS`` are spawned directly ONLY under a local transport;
    with SPARKNET_SSH_CMD set even ``localhost`` rides the ssh wire
    format (that is the CI fake-ssh rig — the argv/env/stdio plumbing is
    the production path, no sshd required).

    Health plane: under a local transport ranks beat straight into the
    shared ``heartbeat_dir``; under a remote one each rank beats into a
    host-local staging dir and the supervisor's monitor relays beats
    back over the transport each tick, with LEASE discipline on top —
    a whole-host beacon silence marks the host SUSPECT and *suspends*
    its ranks (a network partition must not kill a healthy gang or burn
    restart budget) unless ``host_down_probe`` confirms real death, in
    which case the straggler kill proceeds and the resilience layer
    takes the lost-host path.  ``host_suspect_probe`` lets the fleet
    feed externally-known suspicion into the same suspension.

    ``platform``/``devices_per_proc`` apply to direct local spawns
    (remote hosts see their chips natively).  ``host_map`` gives each
    rank its host *label* (defaults to its address) for beacon routing
    and the SPARKNET_FLEET_HOST tag.  ``on_spawn`` receives the local
    ``Popen`` handles (signalling an ssh one ends its remote command via
    the ssh session, so preemption still works, host by host)."""
    _check_host_map(host_map, len(hosts))
    if host_map is None:
        host_map = [str(h) for h in hosts]
    if transport is None:
        from ..parallel.transport import default_transport
        transport = default_transport(hosts)
    all_local = all(h in LOCAL_ADDRS for h in hosts)
    port = coordinator_port or (free_port() if all_local else 9876)
    addr0 = "127.0.0.1" if hosts[0] in LOCAL_ADDRS else hosts[0]
    coordinator = f"{addr0}:{port}"
    cwd = cwd or os.getcwd()
    monitor = _make_monitor(heartbeat_dir, round_deadline,
                            host_map=host_map, transport=transport,
                            host_suspect_probe=host_suspect_probe,
                            host_down_probe=host_down_probe)
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    procs = []
    threads = []
    for pid, host in enumerate(hosts):
        direct = host in LOCAL_ADDRS and transport.local
        if direct:
            hb = _rank_hb_dir(heartbeat_dir, host_map, pid)
            env = _proc_env(os.environ, coordinator, len(hosts), pid,
                            platform, devices_per_proc, extra_env)
            if hb:
                os.makedirs(hb, exist_ok=True)
                env["SPARKNET_HEARTBEAT_DIR"] = hb
            env["SPARKNET_FLEET_HOST"] = str(host_map[pid])
            p = subprocess.Popen(cmd, env=env, cwd=cwd,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT)
        else:
            pairs = [
                ("SPARKNET_COORDINATOR", coordinator),
                ("SPARKNET_NUM_PROCS", str(len(hosts))),
                ("SPARKNET_PROC_ID", str(pid)),
                ("SPARKNET_FLEET_HOST", str(host_map[pid])),
            ]
            if heartbeat_dir:
                # remote ranks beat into host-local staging; the
                # monitor's relay moves beats into host_<name>/ — the
                # shared-filesystem assumption stops at the supervisor
                from ..parallel.health import stage_dir
                pairs.append(("SPARKNET_HEARTBEAT_DIR",
                              stage_dir(heartbeat_dir,
                                        str(host_map[pid]))))
            if extra_env:
                pairs.extend((k, str(v)) for k, v in extra_env.items())
            p = transport.popen(host, cmd, env_pairs=pairs, cwd=cwd)
        log = os.path.join(log_dir, f"rank_{pid}.log") if log_dir else None
        tag = host_map[pid] if direct else host
        t = threading.Thread(target=_stream, args=(tag, p.stdout, log),
                             daemon=True)
        t.start()
        procs.append(p)
        threads.append(t)
    if on_spawn is not None:
        on_spawn(list(procs))
    rc = _wait_all(procs, timeout, monitor=monitor, report=report)
    for t in threads:
        t.join(timeout=5)
    if report is not None:
        report["transport"] = transport.kind
        if monitor is not None and hasattr(monitor, "ever_suspect"):
            report["suspect_hosts"] = sorted(monitor.ever_suspect)
            report["confirmed_down"] = sorted(monitor.confirmed_down)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="spark-submit analog: place N framework processes")
    ap.add_argument("--nprocs", type=int, default=None,
                    help="local mode: number of processes")
    ap.add_argument("--hosts", default=None,
                    help="ssh mode: comma-separated host list")
    ap.add_argument("--platform", default=None,
                    help="force JAX platform in children (e.g. cpu)")
    ap.add_argument("--devices-per-proc", type=int, default=None,
                    help="virtual CPU devices per process (test rigs)")
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--heartbeat-dir", default=None,
                    help="shared dir for worker liveness beacons")
    ap.add_argument("--round-deadline", type=float, default=None,
                    help="seconds of beacon silence before a rank is "
                         "declared hung and killed (needs --heartbeat-dir)")
    ap.add_argument("--log-dir", default=None,
                    help="tee each rank's output to rank_<i>.log here")
    ap.add_argument("--feed-workers", type=int, default=None,
                    help="decode-pool width per worker (exported as "
                         "SPARKNET_FEED_WORKERS; 0 = serial feed path)")
    ap.add_argument("--feed-depth", type=int, default=None,
                    help="prefetch depth per worker (exported as "
                         "SPARKNET_FEED_DEPTH)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run (prefix with --)")
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    if not cmd:
        ap.error("no command given")
    if args.round_deadline and not args.heartbeat_dir:
        ap.error("--round-deadline requires --heartbeat-dir")
    if args.feed_workers is not None and args.feed_workers < 0:
        ap.error("--feed-workers must be >= 0")
    if args.feed_depth is not None and args.feed_depth < 1:
        ap.error("--feed-depth must be >= 1")
    # feed-pipeline knobs ride the same env contract every other
    # per-process setting uses (consumed by data.pipeline at feed build)
    feed_env = {}
    if args.feed_workers is not None:
        feed_env["SPARKNET_FEED_WORKERS"] = args.feed_workers
    if args.feed_depth is not None:
        feed_env["SPARKNET_FEED_DEPTH"] = args.feed_depth
    health = dict(heartbeat_dir=args.heartbeat_dir,
                  round_deadline=args.round_deadline, log_dir=args.log_dir,
                  extra_env=feed_env or None)
    if args.hosts:
        return launch_ssh(cmd, args.hosts.split(","), timeout=args.timeout,
                          **health)
    if not args.nprocs:
        ap.error("--nprocs or --hosts required")
    return launch_local(cmd, args.nprocs, platform=args.platform,
                        devices_per_proc=args.devices_per_proc,
                        timeout=args.timeout, **health)


if __name__ == "__main__":
    sys.exit(main())
