"""Resilient-runtime coverage: fault-spec grammar, injector semantics,
restart policy/backoff, launcher supervision, bounded control-plane
retries, checkpoint integrity (checksums, CheckpointError), round-granular
trainer checkpoint/resume, and the end-to-end chaos paths — a rank killed
mid-job recovers through ResilientRunner and matches the fault-free run
(the recovery half of the reference's spark.task.maxFailures contract,
CifarApp.scala:36; snapshots-as-recovery per Caffe's Solver::Snapshot).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from sparknet_tpu.parallel.resilience import (
    Attempt, ResilientRunner, RestartPolicy,
)
from sparknet_tpu.utils import faults
from sparknet_tpu.utils.checkpoint import (
    CheckpointError, load_checkpoint, save_checkpoint,
)
from sparknet_tpu.utils.retry import backoff_delays, retry_call

DRIVER = os.path.join(os.path.dirname(__file__), "multihost_driver.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fault grammar + injector
# ---------------------------------------------------------------------------

def test_parse_faults_grammar():
    specs = faults.parse_faults(
        "crash@round:3@rank:1, slow_feed:200ms, corrupt_ckpt@round:2,"
        "hang@round:5@attempt:2")
    assert specs[0] == faults.FaultSpec("crash", round=3, rank=1)
    assert specs[1].kind == "slow_feed"
    assert specs[1].delay_s == pytest.approx(0.2)
    assert specs[2] == faults.FaultSpec("corrupt_ckpt", round=2)
    assert specs[3] == faults.FaultSpec("hang", round=5, attempt=2)


def test_parse_faults_elastic_kinds():
    specs = faults.parse_faults(
        "perma_crash@rank:3, straggle:1.5s@round:2, nan_inject@round:4,"
        "crash_in_ckpt@round:1")
    assert specs[0] == faults.FaultSpec("perma_crash", rank=3)
    assert specs[1].kind == "straggle" and specs[1].delay_s == 1.5
    assert specs[1].round == 2
    assert specs[2] == faults.FaultSpec("nan_inject", round=4)
    assert specs[3] == faults.FaultSpec("crash_in_ckpt", round=1)


@pytest.mark.parametrize("bad, msg", [
    ("explode@round:1", "unknown fault kind"),
    ("crash", "needs @round"),
    ("crash@round:x", "not an integer"),
    ("crash@rnd:1", "bad modifier"),
    ("slow_feed", "needs a duration"),
    ("slow_feed:fast", "bad duration"),
    ("crash:3@round:1", "takes no ':' arg"),
    ("straggle@round:1", "needs a duration"),
    ("nan_inject", "needs @round"),
    ("crash_in_ckpt", "needs @round"),
    ("perma_crash", "needs @rank"),
])
def test_parse_faults_rejects(bad, msg):
    with pytest.raises(ValueError, match=msg):
        faults.parse_faults(bad)


def test_perma_crash_fires_on_every_attempt_matching_rank_only():
    inj, calls = _injector("perma_crash@rank:2", attempt=5, rank=2)
    with pytest.raises(_Exit):
        inj.on_round(0, rank=2)            # any round, any attempt
    assert calls["exit"] == [43]
    inj2, calls2 = _injector("perma_crash@rank:2", attempt=5, rank=1)
    inj2.on_round(0, rank=1)               # survivor ranks untouched
    assert calls2["exit"] == []


def test_straggle_sleeps_then_continues():
    inj, calls = _injector("straggle:2.5s@round:1")
    inj.on_round(0)                        # wrong round: no-op
    assert calls["sleep"] == []
    with pytest.raises(_Exit):             # test sleep raises to observe
        inj.on_round(1)
    assert calls["sleep"] == [2.5]
    # one-shot default: the relaunched attempt runs clean
    inj2, calls2 = _injector("straggle:2.5s@round:1", attempt=1)
    inj2.on_round(1)
    assert calls2["sleep"] == []


def test_nan_inject_fires_once_per_process():
    inj, _ = _injector("nan_inject@round:2")
    assert not inj.nan_inject(1)
    assert inj.nan_inject(2)
    assert not inj.nan_inject(2)           # rollback replay runs clean
    inj2, _ = _injector("nan_inject@round:2@rank:1", rank=0)
    assert not inj2.nan_inject(2)          # other ranks unpoisoned


def test_crash_in_ckpt_hook():
    inj, calls = _injector("crash_in_ckpt@round:3")
    inj.on_checkpoint_write(2)             # wrong round: no-op
    assert calls["exit"] == []
    with pytest.raises(_Exit):
        inj.on_checkpoint_write(3)
    assert calls["exit"] == [43]
    inj1, calls1 = _injector("crash_in_ckpt@round:3", attempt=1)
    inj1.on_checkpoint_write(3)            # restarted job writes clean
    assert calls1["exit"] == []


def test_duration_units():
    assert faults.parse_faults("slow_feed:1.5s")[0].delay_s == 1.5
    assert faults.parse_faults("slow_feed:2")[0].delay_s == 2.0


class _Exit(Exception):
    pass


def _injector(spec, attempt=0, rank=0):
    calls = {"exit": [], "sleep": []}

    def fake_exit(code):
        calls["exit"].append(code)
        raise _Exit()  # real os._exit never returns; simulate that

    def fake_sleep(s):
        calls["sleep"].append(s)
        raise _Exit()  # break the hang loop

    inj = faults.FaultInjector(faults.parse_faults(spec), attempt=attempt,
                               rank=rank, _exit=fake_exit, _sleep=fake_sleep)
    return inj, calls


def test_crash_fires_on_matching_round_and_rank_only():
    inj, calls = _injector("crash@round:3@rank:1", rank=1)
    inj.on_round(2, rank=1)            # wrong round: no-op
    inj.on_round(3, rank=0)            # wrong rank: no-op
    assert calls["exit"] == []
    with pytest.raises(_Exit):
        inj.on_round(3, rank=1)
    assert calls["exit"] == [43]


def test_one_shot_faults_default_to_first_attempt_only():
    inj, calls = _injector("crash@round:1", attempt=1)
    inj.on_round(1)                    # restarted job: fault suppressed
    assert calls["exit"] == []
    inj0, calls0 = _injector("crash@round:1", attempt=0)
    with pytest.raises(_Exit):
        inj0.on_round(1)


def test_attempt_scoped_fault():
    inj, calls = _injector("hang@round:2@attempt:1", attempt=1)
    with pytest.raises(_Exit):
        inj.on_round(2)
    assert calls["sleep"]              # entered the hang loop


def test_slow_feed_applies_on_every_attempt():
    inj, _ = _injector("slow_feed:50ms", attempt=3)
    assert inj.feed_delay() == pytest.approx(0.05)
    assert inj.feed_delay(rank=7) == pytest.approx(0.05)
    inj2, _ = _injector("slow_feed:50ms@rank:1", attempt=0)
    assert inj2.feed_delay(rank=0) == 0.0


def test_corrupt_ckpt_matching():
    inj, _ = _injector("corrupt_ckpt@round:2")
    assert inj.corrupt_checkpoint(2)
    assert not inj.corrupt_checkpoint(3)
    inj1, _ = _injector("corrupt_ckpt@round:2", attempt=1)
    assert not inj1.corrupt_checkpoint(2)   # one-shot: attempt 0 only


def test_get_injector_tracks_env(monkeypatch):
    monkeypatch.setenv("SPARKNET_FAULT", "slow_feed:10ms")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    assert faults.get_injector().feed_delay() == pytest.approx(0.01)
    monkeypatch.setenv("SPARKNET_FAULT", "")
    assert faults.get_injector().feed_delay() == 0.0


# ---------------------------------------------------------------------------
# restart policy + ResilientRunner (fake launcher)
# ---------------------------------------------------------------------------

def test_restart_policy_backoff_sequence_and_cap():
    p = RestartPolicy(max_restarts=5, backoff_base=1.0, backoff_factor=3.0,
                      backoff_max=10.0, jitter=0.0)
    assert [p.delay(i) for i in range(4)] == [1.0, 3.0, 9.0, 10.0]


def test_restart_policy_jitter_spreads_but_bounds_delays():
    """Jitter (on by default) must keep every delay inside
    [d·(1-j), d·(1+j)] and actually decorrelate two runners — the
    anti-thundering-herd contract."""
    import random
    p = RestartPolicy(backoff_base=4.0, jitter=0.25)
    a = [p.delay(0, random.Random(1)) for _ in range(50)]
    b = [p.delay(0, random.Random(2)) for _ in range(50)]
    assert all(3.0 <= d <= 5.0 for d in a + b)
    assert a[0] != b[0]                      # different rank seeds differ
    deterministic = RestartPolicy(backoff_base=4.0, jitter=0.0)
    assert deterministic.delay(0) == 4.0


def test_runner_requires_exactly_one_mode():
    with pytest.raises(ValueError, match="exactly one"):
        ResilientRunner(["true"])
    with pytest.raises(ValueError, match="exactly one"):
        ResilientRunner(["true"], nprocs=2, hosts=["a"])


def _fake_runner(monkeypatch, rcs):
    """ResilientRunner whose launch returns scripted rcs and records the
    per-attempt env stamps and sleeps."""
    import sparknet_tpu.parallel.resilience as R
    seen = {"envs": [], "sleeps": []}
    it = iter(rcs)

    def fake_local(cmd, nprocs, **kw):
        seen["envs"].append(dict(kw["extra_env"]))
        return next(it)

    monkeypatch.setattr(R, "launch_local", fake_local)
    runner = ResilientRunner(
        ["job"], nprocs=2,
        policy=RestartPolicy(max_restarts=3, backoff_base=0.5, jitter=0.0),
        sleep=lambda s: seen["sleeps"].append(s))
    return runner, seen


def test_runner_success_first_try_no_restart(monkeypatch):
    runner, seen = _fake_runner(monkeypatch, [0])
    assert runner.run() == 0
    assert seen["sleeps"] == []
    assert [a.returncode for a in runner.attempts] == [0]
    assert seen["envs"][0]["SPARKNET_FAULT_ATTEMPT"] == "0"


def test_runner_restarts_with_backoff_and_attempt_stamp(monkeypatch):
    runner, seen = _fake_runner(monkeypatch, [43, 1, 0])
    assert runner.run() == 0
    assert seen["sleeps"] == [0.5, 1.0]          # exponential backoff
    assert [e["SPARKNET_FAULT_ATTEMPT"] for e in seen["envs"]] == \
        ["0", "1", "2"]
    assert [a.returncode for a in runner.attempts] == [43, 1, 0]
    assert isinstance(runner.attempts[0], Attempt)


def test_runner_bounded_budget_gives_up(monkeypatch):
    runner, seen = _fake_runner(monkeypatch, [7, 7, 7, 7])
    assert runner.run() == 7
    assert len(runner.attempts) == 4             # max_restarts=3 → 4 tries
    assert seen["sleeps"] == [0.5, 1.0, 2.0]     # no sleep after final try


# ---------------------------------------------------------------------------
# bounded retry helper + control-plane edges
# ---------------------------------------------------------------------------

def test_backoff_delays_shape():
    assert list(backoff_delays(4, 0.1, 2.0, 0.3)) == \
        pytest.approx([0.1, 0.2, 0.3])
    assert list(backoff_delays(1, 0.1)) == []


def test_retry_call_recovers_then_gives_up():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    sleeps = []
    assert retry_call(flaky, attempts=3, base_delay=0.01,
                      sleep=sleeps.append) == "ok"
    assert sleeps == pytest.approx([0.01, 0.02])

    calls["n"] = -10  # always failing now
    with pytest.raises(OSError, match="transient"):
        retry_call(flaky, attempts=2, base_delay=0.01, sleep=sleeps.append)


def test_retry_call_non_matching_exception_propagates_immediately():
    def boom():
        raise KeyError("nope")

    sleeps = []
    with pytest.raises(KeyError):
        retry_call(boom, attempts=5, sleep=sleeps.append)
    assert sleeps == []


def test_io_retry_env_knobs(monkeypatch, tmp_path):
    monkeypatch.setenv("SPARKNET_IO_RETRIES", "4")
    monkeypatch.setenv("SPARKNET_IO_BACKOFF", "0")
    from sparknet_tpu.utils.retry import io_retry
    calls = {"n": 0}

    def flaky_open():
        calls["n"] += 1
        raise OSError("gone")

    with pytest.raises(OSError):
        io_retry(flaky_open)
    assert calls["n"] == 4


def test_lmdb_reader_retries_transient_open(tmp_path, monkeypatch):
    from sparknet_tpu.data import lmdb_io
    db = tmp_path / "db"
    lmdb_io.write_lmdb(str(db), [(b"k", b"v")])
    monkeypatch.setenv("SPARKNET_IO_RETRIES", "3")
    monkeypatch.setenv("SPARKNET_IO_BACKOFF", "0")
    real_open, state = open, {"n": 0}

    def flaky(path, *a, **kw):
        state["n"] += 1
        if state["n"] == 1:
            raise OSError("NFS blip")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", flaky)
    with lmdb_io.LmdbReader(str(db)) as r:
        assert r.first() == (b"k", b"v")
    assert state["n"] >= 2


def test_init_cluster_from_env_validation(monkeypatch):
    from sparknet_tpu.parallel import cluster
    joined = []
    monkeypatch.setattr(cluster, "init_cluster",
                        lambda *a: joined.append(a))
    for var in ("SPARKNET_COORDINATOR", "SPARKNET_NUM_PROCS",
                "SPARKNET_PROC_ID"):
        monkeypatch.delenv(var, raising=False)
    assert cluster.init_cluster_from_env() is False

    monkeypatch.setenv("SPARKNET_COORDINATOR", "127.0.0.1:1234")
    with pytest.raises(ValueError, match="SPARKNET_NUM_PROCS is missing"):
        cluster.init_cluster_from_env()
    monkeypatch.setenv("SPARKNET_NUM_PROCS", "two")
    monkeypatch.setenv("SPARKNET_PROC_ID", "0")
    with pytest.raises(ValueError, match="SPARKNET_NUM_PROCS='two' is not"):
        cluster.init_cluster_from_env()
    monkeypatch.setenv("SPARKNET_NUM_PROCS", "2")
    monkeypatch.setenv("SPARKNET_PROC_ID", "2")
    with pytest.raises(ValueError, match="out of range"):
        cluster.init_cluster_from_env()
    monkeypatch.setenv("SPARKNET_PROC_ID", "1")
    assert cluster.init_cluster_from_env() is True
    assert joined == [("127.0.0.1:1234", 2, 1)]
    # partial contract without coordinator is named, not silently ignored
    monkeypatch.delenv("SPARKNET_COORDINATOR")
    with pytest.raises(ValueError, match="SPARKNET_COORDINATOR is not"):
        cluster.init_cluster_from_env()


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_with_checksum(tmp_path):
    p = str(tmp_path / "c.npz")
    tree = {"w": np.arange(6.0).reshape(2, 3), "n": [np.int64(3)]}
    save_checkpoint(p, tree)
    out = load_checkpoint(p)
    np.testing.assert_array_equal(out["w"], tree["w"])
    assert int(out["n"][0]) == 3


def test_truncated_checkpoint_raises_checkpoint_error(tmp_path):
    p = str(tmp_path / "trunc.npz")
    save_checkpoint(p, {"w": np.zeros(1000)})
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(CheckpointError) as ei:
        load_checkpoint(p)
    assert ei.value.path == p


def test_bitflip_fails_checksum(tmp_path):
    p = str(tmp_path / "rot.npz")
    save_checkpoint(p, {"w": np.zeros(4096, np.float32)})
    faults.scribble(p)
    with pytest.raises(CheckpointError):
        load_checkpoint(p)


def test_missing_checkpoint_raises_checkpoint_error(tmp_path):
    with pytest.raises(CheckpointError):
        load_checkpoint(str(tmp_path / "absent.npz"))


# ---------------------------------------------------------------------------
# launcher supervision
# ---------------------------------------------------------------------------

def test_first_worker_death_tears_down_survivors_fast():
    """One worker exits nonzero while its sibling would sleep for 60s: the
    supervisor must kill the sibling and return well before that (the
    stage-abort, without waiting for the job timeout)."""
    from sparknet_tpu.tools.launch import _wait_all
    quick = subprocess.Popen([sys.executable, "-c", "import sys; sys.exit(5)"])
    slow = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    t0 = time.monotonic()
    rc = _wait_all([quick, slow], timeout=50)
    assert rc == 5
    assert time.monotonic() - t0 < 30
    assert slow.poll() is not None  # sibling was killed


def test_wait_all_timeout_returns_124():
    from sparknet_tpu.tools.launch import _wait_all
    p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    assert _wait_all([p], timeout=0.5) == 124


def test_launch_local_extra_env_reaches_children(tmp_path):
    from sparknet_tpu.tools.launch import launch_local
    out = tmp_path / "env.txt"
    code = (f"import os; open({str(out)!r}, 'a').write("
            f"os.environ['SPARKNET_FAULT_ATTEMPT'] + '\\n')")
    rc = launch_local([sys.executable, "-c", code], nprocs=2,
                      timeout=60, extra_env={"SPARKNET_FAULT_ATTEMPT": "7"})
    assert rc == 0
    assert out.read_text().splitlines() == ["7", "7"]


# ---------------------------------------------------------------------------
# trainer round-granular checkpoint / resume (in-process, 4 virtual devices)
# ---------------------------------------------------------------------------

def _make_trainer(ckpt_dir, seed=0, every=1, keep=3, *, strategy="local_sgd",
                  batch=16, workers=4, lr=0.05, **cfg_kw):
    from sparknet_tpu.models import lenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, make_mesh,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    sp = load_solver_prototxt_with_net(
        f'base_lr: {lr}\nmomentum: 0.9\nlr_policy: "fixed"\n',
        lenet(batch, batch))
    cfg = TrainerConfig(strategy=strategy, tau=2,
                        checkpoint_dir=str(ckpt_dir) if ckpt_dir else None,
                        checkpoint_every=every, checkpoint_keep=keep,
                        **cfg_kw)
    return DistributedTrainer(sp, make_mesh(workers), cfg, seed=seed)


def _batch(r, batch=16):
    rng = np.random.default_rng(100 + r)
    return {"data": rng.normal(size=(2, batch, 1, 28, 28)).astype(np.float32),
            "label": rng.integers(0, 10, size=(2, batch)).astype(np.float32)}


def test_round_checkpoint_resume_is_exact(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    for r in range(3):
        tr.data_cursor = {"next_round": r + 1}
        tr.train_round(_batch(r))
    # fresh trainer auto-resumes at round 3 with identical state
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None
    assert tr2.round == 3 and tr2.iter == 6
    assert tr2.data_cursor == {"next_round": 3}
    np.testing.assert_allclose(np.asarray(tr2.params["conv1"][0]),
                               np.asarray(tr.params["conv1"][0]))
    # one more round on both: bit-identical continuation (RNG restored too)
    tr.train_round(_batch(3))
    tr2.train_round(_batch(3))
    np.testing.assert_allclose(np.asarray(tr2.params["conv1"][0]),
                               np.asarray(tr.params["conv1"][0]))
    np.testing.assert_allclose(np.asarray(tr2.params["ip2"][0]),
                               np.asarray(tr.params["ip2"][0]))


def test_checkpoint_every_and_pruning(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d, every=2, keep=2)
    for r in range(8):
        tr.train_round(_batch(r))
    tr.flush_checkpoints()             # settle the async writer
    rounds = sorted(int(f[len("manifest_"):-len(".json")])
                    for f in os.listdir(d) if f.startswith("manifest_"))
    assert rounds == [6, 8]            # every 2 rounds, newest 2 kept
    assert sorted(f for f in os.listdir(d) if f.endswith(".npz")) == \
        ["ckpt_round_00000006.npz", "ckpt_round_00000008.npz"]


@pytest.mark.chaos
def test_corrupt_checkpoint_falls_back_to_previous_manifest(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    for r in range(3):
        tr.train_round(_batch(r))
    tr.flush_checkpoints()
    # scribble the NEWEST snapshot (round 3) — manifest checksum now lies
    faults.scribble(str(d / "ckpt_round_00000003.npz"))
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None
    assert tr2.round == 2              # fell back, did not crash
    assert tr2.resumed["file"] == "ckpt_round_00000002.npz"


@pytest.mark.chaos
def test_corrupt_ckpt_fault_injection_end_to_end(tmp_path, monkeypatch):
    """The writer-side corrupt_ckpt fault produces exactly the
    corrupt-newest layout, and auto-resume survives it."""
    d = tmp_path / "ck"
    monkeypatch.setenv("SPARKNET_FAULT", "corrupt_ckpt@round:3")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    tr = _make_trainer(d)
    for r in range(3):
        tr.train_round(_batch(r))
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "1")  # the restarted job
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None and tr2.round == 2
    # and the restarted job's own round-3 checkpoint is clean this time
    tr2.train_round(_batch(2))
    tr2.flush_checkpoints()
    blob = load_checkpoint(str(d / "ckpt_round_00000003.npz"))
    assert int(blob["round"]) == 3


def test_mesh_shape_mismatch_raises_not_skips(tmp_path):
    from sparknet_tpu.models import lenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, make_mesh,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    tr.train_round(_batch(0))
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.05\nmomentum: 0.9\nlr_policy: "fixed"\n', lenet(16, 16))
    cfg = TrainerConfig(strategy="local_sgd", tau=2, checkpoint_dir=str(d))
    with pytest.raises(ValueError, match="mesh shape|workers"):
        DistributedTrainer(sp, make_mesh(8), cfg, seed=0)


# ---------------------------------------------------------------------------
# end-to-end chaos: crash → automatic restart → exact recovery
# ---------------------------------------------------------------------------

def _clean_launch_env():
    saved = dict(os.environ)
    os.environ.pop("XLA_FLAGS", None)  # conftest's 8-device flag
    for k in list(os.environ):
        if k.startswith("SPARKNET_"):
            os.environ.pop(k)
    return saved


def _run_crash_restart(tmp_path, *, nprocs, devices_per_proc,
                       local_devices, fault):
    """Shared body: fault-free baseline vs ResilientRunner-supervised run
    with an injected crash; returns (runner, baseline npz, chaos npz,
    ckpt dir)."""
    base = str(tmp_path / "base.npz")
    out = str(tmp_path / "chaos.npz")
    ck = str(tmp_path / "ck")
    extra = ["--rounds", "4"]
    if local_devices:
        extra += ["--local-devices", str(local_devices)]

    saved = _clean_launch_env()
    try:
        from sparknet_tpu.tools.launch import launch_local
        rc = launch_local(
            [sys.executable, DRIVER, "--strategy", "sync", "--out", base]
            + extra,
            nprocs=nprocs, platform="cpu",
            devices_per_proc=devices_per_proc, timeout=300)
        assert rc == 0, f"fault-free run failed rc={rc}"

        runner = ResilientRunner(
            [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
             "--ckpt-dir", ck] + extra,
            nprocs=nprocs, platform="cpu",
            devices_per_proc=devices_per_proc, timeout=300,
            policy=RestartPolicy(max_restarts=2, backoff_base=0.2),
            extra_env={"SPARKNET_FAULT": fault})
        rc = runner.run()
    finally:
        os.environ.clear()
        os.environ.update(saved)

    assert rc == 0, f"job did not recover, rc={rc}"
    # exactly one failed attempt (the injected crash) then a clean recovery
    assert len(runner.attempts) == 2
    assert runner.attempts[0].returncode != 0
    assert runner.attempts[1].returncode == 0
    a, b = np.load(base), np.load(out)
    for k in a.files:
        if k.startswith("__"):
            continue
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                   err_msg=f"param {k} diverged after "
                                           f"crash-restart recovery")
    np.testing.assert_allclose(a["__scores__"], b["__scores__"],
                               rtol=1e-5, atol=1e-5)
    # the crash cost one round, not the run: manifests exist on disk
    assert any(f.startswith("manifest_") for f in os.listdir(ck))
    return runner, base, out, ck


@pytest.mark.chaos
def test_crash_restart_completes_and_matches_fault_free(tmp_path):
    """THE acceptance path: the worker dies at round 3 of 4
    (SPARKNET_FAULT=crash@round:3); ResilientRunner relaunches, the job
    auto-resumes from the newest valid manifest, and the final params
    equal a fault-free run of the same config — recovery is exact at
    round granularity."""
    runner, _, _, ck = _run_crash_restart(
        tmp_path, nprocs=1, devices_per_proc=None, local_devices=4,
        fault="crash@round:3")
    assert runner.attempts[0].returncode == 43  # the injected os._exit


@pytest.mark.chaos
def test_crash_restart_two_process_one_rank(tmp_path, multiprocess_cpu):
    """Same contract with a REAL 2-process mesh and only rank 1 dying:
    the supervisor must tear down the surviving rank and relaunch both.
    Skips on CPU backends without multiprocess computations (those rigs
    skip test_multihost identically)."""
    if not multiprocess_cpu:
        pytest.skip("CPU backend lacks multiprocess XLA computations")
    _run_crash_restart(
        tmp_path, nprocs=2, devices_per_proc=2, local_devices=None,
        fault="crash@round:3@rank:1")


# ---------------------------------------------------------------------------
# preemption (SNAPSHOT_STOP) x in-flight AsyncCheckpointWriter: a preempt
# that lands while a background checkpoint write is still queued must
# FLUSH the write, never tear it (the PR-2 x PR-5 interaction)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_preemption_guard_flushes_inflight_async_writer(tmp_path,
                                                        monkeypatch):
    import signal as _signal

    from sparknet_tpu.utils import checkpoint as ckpt_mod
    from sparknet_tpu.utils.signals import SolverAction, preemption_guard

    # slow the durable write down so the preemption provably arrives
    # while the writer job is still in the queue/in flight
    real_save = ckpt_mod.save_checkpoint

    def slow_save(path, tree):
        time.sleep(0.4)
        real_save(path, tree)

    monkeypatch.setattr(ckpt_mod, "save_checkpoint", slow_save)

    d = tmp_path / "ck"
    tr = _make_trainer(d)          # async checkpointing is the default
    tr.train_round(_batch(0))      # round-1 checkpoint enters the queue
    assert tr._ckpt_writer is not None
    pending_at_signal = tr._ckpt_writer.pending
    assert pending_at_signal >= 1  # the write is genuinely in flight

    with preemption_guard() as guard:
        os.kill(os.getpid(), _signal.SIGTERM)   # the preemption notice
        action = SolverAction.NONE
        for _ in range(200):       # delivery is at a bytecode boundary
            action = guard.check()
            if action != SolverAction.NONE:
                break
            time.sleep(0.01)
        assert action == SolverAction.SNAPSHOT_STOP
        # the driver's preemption sequence (multihost_driver.py): settle
        # in-flight rounds, one final checkpoint, durability barrier
        tr.drain()
        tr.save_round_checkpoint()
        tr.flush_checkpoints()     # must flush the queued write, not tear

    # every manifest on disk validates, and the newest is the final round
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None
    assert tr2.round == tr.round == 1
    assert np.array_equal(np.asarray(tr2.params["conv1"][0]),
                          np.asarray(tr.params["conv1"][0]))
    assert np.array_equal(np.asarray(tr2.params["ip2"][0]),
                          np.asarray(tr.params["ip2"][0]))


@pytest.mark.chaos
def test_sigterm_preemption_with_async_writer_driver_e2e(tmp_path):
    """End to end across processes: SIGTERM a live driver mid-run (async
    checkpoint writer active), expect a clean rc-0 exit with a durable
    final snapshot, then resume and finish — params bit-identical to an
    uninterrupted run."""
    import signal as _signal

    saved = _clean_launch_env()
    try:
        base = str(tmp_path / "base.npz")
        r = subprocess.run(
            [sys.executable, DRIVER, "--strategy", "sync", "--out", base,
             "--local-devices", "4", "--rounds", "5"],
            timeout=300, capture_output=True)
        assert r.returncode == 0, r.stdout.decode(errors="replace")

        out = str(tmp_path / "out.npz")
        ck = str(tmp_path / "ck")
        cmd = [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
               "--local-devices", "4", "--rounds", "5", "--ckpt-dir", ck]
        p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 240
        tail = []
        for line in iter(p.stdout.readline, b""):
            tail.append(line)
            if b"round 1 done" in line:
                p.send_signal(_signal.SIGTERM)
                break
            assert time.monotonic() < deadline, b"".join(tail).decode()
        rest, _ = p.communicate(timeout=240)
        text = (b"".join(tail) + rest).decode(errors="replace")
        assert p.returncode == 0, text     # preemption is a CLEAN exit
        assert "preempted; stopped cleanly" in text
        assert not os.path.exists(out)     # stopped, not finished
        assert any(f.startswith("manifest_") for f in os.listdir(ck))

        r = subprocess.run(cmd, timeout=300, capture_output=True)
        text2 = r.stdout.decode(errors="replace")
        assert r.returncode == 0, text2
        assert "driver: resumed at round" in text2
    finally:
        os.environ.clear()
        os.environ.update(saved)

    a, b = np.load(base), np.load(out)
    for k in a.files:
        if k.startswith("__"):
            continue
        assert np.array_equal(a[k], b[k]), \
            f"param {k} diverged across preempt/resume"


@pytest.mark.chaos
@pytest.mark.slow
def test_hang_restart_recovers_via_timeout(tmp_path):
    """A HUNG worker (not dead — blocked forever) is only detectable by
    the job timeout: the supervisor must kill it (rc 124) and the restart
    must still recover from the checkpoint."""
    out = str(tmp_path / "hang.npz")
    ck = str(tmp_path / "ck")
    saved = _clean_launch_env()
    try:
        runner = ResilientRunner(
            [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
             "--local-devices", "4", "--rounds", "2", "--ckpt-dir", ck],
            nprocs=1, platform="cpu", timeout=60,
            policy=RestartPolicy(max_restarts=1, backoff_base=0.2),
            extra_env={"SPARKNET_FAULT": "hang@round:1"})
        rc = runner.run()
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert rc == 0, f"hung job did not recover, rc={rc}"
    assert [a.returncode for a in runner.attempts] == [124, 0]
    assert os.path.exists(out)


# ---------------------------------------------------------------------------
# jittered retry backoff (satellite: anti-thundering-herd)
# ---------------------------------------------------------------------------

def test_backoff_delays_jitter_bounds_and_validation():
    import random
    base = list(backoff_delays(4, 1.0, 2.0, 10.0))
    jittered = list(backoff_delays(4, 1.0, 2.0, 10.0, jitter=0.5,
                                   rng=random.Random(7)))
    assert len(jittered) == len(base) == 3
    for d, j in zip(base, jittered):
        assert d * 0.5 <= j <= d * 1.5
    assert jittered != base                  # jitter actually moved them
    # two processes (different rng seeds) must NOT sleep in lockstep
    a = list(backoff_delays(3, 1.0, jitter=0.3, rng=random.Random(1)))
    b = list(backoff_delays(3, 1.0, jitter=0.3, rng=random.Random(2)))
    assert a != b
    with pytest.raises(ValueError, match="jitter"):
        list(backoff_delays(3, 1.0, jitter=1.5))


def test_retry_call_accepts_jitter():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise OSError("blip")
        return "ok"

    sleeps = []
    assert retry_call(flaky, attempts=3, base_delay=1.0, jitter=0.5,
                      sleep=sleeps.append) == "ok"
    assert len(sleeps) == 1 and 0.5 <= sleeps[0] <= 1.5


# ---------------------------------------------------------------------------
# resume_latest edge cases (satellite)
# ---------------------------------------------------------------------------

def test_resume_latest_empty_and_missing_dir(tmp_path):
    tr = _make_trainer(tmp_path / "empty")          # dir never written to
    assert tr.resumed is None and tr.round == 0
    assert tr.resume_latest(str(tmp_path / "never_created")) is None


def test_resume_latest_all_manifests_corrupt(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    for r in range(2):
        tr.train_round(_batch(r))
    tr.flush_checkpoints()
    for f in os.listdir(d):
        if f.startswith("manifest_"):
            (d / f).write_text("{ not json at all")
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is None and tr2.round == 0   # fresh start, no crash


def test_resume_latest_mixed_valid_and_corrupt(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    for r in range(3):
        tr.train_round(_batch(r))
    tr.flush_checkpoints()
    # newest manifest: unparsable JSON; next: points at a missing file;
    # round 1 stays intact — resume must land exactly there
    (d / "manifest_00000003.json").write_text("!!")
    m2 = json.loads((d / "manifest_00000002.json").read_text())
    m2["file"] = "ckpt_round_99999999.npz"
    (d / "manifest_00000002.json").write_text(json.dumps(m2))
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None
    assert tr2.round == 1
    assert tr2.resumed["file"] == "ckpt_round_00000001.npz"


def test_pruning_keeps_exactly_checkpoint_keep_newest(tmp_path):
    d = tmp_path / "ck"
    tr = _make_trainer(d, keep=2)
    for r in range(5):
        tr.train_round(_batch(r))
    tr.flush_checkpoints()
    rounds = sorted(int(f[len("manifest_"):-len(".json")])
                    for f in os.listdir(d) if f.startswith("manifest_"))
    assert rounds == [4, 5]
    npzs = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    assert npzs == ["ckpt_round_00000004.npz", "ckpt_round_00000005.npz"]


# ---------------------------------------------------------------------------
# crash-safe checkpoint writes (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kill_during_npz_write_leaves_no_referenced_garbage(tmp_path,
                                                            monkeypatch):
    """A worker killed INSIDE the npz write (before the atomic rename)
    must leave no final-name npz, no manifest, and a resumable dir.
    Pinned to the SYNCHRONOUS write path (the kill is simulated by an
    exception through the caller's stack); the async-writer variant is
    test_async_ckpt_crash_in_background_write."""
    monkeypatch.setenv("SPARKNET_ASYNC_CKPT", "0")
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    for r in range(2):
        tr.train_round(_batch(r))

    class _Killed(BaseException):
        pass

    real_replace = os.replace

    def killed_replace(src, dst):
        if dst.endswith(".npz"):           # die before the rename lands
            raise _Killed()
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", killed_replace)
    with pytest.raises(_Killed):
        tr.train_round(_batch(2))
    monkeypatch.setattr(os, "replace", real_replace)
    names = set(os.listdir(d))
    assert "ckpt_round_00000003.npz" not in names
    assert "manifest_00000003.json" not in names
    assert any(".tmp." in n for n in names)         # the orphan temp
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None and tr2.round == 2
    # the next successful checkpoint sweeps the orphan temp away
    tr2.train_round(_batch(2))
    assert not any(".tmp." in n for n in os.listdir(d))


@pytest.mark.chaos
def test_crash_between_npz_and_manifest_is_invisible_to_resume(tmp_path,
                                                               monkeypatch):
    """The crash_in_ckpt fault kills in the torn-write window: npz
    durable, manifest never written.  resume_latest must skip the orphan
    npz (no manifest references it) and land on the previous round.
    Synchronous-path variant (the fake _exit raises through train_round);
    the async window is test_async_ckpt_crash_in_background_write."""
    monkeypatch.setenv("SPARKNET_ASYNC_CKPT", "0")
    d = tmp_path / "ck"
    monkeypatch.setenv("SPARKNET_FAULT", "crash_in_ckpt@round:3")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")

    class _Killed(BaseException):
        pass

    def fake_exit(code):
        raise _Killed()

    import sparknet_tpu.utils.faults as F
    monkeypatch.setattr(F.get_injector(), "_exit", fake_exit)
    tr = _make_trainer(d)
    tr.train_round(_batch(0))
    tr.train_round(_batch(1))
    with pytest.raises(_Killed):
        tr.train_round(_batch(2))          # dies mid-checkpoint of round 3
    names = set(os.listdir(d))
    assert "ckpt_round_00000003.npz" in names       # npz IS durable...
    assert "manifest_00000003.json" not in names    # ...but unreferenced
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "1")   # the restart
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None and tr2.round == 2
    # the restarted job replays round 2 and overwrites the orphan cleanly
    tr2.train_round(_batch(2))
    blob = load_checkpoint(str(d / "ckpt_round_00000003.npz"))
    assert int(blob["round"]) == 3


# ---------------------------------------------------------------------------
# elastic degraded-mode resume (tentpole: re-form on the survivors)
# ---------------------------------------------------------------------------

def test_elastic_resume_shrink_preserves_consensus_params(tmp_path):
    """4-worker sync job checkpoints; a 3-worker elastic trainer resumes
    it: the averaged params ARE the consensus and must restore exactly."""
    d = tmp_path / "ck"
    a = _make_trainer(d, strategy="sync", batch=24, workers=4, lr=0.005)
    for r in range(2):
        a.train_round(_batch(r, 24))
    b = _make_trainer(d, seed=99, strategy="sync", batch=24, workers=3, lr=0.005,
                      elastic=True)
    assert b.resumed is not None
    assert b.round == 2 and b.iter == a.iter
    np.testing.assert_array_equal(np.asarray(b.params["conv1"][0]),
                                  np.asarray(a.params["conv1"][0]))
    # the degraded world trains on: 24-row batches over 3 workers
    loss = b.train_round(_batch(2, 24))
    assert np.isfinite(loss)


def test_elastic_resume_without_flag_still_raises(tmp_path):
    d = tmp_path / "ck"
    a = _make_trainer(d, strategy="sync", batch=24, workers=4, lr=0.005)
    a.train_round(_batch(0, 24))
    with pytest.raises(ValueError, match="elastic"):
        _make_trainer(d, seed=99, strategy="sync", batch=24, workers=3, lr=0.005)


def test_elastic_retier_local_sgd_state_shrink_and_grow(tmp_path):
    """Per-worker optimizer state re-tiers deterministically: survivor i
    inherits saved row i mod saved_n (shrink drops the dead rows; a
    rejoined worker is seeded from row 0)."""
    d = tmp_path / "ck"
    a = _make_trainer(d, batch=24, workers=4, lr=0.005)      # local_sgd
    for r in range(2):
        a.train_round(_batch(r, 24))

    def rows(tr):
        leaf = jax.tree_util.tree_leaves(tr.state)[0]
        return np.asarray(leaf)

    import jax
    a_rows = rows(a)
    assert a_rows.shape[0] == 4
    b = _make_trainer(d, seed=99, batch=24, workers=3, lr=0.005, elastic=True)
    b_rows = rows(b)
    assert b_rows.shape[0] == 3
    np.testing.assert_array_equal(b_rows, a_rows[:3])
    loss = b.train_round(_batch(2, 24))            # degraded world trains
    assert np.isfinite(loss)
    # grow (rejoin): a 4-worker trainer resumes the 3-worker checkpoint
    c = _make_trainer(d, seed=7, batch=24, workers=4, lr=0.005, elastic=True)
    c_rows = rows(c)
    assert c_rows.shape[0] == 4
    np.testing.assert_array_equal(c_rows[3], c_rows[0])   # seeded from row 0
    loss = c.train_round(_batch(c.round, 24))
    assert np.isfinite(loss)


@pytest.mark.chaos
def test_elastic_reform_matches_native_3worker_run_bit_for_bit(tmp_path):
    """THE elastic acceptance contract: from the re-form point, the
    elastic continuation (4-worker checkpoint resumed on 3 workers) is
    bit-for-bit the 3-worker fault-free run from the same consensus
    state.  The 'native' side resumes a checkpoint REWRITTEN as a
    genuine 3-worker checkpoint (elastic=False), so the two runs share
    state but take entirely different resume paths."""
    import jax
    d4 = tmp_path / "ck4"
    a = _make_trainer(d4, batch=24, workers=4, lr=0.005)     # local_sgd, the
    for r in range(2):                             # re-tier-bearing case
        a.train_round(_batch(r, 24))
    a.flush_checkpoints()

    # elastic side: resume the 4-worker checkpoint on 3 workers
    b = _make_trainer(d4, seed=99, batch=24, workers=3, lr=0.005, elastic=True)
    assert b.resumed is not None and b.round == 2

    # native side: rewrite the same state as a true 3-worker checkpoint
    blob = load_checkpoint(str(d4 / "ckpt_round_00000002.npz"))
    blob["n_workers"] = np.int64(3)
    blob["state"] = jax.tree_util.tree_map(
        lambda x: np.asarray(x)[:3] if np.asarray(x).ndim else x,
        blob["state"])
    d3 = tmp_path / "ck3"
    c = _make_trainer(None, seed=7, batch=24, workers=3, lr=0.005)
    c._apply_blob(blob)
    c.round = 2

    for r in range(2, 4):                          # the shared continuation
        lb = b.train_round(_batch(r, 24))
        lc = c.train_round(_batch(r, 24))
        assert lb == lc
    for name in ("conv1", "ip2"):
        np.testing.assert_array_equal(
            np.asarray(b.params[name][0]), np.asarray(c.params[name][0]),
            err_msg=f"elastic re-form diverged from the native 3-worker "
                    f"run at {name}")


def test_elastic_retile_sharded_matches_native_2worker_run_bit_for_bit(
        tmp_path):
    """The elastic contract survives tensor sharding: a 4-worker
    checkpoint written as per-shard npz tiles (shard="auto" +
    shard_checkpoint=True, so ip1 lives as four 125-row tiles on disk)
    resumes on 2 workers — a DIFFERENT plan with different tile shapes —
    and the continuation is bit-for-bit the 2-worker run started from
    the same consensus state natively.  Blobs carry full logical leaves
    (the per-shard layout is a write-side split), so the re-tile is a
    re-slice, not arithmetic."""
    import jax
    d4 = tmp_path / "ck4"
    a = _make_trainer(d4, batch=24, workers=4, lr=0.005, shard="auto",
                      shard_checkpoint=True)
    assert a.shard_plan is not None and a.shard_plan.n_shards == 4
    for r in range(2):
        a.train_round(_batch(r, 24))
    a.flush_checkpoints()
    tiles = sorted(p.name for p in d4.glob("ckpt_round_00000002.shard*"))
    assert len(tiles) == 4, tiles

    # elastic side: re-tile the 4-shard tiles onto a 2-shard plan
    b = _make_trainer(d4, seed=99, batch=24, workers=2, lr=0.005,
                      shard="auto", shard_checkpoint=True, elastic=True)
    assert b.resumed is not None and b.round == 2
    assert b.shard_plan is not None and b.shard_plan.n_shards == 2

    # native side: the same consensus applied to a fresh sharded
    # 2-worker trainer that never saw the 4-worker checkpoint
    blob = a._host_blob()
    blob["n_workers"] = np.int64(2)
    blob["state"] = jax.tree_util.tree_map(
        lambda x: np.asarray(x)[:2] if np.asarray(x).ndim else x,
        blob["state"])
    c = _make_trainer(None, seed=7, batch=24, workers=2, lr=0.005,
                      shard="auto")
    c._apply_blob(blob)
    c.round = 2

    for r in range(2, 4):
        lb = b.train_round(_batch(r, 24))
        lc = c.train_round(_batch(r, 24))
        assert lb == lc
    for name in ("conv1", "ip1", "ip2"):
        np.testing.assert_array_equal(
            np.asarray(b.params[name][0]), np.asarray(c.params[name][0]),
            err_msg=f"sharded elastic re-tile diverged from the native "
                    f"2-worker run at {name}")


# ---------------------------------------------------------------------------
# numerical-integrity guard (tentpole: never checkpoint poisoned weights)
# ---------------------------------------------------------------------------

def test_guard_requires_checkpoint_dir():
    with pytest.raises(ValueError, match="guard_numerics"):
        _make_trainer(None, guard_numerics=True)


@pytest.mark.chaos
def test_nan_inject_rolls_back_and_matches_fault_free(tmp_path, monkeypatch):
    """Acceptance: nan_inject at round 2 trips the guard, the poisoned
    round is dropped, the checkpoint chain stays NaN/Inf-free, and the
    run converges to the fault-free result EXACTLY (rollback restores
    params+state+RNG, and the replayed round is clean)."""
    clean_dir, chaos_dir = tmp_path / "clean", tmp_path / "chaos"
    clean = _make_trainer(clean_dir, guard_numerics=True)
    clean_losses = [clean.train_round(_batch(r)) for r in range(4)]

    monkeypatch.setenv("SPARKNET_FAULT", "nan_inject@round:2")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    faults.reset_injector()      # re-arm the once-per-process fault
    tr = _make_trainer(chaos_dir, guard_numerics=True)
    losses = []
    while tr.round < 4:
        losses.append(tr.train_round(_batch(tr.round)))
    tr.flush_checkpoints()
    assert tr.guard_trips == 1
    assert sum(1 for l in losses if not np.isfinite(l)) == 1  # the dropped one
    # checkpoint chain: every surviving snapshot is finite
    for f in sorted(os.listdir(chaos_dir)):
        if f.endswith(".npz"):
            blob = load_checkpoint(str(chaos_dir / f))
            import jax
            for leaf in jax.tree_util.tree_leaves(blob["params"]):
                assert np.all(np.isfinite(leaf)), f"NaN survived in {f}"
    # exact recovery: the fault-free trajectory, bit for bit
    np.testing.assert_array_equal(np.asarray(tr.params["conv1"][0]),
                                  np.asarray(clean.params["conv1"][0]))
    finite = [l for l in losses if np.isfinite(l)]
    np.testing.assert_allclose(finite, clean_losses, rtol=1e-6)


def test_guard_loss_spike_detection(tmp_path):
    tr = _make_trainer(tmp_path / "ck", guard_numerics=True,
                       loss_spike_factor=3.0)
    tr._loss_history = [1.0, 1.1, 0.9]
    assert tr._poison_reason(10.0) is not None        # 10 > 3 x ~1.0
    assert tr._poison_reason(2.0) is None
    assert tr._poison_reason(float("inf")) is not None
    assert tr._poison_reason(float("nan")) is not None


def test_guard_lr_backoff_applies_and_checkpoints(tmp_path, monkeypatch):
    monkeypatch.setenv("SPARKNET_FAULT", "nan_inject@round:1")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    faults.reset_injector()      # re-arm the once-per-process fault
    d = tmp_path / "ck"
    tr = _make_trainer(d, guard_numerics=True, guard_lr_backoff=0.5)
    while tr.round < 3:
        tr.train_round(_batch(tr.round))
    assert tr.guard_trips == 1
    assert tr.lr_scale == pytest.approx(0.5)
    # the backed-off scale persists through checkpoint/resume
    monkeypatch.setenv("SPARKNET_FAULT", "")
    tr2 = _make_trainer(d, seed=99, guard_numerics=True)
    assert tr2.lr_scale == pytest.approx(0.5)


def test_guard_max_trips_raises_training_diverged(tmp_path, monkeypatch):
    from sparknet_tpu.parallel import TrainingDivergedError
    monkeypatch.setenv("SPARKNET_FAULT", "nan_inject@round:1")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    faults.reset_injector()      # re-arm the once-per-process fault
    tr = _make_trainer(tmp_path / "ck", guard_numerics=True,
                       guard_max_trips=0)
    tr.train_round(_batch(0))
    with pytest.raises(TrainingDivergedError, match="guard_max_trips"):
        tr.train_round(_batch(1))


@pytest.mark.chaos
def test_nan_inject_driver_end_to_end(tmp_path):
    """The guard through the real driver: a single run (no relaunch —
    rollback is in-process) absorbs the poison and lands on the
    fault-free params bit-for-bit."""
    base, out = str(tmp_path / "base.npz"), str(tmp_path / "chaos.npz")
    saved = _clean_launch_env()
    try:
        from sparknet_tpu.tools.launch import launch_local
        common = [sys.executable, DRIVER, "--strategy", "sync",
                  "--local-devices", "4", "--rounds", "4", "--guard"]
        rc = launch_local(
            common + ["--out", base, "--ckpt-dir", str(tmp_path / "ck_a")],
            nprocs=1, platform="cpu", timeout=300)
        assert rc == 0
        rc = launch_local(
            common + ["--out", out, "--ckpt-dir", str(tmp_path / "ck_b")],
            nprocs=1, platform="cpu", timeout=300,
            extra_env={"SPARKNET_FAULT": "nan_inject@round:2"})
        assert rc == 0
    finally:
        os.environ.clear()
        os.environ.update(saved)
    a, b = np.load(base), np.load(out)
    assert int(b["__guard_trips__"]) == 1 and int(a["__guard_trips__"]) == 0
    for k in a.files:
        if k.startswith("__"):
            continue
        assert np.all(np.isfinite(b[k])), f"NaN reached final params at {k}"
        np.testing.assert_array_equal(
            a[k], b[k], err_msg=f"guard recovery diverged at {k}")


# ---------------------------------------------------------------------------
# zero-stall outer loop: async checkpointing + deferred guard/audit harvest
# ---------------------------------------------------------------------------

def test_harvest_lag_retention_validation(tmp_path):
    """harvest_lag must not outrun checkpoint retention: a poison at
    round r surfaces up to lag (+ audit cadence) rounds later, and the
    pre-poison checkpoint must still exist then."""
    with pytest.raises(ValueError, match="harvest_lag must be >= 0"):
        _make_trainer(tmp_path / "ck", harvest_lag=-1)
    with pytest.raises(ValueError, match="outruns the checkpoint"):
        _make_trainer(tmp_path / "ck", keep=2, guard_numerics=True,
                      harvest_lag=2)
    with pytest.raises(ValueError, match="outruns the checkpoint"):
        # the audit's own cadence adds to the detection latency
        _make_trainer(tmp_path / "ck", keep=3, audit_every=1,
                      harvest_lag=2)
    # enough retention: fine (and lag without guard/audit needs none)
    _make_trainer(tmp_path / "ck", keep=4, audit_every=1,
                  guard_numerics=True, harvest_lag=2)
    _make_trainer(None, harvest_lag=3)


def test_async_pipelined_loop_matches_sync_bit_for_bit(tmp_path):
    """THE zero-stall parity contract: with checkpointing + numerics
    guard + cross-replica audit ALL enabled, the pipelined loop
    (harvest_lag=2, async checkpoint writer) produces the same
    per-round losses and bit-identical params as the fully synchronous
    loop — the tentpole is a latency optimization, not a semantics
    change."""
    kw = dict(lr=0.005, keep=4, guard_numerics=True, audit_every=1)
    sync = _make_trainer(tmp_path / "sync", **kw)
    sync_losses = [sync.train_round(_batch(r)) for r in range(5)]
    sync.drain()
    tr = _make_trainer(tmp_path / "async", harvest_lag=2, **kw)
    first = tr.train_round(_batch(0))
    assert np.isnan(first)          # nothing harvested yet — by design
    while tr.round < 5:
        tr.train_round(_batch(tr.round))
    losses = tr.drain()
    assert [losses[r] for r in range(5)] == sync_losses
    for name in ("conv1", "ip2"):
        np.testing.assert_array_equal(
            np.asarray(tr.params[name][0]),
            np.asarray(sync.params[name][0]),
            err_msg=f"pipelined loop diverged at {name}")
    # both modes wrote the same checkpoint chain (content-identical)
    for d in (tmp_path / "sync", tmp_path / "async"):
        assert "manifest_00000005.json" in os.listdir(d)
    a = load_checkpoint(str(tmp_path / "sync" / "ckpt_round_00000005.npz"))
    b = load_checkpoint(str(tmp_path / "async" / "ckpt_round_00000005.npz"))
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                    jax.tree_util.tree_leaves(b["params"])):
        np.testing.assert_array_equal(x, y)
    # the async loop recorded its (near-zero) stalls under the same keys
    assert set(tr.stall_s) == set(sync.stall_s) >= {
        "loss_fetch", "finite_check", "audit_fetch", "checkpoint"}


def test_async_ckpt_escape_hatch_restores_sync_path(tmp_path, monkeypatch):
    """SPARKNET_ASYNC_CKPT=0 restores today's fully synchronous write:
    durable before train_round returns, no writer thread at all."""
    monkeypatch.setenv("SPARKNET_ASYNC_CKPT", "0")
    d = tmp_path / "ck"
    tr = _make_trainer(d)
    tr.train_round(_batch(0))
    assert tr._ckpt_writer is None
    assert "manifest_00000001.json" in os.listdir(d)
    # flipping the env back re-enables the async tier mid-run
    monkeypatch.delenv("SPARKNET_ASYNC_CKPT")
    tr.train_round(_batch(1))
    assert tr._ckpt_writer is not None
    tr.flush_checkpoints()
    assert "manifest_00000002.json" in os.listdir(d)


@pytest.mark.chaos
def test_async_ckpt_crash_in_background_write(tmp_path, monkeypatch):
    """crash_in_ckpt with the ASYNC writer: the kill lands on the writer
    thread inside the torn window (npz durable, manifest not yet), the
    failure surfaces at the flush barrier — not silently — and resume
    treats the orphan npz as if the checkpoint never happened."""
    d = tmp_path / "ck"
    monkeypatch.setenv("SPARKNET_FAULT", "crash_in_ckpt@round:2")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")

    class _Killed(BaseException):
        pass

    def fake_exit(code):
        raise _Killed()

    faults.reset_injector()
    monkeypatch.setattr(faults.get_injector(), "_exit", fake_exit)
    tr = _make_trainer(d)
    tr.train_round(_batch(0))
    tr.train_round(_batch(1))      # round-2 job dies on the writer thread
    with pytest.raises(_Killed):
        tr.flush_checkpoints()
    names = set(os.listdir(d))
    assert "ckpt_round_00000002.npz" in names        # npz IS durable...
    assert "manifest_00000002.json" not in names     # ...but unreferenced
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "1")    # the restart
    tr2 = _make_trainer(d, seed=99)
    assert tr2.resumed is not None and tr2.round == 1


@pytest.mark.chaos
def test_async_guard_trip_at_harvest_lag_bit_for_bit(tmp_path,
                                                     monkeypatch):
    """Acceptance: nan_inject at round 2 under harvest_lag=2 — the
    verdict arrives up to two rounds late, every in-flight round after
    the poison is discarded, newer (poison-descended) checkpoints are
    pruned, and the replay lands bit-for-bit on the fault-free run."""
    kw = dict(lr=0.005, keep=4, guard_numerics=True)
    clean = _make_trainer(tmp_path / "clean", **kw)
    clean_losses = [clean.train_round(_batch(r)) for r in range(5)]
    clean.drain()

    monkeypatch.setenv("SPARKNET_FAULT", "nan_inject@round:2")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    faults.reset_injector()
    tr = _make_trainer(tmp_path / "chaos", harvest_lag=2, **kw)
    while tr.round < 5:
        tr.train_round(_batch(tr.round))
    losses = tr.drain()
    assert tr.guard_trips == 1
    assert [losses[r] for r in range(5)] == clean_losses
    for name in ("conv1", "ip2"):
        np.testing.assert_array_equal(
            np.asarray(tr.params[name][0]),
            np.asarray(clean.params[name][0]),
            err_msg=f"deferred guard recovery diverged at {name}")
    # no checkpoint on disk carries the poison (lag-window snapshots
    # were pruned on the trip, then re-written clean by the replay)
    import jax
    for f in sorted(os.listdir(tmp_path / "chaos")):
        if f.endswith(".npz"):
            blob = load_checkpoint(str(tmp_path / "chaos" / f))
            for leaf in jax.tree_util.tree_leaves(blob["params"]):
                assert np.all(np.isfinite(leaf)), f"NaN survived in {f}"


@pytest.mark.chaos
def test_async_audit_trip_at_harvest_lag_bit_for_bit(tmp_path,
                                                     monkeypatch):
    """bitflip_params at round 3 under harvest_lag=2 and audit_every=1:
    the fingerprint mismatch is harvested late, rolls back to a
    checkpoint at or before the last PASSED audit, and the replay (flip
    is once-per-process) finishes bit-for-bit fault-free."""
    kw = dict(lr=0.005, keep=5, audit_every=1)
    clean = _make_trainer(tmp_path / "clean", **kw)
    while clean.round < 6:
        clean.train_round(_batch(clean.round))
    clean.drain()
    assert clean.audit_trips == 0

    monkeypatch.setenv("SPARKNET_FAULT", "bitflip_params@rank:1@round:3")
    monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    faults.reset_injector()
    tr = _make_trainer(tmp_path / "chaos", harvest_lag=2, **kw)
    while tr.round < 6:
        tr.train_round(_batch(tr.round))
    losses = tr.drain()
    assert tr.audit_trips == 1
    assert [losses[r] for r in range(6)] == \
        [clean.round_losses[r] for r in range(6)]
    for name in ("conv1", "ip2"):
        np.testing.assert_array_equal(
            np.asarray(tr.params[name][0]),
            np.asarray(clean.params[name][0]),
            err_msg=f"deferred audit recovery diverged at {name}")


@pytest.mark.chaos
def test_nan_inject_driver_end_to_end_pipelined(tmp_path):
    """The guard acceptance path re-run under the async loop: the real
    driver with --harvest-lag 2, nan_inject at round 2, absorbs the
    poison through the DEFERRED verdict and still lands on the
    fault-free params bit-for-bit."""
    base, out = str(tmp_path / "base.npz"), str(tmp_path / "chaos.npz")
    saved = _clean_launch_env()
    try:
        from sparknet_tpu.tools.launch import launch_local
        common = [sys.executable, DRIVER, "--strategy", "sync",
                  "--local-devices", "4", "--rounds", "4", "--guard",
                  "--harvest-lag", "2"]
        rc = launch_local(
            common + ["--out", base, "--ckpt-dir", str(tmp_path / "ck_a")],
            nprocs=1, platform="cpu", timeout=300)
        assert rc == 0
        rc = launch_local(
            common + ["--out", out, "--ckpt-dir", str(tmp_path / "ck_b")],
            nprocs=1, platform="cpu", timeout=300,
            extra_env={"SPARKNET_FAULT": "nan_inject@round:2"})
        assert rc == 0
    finally:
        os.environ.clear()
        os.environ.update(saved)
    a, b = np.load(base), np.load(out)
    assert int(b["__guard_trips__"]) == 1 and int(a["__guard_trips__"]) == 0
    for k in a.files:
        if k.startswith("__"):
            continue
        assert np.all(np.isfinite(b[k])), f"NaN reached final params at {k}"
        np.testing.assert_array_equal(
            a[k], b[k], err_msg=f"pipelined guard recovery diverged at {k}")


def test_roundbench_smoke(tmp_path):
    """tools/roundbench.py (the SPARKNET_ROUNDBENCH=1 CI gate) passes
    in-process: the async loop reproduces the sync loop's losses,
    params, and newest checkpoint, and reports the stall accounting."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "roundbench", os.path.join(REPO, "tools", "roundbench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "rb.json"
    assert mod.main(["--rounds", "3", "--out", str(out)]) == 0
    rec = json.loads(out.read_text())
    assert rec["ok"] is True and rec["failures"] == []
    assert rec["stall_total_sync_s"] >= 0


@pytest.mark.chaos
@pytest.mark.slow
def test_ssh_mode_crash_restart_via_shim(tmp_path, multiprocess_cpu):
    """ResilientRunner over launch_ssh (shimmed ssh, as in
    test_multihost.test_ssh_mode_via_shim): a crashed 'host' is restarted
    and the job completes from its checkpoint."""
    if not multiprocess_cpu:
        pytest.skip("CPU backend lacks multiprocess XLA computations")
    shim_dir = tmp_path / "bin"
    shim_dir.mkdir()
    shim = shim_dir / "ssh"
    shim.write_text("#!/bin/bash\nexec bash -c \"$4\"\n")
    shim.chmod(0o755)

    out = str(tmp_path / "ssh_chaos.npz")
    ck = str(tmp_path / "ck")
    saved = _clean_launch_env()
    os.environ["PATH"] = f"{shim_dir}:{os.environ['PATH']}"
    try:
        runner = ResilientRunner(
            [sys.executable, DRIVER, "--strategy", "sync", "--out", out,
             "--local-devices", "2", "--rounds", "3", "--ckpt-dir", ck],
            hosts=["127.0.0.1", "localhost"], cwd=REPO, timeout=300,
            policy=RestartPolicy(max_restarts=2, backoff_base=0.2),
            extra_env={"SPARKNET_FAULT": "crash@round:2@rank:1"})
        rc = runner.run()
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert rc == 0, f"ssh-mode job did not recover, rc={rc}"
    assert len(runner.attempts) == 2
    assert os.path.exists(out)
