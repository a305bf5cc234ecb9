"""The program's spans on the profiler's clock (ISSUE 25).

``telemetry.span`` enters a ``jax.profiler.TraceAnnotation`` named
``sparknet.<name>``, so under a profiler session (a CPU session records
annotations the same way a TPU one does) the step, the feed and the round
leave their spans in the ``.xplane.pb``: by name, children inside parents,
each carrying the ordinal of its unit of work.  With no session and no
``SPARKNET_TRACE_DIR`` nothing is written, under ``SPARKNET_TELEMETRY=0``
the span is the shared no-op, and a session changes no loss.
"""

import glob
import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.data import device_feed
from sparknet_tpu.data.pipeline import FeedStats
from sparknet_tpu.data.records import convert_to_shards, records_feed
from sparknet_tpu.models import lenet
from sparknet_tpu.models.dsl import java_data_layer, layer, net_param
from sparknet_tpu.ops.augment import AugmentSpec
from sparknet_tpu.parallel import (
    DistributedTrainer, TrainerConfig, device_crop_mirror_mean, make_mesh,
)
from sparknet_tpu.proto import load_solver_prototxt_with_net
from sparknet_tpu.proto.caffe_pb import Phase
from sparknet_tpu.solvers import Solver
from sparknet_tpu.utils import telemetry

SOLVER_TXT = ("base_lr: 0.0005\nmomentum: 0.9\nweight_decay: 0.004\n"
              "lr_policy: \"fixed\"\n")
SPEC = AugmentSpec(crop=28, mirror=True, mean=[16.0], scale=1.0 / 255,
                   train=True)
BATCH, RECORDS = 8, 40


@pytest.fixture
def plane(monkeypatch):
    """The telemetry plane re-read from a clean environment, and again
    after the test."""
    for k in ("SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
              "SPARKNET_METRICS_SNAP"):
        monkeypatch.delenv(k, raising=False)
    telemetry.reset()
    yield monkeypatch
    telemetry.reset()


def raw_batches(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [{"data": rng.integers(0, 256, size=(BATCH, 1, 32, 32)
                                  ).astype(np.uint8),
             "label": rng.integers(0, 10, size=BATCH).astype(np.float32)}
            for _ in range(n)]


def tiny_solver(batches):
    solver = Solver(load_solver_prototxt_with_net(SOLVER_TXT, lenet(16, 16)),
                    seed=0)
    solver.set_augment(SPEC, device=True)
    solver.set_train_data(batches)
    return solver


def shard_dir(tmp_path) -> str:
    rng = np.random.default_rng(1)
    out = str(tmp_path / "shards")
    convert_to_shards(
        ((rng.integers(0, 256, size=(1, 32, 32)).astype(np.uint8),
          int(rng.integers(0, 10))) for _ in range(RECORDS)),
        out, shard_bytes=10 * 1100)          # a few shards
    assert len(os.listdir(out)) >= 3
    return out


def fed(source, stats=None, host_stats=None):
    lp = layer("data", "Data", [], ["data", "label"], data_param={
        "source": source, "batch_size": BATCH, "backend": "RECORDS"})
    host = records_feed(lp, Phase.TRAIN, raw=True, workers=2,
                        stats=host_stats)
    return device_feed(host, depth=2, stats=stats)


def tiny_trainer(raw=False):
    """``raw``: the feed ships uint8 and the mean is per-channel values
    broadcast to an image, as the benchmark's round has them."""
    crop, full = 6, 8
    net = net_param("devpre", [
        java_data_layer("input", ["data", "label"], None,
                        (8, 1, crop, crop), (8,)),
        layer("ip", "InnerProduct", ["data"], ["ip"],
              inner_product_param={"num_output": 4,
                                   "weight_filler": {"type": "xavier"}}),
        layer("loss", "SoftmaxWithLoss", ["ip", "label"], ["loss"]),
    ])
    sp = load_solver_prototxt_with_net(SOLVER_TXT, net)
    rng = np.random.default_rng(2)
    mean = rng.normal(size=(1, full, full)).astype(np.float32)
    data = rng.normal(size=(2, 8, 1, full, full)).astype(np.float32)
    if raw:
        mean = np.full((1, full, full), 16.0, np.float32)
        data = rng.integers(0, 256, size=data.shape).astype(np.uint8)
    trainer = DistributedTrainer(
        sp, make_mesh(2), TrainerConfig(
            strategy="local_sgd", tau=2,
            device_preprocess=device_crop_mirror_mean(
                crop, mirror=True, mean=mean)),
        seed=0)
    batches = {"data": data,
               "label": rng.integers(0, 4, size=(2, 8)).astype(np.float32)}
    return trainer, batches


def run_steps(tmp_path):
    solver = tiny_solver(itertools.cycle(raw_batches()))
    return [float(solver.step(2)) for _ in range(2)]


def run_feed(tmp_path):
    with fed(shard_dir(tmp_path)) as feed:
        solver = tiny_solver(feed)
        return [float(solver.step(2)) for _ in range(2)]


def run_rounds(tmp_path):
    trainer, batches = tiny_trainer()
    return [float(trainer.train_round(batches)) for _ in range(3)]


def profiled(fn, tmp_path):
    """Run ``fn(tmp_path)`` under a profiler session; its result and the
    ``sparknet.`` events of the trace as (name, start, end, stats)."""
    log_dir = str(tmp_path / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        result = fn(tmp_path)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for p in jax.profiler.ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for line in p.lines:
            events += [(e.name[len("sparknet."):], e.start_ns,
                        e.start_ns + e.duration_ns, dict(e.stats))
                       for e in line.events
                       if e.name.startswith("sparknet.")]
    return result, sorted(events, key=lambda e: e[1])


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(events, child, parent):
    """Every ``child`` span lies within some ``parent`` span."""
    return all(any(p[1] <= c[1] and c[2] <= p[2]
                   for p in named(events, parent))
               for c in named(events, child))


# what each flow must leave in the trace: span -> (its ordinal's key, the
# parent it lies inside or None)
FLOWS = {
    "step": (run_steps, {
        "step.next_batch": ("iter", None),
        "step.dispatch": ("iter", None),
        "step.loss_fetch": ("iter", None)}),
    "feed": (run_feed, {
        "feed.wait": ("batch", "step.next_batch"),
        "feed.device_put": ("batch", None),
        "feed.assemble": ("batch", None),
        "feed.submit": ("batch", "feed.assemble"),
        "feed.collect": ("batch", "feed.assemble"),
        "feed.stack": ("batch", "feed.assemble")}),
    "round": (run_rounds, {
        "trainer.round": ("round", None),
        "trainer.stage": ("round", "trainer.round"),
        "trainer.dispatch": ("round", "trainer.round"),
        "trainer.loss_fetch": ("round", "trainer.round")}),
}


@pytest.mark.parametrize("flow", sorted(FLOWS))
def test_spans_reach_the_profilers_trace(flow, plane, tmp_path):
    fn, want = FLOWS[flow]
    losses, events = profiled(fn, tmp_path)
    assert all(np.isfinite(losses))
    for name, (key, parent) in want.items():
        found = named(events, name)
        assert found, f"no sparknet.{name} in the trace"
        ordinals = [e[3][key] for e in found]
        if name == "feed.device_put":       # two threads: by batch, not time
            ordinals = sorted(ordinals)
        assert ordinals == sorted(set(ordinals)), (name, ordinals)
        if parent:
            assert inside(events, name, parent), (name, parent)
    # nothing was written beside the profiler's own directory
    assert telemetry.get_tracer() is None
    assert sorted(os.listdir(tmp_path)) in (["profile"],
                                            ["profile", "shards"])


def test_a_fed_batch_is_one_assemble_with_its_three_children(
        plane, tmp_path):
    """The feed's thread as it is since the batch is assembled in place:
    one ``feed.assemble`` a host batch, ordinals rising from 0, and
    inside each one ``feed.submit`` (the pulls of the batch after),
    ``feed.collect`` and ``feed.stack`` (what is left of the stacking:
    the labels' cast), in that order and with the batch's ordinal;
    nothing a record."""
    _, events = profiled(run_feed, tmp_path)
    assembles = named(events, "feed.assemble")
    assert [e[3]["batch"] for e in assembles] == list(range(len(assembles)))
    assert len(assembles) >= 4              # two calls of two steps
    children = [e for e in events
                if e[0] in ("feed.submit", "feed.collect", "feed.stack")]
    for a in assembles:
        mine = [c for c in children if a[1] <= c[1] and c[2] <= a[2]]
        assert [c[0] for c in mine] == [
            "feed.submit", "feed.collect", "feed.stack"]
        assert {c[3]["batch"] for c in mine} == {a[3]["batch"]}
    assert len(children) == 3 * len(assembles)
    feed_names = {e[0] for e in events if e[0].startswith("feed.")}
    assert feed_names == {"feed.assemble", "feed.submit", "feed.collect",
                          "feed.stack", "feed.device_put", "feed.wait"}


def test_step_ordinals_rise_and_the_fetch_is_once_a_call(plane, tmp_path):
    def three_calls(_):
        solver = tiny_solver(itertools.cycle(raw_batches()))
        return [float(solver.step(3)) for _ in range(3)]

    _, events = profiled(three_calls, tmp_path)
    for name in ("step.next_batch", "step.dispatch"):
        assert [e[3]["iter"] for e in named(events, name)] == list(range(9))
    assert [e[3]["iter"] for e in named(events, "step.loss_fetch")] == [
        3, 6, 9]


def test_without_a_session_or_a_trace_dir_nothing_is_written(
        plane, tmp_path):
    plane.chdir(tmp_path)
    run_steps(tmp_path)
    run_feed(tmp_path)
    run_rounds(tmp_path)
    assert telemetry.get_tracer() is None
    assert os.listdir(tmp_path) == ["shards"]
    # the span is still the profiler's annotation: an operator's session
    # started later would see it
    assert isinstance(telemetry.span("x", iter=0),
                      jax.profiler.TraceAnnotation)


def test_disabled_plane_spans_are_the_shared_no_op(plane):
    plane.setenv("SPARKNET_TELEMETRY", "0")
    telemetry.reset()
    assert telemetry.span("step.dispatch", cat="step", iter=1) \
        is telemetry.NULL_SPAN
    solver = tiny_solver(itertools.cycle(raw_batches()))
    assert np.isfinite(solver.step(2))


def test_trace_dir_gets_per_batch_spans_and_no_per_record_event(
        plane, tmp_path):
    trace_dir = tmp_path / "jsonl"
    plane.setenv("SPARKNET_TRACE_DIR", str(trace_dir))
    telemetry.reset()
    stats = FeedStats()
    with fed(shard_dir(tmp_path), stats=stats, host_stats=stats) as feed:
        for _ in range(3):
            next(feed)
    telemetry.reset()                       # flushes the shard
    (shard,) = glob.glob(str(trace_dir / "trace_*.jsonl"))
    with open(shard) as f:
        events = [json.loads(line) for line in f]
    spans = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"feed.wait", "feed.device_put", "feed.assemble", "feed.submit",
            "feed.collect", "feed.stack"} <= names
    # the records' reads and decodes are timed (FeedStats has them) and
    # leave no event each
    assert stats.snapshot()["read_s"] > 0
    assert not names & {"feed.read", "feed.decode", "feed.transform"}
    assert len(spans) < 12 * 8              # a few a batch, none a record
    waits = [e["args"]["batch"] for e in spans if e["name"] == "feed.wait"]
    assert waits == [0, 1, 2]


@pytest.mark.parametrize("what", ["step", "round"])
def test_lowered_program_names_the_augment_scope(what):
    if what == "step":
        solver = tiny_solver(itertools.cycle(raw_batches()))
        stacked = solver._next_batches()
        lowered = solver._step.lower(solver.params, solver.state,
                                     solver.iter, stacked, solver._rng)
    else:
        trainer, batches = tiny_trainer(raw=True)
        lowered = trainer._round.lower(
            trainer.params, trainer.state, jnp.asarray(trainer.iter),
            {k: jnp.asarray(v) for k, v in batches.items()}, trainer._rng,
            jnp.asarray(trainer.lr_scale, jnp.float32))
    scoped = [line for line in lowered.as_text(debug_info=True).splitlines()
              if "L[augment]" in line]
    # a uint8 batch is cropped and mirrored by selection: the products
    # carry the scope, and nothing under it gathers or slices by sample
    assert any("dot_general" in line for line in scoped)
    assert not any("gather" in line or "dynamic_slice" in line
                   or "reverse" in line for line in scoped)


@pytest.mark.parametrize("flow", ["step", "feed", "round"])
def test_a_session_changes_no_loss(flow, plane, tmp_path):
    fn = FLOWS[flow][0]
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    plain = fn(tmp_path / "a")
    traced, _ = profiled(fn, tmp_path / "b")
    assert plain == traced                  # bit for bit


@pytest.mark.parametrize("shared", [True, False])
def test_feed_stats_count_a_delivered_batch_once_and_the_wait(
        shared, plane, tmp_path):
    """One ``FeedStats`` under both stages counts what the ``DeviceFeed``
    delivered, not that and the host stage's batches again; one a stage
    counts each stage's own.  The consumer's wait is a stage of the
    ``DeviceFeed``'s object; how the records' bytes reached their rows
    (``read_in_place``, ``read_copied``) is the host stage's."""
    device = FeedStats()
    host = device if shared else FeedStats()
    with fed(shard_dir(tmp_path), stats=device, host_stats=host) as feed:
        for _ in range(4):
            next(feed)
    # the feed is closed: no stage runs on
    snap, per = device.snapshot(), device.per_batch()
    assert snap["batches"] == 4
    assert snap["wait_s"] > 0 and snap["device_put_s"] > 0
    assert per["wait_s"] == pytest.approx(snap["wait_s"] / 4, abs=1e-6)
    assert per["device_put_s"] == pytest.approx(
        snap["device_put_s"] / 4, abs=1e-6)
    made = host.snapshot()
    # a LocalStore and no fault: every record was read into its row
    assert made["read_in_place"] == made["records"] >= 4 * BATCH
    assert made["read_copied"] == 0
    if shared:
        assert snap["read_s"] > 0
    else:
        # the host stage runs ahead of the consumer by the queues
        assert made["batches"] >= 4 and made["wait_s"] == 0.0
        assert snap["read_s"] == 0.0
        assert snap["read_in_place"] == snap["read_copied"] == 0


_KEYED_BY_SCOPE = """
import sys
import jax, jax.numpy as jnp
from sparknet_tpu.utils.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def step(x):
    if sys.argv[1] == "scoped":
        with jax.named_scope("L[augment]"):
            y = jnp.sin(x) * 2
    else:
        y = jnp.sin(x) * 2
    return (y @ y.T).sum()
jax.jit(step)(jnp.ones((16, 16))).block_until_ready()
"""


def test_compile_cache_keys_a_program_by_its_scopes_too(tmp_path):
    """An executable cached before a scope existed must not be served to
    the program that has it, or the trace would lack the scope:
    ``use_compile_cache`` puts debug information into the key, which JAX
    leaves out by default."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "keyed.py"
    script.write_text(_KEYED_BY_SCOPE)
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}

    def entries_after(which):
        subprocess.run([sys.executable, str(script), which], env=env,
                       check=True, timeout=120, capture_output=True)
        return sorted(p.name for p in cache.glob("jit_step-*"))

    plain = entries_after("plain")
    assert len(plain) == 1
    both = entries_after("scoped")
    assert len(both) == 2 and set(plain) < set(both)
    assert entries_after("scoped") == both          # and found again
