"""The readers of the program's own spans against recorded traces.

``data/tiny_program_spans.xplane.pb.gz`` was recorded on the CPU in PR 25:
under ``jax.profiler`` with the Python tracer off and inside one
``bench.window`` annotation, two calls of three ``Solver.step`` steps of
lenet at batch 8 fed by ``records_feed`` -> ``device_feed`` over four
shards, then three rounds of a two-worker ``DistributedTrainer``.  Of the
file the profiler wrote, only the host plane's lines that hold a
``sparknet.`` or ``bench.`` event are kept.  A CPU trace has no device
plane, so the whole window is one idle gap; the arithmetic against device
operations is checked on traces written out by hand.  The traces recorded
on the chip in PR 22 hold none of the program's spans and no ``L[augment]``
scope: they stand for a parent commit.
"""

import gzip
import os
import shutil
import statistics
import types

import pytest

from benchmark.lib import harness, program_spans
from benchmark.lib import trace as tracelib
from benchmark.lib.program_spans import Span
from benchmark.lib.trace import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("feed_starved_share", "feed_assemble_ms", "feed_stack_ms",
       "step_dispatch_ms", "round_host_ms", "augment_share")


def capture(tmp_path, recorded: str, trace=None):
    """A capture whose run left ``recorded`` where ``run.py`` writes a
    cell's trace."""
    where = tmp_path / "trace" / "tiny" / "plugins" / "profile" / "0"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    with gzip.open(os.path.join(DATA, recorded + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    cell = types.SimpleNamespace(cache_dir=str(tmp_path), name="tiny")
    return harness.Capture(
        cell=cell, driver=None, device={}, setup={}, window=None,
        traced=None, counters={}, extra={},
        trace=trace or tracelib.load(str(path)))


@pytest.fixture
def cap(tmp_path):
    return capture(tmp_path, "tiny_program_spans")


def test_load_keeps_the_programs_spans_of_the_window(cap):
    spans = program_spans.load(cap)
    count = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    assert count == {
        "step.next_batch": 6, "step.dispatch": 6, "step.loss_fetch": 2,
        "feed.wait": 6, "feed.device_put": 6, "feed.assemble": 6,
        "feed.submit": 6, "feed.collect": 6, "feed.stack": 6,
        "trainer.round": 3, "trainer.stage": 3, "trainer.dispatch": 3,
        "trainer.loss_fetch": 3}
    lo, hi = cap.trace.window()
    assert all(lo <= s.start_ps < s.end_ps <= hi for s in spans)
    assert [s.start_ps for s in spans] == sorted(s.start_ps for s in spans)
    # the feed's threads run on while the consumer is elsewhere: some of
    # their spans are cut by the window's edge, and none of the caller's
    cut = {s.name for s in spans if not s.whole}
    assert cut <= {"feed.assemble", "feed.collect", "feed.device_put"}
    # six readers decode one trace once
    before = program_spans.read_file.cache_info().hits
    assert program_spans.load(cap) == spans
    assert program_spans.read_file.cache_info().hits == before + 1


def test_load_has_nothing_without_a_trace_or_a_span(tmp_path):
    parent = capture(tmp_path, "tiny_resident")
    assert program_spans.load(parent) == []
    assert program_spans.load(harness.Capture(
        cell=parent.cell, driver=None, device={}, setup={}, window=None,
        traced=None, counters={}, extra={}, trace=None)) == []
    nowhere = types.SimpleNamespace(cache_dir=str(tmp_path / "x"), name="y")
    assert program_spans.load(harness.Capture(
        cell=nowhere, driver=None, device={}, setup={}, window=None,
        traced=None, counters={}, extra={}, trace=parent.trace)) == []


def test_children_lie_inside_their_parents(cap):
    spans = program_spans.load(cap)

    def inside(child, parent):
        return all(any(p.start_ps <= c.start_ps and c.end_ps <= p.end_ps
                       for p in spans if p.name == parent)
                   for c in spans if c.name == child and c.whole)

    for child in ("trainer.stage", "trainer.dispatch", "trainer.loss_fetch"):
        assert inside(child, "trainer.round")
    for child in ("feed.submit", "feed.collect", "feed.stack"):
        assert inside(child, "feed.assemble")
    assert inside("feed.wait", "step.next_batch")


def test_self_seconds_is_the_parent_less_what_its_children_cover(cap):
    spans = program_spans.load(cap)
    rounds = [s for s in spans if s.name == "trainer.round"]
    fetches = [s for s in spans if s.name == "trainer.loss_fetch"]
    own = program_spans.self_seconds(spans, "trainer.round",
                                     ("trainer.loss_fetch",))
    assert own == [pytest.approx((r.dur_ps - f.dur_ps) / 1e12)
                   for r, f in zip(rounds, fetches)]
    every = program_spans.self_seconds(
        spans, "trainer.round",
        ("trainer.stage", "trainer.dispatch", "trainer.loss_fetch"))
    assert all(0 < a < b for a, b in zip(every, own))
    assert program_spans.self_seconds(spans, "no.such", ()) == []
    # by hand: children that overlap each other or the parent's edge
    # count once, and only inside it; a parent the window cut is left out
    us = 1_000_000
    hand = [Span("p", 0, 100 * us), Span("c", 10 * us, 30 * us),
            Span("d", 20 * us, 40 * us), Span("c", 90 * us, 120 * us),
            Span("p", 200 * us, 300 * us, whole=False)]
    assert program_spans.self_seconds(hand, "p", ("c", "d")) == [
        pytest.approx(60e-6)]
    assert program_spans.seconds(hand, "c") == pytest.approx(50e-6)
    assert program_spans.length_ms(hand, "p") == pytest.approx(0.1)
    assert program_spans.length_ms(hand, "q") is None


def test_idle_by_span_on_the_recorded_trace(cap):
    rows = program_spans.idle_by_span(cap)
    lo, hi = cap.trace.window()
    # no device plane in a CPU trace: the whole window is idle, and every
    # second of it goes to one label
    assert sum(s for _, s in rows) == pytest.approx((hi - lo) / 1e12)
    labels = [label for label, _ in rows]
    assert labels[0] == "step.loss_fetch"   # lenet's steps on a CPU
    assert "(no span)" in labels
    # a parent gets only the pieces none of its children covers
    got = dict(rows)
    assert got["feed.assemble"] < got["feed.collect"]
    assert got["trainer.round"] < program_spans.seconds(
        program_spans.load(cap), "trainer.round")


def test_idle_goes_piecewise_to_the_innermost_span():
    us = 1_000_000
    ops = [Op(0, 10 * us, "fusion.1", "convolution fusion"),
           Op(60 * us, 10 * us, "fusion.2", "loop fusion")]
    trace = Trace(devices={0: ops}, spans=[Op(0, 100 * us, "bench.window")])
    spans = [Span("step.next_batch", 5 * us, 55 * us),
             Span("feed.wait", 20 * us, 50 * us),
             Span("feed.stack", 30 * us, 40 * us),      # another thread
             Span("step.dispatch", 56 * us, 58 * us)]
    # the gap 10..60: next_batch 10..20 and 50..55, wait 20..30 and
    # 40..50, stack 30..40, nothing 55..56 and 58..60, dispatch 56..58;
    # the gap 70..100 has no span
    assert program_spans.idle_of(trace, spans) == [
        ["(no span)", pytest.approx(33e-6)],
        ["feed.wait", pytest.approx(20e-6)],
        ["step.next_batch", pytest.approx(15e-6)],
        ["feed.stack", pytest.approx(10e-6)],
        ["step.dispatch", pytest.approx(2e-6)]]
    # with no span at all it is idle_gaps's own answer
    assert program_spans.idle_of(trace, []) == [
        ["(no span)", pytest.approx(80e-6)]] == tracelib.idle_gaps(trace, 0)


def test_the_six_readers_on_the_recorded_trace(cap):
    spans = program_spans.load(cap)
    lo, hi = cap.trace.window()

    def median_ms(name):
        return statistics.median(
            s.dur_ps for s in spans if s.name == name and s.whole) / 1e9

    def read(name):
        return harness.load_metric("per_layer", name).read(cap)

    assert read("feed_starved_share") == pytest.approx(
        100.0 * sum(s.dur_ps for s in spans if s.name == "feed.wait")
        / (hi - lo))
    assert 0 < read("feed_starved_share") < 5       # lenet waits for nothing
    assert read("feed_assemble_ms") == pytest.approx(median_ms(
        "feed.assemble"))
    assert read("feed_stack_ms") == pytest.approx(median_ms("feed.stack"))
    assert read("feed_stack_ms") < read("feed_assemble_ms")
    assert read("step_dispatch_ms") == pytest.approx(median_ms(
        "step.dispatch"))
    assert read("round_host_ms") == pytest.approx(1000.0 * statistics.median(
        program_spans.self_seconds(spans, "trainer.round",
                                   ("trainer.loss_fetch",))))
    assert 0 < read("round_host_ms") < median_ms("trainer.round")


@pytest.mark.parametrize("recorded", ["tiny_resident", "tiny_rounds"])
@pytest.mark.parametrize("metric", NEW)
def test_a_reader_finds_nothing_in_a_parents_trace(tmp_path, recorded,
                                                   metric):
    parent = capture(tmp_path, recorded)
    assert harness.load_metric("per_layer", metric).read(parent) is None


def test_augment_share_reads_the_scope(tmp_path):
    us = 1_000_000
    ops = [Op(0, 10 * us, "fusion.1", "loop fusion",
              "jit(step)/L[augment]/vmap()/gather"),
           Op(10 * us, 5 * us, "fusion.2", "data formatting",
              "jit(step)/L[augment]/rev"),
           Op(15 * us, 45 * us, "fusion.3", "convolution fusion",
              "jit(step)/transpose(jvp(L[conv1]))/conv_general_dilated")]
    trace = Trace(devices={0: ops}, spans=[Op(0, 100 * us, "bench.window")])
    cap = capture(tmp_path, "tiny_resident", trace=trace)
    assert harness.load_metric("per_layer", "augment_share").read(
        cap) == pytest.approx(25.0)
    assert tracelib.top_ops(ops)[1][0].startswith("augment fwd")


def test_the_new_entries_name_their_cells_and_an_end_to_end_metric():
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    added = {m["name"]: m for m in spec["per_layer"] if m["name"] in NEW}
    assert sorted(added) == sorted(NEW)
    assert [m["name"] for m in spec["per_layer"]][-6:] == list(NEW)
    cells = {w["name"] for w in spec["workloads"]}
    for m in added.values():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= cells
        e2e = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e["workloads"])
