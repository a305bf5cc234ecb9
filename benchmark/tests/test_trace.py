"""The trace reduction against a small recorded trace.

``data/tiny_resident.xplane.pb.gz`` was recorded on a TPU v5 lite in PR 22:
two calls of three ``Solver.step`` steps of the tiny test cell (lenet, batch
8) under ``jax.profiler`` with the Python tracer off, inside one
``bench.window`` span.  Of the file the profiler wrote, the device plane
and the host plane are kept as they were; the other planes (compiler
metadata, 575 KB of 800) are dropped.  ``data/tiny_rounds.xplane.pb.gz`` is
two rounds of the tiny round cell on four chips, kept the same way.
"""

import os

import pytest

from benchmark.lib import trace as tracelib
from benchmark.lib.trace import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def tiny():
    return tracelib.load(os.path.join(DATA, "tiny_resident.xplane.pb.gz"))


def sweep_busy(ops, lo, hi):
    """Busy picoseconds by another road: count open intervals over the
    sorted boundaries."""
    edges = []
    for o in ops:
        s, e = max(o.start_ps, lo), min(o.end_ps, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    busy = depth = 0
    for (t, d), (t_next, _) in zip(edges, edges[1:] + [(hi, 0)]):
        depth += d
        if depth > 0:
            busy += t_next - t
    return busy


def test_what_the_recorded_trace_holds(tiny):
    assert list(tiny.devices) == [0]
    assert len(tiny.devices[0]) == 1212
    names = [s.name for s in tiny.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.step_call") == 2
    assert names.count("bench.next_batch") == 6
    lo, hi = tiny.window()
    assert (hi - lo) / 1e12 == pytest.approx(0.015052968)
    # host spans and device operations share a clock: every operation of
    # the two calls lies inside the window span
    assert all(lo < o.start_ps and o.end_ps < hi for o in tiny.devices[0])


def test_busy_is_a_union_and_idle_is_the_rest(tiny):
    lo, hi = tiny.window()
    ops = tiny.devices[0]
    merged = tracelib.union(ops, lo, hi)
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    busy = sum(e - s for s, e in merged)
    assert busy == sweep_busy(ops, lo, hi)
    # on one device's line no two operations overlap, so here the union
    # is the sum; the hand-written trace below has an overlap
    assert busy == sum(o.dur_ps for o in ops)
    busy_s, window_s = tracelib.busy_seconds(tiny)
    assert busy_s == pytest.approx(busy / 1e12)
    assert busy_s == pytest.approx(0.00040156665)
    # lenet at batch 8 leaves the chip idle nearly all the time
    assert 1 - busy_s / window_s == pytest.approx(0.97332, abs=1e-5)


def test_operations_by_category_and_by_layer(tiny):
    ops = tracelib.in_window(tiny, 0)
    mxu = [o for o in ops if tracelib.is_mxu(o)]
    # six steps: forward and two gradients a layer, and conv1, fed by the
    # data, has no gradient with respect to its input
    by_layer = {}
    for o in mxu:
        by_layer[o.layer()] = by_layer.get(o.layer(), 0) + 1
    assert by_layer == {"conv1": 12, "conv2": 18, "ip1": 18, "ip2": 18}
    share = tracelib.time_share(ops, tracelib.is_mxu)
    assert share == pytest.approx(
        sum(o.dur_ps for o in mxu) / sum(o.dur_ps for o in ops))
    assert 0 < share < 1
    assert not any(tracelib.is_collective(o) for o in ops)
    assert tracelib.time_share([], tracelib.is_mxu) is None


def test_a_kernel_is_found_by_its_name(tiny):
    ops = tracelib.in_window(tiny, 0)
    found = tracelib.kernel_ops(ops, "convolution_add_fusion")
    assert len(found) == 18 and all(
        tracelib.category(o) == "convolution fusion" for o in found)
    assert tracelib.kernel_ops(ops, "relu_lrn_fwd") == []


def test_breakdown_lists(tiny):
    top = tracelib.top_ops(tracelib.in_window(tiny, 0))
    assert len(top) == 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert any(label.startswith("ip1 bwd") for label, _ in top)
    gaps = tracelib.idle_gaps(tiny, 0)
    busy_s, window_s = tracelib.busy_seconds(tiny)
    assert sum(s for _, s in gaps) == pytest.approx(window_s - busy_s)
    assert {label for label, _ in gaps} <= {"step_call", "next_batch",
                                            "(no span)"}
    # the device waits while the host is inside Solver.step
    assert gaps[0][0] == "step_call"


def test_gaps_go_to_the_innermost_span_and_collectives_to_their_share():
    """The arithmetic on a trace written out by hand."""
    us = 1_000_000
    ops = [Op(0, 10 * us, "fusion.1", "convolution fusion"),
           Op(10 * us, 5 * us, "all-reduce.3", "all-reduce"),
           Op(12 * us, 2 * us, "copy-start.1", "copy-start"),   # overlaps
           Op(40 * us, 10 * us, "fusion.2", "loop fusion")]
    spans = [Op(0, 100 * us, "bench.window"),
             Op(14 * us, 30 * us, "bench.step_call"),
             Op(20 * us, 10 * us, "bench.next_batch")]
    t = Trace(devices={0: ops}, spans=spans)
    assert tracelib.busy_seconds(t) == (pytest.approx(25e-6),
                                        pytest.approx(100e-6))
    assert tracelib.time_share(ops, tracelib.is_collective) == \
        pytest.approx(5 / 27)
    # the gap 15..40 has its middle at 27.5, inside next_batch; the gap
    # 50..100 is covered by no span but the window
    assert tracelib.idle_gaps(t, 0) == [["(no span)", pytest.approx(50e-6)],
                                        ["next_batch", pytest.approx(25e-6)]]


def test_four_chips_collectives_and_the_average_over_chips():
    rounds = tracelib.load(os.path.join(DATA, "tiny_rounds.xplane.pb.gz"))
    assert sorted(rounds.devices) == [0, 1, 2, 3]
    names = [s.name for s in rounds.spans]
    assert names.count("bench.train_round") == 2
    lo, hi = rounds.window()
    per_device = []
    for d, ops in rounds.devices.items():
        ops = tracelib.in_window(rounds, d)
        # one boundary average a round, on every chip
        found = [o for o in ops if tracelib.is_collective(o)]
        assert [tracelib.category(o) for o in found] == ["all-reduce"] * 2
        per_device.append(sweep_busy(ops, lo, hi))
    share = tracelib.time_share(tracelib.in_window(rounds, 0),
                                tracelib.is_collective)
    assert share == pytest.approx(0.22918, abs=1e-5)   # lenet: all exchange
    busy_s, window_s = tracelib.busy_seconds(rounds)
    assert busy_s == pytest.approx(sum(per_device) / 4 / 1e12)
    assert window_s == pytest.approx((hi - lo) / 1e12)


def test_the_gap_between_rounds_is_between_runs_of_the_round_program():
    rounds = tracelib.load(os.path.join(DATA, "tiny_rounds.xplane.pb.gz"))
    # the first device also runs what the host does between two rounds (a
    # key split, an unstack, two casts); the others run the round alone
    assert [len(rounds.modules[d]) for d in range(4)] == [10, 2, 2, 2]
    assert {m.name.split("(")[0] for m in rounds.modules[1]} == {
        "jit_local_sgd_body"}
    # two rounds, one hand-over: the same 5.5 ms on every chip, though the
    # first is busy inside it
    assert tracelib.program_gaps(rounds, 0) == [pytest.approx(5.474358906e-3)]
    for d in (1, 2, 3):
        assert tracelib.program_gaps(rounds, d) == [
            pytest.approx(5.5e-3, rel=0.01)]


def test_program_gaps_by_hand(tiny):
    us = 1_000_000
    t = Trace(devices={0: []}, spans=[Op(0, 100 * us, "bench.window")],
              modules={0: [Op(1 * us, 30 * us, "jit_round(1)"),
                           Op(32 * us, 1 * us, "jit_split(2)"),
                           Op(35 * us, 30 * us, "jit_round(1)"),
                           Op(69 * us, 30 * us, "jit_round(1)"),
                           Op(99 * us, 30 * us, "jit_round(1)")]})  # cut
    assert tracelib.program_gaps(t, 0) == [pytest.approx(4e-6),
                                           pytest.approx(4e-6)]
    assert tracelib.program_gaps(Trace(devices={0: []}, spans=t.spans), 0) == []
    # six steps in two calls of three: five hand-overs
    assert len(tracelib.program_gaps(tiny, 0)) == 5


@pytest.mark.parametrize("recorded, metric, value", [
    ("tiny_resident", "device_idle_share", 97.332),
    ("tiny_resident", "non_mxu_share", None),     # checked against the share
    ("tiny_resident", "exchange_share", "absent"),
    ("tiny_resident", "round_gap_ms", "absent"),  # steps, not rounds
    ("tiny_rounds", "exchange_share", 22.918),
    ("tiny_rounds", "round_gap_ms", 5.474358906),
])
def test_the_readers_of_the_trace(recorded, metric, value):
    """Each per-layer metric that comes from the trace alone, read by its
    own file from a recorded trace."""
    from benchmark.lib import harness
    trace = tracelib.load(os.path.join(DATA, recorded + ".xplane.pb.gz"))
    cap = harness.Capture(cell=None, driver=None, device={}, setup={},
                          window=None, traced=None, counters={}, extra={},
                          trace=trace)
    got = harness.load_metric("per_layer", metric).read(cap)
    if value == "absent":
        assert got is None
    elif value is None:
        assert got == pytest.approx(100 * (1 - tracelib.time_share(
            tracelib.in_window(trace, 0), tracelib.is_mxu)))
    else:
        assert got == pytest.approx(value, rel=1e-4)
