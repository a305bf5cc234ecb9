"""The four readers of the step's scopes (``unscoped_share``,
``update_share``, ``cast_share``, ``recompute_share``) on traces written out
by hand, as ``test_program_spans.py`` makes its own: a CPU trace has no
device plane, and the arithmetic is the readers' whole content.  The scope
paths are what JAX writes into an operation's ``tf_op``: the name stack of
``jax.named_scope``s, ``transpose(jvp(...))`` round the innermost scope the
backward pass was differentiated under, ``rematted_computation`` where
``jax.checkpoint`` runs a forward again.
"""

import os

import pytest
from test_program_spans import capture

from benchmark.lib import harness
from benchmark.lib import trace as tracelib
from benchmark.lib.trace import Op, Trace

US = 1_000_000
NEW = ("unscoped_share", "update_share", "cast_share", "recompute_share")
FIVE = ["caffenet_train_resident", "googlenet_train_resident",
        "caffenet_rounds_x4", "laguna_xs_2_train_8k",
        "lfm2_24b_a2b_train_8k"]


def read(cap, name):
    return harness.load_metric("per_layer", name).read(cap)


def traced(tmp_path, ops):
    trace = Trace(devices={0: ops}, spans=[Op(0, 1000 * US, "bench.window")])
    return capture(tmp_path, "tiny_resident", trace=trace)


def a_step():
    """100 us of one device: a token step as the program scopes it."""
    rows = [
        (5, "loop fusion", "jit(step)/L[step.input]/L[augment]/mul"),
        (3, "data formatting", "jit(step)/L[conv1]/cast/convert_element_type"),
        (20, "convolution fusion", "jit(step)/L[conv1]/conv_general_dilated"),
        (10, "custom-call", "jit(step)/L[L0/attn]/while/body/closed_call/"
                            "checkpoint/attn_core/pallas_call"),
        # the forward again, in the backward pass
        (12, "custom-call", "jit(step)/transpose(jvp(L[L0/attn]))/while/body/"
                            "closed_call/checkpoint/rematted_computation/"
                            "attn_core/pallas_call"),
        (18, "custom-call", "jit(step)/transpose(jvp(L[L0/attn]))/while/body/"
                            "closed_call/checkpoint/attn_core/pallas_call"),
        (2, "loop fusion", "jit(step)/transpose(jvp(L[conv1]))/cast/"
                           "convert_element_type"),
        (15, "convolution fusion", "jit(step)/transpose(jvp(L[conv1]))/"
                                   "conv_general_dilated"),
        (4, "loop fusion", "jit(step)/L[step.grads]/mul"),
        (6, "loop fusion", "jit(step)/L[step.update]/sub"),
        (5, "copy-done", ""),           # the compiler's own: no metadata
    ]
    ops, t = [], 0
    for i, (us, cat, scope) in enumerate(rows):
        ops.append(Op(t, us * US, f"fusion.{i}", cat, scope))
        t += us * US
    return ops


def test_the_four_readers_on_a_step_written_out_by_hand(tmp_path):
    cap = traced(tmp_path, a_step())
    assert read(cap, "unscoped_share") == pytest.approx(5.0)
    assert read(cap, "update_share") == pytest.approx(10.0)
    assert read(cap, "cast_share") == pytest.approx(5.0)
    assert read(cap, "recompute_share") == pytest.approx(12.0)


@pytest.mark.parametrize("metric", NEW[1:])
def test_a_reader_finds_nothing_where_nothing_carries_the_name(
        tmp_path, metric):
    """A parent's trace: layers and ``L[augment]``, none of the new names.
    What is under no scope is there to read whatever the program is."""
    ops = [Op(0, 30 * US, "fusion.1", "convolution fusion",
              "jit(step)/L[conv1]/conv_general_dilated"),
           Op(30 * US, 10 * US, "fusion.2", "loop fusion",
              "jit(step)/convert_element_type"),
           # a layer called after a cast is no cast, nor is a longer name
           Op(40 * US, 10 * US, "fusion.3", "loop fusion",
              "jit(step)/L[cast]/castle/recast/mul")]
    cap = traced(tmp_path, ops)
    assert read(cap, metric) is None
    assert read(cap, "unscoped_share") == pytest.approx(20.0)


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_finds_nothing_without_a_device_operation(tmp_path, metric):
    assert read(traced(tmp_path, []), metric) is None
    assert read(capture(tmp_path / "cpu", "tiny_program_spans"),
                metric) is None


def test_the_innermost_scope_wins(tmp_path):
    """A phase's scope round a scan holds the layers inside it: each
    operation goes to the last ``L[...]`` of its path."""
    ops = [Op(0, 10 * US, "fusion.1", "convolution fusion",
              "jit(round)/L[step.input]/while/body/L[conv1]/conv"),
           Op(10 * US, 10 * US, "fusion.2", "loop fusion",
              "jit(round)/L[step.input]/while/body/L[step.update]/sub"),
           Op(20 * US, 10 * US, "fusion.3", "loop fusion",
              "jit(round)/L[step.input]/while/body/add"),
           Op(30 * US, 10 * US, "all-reduce.1", "all-reduce",
              "jit(round)/L[round.average]/psum")]
    cap = traced(tmp_path, ops)
    assert [o.layer() for o in ops] == ["conv1", "step.update", "step.input",
                                        "round.average"]
    assert read(cap, "update_share") == pytest.approx(25.0)
    assert read(cap, "unscoped_share") == 0.0


def test_recomputation_is_told_from_the_backward_pass(tmp_path):
    ops = a_step()
    bwd = [o for o in ops if "transpose(" in o.scope]
    again = [o for o in bwd if "rematted_computation" in o.scope]
    assert len(bwd) == 4 and len(again) == 1
    # ``top_ops`` folds the recomputed forward into ``bwd``: 12 + 18
    rows = dict(map(tuple, tracelib.top_ops(ops)))
    assert rows["L0/attn bwd custom-call"] == pytest.approx(30e-6)
    # the reader does not: 12 of the 100, and 0 once nothing is recomputed
    cap = traced(tmp_path, [o for o in ops if o not in again])
    assert read(cap, "recompute_share") is None


def test_a_cast_counts_in_both_passes_and_a_fused_convert_does_not(tmp_path):
    ops = a_step()
    casts = [o for o in ops if "/cast/" in o.scope]
    assert sorted(o.dur_ps // US for o in casts) == [2, 3]
    assert {"transpose(" in o.scope for o in casts} == {True, False}
    # the convolution that holds its own convert is named after itself
    assert all("conv_general_dilated" not in o.scope for o in casts)
    # a path that ends in the sub-scope counts too
    tail = [Op(0, 10 * US, "copy.1", "data formatting",
               "jit(step)/L[conv1]/cast"),
            Op(10 * US, 30 * US, "fusion.1", "convolution fusion",
               "jit(step)/L[conv1]/conv")]
    assert read(traced(tmp_path / "tail", tail),
                "cast_share") == pytest.approx(25.0)


def test_the_new_entries_name_their_cells_and_an_end_to_end_metric():
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    assert [m["name"] for m in spec["per_layer"]][-4:] == list(NEW)
    added = {m["name"]: m for m in spec["per_layer"][-4:]}
    e2e = next(e for e in spec["end_to_end"] if e["name"] == "train_img_s")
    for m in added.values():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "device_trace", "train_img_s")
        assert set(m["workloads"]) <= set(e2e["workloads"])
    assert added["unscoped_share"]["workloads"] == FIVE
    assert added["update_share"]["workloads"] == FIVE
    # the rounds cell stores and computes in float32: it casts nothing
    assert added["cast_share"]["workloads"] == [
        c for c in FIVE if c != "caffenet_rounds_x4"]
    assert added["recompute_share"]["workloads"] == FIVE[3:]
    assert {m["layer"] for m in added.values()} == {"graph", "step"}
