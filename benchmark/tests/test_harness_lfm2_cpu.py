"""The harness end to end on the CPU for a tiny cell of the sequence
driver (``drivers/solver_seq.py``: reference, operations and checked leaves
by name from the configuration), its controls, the five readers PR 37 adds
on a trace made by hand, and ``seq_flops`` against counts by hand for one layer of
each type.  A fixture of its own, as ``test_harness_tokens_cpu.py`` has and
for its reason.
"""

import json
import os
import types

import pytest

from benchmark import run
from benchmark.lib import harness, seq_flops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "lfm2_24b_a2b_train_8k"


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """``BENCHMARK.json`` with the new cell's metrics kept and its
    configuration and mix replaced by the tiny ones."""
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    spec["configs"] = [{"name": "lfm2_tiny", "file": os.path.relpath(
        os.path.join(DATA, "lfm2_tiny.json"), harness.REPO)}]
    spec["workloads"] = [{"name": "tiny_seq", "config": "lfm2_tiny",
                          "traffic": "tiny_seq", "chips": 1}]
    for group in ("end_to_end", "per_layer"):
        spec[group] = [
            {**m, **({"workloads": ["tiny_seq"]} if "workloads" in m
                     else {})}
            for m in spec[group] if CELL in m.get("workloads", [CELL])
            # the one raises on a trace without device planes, which a CPU
            # trace is; the other divides by a chip's peak, and the CPU has
            # no row in ``lib/peaks.py``
            and m["name"] not in ("device_idle_share", "mfu_seq")]
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


def test_the_cell_is_declared_with_its_readers():
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    cell = spec["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]
            ) == (CELL, "lfm2_24b_a2b", "train_seq_resident", 1)
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {
        "compile_s", "mfu_seq", "conv_share", "conv_mix_roofline",
        "attn_core_roofline_seq", "moe_experts_roofline_seq", "attn_share", "moe_share", "moe_imbalance",
        "device_idle_share", "hbm_peak_gb", "step_dispatch_ms"}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    # the mix has the Laguna cell's numbers and another driver
    mix, laguna = (harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", name + ".json")) for name in
        ("train_seq_resident", "train_tokens_resident"))
    assert mix.pop("driver") == "solver_seq" and mix.pop("what")
    assert mix == {k: v for k, v in laguna.items()
                   if k not in ("driver", "what")}


def test_end_to_end_line(capsys, monkeypatch, tmp_path, spec_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = run.main(["--workload", "tiny_seq", "--seed", "2147483659",
                   "--seconds", "1.5", "--trace", "0"],
                  spec_path=spec_path, platform="cpu",
                  traffic_dir=os.path.join(DATA, "traffic"),
                  cache_dir=str(tmp_path / "cache"))
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    out, earlier = json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"train_img_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"        # never a device number
    verdict = next(e["check"] for e in earlier if "check" in e)
    assert verdict["grad_leaves"] == ["L2/moe/0", "L2/moe/3", "L3/conv/1",
                                      "L3/conv/0", "L2/attn/4", "L0/mlp/0"]
    assert verdict["logits_rel_err"] < 1e-4
    assert max(verdict["grads_rel_err"]) < 1e-3
    assert verdict["rows_rel_err"] == 0.0 and set(
        verdict["rows_rel_err_by_layer"]) == {f"L{i}/moe" for i in
                                              (2, 3, 4, 5)}
    assert verdict["precision"]["products_fed"] == ["float32"]
    window = next(e["window"] for e in earlier if "window" in e)
    assert window["compile_events_in_window"] == 0
    counters = next(e["counters"] for e in earlier if "counters" in e)
    for when in ("moe_load", "moe_load_seeded"):
        assert set(counters[when]) == {f"L{i}/moe" for i in (2, 3, 4, 5)}
        assert all(v["dropped"] == 0 for v in counters[when].values())
    assert "xla" in {s["labels"]["path"] for s in
                     counters["attn_lowering_total"]["samples"]}


def test_the_comparison_refuses_its_controls(capsys, monkeypatch, tmp_path,
                                             spec_path):
    """``control.py`` on the tiny cell: the reference with float8 operands
    in the program's place, and the program against the reference without
    the selection bias, both through ``Driver.compare`` and both refused,
    each line naming the numbers that refuse it."""
    from benchmark import control
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = control.main(["--workload", "tiny_seq", "--seed", "2147483659"],
                      spec_path=spec_path,
                      traffic_dir=os.path.join(DATA, "traffic"))
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert rc == 0
    assert [l["control"] for l in lines] == ["float8_operands",
                                             "no_select_bias"]
    for line in lines:
        assert line["ok"] is False and line["finite"] and line["refused_by"]
        assert line["positions"] == 32 and line["logits_tol"] == 1e-3
    # float8 leaves nothing of any of the six gradients
    assert set(lines[0]["grad_leaves"]) <= set(lines[0]["refused_by"])
    # without the bias other tokens reach the held experts
    assert {"rows", "L2/moe/3"} <= set(lines[1]["refused_by"])
    assert lines[1]["rows_rel_err"] > lines[1]["rows_tol"] == 0.01


def test_a_checkout_without_the_builder_refuses_the_cell_at_once():
    """What the parent commit does with this cell: no builder of that
    name, so the driver exits before anything is built."""
    from benchmark.drivers import solver_seq
    cfg = {**harness.load_json(os.path.join(DATA, "lfm2_tiny.json")),
           "builder": "no_such_builder"}
    mix = harness.load_json(os.path.join(DATA, "traffic", "tiny_seq.json"))
    driver = solver_seq.Driver(harness.Cell(
        name="t", config=cfg, mix=mix, chips=1, seed=0, cache_dir=""))
    with pytest.raises(SystemExit, match="no builder"):
        driver.make_solver()


def tiny_net(**over):
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    args = {**harness.load_json(os.path.join(DATA, "lfm2_tiny.json"))[
        "builder_args"], **over}
    return models.lfm2(2, 1, seq_len=32, **args).filtered(
        NetState(Phase.TRAIN))


def test_readers_on_a_trace_made_by_hand():
    """Each new reader against the arithmetic of its docstring, and the
    accepted ``attn_share`` and ``moe_share`` on the new net."""
    from benchmark.layer_metrics import (attn_core_roofline_seq, attn_share,
                                         conv_mix_roofline, conv_share,
                                         mfu_seq, moe_experts_roofline_seq,
                                         moe_share)
    from benchmark.lib import peaks
    from benchmark.lib import trace as tracelib

    net = tiny_net()
    ms = 10 ** 9                                        # picoseconds
    op = lambda i, dur, scope: tracelib.Op(i * 10 * ms, dur * ms, f"op{i}",
                                           "fusion", scope)
    ops = [op(0, 2, "jit(step)/L[L2/attn]/attn_core/x"),
           op(1, 1, "jit(step)/L[L2/attn]/dot"),
           op(2, 2, "jit(step)/transpose(jvp(L[L3/conv]))/conv_mix/mul"),
           op(3, 1, "jit(step)/L[L0/conv]/sh,hjo->jso/dot_general"),
           op(4, 1, "jit(step)/L[L2/moe]/moe_route/sort"),
           op(5, 2, "jit(step)/L[embed]/gather"),
           op(6, 1, "jit(step)/L[L3/moe]/moe_experts/gmm")]
    trace = tracelib.Trace(devices={0: ops}, spans=[
        tracelib.Op(0, 100 * ms, tracelib.WINDOW_SPAN)])
    cap = types.SimpleNamespace(
        trace=trace, device={"kind": "TPU v5 lite"},
        driver=types.SimpleNamespace(train_net_param=lambda: net),
        traced=types.SimpleNamespace(steps=3, img_s=5.0),
        cell=types.SimpleNamespace(chips=1,
                                   mix={"compute_dtype": "bfloat16"}))
    assert attn_share.read(cap) == pytest.approx(30.0)
    assert conv_share.read(cap) == pytest.approx(30.0)
    assert moe_share.read(cap) == pytest.approx(20.0)
    peak = peaks.peaks("TPU v5 lite")
    f = seq_flops.train_flops_per_sequence(net)
    assert mfu_seq.read(cap) == pytest.approx(
        100 * f["total"] * 5.0 / peak["flops_per_s"])
    # 3 steps of 2 sequences; the core ran 2 ms, conv_mix 2 ms
    assert attn_core_roofline_seq.read(cap) == pytest.approx(
        100 * (f["core"] * 2 * 3 / peak["flops_per_s"]) / 2e-3)
    moved = seq_flops.conv_mix_bytes_per_sequence(net, 2)
    # 4 short convolutions, 11 passes of 32 positions x 32 wide in bfloat16
    assert moved == 4 * 11 * 32 * 32 * 2
    assert conv_mix_roofline.read(cap) == pytest.approx(
        100 * (moved * 2 * 3 / peak["hbm_bytes_per_s"]) / 2e-3)
    # 4 expert layers: 4 held experts' three matrices of 32 x 16 once a pass
    # and a step, 32 routed rows a sequence in and out of both stages, in
    # bfloat16; the operations are the larger time on this chip's ridge
    moved = seq_flops.expert_bytes_per_sequence(net, 2)
    assert moved == 4 * 3 * (3 * 4 * 32 * 16 * 2 / 2 + 32 * 2 * 32 * 2)
    least = max(f["experts"] * 2 * 3 / peak["flops_per_s"],
                moved * 2 * 3 / peak["hbm_bytes_per_s"])
    assert least == moved * 2 * 3 / peak["hbm_bytes_per_s"]
    assert moe_experts_roofline_seq.read(cap) == pytest.approx(
        100 * least / 1e-3)
    # where no operation carries the scope, or there is no device plane,
    # as on a program that lacks the layer: nothing, and no error
    readers = (attn_core_roofline_seq, conv_mix_roofline, conv_share,
               moe_experts_roofline_seq)
    cap.trace = tracelib.Trace(devices={0: ops[5:6]}, spans=trace.spans)
    for reader in readers:
        assert reader.read(cap) is None
    cap.trace = tracelib.Trace(devices={}, spans=trace.spans)
    for reader in readers:
        assert reader.read(cap) is None


def test_operations_by_hand_for_one_layer_of_each_type():
    net = tiny_net()
    rows = {lp.name: (lp, g) for lp, g in seq_flops.layers(net)}
    h, s = 32, 32
    macs = lambda name: seq_flops.forward_macs(*rows[name])
    params = lambda name: seq_flops.parameters(*rows[name])
    # short convolution: W_in 32 -> 3 x 32, 3 taps a channel, W_out 32 -> 32
    assert params("L0/conv") == h * 3 * h + h * 3 + h * h
    assert macs("L0/conv") == {"core": 0.0, "experts": 0.0,
                               "other": s * (3 * h * h + h * h)}
    # attention: 4 query heads over 2 key/value heads of 8, no gate, two
    # norm weights of 8; the core at its causal pairs and its own head size
    assert params("L2/attn") == h * (2 * 4 * 8 + 2 * 2 * 8) + 2 * 8
    assert macs("L2/attn")["other"] == s * h * (2 * 4 * 8 + 2 * 2 * 8)
    assert macs("L2/attn")["core"] == 2 * (s * (s + 1) // 2) * 4 * 8
    # dense MLP of 64
    assert params("L0/mlp") == 3 * h * 64
    assert macs("L0/mlp")["other"] == s * 3 * h * 64
    # experts: a router of 16, 4 held of width 16, a bias of 16, no shared
    # expert; top 4 of 16 send the 4 held experts 32 rows a sequence
    assert params("L2/moe") == h * 16 + 3 * 4 * h * 16 + 16
    assert macs("L2/moe") == {"core": 0.0, "other": s * h * 16,
                              "experts": 32 * 3 * h * 16}
    # the head is the embedding: counted once among the parameters, its
    # product counted in the head
    assert params("embed") == params("lm_loss") == 64 * h
    assert macs("lm_loss")["other"] == s * h * 64 and macs("embed")[
        "other"] == 0
    total = (64 * h + 11 * h + 4 * params("L0/conv") + params("L2/attn")
             + params("L0/mlp") + 4 * params("L2/moe"))
    assert seq_flops.as_built(net)["parameters"] == total
    f = seq_flops.train_flops_per_sequence(net)
    assert f["core"] == 6 * macs("L2/attn")["core"]
    assert f["experts"] == 6 * 4 * macs("L2/moe")["experts"]
    assert f["total"] == f["core"] + f["experts"] + f["other"]


def test_seq_flops_counts_laguna_as_lm_flops_does():
    """The net the accepted module counts is counted alike: the module
    that folds into this one."""
    from benchmark.lib import lm_flops
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    args = harness.load_json(os.path.join(DATA, "laguna_tiny.json"))[
        "builder_args"]
    net = models.laguna(2, 1, seq_len=32, **args).filtered(
        NetState(Phase.TRAIN))
    assert seq_flops.train_flops_per_sequence(net) == \
        lm_flops.train_flops_per_sequence(net)
    assert seq_flops.as_built(net)["parameters"] == \
        lm_flops.as_built(net)["parameters"]


def test_as_built_refuses_a_net_with_one_width_changed():
    cfg = harness.load_json(os.path.join(DATA, "lfm2_tiny.json"))
    seq_flops.check_as_built(cfg, tiny_net())
    for change in ({"expert_width": 32}, {"dense_width": 48},
                   {"conv_kernel": 4}, {"kv_heads": 4}):
        with pytest.raises(SystemExit, match="not the one"):
            seq_flops.check_as_built(cfg, tiny_net(**change))
