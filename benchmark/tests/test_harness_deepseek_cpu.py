"""The harness end to end on the CPU for a tiny cell of DeepSeek-V2 through
the sequence driver (``drivers/solver_seq.py``, which takes reference,
operations module and checked leaves from the configuration): the run's
last line, its controls, the five readers the cell adds on a trace made by
hand, and ``mla_flops`` against counts by hand for one latent attention
layer and one expert layer.  A fixture of its own, as
``test_harness_lfm2_cpu.py`` has and for its reason.
"""

import json
import os
import types

import pytest

from benchmark import run
from benchmark.lib import harness, mla_flops, seq_flops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "deepseek_v2_lite_train_8k"
NEW = ("mla_share", "mla_latent_share", "mla_core_roofline",
       "moe_experts_roofline_ops", "mfu_ops")


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """``BENCHMARK.json`` with the cell's metrics kept and its
    configuration and mix replaced by the tiny ones."""
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    spec["configs"] = [{"name": "deepseek_tiny", "file": os.path.relpath(
        os.path.join(DATA, "deepseek_tiny.json"), harness.REPO)}]
    spec["workloads"] = [{"name": "tiny_seq", "config": "deepseek_tiny",
                          "traffic": "tiny_seq", "chips": 1}]
    for group in ("end_to_end", "per_layer"):
        spec[group] = [
            {**m, **({"workloads": ["tiny_seq"]} if "workloads" in m
                     else {})}
            for m in spec[group] if CELL in m.get("workloads", [CELL])]
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


def test_the_cell_is_declared_with_its_readers():
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek_v2_lite", "train_seq_resident", 1)
    config = next(c for c in spec["configs"]
                  if c["name"] == "deepseek_v2_lite")
    assert config["reduced"] == harness.load_json(os.path.join(
        harness.REPO, config["file"]))["reduced"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {
        "compile_s", "device_idle_share", "hbm_peak_gb", "step_dispatch_ms",
        "moe_share", "moe_imbalance", "unscoped_share", "update_share",
        "cast_share", "recompute_share", *NEW}
    # the new metrics are the last five, each for this cell alone
    assert [m["name"] for m in spec["per_layer"][-5:]] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in spec["per_layer"][-5:])
    train = next(m for m in spec["end_to_end"] if m["name"] == "train_img_s")
    assert train["workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


def test_end_to_end_line(capsys, monkeypatch, tmp_path, spec_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = run.main(["--workload", "tiny_seq", "--seed", "3000000019",
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
    assert verdict["grad_leaves"] == [
        "L1/attn/0", "L1/attn/2", "L1/attn/4", "L1/attn/3", "L1/moe/0",
        "L1/moe/3", "L1/moe/6"]
    assert verdict["logits_rel_err"] < 1e-4
    assert max(verdict["grads_rel_err"]) < 1e-3
    assert verdict["rows_rel_err"] == 0.0 and set(
        verdict["rows_rel_err_by_layer"]) == {"L1/moe", "L3/moe"}
    assert verdict["precision"]["products_fed"] == ["float32"]
    counters = next(e["counters"] for e in earlier if "counters" in e)
    for when in ("moe_load", "moe_load_seeded"):
        assert set(counters[when]) == {"L1/moe", "L3/moe"}
        assert all(v["dropped"] == 0 for v in counters[when].values())
    assert {s["labels"]["path"] for s in
            counters["attn_lowering_total"]["samples"]} == {"xla"}


def test_the_comparison_refuses_its_controls(capsys, monkeypatch, tmp_path,
                                             spec_path):
    """``control.py`` on the tiny cell: the reference with float8 operands
    in the program's place, and the program against the reference without
    the rotary key, both refused, each line naming the numbers that refuse
    it."""
    from benchmark import control
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = control.main(["--workload", "tiny_seq", "--seed", "3000000019"],
                      spec_path=spec_path,
                      traffic_dir=os.path.join(DATA, "traffic"))
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert rc == 0
    assert [l["control"] for l in lines] == ["float8_operands",
                                             "no_rotary_key"]
    for line in lines:
        assert line["ok"] is False and line["finite"] and line["refused_by"]
        assert line["positions"] == 32 and line["logits_tol"] == 1e-3
    assert set(lines[0]["grad_leaves"]) <= set(lines[0]["refused_by"])
    # without the rotary key the rotary half of each query head is sent
    # another gradient (0.94 of it here), which the logits hardly show at
    # toy widths
    assert "L1/attn/0" in lines[1]["refused_by"]
    assert lines[1]["grads_rel_err"][0] > 0.5


def test_a_checkout_without_the_builder_refuses_the_cell_at_once():
    """What the parent commit does with this cell: no builder of that
    name, so the driver exits before anything is built."""
    from benchmark.drivers import solver_seq
    cfg = {**harness.load_json(os.path.join(DATA, "deepseek_tiny.json")),
           "builder": "no_such_builder"}
    mix = harness.load_json(os.path.join(DATA, "traffic", "tiny_seq.json"))
    driver = solver_seq.Driver(harness.Cell(
        name="t", config=cfg, mix=mix, chips=1, seed=0, cache_dir=""))
    with pytest.raises(SystemExit, match="no builder"):
        driver.make_solver()


def tiny_net(**over):
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    args = {**harness.load_json(os.path.join(DATA, "deepseek_tiny.json"))[
        "builder_args"], **over}
    return models.deepseek_v2(2, 1, seq_len=32, **args).filtered(
        NetState(Phase.TRAIN))


def test_readers_on_a_trace_made_by_hand():
    """Each new reader against the arithmetic of its docstring."""
    from benchmark.layer_metrics import (mfu_ops, mla_core_roofline,
                                         mla_latent_share, mla_share,
                                         moe_experts_roofline_ops, moe_share)
    from benchmark.lib import peaks
    from benchmark.lib import trace as tracelib

    net = tiny_net()
    ms = 10 ** 9                                        # picoseconds
    op = lambda i, dur, scope: tracelib.Op(i * 10 * ms, dur * ms, f"op{i}",
                                           "fusion", scope)
    ops = [op(0, 2, "jit(step)/L[L1/attn]/attn_core/x"),
           op(1, 1, "jit(step)/L[L1/attn]/mla_latent/dot"),
           op(2, 1, "jit(step)/transpose(jvp(L[L3/attn]))/mla_latent/mul"),
           op(3, 1, "jit(step)/L[L0/attn]/sh,hkgd->kgsd/dot_general"),
           op(4, 1, "jit(step)/L[L1/moe]/moe_route/sort"),
           op(5, 3, "jit(step)/L[embed]/gather"),
           op(6, 1, "jit(step)/L[L3/moe]/moe_experts/gmm")]
    trace = tracelib.Trace(devices={0: ops}, spans=[
        tracelib.Op(0, 100 * ms, tracelib.WINDOW_SPAN)])
    cap = types.SimpleNamespace(
        trace=trace, device={"kind": "TPU v5 lite"},
        driver=types.SimpleNamespace(train_net_param=lambda: net,
                                     ops=mla_flops),
        traced=types.SimpleNamespace(steps=3, img_s=5.0),
        cell=types.SimpleNamespace(chips=1,
                                   mix={"compute_dtype": "bfloat16"}))
    assert mla_share.read(cap) == pytest.approx(50.0)
    assert mla_latent_share.read(cap) == pytest.approx(20.0)
    assert moe_share.read(cap) == pytest.approx(20.0)
    peak = peaks.peaks("TPU v5 lite")
    f = mla_flops.train_flops_per_sequence(net)
    assert mfu_ops.read(cap) == pytest.approx(
        100 * f["total"] * 5.0 / peak["flops_per_s"])
    # 3 steps of 2 sequences; the core ran 2 ms
    assert mla_core_roofline.read(cap) == pytest.approx(
        100 * (f["core"] * 2 * 3 / peak["flops_per_s"]) / 2e-3)
    moved = mla_flops.expert_bytes_per_sequence(net, 2)
    least = max(f["experts"] * 2 * 3 / peak["flops_per_s"],
                moved * 2 * 3 / peak["hbm_bytes_per_s"])
    assert moe_experts_roofline_ops.read(cap) == pytest.approx(
        100 * least / 1e-3)
    # where no operation carries the scope, or there is no device plane,
    # as on a program that lacks the layer: nothing, and no error
    readers = (mla_share, mla_latent_share, mla_core_roofline,
               moe_experts_roofline_ops)
    cap.trace = tracelib.Trace(devices={0: ops[5:6]}, spans=trace.spans)
    for reader in readers:
        assert reader.read(cap) is None
    cap.trace = tracelib.Trace(devices={}, spans=trace.spans)
    for reader in readers:
        assert reader.read(cap) is None
    # a driver that names no operations module
    cap.driver = types.SimpleNamespace(train_net_param=lambda: net)
    assert mfu_ops.read(cap) is None


def test_operations_by_hand_for_a_latent_and_an_expert_layer():
    net = tiny_net()
    rows = {lp.name: (lp, g) for lp, g in mla_flops.layers(net)}
    h, s = 32, 32
    macs = lambda name: mla_flops.forward_macs(*rows[name])
    params = lambda name: mla_flops.parameters(*rows[name])
    # latent attention: 2 heads; W_q 32 -> 2 x (8 + 8), W_dkv 32 -> 16,
    # W_kr 32 -> 8, gamma_kv 16, W_ukv 16 -> 2 x (8 + 8), W_o 2 x 8 -> 32
    assert params("L0/attn") == (h * 2 * 16 + h * 16 + h * 8 + 16
                                 + 16 * 2 * 16 + 2 * 8 * h)
    assert macs("L0/attn") == {
        "core": (s * (s + 1) // 2) * 2 * (16 + 8), "experts": 0.0,
        "other": s * (params("L0/attn") - 16)}
    # experts: a softmax router of 8, 4 held of width 16, two shared of 16;
    # top 3 of 8 send the 4 held experts 48 rows a sequence
    assert params("L1/moe") == h * 8 + 3 * 4 * h * 16 + 3 * h * 32
    assert macs("L1/moe") == {"core": 0.0, "other": s * h * (8 + 3 * 32),
                              "experts": 48 * 3 * h * 16}
    assert rows["L1/moe"][1]["scoring"] == "softmax"
    assert rows["L1/moe"][1]["norm_topk"] == 0
    # the types seq_flops knows are its own counts
    for name in ("embed", "L0/mlp", "L0/norm1", "lm_loss"):
        assert macs(name) == seq_flops.forward_macs(*rows[name])
        assert params(name) == seq_flops.parameters(*rows[name])
    f = mla_flops.train_flops_per_sequence(net)
    assert f["core"] == 6 * 3 * macs("L0/attn")["core"]
    assert f["experts"] == 6 * 2 * macs("L1/moe")["experts"]
    assert f["total"] == f["core"] + f["experts"] + f["other"]
    assert mla_flops.expert_bytes_per_sequence(net, 2) == 2 * 3 * (
        3 * 4 * h * 16 * 2 / 2 + 48 * 2 * h * 2)


def test_as_built_refuses_a_net_with_one_width_changed():
    cfg = harness.load_json(os.path.join(DATA, "deepseek_tiny.json"))
    mla_flops.check_as_built(cfg, tiny_net())
    for change in ({"expert_width": 32}, {"kv_lora_rank": 8},
                   {"qk_rope_head_dim": 4}, {"v_head_dim": 16},
                   {"shared_experts": 1}, {"mscale_all_dim": 1.0}):
        with pytest.raises(SystemExit, match="not the one"):
            mla_flops.check_as_built(cfg, tiny_net(**change))


def test_the_published_configuration_is_as_built():
    """The configuration file's record is what the builder makes of its
    arguments: 635,466,752 parameters, 10.17 GB at 16 bytes, 2.529 GFLOP a
    token; uncut, 15.71 billion parameters."""
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    cfg = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                         "deepseek_v2_lite.json"))
    net = models.deepseek_v2(4, 1, **cfg["builder_args"]).filtered(
        NetState(Phase.TRAIN))
    mla_flops.check_as_built(cfg, net)
    assert cfg["as_built"]["parameters"] == 635_466_752
    assert cfg["as_built"]["bytes_at_16_a_parameter"] == 16 * 635_466_752
    assert cfg["as_built"]["train_flops_per_token_at_8192"] == int(
        mla_flops.train_flops_per_sequence(net)["total"] / 8192)
    whole = models.deepseek_v2(1, 1).filtered(NetState(Phase.TRAIN))
    assert 15.70e9 < mla_flops.as_built(whole)["parameters"] < 15.72e9
