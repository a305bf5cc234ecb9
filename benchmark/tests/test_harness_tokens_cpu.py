"""The harness end to end on the CPU for a tiny token cell, and the token
cell's own library files: ``lm_flops`` against hand counts and
``reference_lm``'s expert layer against a loop over tokens and picks.

``test_harness_cpu.py``'s fixture maps every cell named in a metric's
``workloads`` through a table of the cells PR 22 had, so it cannot hold a
cell added since; this file brings a fixture of its own.
"""

import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import harness, lm_flops, reference_lm

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "laguna_xs_2_train_8k"


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """``BENCHMARK.json`` with the token cell's metrics kept and its
    configuration and mix replaced by the tiny ones."""
    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    spec["configs"] = [{"name": "laguna_tiny", "file": os.path.relpath(
        os.path.join(DATA, "laguna_tiny.json"), harness.REPO)}]
    spec["workloads"] = [{"name": "tiny_tokens", "config": "laguna_tiny",
                          "traffic": "tiny_tokens", "chips": 1}]
    for group in ("end_to_end", "per_layer"):
        spec[group] = [
            {**m, **({"workloads": ["tiny_tokens"]} if "workloads" in m
                     else {})}
            for m in spec[group] if CELL in m.get("workloads", [CELL])
            # the one raises on a trace without device planes, which a CPU
            # trace is; the other divides by a chip's peak, and the CPU has
            # no row in ``lib/peaks.py``
            and m["name"] not in ("device_idle_share", "mfu_lm")]
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


def run_cell(capsys, monkeypatch, tmp_path, spec_path, trace):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    rc = run.main(["--workload", "tiny_tokens", "--seed", "2147483659",
                   "--seconds", "1.5", "--trace", str(trace)],
                  spec_path=spec_path, platform="cpu",
                  traffic_dir=os.path.join(DATA, "traffic"),
                  cache_dir=str(tmp_path / "cache"))
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]


def test_end_to_end_line(capsys, monkeypatch, tmp_path, spec_path):
    out, earlier = run_cell(capsys, monkeypatch, tmp_path, spec_path, 0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"train_img_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"        # never a device number
    verdict = next(e["check"] for e in earlier if "check" in e)
    assert verdict["logits_rel_err"] < 1e-4
    assert max(verdict["grads_rel_err"]) < 1e-3
    assert verdict["precision"]["products_fed"] == ["float32"]
    window = next(e["window"] for e in earlier if "window" in e)
    assert window["compile_events_in_window"] == 0
    counters = next(e["counters"] for e in earlier if "counters" in e)
    for when in ("moe_load", "moe_load_seeded"):
        assert all(v["dropped"] == 0 for v in counters[when].values())
        assert len(counters[when]) == 4
    paths = {s["labels"]["path"] for s in
             counters["moe_lowering_total"]["samples"]}
    assert paths == {"ragged_dot"}


def test_traced_run_reaches_the_counters(capsys, monkeypatch, tmp_path,
                                         spec_path):
    """``run.py`` refuses a traced run in which no operation ran on a
    device, which every CPU run is; by then the traced window, the verdict
    and the counters are through, and the readers are tried below on a
    trace made by hand."""
    with pytest.raises(ValueError, match="no device operation"):
        run_cell(capsys, monkeypatch, tmp_path, spec_path, 1)
    earlier = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
    window = next(e["window"] for e in earlier if "window" in e)
    assert window["failed"] == 0 and window["verdict"].startswith("loss")
    counters = next(e["counters"] for e in earlier if "counters" in e)
    assert set(counters["moe_load"]) == {f"L{i}/moe" for i in (1, 2, 3, 4)}


def test_readers_on_a_trace_made_by_hand():
    """Each new reader against the arithmetic of its docstring."""
    import types

    from benchmark.layer_metrics import (attn_core_roofline, attn_share,
                                         mfu_lm, moe_experts_roofline,
                                         moe_imbalance, moe_share)
    from benchmark.lib import peaks
    from benchmark.lib import trace as tracelib

    net = tiny_net()
    ms = 10 ** 9                                        # picoseconds
    op = lambda i, dur, scope: tracelib.Op(i * 10 * ms, dur * ms, f"op{i}",
                                           "fusion", scope)
    ops = [op(0, 2, "jit(step)/L[L0/attn]/attn_core/x"),
           op(1, 1, "jit(step)/L[L0/attn]/dot"),
           op(2, 3, "jit(step)/transpose(jvp(L[L1/moe]))/moe_experts/gmm"),
           op(3, 1, "jit(step)/L[L1/moe]/moe_route/sort"),
           op(4, 3, "jit(step)/L[embed]/gather")]
    trace = tracelib.Trace(devices={0: ops}, spans=[
        tracelib.Op(0, 100 * ms, tracelib.WINDOW_SPAN)])
    cap = types.SimpleNamespace(
        trace=trace, device={"kind": "TPU v5 lite"},
        driver=types.SimpleNamespace(train_net_param=lambda: net),
        traced=types.SimpleNamespace(steps=3, img_s=5.0),
        cell=types.SimpleNamespace(chips=1,
                                   mix={"compute_dtype": "bfloat16"}),
        counters={"moe_load": {"a": {"rows": [2, 2, 4], "dropped": 0},
                               "b": {"rows": [3, 3, 3], "dropped": 0}}})
    assert attn_share.read(cap) == pytest.approx(30.0)
    assert moe_share.read(cap) == pytest.approx(40.0)
    assert moe_imbalance.read(cap) == pytest.approx(1.5)
    peak = peaks.peaks("TPU v5 lite")
    f = lm_flops.train_flops_per_sequence(net)
    assert mfu_lm.read(cap) == pytest.approx(
        100 * f["total"] * 5.0 / peak["flops_per_s"])
    assert attn_core_roofline.read(cap) == pytest.approx(
        100 * (f["core"] * 2 * 3 / peak["flops_per_s"]) / 2e-3)
    moved = lm_flops.expert_bytes_per_sequence(net, 2) * 2
    least = 3 * max(f["experts"] * 2 / peak["flops_per_s"],
                    moved / peak["hbm_bytes_per_s"])
    assert moe_experts_roofline.read(cap) == pytest.approx(
        100 * least / 3e-3)
    # 4 expert layers: each pass moves 4 held experts' three 32x16
    # matrices and the 32 routed rows of each of 2 sequences, in and out
    assert moved == 4 * 3 * (3 * 4 * 32 * 16 + 2 * 32 * 2 * 32) * 2
    # where no operation carries the scope, or there is no device plane
    cap.trace = tracelib.Trace(devices={0: ops[-1:]}, spans=trace.spans)
    assert attn_core_roofline.read(cap) is None
    assert attn_share.read(cap) is None
    cap.trace = tracelib.Trace(devices={}, spans=trace.spans)
    assert moe_experts_roofline.read(cap) is None
    assert moe_share.read(cap) is None


# -- lm_flops against hand counts ---------------------------------------------

def tiny_net(**over):
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    args = {**harness.load_json(os.path.join(DATA, "laguna_tiny.json"))[
        "builder_args"], **over}
    return models.laguna(2, 1, seq_len=32, **args).filtered(
        NetState(Phase.TRAIN))


def test_causal_pairs_by_hand():
    assert lm_flops.causal_pairs(4) == 10              # 1 + 2 + 3 + 4
    assert lm_flops.causal_pairs(6, 3) == 1 + 2 + 3 + 3 + 3 + 3
    assert lm_flops.causal_pairs(3, 8) == 6


def test_parameters_and_flops_by_hand():
    net = tiny_net()
    h, d, s = 32, 8, 32
    attn = lambda heads: h * (2 * heads * d + 2 * 2 * d + heads)
    moe = h * 16 + 3 * 4 * h * 16 + 3 * h * 16
    params = (2 * 64 * h + 11 * h + 2 * attn(4) + 3 * attn(6)
              + 3 * h * 64 + 4 * moe)
    assert lm_flops.as_built(net)["parameters"] == params
    f = lm_flops.train_flops_per_sequence(net)
    core = 2 * d * (2 * 4 * lm_flops.causal_pairs(s)
                    + 3 * 6 * lm_flops.causal_pairs(s, 8))
    assert f["core"] == 6 * core
    rows = s * 4 * 4 / 16                   # top 4 of 16, 4 held
    assert f["experts"] == 6 * 4 * rows * 3 * h * 16
    other = s * (2 * attn(4) + 3 * attn(6) + 3 * h * 64
                 + 4 * (h * 16 + 3 * h * 16) + h * 64)
    assert f["other"] == 6 * other
    assert f["total"] == f["core"] + f["experts"] + f["other"]


def test_as_built_refuses_another_net():
    cfg = harness.load_json(os.path.join(DATA, "laguna_tiny.json"))
    lm_flops.check_as_built(cfg, tiny_net())
    with pytest.raises(SystemExit, match="not the one"):
        lm_flops.check_as_built(cfg, tiny_net(expert_width=32))


# -- the reference's expert layer against a loop ------------------------------

def test_reference_moe_against_a_loop_over_tokens():
    rng = np.random.default_rng(0)
    s, h, w, experts, k, lo, hi = 24, 16, 8, 12, 3, 4, 9
    blobs = [rng.normal(0, 0.3, (h, experts)),
             *(rng.normal(0, 0.3, shape) for shape in (
                 (hi - lo, h, w), (hi - lo, h, w), (hi - lo, w, h),
                 (h, w), (h, w), (w, h)))]
    blobs = [b.astype(np.float32) for b in blobs]
    x = rng.normal(0, 1, (s, h)).astype(np.float32)
    m = {"top_k": k, "held": (lo, hi), "scaling": 2.5}
    got = np.asarray(reference_lm.highest(
        lambda x, b: reference_lm.moe(x, b, m))(x, blobs))

    silu = lambda v: v / (1 + np.exp(-v))
    mlp = lambda v, g, u, d: (silu(v @ g) * (v @ u)) @ d
    wr, eg, eu, ed, sg, su, sd = (b.astype(np.float64) for b in blobs)
    want = np.zeros((s, h))
    for t in range(s):
        scores = 1 / (1 + np.exp(-(x[t].astype(np.float64) @ wr)))
        picks = np.argsort(-scores, kind="stable")[:k]
        for e in picks:
            if lo <= e < hi:
                weight = scores[e] / scores[picks].sum() * 2.5
                want[t] += weight * mlp(x[t], eg[e - lo], eu[e - lo],
                                        ed[e - lo])
        want[t] += mlp(x[t], sg, su, sd)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
