"""Vertical fusion pass tests (graph/fusion.py planning, graph/net.py
block execution, ops/vision.py + ops/pallas_kernels.py LRN epilogues):
legality, plan sources and replay, fwd/bwd parity per chain shape,
gradcheck on the custom-VJP epilogue, the SPARKNET_FUSE=off escape
hatch, and the unfused-run telemetry signal."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.graph import Net, fusion
from sparknet_tpu.models.dsl import (
    concat_layer,
    convolution_layer,
    dropout_layer,
    inner_product_layer,
    layer,
    lrn_layer,
    net_param,
    pooling_layer,
    relu_layer,
    softmax_with_loss_layer,
)
from sparknet_tpu.proto import NetState, Phase

pytestmark = pytest.mark.fusion

WF = {"type": "gaussian", "std": 0.05}
BF = {"type": "constant", "value": 0.1}


def _input(batch=2, c=3, side=10, label=True):
    shapes = [{"dim": [batch, c, side, side]}]
    tops = ["data"]
    if label:
        shapes.append({"dim": [batch]})
        tops.append("label")
    return layer("data", "Input", tops=tops,
                 input_param={"shape": shapes})


def _conv(name, bottom, top, **kw):
    kw.setdefault("num_output", 8)
    kw.setdefault("kernel", 3)
    kw.setdefault("pad", 1)
    kw.setdefault("weight_filler", WF)
    kw.setdefault("bias_filler", BF)
    return convolution_layer(name, bottom, top, **kw)


def _chain_net(*, pool=False, lrn=False, leaky=False, within=False):
    """conv -> relu [-> pool] [-> lrn] -> ip -> loss."""
    layers = [_input(), _conv("conv", "data", "conv")]
    relu = relu_layer("relu", "conv", "conv")
    if leaky:
        relu.params["relu_param"] = relu.params.get("relu_param") or None
        relu = layer("relu", "ReLU", ["conv"], ["conv"],
                     relu_param={"negative_slope": 0.1})
    layers.append(relu)
    head = "conv"
    if pool:
        layers.append(pooling_layer("pool", head, "pool", kernel=2,
                                    stride=2))
        head = "pool"
    if lrn:
        lp = lrn_layer("norm", head, "norm", local_size=5, alpha=1e-3,
                       beta=0.75)
        if within:
            lp.params["lrn_param"].add("norm_region", "WITHIN_CHANNEL")
        layers.append(lp)
        head = "norm"
    layers += [
        inner_product_layer("ip", head, "ip", num_output=5,
                            weight_filler={"type": "gaussian", "std": 0.01}),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ]
    return net_param("chain", layers)


def _build(netp, fuse, dtype=None, phase=Phase.TRAIN):
    os.environ["SPARKNET_FUSE"] = fuse
    try:
        return Net(netp, NetState(phase), compute_dtype=dtype)
    finally:
        os.environ.pop("SPARKNET_FUSE", None)


def _inputs(net, seed=0):
    r = np.random.default_rng(seed)
    out = {}
    for b, shape in net.input_blobs.items():
        if b == "label":
            out[b] = jnp.asarray(r.integers(0, 5, size=shape), jnp.float32)
        else:
            out[b] = jnp.asarray(r.normal(size=shape), jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Legality
# ---------------------------------------------------------------------------

def test_candidates_cover_every_chain_family():
    net = _build(_chain_net(pool=True, lrn=True), "off")
    (c,) = fusion.chain_candidates(net)
    assert c.members == ["conv", "relu", "pool", "norm"]
    assert c.kind == "conv+bias+relu+pool+LRN"
    assert c.epilogue == "lrn"          # pool between relu and LRN: the
    #                                     ReLU can't fold into the kernel
    net2 = _build(_chain_net(lrn=True), "off")
    (c2,) = fusion.chain_candidates(net2)
    assert c2.members == ["conv", "relu", "norm"]
    assert c2.epilogue == "relu+lrn"    # zero-slope ReLU folds in
    net3 = _build(_chain_net(), "off")
    (c3,) = fusion.chain_candidates(net3)
    assert c3.members == ["conv", "relu"]
    assert c3.epilogue == "none"


def test_leaky_relu_does_not_fold_into_the_epilogue():
    net = _build(_chain_net(lrn=True, leaky=True), "off")
    (c,) = fusion.chain_candidates(net)
    assert c.members == ["conv", "relu", "norm"]
    assert c.epilogue == "lrn"          # leaky slope: in-block ReLU impl


def test_within_channel_lrn_gets_no_epilogue():
    net = _build(_chain_net(lrn=True, within=True), "off")
    (c,) = fusion.chain_candidates(net)
    assert c.epilogue == "none"         # runs its own impl inside the block


def test_fanout_blocks_the_chain():
    netp = net_param("fan", [
        _input(label=False),
        _conv("conv", "data", "conv"),
        relu_layer("relu", "conv", "convr"),
        concat_layer("cat", ["conv", "convr"], "out"),
    ])
    net = _build(netp, "off", phase=Phase.TEST)
    assert fusion.chain_candidates(net) == []


def test_inplace_reread_blocks_the_chain():
    # 'conv' is rewritten in place by relu; a later reader of the post-
    # relu version is the chain, but a reader of the PRE-relu version
    # makes the intermediate multi-consumer at its produced version
    netp = net_param("ver", [
        _input(label=False),
        _conv("conv", "data", "conv"),
        _conv("side", "conv", "side"),     # reads conv@1 (pre-relu)
        relu_layer("relu", "conv", "conv"),
        concat_layer("cat", ["conv", "side"], "out"),
    ])
    net = _build(netp, "off", phase=Phase.TEST)
    assert [c.members for c in fusion.chain_candidates(net)] == []


def test_stochastic_members_are_refused():
    netp = net_param("rngnet", [
        _input(),
        _conv("conv", "data", "conv"),
        relu_layer("relu", "conv", "conv"),
        dropout_layer("drop", "conv", "conv"),
        inner_product_layer("ip", "conv", "ip", num_output=5,
                            weight_filler=WF),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ])
    net = _build(netp, "off")
    # the chain stops before the dropout, it never joins
    (c,) = fusion.chain_candidates(net)
    assert c.members == ["conv", "relu"]


def test_hfuse_members_are_off_limits():
    # two sibling 1x1 convs form a horizontal group; the vertical pass
    # must not claim them even though each tails a legal relu chain
    netp = net_param("sib", [
        _input(label=False),
        _conv("a", "data", "a", kernel=1, pad=0),
        relu_layer("ar", "a", "a"),
        _conv("b", "data", "b", kernel=1, pad=0),
        relu_layer("br", "b", "b"),
        concat_layer("cat", ["a", "b"], "out"),
    ])
    net = _build(netp, "all", phase=Phase.TEST)
    assert set(net._hfuse_member) | set(net._hfuse_first) == {"a", "b"}
    assert net._vfuse_head == {}


# ---------------------------------------------------------------------------
# Plan sources
# ---------------------------------------------------------------------------

def test_off_is_the_escape_hatch():
    net = _build(_chain_net(lrn=True), "off")
    assert net.fuse_plan_id() == "off"
    assert net._vfuse_head == {}


def test_all_plans_every_legal_chain():
    net = _build(_chain_net(pool=True, lrn=True), "all")
    assert list(net._vfuse_head) == ["conv"]
    assert net.fuse_plan_id().startswith("vf1-")


def test_plan_id_is_stable_and_plan_sensitive():
    a = _build(_chain_net(lrn=True), "all")
    b = _build(_chain_net(lrn=True), "all")
    c = _build(_chain_net(pool=True, lrn=True), "all")
    assert a.fuse_plan_id() == b.fuse_plan_id()
    assert a.fuse_plan_id() != c.fuse_plan_id()


def test_plan_file_roundtrip_and_stale_refusal(tmp_path):
    net = _build(_chain_net(pool=True, lrn=True), "all")
    path = str(tmp_path / "fusion_plan.json")
    net._fuse_plan.save(path)
    replay = _build(_chain_net(pool=True, lrn=True), path)
    assert replay.fuse_plan_id() == net.fuse_plan_id()
    assert replay._fuse_plan.source == f"file:{path}"
    # graph drift: the recorded chain no longer exists -> refused
    drifted = _build(_chain_net(pool=False, lrn=True), path)
    assert drifted._vfuse_head == {}
    assert any("not legal" in r["reason"]
               for r in drifted._fuse_plan.refused)


def test_plan_version_gate(tmp_path):
    doc = {"version": fusion.PLAN_VERSION + 1, "chains": []}
    p = tmp_path / "future.json"
    p.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="newer"):
        fusion.FusionPlan.load(str(p))


def test_profile_plan_fuses_worklist_hits_and_refuses_misses():
    netp = _chain_net(pool=True, lrn=True)
    net = _build(netp, "off")
    table = {"by_layer": [
        # tail of the legal chain, bandwidth-bound: must fuse
        {"op": "norm", "total_ms": 50.0, "pct": 40.0, "gb_per_s": 500.0,
         "gflops_per_s": 100.0},
        # not in this net at all: must be refused with a reason
        {"op": "ghost", "total_ms": 20.0, "pct": 20.0, "gb_per_s": 300.0},
        # the band-setting neighbor (not a candidate itself)
        {"op": "ip", "total_ms": 30.0, "pct": 30.0, "gb_per_s": 1100.0},
    ]}
    plan = fusion.plan_from_profile(net, table, source="auto:test")
    assert [c.members for c in plan.chains] == [
        ["conv", "relu", "pool", "norm"]]
    assert plan.chains[0].source["reclaimable_ms"] is not None
    assert [r["candidate"] for r in plan.refused] == ["ghost"]


def test_bad_fuse_value_is_a_loud_error():
    with pytest.raises(ValueError, match="SPARKNET_FUSE"):
        _build(_chain_net(), "onn")


def test_auto_without_profile_plans_nothing(monkeypatch):
    monkeypatch.setattr(fusion, "default_profile_table", lambda name: None)
    net = _build(_chain_net(lrn=True), "auto")
    assert net.fuse_plan_id() == "off"
    assert net._fuse_plan.source == "auto:no-profile"


def test_committed_googlenet_profile_drives_the_auto_plan():
    # the acceptance chain: profiles/googlenet names conv2/norm2 first;
    # auto must fuse the chain that contains it
    from sparknet_tpu.models import googlenet
    net = _build(googlenet(2, 2), "auto")
    scopes = [net._vfuse_head[h].scope() for h in net._vfuse_head]
    assert any("conv2/norm2" in s for s in scopes), scopes
    assert net._fuse_plan.source.startswith("auto:profiles/googlenet")


# ---------------------------------------------------------------------------
# Execution parity (the fusebench contract, in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["relu", "pool", "lrn", "pool_lrn",
                                   "leaky_lrn", "within_lrn"])
def test_fused_chain_parity_fwd_bit_bwd_ulp(shape, rng):
    netp = _chain_net(pool="pool" in shape, lrn="lrn" in shape,
                      leaky="leaky" in shape, within="within" in shape)
    net_off = _build(netp, "off")
    net_all = _build(netp, "all")
    assert net_all._vfuse_head, "nothing fused — test is vacuous"
    params = net_off.init(rng)
    ins = _inputs(net_off)

    def loss(net):
        return lambda p: net.apply(p, ins, rng=rng).loss

    l0, g0 = jax.value_and_grad(loss(net_off))(params)
    l1, g1 = jax.value_and_grad(loss(net_all))(params)
    assert float(l0) == float(l1)          # forward: bit-identical
    for k in g0:
        for a, b in zip(g0[k], g1[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_fused_chain_parity_bf16(rng):
    netp = _chain_net(pool=True, lrn=True)
    net_off = _build(netp, "off", dtype=jnp.bfloat16)
    net_all = _build(netp, "all", dtype=jnp.bfloat16)
    params = net_off.init(rng)
    ins = _inputs(net_off)
    l0 = net_off.apply(params, ins, rng=rng).loss
    l1 = net_all.apply(params, ins, rng=rng).loss
    assert float(l0) == float(l1)


def test_fused_training_chain_gradcheck(rng):
    """Finite-difference gradcheck THROUGH the fused relu+lrn epilogue:
    the custom VJP must match the numerical derivative of the fused
    forward, not merely the unfused path."""
    netp = _chain_net(lrn=True)
    net = _build(netp, "all")
    params = net.init(rng)
    ins = _inputs(net)
    f = lambda p: float(net.apply(p, ins, rng=rng).loss)  # noqa: E731
    g = jax.grad(lambda p: net.apply(p, ins, rng=rng).loss)(params)
    eps = 1e-3
    r = np.random.default_rng(2)
    for key in ("conv", "ip"):
        w = np.asarray(params[key][0])
        for _ in range(3):
            idx = tuple(r.integers(0, d) for d in w.shape)
            wp, wm = w.copy(), w.copy()
            wp[idx] += eps
            wm[idx] -= eps
            pp = dict(params); pp[key] = [jnp.asarray(wp)] + params[key][1:]
            pm = dict(params); pm[key] = [jnp.asarray(wm)] + params[key][1:]
            num = (f(pp) - f(pm)) / (2 * eps)
            ana = float(np.asarray(g[key][0])[idx])
            assert num == pytest.approx(ana, rel=5e-2, abs=1e-4), (key, idx)


def test_relu_lrn_reference_gradcheck(np_rng):
    """The epilogue op itself (ops/vision.py custom VJP) against
    jax.test_util-style numerical differentiation, relu on and off."""
    from sparknet_tpu.ops.vision import relu_lrn_reference
    x = jnp.asarray(np_rng.normal(size=(2, 8, 3, 3)), jnp.float32)
    for relu in (False, True):
        fn = lambda x: jnp.sum(jnp.sin(  # noqa: E731
            relu_lrn_reference(x, 5, 1e-2, 0.75, 1.0, relu)))
        g = jax.grad(fn)(x)
        eps = 1e-3
        r = np.random.default_rng(3)
        xf = np.asarray(x)
        for _ in range(5):
            idx = tuple(r.integers(0, d) for d in x.shape)
            if relu and abs(xf[idx]) < 2 * eps:
                continue   # kink at 0: numerical diff is undefined there
            xp, xm = xf.copy(), xf.copy()
            xp[idx] += eps
            xm[idx] -= eps
            num = (float(fn(jnp.asarray(xp))) - float(fn(jnp.asarray(xm)))
                   ) / (2 * eps)
            assert num == pytest.approx(float(g[idx]), rel=2e-2, abs=1e-5)


def test_pallas_relu_lrn_epilogue_matches_reference(np_rng, monkeypatch):
    """The Pallas kernel face (interpret mode on CPU) against the XLA
    reference: forward and VJP, relu folded and not."""
    from sparknet_tpu.ops import pallas_kernels
    from sparknet_tpu.ops.pallas_kernels import relu_lrn_across_channels
    monkeypatch.setattr(pallas_kernels, "_INTERPRET", True)
    from sparknet_tpu.ops.vision import relu_lrn_reference
    x = jnp.asarray(np_rng.normal(size=(2, 8, 3, 5)), jnp.float32)
    for relu in (False, True):
        y_k = relu_lrn_across_channels(x, 5, 1e-2, 0.75, 1.0, relu)
        y_r = relu_lrn_reference(x, 5, 1e-2, 0.75, 1.0, relu)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=1e-5, atol=1e-6)
        g_k = jax.grad(lambda x: jnp.sum(jnp.sin(
            relu_lrn_across_channels(x, 5, 1e-2, 0.75, 1.0, relu))))(x)
        g_r = jax.grad(lambda x: jnp.sum(jnp.sin(
            relu_lrn_reference(x, 5, 1e-2, 0.75, 1.0, relu))))(x)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-5)


def test_apply_all_surfaces_real_intermediates(rng):
    """apply_all must return REAL per-layer blobs even on a fused net —
    it runs the unfused path (introspection), and those intermediates
    must agree with what the fused chain computes internally."""
    netp = _chain_net(lrn=True)
    net = _build(netp, "all")
    params = net.init(rng)
    ins = _inputs(net)
    blobs = net.apply_all(params, ins, rng=rng)
    assert "conv" in blobs and "norm" in blobs
    # and the fused full run agrees with the introspected loss
    assert float(net.apply(params, ins, rng=rng).loss) == float(
        blobs["loss"])


# ---------------------------------------------------------------------------
# Telemetry: the silent-skip blind spot
# ---------------------------------------------------------------------------

def test_unfused_run_of_fusable_net_is_not_silent(rng, tmp_path,
                                                  monkeypatch):
    from sparknet_tpu.utils import telemetry
    monkeypatch.setenv("SPARKNET_TELEMETRY", "1")
    monkeypatch.setenv("SPARKNET_TRACE_DIR", str(tmp_path))
    telemetry.reset()
    try:
        net = _build(_chain_net(lrn=True), "all")
        params = net.init(rng)
        ins = _inputs(net)
        net.apply_all(params, ins, rng=rng, upto="relu")   # ranged
        net.apply_all(params, ins, rng=rng, upto="relu")   # same reason
        net.apply_all(params, ins, rng=rng)                # introspect
        reg = telemetry.get_registry()
        snap = reg.snapshot()
        fam = snap.get("fusion_unfused_runs_total") or {}
        by_reason = {tuple(sorted((s.get("labels") or {}).items())):
                     s["value"] for s in fam.get("samples") or []}
        assert by_reason.get((("reason", "ranged"),)) == 2.0
        assert by_reason.get((("reason", "introspect"),)) == 1.0
        # the instant() is one-shot per reason
        tr = telemetry.get_tracer()
        tr.flush()
        events = []
        for fn in os.listdir(tmp_path):
            if fn.startswith("trace_"):
                with open(tmp_path / fn) as f:
                    events += [json.loads(line) for line in f if
                               line.strip()]
        names = [e["name"] for e in events
                 if e.get("name") == "fusion.unfused_run"]
        assert len(names) == 2          # ranged once + introspect once
    finally:
        telemetry.reset()


def test_full_fused_run_emits_no_skip_signal(rng, tmp_path, monkeypatch):
    from sparknet_tpu.utils import telemetry
    monkeypatch.setenv("SPARKNET_TELEMETRY", "1")
    monkeypatch.setenv("SPARKNET_TRACE_DIR", str(tmp_path))
    telemetry.reset()
    try:
        net = _build(_chain_net(lrn=True), "all")
        params = net.init(rng)
        net.apply(params, _inputs(net), rng=rng)
        snap = telemetry.get_registry().snapshot()
        assert not (snap.get("fusion_unfused_runs_total") or {}).get(
            "samples")
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# The worklist library + the cumsum default
# ---------------------------------------------------------------------------

def test_worklist_reports_fused_chains_against_ref_band():
    doc = {"by_layer": [
        {"op": "a+b+c", "total_ms": 20.0, "pct": 10.0, "gb_per_s": 1000.0},
        {"op": "slow+chain", "total_ms": 10.0, "pct": 5.0,
         "gb_per_s": 400.0},
        {"op": "norm", "total_ms": 30.0, "pct": 20.0, "gb_per_s": 500.0,
         "gflops_per_s": 100.0},
    ]}
    wl = fusion.fusion_worklist(doc)
    assert [c["chain"] for c in wl["candidates"]] == ["norm"]
    fused = {c["chain"]: c for c in wl["fused_chains"]}
    assert fused["a+b+c"]["at_ref_band"] is True
    assert fused["slow+chain"]["at_ref_band"] is False


def test_lrn_cumsum_default_is_backend_and_width_aware(monkeypatch):
    from sparknet_tpu.ops import vision
    # this rig is CPU: the probe verdict (RESULTS.md r10) keeps the
    # unset default on reduce_window at EVERY width
    assert vision.lrn_use_cumsum(vision.LRN_CUMSUM_AUTO_C) is False
    assert vision.lrn_use_cumsum(4096) is False
    # on TPU the unset default picks by channel count
    monkeypatch.setattr(vision.jax, "default_backend", lambda: "tpu")
    assert vision.lrn_use_cumsum(vision.LRN_CUMSUM_AUTO_C) is True
    assert vision.lrn_use_cumsum(vision.LRN_CUMSUM_AUTO_C - 1) is False


def test_lrn_cumsum_and_reduce_window_agree(np_rng):
    """The two window-sum forms are the same addends associated
    differently — values agree to fp tolerance at any channel count,
    so the auto flip can never change semantics."""
    from sparknet_tpu.ops import vision
    x = jnp.asarray(np_rng.normal(size=(2, 160, 4, 4)) ** 2, jnp.float32)
    a = vision.lrn_window_sum(x, 2, 2, use_cumsum=True)
    b = vision.lrn_window_sum(x, 2, 2, use_cumsum=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The CI gate itself
# ---------------------------------------------------------------------------

def test_fusebench_gate_passes(tmp_path):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "fusebench", os.path.join(repo, "tools", "fusebench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "fb.json")
    # --iters 0: the timing leg is noise at smoke size on a loaded CI
    # box; the parity/refusal contracts are what this test pins
    rc = mod.main(["--batch", "2", "--iters", "0", "--out", out])
    with open(out) as f:
        rep = json.load(f)
    assert rc == 0, rep["failures"]
    assert rep["chains"] == mod.EXPECTED_CHAINS
    assert rep["grad_max_rel"] < 1e-5
