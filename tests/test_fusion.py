"""Vertical fusion pass tests (graph/fusion.py planning, graph/net.py
block execution, ops/vision.py + ops/pallas_kernels.py LRN epilogues):
legality, the plan as a function of the graph, fwd/bwd parity per chain
shape, gradcheck on the custom-VJP epilogue, the SPARKNET_FUSE=off
escape hatch, and the unfused-run telemetry signal."""

import ast
import builtins
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import models
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


def _conv(name, bottom, top, bias_term=True, **kw):
    kw.setdefault("num_output", 8)
    kw.setdefault("kernel", 3)
    kw.setdefault("pad", 1)
    kw.setdefault("weight_filler", WF)
    kw.setdefault("bias_filler", BF)
    lp = convolution_layer(name, bottom, top, **kw)
    if not bias_term:
        lp.params["convolution_param"].add("bias_term", False)
    return lp


def _chain_net(*, pool=False, lrn=False, leaky=False, within=False,
               lrn_first=False, c=3, **conv_kw):
    """conv -> relu [-> pool] [-> lrn] -> ip -> loss; ``lrn_first`` puts
    the LRN before the pool (AlexNet's order)."""
    layers = [_input(c=c), _conv("conv", "data", "conv", **conv_kw)]
    relu = relu_layer("relu", "conv", "conv")
    if leaky:
        relu = layer("relu", "ReLU", ["conv"], ["conv"],
                     relu_param={"negative_slope": 0.1})
    layers.append(relu)
    head = "conv"
    stages = [s for s, on in (("pool", pool), ("norm", lrn)) if on]
    for stage in reversed(stages) if lrn_first else stages:
        if stage == "pool":
            layers.append(pooling_layer("pool", head, "pool", kernel=2,
                                        stride=2))
        else:
            lp = _lrn(head)
            if within:
                lp.params["lrn_param"].add("norm_region", "WITHIN_CHANNEL")
            layers.append(lp)
        head = stage
    layers += [
        inner_product_layer("ip", head, "ip", num_output=5,
                            weight_filler={"type": "gaussian", "std": 0.01}),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ]
    return net_param("chain", layers)


def _build(netp, fuse=None, dtype=None, phase=Phase.TRAIN):
    """``fuse``: None leaves the knob unset (the graph's plan), "off" is
    per-layer execution."""
    os.environ.pop("SPARKNET_FUSE", None)
    if fuse is not None:
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
    assert c.epilogue == "lrn"          # pool between relu and LRN: the
    #                                     ReLU can't fold into the kernel
    net2 = _build(_chain_net(lrn=True), "off")
    (c2,) = fusion.chain_candidates(net2)
    assert c2.members == ["conv", "relu", "norm"]
    assert c2.epilogue == "relu+lrn"    # zero-slope ReLU folds in
    net3 = _build(_chain_net(pool=True, lrn=True, lrn_first=True), "off")
    (c3,) = fusion.chain_candidates(net3)
    assert c3.members == ["conv", "relu", "norm"]   # the pool stays out
    # a ReLU or a pool behind a convolution is no chain without the LRN
    for plain in (_chain_net(), _chain_net(pool=True)):
        assert fusion.chain_candidates(_build(plain, "off")) == []


def test_leaky_relu_does_not_fold_into_the_epilogue():
    net = _build(_chain_net(lrn=True, leaky=True), "off")
    (c,) = fusion.chain_candidates(net)
    assert c.members == ["conv", "relu", "norm"]
    assert c.epilogue == "lrn"          # leaky slope: in-block ReLU impl


@pytest.mark.parametrize("pool", [False, True],
                         ids=["within_lrn", "pool_within_lrn"])
def test_within_channel_lrn_gets_no_epilogue(pool, rng):
    """A WITHIN_CHANNEL LRN is an AVE pool over space, not a window over
    channels: no epilogue, so no chain, and the unset knob runs what
    ``off`` runs."""
    netp = _chain_net(pool=pool, lrn=True, within=True)
    net_off, net = _build(netp, "off"), _build(netp)
    assert fusion.chain_candidates(net) == []
    assert net.fuse_plan_id() == "off" and net._vfuse_head == {}
    params, ins = net.init(rng), _inputs(net)
    l0, g0 = jax.value_and_grad(
        lambda p: net_off.apply(p, ins, rng=rng).loss)(params)
    l1, g1 = jax.value_and_grad(
        lambda p: net.apply(p, ins, rng=rng).loss)(params)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _lrn(bottom, top="norm"):
    return lrn_layer(top, bottom, top, local_size=5, alpha=1e-3, beta=0.75)


def test_fanout_blocks_the_chain():
    def netp(fan_out):
        return net_param("fan", [
            _input(label=False),
            _conv("conv", "data", "conv"),
            relu_layer("relu", "conv", "convr"),
            _lrn("convr"),
            concat_layer("cat", ["conv" if fan_out else "data", "norm"],
                         "out"),
        ])
    # the control chains; a second reader of the conv's top blocks it
    (c,) = fusion.chain_candidates(_build(netp(False), "off", phase=Phase.TEST))
    assert c.members == ["conv", "relu", "norm"]
    net = _build(netp(True), "off", phase=Phase.TEST)
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
        _lrn("conv"),
        concat_layer("cat", ["norm", "side"], "out"),
    ])
    net = _build(netp, "off", phase=Phase.TEST)
    assert [c.members for c in fusion.chain_candidates(net)] == []


@pytest.mark.parametrize("member", ["dropout", "stochastic_pool"])
def test_stochastic_members_are_refused(member):
    between = (dropout_layer("drop", "conv", "conv") if member == "dropout"
               else layer("pool", "Pooling", ["conv"], ["conv"],
                          pooling_param={"pool": "STOCHASTIC",
                                         "kernel_size": 2, "stride": 2}))
    netp = net_param("rngnet", [
        _input(),
        _conv("conv", "data", "conv"),
        relu_layer("relu", "conv", "conv"),
        between,
        _lrn("conv"),
        inner_product_layer("ip", "norm", "ip", num_output=5,
                            weight_filler=WF),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ])
    # the walk stops before the stochastic layer, so it never reaches
    # the LRN and nothing chains
    assert fusion.chain_candidates(_build(netp, "off")) == []


def test_hfuse_members_are_off_limits(monkeypatch):
    # two sibling 1x1 convs form a horizontal group; the vertical pass
    # must not claim them even though each heads a legal LRN chain
    netp = net_param("sib", [
        _input(label=False),
        _conv("a", "data", "a", kernel=1, pad=0),
        relu_layer("ar", "a", "a"),
        _lrn("a", "an"),
        _conv("b", "data", "b", kernel=1, pad=0),
        relu_layer("br", "b", "b"),
        _lrn("b", "bn"),
        concat_layer("cat", ["an", "bn"], "out"),
    ])
    net = _build(netp, phase=Phase.TEST)
    assert set(net._hfuse_member) | set(net._hfuse_first) == {"a", "b"}
    assert net._vfuse_head == {}
    monkeypatch.setenv("SPARKNET_NO_HFUSE", "1")     # the control
    assert list(_build(netp, phase=Phase.TEST)._vfuse_head) == ["a", "b"]


# ---------------------------------------------------------------------------
# The plan: a function of the graph
# ---------------------------------------------------------------------------

def test_off_is_the_escape_hatch():
    net = _build(_chain_net(lrn=True), "off")
    assert net.fuse_plan_id() == "off"
    assert net._vfuse_head == {}


def test_unset_plans_every_lrn_tailed_chain():
    net = _build(_chain_net(pool=True, lrn=True))
    assert list(net._vfuse_head) == ["conv"]
    assert net.fuse_plan_id().startswith("vf1-")


def test_plan_id_is_stable_and_plan_sensitive():
    a = _build(_chain_net(lrn=True))
    b = _build(_chain_net(lrn=True))
    c = _build(_chain_net(pool=True, lrn=True))
    assert a.fuse_plan_id() == b.fuse_plan_id()
    assert a.fuse_plan_id() != c.fuse_plan_id()


def test_bad_fuse_value_is_a_loud_error():
    with pytest.raises(ValueError, match="SPARKNET_FUSE"):
        _build(_chain_net(), "onn")


@pytest.mark.parametrize("value", ["auto", "all", "a_path"])
def test_fuse_knob_is_off_or_unset(value, tmp_path):
    """The values the profile-driven planner took are typos now, an
    existing file's path too."""
    if value == "a_path":
        value = str(tmp_path / "fusion_plan.json")
        with open(value, "w") as f:
            json.dump({"version": 1, "chains": []}, f)
    with pytest.raises(ValueError, match="SPARKNET_FUSE"):
        _build(_chain_net(lrn=True), value)


EXPECTED_PLANS = {
    "caffenet": ("vf2-15898bac", [
        ("conv1+relu1+pool1+norm1", "lrn"),
        ("conv2+relu2+pool2+norm2", "lrn")]),
    "googlenet": ("vf2-0a31f515", [
        ("conv1/7x7_s2+conv1/7x7_s2/relu+pool1/3x3_s2+pool1/norm1", "lrn"),
        ("conv2/3x3+conv2/3x3/relu+conv2/norm2", "relu+lrn")]),
    "alexnet": ("vf2-a559887c", [
        ("conv1+relu1+norm1", "relu+lrn"),
        ("conv2+relu2+norm2", "relu+lrn")]),
    "vgg16": ("off", []),
    "lenet": ("off", []),
    "cifar10_quick": ("off", []),
    "cifar10_full": ("off", []),     # its LRNs are WITHIN_CHANNEL
}


@pytest.mark.parametrize("model", list(EXPECTED_PLANS))
def test_plan_is_a_function_of_the_graph(model):
    plan_id, chains = EXPECTED_PLANS[model]
    net = _build(getattr(models, model)(2, 2))
    assert [(c.scope(), c.epilogue) for c in net._fuse_plan.chains] == chains
    assert net.fuse_plan_id() == plan_id


@pytest.mark.parametrize("model", ["caffenet", "googlenet", "alexnet"])
def test_plan_ignores_the_name_and_the_repository(model, tmp_path,
                                                  monkeypatch):
    """The same graph under another name, built from another directory
    with ``profiles/`` unreadable, gets the same plan."""
    netp = getattr(models, model)(2, 2)
    want = _build(netp).fuse_plan_id()
    assert want == EXPECTED_PLANS[model][0]
    netp.name = "somebody_elses_net"
    monkeypatch.chdir(tmp_path)
    real_open, real_listdir = builtins.open, os.listdir

    def no_profiles(real):
        def guarded(path, *a, **kw):
            assert "profiles" not in str(path), f"the plan read {path}"
            return real(path, *a, **kw)
        return guarded

    monkeypatch.setattr(builtins, "open", no_profiles(real_open))
    monkeypatch.setattr(os, "listdir", no_profiles(real_listdir))
    assert _build(netp).fuse_plan_id() == want


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py_files(*parts):
    top = os.path.join(REPO, "sparknet_tpu", *parts)
    if top.endswith(".py"):
        return [top]
    return [os.path.join(d, f) for d, _, fs in os.walk(top)
            for f in fs if f.endswith(".py")]


def test_ops_import_nothing_from_graph():
    """``ops`` is the lower layer: no import of ``graph``, at module
    level or inside a function."""
    for path in _py_files("ops"):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                base = "." * node.level + (node.module or "")
                names = [base] + [f"{base}.{a.name}".replace("...", "..")
                                  for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            assert not any(n.startswith(("..graph", "sparknet_tpu.graph"))
                           for n in names), \
                f"{path}:{node.lineno} imports graph"


def test_program_opens_no_repo_record():
    """Nothing on the training path names the ``profiles`` directory: the
    program does not read the repository's records."""
    for path in (_py_files("graph") + _py_files("ops") + _py_files("solvers")
                 + _py_files("parallel", "trainer.py")):
        with open(path) as f:
            for n, line in enumerate(f, 1):
                assert not any(w in line for w in (
                    "profiles/", '"profiles"', "'profiles'")), \
                    f"{path}:{n}: {line.strip()}"


# ---------------------------------------------------------------------------
# Execution parity
# ---------------------------------------------------------------------------

PARITY_SHAPES = {
    "lrn": dict(lrn=True),
    "pool_lrn": dict(pool=True, lrn=True),
    "leaky_lrn": dict(lrn=True, leaky=True),
    # AlexNet's order: conv -> relu -> LRN -> pool, the pool outside
    "relu_lrn_alexnet_order": dict(pool=True, lrn=True, lrn_first=True),
    "pool_lrn_grouped_conv": dict(pool=True, lrn=True, c=4, group=2),
    "lrn_conv_without_bias": dict(lrn=True, bias_term=False),
}


@pytest.mark.parametrize("shape", list(PARITY_SHAPES))
def test_fused_chain_parity_fwd_bit_bwd_ulp(shape, rng):
    netp = _chain_net(**PARITY_SHAPES[shape])
    net_off = _build(netp, "off")
    net_all = _build(netp)
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
    net_all = _build(netp, dtype=jnp.bfloat16)
    params = net_off.init(rng)
    ins = _inputs(net_off)
    l0 = net_off.apply(params, ins, rng=rng).loss
    l1 = net_all.apply(params, ins, rng=rng).loss
    assert float(l0) == float(l1)


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16],
                         ids=["alexnet-f32", "alexnet-bf16"])
def test_fused_matches_per_layer(dtype, rng):
    """AlexNet (two relu+lrn chains, no cell runs it) at its published
    widths: the fused loss is the per-layer loss bit for bit, the
    gradients agree to the custom VJP's bound."""
    netp = models.alexnet(2, 2)
    net_off, net = _build(netp, "off", dtype=dtype), _build(netp, dtype=dtype)
    assert len(net._vfuse_head) == 2
    params = net_off.init(rng)
    r = np.random.default_rng(0)
    ins = {b: jnp.asarray(r.integers(0, 1000, size=shape) if b == "label"
                          else r.normal(size=shape), jnp.float32)
           for b, shape in net.input_blobs.items()}
    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: net_off.apply(p, ins, rng=rng).loss))(params)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: net.apply(p, ins, rng=rng).loss))(params)
    assert float(l0) == float(l1)
    tol = 1e-5 if dtype is None else 2e-2
    for k in g0:
        for a, b in zip(g0[k], g1[k]):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            scale = float(np.max(np.abs(a))) or 1.0
            assert float(np.max(np.abs(a - b))) / scale <= tol, k


def test_fused_training_chain_gradcheck(rng):
    """Finite-difference gradcheck THROUGH the fused relu+lrn epilogue:
    the custom VJP must match the numerical derivative of the fused
    forward, not merely the unfused path."""
    netp = _chain_net(lrn=True)
    net = _build(netp)
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
    net = _build(netp)
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
        net = _build(_chain_net(lrn=True))
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
        net = _build(_chain_net(lrn=True))
        params = net.init(rng)
        net.apply(params, _inputs(net), rng=rng)
        snap = telemetry.get_registry().snapshot()
        assert not (snap.get("fusion_unfused_runs_total") or {}).get(
            "samples")
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# The cumsum default
# ---------------------------------------------------------------------------

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
