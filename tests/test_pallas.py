"""Pallas kernel tests (the Pallas interpreter, asked for here: the CPU
has no Mosaic compiler): the fused LRN must match the XLA lowering in
forward and VJP, including through the LRNLayer dispatch."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from sparknet_tpu.models.dsl import layer
from sparknet_tpu.ops import get_layer_impl
from sparknet_tpu.ops.pallas_kernels import relu_lrn_across_channels

SIZE, ALPHA, BETA, K = 5, 1e-2, 0.75, 1.0


def lrn_across_channels(x, size, alpha, beta, k):
    return relu_lrn_across_channels(x, size, alpha, beta, k, False)


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    from sparknet_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_INTERPRET", True)


def _xla_lrn(x, size=SIZE, alpha=ALPHA, beta=BETA, k=K):
    pre = (size - 1) // 2
    post = size - 1 - pre
    ssum = lax.reduce_window(x * x, 0.0, lax.add, (1, size, 1, 1),
                             (1, 1, 1, 1),
                             ((0, 0), (pre, post), (0, 0), (0, 0)))
    return x / (k + (alpha / size) * ssum) ** beta


@pytest.fixture
def x(np_rng):
    return jnp.asarray(np_rng.normal(size=(2, 6, 5, 7)).astype(np.float32))


def test_pallas_lrn_forward(x):
    y = lrn_across_channels(x, SIZE, ALPHA, BETA, K)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_xla_lrn(x)),
                               rtol=1e-5, atol=1e-6)


def test_pallas_lrn_vjp(x):
    g1 = jax.grad(lambda x: jnp.sum(
        jnp.sin(lrn_across_channels(x, SIZE, ALPHA, BETA, K))))(x)
    g2 = jax.grad(lambda x: jnp.sum(jnp.sin(_xla_lrn(x))))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


def test_pallas_lrn_odd_window(np_rng):
    x = jnp.asarray(np_rng.normal(size=(1, 8, 3, 3)).astype(np.float32))
    y = lrn_across_channels(x, 3, 0.1, 0.5, 2.0)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_xla_lrn(x, 3, 0.1, 0.5, 2.0)),
        rtol=1e-5, atol=1e-6)


def test_planned_chain_lrn_reaches_the_kernel(rng, monkeypatch):
    """On the chip a planned chain's LRN is the kernel: with the backend
    check steered to ``tpu`` the train net's lowered gradient holds the
    forward and backward Pallas calls (interpret mode here, so by their
    jaxpr names), and its loss matches per-layer execution."""
    from sparknet_tpu.graph import Net
    from sparknet_tpu.models.dsl import (
        convolution_layer, inner_product_layer, lrn_layer, net_param,
        relu_layer, softmax_with_loss_layer)
    from sparknet_tpu.ops import vision
    from sparknet_tpu.proto import NetState, Phase

    netp = net_param("chain", [
        layer("data", "Input", tops=["data", "label"], input_param={
            "shape": [{"dim": [2, 3, 6, 6]}, {"dim": [2]}]}),
        convolution_layer("conv", "data", "conv", num_output=8, kernel=3,
                          pad=1, weight_filler={"type": "gaussian",
                                                "std": 0.05}),
        relu_layer("relu", "conv", "conv"),
        lrn_layer("norm", "conv", "norm", local_size=SIZE, alpha=ALPHA,
                  beta=BETA),
        inner_product_layer("ip", "norm", "ip", num_output=5,
                            weight_filler={"type": "gaussian", "std": 0.01}),
        softmax_with_loss_layer("loss", ["ip", "label"])])
    net = Net(netp, NetState(Phase.TRAIN))
    assert [c.scope() for c in net._fuse_plan.chains] == ["conv+relu+norm"]
    monkeypatch.setenv("SPARKNET_FUSE", "off")
    per_layer = Net(netp, NetState(Phase.TRAIN))
    params = net.init(rng)
    ins = {"data": jnp.ones((2, 3, 6, 6)) * 0.5,
           "label": jnp.asarray([1.0, 3.0])}

    def loss(net):
        return lambda p: net.apply(p, ins, rng=rng).loss

    ref = loss(per_layer)(params)
    monkeypatch.setattr(vision.jax, "default_backend", lambda: "tpu")
    jaxpr = str(jax.make_jaxpr(jax.grad(loss(net)))(params))
    assert "relu_lrn_fwd" in jaxpr and "relu_lrn_bwd" in jaxpr
    np.testing.assert_allclose(float(loss(net)(params)), float(ref),
                               rtol=1e-5)


def test_pallas_lrn_even_window_vjp(np_rng):
    """Even local_size has an asymmetric window — the VJP must use the
    reflected offsets (regression for the window-reflection bug)."""
    x = jnp.asarray(np_rng.normal(size=(1, 8, 3, 3)).astype(np.float32))
    g1 = jax.grad(lambda x: jnp.sum(
        jnp.sin(lrn_across_channels(x, 4, 0.1, 0.5, 2.0))))(x)
    g2 = jax.grad(lambda x: jnp.sum(jnp.sin(_xla_lrn(x, 4, 0.1, 0.5, 2.0))))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


# The zoo's LRN shapes cut down, over both lane choices: batches of 128
# and 256 put the batch on the lanes, 1, 8 and 50 the positions; C as
# CaffeNet (96, 256) and GoogLeNet (64, 192) have it; H*W 169 and 729, and
# one a block does not hold (28x28 of many lanes; 55x55 and 56x56 of one
# image, where the lanes are tiled or two images share a block); GoogLeNet
# pools before its LRN, so no ReLU folds there.
_LRN_CASES = [
    # (N, C, H, W, dtype, relu, local_size)
    (128, 96, 27, 27, "float32", True, 5),       # CaffeNet norm1, rounds
    (128, 256, 13, 13, "float32", True, 5),      # CaffeNet norm2, rounds
    (256, 96, 13, 13, "bfloat16", True, 5),
    (256, 256, 13, 13, "bfloat16", True, 5),
    (128, 64, 28, 28, "bfloat16", False, 5),     # GoogLeNet norm1
    (128, 192, 13, 13, "bfloat16", False, 5),    # GoogLeNet norm2
    (128, 64, 13, 13, "float32", False, 4),
    (256, 192, 13, 13, "float32", True, 4),
    (1, 96, 27, 27, "bfloat16", True, 5),        # the engine's buckets
    (8, 256, 13, 13, "bfloat16", True, 5),
    (50, 96, 27, 27, "float32", True, 5),        # a test net at 50
    (50, 256, 13, 13, "float32", False, 4),
    (1, 96, 55, 55, "float32", True, 5),         # AlexNet norm1
    (8, 64, 56, 56, "bfloat16", False, 5),
    (8, 192, 28, 28, "bfloat16", False, 5),
    (1, 64, 13, 13, "float32", False, 4),
    (50, 192, 13, 13, "bfloat16", True, 4),
    (8, 96, 13, 13, "float32", False, 5),
]


@pytest.mark.parametrize(
    "n,c,h,w,dtype,relu,size", _LRN_CASES,
    ids=[f"{n}x{c}x{h}x{w}-{d}-{'relu' if r else 'plain'}-w{s}"
         for n, c, h, w, d, r, s in _LRN_CASES])
def test_epilogue_kernel_matches_reference(n, c, h, w, dtype, relu, size):
    """Outputs and gradients of the kernel against ``relu_lrn_reference``
    in float32 on the same stored values: float32 to rounding, bfloat16
    to its own 8 bits (the kernel rounds y, scale and dx once each)."""
    from sparknet_tpu.ops.pallas_kernels import lrn_lanes
    from sparknet_tpu.ops.vision import relu_lrn_reference
    assert lrn_lanes((n, c, h, w)) == ("batch_lanes" if n in (128, 256)
                                       else "space_lanes")
    geom = (size, 0.05, 0.75, 1.0)
    r = np.random.default_rng(n * 1000 + c + h)
    x = jnp.asarray(r.normal(scale=4.0, size=(n, c, h, w)), dtype)
    dy = jnp.asarray(r.normal(size=(n, c, h, w)), dtype)

    def out_and_grad(fn, x, dy):
        @jax.jit
        def both(x, dy):
            y, vjp = jax.vjp(lambda x: fn(x, *geom, relu), x)
            return y, vjp(dy)[0]
        return tuple(np.asarray(v, np.float32) for v in both(x, dy))

    got = out_and_grad(relu_lrn_across_channels, x, dy)
    want = out_and_grad(relu_lrn_reference, x.astype(jnp.float32),
                        dy.astype(jnp.float32))
    tol = 1e-5 if dtype == "float32" else 2e-2
    for what, k, ref in zip(("y", "dx"), got, want):
        assert np.isfinite(k).all(), what
        assert np.max(np.abs(k - ref)) <= tol * np.max(np.abs(ref)), what
    # the inference kernel (no residual) is the training one's y
    y = relu_lrn_across_channels(x, *geom, relu)
    assert np.array_equal(np.asarray(y, np.float32), got[0])


@pytest.mark.parametrize("shape,itemsize", [
    ((1024, 96, 27, 27), 2), ((1024, 256, 13, 13), 2),
    ((256, 64, 56, 56), 2), ((256, 192, 56, 56), 2),
    ((512, 96, 27, 27), 4), ((512, 256, 13, 13), 4),
    ((4096, 512, 7, 7), 4), ((128, 2048, 7, 7), 4),
    ((50, 96, 27, 27), 4), ((1, 96, 27, 27), 2), ((8, 256, 13, 13), 2),
    ((16, 96, 55, 55), 4), ((10, 192, 56, 56), 2), ((3, 1024, 112, 112), 4),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"{v}B")
def test_epilogue_blocking_fits_and_covers(shape, itemsize):
    """A block is a legal tile (lanes a multiple of 128 or the whole
    axis), the grid covers the operand, and a block holds at most what
    the backward kernel's four operands, double-buffered, can keep in
    16 MB of VMEM; at the cells' shapes it is not a small one either."""
    from sparknet_tpu.ops import pallas_kernels as pk
    n, c, h, w = shape
    to_operand, from_operand, grid, spec = pk._blocked(shape, itemsize)
    operand = jax.eval_shape(to_operand, jax.ShapeDtypeStruct(
        shape, jnp.float32)).shape
    assert operand == ((h * w, c, n) if n % 128 == 0 else (n, c, h * w))
    assert jax.eval_shape(from_operand, jax.ShapeDtypeStruct(
        operand, jnp.float32)).shape == shape
    rows, channels, lanes = spec.block_shape
    assert channels == c and (lanes % 128 == 0 or lanes == operand[2])
    assert grid == (-(-operand[0] // rows), -(-operand[2] // lanes))
    held = rows * c * -(-lanes // 128) * 128 * itemsize
    assert held <= max(pk._BLOCK_BYTES, c * 128 * itemsize)
    assert 4 * 2 * held <= 16 << 20
    if n >= 256 and c <= 256:              # the cells: blocks near 1 MB
        assert held >= pk._BLOCK_BYTES // 2


@pytest.mark.parametrize("shape,dtype,backend,path", [
    ((128, 8, 3, 3), "float32", "tpu", "batch_lanes"),
    ((256, 8, 3, 3), "bfloat16", "tpu", "batch_lanes"),
    ((384, 8, 3, 3), "float32", "tpu", "batch_lanes"),
    ((1, 8, 3, 3), "float32", "tpu", "space_lanes"),
    ((8, 8, 3, 3), "bfloat16", "tpu", "space_lanes"),
    ((50, 8, 3, 3), "float32", "tpu", "space_lanes"),
    ((200, 8, 3, 3), "float32", "tpu", "space_lanes"),
    ((128, 8, 3, 3), "float16", "tpu", "reference"),
    ((128, 8, 3, 3), "float32", "cpu", "reference"),
])
def test_epilogue_path_follows_the_shape_and_is_counted(
        monkeypatch, shape, dtype, backend, path):
    """Which axis rides the lanes is a function of the operand's shape
    alone, the lowered text holds the kernel's operand in that layout,
    and ``lrn_epilogue_lowering_total`` counts the choice once a
    trace."""
    from sparknet_tpu.ops import vision
    from sparknet_tpu.utils import telemetry
    for k in ("SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
              "SPARKNET_METRICS_SNAP"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(vision.jax, "default_backend", lambda: backend)
    telemetry.reset()
    try:
        x = jnp.ones(shape, dtype)
        step = jax.jit(lambda x: vision.lrn_chain_epilogue(
            x, SIZE, ALPHA, BETA, K, relu=True))
        jaxpr = str(jax.make_jaxpr(step)(x))
        n, c, h, w = shape
        operand = {"batch_lanes": f"[{h * w},{c},{n}]",
                   "space_lanes": f"[{n},{c},{h * w}]"}.get(path)
        assert ("relu_lrn_infer" in jaxpr) == (path != "reference")
        if operand:
            assert operand in jaxpr.replace(" ", "")
        for _ in range(3):                  # three calls of one trace
            step(x)
        fam = telemetry.get_registry().snapshot()[
            "lrn_epilogue_lowering_total"]
        assert fam["kind"] == "counter"
        assert {s["labels"]["path"]: s["value"]
                for s in fam["samples"]} == {path: 1.0}
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# VMEM-resident maxpool backward
# ---------------------------------------------------------------------------

from sparknet_tpu.ops.pallas_kernels import max_pool_vmem_bwd  # noqa: E402
from sparknet_tpu.ops.vision import max_pool, pool_output_size  # noqa: E402

POOL_GEOMS = [
    # (h, w, kh, sh, ph) — GoogLeNet's two pool families + a padded s2
    (14, 14, 3, 1, 1),   # inception branch pool (SAME, stride 1)
    (28, 28, 3, 2, 0),   # pool3-style ceil-mode stride 2
    (13, 13, 3, 2, 1),   # padded + ceil (odd remainder)
    (7, 7, 5, 3, 2),     # kernel > 2*stride, fat overlap
    (17, 17, 2, 3, 1),   # stride > kernel: ceil-clip can leave
                         # (ow-1)*sw+kw < w+pw (padded-width floor)
]


def _np_caffe_maxpool_bwd(x, dy, kh, kw, sh, sw, ph, pw, oh, ow):
    """Literal transcription of pooling_layer.cpp Backward_cpu MAX: the
    forward's row-major argmax scan keeps the FIRST maximum; backward
    adds each dy into its recorded argmax."""
    n, c, h, w = x.shape
    dx = np.zeros_like(x, np.float32)
    for ni in range(n):
        for ci in range(c):
            for oi in range(oh):
                for oj in range(ow):
                    hs, ws = oi * sh - ph, oj * sw - pw
                    he, we = min(hs + kh, h), min(ws + kw, w)
                    hs, ws = max(hs, 0), max(ws, 0)
                    win = x[ni, ci, hs:he, ws:we]
                    k = np.argmax(win)  # first max (row-major), like caffe
                    dx[ni, ci, hs + k // win.shape[1],
                       ws + k % win.shape[1]] += dy[ni, ci, oi, oj]
    return dx


@pytest.mark.parametrize("h,w,kh,sh,ph", POOL_GEOMS)
def test_maxpool_vmem_bwd_matches_select_and_scatter(np_rng, h, w, kh, sh, ph):
    x = jnp.asarray(np_rng.normal(size=(2, 4, h, w)).astype(np.float32))
    oh, ow = pool_output_size(h, w, kh, kh, sh, sh, ph, ph)

    def f_pallas(x):
        return jnp.sum(jnp.sin(
            max_pool_vmem_bwd(x, kh, kh, sh, sh, ph, ph, oh, ow)))

    def f_xla(x):
        return jnp.sum(jnp.sin(
            max_pool(x, kh, kh, sh, sh, ph, ph, oh, ow)))

    np.testing.assert_allclose(np.asarray(f_pallas(x)), np.asarray(f_xla(x)),
                               rtol=1e-6)
    g1 = jax.grad(f_pallas)(x)
    g2 = jax.grad(f_xla)(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("h,w,kh,sh,ph", POOL_GEOMS)
def test_maxpool_vmem_bwd_first_max_ties(np_rng, h, w, kh, sh, ph):
    """Post-ReLU activations tie constantly (zeros); the gradient must go
    to the FIRST max of each window, exactly like caffe's argmax scan."""
    x = np.maximum(np_rng.normal(size=(1, 3, h, w)), 0).astype(np.float32)
    # quantize to force many non-zero ties too
    x = np.round(x * 2) / 2
    oh, ow = pool_output_size(h, w, kh, kh, sh, sh, ph, ph)
    dy = np_rng.normal(size=(1, 3, oh, ow)).astype(np.float32)

    _, vjp = jax.vjp(
        lambda x: max_pool_vmem_bwd(x, kh, kh, sh, sh, ph, ph, oh, ow),
        jnp.asarray(x))
    (dx,) = vjp(jnp.asarray(dy))
    expect = _np_caffe_maxpool_bwd(x, dy, kh, kh, sh, sh, ph, ph, oh, ow)
    np.testing.assert_allclose(np.asarray(dx), expect, rtol=1e-5, atol=1e-6)


def test_maxpool_layer_pallas_dispatch(np_rng, monkeypatch):
    """SPARKNET_PALLAS_MAXPOOL=1 routes MAX pooling's backward through
    the kernel; forward and gradient match the default path."""
    from sparknet_tpu.ops.registry import get_layer_impl as gli
    lp = layer("p", "Pooling", ["x"], ["y"],
               pooling_param={"pool": "MAX", "kernel_size": 3, "stride": 2})
    impl = gli("Pooling")
    x = jnp.asarray(np_rng.normal(size=(2, 4, 13, 13)).astype(np.float32))
    monkeypatch.setenv("SPARKNET_PALLAS_MAXPOOL", "0")
    ref, gref = jax.value_and_grad(
        lambda x: jnp.sum(jnp.sin(impl.apply(lp, [], [x], True, None)[0])))(x)
    monkeypatch.setenv("SPARKNET_PALLAS_MAXPOOL", "1")
    got, ggot = jax.value_and_grad(
        lambda x: jnp.sum(jnp.sin(impl.apply(lp, [], [x], True, None)[0])))(x)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ggot), np.asarray(gref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stride,pad", [(1, 1), (2, 0)])
def test_maxpool_vmem_bwd_bf16(np_rng, stride, pad):
    """bf16 activations through BOTH kernels (stride-1 and strided):
    accumulation stays f32 inside, output comes back bf16."""
    x = jnp.asarray(np_rng.normal(size=(1, 4, 14, 14)), jnp.bfloat16)
    oh, ow = pool_output_size(14, 14, 3, 3, stride, stride, pad, pad)
    _, vjp = jax.vjp(
        lambda x: max_pool_vmem_bwd(x, 3, 3, stride, stride, pad, pad,
                                    oh, ow), x)
    (dx,) = vjp(jnp.ones((1, 4, oh, ow), jnp.bfloat16))
    _, vjp2 = jax.vjp(
        lambda x: max_pool(x.astype(jnp.float32), 3, 3, stride, stride,
                           pad, pad, oh, ow), x)
    (dx2,) = vjp2(jnp.ones((1, 4, oh, ow), jnp.float32))
    assert dx.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dx2, np.float32),
                               rtol=2e-2, atol=1e-2)


def test_pallas_lrn_bf16(np_rng):
    """bf16 I/O with f32 in-kernel math: forward and gradient track the
    f32 reference to bf16 tolerance (the mixed-precision train path)."""
    xf = np_rng.normal(size=(2, 16, 5, 5)).astype(np.float32)
    x16 = jnp.asarray(xf, jnp.bfloat16)
    y = lrn_across_channels(x16, SIZE, ALPHA, BETA, K)
    assert y.dtype == jnp.bfloat16
    yref = _xla_lrn(jnp.asarray(xf))
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yref),
                               rtol=2e-2, atol=2e-2)
    g = jax.grad(lambda x: jnp.sum(
        lrn_across_channels(x, SIZE, ALPHA, BETA, K).astype(jnp.float32)))(x16)
    gref = jax.grad(lambda x: jnp.sum(_xla_lrn(x)))(jnp.asarray(xf))
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(gref),
                               rtol=5e-2, atol=2e-2)
