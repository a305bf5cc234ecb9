"""Per-op tests: Caffe-exact shape inference, value checks against naive
numpy references, and gradient checks via jax.test_util.check_grads — the
GradientChecker analog (reference:
caffe/include/caffe/test/test_gradient_check_util.hpp:19)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from sparknet_tpu.models.dsl import layer
from sparknet_tpu.ops import get_layer_impl
from sparknet_tpu.ops.vision import pool_output_size


def make(type_, **type_params):
    return layer("t", type_, ["b0"], ["t0"], **type_params)


def apply_op(lp, bottoms, params=(), train=True, rng=None):
    impl = get_layer_impl(lp.type)
    out = impl.apply(lp, list(params), [jnp.asarray(b) for b in bottoms],
                     train, rng)
    if getattr(impl, "has_state", False):
        out = out[0]
    return out


# -- convolution ------------------------------------------------------------

def test_conv_shapes_caffe_floor(rng):
    # (in + 2p - k)/s + 1 floor: caffe base_conv_layer.cpp
    lp = make("Convolution", convolution_param={
        "num_output": 8, "kernel_size": 3, "stride": 2, "pad": 1})
    impl = get_layer_impl("Convolution")
    assert impl.out_shapes(lp, [(2, 3, 11, 11)]) == [(2, 8, 6, 6)]
    params = impl.init(rng, lp, [(2, 3, 11, 11)])
    assert params[0].shape == (8, 3, 3, 3)
    assert params[1].shape == (8,)
    y = apply_op(lp, [np.ones((2, 3, 11, 11), np.float32)], params)
    assert y[0].shape == (2, 8, 6, 6)


def test_conv_matches_numpy(rng, np_rng):
    x = np_rng.normal(size=(1, 2, 5, 5)).astype(np.float32)
    w = np_rng.normal(size=(3, 2, 3, 3)).astype(np.float32)
    b = np_rng.normal(size=(3,)).astype(np.float32)
    lp = make("Convolution", convolution_param={
        "num_output": 3, "kernel_size": 3})
    y = np.asarray(apply_op(lp, [x], [jnp.asarray(w), jnp.asarray(b)])[0])
    # naive correlation
    ref = np.zeros((1, 3, 3, 3), np.float32)
    for o in range(3):
        for i in range(3):
            for j in range(3):
                patch = x[0, :, i:i + 3, j:j + 3]
                ref[0, o, i, j] = np.sum(patch * w[o]) + b[o]
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_space_to_depth_conv_exact(rng, np_rng, monkeypatch):
    """The stride-phase regroup rewrite (vision._space_to_depth_conv,
    engaged for small-C strided stems) must match the direct strided conv
    bitwise-close, forward and gradient, across stem geometries."""
    from sparknet_tpu.ops import vision

    impl = get_layer_impl("Convolution")
    geoms = [  # (C, H, W, num_output, k, s, p) — CaffeNet & GoogLeNet stems
        (3, 35, 35, 8, 11, 4, 0),
        (3, 32, 32, 8, 7, 2, 3),
        (2, 17, 19, 4, 5, 3, 1),
    ]
    for c, h, w, o, k, s, p in geoms:
        lp = make("Convolution", convolution_param={
            "num_output": o, "kernel_size": k, "stride": s, "pad": p})
        params = impl.init(rng, lp, [(2, c, h, w)])
        x = jnp.asarray(np_rng.normal(size=(2, c, h, w)).astype(np.float32))
        assert vision._s2d_eligible(c, k, k, s, s, p, p, 1, 1, 1)

        def loss(pp, xx):
            return jnp.sum(jnp.sin(impl.apply(lp, pp, [xx], False, None)[0]))

        y1, g1 = jax.value_and_grad(loss)(params, x)
        monkeypatch.setenv("SPARKNET_NO_S2D", "1")
        assert not vision._s2d_eligible(c, k, k, s, s, p, p, 1, 1, 1)
        y2, g2 = jax.value_and_grad(loss)(params, x)
        monkeypatch.delenv("SPARKNET_NO_S2D")
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-5)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)


def test_space_to_depth_gating():
    """Grouped, dilated, stride-1, and wide-C convs must NOT be rewritten."""
    from sparknet_tpu.ops import vision
    ok = vision._s2d_eligible
    assert not ok(3, 11, 11, 4, 4, 0, 0, 1, 1, 2)      # grouped
    assert not ok(3, 11, 11, 4, 4, 0, 0, 2, 2, 1)      # dilated
    assert not ok(3, 3, 3, 1, 1, 1, 1, 1, 1, 1)        # stride 1
    assert not ok(64, 3, 3, 2, 2, 1, 1, 1, 1, 1)       # C*s*s > 64
    assert not ok(3, 2, 2, 4, 4, 0, 0, 1, 1, 1)        # kernel < stride
    assert ok(3, 11, 11, 4, 4, 0, 0, 1, 1, 1)


def test_grouped_conv(rng):
    lp = make("Convolution", convolution_param={
        "num_output": 4, "kernel_size": 1, "group": 2})
    impl = get_layer_impl("Convolution")
    params = impl.init(rng, lp, [(1, 4, 2, 2)])
    assert params[0].shape == (4, 2, 1, 1)
    y = apply_op(lp, [np.ones((1, 4, 2, 2), np.float32)], params)
    assert y[0].shape == (1, 4, 2, 2)


def test_conv_gradients(rng, np_rng):
    lp = make("Convolution", convolution_param={
        "num_output": 2, "kernel_size": 3, "pad": 1, "stride": 2})
    impl = get_layer_impl("Convolution")
    params = impl.init(rng, lp, [(2, 3, 6, 6)])
    x = jnp.asarray(np_rng.normal(size=(2, 3, 6, 6)).astype(np.float32))

    def f(w, b, x):
        return impl.apply(lp, [w, b], [x], True, None)[0]

    check_grads(f, (params[0], params[1], x), order=1, modes=["rev"],
                atol=1e-2, rtol=1e-2)


def test_deconv_shape_and_transpose_equivalence(rng, np_rng):
    # deconv out = s(in-1) + k - 2p (deconv_layer.cpp)
    lp = make("Deconvolution", convolution_param={
        "num_output": 3, "kernel_size": 4, "stride": 2, "pad": 1})
    impl = get_layer_impl("Deconvolution")
    assert impl.out_shapes(lp, [(1, 2, 5, 5)]) == [(1, 3, 10, 10)]
    params = impl.init(rng, lp, [(1, 2, 5, 5)])
    assert params[0].shape == (2, 3, 4, 4)
    # equivalence: deconv(x, w) == vjp of conv wrt input with same geometry
    x = jnp.asarray(np_rng.normal(size=(1, 2, 5, 5)).astype(np.float32))
    w = params[0]
    y = impl.apply(lp, [w, jnp.zeros(3)], [x], True, None)[0]

    clp = make("Convolution", convolution_param={
        "num_output": 2, "kernel_size": 4, "stride": 2, "pad": 1,
        "bias_term": False})
    cimpl = get_layer_impl("Convolution")

    def conv_fn(inp):
        # conv maps (1,3,10,10) -> (1,2,5,5) with weight (out=2, in=3, 4, 4),
        # which is exactly the deconv blob (C_in=2, C_out=3, kh, kw)
        return cimpl.apply(clp, [w], [inp], True, None)[0]

    _, vjp = jax.vjp(conv_fn, jnp.zeros((1, 3, 10, 10)))
    ref = vjp(x)[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


# -- pooling ----------------------------------------------------------------

def test_pool_output_size_ceil():
    # caffe pooling ceil: e.g. 6->3 with k3 s2: ceil((6-3)/2)+1 = 3
    assert pool_output_size(6, 6, 3, 3, 2, 2, 0, 0) == (3, 3)
    # 7 -> ceil((7-3)/2)+1 = 3
    assert pool_output_size(7, 7, 3, 3, 2, 2, 0, 0) == (3, 3)
    # 8 -> ceil(5/2)+1 = 4  (torch floor would give 3)
    assert pool_output_size(8, 8, 3, 3, 2, 2, 0, 0) == (4, 4)
    # padding clip: start of last window must be < h + p
    assert pool_output_size(4, 4, 2, 2, 2, 2, 1, 1) == (3, 3)


def test_max_pool_values():
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    lp = make("Pooling", pooling_param={"pool": "MAX", "kernel_size": 2,
                                        "stride": 2})
    y = np.asarray(apply_op(lp, [x])[0])
    np.testing.assert_array_equal(y[0, 0], [[5, 7], [13, 15]])


def test_ave_pool_caffe_denominator():
    # with padding, caffe divides by the window clipped to [0, dim+pad)
    x = np.ones((1, 1, 2, 2), np.float32)
    lp = make("Pooling", pooling_param={"pool": "AVE", "kernel_size": 2,
                                        "stride": 2, "pad": 1})
    y = np.asarray(apply_op(lp, [x])[0])
    # out 2x2; each window covers exactly 1 real pixel but denominator is the
    # clipped window: corner windows span [−1,1)x[−1,1) -> clipped to
    # [−1,1)∩[0,3)=2x2... caffe: hstart=-1, hend=min(1, 2+1)=1 -> size 2x2=4?
    # Actually caffe clips hend to h+pad=3 (no-op here), pool_size=(1-(-1))²=4,
    # then sums only real pixels (1) -> 0.25.
    np.testing.assert_allclose(y[0, 0], [[0.25, 0.25], [0.25, 0.25]])


def test_global_pooling():
    x = np.arange(8, dtype=np.float32).reshape(1, 2, 2, 2)
    lp = make("Pooling", pooling_param={"pool": "AVE", "global_pooling": True})
    y = np.asarray(apply_op(lp, [x])[0])
    np.testing.assert_allclose(y.reshape(2), [1.5, 5.5])


def test_pool_gradients(np_rng):
    x = jnp.asarray(np_rng.normal(size=(1, 2, 6, 6)).astype(np.float32))
    for method in ("MAX", "AVE"):
        lp = make("Pooling", pooling_param={"pool": method, "kernel_size": 3,
                                            "stride": 2})
        impl = get_layer_impl("Pooling")
        f = lambda x: impl.apply(lp, [], [x], True, None)[0]
        check_grads(f, (x,), order=1, modes=["rev"], atol=1e-2, rtol=1e-2)


def test_stochastic_pool_train_samples_proportionally(np_rng):
    # non-overlapping 2x2 windows; element picked with prob ∝ value
    # (pooling_layer.cu StoPoolForwardTrain)
    x = np.zeros((1, 1, 2, 2), np.float32)
    x[0, 0] = [[1.0, 3.0], [0.0, 0.0]]
    lp = make("Pooling", pooling_param={"pool": "STOCHASTIC",
                                        "kernel_size": 2, "stride": 2})
    picks = []
    for i in range(400):
        y = np.asarray(apply_op(lp, [x], train=True,
                                rng=jax.random.PRNGKey(i))[0])
        assert y.reshape(()) in (1.0, 3.0)  # always a window element
        picks.append(float(y.reshape(())))
    frac3 = sum(1 for p in picks if p == 3.0) / len(picks)
    assert 0.65 < frac3 < 0.85  # expect 0.75


def test_stochastic_pool_train_gradient_routes_to_sample(np_rng):
    # d(sum y)/dx is a one-hot mask per (non-overlapping) window at the
    # sampled element — StoPoolBackward semantics
    x = jnp.asarray(np_rng.uniform(0.1, 1.0, size=(2, 3, 4, 4))
                    .astype(np.float32))
    lp = make("Pooling", pooling_param={"pool": "STOCHASTIC",
                                        "kernel_size": 2, "stride": 2})
    impl = get_layer_impl("Pooling")
    key = jax.random.PRNGKey(7)
    f = lambda x: jnp.sum(impl.apply(lp, [], [x], True, key)[0])
    g = np.asarray(jax.grad(f)(x))
    assert set(np.unique(g)) == {0.0, 1.0}
    # exactly one selected element per 2x2 window
    gsum = g.reshape(2, 3, 2, 2, 2, 2).sum(axis=(3, 5))
    np.testing.assert_array_equal(gsum, np.ones((2, 3, 2, 2)))
    # and the sampled value is what the forward returned
    y = np.asarray(impl.apply(lp, [], [x], True, key)[0])
    picked = (g * np.asarray(x)).reshape(2, 3, 2, 2, 2, 2).sum(axis=(3, 5))
    np.testing.assert_allclose(picked, y, rtol=1e-6)


def test_stochastic_pool_test_mode_weighted_average(np_rng):
    x = np.abs(np_rng.normal(size=(1, 2, 4, 4))).astype(np.float32)
    lp = make("Pooling", pooling_param={"pool": "STOCHASTIC",
                                        "kernel_size": 2, "stride": 2})
    y = np.asarray(apply_op(lp, [x], train=False)[0])
    # sum x^2 / sum x per window
    xr = x.reshape(1, 2, 2, 2, 2, 2).transpose(0, 1, 2, 4, 3, 5)
    num = (xr ** 2).sum(axis=(-1, -2))
    den = xr.sum(axis=(-1, -2))
    np.testing.assert_allclose(y, num / den, rtol=1e-5)
    assert get_layer_impl("Pooling").needs_rng(lp, train=True)
    assert not get_layer_impl("Pooling").needs_rng(lp, train=False)


# -- LRN --------------------------------------------------------------------

def test_lrn_across_channels_matches_numpy(np_rng):
    x = np_rng.normal(size=(2, 6, 3, 3)).astype(np.float32)
    lp = make("LRN", lrn_param={"local_size": 5, "alpha": 1e-4, "beta": 0.75})
    y = np.asarray(apply_op(lp, [x])[0])
    ref = np.empty_like(x)
    C = x.shape[1]
    for c in range(C):
        lo, hi = max(0, c - 2), min(C, c + 3)
        ssum = np.sum(x[:, lo:hi] ** 2, axis=1)
        scale = 1.0 + (1e-4 / 5) * ssum
        ref[:, c] = x[:, c] / scale ** 0.75
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)


def test_lrn_gradient(np_rng):
    x = jnp.asarray(np_rng.normal(size=(1, 4, 3, 3)).astype(np.float32))
    lp = make("LRN", lrn_param={"local_size": 3, "alpha": 0.1, "beta": 0.75})
    impl = get_layer_impl("LRN")
    f = lambda x: impl.apply(lp, [], [x], True, None)[0]
    check_grads(f, (x,), order=1, modes=["rev"], atol=1e-2, rtol=1e-2)


def test_lrn_cumsum_reformulation_matches_default(np_rng):
    """The cumsum lowering (prefix-sum window reformulation of the
    cross-channel sum, ``use_cumsum=True``) must match the reduce_window
    path to float tolerance — the window total is the same set of
    addends, associated differently — including the clipped windows at
    both channel edges, and its gradient must check (cumsum
    transpose)."""
    from sparknet_tpu.ops.vision import lrn_window_sum
    x = np_rng.normal(size=(2, 9, 5, 5)).astype(np.float32)
    lp = make("LRN", lrn_param={"local_size": 5, "alpha": 1e-2,
                                "beta": 0.75})
    base = np.asarray(apply_op(lp, [x])[0])

    def f(x):
        ssum = lrn_window_sum(x * x, 2, 2, use_cumsum=True)
        return x / (1.0 + (1e-2 / 5) * ssum) ** 0.75

    np.testing.assert_allclose(np.asarray(f(jnp.asarray(x))), base,
                               rtol=1e-5, atol=1e-6)
    # bf16 input keeps its dtype out (f32 prefix accumulation inside)
    assert f(jnp.asarray(x, jnp.bfloat16)).dtype == jnp.bfloat16
    check_grads(f, (jnp.asarray(x),), order=1, modes=["rev"],
                atol=1e-2, rtol=1e-2)


# -- one lowering a family, and the ones it was chosen over -------------------

ZOO_LRN = {"googlenet_norm1": (2, 64, 56, 56),
           "googlenet_norm2": (2, 192, 56, 56),
           "caffenet_norm1": (2, 96, 55, 55),
           "caffenet_norm2": (2, 256, 27, 27)}
# (c_in, side, num_output, kernel, stride, pad)
ZOO_STEMS = {"caffenet_conv1": (3, 227, 96, 11, 4, 0),
             "googlenet_conv1": (3, 224, 64, 7, 2, 3)}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) or 1.0)


def _plain_lrn(x, relu, size=5, alpha=1e-4, beta=0.75, k=1.0):
    """Caffe's [ReLU +] ACROSS_CHANNELS LRN as written in lrn_layer.cpp,
    differentiated by JAX through the window sum."""
    from jax import lax
    a = jnp.maximum(x, 0.0) if relu else x
    pre = (size - 1) // 2
    ssum = lax.reduce_window(a * a, 0.0, lax.add, (1, size, 1, 1),
                             (1, 1, 1, 1),
                             ((0, 0), (pre, size - 1 - pre), (0, 0), (0, 0)))
    return a / (k + (alpha / size) * ssum) ** beta


LOWERING_CASES = (
    [("cumsum", s, d) for s in ZOO_LRN for d in ("f32", "bf16")]
    + [("s2d", s, d) for s in ZOO_STEMS for d in ("f32", "bf16")]
    + [("epilogue", s, r) for s in ZOO_LRN for r in ("relu", "norelu")])


@pytest.mark.parametrize("family,shape,variant", LOWERING_CASES,
                         ids=["-".join(c) for c in LOWERING_CASES])
def test_lowerings_agree_at_zoo_shapes(family, shape, variant, np_rng):
    """What every family's chosen lowering is held to against the plain
    one, at the shapes the zoo runs: cumsum against reduce_window,
    space-to-depth against the direct convolution (forward and both
    gradients), ``relu_lrn_reference`` against the plain formulas
    (forward bit for bit, its closed-form VJP within 1e-5)."""
    from jax import lax
    from sparknet_tpu.ops import vision
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    tol = 2e-2 if variant == "bf16" else 1e-5
    if family == "cumsum":
        sq = jnp.asarray(np_rng.normal(size=ZOO_LRN[shape]) ** 2, dtype)
        a = vision.lrn_window_sum(sq, 2, 2, use_cumsum=True)
        b = vision.lrn_window_sum(sq, 2, 2, use_cumsum=False)
        assert a.dtype == b.dtype == dtype
        assert _rel(a, b) <= tol
    elif family == "s2d":
        c, side, o, k, s, p = ZOO_STEMS[shape]
        assert vision._s2d_eligible(c, k, k, s, s, p, p, 1, 1, 1)
        x = jnp.asarray(np_rng.normal(size=(2, c, side, side)), dtype)
        w = jnp.asarray(np_rng.normal(size=(o, c, k, k)) * 0.05, dtype)

        def s2d(x, w):
            return vision._space_to_depth_conv(x, w, k, k, s, s, p, p)

        def native(x, w):
            return lax.conv_general_dilated(
                x, w, (s, s), ((p, p), (p, p)),
                dimension_numbers=vision.DIMNUMS)

        def grads(f):
            return jax.grad(lambda x, w: jnp.sum(jnp.sin(
                f(x, w).astype(jnp.float32))), argnums=(0, 1))(x, w)

        assert s2d(x, w).shape == native(x, w).shape
        assert _rel(s2d(x, w), native(x, w)) <= tol
        for got, ref in zip(grads(s2d), grads(native)):
            assert _rel(got, ref) <= tol
    else:
        relu = variant == "relu"
        x = jnp.asarray(np_rng.normal(size=ZOO_LRN[shape]) * 20.0,
                        jnp.float32)
        y = vision.relu_lrn_reference(x, 5, 1e-4, 0.75, 1.0, relu)
        ref = _plain_lrn(x, relu)
        assert np.asarray(y).tobytes() == np.asarray(ref).tobytes()
        g = jax.grad(lambda x: jnp.mean(vision.relu_lrn_reference(
            x, 5, 1e-4, 0.75, 1.0, relu)))(x)
        g_ref = jax.grad(lambda x: jnp.mean(_plain_lrn(x, relu)))(x)
        assert _rel(g, g_ref) <= 1e-5


@pytest.mark.parametrize("case,want", [
    # (c_in, kh, kw, sh, sw, ph, pw, dh, dw, group)
    ("s2d-caffenet_conv1_11x11s4", True),
    ("s2d-googlenet_conv1_7x7s2", True),
    ("s2d-vgg_3x3s1", False),
    ("s2d-caffenet_conv2_grouped_5x5", False),
    ("s2d-dilated_3x3s2", False),
    ("cumsum-by_backend_and_width", None),
])
def test_default_lowering(case, want, monkeypatch):
    """The one place each family picks its lowering, from shape, dtype
    and backend: ``_s2d_eligible`` over the zoo's convolution geometries,
    ``lrn_use_cumsum`` by backend and channel count."""
    from sparknet_tpu.ops import vision
    geometry = {
        "s2d-caffenet_conv1_11x11s4": (3, 11, 11, 4, 4, 0, 0, 1, 1, 1),
        "s2d-googlenet_conv1_7x7s2": (3, 7, 7, 2, 2, 3, 3, 1, 1, 1),
        "s2d-vgg_3x3s1": (3, 3, 3, 1, 1, 1, 1, 1, 1, 1),
        "s2d-caffenet_conv2_grouped_5x5": (96, 5, 5, 1, 1, 2, 2, 1, 1, 2),
        "s2d-dilated_3x3s2": (3, 3, 3, 2, 2, 1, 1, 2, 2, 1),
    }
    if case in geometry:
        assert vision._s2d_eligible(*geometry[case]) is want
        monkeypatch.setenv("SPARKNET_NO_S2D", "1")
        assert vision._s2d_eligible(*geometry[case]) is False
        return
    c = vision.LRN_CUMSUM_AUTO_C
    assert not any(vision.lrn_use_cumsum(w) for w in (c - 1, c, 4096))
    monkeypatch.setattr(vision.jax, "default_backend", lambda: "tpu")
    assert [vision.lrn_use_cumsum(w) for w in (96, c - 1, c, 256)] == [
        False, False, True, True]


# -- inner product ----------------------------------------------------------

def test_inner_product(rng, np_rng):
    lp = make("InnerProduct", inner_product_param={"num_output": 7})
    impl = get_layer_impl("InnerProduct")
    assert impl.out_shapes(lp, [(4, 3, 2, 2)]) == [(4, 7)]
    params = impl.init(rng, lp, [(4, 3, 2, 2)])
    assert params[0].shape == (7, 12)
    x = np_rng.normal(size=(4, 3, 2, 2)).astype(np.float32)
    y = np.asarray(apply_op(lp, [x], params)[0])
    ref = x.reshape(4, 12) @ np.asarray(params[0]).T + np.asarray(params[1])
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_inner_product_transpose(rng, np_rng):
    lp = make("InnerProduct", inner_product_param={"num_output": 5,
                                                   "transpose": True})
    impl = get_layer_impl("InnerProduct")
    params = impl.init(rng, lp, [(2, 6)])
    assert params[0].shape == (6, 5)


# -- neuron layers ----------------------------------------------------------

def test_relu_negative_slope():
    x = np.array([[-2.0, 3.0]], np.float32)
    lp = make("ReLU", relu_param={"negative_slope": 0.1})
    y = np.asarray(apply_op(lp, [x])[0])
    np.testing.assert_allclose(y, [[-0.2, 3.0]], rtol=1e-6)


def test_dropout_train_test(rng):
    x = np.ones((100, 100), np.float32)
    lp = make("Dropout", dropout_param={"dropout_ratio": 0.5})
    y_test = np.asarray(apply_op(lp, [x], train=False)[0])
    np.testing.assert_array_equal(y_test, x)
    y_train = np.asarray(apply_op(lp, [x], train=True, rng=rng)[0])
    # inverted dropout: survivors scaled by 2, mean preserved
    assert set(np.unique(y_train)) <= {0.0, 2.0}
    assert abs(y_train.mean() - 1.0) < 0.05


def test_power_exp_log_bnll_threshold_absval(np_rng):
    x = np.abs(np_rng.normal(size=(3, 4)).astype(np.float32)) + 0.5
    cases = [
        (make("Power", power_param={"power": 2.0, "scale": 3.0, "shift": 1.0}),
         (1 + 3 * x) ** 2),
        (make("Exp"), np.exp(x)),
        (make("Exp", exp_param={"base": 2.0}), 2.0 ** x),
        (make("Log"), np.log(x)),
        (make("AbsVal"), np.abs(x)),
        (make("BNLL"), np.log1p(np.exp(x))),
        (make("Threshold", threshold_param={"threshold": 1.0}),
         (x > 1.0).astype(np.float32)),
        (make("TanH"), np.tanh(x)),
        (make("Sigmoid"), 1 / (1 + np.exp(-x))),
    ]
    for lp, ref in cases:
        y = np.asarray(apply_op(lp, [x])[0])
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5,
                                   err_msg=lp.type)


def test_prelu(rng):
    lp = make("PReLU")
    impl = get_layer_impl("PReLU")
    params = impl.init(rng, lp, [(1, 3, 2, 2)])
    assert params[0].shape == (3,)
    np.testing.assert_allclose(np.asarray(params[0]), [0.25] * 3)
    x = -np.ones((1, 3, 2, 2), np.float32)
    y = np.asarray(apply_op(lp, [x], params)[0])
    np.testing.assert_allclose(y, -0.25 * np.ones_like(x))


# -- shape/common layers ----------------------------------------------------

def test_concat_slice_roundtrip(np_rng):
    x = np_rng.normal(size=(2, 6, 2, 2)).astype(np.float32)
    slp = layer("s", "Slice", ["b"], ["a", "b2", "c"],
                slice_param={"slice_point": [1, 3]})
    parts = apply_op(slp, [x])
    assert [p.shape[1] for p in parts] == [1, 2, 3]
    clp = layer("c", "Concat", ["a", "b2", "c"], ["out"])
    y = apply_op(clp, parts)[0]
    np.testing.assert_array_equal(np.asarray(y), x)


def test_flatten_reshape():
    x = np.zeros((2, 3, 4, 5), np.float32)
    f = make("Flatten")
    assert apply_op(f, [x])[0].shape == (2, 60)
    r = make("Reshape", reshape_param={"shape": {"dim": [0, -1, 10]}})
    assert apply_op(r, [x])[0].shape == (2, 6, 10)


def test_eltwise(np_rng):
    a = np_rng.normal(size=(2, 3)).astype(np.float32)
    b = np_rng.normal(size=(2, 3)).astype(np.float32)
    lp = layer("e", "Eltwise", ["a", "b"], ["o"],
               eltwise_param={"operation": "SUM", "coeff": [1.0, -1.0]})
    np.testing.assert_allclose(np.asarray(apply_op(lp, [a, b])[0]), a - b,
                               rtol=1e-6)
    lp2 = layer("e", "Eltwise", ["a", "b"], ["o"],
                eltwise_param={"operation": "MAX"})
    np.testing.assert_allclose(np.asarray(apply_op(lp2, [a, b])[0]),
                               np.maximum(a, b))
    lp3 = layer("e", "Eltwise", ["a", "b"], ["o"],
                eltwise_param={"operation": "PROD"})
    np.testing.assert_allclose(np.asarray(apply_op(lp3, [a, b])[0]), a * b,
                               rtol=1e-6)


def test_softmax_and_argmax(np_rng):
    x = np_rng.normal(size=(3, 5)).astype(np.float32)
    y = np.asarray(apply_op(make("Softmax"), [x])[0])
    e = np.exp(x - x.max(1, keepdims=True))
    np.testing.assert_allclose(y, e / e.sum(1, keepdims=True), rtol=1e-5,
                               atol=1e-6)
    am = np.asarray(apply_op(make("ArgMax"), [x])[0])
    np.testing.assert_array_equal(am.reshape(3), x.argmax(1))


def test_accuracy_topk():
    scores = np.array([[1, 2, 3], [3, 2, 1], [1, 3, 2]], np.float32)
    labels = np.array([2, 0, 0], np.float32)
    lp = layer("a", "Accuracy", ["s", "l"], ["acc"])
    acc = float(apply_op(lp, [scores, labels])[0])
    assert acc == pytest.approx(2 / 3)
    lp5 = layer("a", "Accuracy", ["s", "l"], ["acc"],
                accuracy_param={"top_k": 2})
    acc2 = float(apply_op(lp5, [scores, labels])[0])
    assert acc2 == pytest.approx(2 / 3)  # sample 3: label 0 ranks 3rd


def test_batchnorm_train_updates_stats(rng, np_rng):
    lp = make("BatchNorm")
    impl = get_layer_impl("BatchNorm")
    params = impl.init(rng, lp, [(4, 3, 2, 2)])
    x = jnp.asarray(np_rng.normal(loc=5.0, size=(4, 3, 2, 2)).astype(np.float32))
    (tops, new_params) = impl.apply(lp, params, [x], True, None)
    y = np.asarray(tops[0])
    assert abs(y.mean()) < 1e-5 and abs(y.std() - 1.0) < 1e-2
    # running stats accumulated
    assert float(new_params[2][0]) == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(new_params[0]),
                               np.asarray(x.mean(axis=(0, 2, 3))), rtol=1e-4)
    # inference path uses the stats
    (tops2, _) = impl.apply(lp, new_params, [x], False, None)
    y2 = np.asarray(tops2[0])
    assert abs(y2.mean()) < 0.2


def test_scale_bias(rng, np_rng):
    x = np_rng.normal(size=(2, 3, 2, 2)).astype(np.float32)
    slp = make("Scale", scale_param={"bias_term": True})
    impl = get_layer_impl("Scale")
    params = impl.init(rng, slp, [x.shape])
    assert params[0].shape == (3,) and params[1].shape == (3,)
    y = np.asarray(apply_op(slp, [x], [jnp.full(3, 2.0), jnp.full(3, 1.0)])[0])
    np.testing.assert_allclose(y, 2 * x + 1, rtol=1e-5)


def test_mvn(np_rng):
    x = np_rng.normal(loc=3.0, scale=2.0, size=(2, 3, 4, 4)).astype(np.float32)
    y = np.asarray(apply_op(make("MVN"), [x])[0])
    m = y.mean(axis=(2, 3))
    s = y.std(axis=(2, 3))
    np.testing.assert_allclose(m, np.zeros_like(m), atol=1e-5)
    np.testing.assert_allclose(s, np.ones_like(s), atol=1e-2)


def test_embed(rng):
    lp = make("Embed", embed_param={"num_output": 4, "input_dim": 10})
    impl = get_layer_impl("Embed")
    params = impl.init(rng, lp, [(3,)])
    assert params[0].shape == (10, 4)
    idx = np.array([1, 5, 9], np.float32)
    y = apply_op(lp, [idx], params)[0]
    assert y.shape == (3, 4)


def test_tile_reduction_batchreindex(np_rng):
    x = np_rng.normal(size=(2, 3)).astype(np.float32)
    t = make("Tile", tile_param={"axis": 1, "tiles": 2})
    assert apply_op(t, [x])[0].shape == (2, 6)
    r = make("Reduction", reduction_param={"operation": "MEAN", "axis": 1})
    np.testing.assert_allclose(np.asarray(apply_op(r, [x])[0]), x.mean(1),
                               rtol=1e-5)
    br = layer("br", "BatchReindex", ["x", "i"], ["o"])
    idx = np.array([1, 1, 0], np.float32)
    y = np.asarray(apply_op(br, [x, idx])[0])
    np.testing.assert_array_equal(y, x[[1, 1, 0]])


# -- losses -----------------------------------------------------------------

def test_softmax_with_loss_matches_manual(np_rng):
    x = np_rng.normal(size=(4, 5)).astype(np.float32)
    labels = np.array([0, 1, 2, 3], np.float32)
    lp = layer("l", "SoftmaxWithLoss", ["x", "y"], ["loss"])
    loss = float(apply_op(lp, [x, labels])[0])
    e = np.exp(x - x.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    ref = -np.mean(np.log(p[np.arange(4), labels.astype(int)]))
    assert loss == pytest.approx(ref, rel=1e-5)


def test_softmax_loss_ignore_label(np_rng):
    x = np_rng.normal(size=(4, 5)).astype(np.float32)
    labels = np.array([0, 1, 255, 3], np.float32)
    # ignore_label must drop sample 2 from both sum and count
    lp = layer("l", "SoftmaxWithLoss", ["x", "y"], ["loss"],
               loss_param={"ignore_label": 255})
    loss = float(apply_op(lp, [x, labels])[0])
    e = np.exp(x - x.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    keep = [0, 1, 3]
    ref = -np.mean(np.log(p[keep, labels.astype(int)[keep]]))
    assert loss == pytest.approx(ref, rel=1e-4)


def test_euclidean_loss(np_rng):
    a = np_rng.normal(size=(3, 4)).astype(np.float32)
    b = np_rng.normal(size=(3, 4)).astype(np.float32)
    lp = layer("l", "EuclideanLoss", ["a", "b"], ["loss"])
    loss = float(apply_op(lp, [a, b])[0])
    assert loss == pytest.approx(((a - b) ** 2).sum() / 6, rel=1e-5)


def test_hinge_loss():
    s = np.array([[0.5, -0.5], [0.2, 0.3]], np.float32)
    y = np.array([0, 1], np.float32)
    lp = layer("l", "HingeLoss", ["s", "y"], ["loss"])
    # margins: sample0: max(0,1-0.5)+max(0,1-0.5)=1.0; sample1:
    # max(0,1+0.2)+max(0,1-0.3)=1.9 -> mean 1.45
    assert float(apply_op(lp, [s, y])[0]) == pytest.approx((1.0 + 1.9) / 2)


def test_sigmoid_ce_loss(np_rng):
    x = np_rng.normal(size=(3, 4)).astype(np.float32)
    t = (np_rng.uniform(size=(3, 4)) > 0.5).astype(np.float32)
    lp = layer("l", "SigmoidCrossEntropyLoss", ["x", "t"], ["loss"])
    loss = float(apply_op(lp, [x, t])[0])
    p = 1 / (1 + np.exp(-x))
    ref = -np.sum(t * np.log(p) + (1 - t) * np.log(1 - p)) / 3
    assert loss == pytest.approx(ref, rel=1e-4)


def test_contrastive_loss(np_rng):
    a = np_rng.normal(size=(4, 3)).astype(np.float32)
    b = np_rng.normal(size=(4, 3)).astype(np.float32)
    y = np.array([1, 0, 1, 0], np.float32)
    lp = layer("l", "ContrastiveLoss", ["a", "b", "y"], ["loss"])
    loss = float(apply_op(lp, [a, b, y])[0])
    d2 = ((a - b) ** 2).sum(1)
    d = np.sqrt(d2)
    neg = np.maximum(1.0 - d, 0) ** 2
    ref = np.sum(y * d2 + (1 - y) * neg) / 8
    assert loss == pytest.approx(ref, rel=1e-3)


def test_softmax_loss_normalize_false_axis(np_rng):
    """normalize=false divides by outer_num_ = prod(shape[:axis]), not the
    batch dim (softmax_loss_layer.cpp Forward) — differs when axis != 1."""
    x = np_rng.normal(size=(2, 3, 5)).astype(np.float32)  # axis=2: C=5
    labels = np_rng.integers(0, 5, size=(2, 3)).astype(np.float32)
    lp = layer("l", "SoftmaxWithLoss", ["x", "y"], ["loss"],
               softmax_param={"axis": 2}, loss_param={"normalize": False})
    loss = float(apply_op(lp, [x, labels])[0])
    logp = np.log(np.exp(x) / np.exp(x).sum(-1, keepdims=True))
    nll = -np.take_along_axis(
        logp, labels.astype(np.int64)[..., None], axis=-1)
    ref = nll.sum() / (2 * 3)  # outer_num_ = 6, not batch 2
    assert loss == pytest.approx(ref, rel=1e-4)


def test_filter_layer_eager_and_taint(np_rng):
    x = np_rng.normal(size=(4, 3)).astype(np.float32)
    sel = np.array([1, 0, 1, 0], np.float32)
    lp = layer("f", "Filter", ["x", "sel"], ["out"])
    out = apply_op(lp, [x, sel])[0]
    np.testing.assert_allclose(np.asarray(out), x[[0, 2]])

    # downstream of Filter: a consumer whose params ignore the batch dim
    # (InnerProduct axis=1) still builds — it runs fine eager — but one
    # whose param shapes depend on the batch dim (axis=0) is rejected
    from sparknet_tpu.graph import Net
    from sparknet_tpu.proto import load_net_prototxt
    ok_txt = """
    layer { name: "d" type: "Input" top: "x" top: "sel"
            input_param { shape { dim: 4 dim: 3 } shape { dim: 4 } } }
    layer { name: "f" type: "Filter" bottom: "x" bottom: "sel" top: "fx" }
    layer { name: "ip" type: "InnerProduct" bottom: "fx" top: "y"
            inner_product_param { num_output: 2
                                  weight_filler { type: "xavier" } } }
    """
    net = Net(load_net_prototxt(ok_txt))
    params = net.init(jax.random.PRNGKey(0))
    out = net.apply(params, {"x": jnp.asarray(x), "sel": jnp.asarray(sel)},
                    train=False)
    assert out.blobs["y"].shape == (2, 2)  # eager: real filtered batch

    bad_txt = ok_txt.replace("num_output: 2",
                             "num_output: 2 axis: 0")
    with pytest.raises(ValueError, match="data-dependent batch"):
        Net(load_net_prototxt(bad_txt))


def test_loss_gradients(np_rng):
    x = jnp.asarray(np_rng.normal(size=(4, 5)).astype(np.float32))
    labels = jnp.asarray(np.array([0, 1, 2, 3], np.float32))
    lp = layer("l", "SoftmaxWithLoss", ["x", "y"], ["loss"])
    impl = get_layer_impl("SoftmaxWithLoss")
    f = lambda x: impl.apply(lp, [], [x, labels], True, None)[0]
    check_grads(f, (x,), order=1, modes=["rev"], atol=1e-2, rtol=1e-2)


def test_infogain_loss_source_file(tmp_path, np_rng):
    """H supplied via infogain_loss_param.source (a BlobProto file) matches
    the third-bottom variant (infogain_loss_layer.cpp LayerSetUp)."""
    from sparknet_tpu.proto.caffemodel import save_mean_binaryproto

    probs = np.abs(np_rng.normal(size=(4, 3))).astype(np.float32)
    probs /= probs.sum(1, keepdims=True)
    labels = np.array([0, 1, 2, 1], np.float32)
    H = np.eye(3, dtype=np.float32) * 2.0
    path = str(tmp_path / "H.binaryproto")
    save_mean_binaryproto(path, H[None])

    lp3 = layer("l", "InfogainLoss", ["p", "y", "H"], ["loss"])
    ref = float(apply_op(lp3, [probs, labels, H])[0])
    lp2 = layer("l", "InfogainLoss", ["p", "y"], ["loss"],
                infogain_loss_param={"source": path})
    got = float(apply_op(lp2, [probs, labels])[0])
    assert got == pytest.approx(ref, rel=1e-5)


def test_accuracy_per_class_top(np_rng):
    scores = np.array([[3.0, 1.0, 0.0],
                       [0.0, 2.0, 1.0],
                       [1.0, 0.0, 3.0],
                       [2.0, 1.0, 0.0]], np.float32)
    labels = np.array([0, 1, 2, 1], np.float32)  # last one wrong (pred 0)
    lp = layer("a", "Accuracy", ["s", "y"], ["acc", "per_class"])
    from sparknet_tpu.ops import get_layer_impl
    impl = get_layer_impl("Accuracy")
    assert impl.out_shapes(lp, [(4, 3), (4,)]) == [(), (3,)]
    acc, per = apply_op(lp, [scores, labels])
    assert float(acc) == pytest.approx(0.75)
    np.testing.assert_allclose(np.asarray(per), [1.0, 0.5, 1.0])
