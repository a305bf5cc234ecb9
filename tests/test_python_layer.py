"""User-defined Python layer adapter (reference:
caffe/python/caffe/test/test_python_layer.py — SimpleLayer ×3 chain,
parameter/phase semantics; caffe/include/caffe/layers/python_layer.hpp)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import reference_path

from sparknet_tpu.graph import Net
from sparknet_tpu.ops import register_python_layer
from sparknet_tpu.proto import NetState, Phase, load_net_prototxt


# -- functional (TPU-native) protocol ---------------------------------------

class TimesTen:
    """The reference's SimpleLayer (×10), functional protocol: traced jnp
    forward, autodiff backward."""

    def out_shapes(self, bottom_shapes):
        return [tuple(bottom_shapes[0])]

    def forward(self, x):
        return 10.0 * x


class ScaleByParam:
    """param_str-configured scale, exercising setup()."""

    def setup(self, bottom_shapes, param_str):
        self.k = float(param_str or 1.0)

    def out_shapes(self, bottom_shapes):
        return [tuple(bottom_shapes[0])]

    def forward(self, x):
        return self.k * x


register_python_layer("TimesTen", TimesTen)
register_python_layer("ScaleByParam", ScaleByParam)

CHAIN = """
name: 'pythonnet' force_backward: true
input: 'data' input_shape { dim: 4 dim: 3 dim: 2 }
layer { type: 'Python' name: 'one' bottom: 'data' top: 'one'
  python_param { module: 'x' layer: 'TimesTen' } }
layer { type: 'Python' name: 'two' bottom: 'one' top: 'two'
  python_param { module: 'x' layer: 'TimesTen' } }
layer { type: 'Python' name: 'three' bottom: 'two' top: 'three'
  python_param { module: 'x' layer: 'TimesTen' } }
"""


def test_functional_chain_like_reference():
    # test_python_layer.py test_forward: chain of three ×10 layers
    net = Net(load_net_prototxt(CHAIN), NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).normal(size=(4, 3, 2)).astype(np.float32)
    blobs = net.apply_all(params, {"data": jnp.asarray(x)})
    np.testing.assert_allclose(np.asarray(blobs["three"]), 1000.0 * x,
                               rtol=1e-5)


def test_functional_chain_gradient():
    # test_python_layer.py test_backward analog: d(sum 1000x)/dx = 1000
    net = Net(load_net_prototxt(CHAIN), NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    x = jnp.ones((4, 3, 2), jnp.float32)

    def f(x):
        return jnp.sum(net.apply_all(params, {"data": x})["three"])
    g = np.asarray(jax.grad(f)(x))
    np.testing.assert_allclose(g, 1000.0, rtol=1e-5)


def test_param_str():
    txt = """
    name: 'p' input: 'data' input_shape { dim: 2 dim: 2 }
    layer { type: 'Python' name: 's' bottom: 'data' top: 's'
      python_param { module: 'x' layer: 'ScaleByParam' param_str: '2.5' } }
    """
    net = Net(load_net_prototxt(txt), NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    y = net.apply_all(params, {"data": jnp.ones((2, 2))})["s"]
    np.testing.assert_allclose(np.asarray(y), 2.5)


def test_unknown_module_clear_error():
    txt = """
    name: 'p' input: 'data' input_shape { dim: 2 }
    layer { type: 'Python' name: 's' bottom: 'data' top: 's'
      python_param { module: 'no_such_module_xyz' layer: 'Nope' } }
    """
    with pytest.raises(ImportError, match="no_such_module_xyz"):
        Net(load_net_prototxt(txt), NetState(Phase.TRAIN))


# -- pycaffe-compatible (host-callback) protocol ----------------------------

def _install_shim():
    from sparknet_tpu import pycaffe_compat
    pycaffe_compat.install()
    return pycaffe_compat


def test_caffe_style_forward_and_backward():
    """A pycaffe-interface layer (setup/reshape/forward/backward mutating
    blob buffers) runs inside jit and its hand-written backward feeds
    autodiff via the custom_vjp bridge."""
    shim = _install_shim()

    class HalfLayer(shim.Layer):
        def setup(self, bottom, top):
            self.calls = 0

        def reshape(self, bottom, top):
            top[0].reshape(*bottom[0].data.shape)

        def forward(self, bottom, top):
            self.calls += 1
            top[0].data[...] = 0.5 * bottom[0].data

        def backward(self, top, propagate_down, bottom):
            bottom[0].diff[...] = 0.5 * top[0].diff

    register_python_layer("HalfLayer", HalfLayer)
    txt = """
    name: 'h' input: 'data' input_shape { dim: 3 dim: 4 }
    layer { type: 'Python' name: 'half' bottom: 'data' top: 'half'
      python_param { module: 'x' layer: 'HalfLayer' } }
    """
    net = Net(load_net_prototxt(txt), NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 4))
                    .astype(np.float32))

    @jax.jit
    def f(x):
        return jnp.sum(net.apply_all(params, {"data": x})["half"] ** 2)

    y = float(f(x))
    assert np.isclose(y, float(jnp.sum((0.5 * x) ** 2)), rtol=1e-5)
    g = np.asarray(jax.grad(lambda x: f(x))(x))
    # d/dx sum((x/2)^2) = 2·(x/2)·(1/2) = x/2, routed through user backward
    np.testing.assert_allclose(g, np.asarray(x) / 2.0, rtol=1e-4, atol=1e-6)


def test_per_net_instance_isolation():
    """Two Nets built from the same prototxt get independent user-layer
    instances (caffe instantiates layer objects per net — net.cpp Init):
    a stateful layer's counter must not interleave between nets."""
    shim = _install_shim()

    class CountingLayer(shim.Layer):
        def setup(self, bottom, top):
            self.n = 0

        def reshape(self, bottom, top):
            top[0].reshape(*bottom[0].data.shape)

        def forward(self, bottom, top):
            self.n += 1
            top[0].data[...] = bottom[0].data + self.n

        def backward(self, top, propagate_down, bottom):
            bottom[0].diff[...] = top[0].diff

    register_python_layer("CountingLayer", CountingLayer)
    txt = """
    name: 'c' input: 'data' input_shape { dim: 2 }
    layer { type: 'Python' name: 'cnt' bottom: 'data' top: 'cnt'
      python_param { module: 'x' layer: 'CountingLayer' } }
    """
    netp = load_net_prototxt(txt)
    net_a = Net(netp, NetState(Phase.TRAIN))
    net_b = Net(netp, NetState(Phase.TRAIN))
    pa = net_a.init(jax.random.PRNGKey(0))
    pb = net_b.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2,), jnp.float32)
    # interleave: each net's counter advances independently from 1
    ya1 = float(net_a.apply_all(pa, {"data": x})["cnt"][0])
    yb1 = float(net_b.apply_all(pb, {"data": x})["cnt"][0])
    ya2 = float(net_a.apply_all(pa, {"data": x})["cnt"][0])
    assert (ya1, yb1, ya2) == (1.0, 1.0, 2.0)


def test_reference_pyloss_matches_formula():
    """The reference's own examples/pycaffe/layers/pyloss.py runs
    unmodified; its loss and gradients match the Euclidean-loss formula
    (and hence the C++ EuclideanLossLayer it mirrors)."""
    import sys
    _install_shim()
    layers_dir = reference_path("caffe/examples/pycaffe/layers")
    if layers_dir not in sys.path:
        sys.path.insert(0, layers_dir)
    txt = """
    name: 'el' force_backward: true
    input: 'a' input_shape { dim: 5 dim: 3 }
    input: 'b' input_shape { dim: 5 dim: 3 }
    layer { type: 'Python' name: 'loss' bottom: 'a' bottom: 'b' top: 'loss'
      python_param { module: 'pyloss' layer: 'EuclideanLossLayer' }
      loss_weight: 1 }
    """
    net = Net(load_net_prototxt(txt), NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    r = np.random.default_rng(2)
    a = jnp.asarray(r.normal(size=(5, 3)).astype(np.float32))
    b = jnp.asarray(r.normal(size=(5, 3)).astype(np.float32))

    def loss_fn(a, b):
        return net.apply(params, {"a": a, "b": b}).loss

    l = float(loss_fn(a, b))
    expect = float(np.sum((np.asarray(a) - np.asarray(b)) ** 2) / 5 / 2)
    assert np.isclose(l, expect, rtol=1e-5)
    ga, gb = jax.grad(loss_fn, argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga),
                               (np.asarray(a) - np.asarray(b)) / 5,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gb),
                               -(np.asarray(a) - np.asarray(b)) / 5,
                               rtol=1e-4, atol=1e-6)
