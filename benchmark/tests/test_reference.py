"""The reference interpreter against the system's float32 forward."""

import jax
import numpy as np
import pytest

from benchmark.lib import check, reference


def both(net_param, shape, classes, n, logits_blob, loss_blob):
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    test = NetState(Phase.TEST)
    params = Net(net_param, test).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"data": rng.normal(scale=50, size=(n, *shape)).astype(
                 np.float32),
             "label": rng.integers(0, classes, size=n).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        logits = Net(check.logits_net_param(net_param), test).apply(
            params, batch, train=False).blobs[logits_blob]
        loss = Net(net_param, test).apply(
            params, batch, train=False).blobs[loss_blob]
    ref = reference.forward(net_param.filtered(test), params, batch)
    return logits, loss, ref[logits_blob], ref[loss_blob]


def test_lenet_matches_the_system():
    from sparknet_tpu.models import lenet
    logits, loss, ref_logits, ref_loss = both(
        lenet(4, 4), (1, 28, 28), 10, 4, "ip2", "loss")
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def test_caffenet_lrn_groups_and_ceil_pools_match_the_system():
    from sparknet_tpu.models import caffenet
    logits, loss, ref_logits, ref_loss = both(
        caffenet(2, 2), (3, 227, 227), 1000, 2, "fc8", "loss")
    scale = float(np.max(np.abs(ref_logits)))
    assert float(np.max(np.abs(logits - ref_logits))) / scale < 1e-5
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def test_average_pool_divides_by_the_window_clipped_to_the_padding():
    # 5x5 input, 3x3 window, stride 2, pad 1: Caffe's corner windows
    # cover 3x3 positions of the padded image and divide by 9
    from sparknet_tpu.models.dsl import pooling_layer
    lp = pooling_layer("p", "x", "y", pool="AVE", kernel=3, stride=2, pad=1)
    y = reference._pool(lp, np.ones((1, 1, 5, 5), np.float32))
    assert y.shape == (1, 1, 3, 3)
    np.testing.assert_allclose(y[0, 0, 0, 0], 4 / 9, rtol=1e-6)
    np.testing.assert_allclose(y[0, 0, 1, 1], 1.0, rtol=1e-6)


def test_a_lower_precision_fails_the_tolerance():
    """What the tolerances are for: a forward in a lower precision than
    the cell states is out of them."""
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.models import lenet
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    import jax.numpy as jnp
    net_param, test = lenet(8, 8), NetState(Phase.TEST)
    params = Net(net_param, test).init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    batch = {"data": rng.normal(size=(8, 1, 28, 28)).astype(np.float32),
             "label": rng.integers(0, 10, size=8).astype(np.float32)}

    def verdict(dtype, compute):
        logits = Net(check.logits_net_param(net_param), test,
                     compute_dtype=compute).apply(
                         params, batch, train=False).blobs["ip2"]
        loss = Net(net_param, test, compute_dtype=compute).apply(
            params, batch, train=False).blobs["loss"]
        return check.compare(dtype, net_param.filtered(test), params, batch,
                             logits, loss, "ip2", "loss")

    assert verdict("float32", None)["ok"]
    assert verdict("bfloat16", jnp.bfloat16)["ok"]
    assert not verdict("float32", jnp.bfloat16)["ok"]
    assert not verdict("bfloat16", jnp.float8_e4m3fn)["ok"]


@pytest.mark.parametrize("stated, compute, held", [
    ("float32", None, True),
    ("bfloat16", "bfloat16", True),
    ("bfloat16", None, True),              # wider than stated is no fault
    ("float32", "bfloat16", False),        # what no tolerance sees on a TPU
    ("bfloat16", "float8_e4m3fn", False),
])
def test_the_types_say_what_the_tolerance_cannot(stated, compute, held):
    """On the chip float32 storage and bfloat16 storage are equally far
    from the reference, so the check reads the types of the train net's
    weights and of what its products are fed."""
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.models import caffenet
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    import jax.numpy as jnp
    net = Net(caffenet(8, 8), NetState(Phase.TRAIN),
              compute_dtype=compute and jnp.dtype(compute))
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    inp = {"crop": 227, "channels": 3}
    got = check.held_precision(stated, net, params, 8, inp)
    assert got["ok"] is held
    assert got["params_stored"] == ["float32"]
    assert got["products_fed"] == [compute or "float32"]


def test_weights_stored_narrower_than_stated_fail():
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.models import lenet
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    import jax.numpy as jnp
    net = Net(lenet(8, 8), NetState(Phase.TRAIN),
              compute_dtype=jnp.dtype("bfloat16"))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        jax.eval_shape(net.init, jax.random.PRNGKey(0)))
    inp = {"crop": 28, "channels": 1}
    got = check.held_precision("float32", net, params, 8, inp)
    assert not got["ok"] and got["params_stored"] == ["bfloat16"]
    assert check.held_precision("bfloat16", net, params, 8, inp)["ok"]
