"""Model-zoo compatibility pins.

Every net/solver prototxt shipped with the reference (caffe/models + the
caffe/examples tutorials) must keep loading through the prototxt front end
and — for the net files — building and forward-running through the graph
compiler.  This freezes the compatibility the reference gets for free from
its protobuf schema (reference: caffe/src/caffe/proto/caffe.proto) so a
parser or shape-inference regression fails loudly.

The data-layer swap mirrors the reference apps' ProtoLoader.replaceDataLayers
(reference: src/main/scala/libs/ProtoLoader.scala:50-57); deploy files run
from their own net-level input declarations.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import reference_path

from sparknet_tpu.graph import Net
from sparknet_tpu.proto import (
    NetState,
    Phase,
    load_net_prototxt,
    load_solver_prototxt,
    replace_data_layers,
)


def _ref(rel=""):
    return reference_path(os.path.join("caffe", rel))


# train/test net prototxts: path -> (channels, height, width) fed after the
# data-layer swap.  Geometry is what the reference apps feed each model
# (crop_size from transform_param where present).
TRAIN_NETS = {
    "examples/cifar10/cifar10_quick_train_test.prototxt": (3, 32, 32),
    "examples/cifar10/cifar10_full_train_test.prototxt": (3, 32, 32),
    "examples/cifar10/cifar10_full_java_train_test.prototxt": (3, 32, 32),
    "examples/cifar10/cifar10_full_sigmoid_train_test.prototxt": (3, 32, 32),
    "examples/cifar10/cifar10_full_sigmoid_train_test_bn.prototxt": (3, 32, 32),
    "examples/mnist/lenet_train_test.prototxt": (1, 28, 28),
    "examples/mnist/mnist_autoencoder.prototxt": (1, 28, 28),
    "examples/siamese/mnist_siamese_train_test.prototxt": (2, 28, 28),
    "examples/hdf5_classification/train_val.prototxt": (4, 1, 1),
    "examples/hdf5_classification/nonlinear_train_val.prototxt": (4, 1, 1),
    "examples/hdf5_classification/nonlinear_auto_train.prototxt": (4, 1, 1),
    "examples/hdf5_classification/nonlinear_auto_test.prototxt": (4, 1, 1),
    "models/bvlc_alexnet/train_val.prototxt": (3, 227, 227),
    "models/bvlc_reference_caffenet/train_val.prototxt": (3, 227, 227),
    "models/bvlc_googlenet/train_val.prototxt": (3, 224, 224),
    "models/finetune_flickr_style/train_val.prototxt": (3, 227, 227),
    "examples/finetune_pascal_detection/pascal_finetune_trainval_test.prototxt":
        (3, 227, 227),
    "examples/feature_extraction/imagenet_val.prototxt": (3, 227, 227),
}

# deploy-style nets: run straight from their input declarations.
DEPLOY_NETS = [
    "examples/mnist/lenet.prototxt",
    "examples/cifar10/cifar10_quick.prototxt",
    "examples/cifar10/cifar10_full.prototxt",
    "examples/net_surgery/conv.prototxt",
    "examples/siamese/mnist_siamese.prototxt",
    "models/bvlc_alexnet/deploy.prototxt",
    "models/bvlc_reference_caffenet/deploy.prototxt",
    "models/bvlc_googlenet/deploy.prototxt",
    "models/bvlc_reference_rcnn_ilsvrc13/deploy.prototxt",
    "models/finetune_flickr_style/deploy.prototxt",
    "examples/net_surgery/bvlc_caffenet_full_conv.prototxt",
]

# nets whose user-defined Python layers resolve through the pycaffe-compat
# adapter: built raw (DummyData feeds itself; python_param.module imports
# from the reference's examples/pycaffe/layers on sys.path).
PYLAYER_NETS = [
    "examples/pycaffe/linreg.prototxt",
]

SOLVERS = [
    "examples/cifar10/cifar10_quick_solver.prototxt",
    "examples/cifar10/cifar10_quick_solver_lr1.prototxt",
    "examples/cifar10/cifar10_full_solver.prototxt",
    "examples/cifar10/cifar10_full_solver_lr1.prototxt",
    "examples/cifar10/cifar10_full_solver_lr2.prototxt",
    "examples/cifar10/cifar10_full_java_solver.prototxt",
    "examples/cifar10/cifar10_full_sigmoid_solver.prototxt",
    "examples/cifar10/cifar10_full_sigmoid_solver_bn.prototxt",
    "examples/mnist/lenet_solver.prototxt",
    "examples/mnist/lenet_solver_adam.prototxt",
    "examples/mnist/lenet_solver_rmsprop.prototxt",
    "examples/mnist/lenet_adadelta_solver.prototxt",
    "examples/mnist/lenet_auto_solver.prototxt",
    "examples/mnist/lenet_multistep_solver.prototxt",
    "examples/mnist/lenet_stepearly_solver.prototxt",
    "examples/mnist/lenet_consolidated_solver.prototxt",  # V1 `layers` net
    "examples/mnist/mnist_autoencoder_solver.prototxt",
    "examples/mnist/mnist_autoencoder_solver_adadelta.prototxt",
    "examples/mnist/mnist_autoencoder_solver_adagrad.prototxt",
    "examples/mnist/mnist_autoencoder_solver_nesterov.prototxt",
    "examples/siamese/mnist_siamese_solver.prototxt",
    "examples/hdf5_classification/solver.prototxt",
    "examples/hdf5_classification/nonlinear_solver.prototxt",
    "examples/finetune_pascal_detection/pascal_finetune_solver.prototxt",
    "models/bvlc_alexnet/solver.prototxt",
    "models/bvlc_reference_caffenet/solver.prototxt",
    "models/bvlc_googlenet/solver.prototxt",
    "models/bvlc_googlenet/quick_solver.prototxt",
    "models/finetune_flickr_style/solver.prototxt",
]

# nets too large to forward on the CPU test rig every run — build/init only.
BUILD_ONLY = {
    "models/bvlc_alexnet/train_val.prototxt",
    "models/bvlc_reference_caffenet/train_val.prototxt",
    "models/bvlc_googlenet/train_val.prototxt",
    "models/finetune_flickr_style/train_val.prototxt",
    "examples/finetune_pascal_detection/pascal_finetune_trainval_test.prototxt",
    "examples/feature_extraction/imagenet_val.prototxt",
    "models/bvlc_alexnet/deploy.prototxt",
    "models/bvlc_reference_caffenet/deploy.prototxt",
    "models/bvlc_googlenet/deploy.prototxt",
    "models/bvlc_reference_rcnn_ilsvrc13/deploy.prototxt",
    "models/finetune_flickr_style/deploy.prototxt",
    "examples/net_surgery/bvlc_caffenet_full_conv.prototxt",
}


def _read(rel):
    with open(_ref(rel)) as f:
        return f.read()


def test_zoo_inventory_complete():
    """Every .prototxt in the reference tree is classified above."""
    import glob
    known = (set(TRAIN_NETS) | set(DEPLOY_NETS) | set(PYLAYER_NETS)
             | set(SOLVERS))
    found = set()
    for root in ("models", "examples"):
        for p in glob.glob(os.path.join(_ref(root), "**", "*.prototxt"),
                           recursive=True):
            found.add(os.path.relpath(p, _ref()))
    missing = found - known
    assert not missing, f"unclassified zoo prototxts: {sorted(missing)}"


@pytest.mark.parametrize("rel", sorted(TRAIN_NETS), ids=lambda r: r)
def test_train_net_builds(rel):
    c, h, w = TRAIN_NETS[rel]
    netp = load_net_prototxt(_read(rel))
    netp = replace_data_layers(netp, train_batch_size=2, test_batch_size=2,
                               channels=c, height=h, width=w)
    net = Net(netp, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    if rel in BUILD_ONLY:
        assert net.blob_shapes  # shape inference completed
        return
    inputs = {}
    for name, shape in net.input_blobs.items():
        if name == "label" or name.startswith("sim"):
            inputs[name] = jnp.zeros(shape)
        else:
            inputs[name] = jnp.asarray(
                np.random.default_rng(0).normal(size=shape).astype(np.float32))
    out = net.apply(params, inputs, rng=jax.random.PRNGKey(1))
    assert np.isfinite(float(out.loss))


@pytest.mark.parametrize("rel", sorted(DEPLOY_NETS), ids=lambda r: r)
def test_deploy_net_builds(rel):
    netp = load_net_prototxt(_read(rel))
    # shrink declared batch to 1 to keep the CPU rig fast
    for s in netp.input_shape:
        if len(s.dim) >= 1:
            s.dim[0] = 1
    net = Net(netp, NetState(Phase.TEST))
    params = net.init(jax.random.PRNGKey(0))
    if rel in BUILD_ONLY:
        assert net.blob_shapes
        return
    inputs = {
        name: jnp.zeros(shape) for name, shape in net.input_blobs.items()
    }
    blobs = net.apply_all(params, inputs)
    assert all(np.all(np.isfinite(np.asarray(v))) for v in blobs.values())


@pytest.mark.parametrize("rel", sorted(PYLAYER_NETS), ids=lambda r: r)
def test_python_layer_net_runs(rel):
    """Nets with ``Python`` layers build and train-step end-to-end: the
    adapter resolves python_param {module, layer} against the reference's
    own pycaffe example layers (reference: layer_factory.cpp Python
    registration; examples/pycaffe/linreg.prototxt)."""
    import sys

    from sparknet_tpu import pycaffe_compat
    pycaffe_compat.install()
    layers_dir = _ref("examples/pycaffe/layers")
    if layers_dir not in sys.path:
        sys.path.insert(0, layers_dir)
    netp = load_net_prototxt(_read(rel))
    net = Net(netp, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    out = net.apply(params, {}, rng=jax.random.PRNGKey(1))
    assert np.isfinite(float(out.loss))
    # and the Python loss is differentiable end-to-end (autodiff through
    # the pure_callback custom_vjp)
    def loss_fn(p):
        return net.apply(p, {}, rng=jax.random.PRNGKey(1)).loss
    grads = jax.grad(loss_fn)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert any(float(np.max(np.abs(np.asarray(g)))) > 0 for g in flat)


@pytest.mark.parametrize("rel", sorted(SOLVERS), ids=lambda r: r)
def test_solver_parses(rel):
    sp = load_solver_prototxt(_read(rel))
    assert sp.base_lr > 0
    assert sp.lr_policy in {"fixed", "step", "exp", "inv", "multistep",
                            "poly", "sigmoid", "stepearly"}


@pytest.mark.parametrize("rel", sorted(list(TRAIN_NETS) + DEPLOY_NETS
                                       + PYLAYER_NETS))
def test_zoo_serialize_roundtrip(rel):
    """Every zoo prototxt survives load -> to_pmsg -> serialize -> reload
    with the same layer structure — the write half (save_net_prototxt /
    upgrade tools) exercised over every real prototxt construct,
    including V0/V1-format files which round-trip as upgraded V2."""
    from sparknet_tpu.proto import save_net_prototxt

    net = load_net_prototxt(_ref(rel))
    back = load_net_prototxt(save_net_prototxt(net))
    assert [l.name for l in back.layer] == [l.name for l in net.layer]
    assert [l.type for l in back.layer] == [l.type for l in net.layer]
    assert [l.bottom for l in back.layer] == [l.bottom for l in net.layer]
    assert [l.top for l in back.layer] == [l.top for l in net.layer]
    for a, b in zip(net.layer, back.layer):
        assert a.params == b.params, a.name
        assert [(r.phase, r.stage) for r in a.include] == \
            [(r.phase, r.stage) for r in b.include], a.name
        assert [(r.phase, r.stage) for r in a.exclude] == \
            [(r.phase, r.stage) for r in b.exclude], a.name
        assert [(p.name, p.raw_lr_mult, p.raw_decay_mult)
                for p in a.param] == \
            [(p.name, p.raw_lr_mult, p.raw_decay_mult)
             for p in b.param], a.name
        assert a.loss_weight == b.loss_weight and a.phase == b.phase
    assert back.input == net.input
    assert [s.dim for s in back.input_shape] == \
        [s.dim for s in net.input_shape]
