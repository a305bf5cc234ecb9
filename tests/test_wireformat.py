"""Binary protobuf interchange tests — the analog of the reference's
test_upgrade_proto.cpp + test_io.cpp + the snapshot/restore halves of
test_gradient_based_solver.cpp.  Includes a bidirectional cross-check
against the *official* protobuf implementation (protoc-generated pb2 over
the reference caffe.proto), when protoc is available."""

import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import reference_path

from sparknet_tpu.models import lenet
from sparknet_tpu.proto import (
    load_solver_prototxt_with_net,
    parse,
)
from sparknet_tpu.proto.caffe_pb import NetParameter, SolverParameter
from sparknet_tpu.proto.caffemodel import (
    array_to_blob,
    load_caffemodel,
    load_mean_binaryproto,
    load_net_binaryproto,
    load_solverstate,
    save_caffemodel,
    save_mean_binaryproto,
    save_solverstate,
)
from sparknet_tpu.proto.wireformat import decode, encode
from sparknet_tpu.solvers import Solver

REF_PROTO = "caffe/src/caffe/proto/caffe.proto"
SOLVER_TXT = 'base_lr: 0.01\nmomentum: 0.9\nlr_policy: "fixed"\n'


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def test_solver_prototxt_binary_roundtrip():
    text = open(reference_path(
        "caffe/models/bvlc_googlenet/solver.prototxt")).read()
    m = parse(text)
    raw = encode(m, "SolverParameter")
    sp = SolverParameter.from_pmsg(decode(raw, "SolverParameter"))
    ref = SolverParameter.from_pmsg(m)
    assert sp.lr_policy == ref.lr_policy
    assert sp.base_lr == pytest.approx(ref.base_lr)  # float32 storage
    assert sp.momentum == pytest.approx(ref.momentum)
    assert sp.max_iter == ref.max_iter
    assert sp.stepvalue == ref.stepvalue or sp.stepsize == ref.stepsize
    # re-encode is byte-stable
    assert encode(decode(raw, "SolverParameter"), "SolverParameter") == raw


def test_net_prototxt_binary_roundtrip():
    text = open(reference_path(
        "caffe/models/bvlc_alexnet/train_val.prototxt")).read()
    m = parse(text)
    raw = encode(m, "NetParameter")
    got = NetParameter.from_pmsg(decode(raw, "NetParameter"))
    ref = NetParameter.from_pmsg(m)
    assert [l.name for l in got.layer] == [l.name for l in ref.layer]
    assert [l.type for l in got.layer] == [l.type for l in ref.layer]
    conv_got = next(l for l in got.layer if l.name == "conv2")
    conv_ref = next(l for l in ref.layer if l.name == "conv2")
    assert int(conv_got.sub("convolution_param").get("group")) == \
        int(conv_ref.sub("convolution_param").get("group"))


def test_scale_bias_input_params_roundtrip():
    """Post-fork upstream fields (Scale/Bias/Input) must survive the wire —
    ResNet-class zoo models carry scale_param in their .caffemodel."""
    m = parse('layer { name: "s" type: "Scale" '
              'scale_param { bias_term: true axis: 1 } }\n'
              'layer { name: "in" type: "Input" '
              'input_param { shape { dim: 1 dim: 3 } } }')
    raw = encode(m, "NetParameter")
    net = NetParameter.from_pmsg(decode(raw, "NetParameter"))
    assert bool(net.layer[0].sub("scale_param").get("bias_term")) is True
    from sparknet_tpu.proto.caffe_pb import BlobShape
    shp = BlobShape.from_pmsg(net.layer[1].sub("input_param").get("shape"))
    assert shp.dim == [1, 3]


def test_layout_mismatch_rejected(tmp_path):
    """Same-size but different-layout blobs must raise, not silently
    reshape (Caffe shape CHECK semantics)."""
    a = _solver()
    key = next(iter(a.params))
    shape = np.asarray(a.params[key][0]).shape
    bad = {key: [np.zeros(shape[::-1], np.float32)]
           + [np.asarray(b) for b in a.params[key][1:]]}
    with pytest.raises(ValueError, match="incompatible"):
        a.copy_trained_layers_from(bad)


def test_negative_and_bool_fields_roundtrip():
    sp_msg = parse("random_seed: -1\ntest_initialization: false\n"
                   "clip_gradients: -1.0\n")
    raw = encode(sp_msg, "SolverParameter")
    sp = SolverParameter.from_pmsg(decode(raw, "SolverParameter"))
    assert sp.random_seed == -1
    assert sp.test_initialization is False
    assert sp.clip_gradients == -1.0


# ---------------------------------------------------------------------------
# caffemodel / binaryproto
# ---------------------------------------------------------------------------

def test_caffemodel_save_load_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    params = {
        "conv1": [rng.normal(size=(4, 3, 3, 3)).astype(np.float32),
                  rng.normal(size=(4,)).astype(np.float32)],
        "fc1": [rng.normal(size=(10, 36)).astype(np.float32)],
    }
    path = str(tmp_path / "model.caffemodel")
    save_caffemodel(path, params)
    loaded = load_caffemodel(path)
    assert set(loaded) == {"conv1", "fc1"}
    for k in params:
        for a, b in zip(params[k], loaded[k]):
            np.testing.assert_array_equal(a, b)


def test_legacy_blob_shape_load(tmp_path):
    """Legacy (num,channels,height,width) BlobProto spellings load and
    reshape into new-style nets (Blob::ShapeEquals legacy tolerance,
    reference: blob.cpp)."""
    from sparknet_tpu.proto.textformat import PMessage
    w = np.arange(20, dtype=np.float32)
    blob = PMessage()
    for k, v in zip(("num", "channels", "height", "width"), (1, 1, 4, 5)):
        blob.add(k, v)
    blob.add("data", w)
    lmsg = PMessage()
    lmsg.add("name", "ip")
    lmsg.add("blobs", blob)
    netmsg = PMessage()
    netmsg.add("layer", lmsg)
    path = tmp_path / "legacy.caffemodel"
    path.write_bytes(encode(netmsg, "NetParameter"))
    loaded = load_caffemodel(str(path))
    assert loaded["ip"][0].shape == (1, 1, 4, 5)


def test_v1_format_caffemodel_loads(tmp_path):
    """V1-format files (repeated V1LayerParameter ``layers``, enum types) —
    the format of every published BVLC zoo .caffemodel (reference:
    upgrade_proto.cpp UpgradeV1Net)."""
    from sparknet_tpu.proto.textformat import PMessage
    w = np.arange(6, dtype=np.float32).reshape(2, 3)
    blob = array_to_blob(w)
    v1 = PMessage()
    v1.add("name", "ip1")
    v1.add("type", "INNER_PRODUCT")
    v1.add("bottom", "data")
    v1.add("top", "ip1")
    v1.add("blobs", blob)
    netmsg = PMessage()
    netmsg.add("name", "v1net")
    netmsg.add("layers", v1)
    raw = encode(netmsg, "NetParameter")
    net = NetParameter.from_pmsg(decode(raw, "NetParameter"))
    assert net.layer[0].type == "InnerProduct"  # V1 enum -> V2 name
    assert net.layer[0].name == "ip1"
    np.testing.assert_array_equal(net.layer[0].blobs[0], w)
    loaded = load_caffemodel(raw)
    np.testing.assert_array_equal(loaded["ip1"][0], w)


def test_mean_binaryproto_roundtrip(tmp_path):
    mean = np.random.default_rng(0).normal(size=(3, 8, 8)).astype(np.float32)
    path = str(tmp_path / "mean.binaryproto")
    save_mean_binaryproto(path, mean)
    np.testing.assert_allclose(load_mean_binaryproto(path), mean, rtol=1e-6)


# ---------------------------------------------------------------------------
# Solver integration
# ---------------------------------------------------------------------------

def _solver(batch=4):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(batch, batch))
    return Solver(sp, seed=0)


def _feed(batch=4, n=64):
    rng = np.random.default_rng(1)
    while True:
        yield {"data": rng.normal(size=(batch, 1, 28, 28)).astype(np.float32),
               "label": rng.integers(0, 10, size=(batch,)).astype(np.float32)}


def test_solver_caffe_snapshot_restore_equivalence(tmp_path):
    """Training N steps, caffe-format snapshot, restore into a fresh solver,
    then continuing, matches uninterrupted training — the core assertion of
    test_gradient_based_solver.cpp's snapshot tests."""
    a = _solver()
    a.set_train_data(_feed())
    a.step(3)
    model, state = a.snapshot_caffe(str(tmp_path / "snap"))
    a.step(2)

    b = _solver()
    b.load_weights(model)
    b.restore_caffe(state)
    assert b.iter == 3
    # re-align the data stream: a consumed 3 batches before the fork
    it = _feed()
    for _ in range(3):
        next(it)
    b.set_train_data(it)
    b._rng = a._rng  # jitter alignment is not part of the snapshot contract
    b.step(2)
    for k in a.params:
        for x, y in zip(a.params[k], b.params[k]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-4, atol=2e-5)


def test_solver_hdf5_snapshot_restore_equivalence(tmp_path):
    """``snapshot_format: HDF5`` writes .caffemodel.h5/.solverstate.h5 in
    the reference layout (solver.cpp:449-459 SnapshotToHDF5,
    sgd_solver.cpp:275-338, net.cpp:926-975 ToHDF5) and restores to the
    exact state the binaryproto path restores to."""
    h5py = pytest.importorskip("h5py")
    sp_txt = SOLVER_TXT + "snapshot_format: HDF5\n"
    sp = load_solver_prototxt_with_net(sp_txt, lenet(4, 4))
    assert sp.snapshot_format == "HDF5"
    a = Solver(sp, seed=0)
    a.set_train_data(_feed())
    a.step(3)
    model, state = a.snapshot_caffe(str(tmp_path / "snap"))
    assert model.endswith(".caffemodel.h5")
    assert state.endswith(".solverstate.h5")

    # reference on-disk layout: data/<layer>/<i> groups, history/<i>
    with h5py.File(model) as f:
        assert "conv1" in f["data"] and "0" in f["data"]["conv1"]
    with h5py.File(state) as f:
        assert int(np.asarray(f["iter"])) == 3 and "0" in f["history"]

    # cross-format: restoring h5 == restoring binaryproto
    bp = _solver()
    bp_model, bp_state = None, None
    a.sp.snapshot_format = "BINARYPROTO"
    bp_model, bp_state = a.snapshot_caffe(str(tmp_path / "snap_bp"))

    h = _solver()
    h.load_weights(model)
    h.restore_caffe(state)
    bp.load_weights(bp_model)
    bp.restore_caffe(bp_state)
    assert h.iter == bp.iter == 3
    for k in h.params:
        for x, y in zip(h.params[k], bp.params[k]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6)
    for slot in h.state:
        if slot == "iter":
            continue
        for k in h.state[slot]:
            for x, y in zip(h.state[slot][k], bp.state[slot][k]):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=1e-6)


SIAMESE_SOLVER_NET = """
name: "siamese"
layer { name: "d" type: "JavaData" top: "a" top: "label"
        java_data_param { shape { dim: 4 dim: 8 } shape { dim: 4 } } }
layer { name: "ip_a" type: "InnerProduct" bottom: "a" top: "fa"
        param { name: "w" }
        inner_product_param { num_output: 8
                              weight_filler { type: "xavier" }
                              bias_filler { type: "constant" value: 1 } } }
layer { name: "ip_b" type: "InnerProduct" bottom: "fa" top: "fb"
        param { name: "w" }
        inner_product_param { num_output: 8
                              weight_filler { type: "xavier" }
                              bias_filler { type: "constant" value: 2 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "fb" bottom: "a"
        top: "loss" }
"""


def test_caffemodel_interop_with_shared_params(tmp_path):
    """A partially-shared net saves caffemodels with FULL per-layer blob
    lists (Net::ToProto convention — Caffe CHECK_EQs blob counts on load)
    and loads them back through the sharing map."""
    from sparknet_tpu.proto import load_net_prototxt
    from sparknet_tpu.proto.caffemodel import load_net_binaryproto

    def make():
        sp = load_solver_prototxt_with_net(
            SOLVER_TXT, load_net_prototxt(SIAMESE_SOLVER_NET))
        return Solver(sp, seed=0)

    a = make()
    assert len(a.params["ip_a"]) == 2 and len(a.params["ip_b"]) == 1
    model, _ = a.snapshot_caffe(str(tmp_path / "shared"))

    # the file carries 2 blobs for BOTH ip layers (sharer repeats the weight)
    net = load_net_binaryproto(model)
    by_name = {lp.name: lp for lp in net.layer}
    assert len(by_name["ip_a"].blobs) == 2
    assert len(by_name["ip_b"].blobs) == 2
    np.testing.assert_allclose(by_name["ip_a"].blobs[0],
                               by_name["ip_b"].blobs[0])  # same shared w

    b = make()
    b.load_weights(model)
    for k in a.params:
        for x, y in zip(a.params[k], b.params[k]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6)


def test_load_weights_sniffs_caffemodel(tmp_path):
    a = _solver()
    path = str(tmp_path / "w.caffemodel")
    save_caffemodel(path, {k: [np.asarray(b) for b in v]
                           for k, v in a.params.items()})
    b = _solver(batch=2)
    b.load_weights(path)
    for k in a.params:
        for x, y in zip(a.params[k], b.params[k]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Cross-check vs official protobuf (skipped when protoc is unavailable)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def caffe_pb2(tmp_path_factory):
    if shutil.which("protoc") is None:
        pytest.skip("protoc not available")
    gen = tmp_path_factory.mktemp("protogen")
    shutil.copy(reference_path(REF_PROTO), gen / "caffe.proto")
    subprocess.run(["protoc", "--python_out=.", "caffe.proto"],
                   cwd=gen, check=True)
    sys.path.insert(0, str(gen))
    try:
        import caffe_pb2 as mod
    except Exception as e:  # pragma: no cover
        pytest.skip(f"generated pb2 unusable: {e}")
    finally:
        sys.path.remove(str(gen))
    return mod


def test_interop_with_official_protobuf(caffe_pb2):
    net = caffe_pb2.NetParameter()
    net.name = "interop"
    l = net.layer.add()
    l.name = "conv1"
    l.type = "Convolution"
    l.bottom.append("data")
    l.top.append("conv1")
    l.convolution_param.num_output = 4
    l.convolution_param.kernel_size.append(3)
    b = l.blobs.add()
    b.shape.dim.extend([4, 3, 3, 3])
    b.data.extend(np.arange(108, dtype=np.float32).tolist())

    # official encode -> our decode
    got = NetParameter.from_pmsg(decode(net.SerializeToString(), "NetParameter"))
    assert got.name == "interop"
    assert got.layer[0].blobs[0].shape == (4, 3, 3, 3)
    assert got.layer[0].blobs[0].sum() == np.arange(108).sum()

    # our encode -> official decode
    raw2 = encode(decode(net.SerializeToString(), "NetParameter"),
                  "NetParameter")
    net2 = caffe_pb2.NetParameter()
    net2.ParseFromString(raw2)
    assert net2.layer[0].name == "conv1"
    assert list(net2.layer[0].blobs[0].shape.dim) == [4, 3, 3, 3]
    np.testing.assert_array_equal(
        np.asarray(net2.layer[0].blobs[0].data),
        np.arange(108, dtype=np.float32))


def test_solverstate_interop_with_official(caffe_pb2, tmp_path):
    path = str(tmp_path / "s.solverstate")
    hist = [np.arange(4, dtype=np.float32), np.ones((2, 2), np.float32)]
    save_solverstate(path, 42, hist, learned_net="m.caffemodel",
                     current_step=7)
    st = caffe_pb2.SolverState()
    st.ParseFromString(open(path, "rb").read())
    assert st.iter == 42
    assert st.current_step == 7
    assert st.learned_net == "m.caffemodel"
    assert len(st.history) == 2
    np.testing.assert_array_equal(np.asarray(st.history[0].data),
                                  hist[0])
    back = load_solverstate(path)
    assert back["iter"] == 42
    np.testing.assert_array_equal(back["history"][1], hist[1])
