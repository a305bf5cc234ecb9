"""The classic pycaffe workflow, unchanged on this framework.

Mirrors the reference's pycaffe examples (caffe/examples/00-classification
and 01-learning-lenet notebooks, python/caffe/test usage): build a net
with NetSpec, train it with get_solver, inspect blobs/params, do net
surgery, save/reload, and classify with a Transformer-preprocessed input.

Run:  python examples/pycaffe_workflow.py        (CPU or TPU)
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparknet_tpu import pycaffe_compat  # noqa: E402

pycaffe_compat.install()

import caffe  # noqa: E402  (resolves to the shim)
from caffe import layers as L, params as P  # noqa: E402


def make_nets(workdir: str) -> str:
    """Author train/test nets with NetSpec and a solver prototxt."""
    n = caffe.NetSpec()
    n.data, n.label = L.DummyData(
        dummy_data_param=dict(
            shape=[dict(dim=[32, 1, 12, 12]), dict(dim=[32])],
            data_filler=[dict(type="gaussian", std=1.0),
                         dict(type="constant", value=1.0)]),
        ntop=2)
    n.conv1 = L.Convolution(n.data, kernel_size=3, num_output=8,
                            weight_filler=dict(type="xavier"))
    n.relu1 = L.ReLU(n.conv1, in_place=True)
    n.pool1 = L.Pooling(n.relu1, kernel_size=2, stride=2,
                        pool=P.Pooling.MAX)
    n.score = L.InnerProduct(n.pool1, num_output=3,
                             weight_filler=dict(type="xavier"))
    n.loss = L.SoftmaxWithLoss(n.score, n.label)
    n.acc = L.Accuracy(n.score, n.label, include=dict(phase="TEST"))
    net_path = os.path.join(workdir, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(str(n.to_proto()))

    solver_path = os.path.join(workdir, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write('net: "net.prototxt"\nbase_lr: 0.1\nmomentum: 0.9\n'
                'test_iter: 2\ntest_interval: 1000\nrandom_seed: 1\n')
    return solver_path


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="pycaffe_example_")
    solver_path = make_nets(workdir)
    os.chdir(workdir)  # net: reference resolves like Caffe (cwd first)

    # --- train ----------------------------------------------------------
    solver = caffe.get_solver(solver_path)
    l0 = solver.step(1)
    l1 = solver.step(60)
    print(f"loss {l0:.3f} -> {l1:.3f} after {solver.iter} iters")

    # --- inspect --------------------------------------------------------
    print("layers:", [(ly.type, [b.shape for b in ly.blobs])
                      for ly in solver.net.layers][:3], "...")
    out = solver.test_nets[0].forward()
    print("test net loss:", float(out["loss"]))

    # --- net surgery + save/reload -------------------------------------
    solver.net.params["score"][0].data[...] *= 0.5
    model_path = os.path.join(workdir, "surgery.caffemodel")
    solver.net.save(model_path)
    net = caffe.Net(open(os.path.join(workdir, "net.prototxt")).read(),
                    weights=model_path, phase=caffe.TEST)
    out = net.forward()
    print("reloaded net forward loss:", float(out["loss"]))

    # --- Transformer-preprocessed classification -----------------------
    deploy = caffe.NetSpec()
    deploy.data = L.Input(input_param=dict(
        shape=dict(dim=[1, 1, 12, 12])))
    deploy.conv1 = L.Convolution(deploy.data, kernel_size=3, num_output=8)
    deploy.relu1 = L.ReLU(deploy.conv1, in_place=True)
    deploy.pool1 = L.Pooling(deploy.relu1, kernel_size=2, stride=2,
                             pool=P.Pooling.MAX)
    deploy.score = L.InnerProduct(deploy.pool1, num_output=3)
    deploy.prob = L.Softmax(deploy.score)
    dnet = caffe.Net(str(deploy.to_proto()), weights=model_path,
                     phase=caffe.TEST)
    t = caffe.io.Transformer({"data": dnet.blobs["data"].shape})
    t.set_transpose("data", (2, 0, 1))
    img = np.random.default_rng(0).uniform(size=(12, 12, 1)).astype(np.float32)
    dnet.blobs["data"].data[...] = t.preprocess("data", img)
    probs = dnet.forward()["prob"]
    print("class probabilities:", np.round(probs[0], 3))
    assert abs(probs.sum() - 1.0) < 1e-4

    # --- deploy-time reshape (the batch-size idiom) ---------------------
    dnet.blobs["data"].reshape(5, 1, 12, 12)
    dnet.blobs["data"].data[...] = np.random.default_rng(1).uniform(
        size=(5, 1, 12, 12)).astype(np.float32)
    probs5 = dnet.forward()["prob"]
    print("after reshape to batch 5:", probs5.shape)
    assert probs5.shape == (5, 3)

    # --- batched scoring over many samples ------------------------------
    imgs = np.random.default_rng(2).uniform(
        size=(13, 1, 12, 12)).astype(np.float32)
    outs = dnet.forward_all(data=imgs)
    print("forward_all over 13 samples:", outs["prob"].shape)
    assert outs["prob"].shape == (13, 3)

    # --- saliency via ranged backward (the DeepDream pattern) -----------
    dnet.blobs["data"].reshape(1, 1, 12, 12)
    dnet.blobs["data"].data[...] = t.preprocess("data", img)
    dnet.forward(end="score")
    dnet.blobs["score"].diff[...] = np.eye(3, dtype=np.float32)[0]
    sal = dnet.backward(start="score")["data"]
    print("saliency |grad| for class 0:", round(float(np.abs(sal).sum()), 4))
    assert sal.shape == (1, 1, 12, 12) and np.any(sal != 0)
    print("OK")


if __name__ == "__main__":
    main()
