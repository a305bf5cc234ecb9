"""Training-trajectory equivalence vs an independent torch reimplementation.

The accuracy-parity proxy runnable on this rig (real CIFAR/ImageNet are
absent): the SAME cifar10_quick config — architecture from
examples/cifar10/cifar10_quick_train_test.prototxt, solver from
cifar10_quick_solver.prototxt (base_lr 0.001, momentum 0.9, weight_decay
0.004, lr_policy fixed), the SAME initial weights (moved through this
repo's own .caffemodel interchange), and the SAME synthetic batches —
must produce the SAME per-step loss curve in this framework and in a
from-scratch torch implementation whose update rule transcribes
sgd_solver.cpp:27-143 (Regularize: grad += λ·decay_mult·w; then
history = local_lr·grad + momentum·history; w -= history).

This is strictly stronger than the per-op cross-checks in
test_torch_crosscheck.py: it pins the whole loop — forward, backward,
regularization, momentum, lr_mult handling — over many steps, the way
test_gradient_based_solver.cpp pins the C++ solvers.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402

import jax  # noqa: E402
from conftest import reference_path  # noqa: E402

from sparknet_tpu.proto import (  # noqa: E402
    load_net_prototxt,
    load_solver_prototxt_with_net,
    replace_data_layers,
)
from sparknet_tpu.solvers import Solver  # noqa: E402

REF_NET = "caffe/examples/cifar10/cifar10_quick_train_test.prototxt"
SOLVER_TXT = ("base_lr: 0.001\nmomentum: 0.9\nweight_decay: 0.004\n"
              'lr_policy: "fixed"\n')
BATCH = 16


def _make_solver(compute_dtype=None):
    netp = load_net_prototxt(open(reference_path(REF_NET)).read())
    netp = replace_data_layers(netp, BATCH, BATCH, 3, 32, 32)
    sp = load_solver_prototxt_with_net(SOLVER_TXT, netp)
    import jax.numpy as jnp
    dt = jnp.bfloat16 if compute_dtype == "bf16" else None
    return Solver(sp, seed=0, compute_dtype=dt)


def _batches(n_steps, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_steps):
        out.append({
            "data": rng.normal(size=(BATCH, 3, 32, 32)).astype(np.float32),
            "label": rng.integers(0, 10, size=(BATCH,)).astype(np.float32),
        })
    return out


# -- independent torch model -------------------------------------------------

class TorchQuick:
    """cifar10_quick transcribed from the prototxt, NOT from this repo's
    graph code: conv1→maxpool→relu / conv2→relu→avepool /
    conv3→relu→avepool / ip1→ip2, caffe ceil-mode pooling."""

    LAYERS = ["conv1", "conv2", "conv3", "ip1", "ip2"]
    # (lr_mult_w, lr_mult_b) per the prototxt param blocks; decay_mult
    # defaults to 1 (caffe.proto ParamSpec)
    LR_MULTS = {n: (1.0, 2.0) for n in LAYERS}

    def __init__(self, caffemodel_blobs):
        self.p = {}
        self.hist = {}
        for name in self.LAYERS:
            w, b = caffemodel_blobs[name]
            self.p[name + ".w"] = torch.tensor(np.asarray(w),
                                               requires_grad=True)
            self.p[name + ".b"] = torch.tensor(np.asarray(b),
                                               requires_grad=True)
        for k, v in self.p.items():
            self.hist[k] = torch.zeros_like(v)

    @staticmethod
    def _ave_pool_caffe(x):
        # caffe AVE 3x3 s2 ceil-mode, denominator = window clipped to the
        # input extent (pooling_layer.cpp AVE branch, pad == 0)
        return F.avg_pool2d(x, 3, 2, ceil_mode=True,
                            count_include_pad=False)

    def forward(self, x, y):
        p = self.p
        h = F.conv2d(x, p["conv1.w"], p["conv1.b"], padding=2)
        h = F.max_pool2d(h, 3, 2, ceil_mode=True)
        h = F.relu(h)
        h = F.conv2d(h, p["conv2.w"], p["conv2.b"], padding=2)
        h = F.relu(h)
        h = self._ave_pool_caffe(h)
        h = F.conv2d(h, p["conv3.w"], p["conv3.b"], padding=2)
        h = F.relu(h)
        h = self._ave_pool_caffe(h)
        h = h.reshape(h.shape[0], -1)
        h = F.linear(h, p["ip1.w"], p["ip1.b"])
        h = F.linear(h, p["ip2.w"], p["ip2.b"])
        return h, F.cross_entropy(h, y)

    def sgd_step(self, loss, base_lr=0.001, momentum=0.9, wd=0.004):
        """sgd_solver.cpp update order: Regularize (L2: grad += λ·w),
        ComputeUpdateValue (history = local_rate·grad + m·history),
        Blob::Update (w -= history)."""
        grads = torch.autograd.grad(loss, list(self.p.values()))
        with torch.no_grad():
            for (k, v), g in zip(self.p.items(), grads):
                layer, kind = k.split(".")
                lmw, lmb = self.LR_MULTS[layer]
                local_lr = base_lr * (lmw if kind == "w" else lmb)
                g = g + wd * v  # decay_mult 1 on weights AND biases here
                self.hist[k] = local_lr * g + momentum * self.hist[k]
                v -= self.hist[k]


def _export_initial_weights(solver, tmp_path):
    model, _ = solver.snapshot_caffe(str(tmp_path / "init"))
    from sparknet_tpu.proto.caffemodel import load_caffemodel
    return load_caffemodel(model)


# -- tests -------------------------------------------------------------------

def test_forward_activation_fixture(tmp_path):
    """Golden-activation check: identical weights (through the
    .caffemodel interchange), identical input ⇒ layer-by-layer identical
    activations between the two frameworks."""
    solver = _make_solver()
    blobs = _export_initial_weights(solver, tmp_path)
    tq = TorchQuick(blobs)
    b = _batches(1)[0]
    ours = solver.train_net.apply_all(
        solver.params, {"data": b["data"], "label": b["label"]}, train=False)
    x = torch.tensor(b["data"])
    p = tq.p
    h = F.conv2d(x, p["conv1.w"], p["conv1.b"], padding=2)
    np.testing.assert_allclose(np.asarray(ours["conv1"]), h.detach().numpy(),
                               atol=1e-5, rtol=1e-4)
    h = F.relu(F.max_pool2d(h, 3, 2, ceil_mode=True))
    np.testing.assert_allclose(np.asarray(ours["pool1"]), h.detach().numpy(),
                               atol=1e-5, rtol=1e-4)
    h = F.relu(F.conv2d(h, p["conv2.w"], p["conv2.b"], padding=2))
    h = TorchQuick._ave_pool_caffe(h)
    np.testing.assert_allclose(np.asarray(ours["pool2"]), h.detach().numpy(),
                               atol=1e-5, rtol=1e-4)
    h = F.relu(F.conv2d(h, p["conv3.w"], p["conv3.b"], padding=2))
    h = TorchQuick._ave_pool_caffe(h)
    np.testing.assert_allclose(np.asarray(ours["pool3"]), h.detach().numpy(),
                               atol=1e-5, rtol=1e-4)
    h = F.linear(h.reshape(h.shape[0], -1), p["ip1.w"], p["ip1.b"])
    np.testing.assert_allclose(np.asarray(ours["ip1"]), h.detach().numpy(),
                               atol=1e-5, rtol=1e-4)
    h = F.linear(h, p["ip2.w"], p["ip2.b"])
    np.testing.assert_allclose(np.asarray(ours["ip2"]), h.detach().numpy(),
                               atol=1e-4, rtol=1e-4)


def test_training_trajectory_tracks_torch(tmp_path):
    """~300 steps of the full solver loop: per-step losses of the two
    frameworks track within float32 drift tolerance, and final weights
    agree — same config ⇒ same trajectory."""
    n_steps = 300
    solver = _make_solver()
    blobs = _export_initial_weights(solver, tmp_path)
    tq = TorchQuick(blobs)
    batches = _batches(n_steps)

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])

    theirs = []
    for b in batches:
        _, loss = tq.forward(torch.tensor(b["data"]),
                             torch.tensor(b["label"], dtype=torch.long))
        tq.sgd_step(loss)
        theirs.append(float(loss))

    ours = np.asarray(ours)
    theirs = np.asarray(theirs)
    # identical math in different frameworks: tight at the start, f32
    # accumulation drift allowed to grow with steps
    np.testing.assert_allclose(ours[:10], theirs[:10], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours[:100], theirs[:100],
                               rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(ours, theirs, rtol=2e-2, atol=2e-3)
    # and the trained weights still agree at the end
    final = dict(_export_initial_weights(solver, tmp_path))  # iter_300 file
    for name in TorchQuick.LAYERS:
        np.testing.assert_allclose(
            np.asarray(final[name][0]), tq.p[name + ".w"].detach().numpy(),
            rtol=2e-2, atol=2e-3)


def test_multistep_lr_trajectory_tracks_torch(tmp_path):
    """lr_policy "multistep" crossing TWO boundaries: the per-iteration
    rate schedule of SGDSolver::GetLearningRate (sgd_solver.cpp:27-79,
    multistep branch: current_step_ advances when iter_ >= stepvalue)
    must agree with an independent transcription — rate factor at iter i
    is gamma^#{v : i >= v}."""
    n_steps = 75
    netp = load_net_prototxt(open(reference_path(REF_NET)).read())
    netp = replace_data_layers(netp, BATCH, BATCH, 3, 32, 32)
    sp = load_solver_prototxt_with_net(
        ("base_lr: 0.001\nmomentum: 0.9\nweight_decay: 0.004\n"
         'lr_policy: "multistep"\ngamma: 0.1\n'
         "stepvalue: 25\nstepvalue: 50\n"), netp)
    solver = Solver(sp, seed=0)
    blobs = _export_initial_weights(solver, tmp_path)
    tq = TorchQuick(blobs)
    batches = _batches(n_steps, seed=7)

    solver.set_train_data(iter(batches))
    ours, wdeltas = [], []
    prev_w = np.array(np.asarray(solver.params["conv1"][0]))
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
        cur_w = np.asarray(solver.params["conv1"][0])
        wdeltas.append(float(np.abs(cur_w - prev_w).mean()))
        prev_w = np.array(cur_w)
    theirs = []
    for i, b in enumerate(batches):
        _, loss = tq.forward(torch.tensor(b["data"]),
                             torch.tensor(b["label"], dtype=torch.long))
        rate = 0.001 * (0.1 ** sum(i >= v for v in (25, 50)))
        tq.sgd_step(loss, base_lr=rate)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours, theirs, rtol=5e-3, atol=5e-4)
    # the boundaries bite: weight motion scales with the rate (modulo the
    # 0.9^k decay of pre-boundary momentum history) — two drops of 10x
    # leave late-window motion far below the full-rate window
    assert np.mean(wdeltas[65:75]) < 0.3 * np.mean(wdeltas[15:25])


# -- BN-bearing net (cifar10_full_sigmoid_bn shape) --------------------------

BN_NET = ("caffe/examples/cifar10/"
          "cifar10_full_sigmoid_train_test_bn.prototxt")


class TorchSigmoidBN:
    """cifar10_full_sigmoid_bn transcribed from the prototxt and
    batch_norm_layer.cpp, NOT from this repo's graph code:
    conv(no bias)→maxpool→BN→sigmoid / conv→BN→sigmoid→avepool /
    conv→BN→sigmoid→avepool / ip1.  Caffe BatchNorm: train-mode
    normalization by BATCH stats (biased variance), running blobs kept as
    λ-decayed sums with a scale factor (blobs_[2]), variance stored with
    the m/(m-1) unbiased correction; eval divides blobs by the scale
    factor (batch_norm_layer.cpp:Forward_cpu)."""

    CONVS = ["conv1", "conv2", "conv3"]
    BNS = ["bn1", "bn2", "bn3"]
    EPS, LAM = 1e-5, 0.999

    def __init__(self, caffemodel_blobs):
        self.p, self.hist, self.bn = {}, {}, {}
        for name in self.CONVS:
            (w,) = caffemodel_blobs[name]  # bias_term: false
            self.p[name + ".w"] = torch.tensor(np.asarray(w),
                                               requires_grad=True)
        w, b = caffemodel_blobs["ip1"]
        self.p["ip1.w"] = torch.tensor(np.asarray(w), requires_grad=True)
        self.p["ip1.b"] = torch.tensor(np.asarray(b), requires_grad=True)
        for k, v in self.p.items():
            self.hist[k] = torch.zeros_like(v)
        for name in self.BNS:
            mean, var, scale = caffemodel_blobs[name]
            self.bn[name] = [torch.tensor(np.asarray(mean)),
                             torch.tensor(np.asarray(var)),
                             torch.tensor(np.asarray(scale))]

    def _bn(self, x, name, training):
        mean_b, var_b, scale_b = self.bn[name]
        view = (1, -1, 1, 1)
        if not training:
            factor = 0.0 if float(scale_b[0]) == 0 else 1.0 / float(scale_b[0])
            mean = mean_b * factor
            var = var_b * factor
            return (x - mean.view(view)) / torch.sqrt(var.view(view)
                                                      + self.EPS)
        mean = x.mean(dim=(0, 2, 3))
        xc = x - mean.view(view)
        var = (xc * xc).mean(dim=(0, 2, 3))
        with torch.no_grad():
            m = x.numel() // x.shape[1]
            corr = m / max(m - 1, 1)
            self.bn[name][0] = self.LAM * mean_b + mean.detach()
            self.bn[name][1] = self.LAM * var_b + corr * var.detach()
            self.bn[name][2] = self.LAM * scale_b + 1.0
        return xc / torch.sqrt(var.view(view) + self.EPS)

    def forward(self, x, y, training=True):
        p = self.p
        h = F.conv2d(x, p["conv1.w"], padding=2)
        h = F.max_pool2d(h, 3, 2, ceil_mode=True)
        h = torch.sigmoid(self._bn(h, "bn1", training))
        h = F.conv2d(h, p["conv2.w"], padding=2)
        h = torch.sigmoid(self._bn(h, "bn2", training))
        h = F.avg_pool2d(h, 3, 2, ceil_mode=True, count_include_pad=False)
        h = F.conv2d(h, p["conv3.w"], padding=2)
        h = torch.sigmoid(self._bn(h, "bn3", training))
        h = F.avg_pool2d(h, 3, 2, ceil_mode=True, count_include_pad=False)
        h = F.linear(h.reshape(h.shape[0], -1), p["ip1.w"], p["ip1.b"])
        return h, F.cross_entropy(h, y)

    def sgd_step(self, loss, base_lr=0.001, momentum=0.9, wd=0.004):
        # conv params: one ParamSpec {lr_mult: 1}, decay_mult defaults 1;
        # ip1: w (1, 1), b (1, 0); BN blobs lr_mult 0 -> never updated by
        # the solver (their only motion is the forward moving average)
        grads = torch.autograd.grad(loss, list(self.p.values()))
        with torch.no_grad():
            for (k, v), g in zip(self.p.items(), grads):
                decay_mult = 0.0 if k == "ip1.b" else 1.0
                g = g + wd * decay_mult * v
                self.hist[k] = base_lr * g + momentum * self.hist[k]
                v -= self.hist[k]


def test_bn_trajectory_and_running_stats_track_torch(tmp_path):
    """BN-bearing net over the full solver loop: per-step train losses
    track, the λ-decayed running-stat blobs agree after training, and a
    TEST-phase (use_global_stats) forward produces the same logits —
    pinning caffe's BN update semantics end to end
    (batch_norm_layer.cpp + sgd_solver.cpp)."""
    n_steps = 60
    netp = load_net_prototxt(open(reference_path(BN_NET)).read())
    netp = replace_data_layers(netp, BATCH, BATCH, 3, 32, 32)
    sp = load_solver_prototxt_with_net(SOLVER_TXT, netp)
    solver = Solver(sp, seed=0)
    blobs = _export_initial_weights(solver, tmp_path)
    tbn = TorchSigmoidBN(blobs)
    batches = _batches(n_steps, seed=9)

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for b in batches:
        _, loss = tbn.forward(torch.tensor(b["data"]),
                              torch.tensor(b["label"], dtype=torch.long))
        tbn.sgd_step(loss)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours[:10], theirs[:10], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours, theirs, rtol=1e-2, atol=1e-3)

    # running-stat blobs: λ-decayed sums + scale factor agree
    final = dict(_export_initial_weights(solver, tmp_path))
    for name in TorchSigmoidBN.BNS:
        for i in range(3):
            np.testing.assert_allclose(
                np.asarray(final[name][i]),
                tbn.bn[name][i].numpy(), rtol=1e-3, atol=1e-4)

    # TEST-phase forward (use_global_stats) on a held-out batch: same
    # logits from the accumulated statistics
    hb = _batches(1, seed=11)[0]
    out = solver.test_net.apply_all(
        solver.params, {"data": hb["data"], "label": hb["label"]},
        train=False)
    logits, _ = tbn.forward(torch.tensor(hb["data"]),
                            torch.tensor(hb["label"], dtype=torch.long),
                            training=False)
    np.testing.assert_allclose(np.asarray(out["ip1"]),
                               logits.detach().numpy(),
                               rtol=1e-3, atol=1e-4)


def test_bf16_trajectory_tracks_f32_torch(tmp_path):
    """The bf16 mixed-precision path follows the same trajectory at bf16
    resolution — parity of the reduced-precision config against the
    independent f32 reference."""
    n_steps = 60
    solver = _make_solver(compute_dtype="bf16")
    blobs = _export_initial_weights(solver, tmp_path)
    tq = TorchQuick(blobs)
    batches = _batches(n_steps, seed=4)

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for b in batches:
        _, loss = tq.forward(torch.tensor(b["data"]),
                             torch.tensor(b["label"], dtype=torch.long))
        tq.sgd_step(loss)
        theirs.append(float(loss))
    ours = np.asarray(ours)
    theirs = np.asarray(theirs)
    # bf16 has ~3 decimal digits; curves must track loosely and end in
    # the same regime
    assert float(np.max(np.abs(ours - theirs))) < 0.15
    assert abs(ours[-5:].mean() - theirs[-5:].mean()) < 0.05


# -- AlexNet-class layer mix: LRN + grouped conv ------------------------------

MIX_NET = """
name: "alexmix"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 16 dim: 3 dim: 16 dim: 16 } } }
layer { name: "label" type: "Input" top: "label"
  input_param { shape { dim: 16 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 16 kernel_size: 5 pad: 2
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.1 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
  lrn_param { local_size: 5 alpha: 0.1 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 32 kernel_size: 3 pad: 1 group: 2
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "relu2" type: "ReLU" bottom: "conv2" top: "conv2" }
layer { name: "pool2" type: "Pooling" bottom: "conv2" top: "pool2"
  pooling_param { pool: AVE kernel_size: 3 stride: 2 } }
layer { name: "ip" type: "InnerProduct" bottom: "pool2" top: "ip"
  param { lr_mult: 1 } param { lr_mult: 2 }
  inner_product_param { num_output: 10
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
"""


class TorchAlexMix:
    """The CaffeNet layer-mix slice — LRN (lrn_layer.cpp cross-channel
    formula, which torch's local_response_norm shares) + GROUPED conv
    (conv_layer.cpp group>1) + ceil-mode max/ave pooling — transcribed
    into torch independently of this repo's graph code."""

    LAYERS = ["conv1", "conv2", "ip"]
    LR_MULTS = {n: (1.0, 2.0) for n in LAYERS}

    def __init__(self, blobs):
        self.p, self.hist = {}, {}
        for name in self.LAYERS:
            w, b = blobs[name]
            self.p[name + ".w"] = torch.tensor(np.asarray(w),
                                               requires_grad=True)
            self.p[name + ".b"] = torch.tensor(np.asarray(b),
                                               requires_grad=True)
        for k, v in self.p.items():
            self.hist[k] = torch.zeros_like(v)

    def forward(self, x, y):
        p = self.p
        h = F.relu(F.conv2d(x, p["conv1.w"], p["conv1.b"], padding=2))
        h = F.local_response_norm(h, 5, alpha=0.1, beta=0.75, k=1.0)
        h = F.max_pool2d(h, 3, 2, ceil_mode=True)
        h = F.relu(F.conv2d(h, p["conv2.w"], p["conv2.b"], padding=1,
                            groups=2))
        h = F.avg_pool2d(h, 3, 2, ceil_mode=True, count_include_pad=False)
        h = F.linear(h.reshape(h.shape[0], -1), p["ip.w"], p["ip.b"])
        return h, F.cross_entropy(h, y)

    def sgd_step(self, loss, base_lr=0.001, momentum=0.9, wd=0.004):
        grads = torch.autograd.grad(loss, list(self.p.values()))
        with torch.no_grad():
            for (k, v), g in zip(self.p.items(), grads):
                layer, kind = k.split(".")
                lmw, lmb = self.LR_MULTS[layer]
                local_lr = base_lr * (lmw if kind == "w" else lmb)
                g = g + wd * v  # decay_mult defaults 1 on w and b
                self.hist[k] = local_lr * g + momentum * self.hist[k]
                v -= self.hist[k]


def test_alexnet_mix_trajectory_tracks_torch(tmp_path):
    """LRN + grouped-conv layer mix over the full solver loop: the last
    CaffeNet-family gradient paths not yet pinned end-to-end (LRN VJP,
    group>1 conv backward, lr_mult 2 biases) track an independent torch
    transcription step for step."""
    n_steps = 60
    netp = load_net_prototxt(MIX_NET)
    sp = load_solver_prototxt_with_net(SOLVER_TXT, netp)
    solver = Solver(sp, seed=0)
    blobs = _export_initial_weights(solver, tmp_path)
    tam = TorchAlexMix(blobs)
    rng = np.random.default_rng(13)
    batches = [{
        "data": rng.normal(size=(16, 3, 16, 16)).astype(np.float32),
        "label": rng.integers(0, 10, size=(16,)).astype(np.float32),
    } for _ in range(n_steps)]

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for b in batches:
        _, loss = tam.forward(torch.tensor(b["data"]),
                              torch.tensor(b["label"], dtype=torch.long))
        tam.sgd_step(loss)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours[:10], theirs[:10], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours, theirs, rtol=1e-2, atol=1e-3)
    # grouped-conv weights agree at the end (the group split is the
    # likeliest silent-divergence point)
    final = dict(_export_initial_weights(solver, tmp_path))
    np.testing.assert_allclose(
        np.asarray(final["conv2"][0]), tam.p["conv2.w"].detach().numpy(),
        rtol=1e-2, atol=1e-3)


# -- the non-SGD update rules, transcribed from the reference solvers --------

def _caffe_rule_step(rule, p, hist, grads, lr_mults, base_lr, it,
                     momentum=0.9, wd=0.004):
    """One update under `rule`, transcribing the reference solver .cpp
    files verbatim (adam/adadelta/adagrad/nesterov/rmsprop_solver.cpp)
    after Regularize (g += wd*decay_mult*w, sgd_solver.cpp:Regularize)."""
    with torch.no_grad():
        for (k, v), g in zip(p.items(), grads):
            layer, kind = k.split(".")
            lmw, lmb = lr_mults[layer]
            local_lr = base_lr * (lmw if kind == "w" else lmb)
            g = g + wd * v
            if rule == "Nesterov":
                h_old = hist[k].clone()
                hist[k] = momentum * hist[k] + local_lr * g
                v -= (1 + momentum) * hist[k] - momentum * h_old
            elif rule == "AdaGrad":
                hist[k] = hist[k] + g * g
                v -= local_lr * g / (torch.sqrt(hist[k]) + 1e-8)
            elif rule == "RMSProp":
                hist[k] = 0.98 * hist[k] + 0.02 * g * g
                v -= local_lr * g / (torch.sqrt(hist[k]) + 1e-8)
            elif rule == "Adam":
                b1, b2, eps = 0.9, 0.999, 1e-8
                m, vv = hist[k]
                m = b1 * m + (1 - b1) * g
                vv = b2 * vv + (1 - b2) * g * g
                hist[k] = (m, vv)
                t = it + 1
                corr = (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
                v -= local_lr * corr * m / (torch.sqrt(vv) + eps)
            elif rule == "AdaDelta":
                delta = 1e-6
                h1, h2 = hist[k]
                h1 = momentum * h1 + (1 - momentum) * g * g  # grad² hist
                upd = g * torch.sqrt((h2 + delta) / (h1 + delta))
                h2 = momentum * h2 + (1 - momentum) * upd * upd
                hist[k] = (h1, h2)
                v -= local_lr * upd
            else:
                raise ValueError(rule)


RULE_SOLVERS = {
    "Nesterov": ('type: "Nesterov"\nbase_lr: 0.001\nmomentum: 0.9\n'
                 'weight_decay: 0.004\nlr_policy: "fixed"\n'),
    "AdaGrad": ('type: "AdaGrad"\nbase_lr: 0.01\ndelta: 1e-8\n'
                'weight_decay: 0.004\nlr_policy: "fixed"\n'),
    "RMSProp": ('type: "RMSProp"\nbase_lr: 0.001\nrms_decay: 0.98\n'
                'delta: 1e-8\nweight_decay: 0.004\nlr_policy: "fixed"\n'),
    "Adam": ('type: "Adam"\nbase_lr: 0.001\nmomentum: 0.9\n'
             'momentum2: 0.999\ndelta: 1e-8\nweight_decay: 0.004\n'
             'lr_policy: "fixed"\n'),
    "AdaDelta": ('type: "AdaDelta"\nbase_lr: 1.0\nmomentum: 0.95\n'
                 'delta: 1e-6\nweight_decay: 0.004\nlr_policy: "fixed"\n'),
}


@pytest.mark.parametrize("rule", sorted(RULE_SOLVERS))
def test_rule_trajectory_tracks_torch(rule, tmp_path):
    """Every non-SGD update rule over the full solver loop on
    cifar10_quick: gradients from torch autograd + the reference solver's
    transcribed update must reproduce this framework's losses step for
    step (adam/adadelta/adagrad/nesterov/rmsprop_solver.cpp)."""
    n_steps = 30
    netp = load_net_prototxt(open(reference_path(REF_NET)).read())
    netp = replace_data_layers(netp, BATCH, BATCH, 3, 32, 32)
    sp = load_solver_prototxt_with_net(RULE_SOLVERS[rule], netp)
    solver = Solver(sp, seed=0)
    blobs = _export_initial_weights(solver, tmp_path)
    tq = TorchQuick(blobs)
    momentum = 0.95 if rule == "AdaDelta" else 0.9
    base_lr = {"AdaGrad": 0.01, "AdaDelta": 1.0}.get(rule, 0.001)
    hist = {}
    for k, v in tq.p.items():
        if rule in ("Adam", "AdaDelta"):
            hist[k] = (torch.zeros_like(v), torch.zeros_like(v))
        else:
            hist[k] = torch.zeros_like(v)
    batches = _batches(n_steps, seed=17)

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for it, b in enumerate(batches):
        _, loss = tq.forward(torch.tensor(b["data"]),
                             torch.tensor(b["label"], dtype=torch.long))
        grads = torch.autograd.grad(loss, list(tq.p.values()))
        _caffe_rule_step(rule, tq.p, hist, grads, TorchQuick.LR_MULTS,
                         base_lr, it, momentum=momentum)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours[:5], theirs[:5], rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(ours, theirs, rtol=2e-2, atol=2e-3)


# -- inception-style branching net with an auxiliary loss head ---------------

INCEPTION_NET = """
name: "miniception"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 8 dim: 3 dim: 16 dim: 16 } } }
layer { name: "label" type: "Input" top: "label"
  input_param { shape { dim: 8 } } }
layer { name: "stem" type: "Convolution" bottom: "data" top: "stem"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 16 kernel_size: 3 pad: 1
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.1 } } }
layer { name: "stem/relu" type: "ReLU" bottom: "stem" top: "stem" }
layer { name: "pool_stem" type: "Pooling" bottom: "stem" top: "pool_stem"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "b1x1" type: "Convolution" bottom: "pool_stem" top: "b1x1"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 8 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "b1x1/relu" type: "ReLU" bottom: "b1x1" top: "b1x1" }
layer { name: "b3x3_reduce" type: "Convolution" bottom: "pool_stem"
  top: "b3x3_reduce" param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 8 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "b3x3_reduce/relu" type: "ReLU" bottom: "b3x3_reduce"
  top: "b3x3_reduce" }
layer { name: "b3x3" type: "Convolution" bottom: "b3x3_reduce" top: "b3x3"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 12 kernel_size: 3 pad: 1
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "b3x3/relu" type: "ReLU" bottom: "b3x3" top: "b3x3" }
layer { name: "bpool" type: "Pooling" bottom: "pool_stem" top: "bpool"
  pooling_param { pool: MAX kernel_size: 3 stride: 1 pad: 1 } }
layer { name: "pool_proj" type: "Convolution" bottom: "bpool" top: "pool_proj"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 8 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "pool_proj/relu" type: "ReLU" bottom: "pool_proj"
  top: "pool_proj" }
layer { name: "concat" type: "Concat" bottom: "b1x1" bottom: "b3x3"
  bottom: "pool_proj" top: "concat" }
layer { name: "gpool" type: "Pooling" bottom: "concat" top: "gpool"
  pooling_param { pool: AVE global_pooling: true } }
layer { name: "ip_main" type: "InnerProduct" bottom: "gpool" top: "ip_main"
  param { lr_mult: 1 } param { lr_mult: 2 }
  inner_product_param { num_output: 10
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "loss_main" type: "SoftmaxWithLoss" bottom: "ip_main"
  bottom: "label" top: "loss_main" loss_weight: 1.0 }
layer { name: "ip_aux" type: "InnerProduct" bottom: "concat" top: "ip_aux"
  param { lr_mult: 1 } param { lr_mult: 2 }
  inner_product_param { num_output: 10
    weight_filler { type: "gaussian" std: 0.05 }
    bias_filler { type: "constant" value: 0.0 } } }
layer { name: "loss_aux" type: "SoftmaxWithLoss" bottom: "ip_aux"
  bottom: "label" top: "loss_aux" loss_weight: 0.3 }
"""


class TorchMiniception:
    """GoogLeNet's training-graph mechanics in miniature, transcribed
    independently of this repo's graph code: concat fan-out (pool_stem
    feeds THREE branches and concat feeds TWO heads — the InsertSplits
    gradient-accumulation paths), ceil-mode pooling, global AVE pooling,
    and two SoftmaxWithLoss heads combined per Caffe's loss_weight
    semantics (net.cpp: total objective = sum loss_weight_i * loss_i)."""

    LAYERS = ["stem", "b1x1", "b3x3_reduce", "b3x3", "pool_proj",
              "ip_main", "ip_aux"]
    LR_MULTS = {n: (1.0, 2.0) for n in LAYERS}

    def __init__(self, blobs):
        self.p, self.hist = {}, {}
        for name in self.LAYERS:
            w, b = blobs[name]
            self.p[name + ".w"] = torch.tensor(np.asarray(w),
                                               requires_grad=True)
            self.p[name + ".b"] = torch.tensor(np.asarray(b),
                                               requires_grad=True)
        for k, v in self.p.items():
            self.hist[k] = torch.zeros_like(v)

    def forward(self, x, y):
        p = self.p
        h = F.relu(F.conv2d(x, p["stem.w"], p["stem.b"], padding=1))
        h = F.max_pool2d(h, 3, 2, ceil_mode=True)
        b1 = F.relu(F.conv2d(h, p["b1x1.w"], p["b1x1.b"]))
        b3 = F.relu(F.conv2d(h, p["b3x3_reduce.w"], p["b3x3_reduce.b"]))
        b3 = F.relu(F.conv2d(b3, p["b3x3.w"], p["b3x3.b"], padding=1))
        bp = F.max_pool2d(h, 3, 1, padding=1)
        bp = F.relu(F.conv2d(bp, p["pool_proj.w"], p["pool_proj.b"]))
        cat = torch.cat([b1, b3, bp], dim=1)
        g = cat.mean(dim=(2, 3))
        main = F.linear(g, p["ip_main.w"], p["ip_main.b"])
        aux = F.linear(cat.reshape(cat.shape[0], -1),
                       p["ip_aux.w"], p["ip_aux.b"])
        loss = (F.cross_entropy(main, y)
                + 0.3 * F.cross_entropy(aux, y))
        return main, loss

    def sgd_step(self, loss, base_lr=0.001, momentum=0.9, wd=0.004):
        grads = torch.autograd.grad(loss, list(self.p.values()))
        with torch.no_grad():
            for (k, v), g in zip(self.p.items(), grads):
                layer, kind = k.split(".")
                lmw, lmb = self.LR_MULTS[layer]
                local_lr = base_lr * (lmw if kind == "w" else lmb)
                g = g + wd * v
                self.hist[k] = local_lr * g + momentum * self.hist[k]
                v -= self.hist[k]


def test_inception_aux_loss_trajectory_tracks_torch(tmp_path):
    """The GoogLeNet mechanics not pinned by any other trajectory test:
    branch fan-out gradient accumulation (one blob feeding several
    consumers), Concat backward slicing, global AVE pooling, and
    multi-head loss_weight combination — per-step total losses and final
    stem weights track an independent torch transcription."""
    n_steps = 60
    netp = load_net_prototxt(INCEPTION_NET)
    sp = load_solver_prototxt_with_net(SOLVER_TXT, netp)
    solver = Solver(sp, seed=0)
    blobs = _export_initial_weights(solver, tmp_path)
    tm = TorchMiniception(blobs)
    rng = np.random.default_rng(23)
    batches = [{
        "data": rng.normal(size=(8, 3, 16, 16)).astype(np.float32),
        "label": rng.integers(0, 10, size=(8,)).astype(np.float32),
    } for _ in range(n_steps)]

    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for b in batches:
        _, loss = tm.forward(torch.tensor(b["data"]),
                             torch.tensor(b["label"], dtype=torch.long))
        tm.sgd_step(loss)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours[:10], theirs[:10], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours, theirs, rtol=1e-2, atol=1e-3)
    # the stem sits behind BOTH heads and all three branches — its final
    # weights agreeing pins the whole fan-out/fan-in gradient flow
    final = dict(_export_initial_weights(solver, tmp_path))
    np.testing.assert_allclose(
        np.asarray(final["stem"][0]), tm.p["stem.w"].detach().numpy(),
        rtol=1e-2, atol=1e-3)


# -- siamese: shared weights + ContrastiveLoss end-to-end --------------------

SIAMESE_NET = """
name: "mini_siamese"
input: "pair_data"
input_shape { dim: 16 dim: 2 dim: 12 dim: 12 }
input: "sim"
input_shape { dim: 16 }
layer { name: "slice_pair" type: "Slice" bottom: "pair_data"
  top: "data" top: "data_p" slice_param { slice_dim: 1 slice_point: 1 } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  param { name: "conv1_w" lr_mult: 1 } param { name: "conv1_b" lr_mult: 2 }
  convolution_param { num_output: 8 kernel_size: 3 stride: 1
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  param { name: "ip1_w" lr_mult: 1 } param { name: "ip1_b" lr_mult: 2 }
  inner_product_param { num_output: 16 weight_filler { type: "xavier" }
    bias_filler { type: "constant" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "feat" type: "InnerProduct" bottom: "ip1" top: "feat"
  param { name: "feat_w" lr_mult: 1 } param { name: "feat_b" lr_mult: 2 }
  inner_product_param { num_output: 2 weight_filler { type: "xavier" }
    bias_filler { type: "constant" } } }
layer { name: "conv1_p" type: "Convolution" bottom: "data_p" top: "conv1_p"
  param { name: "conv1_w" lr_mult: 1 } param { name: "conv1_b" lr_mult: 2 }
  convolution_param { num_output: 8 kernel_size: 3 stride: 1
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "pool1_p" type: "Pooling" bottom: "conv1_p" top: "pool1_p"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip1_p" type: "InnerProduct" bottom: "pool1_p" top: "ip1_p"
  param { name: "ip1_w" lr_mult: 1 } param { name: "ip1_b" lr_mult: 2 }
  inner_product_param { num_output: 16 weight_filler { type: "xavier" }
    bias_filler { type: "constant" } } }
layer { name: "relu1_p" type: "ReLU" bottom: "ip1_p" top: "ip1_p" }
layer { name: "feat_p" type: "InnerProduct" bottom: "ip1_p" top: "feat_p"
  param { name: "feat_w" lr_mult: 1 } param { name: "feat_b" lr_mult: 2 }
  inner_product_param { num_output: 2 weight_filler { type: "xavier" }
    bias_filler { type: "constant" } } }
layer { name: "loss" type: "ContrastiveLoss"
  bottom: "feat" bottom: "feat_p" bottom: "sim" top: "loss"
  contrastive_loss_param { margin: 1.0 } }
"""


class TorchSiamese:
    """mnist_siamese transcribed from the reference prototxt
    (examples/siamese/mnist_siamese_train_test.prototxt, shrunk): ONE
    set of weights applied to both slices of the pair — torch autograd
    then sums the two branches' gradients into the shared tensors, which
    is exactly Caffe's AppendParam owner-accumulation (net.cpp) that the
    solver-side trajectory must reproduce."""

    LAYERS = ["conv1", "ip1", "feat"]
    LR_MULTS = {n: (1.0, 2.0) for n in LAYERS}

    def __init__(self, caffemodel_blobs):
        self.p = {}
        self.hist = {}
        for name in self.LAYERS:
            # sharer layers (conv1_p, ...) carry the same blobs; owners
            # are enough
            w, b = caffemodel_blobs[name]
            self.p[name + ".w"] = torch.tensor(np.asarray(w),
                                               requires_grad=True)
            self.p[name + ".b"] = torch.tensor(np.asarray(b),
                                               requires_grad=True)
        for k, v in self.p.items():
            self.hist[k] = torch.zeros_like(v)

    def branch(self, x):
        p = self.p
        h = F.conv2d(x, p["conv1.w"], p["conv1.b"])
        h = F.max_pool2d(h, 2, 2, ceil_mode=True)
        h = F.relu(F.linear(h.reshape(h.shape[0], -1),
                            p["ip1.w"], p["ip1.b"]))
        return F.linear(h, p["feat.w"], p["feat.b"])

    def forward(self, pair, sim):
        a = self.branch(pair[:, :1])
        b = self.branch(pair[:, 1:])
        # contrastive_loss_layer.cpp (non-legacy): y*d^2 +
        # (1-y)*max(margin - d, 0)^2 over 2N; the +1e-12 inside the
        # sqrt mirrors ops/loss.py's guard so gradients match exactly
        d2 = ((a - b) ** 2).sum(dim=1)
        dist = torch.clamp(1.0 - torch.sqrt(d2 + 1e-12), min=0.0)
        loss = (sim * d2 + (1.0 - sim) * dist * dist).sum() / (2.0 * a.shape[0])
        return loss

    def sgd_step(self, loss, base_lr=0.01, momentum=0.9, wd=0.0005):
        grads = torch.autograd.grad(loss, list(self.p.values()))
        with torch.no_grad():
            for (k, v), g in zip(self.p.items(), grads):
                layer, kind = k.split(".")
                lmw, lmb = self.LR_MULTS[layer]
                local_lr = base_lr * (lmw if kind == "w" else lmb)
                g = g + wd * v
                self.hist[k] = local_lr * g + momentum * self.hist[k]
                v -= self.hist[k]


def test_siamese_shared_weight_trajectory_tracks_torch(tmp_path):
    """End-to-end siamese training pin (examples/siamese/): the solver's
    gradient ACCUMULATION through shared blobs — both branches' grads
    summed into the owner before Regularize/momentum, Caffe's
    AppendParam semantics — tracked against torch for 60 steps, weights
    compared at the end."""
    netp = load_net_prototxt(SIAMESE_NET)
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.01\nmomentum: 0.9\nweight_decay: 0.0005\n'
        'lr_policy: "fixed"\n', netp)
    solver = Solver(sp, seed=0)
    tm = TorchSiamese(_export_initial_weights(solver, tmp_path))

    n_steps, B = 60, 16
    rng = np.random.default_rng(9)
    batches = []
    for _ in range(n_steps):
        batches.append({
            "pair_data": rng.normal(
                size=(B, 2, 12, 12)).astype(np.float32),
            "sim": rng.integers(0, 2, size=(B,)).astype(np.float32),
        })
    solver.set_train_data(iter(batches))
    ours = []
    for _ in range(n_steps):
        solver.step(1)
        ours.append(solver._smoothed[-1])
    theirs = []
    for b in batches:
        loss = tm.forward(torch.tensor(b["pair_data"]),
                          torch.tensor(b["sim"]))
        tm.sgd_step(loss)
        theirs.append(float(loss))
    np.testing.assert_allclose(ours[:10], theirs[:10], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours, theirs, rtol=1e-2, atol=1e-3)
    # final shared weights agree -> the two-branch accumulation into the
    # owner matched step for step (the subtlest AppendParam behavior)
    final = dict(_export_initial_weights(solver, tmp_path))
    for name in TorchSiamese.LAYERS:
        np.testing.assert_allclose(
            np.asarray(final[name][0]), tm.p[name + ".w"].detach().numpy(),
            rtol=1e-2, atol=1e-4, err_msg=name)
    # and the sharer layers serialized the same (shared) blobs
    np.testing.assert_array_equal(np.asarray(final["conv1"][0]),
                                  np.asarray(final["conv1_p"][0]))
