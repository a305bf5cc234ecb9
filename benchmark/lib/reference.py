"""The plain reference: a float32 interpreter over a Caffe layer graph.

It executes the test phase of a ``NetParameter`` layer by layer in
straightforward ``jax.numpy``/``lax`` at the highest matrix precision, with
no fusion, no kernels, no reduced precision and no code from
``sparknet_tpu/ops``: the layer equations are Caffe's own
(``caffe/src/caffe/layers/*.cpp``), written out here.  The benchmark
compares the system's test-phase forward with it on seeded weights and a
seeded batch, outside the measured window, and that comparison decides
``correct``.

Covered: what the test phase of the zoo nets holds.  Convolution, ReLU,
LRN (across channels), Pooling (MAX and AVE, Caffe's ceil-mode sizes),
InnerProduct, Concat, Dropout (identity at test time), Softmax,
SoftmaxWithLoss.  Accuracy layers are skipped: with random weights the
largest logit changes on rounding, so logits and loss are what is compared.
Any other layer type is an error.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .flops import conv_geometry, pool_geometry

_HI = lax.Precision.HIGHEST


def _conv(lp, blobs, x):
    g = conv_geometry(lp)
    y = lax.conv_general_dilated(
        x, blobs[0], window_strides=(g["sh"], g["sw"]),
        padding=((g["ph"], g["ph"]), (g["pw"], g["pw"])),
        rhs_dilation=(g["dh"], g["dw"]),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=g["group"], precision=_HI)
    if g["bias"]:
        y = y + blobs[1].reshape(1, -1, 1, 1)
    return y


def _pool(lp, x):
    _, _, h, w = x.shape
    g = pool_geometry(lp, h, w)
    # the last window may hang over the padded edge (ceil mode)
    hi_h = max((g["oh"] - 1) * g["sh"] + g["kh"] - h - g["ph"], 0)
    hi_w = max((g["ow"] - 1) * g["sw"] + g["kw"] - w - g["pw"], 0)
    pad = ((0, 0), (0, 0), (g["ph"], hi_h), (g["pw"], hi_w))
    dims, strides = (1, 1, g["kh"], g["kw"]), (1, 1, g["sh"], g["sw"])
    if g["pool"] == "MAX":
        y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
    elif g["pool"] == "AVE":
        s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)

        def sizes(n, k, stride, p, o):
            # Caffe's divisor: the window clipped to the padded image
            start = np.arange(o) * stride - p
            return np.minimum(start + k, n + p) - start

        div = np.outer(sizes(h, g["kh"], g["sh"], g["ph"], g["oh"]),
                       sizes(w, g["kw"], g["sw"], g["pw"], g["ow"]))
        y = s / jnp.asarray(div, jnp.float32)
    else:
        raise ValueError(f"layer {lp.name!r}: pool {g['pool']!r}")
    return y[:, :, :g["oh"], :g["ow"]]


def _lrn(lp, x):
    p = lp.sub("lrn_param")
    if str(p.get("norm_region", "ACROSS_CHANNELS")) != "ACROSS_CHANNELS":
        raise ValueError(f"layer {lp.name!r}: only ACROSS_CHANNELS")
    size = int(p.get("local_size", 5))
    alpha, beta = float(p.get("alpha", 1.0)), float(p.get("beta", 0.75))
    k = float(p.get("k", 1.0))
    half = (size - 1) // 2
    sq = jnp.pad(x * x, ((0, 0), (half, size - 1 - half), (0, 0), (0, 0)))
    c = x.shape[1]
    window = sum(sq[:, i:i + c] for i in range(size))
    return x * (k + (alpha / size) * window) ** -beta


def _inner_product(lp, blobs, x):
    p = lp.sub("inner_product_param")
    axis = int(p.get("axis", 1))
    flat = x.reshape(x.shape[:axis] + (-1,))
    w = blobs[0] if p.get("transpose", False) else blobs[0].T
    y = jnp.matmul(flat, w, precision=_HI)
    if p.get("bias_term", True):
        y = y + blobs[1]
    return y


def _softmax_loss(lp, logits, label):
    logp = jax.nn.log_softmax(logits.reshape(logits.shape[0], -1), axis=1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=1)
    return -jnp.mean(picked)


def forward(net_param, params, inputs) -> dict:
    """Run the (already phase-filtered) ``net_param`` on ``inputs`` with
    the weights ``params`` (``{layer name: [weight, bias]}``); every
    array is taken as float32.  Returns every blob by name."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    blobs = {k: f32(v) for k, v in inputs.items()}
    for lp in net_param.layer:
        t = lp.type
        if t in ("JavaData", "Input"):
            missing = [top for top in lp.top if top not in blobs]
            if missing:
                raise ValueError(f"input blobs {missing} were not given")
            continue
        if t == "Accuracy":
            continue
        x = [blobs[b] for b in lp.bottom]
        w = [f32(b) for b in params.get(lp.name, [])]
        if t == "Convolution":
            y = _conv(lp, w, x[0])
        elif t == "ReLU":
            slope = float(lp.sub("relu_param").get("negative_slope", 0.0))
            y = jnp.where(x[0] > 0, x[0], slope * x[0])
        elif t == "LRN":
            y = _lrn(lp, x[0])
        elif t == "Pooling":
            y = _pool(lp, x[0])
        elif t == "InnerProduct":
            y = _inner_product(lp, w, x[0])
        elif t == "Concat":
            y = jnp.concatenate(
                x, axis=int(lp.sub("concat_param").get("axis", 1)))
        elif t == "Dropout":
            y = x[0]
        elif t == "Softmax":
            y = jax.nn.softmax(x[0], axis=1)
        elif t == "SoftmaxWithLoss":
            y = _softmax_loss(lp, x[0], x[1])
        else:
            raise ValueError(
                f"layer {lp.name!r}: type {t!r} is not in the reference "
                f"interpreter (benchmark/lib/reference.py)")
        blobs[lp.top[0]] = y
    return blobs
