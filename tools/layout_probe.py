"""Measure the conv-layout lever: NCHW vs NHWC dimension numbers.

The per-op tables (RESULTS.md) show grad-weight convs at 50-88 TF/s and
VGG conv1_2 at 45.6 TF/s while forward convs reach 123-157 TF/s.  The one
conventional TPU lever not yet tried is layout: XLA's TPU conv codegen
sees the logical dimension order, and NHWC puts channels on the minor
(lane) dimension the way the MXU wants them.  This probe times the three
conv ops (forward, grad-input, grad-weight — the grads via
jax.linear_transpose, exactly the transpose convs AD emits in the train
step) for the headline models' slowest conv shapes under both layouts,
isolated, on the real chip.

Timing protocol: each measurement is ONE compiled lax.fori_loop of n
inner iterations with a loop-carried one-element perturbation (prevents
loop-invariant-code-motion from hoisting the conv), synced by a scalar
host fetch; per-op time is the slope between n=10 and n=50 runs, which
cancels the fixed dispatch+sync cost.

Usage: python tools/layout_probe.py [--dtype bf16]
Emits one JSON line per (shape, op, layout) plus per-shape ratios.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# (name, batch, c_in, h, w, c_out, k, stride, pad, group)
SHAPES = [
    # CaffeNet batch 256 (bf16 headline) — the 50-88 TF/s grad-weight rows
    ("caffenet_conv2", 256, 96, 27, 27, 256, 5, 1, 2, 2),
    ("caffenet_conv3", 256, 256, 13, 13, 384, 3, 1, 1, 1),
    # VGG-16 batch 64 — conv1_2 measured 45.6 TF/s
    ("vgg_conv1_2", 64, 64, 224, 224, 64, 3, 1, 1, 1),
    # GoogLeNet batch 128 — the one big MXU conv, 88.9 TF/s
    ("googlenet_conv2_3x3", 128, 64, 56, 56, 192, 3, 1, 1, 1),
]


def conv_flops(n, c_in, oh, ow, c_out, k, group):
    return 2 * n * oh * ow * c_out * (c_in // group) * k * k


def make_ops(layout, n, c_in, h, w, c_out, k, s, p, group, dtype):
    """-> {op: (fn(a_fixed, b_perturbed) -> out, a, b)} — b is the operand
    the bench loop perturbs one element of, so the loop body is never
    invariant; a is closed over as a jit argument."""
    if layout == "NCHW":
        dims = ("NCHW", "OIHW", "NCHW")
        x_shape = (n, c_in, h, w)
        w_shape = (c_out, c_in // group, k, k)
    else:
        dims = ("NHWC", "HWIO", "NHWC")
        x_shape = (n, h, w, c_in)
        w_shape = (k, k, c_in // group, c_out)

    def fwd(x, wt):
        return lax.conv_general_dilated(
            x, wt, window_strides=(s, s), padding=((p, p), (p, p)),
            feature_group_count=group, dimension_numbers=dims)

    key = jax.random.PRNGKey(0)
    kx, kw, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, x_shape, jnp.float32).astype(dtype)
    wt = (jax.random.normal(kw, w_shape, jnp.float32) * 0.05).astype(dtype)
    y_shape = jax.eval_shape(fwd, x, wt).shape
    dy = jax.random.normal(kd, y_shape, jnp.float32).astype(dtype)
    x_spec = jax.ShapeDtypeStruct(x_shape, dtype)
    w_spec = jax.ShapeDtypeStruct(w_shape, dtype)

    def dgrad(dy_, wt_):  # the AD transpose wrt the input
        return jax.linear_transpose(lambda xx: fwd(xx, wt_), x_spec)(dy_)[0]

    def wgrad(x_, dy_):   # the AD transpose wrt the weights
        return jax.linear_transpose(lambda ww: fwd(x_, ww), w_spec)(dy_)[0]

    return {
        "fwd": (fwd, x, wt),       # perturb wt (small)
        "dgrad": (dgrad, dy, wt),  # perturb wt
        "wgrad": (wgrad, x, dy),   # perturb dy
    }


def _sync(arr):
    """Fence by a host fetch; one element keeps transfer out of the
    measurement."""
    return float(np.asarray(jax.device_get(arr.ravel()[0])))


def make_loop(fn):
    @jax.jit
    def run(a, b, n):
        def body(_, b):
            out = fn(a, b)
            # full-output data dependence on the previous iteration: the
            # conv operand changes every iteration (LICM cannot hoist),
            # and consuming EVERY element via the mean stops XLA from
            # narrowing the conv to the one element a [0]-fetch would
            # need.  Numerically a no-op (mean*1e-30 underflows vs b[0]);
            # the reduce costs one read of out, identical across layouts.
            eps = (jnp.mean(out.astype(jnp.float32)) * 1e-30).astype(b.dtype)
            return b.at[(0,) * b.ndim].add(eps)
        return lax.fori_loop(0, n, body, b)
    return run


def time_op(fn, a, b, n_lo=10, n_hi=110):
    run = make_loop(fn)
    _sync(run(a, b, n_lo))  # compile both loop trip counts? n is dynamic
    _sync(run(a, b, n_lo))  # warm

    def once(n):
        t0 = time.perf_counter()
        _sync(run(a, b, n))
        return time.perf_counter() - t0

    t_lo, t_hi = once(n_lo), once(n_hi)
    t_lo, t_hi = min(t_lo, once(n_lo)), min(t_hi, once(n_hi))
    return (t_hi - t_lo) / (n_hi - n_lo)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--shapes", default=None,
                    help="comma-separated subset of shape names")
    args = ap.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    dev = jax.devices()[0]
    print(f"# device: {dev.platform}/{dev.device_kind}", flush=True)

    rows = []
    for (name, n, c_in, h, w, c_out, k, s, p, group) in SHAPES:
        if args.shapes and name not in args.shapes.split(","):
            continue
        oh = (h + 2 * p - k) // s + 1
        ow = (w + 2 * p - k) // s + 1
        flops = conv_flops(n, c_in, oh, ow, c_out, k, group)
        per_shape = {}
        for layout in ("NCHW", "NHWC"):
            for op, (fn, a, b) in make_ops(
                    layout, n, c_in, h, w, c_out, k, s, p, group,
                    dtype).items():
                dt = time_op(fn, a, b)
                tfs = flops / dt / 1e12
                per_shape[(layout, op)] = dt
                row = {"shape": name, "layout": layout, "op": op,
                       "ms": round(dt * 1e3, 4), "tflops_s": round(tfs, 1),
                       "dtype": args.dtype}
                rows.append(row)
                print(json.dumps(row), flush=True)
        for op in ("fwd", "dgrad", "wgrad"):
            a, b = per_shape[("NCHW", op)], per_shape[("NHWC", op)]
            print(f"# {name} {op}: NHWC/NCHW time ratio "
                  f"{b / a:.3f} ({'NHWC faster' if b < a else 'NCHW faster'})",
                  flush=True)
    tot = {}
    for layout in ("NCHW", "NHWC"):
        tot[layout] = round(
            sum(r["ms"] for r in rows if r["layout"] == layout), 3)
    print(json.dumps({"summary": "total_ms_all_ops", **tot}), flush=True)


if __name__ == "__main__":
    main()
