"""Vision layers: Convolution, Deconvolution, Pooling, LRN, Im2col, SPP.

Caffe-exact shape/padding semantics (reference:
caffe/src/caffe/layers/base_conv_layer.cpp shape setup,
caffe/src/caffe/layers/pooling_layer.cpp:90-110 ceil-mode output sizing,
caffe/src/caffe/layers/lrn_layer.cpp scale formula).  All of Caffe's
im2col + GEMM lowering (caffe/src/caffe/util/im2col.cpp/.cu,
math_functions) collapses into ``lax.conv_general_dilated``, which XLA tiles
onto the MXU directly.  Layout is logical NCHW to match prototxt semantics;
XLA's layout assignment picks the physical TPU layout.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..proto.caffe_pb import FillerParameter, LayerParameter
from ..utils import knobs, telemetry
from .fillers import fill
from .registry import LayerImpl, Shape, register_layer

DIMNUMS = ("NCHW", "OIHW", "NCHW")


def _pair(p, key: str, default: int, hkey: str | None = None, wkey: str | None = None):
    """Caffe's kernel/stride/pad convention: repeated `key` or `key_h`/`key_w`."""
    hkey = hkey or f"{key}_h"
    wkey = wkey or f"{key}_w"
    vals = [int(v) for v in p.get_all(key)]
    if p.has(hkey) or p.has(wkey):
        return int(p.get(hkey, default)), int(p.get(wkey, default))
    if len(vals) >= 2:
        return vals[0], vals[1]
    if len(vals) == 1:
        return vals[0], vals[0]
    return default, default


def conv_geometry(lp: LayerParameter):
    p = lp.sub("convolution_param")
    kh, kw = _pair(p, "kernel_size", 0, "kernel_h", "kernel_w")
    sh, sw = _pair(p, "stride", 1)
    ph, pw = _pair(p, "pad", 0)
    dh, dw = _pair(p, "dilation", 1)
    num_output = int(p.get("num_output", 0))
    group = int(p.get("group", 1))
    bias_term = bool(p.get("bias_term", True))
    if kh <= 0 or kw <= 0:
        raise ValueError(
            f"layer {lp.name!r}: kernel_size (or kernel_h/kernel_w) required")
    if num_output <= 0:
        raise ValueError(f"layer {lp.name!r}: num_output required")
    return kh, kw, sh, sw, ph, pw, dh, dw, num_output, group, bias_term


def _s2d_eligible(c_in: int, kh, kw, sh, sw, ph, pw, dh, dw, group) -> bool:
    """Space-to-depth rewrite pays off when the input-channel count starves
    the MXU's 128-wide contraction (RGB stems: C=3 → C·s² after regroup).

    SPARKNET_NO_S2D=1 disables it — read at TRACE time: set it before the
    net/Solver is built (jit caches the traced graph; flipping the env
    after compilation has no effect on cached executables)."""
    if knobs.raw("SPARKNET_NO_S2D") == "1":
        return False
    return (group == 1 and dh == 1 and dw == 1 and c_in * sh * sw <= 64
            and (sh > 1 or sw > 1) and kh >= sh and kw >= sw)


def _space_to_depth_conv(x, weight, kh, kw, sh, sw, ph, pw):
    """Stride-s conv as a stride-1 conv on stride-phase-regrouped input.

    Exact rewrite (the MLPerf-era TPU stem trick): zero-pad the kernel up to
    a stride multiple k' = ceil(k/s)·s, pad/clip the input so its extent is
    exactly (O-1)·s + k', then fold the s×s stride phases of both operands
    into channels and convolve with stride 1.  Zero kernel columns multiply
    only padding, so outputs are identical up to float summation order; the
    contraction dim grows C → C·s·s (3 → 48 for an 11×11/4 RGB stem),
    filling MXU lanes that a 3-deep contraction leaves 97% idle.
    """
    n, c, h, w = x.shape
    o = weight.shape[0]
    kph = -kh % sh  # kernel zero-pad up to the next stride multiple
    kpw = -kw % sw
    keh, kew = kh + kph, kw + kpw
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    # input extent consumed by the padded windows ((O-1)·s + k'); the edge
    # delta vs h+ph can be positive (zero-pad) or negative (clip unused rows)
    hi_h = (oh - 1) * sh + keh - h - ph
    hi_w = (ow - 1) * sw + kew - w - pw
    zero = jnp.zeros((), x.dtype)
    x = lax.pad(x, zero, ((0, 0, 0), (0, 0, 0), (ph, hi_h, 0), (pw, hi_w, 0)))
    hp, wp = x.shape[2], x.shape[3]
    x = x.reshape(n, c, hp // sh, sh, wp // sw, sw)
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4)).reshape(
        n, c * sh * sw, hp // sh, wp // sw)
    wz = jnp.zeros((), weight.dtype)
    weight = lax.pad(weight, wz,
                     ((0, 0, 0), (0, 0, 0), (0, kph, 0), (0, kpw, 0)))
    weight = weight.reshape(o, c, keh // sh, sh, kew // sw, sw)
    weight = jnp.transpose(weight, (0, 1, 3, 5, 2, 4)).reshape(
        o, c * sh * sw, keh // sh, kew // sw)
    return lax.conv_general_dilated(
        x, weight, window_strides=(1, 1), padding=((0, 0), (0, 0)),
        dimension_numbers=DIMNUMS)


def _conv(x, weight, kh, kw, sh, sw, ph, pw, dh, dw, group):
    """One conv bottom: the space-to-depth rewrite where it is eligible,
    else the direct convolution."""
    if _s2d_eligible(x.shape[1], kh, kw, sh, sw, ph, pw, dh, dw, group):
        return _space_to_depth_conv(x, weight, kh, kw, sh, sw, ph, pw)
    return lax.conv_general_dilated(
        x, weight,
        window_strides=(sh, sw),
        padding=((ph, ph), (pw, pw)),
        rhs_dilation=(dh, dw),
        feature_group_count=group,
        dimension_numbers=DIMNUMS,
    )


@register_layer("Convolution")
class ConvolutionLayer(LayerImpl):
    """2-D convolution (reference: caffe/src/caffe/layers/conv_layer.cpp;
    weight blob (out, in/group, kh, kw), out_dim = (in + 2p - ke)/s + 1 with
    ke = d*(k-1)+1, floor division — base_conv_layer.cpp compute_output_shape)."""

    def out_shapes(self, lp: LayerParameter, bottom_shapes: Sequence[Shape]) -> list[Shape]:
        n, c, h, w = bottom_shapes[0]
        kh, kw, sh, sw, ph, pw, dh, dw, num_output, group, _ = conv_geometry(lp)
        keh, kew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
        oh = (h + 2 * ph - keh) // sh + 1
        ow = (w + 2 * pw - kew) // sw + 1
        return [(n, num_output, oh, ow) for _ in lp.bottom]

    def init(self, rng, lp, bottom_shapes):
        _, c, _, _ = bottom_shapes[0]
        kh, kw, _, _, _, _, _, _, num_output, group, bias_term = conv_geometry(lp)
        p = lp.sub("convolution_param")
        wf = FillerParameter.from_pmsg(p.get("weight_filler"))
        r1, r2 = jax.random.split(rng)
        blobs = [fill(r1, wf, (num_output, c // group, kh, kw))]
        if bias_term:
            bf = FillerParameter.from_pmsg(p.get("bias_filler"))
            blobs.append(fill(r2, bf, (num_output,)))
        return blobs

    def apply(self, lp, params, bottoms, train, rng):
        kh, kw, sh, sw, ph, pw, dh, dw, num_output, group, bias_term = conv_geometry(lp)
        weight = params[0]
        tops = []
        for x in bottoms:
            y = _conv(x, weight, kh, kw, sh, sw, ph, pw, dh, dw, group)
            if bias_term:
                y = y + params[1].reshape(1, -1, 1, 1)
            tops.append(y)
        return tops


@register_layer("Deconvolution")
class DeconvolutionLayer(LayerImpl):
    """Transposed convolution (reference:
    caffe/src/caffe/layers/deconv_layer.cpp; weight blob (in, out/group, kh,
    kw), out_dim = s*(in-1) + ke - 2p).  Implemented as an input-dilated
    forward conv with spatially flipped, group-transposed weights — the exact
    transpose of ConvolutionLayer, without writing a backward pass."""

    def out_shapes(self, lp, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        kh, kw, sh, sw, ph, pw, dh, dw, num_output, group, _ = conv_geometry(lp)
        keh, kew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
        oh = sh * (h - 1) + keh - 2 * ph
        ow = sw * (w - 1) + kew - 2 * pw
        return [(n, num_output, oh, ow) for _ in lp.bottom]

    def init(self, rng, lp, bottom_shapes):
        _, c, _, _ = bottom_shapes[0]
        kh, kw, _, _, _, _, _, _, num_output, group, bias_term = conv_geometry(lp)
        p = lp.sub("convolution_param")
        wf = FillerParameter.from_pmsg(p.get("weight_filler"))
        r1, r2 = jax.random.split(rng)
        blobs = [fill(r1, wf, (c, num_output // group, kh, kw))]
        if bias_term:
            bf = FillerParameter.from_pmsg(p.get("bias_filler"))
            blobs.append(fill(r2, bf, (num_output,)))
        return blobs

    def apply(self, lp, params, bottoms, train, rng):
        kh, kw, sh, sw, ph, pw, dh, dw, num_output, group, bias_term = conv_geometry(lp)
        w = params[0]  # (C_in, C_out/group, kh, kw)
        c_in = w.shape[0]
        # -> (C_out, C_in/group, kh, kw), spatially flipped
        wg = w.reshape(group, c_in // group, num_output // group, kh, kw)
        wg = jnp.transpose(wg, (0, 2, 1, 3, 4)).reshape(
            num_output, c_in // group, kh, kw)
        wg = jnp.flip(wg, axis=(-2, -1))
        keh, kew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
        tops = []
        for x in bottoms:
            y = lax.conv_general_dilated(
                x, wg,
                window_strides=(1, 1),
                padding=((keh - 1 - ph, keh - 1 - ph), (kew - 1 - pw, kew - 1 - pw)),
                lhs_dilation=(sh, sw),
                rhs_dilation=(dh, dw),
                feature_group_count=group,
                dimension_numbers=DIMNUMS,
            )
            if bias_term:
                y = y + params[1].reshape(1, -1, 1, 1)
            tops.append(y)
        return tops


def pool_output_size(h: int, w: int, kh: int, kw: int, sh: int, sw: int,
                     ph: int, pw: int) -> tuple[int, int]:
    """Caffe's ceil-mode pooled size with the start-inside-padding clip
    (reference: pooling_layer.cpp:90-102)."""
    oh = int(math.ceil((h + 2 * ph - kh) / sh)) + 1
    ow = int(math.ceil((w + 2 * pw - kw) / sw)) + 1
    if ph or pw:
        if (oh - 1) * sh >= h + ph:
            oh -= 1
        if (ow - 1) * sw >= w + pw:
            ow -= 1
    return oh, ow


def _pool_geometry(lp: LayerParameter, bottom_shape: Shape):
    p = lp.sub("pooling_param")
    n, c, h, w = bottom_shape
    if bool(p.get("global_pooling", False)):
        kh, kw, sh, sw, ph, pw = h, w, 1, 1, 0, 0
    else:
        kh, kw = _pair(p, "kernel_size", 0, "kernel_h", "kernel_w")
        sh, sw = _pair(p, "stride", 1)
        ph, pw = _pair(p, "pad", 0)
        if kh <= 0 or kw <= 0:
            raise ValueError(
                f"layer {lp.name!r}: kernel_size (or kernel_h/kernel_w) "
                f"required unless global_pooling")
    method = str(p.get("pool", "MAX"))
    return kh, kw, sh, sw, ph, pw, method


def max_pool(x, kh, kw, sh, sw, ph, pw, oh, ow):
    """MAX pooling via ``reduce_window``; backward is XLA's
    select-and-scatter, which routes each output's gradient to the
    window's first maximum — Caffe's argmax scan (pooling_layer.cpp
    Forward_cpu MAX branch).  A hand-unrolled compare/dilated-pad backward
    was measured SLOWER on TPU v5e (XLA re-reads dy/idx once per kernel
    tap in the fused form: 3.1 GB vs ~0.6 GB minimum traffic for CaffeNet
    pool1, 4.2 ms vs 1.1 ms) — keep select-and-scatter."""
    h, w = x.shape[2], x.shape[3]
    pad_hi_h = (oh - 1) * sh + kh - h - ph
    pad_hi_w = (ow - 1) * sw + kw - w - pw
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, kh, kw), (1, 1, sh, sw),
        ((0, 0), (0, 0), (ph, max(pad_hi_h, 0)), (pw, max(pad_hi_w, 0))),
    )


def ave_pool(x, kh, kw, sh, sw, ph, pw, oh, ow):
    """Caffe AVE pooling: zero-pad, divide by the pool window size clipped to
    the padded extent [0, dim+pad) — not the kernel area and not the valid
    area (reference: pooling_layer.cpp Forward_cpu AVE branch)."""
    h, w = x.shape[2], x.shape[3]
    pad_hi_h = (oh - 1) * sh + kh - h - ph
    pad_hi_w = (ow - 1) * sw + kw - w - pw
    s = lax.reduce_window(
        x, 0.0, lax.add, (1, 1, kh, kw), (1, 1, sh, sw),
        ((0, 0), (0, 0), (ph, max(pad_hi_h, 0)), (pw, max(pad_hi_w, 0))),
    )

    def counts(dim: int, k: int, stride: int, pad: int, out: int) -> np.ndarray:
        starts = np.arange(out) * stride - pad
        ends = np.minimum(starts + k, dim + pad)
        return (ends - starts).astype(np.float32)

    ch = counts(h, kh, sh, ph, oh)
    cw = counts(w, kw, sw, pw, ow)
    denom = jnp.asarray(np.outer(ch, cw))[None, None, :, :]
    return s / denom


def stochastic_pool_train(x, kh, kw, sh, sw, ph, pw, oh, ow, rng):
    """Train-mode stochastic pooling (reference: pooling_layer.cu
    StoPoolForwardTrain): draw thres = U(0,1)·Σwindow, output the first
    element whose running cumsum exceeds thres; gradient routes to the
    sampled element only (StoPoolBackward).  Inputs are assumed
    non-negative (the reference samples after ReLU the same way); an
    all-zero window yields 0 with gradient to its first element."""
    n, c, h, w = x.shape
    pad_hi_h = (oh - 1) * sh + kh - h - ph
    pad_hi_w = (ow - 1) * sw + kw - w - pw
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw),
        ((ph, max(pad_hi_h, 0)), (pw, max(pad_hi_w, 0))),
        dimension_numbers=DIMNUMS)  # (N, C·kh·kw, oh, ow)
    p = patches.reshape(n, c, kh * kw, oh, ow)
    cs = jnp.cumsum(p, axis=2)
    total = cs[:, :, -1:, :, :]
    thres = jax.random.uniform(rng, (n, c, 1, oh, ow), x.dtype) * total
    idx = jnp.argmax(cs > thres, axis=2)  # first exceedance; all-False → 0
    return jnp.take_along_axis(p, idx[:, :, None], axis=2)[:, :, 0]


@register_layer("Pooling")
class PoolingLayer(LayerImpl):
    """MAX/AVE/STOCHASTIC pooling (reference: pooling_layer.cpp).  STOCHASTIC
    samples a window element with probability ∝ its value in train mode
    (pooling_layer.cu StoPoolForwardTrain) and uses the weighted-average
    form (sum x² / sum x) at test (StoPoolForwardTest)."""

    def needs_rng(self, lp, train: bool = True) -> bool:
        return train and str(
            lp.sub("pooling_param").get("pool", "MAX")) == "STOCHASTIC"

    def out_shapes(self, lp, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        kh, kw, sh, sw, ph, pw, _ = _pool_geometry(lp, bottom_shapes[0])
        oh, ow = pool_output_size(h, w, kh, kw, sh, sw, ph, pw)
        return [(n, c, oh, ow)]

    @staticmethod
    def _use_pallas_bwd() -> bool:
        return knobs.raw("SPARKNET_PALLAS_MAXPOOL") == "1"

    def apply(self, lp, params, bottoms, train, rng):
        x = bottoms[0]
        n, c, h, w = x.shape
        kh, kw, sh, sw, ph, pw, method = _pool_geometry(lp, x.shape)
        oh, ow = pool_output_size(h, w, kh, kw, sh, sw, ph, pw)
        if method == "MAX":
            if self._use_pallas_bwd():
                # opt-in VMEM-resident Pallas backward (forward stays
                # XLA reduce_window); see ops/pallas_kernels.py
                from .pallas_kernels import max_pool_vmem_bwd
                return [max_pool_vmem_bwd(x, kh, kw, sh, sw, ph, pw,
                                          oh, ow)]
            return [max_pool(x, kh, kw, sh, sw, ph, pw, oh, ow)]
        if method == "AVE":
            return [ave_pool(x, kh, kw, sh, sw, ph, pw, oh, ow)]
        if method == "STOCHASTIC":
            if train:
                return [stochastic_pool_train(x, kh, kw, sh, sw, ph, pw,
                                              oh, ow, rng)]
            num = ave_pool(x * x, kh, kw, sh, sw, ph, pw, oh, ow)
            den = ave_pool(x, kh, kw, sh, sw, ph, pw, oh, ow)
            return [num / jnp.where(den == 0, 1.0, den)]
        raise ValueError(f"unknown pool method {method!r}")


def lrn_geometry(lp: LayerParameter):
    """(size, alpha, beta, k, region) from lrn_param — shared by
    LRNLayer and the fused-chain executor (graph/fusion.py)."""
    p = lp.sub("lrn_param")
    return (int(p.get("local_size", 5)), float(p.get("alpha", 1.0)),
            float(p.get("beta", 0.75)), float(p.get("k", 1.0)),
            str(p.get("norm_region", "ACROSS_CHANNELS")))


# Channel-count floor for the cumsum window sum, TPU only.  The round-10
# CPU probe re-run (tools/perf_probe.py lrn, RESULTS.md r10 table)
# REVERSED the round-6 CPU verdict: on the current XLA CPU build
# reduce_window wins every zoo LRN shape fwd+bwd (cumsum at 0.64-0.95x),
# so auto stays OFF on CPU — measured, not assumed.  On TPU the O(C) vs
# O(C·size) HBM-read argument still only pays where the channel axis is
# wide, hence the floor; the TPU capture remains the final decider — a
# capture that contradicts this floor should update it, not hand-set the
# env.
LRN_CUMSUM_AUTO_C = 128


def lrn_use_cumsum(c_dim: int) -> bool:
    """LRN window-sum formulation when the caller does not say (read at
    TRACE time, like the other vision-layer toggles): off everywhere but
    TPU (the CPU probe says reduce_window wins there), by channel count
    on TPU.  To force one form, pass ``use_cumsum=`` to
    :func:`lrn_window_sum`."""
    if jax.default_backend() != "tpu":
        return False
    return c_dim >= LRN_CUMSUM_AUTO_C


def lrn_window_sum(sq, pre: int, post: int, use_cumsum: bool | None = None):
    """Σ over the [-pre, +post] channel window of a (N,C,H,W) tensor.

    Two exact-to-association formulations: ``reduce_window`` (each value
    touched ``size`` times) or a single channel-axis cumsum with two
    static gathers (``ssum[c] = cs[c+post] - cs[c-pre-1]`` — O(C) reads
    per element).  ``use_cumsum=None`` defers to :func:`lrn_use_cumsum`;
    probes and tests pass it explicitly."""
    c_dim = sq.shape[1]
    if use_cumsum is None:
        use_cumsum = lrn_use_cumsum(c_dim)
    if sq.shape[0] < 8 and jax.default_backend() == "tpu":
        # XLA's TPU SpaceToBatchConverter rewrites convolutions whose
        # batch is below 8 and carries the rewrite into their users.
        # Carried into a window sum over channels it fails the compile
        # (reduce_window: "Binary op with incompatible shapes:
        # bf16[27,8,4,96] and bf16[27,8,4,92]" at CaffeNet norm1 in bf16,
        # every batch from 1 to 7) or aborts the compiler (cumsum: check
        # at space_to_batch_converter.cc:2824); libtpu 0.0.34.  A barrier
        # on the operand stops the rewrite here; batch 8 and up is not
        # rewritten and compiles as before.
        sq = lax.optimization_barrier(sq)
    if sq.ndim == 4 and use_cumsum:
        cs = jnp.cumsum(sq.astype(jnp.float32), axis=1)
        cs = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
        hi = np.minimum(np.arange(c_dim) + post + 1, c_dim)
        lo = np.clip(np.arange(c_dim) - pre, 0, c_dim)
        return (jnp.take(cs, hi, axis=1)
                - jnp.take(cs, lo, axis=1)).astype(sq.dtype)
    return lax.reduce_window(
        sq, 0.0, lax.add, (1, pre + post + 1, 1, 1), (1, 1, 1, 1),
        ((0, 0), (pre, post), (0, 0), (0, 0)),
    )


def _relu_lrn_primal(x, size, alpha, beta, k, relu):
    """The fused-chain tail as plain XLA ops — literally the unfused
    ReLU + LRN formulas in sequence, so the undifferentiated fused
    forward is the same HLO as the per-layer path (bit parity on the
    CPU, tests/test_fusion.py)."""
    a = jnp.maximum(x, 0.0) if relu else x
    pre = (size - 1) // 2
    post = size - 1 - pre
    scale = k + (alpha / size) * lrn_window_sum(a * a, pre, post)
    return a, scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def relu_lrn_reference(x, size: int, alpha: float, beta: float, k: float,
                       relu: bool = False):
    """XLA-lowered [ReLU+]LRN epilogue with the Pallas kernels' custom
    VJP (ops/pallas_kernels.py): forward saves only ``scale`` (Caffe's
    lrn_layer.cpp residual), backward applies the closed-form gradient
    instead of differentiating through the window sum — on CPU this is
    the fused chain's measured win (no reduce_window transpose, no
    scale recompute), and it is the backend-portable fallback the fused
    executor uses wherever the Pallas kernel doesn't run."""
    a, scale = _relu_lrn_primal(x, size, alpha, beta, k, relu)
    return a / scale ** beta


def _relu_lrn_ref_vjp_fwd(x, size, alpha, beta, k, relu):
    a, scale = _relu_lrn_primal(x, size, alpha, beta, k, relu)
    return a / scale ** beta, (x, scale)


def _relu_lrn_ref_vjp_bwd(size, alpha, beta, k, relu, res, dy):
    x, scale = res
    xf = x.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    a = jnp.maximum(xf, 0.0) if relu else xf
    y = a * s ** -beta
    pre = (size - 1) // 2
    post = size - 1 - pre
    ratio = lrn_window_sum(dyf * y / s, post, pre)  # reflected window
    da = dyf * s ** -beta - (2.0 * alpha * beta / size) * a * ratio
    if relu:
        da = jnp.where(xf > 0, da, 0.0)
    return (da.astype(x.dtype),)


relu_lrn_reference.defvjp(_relu_lrn_ref_vjp_fwd, _relu_lrn_ref_vjp_bwd)


def lrn_chain_epilogue(x, size: int, alpha: float, beta: float, k: float,
                       *, relu: bool):
    """The fused conv-chain tail: [ReLU +] ACROSS_CHANNELS LRN in one
    pass over the producer's output.  On TPU, for 4-D float32/bfloat16,
    this is the Pallas epilogue kernel (one VMEM trip instead of the
    reduce_window chain), with the batch or the positions on the lanes
    as the shape says (``pallas_kernels.lrn_lanes``); elsewhere the XLA
    reference above (same custom VJP, same residuals).  The choice is
    made while tracing and counted there, in
    ``lrn_epilogue_lowering_total{path=batch_lanes|space_lanes|
    reference}``."""
    path = "reference"
    if (x.ndim == 4 and x.dtype in (jnp.float32, jnp.bfloat16)
            and jax.default_backend() == "tpu"):
        from .pallas_kernels import lrn_lanes, relu_lrn_across_channels
        path = lrn_lanes(x.shape)
    telemetry.get_registry().counter(
        "lrn_epilogue_lowering_total",
        "traces of the conv-chain [ReLU+]LRN epilogue, by lowering").inc(
            path=path)
    if path == "reference":
        return relu_lrn_reference(x, size, alpha, beta, k, relu)
    return relu_lrn_across_channels(x, size, alpha, beta, k, relu)


@register_layer("LRN")
class LRNLayer(LayerImpl):
    """Local response normalization (reference:
    caffe/src/caffe/layers/lrn_layer.cpp): scale = k + (alpha/n)·Σ x² over a
    size-n window, out = x / scale^beta.  ACROSS_CHANNELS windows the channel
    axis; WITHIN_CHANNEL uses AVE-pooling semantics spatially.

    Behind a convolution (``graph/fusion.py``) the ACROSS_CHANNELS form
    runs in :func:`lrn_chain_epilogue` instead of here.

    The cumsum formulation rewrites the ACROSS_CHANNELS window sum
    algebraically: instead of ``reduce_window`` touching each x² value
    ``local_size`` times (the 555 GB/s chain in the GoogLeNet per-layer
    table — 17% of its step), a single channel-axis ``cumsum`` followed
    by two static gathers computes every window as a prefix-sum
    difference (ssum[c] = cs[c+post] - cs[c-pre-1]) — O(C) reads per
    element instead of O(C·size).  EXACT up to float summation order
    (the window total is the same set of addends, associated
    differently); gradients flow through cumsum's transpose.  The unset
    default is per-backend (:func:`lrn_use_cumsum`): OFF on CPU — the
    round-10 probe re-run reversed round 6's CPU verdict, reduce_window
    now wins every zoo shape there (RESULTS.md r10 table) — and
    channel-count-gated on TPU, where the capture remains the final
    decider.  tools/perf_probe.py ``lrn`` is the harness (its ``auto``
    variant audits the default)."""

    def apply(self, lp, params, bottoms, train, rng):
        size, alpha, beta, k, region = lrn_geometry(lp)
        x = bottoms[0]
        sq = x * x
        if region == "ACROSS_CHANNELS":
            pre = (size - 1) // 2
            post = size - 1 - pre
            ssum = lrn_window_sum(sq, pre, post)
        else:  # WITHIN_CHANNEL: x · (1 + α·avgpool(x²))^-β  (lrn_layer.cpp
            # WithinChannelForward: square → AVE pool → power(shift=1,
            # scale=α, power=-β) → eltwise product; k is unused there)
            pre = (size - 1) // 2
            h, w = x.shape[2], x.shape[3]
            savg = ave_pool(sq, size, size, 1, 1, pre, pre, h, w)
            return [x * (1.0 + alpha * savg) ** (-beta)]
        scale = k + (alpha / size) * ssum
        return [x / scale ** beta]


@register_layer("Im2col")
class Im2colLayer(LayerImpl):
    """Patch extraction as a standalone layer (reference:
    caffe/src/caffe/layers/im2col_layer.cpp)."""

    def out_shapes(self, lp, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        kh, kw, sh, sw, ph, pw, dh, dw, _, _, _ = conv_geometry(lp)
        keh, kew = dh * (kh - 1) + 1, dw * (kw - 1) + 1
        oh = (h + 2 * ph - keh) // sh + 1
        ow = (w + 2 * pw - kew) // sw + 1
        return [(n, c * kh * kw, oh, ow)]

    def apply(self, lp, params, bottoms, train, rng):
        kh, kw, sh, sw, ph, pw, dh, dw, _, _, _ = conv_geometry(lp)
        y = lax.conv_general_dilated_patches(
            bottoms[0], (kh, kw), (sh, sw), ((ph, ph), (pw, pw)),
            rhs_dilation=(dh, dw), dimension_numbers=DIMNUMS,
        )
        return [y]


@register_layer("SPP")
class SPPLayer(LayerImpl):
    """Spatial pyramid pooling (reference: caffe/src/caffe/layers/spp_layer.cpp):
    pyramid_height levels; level l has 2^l × 2^l bins, each max-pooled and
    flattened, concatenated along channels."""

    def _levels(self, lp, shape):
        p = lp.sub("spp_param")
        height = int(p.get("pyramid_height", 1))
        n, c, h, w = shape
        out = []
        for l in range(height):
            bins = 2 ** l
            kh = int(math.ceil(h / bins))
            kw = int(math.ceil(w / bins))
            ph = (kh * bins - h + 1) // 2
            pw = (kw * bins - w + 1) // 2
            out.append((bins, kh, kw, ph, pw))
        return out

    def out_shapes(self, lp, bottom_shapes):
        n, c, h, w = bottom_shapes[0]
        total = sum(c * bins * bins for bins, *_ in self._levels(lp, bottom_shapes[0]))
        return [(n, total)]

    def apply(self, lp, params, bottoms, train, rng):
        x = bottoms[0]
        n, c, h, w = x.shape
        p = lp.sub("spp_param")
        method = str(p.get("pool", "MAX"))
        outs = []
        for bins, kh, kw, ph, pw in self._levels(lp, x.shape):
            fn = max_pool if method == "MAX" else ave_pool
            y = fn(x, kh, kw, kh, kw, ph, pw, bins, bins)
            outs.append(y.reshape(n, -1))
        return [jnp.concatenate(outs, axis=1)]
