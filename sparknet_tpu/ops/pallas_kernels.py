"""Pallas TPU kernels for ops XLA fuses poorly.

Cross-channel LRN is AlexNet/CaffeNet's one non-matmul hot op (~13% of
the measured f32 train step: 24.2 -> 21.2 ms/step with LRN stripped, TPU
v5e batch 256).  XLA lowers it as reduce_window + pow + div in forward
and a second windowed reduction in backward; these kernels do each pass
in ONE trip through VMEM with the channel-window sums computed as
unrolled shifted adds on the VPU, and a custom VJP that saves only
``scale`` (Caffe's own trick — lrn_layer.cpp stores scale_ for
CrossMapBackward).

Math (reference: caffe/src/caffe/layers/lrn_layer.cpp):
  scale(c) = k + alpha/n * sum_{d in window} x(c+d)^2
  y        = x * scale^-beta
  dx(c)    = dy(c)*scale(c)^-beta
             - (2*alpha*beta/n) * x(c) * sum_{d} dy(c+d)*y(c+d)/scale(c+d)

Layout: the operand handed to ``pallas_call`` is three-dimensional,
``(rows, C, lanes)``, and which axis of the activation rides the lanes is
a function of its shape (:func:`lrn_lanes`):

- ``batch_lanes`` (the batch is a multiple of 128: every training
  batch of the zoo): ``[H*W, C, N]``.  XLA's TPU layouts keep a
  convolution's activations with the batch on the lanes, the channels on
  the sublanes and the positions major, so this logical transpose is a
  bitcast of what the producing convolution wrote and of what the pool
  or the convolution behind reads: the kernel's edges cost no copy.
  Every lane is full whatever H*W is.
- ``space_lanes`` (any other batch: test nets at 50, the classify
  engine's small buckets): ``[N, C, H*W]``, the positions on the lanes
  and several images a block, so that a small batch does not pay for
  128 lanes of one image.

Either way the windowed sum runs along the sublanes, a block holds
about ``_BLOCK_BYTES`` of the operand, and the body walks it a row and a
lane tile at a time so that its float32 temporaries stay in registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_BYTES = 1 << 20   # of one operand, in the type it is stored in
_LANE_TILE = 128         # lanes the body handles at once

# Every call below is the compiled Mosaic kernel.  The CPU tests that
# check the kernels' math patch this to True (the Pallas interpreter);
# nothing else does, so what runs never depends on the backend JAX found.
_INTERPRET = False


def _window_sum(v: jnp.ndarray, pre: int, post: int) -> jnp.ndarray:
    """Σ over the [-pre, +post] channel window along axis 0, zero-padded
    — unrolled shifted adds.  Forward uses Caffe's (pre=(n-1)/2, post);
    the VJP uses the REFLECTED window (post, pre): c' contributes to c's
    gradient iff c lies in c''s forward window."""
    c = v.shape[0]
    padded = jnp.pad(v, ((pre, post), (0, 0)))
    out = padded[0:c]
    for d in range(1, pre + post + 1):
        out = out + padded[d:d + c]
    return out


def _fwd_window(size: int) -> tuple[int, int]:
    pre = (size - 1) // 2
    return pre, size - 1 - pre


def _fwd_math(x, *, size, alpha, beta, k, relu):
    # Math in f32 regardless of I/O dtype; bf16 blocks cast at the VMEM
    # boundary so mixed-precision nets keep f32 window sums.  With
    # ``relu`` the block consumes the producer conv's biased output
    # directly and applies the chain's ReLU in-register — the vertical
    # fusion pass's LRN epilogue (graph/fusion.py) — so the post-ReLU
    # activation never round-trips through HBM between the two layers.
    x = x.astype(jnp.float32)
    a = jnp.maximum(x, 0.0) if relu else x
    pre, post = _fwd_window(size)
    scale = k + (alpha / size) * _window_sum(a * a, pre, post)
    return a * scale ** -beta, scale


def _bwd_math(x, scale, dy, *, size, alpha, beta, relu):
    # The ReLU'd activation is recomputed from the saved pre-activation
    # (one VPU max) rather than stored — residuals stay (x, scale),
    # exactly Caffe's CrossMapBackward memory footprint even with the
    # epilogue fused on top.
    x = x.astype(jnp.float32)
    scale = scale.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    a = jnp.maximum(x, 0.0) if relu else x
    y = a * scale ** -beta
    pre, post = _fwd_window(size)
    ratio = _window_sum(dy * y / scale, post, pre)  # reflected window
    da = (dy * scale ** -beta
          - (2.0 * alpha * beta / size) * a * ratio)
    if relu:
        # relu_layer.cpp Backward: dx = da * (x > 0); ties at exactly 0
        # route no gradient, matching the unfused ReLU->LRN pair
        da = jnp.where(x > 0, da, 0.0)
    return da


def _over_block(ins, outs, math):
    """``outs = math(*ins)`` over a ``(rows, C, lanes)`` block, one ``(C,
    lane tile)`` slab at a time: each slab's float32 temporaries are a
    few dozen registers, where the whole block's would live in VMEM."""
    rows, _, lanes = ins[0].shape
    tiles = [(at, min(_LANE_TILE, lanes - at))
             for at in range(0, lanes, _LANE_TILE)]

    def row(r, carry):
        for at, width in tiles:
            at_slab = (r, slice(None), pl.ds(at, width))
            for ref, v in zip(outs, math(*(ref[at_slab] for ref in ins))):
                ref[at_slab] = v.astype(ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def _lrn_fwd_kernel(x_ref, y_ref, scale_ref, **geometry):
    _over_block((x_ref,), (y_ref, scale_ref),
                functools.partial(_fwd_math, **geometry))


def _lrn_infer_kernel(x_ref, y_ref, **geometry):
    """Forward without the scale residual — the primal/inference path
    (a pallas output cannot be dead-code-eliminated by XLA, so writing
    scale when nothing consumes it costs a full HBM pass)."""
    _over_block((x_ref,), (y_ref,),
                lambda x: _fwd_math(x, **geometry)[:1])


def _lrn_bwd_kernel(x_ref, scale_ref, dy_ref, dx_ref, **geometry):
    _over_block((x_ref, scale_ref, dy_ref), (dx_ref,),
                lambda *res_dy: (_bwd_math(*res_dy, **geometry),))


def lrn_lanes(shape) -> str:
    """Which axis of an ``[N, C, H, W]`` activation rides the lanes:
    the batch where it fills them, the positions where it does not."""
    return "batch_lanes" if shape[0] % 128 == 0 else "space_lanes"


def _largest_tile(extent: int, fits: int) -> int:
    """The largest multiple of 128 that divides ``extent`` and is at most
    ``fits`` (128 if none is)."""
    best = 128
    for t in range(128, min(extent, max(fits, 128)) + 1, 128):
        if extent % t == 0:
            best = t
    return best


def _blocked(shape, itemsize: int):
    """``(to_operand, from_operand, grid, spec)`` for an ``[N, C, H, W]``
    activation: the two reshapes round the call (bitcasts of the layout
    the neighbours hold), and the blocking of the 3-D operand."""
    n, c, h, w = shape
    s = h * w
    lane_bytes = c * itemsize                # of one row of the operand
    if lrn_lanes(shape) == "batch_lanes":
        extent = (s, n)                      # the operand's rows, lanes
        lanes = _largest_tile(n, _BLOCK_BYTES // lane_bytes)

        def to_operand(v):
            return v.reshape(n, c, s).transpose(2, 1, 0)

        def from_operand(v):
            return v.transpose(2, 1, 0).reshape(shape)
    else:
        extent = (n, s)
        lanes = s                            # whole, or tiles of 128s
        if lane_bytes * s > _BLOCK_BYTES:
            lanes = max(128, _BLOCK_BYTES // lane_bytes // 128 * 128)

        def to_operand(v):
            return v.reshape(n, c, s)

        def from_operand(v):
            return v.reshape(shape)
    held = lane_bytes * pl.cdiv(lanes, 128) * 128    # VMEM pads the lanes
    rows = max(1, min(extent[0], _BLOCK_BYTES // held))
    return (to_operand, from_operand,
            (pl.cdiv(extent[0], rows), pl.cdiv(extent[1], lanes)),
            pl.BlockSpec((rows, c, lanes), lambda i, j: (i, 0, j)))


def _call(kernel, name, ins, n_out, **geometry):
    """One ``pallas_call`` of ``kernel`` over ``[N, C, H, W]`` operands of
    one shape and type, with ``n_out`` results of the same."""
    x = ins[0]
    to_operand, from_operand, grid, spec = _blocked(x.shape,
                                                    x.dtype.itemsize)
    ins = [to_operand(v) for v in ins]
    like = jax.ShapeDtypeStruct(ins[0].shape, x.dtype)
    outs = pl.pallas_call(
        functools.partial(kernel, **geometry),
        out_shape=(like,) * n_out,
        grid=grid,
        in_specs=[spec] * len(ins),
        out_specs=(spec,) * n_out,
        interpret=_INTERPRET,
        name=name,
    )(*ins)
    return tuple(from_operand(v) for v in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def relu_lrn_across_channels(x, size: int, alpha: float, beta: float,
                             k: float, relu: bool = False):
    """Caffe ACROSS_CHANNELS LRN as a fused Pallas kernel, with the
    producing chain's zero-slope ReLU optionally folded in-register
    (``relu=True``) — the vertical fusion pass's LRN epilogue: the conv
    output is read from HBM ONCE, bias/ReLU/window-sum/normalize all
    happen in VMEM, and only the normalized activation is written back
    (plus ``scale`` on the VJP path, Caffe's own residual)."""
    (y,) = _call(_lrn_infer_kernel, "relu_lrn_infer", [x], 1, size=size,
                 alpha=alpha, beta=beta, k=k, relu=relu)
    return y


def _lrn_vjp_fwd(x, size, alpha, beta, k, relu):
    y, scale = _call(_lrn_fwd_kernel, "relu_lrn_fwd", [x], 2, size=size,
                     alpha=alpha, beta=beta, k=k, relu=relu)
    return y, (x, scale)


def _lrn_vjp_bwd(size, alpha, beta, k, relu, res, dy):
    x, scale = res
    return _call(_lrn_bwd_kernel, "relu_lrn_bwd", [x, scale, dy], 1,
                 size=size, alpha=alpha, beta=beta, relu=relu)


relu_lrn_across_channels.defvjp(_lrn_vjp_fwd, _lrn_vjp_bwd)


# ---------------------------------------------------------------------------
# VMEM-resident MAX-pool backward
#
# XLA lowers maxpool backward as select-and-scatter, measured at an HBM
# traffic floor ~2.5x the minimum on GoogLeNet's 13 pools (5.3 ms of the
# 26.4 ms bf16 step); two pure-XLA rewrites measured OUT (see
# RESULTS.md).  This kernel does the whole backward in ONE trip: read x
# and dy once, recompute each window's FIRST argmax on the VPU (Caffe's
# tie-break — pooling_layer.cpp Forward_cpu MAX branch scans row-major
# and keeps the first maximum), route dy through the argmax, write dx
# once.  The grid tiles (batch, channels) and keeps the full spatial
# plane per block in VMEM, so no halo exchange is needed.
# ---------------------------------------------------------------------------


def _pool_taps(kh: int, kw: int):
    """Window taps in Caffe's scan order (row-major; first max wins)."""
    return [(dh, dw) for dh in range(kh) for dw in range(kw)]


def _maxpool_bwd_kernel_s1(x_ref, dy_ref, dx_ref, *, kh, kw, ph, pw,
                           oh, ow, h, w):
    """Stride-1 path: row taps are contiguous sublane slices; column
    taps ride the MXU as exact one-hot matmuls.  Hard-won Mosaic
    constraints (each crashes the compiler if violated): no lane-offset
    pads of compare-derived values, compares in f32 (bf16 cmpf
    miscompiles at 3-D shapes), and the padded plane widened to >=128
    lanes (free — vregs are 128 lanes regardless; narrow matmul K-dims
    crash at 7x7)."""
    x = x_ref[:]
    dy = dy_ref[:]
    c = x.shape[0]
    hp = oh + kh - 1
    wp = max(ow + kw - 1, 128)
    # Sentinel must be exactly bf16-representable: the MXU's bf16-pass
    # f32 matmul turns finfo(f32).min into -inf and the one-hot gather
    # into NaN (inf*0), silently zeroing every f32-mode gradient.
    # Domain restriction this buys: f32 activations below bf16 min
    # (-3.3895e38) would lose the argmax to padding — next stop after
    # that magnitude is inf, so no practical net is affected.
    neg = jnp.asarray(jnp.finfo(jnp.bfloat16).min, x.dtype)
    xp = jnp.pad(x, ((0, 0), (ph, hp - h - ph), (pw, wp - w - pw)),
                 constant_values=neg)
    gathers = [_col_onehot(dw, 1, wp, ow, x.dtype) for dw in range(kw)]
    # True-f32 nets need the exact multi-pass matmul: the default
    # single bf16 pass rounds the gathered VALUES and corrupts argmax
    # routing.  bf16 nets are single-pass-exact, and HIGHEST on bf16
    # inputs crashes Mosaic — so pick per dtype.
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def window(dh, dw):
        # f32 MXU accumulator doubles as the compare domain (exact —
        # the matmul just selects single bf16 values).
        slab = xp[:, dh:dh + oh, :]
        return jax.lax.dot_general(
            slab.reshape(c * oh, wp), gathers[dw], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec).reshape(c, oh, ow)

    taps = _pool_taps(kh, kw)
    wins = [window(dh, dw) for dh, dw in taps]  # one gather per tap
    best = functools.reduce(jnp.maximum, wins)
    # Route dy to the FIRST tap equal to the max (Caffe's row-major
    # tie-break).  A boolean "claimed" plane replaces an int argmax
    # plane: constant-init int planes get a replicated Mosaic layout
    # that the mask relayout then rejects.
    dyf = dy.astype(jnp.float32)
    scatters = [_col_onehot(dw, 1, wp, ow, jnp.float32) for dw in range(kw)]
    acc = None
    claimed = None
    for (dh, dw), v in zip(taps, wins):
        eq = v == best
        m = eq if claimed is None else eq & ~claimed
        claimed = eq if claimed is None else claimed | eq
        cont = jnp.where(m, dyf, 0.0)
        wide = jax.lax.dot_general(
            cont.reshape(c * oh, ow), scatters[dw], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec).reshape(c, oh, wp)
        part = jnp.pad(wide, ((0, 0), (dh, hp - oh - dh), (0, 0)))
        acc = part if acc is None else acc + part
    dx_ref[:] = acc[:, ph:ph + h, pw:pw + w].astype(dx_ref.dtype)


def _col_onehot(dw: int, sw: int, wp: int, ow: int, dtype):
    """(wp, ow) selection matrix: column s picks padded-plane lane
    dw + s*sw.  Lane-strided gather/placement isn't lowerable on the
    VPU, so both directions ride the MXU as exact one-hot matmuls."""
    rowi = jax.lax.broadcasted_iota(jnp.int32, (wp, ow), 0)
    coli = jax.lax.broadcasted_iota(jnp.int32, (wp, ow), 1)
    return (rowi == dw + coli * sw).astype(dtype)


def _maxpool_bwd_kernel_strided(x_ref, dy_ref, dx_ref, *, kh, kw, sh, sw,
                                ph, pw, oh, ow, h, w):
    """General strided path.  Row stride is handled by splitting the
    sublane dim into (rows, sh) phases (a reshape Mosaic supports);
    column stride via one-hot selection matmuls (_col_onehot), since
    lane-dim strided slices and interior pads don't lower."""
    x = x_ref[:]
    dy = dy_ref[:]
    c = x.shape[0]
    rows = (kh - 1) // sh + oh
    hp = rows * sh
    # >=128-lane widening as in the stride-1 kernel: free (vregs are
    # 128 lanes regardless) and keeps the matmul K-dim off the narrow
    # sizes that crash Mosaic.  The w + pw floor covers stride > kernel
    # under Caffe's ceil-mode clip, where (ow-1)*sw + kw can fall short
    # of the input width and the pad amount would go negative.
    wp = max((ow - 1) * sw + kw, w + pw, 128)
    # bf16-representable sentinel — see the stride-1 kernel's comment.
    neg = jnp.asarray(jnp.finfo(jnp.bfloat16).min, x.dtype)
    xp = jnp.pad(x, ((0, 0), (ph, hp - h - ph), (pw, wp - w - pw)),
                 constant_values=neg)
    x4 = xp.reshape(c, rows, sh, wp)
    taps = _pool_taps(kh, kw)
    gathers = [_col_onehot(dw, sw, wp, ow, x.dtype) for dw in range(kw)]
    # True-f32 nets need the exact multi-pass matmul: the default
    # single bf16 pass rounds the gathered VALUES and corrupts argmax
    # routing.  bf16 nets are single-pass-exact, and HIGHEST on bf16
    # inputs crashes Mosaic — so pick per dtype.
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def window(dh, dw):
        # One-hot MXU gather; keep the mandatory 32-bit accumulator as
        # the compare domain too (bf16 cmpf crashes Mosaic; exact both
        # ways since the matmul just selects single values).
        slab = x4[:, dh // sh:dh // sh + oh, dh % sh, :]
        return jax.lax.dot_general(
            slab.reshape(c * oh, wp), gathers[dw], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec).reshape(c, oh, ow)

    wins = [window(dh, dw) for dh, dw in taps]  # one gather per tap
    best = functools.reduce(jnp.maximum, wins)
    # First-equal-claims routing (see the stride-1 kernel's comment).
    dyf = dy.astype(jnp.float32)
    scatters = [_col_onehot(dw, sw, wp, ow, jnp.float32) for dw in range(kw)]
    phase_acc = [None] * sh
    claimed = None
    for (dh, dw), v in zip(taps, wins):
        eq = v == best
        m = eq if claimed is None else eq & ~claimed
        claimed = eq if claimed is None else claimed | eq
        cont = jnp.where(m, dyf, 0.0)
        wide = jax.lax.dot_general(
            cont.reshape(c * oh, ow), scatters[dw], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec).reshape(c, oh, wp)
        q = dh // sh
        part = jnp.pad(wide, ((0, 0), (q, rows - oh - q), (0, 0)))
        p = dh % sh
        phase_acc[p] = part if phase_acc[p] is None else phase_acc[p] + part
    phase_acc = [a if a is not None else jnp.zeros((c, rows, wp), jnp.float32)
                 for a in phase_acc]  # kh < sh leaves untouched phases
    acc = jnp.stack(phase_acc, axis=2).reshape(c, hp, wp)
    dx_ref[:] = acc[:, ph:ph + h, pw:pw + w].astype(dx_ref.dtype)


def _pool_ctile(c: int, h: int, w: int, kh: int, kw: int) -> int:
    """Channels per block, capped at 8 — larger channel tiles crash
    Mosaic on these kernels (empirical: ct=24 dies after 130 s of
    compile, ct<=8 compiles in seconds; the grid pipelines the extra
    steps, so small tiles cost nothing measurable).  The VMEM model:
    kh*kw live f32 window planes (the ``wins`` list) plus ~5 padded
    >=128-lane input/acc/mask planes, kept under a conservative 64 MB
    so the ct<=8 Mosaic cap — not memory — binds for every zoo pool
    shape (~2 MB at ct=8 for 3x3 pools)."""
    per_c = max(h * max(w, 128) * 4 * (kh * kw + 5), 1)
    t = max(1, min(c, 8, (64 << 20) // per_c))
    while c % t:
        t -= 1
    return t


def _maxpool_bwd_call(x, dy, kh, kw, sh, sw, ph, pw, oh, ow):
    n, c, h, w = x.shape
    ct = _pool_ctile(c, h, w, kh, kw)
    grid = (n, c // ct)
    kern = (_maxpool_bwd_kernel_s1 if sh == 1 and sw == 1 else
            functools.partial(_maxpool_bwd_kernel_strided, sh=sh, sw=sw))
    return pl.pallas_call(
        functools.partial(kern, kh=kh, kw=kw, ph=ph, pw=pw,
                          oh=oh, ow=ow, h=h, w=w),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((None, ct, h, w), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((None, ct, oh, ow),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((None, ct, h, w), lambda i, j: (i, j, 0, 0)),
        interpret=_INTERPRET,
        name="maxpool_bwd",
    )(x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def max_pool_vmem_bwd(x, kh: int, kw: int, sh: int, sw: int,
                      ph: int, pw: int, oh: int, ow: int):
    """MAX pool whose forward is XLA's reduce_window (fuses with
    neighbors) and whose BACKWARD is the VMEM-resident Pallas kernel
    instead of select-and-scatter.  The primal IS ops/vision.max_pool —
    one home for the Caffe ceil-mode geometry.

    Domain restriction: the backward pads windows with a bf16-min
    sentinel (-3.3895e38) even in f32 mode (f32-min becomes -inf through
    the MXU's bf16 pass and NaN-poisons the one-hot gather), so an f32
    activation below bf16-min would lose its argmax to padding and
    mis-route the gradient.  No practical activation reaches -3.4e38;
    the next representable magnitude beyond the sentinel is -inf."""
    from .vision import max_pool
    return max_pool(x, kh, kw, sh, sw, ph, pw, oh, ow)


def _maxpool_vjp_fwd(x, kh, kw, sh, sw, ph, pw, oh, ow):
    return max_pool_vmem_bwd(x, kh, kw, sh, sw, ph, pw, oh, ow), x


def _maxpool_vjp_bwd(kh, kw, sh, sw, ph, pw, oh, ow, x, dy):
    return (_maxpool_bwd_call(x, dy, kh, kw, sh, sw, ph, pw, oh, ow),)


max_pool_vmem_bwd.defvjp(_maxpool_vjp_fwd, _maxpool_vjp_bwd)
