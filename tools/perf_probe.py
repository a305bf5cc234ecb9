"""Where-the-time-goes probe for the headline bench (VERDICT r2 item 1).

Every number here is a block-granular measurement: each experiment runs `iters` chained repetitions of the op inside ONE
compiled fori_loop (a scalar tap from each output feeds a tiny
perturbation of the next iteration's *weights*, so XLA can neither DCE
nor hoist the op), with block_until_ready around the whole block and the
median of `reps` blocks reported.

Parts (select with argv, default all):
  ops    — isolated fwd and fwd+bwd cost of every CaffeNet-shaped
           conv/fc/LRN/pool, in NCHW vs NHWC, plus a space-to-depth
           variant of conv1 (C=3 occupies 3/128 MXU lanes; s2d repacks
           the stride-4 11x11 conv as a stride-1 conv at C=48).
  net    — full CaffeNet train-step ablations on the real Solver:
           baseline / no-LRN / no-dropout / eval-forward, batch 256.
  hlo    — transpose/copy census of the optimized HLO for the compiled
           train step (layout-assignment cost evidence).
  lrn    — the cross-channel LRN window sum as reduce_window (default)
           vs the prefix-sum-difference reformulation, use_cumsum=
           passed per variant (VERDICT r5 weak #2), fwd and fwd+bwd, at both
           LRN-bearing headline models' shapes.  PROBE_LRN_DTYPE=f32
           switches from the bf16 default.

Usage: python tools/perf_probe.py [ops|net|hlo|poolbwd|lrn ...]
       [--platform cpu]
Prints one JSON line per experiment to stdout; diagnostics to stderr.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("PROBE_BATCH", 256))
REPS = int(os.environ.get("PROBE_REPS", 3))


def log(msg: str) -> None:
    print(f"[probe] {msg}", file=sys.stderr, flush=True)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# Block timer
# ---------------------------------------------------------------------------

TARGET_BLOCK_S = float(os.environ.get("PROBE_TARGET_S", 2.0))


def time_block(name: str, make_iter, iters: int = 0,
               extra: dict | None = None):
    """make_iter(s) -> new scalar s; time chained evaluations.

    The trip count is a *traced* fori_loop bound (one compile) calibrated
    per experiment until the block runs ≥ TARGET_BLOCK_S; the per-dispatch
    floor is then subtracted out by differencing two block sizes (N and
    N/2).

    A candidate that RAISES (Pallas kernel on CPU, an op a backend can't
    lower, OOM on a small rig) records a typed ``skipped`` entry and
    returns None instead of aborting the whole probe run — callers must
    treat a None per-iter time as "no measurement", never 0."""
    try:
        return _time_block_measured(name, make_iter, extra)
    except Exception as e:  # noqa: BLE001 — typed skip, not abort
        msg = str(e).strip().split("\n")[0][:200]
        reason = f"{type(e).__name__}: {msg}" if msg else type(e).__name__
        emit({"exp": name, "skipped": reason, **(extra or {})})
        log(f"{name}: SKIPPED ({reason})")
        return None


def _time_block_measured(name: str, make_iter, extra: dict | None = None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def block(s, n):
        # no explicit unroll kwarg: it is already the default, and some
        # jax versions reject it outright when the bound is traced
        return lax.fori_loop(0, n, lambda i, s: make_iter(s), s)

    s0 = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(block(s0, 4))
    compile_s = time.perf_counter() - t0

    # calibrate N for the target block time
    n = 64
    while True:
        t0 = time.perf_counter()
        jax.block_until_ready(block(s0, n))
        dt = time.perf_counter() - t0
        if dt >= TARGET_BLOCK_S or n >= 1 << 16:
            break
        n = min(max(int(n * TARGET_BLOCK_S / max(dt, 1e-3) * 1.3), n * 2),
                1 << 16)

    full, half = [], []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(block(s0, n))
        full.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(block(s0, n // 2))
        half.append(time.perf_counter() - t0)
    fmed = sorted(full)[len(full) // 2]
    hmed = sorted(half)[len(half) // 2]
    per_iter_ms = (fmed - hmed) / (n - n // 2) * 1e3  # floor cancels
    rec = {"exp": name, "ms_per_iter": round(per_iter_ms, 4),
           "block_s": round(fmed, 3), "iters": n,
           "compile_s": round(compile_s, 1), **(extra or {})}
    if n >= 1 << 16 and fmed < TARGET_BLOCK_S / 4:
        # 65k reps finishing "instantly" = XLA elided the op; the number
        # is NOT a measurement
        rec["collapsed"] = True
    emit(rec)
    log(f"{name}: {per_iter_ms:.3f} ms/iter (block {fmed:.2f}s @ {n}, "
        f"compile {compile_s:.0f}s)")
    return per_iter_ms


# ---------------------------------------------------------------------------
# Part A: isolated ops
# ---------------------------------------------------------------------------

# CaffeNet conv shapes at batch 256 (in_c, h, w, out_c, k, stride, pad, group)
CONVS = {
    "conv1": (3, 227, 227, 96, 11, 4, 0, 1),
    "conv2": (96, 27, 27, 256, 5, 1, 2, 2),
    "conv3": (256, 13, 13, 384, 3, 1, 1, 1),
    "conv4": (384, 13, 13, 384, 3, 1, 1, 2),
    "conv5": (384, 13, 13, 256, 3, 1, 1, 2),
}
FCS = {"fc6": (9216, 4096), "fc7": (4096, 4096), "fc8": (4096, 1000)}


def run_ops() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.default_rng(0)

    def conv_iter_fn(x, w, strides, pad, group, dn, backward):
        def it(s):
            wp = w + s * 1e-30

            def f(xx, ww):
                return lax.conv_general_dilated(
                    xx, ww, strides, pad, feature_group_count=group,
                    dimension_numbers=dn)

            if backward:
                y, vjp = jax.vjp(f, x, wp)
                # cotangent must depend on the carry too, else dw =
                # conv(x, cot) is loop-invariant and XLA hoists it
                dx, dw = vjp(jnp.ones_like(y) * (1.0 + s * 1e-30))
                return (jnp.sum(y) + jnp.sum(dx) + jnp.sum(dw)) * 1e-30
            return jnp.sum(f(x, wp)) * 1e-30
        return it

    def conv_flops(ci, h, w_, co, k, st, pd, g):
        oh = (h + 2 * pd - k) // st + 1
        return 2 * BATCH * oh * oh * co * (ci // g) * k * k

    only = os.environ.get("PROBE_ONLY", "")
    only_list = [t for t in only.split(",") if t]

    def wanted(name: str) -> bool:
        return not only_list or any(name.startswith(t) for t in only_list)

    for lname, (ci, h, w_, co, k, st, pd, g) in CONVS.items():
        if not wanted(lname):
            continue
        fl = conv_flops(ci, h, w_, co, k, st, pd, g)
        for layout in ("NCHW", "NHWC"):
            if layout == "NCHW":
                x = jnp.asarray(rng.normal(size=(BATCH, ci, h, w_)),
                                jnp.float32)
                dn = ("NCHW", "OIHW", "NCHW")
            else:
                x = jnp.asarray(rng.normal(size=(BATCH, h, w_, ci)),
                                jnp.float32)
                dn = ("NHWC", "HWIO", "NHWC")
            wshape = ((co, ci // g, k, k) if layout == "NCHW"
                      else (k, k, ci // g, co))
            wt = jnp.asarray(rng.normal(size=wshape) * 0.01, jnp.float32)
            for backward in (False, True):
                if backward and layout == "NHWC" and g > 1:
                    # grouped NHWC conv backward FAULTS the v5e chip
                    # (kernel fault -> UNAVAILABLE; XLA bug) — skip
                    emit({"exp": f"{lname}_NHWC_fb", "skipped":
                          "grouped NHWC bwd faults the TPU (XLA bug)"})
                    continue
                tag = "fb" if backward else "fwd"
                time_block(
                    f"{lname}_{layout}_{tag}",
                    conv_iter_fn(x, wt, (st, st), ((pd, pd), (pd, pd)), g, dn,
                                 backward),
                    extra={"gflops": round(fl * (3 if backward else 1) / 1e9,
                                           1)})

    # conv1 space-to-depth: 227x227x3 s4 11x11 -> pad to 228, reshape to
    # 57x57x48 (4x4 blocks), k=3 stride 1 equivalent channel-packed conv.
    # We time the exact-FLOPs repacked conv (weights repacked offline).
    x = jnp.asarray(rng.normal(size=(BATCH, 228, 228, 3)), jnp.float32)
    xs2d = x.reshape(BATCH, 57, 4, 57, 4, 3).transpose(0, 1, 3, 2, 4, 5)
    xs2d = xs2d.reshape(BATCH, 57, 57, 48)
    # 11x11 kernel at stride 4 -> 3x3 kernel over 4x4 blocks needs k=12 cover:
    # pad kernel 11->12, reshape (12,12,3,96) -> (3,3,48,96)
    wt = jnp.asarray(rng.normal(size=(12, 12, 3, 96)) * 0.01, jnp.float32)
    ws2d = wt.reshape(3, 4, 3, 4, 3, 96).transpose(0, 2, 1, 3, 4, 5)
    ws2d = ws2d.reshape(3, 3, 48, 96)

    def s2d_iter(backward):
        def it(s):
            wp = ws2d + s * 1e-30

            def f(xx, ww):
                return lax.conv_general_dilated(
                    xx, ww, (1, 1), ((0, 0), (0, 0)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if backward:
                y, vjp = jax.vjp(f, xs2d, wp)
                dx, dw = vjp(jnp.ones_like(y) * (1.0 + s * 1e-30))
                return (jnp.sum(y) + jnp.sum(dx) + jnp.sum(dw)) * 1e-30
            return jnp.sum(f(xs2d, wp)) * 1e-30
        return it

    if wanted("conv1_s2d"):
        time_block("conv1_s2d_NHWC_fwd", s2d_iter(False))
        time_block("conv1_s2d_NHWC_fb", s2d_iter(True))

    # FC layers
    for lname, (cin, cout) in FCS.items():
        if not wanted(lname):
            continue
        xf = jnp.asarray(rng.normal(size=(BATCH, cin)), jnp.float32)
        wf = jnp.asarray(rng.normal(size=(cin, cout)) * 0.01, jnp.float32)

        def fc_iter(xf=xf, wf=wf, backward=True):
            def it(s):
                wp = wf + s * 1e-30

                def f(xx, ww):
                    return xx @ ww
                y, vjp = jax.vjp(f, xf, wp)
                dx, dw = vjp(jnp.ones_like(y) * (1.0 + s * 1e-30))
                return (jnp.sum(y) + jnp.sum(dx) + jnp.sum(dw)) * 1e-30
            return it
        time_block(f"{lname}_fb", fc_iter(), 60)

    # LRN + pool at CaffeNet stage-1/2 shapes (these perturb x, so ~one
    # extra elementwise pass over x is included; note in analysis)
    from sparknet_tpu.ops.vision import ave_pool, max_pool
    for lname, shape in (("norm1", (BATCH, 96, 27, 27)),
                         ("norm2", (BATCH, 256, 13, 13))):
        if not wanted(lname):
            continue
        xl = jnp.asarray(rng.normal(size=shape), jnp.float32)

        def lrn_iter(xl=xl, backward=True):
            def it(s):
                xp = xl + s * 1e-30

                def f(xx):
                    sq = xx * xx
                    ssum = lax.reduce_window(
                        sq, 0.0, lax.add, (1, 5, 1, 1), (1, 1, 1, 1),
                        ((0, 0), (2, 2), (0, 0), (0, 0)))
                    return xx / (1.0 + (1e-4 / 5) * ssum) ** 0.75
                if backward:
                    y, vjp = jax.vjp(f, xp)
                    (dx,) = vjp(jnp.ones_like(y))
                    return (jnp.sum(y) + jnp.sum(dx)) * 1e-30
                return jnp.sum(f(xp)) * 1e-30
            return it
        time_block(f"{lname}_fb", lrn_iter(), 60)

    for lname, (shape, oh) in (("pool1", ((BATCH, 96, 55, 55), 27)),
                               ("pool2", ((BATCH, 256, 27, 27), 13)),
                               ("pool5", ((BATCH, 256, 13, 13), 6))):
        if not wanted(lname):
            continue
        xp_ = jnp.asarray(rng.normal(size=shape), jnp.float32)

        def pool_iter(xp_=xp_, oh=oh):
            def it(s):
                xq = xp_ + s * 1e-30

                def f(xx):
                    return max_pool(xx, 3, 3, 2, 2, 0, 0, oh, oh)
                y, vjp = jax.vjp(f, xq)
                (dx,) = vjp(jnp.ones_like(y))
                return (jnp.sum(y) + jnp.sum(dx)) * 1e-30
            return it
        time_block(f"{lname}_fb", pool_iter(), 60)


# ---------------------------------------------------------------------------
# Part B: full-net ablations
# ---------------------------------------------------------------------------

def _strip_layers(net, names: set[str]):
    """Remove layers by name, rewiring consumers of their tops to their
    bottoms (valid for in-place-style unary layers like LRN/Dropout)."""
    rewire: dict[str, str] = {}
    kept = []
    for lp in net.layer:
        if lp.name in names:
            rewire[lp.top[0]] = lp.bottom[0]
        else:
            kept.append(lp)
    for lp in kept:
        lp.bottom = [rewire.get(b, b) for b in lp.bottom]
    return dataclasses.replace(net, layer=kept)


def run_net() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from sparknet_tpu.models import caffenet
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver

    solver_txt = ('base_lr: 0.01\nmomentum: 0.9\nweight_decay: 0.0005\n'
                  'lr_policy: "step"\ngamma: 0.1\nstepsize: 100000\n')
    variants = {
        "baseline": lambda n: n,
        "no_lrn": lambda n: _strip_layers(n, {"norm1", "norm2"}),
        "no_dropout": lambda n: _strip_layers(n, {"drop6", "drop7"}),
        "no_lrn_no_drop": lambda n: _strip_layers(
            n, {"norm1", "norm2", "drop6", "drop7"}),
    }
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.normal(size=(1, BATCH, 3, 227, 227)), jnp.float32)
    label = jnp.asarray(rng.integers(0, 1000, size=(1, BATCH)), jnp.float32)
    batch = {"data": data, "label": label}
    iters = int(os.environ.get("PROBE_NET_ITERS", 60))

    for vname, tf in variants.items():
        net = tf(caffenet(BATCH, BATCH))
        sp = load_solver_prototxt_with_net(solver_txt, net)
        solver = Solver(sp, seed=0)
        raw_step = solver.make_train_step()

        def block_fn(params, state, rng):
            def body(i, carry):
                params, state, rng, _ = carry
                rng, sub = jax.random.split(rng)
                params, state, loss = raw_step(params, state, i, batch, sub)
                return (params, state, rng, loss)
            return lax.fori_loop(0, iters, body,
                                 (params, state, rng, jnp.zeros(())))
        block = jax.jit(block_fn)

        t0 = time.perf_counter()
        out = block(solver.params, solver.state, jax.random.PRNGKey(0))
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            out = block(solver.params, solver.state, jax.random.PRNGKey(0))
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        med = sorted(times)[len(times) // 2]
        emit({"exp": f"net_{vname}", "ms_per_step": round(med / iters * 1e3, 3),
              "img_s": round(BATCH * iters / med, 1),
              "compile_s": round(compile_s, 1)})
        log(f"net_{vname}: {med / iters * 1e3:.2f} ms/step "
            f"({BATCH * iters / med:.0f} img/s)")

    # eval forward for scale
    net = caffenet(BATCH, BATCH)
    sp = load_solver_prototxt_with_net(solver_txt, net)
    solver = Solver(sp, seed=0)
    ebatch = {"data": data[0], "label": label[0]}
    out = solver._test_fwd(solver.params, ebatch)
    jax.block_until_ready(out)
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = solver._test_fwd(solver.params, ebatch)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    med = sorted(times)[len(times) // 2]
    emit({"exp": "net_eval_fwd", "ms_per_step": round(med / iters * 1e3, 3),
          "img_s": round(BATCH * iters / med, 1)})
    log(f"net_eval_fwd: {med / iters * 1e3:.2f} ms/step")


# ---------------------------------------------------------------------------
# Part B2: GoogLeNet maxpool backward — select-and-scatter vs the
# VMEM-resident Pallas kernel (VERDICT r3 item 6).  All 13 pools of
# bvlc_googlenet at PROBE_BATCH, fwd+bwd per pool, both paths.
# ---------------------------------------------------------------------------

# (name, c, h/w, kernel, stride, pad) — models/googlenet geometry
GOOGLENET_POOLS = [
    ("pool1_112", 64, 112, 3, 2, 0),
    ("pool2_56", 192, 56, 3, 2, 0),
    ("icp3a_28", 192, 28, 3, 1, 1),
    ("icp3b_28", 256, 28, 3, 1, 1),
    ("pool3_28", 480, 28, 3, 2, 0),
    ("icp4a_14", 480, 14, 3, 1, 1),
    ("icp4b_14", 512, 14, 3, 1, 1),
    ("icp4c_14", 512, 14, 3, 1, 1),
    ("icp4d_14", 512, 14, 3, 1, 1),
    ("icp4e_14", 528, 14, 3, 1, 1),
    ("pool4_14", 832, 14, 3, 2, 0),
    ("icp5a_7", 832, 7, 3, 1, 1),
    ("icp5b_7", 832, 7, 3, 1, 1),
]


def run_poolbwd() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops.pallas_kernels import max_pool_vmem_bwd
    from sparknet_tpu.ops.vision import max_pool, pool_output_size

    rng = np.random.default_rng(0)
    batch = int(os.environ.get("PROBE_POOL_BATCH", 128))
    dtype = jnp.bfloat16 if os.environ.get(
        "PROBE_DTYPE", "bf16") == "bf16" else jnp.float32
    totals = {"s&s": 0.0, "pallas": 0.0}
    skipped: set = set()
    for name, c, hw, k, s, p in GOOGLENET_POOLS:
        oh, ow = pool_output_size(hw, hw, k, k, s, s, p, p)
        x = jnp.asarray(
            np.maximum(rng.normal(size=(batch, c, hw, hw)), 0), dtype)

        def make_iter(fn):
            def it(sc):
                # cast the f32 loop scalar BEFORE the add: bf16 + f32
                # would silently promote the timed tensor to f32
                xq = x + (sc * 1e-30).astype(dtype)

                def f(xx):
                    return fn(xx, k, k, s, s, p, p, oh, ow)
                y, vjp = jax.vjp(f, xq)
                (dx,) = vjp(jnp.ones_like(y))
                return (jnp.sum(y) + jnp.sum(dx)).astype(jnp.float32) * 1e-30
            return it

        for label, fn in (("ss", max_pool), ("pallas", max_pool_vmem_bwd)):
            ms = time_block(f"poolbwd_{name}_{label}", make_iter(fn), 0,
                            extra={"c": c, "hw": hw, "stride": s,
                                   "batch": batch, "dtype": str(dtype.__name__)})
            key = "s&s" if label == "ss" else "pallas"
            if ms is None:  # typed skip (e.g. Pallas on CPU) — a total
                skipped.add(key)  # with holes would read as a win
            else:
                totals[key] += ms
    emit({"exp": "poolbwd_total_ms_per_step",
          "select_and_scatter": (None if "s&s" in skipped
                                 else round(totals["s&s"], 3)),
          "pallas_vmem": (None if "pallas" in skipped
                          else round(totals["pallas"], 3)),
          "incomplete": sorted(skipped) or None,
          "note": "sum over all 13 GoogLeNet pools, fwd+bwd per iter"})
    log(f"poolbwd totals: s&s {totals['s&s']:.2f} ms vs pallas "
        f"{totals['pallas']:.2f} ms per step-equivalent")


# ---------------------------------------------------------------------------
# Part: LRN window-sum reformulation (VERDICT r5 weak #2)
# ---------------------------------------------------------------------------

def run_lrn() -> None:
    """reduce_window vs prefix-sum-difference cross-channel LRN,
    forward and forward+backward, at the LRN shapes of both LRN-bearing
    headline models.  Each variant is ``ops.vision.LRNLayer``'s
    ACROSS_CHANNELS formula over ``lrn_window_sum`` with ``use_cumsum=``
    passed (``auto``: left to ``lrn_use_cumsum``), so each compiles its
    own block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops.vision import lrn_window_sum

    size, alpha, beta, k = 5, 1e-4, 0.75, 1.0
    pre = (size - 1) // 2
    post = size - 1 - pre
    dtype = (jnp.float32 if os.environ.get("PROBE_LRN_DTYPE") == "f32"
             else jnp.bfloat16)
    rng = np.random.default_rng(0)
    div = max(1, int(os.environ.get("PROBE_LRN_BATCH_DIV", "1") or 1))
    shapes = {
        f"googlenet_norm1_b{128 // div}": (128 // div, 64, 56, 56),
        f"googlenet_norm2_b{128 // div}": (128 // div, 192, 56, 56),
        f"caffenet_norm1_b{256 // div}": (256 // div, 96, 55, 55),
        f"caffenet_norm2_b{256 // div}": (256 // div, 256, 27, 27),
    }
    only = os.environ.get("PROBE_LRN_SHAPES", "")
    if only:  # comma-separated substring filter (CPU smokes)
        shapes = {k: v for k, v in shapes.items()
                  if any(s and s in k for s in only.split(","))}
    results: dict[str, dict[str, float]] = {}
    for name, shape in shapes.items():
        x = jnp.asarray(rng.normal(size=shape), dtype)
        nbytes = x.size * x.dtype.itemsize
        # the shipping default is measured as its own variant so the
        # flip is auditable
        for variant, use_cumsum in (("reduce_window", False),
                                    ("cumsum", True), ("auto", None)):
            def loss(xx, use_cumsum=use_cumsum):
                scale = k + (alpha / size) * lrn_window_sum(
                    xx * xx, pre, post, use_cumsum=use_cumsum)
                return jnp.mean(xx / scale ** beta).astype(jnp.float32)

            def fwd(s, x=x, loss=loss):
                return loss(x + s.astype(dtype))

            def fwdbwd(s, x=x, loss=loss):
                g = jax.grad(loss)(x + s.astype(dtype))
                return jnp.mean(g).astype(jnp.float32)

            extra = {"shape": list(shape), "dtype": str(jnp.dtype(dtype))}
            f_ms = time_block(f"lrn_{name}_{variant}_fwd", fwd,
                              extra=extra)
            fb_ms = time_block(f"lrn_{name}_{variant}_fwdbwd", fwdbwd,
                               extra=extra)
            # None = typed skip (time_block contract) — leave the
            # variant out of the verdict rather than divide by it
            if fb_ms is not None:
                results.setdefault(name, {})[variant] = fb_ms
            if f_ms is not None:
                # effective traffic at the fwd floor: read x, write y
                results.setdefault(name, {})[f"{variant}_fwd_gbps"] = \
                    round(2 * nbytes / max(f_ms, 1e-6) / 1e6, 1)
    verdict = {
        name: {"speedup_fwdbwd": (
                   round(r["reduce_window"] / max(r["cumsum"], 1e-9), 3)
                   if "reduce_window" in r and "cumsum" in r else None),
               **{k: v for k, v in r.items()}}
        for name, r in results.items()}
    emit({"exp": "lrn_verdict", "dtype": str(jnp.dtype(dtype)),
          "per_shape": verdict})


# ---------------------------------------------------------------------------
# Part C: HLO transpose census
# ---------------------------------------------------------------------------

def run_hlo() -> None:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.models import caffenet
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver

    net = caffenet(BATCH, BATCH)
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.01\nmomentum: 0.9\nweight_decay: 0.0005\n'
        'lr_policy: "step"\ngamma: 0.1\nstepsize: 100000\n', net)
    solver = Solver(sp, seed=0)
    rng = np.random.default_rng(0)
    batch = {"data": jnp.asarray(rng.normal(size=(1, BATCH, 3, 227, 227)),
                                 jnp.float32),
             "label": jnp.asarray(rng.integers(0, 1000, size=(1, BATCH)),
                                  jnp.float32)}
    compiled = solver._step.lower(solver.params, solver.state, 0, batch,
                                  jax.random.PRNGKey(1)).compile()
    txt = compiled.as_text()
    ops: dict[str, int] = {}
    bytes_by_op: dict[str, float] = {}
    for line in txt.splitlines():
        m = re.search(r"=\s+\S+\s+([\w-]+)\(", line)
        mshape = re.search(r"=\s+f32\[([\d,]*)\]", line)
        if not m:
            continue
        op = m.group(1)
        ops[op] = ops.get(op, 0) + 1
        if mshape and op in ("transpose", "copy", "reshape"):
            dims = [int(d) for d in mshape.group(1).split(",") if d]
            nbytes = 4 * int(np.prod(dims)) if dims else 4
            bytes_by_op[op] = bytes_by_op.get(op, 0.0) + nbytes
    top = dict(sorted(ops.items(), key=lambda kv: -kv[1])[:25])
    emit({"exp": "hlo_census", "op_counts": top,
          "layout_bytes_mb": {k: round(v / 1e6, 1)
                              for k, v in bytes_by_op.items()},
          "n_lines": len(txt.splitlines())})
    outp = os.environ.get("PROBE_HLO_OUT")
    if outp:
        with open(outp, "w") as f:
            f.write(txt)
        log(f"HLO written to {outp}")


if __name__ == "__main__":
    argv = list(sys.argv[1:])
    if "--platform" in argv:
        i = argv.index("--platform")
        plat = argv[i + 1]
        del argv[i:i + 2]
        import jax
        jax.config.update("jax_platforms", plat)
    parts = [a for a in argv if not a.startswith("-")] or ["ops", "net", "hlo"]
    import jax
    from sparknet_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev.platform}/{dev.device_kind}")
    emit({"exp": "device", "device": f"{dev.platform}/{dev.device_kind}",
          "batch": BATCH})
    for p in parts:
        {"ops": run_ops, "net": run_net, "hlo": run_hlo,
         "poolbwd": run_poolbwd, "lrn": run_lrn}[p]()
