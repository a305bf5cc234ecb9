"""Fused-vs-reference parity gate for the vertical fusion pass.

The profile-driven conv+bias+relu(+pool/LRN) chain fusion
(``sparknet_tpu/graph/fusion.py`` planning, ``graph/net.py`` block
execution, ``ops/vision.py`` / ``ops/pallas_kernels.py`` LRN epilogues)
must be a pure THROUGHPUT optimization: fused execution has to
reproduce per-layer execution exactly.  This tool builds one synthetic
net containing every chain shape the planner emits —

    conv+bias+relu            (in-block, no epilogue op)
    conv+bias+relu+pool       (in-block)
    conv+bias+relu+LRN        (fused relu+lrn epilogue)
    conv+bias+relu+pool+LRN   (fused lrn epilogue)

— and FAILS unless, SPARKNET_FUSE=off vs =all, on this backend:

- the forward loss and every net-output blob are BIT-IDENTICAL in f32
  and under compute_dtype=bf16 (on CPU the fused primal forward lowers
  to the same op sequence as the per-layer path; on TPU the Pallas
  epilogue is held to the same equality — a failure there is a kernel
  bug, not tolerance);
- every parameter gradient matches within a documented ulp bound
  (rtol 1e-5 f32: the fused chains carry the closed-form custom VJP,
  which is the same arithmetic associated differently);
- the planner REFUSES a planted unfusable hotspot: a profile worklist
  naming a fan-out conv (two consumers) must come back in
  ``plan.refused`` with a reason, never silently fused or dropped;
- ``SPARKNET_FUSE=off`` really is the escape hatch: no chains planned,
  ``fuse_plan_id() == "off"``.

It also times the LRN-chain train step fused vs unfused (the worklist's
#1 chain class) and fails if fusion makes it >25% SLOWER — the win is
recorded, the gate only refuses a gross regression (CPU CI timers are
noisy; the committed BENCH/profile captures are the numbers of record).
``--iters 0`` skips the timing leg entirely (the in-tree smoke does:
at that size on a loaded box the timer measures the scheduler).

Wired into tools/run_tier1.sh behind SPARKNET_FUSEBENCH=1 (or
``--fusebench``); the same contracts run in-process in
tests/test_fusion.py.

Usage:
    python tools/fusebench.py [--batch 4] [--iters 6] [--out FILE]

Prints one JSON line on stdout; rc 0 = parity holds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_layers(batch: int, channels: int = 32, side: int = 14):
    from sparknet_tpu.models.dsl import (
        convolution_layer,
        inner_product_layer,
        layer,
        lrn_layer,
        pooling_layer,
        relu_layer,
        softmax_with_loss_layer,
    )
    wf = {"type": "gaussian", "std": 0.05}
    bf = {"type": "constant", "value": 0.1}
    return [
        layer("data", "Input", tops=["data", "label"],
              input_param={"shape": [{"dim": [batch, 3, side, side]},
                                     {"dim": [batch]}]}),
        # conv+bias+relu (in-block)
        convolution_layer("c1", "data", "c1", num_output=channels, kernel=3,
                          pad=1, weight_filler=wf, bias_filler=bf),
        relu_layer("r1", "c1", "c1"),
        # conv+bias+relu+pool (in-block)
        convolution_layer("c2", "c1", "c2", num_output=channels, kernel=3,
                          pad=1, weight_filler=wf, bias_filler=bf),
        relu_layer("r2", "c2", "c2"),
        pooling_layer("p2", "c2", "p2", kernel=2, stride=2),
        # conv+bias+relu+LRN (fused relu+lrn epilogue)
        convolution_layer("c3", "p2", "c3", num_output=channels, kernel=3,
                          pad=1, weight_filler=wf, bias_filler=bf),
        relu_layer("r3", "c3", "c3"),
        lrn_layer("n3", "c3", "n3", local_size=5, alpha=1e-4, beta=0.75),
        # conv+bias+relu+pool+LRN (fused lrn epilogue after the pool)
        convolution_layer("c4", "n3", "c4", num_output=channels, kernel=3,
                          pad=1, weight_filler=wf, bias_filler=bf),
        relu_layer("r4", "c4", "c4"),
        pooling_layer("p4", "c4", "p4", kernel=2, stride=2),
        lrn_layer("n4", "p4", "n4", local_size=3, alpha=2e-4, beta=0.5),
        inner_product_layer("ip", "n4", "ip", num_output=10,
                            weight_filler={"type": "gaussian", "std": 0.01}),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ]


EXPECTED_CHAINS = {
    "c1+r1": "none",
    "c2+r2+p2": "none",
    "c3+r3+n3": "relu+lrn",
    "c4+r4+p4+n4": "lrn",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--iters", type=int, default=6,
                    help="timed iterations of the LRN-chain microbench")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.graph import fusion
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.models.dsl import net_param
    from sparknet_tpu.proto.caffe_pb import NetState, Phase

    failures: list[str] = []
    netp = net_param("fusebench", _build_layers(args.batch))

    def build(fuse: str, dtype=None) -> Net:
        os.environ["SPARKNET_FUSE"] = fuse
        try:
            return Net(netp, NetState(Phase.TRAIN), compute_dtype=dtype)
        finally:
            os.environ.pop("SPARKNET_FUSE", None)

    net_off = build("off")
    net_all = build("all")

    # -- plan shape: every chain family present, escape hatch clean ------
    planned = {c.scope(): c.epilogue for c in net_all._fuse_plan.chains}
    if planned != EXPECTED_CHAINS:
        failures.append(f"planned chains {planned} != {EXPECTED_CHAINS}")
    if net_off.fuse_plan_id() != "off" or getattr(
            net_off, "_vfuse_head", None):
        failures.append("SPARKNET_FUSE=off still planned chains")

    # -- forward/backward parity, f32 ------------------------------------
    rng = jax.random.PRNGKey(0)
    params = net_off.init(rng)
    r = np.random.default_rng(0)
    ins = {"data": jnp.asarray(
        r.normal(size=net_off.input_blobs["data"]), jnp.float32),
        "label": jnp.asarray(
            r.integers(0, 10, size=net_off.input_blobs["label"]),
            jnp.float32)}

    def loss_fn(net):
        return lambda p: net.apply(p, ins, rng=rng).loss

    l_off, g_off = jax.value_and_grad(loss_fn(net_off))(params)
    l_all, g_all = jax.value_and_grad(loss_fn(net_all))(params)
    if float(l_off) != float(l_all):
        failures.append(
            f"f32 forward loss not bit-identical: {float(l_off)!r} "
            f"(off) vs {float(l_all)!r} (all)")
    grad_rel = 0.0
    for k in g_off:
        for a, b in zip(g_off[k], g_all[k]):
            a64 = np.asarray(a, np.float64)
            b64 = np.asarray(b, np.float64)
            denom = float(np.max(np.abs(a64))) or 1.0
            grad_rel = max(grad_rel,
                           float(np.max(np.abs(a64 - b64))) / denom)
    if grad_rel > 1e-5:
        failures.append(f"f32 gradient divergence {grad_rel:.3e} exceeds "
                        f"the 1e-5 ulp bound")

    # -- forward parity, bf16 compute ------------------------------------
    lb_off = float(loss_fn(build("off", jnp.bfloat16))(params))
    lb_all = float(loss_fn(build("all", jnp.bfloat16))(params))
    if lb_off != lb_all:
        failures.append(f"bf16 forward loss not bit-identical: "
                        f"{lb_off!r} vs {lb_all!r}")

    # -- planted-unfusable refusal ---------------------------------------
    # a worklist hotspot whose conv has TWO consumers (fan-out) names no
    # legal chain; the planner must record the refusal, not fuse or drop
    from sparknet_tpu.models.dsl import (
        concat_layer, convolution_layer, layer, relu_layer,
    )
    fan = net_param("fanout", [
        layer("data", "Input", tops=["data"],
              input_param={"shape": [{"dim": [1, 3, 8, 8]}]}),
        convolution_layer("hot", "data", "hot", num_output=4, kernel=3,
                          pad=1, weight_filler={"type": "xavier"}),
        relu_layer("hotrelu", "hot", "hotr"),
        concat_layer("skip", ["hot", "hotr"], "out"),
    ])
    os.environ["SPARKNET_FUSE"] = "off"
    try:
        fan_net = Net(fan, NetState(Phase.TEST))
    finally:
        os.environ.pop("SPARKNET_FUSE", None)
    fake_profile = {"by_layer": [
        {"op": "hot", "total_ms": 50.0, "pct": 40.0, "gb_per_s": 300.0,
         "gflops_per_s": 100.0},
        {"op": "neighbor", "total_ms": 30.0, "pct": 30.0,
         "gb_per_s": 1000.0},
    ]}
    plan = fusion.plan_from_profile(fan_net, fake_profile, source="planted")
    if plan.chains:
        failures.append(f"planner fused a fan-out conv: "
                        f"{[c.scope() for c in plan.chains]}")
    if not any(rf.get("candidate") == "hot" and rf.get("reason")
               for rf in plan.refused):
        failures.append(f"fan-out hotspot not refused with a reason: "
                        f"{plan.refused}")

    # -- LRN-chain microbench (report the win, refuse a regression) ------
    # --iters 0 skips the timing leg: at in-tree-smoke sizes under a
    # loaded CI box the timer is pure noise; the opt-in gate runs it at
    # a size where a real slowdown is distinguishable from scheduling
    timing: dict = {}
    if args.iters > 0:
        def timed(net) -> float:
            f = jax.jit(jax.value_and_grad(loss_fn(net)))
            _, g = f(params)
            jax.block_until_ready(g)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                _, g = f(params)
            jax.block_until_ready(g)
            return (time.perf_counter() - t0) / args.iters

        t_off = timed(net_off)
        t_all = timed(net_all)
        timing = {
            "unfused_step_ms": round(t_off * 1e3, 2),
            "fused_step_ms": round(t_all * 1e3, 2),
            "fused_speedup_x": round(t_off / t_all, 3) if t_all else None,
        }
        if t_all > 1.25 * t_off:
            failures.append(f"fused step {t_all * 1e3:.1f} ms is >25% "
                            f"slower than unfused {t_off * 1e3:.1f} ms")

    result = {
        "ok": not failures,
        "failures": failures,
        "backend": jax.default_backend(),
        "plan_id": net_all.fuse_plan_id(),
        "chains": planned,
        "grad_max_rel": grad_rel,
        "refused": plan.refused,
        **timing,
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[fusebench] PARITY FAILURE: {failures}", file=sys.stderr,
              flush=True)
        return 1
    t = (f"; LRN-chain step {timing['unfused_step_ms']} -> "
         f"{timing['fused_step_ms']} ms ({timing['fused_speedup_x']}x)"
         if timing else "")
    print(f"[fusebench] parity holds over {len(planned)} chain shapes "
          f"(grad ulp {grad_rel:.1e}){t}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    # a CPU parity gate unless told otherwise; the result names the backend
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    raise SystemExit(main())
