"""Profile the compiled train step and print a per-op time table.

The "where the time goes" tool the round-2 verdict demanded: capture a
``jax.profiler`` trace of N steps of the *same scanned train block the
benchmark times* (bench.py), parse the xplane.pb headlessly
(sparknet_tpu/utils/xplane.py), and print the device-plane op table plus
step-time and MFU so layout/precision experiments have a measured target.

Usage:
    python tools/profile_step.py [--model caffenet] [--batch 256]
        [--iters 20] [--dtype bf16] [--out profiles/caffenet] [--eval]

``--eval`` profiles the forward-only eval pass instead (the `caffe
time` forward leg): the scanned test-net forward with eval MFU in the
summary, written to profiles/<model>[_bf16]_eval by default.

The reference's closest analog is `caffe time` (per-layer fwd/bwd timing,
caffe/tools/caffe.cpp:290-376); this is per-XLA-op, post-fusion — the
view that actually explains TPU step time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="caffenet",
                    choices=["caffenet", "googlenet", "vgg16", "lenet"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--out", default=None,
                    help="trace dir (default profiles/<model>)")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--eval", action="store_true",
                    help="profile the forward-only eval pass (the "
                         "test-net `caffe time` forward leg) instead of "
                         "the train step — eval MFU in the summary")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver
    from sparknet_tpu.utils import xplane
    from sparknet_tpu.utils.compile_cache import use_compile_cache
    from sparknet_tpu.utils.profiling import (
        BENCH_SOLVER_PROTOTXT,
        build_bench_model,
        eval_cost_flops,
        peak_flops,
        scanned_eval_block,
        scanned_train_block,
        step_cost_flops,
    )

    use_compile_cache()
    net, in_shape, classes = build_bench_model(args.model, args.batch)
    sp = load_solver_prototxt_with_net(BENCH_SOLVER_PROTOTXT, net)
    solver = Solver(sp, seed=0,
                    compute_dtype=jnp.bfloat16 if args.dtype == "bf16" else None)

    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.normal(size=(args.batch,) + in_shape).astype(np.float32))
    label = jnp.asarray(rng.integers(0, classes, size=(args.batch,)).astype(np.float32))
    batch = {"data": data[None], "label": label[None]}

    params, state = solver.params, solver.state
    step_rng = jax.random.PRNGKey(0)

    # cost_analysis of the fori_loop block would undercount (the while body
    # is costed once); cost the single step, exactly as bench.py does
    if args.eval:
        eval_batch = {"data": data, "label": label}
        block = scanned_eval_block(solver, args.iters)
        flops_per_step = eval_cost_flops(solver, eval_batch)

        def run_block(s):
            return block(params, eval_batch, s)
    else:
        block = scanned_train_block(solver, args.iters)
        flops_per_step = step_cost_flops(solver, batch)

    t0 = time.perf_counter()
    if args.eval:
        tap = run_block(jnp.zeros(()))
        jax.block_until_ready(tap)
    else:
        params, state, step_rng, loss = block(params, state, 0, batch,
                                              step_rng)
        jax.block_until_ready(loss)
    print(f"[profile] compile+warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    out_dir = args.out or os.path.join(
        "profiles",
        args.model + ("_bf16" if args.dtype == "bf16" else "")
        + ("_eval" if args.eval else ""))
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(out_dir)
    if args.eval:
        tap = run_block(jnp.ones(()))
        jax.block_until_ready(tap)
    else:
        params, state, step_rng, loss = block(params, state, args.iters,
                                              batch, step_rng)
        jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    dt = time.perf_counter() - t0
    step_s = dt / args.iters

    dev = jax.devices()[0]
    peak = peak_flops(dev.device_kind)
    mfu = (flops_per_step / step_s / peak) if (flops_per_step and peak) else None

    # CPU-runtime traces carry instruction names but no scope stats; the
    # optimized HLO of the SAME compiled block supplies the
    # name -> op_name join that recovers L[...] layer attribution
    # (xplane.hlo_layer_map).  Cheap on re-compile: the persistent
    # compilation cache already holds this executable.
    layer_map = None
    try:
        if args.eval:
            lowered = block.lower(params, eval_batch, jnp.zeros(()))
        else:
            lowered = block.lower(params, state, 0, batch, step_rng)
        layer_map = xplane.hlo_layer_map(lowered.compile().as_text())
    except Exception as e:
        print(f"[profile] no HLO layer map: {e}", file=sys.stderr)

    tables = xplane.op_tables(out_dir, top=args.top, layer_map=layer_map)
    print(xplane.format_tables(tables))
    # the profiled net's vertical-fusion plan id, stamped into the
    # summary (the perf-ledger fingerprint field)
    prof_net = solver.test_net if args.eval else solver.train_net
    summary = {
        "model": args.model, "batch": args.batch, "dtype": args.dtype,
        "mode": "eval_forward" if args.eval else "train_step",
        "fuse_plan": prof_net.fuse_plan_id(),
        "device": f"{dev.platform}/{dev.device_kind}",
        "step_ms": round(step_s * 1e3, 2),
        "img_s": round(args.batch / step_s, 1),
        "mfu": round(mfu, 4) if mfu else None,
        "flops_per_step": flops_per_step,
        "trace_dir": out_dir,
    }
    busy_s = tables["total_ms"] / args.iters / 1e3
    summary["device_busy_ms_per_step"] = round(busy_s * 1e3, 2)
    if flops_per_step and peak and busy_s:
        # wall includes host dispatch; the device-busy MFU is the number
        # that reflects the compiled step
        summary["mfu_device_busy"] = round(flops_per_step / busy_s / peak, 4)
    print(json.dumps(summary))
    with open(os.path.join(out_dir, "op_table.json"), "w") as f:
        json.dump({"summary": summary, **tables}, f, indent=1)


if __name__ == "__main__":
    main()
