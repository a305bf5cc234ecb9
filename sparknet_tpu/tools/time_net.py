"""Per-layer forward/backward microbenchmark — the ``caffe time`` analog.

Methodology follows the reference's timing tool (reference:
caffe/tools/caffe.cpp:290-376 ``time()``: average per-layer forward and
backward milliseconds over N iterations, plus whole-net numbers).  One
honest difference is called out in the output: under XLA the whole net
compiles into fused programs, so per-layer times are measured by running
layer-sized jitted programs in isolation — they bound, rather than
partition, the fused whole-net time (which is also reported, and is the
number that matters on TPU).

Run:  python -m sparknet_tpu.tools.time_net --model caffenet --iterations 10
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def time_fn(fn, args, iters: int, warmup: int = 2) -> float:
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="per-layer fwd/bwd timing")
    ap.add_argument("--model", default="caffenet",
                    choices=["lenet", "cifar10_quick", "cifar10_full",
                             "alexnet", "caffenet", "googlenet", "vgg16"])
    ap.add_argument("--prototxt", default=None,
                    help="time a prototxt net instead of a zoo model")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--per-layer", action="store_true",
                    help="also time each layer in isolation (slow)")
    ap.add_argument("--trace", action="store_true",
                    help="profile the FUSED fwd+bwd program and print the "
                         "per-layer device-time partition (L[...] scopes "
                         "via jax.profiler; the `caffe time` view that is "
                         "actually true post-fusion; a layer's row holds "
                         "its casts, and in a trace of a whole step or "
                         "round the step.* and round.* rows are phases, "
                         "not layers)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: temp)")
    args = ap.parse_args(argv)

    from ..utils.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    import jax.numpy as jnp

    from .. import models
    from ..graph import Net
    from ..proto import NetState, Phase, load_net_prototxt

    if args.prototxt:
        net_param = load_net_prototxt(args.prototxt)
    else:
        kw = {}
        if args.batch:
            kw = dict(train_batch=args.batch, test_batch=args.batch)
        net_param = getattr(models, args.model)(**kw)
    net = Net(net_param, NetState(Phase.TRAIN))
    rng = jax.random.PRNGKey(0)
    params = net.init(rng)
    npr = np.random.default_rng(0)
    inputs = {name: jnp.asarray(npr.normal(size=shape).astype(np.float32))
              for name, shape in net.input_blobs.items()}

    @jax.jit
    def fwd(params, inputs):
        return net.apply(params, inputs, train=True,
                         rng=jax.random.PRNGKey(1)).loss

    @jax.jit
    def fwdbwd(params, inputs):
        loss, grads = jax.value_and_grad(
            lambda p: net.apply(p, inputs, train=True,
                                rng=jax.random.PRNGKey(1)).loss)(params)
        return loss, grads

    f_ms = time_fn(fwd, (params, inputs), args.iterations)
    fb_ms = time_fn(fwdbwd, (params, inputs), args.iterations)
    print(f"Average Forward pass:          {f_ms:10.3f} ms")
    print(f"Average Forward-Backward:      {fb_ms:10.3f} ms")
    print(f"  (backward ≈ {fb_ms - f_ms:.3f} ms by subtraction; XLA fuses "
          f"the whole net, so whole-net numbers are the real TPU cost)")

    if args.trace:
        import tempfile

        from ..utils import xplane

        out_dir = args.trace_dir or tempfile.mkdtemp(prefix="time_net_")
        jax.profiler.start_trace(out_dir)
        for _ in range(args.iterations):
            out = fwdbwd(params, inputs)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        try:
            tables = xplane.op_tables(out_dir)
        except (ValueError, FileNotFoundError) as e:
            print(f"\n(per-layer trace needs a TPU/GPU device plane — "
                  f"{e}; trace kept at {out_dir})")
            tables = {}
        rows = tables.get("by_layer")
        if rows:
            print(f"\nPer-layer device time over {args.iterations} fused "
                  f"fwd+bwd iterations (trace: {out_dir}):")
            print(f"{'layer':<28} {'ms/iter':>10} {'%':>6} "
                  f"{'GF/s':>9} {'GB/s':>8}")
            for r in rows:
                print(f"{r['op']:<28} "
                      f"{r['total_ms'] / args.iterations:>10.3f} "
                      f"{r['pct']:>6.1f} {r['gflops_per_s']:>9.1f} "
                      f"{r['gb_per_s']:>8.1f}")
        else:
            print("\n(trace captured no L[...] layer scopes — platform "
                  f"without XLA op events? trace: {out_dir})")

    if args.per_layer:
        print(f"{'layer':<28} {'type':<18} {'fwd ms':>10}")
        blobs = dict(inputs)
        for node in net.nodes:
            if getattr(node.impl, "is_input", lambda: False)():
                continue
            p = net.node_params(params, node)
            bots = [blobs[b] for b in node.bottoms]
            lrng = jax.random.PRNGKey(2)

            def one(p, bots, node=node, lrng=lrng):
                out = node.impl.apply(node.lp, p, bots, True, lrng)
                return out[0] if isinstance(out, tuple) else out

            jit_one = jax.jit(one)
            try:
                ms = time_fn(jit_one, (p, bots), args.iterations)
                print(f"{node.lp.name:<28} {node.lp.type:<18} {ms:>10.3f}")
            except Exception as e:  # non-jittable layer (e.g. Filter)
                print(f"{node.lp.name:<28} {node.lp.type:<18} "
                      f"{'skipped: ' + type(e).__name__:>10}")
            tops = node.impl.apply(node.lp, p, bots, True, lrng)
            if getattr(node.impl, "has_state", False):
                tops = tops[0]
            for t, v in zip(node.tops, tops):
                blobs[t] = v


if __name__ == "__main__":
    main()
