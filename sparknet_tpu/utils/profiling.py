"""Structured tracing — the profiling tier the reference lacked.

The reference's tracing is wall-clock logs + CUDA-event timers (reference:
caffe/src/caffe/util/benchmark.cpp:26-145, app logs CifarApp.scala:41-50,
Spark event log ImageNetApp.scala:44; SURVEY.md §5 "No structured
tracing").  Here: ``jax.profiler`` traces viewable in TensorBoard/Perfetto,
plus annotation helpers that mark app phases inside the trace.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device+host profiler trace for the enclosed block
    (open in TensorBoard's profile tab or Perfetto)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a trace (TraceAnnotation), usable as decorator
    or context manager."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def server(port: int = 9999) -> Iterator[None]:
    """Live profiling server for `jax.profiler`-compatible clients."""
    s = jax.profiler.start_server(port)
    try:
        yield
    finally:
        del s


def device_memory_summary() -> list[dict]:
    """Per-device HBM usage (bytes in use / limit / peak) — the
    observability the reference's SyncedMemory world never exposed; used
    by `caffe device_query` and available for app logs."""
    out = []
    for d in jax.devices():
        stats = getattr(d, "memory_stats", lambda: None)() or {}
        out.append({
            "device": f"{d.platform}:{d.id}",
            "kind": d.device_kind,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


def save_memory_profile(path: str) -> None:
    """Write a pprof-format device memory profile
    (jax.profiler.save_device_memory_profile)."""
    jax.profiler.save_device_memory_profile(path)


# Benchmark-harness pieces shared by bench.py and tools/profile_step.py so
# the profiled program IS the benchmarked one: model table, solver config,
# per-step FLOPs estimate, peak table, and the scanned train block.

BENCH_SOLVER_PROTOTXT = (
    'base_lr: 0.01\nmomentum: 0.9\nweight_decay: 0.0005\n'
    'lr_policy: "step"\ngamma: 0.1\nstepsize: 100000\n')


def build_bench_model(name: str, batch: int):
    """(net_param, input_shape, num_classes) for a benchmark model name."""
    from ..models import caffenet, googlenet, lenet, vgg16
    if name == "lenet":
        return lenet(batch, batch), (1, 28, 28), 10
    if name == "googlenet":
        return googlenet(batch, batch, crop=224), (3, 224, 224), 1000
    if name == "vgg16":
        return vgg16(batch, batch, crop=224), (3, 224, 224), 1000
    if name == "caffenet":
        return caffenet(batch, batch), (3, 227, 227), 1000
    raise ValueError(f"unknown bench model {name!r}")


def step_cost_flops(solver, batch) -> float | None:
    """Model FLOPs of one compiled train step via XLA cost analysis
    (best-effort; a fori_loop block would undercount — cost the single
    step).  Returns None with a stderr breadcrumb where the backend
    doesn't support cost analysis."""
    import sys
    try:
        lowered = solver._step.lower(solver.params, solver.state, 0, batch,
                                     jax.random.PRNGKey(1))
        cost = lowered.compile().cost_analysis()
        if cost:
            return float(cost.get("flops", 0.0)) or None
    except Exception as e:
        print(f"[profiling] cost_analysis unavailable: {e}", file=sys.stderr)
    return None


# bf16 peak FLOP/s by device kind (public spec sheets) — the MFU
# denominator shared by bench.py and tools/profile_step.py.
_PEAK_FLOPS_BF16 = {
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5p": 459e12, "TPU v5": 459e12,
    "TPU v4": 275e12, "TPU v4 lite": 138e12,
    "TPU v3": 123e12, "TPU v2": 46e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> float | None:
    """bf16 peak FLOP/s for a jax device_kind, or None if unknown."""
    return _PEAK_FLOPS_BF16.get(device_kind)


def fwd_cost_flops(jitted_fwd, *args) -> float | None:
    """Model FLOPs of any jitted forward via XLA cost analysis
    (best-effort, like :func:`step_cost_flops`) — shared by the eval-MFU
    numerator and the serving plane's per-model FLOPs estimate."""
    import sys
    try:
        lowered = jitted_fwd.lower(*args)
        cost = lowered.compile().cost_analysis()
        if cost:
            return float(cost.get("flops", 0.0)) or None
    except Exception as e:
        print(f"[profiling] cost_analysis unavailable: {e}", file=sys.stderr)
    return None


def eval_cost_flops(solver, batch) -> float | None:
    """Model FLOPs of one compiled test-net forward (the eval-pass MFU
    numerator), via XLA cost analysis like :func:`step_cost_flops`."""
    return fwd_cost_flops(solver._test_fwd, solver.params, batch, None)


def scanned_eval_block(solver, iters: int):
    """Forward-only analog of :func:`scanned_train_block`: ``iters``
    test-net forward passes as ONE compiled fori_loop, with a scalar
    loop-carried perturbation of the input so XLA can neither hoist nor
    elide the forward (the shared-weights eval pass the bench's
    eval_images_per_sec times; `caffe time`'s forward leg,
    caffe/tools/caffe.cpp:290-376).

    Returns ``block(params, batch, s0) -> s`` (an opaque scalar)."""
    import jax.numpy as jnp
    from jax import lax

    fwd = solver._make_test_forward(solver.test_net)

    def block_fn(params, batch, s0):
        def body(i, s):
            b = {k: (v + (s * 1e-20).astype(v.dtype)
                     if jnp.issubdtype(v.dtype, jnp.floating) else v)
                 for k, v in batch.items()}
            out = fwd(params, b)
            taps = [jnp.sum(v).astype(jnp.float32)
                    for v in jax.tree_util.tree_leaves(out)]
            return jnp.sum(jnp.stack(taps)) * 1e-20
        return lax.fori_loop(0, iters, body, s0)

    return jax.jit(block_fn)


def scanned_train_block(solver, iters: int):
    """The production-shaped benchmark block: ``iters`` solver steps as ONE
    compiled fori_loop with donated params/state — the same execution model
    as DistributedTrainer.train_round.  Shared by bench.py and
    tools/profile_step.py so the profiled program IS the benchmarked one.

    Returns ``block(params, state, it0, batch, rng) -> (params, state,
    rng, loss)``.
    """
    import jax.numpy as jnp
    from jax import lax

    raw_step = solver.make_train_step()

    def block_fn(params, state, it0, batch, rng):
        def body(i, carry):
            params, state, rng, _loss = carry
            rng, sub = jax.random.split(rng)
            params, state, loss = raw_step(params, state, it0 + i,
                                           batch, sub)
            return (params, state, rng, loss)
        return lax.fori_loop(0, iters, body,
                             (params, state, rng, jnp.zeros(())))

    return jax.jit(block_fn, donate_argnums=(0, 1))
