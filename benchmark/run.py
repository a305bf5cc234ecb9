"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it refuses any backend but ``tpu`` and fewer chips than the
cell asks for, resolves the cell by name to its configuration file and its
traffic file, loads the driver the traffic file names, sets up (weights,
inputs, the correctness check against the plain reference, a warm-up of
every shape), measures for ``--seconds``, and prints one JSON object as the
last line of its standard output.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, taken with the profiler off.  With ``--trace 1``
the end of the window runs under the profiler and the metrics are the
cell's per-layer metrics.

Nothing in this file names a model, a mix, a metric or a cell: they are
entries of ``BENCHMARK.json`` and files found by the names there
(``benchmark/README.md``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # as near to process start as Python gets

import argparse
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The profiler runs over the last part of a traced run's window: a few
# units of work are enough for shares of device time, and a trace of the
# whole window would be large and slow to reduce.
TRACE_SECONDS = 3.0


def say(what: str, payload: dict) -> None:
    """An earlier line of the output: context for whoever reads the log."""
    print(json.dumps({what: payload}, default=str), flush=True)


def metric_list(spec: dict, group: str, workload: str) -> list[dict]:
    return [m for m in spec[group]
            if workload in m.get("workloads", [workload])]


def main(argv: list[str] | None = None, *, spec_path: str | None = None,
         platform: str = "tpu", traffic_dir: str | None = None,
         cache_dir: str | None = None) -> int:
    """Run one cell.  The keyword arguments are for ``benchmark/tests``,
    which run a tiny cell of their own on the CPU; the command line always
    runs ``BENCHMARK.json`` on a TPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark.lib import harness

    spec = harness.load_json(spec_path
                             or os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.resolve_cell(spec, args.workload, args.seed,
                                traffic_dir=traffic_dir, cache_dir=cache_dir)

    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} {platform} "
              f"chip(s); JAX found {len(devices)} x "
              f"{devices[0].platform!r} ({devices[0].device_kind}): "
              f"nothing was run", file=sys.stderr)
        return 2

    from sparknet_tpu.utils.compile_cache import use_compile_cache

    cache = use_compile_cache()
    # keep every executable, however quick its compile, so that which
    # programs a later run finds does not depend on how long a compile
    # happened to take
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = harness.CompileClock()

    driver = harness.load_driver(cell.mix).Driver(cell)
    try:
        return _run(args, spec, cell, driver, clock, devices, cache)
    finally:
        driver.close()


def _run(args, spec, cell, driver, clock, devices, cache) -> int:
    import jax
    import jaxlib

    from benchmark.lib import harness
    from benchmark.lib import trace as tracelib

    t0 = time.perf_counter()
    driver.build()
    t_build = time.perf_counter()
    verdict = driver.check()
    t_check = time.perf_counter()
    driver.warm()
    t_warm = time.perf_counter()
    setup = {"setup_s": t_warm - T_START, "compile_s": clock.seconds,
             "import_s": t0 - T_START, "build_s": t_build - t0,
             "check_s": t_check - t_build, "warm_s": t_warm - t_check}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    say("cell", {"workload": cell.name, "seed": cell.seed,
                 "config": cell.config["name"], **driver.describe(),
                 "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                 "libtpu": libtpu, "compile_cache": cache})
    say("setup", setup)
    say("check", verdict)

    events0 = clock.events
    traced = None
    trace = None
    seconds = args.seconds
    if args.trace:
        trace_s = min(TRACE_SECONDS, args.seconds / 3)
        seconds = args.seconds - trace_s
    window = driver.measure(seconds)
    if args.trace:
        trace_dir = os.path.join(cell.cache_dir, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans come from TraceAnnotation
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            traced = driver.measure(trace_s)
        finally:
            jax.profiler.stop_trace()
    compiled_in_window = clock.events - events0
    device = harness.device_info(devices, driver.used_devices())
    windows = [window] + ([traced] if traced else [])
    ok, why = driver.verdict(windows)
    say("window", {"seconds": window.seconds, "attempted": window.attempted,
                   "failed": window.failed, "verdict": why,
                   "compile_events_in_window": compiled_in_window,
                   "unit_s": [round(u, 4) for u in window.unit_seconds]})
    if compiled_in_window:
        print(f"benchmark: {compiled_in_window} compile event(s) inside "
              f"the measured window: the warm-up missed a shape",
              file=sys.stderr)
        return 3

    extra = {}
    if args.trace:
        extra = driver.after_trace()
        trace = tracelib.load(tracelib.find_xplane_file(trace_dir))
    counters = driver.counters()
    say("counters", {**counters, **extra})

    cap = harness.Capture(cell=cell, driver=driver, device=device,
                          setup=setup, window=window, traced=traced,
                          counters=counters, extra=extra, trace=trace)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metric_list(spec, group, cell.name):
        value = harness.load_metric(group, m["name"]).read(cap)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    out = {"correct": bool(verdict["ok"] and ok),
           "attempted": sum(w.attempted for w in windows),
           "failed": sum(w.failed for w in windows),
           "metrics": metrics, "device": device}
    if trace is not None:
        busy_s, window_s = tracelib.busy_seconds(trace)
        device["busy_s"], device["window_s"] = busy_s, window_s
        first = min(trace.devices)
        out["breakdown"] = {
            "device_ops": tracelib.top_ops(
                tracelib.in_window(trace, first)),
            "idle_gaps": tracelib.idle_gaps(trace, first)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
