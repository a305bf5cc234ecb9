"""What every driver and every per-layer metric shares: the cell as it was
resolved from the data files, host spans on the profiler's clock, the
compile-event clock, the measured window and the checks on the losses.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import math
import os
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)

# JAX reports tracing, lowering and backend compilation (or the read of a
# cached executable) as duration events; chip_smoke.py's Clock reads the
# same three.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds and events JAX spent making executables, so that set-up can
    say how much of it was compilation and a window can show it had none."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += seconds
            self.events += 1


class Spans:
    """Host spans recorded by the benchmark's own files around their calls
    into the program.  Each is kept in memory (name, start, end on
    ``time.perf_counter``) and, while a profiler trace is running, also
    written into it as ``bench.<name>``, on the device trace's clock."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name: str, start: float, end: float) -> float:
        """Summed length of the spans called ``name`` that began inside
        [start, end]."""
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and start <= t0 <= end)


class TimedIterator:
    """The iterator handed to the program in place of the feed: it passes
    every item through and records how long ``next`` blocked."""

    def __init__(self, it, spans: Spans, name: str = "next_batch"):
        self._it, self._spans, self._name = iter(it), spans, name

    def __iter__(self):
        return self

    def __next__(self):
        with self._spans.span(self._name):
            return next(self._it)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its data files loaded."""
    name: str
    config: dict
    mix: dict
    chips: int
    seed: int
    cache_dir: str                      # run-time files, inside the checkout
    spans: Spans = dataclasses.field(default_factory=Spans)

    def net_param(self, train_batch: int, test_batch: int):
        """The configuration's net as the program builds it, checked
        against the sizes the configuration file states."""
        from . import flops
        models = importlib.import_module("sparknet_tpu.models")
        builder = getattr(models, self.config["builder"])
        net = builder(train_batch, test_batch,
                      **self.config.get("builder_args", {}))
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        got = flops.widths(net.filtered(NetState(Phase.TRAIN)))
        want = [tuple(r) for r in self.config["as_built"]["layers"]]
        if got != want:
            diff = [(g, w) for g, w in zip(got, want) if g != w]
            raise SystemExit(
                f"configuration {self.config['name']!r}: the net the "
                f"program builds is not the one the configuration file "
                f"states ({len(got)} against {len(want)} layers; first "
                f"difference {diff[:1]})")
        return net


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(spec: dict, workload: str, seed: int, *,
                 traffic_dir: str | None = None,
                 cache_dir: str | None = None) -> Cell:
    """Find ``workload`` in the benchmark's specification and load the
    configuration file and the traffic file it names."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; the benchmark "
                         f"has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(REPO, configs[w["config"]]["file"]))
    traffic_dir = traffic_dir or os.path.join(BENCH_DIR, "traffic")
    mix = load_json(os.path.join(traffic_dir, w["traffic"] + ".json"))
    return Cell(name=workload, config=config, mix=mix, chips=int(w["chips"]),
                seed=seed,
                cache_dir=cache_dir or os.path.join(BENCH_DIR, ".cache"))


def load_driver(mix: dict):
    return importlib.import_module(f"benchmark.drivers.{mix['driver']}")


_METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_metric(group: str, name: str):
    """The reader of one metric: ``benchmark/end_to_end/<name>.py`` or
    ``benchmark/layer_metrics/<name>.py``, with ``read(capture)``."""
    return importlib.import_module(
        f"benchmark.{_METRIC_DIRS[group]}.{name}")


@dataclasses.dataclass
class Window:
    """One measured window: units of work until ``seconds`` have passed,
    closed by the last unit's own fetch."""
    seconds: float = 0.0
    images: int = 0
    steps: int = 0
    failed: int = 0
    t0: float = 0.0
    losses: list = dataclasses.field(default_factory=list)
    unit_seconds: list = dataclasses.field(default_factory=list)

    @property
    def img_s(self) -> float:
        return self.images / self.seconds

    @property
    def attempted(self) -> int:
        return self.steps


def run_window(driver, seconds: float, spans: Spans) -> Window:
    w = Window(t0=time.perf_counter())
    with spans.span("window"):
        while True:
            t = time.perf_counter()
            images, steps, loss = driver.unit()
            now = time.perf_counter()
            w.unit_seconds.append(now - t)
            w.images += images
            w.steps += steps
            w.losses.append(loss)
            if not math.isfinite(loss):
                w.failed += steps
            if now - w.t0 >= seconds:
                break
    w.seconds = now - w.t0
    return w


def losses_ok(losses: list[float], margin: float) -> tuple[bool, str]:
    """A training run is correct only if every fetched loss is finite and
    the last is not above the first by more than ``margin``: random labels
    on random images are learnt slowly, and a run that diverges must
    fail."""
    if not losses or not all(math.isfinite(x) for x in losses):
        return False, f"non-finite loss among {len(losses)} fetched"
    if losses[-1] > losses[0] + margin:
        return False, (f"loss rose from {losses[0]:.4f} to "
                       f"{losses[-1]:.4f} (margin {margin})")
    return True, f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"


class TrainingDriver:
    """What the training drivers share: the window is units of work (a
    call of steps, a round) until the time is up, and a run is correct
    only while its losses are."""

    def __init__(self, cell: Cell):
        from . import check
        self.cell = cell
        self.cfg, self.mix, self.inp = (cell.config, cell.mix,
                                        cell.config["input"])
        self.dtype = self.mix["compute_dtype"]
        self.batch = int(self.cfg["batch"][self.dtype])   # a chip, a step
        self.loss_margin = check.LOSS_MARGIN

    def raw_shape(self, *lead: int) -> tuple:
        """Shape of raw uint8 images as the records and the feed hold
        them, under the leading dimensions ``lead``."""
        e = int(self.inp["raw_edge"])
        return (*lead, int(self.inp["channels"]), e, e)

    def train_net_param(self):
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        return self.net_param.filtered(NetState(Phase.TRAIN))

    def with_precision(self, verdict: dict, net, params) -> dict:
        """The reference check's ``verdict`` joined with whether the
        system's train ``net`` holds the precision the mix states."""
        from . import check
        held = check.held_precision(self.dtype, net, params, self.batch,
                                    self.inp)
        return {**verdict, "ok": verdict["ok"] and held["ok"],
                "precision": held}

    def measure(self, seconds: float) -> Window:
        return run_window(self, seconds, self.cell.spans)

    def verdict(self, windows: list[Window]) -> tuple[bool, str]:
        return losses_ok([x for w in windows for x in w.losses],
                         self.loss_margin)

    def after_trace(self) -> dict:
        return {}

    def close(self) -> None:
        pass


@dataclasses.dataclass
class Capture:
    """Everything a metric's reader may read: the cell, the driver (for
    the net and the program's own counters), set-up times, the measured
    window, and in a traced run the traced window and the reduced
    trace."""
    cell: Cell
    driver: object
    device: dict
    setup: dict
    window: Window
    traced: Window | None
    counters: dict
    extra: dict
    trace: object | None


def device_info(devices, used) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest
    chip used.  The TPU runtime counts the buffers a process holds under
    ``peak_bytes_in_use`` and, apart from them, what it reserves at the
    bottom of memory for the temporaries of each loaded program under
    ``peak_bytes_reserved`` (the reservation stays while the program is
    loaded); the chip holds both, so the peak is their sum."""
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
