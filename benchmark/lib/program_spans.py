"""The program's own spans, read from the trace of a ``--trace 1`` run.

``sparknet_tpu.utils.telemetry.span`` enters a
``jax.profiler.TraceAnnotation("sparknet.<name>")`` wherever the program
does a unit of host work (a step's dispatch, a batch's assembly, a round's
staging), so under the profiler those spans lie in the host plane of the
same ``.xplane.pb`` as the device's operations, on the same clock.
``lib/trace.py`` keeps the benchmark's own ``bench.`` spans only; this file
reads the program's from the trace file ``run.py`` wrote, so that a
per-layer metric can say what the host did from inside the program.

A program without such spans (a parent commit) leaves every function here
with nothing to return: ``load`` gives an empty list and the readers built
on it give ``None``.

    python -m benchmark.lib.program_spans <trace dir or .xplane.pb>

prints the device's idle time by program span for a trace that a run left
behind (``benchmark/.cache/trace/<cell>``).
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import os
import statistics
import sys

from . import trace as tracelib

PREFIX = "sparknet."


@dataclasses.dataclass(frozen=True)
class Span:
    """One span of the program inside the traced window, in picoseconds
    on the trace's clock; ``whole`` is false where the window's edge cut
    it."""
    name: str                   # without the prefix
    start_ps: int
    end_ps: int
    whole: bool = True

    @property
    def dur_ps(self) -> int:
        return self.end_ps - self.start_ps


@functools.lru_cache(maxsize=4)
def read_file(path: str) -> tuple[tracelib.Op, ...]:
    """Every ``sparknet.`` event of the host planes of one ``.xplane.pb``
    (or ``.gz``), by start.  Cached, so that the readers of one run decode
    its trace once."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = memoryview(f.read())
    found: list[tracelib.Op] = []
    for num, _wire, body in tracelib._fields(data):
        if num == 1 and tracelib._plane_name(body).startswith("/host:CPU"):
            for events in tracelib._plane_lines(body,
                                                lambda ln: True).values():
                found.extend(o for o in events if o.name.startswith(PREFIX))
    return tuple(sorted(found, key=lambda o: o.start_ps))


def clip(events, lo: int, hi: int) -> list[Span]:
    """The events that overlap [lo, hi] as spans cut to it."""
    return [Span(o.name[len(PREFIX):], max(o.start_ps, lo),
                 min(o.end_ps, hi), lo <= o.start_ps and o.end_ps <= hi)
            for o in events if o.end_ps > lo and o.start_ps < hi]


def load(cap) -> list[Span]:
    """The program's spans inside the traced window of the run ``cap``
    describes; empty where the run was not traced or the program has
    none."""
    if cap.trace is None:
        return []
    try:
        path = tracelib.find_xplane_file(
            os.path.join(cap.cell.cache_dir, "trace", cap.cell.name))
    except FileNotFoundError:
        return []
    return clip(read_file(path), *cap.trace.window())


def seconds(spans, name: str) -> float:
    """Summed length inside the window of the spans called ``name``."""
    return sum(s.dur_ps for s in spans if s.name == name) / 1e12


def self_seconds(spans, parent: str, children) -> list[float]:
    """For each whole span called ``parent``, its length less the part of
    it that spans named in ``children`` cover: the parent's self time."""
    kids = [s for s in spans if s.name in children]
    out = []
    for p in spans:
        if p.name != parent or not p.whole:
            continue
        covered = sum(e - s for s, e in tracelib.union(
            kids, p.start_ps, p.end_ps))
        out.append((p.dur_ps - covered) / 1e12)
    return out


def median_ms(values) -> float | None:
    values = list(values)
    return 1000.0 * statistics.median(values) if values else None


def length_ms(spans, name: str) -> float | None:
    """Median length in milliseconds of the whole spans called ``name``;
    nothing where there is none."""
    return median_ms(s.dur_ps / 1e12 for s in spans
                     if s.name == name and s.whole)


def idle_of(trace: tracelib.Trace, spans) -> list[list]:
    """``lib/trace.py`` ``idle_gaps``'s rule applied to the program's
    spans, piece by piece: each idle gap of the first device inside the
    window is cut where a span starts or ends, and each piece goes to the
    innermost (shortest) span that covers it, on whichever thread, or to
    ``(no span)``.  The program's spans run on several threads and a fed
    step's gap is as long as a batch's assembly, so a whole gap given to
    the span over its middle would hide the others."""
    lo, hi = trace.window()
    device = min(trace.devices) if trace.devices else None
    busy = tracelib.union(trace.devices.get(device, []), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    spans = sorted(spans, key=lambda sp: sp.start_ps)
    acc: dict[str, int] = {}
    active: list[Span] = []
    i = 0
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        while i < len(spans) and spans[i].start_ps < e:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp.end_ps > s]
        cuts = sorted({s, e, *(t for sp in active
                               for t in (sp.start_ps, sp.end_ps)
                               if s < t < e)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in active
                     if sp.start_ps <= a and b <= sp.end_ps]
            label = (min(cover, key=lambda sp: sp.dur_ps).name if cover
                     else "(no span)")
            acc[label] = acc.get(label, 0) + (b - a)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])
    return [[k, v / 1e12] for k, v in rows]


def idle_by_span(cap) -> list[list]:
    """[[span, seconds], ...]: the first device's idle time in the traced
    window of ``cap``, by what the program was doing."""
    return idle_of(cap.trace, load(cap))


def main(argv: list[str]) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = tracelib.find_xplane_file(path)
    trace = tracelib.load(path)
    lo, hi = trace.window()
    spans = clip(read_file(path), lo, hi)
    print(f"window {(hi - lo) / 1e12:.6f} s; device idle by program span")
    for label, s in idle_of(trace, spans):
        print(f"  {label:24s} {s:.6f}")
    print("span: count, summed s, median ms")
    for name in sorted({s.name for s in spans}):
        count = sum(1 for s in spans if s.name == name)
        print(f"  {name:24s} {count:6d} {seconds(spans, name):10.6f} "
              f"{length_ms(spans, name)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
