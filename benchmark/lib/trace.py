"""From a profiler trace to device metrics.

``jax.profiler`` writes a trace as ``*.xplane.pb`` (the XSpace protobuf of
the XLA profiler).  This module decodes that wire format directly (the
schema is small and stable: ``tsl/profiler/protobuf/xplane.proto``; the
decoder started as a copy of ``sparknet_tpu/utils/xplane.py``) and keeps,
beyond that file's per-operation durations, every event's start, so that
busy time is a union of intervals and an idle gap has a place on the
clock.  It reads:

- each device plane's ``XLA Ops`` line: one event per operation that ran,
  with its HLO category and its ``L[<layer>]`` scope from the metadata;
- each device plane's ``XLA Modules`` line: one event per execution of a
  compiled program;
- the host plane's ``TraceMe`` events whose names start with ``bench.``:
  the spans the benchmark's own files put around their calls into the
  program (``jax.profiler.TraceAnnotation``), on the same clock.

Everything a per-layer metric takes from a trace is a function here, so
that every PR computes it in the same way.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
import struct

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

# Control-flow containers span their children: counting both would count
# device time twice, and a while loop's own event would hide the gaps
# inside it.
_CONTAINERS = {"while", "call", "conditional", "condition", "body"}
_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute", "collective")
_MXU = ("convolution", "dot")
_MODULES_LINE = "XLA Modules"
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
_LAYER_RE = re.compile(r"L\[([^\]]+)\]")


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def _varint(buf: memoryview, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(data: memoryview):
    """Yield (field number, wire type, value) over a message body."""
    pos, end = 0, len(data)
    while pos < end:
        tag, pos = _varint(data, pos)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val, pos = _varint(data, pos)
        elif wire == 2:
            ln, pos = _varint(data, pos)
            val = data[pos:pos + ln]
            pos += ln
        elif wire == 5:
            val = int.from_bytes(data[pos:pos + 4], "little")
            pos += 4
        elif wire == 1:
            val = int.from_bytes(data[pos:pos + 8], "little")
            pos += 8
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, wire, val


def _text(v: memoryview) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stats(body: memoryview, stat_names: dict[int, str]) -> tuple:
    """One XStat as (name, value); a ref_value resolves to the string it
    refers to."""
    key, val = None, None
    for num, _wire, v in _fields(body):
        if num == 1:
            key = stat_names.get(v, str(v))
        elif num == 2:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num in (3, 4):
            val = v
        elif num in (5, 6):
            val = _text(v)
        elif num == 7:
            val = stat_names.get(v, "")
    return key, val


@dataclasses.dataclass
class Op:
    """One event of a trace line, in picoseconds on the trace's clock."""
    start_ps: int
    dur_ps: int
    name: str
    category: str = ""
    scope: str = ""

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps

    def layer(self) -> str | None:
        hits = _LAYER_RE.findall(self.scope) or _LAYER_RE.findall(self.name)
        return hits[-1] if hits else None


def _plane_name(body: memoryview) -> str:
    for num, _wire, val in _fields(body):
        if num == 2:
            return _text(val)
    return ""


def _plane_lines(body: memoryview, want_line) -> dict[str, list[Op]]:
    """Decode the lines of one XPlane for which ``want_line(name)`` holds:
    line name -> events in order of appearance."""
    stat_names: dict[int, str] = {}
    raw_meta: list[memoryview] = []
    raw_lines: list[memoryview] = []
    for num, _wire, val in _fields(body):
        if num == 3:
            raw_lines.append(val)
        elif num == 4:
            raw_meta.append(val)
        elif num == 5:                      # map<int64, XStatMetadata>
            for n2, _w2, v2 in _fields(val):
                if n2 == 2:
                    mid, mname = 0, ""
                    for n3, _w3, v3 in _fields(v2):
                        if n3 == 1:
                            mid = v3
                        elif n3 == 2:
                            mname = _text(v3)
                    stat_names[mid] = mname

    metas: dict[int, tuple[str, str, str]] = {}   # id -> name, cat, scope
    for raw in raw_meta:                    # map<int64, XEventMetadata>
        for n2, _w2, v2 in _fields(raw):
            if n2 != 2:
                continue
            mid, name, display, cat, scope = 0, "", "", "", ""
            for n3, _w3, v3 in _fields(v2):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    name = _text(v3)
                elif n3 == 4:
                    display = _text(v3)
                elif n3 == 5:
                    k, v = _stats(v3, stat_names)
                    if k == "hlo_category":
                        cat = str(v)
                    elif k == "tf_op":
                        scope = str(v)
            metas[mid] = (display or name, cat, scope)

    lines: dict[str, list[Op]] = {}
    for raw in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for n2, _w2, v2 in _fields(raw):
            if n2 == 2:
                lname = _text(v2)
            elif n2 == 3:
                t0_ns = v2
            elif n2 == 4:
                raw_events.append(v2)
        if not want_line(lname):
            continue
        ops = lines.setdefault(lname, [])
        for ev in raw_events:
            mid = off = dur = 0
            for n3, _w3, v3 in _fields(ev):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    off = v3
                elif n3 == 3:
                    dur = v3
            name, cat, scope = metas.get(mid, (f"#{mid}", "", ""))
            ops.append(Op(t0_ns * 1000 + off, dur, name, cat, scope))
    return lines


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Trace:
    """What the benchmark keeps of one trace: per device ordinal the leaf
    operations that ran and the executions of whole programs, and the
    benchmark's own host spans."""
    devices: dict[int, list[Op]]
    spans: list[Op]
    modules: dict[int, list[Op]] = dataclasses.field(default_factory=dict)

    def window(self) -> tuple[int, int]:
        """The traced window in picoseconds: the ``bench.window`` span
        when the host recorded one, else first to last device event."""
        for s in self.spans:
            if s.name == WINDOW_SPAN:
                return s.start_ps, s.end_ps
        ops = [o for d in self.devices.values() for o in d]
        if not ops:
            raise ValueError("the trace holds no device operation")
        return min(o.start_ps for o in ops), max(o.end_ps for o in ops)


def find_xplane_file(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def category(op: Op) -> str:
    """The HLO category of an operation; where the trace carries none
    (a CPU trace), the stem of its name."""
    if op.category:
        return op.category
    stem = op.name.lstrip("%").split(".", 1)[0].split(" ", 1)[0]
    return stem.rsplit("_", 1)[-1] if "_" in stem else stem


def load(path: str) -> Trace:
    """Decode the device planes and the benchmark's host spans of one
    ``.xplane.pb`` file (or ``.xplane.pb.gz``, as the tests keep theirs)."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = memoryview(f.read())
    devices: dict[int, list[Op]] = {}
    modules: dict[int, list[Op]] = {}
    spans: list[Op] = []
    for num, _wire, body in _fields(data):
        if num != 1:
            continue
        name = _plane_name(body)
        m = _DEVICE_RE.match(name)
        if m:
            lines = _plane_lines(
                body, lambda ln: ln == _MODULES_LINE
                or ("XLA Ops" in ln and "Async" not in ln))
            modules[int(m.group(1))] = sorted(
                lines.pop(_MODULES_LINE, []), key=lambda o: o.start_ps)
            ops = [o for evs in lines.values() for o in evs
                   if category(o) not in _CONTAINERS]
            ops.sort(key=lambda o: o.start_ps)
            devices[int(m.group(1))] = ops
        elif name.startswith("/host:CPU"):
            for evs in _plane_lines(body, lambda ln: True).values():
                spans.extend(o for o in evs
                             if o.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda o: o.start_ps)
    return Trace(devices=devices, spans=spans, modules=modules)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def union(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The merged intervals, clipped to [lo, hi], in which some operation
    of ``ops`` (sorted by start) ran."""
    out: list[tuple[int, int]] = []
    for o in ops:
        s, e = max(o.start_ps, lo), min(o.end_ps, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the devices that ran anything, window
    seconds).  Busy is the union of operation intervals inside the
    window."""
    lo, hi = trace.window()
    per_device = [sum(e - s for s, e in union(ops, lo, hi))
                  for ops in trace.devices.values() if ops]
    if not per_device:
        raise ValueError("the trace holds no device operation")
    return sum(per_device) / len(per_device) / 1e12, (hi - lo) / 1e12


def in_window(trace: Trace, device: int) -> list[Op]:
    lo, hi = trace.window()
    return [o for o in trace.devices.get(device, [])
            if o.end_ps > lo and o.start_ps < hi]


def time_share(ops, pred) -> float | None:
    """Summed duration of the operations for which ``pred`` holds over the
    summed duration of all of them (a share of busy time while operations
    do not overlap, which on one device's ``XLA Ops`` line they do not)."""
    total = sum(o.dur_ps for o in ops)
    if not total:
        return None
    return sum(o.dur_ps for o in ops if pred(o)) / total


def is_collective(op: Op) -> bool:
    c = category(op).lower()
    n = op.name.lower()
    return any(k in c or n.lstrip("%").startswith(k) for k in _COLLECTIVES)


def is_mxu(op: Op) -> bool:
    c = category(op).lower()
    return any(k in c for k in _MXU)


def kernel_ops(ops, name: str) -> list[Op]:
    """The operations of a kernel by its stable name."""
    return [o for o in ops if name in o.name]


def top_ops(ops, n: int = 10) -> list[list]:
    """[[label, seconds], ...] for the operations that took most device
    time, keyed by layer scope, direction and HLO category, so that the
    list reads the same after a fusion changes instruction names."""
    acc: dict[str, int] = {}
    for o in ops:
        layer = o.layer() or "-"
        way = "bwd" if "transpose(" in o.scope else "fwd"
        key = f"{layer} {way} {category(o)}"
        acc[key] = acc.get(key, 0) + o.dur_ps
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e12] for k, v in rows]


def program_gaps(trace: Trace, device: int) -> list[float]:
    """Seconds between one execution and the next of the program that
    took most of the device's time inside the window (the train step, the
    round): from the end of each to the start of the following one, the
    small programs the host runs in between included."""
    lo, hi = trace.window()
    runs = [m for m in trace.modules.get(device, [])
            if m.start_ps >= lo and m.end_ps <= hi]
    total: dict[str, int] = {}
    for m in runs:
        total[m.name] = total.get(m.name, 0) + m.dur_ps
    if not total:
        return []
    main = [m for m in runs if m.name == max(total, key=total.get)]
    return [(b.start_ps - a.end_ps) / 1e12 for a, b in zip(main, main[1:])]


def idle_gaps(trace: Trace, device: int, n: int = 10) -> list[list]:
    """[[label, seconds], ...]: the device's idle time inside the window,
    summed by what the host was doing.  Each gap between two operations is
    given to the innermost benchmark span that covers its middle, and to
    ``(no span)`` where none does."""
    lo, hi = trace.window()
    busy = union(trace.devices.get(device, []), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    acc: dict[str, int] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [sp for sp in spans if sp.start_ps <= mid < sp.end_ps]
        label = (min(cover, key=lambda sp: sp.dur_ps).name[len(SPAN_PREFIX):]
                 if cover else "(no span)")
        acc[label] = acc.get(label, 0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e12] for k, v in rows]
