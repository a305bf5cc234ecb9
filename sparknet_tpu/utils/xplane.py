"""Minimal XSpace/XPlane trace parser — per-op time tables without TensorBoard.

``jax.profiler`` writes traces as ``*.xplane.pb`` (the XSpace protobuf used
by the TF/XLA profiler).  TensorBoard is the usual viewer, but a headless
rig only needs the aggregate: which XLA ops the device spent its time in,
and whether they were FLOP-bound or bandwidth-bound.  This module decodes
the wire format directly (the schema is small and stable:
tensorflow/tsl/profiler/protobuf/xplane.proto) and aggregates the device
plane's "XLA Ops" line by op and by HLO category, carrying each op's
``flops`` and ``bytes_accessed`` stats so achieved FLOP/s and HBM
bandwidth fall out per row.

This is the "where the time goes" tier of the tracing story (the
reference had none — SURVEY.md §5: wall-clock logs + CUDA-event timers
only, caffe/src/caffe/util/benchmark.cpp:26-145).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct

_LAYER_RE = re.compile(r"L\[([^\]]+)\]")


def _read_varint(buf: memoryview, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(data: memoryview):
    """Yield (field_number, wire_type, value) over a message body.
    Wire 0 -> int, wire 2 -> memoryview, wire 5/1 -> raw little-endian ints."""
    pos, end = 0, len(data)
    while pos < end:
        tag, pos = _read_varint(data, pos)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val, pos = _read_varint(data, pos)
        elif wire == 2:
            ln, pos = _read_varint(data, pos)
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


@dataclasses.dataclass
class OpMeta:
    name: str = ""
    display: str = ""
    category: str = ""
    scope: str = ""         # tf_op / named_scope path ("jit(f)/L[conv1]/…")
    flops: int = 0          # model flops per occurrence (XLA 'flops' stat)
    bytes_accessed: int = 0

    @property
    def label(self) -> str:
        return self.display or self.name

    def layer(self) -> str | None:
        """Layer attribution from the net executor's L[...] named scopes
        (graph/net.py); the AD transpose keeps the scope inside
        transpose(jvp(L[...]))."""
        hits = _LAYER_RE.findall(self.scope) or _LAYER_RE.findall(self.name)
        return hits[-1] if hits else None


@dataclasses.dataclass
class Event:
    meta: OpMeta
    duration_ps: int


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict[str, list[Event]]  # line name -> events

    def total_ps(self) -> int:
        return sum(e.duration_ps for evs in self.lines.values() for e in evs)


def _parse_stats(body: memoryview, stat_names: dict[int, str]) -> dict:
    out = {}
    key = None
    for num, wire, val in _fields(body):
        if num == 1:
            key = stat_names.get(val, val)
        elif num == 2:  # double_value: wire type 1 arrives as raw bits
            out[key] = struct.unpack("<d", val.to_bytes(8, "little"))[0]
        elif num in (3, 4, 7):
            out[key] = val
        elif num in (5, 6):
            out[key] = bytes(val)
    return out


def _parse_plane(body: memoryview) -> Plane:
    name = ""
    stat_names: dict[int, str] = {}
    raw_meta: list[memoryview] = []
    raw_lines: list[memoryview] = []
    for num, _wire, val in _fields(body):
        if num == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif num == 3:
            raw_lines.append(val)
        elif num == 4:
            raw_meta.append(val)
        elif num == 5:  # map<int64, XStatMetadata>
            mid, mname = 0, ""
            for n2, _w2, v2 in _fields(val):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    for n3, _w3, v3 in _fields(v2):
                        if n3 == 1:
                            mid = v3
                        elif n3 == 2:
                            mname = bytes(v3).decode("utf-8", "replace")
            stat_names[mid] = mname

    metas: dict[int, OpMeta] = {}
    for raw in raw_meta:  # map<int64, XEventMetadata>
        mid = 0
        meta = OpMeta()
        for n2, _w2, v2 in _fields(raw):
            if n2 == 1:
                mid = v2
            elif n2 == 2:  # XEventMetadata
                for n3, _w3, v3 in _fields(v2):
                    if n3 == 1:
                        mid = v3
                    elif n3 == 2:
                        meta.name = bytes(v3).decode("utf-8", "replace")
                    elif n3 == 4:
                        meta.display = bytes(v3).decode("utf-8", "replace")
                    elif n3 == 5:  # XStat on the metadata
                        st = _parse_stats(v3, stat_names)
                        if "hlo_category" in st:
                            meta.category = st["hlo_category"].decode(
                                "utf-8", "replace")
                        if "tf_op" in st:
                            meta.scope = st["tf_op"].decode("utf-8", "replace")
                        meta.flops = int(st.get("flops", meta.flops) or 0)
                        meta.bytes_accessed = int(
                            st.get("bytes_accessed", meta.bytes_accessed) or 0)
        metas[mid] = meta

    lines: dict[str, list[Event]] = {}
    for raw in raw_lines:
        lname = ""
        events: list[Event] = []
        for n2, _w2, v2 in _fields(raw):
            if n2 == 2:
                lname = bytes(v2).decode("utf-8", "replace")
            elif n2 == 4:  # XEvent
                mid = dur = 0
                for n3, _w3, v3 in _fields(v2):
                    if n3 == 1:
                        mid = v3
                    elif n3 == 3:
                        dur = v3
                events.append(Event(metas.get(mid, OpMeta(f"#{mid}")), dur))
        lines.setdefault(lname or "(unnamed)", []).extend(events)
    return Plane(name=name, lines=lines)


def parse_xspace(path: str) -> list[Plane]:
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [_parse_plane(val) for num, _w, val in _fields(data) if num == 1]


def find_xplane_file(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


# Control-flow containers whose events span their children (counting both
# would double-count device time).
_CONTAINERS = {"while", "call", "conditional", "condition", "body"}


def device_plane(planes: list[Plane]) -> Plane:
    best = None
    for p in planes:
        nm = p.name.lower()
        if ("tpu" in nm or "gpu" in nm) and "host" not in nm:
            if best is None or p.total_ps() > best.total_ps():
                best = p
    if best is None:
        # CPU-platform traces have no accelerator plane; fall back to the
        # busiest plane that carries an "XLA Ops" line (host-side XLA)
        # or a TfrtCpuClient execution line (newer jax CPU runtimes put
        # HLO-named thunk events on "tf_XLATfrtCpuClient/<id>" lines)
        for p in planes:
            if any("XLA Ops" in ln or "tf_XLA" in ln for ln in p.lines):
                if best is None or p.total_ps() > best.total_ps():
                    best = p
    if best is None:
        raise ValueError(f"no device plane (planes: {[p.name for p in planes]})")
    return best


# One optimized-HLO instruction line: `%name.123 = ... metadata={...
# op_name="jit(f)/.../L[conv1]/conv" ...}` — the join key for traces
# whose events carry instruction names but no scope stat (the CPU
# TfrtCpuClient/Eigen runtime).
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*.*?"
    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def hlo_layer_map(compiled_hlo_text: str) -> dict[str, str]:
    """instruction name -> scope path, from the optimized HLO's op_name
    metadata.  TPU traces carry the scope as a per-event stat; CPU thunk
    traces carry only instruction names, so the executor's L[...] layer
    attribution needs this side-channel join (the profiled program's
    ``compiled.as_text()`` is the source of truth — same executable,
    same instruction names the thunk events report)."""
    return {name: op_name
            for name, op_name in _HLO_INSTR_RE.findall(compiled_hlo_text)
            if op_name}


def op_tables(log_dir: str, *, top: int = 30,
              layer_map: dict[str, str] | None = None) -> dict:
    """Aggregate the newest trace under ``log_dir``.

    Returns ``{plane, total_ms, by_category: [...], by_op: [...]}`` where
    rows carry total_ms, count, pct, gflops_per_s (achieved, from XLA's
    model-flops stat) and gb_per_s (achieved HBM bandwidth proxy from
    bytes_accessed).  Only leaf events on the "XLA Ops" line count.
    ``layer_map`` (see :func:`hlo_layer_map`) supplies scopes for events
    that carry none of their own — the CPU-runtime path to a by_layer
    table.
    """
    plane = device_plane(parse_xspace(find_xplane_file(log_dir)))
    events = []
    for lname, evs in plane.lines.items():
        if "XLA Ops" in lname and "Async" not in lname:
            events.extend(evs)
    if not events:
        # CPU TfrtCpuClient traces: HLO-named thunk events on the
        # client's execution line, with no category metadata — derive a
        # category from the HLO name stem and drop the runtime's own
        # bookkeeping events
        for lname, evs in plane.lines.items():
            if "tf_XLA" in lname:
                events.extend(
                    e for e in evs
                    if not e.meta.name.startswith(("ThunkExecutor",
                                                   "ThreadpoolListener")))

    if layer_map:
        for e in events:
            if not e.meta.scope:
                e.meta.scope = layer_map.get(e.meta.name, "")

    def category(m) -> str:
        if m.category:
            return m.category
        stem = m.name.split(".", 1)[0]
        return stem.rsplit("_", 1)[-1] if "_" in stem else stem

    leaf = [e for e in events if category(e.meta) not in _CONTAINERS]

    def agg(key_fn):
        rows: dict[str, dict] = {}
        for e in leaf:
            k = key_fn(e.meta)
            r = rows.setdefault(k, {"key": k, "ps": 0, "count": 0,
                                    "flops": 0, "bytes": 0})
            r["ps"] += e.duration_ps
            r["count"] += 1
            r["flops"] += e.meta.flops
            r["bytes"] += e.meta.bytes_accessed
        total = sum(r["ps"] for r in rows.values()) or 1
        out = []
        for r in sorted(rows.values(), key=lambda r: -r["ps"]):
            secs = r["ps"] / 1e12
            out.append({
                "op": r["key"],
                "total_ms": round(r["ps"] / 1e9, 3),
                "count": r["count"],
                "pct": round(100 * r["ps"] / total, 1),
                "gflops_per_s": round(r["flops"] / secs / 1e9, 1) if secs else 0,
                "gb_per_s": round(r["bytes"] / secs / 1e9, 1) if secs else 0,
            })
        return out

    by_cat = agg(lambda m: category(m) or "(uncategorized)")
    def op_key(m: OpMeta) -> str:
        base = m.label.rsplit(".", 1)
        return base[0] if len(base) == 2 and base[1].isdigit() else m.label
    by_op = agg(op_key)[:top]
    total_ms = sum(r["total_ms"] for r in by_cat)
    out = {"plane": plane.name, "total_ms": round(total_ms, 3),
           "by_category": by_cat, "by_op": by_op}
    # per-layer attribution when the program was built with the net
    # executor's L[...] named scopes (fused ops are attributed to the
    # fusion root's scope — post-fusion reality, unlike `caffe time`'s
    # pre-fusion per-layer timers).  Rows ``step.*`` and ``round.*`` are
    # phases of the step and of the round, not layers: solvers/step.py
    # and parallel/trainer.py open them
    if any(e.meta.layer() for e in leaf):
        out["by_layer"] = agg(lambda m: m.layer() or "(outside layers)")
    return out


def format_tables(tables: dict) -> str:
    out = [f"device plane: {tables['plane']}  "
           f"(busy {tables['total_ms']:.1f} ms total)"]
    sections = [("by HLO category", tables["by_category"]),
                ("top ops", tables["by_op"])]
    if "by_layer" in tables:
        sections.append(("by layer (L[...] scopes)", tables["by_layer"]))
    for title, rows in sections:
        out.append(f"\n-- {title} --")
        out.append(f"{'op':<40} {'ms':>9} {'count':>6} {'%':>6} "
                   f"{'GF/s':>9} {'GB/s':>8}")
        for r in rows:
            out.append(f"{r['op'][:40]:<40} {r['total_ms']:>9.2f} "
                       f"{r['count']:>6} {r['pct']:>6.1f} "
                       f"{r['gflops_per_s']:>9.1f} {r['gb_per_s']:>8.1f}")
    return "\n".join(out)
