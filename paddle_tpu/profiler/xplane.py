"""Minimal XSpace/XPlane protobuf reader + per-op statistics.

Reference analog: paddle/fluid/platform/profiler/event_node.cc +
profiler_statistic.py — the reference walks its own CUPTI event tree into
operator/kernel summary tables. On TPU the device trace is the xplane
protobuf emitted by jax.profiler (tsl/profiler/protobuf/xplane.proto);
rather than depending on tensorflow to decode it, this module parses the
few fields the tables need straight from the protobuf wire format
(varint / length-delimited), ~schema:

  XSpace   { repeated XPlane planes = 1; }
  XPlane   { int64 id=1; string name=2; repeated XLine lines=3;
             map<int64, XEventMetadata> event_metadata=4; }
  XLine    { int64 id=1; string name=2; int64 timestamp_ns=3;
             repeated XEvent events=4; }
  XEvent   { int64 metadata_id=1; int64 offset_ps=2; int64 duration_ps=3; }
  XEventMetadata { int64 id=1; string name=2; }
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List

__all__ = ["parse_xspace", "find_xplane_files", "op_stats",
           "format_op_table", "XPlane", "XLine", "XEvent"]


# -- protobuf wire-format primitives ----------------------------------------

def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """Yield (field_number, wire_type, value) over a message buffer.
    Length-delimited values come back as memoryview slices."""
    pos, n = 0, len(buf)
    while pos < n:
        tag, pos = _varint(buf, pos)
        field, wire = tag >> 3, tag & 7
        if wire == 0:                         # varint
            val, pos = _varint(buf, pos)
        elif wire == 1:                       # fixed64
            val = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 2:                       # length-delimited
            ln, pos = _varint(buf, pos)
            val = buf[pos:pos + ln]
            pos += ln
        elif wire == 5:                       # fixed32
            val = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:                                 # groups: not in this schema
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


# -- the slices of the schema the tables need --------------------------------

@dataclasses.dataclass
class XEvent:
    metadata_id: int = 0
    offset_ps: int = 0
    duration_ps: int = 0


@dataclasses.dataclass
class XLine:
    id: int = 0
    name: str = ""
    timestamp_ns: int = 0
    events: List[XEvent] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class XPlane:
    id: int = 0
    name: str = ""
    lines: List[XLine] = dataclasses.field(default_factory=list)
    event_names: Dict[int, str] = dataclasses.field(default_factory=dict)


def _parse_event(buf):
    e = XEvent()
    for field, _, val in _fields(buf):
        if field == 1:
            e.metadata_id = val
        elif field == 2:
            e.offset_ps = val
        elif field == 3:
            e.duration_ps = val
    return e


def _parse_line(buf):
    ln = XLine()
    for field, wire, val in _fields(buf):
        if field == 1:
            ln.id = val
        elif field == 2 and wire == 2:
            ln.name = bytes(val).decode("utf-8", "replace")
        elif field == 3:
            ln.timestamp_ns = val
        elif field == 4 and wire == 2:
            ln.events.append(_parse_event(val))
    return ln


def _parse_metadata_entry(buf):
    """map<int64, XEventMetadata> entry -> (id, name)."""
    key, name = 0, ""
    for field, wire, val in _fields(buf):
        if field == 1:
            key = val
        elif field == 2 and wire == 2:           # XEventMetadata
            for f2, w2, v2 in _fields(val):
                if f2 == 1:
                    key = v2 or key
                elif f2 == 2 and w2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
    return key, name


def _parse_plane(buf):
    p = XPlane()
    for field, wire, val in _fields(buf):
        if field == 1:
            p.id = val
        elif field == 2 and wire == 2:
            p.name = bytes(val).decode("utf-8", "replace")
        elif field == 3 and wire == 2:
            p.lines.append(_parse_line(val))
        elif field == 4 and wire == 2:
            k, name = _parse_metadata_entry(val)
            p.event_names[k] = name
    return p


def parse_xspace(path) -> List[XPlane]:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for field, wire, val in _fields(buf):
        if field == 1 and wire == 2:
            planes.append(_parse_plane(val))
    return planes


def find_xplane_files(trace_dir) -> List[str]:
    """jax.profiler writes <dir>/plugins/profile/<run>/<host>.xplane.pb."""
    return sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))


# -- aggregation (reference profiler_statistic.py operator/kernel tables) ----

def op_stats(planes: List[XPlane], plane_filter=None) -> Dict[str, dict]:
    """Aggregate event durations per op name across the selected planes.
    plane_filter: predicate on plane name; default = device planes
    (TPU/GPU) falling back to every non-empty plane (CPU runs)."""
    def is_device(name):
        return any(k in name for k in ("TPU", "GPU", "/device:"))

    chosen = [p for p in planes
              if (plane_filter(p.name) if plane_filter else is_device(p.name))]
    if not chosen:
        chosen = planes
    out: Dict[str, dict] = {}
    for plane in chosen:
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_names.get(ev.metadata_id,
                                             f"#{ev.metadata_id}")
                s = out.setdefault(name, {
                    "calls": 0, "total_ps": 0, "min_ps": float("inf"),
                    "max_ps": 0})
                s["calls"] += 1
                s["total_ps"] += ev.duration_ps
                s["min_ps"] = min(s["min_ps"], ev.duration_ps)
                s["max_ps"] = max(s["max_ps"], ev.duration_ps)
    for s in out.values():
        s["avg_ps"] = s["total_ps"] / max(s["calls"], 1)
    return out


def format_op_table(stats: Dict[str, dict], top=30, time_unit="ms") -> str:
    div = {"ms": 1e9, "us": 1e6, "ns": 1e3, "ps": 1.0}[time_unit]
    total = sum(s["total_ps"] for s in stats.values()) or 1
    lines = [f"{'device op':52s} {'calls':>7s} {f'total_{time_unit}':>12s} "
             f"{f'avg_{time_unit}':>10s} {'ratio':>7s}"]
    ranked = sorted(stats.items(), key=lambda kv: -kv[1]["total_ps"])
    for name, s in ranked[:top]:
        lines.append(
            f"{name[:52]:52s} {s['calls']:7d} {s['total_ps']/div:12.3f} "
            f"{s['avg_ps']/div:10.3f} {s['total_ps']/total:6.1%}")
    if len(ranked) > top:
        rest = sum(s["total_ps"] for _, s in ranked[top:])
        lines.append(f"{'… %d more' % (len(ranked) - top):52s} "
                     f"{'':7s} {rest/div:12.3f} {'':10s} {rest/total:6.1%}")
    return "\n".join(lines)
