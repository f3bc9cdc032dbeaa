"""From a profiler trace to device busy time, op attribution and idle gaps.

The JAX profiler writes an XSpace protobuf (``*.xplane.pb``). This module
decodes the few fields it needs straight from the wire format, so it rests
on nothing but the file:

- the device plane's "XLA Ops" line: one event per op run, whose metadata
  carries the HLO op's ``tf_op`` (the op-name path), ``source`` and
  ``source_stack`` (file:line of the JAX call that made it) and
  ``hlo_category``;
- the host thread that holds the benchmark's own spans (``bench.window``,
  ``traffic_wait``, ``engine.run``), with whatever else JAX recorded on that
  thread (dispatch, transfers).

Busy time is the union of the device's op intervals inside the traced
window (first window span's start to last window span's end). An op's name
is the last component of its op-name path and its source line, relative to
the checkout (``top_k @ repro/kernels/topk/ref.py:20``). An idle gap is
named by the innermost span on the benchmark's thread at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import struct
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


# ---------------------------------------------------------------------------
# protobuf wire format, just enough of XSpace
# ---------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of each field of one message;
    length-delimited values come as bytes, fixed64 as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> list[Event]


def _stat(buf: bytes, stat_names: dict):
    mid, value = None, None
    for num, wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num in (3, 7):
            value = v if num == 3 else ("ref", v)
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = v.decode("utf-8", "replace")
        elif num == 6:
            value = v
    if isinstance(value, tuple):
        value = stat_names.get(value[1], "")
    return stat_names.get(mid, str(mid)), value


def _plane(buf: bytes) -> Plane:
    name, raw_lines, ev_meta_raw, stat_names = "", [], [], {}
    for num, wt, v in _fields(buf):
        if num == 2:
            name = v.decode("utf-8", "replace")
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            ev_meta_raw.append(v)
        elif num == 5:
            key, meta = None, b""
            for n2, _, v2 in _fields(v):
                if n2 == 1:
                    key = v2
                elif n2 == 2:
                    meta = v2
            for n3, _, v3 in _fields(meta):
                if n3 == 2:
                    stat_names[key] = v3.decode("utf-8", "replace")
    ev_meta = {}
    for entry in ev_meta_raw:
        key, meta = None, b""
        for n2, _, v2 in _fields(entry):
            if n2 == 1:
                key = v2
            elif n2 == 2:
                meta = v2
        mname, mstats = "", {}
        for n3, _, v3 in _fields(meta):
            if n3 == 2:
                mname = v3.decode("utf-8", "replace")
            elif n3 == 5:
                k, val = _stat(v3, stat_names)
                mstats[k] = val
        ev_meta[key] = (mname, mstats)
    lines = {}
    for raw in raw_lines:
        lname, ts_ns, events = "", 0, []
        for num, wt, v in _fields(raw):
            if num == 2:
                lname = v.decode("utf-8", "replace")
            elif num == 3:
                ts_ns = _signed(v)
            elif num == 4:
                events.append(v)
        out = []
        for ev in events:
            mid, off_ps, dur_ps = 0, 0, 0
            for num, wt, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    off_ps = v
                elif num == 3:
                    dur_ps = v
            mname, mstats = ev_meta.get(mid, ("", {}))
            out.append(Event(mname, ts_ns + off_ps / 1e3, dur_ps / 1e3, mstats))
        lines.setdefault(lname, []).extend(out)
    return Plane(name, lines)


def read_planes(path: Path) -> list[Plane]:
    data = Path(path).read_bytes()
    return [_plane(v) for num, _, v in _fields(data) if num == 1]


def xplane_file(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one *.xplane.pb under {trace_dir}, found {len(files)}")
    return files[0]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def _rel(path: str) -> str:
    """A source path from the checkout on: ``.../src/repro/x.py:7`` ->
    ``repro/x.py:7``."""
    if "/src/" in path:
        return path.split("/src/", 1)[1]
    if "/chipbench/" in path:
        return "chipbench/" + path.split("/chipbench/", 1)[1]
    return path


def op_name(ev: Event) -> str:
    """An op's attributed name: the last component of its op-name path and
    its source line (``gather @ repro/core/filter.py:107``); the HLO
    instruction's own name where the metadata gives neither."""
    tf_op = str(ev.stats.get("tf_op", "")).rstrip(":")
    tail = tf_op.rsplit("/", 1)[-1] if tf_op else ""
    src = _rel(str(ev.stats.get("source", "")))
    if tail or src:
        return f"{tail or '?'} @ {src or '?'}"
    return ev.name.split(" ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduction:
    busy_s: float  # union of device op time in the window, mean over chips
    window_s: float
    windows: int  # benchmark window spans inside the trace
    ops: list  # Event of every device op inside the window
    gaps: dict  # host activity name -> idle seconds
    chips: int

    def seconds_of(self, kernel: str) -> float:
        """Device seconds of the ops made by the program's kernel package
        ``kernel`` (``repro/kernels/<kernel>/`` on the op's source stack),
        whichever implementation ran."""
        mark = f"/repro/kernels/{kernel}/"
        return sum(ev.dur_ns for ev in self.ops
                   if mark in str(ev.stats.get("source_stack", ""))
                   or mark in str(ev.stats.get("source", ""))) / 1e9 / self.chips

    def top_ops(self, n: int) -> list:
        agg = collections.Counter()
        for ev in self.ops:
            agg[op_name(ev)] += ev.dur_ns / 1e9 / self.chips
        return [[k, v] for k, v in agg.most_common(n)]

    def top_gaps(self, n: int) -> list:
        return [[k, v] for k, v in collections.Counter(self.gaps).most_common(n)]


def reduce(path: Path, window_span: str) -> Reduction:
    """Reduce the trace at ``path``; ``window_span`` names the benchmark's
    host span around each engine window."""
    planes = read_planes(path)
    host_line = None
    for p in planes:
        if p.name == "/host:CPU":
            for events in p.lines.values():
                if any(e.name == window_span for e in events):
                    host_line = events
    if host_line is None:
        return Reduction(0.0, 0.0, 0, [], {}, 1)
    wins = [e for e in host_line if e.name == window_span]
    t0 = min(e.start_ns for e in wins)
    t1 = max(e.start_ns + e.dur_ns for e in wins)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    chips = max(1, len(devices))
    ops, busy_ns, gaps = [], 0.0, collections.Counter()
    spans = sorted(host_line, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    for p in devices:
        evs = [e for e in p.lines.get(OPS_LINE, [])
               if e.start_ns < t1 and e.start_ns + e.dur_ns > t0]
        ops.extend(evs)
        busy = _union((max(e.start_ns, t0), min(e.start_ns + e.dur_ns, t1))
                      for e in evs)
        busy_ns += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_host_activity(spans, starts, (s + e) / 2)] += (e - s) / 1e9 / chips
    return Reduction(busy_ns / 1e9 / chips, (t1 - t0) / 1e9, len(wins), ops,
                     dict(gaps), chips)


def _host_activity(spans: list, starts: list, t: float) -> str:
    """The innermost span on the benchmark's thread that covers time t:
    spans on one thread nest, so it is the covering span that started
    last."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        e = spans[i]
        if e.start_ns + e.dur_ns >= t:
            return e.name
    return "(no span)"
