"""Reading a profiler capture back: device busy and idle time, and each
long idle gap of the device named by what the host was doing in it.

While a capture runs, every span and stage of obs/trace.py is also a
`jax.profiler.TraceAnnotation` (stat `theia=<Python thread name>`: a
line's own id is a hash of the thread handle), written by the
profiler onto the host thread's line of the same `.xplane.pb` that
holds the device planes, on the same clock (nanoseconds since the
session's start). `summarize(path)` reads that file — a trace
directory, the `.xplane.pb` itself, or the tar.gz that `theia profile`
downloads — and answers:

    devices, deviceBusySeconds, deviceSpanSeconds, deviceIdleShare
    annotations   seconds and calls per program annotation
    idleGaps      the ten longest gaps with no operation on a device,
                  each with the operation that ended it and, per host
                  thread, the annotation stacks open during it
                  ("ingest.request > detector.fetch") with the share
                  of the gap each covers

The file is an XSpace protobuf (tsl/profiler/protobuf/xplane.proto);
the few messages needed are decoded here from the wire format, so this
module needs neither jax nor protobuf. A CPU-only
process has no `/device:` plane; there the XLA CPU client's operation
events (the ones with an `hlo_op` stat) stand in for device work.

`summarize_planes` works on plain tuples, so a test can hand it a gap
of a recorded shape.
"""

from __future__ import annotations

import glob
import io
import json
import os
import re
import struct
import tarfile
from typing import Dict, Iterator, List, NamedTuple, Tuple

#: the stat every program annotation carries, its value the Python
#: thread's name (manager/profiling.py)
PROGRAM_STAT = "theia"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_HOST = re.compile(r"^/host:")
TOP_GAPS = 10
TOP_STACKS = 6


class Event(NamedTuple):
    name: str
    start_s: float       # seconds since the session's start
    duration_s: float
    stats: Tuple[str, ...]   # names of the stats the event carries
    thread: str = ""     # a program annotation's Python thread name


class Line(NamedTuple):
    id: int
    name: str
    events: List[Event]


# -- protobuf wire format ---------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int
            ) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value): a varint's integer, the
    (start, end) of a length-delimited field, or the raw bytes of a
    fixed one."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield key >> 3, wt, v
        elif wt == 2:
            n, i = _varint(buf, i)
            yield key >> 3, wt, (i, i + n)
            i += n
        elif wt == 1:
            yield key >> 3, wt, buf[i:i + 8]
            i += 8
        elif wt == 5:
            yield key >> 3, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _named(buf: bytes, lo: int, hi: int) -> Tuple[int, str]:
    """(id, name) of one map entry of XEventMetadata / XStatMetadata
    (both carry id = 1, name = 2; a display name, 4, wins for
    events)."""
    key, name, display = 0, "", ""
    for f, _, v in _fields(buf, lo, hi):
        if f == 1:
            key = v
        elif f == 2:
            for g, _, w in _fields(buf, *v):
                if g == 2:
                    name = buf[w[0]:w[1]].decode("utf-8", "replace")
                elif g == 4:
                    display = buf[w[0]:w[1]].decode("utf-8", "replace")
    return key, display or name


def _stat_value(buf: bytes, lo: int, hi: int,
                stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for f, wt, v in _fields(buf, lo, hi):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif f == 7:                 # a string interned as a stat name
            value = stat_names.get(v, str(v))
    return name, value


def _line(buf: bytes, lo: int, hi: int, event_names: Dict[int, str],
          stat_names: Dict[int, str]) -> Line:
    line_id, name, display, t0_ns = 0, "", "", 0
    spans: List[Tuple[int, int]] = []
    for f, _, v in _fields(buf, lo, hi):
        if f == 1:
            line_id = _signed(v)
        elif f == 2:
            name = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif f == 11:
            display = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif f == 3:
            t0_ns = _signed(v)
        elif f == 4:
            spans.append(v)
    events = []
    for elo, ehi in spans:
        meta = offset_ps = duration_ps = 0
        stats: List[str] = []
        thread = ""
        for f, _, v in _fields(buf, elo, ehi):
            if f == 1:
                meta = v
            elif f == 2:
                offset_ps = _signed(v)
            elif f == 3:
                duration_ps = _signed(v)
            elif f == 4:
                for g, _, sid in _fields(buf, *v):
                    if g == 1:
                        stat = stat_names.get(sid, str(sid))
                        stats.append(stat)
                        if stat == PROGRAM_STAT:
                            thread = str(_stat_value(
                                buf, v[0], v[1], stat_names)[1])
                        break
        events.append(Event(event_names.get(meta, str(meta)),
                            t0_ns * 1e-9 + offset_ps * 1e-12,
                            duration_ps * 1e-12, tuple(stats), thread))
    return Line(line_id, display or name, events)


def read_xspace(raw: bytes) -> Tuple[Dict[str, List[Line]],
                                     Dict[str, object]]:
    """({plane name: lines}, the "Task Environment" plane's stats:
    profile_start_time / profile_stop_time, wall-clock ns)."""
    planes: Dict[str, List[Line]] = {}
    env: Dict[str, object] = {}
    for f, _, v in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name = ""
        lines: List[Tuple[int, int]] = []
        stats: List[Tuple[int, int]] = []
        event_names: Dict[int, str] = {}
        stat_names: Dict[int, str] = {}
        for g, _, w in _fields(raw, *v):
            if g == 2:
                name = raw[w[0]:w[1]].decode("utf-8", "replace")
            elif g == 3:
                lines.append(w)
            elif g == 4:
                k, n = _named(raw, *w)
                event_names[k] = n
            elif g == 5:
                k, n = _named(raw, *w)
                stat_names[k] = n
            elif g == 6:
                stats.append(w)
        if name == "Task Environment":
            env.update(_stat_value(raw, lo, hi, stat_names)
                       for lo, hi in stats)
        planes.setdefault(name, []).extend(
            _line(raw, lo, hi, event_names, stat_names)
            for lo, hi in lines)
    return planes, env


# -- reduction ---------------------------------------------------------------

def _union(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def _device_ops(planes: Dict[str, List[Line]]
                ) -> Dict[str, List[Event]]:
    """Operations per device: the "XLA Ops" line of each accelerator
    plane; with no such plane, the host events that carry `hlo_op`
    (the CPU backend's operations) as one device "cpu"."""
    out: Dict[str, List[Event]] = {}
    for name, lines in planes.items():
        if not _DEVICE.match(name):
            continue
        ops = [ev for ln in lines if ln.name == "XLA Ops"
               for ev in ln.events]
        if not ops:
            ops = [ev for ln in lines
                   if ln.name not in ("XLA Modules", "Steps")
                   for ev in ln.events]
        out[name] = ops
    if not out:
        ops = [ev for name, lines in planes.items() if _HOST.match(name)
               for ln in lines for ev in ln.events
               if "hlo_op" in ev.stats]
        if ops:
            out["cpu"] = ops
    return out


def _stacks(events: List[Event]
            ) -> List[Tuple[float, float, str]]:
    """Flat, non-overlapping (start, end, "outer > inner") segments of
    one thread's nested annotations: each instant belongs to the
    innermost annotation open at it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, path)
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, path = stack.pop()
            if end > cursor:
                out.append((cursor, end, path))
                cursor = end

    for ev in sorted(events, key=lambda e: (e.start_s, -e.duration_s)):
        close_until(ev.start_s)
        if stack and ev.start_s > cursor:
            out.append((cursor, ev.start_s, stack[-1][1]))
        cursor = max(cursor, ev.start_s) if stack else ev.start_s
        path = (stack[-1][1] + " > " + ev.name) if stack else ev.name
        end = ev.start_s + ev.duration_s
        if stack:
            end = min(end, stack[-1][0])    # clock jitter: stay nested
        stack.append((end, path))
    close_until(float("inf"))
    return out


def summarize_planes(planes: Dict[str, List[Line]]
                     ) -> Dict[str, object]:
    """The reduction, on plain data (module docstring)."""
    devices = _device_ops(planes)
    busy = [_union([(e.start_s, e.start_s + e.duration_s) for e in ops])
            for ops in devices.values()]
    every = [e for ops in devices.values() for e in ops]
    span = (max(e.start_s + e.duration_s for e in every)
            - min(e.start_s for e in every)) if every else 0.0

    host: List[Tuple[str, List[Tuple[float, float, str]]]] = []
    totals: Dict[str, List[float]] = {}
    for name, lines in planes.items():
        if not _HOST.match(name):
            continue
        for ln in lines:
            own = [e for e in ln.events if PROGRAM_STAT in e.stats]
            if not own:
                continue
            thread = own[0].thread or f"{ln.name}/{ln.id}"
            host.append((thread, _stacks(own)))
            for e in own:
                t = totals.setdefault(e.name, [0.0, 0])
                t[0] += e.duration_s
                t[1] += 1

    gaps: List[Tuple[float, float, str, str]] = []
    for dev, ops in devices.items():
        end = None
        for e in sorted(ops, key=lambda e: e.start_s):
            if end is not None and e.start_s > end:
                gaps.append((e.start_s - end, end, dev, e.name))
            stop = e.start_s + e.duration_s
            end = stop if end is None else max(end, stop)
    gaps.sort(key=lambda g: -g[0])

    idle = []
    for length, start, dev, ended_by in gaps[:TOP_GAPS]:
        stop = start + length
        covered: Dict[Tuple[str, str], float] = {}
        for thread, segs in host:
            for s, e, path in segs:
                if e <= start or s >= stop:
                    continue
                key = (thread, path)
                covered[key] = covered.get(key, 0.0) + (
                    min(e, stop) - max(s, start))
        top = sorted(covered.items(), key=lambda kv: -kv[1])
        idle.append({
            "device": dev, "startSeconds": start, "seconds": length,
            "endedBy": ended_by[:96],
            "host": [{"thread": t, "span": p, "share": c / length}
                     for (t, p), c in top[:TOP_STACKS]]})
    mean_busy = sum(busy) / len(busy) if busy else 0.0
    return {
        "devices": sorted(devices),
        "deviceBusySeconds": mean_busy,
        "deviceSpanSeconds": span,
        "deviceIdleShare": (1.0 - mean_busy / span) if span else None,
        "firstDeviceEventSeconds": (min(e.start_s for e in every)
                                    if every else None),
        "annotations": {
            n: {"seconds": t[0], "calls": t[1]} for n, t in
            sorted(totals.items(), key=lambda kv: -kv[1][0])},
        "idleGaps": idle,
    }


# -- files ------------------------------------------------------------------

def _from_tar(raw: bytes) -> Tuple[bytes, Dict[str, object]]:
    """(the .xplane.pb's bytes, capture.json's document or {}) of a
    capture as manager/profiling.py packs it."""
    pb, capture = None, {}
    with tarfile.open(fileobj=io.BytesIO(raw), mode="r:*") as tar:
        for member in tar:
            if not member.isfile():
                continue
            if member.name.endswith(".xplane.pb") and pb is None:
                pb = tar.extractfile(member).read()
            elif os.path.basename(member.name) == "capture.json":
                capture = json.load(tar.extractfile(member))
    if pb is None:
        raise FileNotFoundError("no .xplane.pb in the archive")
    return pb, capture


def _from_path(path: str) -> Tuple[bytes, Dict[str, object]]:
    """The same from a trace directory, an .xplane.pb, or a tar.gz."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        capture = {}
        side = os.path.join(path, "capture.json")
        if os.path.exists(side):
            with open(side) as f:
                capture = json.load(f)
        path = found[0]
    elif path.endswith(".xplane.pb"):
        capture = {}
    else:
        with open(path, "rb") as f:
            return _from_tar(f.read())
    with open(path, "rb") as f:
        return f.read(), capture


def _summary(pb: bytes, capture: Dict[str, object]
             ) -> Dict[str, object]:
    planes, env = read_xspace(pb)
    out = summarize_planes(planes)
    out["profileStartNs"] = env.get("profile_start_time")
    out["profileStopNs"] = env.get("profile_stop_time")
    for key in ("startedAt", "stoppedAt", "pythonTracer"):
        if key in capture:
            out[key] = capture[key]
    return out


def summarize(path: str) -> Dict[str, object]:
    return _summary(*_from_path(path))


def summarize_archive(raw: bytes) -> Dict[str, object]:
    """The same over the tar.gz bytes a capture is served as."""
    return _summary(*_from_tar(raw))


def render(doc: Dict[str, object]) -> str:
    """The summary as the text `theia profile --summarize` prints."""
    share = doc.get("deviceIdleShare")
    lines = [
        f"devices: {', '.join(doc['devices']) or 'none'}   "
        f"busy {doc['deviceBusySeconds']:.6f}s of "
        f"{doc['deviceSpanSeconds']:.3f}s   idle "
        + ("n/a" if share is None else f"{100 * share:.2f}%")]
    if doc.get("startedAt"):
        lines.append(f"capture started at {doc['startedAt']} "
                     f"(python tracer "
                     f"{'on' if doc.get('pythonTracer') else 'off'})")
    lines.append("program annotations (seconds, calls):")
    for name, t in list(doc["annotations"].items())[:24]:
        lines.append(f"  {name:<32} {t['seconds']:>10.4f} "
                     f"{t['calls']:>8d}")
    lines.append("longest idle gaps of the device, by host span:")
    for g in doc["idleGaps"]:
        lines.append(f"  {g['seconds'] * 1e3:>9.2f} ms at "
                     f"{g['startSeconds']:.3f}s on {g['device']}, "
                     f"ended by {g['endedBy']}")
        if not g["host"]:
            lines.append("      (no program annotation open)")
        for h in g["host"]:
            lines.append(f"      {100 * h['share']:>5.1f}%  "
                         f"{h['thread']}: {h['span']}")
    return "\n".join(lines)
