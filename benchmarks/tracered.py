"""Reduction of a profiler trace (.xplane.pb) to busy time and device
operations. Runs as a child (`python3 -m benchmarks.tracered <dir>
<out.json>`) under JAX_PLATFORMS=cpu: reading the file needs jax, and
the harness's parent stays off it.

busy_s    union of the intervals in which an operation ran on a device
          (the "XLA Ops" line of each /device: plane), averaged over
          the device planes
span_s    first to last timestamp of the device planes' events: the
          least the capture lasted on the device. (The host planes
          are no measure of it: their events run on through the
          profiler's own collection, 25 s for 10 s asked.)
ops       [name, seconds, calls] per jitted program (the "XLA Modules"
          line, the run id in parentheses stripped) and per device
          operation, summed over devices
breakdown the ten device operations that took most time, and the ten
          longest gaps with no operation on the device, each named by
          the operation that ended it

`reduce_planes` works on plain tuples so that selftest.py can check it
on the small recorded trace in benchmarks/data/.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s
_RUN_ID = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: List[Tuple[float, float, str]], top: int = 10
         ) -> List[Tuple[str, float]]:
    """Longest idle gaps between successive busy intervals, named by
    the operation that ended the gap."""
    out = []
    end = None
    for s, e, name in sorted(intervals):
        if end is not None and s > end:
            out.append((f"before {name[:80]}", s - end))
        end = e if end is None else max(end, e)
    return sorted(out, key=lambda g: -g[1])[:top]


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]]) -> Dict:
    """planes: {plane name: {line name: [events]}}."""
    # accelerator planes only: the profiler also writes bookkeeping
    # planes under /device: (e.g. "/device:CUSTOM:Megascale Trace")
    devices = {n: ls for n, ls in planes.items() if _DEVICE.match(n)}
    busy = []
    op_time: Dict[str, List[float]] = {}
    idle: List[Tuple[str, float]] = []
    for lines in devices.values():
        ops = lines.get("XLA Ops")
        if ops is None:
            ops = [e for n, evs in lines.items() if n != "XLA Modules"
                   and n != "Steps" for e in evs]
        busy.append(union_seconds((s, s + d) for _, s, d in ops))
        idle.extend(gaps([(s, s + d, n) for n, s, d in ops]))
        for n, _, d in ops:
            a = op_time.setdefault(n, [0.0, 0])
            a[0] += d
            a[1] += 1
        for n, _, d in lines.get("XLA Modules", []):
            a = op_time.setdefault("module:" + _RUN_ID.sub("", n), [0.0, 0])
            a[0] += d
            a[1] += 1
    every = [e for ls in devices.values() for evs in ls.values()
             for e in evs]
    span = ((min(s for _, s, _ in every), max(s + d for _, s, d in every))
            if every else (0.0, 0.0))
    table = sorted(([n, v[0], v[1]] for n, v in op_time.items()),
                   key=lambda r: -r[1])
    top_ops = [[n[:96], s] for n, s, _ in table
               if not n.startswith("module:")][:10]
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "span_s": span[1] - span[0],
            "devices_traced": len(devices), "ops": table[:200],
            "breakdown": {"device_ops": top_ops,
                          "idle_gaps": sorted(idle, key=lambda g: -g[1])[:10]}}


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events)
    return planes


def main(argv: List[str]) -> int:
    found = glob.glob(os.path.join(argv[1], "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        print("no .xplane.pb under " + argv[1], file=sys.stderr)
        return 1
    planes = read_xplane(found[0])
    if len(argv) > 3:
        # record the first N events of every device line as plain JSON
        # (how benchmarks/data/recorded_trace.json was made)
        n = int(argv[3])
        planes = {p: {ln: [list(e) for e in evs[:n]]
                      for ln, evs in lines.items()}
                  for p, lines in planes.items()}
        with open(argv[2], "w") as f:
            json.dump(planes, f)
        return 0
    out = reduce_planes(planes)
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
