"""Reading the manager's /metrics (Prometheus text) as numbers, and
deltas of counters and histograms over a window."""

from __future__ import annotations

import re
from typing import Dict, Tuple

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")


def parse(text: str) -> Dict[str, float]:
    """{'name{labels}': value}; labels kept verbatim, `le` buckets
    included (readers ask for _sum and _count)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
        except ValueError:
            continue
    return out


def delta(before: Dict[str, float], after: Dict[str, float],
          key: str) -> float:
    """after − before of one series; a series absent on both sides
    raises KeyError so that a reader can leave its metric out."""
    if key not in after and key not in before:
        raise KeyError(key)
    return after.get(key, 0.0) - before.get(key, 0.0)


def hist_delta(before: Dict[str, float], after: Dict[str, float],
               name: str, labels: str = "") -> Tuple[float, float]:
    """(sum seconds, count) of a histogram over the window."""
    return (delta(before, after, f"{name}_sum{labels}"),
            delta(before, after, f"{name}_count{labels}"))
