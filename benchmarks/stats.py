"""Metric arithmetic of the benchmark: percentiles and spreads. Plain
Python, checked by selftest.py.

    python3 -m benchmarks.stats <file>...

reads the result line (the last line) of each file, which are runs of
one cell, and prints per metric the values, their median, the gate's
statistic and the inter-quartile spread."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), of a non-empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)` as the driver takes
    it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def range_spread(values: Sequence[float]) -> float:
    """max − min over the median: the steadiness gate's statistic."""
    return (max(values) - min(values)) / statistics.median(values)


def main(argv: List[str]) -> int:
    import json
    values: Dict[str, List[float]] = {}
    for path in argv[1:]:
        with open(path) as f:
            doc = json.loads(f.read().strip().splitlines()[-1])
        if not doc["correct"]:
            print(f"NOT CORRECT: {path}")
        for name, m in doc["metrics"].items():
            values.setdefault(name, []).append(m["value"])
    for name, xs in values.items():
        line = (f"{name}: n={len(xs)} median={statistics.median(xs):.6g} "
                f"range/median={range_spread(xs):.4f}")
        if len(xs) >= 2:
            line += f" iqr/median={iqr_spread(xs):.4f}"
        print(line, [round(x, 4) for x in xs])
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv))
