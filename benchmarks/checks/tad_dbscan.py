"""Check `tad_dbscan`: the DBSCAN job's result rows are the reference's
decisions, with the reference's deviation at each and nothing in
`algoCalc`.

The rows of the last COMPLETED job are read: one for each point
upstream's `DBSCAN(min_samples=4, eps=250000000)` labels noise in its
connection's series.

  jobs_not_completed        exact: every job of the run COMPLETED
  dbscan_decision_mismatch  (connection, flowEndSeconds) decisions that
                            differ from the reference's / points scored
  dbscan_stddev_gap         largest relative gap of
                            throughputStandardDeviation over the rows
                            whose decision the reference shares (a row
                            it does not share counts under the mismatch)
  dbscan_calc_gap           exact: rows whose algoCalc is not 0 (the
                            placeholder upstream writes) or whose
                            algoType is not DBSCAN

The decisions do the algorithm's duty: a kernel without the
reachability pass flags every border point, one that does not count
the point itself turns core points with three neighbours into noise,
`<` for `<=` loses a pair at eps exactly, and each shows as a mismatch
(benchmarks/tests/test_tad_dbscan.py). A lower precision hardly moves
a decision (a spike would have to lie within its rounding of eps from
the next point); the deviation is what it moves.

The reference (references/dbscan.py) sorts where the program tests
pairs, runs over the generator's own rows at the cell's own size after
the window, and takes about a second. `control` is the reference with
bfloat16 input and float32 arithmetic in the program's place, as
`python3 -m benchmarks.control` asks for it.
"""

from __future__ import annotations

import json
from typing import Dict, Tuple

import numpy as np

from benchmarks import check as _check
from benchmarks import gen as _gen
from benchmarks import reference as _series
from benchmarks.checks.tad_arima import Point, rows_by_point
from benchmarks.references import dbscan as _ref

limits = ("dbscan_decision_mismatch", "dbscan_stddev_gap")


def reference_points(streams, precision: str = "f64"
                     ) -> Tuple[Dict[Point, float], int]:
    """({point: deviation of its series} of the reference's decisions,
    points scored) over (stream, blocks) pairs."""
    want: Dict[Point, float] = {}
    scored = 0
    for stream, n in streams:
        vals, times, mask = _series.series_of(stream, n)
        _, std, anomaly = _ref.dbscan_scores(vals, mask,
                                             precision=precision)
        scored += int(mask.sum())
        for c, t in zip(*np.nonzero(anomaly)):
            want[(stream.producer, int(c), int(times[c, t]))] = \
                float(std[c])
    return want, scored


def compare(got: Dict[Point, float], want: Dict[Point, float],
            scored: int) -> Dict[str, float]:
    """The two tolerated numbers of `got` (a deviation at each
    decision) against the reference's `want`."""
    shared = sorted(got.keys() & want.keys())
    stddev = 0.0
    if shared:
        g = np.array([got[p] for p in shared], np.float64)
        w = np.array([want[p] for p in shared], np.float64)
        stddev = float((np.abs(g - w) / w).max())
    return {"dbscan_decision_mismatch":
            len(got.keys() ^ want.keys()) / max(scored, 1),
            "dbscan_stddev_gap": stddev}


def check(ctx: Dict, rep) -> None:
    bad = n = 0
    last = None
    for spec, res in zip(ctx["specs"], ctx["results"]):
        if spec["role"] != "jobs":
            continue
        for r in res["records"]:
            n += 1
            bad += r.get("state") != "COMPLETED"
        last = res.get("last_result") or last
    rep.attempted += n
    rep.failed += bad
    rep.compare("jobs_not_completed", bad, 0, f"{n} jobs")
    traffic = ctx["traffic"]
    if last is None:
        for name in limits + ("dbscan_calc_gap",):
            rep.compare(name, 1.0, 0, "no job result")
        return
    rows = rows_by_point(
        json.loads(last).get("stats", []),
        int(traffic["generator"].get("start_time", _gen.DEFAULT_START)))
    want, scored = reference_points(
        [(s, k) for s, k, _ in _check.streams(ctx)])
    got = {p: float(r["throughputStandardDeviation"])
           for p, r in rows.items()}
    nums = compare(got, want, scored)
    detail = (f"{len(got)} rows, reference {len(want)} decisions, "
              f"{scored} points scored")
    for name in limits:
        rep.compare(name, nums[name], _check.limit(traffic, name), detail)
    wrong = sum(float(r.get("algoCalc", 1)) != 0
                or r.get("algoType") != "DBSCAN" for r in rows.values())
    rep.compare("dbscan_calc_gap", wrong, 0,
                "algoCalc is upstream's 0.0 placeholder")


def control(traffic: Dict, seed: int, n_blocks: int, precision: str
            ) -> Dict[str, float]:
    """The check's tolerated numbers with the reference in `precision`
    in the program's place."""
    streams = []
    producer = 0
    for group in traffic["workers"]:
        if group["role"] == "producer":
            for _ in range(int(group.get("count", 1))):
                streams.append((_gen.stream(traffic, seed, producer),
                                n_blocks))
                producer += 1
    want, scored = reference_points(streams)
    got, _ = reference_points(streams, precision)
    return compare(got, want, scored)
