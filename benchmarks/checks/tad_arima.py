"""Check `tad_arima`: the ARIMA job's result rows are the float64
reference's decisions, with the reference's forecast and deviation at
each, at the cadence the job states.

Decisions alone would not do here: at the cell's traffic a spike is 50
times the base and the series' sample deviation about 1.5 times, so
every spike is a decision whatever the forecast says, and a kernel that
forecasts nothing would pass. The rows of the last COMPLETED job carry
`algoCalc` (the forecast in levels), `throughputStandardDeviation` and
`refitEvery`; all three are read.

  jobs_not_completed       exact: every job of the run COMPLETED
  arima_decision_mismatch  (connection, flowEndSeconds) decisions that
                           differ from the reference's / points scored
  arima_forecast_gap       largest gap between `algoCalc` and the
                           reference's forecast over the rows whose
                           decision the reference shares (a row it does
                           not share counts under the mismatch), both
                           taken to the Box-Cox scale of that
                           connection in the reference (its lambda and
                           geometric mean): the relative gap in levels
                           wherever the forecast is near the series'
                           level, and well-conditioned where it is not
  arima_stddev_gap         largest relative gap of
                           throughputStandardDeviation
  arima_refit_gap          exact: rows whose refitEvery is not what the
                           spec's resolves to over the window's time
                           axis (max(1, T // 2048) for 0)

The reference (references/arima.py) runs over the generator's own rows
at the cell's own size after the window, and takes a few seconds.
`control` is the reference with bfloat16 input and float32 arithmetic in
the program's place, as `python3 -m benchmarks.control` asks for it.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

from benchmarks import check as _check
from benchmarks import gen as _gen
from benchmarks import reference as _series
from benchmarks.references import arima as _ref

limits = ("arima_decision_mismatch", "arima_forecast_gap",
          "arima_stddev_gap")

Point = Tuple[int, int, int]        # producer, connection, flowEndSeconds


def spec_refit(traffic: Dict) -> int:
    """`refitEvery` of the jobs the traffic file sends; absent is the
    server's default 1."""
    for group in traffic["workers"]:
        if group["role"] == "jobs":
            value = group["job"]["spec"].get("refitEvery")
            return 1 if value is None else int(value)
    return 1


def rows_by_point(rows: List[Dict], start: int) -> Dict[Point, Dict]:
    """A job's anomaly rows by (producer, connection, flowEndSeconds);
    the connection from the generator's key layout, as
    check.tad_decisions_from_rows reads it."""
    out = {}
    for r in rows:
        if r.get("anomaly") != "true":
            continue
        producer = int(r["sourceIP"].split(".")[1])
        j = (int(r["sourceTransportPort"]) - 32768
             + _gen.PORT_SPAN * (start - 10 - int(r["flowStartSeconds"])))
        out[(producer, j, int(r["flowEndSeconds"]))] = r
    return out


def reference_points(streams, refit_spec: int, precision: str = "f64"
                     ) -> Tuple[Dict[Point, Tuple[float, ...]], int, int]:
    """({point: (forecast, deviation, lambda, geometric mean)} of the
    reference's decisions, points scored, the cadence it ran with)
    over (stream, blocks) pairs. Lambda and the geometric mean are the
    scale the point's series was modelled on."""
    want: Dict[Point, Tuple[float, ...]] = {}
    scored = refit = 0
    for stream, n in streams:
        vals, times, mask = _series.series_of(stream, n)
        refit = _ref.effective_refit(refit_spec, vals.shape[1])
        job = _ref.arima_job(vals, mask, refit, precision)
        scored += int(mask.sum())
        for c, t in zip(*np.nonzero(job["anomaly"])):
            want[(stream.producer, int(c), int(times[c, t]))] = (
                float(job["pred"][c, t]), float(job["std"][c]),
                float(job["lam"][c]), float(job["gm"][c]))
    return want, scored, refit


def compare(got: Dict[Point, Tuple[float, ...]],
            want: Dict[Point, Tuple[float, ...]], scored: int
            ) -> Dict[str, float]:
    """The three tolerated numbers of `got` (forecast, deviation, ...)
    against the reference's `want`. The forecasts are compared on the
    scale the reference modelled the series on (references/arima.py
    `on_model_scale`: for a forecast near its series' level the gap
    there is the relative gap in levels, and the point after a spike,
    whose forecast in levels is ill-conditioned or beyond the
    transform's range, is held to the same arithmetic as every other);
    the deviations relatively."""
    shared = sorted(got.keys() & want.keys())
    forecast = stddev = 0.0
    if shared:
        g = np.array([got[p][:2] for p in shared], np.float64)
        w = np.array([want[p] for p in shared], np.float64)
        forecast = float(np.abs(
            _ref.on_model_scale(g[:, 0], w[:, 2], w[:, 3])
            - _ref.on_model_scale(w[:, 0], w[:, 2], w[:, 3])).max())
        stddev = float((np.abs(g[:, 1] - w[:, 1]) / w[:, 1]).max())
    return {"arima_decision_mismatch":
            len(got.keys() ^ want.keys()) / max(scored, 1),
            "arima_forecast_gap": forecast, "arima_stddev_gap": stddev}


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
    names = limits + ("arima_refit_gap",)
    if last is None:
        for name in names:
            rep.compare(name, 1.0, 0, "no job result")
        return
    rows = rows_by_point(
        json.loads(last).get("stats", []),
        int(traffic["generator"].get("start_time", _gen.DEFAULT_START)))
    want, scored, refit = reference_points(
        [(s, k) for s, k, _ in _check.streams(ctx)], spec_refit(traffic))
    got = {p: (float(r["algoCalc"]),
               float(r["throughputStandardDeviation"]))
           for p, r in rows.items()}
    nums = compare(got, want, scored)
    detail = (f"{len(got)} rows, reference {len(want)} decisions, "
              f"{scored} points scored")
    for name in limits:
        rep.compare(name, nums[name], _check.limit(traffic, name), detail)
    wrong = sum(int(r.get("refitEvery", -1)) != refit
                for r in rows.values())
    rep.compare("arima_refit_gap", wrong, 0,
                f"the reference ran with refitEvery {refit}")


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
    refit = spec_refit(traffic)
    want, scored, _ = reference_points(streams, refit)
    got, _, _ = reference_points(streams, refit, precision)
    return compare(got, want, scored)
