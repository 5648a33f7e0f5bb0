"""Check `tad_agg_pod`: the result rows of the last COMPLETED `--agg-flow
pod` DBSCAN job are the reference's, every one and no other, each with
the reference's summed throughput and deviation, and the job counted
the series and the merged rows the reference counts.

The rows of the last job's answer (`stats`) are read by (podNamespace,
podLabels, direction, flowEndSeconds), strings as the answer carries
them, against references/tad_agg_pod.py over the generator's own rows
of every acked block: upstream's pod query (inbound and outbound arms,
labels `<> ''`, SUM a key and second), then references/dbscan.py over
the summed series.

  jobs_not_completed       exact: every job of the run COMPLETED
  aggpod_decision_mismatch (key, flowEndSeconds) decisions that differ
                           from the reference's / points scored; a row
                           under a key the reference has not (a label
                           from the other side's dictionary, '' for an
                           external destination) and a key sent twice
                           count here
  aggpod_stddev_gap        largest relative gap of
                           throughputStandardDeviation over the rows
                           whose decision the reference shares
  aggpod_throughput_gap    exact: of those rows, the ones whose
                           throughput is not the reference's sum, an
                           integer below 2^53: the aggregation itself
  aggpod_kind_gap          exact: rows whose aggType is not pod, whose
                           algoType is not DBSCAN, whose algoCalc is
                           not 0 or that carry a connection's, a pod
                           name's or a service's column
  aggpod_series_gap        exact: |theia_job_series_built_total{agg=
                           "pod"} - jobs x the reference's series|
  aggpod_rows_merged_gap   exact: |theia_job_series_rows_merged_total
                           {agg="pod"} - jobs x the row contributions
                           that fell on a (key, second) another held|

The two counters are the program's account of its own work, read after
quiescence over the process's whole life (the warm-up's job and the
window's). A manager that exports neither (a commit before them) has no
account to compare and the two numbers are left out, as a reader leaves
out a metric it finds nothing for (checks/npr_policies.py does the
same); the rows are compared whatever the manager exports.

A lower precision hardly moves a decision or a sum's string; the
deviation is what it moves. `control` is the reference with bfloat16
input (the summed series rounded) and float32 arithmetic in the
program's place, as `python3 -m benchmarks.control` asks for it.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

from benchmarks import check as _check
from benchmarks import gen as _gen
from benchmarks.references import tad_agg_pod as _ref

limits = ("aggpod_decision_mismatch", "aggpod_stddev_gap")
EXACT = ("aggpod_throughput_gap", "aggpod_kind_gap")
COUNTED = ("aggpod_series_gap", "aggpod_rows_merged_gap")
SERIES_BUILT = 'theia_job_series_built_total{kind="tad",agg="pod"}'
ROWS_MERGED = 'theia_job_series_rows_merged_total{kind="tad",agg="pod"}'
#: the columns of a result row that another mode's key fills
_OTHER_KEYS = (("sourceIP", ""), ("destinationIP", ""),
               ("sourceTransportPort", "0"),
               ("destinationTransportPort", "0"),
               ("protocolIdentifier", "0"), ("flowStartSeconds", "0"),
               ("podName", ""), ("destinationServicePortName", ""))

Point = Tuple[str, str, str, int]


def rows_by_point(rows: List[Dict]) -> Tuple[Dict[Point, Dict], int]:
    """(a job's anomaly rows by (podNamespace, podLabels, direction,
    flowEndSeconds), rows whose point an earlier row had)."""
    out: Dict[Point, Dict] = {}
    again = 0
    for r in rows:
        if r.get("anomaly") != "true":
            continue
        p = (r.get("podNamespace"), r.get("podLabels"), r.get("direction"),
             int(r["flowEndSeconds"]))
        again += p in out
        out[p] = r
    return out, again


def compare(got: Dict[Point, Tuple[float, float]],
            want: Dict[Point, Tuple[int, float]], scored: int,
            again: int = 0) -> Dict[str, float]:
    """The numbers of `got` ((throughput, deviation) at each decision)
    against the reference's `want`."""
    shared = sorted(got.keys() & want.keys())
    stddev = 0.0
    wrong_sum = 0
    if shared:
        g = np.array([got[p] for p in shared], np.float64)
        w = np.array([want[p] for p in shared], np.float64)
        stddev = float((np.abs(g[:, 1] - w[:, 1]) / w[:, 1]).max())
        wrong_sum = int((g[:, 0] != w[:, 0]).sum())
    return {"aggpod_decision_mismatch":
            (len(got.keys() ^ want.keys()) + again) / max(scored, 1),
            "aggpod_stddev_gap": stddev,
            "aggpod_throughput_gap": wrong_sum}


def kind_gap(rows) -> int:
    """Rows that are not a pod-mode DBSCAN job's."""
    return sum(r.get("aggType") != "pod" or r.get("algoType") != "DBSCAN"
               or float(r.get("algoCalc", 1)) != 0
               or any(str(r.get(col, blank)) != blank
                      for col, blank in _OTHER_KEYS)
               for r in rows)


def counted(metrics: Dict[str, float], jobs: int, want: Dict
            ) -> Dict[str, int]:
    """The two numbers of the program's counters, over `jobs` jobs;
    none where the manager exports neither."""
    if SERIES_BUILT not in metrics and ROWS_MERGED not in metrics:
        return {}
    return {
        "aggpod_series_gap": abs(
            int(metrics.get(SERIES_BUILT, 0)) - jobs * want["series"]),
        "aggpod_rows_merged_gap": abs(
            int(metrics.get(ROWS_MERGED, 0)) - jobs * want["merged"]),
    }


def check(ctx: Dict, rep) -> None:
    bad = n = done = 0
    last = None
    for i, spec in enumerate(ctx["specs"]):
        if spec["role"] != "jobs":
            continue
        window = ctx["results"][i]["records"]
        for r in window:
            n += 1
            bad += r.get("state") != "COMPLETED"
        done += sum(r.get("state") == "COMPLETED"
                    for r in ctx["warm"][i]["records"] + window)
        last = ctx["results"][i].get("last_result") or last
    rep.attempted += n
    rep.failed += bad
    rep.compare("jobs_not_completed", bad, 0, f"{n} jobs")
    traffic = ctx["traffic"]
    if last is None:
        for name in limits + EXACT + COUNTED:
            rep.compare(name, 1.0, 0, "no job result")
        return
    want = _ref.pod_job([(s, k) for s, k, _ in _check.streams(ctx)])
    rows, again = rows_by_point(json.loads(last).get("stats", []))
    got = {p: (float(r["throughput"]),
               float(r["throughputStandardDeviation"]))
           for p, r in rows.items()}
    nums = compare(got, want["rows"], want["scored"], again)
    nums["aggpod_kind_gap"] = kind_gap(rows.values())
    nums.update(counted(ctx["metrics_final"], done, want))
    detail = (f"{len(got)} rows, reference {len(want['rows'])} decisions, "
              f"{want['scored']} points of {want['series']} series, "
              f"{want['merged']} of {want['contributions']} row "
              f"contributions merged; {done} jobs counted")
    for name in limits:
        rep.compare(name, nums[name], _check.limit(traffic, name), detail)
    for name in EXACT + COUNTED:
        if name in nums:
            rep.compare(name, nums[name], 0, detail)
        else:
            rep.lines.append(f"check {name}: left out, the manager "
                             f"exports no {SERIES_BUILT}")


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
    want = _ref.pod_job(streams)
    got = _ref.pod_job(streams, precision)
    nums = compare(got["rows"], want["rows"], want["scored"])
    return {name: nums[name] for name in limits}
