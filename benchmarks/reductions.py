"""Reductions from what a run recorded to the manifest's metrics.

A metric's reader is a small JSON file (end_to_end/<name>.json or
layer_metrics/<name>.json): {"reduce": <one of REDUCTIONS, or the name
of a file benchmarks/reduce/<name>.py with `reduce(data, p)`>, ...its
parameters}. The reductions are general (a rate, a percentile, a
histogram's mean over the window, a phase's seconds, a device
operation's time in the trace); a later PR adds a metric by adding a
file. A reader that finds nothing to read returns None and the metric
is left out of the result line.

`data` is what harness.run_cell gathered: worker records, /metrics
before and after the window, phase timers, compile counts from the
manager's log, and (traced runs) the reduced device trace.
"""

from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional

from . import extend as _extend
from . import prom as _prom
from . import roofline as _roofline
from . import stats as _stats


def _records(data: Dict, role: str) -> List[Dict]:
    """The window's records of one role. In a traced run only those
    due and completed inside `clean`, the part of the window in which
    the profiler neither ran nor exported: it slows the host
    severalfold, so it gives the device's numbers and the rest of the
    window the host's."""
    lo, hi = data.get("clean", (0.0, float("inf")))
    out = []
    for spec, res in zip(data["specs"], data["results"]):
        if spec["role"] == role:
            out.extend(r for r in res["records"]
                       if r["due"] >= lo and r["ack"] <= hi)
    return out


def _traced_per_s(data: Dict, role: str) -> float:
    """Requests a second that completed while the profiler ran,
    counted over the part of the capture that is certain (see
    harness.run_cell)."""
    lo, hi = data["trace"]["sure"]
    n = sum(1 for spec, res in zip(data["specs"], data["results"])
            if spec["role"] == role for r in res["records"]
            if _ok(r) and lo <= r["ack"] <= hi)
    return n / (hi - lo)


def _in_window(data: Dict, recs: List[Dict]) -> List[Dict]:
    """Requests completed inside [t_open, t_open + seconds)."""
    end = data["t_open"] + data["seconds"]
    return [r for r in recs if r["ack"] <= end]


def _ok(r: Dict) -> bool:
    return r.get("status") == 200 and not r.get("degraded")


def r_rate(data: Dict, p: Dict) -> Optional[float]:
    """Sum of a record field over the requests that completed in the
    window, over the window's seconds: all the work, all the time."""
    recs = [r for r in _in_window(data, _records(data, p["role"]))
            if _ok(r)]
    return sum(r.get(p["field"]) or 0 for r in recs) / data["seconds"]


def _latencies_ms(data: Dict, p: Dict) -> List[float]:
    """Due time → completion of every request due in the window; one
    that failed or was refused enters at the window's length, so it
    lies beyond any percentile's limit."""
    out = []
    for r in _records(data, p["role"]):
        if p.get("panel") and r.get("panel") != p["panel"]:
            continue
        out.append((r["ack"] - r["due"]) * 1e3 if _ok(r)
                   else data["seconds"] * 1e3)
    return out


def r_latency_percentile(data: Dict, p: Dict) -> Optional[float]:
    lat = _latencies_ms(data, p)
    if not lat:
        return None
    return _stats.percentile(lat, p["q"]) * p.get("scale", 1.0)


def r_starved_ms(data: Dict, p: Dict) -> Optional[float]:
    vals = [res.get("starved_ms") for res in data["results"]
            if res.get("starved_ms") is not None]
    return sum(vals) if vals else None


def r_slowest_group_p50(data: Dict, p: Dict) -> Optional[float]:
    groups: Dict[str, List[float]] = {}
    for r in _records(data, p["role"]):
        if _ok(r):
            groups.setdefault(r[p["group"]], []).append(
                (r["ack"] - r["due"]) * 1e3)
    if not groups:
        return None
    return max(statistics.median(v) for v in groups.values())


def _hist(data: Dict, p: Dict):
    try:
        return _prom.hist_delta(data["metrics_before"],
                                data["metrics_after"], p["series"],
                                p.get("labels", ""))
    except KeyError:
        return None


def r_hist_mean_ms(data: Dict, p: Dict) -> Optional[float]:
    h = _hist(data, p)
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1] * 1e3


def _hist_per_block(data: Dict, p: Dict, which: int) -> Optional[float]:
    """A histogram's seconds (which=0) or observations (which=1) over
    the window, per ingest block acked in it."""
    h = _hist(data, p)
    try:
        n = _prom.delta(data["metrics_before"], data["metrics_after"],
                        "theia_ingest_batches_total")
    except KeyError:
        return None
    if h is None or n <= 0:
        return None
    return h[which] / n


def r_hist_ms_per_block(data: Dict, p: Dict) -> Optional[float]:
    v = _hist_per_block(data, p, 0)
    return None if v is None else v * 1e3


def r_hist_count_per_block(data: Dict, p: Dict) -> Optional[float]:
    return _hist_per_block(data, p, 1)


def r_counter_delta(data: Dict, p: Dict) -> Optional[float]:
    try:
        return _prom.delta(data["metrics_before"], data["metrics_after"],
                           p["series"])
    except KeyError:
        return None


def r_request_minus_legs(data: Dict, p: Dict) -> Optional[float]:
    """Client-side time of an ingest request minus the manager's own
    request histogram (decode + max(legs)): HTTP, admission, dedup and
    the ack, per block."""
    recs = [r for r in _records(data, "producer") if _ok(r)]
    h = _hist(data, {"series": "theia_ingest_request_seconds"})
    if not recs or h is None or h[1] <= 0:
        return None
    client = sum(r["ack"] - r["send"] for r in recs) / len(recs)
    return (client - h[0] / h[1]) * 1e3


def r_phase_s(data: Dict, p: Dict) -> Optional[float]:
    return data["phases"].get(p["phase"])


def r_setup_s(data: Dict, p: Dict) -> Optional[float]:
    return data["setup_s"]


def r_compile(data: Dict, p: Dict) -> Optional[float]:
    return float(data[p.get("when", "setup") + "_compiles"][p["field"]])


def r_job_span_s(data: Dict, p: Dict) -> Optional[float]:
    """Median over the window's jobs of one client-side span of a job:
    `server_run_s` (the job record's endTime − startTime: scan,
    tensorize, score, result write) or `outside_run_s` (turn-around
    minus that: dispatch, polling, and the final answer that carries
    the result rows)."""
    vals = []
    for r in _records(data, "jobs"):
        if r.get("state") != "COMPLETED" or "server_run_s" not in r:
            continue
        total = r["ack"] - r["due"]
        vals.append(r["server_run_s"] if p["span"] == "server_run_s"
                    else total - r["server_run_s"])
    return statistics.median(vals) if vals else None


def _trace(data: Dict) -> Optional[Dict]:
    return data.get("trace")


def r_trace_idle_share(data: Dict, p: Dict) -> Optional[float]:
    t = _trace(data)
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _op_seconds(t: Dict, match: str) -> Optional[List[float]]:
    hits = [(s, n) for name, s, n in t["ops"] if match in name]
    if not hits:
        return None
    return [sum(s for s, _ in hits), sum(n for _, n in hits)]


def r_trace_op_us_per_block(data: Dict, p: Dict) -> Optional[float]:
    """Device time of the operations whose name contains `match`, per
    ingest block acked while the trace ran: blocks a second under the
    profiler, times the seconds the trace spans."""
    t = _trace(data)
    if not t:
        return None
    hit = _op_seconds(t, p["match"])
    blocks = _traced_per_s(data, "producer") * t["window_s"]
    if hit is None or blocks <= 0:
        return None
    return hit[0] / blocks * 1e6


def r_trace_op_ms_per_call(data: Dict, p: Dict) -> Optional[float]:
    t = _trace(data)
    if not t:
        return None
    hit = _op_seconds(t, p["match"])
    if hit is None or hit[1] <= 0:
        return None
    return hit[0] / hit[1] * 1e3 * p.get("calls_per_event", 1)


def r_roofline_share(data: Dict, p: Dict) -> Optional[float]:
    """The least time the chip could take for one call (bytes and
    operations from shapes, roofline.py) over the measured device time
    of one call, in %."""
    t = _trace(data)
    if not t:
        return None
    per_call_ms = r_trace_op_ms_per_call(data, p)
    if not per_call_ms:
        return None
    least_s = _roofline.least_seconds(p["kernel"], data, data["device"])
    return 100.0 * least_s / (per_call_ms / 1e3)


REDUCTIONS: Dict[str, Callable[[Dict, Dict], Optional[float]]] = {
    name[2:]: fn for name, fn in list(globals().items())
    if name.startswith("r_") and callable(fn)}


def resolve_all(bench, cell: str) -> None:
    """Every reduction, and every kernel's roofline function, that the
    cell's readers of either section name: found before the run
    starts, or the run is broken (extend.RunFailed)."""
    for section in ("end_to_end", "per_layer"):
        for m in bench.metrics_of(cell, section):
            reader = bench.reader(section, m["name"])
            _extend.resolve("reduction", reader["reduce"])
            if "kernel" in reader:
                _extend.resolve("kernel", reader["kernel"])


def reduce_all(bench, cell: str, section: str, data: Dict) -> Dict:
    out = {}
    for m in bench.metrics_of(cell, section):
        reader = bench.reader(section, m["name"])
        value = _extend.resolve("reduction", reader["reduce"])(data, reader)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
