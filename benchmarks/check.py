"""The comparison that decides `correct`.

Nothing here depends on when a request landed. During the window the
workers only record; this module runs after the workers have stopped
and the manager has settled (harness.quiesce), and compares what the
manager then holds and what the timed requests returned with the plain
reference (benchmarks/reference.py) over the generator's own rows.
Every number compared is printed beside its limit. Exact comparisons
have the limit 0; the tolerances (`alert_count_gap`,
`alert_probe_block_gap`, `tad_decision_mismatch`) are stated in the
traffic file under `limits` and PERF.md gives the readings they were
set from.

Which checks a cell runs is its traffic file's `checks` list:

  acks            every ingest request answered 200 with the block's
                  own row count, no duplicate, no brownout (`degraded`)
  store_totals    after quiescence `/query` by clusterUUID gives, per
                  producer, exactly the acked blocks' row count and
                  sum(octetDeltaCount) (exactly-once; every acked row
                  readable)
  detector_series no connection series dropped, and as many series as
                  distinct connections were sent
  detector_alerts connection-anomaly alerts against the float64
                  detector reference over the same blocks in the same
                  per-connection order: the manager's counter over the
                  whole run, and block by block over the probe blocks
                  sent one at a time once the window has closed
  jobs            every job COMPLETED; the last job's anomaly
                  decisions per (connection, flowEndSeconds) against
                  the float64 TAD EWMA reference
  panels          every answer of a panel over its closed range is the
                  same bytes, and equals the reference's panel

Those are the built-in checks (`CHECKS`). Any other name in the list is
a file `benchmarks/checks/<name>.py` (extend.py) with `check(ctx, rep)`;
`producer_records`, `streams` and `limit` below are what such a file
builds on, and `ctx` is what harness.run_cell gathered (README.md).
"""

from __future__ import annotations

import json
from typing import Callable, Dict, List, Tuple

import numpy as np

from . import extend as _extend
from . import gen as _gen
from . import reference as _ref
from .extend import RunFailed
from .gen import DEFAULT_START, PORT_SPAN, cluster_uuid

ALERTS_SERIES = 'theia_ingest_alerts_total{kind="connection_anomaly"}'


class Report:
    def __init__(self) -> None:
        self.lines: List[str] = []
        self.numbers: Dict[str, Dict] = {}    # name → value and limit
        self.correct = True
        self.attempted = 0
        self.failed = 0

    def compare(self, name: str, value, limit, detail: str = "") -> None:
        ok = bool(value <= limit)
        self.correct &= ok
        self.numbers[name] = {"value": _plain(value), "limit": _plain(limit)}
        self.lines.append(
            f"check {name}: value={value!r} limit={limit!r} "
            f"{'ok' if ok else 'FAILED'}" + (f" ({detail})" if detail else ""))

    def doc(self) -> Dict:
        return {"lines": self.lines, "numbers": self.numbers,
                "correct": self.correct,
                "attempted": self.attempted, "failed": self.failed}


def _plain(x):
    """A numpy scalar as the Python number json can write."""
    return x.item() if hasattr(x, "item") else x


def config_preconditions(config: Dict, health: Dict) -> None:
    """The manager must be running what the configuration file says
    (engine names as /healthz reports them); a mismatch is a broken
    run, not an incorrect answer."""
    expect = config.get("expect", {})
    got = {"detector_engine": health["ingest"]["engine"]["name"],
           "store_engine": health.get("store", {}).get("engine"),
           "detector_shards": health["ingest"]["shards"]}
    for key, want in expect.items():
        if want is not None and got.get(key) != want:
            raise RunFailed(f"configuration expects {key}={want!r}, the "
                            f"manager reports {got.get(key)!r}")


def limit(traffic: Dict, name: str):
    """A tolerance of the traffic file's `limits`. One that is not
    there is a broken run, never a default."""
    try:
        return traffic["limits"][name]
    except KeyError:
        raise RunFailed(f"traffic file {traffic.get('name')!r} states no "
                        f"limit {name!r}") from None


def producer_records(ctx: Dict) -> List[Tuple[Dict, List[Dict]]]:
    """(spec, all of that producer's records in order) per producer."""
    out = []
    for i, spec in enumerate(ctx["specs"]):
        if spec["role"] != "producer":
            continue
        recs = (ctx["preload"][i]["records"] + ctx["warm"][i]["records"]
                + ctx["results"][i]["records"]
                + ctx["probes"][i]["records"])
        out.append((spec, recs))
    return out


def streams(ctx: Dict) -> List[Tuple["_CachedStream", int, List[Dict]]]:
    """(stream, number of blocks acked, records) per producer. Blocks
    are sent in order one at a time, so the acked blocks are a prefix
    unless a request failed (then `acks` has already failed the run)."""
    if "_streams" not in ctx:        # one regeneration for all checks
        out = []
        for spec, recs in producer_records(ctx):
            stream = _CachedStream(_gen.stream(
                ctx["traffic"], ctx["seed"], spec["producer"]))
            n = sum(1 for r in recs if r["status"] == 200)
            out.append((stream, n, recs))
        ctx["_streams"] = out
    return ctx["_streams"]


class _CachedStream:
    """A stream of whatever key law, keeping each block's values:
    several checks walk the same blocks."""

    def __init__(self, stream) -> None:
        self._stream = stream
        self._values: Dict[int, Dict] = {}

    def __getattr__(self, name: str):
        return getattr(self._stream, name)

    def values(self, b: int) -> Dict:
        v = self._values.get(b)
        if v is None:
            v = self._values[b] = self._stream.values(b)
        return v


def check_acks(ctx: Dict, rep: Report) -> None:
    bad = n = 0
    first = ""
    for _, recs in producer_records(ctx):
        for r in recs:
            n += 1
            if (r["status"] != 200 or r.get("rows") != r["rows_sent"]
                    or r.get("duplicate") or r.get("degraded")
                    or r.get("malformed")):
                bad += 1
                first = first or (
                    f"; first: block {r['block']} status {r['status']} "
                    f"{r.get('error') or r.get('degraded') or r.get('rows')}")
    rep.attempted += n
    rep.failed += bad
    rep.compare("acks_not_whole", bad, 0, f"{n} ingest requests{first}")


def check_store_totals(ctx: Dict, rep: Report) -> None:
    mgr = ctx["manager"]
    doc = mgr.json("/query?group_by=clusterUUID&agg=count,"
                   "sum:octetDeltaCount&k=0&cache=0")
    got = {r["clusterUUID"]: (int(r["count"]),
                              int(r["sum(octetDeltaCount)"]))
           for r in doc["rows"]}
    rows_gap = octets_gap = 0
    want_rows = 0
    for stream, n, _ in streams(ctx):
        rows, octets = _ref.block_totals(stream, n)
        want_rows += rows
        g = got.pop(cluster_uuid(stream.producer), (0, 0))
        rows_gap += abs(g[0] - rows)
        octets_gap += abs(g[1] - octets)
    rows_gap += sum(g[0] for g in got.values())     # rows of no producer
    rep.compare("store_rows_gap", rows_gap, 0, f"{want_rows} acked rows")
    rep.compare("store_octets_gap", octets_gap, 0,
                "sum(octetDeltaCount) by clusterUUID")


def check_detector_series(ctx: Dict, rep: Report) -> None:
    shards = ctx["health"]["ingest"]["perShard"]
    dropped = sum(s["droppedSeries"] for s in shards)
    series = sum(s["series"] for s in shards)
    want = 0
    for stream, n, _ in streams(ctx):
        sent = [stream.conn_index(b) for b in range(n)]
        want += np.unique(np.concatenate(sent)).size if sent else 0
    rep.compare("detector_series_dropped", dropped, 0)
    rep.compare("detector_series_gap", abs(series - want), 0,
                f"{want} distinct connections sent")


def check_detector_alerts(ctx: Dict, rep: Report) -> None:
    """Three numbers, none of which mixes alert kinds. An ack counts
    connection-anomaly, heavy-hitter and traffic-shape alerts together
    (some ten of the latter a block, and their number depends on how
    the producers' requests interleave), the counters of /metrics tell
    the kinds apart but only in total, and the alert ring keeps the
    newest 1,000 of a block's thousands. So:

    `ack_alerts_sum_gap`: all acks' alert counts add up to the
    counters' total, exactly: the acks and the counters tell one story.

    `alert_count_gap`: the counter of connection-anomaly alerts over
    the whole run against the reference's total. A net count: flips in
    both directions cancel in it, so a lower precision hardly moves
    it; it is there for blocks, or parts of blocks, that were acked
    and not scored.

    `alert_probe_block_gap`: once the window has closed, every
    producer in turn sends its stream's next `probe_blocks` blocks one
    at a time and reads the counters around each ack, so each probe
    block's connection-anomaly count is known alone. Block by block
    that count against the reference's for the same block, summed
    without sign, over the probe blocks' points. The reference
    carries every connection's state through the whole run, so a
    detector whose state drifted in the window (lower precision, a
    part of a batch left out) answers the probes differently."""
    final = ctx["metrics_final"]
    raised = int(final.get(ALERTS_SERIES, 0))
    counted = int(sum(v for k, v in final.items()
                      if k.startswith("theia_ingest_alerts_total{")))
    acked_alerts = want = points = 0
    probe_diff = probe_points = probes = 0
    for stream, n, recs in streams(ctx):
        ref_counts = _ref.detector_alerts(stream, n)
        want += int(ref_counts.sum())
        points += n * stream.rows
        acked = [r for r in recs if r["status"] == 200]
        acked_alerts += sum(int(r.get("alerts") or 0) for r in acked)
        for r, c in zip(acked, ref_counts):
            if r.get("counters_read"):        # a probe block
                probes += 1
                probe_diff += abs(int(r["conn_alerts"]) - int(c))
                probe_points += stream.rows
    rep.compare("ack_alerts_sum_gap", abs(acked_alerts - counted), 0,
                f"acks {acked_alerts}, counters {counted} of which "
                f"{counted - raised} of other kinds")
    rep.compare("alert_count_gap", abs(raised - want) / max(points, 1),
                limit(ctx["traffic"], "alert_count_gap"),
                f"{raised} raised, reference {want}, {points} points")
    want_probes = sum(int(s.get("probe_blocks", 0)) for s in ctx["specs"]
                      if s["role"] == "producer")
    rep.compare("probe_blocks_missing", want_probes - probes, 0,
                f"{want_probes} probe blocks, counters read around each")
    rep.compare("alert_probe_block_gap", probe_diff / max(probe_points, 1),
                limit(ctx["traffic"], "alert_probe_block_gap"),
                f"sum over probe blocks |counted - reference| "
                f"{probe_diff}, {probe_points} points")


def tad_decisions_from_rows(rows: List[Dict], start: int) -> set:
    """{(producer, connection, flowEndSeconds)} of a job's anomaly
    rows; the connection index is recovered from the generator's own
    key layout (source port and flowStartSeconds, gen.Population)."""
    out = set()
    for r in rows:
        if r.get("anomaly") != "true":
            continue
        producer = int(r["sourceIP"].split(".")[1])
        j = (int(r["sourceTransportPort"]) - 32768
             + PORT_SPAN * (start - 10 - int(r["flowStartSeconds"])))
        out.add((producer, j, int(r["flowEndSeconds"])))
    return out


def check_jobs(ctx: Dict, rep: Report) -> None:
    jobs = [(spec, res) for spec, res in zip(ctx["specs"], ctx["results"])
            if spec["role"] == "jobs"]
    bad = n = 0
    last = None
    for _, res in jobs:
        for r in res["records"]:
            n += 1
            bad += r.get("state") != "COMPLETED"
        last = res.get("last_result") or last
    rep.attempted += n
    rep.failed += bad
    rep.compare("jobs_not_completed", bad, 0, f"{n} jobs")
    if last is None:
        rep.compare("tad_decision_mismatch", 1.0, 0, "no job result")
        return
    rows = json.loads(last).get("stats", [])
    got = tad_decisions_from_rows(
        rows, int(ctx["traffic"]["generator"].get("start_time",
                                                   DEFAULT_START)))
    want = set()
    scored = 0
    for stream, n, _ in streams(ctx):
        vals, times, mask = _ref.series_of(stream, n)
        anom = _ref.tad_ewma(vals, mask)
        scored += int(mask.sum())
        c, t = np.nonzero(anom)
        want.update(zip([stream.producer] * len(c), c.tolist(),
                        times[c, t].tolist()))
    share = len(got ^ want) / max(scored, 1)
    rep.compare("tad_decision_mismatch", share,
                limit(ctx["traffic"], "tad_decision_mismatch"),
                f"{len(got)} decisions, reference {len(want)}, "
                f"{scored} points scored")


def check_panels(ctx: Dict, rep: Report) -> None:
    from . import panels as _panels
    bad = n = unstable = wrong = 0
    notes: List[str] = []
    # the closed ranges lie inside the preloaded seconds: the reference
    # needs the preloaded blocks alone
    pre = [(s, int(spec.get("preload_blocks", 0)))
           for (spec, _), (s, _, _) in zip(producer_records(ctx),
                                           streams(ctx))]
    # what the panels without a closed range must add up to, now that
    # nothing moves
    rows = sum(n_acked * s.rows for s, n_acked, _ in streams(ctx))
    octets = sum(_ref.block_totals(s, n_acked)[1]
                 for s, n_acked, _ in streams(ctx))
    for i, spec in enumerate(ctx["specs"]):
        if spec["role"] != "reader":
            continue
        res = ctx["results"][i]
        digests: Dict[str, set] = {}
        for r in ctx["warm"][i]["records"] + res["records"]:
            n += 1
            if r["status"] != 200:
                bad += 1
            else:
                digests.setdefault(r["panel"], set()).add(r["digest"])
        for p in spec["panels"]:
            if p.get("closed"):
                unstable += len(digests.get(p["name"], ())) != 1
                body = res["bodies"].get(p["name"])
                got = json.loads(body)["data"] if body else None
                if got != _panels.reference_panel(p, pre):
                    wrong += 1
                    notes.append(p["name"])
            else:
                # no closed range: read once more after quiescence
                data = ctx["manager"].json(p["path"])["data"]
                found = _panels.invariants(_panels.dashboard_of(p["path"]),
                                           data, rows, octets)
                wrong += bool(found)
                notes.extend(found)
    rep.attempted += n
    rep.failed += bad
    rep.compare("panel_requests_failed", bad, 0, f"{n} panel requests")
    rep.compare("panels_changed_over_closed_range", unstable, 0)
    rep.compare("panels_differ_from_reference", wrong, 0,
                "; ".join(notes)[:300])


CHECKS: Dict[str, Callable[[Dict, Report], None]] = {
    "acks": check_acks,
    "store_totals": check_store_totals,
    "detector_series": check_detector_series,
    "detector_alerts": check_detector_alerts,
    "jobs": check_jobs,
    "panels": check_panels,
}


#: the tolerances a built-in check reads from the traffic file
LIMITS: Dict[str, Tuple[str, ...]] = {
    "detector_alerts": ("alert_count_gap", "alert_probe_block_gap"),
    "jobs": ("tad_decision_mismatch",),
}


def resolve_all(traffic: Dict) -> List[Callable[[Dict, Report], None]]:
    """The traffic file's checks, each found by name, and every
    tolerance they declare present in its `limits`: what can be known
    to be missing is known before the run starts."""
    found = []
    for name in traffic["checks"]:
        mod = _extend.module("check", name)
        found.append(CHECKS[name] if mod is None else mod.check)
        wanted = LIMITS.get(name, ()) if mod is None \
            else getattr(mod, "limits", ())
        for tol in wanted:
            limit(traffic, tol)
    return found


def run_checks(ctx: Dict) -> Dict:
    rep = Report()
    for fn in resolve_all(ctx["traffic"]):
        fn(ctx, rep)
    return rep.doc()
