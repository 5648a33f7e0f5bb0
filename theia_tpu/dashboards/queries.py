"""Dashboard read-path queries over the flow store.

Re-provides the data behind the reference's eight Grafana dashboards
(build/charts/theia/provisioning/dashboards/*.json, inventory at SURVEY
§2.5): homepage summary stats, raw flow records, pod-to-pod /
pod-to-service / pod-to-external / node-to-node sankey+timeseries,
networkpolicy chord, and the network-topology dependency graph. The
reference's panels run rawSql against the flows*_view ClickHouse tables
with $__timeFilter macros; here each function reads the equivalent
materialized view (store/views.py) and reduces over dictionary codes —
same data contract, no SQL engine in the path.

Every function returns plain-JSON data (lists/dicts), consumed by both
the HTML renderer (web.py) and the /dashboards/api endpoints
(`panel_json`, which also times a request: span `dashboard.panel`,
stages dash.scan / dash.aggregate / dash.encode).
"""

from __future__ import annotations

import inspect
import json
import os
import time
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..store import FlowDatabase
from ..store.views import read_tally

FLOW_TYPE_TO_EXTERNAL = 3

_M_PANEL = _metrics.histogram(
    "theia_dashboard_panel_seconds",
    "One /dashboards/api/<panel> request, server side: scan, "
    "aggregation and JSON encode", labelnames=("panel",))
_M_STAGE = _trace.StageSeries(
    "theia_dashboard_stage_seconds",
    "A dashboard request by stage (self time): scan (table or view "
    "to a column batch), aggregate (the panel's body), encode "
    "(json.dumps)", labelnames=("stage",))
_M_SCAN = _M_STAGE.labels(stage="scan")
_M_AGGREGATE = _M_STAGE.labels(stage="aggregate")
_M_ENCODE = _M_STAGE.labels(stage="encode")
_M_ROWS = _metrics.counter(
    "theia_dashboard_rows_scanned_total",
    "Rows of the reads behind dashboard panels: the rows of the "
    "batches and view parts a read opened, before its mask (per "
    "panel: the `rows` attribute of its dashboard.panel span)")
_M_PARTS = _metrics.counter(
    "theia_dashboard_parts_total",
    "Batches of `flows` and parts of a materialized view that the "
    "reads behind dashboard panels met, by what the read did with "
    "them: `read` (opened; its rows are in "
    "theia_dashboard_rows_scanned_total) or `pruned` (skipped unread "
    "by its cached bounds of flowEndSeconds)",
    labelnames=("table", "how"))
_M_REGROUP = _metrics.counter(
    "theia_dashboard_regroup_rows_total",
    "Rows of a materialized view that the reads behind dashboard "
    "panels re-grouped exactly (ViewTable.select: the rows a range "
    "took of several parts, or the whole view where it compacted it; "
    "none where one exact part answered), by how: `hash` (the native "
    "pass that compares full keys) or `sort` (numpy's lexsort, "
    "without the native library)",
    labelnames=("table", "how"))


def _scanned(table: str, seen: Mapping[str, object]) -> None:
    """Count one read (a table's or a view's `last_read()`): its rows
    in total and on the enclosing span, its parts by what became of
    them, a view's re-grouped rows by how."""
    _M_ROWS.inc(seen["rows"])
    _M_PARTS.labels(table=table, how="read").inc(seen["read"])
    _M_PARTS.labels(table=table, how="pruned").inc(seen["pruned"])
    if seen.get("regrouped"):
        _M_REGROUP.labels(table=table, how=seen["how"]).inc(
            seen["regrouped"])
    sp = _trace.current_span()
    if sp is not None:
        sp.attrs["rows"] = sp.attrs.get("rows", 0) + seen["rows"]


def _flows_pieces(db, columns, start=None, end=None):
    """`columns` of the flows rows with `start <= flowEndSeconds < end`
    as the batches they lie in (Table.pieces): for a panel that
    reduces batch by batch and never needs the rows as one."""
    with _trace.stage("dash.scan", _M_SCAN):
        out = db.flows.pieces(start, end, "flowEndSeconds",
                              "flowEndSeconds", columns)
        _scanned("flows", db.flows.last_read())
        return out


def _flows_select(db, columns, start=None, end=None):
    """The same rows as one batch, in append order (Table.select)."""
    with _trace.stage("dash.scan", _M_SCAN):
        batch = db.flows.select(start, end, "flowEndSeconds",
                                "flowEndSeconds", columns)
        _scanned("flows", db.flows.last_read())
        return batch


def _view_scan(db, name: str, columns, start=None, end=None):
    with _trace.stage("dash.scan", _M_SCAN):
        batch, seen = _view_batch(db, name, columns, start, end)
        _scanned(name, seen)
        return batch


def _view_batch(db, name: str, columns, start, end):
    """One materialized view in the ViewTable.scan() shape and what
    the read opened, routed by THEIA_DASHBOARD_ROLLUP: unset/0 reads
    the legacy in-memory view table, the panel's range and columns
    pushed down (ViewTable.select: only the parts the range touches,
    only the asked sums summed); `1` reads
    the rollup-backed `__rollup__:<view>` aggregate parts whole
    (query/rollup.py — the view must be declared, e.g. via
    THEIA_ROLLUP_DEFAULTS=1, else legacy serves); `assert` reads the
    rollup path AND verifies it group-for-group against the legacy
    table (the migration parity gate — raises on divergence). The
    panel masks on its range either way."""
    def legacy():
        view = db.views[name]
        return view.select(start, end, columns), view.last_read()

    mode = os.environ.get("THEIA_DASHBOARD_ROLLUP",
                          "").strip().lower()
    if mode in ("", "0", "off", "false", "no"):
        return legacy()
    from ..query import rollup as _rollup
    batch = _rollup.view_scan_batch(db, name)
    if batch is None:
        return legacy()
    if mode == "assert":
        _rollup.assert_view_parity(batch, db.views[name].scan(), name)
    return batch, dict(read_tally(), rows=len(batch))

# NetworkPolicy rule-action codes (reference schema: 0 none, 1 allow,
# 2 drop, 3 reject) — single source for every dashboard consumer.
RULE_ACTION_LABELS = {0: "none", 1: "allow", 2: "drop", 3: "reject"}
DENY_RULE_ACTIONS = (2, 3)


def _distinct(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """`np.unique(x, return_inverse=True)` of non-empty integers. Where
    they lie close together (dictionary codes, the seconds of a range,
    a pair of codes packed into a word) they are counted in place, one
    pass and no sort; a span far wider than its rows is sorted."""
    lo, hi = int(x.min()), int(x.max())
    if hi - lo > 4 * len(x) + 1024:
        return np.unique(x, return_inverse=True)
    x = x - lo
    met = np.bincount(x, minlength=hi - lo + 1) > 0
    return np.flatnonzero(met) + lo, (np.cumsum(met) - 1)[x]


def _top_links(a: np.ndarray, b: np.ndarray, values: np.ndarray,
               names_a, names_b, k: int) -> List[Dict[str, object]]:
    """Aggregate (a, b) → sum(value), return the top-k as sankey links.
    The two codes (below 2**31) are packed into one word, so the groups
    come in (a, b) order from one pass over it."""
    if len(a) == 0:
        return []
    span = int(b.max()) + 1
    pairs, inv = _distinct(a * span + b)
    sums = np.zeros(len(pairs), np.int64)
    np.add.at(sums, inv, values)
    return [{"source": str(names_a[pairs[i] // span]),
             "target": str(names_b[pairs[i] % span]),
             "value": int(sums[i])} for i in np.argsort(-sums)[:k]]


def _decode_table(dicts, name):
    return np.asarray(dicts[name]._strings, dtype=object)


def _time_window(col: np.ndarray, start: Optional[int],
                 end: Optional[int]) -> np.ndarray:
    mask = np.ones(len(col), bool)
    if start is not None:
        mask &= col >= start
    if end is not None:
        mask &= col < end
    return mask


def _throughput_series(times: np.ndarray, groups: np.ndarray,
                       values: np.ndarray, names, k: int
                       ) -> Dict[str, object]:
    """Per-group throughput over time for the top-k groups by volume.
    Fully vectorized (unique + bincount) — this runs on every dashboard
    render over the whole selected window."""
    if len(times) == 0:
        return {"times": [], "series": {}}
    values = np.asarray(values, np.float64)
    uniq_g, g_inv = _distinct(groups)
    totals = np.bincount(g_inv, weights=values)
    top = np.argsort(-totals)[:k]
    t_axis, t_inv = _distinct(times)
    series = {}
    for gi in top:
        sel = g_inv == gi
        ys = np.bincount(t_inv[sel], weights=values[sel],
                         minlength=len(t_axis))
        series[str(names[uniq_g[gi]])] = ys.astype(np.int64).tolist()
    return {"times": t_axis.tolist(), "series": series}


#: homepage's stats of distinct values: (stat, the column counted)
_HOME_DISTINCT = (("podCount", "sourcePodName"),
                  ("namespaceCount", "sourcePodNamespace"),
                  ("nodeCount", "sourceNodeName"),
                  ("serviceCount", "destinationServicePortName"),
                  ("clusterCount", "clusterUUID"))
#: all that homepage reads of a flows row
_HOME_COLUMNS = tuple(col for _, col in _HOME_DISTINCT) + (
    "octetDeltaCount", "throughput", "timeInserted", "flowEndSeconds",
    "ingressNetworkPolicyRuleAction", "egressNetworkPolicyRuleAction")


def _grown(acc: np.ndarray, n: int) -> np.ndarray:
    """`acc` with at least `n` cells, the new ones zero."""
    if len(acc) >= n:
        return acc
    out = np.zeros(max(n, 2 * len(acc)), acc.dtype)
    out[:len(acc)] = acc
    return out


def homepage(db: FlowDatabase) -> Dict[str, object]:
    """Cluster summary (reference homepage.json: 12 stat panels +
    bargauge of top namespaces + cluster-throughput timeseries +
    dashlist — the dashlist is the nav bar on every page). It takes no
    range, so it reads every batch of `flows`; each is reduced where
    it lies (counts, integer sums, which codes were met) and only the
    reductions are gathered: the table is never copied."""
    out: Dict[str, object] = {
        "flowCount": 0,
        "tadAnomalies": 0,
        "recommendations": 0,
        "droppedFlowCount": 0,
        "topNamespaces": [],
        "throughput": {"times": [], "series": {}},
    }
    rows = total_bytes = dropped = newest_throughput = ns_codes = 0
    newest = names = None
    met = {col: np.zeros(0, bool) for _, col in _HOME_DISTINCT}
    ns_octets = np.zeros(0, np.float64)
    by_second: Dict[int, float] = {}
    for flows in _flows_pieces(db, _HOME_COLUMNS):
        rows += len(flows)
        names = flows.dicts["sourcePodNamespace"]    # the table's own
        for col, seen in met.items():
            codes = np.asarray(flows[col])
            met[col] = seen = _grown(seen, int(codes.max()) + 1)
            seen[codes] = True
        total_bytes += int(flows["octetDeltaCount"].sum())
        inserted = np.asarray(flows["timeInserted"])
        last = int(inserted.max())
        if newest is None or last > newest:
            newest, newest_throughput = last, 0
        if last == newest:
            newest_throughput += int(
                flows["throughput"][inserted == last].sum())
        dropped += int(
            (np.isin(flows["ingressNetworkPolicyRuleAction"],
                     DENY_RULE_ACTIONS)
             | np.isin(flows["egressNetworkPolicyRuleAction"],
                       DENY_RULE_ACTIONS)).sum())
        ns = np.asarray(flows["sourcePodNamespace"], np.int64)
        part = np.bincount(ns, weights=np.asarray(
            flows["octetDeltaCount"], np.float64))
        ns_codes = max(ns_codes, len(part))
        ns_octets = _grown(ns_octets, len(part))
        ns_octets[:len(part)] += part
        seconds, inv = _distinct(np.asarray(flows["flowEndSeconds"]))
        sums = np.bincount(inv, weights=np.asarray(
            flows["throughput"], np.float64), minlength=len(seconds))
        for sec, v in zip(seconds.tolist(), sums.tolist()):
            by_second[sec] = by_second.get(sec, 0.0) + v
    out["flowCount"] = rows
    if rows:
        for stat, col in _HOME_DISTINCT:
            out[stat] = int(met[col][1:].sum())    # code 0 == ''
        out["totalBytes"] = total_bytes
        out["currentThroughput"] = newest_throughput
        out["droppedFlowCount"] = dropped
        # bargauge: top namespaces by traffic volume
        totals = ns_octets[:ns_codes]
        totals[0] = 0                  # code 0 == '' (no namespace)
        top = np.argsort(-totals)[:8]
        out["topNamespaces"] = [
            {"name": names.decode_one(int(g)), "value": int(totals[g])}
            for g in top if totals[g] > 0]
        # timeseries: cluster-wide throughput (one constant group)
        times = sorted(by_second)
        out["throughput"] = {
            "times": times,
            "series": {"cluster": np.asarray(
                [by_second[t] for t in times],
                np.float64).astype(np.int64).tolist()}}
    tad = db.tadetector.scan()
    if len(tad):
        out["tadAnomalies"] = int(
            (tad.strings("anomaly") == "true").sum())
    out["dropAnomalies"] = len(db.dropdetection)
    out["recommendations"] = len(db.recommendations)
    return out


def flow_records(db: FlowDatabase, limit: int = 100,
                 start: Optional[int] = None,
                 end: Optional[int] = None) -> List[Dict[str, object]]:
    """Raw recent records (reference flow_records_dashboard.json:90)."""
    cols = ("flowEndSeconds", "sourcePodNamespace", "sourcePodName",
            "destinationPodNamespace", "destinationPodName",
            "destinationIP", "destinationTransportPort",
            "destinationServicePortName", "protocolIdentifier",
            "throughput", "octetDeltaCount",
            "ingressNetworkPolicyName", "egressNetworkPolicyName")
    sub = _flows_select(db, cols, start, end)
    order = np.argsort(-np.asarray(sub["flowEndSeconds"]))[:limit]
    return sub.take(order).to_rows()


def _pair_view(db: FlowDatabase, a_col: str, b_col: str,
               row_filter, k: int, start, end) -> Dict[str, object]:
    view = _view_scan(
        db, "flows_pod_view",
        (a_col, b_col, "flowType", "flowEndSeconds", "throughput",
         "octetDeltaCount"), start, end)
    mask = _time_window(np.asarray(view["flowEndSeconds"]), start, end)
    mask &= row_filter(view)
    a = np.asarray(view[a_col], np.int64)[mask]
    b = np.asarray(view[b_col], np.int64)[mask]
    thr = np.asarray(view["throughput"], np.int64)[mask]
    octets = np.asarray(view["octetDeltaCount"], np.int64)[mask]
    t = np.asarray(view["flowEndSeconds"], np.int64)[mask]
    names_a = _decode_table(view.dicts, a_col)
    names_b = _decode_table(view.dicts, b_col)

    links = _top_links(a, b, octets, names_a, names_b, k)
    ts = _throughput_series(t, a, thr, names_a, k)
    totals_a: Dict[str, int] = {}
    for code, v in zip(a.tolist(), octets.tolist()):
        key = str(names_a[code])
        totals_a[key] = totals_a.get(key, 0) + v
    # ties by name: a view's rows come in no stated order
    pie = sorted(totals_a.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return {"links": links, "throughput": ts,
            "topSources": [{"name": n, "value": v} for n, v in pie]}


def pod_to_pod(db: FlowDatabase, k: int = 10, start=None, end=None):
    return _pair_view(
        db, "sourcePodName", "destinationPodName",
        lambda v: (np.asarray(v["sourcePodName"]) != 0)
        & (np.asarray(v["destinationPodName"]) != 0), k, start, end)


def pod_to_service(db: FlowDatabase, k: int = 10, start=None, end=None):
    return _pair_view(
        db, "sourcePodName", "destinationServicePortName",
        lambda v: np.asarray(v["destinationServicePortName"]) != 0,
        k, start, end)


def pod_to_external(db: FlowDatabase, k: int = 10, start=None,
                    end=None):
    return _pair_view(
        db, "sourcePodName", "destinationIP",
        lambda v: np.asarray(v["flowType"]) == FLOW_TYPE_TO_EXTERNAL,
        k, start, end)


def node_to_node(db: FlowDatabase, k: int = 10, start=None, end=None):
    view = _view_scan(
        db, "flows_node_view",
        ("sourceNodeName", "destinationNodeName", "flowEndSeconds",
         "throughput", "octetDeltaCount"), start, end)
    mask = _time_window(np.asarray(view["flowEndSeconds"]), start, end)
    mask &= (np.asarray(view["sourceNodeName"]) != 0) \
        & (np.asarray(view["destinationNodeName"]) != 0)
    a = np.asarray(view["sourceNodeName"], np.int64)[mask]
    b = np.asarray(view["destinationNodeName"], np.int64)[mask]
    octets = np.asarray(view["octetDeltaCount"], np.int64)[mask]
    thr = np.asarray(view["throughput"], np.int64)[mask]
    t = np.asarray(view["flowEndSeconds"], np.int64)[mask]
    names_a = _decode_table(view.dicts, "sourceNodeName")
    names_b = _decode_table(view.dicts, "destinationNodeName")
    return {"links": _top_links(a, b, octets, names_a, names_b, k),
            "throughput": _throughput_series(t, a, thr, names_a, k)}


def networkpolicy(db: FlowDatabase, k: int = 10, start=None, end=None):
    """Policy traffic chord (reference networkpolicy_dashboard.json):
    bytes per (egress policy, ingress policy) pair + allow/deny split."""
    view = _view_scan(
        db, "flows_policy_view",
        ("egressNetworkPolicyName", "ingressNetworkPolicyName",
         "egressNetworkPolicyRuleAction", "flowEndSeconds",
         "octetDeltaCount"), start, end)
    mask = _time_window(np.asarray(view["flowEndSeconds"]), start, end)
    eg = np.asarray(view["egressNetworkPolicyName"], np.int64)[mask]
    ing = np.asarray(view["ingressNetworkPolicyName"], np.int64)[mask]
    octets = np.asarray(view["octetDeltaCount"], np.int64)[mask]
    eg_act = np.asarray(view["egressNetworkPolicyRuleAction"],
                        np.int64)[mask]
    names_e = _decode_table(view.dicts, "egressNetworkPolicyName")
    names_i = _decode_table(view.dicts, "ingressNetworkPolicyName")
    has_policy = (eg != 0) | (ing != 0)
    links = _top_links(eg[has_policy], ing[has_policy],
                       octets[has_policy], names_e, names_i, k)
    by_action: Dict[str, int] = {}
    for act, v in zip(eg_act.tolist(), octets.tolist()):
        label = RULE_ACTION_LABELS.get(act, str(act))
        by_action[label] = by_action.get(label, 0) + v
    return {"chord": links,
            "byAction": [{"name": n, "value": v}
                         for n, v in sorted(by_action.items())]}


def network_topology(db: FlowDatabase, start=None, end=None):
    """Namespace-level dependency edges (reference
    network_topology_dashboard's mermaid graph, DependencyPanel.tsx)."""
    flows = _flows_select(
        db, ("sourcePodNamespace", "destinationPodNamespace",
             "flowType", "octetDeltaCount"), start, end)
    src = np.asarray(flows["sourcePodNamespace"], np.int64)
    dst_ns = np.asarray(flows["destinationPodNamespace"], np.int64)
    ftype = np.asarray(flows["flowType"])
    octets = np.asarray(flows["octetDeltaCount"], np.int64)
    names = _decode_table(flows.dicts, "sourcePodNamespace")
    dst_names = _decode_table(flows.dicts, "destinationPodNamespace")

    edges: Dict[Tuple[str, str], int] = {}
    for s, d, ft, v in zip(src.tolist(), dst_ns.tolist(),
                           ftype.tolist(), octets.tolist()):
        a = str(names[s]) or "(unknown)"
        b = ("external" if ft == FLOW_TYPE_TO_EXTERNAL
             else str(dst_names[d]) or "(unknown)")
        edges[(a, b)] = edges.get((a, b), 0) + v
    return {"edges": [{"source": a, "target": b, "value": v}
                      for (a, b), v in sorted(edges.items())]}


DASHBOARDS = {
    "homepage": homepage,
    "flow_records": flow_records,
    "pod_to_pod": pod_to_pod,
    "pod_to_service": pod_to_service,
    "pod_to_external": pod_to_external,
    "node_to_node": node_to_node,
    "networkpolicy": networkpolicy,
    "network_topology": network_topology,
}


def panel_json(db: FlowDatabase, name: str, query: Mapping[str, str],
               traceparent: Optional[str] = None) -> bytes:
    """The encoded answer of GET /dashboards/api/<name>: the panel's
    data for the integer parameters of `query` it accepts (start, end,
    limit, k). An unknown panel raises KeyError before anything is
    timed."""
    fn = DASHBOARDS[name]
    accepted = inspect.signature(fn).parameters
    kwargs = {k: int(query[k]) for k in ("start", "end", "limit", "k")
              if k in query and k in accepted}
    t0 = time.perf_counter()
    with _trace.ingress_span("dashboard.panel", traceparent=traceparent,
                             panel=name):
        with _trace.stage("dash.aggregate", _M_AGGREGATE):
            data = fn(db, **kwargs)
        with _trace.stage("dash.encode", _M_ENCODE):
            raw = json.dumps({"dashboard": name, "data": data},
                             default=str).encode()
    _M_PANEL.labels(panel=name).observe(time.perf_counter() - t0)
    return raw
