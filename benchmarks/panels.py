"""The plain reference of the dashboards' panels.

Each function answers what `/dashboards/api/<name>` must answer over a
closed time range, computed from the generator's own rows with numpy
group-bys over strings' table indices: sums are integer sums, top-k is
by value (ties, which random 64-bit sums do not produce, would fall to
the name). Semantics after theia_tpu/dashboards/queries.py and the
upstream dashboards' SQL (build/charts/theia/provisioning/dashboards).

`flow_records` and `homepage` cannot be compared row by row (the first
picks 100 of thousands of rows that share the newest second; the second
takes no range, so it sees rows that arrive during the window): they
are timed, and `invariants` checks what does hold for them.
"""

from __future__ import annotations

import urllib.parse
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .gen import Population

RULE_ACTION_LABELS = {0: "none", 1: "allow", 2: "drop", 3: "reject"}


class Rows:
    """The retained rows inside [start, end) as flat arrays, with each
    string column as (names, index per row) across all producers."""

    def __init__(self, streams: Sequence[Tuple[object, int]],
                 start: int, end: int) -> None:
        prod, conn, t, thr = [], [], [], []
        self.pops: Dict[int, Population] = {}
        for stream, n_blocks in streams:
            self.pops[stream.producer] = Population(
                stream.producer, stream.n_conn, stream.start)
            for b in range(n_blocks):
                v = stream.values(b)
                keep = (v["flow_end"] >= start) & (v["flow_end"] < end)
                if not keep.any():
                    continue
                sub = v["thr"][:, keep]
                conn.append(np.repeat(v["conn"], sub.shape[1]))
                t.append(np.tile(v["flow_end"][keep], len(v["conn"])))
                thr.append(sub.ravel())
                prod.append(np.full(sub.size, stream.producer))
        cat = (lambda xs: np.concatenate(xs) if xs
               else np.zeros(0, np.int64))
        self.prod, self.conn = cat(prod), cat(conn)
        self.t, self.thr = cat(t), cat(thr)
        self.octets = self.thr * next(
            (s.interval for s, _ in streams), 1)

    def strings(self, column: str) -> Tuple[np.ndarray, np.ndarray]:
        """(names [g] as objects, index [rows]) with one index space
        over all producers (equal strings share an index)."""
        names: Dict[str, int] = {}
        idx = np.zeros(len(self.prod), np.int64)
        for p, pop in self.pops.items():
            table, per_conn = pop.strings[column]
            remap = np.array([names.setdefault(s, len(names))
                              for s in table], np.int64)
            sel = self.prod == p
            idx[sel] = remap[per_conn[self.conn[sel]]]
        out = np.empty(len(names), object)
        for s, i in names.items():
            out[i] = s
        return out, idx

    def static(self, column: str) -> np.ndarray:
        out = np.zeros(len(self.prod), np.int64)
        for p, pop in self.pops.items():
            sel = self.prod == p
            out[sel] = pop.static[column][self.conn[sel]]
        return out


def _group_sum(keys: np.ndarray, values: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    if len(keys) == 0:
        return keys.reshape(0, keys.shape[1] if keys.ndim > 1 else 1), values
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv.ravel(), values)
    return uniq, sums


def _top(order_values: np.ndarray, k: int) -> np.ndarray:
    return np.argsort(-order_values, kind="stable")[:k]


def _links(a: np.ndarray, b: np.ndarray, octets: np.ndarray,
           names_a, names_b, k: int) -> List[Dict]:
    uniq, sums = _group_sum(np.stack([a, b], axis=1), octets)
    return [{"source": str(names_a[uniq[i, 0]]),
             "target": str(names_b[uniq[i, 1]]), "value": int(sums[i])}
            for i in _top(sums, k)]


def _series(t: np.ndarray, g: np.ndarray, values: np.ndarray, names,
            k: int) -> Dict:
    if len(t) == 0:
        return {"times": [], "series": {}}
    uniq_g, g_inv = np.unique(g, return_inverse=True)
    totals = np.zeros(len(uniq_g), np.int64)
    np.add.at(totals, g_inv, values)
    t_axis, t_inv = np.unique(t, return_inverse=True)
    series = {}
    for gi in _top(totals, k):
        sel = g_inv == gi
        ys = np.zeros(len(t_axis), np.int64)
        np.add.at(ys, t_inv[sel], values[sel])
        series[str(names[uniq_g[gi]])] = ys.tolist()
    return {"times": t_axis.tolist(), "series": series}


def _pair(rows: Rows, a_col: str, b_col: str, mask: np.ndarray, k: int
          ) -> Dict:
    names_a, a = rows.strings(a_col)
    names_b, b = rows.strings(b_col)
    a, b = a[mask], b[mask]
    octets, thr, t = rows.octets[mask], rows.thr[mask], rows.t[mask]
    uniq_a, sums_a = _group_sum(a[:, None], octets)
    pie = [{"name": str(names_a[uniq_a[i, 0]]), "value": int(sums_a[i])}
           for i in _top(sums_a, k)]
    return {"links": _links(a, b, octets, names_a, names_b, k),
            "throughput": _series(t, a, thr, names_a, k),
            "topSources": pie}


def _nonblank(rows: Rows, column: str) -> np.ndarray:
    names, idx = rows.strings(column)
    return (names != "")[idx]


def pod_to_pod(rows: Rows, k: int) -> Dict:
    return _pair(rows, "sourcePodName", "destinationPodName",
                 _nonblank(rows, "sourcePodName")
                 & _nonblank(rows, "destinationPodName"), k)


def pod_to_service(rows: Rows, k: int) -> Dict:
    return _pair(rows, "sourcePodName", "destinationServicePortName",
                 _nonblank(rows, "destinationServicePortName"), k)


def pod_to_external(rows: Rows, k: int) -> Dict:
    return _pair(rows, "sourcePodName", "destinationIP",
                 rows.static("flowType") == 3, k)


def node_to_node(rows: Rows, k: int) -> Dict:
    mask = (_nonblank(rows, "sourceNodeName")
            & _nonblank(rows, "destinationNodeName"))
    names_a, a = rows.strings("sourceNodeName")
    names_b, b = rows.strings("destinationNodeName")
    a, b = a[mask], b[mask]
    return {"links": _links(a, b, rows.octets[mask], names_a, names_b, k),
            "throughput": _series(rows.t[mask], a, rows.thr[mask],
                                  names_a, k)}


def networkpolicy(rows: Rows, k: int) -> Dict:
    names_e, eg = rows.strings("egressNetworkPolicyName")
    names_i, ing = rows.strings("ingressNetworkPolicyName")
    has = (names_e != "")[eg] | (names_i != "")[ing]
    by_action: Dict[str, int] = {}
    act = rows.static("egressNetworkPolicyRuleAction")
    for code in np.unique(act):
        label = RULE_ACTION_LABELS.get(int(code), str(int(code)))
        by_action[label] = int(rows.octets[act == code].sum())
    return {"chord": _links(eg[has], ing[has], rows.octets[has],
                            names_e, names_i, k),
            "byAction": [{"name": n, "value": v}
                         for n, v in sorted(by_action.items())]}


def network_topology(rows: Rows, k: int = 0) -> Dict:
    names_s, src = rows.strings("sourcePodNamespace")
    names_d, dst = rows.strings("destinationPodNamespace")
    external = rows.static("flowType") == 3
    edges: Dict[Tuple[str, str], int] = {}
    uniq, sums = _group_sum(
        np.stack([src, dst, external.astype(np.int64)], axis=1),
        rows.octets)
    for (s, d, ext), v in zip(uniq.tolist(), sums.tolist()):
        a = str(names_s[s]) or "(unknown)"
        b = "external" if ext else (str(names_d[d]) or "(unknown)")
        edges[(a, b)] = edges.get((a, b), 0) + int(v)
    return {"edges": [{"source": a, "target": b, "value": v}
                      for (a, b), v in sorted(edges.items())]}


PANELS = {"pod_to_pod": pod_to_pod, "pod_to_service": pod_to_service,
          "pod_to_external": pod_to_external, "node_to_node": node_to_node,
          "networkpolicy": networkpolicy,
          "network_topology": network_topology}


def dashboard_of(path: str) -> str:
    """The dashboard a panel's request path names."""
    return urllib.parse.urlsplit(path).path.rsplit("/", 1)[1]


def reference_panel(panel: Dict, streams: Sequence[Tuple[object, int]]):
    """The reference's answer for one panel of a traffic file
    ({"name", "path", "closed"}); the dashboard's name and its
    start/end/k come from the path the reader asks."""
    query = urllib.parse.urlsplit(panel["path"]).query
    q = {k: int(v[0]) for k, v in urllib.parse.parse_qs(query).items()}
    rows = Rows(streams, q["start"], q["end"])
    return PANELS[dashboard_of(panel["path"])](rows, q.get("k", 10))


def invariants(dashboard: str, data, rows_expected: int,
               octets_expected: int) -> List[str]:
    """What holds for the panels that cannot be compared row by row,
    read after quiescence; returns the violations."""
    bad = []
    if dashboard == "homepage":
        if data.get("flowCount") != rows_expected:
            bad.append(f"homepage flowCount {data.get('flowCount')} != "
                       f"{rows_expected}")
        if data.get("totalBytes") != octets_expected:
            bad.append(f"homepage totalBytes {data.get('totalBytes')} != "
                       f"{octets_expected}")
    if dashboard == "flow_records":
        ends = [r.get("flowEndSeconds") for r in data]
        if len(data) != 100 or ends != sorted(ends, reverse=True):
            bad.append(f"flow_records: {len(data)} rows, newest first "
                       f"{ends == sorted(ends, reverse=True)}; expected "
                       f"the 100 newest")
    return bad
