"""Plain reference of the throughput-anomaly job's pod query
(`theia throughput-anomaly-detection run --agg-flow pod`): numpy and the
standard library over the generator's own rows, nothing imported from
the program, no jax.

What upstream's job reads (plugins/anomaly-detection/
anomaly_detection.py `generate_tad_sql_query` :511-565), with no
`--pod-label`, `--pod-name` or `--pod-namespace`:

    SELECT podNamespace, podLabels, direction, flowEndSeconds,
           SUM(throughput)
    FROM (SELECT destinationPodNamespace AS podNamespace,
                 destinationPodLabels AS podLabels,
                 'inbound' AS direction, flowEndSeconds, throughput
          FROM flows WHERE destinationPodLabels <> ''
          UNION ALL
          SELECT sourcePodNamespace, sourcePodLabels, 'outbound', ...
          FROM flows WHERE sourcePodLabels <> '')
    GROUP BY podNamespace, podLabels, direction, flowEndSeconds

so every flow row is read twice, once under its destination pod's key
and once under its source pod's, a row whose side has no labels (an
external destination) is left out on that side, and the rows that fall
on one (key, second) are summed. `remove_meaningless_labels` (:631-644)
then rewrites `podLabels` (:687-695) and the rows of a key are
collected into its series; each series goes through sklearn's
`DBSCAN(min_samples=4, eps=250000000)` (:325-349), which is
references/dbscan.py's `dbscan_scores`, and the points it labels noise
are the result rows, each with the summed throughput, the series'
`stddev_samp` and a 0.0 in `algoCalc`.

Written plainly here: the two sides' `(namespace, labels)` strings of
every connection from `Population`, one integer a distinct key, every
block's `values(b)` laid out as contributions (key, second, value), one
sort on (key, second), `np.add.reduceat` in int64, the series in time
order in a padded [S, T] tensor.

Departures from upstream, each noted where it is made:

* the time filters do not apply in this mode upstream either, and the
  cell sends no namespace ignore list: `contributions` takes neither.
  The three pod filters (`--pod-label`, `--pod-name`,
  `--pod-namespace`) it takes, for the repo's tests; the cell sends
  none;
* upstream rewrites the labels after the SQL's GROUP BY, so two label
  strings that rewrite to one would give one key two rows a second
  (`collect_list` of both); the program keeps two series of one key.
  The generator emits no auto-generated label, the rewrite changes
  nothing on this traffic, and `pod_series` raises where it would;
* the order of a series' points upstream is whatever the shuffle left;
  here, as in the program, it is the order of time, the only one the
  detectors mean. DBSCAN's noise does not depend on it.
"""

from __future__ import annotations

import json
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from benchmarks import gen
from benchmarks.references import dbscan

#: anomaly_detection.py:631-644
MEANINGLESS_LABELS = ("pod-template-hash", "controller-revision-hash",
                      "pod-template-generation")
#: (direction, namespace column, labels column) of the query's two arms
SIDES = (("inbound", "destinationPodNamespace", "destinationPodLabels"),
         ("outbound", "sourcePodNamespace", "sourcePodLabels"))

Key = Tuple[str, str, str]    # podNamespace, podLabels (or podName), direction


def remove_meaningless_labels(labels: str) -> str:
    """Upstream's UDF: the label map without the three auto-generated
    keys, as JSON with sorted keys; what is no JSON map reads ''."""
    try:
        d = json.loads(labels)
    except ValueError:
        return ""
    if not isinstance(d, dict):
        return ""
    return json.dumps({k: v for k, v in d.items()
                       if k not in MEANINGLESS_LABELS}, sort_keys=True)


def connection_keys(population, pod_label: str = "", pod_name: str = "",
                    pod_namespace: str = "") -> List[List[Key]]:
    """For each arm of the query, the key each connection's rows fall
    under, or None where the arm's WHERE clause leaves them out: labels
    `<> ''` with no filter, labels `ILIKE '%pod_label%'`, or the pod's
    name `= pod_name` (the key then holds the name in the labels'
    place, `DF_AGG_GRP_COLUMNS_*` :118-137), and the namespace `= pod_namespace` if given."""
    out = []
    low = pod_label.lower()
    for direction, ns_col, labels_col in SIDES:
        id_col = labels_col.replace("Labels", "Name") if pod_name \
            else labels_col
        ns_table, ns_idx = population.strings[ns_col]
        id_table, id_idx = population.strings[id_col]
        keys = []
        for j in range(population.n_conn):
            ns, ident = ns_table[int(ns_idx[j])], id_table[int(id_idx[j])]
            if pod_name:
                taken = ident == pod_name
            elif pod_label:
                taken = low in ident.lower()
            else:
                taken = ident != ""
            if pod_namespace:
                taken = taken and ns == pod_namespace
            keys.append((ns, ident, direction) if taken else None)
        out.append(keys)
    return out


class PodSeries(NamedTuple):
    """The query's answer: a key a series, each series' summed
    throughput in time order."""
    keys: List[Key]             # labels as remove_meaningless_labels left them
    values: np.ndarray          # [S, T] int64, the sums
    times: np.ndarray           # [S, T] int64, flowEndSeconds
    mask: np.ndarray            # [S, T]
    contributions: int          # rows the two arms read
    merged: int                 # of them, those that fell on a cell another held


def contributions(streams: Sequence[Tuple[object, int]],
                  sides: Sequence[int] = (0, 1), **filters: str
                  ) -> Tuple[List[Key], np.ndarray, np.ndarray, np.ndarray]:
    """(distinct keys, key index [n], flowEndSeconds [n],
    throughput [n]) of every row of the streams' first blocks under
    each arm of `sides` that takes it under `filters`
    (`connection_keys`'). A key is its strings: the same pod seen by
    two producers is one key."""
    index: Dict[Key, int] = {}
    per_stream = []
    for stream, _ in streams:
        pop = gen.Population(stream.producer, stream.n_conn, stream.start)
        arms = connection_keys(pop, **filters)
        per_stream.append([
            np.array([-1 if k is None else index.setdefault(k, len(index))
                      for k in arms[side]], np.int64) for side in sides])
    keys = list(index)
    which, when, value = [], [], []
    for (stream, n_blocks), arms in zip(streams, per_stream):
        for b in range(n_blocks):
            v = stream.values(b)
            conn, thr = v["conn"], v["thr"]
            for of_conn in arms:
                k = of_conn[conn]
                taken = k >= 0
                which.append(np.repeat(k[taken], thr.shape[1]))
                when.append(np.tile(v["flow_end"], int(taken.sum())))
                value.append(thr[taken].ravel())
    if not which:
        empty = np.zeros(0, np.int64)
        return keys, empty, empty, empty
    return (keys, np.concatenate(which), np.concatenate(when),
            np.concatenate(value).astype(np.int64))


def pod_series(streams: Sequence[Tuple[object, int]],
               sides: Sequence[int] = (0, 1), op: str = "sum",
               **filters: str) -> PodSeries:
    """The pod query over (stream, blocks) pairs. `sides` and `op`
    are the query's own (both arms, SUM); benchmarks/tests asks for
    other ones to show that the check tells them apart."""
    keys, which, when, value = contributions(streams, sides, **filters)
    # a pod's name is no label map: the rewrite is the labels' (:687-695)
    clean = keys if filters.get("pod_name") else [
        (ns, remove_meaningless_labels(lb), d) for ns, lb, d in keys]
    if len(set(clean)) != len(keys):
        raise ValueError("two label strings rewrite to one: upstream "
                         "collects both into one key's series, which "
                         "this reference does not stand for")
    n = which.size
    if not n:
        none = np.zeros((0, 0), np.int64)
        return PodSeries([], none, none, none.astype(bool), 0, 0)
    order = np.lexsort((when, which))
    which, when, value = which[order], when[order], value[order]
    first = np.ones(n, bool)
    first[1:] = (which[1:] != which[:-1]) | (when[1:] != when[:-1])
    starts = np.flatnonzero(first)
    reduce = np.add if op == "sum" else np.maximum
    cell_value = reduce.reduceat(value, starts)
    # the keys that have a row (a connection no block visited gives its
    # pod none), and a cell's place in its series: cells are sorted by
    # (key, time)
    present, cell_key = np.unique(which[starts], return_inverse=True)
    cell_time = when[starts]
    keys = [clean[i] for i in present]
    series_start = np.searchsorted(cell_key, np.arange(len(keys)))
    place = np.arange(starts.size) - series_start[cell_key]
    length = int(place.max()) + 1
    shape = (len(keys), length)
    values = np.zeros(shape, np.int64)
    times = np.zeros(shape, np.int64)
    mask = np.zeros(shape, bool)
    values[cell_key, place] = cell_value
    times[cell_key, place] = cell_time
    mask[cell_key, place] = True
    return PodSeries(keys, values, times, mask, n, n - starts.size)


def pod_job(streams: Sequence[Tuple[object, int]],
            precision: str = "f64", **query) -> Dict:
    """What the job has to answer and count: `rows`, {(podNamespace,
    podLabels or podName, direction, flowEndSeconds): (summed throughput,
    deviation of the series)} of the points DBSCAN labels noise, the
    points scored, and the series and merged rows the query made."""
    series = pod_series(streams, **query)
    rows = {}
    if series.mask.any():
        _, std, anomaly = dbscan.dbscan_scores(
            series.values, series.mask, precision=precision)
        for s, t in zip(*np.nonzero(anomaly)):
            rows[series.keys[s] + (int(series.times[s, t]),)] = (
                int(series.values[s, t]), float(std[s]))
    return {"rows": rows, "scored": int(series.mask.sum()),
            "series": len(series.keys), "merged": series.merged,
            "contributions": series.contributions}
