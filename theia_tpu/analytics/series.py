"""Series construction: flow table → padded per-connection tensors.

Re-provides the reference TAD job's SQL + groupby pipeline
(plugins/anomaly-detection/anomaly_detection.py:507-710): filter flows,
aggregate throughput per (group key, flowEndSeconds) — max() for raw
connections, sum() for pod/external/svc aggregations — then collect each
key's time series. The reference materializes ragged `collect_list` rows
and maps Python UDFs over them; here every series lands in one padded
[S, T] tensor + mask, time-sorted, ready for the batched kernels.

Group-key modes (generate_tad_sql_query, :507-614):
  * None       — 6-tuple connection key, max(throughput)
  * "pod"      — (podNamespace, podLabels|podName, direction), inbound ∪
                 outbound, sum(throughput); start/end time filters do NOT
                 apply in this mode (reference behavior)
  * "external" — destinationIP with flowType == 3, sum(throughput)
  * "svc"      — destinationServicePortName, sum(throughput)

Ordering note: the reference's collect_list order is whatever the shuffle
produced (nondeterministic); we sort by flowEndSeconds, which is the only
semantically meaningful order for the time-series detectors.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..schema import ColumnarBatch
from ..utils.native import build_padded_series, group_reduce

MEANINGLESS_LABELS = (
    "pod-template-hash",
    "controller-revision-hash",
    "pod-template-generation",
)


@dataclasses.dataclass
class TadQuerySpec:
    """Mirror of the reference job's query arguments
    (anomaly_detection.py main:744-778)."""
    start_time: Optional[int] = None
    end_time: Optional[int] = None
    ns_ignore_list: Sequence[str] = ()
    agg_flow: str = ""          # "", "pod", "external", "svc"
    pod_label: str = ""
    pod_name: str = ""
    pod_namespace: str = ""
    external_ip: str = ""
    svc_port_name: str = ""
    # Scope the query to one cluster's rows in a multicluster store
    # (rows carry the emitting cluster's UUID, test/e2e_mc). Empty =
    # all clusters, like the reference job's unfiltered SQL.
    cluster_uuid: str = ""
    # ARIMA refit cadence: 1 = the reference's exact refit-per-step
    # (anomaly_detection.py:246-253), k>1 = grouped refits (fit reused
    # for k consecutive steps, a k× compute cut on long series), 0 =
    # auto (max(1, T // 2048), sized so 24h@1s series stay feasible).
    # Ignored by EWMA/DBSCAN. The effective value is emitted in every
    # ARIMA result row as `refitEvery`.
    refit_every: int = 1

    @property
    def agg_type(self) -> str:
        return self.agg_flow if self.agg_flow else "None"


@dataclasses.dataclass
class SeriesBatch:
    """Padded series: values/times [S, T], mask [S, T]; one key row per
    series in `keys` (decoded strings for string keys)."""
    key_names: Tuple[str, ...]
    keys: Dict[str, np.ndarray]
    values: np.ndarray
    times: np.ndarray
    mask: np.ndarray
    agg_type: str

    @property
    def n_series(self) -> int:
        return self.values.shape[0]


CONNECTION_KEY = ("sourceIP", "sourceTransportPort", "destinationIP",
                  "destinationTransportPort", "protocolIdentifier",
                  "flowStartSeconds")
NS_COLUMNS = ("sourcePodNamespace", "destinationPodNamespace")


def _pod_sides(by_name: bool):
    """(direction, namespace column, name-or-labels column) of the two
    sides the pod mode unions."""
    return (("inbound", "destinationPodNamespace",
             "destinationPodName" if by_name else "destinationPodLabels"),
            ("outbound", "sourcePodNamespace",
             "sourcePodName" if by_name else "sourcePodLabels"))


def _group_key(spec: TadQuerySpec) -> Tuple[Tuple[str, ...], str]:
    """Key columns and throughput reduction of the non-pod modes."""
    if spec.agg_flow == "external":
        return ("destinationIP",), "sum"
    if spec.agg_flow == "svc":
        return ("destinationServicePortName",), "sum"
    return CONNECTION_KEY, "max"


def read_columns(spec: TadQuerySpec) -> Tuple[str, ...]:
    """The flow columns `build_series` reads for `spec`, a function of
    the spec alone: the mode's group key, the time axis, the value,
    and what the filters that are set touch. A job hands this to the
    store's `select(columns=...)`; `build_series` takes its names
    from the same `_group_key` / `_pod_sides` / `NS_COLUMNS`, and a
    batch that lacks one raises KeyError."""
    if spec.agg_flow == "pod":
        names = [c for _, ns_col, id_col in _pod_sides(
            bool(spec.pod_name)) for c in (ns_col, id_col)]
    else:
        names = list(_group_key(spec)[0])
        if spec.agg_flow == "external":
            names.append("flowType")
        if spec.start_time is not None:
            names.append("flowStartSeconds")
    names += ["flowEndSeconds", "throughput"]
    if spec.ns_ignore_list:
        names += NS_COLUMNS
    if spec.cluster_uuid:
        names.append("clusterUUID")
    return tuple(dict.fromkeys(names))


def _codes_for_strings(batch: ColumnarBatch, name: str,
                       values: Sequence[str]) -> List[int]:
    """Codes of `values` in the batch's dictionary (missing → -1 which
    matches nothing)."""
    d = batch.dicts[name]
    out = []
    for v in values:
        code = d.lookup(v)
        out.append(-1 if code is None else code)
    return out


def _ns_ignore_mask(batch: ColumnarBatch,
                    ns_ignore_list: Sequence[str]) -> np.ndarray:
    """sourcePodNamespace NOT IN (...) AND destinationPodNamespace NOT IN
    (...) (reference :549-553, :576-580)."""
    mask = np.ones(len(batch), dtype=bool)
    if not ns_ignore_list:
        return mask
    for col in NS_COLUMNS:
        codes = np.asarray(
            _codes_for_strings(batch, col, ns_ignore_list), np.int64)
        mask &= ~np.isin(np.asarray(batch[col], np.int64), codes)
    return mask


def _label_substring_codes(batch: ColumnarBatch, col: str,
                           needle: str) -> np.ndarray:
    """Codes whose decoded string contains `needle` case-insensitively
    (the reference's ilike '%needle%')."""
    d = batch.dicts[col]
    low = needle.lower()
    return np.asarray(
        [i for i, s in enumerate(d._strings) if low in s.lower()],
        np.int64)


def _pack_and_pad(key_mat: np.ndarray, t: np.ndarray, v: np.ndarray,
                  dtype=np.float64):
    """Group rows by key, sort each group by time, pad to [S, T_max]."""
    n = key_mat.shape[0]
    if n == 0:
        return (np.zeros((0, key_mat.shape[1]), np.int64),
                np.zeros((0, 0), dtype),
                np.zeros((0, 0), np.int64), np.zeros((0, 0), bool))
    order = np.lexsort((t,) + tuple(key_mat.T[::-1]))
    sk, st, sv = key_mat[order], t[order], v[order]
    boundary = np.empty(n, dtype=bool)
    boundary[0] = True
    boundary[1:] = np.any(sk[1:] != sk[:-1], axis=1)
    starts = np.flatnonzero(boundary)
    group_id = np.cumsum(boundary) - 1
    lengths = np.diff(np.append(starts, n))
    S, T = len(starts), int(lengths.max())
    pos = np.arange(n) - starts[group_id]
    values = np.zeros((S, T), dtype)
    times = np.zeros((S, T), np.int64)
    mask = np.zeros((S, T), bool)
    values[group_id, pos] = sv.astype(dtype)
    times[group_id, pos] = st
    mask[group_id, pos] = True
    return sk[starts], values, times, mask


class SeriesRows(NamedTuple):
    """Rows to group, where they lie: the key columns, the time and
    the value column of one batch, each in its stored dtype, and the
    filters' row mask (None = every row). Nothing is copied to make
    one."""
    key_cols: Sequence[np.ndarray]
    times: np.ndarray
    values: np.ndarray
    mask: Optional[np.ndarray]

    @property
    def kept(self) -> int:
        """Rows the mask keeps."""
        if self.mask is None:
            return len(self.times)
        return int(np.count_nonzero(self.mask))


def _group_and_pad(parts: Sequence[SeriesRows], op: str, dtype):
    """Stage-1 (key,time) reduction + ragged→padded packing of the
    rows of `parts` as one table; returns the four tensors, the path
    that made them and, from the builder, its `SeriesWays` (how it
    wrote the series; None from numpy).

    One seam with two equivalent implementations: `columns`, the native
    C++ builder (native/seriesbuild.cc — one hash-group pass over the
    columns in place; the host tensorize hot path), and `numpy`, the
    lexsort pipeline over an [n, k] matrix it builds itself: the
    builder whenever it answers, numpy when it returns None (no
    library, or a column it does not take)."""
    res = build_padded_series(parts, op, dtype)
    if res is not None:
        return res[:4], "columns", res[4]
    stage1, values = [], []
    for key_cols, t, v, mask in parts:
        rows = slice(None) if mask is None else mask
        stage1.append(np.stack(
            [np.asarray(c, np.int64)[rows] for c in (*key_cols, t)],
            axis=1))
        values.append(np.asarray(v, np.int64)[rows])
    gk, gv = group_reduce(np.concatenate(stage1),
                          np.concatenate(values)[:, None], op)
    return (_pack_and_pad(gk[:, :-1], gk[:, -1], gv[:, 0], dtype),
            "numpy", None)


def remove_meaningless_labels(labels_json: str) -> str:
    """Drop autogenerated label keys (reference :631-644); non-JSON
    input → empty string."""
    try:
        d = json.loads(labels_json)
        if not isinstance(d, dict):
            return ""
    except Exception:
        return ""
    return json.dumps(
        {k: v for k, v in d.items() if k not in MEANINGLESS_LABELS},
        sort_keys=True)


def job_part(progress, name: str):
    """A named part of the stage `progress` (a job's, or None) is in:
    the context manager to do that part's work under."""
    return progress.part(name) if progress else contextlib.nullcontext()


def _base_mask(flows: ColumnarBatch, spec: TadQuerySpec) -> np.ndarray:
    """The filters every mode applies: ignored namespaces, cluster."""
    base = _ns_ignore_mask(flows, spec.ns_ignore_list)
    if spec.cluster_uuid:
        code = flows.dicts["clusterUUID"].lookup(spec.cluster_uuid)
        base &= (np.asarray(flows["clusterUUID"])
                 == (-1 if code is None else code))
    return base


def build_series(flows: ColumnarBatch, spec: TadQuerySpec,
                 dtype=np.float64, progress=None) -> SeriesBatch:
    """Build the padded series batch for one TAD query. `progress`
    (the job's, in its `tensorize` stage) times the stage's three
    parts, named by what each produces: `keys` (the filter masks: the
    key columns are handed on as the batch holds them), `group` (those
    columns, the time and the value column and the mask through
    `_group_and_pad` into the padded tensors) and `decode` (the series'
    keys as the result rows show them), and counts the rows grouped by
    the path that grouped them, the series built (and, from the
    builder, by the way it wrote them) and the rows merged into a
    point another row held (rows grouped less the mask's points: no
    pass over the rows)."""
    pod = spec.agg_flow == "pod"
    with job_part(progress, "keys"):
        if pod:
            parts, op = _pod_rows(flows, spec), "sum"
        else:
            key_names, op = _group_key(spec)
            parts = [_rows(flows, [flows[c] for c in key_names],
                           _filter_mask(flows, spec))]
    with job_part(progress, "group"):
        (key_mat, values, times, mask), path, ways = _group_and_pad(
            parts, op, dtype)
    with job_part(progress, "decode"):
        if pod:
            key_names, keys = _decode_pod_keys(flows, spec, key_mat)
        else:
            keys = _decode_keys(flows, key_names, key_mat)
    if progress:
        progress.tensorized(sum(p.kept for p in parts), path,
                            spec.agg_type, len(key_mat),
                            int(np.count_nonzero(mask)), ways)
    return SeriesBatch(key_names, keys, values, times, mask, spec.agg_type)


def _rows(flows: ColumnarBatch, key_cols: Sequence[np.ndarray],
          mask: np.ndarray) -> SeriesRows:
    """`flows`' rows under `mask`, keyed by `key_cols`, in place."""
    return SeriesRows(key_cols, flows["flowEndSeconds"],
                      flows["throughput"], None if mask.all() else mask)


def _filter_mask(flows: ColumnarBatch, spec: TadQuerySpec) -> np.ndarray:
    """The non-pod modes' WHERE clause as one row mask."""
    base = _base_mask(flows, spec)
    if spec.start_time is not None:
        base &= np.asarray(flows["flowStartSeconds"]) >= spec.start_time
    if spec.end_time is not None:
        base &= np.asarray(flows["flowEndSeconds"]) < spec.end_time

    if spec.agg_flow == "external":
        base &= np.asarray(flows["flowType"]) == 3
        if spec.external_ip:
            code = flows.dicts["destinationIP"].lookup(spec.external_ip)
            base &= (np.asarray(flows["destinationIP"])
                     == (-1 if code is None else code))
    elif spec.agg_flow == "svc":
        if spec.svc_port_name:
            code = flows.dicts["destinationServicePortName"].lookup(
                spec.svc_port_name)
            base &= (np.asarray(flows["destinationServicePortName"])
                     == (-1 if code is None else code))
        else:
            base &= np.asarray(flows["destinationServicePortName"]) != 0
    return base


def _pod_rows(flows: ColumnarBatch, spec: TadQuerySpec
              ) -> List[SeriesRows]:
    """Inbound ∪ outbound pod aggregation (reference :511-565): the
    rows of each side, keyed by the side's namespace and
    name-or-labels codes and its direction (0 inbound, 1 outbound: a
    constant column)."""
    base = _base_mask(flows, spec)
    by_name = bool(spec.pod_name)
    parts = []
    for direction, (_, ns_col, id_col) in enumerate(_pod_sides(by_name)):
        m = base.copy()
        if by_name:
            code = flows.dicts[id_col].lookup(spec.pod_name)
            m &= np.asarray(flows[id_col]) == (
                -1 if code is None else code)
        elif spec.pod_label:
            codes = _label_substring_codes(flows, id_col, spec.pod_label)
            m &= np.isin(np.asarray(flows[id_col], np.int64), codes)
        else:
            m &= np.asarray(flows[id_col]) != 0  # labels <> ''
        if spec.pod_namespace:
            code = flows.dicts[ns_col].lookup(spec.pod_namespace)
            m &= np.asarray(flows[ns_col]) == (
                -1 if code is None else code)
        parts.append(_rows(
            flows,
            [flows[ns_col], flows[id_col],
             np.broadcast_to(np.int64(direction), len(flows))], m))
    return parts


def _decode_pod_keys(flows: ColumnarBatch, spec: TadQuerySpec,
                     key_mat: np.ndarray):
    """(key names, the series' keys as strings) of the pod mode."""
    by_name = bool(spec.pod_name)
    id_name = "podName" if by_name else "podLabels"
    key_names = ("podNamespace", id_name, "direction")
    # Source- and destination-side columns share string values but not
    # dictionaries; decode via the side each row came from is impossible
    # after the union, so decode against a merged lookup.
    (_, ns_in, id_in), (_, ns_out, id_out) = _pod_sides(by_name)
    ns_dict, id_dict = flows.dicts[ns_in], flows.dicts[id_in]
    src_ns, src_id = flows.dicts[ns_out], flows.dicts[id_out]

    def dual_decode(codes, primary, secondary, is_outbound):
        out = np.empty(len(codes), dtype=object)
        for i, (c, ob) in enumerate(zip(codes, is_outbound)):
            d = secondary if ob else primary
            out[i] = d.decode_one(int(c))
        return out

    is_outbound = key_mat[:, 2] == 1
    ns_strings = dual_decode(key_mat[:, 0], ns_dict, src_ns, is_outbound)
    id_strings = dual_decode(key_mat[:, 1], id_dict, src_id, is_outbound)
    if not by_name:
        # remove_meaningless_labels UDF applies in the label mode
        # (reference :687-695)
        id_strings = np.asarray(
            [remove_meaningless_labels(s) for s in id_strings],
            dtype=object)
    keys = {
        "podNamespace": ns_strings,
        id_name: id_strings,
        "direction": np.where(is_outbound, "outbound", "inbound").astype(
            object),
    }
    return key_names, keys


def _decode_keys(flows: ColumnarBatch, key_names, key_mat) -> Dict[
        str, np.ndarray]:
    keys: Dict[str, np.ndarray] = {}
    for i, name in enumerate(key_names):
        col = key_mat[:, i] if key_mat.size else np.zeros(
            key_mat.shape[0], np.int64)
        if name in flows.dicts:
            keys[name] = flows.dicts[name].decode(col)
        else:
            keys[name] = col
    return keys
