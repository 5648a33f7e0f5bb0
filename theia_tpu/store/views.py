"""Streaming materialized views over the flows table.

Re-provides the reference's three SummingMergeTree materialized views
(build/charts/theia/provisioning/datasources/create_table.sh:92-351):

  * flows_pod_view    — per-pod aggregation       (create_table.sh:92-175)
  * flows_node_view   — per-node aggregation      (create_table.sh:178-241)
  * flows_policy_view — per-NetworkPolicy totals  (create_table.sh:244-351)

Semantics match ClickHouse: each *insert block* is grouped by the view's
key columns with the metric columns summed (the MV GROUP BY runs per
block); further collapsing of identical keys across blocks happens at
"merge" time — here at read time: `scan()` compacts the view, a ranged
`select()` re-groups only the rows it takes. All group keys are integers
(dictionary codes for strings), so both group-bys are one native hash
pass over fixed-width arrays that compares the full key on every hash
match (`utils/native.py`: `native_group_sum` an insert block,
`group_sum_exact` a read; numpy's hash-sort and lexsort without the
library) — no Python-object work on either path. A read returns the view
grouped exactly, **in no stated order** (a `SELECT` without `ORDER BY`):
the order is deterministic for given parts in a given order and nothing
more; a consumer that needs one sorts.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..schema import ColumnarBatch, StringDictionary
from ..analysis.lockdep import named_lock
from ..utils.native import (group_sum_exact, group_sum_fast,
                            native_group_sum)


def materialize_view_batch(spec: "ViewSpec", keys: np.ndarray,
                           values: np.ndarray,
                           dicts: Dict[str, StringDictionary],
                           columns: Optional[Sequence[str]] = None
                           ) -> ColumnarBatch:
    """(keys [g,k], values [g,m]) → a ColumnarBatch in the view's row
    shape. The single materialization point for view reads — ViewTable
    (single node) and DistributedView (sharded) both go through it, so
    the two read paths cannot drift. With `columns` only those are
    built (in the view's order), and `values` holds the asked sum
    columns alone (`ViewSpec.asked_sums`)."""
    cols: Dict[str, np.ndarray] = {}
    for i, name in enumerate(spec.key_columns):
        if columns is None or name in columns:
            cols[name] = keys[:, i].astype(
                np.int32 if name in dicts else np.int64)
    for i, (_, name) in enumerate(spec.asked_sums(columns)):
        cols[name] = values[:, i]
    return ColumnarBatch(cols, {n: dicts[n] for n in cols if n in dicts})


def read_tally() -> Dict[str, int]:
    """What a ranged read opened, before it has opened anything: the
    batches or parts it `read` and those it `pruned` by their cached
    bounds, and the `rows` of those read (before the mask). A table's
    and a view's `last_read()` answer one."""
    return {"read": 0, "pruned": 0, "rows": 0}


def window_fate(start: Optional[int], end: Optional[int],
                first: Optional[Tuple[int, int]],
                last: Optional[Tuple[int, int]]) -> Optional[bool]:
    """What a part's cached bounds say of the window `a >= start AND
    b < end` (either side may be open): `first` is the part's (min,
    max) of a, `last` of b, None where it is not cached. False: no row
    of the part can lie inside, skip it unread; True: every row does,
    take it whole; None: the window may cut it, mask it."""
    if (start is not None and first is not None and first[1] < start) \
            or (end is not None and last is not None and last[0] >= end):
        return False
    if (start is None or (first is not None and first[0] >= start)) \
            and (end is None or (last is not None and last[1] < end)):
        return True
    return None


@dataclasses.dataclass(frozen=True)
class ViewSpec:
    key_columns: Tuple[str, ...]
    sum_columns: Tuple[str, ...]

    def asked_sums(self, columns: Optional[Sequence[str]]
                   ) -> List[Tuple[int, str]]:
        """(index, name) of the sum columns a read of `columns` asks
        for, in the view's order; all of them without `columns`."""
        return [(i, n) for i, n in enumerate(self.sum_columns)
                if columns is None or n in columns]


# Column lists transcribed from the reference MV definitions (see module
# docstring for the create_table.sh line ranges).
MATERIALIZED_VIEWS: Dict[str, ViewSpec] = {
    "flows_pod_view": ViewSpec(
        key_columns=(
            "timeInserted", "flowEndSeconds", "flowEndSecondsFromSourceNode",
            "flowEndSecondsFromDestinationNode", "sourcePodName",
            "destinationPodName", "destinationIP", "destinationServicePort",
            "destinationServicePortName", "flowType", "sourcePodNamespace",
            "destinationPodNamespace", "sourceTransportPort",
            "destinationTransportPort", "clusterUUID"),
        sum_columns=(
            "octetDeltaCount", "reverseOctetDeltaCount", "throughput",
            "reverseThroughput", "throughputFromSourceNode",
            "throughputFromDestinationNode")),
    "flows_node_view": ViewSpec(
        key_columns=(
            "timeInserted", "flowEndSeconds", "flowEndSecondsFromSourceNode",
            "flowEndSecondsFromDestinationNode", "sourceNodeName",
            "destinationNodeName", "sourcePodNamespace",
            "destinationPodNamespace", "clusterUUID"),
        sum_columns=(
            "octetDeltaCount", "reverseOctetDeltaCount", "throughput",
            "reverseThroughput", "throughputFromSourceNode",
            "reverseThroughputFromSourceNode",
            "throughputFromDestinationNode",
            "reverseThroughputFromDestinationNode")),
    "flows_policy_view": ViewSpec(
        key_columns=(
            "timeInserted", "flowEndSeconds", "flowEndSecondsFromSourceNode",
            "flowEndSecondsFromDestinationNode", "egressNetworkPolicyName",
            "egressNetworkPolicyNamespace", "egressNetworkPolicyRuleAction",
            "ingressNetworkPolicyName", "ingressNetworkPolicyNamespace",
            "ingressNetworkPolicyRuleAction", "sourcePodName",
            "sourceTransportPort", "sourcePodNamespace",
            "destinationPodName", "destinationTransportPort",
            "destinationPodNamespace", "destinationServicePort",
            "destinationServicePortName", "destinationIP", "clusterUUID"),
        sum_columns=(
            "octetDeltaCount", "reverseOctetDeltaCount", "throughput",
            "reverseThroughput", "throughputFromSourceNode",
            "reverseThroughputFromSourceNode",
            "throughputFromDestinationNode",
            "reverseThroughputFromDestinationNode")),
}


class ViewTable:
    """One materialized view: accumulated (keys, sums) parts + compaction."""

    #: the key columns whose (min, max) is kept a part: a panel's range
    #: is on `flowEndSeconds`, a retention round's on `timeInserted`
    BOUND_COLUMNS = ("timeInserted", "flowEndSeconds")

    def __init__(self, name: str, spec: ViewSpec,
                 dicts: Dict[str, StringDictionary]) -> None:
        self.name = name
        self.spec = spec
        # Shared with the flows table, so view key codes decode with the
        # same dictionaries.
        self.dicts = dicts
        # Parts are (keys, values, exact). `exact` records whether the
        # part is known collision-free (a grouping that compared full
        # keys: native_group_sum's, or a read-time group_sum_exact);
        # group_sum_fast parts are not — a 64-bit row-hash collision
        # can split one key across rows. No part is empty.
        self._parts: List[Tuple[np.ndarray, np.ndarray, bool]] = []
        # Aligned with _parts: {column: (min, max)} of each part's keys
        # for the BOUND_COLUMNS the view has. A ranged read skips a
        # part that cannot meet its range, a delete drops or keeps a
        # part whole, neither reading a key.
        self._bound_index = {c: spec.key_columns.index(c)
                             for c in self.BOUND_COLUMNS
                             if c in spec.key_columns}
        self._bounds: List[Dict[str, Tuple[int, int]]] = []
        #: bumped by every insert, delete, restore and truncate: a
        #: compaction is swapped in only if it is still the one it
        #: was read at (a delete can cut an old part and leave the
        #: parts' count and the last part as they were)
        self.generation = 0
        self._lock = named_lock("store.view")
        # what the calling thread's last `select` opened (`last_read`)
        self._last = threading.local()

    def __len__(self) -> int:
        keys, _ = self._merged()
        return keys.shape[0]

    def _bounds_of(self, keys: np.ndarray) -> Dict[str, Tuple[int, int]]:
        return {c: (int(keys[:, i].min()), int(keys[:, i].max()))
                for c, i in self._bound_index.items()}

    def _empty(self) -> Tuple[np.ndarray, np.ndarray]:
        return (np.zeros((0, len(self.spec.key_columns)), np.int64),
                np.zeros((0, len(self.spec.sum_columns)), np.int64))

    def apply_insert_block(self, block: ColumnarBatch) -> None:
        """Aggregate one flows insert block into this view (the MV SELECT
        ... GROUP BY per inserted block). Native single-pass hash
        grouping when available (native/groupsum.cc); numpy hash-sort
        otherwise — both emit unordered SummingMergeTree parts that
        a read re-groups exactly."""
        if len(block) == 0:
            return
        out = native_group_sum(
            [block[c] for c in self.spec.key_columns],
            [block[c] for c in self.spec.sum_columns])
        exact = out is not None  # native grouping memcmps full keys
        if out is None:
            keys = np.stack([np.asarray(block[c], np.int64)
                             for c in self.spec.key_columns], axis=1)
            values = np.stack([np.asarray(block[c], np.int64)
                               for c in self.spec.sum_columns], axis=1)
            out = group_sum_fast(keys, values)
        bounds = self._bounds_of(out[0])
        with self._lock:
            self._parts.append((out[0], out[1], exact))
            self._bounds.append(bounds)
            self.generation += 1

    def _merged(self) -> Tuple[np.ndarray, np.ndarray]:
        return self._compacted()[:2]

    def _compacted(self) -> Tuple[np.ndarray, np.ndarray, int,
                                  Optional[str]]:
        """The view as one exact part (keys, values), the rows that
        were re-grouped for it and how (`group_sum_exact`'s word; 0 and
        None where the view was one exact part, or empty, already)."""
        with self._lock:
            parts = list(self._parts)
            generation = self.generation
        if not parts:
            return (*self._empty(), 0, None)
        if len(parts) == 1 and parts[0][2]:
            return parts[0][0], parts[0][1], 0, None
        # Re-group even a lone inexact part: group_sum_fast may have
        # split a hash-colliding key into two rows, and scan() promises
        # exact re-grouping at read time.
        gk, gv, how = group_sum_exact([p[:2] for p in parts])
        bounds = self._bounds_of(gk)
        with self._lock:
            # Swap in the compacted part only if no insert, delete,
            # restore or truncate raced us.
            if self.generation == generation:
                self._parts = [(gk, gv, True)]
                self._bounds = [bounds]
        return gk, gv, sum(len(p[0]) for p in parts), how

    def compact(self) -> None:
        self._merged()

    def scan(self) -> ColumnarBatch:
        """The view as a ColumnarBatch (keys + summed metrics): grouped
        exactly, its rows in no stated order."""
        keys, values = self._merged()
        return materialize_view_batch(self.spec, keys, values,
                                      self.dicts)

    #: a range that opens at least this share of the view's rows is
    #: answered from the compacted view (see `select`)
    COMPACT_SHARE = 1 / 8

    def select(self, start: Optional[int] = None,
               end: Optional[int] = None,
               columns: Optional[Sequence[str]] = None) -> ColumnarBatch:
        """The view's rows with `start <= flowEndSeconds < end` (the
        panels' `$__timeFilter`), in `scan()`'s row shape and grouped
        as exactly: the rows `scan()` then that mask gives, like them
        in no stated order; `columns` projects the result to that
        subset (the rows are still grouped by every key; only the
        asked sums are gathered and summed).

        The parts are walked by their cached bounds: one that cannot
        meet the range is skipped unread. What the walk opens decides
        how the rest is read. **A range that opens a small share of the
        view** (under `COMPACT_SHARE` of its rows: the last minutes of
        a store that holds an hour) takes each opened part whole, or
        masked on its own where the range cuts it, and re-groups only
        the rows taken (`group_sum_exact`, the parts read where they
        lie: equal keys of different insert blocks collapse and a
        `group_sum_fast` part's hash-split key is rejoined; the column
        is a key, so every row of a key is on one side of the range);
        the view is left as it lies. **A range
        that opens most of the view** gains little from the walk and
        would re-group nearly the whole view at every request, so it
        compacts the view as `scan()` does (once an insert block; the
        copy is swapped in only at the generation it was read at) and
        masks the one exact part, which needs no re-grouping; every
        later read of the view then finds that part. `last_read()`
        says what was opened and what was re-grouped."""
        col = self._bound_index["flowEndSeconds"]
        sums = [i for i, _ in self.spec.asked_sums(columns)]
        with self._lock:
            parts = list(self._parts)
            bounds = list(self._bounds)
        fates = [window_fate(start, end, known["flowEndSeconds"],
                             known["flowEndSeconds"]) for known in bounds]
        opened = [(part, fate) for part, fate in zip(parts, fates)
                  if fate is not False]
        rows = sum(len(part[0]) for part, _ in opened)
        total = sum(len(part[0]) for part in parts)
        if len(parts) > 1 and rows >= self.COMPACT_SHARE * total > 0:
            seen = dict(read=len(parts), pruned=0, rows=total)
            keys, values, regrouped, how = self._compacted()
            pair = (int(keys[:, col].min()), int(keys[:, col].max()))
            opened = [((keys, values, True),
                       window_fate(start, end, pair, pair))]
        else:
            seen = dict(read=len(opened), rows=rows,
                        pruned=len(parts) - len(opened))
            regrouped, how = 0, None
        taken: List[Tuple[np.ndarray, np.ndarray]] = []
        for (keys, values, _), fate in opened:
            if fate is None:
                mask = np.ones(len(keys), bool)
                if start is not None:
                    mask &= keys[:, col] >= start
                if end is not None:
                    mask &= keys[:, col] < end
                keys, values = keys[mask], values[mask]
            if len(keys):
                taken.append((keys, values if columns is None
                              else values[:, sums]))
        if not taken:
            gk, gv = self._empty()
            gv = gv[:, sums]
        elif len(opened) == 1 and opened[0][0][2]:
            gk, gv = taken[0]          # one exact part: grouped already
        else:
            # (`values[:, sums]` is column-major: the one-part path
            # above hands its columns on as they are, this one pays
            # the row-major copy the grouping reads in place)
            gk, gv, how = group_sum_exact(
                [(k, np.ascontiguousarray(v)) for k, v in taken])
            regrouped = sum(len(k) for k, _ in taken)
        self._last.read = dict(seen, regrouped=regrouped, how=how)
        return materialize_view_batch(self.spec, gk, gv, self.dicts,
                                      columns)

    def last_read(self) -> Dict[str, object]:
        """What the calling thread's last `select` opened: parts `read`
        and `pruned` by their bounds, the `rows` of those read (before
        the mask and the re-grouping), the rows it `regrouped` (those
        handed to `group_sum_exact`, by the compaction or for the rows
        taken; 0 where one exact part answered) and `how` (`hash`, the
        native pass, or `sort`, the lexsort; None with 0 rows)."""
        return getattr(self._last, "read", None) or dict(
            read_tally(), regrouped=0, how=None)

    def restore(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Install persisted (keys, values) aggregates wholesale — the
        parts-aware snapshot saves views instead of rebuilding them
        from rows at load (the flat-load discipline would force every
        lazy part to decode). The arrays come from a `_merged()`
        capture, so the single part is exact."""
        keys = np.asarray(keys, np.int64).reshape(
            -1, len(self.spec.key_columns))
        values = np.asarray(values, np.int64).reshape(
            -1, len(self.spec.sum_columns))
        with self._lock:
            self._parts = [(keys, values, True)] if len(keys) else []
            self._bounds = [self._bounds_of(k) for k, _, _ in self._parts]
            self.generation += 1

    def delete_older_than(self, boundary: int) -> int:
        """Drop view rows with timeInserted < boundary (retention trim
        deletes from MVs too, clickhouse-monitor/main.go:284-293).
        Walks part-by-part under the lock — no insert can be lost: a
        part wholly on one side of the boundary by its cached bounds is
        kept or dropped as it is, one that straddles it is filtered."""
        ti = self.spec.key_columns.index("timeInserted")
        with self._lock:
            dropped = 0
            new_parts, new_bounds = [], []
            for part, known in zip(self._parts, self._bounds):
                keys, values, exact = part
                lo, hi = known["timeInserted"]
                if hi < boundary:
                    dropped += len(keys)
                    continue
                if lo < boundary:          # straddles: some go, some stay
                    keep = keys[:, ti] >= boundary
                    dropped += len(keys) - int(np.count_nonzero(keep))
                    keys = keys[keep]
                    part = (keys, values[keep], exact)
                    known = self._bounds_of(keys)
                new_parts.append(part)
                new_bounds.append(known)
            self._parts, self._bounds = new_parts, new_bounds
            if dropped:
                self.generation += 1
        return dropped

    def totals(self) -> Dict[str, int]:
        """sum(`octetDeltaCount`) and `oldestTimeInserted` over the
        parts as they lie; equal whether or not they were merged
        (FlowDatabase.view_totals)."""
        oc = self.spec.sum_columns.index("octetDeltaCount")
        with self._lock:
            parts = list(self._parts)
            oldest = [b["timeInserted"][0] for b in self._bounds]
        doc = {"octetDeltaCount": sum(int(v[:, oc].sum())
                                      for _, v, _ in parts)}
        if oldest:
            doc["oldestTimeInserted"] = min(oldest)
        return doc

    def truncate(self) -> None:
        with self._lock:
            self._parts = []
            self._bounds = []
            self.generation += 1
