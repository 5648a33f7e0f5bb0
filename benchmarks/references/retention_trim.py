"""What one round of the capacity monitor must do to a store that
holds given blocks: the plain reference of checks/retention_trim.py.

Numpy and the standard library, int64 only, over the generator's own
rows (`stream.values(b)`, `gen.Population`); nothing the program
computed enters but one count, `rows_before`: how many rows the round
says it found, from which follows how many blocks were in the store.

The round is upstream's, `plugins/clickhouse-monitor/main.go`, written
plainly:

  :258-276  usage = bytes used / capacity; over `threshold` (0.5) the
            round goes on
  :296-300  `SELECT COUNT() FROM flows`; delete_n = int(count x
            deletePercentage)
  :301-318  the boundary: `SELECT timeInserted FROM flows ORDER BY
            timeInserted LIMIT 1 OFFSET delete_n - 1`, here a full
            sort of every row's timeInserted
  :284-293  `ALTER TABLE t DELETE WHERE timeInserted < boundary` on
            flows and on every materialized view: rows of the
            boundary's own second stay
  :320      skip the next `skipRoundsNum` (3) rounds

WHICH BLOCKS WERE IN THE STORE is decided here, without the program: a
producer sends its blocks in order, one outstanding at a time, so the
blocks acked before the role sent its request (`acked_before`) were
certainly in, and of each producer's next block (in flight while the
round ran) nobody outside can say. `rows_before / rows a block` blocks
were in, in all. The blocks in flight are each producer's newest and
their rows are younger than the delete_n-th oldest row of the certain
ones, so the boundary does not depend on which of them were in: it is
the delete_n-th smallest timeInserted of the certain blocks' rows. A
run that breaks an assumption of this is not guessed at: each such
block counts in `ambiguous_blocks`, which the check holds to 0.

Of a view after the round the reference gives what does not depend on
how the view's parts lie, merged or not: sum(`octetDeltaCount`) and
the oldest `timeInserted` over the retained rows. What the ROUND says
it deleted of a view (`viewRowsDeleted`) it counts as the parts held
them at that moment, as ClickHouse's `ALTER TABLE ... DELETE` counts
the rows of a SummingMergeTree's parts before a merge: the view's
SELECT ... GROUP BY runs per insert block (create_table.sh:92-351; the
key columns below, sums of `octetDeltaCount` among others), so a block
added one row for every distinct key among its rows, and nothing in
this cell reads (and so merges) a view before the round. All four time
columns of the key are the point's own second here, and every other
key column is fixed per connection: a block of `cpb` connections x
`points` seconds added `points` x (distinct keys among its
connections) rows.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.gen import Population

#: the views' GROUP BY columns besides the four time columns
#: (timeInserted, flowEndSeconds, flowEndSecondsFromSourceNode,
#: flowEndSecondsFromDestinationNode), create_table.sh:92-351
VIEW_KEYS: Dict[str, Tuple[str, ...]] = {
    "flows_pod_view": (
        "sourcePodName", "destinationPodName", "destinationIP",
        "destinationServicePort", "destinationServicePortName", "flowType",
        "sourcePodNamespace", "destinationPodNamespace",
        "sourceTransportPort", "destinationTransportPort", "clusterUUID"),
    "flows_node_view": (
        "sourceNodeName", "destinationNodeName", "sourcePodNamespace",
        "destinationPodNamespace", "clusterUUID"),
    "flows_policy_view": (
        "egressNetworkPolicyName", "egressNetworkPolicyNamespace",
        "egressNetworkPolicyRuleAction", "ingressNetworkPolicyName",
        "ingressNetworkPolicyNamespace", "ingressNetworkPolicyRuleAction",
        "sourcePodName", "sourceTransportPort", "sourcePodNamespace",
        "destinationPodName", "destinationTransportPort",
        "destinationPodNamespace", "destinationServicePort",
        "destinationServicePortName", "destinationIP", "clusterUUID"),
}


def key_codes(pop: Population, columns: Sequence[str]) -> np.ndarray:
    """[n_conn, len(columns)] int64: per connection a code of each key
    column's value (equal strings share a code)."""
    cols = []
    for name in columns:
        if name in pop.strings:
            table, idx = pop.strings[name]
            _, code = np.unique(np.asarray(table, dtype=object),
                                return_inverse=True)
            cols.append(code[np.asarray(idx)])
        else:
            cols.append(np.asarray(pop.static[name], np.int64))
    return np.stack(cols, axis=1).astype(np.int64)


class Groups:
    """Distinct keys of each view among the connections of a block,
    per producer and slice of connections."""

    def __init__(self) -> None:
        self._codes: Dict[Tuple[int, str], np.ndarray] = {}
        self._count: Dict[Tuple[int, str, bytes], int] = {}

    def distinct(self, stream, b: int, view: str) -> int:
        conn = np.asarray(stream.conn_index(b))
        key = (stream.producer, view, conn.tobytes())
        n = self._count.get(key)
        if n is None:
            codes = self._codes.get((stream.producer, view))
            if codes is None:
                pop = Population(stream.producer, stream.n_conn,
                                 stream.start)
                codes = self._codes[(stream.producer, view)] = \
                    key_codes(pop, VIEW_KEYS[view])
            n = self._count[key] = len(np.unique(codes[conn], axis=0))
        return n


def block_seconds(stream, b: int) -> Tuple[np.ndarray, np.ndarray]:
    """(timeInserted of each of block b's seconds [points], the sum of
    octetDeltaCount over the block's rows of that second [points]);
    every second holds `cpb` rows (gen.py `block`: timeInserted =
    flowEndSeconds, octetDeltaCount = throughput x interval)."""
    v = stream.values(b)
    return (np.asarray(v["flow_end"], np.int64),
            v["thr"].sum(axis=0).astype(np.int64) * stream.interval)


def round_of(streams: Sequence[Tuple[object, int]],
             acked_before: Sequence[int], rows_before: int,
             delete_percentage: float = 0.5) -> Dict:
    """The round over a store that held `rows_before` rows, of which
    certainly the first `acked_before[p]` blocks of each producer.
    `streams`: (stream, blocks acked in the whole run) per producer.
    Returns `delete_n`, `boundary`, `rows_deleted`,
    `view_rows_deleted` by view, `ambiguous_blocks` and `why` (what
    was ambiguous)."""
    why: List[str] = []
    ambiguous = 0
    rows_a_block = streams[0][0].rows
    if rows_before % rows_a_block:
        ambiguous += 1
        why.append(f"rows_before {rows_before} is no whole number of "
                   f"{rows_a_block}-row blocks")
    certain = sum(acked_before) * rows_a_block
    in_flight = sum(n > a for (_, n), a in zip(streams, acked_before))
    extra = (rows_before - certain) // rows_a_block
    if extra < 0 or extra > in_flight:
        ambiguous += abs(extra) if extra < 0 else extra - in_flight
        why.append(f"{rows_before} rows before, {certain} certain, "
                   f"{in_flight} blocks in flight")
    delete_n = int(rows_before * delete_percentage)
    times = [np.tile(block_seconds(s, b)[0], s.cpb)
             for (s, _), a in zip(streams, acked_before)
             for b in range(a)]
    times = np.sort(np.concatenate(times)) if times \
        else np.zeros(0, np.int64)
    if not 0 < delete_n <= len(times):
        return {"delete_n": delete_n, "boundary": None, "rows_deleted": 0,
                "view_rows_deleted": {v: 0 for v in VIEW_KEYS},
                "ambiguous_blocks": ambiguous + 1,
                "why": why + [f"delete_n {delete_n} of {len(times)} "
                              f"certain rows"]}
    boundary = int(times[delete_n - 1])      # LIMIT 1 OFFSET delete_n - 1
    # every block that holds a row below the boundary was certainly in
    for (s, n), a in zip(streams, acked_before):
        late = sum(int(block_seconds(s, b)[0].min()) < boundary
                   for b in range(a, n))
        if late:
            ambiguous += late
            why.append(f"producer {s.producer}: {late} blocks acked "
                       f"after the request hold rows under {boundary}")
    groups = Groups()
    dropped = {v: 0 for v in VIEW_KEYS}
    for (s, _), a in zip(streams, acked_before):
        for b in range(a):
            old = int((block_seconds(s, b)[0] < boundary).sum())
            for v in VIEW_KEYS:
                dropped[v] += old * groups.distinct(s, b, v)
    return {"delete_n": delete_n, "boundary": boundary,
            "rows_deleted": int(np.searchsorted(times, boundary)),
            "view_rows_deleted": dropped,
            "ambiguous_blocks": ambiguous, "why": why}


def retained(streams: Sequence[Tuple[object, int]], boundary: int) -> Dict:
    """What the store holds after quiescence: of every acked block the
    rows with timeInserted >= boundary (a later block holds no other).
    `by_producer`: {producer: (rows, sum(octetDeltaCount))}; `views`:
    {view: {"octetDeltaCount", "oldestTimeInserted"}}, the same for
    every view and whichever way its parts lie; `oldest`: the oldest
    row's timeInserted (None for an empty store)."""
    by_producer = {}
    oldest = None
    for s, n in streams:
        rows = octets = 0
        for b in range(n):
            t, o = block_seconds(s, b)
            keep = t >= boundary
            if not keep.any():
                continue
            rows += int(keep.sum()) * s.cpb
            octets += int(o[keep].sum())
            first = int(t[keep].min())
            oldest = first if oldest is None else min(oldest, first)
        by_producer[s.producer] = (rows, octets)
    total = sum(o for _, o in by_producer.values())
    return {"by_producer": by_producer, "oldest": oldest,
            "views": {v: {"octetDeltaCount": total,
                          "oldestTimeInserted": oldest}
                      for v in VIEW_KEYS}}
