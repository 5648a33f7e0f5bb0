"""checks/retention_trim.py is sharp: over a small store kept here in
numpy, whose sound round is `main.go`'s, the check is correct, and
each round that does something else gives `correct: false` by the
number that names the fault. No manager is started: the check reads
records, counters and three answers, and these are made here.

Three producers (48 rows a second while all three send), 64-row blocks;
when the trimmer asks, 8 + 7 + 4 blocks are acked and one more of each
producer is in flight, of which the first's is in the store: 20
blocks, 1,280 rows, delete_n 640 = 13 seconds and a third of the
14th, so `<`, `<=` and "exactly delete_n rows" are three different
rounds."""

import copy

import numpy as np
import pytest

from benchmarks import check as _check
from benchmarks import gen
from benchmarks.checks import retention_trim as rt
from benchmarks.references import retention_trim as ref

TRAFFIC = {"name": "t", "generator": {
    "connections_per_producer": 64, "conns_per_block": 16,
    "points_per_conn": 4, "spike_rate": 0.0}, "limits": {}}
SEED = 2147486101
CERTAIN = (8, 7, 4)        # blocks acked before the request, a producer
IN_STORE = (9, 7, 4)       # ... in the store when the round counts
TOTAL = (12, 11, 9)        # ... acked by the end of the run
T_ASK = 1000.0
NUMBERS = ("trim_rounds_gap", "trim_boundary_gap", "trim_rows_deleted_gap",
           "trim_store_rows_gap", "trim_store_octets_gap",
           "trim_oldest_row_gap", "trim_view_rows_gap",
           "trim_detector_series_gap", "trim_ambiguous_blocks")


class Store:
    """flows as (producer, second, octets) a row; a view as (producer,
    second, octets) a group and insert block."""

    def __init__(self):
        self.streams = [gen.stream(TRAFFIC, SEED, p) for p in range(3)]
        self.flows = np.zeros((0, 3), np.int64)
        self.views = {v: np.zeros((0, 3), np.int64) for v in ref.VIEW_KEYS}
        self.codes = {(p, v): ref.key_codes(
            gen.Population(p, s.n_conn, s.start), ref.VIEW_KEYS[v])
            for p, s in enumerate(self.streams) for v in ref.VIEW_KEYS}
        self.deleted_total = 0

    def append(self, p, b):
        s = self.streams[p]
        v = s.values(b)
        octets = v["thr"] * s.interval                   # [cpb, points]
        t = np.broadcast_to(v["flow_end"], octets.shape)
        rows = np.stack([np.full(octets.size, p), t.ravel(),
                         octets.ravel()], axis=1)
        self.flows = np.concatenate([self.flows, rows])
        for name in self.views:
            _, g = np.unique(self.codes[(p, name)][v["conn"]], axis=0,
                             return_inverse=True)
            g = np.asarray(g).ravel()
            sums = np.zeros((g.max() + 1, s.points), np.int64)
            np.add.at(sums, g, octets)
            tt = np.broadcast_to(v["flow_end"], sums.shape)
            part = np.stack([np.full(sums.size, p), tt.ravel(),
                             sums.ravel()], axis=1)
            self.views[name] = np.concatenate([self.views[name], part])

    def round(self, mode="sound"):
        """One round; `mode` names what it does other than main.go."""
        n = len(self.flows)
        delete_n = int(n * 0.5)
        order = np.argsort(self.flows[:, 1], kind="stable")
        boundary = int(self.flows[order[delete_n - 1], 1])
        if mode == "boundary_off_by_one":
            boundary += 1
        gone = self.flows[:, 1] < boundary
        if mode == "at_most":                 # `<=` for `<`
            gone = self.flows[:, 1] <= boundary
        if mode == "exactly_delete_n":        # cuts a second in two
            gone = np.zeros(n, bool)
            gone[order[:delete_n]] = True
        dropped = {}
        for name, part in self.views.items():
            cut = boundary
            if mode == "view_left" and name == "flows_node_view":
                cut = 0
            if mode == "view_by_its_own_median" \
                    and name == "flows_node_view":
                # its own `LIMIT 1 OFFSET n/2 - 1`, over what the view
                # holds by then: the two blocks in flight as well (at
                # the same rows a second as flows its median would be
                # the table's)
                s1, s2 = self.streams[1], self.streams[2]
                own = np.concatenate([
                    part[:, 1],
                    np.tile(s1.values(IN_STORE[1])["flow_end"], s1.cpb),
                    np.tile(s2.values(IN_STORE[2])["flow_end"], s2.cpb)])
                cut = int(np.sort(own)[len(own) // 2 - 1])
            keep = part[:, 1] >= cut
            dropped[name] = int((~keep).sum())
            self.views[name] = part[keep]
        self.flows = self.flows[~gone]
        self.deleted_total += int(gone.sum())
        return {"result": "trimmed", "rows_before": n,
                "delete_n": delete_n, "boundary": boundary,
                "rows_deleted": int(gone.sum()),
                "view_rows_deleted": dropped}

    # -- what the check reads ------------------------------------------

    def json(self, path, doc=None, timeout=None):
        if path == rt.QUERY:
            rows = []
            for p in np.unique(self.flows[:, 0]):
                mine = self.flows[self.flows[:, 0] == p]
                rows.append({"clusterUUID": gen.cluster_uuid(int(p)),
                             "count": len(mine),
                             "sum(octetDeltaCount)": int(mine[:, 2].sum()),
                             "min(timeInserted)": int(mine[:, 1].min())})
            return {"rows": rows}
        assert path == "/debug/retention"
        return {"views": {
            name: {"octetDeltaCount": int(part[:, 2].sum()),
                   "oldestTimeInserted": int(part[:, 1].min())}
            for name, part in self.views.items()}}

    def merge_views(self):
        """What a read does to a view: equal keys of two blocks become
        one row."""
        for name, part in self.views.items():
            keys, g = np.unique(part[:, :2], axis=0, return_inverse=True)
            sums = np.zeros(len(keys), np.int64)
            np.add.at(sums, np.asarray(g).ravel(), part[:, 2])
            self.views[name] = np.column_stack([keys, sums])


def run(mode="sound", rounds_in_window=1, late_block_deleted=False,
        series_forgotten=0, certain=CERTAIN, views_merged=False):
    """The check's ctx after a run whose round was `mode`."""
    store = Store()
    for p, n in enumerate(IN_STORE):
        for b in range(n):
            store.append(p, b)
    asked = store.round(mode)
    for p, (a, n) in enumerate(zip(IN_STORE, TOTAL)):
        for b in range(a, n):
            store.append(p, b)
    if late_block_deleted:          # a block appended after the round
        s = store.streams[1]
        late = (store.flows[:, 0] == 1) \
            & (store.flows[:, 1] >= s.start + 8 * s.points)
        store.flows = store.flows[~late]
    if views_merged:                # something read the views since
        store.merge_views()

    def rec(p, b):
        # acked before the request, or sent before it and acked after,
        # or later altogether
        ack = T_ASK - 50 + b if b < certain[p] else T_ASK + 1 + b
        return {"block": b, "status": 200, "send": ack - 0.4, "ack": ack,
                "rows": 64, "rows_sent": 64}

    specs = [{"role": "producer", "producer": p} for p in range(3)]
    specs.append({"role": "trimmer"})
    none = [{"records": []} for _ in specs]
    results = [{"records": [rec(p, b) for b in range(TOTAL[p])]}
               for p in range(3)]
    asked.update(status=200, send=T_ASK, ack=T_ASK + 2)
    results.append({
        "records": [asked],
        "rounds_at_open": {"idle": 2, "healthz": 2},
        "rounds_at_close": {"idle": 2, "trimmed": rounds_in_window,
                            "healthz": 2 + rounds_in_window}})
    warm = copy.deepcopy(none)
    warm[3]["records"] = [{"status": 200, "result": "idle",
                           "send": 900.0, "ack": 900.1}]
    sent = sum(np.unique(np.concatenate(
        [s.conn_index(b) for b in range(n)])).size
        for s, n in zip(store.streams, TOTAL))
    return {
        "specs": specs, "preload": none, "warm": warm, "results": results,
        "probes": none, "traffic": TRAFFIC, "seed": SEED,
        "config": {"monitor": {"delete_percentage": 0.5}},
        "manager": store,
        "metrics_final": {rt.DELETED: float(store.deleted_total)},
        "health": {"ingest": {"perShard": [
            {"series": sent - series_forgotten}]}}}


def numbers(ctx):
    rep = _check.Report()
    rt.check(ctx, rep)
    assert set(rep.numbers) == set(NUMBERS)
    return rep, {k: v["value"] for k, v in rep.numbers.items() if v["value"]}


def test_a_sound_round_is_correct():
    rep, bad = numbers(run())
    assert rep.correct and not bad, rep.lines
    assert (rep.attempted, rep.failed) == (2, 0)
    # the round the three-way split was sized for
    s = Store().streams[0]
    want = ref.round_of([(st, n) for st, n in zip(Store().streams, TOTAL)],
                        CERTAIN, 20 * 64)
    assert (want["delete_n"], want["boundary"], want["rows_deleted"]) \
        == (640, s.start + 13, 13 * 48)


@pytest.mark.parametrize("fault,named", [
    # the boundary's own second deleted as well
    ({"mode": "at_most"}, "trim_rows_deleted_gap"),
    ({"mode": "at_most"}, "trim_oldest_row_gap"),
    # delete_n rows exactly: a second cut in two
    ({"mode": "exactly_delete_n"}, "trim_store_rows_gap"),
    ({"mode": "exactly_delete_n"}, "trim_rows_deleted_gap"),
    # flows trimmed, a view left as it was
    ({"mode": "view_left"}, "trim_view_rows_gap"),
    # a view trimmed by its own median
    ({"mode": "view_by_its_own_median"}, "trim_view_rows_gap"),
    # a block appended after the round deleted
    ({"late_block_deleted": True}, "trim_store_rows_gap"),
    ({"late_block_deleted": True}, "trim_store_octets_gap"),
    # a second trim in the window, none
    ({"rounds_in_window": 2}, "trim_rounds_gap"),
    ({"rounds_in_window": 0}, "trim_rounds_gap"),
    # a boundary one second off
    ({"mode": "boundary_off_by_one"}, "trim_boundary_gap"),
    # a detector that forgot a trimmed connection
    ({"series_forgotten": 1}, "trim_detector_series_gap"),
    # a block acked after the request that holds rows under the
    # boundary: the reference cannot say whether the round saw it
    ({"certain": (8, 7, 3)}, "trim_ambiguous_blocks"),
])
def test_a_round_that_does_something_else_is_not_correct(fault, named):
    rep, bad = numbers(run(**fault))
    assert not rep.correct
    assert named in bad, rep.lines


def test_only_the_faults_own_numbers_move():
    """A view left untrimmed moves the views' number and no other; a
    forgotten series the detector's alone."""
    assert set(numbers(run(mode="view_left"))[1]) == {"trim_view_rows_gap"}
    assert set(numbers(run(series_forgotten=3))[1]) \
        == {"trim_detector_series_gap"}
    assert set(numbers(run(rounds_in_window=2))[1]) == {"trim_rounds_gap"}


def test_a_refused_request_counts_as_failed_and_no_answer_is_a_gap():
    ctx = run()
    asked = ctx["results"][3]["records"][0]
    for key in ("result", "rows_before", "delete_n", "boundary",
                "rows_deleted", "view_rows_deleted"):
        asked.pop(key)
    asked.update(status=409, error="no loop")
    rep, bad = numbers(ctx)
    assert not rep.correct and rep.failed == 1
    assert {"trim_rounds_gap", "trim_boundary_gap",
            "trim_rows_deleted_gap", "trim_ambiguous_blocks"} <= set(bad)


def test_a_view_merged_after_the_round_is_still_correct():
    """What the check reads of a view does not depend on how its parts
    lie: a panel asked after the window merges them, fewer rows hold
    the same sums, and the run is as correct as before."""
    ctx = run(views_merged=True)
    unmerged = run()["manager"]
    assert all(len(ctx["manager"].views[v]) < len(unmerged.views[v])
               for v in ref.VIEW_KEYS)
    rep, bad = numbers(ctx)
    assert rep.correct and not bad, rep.lines


def test_the_references_views_are_the_programs_group_by():
    """The reference counts what a round deletes of a view from
    distinct keys a block; the store above materializes the groups:
    both agree, view by view, and a view's sums add up to the rows'
    own."""
    store = Store()
    for p, n in enumerate(TOTAL):
        for b in range(n):
            store.append(p, b)
    streams = list(zip(store.streams, TOTAL))
    kept = ref.retained(streams, 0)
    assert kept["oldest"] == store.streams[0].start
    for name, part in store.views.items():
        assert kept["views"][name]["octetDeltaCount"] \
            == int(part[:, 2].sum()) == int(store.flows[:, 2].sum())
    # 64 connections fall on fewer node-pair keys than pod keys
    assert len(store.views["flows_node_view"]) \
        <= len(store.views["flows_pod_view"]) == len(store.flows)
    rows = len(store.flows)
    asked = store.round()
    want = ref.round_of(streams, TOTAL, rows)
    assert want["view_rows_deleted"] == asked["view_rows_deleted"]
    assert 0 < want["view_rows_deleted"]["flows_node_view"] \
        <= want["view_rows_deleted"]["flows_pod_view"] \
        == want["rows_deleted"]
