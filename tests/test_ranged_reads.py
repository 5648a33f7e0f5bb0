"""The ranged reads behind the dashboards (store/flow_store.py
`Table.pieces` / `select`, store/views.py `ViewTable.select`): held to
the straightforward form they replaced, the whole table or view
gathered and then masked, and to what they promise beside the rows:
parts outside the range unread, nothing swapped into the store, the
cached bounds in step with the parts through every kind of change, a
compaction that raced a delete refused, the panels' answers the same
bytes however the store was built, the Grafana export carrying the
dashboard's range."""

import json

import numpy as np
import pytest

from benchmarks import gen, manifest, panels
from theia_tpu.dashboards import grafana_dashboard, queries
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.obs import trace
from theia_tpu.schema import ColumnarBatch
from theia_tpu.store import FlowDatabase
from theia_tpu.store import views as views_mod
from theia_tpu.store.views import MATERIALIZED_VIEWS, ViewTable
from theia_tpu.utils.native import native_available
from theia_tpu.store.wire import decode_block

T0 = 1_700_000_000
VIEWS = sorted(MATERIALIZED_VIEWS)
#: blocks of four seconds each; the third is sent twice (equal keys in
#: two parts) and the sixth reaches back over the fifth's seconds; 34
#: later blocks make the view large beside a range of two or three
STARTS = (0, 4, 8, 8, 12, 14) + tuple(range(20, 156, 4))
#: (start, end) offsets from T0, None = open
RANGES = {
    "aligned_to_parts": (4, 12),
    "cuts_a_part": (2, 9),
    "equal_keys_in_two_parts": (8, 12),
    "cuts_overlapping_parts": (13, 17),
    "empty": (1000, 2000),
    "everything": (None, None),
    "open_start": (None, 6),
    "open_end": (10, None),
}


def _regroup(rows):
    """What `last_read()` says of a re-group of `rows` rows."""
    return {"regrouped": rows,
            "how": ("hash" if native_available() else "sort") if rows
            else None}


def _compacts(parts, opened):
    """Whether a range that opens `opened` of the view's `parts` is
    answered from the compacted view (ViewTable.COMPACT_SHARE)."""
    rows = sum(len(k) for k in opened)
    return len(parts) > 1 and 0 < sum(len(p[0]) for p in parts) \
        <= rows / ViewTable.COMPACT_SHARE


def _block(start, seed=3):
    """24 connections x 4 seconds from T0 + start; the same seed gives
    the same connections, so two blocks over the same seconds carry
    equal keys."""
    return generate_flows(SynthConfig(
        n_series=24, points_per_series=4, start_time=T0 + start,
        service_fraction=0.3, external_fraction=0.2,
        protected_fraction=0.4, seed=seed))


def _db(compacted=False):
    db = FlowDatabase()
    for start in STARTS:
        db.insert_flows(_block(start))
    if compacted:
        db.flows.scan()
        for view in db.views.values():
            view.compact()
        assert len(db.flows._batches) == 1
        assert all(len(v._parts) == 1 for v in db.views.values())
    return db


def _bounds(r):
    start, end = RANGES[r]
    return (None if start is None else T0 + start,
            None if end is None else T0 + end)


def _sorted_rows(batch):
    """The batch's rows as one matrix in a canonical order."""
    names = sorted(batch.column_names)
    rows = np.stack([np.asarray(batch[n], np.int64) for n in names], 1)
    return names, rows[np.lexsort(rows.T[::-1])]


def _window(col, start, end):
    mask = np.ones(len(col), bool)
    if start is not None:
        mask &= col >= start
    if end is not None:
        mask &= col < end
    return mask


# -- a materialized view ---------------------------------------------------

@pytest.mark.parametrize("compacted", [False, True],
                         ids=["as_inserted", "compacted"])
@pytest.mark.parametrize("r", RANGES)
@pytest.mark.parametrize("name", VIEWS)
def test_view_select_is_scan_then_mask(name, r, compacted):
    db = _db(compacted)
    view = db.views[name]
    start, end = _bounds(r)
    parts = list(view._parts)
    got = view.select(start, end)
    seen = view.last_read()
    assert seen["read"] + seen["pruned"] == len(parts)
    fe = view.spec.key_columns.index("flowEndSeconds")
    opened = [k for k, _, _ in parts
              if (start is None or k[:, fe].max() >= start)
              and (end is None or k[:, fe].min() < end)]
    if _compacts(parts, opened):
        # most of the view: answered from its compaction, swapped in
        assert r in ("everything", "open_end")
        rows = sum(len(k) for k, _, _ in parts)
        assert seen == {"read": len(parts), "pruned": 0, "rows": rows,
                        **_regroup(rows)}
        assert len(view._parts) == 1 and view._parts[0][2]
    else:
        # a small share: nothing swapped in, the walk's figures are
        # the opened parts'
        assert len(parts) == len(view._parts)
        assert all(a is b for a, b in zip(parts, view._parts))
        assert seen["rows"] == sum(len(k) for k in opened)
        assert seen["read"] == len(opened)
        # one exact part answers as it is; several are re-grouped, the
        # rows the range took of them
        assert seen["regrouped"] <= seen["rows"]
        assert (seen["how"] is None) == (seen["regrouped"] == 0)
        assert (seen["regrouped"] > 0) == (
            len(opened) > 1
            or len(opened) == 1 and not all(p[2] for p in parts))
    whole = view.scan()
    want = whole.filter(_window(np.asarray(whole["flowEndSeconds"]),
                                start, end))
    names, rows = _sorted_rows(got)
    assert (names, rows.tolist()) == (
        _sorted_rows(want)[0], _sorted_rows(want)[1].tolist())
    for n in names:
        assert got[n].dtype == want[n].dtype
    if r == "empty":
        assert len(got) == 0 and seen["read"] == 0
    if r == "aligned_to_parts" and not compacted:
        rows = sum(len(k) for k, _, _ in parts[1:4])
        assert seen == {"read": 3, "pruned": len(STARTS) - 3,
                        "rows": rows, **_regroup(rows)}
    if r == "equal_keys_in_two_parts" and not compacted:
        # the block sent twice collapsed: half the rows, twice the sums
        assert len(got) * 2 == seen["rows"]
        once = parts[2][1].sum(axis=0)
        assert [int(got[c].sum()) for c in view.spec.sum_columns] \
            == (2 * once).tolist()


@pytest.mark.parametrize("r", ["cuts_a_part", "equal_keys_in_two_parts",
                               "empty", "everything"])
@pytest.mark.parametrize("name", VIEWS)
def test_view_select_projects_to_the_asked_columns(name, r):
    """`columns` keeps those columns of the same rows: the rows are
    grouped by every key whichever are asked, and only the asked sums
    are summed. (Column by column, row for row: a view's rows come in
    no stated order, but two reads of the same parts in the same order
    group them in the same order, whatever sums they carry.)"""
    view = _db().views[name]
    start, end = _bounds(r)
    asked = ("throughput", "flowEndSeconds", "clusterUUID",
             "octetDeltaCount", "no_such_column")
    whole = view.select(start, end)
    tally = view.last_read()
    got = view.select(start, end, asked)
    assert view.last_read() == tally or r == "everything"
    assert list(got.column_names) == [
        "flowEndSeconds", "clusterUUID", "octetDeltaCount", "throughput"]
    assert set(got.dicts) == {"clusterUUID"}
    for n in got.column_names:
        assert got[n].dtype == whole[n].dtype
        assert np.array_equal(got[n], whole[n]), n


@pytest.mark.parametrize("name", VIEWS)
def test_view_select_rejoins_a_hash_split_key(name, monkeypatch):
    """Parts grouped by `group_sum_fast` are not known to be exact: a
    row-hash collision may have left one key on two rows. A part that
    holds such a pair, and the same key again in a later part, comes
    back as one row."""
    monkeypatch.setattr(views_mod, "native_group_sum",
                        lambda keys, values: None)
    db = FlowDatabase()
    for start in (0, 4, 4) + STARTS[6:]:
        db.insert_flows(_block(start))
    view = db.views[name]
    assert not any(exact for _, _, exact in view._parts)
    keys, values, _ = view._parts[1]
    split = (np.concatenate([keys, keys[:5]]),
             np.concatenate([values, 7 * values[:5]]), False)
    view._parts[1] = split
    view._bounds[1] = view._bounds_of(split[0])
    got = view.select(T0 + 4, T0 + 8)
    assert view.last_read() == {"read": 2, "pruned": len(STARTS) - 5,
                                "rows": 2 * len(keys) + 5,
                                **_regroup(2 * len(keys) + 5)}
    assert len(got) == len(keys)
    total = (split[1].sum(axis=0) + view._parts[2][1].sum(axis=0))
    assert [int(got[c].sum()) for c in view.spec.sum_columns] \
        == total.tolist()
    whole = view.scan()
    want = whole.filter(_window(np.asarray(whole["flowEndSeconds"]),
                                T0 + 4, T0 + 8))
    assert _sorted_rows(got)[1].tolist() == _sorted_rows(want)[1].tolist()


@pytest.mark.parametrize("name", VIEWS)
def test_a_compaction_that_raced_a_delete_is_not_swapped_in(
        name, monkeypatch):
    """`delete_older_than` can cut one old part and drop none: the
    parts' count and the last part are then what a compaction that
    began before it saw. Its copy still holds the deleted rows and
    must not become the view."""
    db = FlowDatabase()
    for start in (0, 4, 8):
        db.insert_flows(_block(start))
    view = db.views[name]
    boundary = T0 + 2
    real, raced = views_mod.group_sum_exact, []

    def group_sum_after_a_delete(parts):
        if not raced:       # between the read of the parts and the swap
            raced.append(view.delete_older_than(boundary))
        return real(parts)

    monkeypatch.setattr(views_mod, "group_sum_exact",
                        group_sum_after_a_delete)
    count, last = len(view._parts), view._parts[-1]
    view.compact()
    assert raced and raced[0] > 0
    assert len(view._parts) == count and view._parts[-1] is last
    ti = view.spec.key_columns.index("timeInserted")
    assert all(k[:, ti].min() >= boundary for k, _, _ in view._parts)
    assert int(np.asarray(view.scan()["timeInserted"]).min()) >= boundary
    assert view.totals()["oldestTimeInserted"] == boundary


def _view_bounds_hold(view):
    assert len(view._bounds) == len(view._parts)
    for (keys, values, _), known in zip(view._parts, view._bounds):
        assert len(keys) and len(keys) == len(values)
        assert known == {
            c: (int(keys[:, view.spec.key_columns.index(c)].min()),
                int(keys[:, view.spec.key_columns.index(c)].max()))
            for c in ViewTable.BOUND_COLUMNS}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("name", VIEWS)
def test_view_bounds_follow_every_change(name, seed):
    """After any sequence of insert, delete, restore, compact and
    truncate the cached bounds are what the parts' keys say, no part
    is empty, and the generation has moved with every change."""
    rng = np.random.default_rng([51, seed])
    db = FlowDatabase()
    view = db.views[name]
    saved = None
    for step in range(40):
        op = rng.choice(["insert", "insert", "insert", "delete",
                         "compact", "capture", "restore", "truncate"],
                        p=[.2, .2, .2, .15, .1, .06, .06, .03])
        before = view.generation
        if op == "insert":
            db.insert_flows(_block(int(rng.integers(0, 40)),
                                   seed=int(rng.integers(0, 3))))
            assert view.generation == before + 1
        elif op == "delete":
            gone = view.delete_older_than(T0 + int(rng.integers(0, 44)))
            assert (view.generation == before + 1) == (gone > 0)
        elif op == "compact":
            rows = len(view)
            view.compact()
            assert len(view._parts) <= 1 and len(view) == rows
            assert view.generation == before
        elif op == "capture":
            saved = view._merged()
        elif op == "restore" and saved is not None:
            view.restore(*saved)
            assert view.generation == before + 1
            assert len(view) == len(saved[0])
        elif op == "truncate":
            view.truncate()
            assert view._parts == [] and view.generation == before + 1
        _view_bounds_hold(view)
        totals = view.totals()
        if view._parts:
            ti = view.spec.key_columns.index("timeInserted")
            assert totals["oldestTimeInserted"] == min(
                int(k[:, ti].min()) for k, _, _ in view._parts)
        else:
            assert "oldestTimeInserted" not in totals


def test_a_sharded_views_select_is_its_scan_then_mask():
    from theia_tpu.store import ShardedFlowDatabase
    db = ShardedFlowDatabase(n_shards=3, seed=5)
    for start in STARTS:
        db.insert_flows(_block(start))
    for name in VIEWS:
        view = db.views[name]
        got = view.select(T0 + 2, T0 + 9)
        seen = view.last_read()
        assert seen["pruned"] > 0 and seen["read"] > 0
        some = view.select(T0 + 2, T0 + 9, ("octetDeltaCount",
                                            "flowEndSeconds"))
        assert list(some.column_names) == ["flowEndSeconds",
                                           "octetDeltaCount"]
        assert np.array_equal(some["octetDeltaCount"],
                              got["octetDeltaCount"])
        whole = view.scan()
        want = whole.filter(_window(
            np.asarray(whole["flowEndSeconds"]), T0 + 2, T0 + 9))
        assert got.strings("clusterUUID").tolist() \
            == want.strings("clusterUUID").tolist()
        assert _sorted_rows(got)[1].shape == _sorted_rows(want)[1].shape
        for c in view.spec.sum_columns:
            assert int(got[c].sum()) == int(want[c].sum())


# -- the flat table ---------------------------------------------------------

def _flows(state):
    db = FlowDatabase()
    for start in STARTS:
        db.insert_flows(_block(start))
    t = db.flows
    if state == "cut_by_a_delete":
        # timeInserted is the row's flowEndSeconds: the first batch is
        # cut, none is dropped
        assert t.delete_older_than(T0 + 2) == 24 * 2
        assert t.last_walk()["batchesCut"] == 1
    if state == "compacted":
        t.scan()
        assert len(t._batches) == 1
    return t


def _straight(t, start, end, time_column, end_column, columns):
    """The form `select` had: the table as one batch, then the mask."""
    data = ColumnarBatch.concat(list(t._batches))
    mask = np.ones(len(data), bool)
    if start is not None:
        mask &= data[time_column] >= start
    if end is not None:
        mask &= data[end_column] < end
    if columns is not None:
        data = data.select(columns)
    return data.filter(mask)


SOME = ("flowEndSeconds", "sourcePodName", "octetDeltaCount",
        "destinationIP")


@pytest.mark.parametrize("columns", [None, SOME],
                         ids=["all_columns", "four_columns"])
@pytest.mark.parametrize("on", ["jobs", "panels"])
@pytest.mark.parametrize("r", RANGES)
@pytest.mark.parametrize("state", ["as_appended", "cut_by_a_delete",
                                   "compacted"])
def test_table_select_is_scan_then_mask(state, r, on, columns):
    t = _flows(state)
    start, end = _bounds(r)
    if on == "jobs":        # flowStartSeconds >= start AND flowEndSeconds < end
        tc = "flowStartSeconds"
        if start is not None:       # a connection starts before its block
            start = int(np.median(np.concatenate(
                [b["flowStartSeconds"] for b in t._batches])))
        args = (start, end)
    else:                   # the panels': flowEndSeconds on both sides
        args = (start, end, "flowEndSeconds", "flowEndSeconds")
        tc = "flowEndSeconds"
    batches = list(t._batches)
    generation = t.generation
    got = t.select(*args, columns=columns)
    if start is None and end is None and columns is None:
        return                      # that is scan(), which compacts
    seen = t.last_read()
    assert [id(b) for b in t._batches] == [id(b) for b in batches]
    assert t.generation == generation
    assert seen["read"] + seen["pruned"] == len(batches)
    want = _straight(t, start, end, tc, "flowEndSeconds", columns)
    assert list(got.column_names) == list(want.column_names)
    for n in want.column_names:
        assert got[n].dtype == want[n].dtype
        assert np.array_equal(got[n], want[n]), n
    assert len(got) <= seen["rows"] <= sum(len(b) for b in batches)
    if on == "panels" and state == "as_appended":
        if r == "aligned_to_parts":
            assert seen == {"read": 3, "pruned": len(STARTS) - 3,
                            "rows": 3 * 96}
        if r == "empty":
            assert seen == {"read": 0, "pruned": len(STARTS), "rows": 0}
            assert len(got) == 0
    pieces = t.pieces(*args, columns=columns)
    assert sum(len(p) for p in pieces) == len(got)
    assert all(len(p) for p in pieces)


def _table_bounds_hold(t):
    assert len(t._batch_bounds) == len(t._batches) == len(t._batch_meta)
    for batch, known, pair in zip(t._batches, t._batch_bounds,
                                  t._batch_meta):
        assert known == {c: (int(batch[c].min()), int(batch[c].max()))
                         for c in t.TIME_BOUND_COLUMNS}
        assert pair == (int(batch["timeInserted"].min()),
                        int(batch["timeInserted"].max()))


@pytest.mark.parametrize("seed", range(4))
def test_table_bounds_follow_every_change(seed):
    rng = np.random.default_rng([510, seed])
    db = FlowDatabase()
    t = db.flows
    for step in range(40):
        op = rng.choice(["insert", "older", "where", "scan", "truncate"],
                        p=[.55, .2, .1, .1, .05])
        if op == "insert":
            db.insert_flows(_block(int(rng.integers(0, 40)),
                                   seed=int(rng.integers(0, 3))))
        elif op == "older":
            t.delete_older_than(T0 + int(rng.integers(0, 44)))
        elif op == "where" and len(t):
            t.delete_where(rng.random(len(t)) < 0.3)
        elif op == "scan":
            t.scan()
        elif op == "truncate":
            t.truncate()
        _table_bounds_hold(t)


# -- the panels -------------------------------------------------------------

BENCH = manifest.load()
TRAFFIC = BENCH.traffic("dashboards-volume")
PANELS = next(g for g in TRAFFIC["workers"]
              if g["role"] == "reader")["panels"]
#: the rehearsal's size with the cell's four producers
TINY = {"connections_per_producer": 64, "conns_per_block": 64,
        "points_per_conn": 4}
TINY_BLOCKS = 32


@pytest.fixture(scope="module")
def stores():
    """(as the blocks arrived, compacted first, the streams, the
    blocks)."""
    traffic = json.loads(json.dumps(TRAFFIC))
    traffic["generator"].update(TINY)
    streams = [gen.stream(traffic, 2147651001, p) for p in range(4)]
    blocks = [s.block(b)[0] for b in range(TINY_BLOCKS)
              for s in streams]         # the four streams in turn
    many, one = _built(blocks), _built(blocks)
    one.flows.scan()
    for view in one.views.values():
        view.compact()
    assert len(many.flows._batches) == 4 * TINY_BLOCKS
    assert len(one.flows._batches) == 1
    fresh = [gen.stream(traffic, 2147651001, p) for p in range(4)]
    return many, one, [(s, TINY_BLOCKS) for s in fresh], blocks


def _built(blocks):
    db = FlowDatabase()
    for payload in blocks:
        db.insert_flows(decode_block(payload))
    return db


def _query(panel):
    import urllib.parse
    url = urllib.parse.urlsplit(panel["path"])
    return (url.path.rsplit("/", 1)[1],
            {k: v[0] for k, v in urllib.parse.parse_qs(url.query).items()})


@pytest.mark.parametrize("panel", PANELS, ids=[p["name"] for p in PANELS])
def test_a_panels_answer_is_the_same_bytes_however_the_store_was_built(
        stores, panel):
    many, one, streams, _ = stores
    name, query = _query(panel)
    raw = queries.panel_json(many, name, query)
    assert raw == queries.panel_json(one, name, query)
    assert raw == queries.panel_json(many, name, query)
    # no read gathers `flows` into one batch (a range that opens an
    # eighth of a view this small does compact the view)
    assert len(many.flows._batches) == 4 * TINY_BLOCKS
    data = json.loads(raw)["data"]
    if panel["closed"]:
        assert data == panels.reference_panel(panel, streams)
        if int(query["start"]) >= gen.DEFAULT_START + 4 * TINY_BLOCKS:
            # beyond the rehearsal's seconds: the answer to an empty
            # range, which Grafana asks for too
            assert not any(data.values()) or data == {
                k: ({"times": [], "series": {}} if k == "throughput"
                    else []) for k in data}
    else:
        rows = 4 * TINY_BLOCKS * 256
        octets = sum(int(s.values(b)["thr"].sum()) * s.interval
                     for s, n in streams for b in range(n))
        assert panels.invariants(name, data, rows, octets) == []


@pytest.mark.parametrize("panel", PANELS, ids=[p["name"] for p in PANELS])
def test_a_ranged_panel_opens_only_the_parts_its_range_touches(
        stores, panel):
    """`rows` on the `dashboard.panel` span and the counters: the rows
    of the parts that were opened, not of the table; the parts met are
    all read or pruned."""
    from benchmarks import prom
    from theia_tpu.obs import prom as exposition

    many = _built(stores[3])            # a store no read has compacted
    name, query = _query(panel)
    before = prom.parse(exposition.render())
    queries.panel_json(many, name, query)
    after = prom.parse(exposition.render())

    def rise(series):
        return after.get(series, 0) - before.get(series, 0)

    table = {"pod_to_pod": "flows_pod_view",
             "pod_to_service": "flows_pod_view",
             "pod_to_external": "flows_pod_view",
             "node_to_node": "flows_node_view",
             "networkpolicy": "flows_policy_view"}.get(name, "flows")
    read = rise('theia_dashboard_parts_total{table="%s",how="read"}'
                % table)
    pruned = rise('theia_dashboard_parts_total{table="%s",how="pruned"}'
                  % table)
    rows = rise("theia_dashboard_rows_scanned_total")
    assert read + pruned == 4 * TINY_BLOCKS
    if "start" not in query:                    # homepage: every batch
        assert (read, pruned, rows) == (128, 0, 128 * 256)
        return
    start = int(query["start"]) - gen.DEFAULT_START
    end = int(query["end"]) - gen.DEFAULT_START
    # a stream's block b holds seconds [4b, 4b + 4)
    touched = sum(1 for b in range(TINY_BLOCKS)
                  if 4 * b < end and 4 * b + 4 > start) * 4
    if table != "flows" and touched * 8 >= 128:
        # an eighth of a view this small: answered from the compacted
        # view, every part read (`pod_to_external`, `networkpolicy`)
        assert (read, pruned) == (128, 0) and rows > touched * 64
        assert len(many.views[table]._parts) == 1
    else:
        assert (read, pruned) == (touched, 128 - touched)
        assert pruned > 0
        if table == "flows":
            assert rows == touched * 256
        else:
            assert len(many.views[table]._parts) == 128
            assert 0 < rows <= touched * 256 or touched == 0
    span = next(s for s in trace.recent()
                if s["op"] == "dashboard.panel")
    assert span["panel"] == name
    assert span.get("rows", 0) == rows


def _homepage_of_one_batch(db):
    """The form `homepage` had: every statistic over the whole table
    as one batch."""
    flows = db.flows.scan()
    out = {"flowCount": len(flows), "tadAnomalies": 0,
           "recommendations": 0, "droppedFlowCount": 0}
    for stat, col in (("podCount", "sourcePodName"),
                      ("namespaceCount", "sourcePodNamespace"),
                      ("nodeCount", "sourceNodeName"),
                      ("serviceCount", "destinationServicePortName"),
                      ("clusterCount", "clusterUUID")):
        out[stat] = int((np.unique(flows[col]) != 0).sum())
    out["totalBytes"] = int(flows["octetDeltaCount"].sum())
    out["currentThroughput"] = int(flows["throughput"][
        flows["timeInserted"] == flows["timeInserted"].max()].sum())
    out["droppedFlowCount"] = int(
        (np.isin(flows["ingressNetworkPolicyRuleAction"], (2, 3))
         | np.isin(flows["egressNetworkPolicyRuleAction"], (2, 3))).sum())
    names = flows.dicts["sourcePodNamespace"]
    totals = np.bincount(np.asarray(flows["sourcePodNamespace"], np.int64),
                         weights=np.asarray(flows["octetDeltaCount"],
                                            np.float64))
    totals[0] = 0
    out["topNamespaces"] = [
        {"name": names.decode_one(int(g)), "value": int(totals[g])}
        for g in np.argsort(-totals)[:8] if totals[g] > 0]
    times, inv = np.unique(flows["flowEndSeconds"], return_inverse=True)
    out["throughput"] = {"times": times.tolist(), "series": {
        "cluster": np.bincount(inv, weights=np.asarray(
            flows["throughput"], np.float64)).astype(np.int64).tolist()}}
    out["dropAnomalies"] = 0
    return out


def test_homepage_reduced_batch_by_batch_is_the_whole_tables(stores):
    many, one, _, _ = stores
    got = queries.homepage(many)
    assert len(many.flows._batches) == 4 * TINY_BLOCKS      # no copy swapped in
    want = _homepage_of_one_batch(one)
    assert got == want
    assert json.dumps(got) == json.dumps(queries.homepage(one))
    assert queries.homepage(FlowDatabase()) == {
        "flowCount": 0, "tadAnomalies": 0, "recommendations": 0,
        "droppedFlowCount": 0, "topNamespaces": [],
        "throughput": {"times": [], "series": {}}, "dropAnomalies": 0}


@pytest.mark.parametrize("name", list(queries.DASHBOARDS))
def test_the_grafana_export_sends_the_dashboards_range(name):
    doc = grafana_dashboard(name)
    targets = [t for p in doc["panels"] for t in p["targets"]]
    assert targets
    for t in targets:
        assert t["urlPath"] == f"/dashboards/api/{name}"
        if name == "homepage":
            assert "params" not in t
        else:
            assert t["params"] == [["start", "${__from:date:seconds}"],
                                   ["end", "${__to:date:seconds}"]]
