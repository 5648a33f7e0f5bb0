"""A retention round that can be asked for (store/flow_store.py
`RetentionLoop.request`, POST /admin/retention), the record every
round fills, an append's wait for the table's lock, and the law the
round keeps: what `plugins/clickhouse-monitor/main.go:258-320` does to
`flows` and to the three materialized views, written here in numpy
with a full sort and `<`, on seeded random rows."""

import collections
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.manager import TheiaManagerServer
from theia_tpu.obs import metrics, trace
from theia_tpu.schema import ColumnarBatch
from theia_tpu.store import FlowDatabase, RetentionLoop, wire
from theia_tpu.store.flow_store import (RETENTION_STAGES, TRIM_WALK,
                                        RetentionUnavailable)
from theia_tpu.store.views import MATERIALIZED_VIEWS

RECORD_KEYS = {"result", "usageBefore", "rowsBefore", "deleteN",
               "boundary", "rowsDeleted", "viewRowsDeleted",
               "bytesFreed", "rowsAfter", "seconds", "stagesMs",
               # what the flat table's walk did with its batches
               "batchesDropped", "batchesCut", "batchesKept",
               "bytesCopied"}


def _batch(seed, n_series=12, points=6, times=None):
    """One seeded block; `times` replaces timeInserted (so that blocks
    interleave in time and many rows share a second)."""
    b = generate_flows(SynthConfig(n_series=n_series,
                                   points_per_series=points, seed=seed))
    if times is None:
        return b
    cols = dict(b.columns)
    cols["timeInserted"] = np.asarray(times, cols["timeInserted"].dtype)
    return ColumnarBatch(cols, b.dicts)


def _full(db, blocks=4, **kw):
    for i in range(blocks):
        db.insert_flows(_batch(i, **kw))
    return db


def _counter(name, **labels):
    m = metrics.REGISTRY.get(name)
    return (m.labels(**labels) if labels else m._default).value()


def _post(port, path, token=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method="POST", data=b"",
        headers={"Authorization": f"Bearer {token}"} if token else {})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


# -- the request -------------------------------------------------------------

class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class SlowMonitor:
    """A monitor whose round takes 5 s on the loop's clock and says on
    which thread it ran."""

    capacity_bytes = 1

    def __init__(self, clock):
        self.clock, self.threads = clock, []

    def tick(self):
        self.threads.append(threading.current_thread().name)
        self.clock.t += 5.0
        return 0

    def usage(self):
        return 0.0


def test_a_requested_round_runs_on_the_loops_thread_and_is_the_tick():
    clock = Clock()
    mon = SlowMonitor(clock)
    loop = RetentionLoop(mon, interval=60.0, clock=clock)
    with pytest.raises(RetentionUnavailable):
        loop.request(timeout=1)
    loop.start()
    try:
        clock.t += 20.0               # a third of the way to the tick
        rec = loop.request(timeout=10)
        assert mon.threads == ["theia-retention"]
        assert rec["result"] == "idle" and loop.rounds == 1
        # the next tick is one interval after the round ENDED (1025),
        # not one after the loop started (1060)
        assert loop.next_due == 1025.0 + 60.0
        clock.t += 59.0
        loop.request(timeout=10)      # asked for just before it
        assert loop.rounds == 2 and loop.next_due == 1089.0 + 60.0
    finally:
        loop.stop()
    with pytest.raises(RetentionUnavailable):
        loop.request(timeout=1)


def test_the_timer_ticks_one_interval_after_a_requested_round():
    db = _full(FlowDatabase())
    loop = RetentionLoop(db.monitor(capacity_bytes=db.flows.nbytes * 8),
                         interval=1.0)
    loop.start()
    try:
        time.sleep(0.5)
        assert loop.rounds == 0
        assert loop.request(timeout=10)["result"] == "idle"
        t_end = time.monotonic()
        # the timer's own tick would have come 1.0 s after the start
        time.sleep(max(0.0, t_end + 0.75 - time.monotonic()))
        assert loop.rounds == 1
        while loop.rounds < 2 and time.monotonic() < t_end + 3.0:
            time.sleep(0.02)
        assert loop.rounds == 2 and time.monotonic() >= t_end + 0.95
    finally:
        loop.stop()


def test_a_timers_round_and_a_requested_one_fill_the_same_record():
    """`run_once()` is what both run; the record, the counters and the
    skip count are the monitor's, whoever asked."""
    db = _full(FlowDatabase())
    loop = RetentionLoop(db.monitor(capacity_bytes=db.flows.nbytes),
                         interval=3600)
    before = {r: _counter("theia_retention_rounds_total", result=r)
              for r in ("trimmed", "skipped", "idle")}
    freed0 = _counter("theia_retention_bytes_freed_total")
    views0 = {v: _counter("theia_retention_view_rows_deleted_total",
                          view=v) for v in MATERIALIZED_VIEWS}
    rows, nbytes = len(db.flows), db.flows.nbytes
    assert loop.run_once() > 0        # the timer's routine, by hand
    timed = loop.last_round
    assert set(timed) == RECORD_KEYS and timed["result"] == "trimmed"
    assert timed["rowsBefore"] == rows and timed["usageBefore"] == 1.0
    assert timed["deleteN"] == rows // 2
    assert timed["rowsAfter"] == rows - timed["rowsDeleted"] \
        == len(db.flows)
    assert timed["bytesFreed"] == nbytes - db.flows.nbytes > 0
    assert set(timed["viewRowsDeleted"]) == set(MATERIALIZED_VIEWS)
    assert set(timed["stagesMs"]) == set(RETENTION_STAGES)
    assert sum(timed["stagesMs"].values()) <= timed["seconds"] * 1e3
    assert _counter("theia_retention_bytes_freed_total") - freed0 \
        == timed["bytesFreed"]
    for v, n in timed["viewRowsDeleted"].items():
        assert _counter("theia_retention_view_rows_deleted_total",
                        view=v) - views0[v] == n > 0
    loop.start()
    try:
        # three rounds sit out, counting down, then it trims again
        for left in (2, 1, 0):
            rec = loop.request(timeout=10)
            assert rec["result"] == "skipped" \
                and rec["roundsToSkip"] == left
            assert set(rec) == {"result", "roundsToSkip", "seconds",
                                "stagesMs"}
        asked = loop.request(timeout=10)
        assert set(asked) == RECORD_KEYS and asked["result"] == "trimmed"
        assert asked["rowsBefore"] == timed["rowsAfter"]
    finally:
        loop.stop()
    assert loop.rounds == 5 and loop.stats()["lastRound"] == asked
    assert loop.rows_deleted == timed["rowsDeleted"] \
        + asked["rowsDeleted"]
    after = {r: _counter("theia_retention_rounds_total", result=r)
             for r in before}
    assert {r: after[r] - before[r] for r in before} == {
        "trimmed": 2, "skipped": 3, "idle": 0}
    # the span of either round carries the stages
    spans = [s for s in trace.recent(50) if s["op"] == "bg.retention"]
    assert {"retention.delete_flows", "retention.boundary"} \
        <= set(spans[0]["stagesMs"])


def test_a_failed_round_answers_error_and_backs_off():
    class Boom:
        capacity_bytes = 1

        def tick(self):
            raise RuntimeError("store is down")

        def usage(self):
            raise RuntimeError("store is down")

    loop = RetentionLoop(Boom(), interval=0.5)
    loop.start()
    try:
        rec = loop.request(timeout=10)
    finally:
        loop.stop()
    assert rec["result"] == "error" and "store is down" in rec["error"]
    assert loop.failures == 1 and loop.current_delay > loop.interval


# -- the endpoint -----------------------------------------------------------

@pytest.fixture()
def server(monkeypatch):
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "3600")
    db = _full(FlowDatabase())
    srv = TheiaManagerServer(db, port=0, ingest_shards=2,
                             capacity_bytes=db.flows.nbytes)
    srv.start_background()
    yield srv, db
    srv.shutdown()


def test_post_admin_retention_answers_the_rounds_record(server):
    srv, db = server
    rows = len(db.flows)
    doc = _post(srv.port, "/admin/retention")
    assert set(doc) == RECORD_KEYS and doc["result"] == "trimmed"
    assert doc["rowsBefore"] == rows
    assert doc["rowsAfter"] == len(db.flows) == rows - doc["rowsDeleted"]
    health = _get(srv.port, "/healthz")["retention"]
    assert health["lastRound"] == doc and health["rounds"] == 1
    assert _post(srv.port, "/admin/retention")["result"] == "skipped"
    doc2 = _get(srv.port, "/debug/retention")
    assert doc2["lastRound"]["result"] == "skipped"
    assert set(doc2["views"]) == set(MATERIALIZED_VIEWS)
    octets = int(db.flows.scan()["octetDeltaCount"].sum())
    for name, v in doc2["views"].items():
        assert v == {"octetDeltaCount": octets,
                     "oldestTimeInserted": doc["boundary"]}, name
        # and the same however the parts lie: a read merges them
        assert len(db.views[name]) > 0
    assert _get(srv.port, "/debug/retention")["views"] == doc2["views"]


def _walk_counters():
    batches = "theia_retention_batches_total"
    return {"batchesDropped": _counter(batches, fate="dropped"),
            "batchesCut": _counter(batches, fate="cut"),
            "batchesKept": _counter(batches, fate="kept"),
            "bytesCopied": _counter("theia_retention_bytes_copied_total")}


def test_the_rounds_record_says_what_the_walk_did_and_metrics_count_it(
        monkeypatch):
    """Five blocks of 72 rows lie in the table as they were appended,
    in time order, the third over two seconds (20 rows and 52): the
    180th oldest row is one of its 52, so the round drops two blocks
    whole, cuts the third (52 rows copied) and keeps two; its record
    says so, and the two /metrics families rise by the same numbers."""
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "3600")
    t0 = 1_700_000_000
    db = FlowDatabase()
    for i, times in enumerate([np.full(72, t0), np.full(72, t0 + 1),
                               np.repeat([t0 + 2, t0 + 3], [20, 52]),
                               np.full(72, t0 + 4), np.full(72, t0 + 5)]):
        db.insert_flows(_batch(i, times=times))
    srv = TheiaManagerServer(db, port=0, ingest_shards=2,
                             capacity_bytes=db.flows.nbytes)
    srv.start_background()
    try:
        before = _walk_counters()
        doc = _post(srv.port, "/admin/retention")
        assert set(doc) == RECORD_KEYS and doc["boundary"] == t0 + 3
        assert {k: doc[k] for k in TRIM_WALK} == {
            "batchesDropped": 2, "batchesCut": 1, "batchesKept": 2,
            "bytesCopied": 52 * 284}
        assert doc["rowsDeleted"] == 164 \
            and doc["bytesFreed"] == 164 * 284
        after = _walk_counters()
        assert {k: after[k] - before[k] for k in TRIM_WALK} \
            == {k: doc[k] for k in TRIM_WALK}
        assert _get(srv.port, "/healthz")["retention"]["lastRound"] == doc
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics",
            timeout=30).read().decode()
        for fate in ("dropped", "cut", "kept"):
            assert f'theia_retention_batches_total{{fate="{fate}"}}' in text
        assert "\ntheia_retention_bytes_copied_total " in text
        # a round that sits out walks nothing
        assert not TRIM_WALK & _post(srv.port, "/admin/retention").keys()
        assert _walk_counters() == after
    finally:
        srv.shutdown()


def test_409_with_the_loop_off(monkeypatch):
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "0")
    srv = TheiaManagerServer(FlowDatabase(), port=0, ingest_shards=2)
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/admin/retention")
        assert ei.value.code == 409
        assert "THEIA_RETENTION_INTERVAL" in json.loads(
            ei.value.read())["message"]
        assert "retention" not in _get(srv.port, "/healthz")
    finally:
        srv.shutdown()


def test_the_request_is_token_gated(monkeypatch):
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "3600")
    srv = TheiaManagerServer(FlowDatabase(), port=0, ingest_shards=2,
                             auth_token="sekrit")
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/admin/retention")
        assert ei.value.code == 401
        assert _post(srv.port, "/admin/retention",
                     token="sekrit")["result"] == "idle"
    finally:
        srv.shutdown()


# -- an append's wait for the table's lock -------------------------------------

def test_an_appends_wait_for_the_tables_lock_is_a_stage_of_its_request(
        server):
    """A request whose append stood behind a holder of the flat
    table's lock (a round's delete holds it for its whole copy) says
    so: `store.table_lock_wait` on its `ingest.request` span and on
    theia_ingest_table_lock_wait_seconds, timed on the pool thread."""
    srv, db = server
    hist = metrics.REGISTRY.get("theia_ingest_table_lock_wait_seconds")
    n0, s0 = hist.count(), hist.sum()
    payload = wire.encode_block(_batch(99))
    db.flows._lock.acquire()
    try:
        t = threading.Thread(target=lambda: urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/ingest?stream=w&seq=1",
                method="POST", data=payload), timeout=30).read())
        t.start()
        time.sleep(0.3)
        assert t.is_alive()           # the append waits for the lock
    finally:
        db.flows._lock.release()
    t.join(timeout=30)
    assert hist.count() == n0 + 1 and hist.sum() - s0 >= 0.25
    span = next(s for s in trace.recent(50)
                if s["op"] == "ingest.request")
    assert span["stagesMs"]["store.table_lock_wait"] >= 250.0
    # the parts engine's table is another lock: not timed
    assert FlowDatabase(engine="parts").table_lock_wait() is None


# -- the law, against main.go's round in numpy ---------------------------------

def reference_round(rows, delete_percentage=0.5):
    """main.go:258-320 over decoded rows: `delete_n` rows are to go
    (:300), the boundary is the timeInserted of the delete_n-th oldest
    (`ORDER BY timeInserted LIMIT 1 OFFSET n-1`, :301-318), and `ALTER
    TABLE ... DELETE WHERE timeInserted < boundary` (:284-293) runs on
    flows and on every view. Returns (boundary, kept rows)."""
    delete_n = int(len(rows) * delete_percentage)
    times = np.sort(np.array([r["timeInserted"] for r in rows]))
    boundary = int(times[delete_n - 1])
    return boundary, [r for r in rows if r["timeInserted"] >= boundary]


def reference_view(rows, spec):
    """The view's SELECT ... GROUP BY over `rows`, as a multiset of
    (key..., sum...) tuples."""
    sums = collections.defaultdict(lambda: [0] * len(spec.sum_columns))
    for r in rows:
        acc = sums[tuple(r[k] for k in spec.key_columns)]
        for i, c in enumerate(spec.sum_columns):
            acc[i] += r[c]
    return sorted(k + tuple(v) for k, v in sums.items())


def _tuples(batch, columns):
    return sorted(tuple(r[c] for c in columns) for r in batch.to_rows())


@pytest.mark.parametrize("engine", ["flat", "parts"])
@pytest.mark.parametrize("blocks,span", [(1, 3), (3, 5), (6, 40),
                                         (5, 1)])
def test_flows_and_views_after_a_round_equal_the_reference(
        engine, blocks, span):
    """Interleaved timeInserted with many ties (`span` distinct
    seconds over all blocks; 1 = every row shares one second, so the
    boundary's own second is the whole table and nothing may go)."""
    rng = np.random.default_rng([20261002, blocks, span])
    db = FlowDatabase(engine=engine)
    sent = []
    for i in range(blocks):
        b = _batch(100 + i, n_series=9, points=5,
                   times=1_700_000_000 + rng.integers(0, span, 45))
        sent.extend(b.to_rows())
        db.insert_flows(b)
    if engine == "parts":
        db.flows.seal()
    mon = db.monitor(capacity_bytes=max(db.flows.nbytes, 1),
                     skip_rounds=0)
    deleted = mon.tick()
    boundary, kept = reference_round(sent)
    assert mon.last_round["boundary"] == boundary
    assert deleted == len(sent) - len(kept) \
        == mon.last_round["rowsDeleted"]
    assert mon.last_round["result"] == ("trimmed" if deleted else "idle")
    # both engines count the bytes under the table's lock: the flat
    # one its 284 B a row, the parts engine its encoded parts' fall
    assert (mon.last_round["bytesFreed"] > 0) == (deleted > 0)
    if engine == "flat":
        assert mon.last_round["bytesFreed"] == deleted * 284
    columns = [c.name for c in db.flows.schema]
    assert _tuples(db.flows.scan(), columns) \
        == sorted(tuple(r[c] for c in columns) for r in kept)
    for name, spec in MATERIALIZED_VIEWS.items():
        got = _tuples(db.views[name].scan(),
                      spec.key_columns + spec.sum_columns)
        assert got == reference_view(kept, spec), name
        # nothing older than the boundary survives, its own second does
        assert db.views[name].totals()["oldestTimeInserted"] == boundary
    # a block appended after the round is not the round's to delete
    late = _batch(999, n_series=9, points=5,
                  times=np.full(45, boundary - 1))
    db.insert_flows(late)
    assert len(db.flows) == len(kept) + 45


def test_the_detector_keeps_a_connection_whose_rows_were_trimmed(server):
    """A trim takes rows of the store, no state of the detector."""
    srv, db = server
    payload = wire.encode_block(_batch(7))
    urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/ingest?stream=d&seq=1",
        method="POST", data=payload), timeout=30).read()

    def series():
        return sum(s["series"] for s in
                   _get(srv.port, "/healthz")["ingest"]["perShard"])

    before = series()
    assert before > 0
    assert _post(srv.port, "/admin/retention")["result"] == "trimmed"
    assert series() == before
