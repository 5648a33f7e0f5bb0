"""Observability subsystem: metrics primitives, tracing, Prometheus
exposition, /metrics wiring, and the supervised retention loop.

The registry is process-global (instrumented modules hold their
handles at import), so every assertion here is either a DELTA against
a sample taken at test start or runs after REGISTRY.zero().
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from theia_tpu.cli.__main__ import main as cli_main
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BlockEncoder
from theia_tpu.manager import TheiaManagerServer
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.manager.stats import StatsProvider
from theia_tpu.obs import metrics, prom, trace
from theia_tpu.store import FlowDatabase, RetentionLoop

pytestmark = pytest.mark.obs

TOKEN = "obs-test-token"


@pytest.fixture(autouse=True)
def _clean_obs():
    metrics.enable()
    metrics.REGISTRY.zero()
    trace.reset()
    yield
    metrics.enable()


def _counter_value(name, **labels):
    m = metrics.REGISTRY.get(name)
    if m is None:
        return 0.0
    child = m.labels(**labels) if labels else m._default
    return child.value()


# -- counter striping ----------------------------------------------------

def test_striped_counter_exact_under_concurrency():
    """K threads, each owning its stripe, racing the locked default
    path — the merged total is exact (no lost increments)."""
    c = metrics.counter("test_striped_total", "test")
    k, per = 8, 20000

    def owned(stripe):
        child = c._default
        for _ in range(per):
            child.inc(1, stripe=stripe)

    def unowned():
        for _ in range(per):
            c.inc(1)

    threads = [threading.Thread(target=owned, args=(i,))
               for i in range(k)]
    threads += [threading.Thread(target=unowned) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == (k + 2) * per


def test_counter_labels_and_idempotent_registration():
    c1 = metrics.counter("test_labeled_total", "x", ("kind",))
    c2 = metrics.counter("test_labeled_total", "x", ("kind",))
    assert c1 is c2
    c1.labels(kind="a").inc(3)
    c1.labels(kind="b").inc(4)
    assert c1.labels(kind="a").value() == 3
    with pytest.raises(ValueError):
        metrics.gauge("test_labeled_total", "x", ("kind",))
    with pytest.raises(ValueError):
        metrics.counter("test_labeled_total", "x", ("other",))


def test_metrics_disable_is_a_no_op_switch():
    c = metrics.counter("test_disable_total", "x")
    h = metrics.histogram("test_disable_seconds", "x")
    c.inc(5)
    metrics.disable()
    c.inc(100)
    h.observe(1.0)
    metrics.enable()
    assert c.value() == 5
    assert h.count() == 0


# -- histogram buckets ---------------------------------------------------

def test_bucket_index_boundaries():
    lo = 2.0 ** metrics.EXP_MIN
    top = 2.0 ** (metrics.EXP_MIN + metrics.N_BUCKETS - 1)
    # exact powers of two land IN their own bucket (le semantics)
    assert metrics.bucket_index(lo) == 0
    assert metrics.bucket_index(1.0) == -metrics.EXP_MIN
    assert metrics.bucket_index(top) == metrics.N_BUCKETS - 1
    # epsilon above a bound rolls into the next bucket
    assert metrics.bucket_index(1.0 + 1e-9) == -metrics.EXP_MIN + 1
    # clamps: below range → first bucket, above range → +Inf
    assert metrics.bucket_index(lo / 4) == 0
    assert metrics.bucket_index(0.0) == 0
    assert metrics.bucket_index(top * 1.01) == metrics.N_BUCKETS


def test_histogram_cumulative_counts_sum_count():
    h = metrics.histogram("test_hist_seconds", "x")
    values = [0.25, 0.5, 0.5, 1.0, 100000.0]   # last overflows to +Inf
    for v in values:
        h.observe(v)
    cumulative, total, count = h._default.snapshot()
    bounds = metrics.bucket_bounds()
    assert count == len(values)
    assert total == pytest.approx(sum(values))
    by_bound = dict(zip(bounds, cumulative))
    assert by_bound[0.25] == 1
    assert by_bound[0.5] == 3
    assert by_bound[1.0] == 4
    assert by_bound[bounds[-1]] == 4          # overflow not in finite
    assert cumulative[-1] == 5                # +Inf sees everything
    assert np.all(np.diff(cumulative) >= 0)   # cumulative is monotone


def test_histogram_striped_observe_exact():
    h = metrics.histogram("test_hist_striped_seconds", "x")
    k, per = 4, 5000

    def feed(stripe):
        child = h._default
        for _ in range(per):
            child.observe(0.5, stripe=stripe)

    threads = [threading.Thread(target=feed, args=(i,))
               for i in range(k)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count() == k * per
    assert h.sum() == pytest.approx(0.5 * k * per)


# -- exposition golden ---------------------------------------------------

def test_exposition_golden_render():
    reg = metrics.Registry()
    c = reg.counter("g_requests_total", "Requests served", ("code",))
    c.labels(code="200").inc(3)
    c.labels(code="500").inc(1)
    g = reg.gauge("g_depth", "Queue depth")
    g.set(7)
    text = prom.render(reg)
    lines = text.splitlines()
    assert "# HELP g_requests_total Requests served" in lines
    assert "# TYPE g_requests_total counter" in lines
    assert 'g_requests_total{code="200"} 3' in lines
    assert 'g_requests_total{code="500"} 1' in lines
    assert "# TYPE g_depth gauge" in lines
    assert "g_depth 7" in lines
    # byte-stable: metrics sorted by name, children by label values
    assert text == prom.render(reg)
    assert lines.index("# TYPE g_depth gauge") < lines.index(
        "# TYPE g_requests_total counter")


def test_exposition_round_trip_and_label_escaping():
    reg = metrics.Registry()
    c = reg.counter("g_weird_total", "esc", ("v",))
    c.labels(v='a"b\\c\nd').inc(2)
    h = reg.histogram("g_lat_seconds", "lat")
    h.observe(0.5)
    h.observe(3.0)
    parsed = prom.parse(prom.render(reg))
    assert parsed[("g_weird_total", (("v", 'a"b\\c\nd'),))] == 2
    assert parsed[("g_lat_seconds_count", ())] == 2
    assert parsed[("g_lat_seconds_sum", ())] == pytest.approx(3.5)
    assert parsed[("g_lat_seconds_bucket", (("le", "0.5"),))] == 1
    assert parsed[("g_lat_seconds_bucket", (("le", "+Inf"),))] == 2


def test_all_registered_counters_end_in_total():
    # load every instrumented module so its handles are registered
    import theia_tpu.manager.jobs      # noqa: F401
    import theia_tpu.manager.reconciler  # noqa: F401
    import theia_tpu.store.replicated  # noqa: F401
    import theia_tpu.utils.faults      # noqa: F401
    for m in metrics.REGISTRY.collect():
        if m.kind == "counter" and m.name.startswith("theia_"):
            assert m.name.endswith("_total"), m.name


# -- tracing -------------------------------------------------------------

def test_trace_ring_is_bounded():
    for i in range(trace._ring.maxlen + 50):
        with trace.span(f"op{i % 7}", i=i):
            pass
    spans = trace.recent(limit=10 ** 6)
    assert len(spans) == trace._ring.maxlen
    # newest first
    assert spans[0]["i"] > spans[-1]["i"]


def test_trace_slowest_exemplar_selection():
    for pause, tag in ((0.002, None), (0.05, "worst"), (0.01, None)):
        with trace.span("slowop") as sp:
            if tag:
                sp.attrs["tag"] = tag
            time.sleep(pause)
    with trace.span("fastop"):
        pass
    slowest = trace.slowest()
    assert slowest["slowop"]["durationMs"] >= 50.0
    assert slowest["slowop"]["tag"] == "worst"
    assert "fastop" in slowest


def test_span_nesting_records_parent():
    with trace.span("outer"):
        assert trace.current_op() == "outer"
        with trace.span("inner"):
            pass
    spans = trace.recent(2)
    assert [s["op"] for s in spans] == ["outer", "inner"]
    assert spans[1]["parent"] == "outer"
    assert spans[0]["parent"] is None


def test_span_error_tagging():
    with pytest.raises(RuntimeError):
        with trace.span("boomop"):
            raise RuntimeError("x")
    assert trace.recent(1)[0]["error"] == "RuntimeError"


# -- ingest instrumentation ----------------------------------------------

def _distinct_population(sid, n_series=16, seed=7):
    """Per-producer flow population in its own address blocks, so
    concurrent streams hit different detector keys (and shards)."""
    from theia_tpu.schema import ColumnarBatch, StringDictionary
    batch = generate_flows(SynthConfig(
        n_series=n_series, points_per_series=10, seed=seed))
    if sid == 0:
        return batch
    dicts = dict(batch.dicts)
    for col in ("sourceIP", "destinationIP"):
        nd = StringDictionary()
        for s in batch.dicts[col].entries_since(0):
            if s:
                s = s.replace("10.0.", f"10.{sid}.", 1).replace(
                    "203.0.", f"203.{sid}.", 1)
            nd.encode_one(s)
        dicts[col] = nd
    return ColumnarBatch(dict(batch.columns), dicts)


def test_counter_totals_deterministic_under_sharded_ingest():
    """K concurrent producer streams through a 4-shard IngestManager:
    the striped scored-rows counter and the acked-rows counter both
    land on exactly the number of rows sent."""
    rows0 = _counter_value("theia_ingest_rows_total")
    scored0 = _counter_value("theia_ingest_scored_rows_total")
    batches0 = _counter_value("theia_ingest_batches_total")
    im = IngestManager(FlowDatabase(), n_shards=4)
    k, per_stream = 4, 5
    pops = [_distinct_population(i) for i in range(k)]
    encs = [BlockEncoder(dicts=pops[i].dicts) for i in range(k)]
    payloads = [[encs[i].encode(pops[i]) for _ in range(per_stream)]
                for i in range(k)]

    def feed(i):
        for p in payloads[i]:
            im.ingest(p, stream=f"s{i}")

    threads = [threading.Thread(target=feed, args=(i,))
               for i in range(k)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total_rows = sum(len(pops[i]) * per_stream for i in range(k))
    assert _counter_value("theia_ingest_rows_total") - rows0 \
        == total_rows
    assert _counter_value("theia_ingest_scored_rows_total") - scored0 \
        == total_rows
    assert _counter_value("theia_ingest_batches_total") - batches0 \
        == k * per_stream
    im.close()


def test_ingest_stage_histograms_move():
    im = IngestManager(FlowDatabase(), n_shards=2)
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    enc = BlockEncoder(dicts=batch.dicts)
    h = metrics.REGISTRY.get("theia_ingest_stage_seconds")
    before = {s: h.labels(stage=s).count()
              for s in ("decode", "store_insert", "detector")}
    im.ingest(enc.encode(batch))
    for s, prev in before.items():
        assert h.labels(stage=s).count() == prev + 1, s
    im.close()


# -- /metrics endpoint ---------------------------------------------------

def _get(port, path, token=None):
    headers = {}
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.read().decode(), r.headers

def _code_of(fn):
    try:
        return fn()[0]
    except urllib.error.HTTPError as e:
        return e.code


@pytest.fixture()
def open_server():
    db = FlowDatabase()
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    yield srv
    srv.shutdown()


def test_metrics_endpoint_serves_exposition(open_server):
    srv = open_server
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    enc = BlockEncoder(dicts=batch.dicts)
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/ingest",
        data=enc.encode(batch), method="POST")
    urllib.request.urlopen(req, timeout=10).read()
    status, text, headers = _get(srv.port, "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    parsed = prom.parse(text)        # must be valid exposition
    flat = {name for name, _ in parsed}
    # every instrumented layer shows up under stable names
    for required in (
            "theia_ingest_rows_total",
            "theia_ingest_stage_seconds_bucket",
            "theia_ingest_request_seconds_count",
            "theia_store_inserted_rows_total",
            "theia_store_inserted_bytes_total",
            "theia_store_mv_fanout_seconds_count",
            "theia_replica_quarantines_total",
            "theia_job_retries_total",
            "theia_job_deadline_kills_total",
            "theia_job_queue_wait_seconds_count",
            "theia_retention_rows_deleted_total",
            "theia_store_flow_rows",
    ):
        assert required in flat, required
    assert parsed[("theia_ingest_rows_total", ())] == len(batch)


def test_metrics_and_traces_auth_gating():
    srv = TheiaManagerServer(FlowDatabase(), port=0, auth_token=TOKEN)
    srv.start_background()
    try:
        for path in ("/metrics", "/debug/traces"):
            assert _code_of(lambda: _get(srv.port, path)) == 401
            assert _code_of(lambda: _get(srv.port, path,
                                         token="wrong")) == 403
            assert _code_of(lambda: _get(srv.port, path,
                                         token=TOKEN)) == 200
    finally:
        srv.shutdown()


def test_metrics_open_when_auth_off(open_server):
    assert _code_of(lambda: _get(open_server.port, "/metrics")) == 200
    assert _code_of(
        lambda: _get(open_server.port, "/debug/traces")) == 200


def test_debug_traces_payload(open_server):
    with trace.span("testop"):
        with trace.stage("testop.part"):
            time.sleep(0.025)
    status, text, _ = _get(open_server.port, "/debug/traces")
    doc = json.loads(text)
    assert "recent" in doc and "slowest" in doc
    slow = doc["slowest"]["testop"]
    assert slow["durationMs"] >= 25.0
    # the exemplar carries its stage breakdown
    assert slow["stagesMs"]["testop.part"] >= 25.0


# -- retention loop ------------------------------------------------------

def test_retention_loop_trim_observable_via_metrics():
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=32, points_per_series=10)))
    loop = RetentionLoop(db.monitor(capacity_bytes=1), interval=0.01)
    deleted = loop.run_once()
    assert deleted > 0
    assert loop.rounds == 1 and loop.rows_deleted == deleted
    assert _counter_value("theia_retention_rows_deleted_total") \
        >= deleted
    assert _counter_value("theia_retention_rounds_total",
                          result="trimmed") >= 1
    assert _counter_value("theia_store_deleted_rows_total",
                          reason="retention") >= deleted
    stats = loop.stats()
    assert stats["rowsDeleted"] == deleted


def test_retention_loop_backs_off_on_failure():
    class BoomMonitor:
        capacity_bytes = 1

        def tick(self):
            raise RuntimeError("store is down")

        def usage(self):
            raise RuntimeError("store is down")

    loop = RetentionLoop(BoomMonitor(), interval=0.5)
    assert loop.run_once() == 0
    assert loop.failures == 1
    first_delay = loop.current_delay
    assert first_delay > loop.interval
    loop.run_once()
    assert loop.current_delay > first_delay     # exponential
    assert _counter_value("theia_retention_rounds_total",
                          result="error") >= 2
    stats = loop.stats()
    assert stats["failures"] == 2


def test_server_wires_retention_loop(monkeypatch):
    monkeypatch.setenv("THEIA_STORE_CAPACITY_BYTES", "1")
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "0.02")
    db = FlowDatabase()
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    try:
        db.insert_flows(generate_flows(SynthConfig(
            n_series=32, points_per_series=10)))
        deadline = time.time() + 10
        doc = {}
        while time.time() < deadline:
            _, text, _ = _get(srv.port, "/healthz")
            doc = json.loads(text)
            if doc.get("retention", {}).get("rowsDeleted", 0) > 0:
                break
            time.sleep(0.02)
        assert doc["retention"]["rowsDeleted"] > 0
        assert doc["retention"]["rounds"] >= 1
        _, text, _ = _get(srv.port, "/metrics")
        parsed = prom.parse(text)
        assert parsed[("theia_retention_rows_deleted_total", ())] > 0
    finally:
        srv.shutdown()


def test_server_retention_disabled_by_env(monkeypatch):
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "0")
    srv = TheiaManagerServer(FlowDatabase(), port=0)
    srv.start_background()
    try:
        assert srv.retention is None
        _, text, _ = _get(srv.port, "/healthz")
        assert "retention" not in json.loads(text)
    finally:
        srv.shutdown()


# -- satellites ----------------------------------------------------------

def test_insert_rates_survive_retention_trim():
    """The under-reporting fix: a delete between samples must not mask
    real insert throughput (net-size sampling reported ~0 here)."""
    db = FlowDatabase()
    stats = StatsProvider(db)
    db.insert_flows(generate_flows(SynthConfig(
        n_series=16, points_per_series=10, seed=1)))
    stats.insert_rates()                       # establish a sample
    # trim EVERYTHING, then insert a fresh batch
    db.delete_flows_older_than(2 ** 60)
    assert len(db.flows) == 0
    fresh = generate_flows(SynthConfig(
        n_series=16, points_per_series=10, seed=2))
    db.insert_flows(fresh)
    rate = stats.insert_rates()[0]
    assert int(rate["rowsPerSec"]) > 0
    assert int(rate["bytesPerSec"]) > 0


def test_cumulative_insert_totals_monotone():
    db = FlowDatabase()
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    db.insert_flows(batch)
    rows1, bytes1 = db.rows_inserted_total, db.bytes_inserted_total
    assert rows1 == len(batch) and bytes1 > 0
    db.delete_flows_older_than(2 ** 60)
    assert db.rows_inserted_total == rows1     # deletes don't decrease
    db.insert_flows(generate_flows(SynthConfig(
        n_series=8, points_per_series=10, seed=3)))
    assert db.rows_inserted_total > rows1


def test_sharded_store_cumulative_totals():
    from theia_tpu.store import ShardedFlowDatabase
    db = ShardedFlowDatabase(n_shards=2)
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    db.insert_flows(batch)
    assert db.rows_inserted_total == len(batch)
    assert db.bytes_inserted_total > 0


def test_pool_size_mismatch_warns_once():
    from theia_tpu.utils import dump_logs
    from theia_tpu.utils.pool import get_pool
    name = f"obs-test-pool-{time.time_ns()}"
    p1 = get_pool(name, 2)
    p2 = get_pool(name, 4)
    assert p1 is p2
    logs = dump_logs()
    assert f"pool '{name}' already created with max_workers=2" in logs
    assert "ignoring requested max_workers=4" in logs


def test_theia_top_renders_rates_table(open_server, capsys):
    srv = open_server
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    enc = BlockEncoder(dicts=batch.dicts)
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/ingest",
        data=enc.encode(batch), method="POST")
    urllib.request.urlopen(req, timeout=10).read()
    cli_main(["--manager-addr", f"http://127.0.0.1:{srv.port}",
              "top", "-n", "2", "-i", "0.05", "--no-clear"])
    out = capsys.readouterr().out
    assert "theia top —" in out
    assert "theia_ingest_rows_total" in out
    assert "RATE/s" in out
    # second render carries rates (first has no previous sample)
    assert out.count("METRIC") == 2


def test_stripe_out_of_range_falls_back_to_locked_slot():
    """A stripe index >= N_STRIPES must NOT alias onto another owner's
    lock-free slot — it takes the locked path, and totals stay exact
    even with more shards than stripes."""
    c = metrics.counter("test_overflow_total", "x")
    k, per = 6, 10000
    big_stripes = [metrics.N_STRIPES + i for i in range(k)]

    def feed(stripe):
        child = c._default
        for _ in range(per):
            child.inc(1, stripe=stripe)

    threads = [threading.Thread(target=feed, args=(s,))
               for s in big_stripes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == k * per
    h = metrics.histogram("test_overflow_seconds", "x")
    h.observe(0.5, stripe=metrics.N_STRIPES + 3)
    h.observe(0.5, stripe=-1)
    assert h.count() == 2


def test_detector_leg_error_counted():
    from theia_tpu.utils import faults
    im = IngestManager(FlowDatabase(), n_shards=2)
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    enc = BlockEncoder(dicts=batch.dicts)
    payload = enc.encode(batch)
    before = _counter_value("theia_ingest_errors_total",
                            stage="detector")
    orig = im.score_batch
    def boom(b):
        raise RuntimeError("detector down")
    im.score_batch = boom
    with pytest.raises(RuntimeError):
        im.ingest(payload)
    assert _counter_value("theia_ingest_errors_total",
                          stage="detector") == before + 1
    im.score_batch = orig
    im.close()


def test_replicated_insert_totals_monotone_across_resync():
    """Logical counters count each fan-out write ONCE and do not jump
    when a repaired replica resyncs (truncate + full re-insert used to
    inflate the active-replica proxy on failover)."""
    from theia_tpu.store import ReplicatedFlowDatabase
    db = ReplicatedFlowDatabase(replicas=2)
    batch = generate_flows(SynthConfig(n_series=8,
                                       points_per_series=10))
    db.insert_flows(batch)
    assert db.rows_inserted_total == len(batch)
    bytes1 = db.bytes_inserted_total
    assert bytes1 > 0
    # quarantine replica 0, write on the survivor, then repair
    # (resync re-inserts the whole table into replica 0)
    db.set_replica_down(0)
    db.insert_flows(batch)
    assert db.rows_inserted_total == 2 * len(batch)
    db.set_replica_up(0, resync=True)
    assert db.rows_inserted_total == 2 * len(batch)   # no resync jump
    assert db.bytes_inserted_total == 2 * bytes1


def test_metrics_scrapeable_with_all_replicas_down():
    from theia_tpu.store import ReplicatedFlowDatabase
    db = ReplicatedFlowDatabase(replicas=1)
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    try:
        db.set_replica_down(0)
        status, text, _ = _get(srv.port, "/metrics")
        assert status == 200
        parsed = prom.parse(text)
        assert ("theia_job_retries_total", ()) in parsed
    finally:
        db.set_replica_up(0, resync=False)
        srv.shutdown()


def test_trace_ring_zero_disables_exemplars_too(monkeypatch):
    import collections
    monkeypatch.setattr(trace, "_ring",
                        collections.deque(maxlen=0))
    with trace.span("zombieop"):
        pass
    assert trace.recent(10) == []
    assert "zombieop" not in trace.slowest()


def test_fault_firings_counted():
    from theia_tpu.utils import faults
    before = _counter_value("theia_fault_firings_total",
                            site="store.insert", mode="error")
    faults.arm("store.insert:error")
    try:
        db = FlowDatabase()
        with pytest.raises(faults.FaultError):
            db.insert_flows(generate_flows(SynthConfig(
                n_series=4, points_per_series=5)))
    finally:
        faults.disarm()
    assert _counter_value("theia_fault_firings_total",
                          site="store.insert",
                          mode="error") == before + 1
