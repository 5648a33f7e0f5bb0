"""Overload control: admission, backpressure, brownout, exactly-once.

Deterministic throughout — injectable clocks drive every token-bucket
refill and ladder transition; no test sleeps on wall time. The
server-level tests force rungs via THEIA_ADMISSION_FORCE_LEVEL / the
admission.pressure fault site rather than generating real load, so
they hold on a loaded 1-core CI host."""

import json
import os
import urllib.error
import urllib.request

import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BlockEncoder
from theia_tpu.manager.admission import (
    HYSTERESIS_MARGIN,
    LEVEL_NAMES,
    LEVEL_OK,
    LEVEL_REJECT,
    LEVEL_SAMPLED,
    LEVEL_SHED,
    LEVEL_THRESHOLDS,
    AdmissionController,
    AdmissionRejected,
    DedupWindow,
    TokenBucket,
)
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.store import FlowDatabase

pytestmark = pytest.mark.overload


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _producer(n_series=4, points=10, seed=1):
    """(encoder, batch): encode per send — TFB2 blocks carry
    dictionary DELTAS, so each block must come from the live encoder
    chain (a re-sent identical byte string is only legal as a dedup
    retry, which never decodes)."""
    enc = BlockEncoder()
    batch = generate_flows(
        SynthConfig(n_series=n_series, points_per_series=points,
                    anomaly_fraction=0.0, seed=seed), dicts=enc.dicts)
    return enc, batch


def _block(n_series=4, points=10, seed=1):
    enc, batch = _producer(n_series, points, seed)
    return enc.encode(batch), len(batch)


# -- token bucket ---------------------------------------------------------

def test_token_bucket_deterministic_refill():
    clk = FakeClock()
    b = TokenBucket(rate=100.0, burst=50.0, clock=clk)
    assert b.try_charge(50) == 0.0          # full burst admits
    wait = b.try_charge(10)                 # empty: 10 tokens / 100/s
    assert wait == pytest.approx(0.1)
    clk.advance(0.05)
    assert b.tokens() == pytest.approx(5.0)
    clk.advance(0.05)
    assert b.try_charge(10) == 0.0          # exactly refilled
    assert b.tokens() == pytest.approx(0.0)
    clk.advance(10.0)
    assert b.tokens() == pytest.approx(50.0)   # capped at burst


def test_token_bucket_debt_and_oversize():
    clk = FakeClock()
    b = TokenBucket(rate=100.0, burst=50.0, clock=clk)
    # a batch larger than the whole burst is admitted from a full
    # bucket, into debt — otherwise it could never land at all
    assert b.try_charge(120) == 0.0
    assert b.tokens() == pytest.approx(-70.0)
    # debt rejects until the refill clears it
    assert b.wait_for_positive() == pytest.approx(0.71)
    clk.advance(0.71)
    assert b.wait_for_positive() == 0.0


# -- brownout ladder ------------------------------------------------------

def _controller(clk, hold=1.0):
    adm = AdmissionController(rate=0, byte_rate=0, hold_seconds=hold,
                              clock=clk)
    adm._test_pressure = 0.0
    adm.add_signal("test", lambda: adm._test_pressure, high=1.0)
    return adm


def test_brownout_ladder_up_and_down():
    clk = FakeClock()
    adm = _controller(clk)
    assert adm.evaluate() == LEVEL_OK
    # escalation is immediate, rung by pressure band
    adm._test_pressure = LEVEL_THRESHOLDS[LEVEL_SAMPLED]
    assert adm.evaluate() == LEVEL_SAMPLED
    adm._test_pressure = LEVEL_THRESHOLDS[LEVEL_REJECT]
    assert adm.evaluate() == LEVEL_REJECT
    with pytest.raises(AdmissionRejected) as ei:
        adm.admit("s", 100)
    assert ei.value.reason == "pressure"
    assert ei.value.retry_after > 0
    # de-escalation: pressure must stay below the entry threshold
    # minus the hysteresis margin for hold_seconds CONTINUOUSLY, then
    # steps down ONE rung at a time
    adm._test_pressure = (LEVEL_THRESHOLDS[LEVEL_REJECT]
                          - HYSTERESIS_MARGIN / 2)
    clk.advance(10.0)
    assert adm.evaluate() == LEVEL_REJECT   # inside the margin: stays
    adm._test_pressure = 0.0
    assert adm.evaluate() == LEVEL_REJECT   # dip seen, hold starts
    clk.advance(1.01)
    assert adm.evaluate() == LEVEL_SHED     # sustained: one rung
    assert adm.evaluate() == LEVEL_SHED     # next hold restarts
    clk.advance(1.01)
    assert adm.evaluate() == LEVEL_SAMPLED
    clk.advance(1.01)
    assert adm.evaluate() == LEVEL_OK


def test_brownout_flapping_signal_does_not_deescalate():
    """One momentary dip must not step the ladder down: the hold
    clock measures time BELOW the threshold, not time at the rung."""
    clk = FakeClock()
    adm = _controller(clk)
    adm._test_pressure = 1.2
    assert adm.evaluate() == LEVEL_REJECT
    clk.advance(10.0)                        # long time AT the rung
    adm._test_pressure = 0.0
    assert adm.evaluate() == LEVEL_REJECT    # dip starts, hold not met
    clk.advance(0.5)
    adm._test_pressure = 0.95                # flap back above margin
    assert adm.evaluate() == LEVEL_REJECT    # dip clock reset
    adm._test_pressure = 0.0
    assert adm.evaluate() == LEVEL_REJECT
    clk.advance(0.6)                         # only 0.6s of the NEW dip
    assert adm.evaluate() == LEVEL_REJECT
    clk.advance(0.5)                         # 1.1s sustained below
    assert adm.evaluate() == LEVEL_SHED


def test_brownout_sampling_fraction_declines():
    clk = FakeClock()
    adm = _controller(clk)
    lo = LEVEL_THRESHOLDS[LEVEL_SAMPLED]
    hi = LEVEL_THRESHOLDS[LEVEL_SHED]
    adm._test_pressure = lo
    adm.evaluate()
    assert sum(adm.should_score(LEVEL_SAMPLED)
               for _ in range(100)) == 100   # band entry: score all
    adm._test_pressure = (lo + hi) / 2
    adm.evaluate()
    kept = sum(adm.should_score(LEVEL_SAMPLED) for _ in range(100))
    assert kept == 50                        # mid-band: half, exactly
    assert not adm.should_score(LEVEL_SHED)
    assert adm.should_score(LEVEL_OK)


def test_forced_level_env(monkeypatch):
    clk = FakeClock()
    adm = _controller(clk)
    monkeypatch.setenv("THEIA_ADMISSION_FORCE_LEVEL", "shed_detector")
    assert adm.evaluate() == LEVEL_SHED
    monkeypatch.setenv("THEIA_ADMISSION_FORCE_LEVEL", "3")
    assert adm.evaluate() == LEVEL_REJECT
    monkeypatch.delenv("THEIA_ADMISSION_FORCE_LEVEL")
    assert adm.evaluate() == LEVEL_REJECT   # hysteresis holds the rung
    clk.advance(1.01)
    assert adm.evaluate() == LEVEL_SHED


def test_admission_fault_site_forces_reject():
    from theia_tpu.utils import faults
    clk = FakeClock()
    adm = _controller(clk)
    faults.arm("admission.pressure:error")
    try:
        with pytest.raises(AdmissionRejected) as ei:
            adm.admit("s", 10)
        assert ei.value.reason == "fault"
    finally:
        faults.disarm()
    assert adm.admit("s", 10) == LEVEL_OK   # disarmed: clean again


# -- rate limiting + fair share -------------------------------------------

def test_row_bucket_rejects_with_retry_after():
    clk = FakeClock()
    adm = AdmissionController(rate=1000.0, burst=1000.0,
                              clock=clk)
    assert adm.admit("s", 10) == LEVEL_OK
    adm.charge_rows("s", 1500)              # post-decode: into debt
    with pytest.raises(AdmissionRejected) as ei:
        adm.admit("s", 10)
    assert ei.value.reason == "rows"
    assert ei.value.retry_after == pytest.approx(0.501)
    clk.advance(0.501)
    assert adm.admit("s", 10) == LEVEL_OK


def test_fair_share_protects_polite_streams():
    """One hot producer offering ~4x the whole budget cannot starve a
    polite stream: the hog absorbs every rejection (fair-share or
    debt), the under-share stream is admitted on every attempt."""
    clk = FakeClock()
    adm = AdmissionController(rate=1000.0, burst=1000.0, clock=clk)
    hog_rejects = 0
    hog_reasons = set()
    for _ in range(200):                    # 10 s of steady state
        clk.advance(0.05)
        try:
            adm.admit("hog", 10)
            adm.charge_rows("hog", 200)     # offers ~4000 rows/s
        except AdmissionRejected as e:
            hog_rejects += 1
            hog_reasons.add(e.reason)
        # the polite streams (~100 rows/s each, well under the fair
        # share of 333) must land on EVERY attempt — no exception
        # tolerated, whatever debt the hog has run up
        for cold in ("cold-a", "cold-b"):
            adm.admit(cold, 10)
            adm.charge_rows(cold, 5)
    assert hog_rejects > 100                # hog throttled hard
    # the hog saw the SPECIFIC over-share rejection, not only the
    # generic everyone-slow-down debt one
    assert "fair_share" in hog_reasons
    # aggregate stayed near the configured rate: hog admits bounded by
    # the budget the cold streams left behind
    hog_admitted = (200 - hog_rejects) * 200
    assert hog_admitted <= 1.2 * (1000 - 200) * 10


# -- dedup window ---------------------------------------------------------

def test_dedup_window_hit_miss_eviction():
    w = DedupWindow(window=3)
    assert w.lookup("a", 1) is None          # miss
    w.record("a", 1, 100)
    w.record("a", 2, 200)
    assert w.lookup("a", 1) == 100           # hit
    assert w.lookup("a", 2) == 200
    w.record("a", 3, 300)
    w.record("a", 4, 400)                    # evicts seq 1
    assert w.lookup("a", 1) is None          # beyond the window
    assert w.lookup("a", 2) == 200
    assert w.lookup("b", 2) is None          # streams are independent
    st = w.stats()
    assert st["entries"] == 3 and st["streams"] == 1
    assert st["hits"] == 3 and st["misses"] == 3


def test_dedup_window_bounds_streams():
    w = DedupWindow(window=8, max_streams=4)
    for i in range(6):
        w.record(f"s{i}", 1, 1)
    assert w.stats()["streams"] == 4         # LRU streams evicted
    assert w.lookup("s0", 1) is None
    assert w.lookup("s5", 1) == 1


def test_dedup_window_stream_cardinality_bounded():
    """ROADMAP item-5 pre-work regression: ~100k DISTINCT stream ids
    (a router mesh's per-origin sub-streams, a producer fleet minting
    ids) must hold the stream LRU at its cap, keep the GLOBAL entry
    budget, and keep the running entry count exact — all O(1) per op
    (this loop is ~100k records; an O(streams) stats() or eviction
    would blow the test budget immediately)."""
    w = DedupWindow(window=4, max_streams=1000, max_entries=2500)
    n = 100_000
    for i in range(n):
        w.record(f"s{i}", 1, i)
        if i % 10_000 == 0:
            st = w.stats()   # O(1): running counters, no walk
            assert st["streams"] <= 1000
            assert st["entries"] <= 2500
    st = w.stats()
    assert st["streams"] <= 1000
    assert st["entries"] == sum(
        len(win) for win in w._streams.values())   # exact accounting
    assert st["evictedStreams"] == n - st["streams"]
    # the newest streams are still answerable; ancient ones aged out
    assert w.lookup(f"s{n - 1}", 1) == n - 1
    assert w.lookup("s0", 1) is None
    # the global ENTRY budget evicts whole cold streams even when the
    # stream cap alone would admit them
    w2 = DedupWindow(window=1000, max_streams=1000, max_entries=100)
    for i in range(50):
        for seq in range(10):
            w2.record(f"t{i}", seq, 1)
    st2 = w2.stats()
    assert st2["entries"] <= 100
    assert w2.lookup("t49", 9) == 1


def test_dedup_lookup_refreshes_stream_lru():
    """A producer replaying already-acked seqs (lookups only) is
    active — it must not age out of the stream LRU mid-replay while
    other streams mint entries."""
    w = DedupWindow(window=8, max_streams=2)
    w.record("replayer", 1, 10)
    w.record("other", 1, 10)
    assert w.lookup("replayer", 1) == 10     # refreshes LRU position
    w.record("newcomer", 1, 10)              # evicts "other", not us
    assert w.lookup("replayer", 1) == 10
    assert w.lookup("other", 1) is None


# -- ingest-path integration ----------------------------------------------

def test_ingest_duplicate_retry_is_idempotent():
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        enc, batch = _producer()
        n = len(batch)
        payload1 = enc.encode(batch)
        out = im.ingest(payload1, stream="p", seq=1)
        assert out["rows"] == n and "duplicate" not in out
        before = len(db.flows)
        dup = im.ingest(payload1, stream="p", seq=1)  # byte-identical
        assert {k: dup[k] for k in ("rows", "alerts", "duplicate")} \
            == {"rows": n, "alerts": 0, "duplicate": True}
        assert len(db.flows) == before                # nothing moved
        # the producer's NEXT block (new seq) is new work — rows
        # insert again, and the dedup retry above did not desync the
        # stream's dictionary-delta chain (duplicates never decode)
        out2 = im.ingest(enc.encode(batch), stream="p", seq=2)
        assert out2["rows"] == n
        assert len(db.flows) == before + n
    finally:
        im.close()


def test_inflight_retry_rejected_not_double_inserted():
    """A retry racing its still-processing original (client timeout
    shorter than a stalled insert) must not decode+insert a second
    copy: it gets 429 (come back for the duplicate ack), and the
    stream's dictionary-delta chain stays intact."""
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        enc, batch = _producer(seed=13)
        n = len(batch)
        payload = enc.encode(batch)
        im._pending.add(("p", 1))           # the original, in flight
        with pytest.raises(AdmissionRejected) as ei:
            im.ingest(payload, stream="p", seq=1)
        assert ei.value.reason == "in_flight"
        assert len(db.flows) == 0           # nothing decoded/inserted
        im._pending.discard(("p", 1))       # original "completes"
        assert im.ingest(payload, stream="p", seq=1)["rows"] == n
        assert len(db.flows) == n
    finally:
        im.close()


def test_dedup_tag_survives_separator_in_stream_id(tmp_path):
    """Stream ids are producer-controlled and may contain the tag
    separator; the pack/split round trip (and crash recovery) must
    not lose the ack for such a stream."""
    from theia_tpu.store.wal import pack_dedup_tag, split_dedup_tag
    hostile = "a\x1fb\x1fc"
    table, tag = split_dedup_tag(
        pack_dedup_tag("flows", hostile, 7, 500))
    assert table == "flows" and tag == (hostile, 7, 500)
    assert split_dedup_tag("flows") == ("flows", None)
    # end to end through WAL recovery
    wal_dir = str(tmp_path / "wal")
    db = FlowDatabase()
    db.attach_wal(wal_dir, sync="always")
    im = IngestManager(db, n_shards=1)
    payload, n = _block(seed=17)
    assert im.ingest(payload, stream=hostile, seq=1)["rows"] == n
    im.close()
    db2 = FlowDatabase()
    db2.attach_wal(wal_dir, sync="always")
    assert (hostile, 1, n, n) in db2.recovered_acks()
    db2.close_wal()


def test_retry_racing_completing_original_gets_duplicate(monkeypatch):
    """TOCTOU window: the retry's lock-free dedup lookup misses, the
    original then records its ack and drops its reservation, and the
    retry proceeds into the pending check. The re-check under the
    pending lock must catch the freshly-recorded ack instead of
    double-inserting."""
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        enc, batch = _producer(seed=23)
        n = len(batch)
        payload = enc.encode(batch)
        im.dedup.record("p", 1, n)           # the original's ack
        calls = []
        real_lookup = im.dedup.lookup

        def racy_lookup(stream, seq):
            calls.append(1)
            if len(calls) == 1:
                return None                  # lock-free miss: the
            return real_lookup(stream, seq)  # original recorded since
        monkeypatch.setattr(im.dedup, "lookup", racy_lookup)
        out = im.ingest(payload, stream="p", seq=1)
        assert {k: out[k] for k in ("rows", "alerts", "duplicate")} \
            == {"rows": n, "alerts": 0, "duplicate": True}
        assert len(calls) == 2               # the in-lock re-check ran
        assert len(db.flows) == 0            # nothing double-inserted
    finally:
        im.close()


def test_fresh_stream_ids_cannot_unbound_the_debt():
    """The under-fair-share debt bypass is floored at one extra burst:
    a fleet minting a fresh stream id per batch (no rate history, so
    trivially 'under share') cannot push the row bucket arbitrarily
    deep and defeat THEIA_INGEST_RATE."""
    clk = FakeClock()
    adm = AdmissionController(rate=1000.0, burst=1000.0, clock=clk)
    admitted = 0
    for i in range(50):                      # 50 distinct streams
        try:
            adm.admit(f"fresh-{i}", 10)
            adm.charge_rows(f"fresh-{i}", 600)
            admitted += 1
        except AdmissionRejected as e:
            assert e.reason == "rows"
    # burst (1000) + one extra burst of debt (1000) / 600-row batches
    assert admitted <= 4
    assert adm.rows.tokens() > -2 * adm.rows.burst


def test_detector_failure_still_records_ack(monkeypatch):
    """If the insert leg succeeded but scoring raised (request 500s),
    the ack is recorded anyway — the rows are durable, so the
    producer's retry must be answered duplicate:true, not
    double-inserted (mirrors what a crash+WAL-replay of the same
    record would do)."""
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        enc, batch = _producer(seed=19)
        n = len(batch)
        payload = enc.encode(batch)

        def boom(b):
            raise RuntimeError("detector down")
        monkeypatch.setattr(im, "score_batch", boom)
        with pytest.raises(RuntimeError):
            im.ingest(payload, stream="p", seq=1)
        assert len(db.flows) == n           # insert leg landed
        out = im.ingest(payload, stream="p", seq=1)   # the retry
        assert out["duplicate"] is True and out["rows"] == n
        assert len(db.flows) == n           # not double-inserted
    finally:
        im.close()


def test_partial_recovered_ack_still_seeds():
    """A sharded batch whose slices were only partially durable at the
    crash (interval sync) seeds the dedup window with the recovered
    count — NOT seeding would make the retry duplicate every
    recovered row; the shortfall is logged and bounded by the WAL
    sync policy."""
    class FakeDb:
        def recovered_acks(self):
            return [("s", 1, 60, 100)]      # 60 of 100 rows durable
    im = IngestManager(FakeDb(), n_shards=1)
    try:
        assert im.dedup.lookup("s", 1) == 60
    finally:
        im.close()


def test_ingest_shed_rung_stores_but_does_not_score(monkeypatch):
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        enc = BlockEncoder()
        spike = generate_flows(SynthConfig(
            n_series=6, points_per_series=30, anomaly_fraction=1.0,
            anomaly_magnitude=80.0, seed=21), dicts=enc.dicts)
        monkeypatch.setenv("THEIA_ADMISSION_FORCE_LEVEL",
                           "shed_detector")
        out = im.ingest(enc.encode(spike), stream="p")
        # durability-first: rows acked into the store, scoring shed
        assert out["rows"] == len(spike)
        assert out["alerts"] == 0
        assert out["degraded"] == "shed_detector"
        assert len(db.flows) == len(spike)
        assert im.shards[0].streaming.n_series == 0
        monkeypatch.delenv("THEIA_ADMISSION_FORCE_LEVEL")
    finally:
        im.close()


def test_inflight_backlog_feeds_pressure():
    db = FlowDatabase()
    im = IngestManager(db, n_shards=1)
    try:
        assert im.inflight_high == 2 * im._insert_workers
        ratios = im.admission.signal_ratios()
        assert ratios["insertBacklog"] == 0.0
        # a stalled store shows up as backlog ratio -> reject rung
        im.admission._signals["insertBacklog"] = (
            lambda: im.inflight_high, float(im.inflight_high))
        assert im.admission.pressure() >= 1.0
        assert im.admission.evaluate() == LEVEL_REJECT
    finally:
        im.close()


def test_dedup_survives_kill9_wal_recovery(tmp_path):
    """A producer retrying across a manager crash loses zero acked
    rows and duplicates zero rows: the (stream, seq) tag rides the
    WAL record, so replay restores rows AND the dedup entry."""
    wal_dir = str(tmp_path / "wal")
    db = FlowDatabase()
    db.attach_wal(wal_dir, sync="always")
    im = IngestManager(db, n_shards=1)
    payload, n = _block(seed=7)
    out = im.ingest(payload, stream="prod", seq=1)
    assert out["rows"] == n
    im.close()
    # kill -9: no close_wal, no snapshot — reopen from disk only
    db2 = FlowDatabase()
    stats = db2.attach_wal(wal_dir, sync="always")
    assert stats["recoveredRows"] == n
    assert len(db2.flows) == n              # zero acked rows lost
    assert ("prod", 1, n, n) in db2.recovered_acks()
    im2 = IngestManager(db2, n_shards=1)
    try:
        dup = im2.ingest(payload, stream="prod", seq=1)  # the retry
        assert dup["duplicate"] is True and dup["rows"] == n
        assert len(db2.flows) == n          # zero rows duplicated
    finally:
        im2.close()
        db2.close_wal()


def test_retrying_producer_conserves_rows_across_crash(tmp_path):
    """Acceptance shape: a producer mid-run through a kill -9 loses
    zero acked rows and duplicates zero rows. Five acked batches, a
    crash, the producer retries its un-acked tail (it cannot know
    whether 4 and 5 landed), then continues with a fresh encoder —
    the store ends with exactly six batches' rows."""
    wal_dir = str(tmp_path / "wal")
    db = FlowDatabase()
    db.attach_wal(wal_dir, sync="always")
    im = IngestManager(db, n_shards=1)
    enc, batch = _producer(seed=11)
    n = len(batch)
    payloads = {seq: enc.encode(batch) for seq in range(1, 6)}
    for seq in range(1, 6):
        assert im.ingest(payloads[seq], stream="p",
                         seq=seq)["rows"] == n
    im.close()
    # kill -9 mid-run (acks for 4 and 5 "lost on the wire")
    db2 = FlowDatabase()
    db2.attach_wal(wal_dir, sync="always")
    assert len(db2.flows) == 5 * n          # zero acked rows lost
    im2 = IngestManager(db2, n_shards=1)
    try:
        for seq in (4, 5):                  # the producer's retry tail
            out = im2.ingest(payloads[seq], stream="p", seq=seq)
            assert out["duplicate"] is True and out["rows"] == n
        # reconnected producers restart their encoder (delta chain);
        # the next batch is new work
        enc2, batch2 = _producer(seed=11)
        assert im2.ingest(enc2.encode(batch2), stream="p",
                          seq=6)["rows"] == n
        assert len(db2.flows) == 6 * n      # zero rows duplicated
    finally:
        im2.close()
        db2.close_wal()


def test_dedup_survives_kill9_sharded(tmp_path):
    """A batch split across shard WALs recovers ONE logical ack (the
    per-shard slice counts re-sum)."""
    from theia_tpu.store import ShardedFlowDatabase
    wal_dir = str(tmp_path / "wal")
    db = ShardedFlowDatabase(n_shards=2)
    db.attach_wal(wal_dir, sync="always")
    im = IngestManager(db, n_shards=1)
    payload, n = _block(n_series=8, seed=9)
    assert im.ingest(payload, stream="p", seq=5)["rows"] == n
    im.close()
    db2 = ShardedFlowDatabase(n_shards=2)
    db2.attach_wal(wal_dir, sync="always")
    acks = db2.recovered_acks()
    assert acks == [("p", 5, n, n)]         # re-summed across shards
    im2 = IngestManager(db2, n_shards=1)
    try:
        dup = im2.ingest(payload, stream="p", seq=5)
        assert dup["duplicate"] is True and dup["rows"] == n
        assert len(db2.flows) == n
    finally:
        im2.close()
        db2.close_wal()


# -- API taxonomy + never-shed control endpoints --------------------------

@pytest.fixture()
def server():
    from theia_tpu.manager import TheiaManagerServer
    db = FlowDatabase()
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    yield srv
    srv.shutdown()


def _post_ingest(srv, payload, query=""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/ingest{query}", method="POST",
        data=payload,
        headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def _get(srv, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10) as r:
        return r.status, json.loads(r.read())


def test_429_vs_503_taxonomy_and_never_shed_endpoints(server,
                                                      monkeypatch):
    payload, n = _block()
    assert _post_ingest(server, payload, "?stream=a&seq=1")["rows"] == n

    monkeypatch.setenv("THEIA_ADMISSION_FORCE_LEVEL", "reject")
    # capacity rejection: 429 + Retry-After, body carries the float
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_ingest(server, payload, "?stream=a&seq=2")
    e = ei.value
    assert e.code == 429
    assert int(e.headers["Retry-After"]) >= 1
    body = json.loads(e.read())
    assert body["retryAfterSeconds"] > 0
    assert body["reason"] == "pressure"

    # a duplicate retry of ACKED work still answers while rejecting
    # new work (that is how a producer learns its batch landed)
    dup = _post_ingest(server, payload, "?stream=a&seq=1")
    assert dup["duplicate"] is True

    # control/observability endpoints are never shed
    code, health = _get(server, "/healthz")
    assert code == 200
    assert health["admission"]["levelName"] == "reject"
    assert health["status"] == "degraded"
    assert health["dedup"]["entries"] >= 1
    assert _get(server, "/readyz")[0] == 200
    assert _get(server, "/alerts?limit=5")[0] == 200
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/metrics")
    with urllib.request.urlopen(req, timeout=10) as r:
        text = r.read().decode()
    assert "theia_admission_level 3" in text
    assert "theia_admission_rejected_total" in text

    monkeypatch.delenv("THEIA_ADMISSION_FORCE_LEVEL")
    # 503 stays the UNAVAILABILITY signal, distinct from 429: every
    # store replica down is not a capacity condition
    from theia_tpu.store import AllReplicasDownError

    def down(*a, **kw):
        raise AllReplicasDownError("all replicas down")
    monkeypatch.setattr(server.ingest, "ingest", down)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_ingest(server, payload, "?stream=a&seq=3")
    assert ei.value.code == 503


def test_seq_must_be_integer(server):
    payload, _ = _block()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_ingest(server, payload, "?stream=a&seq=nope")
    assert ei.value.code == 400


# -- client ---------------------------------------------------------------

def test_ingest_client_honors_retry_after(server, monkeypatch):
    """End to end: the producer client absorbs a 429 (sleeping the
    server's hint + jittered capped backoff) and the retry of the SAME
    seq lands exactly once."""
    import random

    from theia_tpu.ingest.client import IngestClient

    sleeps = []
    client = IngestClient(
        f"http://127.0.0.1:{server.port}", stream="cli",
        rng=random.Random(0), sleep=sleeps.append)
    enc, batch = _producer()
    n = len(batch)
    assert client.send(enc.encode(batch))["rows"] == n

    # next send hits a forced reject once, then the level clears
    real_admit = server.ingest.admission.admit
    calls = {"n": 0}

    def admit_once_rejected(stream, nbytes, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise AdmissionRejected("pressure", 0.25, "drill")
        return real_admit(stream, nbytes, **kw)
    monkeypatch.setattr(server.ingest.admission, "admit",
                        admit_once_rejected)
    out = client.send(enc.encode(batch))
    assert out["rows"] == n and "duplicate" not in out
    assert client.rejected == 1
    assert len(sleeps) == 1
    assert sleeps[0] >= 0.25                # honored the server hint
    s = client.summary()
    assert s["rowsAcked"] == 2 * n and s["batchesAcked"] == 2


def test_ingest_client_retries_500_and_raw_timeouts(server,
                                                    monkeypatch):
    """A 500'd-but-stored batch must be RETRIED (the server recorded
    its ack — the retry collects duplicate:true), and a read-phase
    socket timeout (which urllib does NOT wrap in URLError) must also
    re-enter the retry loop instead of escaping it."""
    import random
    import urllib.request as _ur

    from theia_tpu.ingest.client import IngestClient

    # one detector failure → the request 500s AFTER the insert landed
    real_score = server.ingest.score_batch
    state = {"boom": True}

    def score_once_broken(batch):
        if state["boom"]:
            state["boom"] = False
            raise RuntimeError("transient detector failure")
        return real_score(batch)
    monkeypatch.setattr(server.ingest, "score_batch",
                        score_once_broken)
    sleeps = []
    client = IngestClient(
        f"http://127.0.0.1:{server.port}", stream="r500",
        rng=random.Random(0), sleep=sleeps.append)
    enc, batch = _producer(seed=29)
    n = len(batch)
    out = client.send(enc.encode(batch))
    assert out["duplicate"] is True and out["rows"] == n
    assert client.retries == 1               # the 500 was transient
    before = len(server.controller.db.flows)

    # raw TimeoutError from the read phase: retried, not propagated
    real_urlopen = _ur.urlopen
    state2 = {"boom": True}

    def timeout_once(*a, **kw):
        if state2["boom"]:
            state2["boom"] = False
            raise TimeoutError("timed out")
        return real_urlopen(*a, **kw)
    monkeypatch.setattr(_ur, "urlopen", timeout_once)
    out2 = client.send(enc.encode(batch))
    assert out2["rows"] == n and "duplicate" not in out2
    assert client.retries == 2
    assert len(server.controller.db.flows) == before + n


def test_ingest_client_no_sleep_after_final_attempt(server,
                                                    monkeypatch):
    """An exhausted retry budget raises immediately — no dead sleep
    between the last failure and the error."""
    import random

    from theia_tpu.ingest.client import IngestClient, IngestError

    def always_reject(stream, nbytes, **kw):
        raise AdmissionRejected("pressure", 0.2, "drill")
    monkeypatch.setattr(server.ingest.admission, "admit",
                        always_reject)
    sleeps = []
    client = IngestClient(
        f"http://127.0.0.1:{server.port}", stream="x",
        max_attempts=3, rng=random.Random(0), sleep=sleeps.append)
    payload, _ = _block()
    with pytest.raises(IngestError):
        client.send(payload)
    assert len(sleeps) == 2                  # attempts-1, not attempts
    assert client.rejected == 3


def test_streaming_detector_injectable_clock():
    """latency_s is measured on the detector's injectable clock — the
    substrate of the deterministic bound in test_manager_cli."""
    from theia_tpu.analytics.streaming import StreamingDetector
    clk = FakeClock()
    det = StreamingDetector(capacity=64, clock=clk)
    spike = generate_flows(SynthConfig(
        n_series=3, points_per_series=20, anomaly_fraction=1.0,
        anomaly_magnitude=90.0, seed=4))
    alerts = det.ingest(spike)
    assert alerts
    assert all(a["latency_s"] == 0.0 for a in alerts)


def test_admission_cannot_be_switched_off(monkeypatch):
    """The retired THEIA_ADMISSION_DISABLED switch does nothing: the
    manager keeps its controller and a block is admitted and acked."""
    monkeypatch.setenv("THEIA_ADMISSION_DISABLED", "1")
    im = IngestManager(FlowDatabase(), n_shards=1)
    try:
        assert isinstance(im.admission, AdmissionController)
        payload, n = _block()
        before = im.admission.admitted
        assert im.ingest(payload)["rows"] == n
        assert im.admission.admitted == before + 1
    finally:
        im.close()
