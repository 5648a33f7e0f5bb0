"""Concurrency stress: ingest streams vs TTL vs retention vs readers.

The reference runs its whole unit suite under `go test -race`
(Makefile:101-107); this is the equivalent discipline for the Python/
C++ runtime — threaded harnesses hammering the shared store and
asserting ROW CONSERVATION (every acked row is either in the store or
counted deleted), no deadlocks (bounded joins), and stream-reset
correctness under interleaving.
"""

import threading
import time

import numpy as np
import pytest

from theia_tpu.analytics.streaming import CONNECTION_KEY_COLUMNS
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BlockEncoder
from theia_tpu.manager import ingest as ingest_mod
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.schema import FLOW_SCHEMA, ColumnarBatch
from theia_tpu.store import FlowDatabase

N_THREADS = 4
BLOCKS_PER_THREAD = 6


def _mk_batch(thread_id: int, block: int, dicts, t_base: int):
    rows = [{
        "sourceIP": f"10.{thread_id}.0.{i % 16}",
        "destinationIP": f"10.{thread_id}.1.{i % 8}",
        "sourceTransportPort": 30000 + i,
        "destinationTransportPort": 80,
        "protocolIdentifier": 6,
        "octetDeltaCount": 1000 + i,
        "packetDeltaCount": 3,
        "throughput": 5000 + i,
        "timeInserted": t_base + block * 10 + (i % 10),
        "flowStartSeconds": t_base,
        "flowEndSeconds": t_base + block * 10 + (i % 10),
    } for i in range(400)]
    return ColumnarBatch.from_rows(rows, FLOW_SCHEMA, dicts)


def test_concurrent_streams_ttl_retention_readers_conserve_rows():
    """N producer streams, a TTL/retention trimmer, and view/table
    readers run concurrently; at the end every acknowledged row is
    accounted for: still stored, TTL-evicted, or retention-trimmed."""
    db = FlowDatabase(ttl_seconds=None)
    im = IngestManager(db)
    t_base = 1_700_000_000
    acked = [0] * N_THREADS
    deleted = []
    deleted_lock = threading.Lock()
    stop_aux = threading.Event()
    errors = []

    def producer(tid):
        try:
            enc = BlockEncoder()
            for b in range(BLOCKS_PER_THREAD):
                batch = _mk_batch(tid, b, enc.dicts, t_base)
                out = im.ingest(enc.encode(batch), stream=f"p{tid}")
                acked[tid] += out["rows"]
        except Exception as e:   # pragma: no cover - failure surface
            errors.append(f"producer {tid}: {e!r}")

    def trimmer():
        # retention trims under a tiny capacity so deletions really
        # interleave with inserts; deletions are counted for the
        # conservation check
        mon = db.monitor(capacity_bytes=1, threshold=0.5,
                         delete_percentage=0.3, skip_rounds=0)
        try:
            while not stop_aux.is_set():
                n = mon.tick()
                n += db.delete_flows_older_than(t_base - 10_000)
                if n:
                    with deleted_lock:
                        deleted.append(n)
                time.sleep(0.002)
        except Exception as e:   # pragma: no cover
            errors.append(f"trimmer: {e!r}")

    def reader():
        try:
            while not stop_aux.is_set():
                db.flows.scan()
                for v in db.views.values():
                    v.scan()
                im.recent_alerts(50)
                time.sleep(0.003)
        except Exception as e:   # pragma: no cover
            errors.append(f"reader: {e!r}")

    producers = [threading.Thread(target=producer, args=(i,))
                 for i in range(N_THREADS)]
    aux = [threading.Thread(target=trimmer),
           threading.Thread(target=reader)]
    for t in aux + producers:
        t.start()
    for t in producers:
        t.join(timeout=300)
        assert not t.is_alive(), "producer deadlocked"
    stop_aux.set()
    for t in aux:
        t.join(timeout=60)
        assert not t.is_alive(), "aux thread deadlocked"

    assert not errors, errors
    total_acked = sum(acked)
    assert total_acked == N_THREADS * BLOCKS_PER_THREAD * 400
    with deleted_lock:
        total_deleted = sum(deleted)
    remaining = len(db.flows)
    assert remaining + total_deleted == total_acked, (
        f"row conservation violated: {remaining} stored + "
        f"{total_deleted} deleted != {total_acked} acked")
    assert im.rows_ingested == total_acked
    # views stayed consistent with the surviving flows
    pod_view = db.views["flows_pod_view"].scan()
    flows = db.flows.scan()
    assert np.asarray(pod_view["octetDeltaCount"]).sum() == \
        np.asarray(flows["octetDeltaCount"]).sum()


def test_concurrent_stream_resets_do_not_desync():
    """Producers that interleave malformed payloads (stream resets)
    with fresh encoders still land every good row with correct string
    identities — a reset must never leave a half-applied dictionary
    chain behind."""
    db = FlowDatabase()
    im = IngestManager(db)
    good_rows = [0] * N_THREADS
    errors = []

    def producer(tid):
        try:
            for b in range(BLOCKS_PER_THREAD):
                # malformed payload resets the stream
                try:
                    im.ingest(b"garbage-payload", stream=f"r{tid}")
                    errors.append(f"{tid}: garbage accepted")
                except ValueError:
                    pass
                # fresh encoder after the reset, like a real producer
                enc = BlockEncoder()
                batch = ColumnarBatch.from_rows([{
                    "sourceIP": f"172.16.{tid}.{b}",
                    "destinationIP": f"172.17.{tid}.{b}",
                    "octetDeltaCount": 7,
                    "packetDeltaCount": 1,
                }], FLOW_SCHEMA, enc.dicts)
                out = im.ingest(enc.encode(batch), stream=f"r{tid}")
                good_rows[tid] += out["rows"]
        except Exception as e:   # pragma: no cover
            errors.append(f"producer {tid}: {e!r}")

    threads = [threading.Thread(target=producer, args=(i,))
               for i in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "producer deadlocked"
    assert not errors, errors
    assert sum(good_rows) == N_THREADS * BLOCKS_PER_THREAD
    # decoded identities survived every reset intact
    flows = db.flows.scan()
    srcs = set(flows.strings("sourceIP"))
    for tid in range(N_THREADS):
        for b in range(BLOCKS_PER_THREAD):
            assert f"172.16.{tid}.{b}" in srcs


def _spike_payloads(n_streams, n_blocks, rows_per_block=48):
    """Per-stream TFB2 block sequences with DISTINCT connection
    populations and deterministic throughput spikes (so per-connection
    EWMA alerts fire on known points)."""
    t_base = 1_700_000_000
    payloads = []
    for sid in range(n_streams):
        enc = BlockEncoder()
        blocks = []
        for b in range(n_blocks):
            rows = [{
                "sourceIP": f"10.{sid}.2.{i}",
                "destinationIP": f"10.{sid}.3.{i % 12}",
                "sourceTransportPort": 40000 + i,
                "destinationTransportPort": 443,
                "protocolIdentifier": 6,
                "octetDeltaCount": 900 + i,
                "packetDeltaCount": 2,
                # steady-ish series with a large spike at block 4
                "throughput": 1000 + 7 * i + (b % 3) +
                (90000 if b == 4 else 0),
                "timeInserted": t_base + b * 10,
                "flowStartSeconds": t_base,
                "flowEndSeconds": t_base + b * 10,
            } for i in range(rows_per_block)]
            blocks.append(enc.encode(
                ColumnarBatch.from_rows(rows, FLOW_SCHEMA, enc.dicts)))
        payloads.append(blocks)
    return payloads


def _conn_alert_sequences(im):
    """connection_anomaly alerts grouped per connection identity, in
    publication order (ring is newest-first, so reverse), with the
    nondeterministic fields (latency, wall time, shard-local slot)
    stripped."""
    key_cols = ("sourceIP", "sourceTransportPort", "destinationIP",
                "destinationTransportPort", "protocolIdentifier",
                "flowStartSeconds")
    seqs = {}
    for a in reversed(im.recent_alerts(10_000)):
        if a.get("kind") != "connection_anomaly":
            continue
        key = tuple(a[c] for c in key_cols)
        seqs.setdefault(key, []).append(
            (a["kind"], a["flowEndSeconds"], a["throughput"]))
    return seqs


def test_sharded_ingest_alerts_deterministic_vs_serial():
    """The per-connection ordering guarantee of the sharded, pipelined
    ingest path: N threads ingesting distinct streams produce exactly
    the serial run's per-connection alert sequence (kind, connection
    identity, order) — a key always hashes to the same shard, and a
    shard applies one stream's batches in ack order."""
    n_streams, n_blocks = 4, 6
    serial_payloads = _spike_payloads(n_streams, n_blocks)
    threaded_payloads = _spike_payloads(n_streams, n_blocks)

    im_serial = IngestManager(FlowDatabase(), n_shards=4)
    for sid in range(n_streams):
        for p in serial_payloads[sid]:
            im_serial.ingest(p, stream=f"s{sid}")

    im_threaded = IngestManager(FlowDatabase(), n_shards=4)
    errors = []

    def feed(sid):
        try:
            for p in threaded_payloads[sid]:
                im_threaded.ingest(p, stream=f"s{sid}")
        except Exception as e:   # pragma: no cover
            errors.append(f"stream {sid}: {e!r}")

    threads = [threading.Thread(target=feed, args=(sid,))
               for sid in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "ingest thread deadlocked"
    assert not errors, errors

    serial_seqs = _conn_alert_sequences(im_serial)
    threaded_seqs = _conn_alert_sequences(im_threaded)
    assert serial_seqs, "expected connection_anomaly alerts"
    assert threaded_seqs == serial_seqs

    # CMS updates are per-destination-shard too, so the final sketched
    # volume of every destination matches the serial run exactly.
    for sid in range(n_streams):
        for i in range(12):
            dst = f"10.{sid}.3.{i}"
            est = []
            for im in (im_serial, im_threaded):
                code = im._global_dicts["destinationIP"].lookup(dst)
                assert code is not None, dst
                shard = im.shards[im.shard_of_destination(dst)]
                est.append(shard.heavy.volume_estimate(code))
            assert est[0] == est[1], dst
    im_serial.close()
    im_threaded.close()


def test_shard_partition_is_stable():
    """Same key → same shard: across batches, across manager
    instances (restart), and matching the public stable-hash
    assignment — detector state for a key can never migrate."""
    payloads = _spike_payloads(1, 3)[0]
    ims = [IngestManager(FlowDatabase(), n_shards=4) for _ in range(2)]
    for im in ims:
        for p in payloads:
            im.ingest(p)
    dests = [f"10.0.3.{i}" for i in range(12)]
    for dst in dests:
        shards = {im.shard_of_destination(dst) for im in ims}
        assert len(shards) == 1, f"{dst} moved shards across restarts"
        for im in ims:
            code = im._global_dicts["destinationIP"].lookup(dst)
            # the row-partition table agrees with the public hash
            assert im._dst_shard[code] == im.shard_of_destination(dst)
            # and the key's detector state actually lives there: its
            # connections were slotted in exactly that shard's table
            shard = im.shards[im.shard_of_destination(dst)]
            assert shard.heavy.volume_estimate(code) > 0
    # the population spreads over >1 shard (the test would otherwise
    # not exercise partitioning at all)
    assert len({ims[0].shard_of_destination(d) for d in dests}) > 1
    for im in ims:
        im.close()


# -- one grouping of a block by shard (`IngestManager._partition`) ---------

def _whole_column_slices(im, scored, shard_ids):
    """What `_partition` was before it grouped a block once: one
    `flatnonzero` and one 52-column `take` per shard. Kept here as the
    plain reference for the projected slices."""
    for s in range(im.n_shards):
        idx = np.flatnonzero(shard_ids == s)
        if idx.size:
            yield im.shards[s], scored.take(idx)


def _declared(im):
    """column → dtype the manager's own detectors declare."""
    return {**im.shards[0].heavy.reads, **im.shards[0].streaming.reads}


def _remapped_block(im, seed=5, n_series=160, points=4):
    batch = generate_flows(SynthConfig(n_series=n_series,
                                       points_per_series=points,
                                       seed=seed))
    # interleave the connections' points so that a slice has to keep
    # batch order, not series order
    order = np.random.default_rng(seed).permutation(len(batch))
    return im._remap_global(batch.take(order))


@pytest.mark.parametrize("seed", (5, 6))
def test_partition_slices_equal_whole_column_take_on_declared_columns(
        seed):
    im = IngestManager(FlowDatabase(), n_shards=8)
    declared = _declared(im)
    assert len(declared) == 10, sorted(declared)
    scored, shard_ids = _remapped_block(im, seed=seed)
    parts = list(im._partition(scored, shard_ids))
    assert [s.index for s, _ in parts] == sorted(
        set(shard_ids.tolist()))
    assert len(parts) > 1
    assert sum(len(p) for _, p in parts) == len(scored)
    for shard, part in parts:
        ref = scored.take(np.flatnonzero(shard_ids == shard.index))
        assert set(part.column_names) == set(declared)
        for c, dtype in declared.items():
            # same values in the same (batch) order, already in the
            # dtype the detector converts to, and a view of the
            # grouped column: nothing copied per shard
            assert part[c].dtype == dtype, c
            assert np.array_equal(part[c], ref[c]), c
            assert part[c].base is not None, c
            assert np.asarray(part[c], dtype) is part[c], c
        for c in ("sourceIP", "destinationIP"):
            assert part.dicts[c] is im._global_dicts[c]
    im.close()


def _strip(alert):
    return {k: v for k, v in alert.items()
            if k not in ("time", "latency_s")}


def test_projected_slices_give_the_alerts_of_whole_column_slices():
    """Eight shards, six blocks so that state carries: every ack's
    count and split, and every published alert record, equal those of
    a manager that still slices all 52 columns per shard."""
    n_blocks = 6
    payloads = _spike_payloads(2, n_blocks, rows_per_block=96)
    twins = _spike_payloads(2, n_blocks, rows_per_block=96)
    im_new = IngestManager(FlowDatabase(), n_shards=8)
    im_old = IngestManager(FlowDatabase(), n_shards=8)
    im_old._partition = (
        lambda scored, ids: _whole_column_slices(im_old, scored, ids))
    n_conn = 0
    for b in range(n_blocks):
        for sid in range(2):
            new = im_new.ingest(payloads[sid][b], stream=f"s{sid}")
            old = im_old.ingest(twins[sid][b], stream=f"s{sid}")
            assert new["alerts"] == old["alerts"], (b, sid)
            assert new["alertsByKind"] == old["alertsByKind"], (b, sid)
            n_conn += new["alertsByKind"]["connection_anomaly"]
    assert n_conn > 0, "expected connection_anomaly alerts"
    got = [_strip(a) for a in im_new.recent_alerts(10_000)]
    want = [_strip(a) for a in im_old.recent_alerts(10_000)]
    assert got == want
    assert _conn_alert_sequences(im_new) == _conn_alert_sequences(im_old)
    for a, b in zip(im_new.shards, im_old.shards):
        assert a.streaming.n_series == b.streaming.n_series
        assert a.heavy.total_volume == b.heavy.total_volume
    im_new.close()
    im_old.close()


class _UndeclaredHeavy:
    """A heavy-hitter double that says nothing about what it reads."""
    total_volume = 0.0

    def __init__(self):
        self.seen = []

    def update(self, batch, extra_total=0.0):
        self.seen.append(batch)
        return []


def test_a_detector_that_declares_no_columns_receives_all_52():
    im = IngestManager(FlowDatabase(), n_shards=4)
    double = _UndeclaredHeavy()
    im.shards[2].heavy = double            # one undeclared is enough
    assert im._slice_columns() is None
    scored, shard_ids = _remapped_block(im)
    before = ingest_mod._M_PARTITION_BYTES.value()
    parts = list(im._partition(scored, shard_ids))
    assert len(parts) == 4
    gathered = 0
    for shard, part in parts:
        ref = scored.take(np.flatnonzero(shard_ids == shard.index))
        assert list(part.column_names) == list(scored.column_names)
        assert len(part.columns) == 52
        for c in scored.column_names:
            assert part[c].dtype == scored[c].dtype, c
            assert np.array_equal(part[c], ref[c]), c
            gathered += part[c].nbytes
        assert part.dicts == scored.dicts
    assert ingest_mod._M_PARTITION_BYTES.value() - before == gathered
    assert gathered == sum(v.nbytes for v in scored.columns.values())
    # and the leg runs on such slices: the double gets its shard's
    im.score_batch(generate_flows(SynthConfig(
        n_series=160, points_per_series=2, seed=9)))
    assert len(double.seen) == 1 and len(double.seen[0].columns) == 52
    im.close()


def test_partition_shortcuts_yield_the_block_itself():
    one = IngestManager(FlowDatabase(), n_shards=1)
    scored, shard_ids = _remapped_block(one)
    assert shard_ids is None
    assert list(one._partition(scored, None)) == [(one.shards[0],
                                                   scored)]
    one.close()
    im = IngestManager(FlowDatabase(), n_shards=8)
    scored, shard_ids = _remapped_block(im)
    home = int(shard_ids[0])
    mine = scored.take(np.flatnonzero(shard_ids == home))
    before = ingest_mod._M_PARTITION_BYTES.value()
    parts = list(im._partition(mine, np.full(len(mine), home)))
    assert len(parts) == 1
    assert parts[0][0] is im.shards[home] and parts[0][1] is mine
    assert ingest_mod._M_PARTITION_BYTES.value() == before
    im.close()


def test_describe_alert_on_a_projected_slice_decodes_the_same_keys():
    im = IngestManager(FlowDatabase(), n_shards=8)
    scored, shard_ids = _remapped_block(im)
    for (shard, part), (_, ref) in zip(
            im._partition(scored, shard_ids),
            _whole_column_slices(im, scored, shard_ids)):
        for row in (0, len(part) // 2, len(part) - 1):
            got = shard.streaming.describe_alert(part, {"row": row})
            want = shard.streaming.describe_alert(ref, {"row": row})
            assert got == want
            assert isinstance(got["sourceIP"], str)
            assert isinstance(got["destinationIP"], str)
            assert [type(got[c]) for c in CONNECTION_KEY_COLUMNS] == [
                type(want[c]) for c in CONNECTION_KEY_COLUMNS]
    im.close()


def test_pipelined_insert_leg_errors_surface():
    """The store-insert leg runs overlapped with detector scoring; its
    exceptions must still reach the producer (an acked row that never
    hit the store would break row conservation silently)."""

    class _FailingDB:
        def insert_flows(self, batch):
            raise RuntimeError("store exploded")

    im = IngestManager(_FailingDB(), n_shards=2)
    enc = BlockEncoder()
    batch = ColumnarBatch.from_rows([{
        "sourceIP": "10.9.9.1", "destinationIP": "10.9.9.2",
        "octetDeltaCount": 10, "packetDeltaCount": 1,
    }], FLOW_SCHEMA, enc.dicts)
    try:
        im.ingest(enc.encode(batch))
        assert False, "expected the insert leg's error"
    except RuntimeError as e:
        assert "store exploded" in str(e)
    im.close()


def test_concurrent_jobs_and_ingest_no_deadlock():
    """Job lifecycle (create/read/delete) racing live ingest: the
    controller's result-table GC and the ingest path share the store;
    nothing may deadlock and completed jobs must hold valid results."""
    from theia_tpu.manager.jobs import KIND_TAD, JobController

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=8, points_per_series=16, anomaly_fraction=0.5,
        anomaly_magnitude=50.0, seed=3)))
    im = IngestManager(db)
    ctl = JobController(db, workers=2)
    stop = threading.Event()
    errors = []

    def ingester():
        try:
            enc = BlockEncoder()
            b = 0
            while not stop.is_set():
                batch = _mk_batch(9, b, enc.dicts, 1_700_000_000)
                im.ingest(enc.encode(batch), stream="jobs-race")
                b += 1
        except Exception as e:   # pragma: no cover
            errors.append(f"ingester: {e!r}")

    t = threading.Thread(target=ingester)
    t.start()
    try:
        names = []
        for _ in range(4):
            names.append(ctl.create(KIND_TAD, {"jobType": "EWMA"}).name)
        assert ctl.wait_all(timeout=300)
        for name in names:
            rec = ctl.get(name)
            assert rec.state == "COMPLETED", rec.error_msg
            assert ctl.tad_stats(name) is not None
            ctl.delete(name)
        assert len(db.tadetector) == 0
    finally:
        stop.set()
        t.join(timeout=60)
        assert not t.is_alive(), "ingester deadlocked"
        ctl.shutdown()
    assert not errors, errors
