"""TBLK columnar wire format: the zero-copy ingest spine.

Load-bearing claims under test (docs/ingest.md "TBLK self-contained
columnar blocks"): the codec round-trips byte-stably and rejects
garbage structurally; a TBLK producer and a TFB2 producer are
indistinguishable downstream (byte-identical alerts AND byte-identical
WAL streams AND identical query results); the WAL journals a received
TBLK body VERBATIM (no re-encode between producer and disk); the
router re-slices cross-node forwards by column gather on the encoded
bytes, decoding only `destinationIP` (never the full batch); admission
charges rows from the 10-byte header without any decode; and
exactly-once survives kill -9 mid-stream with dedup tags restored from
the verbatim-journaled frames.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BlockEncoder, TblkEncoder, decode_tblk, \
    make_block_encoder
from theia_tpu.manager.admission import AdmissionController, \
    AdmissionRejected
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.store import FlowDatabase
from theia_tpu.store import wal as _wal
from theia_tpu.store import wire
from theia_tpu.utils import faults
from theia_tpu.utils.faults import FaultError

pytestmark = pytest.mark.wire


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


def _batch(seed=8, n=32, t=10, **kw):
    return generate_flows(SynthConfig(
        n_series=n, points_per_series=t, seed=seed, **kw))


def _rows(db):
    """Order-insensitive logical contents of the flows table."""
    data = db.flows.scan()
    return sorted(zip(
        data["timeInserted"].tolist(),
        data["flowStartSeconds"].tolist(),
        data["octetDeltaCount"].tolist(),
        data.strings("sourceIP").tolist(),
        data.strings("destinationIP").tolist(),
        data.strings("sourcePodName").tolist(),
    ))


def _batch_rows(b):
    cols = sorted(b.column_names)
    out = []
    for i in range(len(b)):
        row = []
        for c in cols:
            if c in b.dicts:
                row.append(b.strings(c)[i])
            else:
                row.append(np.asarray(b[c])[i].item())
        out.append(tuple(row))
    return out


def _wal_bodies(db):
    db._wal.sync()
    frames, _last, algo = db._wal.read_frames(0)
    return [bytes(b) for (_, _, b) in _wal.iter_frames(frames, algo)]


# -- codec ---------------------------------------------------------------


def test_tblk_golden_roundtrip():
    batch = _batch()
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    assert payload[:4] == wire.BLOCK_MAGIC
    out = decode_tblk(payload)
    assert len(out) == len(batch)
    for name in batch.column_names:
        if name in batch.dicts:
            np.testing.assert_array_equal(
                out.strings(name), batch.strings(name), err_msg=name)
        else:
            np.testing.assert_array_equal(
                np.asarray(out[name]), np.asarray(batch[name]),
                err_msg=name)
    # canonical form: re-encoding the decoded batch reproduces the
    # exact bytes (decode mints batch-local dicts in code order, which
    # is what the encoder writes) — the property the WAL byte-parity
    # and router gather paths stand on
    assert wire.encode_block(out) == payload
    # stateless: a fresh decode of the same bytes needs no stream
    # state and yields the same rows
    assert _batch_rows(decode_tblk(payload)) == _batch_rows(out)


def test_tblk_peek_counts_matches_without_decode():
    batch = _batch(seed=3, n=16, t=4)
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    n_rows, n_cols = wire.peek_counts(payload, 4)
    assert n_rows == len(batch)
    assert n_cols == len(batch.column_names)


def test_tblk_fuzzed_garbage_rejected():
    batch = _batch(seed=5, n=8, t=4)
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    rng = np.random.default_rng(0)
    # truncations at every prefix band: clean structural error, never
    # a crash or a silently short batch
    for cut in (4, 6, 9, 10, 20, len(payload) // 2, len(payload) - 1):
        with pytest.raises(ValueError):
            decode_tblk(payload[:cut])
    # random byte flips: either WireCorruption (a ValueError) or a
    # well-formed batch (flips in string blobs/values decode fine) —
    # anything else (IndexError, segfault, hang) fails the test
    for _ in range(300):
        buf = bytearray(payload)
        for _ in range(int(rng.integers(1, 4))):
            buf[int(rng.integers(4, len(buf)))] = int(
                rng.integers(0, 256))
        try:
            out = decode_tblk(bytes(buf))
        except ValueError:
            continue
        assert len(out) == len(batch)
    # pure noise
    for size in (0, 1, 5, 64):
        blob = bytes(rng.integers(0, 256, size, dtype=np.uint8))
        with pytest.raises(ValueError):
            decode_tblk(wire.BLOCK_MAGIC + blob)
    # trailing garbage after a valid block is corruption, not ignored
    with pytest.raises(ValueError):
        decode_tblk(payload + b"\x00")


# -- admission: header-charge without decode -----------------------------


def test_admission_charges_rows_from_header_without_decode():
    batch = _batch(seed=7, n=20, t=10)   # 200 rows
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    clock = [0.0]
    adm = AdmissionController(rate=1000.0, burst=1000.0,
                              clock=lambda: clock[0])
    db = FlowDatabase()
    im = IngestManager(db, admission=adm, n_shards=1)
    try:
        before = adm.rows.tokens()
        out = im.ingest(payload, stream="s", seq=1)
        assert out["rows"] == len(batch)
        # charged exactly once: the pre-decode rows_hint charge, with
        # no second post-decode charge_rows on top
        spent = before - adm.rows.tokens()
        assert spent == pytest.approx(len(batch), abs=1e-6)
        # drive the bucket into deep debt, poison the decoder, and
        # send again: the block must be refused by ADMISSION — a
        # FaultError here would mean the reject path decoded the block
        adm.rows.charge(10_000)
        inj = faults.arm("wire.decode:error")
        with pytest.raises(AdmissionRejected):
            im.ingest(payload, stream="s", seq=2)
        assert inj.counts().get("wire.decode", 0) == 0
        faults.disarm()
        assert len(db.flows) == len(batch)   # only the admitted batch
        # an admitted block with a poisoned decoder DOES surface the
        # decode fault — decode happens after admission, exactly once
        clock[0] += 20.0                     # refill the bucket
        faults.arm("wire.decode:error")
        with pytest.raises(FaultError):
            im.ingest(payload, stream="s", seq=2)
    finally:
        im.close()


# -- mixed-producer parity ----------------------------------------------


def test_mixed_producer_parity_single_node(tmp_path):
    """A TBLK producer and a TFB2 producer sending the same batches
    are indistinguishable downstream: byte-identical alert stream,
    byte-identical WAL stream, identical store contents."""
    big = _batch(seed=11, n=64, t=6)

    def run(enc_cls, wdir):
        enc = enc_cls(dicts=big.dicts)
        db = FlowDatabase()
        db.attach_wal(str(wdir), sync="always")
        im = IngestManager(db, n_shards=1)
        acks = [im.ingest(enc.encode(big), stream="s", seq=i)
                for i in range(3)]
        alerts = im.recent_alerts(10_000)
        im.close()
        return db, acks, alerts

    db_t, acks_t, alerts_t = run(TblkEncoder, tmp_path / "tblk")
    db_f, acks_f, alerts_f = run(BlockEncoder, tmp_path / "tfb2")
    assert [a["rows"] for a in acks_t] == [a["rows"] for a in acks_f]
    assert [a["alerts"] for a in acks_t] == [a["alerts"] for a in acks_f]
    # byte-identical alerts, modulo the two wall-clock measurement
    # stamps (`time` arrival, `latency_s` measured request latency) —
    # everything content-derived (identity, slot, scores, thresholds)
    # must match exactly
    def canon(alerts):
        return json.dumps(
            [{k: v for k, v in a.items()
              if k not in ("time", "latency_s")}
             for a in alerts], sort_keys=True, default=str)
    assert canon(alerts_t) == canon(alerts_f)
    # identical query results
    assert _rows(db_t) == _rows(db_f)
    # byte-identical WAL streams: the verbatim-journaled TBLK bodies
    # equal the TFB2 path's re-encoded record bodies, frame for frame
    assert _wal_bodies(db_t) == _wal_bodies(db_f)
    db_t.close_wal()
    db_f.close_wal()


def test_wal_journal_is_received_body_verbatim(tmp_path):
    """Zero-copy is load-bearing: the WAL frame body for a TBLK ingest
    IS the received column section, byte for byte, behind the
    dedup-tag table header — not a re-encode that happens to match."""
    batch = _batch(seed=2)
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    db = FlowDatabase()
    db.attach_wal(str(tmp_path / "w"), sync="always")
    im = IngestManager(db, n_shards=1)
    out = im.ingest(payload, stream="prod", seq=7)
    assert out["rows"] == len(batch)
    tag = _wal.pack_dedup_tag("flows", "prod", 7, len(batch))
    expect = _wal.pack_table_header(tag) + payload[4:]
    assert _wal_bodies(db)[-1] == expect
    im.close()
    db.close_wal()


# -- router: column gather, no full decode -------------------------------


def test_router_gather_slice_parity_vs_oracle(monkeypatch):
    """split_wire must produce exactly the slices the decode-and-split
    oracle produces, while decoding ONLY destinationIP and gathering
    everything else on the encoded bytes."""
    from theia_tpu.cluster import ClusterMap, IngestRouter, parse_peers
    from theia_tpu.store.wal import RECORD_MAGIC, decode_record_body

    batch = _batch(seed=3, n=40, t=8)
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    cmap = ClusterMap(
        parse_peers("a=http://h:1,b=http://h:2,c=http://h:3"), "a")
    r = IngestRouter(cmap)

    decoded_columns = []
    real_decode = wire.decode_columns

    def spy(buf, offset=0, columns=None):
        decoded_columns.append(columns)
        return real_decode(buf, offset, columns=columns)

    monkeypatch.setattr(wire, "decode_columns", spy)
    fwd = r.split_wire(memoryview(payload)[4:])
    monkeypatch.undo()
    assert fwd is not None
    local_wire, remote = fwd
    # every decode inside the forward path was the ownership-column
    # subset — a None (full-batch) decode fails the zero-copy claim
    assert decoded_columns and all(
        c is not None and set(c) == {"destinationIP"}
        for c in decoded_columns)

    local_oracle, remote_oracle = r.split(batch)
    omap = {p: b for (p, b) in remote_oracle}
    assert {p for (p, _, _) in remote} == set(omap)
    for peer, pay, rows in remote:
        assert pay[:4] == RECORD_MAGIC
        tname, rb = decode_record_body(pay[4:])
        assert tname == "flows" and rows == len(rb)
        assert _batch_rows(rb) == _batch_rows(omap[peer])
    lb, _end = wire.decode_columns(memoryview(local_wire))
    assert _batch_rows(lb) == _batch_rows(local_oracle)
    # row conservation
    assert len(lb) + sum(rows for (_, _, rows) in remote) == len(batch)
    r.close()


# -- crash recovery ------------------------------------------------------


def test_kill9_mid_tblk_ingest_recovery(tmp_path):
    """kill -9 after acking TBLK batches: a fresh process replays the
    verbatim-journaled frames, restores the rows AND the dedup tags,
    and answers the producer's retries duplicate:true."""
    batch = _batch(seed=13)
    payload = TblkEncoder(dicts=batch.dicts).encode(batch)
    db = FlowDatabase()
    db.attach_wal(str(tmp_path / "w"), sync="always")
    im = IngestManager(db, n_shards=1)
    for i in range(2):
        assert im.ingest(payload, stream="s", seq=i)["rows"] == \
            len(batch)
    im.close()
    # kill -9: all process state gone; reopen from disk alone
    db2 = FlowDatabase()
    stats = db2.attach_wal(str(tmp_path / "w"), sync="always")
    assert stats["recoveredRows"] == 2 * len(batch)
    assert _rows(db2) == _rows(db)
    im2 = IngestManager(db2, n_shards=1)   # seeds from recovered_acks
    for i in range(2):
        retry = im2.ingest(payload, stream="s", seq=i)
        assert retry.get("duplicate") is True
        assert retry["rows"] == len(batch)
    assert len(db2.flows) == 2 * len(batch)
    im2.close()
    db.close_wal()
    db2.close_wal()


# -- routed two-node parity (real HTTP mesh) ------------------------------


@pytest.mark.cluster
def test_routed_two_node_tblk_parity(tmp_path):
    """The byte-parity gate, routed: a TBLK producer and a TFB2
    producer against identical 2-node meshes land identical rows with
    identical spread, and the TBLK mesh's forwards ride the gather
    path (remote slices, no full-batch decode on the sender)."""
    from tests.test_cluster import free_port, make_server

    big = _batch(seed=17, n=24, t=8)

    def run(enc_cls, sub):
        ports = [free_port(), free_port()]
        peers = ",".join(f"n{i}=http://127.0.0.1:{p}"
                         for i, p in enumerate(ports))
        dbs = [FlowDatabase(), FlowDatabase()]
        for i, db in enumerate(dbs):
            db.attach_wal(str(tmp_path / sub / f"w{i}"))
        servers = [make_server(dbs[i], ports[i], peers, f"n{i}", "peer")
                   for i in range(2)]
        try:
            enc = enc_cls(dicts=big.dicts)
            acks = []
            for i in range(2):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{ports[0]}/ingest"
                    f"?stream=mesh&seq={i}",
                    data=enc.encode(big), method="POST")
                with urllib.request.urlopen(req, timeout=30) as resp:
                    acks.append(json.load(resp))
            # duplicate retry across the mesh
            req = urllib.request.Request(
                f"http://127.0.0.1:{ports[0]}/ingest"
                f"?stream=mesh&seq=1",
                data=enc.encode(big), method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                dup = json.load(resp)
            assert dup.get("duplicate") is True
            return dbs, acks
        finally:
            for s in servers:
                s.shutdown()

    dbs_t, acks_t = run(TblkEncoder, "tblk")
    dbs_f, acks_f = run(BlockEncoder, "tfb2")
    for acks in (acks_t, acks_f):
        assert [a["rows"] for a in acks] == [len(big)] * 2
        assert all(a.get("forwardedRows", 0) > 0 for a in acks)
    # same rows, same per-node placement (ownership hashes bytes, not
    # wire format), across both formats
    for i in range(2):
        assert _rows(dbs_t[i]) == _rows(dbs_f[i])
        assert len(dbs_t[i].flows) > 0
    assert sum(len(db.flows) for db in dbs_t) == 2 * len(big)
    for dbs in (dbs_t, dbs_f):
        for db in dbs:
            db.close_wal()


# -- producer surface ----------------------------------------------------


def test_make_block_encoder_honors_env(monkeypatch):
    monkeypatch.delenv("THEIA_INGEST_FORMAT", raising=False)
    assert isinstance(make_block_encoder(), TblkEncoder)
    monkeypatch.setenv("THEIA_INGEST_FORMAT", "tfb2")
    enc = make_block_encoder()
    assert isinstance(enc, BlockEncoder) and \
        not isinstance(enc, TblkEncoder)
    monkeypatch.setenv("THEIA_INGEST_FORMAT", "native")
    with pytest.raises(ValueError):
        make_block_encoder()


def test_ingest_ack_fast_path_serialization():
    from theia_tpu.manager.api import _fast_ack_bytes
    hot = [
        {"rows": 320, "alerts": 121, "traceId": "ab" * 16},
        {"rows": 0, "alerts": 0},
        {"rows": 5, "alerts": 0, "duplicate": True, "traceId": "0" * 32},
        {"rows": 320, "alerts": 9,
         "alertsByKind": {"heavy_hitter": 2, "connection_anomaly": 7},
         "traceId": "cd" * 16},
        {"rows": 1, "alerts": 0,
         "alertsByKind": {"heavy_hitter": 0, "connection_anomaly": 0}},
    ]
    for doc in hot:
        raw = _fast_ack_bytes(doc)
        assert raw == json.dumps(
            doc, separators=(",", ":")).encode()
        assert json.loads(raw) == doc
    # anything off the two hot shapes falls back to json.dumps
    cold = [
        {"rows": 5, "alerts": 0, "forwardedRows": 2},
        {"rows": 5, "alerts": 0, "degraded": "sampled"},
        {"rows": "5", "alerts": 0},
        {"rows": 5, "alerts": 0, "duplicate": False},
        {"rows": 5, "alerts": 0, "traceId": 'a"b'},
        {"rows": 5, "alerts": 1, "alertsByKind": {"heavy_hitter": 1}},
    ]
    for doc in cold:
        assert _fast_ack_bytes(doc) is None
