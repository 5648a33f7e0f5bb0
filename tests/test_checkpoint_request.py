"""A snapshot that can be asked for (store/checkpoint.py `request`,
POST /admin/checkpoint, `theia checkpoint`), the ack's `walLsn`, and
the law that ties them: a snapshot stamped at L holds exactly the rows
of the flows records with LSN <= L, and snapshot + retained log is
every acked row."""

import json
import shutil
import threading
import time
import urllib.error
import urllib.request

import pytest

from theia_tpu.cli.__main__ import main as cli_main
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.manager import TheiaManagerServer
from theia_tpu.manager.api import _fast_ack_bytes
from theia_tpu.obs import metrics, trace
from theia_tpu.store import Checkpointer, FlowDatabase
from theia_tpu.store import wire as _wire
from theia_tpu.store.checkpoint import CheckpointUnavailable
from theia_tpu.store.flow_store import CHECKPOINT_STAGES
from theia_tpu.utils import faults

SEED = 20260928


def _block(stream: int, seq: int, n_series: int = 24, points: int = 3):
    """(rows as sorted tuples, TBLK payload) of one seeded block; no
    two blocks of a test share a row (the seed moves the values)."""
    batch = generate_flows(SynthConfig(
        n_series=n_series, points_per_series=points,
        seed=SEED + 1000 * stream + seq))
    return batch, _wire.encode_block(batch)


def _rows(batch):
    """A table's rows as a sorted list of tuples, strings decoded."""
    return sorted(tuple(sorted(r.items())) for r in batch.to_rows())


def _post(port, path, data=b"", timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method="POST", data=data,
        headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


@pytest.fixture()
def deployment(tmp_path):
    """The documented deployment in one process: --db, a WAL, the
    checkpointer on its thread (an interval nobody waits for), the
    manager's own server."""
    db = FlowDatabase()
    db.attach_wal(str(tmp_path / "wal"))
    srv = TheiaManagerServer(db, port=0, ingest_shards=2)
    ck = Checkpointer(db, str(tmp_path / "db.npz"), interval=3600)
    ck.start()
    srv.attach_checkpointer(ck)
    srv.start_background()
    yield srv, db, ck, tmp_path
    ck.stop()
    srv.shutdown()
    db.close_wal()


# -- the law, under concurrent ingest -------------------------------------

def test_requested_snapshot_is_the_logs_prefix_under_concurrent_ingest(
        deployment):
    srv, db, ck, tmp = deployment
    n_streams, n_blocks = 4, 10
    blocks = {(s, q): _block(s, q) for s in range(n_streams)
              for q in range(1, n_blocks + 1)}
    acks = {}
    go = threading.Event()

    def produce(s):
        go.wait()
        for q in range(1, n_blocks + 1):
            acks[(s, q)] = _post(
                srv.port, f"/ingest?stream=s{s}&seq={q}", blocks[(s, q)][1])
            time.sleep(0.01)

    threads = [threading.Thread(target=produce, args=(s,))
               for s in range(n_streams)]
    for t in threads:
        t.start()
    go.set()
    answers = []
    for k in range(2):
        # asked for while the four streams ingest
        while len(acks) < (k + 1) * n_streams * 3:
            time.sleep(0.005)
        ans = _post(srv.port, "/admin/checkpoint")
        snap = str(tmp / f"snap{k}.npz")
        shutil.copy(str(tmp / "db.npz"), snap)
        answers.append((ans, snap))
    for t in threads:
        t.join()

    assert [a["generation"] for a, _ in answers] == [1, 2]
    assert answers[0][0]["stamp"] < answers[1][0]["stamp"]
    for ans, snap in answers:
        assert not ans["skipped"] and set(ans["stagesMs"]) == set(
            CHECKPOINT_STAGES)
        want = []
        for key, ack in acks.items():
            if ack["walLsn"] <= ans["stamp"]:
                want.extend(_rows(blocks[key][0]))
        loaded = FlowDatabase.load(snap)
        assert loaded._snapshot_lsns == [ans["stamp"]]
        got = _rows(loaded.flows.scan())
        assert len(got) == ans["rows"]
        assert got == sorted(want)         # row for row
        # per stream the blocks at or below the stamp are a prefix
        for s in range(n_streams):
            inside = [acks[(s, q)]["walLsn"] <= ans["stamp"]
                      for q in range(1, n_blocks + 1)]
            assert inside == sorted(inside, reverse=True)

    # recovery: the last snapshot + the retained log = every acked row
    db.wal_sync()
    shutil.copytree(str(tmp / "wal"), str(tmp / "wal-copy"))
    fresh = FlowDatabase.load(str(tmp / "db.npz"))
    stats = fresh.attach_wal(str(tmp / "wal-copy"))
    try:
        everything = sorted(r for b, _ in blocks.values()
                            for r in _rows(b))
        assert _rows(fresh.flows.scan()) == everything
        assert stats["recoveredRows"] == len(everything) \
            - answers[1][0]["rows"]
    finally:
        fresh.close_wal()
    # and the previous generation is there, with the first stamp
    prev = FlowDatabase.load(str(tmp / "db.npz.prev"))
    assert prev._snapshot_lsns == [answers[0][0]["stamp"]]


def test_wal_lsn_rises_per_stream_and_is_absent_without_a_wal(deployment):
    srv, *_ = deployment
    lsns = {s: [_post(srv.port, f"/ingest?stream=w{s}&seq={q}",
                      _block(s, q)[1])["walLsn"] for q in range(1, 5)]
            for s in range(2)}
    for seen in lsns.values():
        assert all(a < b for a, b in zip(seen, seen[1:]))
    assert len(set(lsns[0]) | set(lsns[1])) == 8
    dup = _post(srv.port, "/ingest?stream=w0&seq=1", _block(0, 1)[1])
    assert dup["duplicate"] is True and "walLsn" not in dup
    bare = TheiaManagerServer(FlowDatabase(), port=0, ingest_shards=2)
    bare.start_background()
    try:
        ack = _post(bare.port, "/ingest?stream=a&seq=1", _block(0, 1)[1])
        assert ack["rows"] == 72 and "walLsn" not in ack
    finally:
        bare.shutdown()


def test_ack_fast_path_carries_wal_lsn_byte_for_byte():
    doc = {"rows": 320, "alerts": 9,
           "alertsByKind": {"heavy_hitter": 2, "connection_anomaly": 7},
           "walLsn": 12345, "traceId": "cd" * 16}
    assert _fast_ack_bytes(doc) == json.dumps(
        doc, separators=(",", ":")).encode()
    del doc["traceId"]
    assert _fast_ack_bytes(doc) == json.dumps(
        doc, separators=(",", ":")).encode()
    assert _fast_ack_bytes({"rows": 1, "alerts": 0, "walLsn": 3}) is None
    assert _fast_ack_bytes({**doc, "walLsn": "7"}) is None


# -- the request ------------------------------------------------------------

def test_request_during_a_running_snapshot_gets_the_next_one(
        deployment, monkeypatch):
    srv, db, ck, _ = deployment
    _post(srv.port, "/ingest?stream=r&seq=1", _block(0, 1)[1])
    save = db.save
    started, release = threading.Event(), threading.Event()

    def slow_save(*a, **kw):
        stamp = save(*a, **kw)
        started.set()
        release.wait(10)              # published, not yet answered
        return stamp
    monkeypatch.setattr(db, "save", slow_save)
    first = {}
    t = threading.Thread(
        target=lambda: first.update(_post(srv.port, "/admin/checkpoint")))
    t.start()
    assert started.wait(10)
    assert _get(srv.port, "/healthz")["checkpoint"]["running"] is True
    # acked while the first snapshot runs, before the second is asked for
    lsn = _post(srv.port, "/ingest?stream=r&seq=2",
                _block(0, 2)[1])["walLsn"]
    second = {}
    t2 = threading.Thread(
        target=lambda: second.update(_post(srv.port, "/admin/checkpoint")))
    t2.start()
    time.sleep(0.1)
    assert not second                 # waits: one at a time
    release.set()
    t.join(10)
    t2.join(10)
    assert first["generation"] == 1 and first["stamp"] < lsn
    assert second["generation"] == 2 and second["stamp"] >= lsn
    assert second["rows"] == first["rows"] + 72


def test_a_requested_snapshot_counts_as_the_tick(tmp_path):
    db = FlowDatabase()
    db.insert_flows(_block(0, 1)[0])
    ck = Checkpointer(db, str(tmp_path / "f.npz"), interval=1.0)
    ck.start()
    try:
        time.sleep(0.5)
        assert ck._rounds.started == 0
        assert ck.request(timeout=10)["generation"] == 1
        t_end = time.monotonic()
        # the timer's own tick would have come 1.0 s after the start
        time.sleep(max(0.0, t_end + 0.75 - time.monotonic()))
        assert ck._rounds.started == 1
        deadline = t_end + 3.0
        while ck._rounds.started < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        # one interval after the requested one ENDED, and skipped:
        # nothing changed
        assert ck._rounds.started == 2 and time.monotonic() >= t_end + 0.95
        while ck.last_result.get("skipped") is not True \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert ck.last_result["skipped"] is True
        assert ck.checkpoints_written == 1
    finally:
        assert ck.stop()
    with pytest.raises(CheckpointUnavailable):
        ck.request(timeout=1)


def test_409_when_no_snapshot_can_be_asked_for():
    # a manager without --db, or with --checkpoint-interval 0, has no
    # checkpointer to hand to its server
    srv = TheiaManagerServer(FlowDatabase(), port=0, ingest_shards=2)
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/admin/checkpoint")
        assert ei.value.code == 409
        assert "checkpoint-interval" in json.loads(
            ei.value.read())["message"]
        assert "checkpoint" not in _get(srv.port, "/healthz")
    finally:
        srv.shutdown()


def test_the_request_is_token_gated(tmp_path):
    srv = TheiaManagerServer(FlowDatabase(), port=0, ingest_shards=2,
                             auth_token="sekrit")
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/admin/checkpoint")
        assert ei.value.code == 401
    finally:
        srv.shutdown()


def test_healthz_block_and_cli(deployment, capsys):
    srv, db, ck, _ = deployment
    doc = _get(srv.port, "/healthz")
    assert doc["checkpoint"] == {"intervalSeconds": 3600, "written": 0,
                                 "running": False, "lastError": None}
    assert doc["wal"]["firstRetainedLsn"] == 1
    _post(srv.port, "/ingest?stream=c&seq=1", _block(0, 1)[1])
    addr = f"http://127.0.0.1:{srv.port}"
    cli_main(["--manager-addr", addr, "checkpoint"])
    out = capsys.readouterr().out
    assert "snapshot 1: stamp 1, 72 flow rows" in out and "hold" in out
    cli_main(["--manager-addr", addr, "checkpoint", "--json"])
    assert json.loads(capsys.readouterr().out)["skipped"] is True
    last = _get(srv.port, "/healthz")["checkpoint"]
    assert last["written"] == 1 and last["last"]["skipped"] is True
    assert last["last"]["stamp"] == 1


def test_last_error_is_cleared_by_a_later_success(deployment):
    srv, db, ck, _ = deployment
    _post(srv.port, "/ingest?stream=e&seq=1", _block(0, 1)[1])
    faults.arm("checkpoint.save:error@1")
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/admin/checkpoint")
    finally:
        faults.disarm()
    assert ei.value.code == 500 and "FaultError" in json.loads(
        ei.value.read())["error"]
    assert "FaultError" in _get(
        srv.port, "/healthz")["checkpoint"]["lastError"]
    ok = _post(srv.port, "/admin/checkpoint")
    assert ok["generation"] == 1 and "error" not in ok
    block = _get(srv.port, "/healthz")["checkpoint"]
    assert block["lastError"] is None and ck.last_error is None
    counts = metrics.REGISTRY.get("theia_checkpoints_total")
    assert counts.labels(result="failed").value() >= 1
    assert counts.labels(result="written").value() >= 1


# -- stages -------------------------------------------------------------------

def test_stage_times_add_up_to_the_checkpoint_span(tmp_path):
    trace.reset()
    db = FlowDatabase()
    db.attach_wal(str(tmp_path / "wal"))
    batch = generate_flows(SynthConfig(n_series=2000, points_per_series=10,
                                       seed=SEED))
    for _ in range(8):                 # 160,000 rows: a write of ~1 s
        db.insert_flows(batch)
    ck = Checkpointer(db, str(tmp_path / "f.npz"), interval=3600)
    ck.start()
    hist = metrics.REGISTRY.get("theia_checkpoint_stage_seconds")
    before = {s: hist.labels(stage=s).count() for s in CHECKPOINT_STAGES}
    rows0 = metrics.REGISTRY.get("theia_checkpoint_rows_total").value()
    try:
        ans = ck.request(timeout=120)
    finally:
        ck.stop()
        db.close_wal()
    span = next(s for s in trace.recent(100) if s["op"] == "bg.checkpoint")
    assert list(span["stagesMs"]) == [
        "checkpoint." + s for s in CHECKPOINT_STAGES]
    total = sum(span["stagesMs"].values())
    assert total == pytest.approx(span["durationMs"], rel=0.05)
    assert sum(ans["stagesMs"].values()) == pytest.approx(total, abs=0.01)
    assert ans["seconds"] * 1e3 >= span["durationMs"]
    assert ans["rows"] == 160000
    assert ans["bytesIn"] > ans["bytes"] > 0
    for s in CHECKPOINT_STAGES:
        assert hist.labels(stage=s).count() == before[s] + 1
    assert metrics.REGISTRY.get(
        "theia_checkpoint_rows_total").value() == rows0 + 160000


def test_latch_wait_is_a_stage_of_the_request_that_waited(deployment):
    srv, db, ck, _ = deployment
    _post(srv.port, "/ingest?stream=l&seq=1", _block(0, 1)[1])
    trace.reset()
    hist = metrics.REGISTRY.get("theia_ingest_latch_wait_seconds")
    s0, n0 = hist.sum(), hist.count()
    ack = {}
    with db._wal.quiesce():            # what a snapshot's hold does
        t = threading.Thread(target=lambda: ack.update(_post(
            srv.port, "/ingest?stream=l&seq=2", _block(0, 2)[1])))
        t.start()
        time.sleep(0.3)
        assert not ack                 # held
    t.join(10)
    assert ack["rows"] == 72
    span = next(s for s in trace.recent(100)
                if s["op"] == "ingest.request")
    assert span["stagesMs"]["store.latch_wait"] >= 250.0
    assert hist.count() == n0 + 1 and hist.sum() - s0 >= 0.25
