"""Stage self-times, profiler annotations and the capture summary
(obs/trace.py stages, obs/xplane.py, manager/profiling.py) and the
call sites that name the parts of a request, a job and a panel."""

import gc
import io
import json
import tarfile
import threading
import time

import numpy as np
import pytest

from theia_tpu.analytics import TadQuerySpec, run_tad
from theia_tpu.analytics.streaming import (H2D_BYTES, DETECTOR_STAGE,
                                           StreamingDetector)
from theia_tpu.cli.__main__ import main as cli_main
from theia_tpu.dashboards import queries
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.manager import ingest as ingest_mod
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.manager.profiling import ProfileManager
from theia_tpu.obs import metrics, trace, xplane
from theia_tpu.runner.progress import TAD_STAGES, JobProgress
from theia_tpu.store import FlowDatabase
from theia_tpu.store import wire as _wire


@pytest.fixture(autouse=True)
def _clean_ring():
    trace.reset()
    yield
    trace.set_annotation_factory(None)
    trace.reset()


def _hist(name, **labels):
    child = metrics.REGISTRY.get(name)
    if labels:
        child = child.labels(**labels)
    return child.sum(), child.count()


def _span(op):
    return next(s for s in trace.recent(1000) if s["op"] == op)


# -- the primitive -------------------------------------------------------

def test_stage_self_time_lands_in_span_and_histogram():
    h = metrics.histogram("t_stage_seconds", "test",
                          labelnames=("stage",))
    outer, inner = h.labels(stage="outer"), h.labels(stage="inner")
    with trace.span("req"):
        for _ in range(2):            # a repeated stage sums
            with trace.stage("outer", outer):
                time.sleep(0.01)
                with trace.stage("inner", inner):
                    time.sleep(0.02)
    rec = _span("req")
    st = rec["stagesMs"]
    assert set(st) == {"outer", "inner"}
    assert 40.0 <= st["inner"] < 60.0
    # self time: the nested stage is not counted twice
    assert 20.0 <= st["outer"] < 35.0
    assert st["outer"] + st["inner"] <= rec["durationMs"]
    assert outer.count() == inner.count() == 2
    assert outer.sum() * 1e3 == pytest.approx(st["outer"], abs=0.01)


def test_stage_outside_any_span_feeds_only_its_histogram():
    h = metrics.histogram("t_lone_seconds", "test")
    before = h.count()
    with trace.stage("lonely", h):
        pass
    assert h.count() == before + 1
    assert trace.recent() == []


def test_stage_marks_close_the_stage_before():
    h = metrics.histogram("t_marks_seconds", "test",
                          labelnames=("stage",))
    marks = trace.StageMarks()
    with trace.span("run"):
        marks.mark("m.a", h.labels(stage="a"))
        time.sleep(0.01)
        marks.mark("m.b", h.labels(stage="b"))
        time.sleep(0.02)
        marks.end()
        marks.end()                    # idempotent
    st = _span("run")["stagesMs"]
    assert list(st) == ["m.a", "m.b"]
    assert st["m.a"] >= 10.0 and st["m.b"] >= 20.0
    assert h.labels(stage="a").count() == 1
    assert h.labels(stage="b").count() == 1


def test_ingress_span_records_thread_cpu_time():
    with trace.ingress_span("cpu.req"):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.02:   # burn CPU
            pass
        time.sleep(0.05)                         # and wait
    rec = _span("cpu.req")
    assert 20.0 <= rec["cpuMs"] < rec["durationMs"] - 40.0
    with trace.span("inner.only"):
        pass
    assert "cpuMs" not in _span("inner.only")


class _FakeAnnotation:
    def __init__(self, log, name):
        self.log, self.name = log, name
        log.append(("made", name))

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_no_annotation_is_built_without_a_capture():
    log = []

    def work():
        with trace.ingress_span("a.req"):
            with trace.stage("a.stage"):
                pass
        with trace.background("a_task"):
            pass

    work()
    assert log == []
    trace.set_annotation_factory(lambda n: _FakeAnnotation(log, n))
    work()
    trace.set_annotation_factory(None)
    assert [e for e in log if e[0] == "made"] == [
        ("made", "a.req"), ("made", "a.stage"), ("made", "bg.a_task")]
    # properly nested: the stage closes inside the span
    assert log.index(("exit", "a.stage")) < log.index(("exit", "a.req"))
    n = len(log)
    work()
    assert len(log) == n


def test_background_span_feeds_histogram_and_ring():
    _, before = _hist("theia_background_seconds", task="unit")
    with trace.background("unit", rows=3):
        time.sleep(0.005)
    s, after = _hist("theia_background_seconds", task="unit")
    assert after == before + 1 and s >= 0.005
    rec = _span("bg.unit")
    assert rec["rows"] == 3 and rec["durationMs"] >= 5.0


def test_full_collections_are_reported_as_bg_gc():
    _, before = _hist("theia_background_seconds", task="gc")
    trace.watch_gc()
    trace.watch_gc()                   # idempotent
    try:
        with trace.span("victim"):
            gc.collect()               # generation 2
        gc.collect(0)                  # young generations: not reported
    finally:
        trace.unwatch_gc()
    spans = [s for s in trace.recent(100) if s["op"] == "bg.gc"]
    assert len(spans) == 1
    assert spans[0]["parent"] == "victim"
    assert _hist("theia_background_seconds", task="gc")[1] == before + 1
    assert trace._on_gc not in gc.callbacks


# -- the detector leg ----------------------------------------------------

def _detector_sums():
    return {key[0]: (child.sum(), child.count())
            for key, child in DETECTOR_STAGE.children()}


def _block(seed=3, n_series=64, points=4):
    batch = generate_flows(SynthConfig(n_series=n_series,
                                       points_per_series=points,
                                       seed=seed))
    return batch, _wire.encode_block(batch)


def test_detector_stages_sum_to_the_leg_over_four_shards():
    batch, payload = _block()
    mgr = IngestManager(FlowDatabase(), n_shards=4)
    try:
        mgr.ingest(payload, stream="warm")       # compiles
        trace.reset()
        before = _detector_sums()
        leg0 = ingest_mod._M_STAGE_DET.sum()
        cpu0 = ingest_mod._M_STAGE_CPU_DET.count()
        out = mgr.ingest(_block(seed=4)[1], stream="s")
    finally:
        mgr.close()
    leg = ingest_mod._M_STAGE_DET.sum() - leg0
    after = _detector_sums()
    delta = {k: after[k][0] - before.get(k, (0.0, 0))[0] for k in after}
    count = {k: after[k][1] - before.get(k, (0.0, 0))[1] for k in after}
    assert set(delta) >= {"remap", "partition", "heavy_hitters",
                          "plan", "dispatch", "fetch", "alerts"}
    # one request: remap and partition once, the per-shard stages
    # once per shard
    assert count["remap"] == count["partition"] == 1
    assert count["plan"] == count["dispatch"] == count["fetch"] == 4
    assert count["heavy_hitters"] == 4
    total = sum(delta.values())
    assert 0.8 * leg <= total <= leg * 1.001, (delta, leg)
    assert ingest_mod._M_STAGE_CPU_DET.count() == cpu0 + 1
    # the same seconds ride on the request's span, so the slowest
    # exemplar carries the breakdown
    rec = _span("ingest.request")
    st = rec["stagesMs"]
    assert sum(st.values()) == pytest.approx(total * 1e3, rel=0.02)
    assert st["detector.dispatch"] > 0 and rec["cpuMs"] > 0
    assert trace.slowest()["ingest.request"]["stagesMs"]
    kinds = out["alertsByKind"]
    assert set(kinds) == {"heavy_hitter", "connection_anomaly"}
    assert sum(kinds.values()) == out["alerts"]


def test_lock_wait_grows_while_another_thread_holds_the_shards():
    batch, _ = _block()
    mgr = IngestManager(FlowDatabase(), n_shards=2)
    try:
        mgr.score_batch(batch)                   # compiles
        s0, n0 = _hist("theia_detector_stage_seconds",
                       stage="lock_wait")
        held, release = threading.Event(), threading.Event()

        def holder():
            for s in mgr.shards:
                s.lock.acquire()
            held.set()
            release.wait(10)
            for s in mgr.shards:
                s.lock.release()

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(10)
        timer = threading.Timer(0.2, release.set)
        timer.start()
        with trace.span("blocked"):
            mgr.score_batch(batch)
        t.join(10)
        assert not t.is_alive()
    finally:
        mgr.close()
    s1, n1 = _hist("theia_detector_stage_seconds", stage="lock_wait")
    assert n1 > n0 and s1 - s0 >= 0.15
    assert _span("blocked")["stagesMs"]["detector.lock_wait"] >= 150.0


def test_h2d_bytes_count_the_padded_tile():
    batch, _ = _block(n_series=70, points=3)
    twin = StreamingDetector()
    keys = np.stack([np.asarray(batch[c], np.int64) for c in
                     ("sourceIP", "sourceTransportPort",
                      "destinationIP", "destinationTransportPort",
                      "protocolIdentifier", "flowStartSeconds")],
                    axis=1)
    plan = twin.build_plan(keys, np.asarray(batch["throughput"],
                                            np.float64))
    assert plan.x.shape[1] == 128               # 70 slots pad to 128
    before = H2D_BYTES.value()
    StreamingDetector().ingest(batch)
    assert H2D_BYTES.value() - before == (
        plan.slots.nbytes + plan.x.nbytes + plan.active.nbytes)


@pytest.mark.parametrize("n_shards,bytes_a_row", [(8, 80), (1, 0)])
def test_partition_bytes_count_what_the_slices_hold(n_shards,
                                                    bytes_a_row):
    """`theia_detector_partition_bytes_total` grows by the slices'
    bytes once a block, inside the partition stage: ten 8-byte columns
    a row over several shards, nothing for a block taken whole."""
    batch, payload = _block(n_series=96, points=4)
    mgr = IngestManager(FlowDatabase(), n_shards=n_shards)
    try:
        scored, shard_ids = mgr._remap_global(batch)
        c0 = ingest_mod._M_PARTITION_BYTES.value()
        parts = list(mgr._partition(scored, shard_ids))
        held = sum(col.nbytes for _, part in parts
                   for col in part.columns.values())
        grown = ingest_mod._M_PARTITION_BYTES.value() - c0
        assert grown == len(batch) * bytes_a_row
        if n_shards > 1:
            assert len(parts) > 1 and grown == held
            # under half of what 52-column slices would copy
            assert grown < 0.5 * sum(
                v.nbytes for v in scored.columns.values())
        # the request path counts the same, once a block
        c1 = ingest_mod._M_PARTITION_BYTES.value()
        n0 = _hist("theia_detector_stage_seconds",
                   stage="partition")[1]
        mgr.ingest(payload, stream="s")
        assert (ingest_mod._M_PARTITION_BYTES.value() - c1
                == len(batch) * bytes_a_row)
        assert _hist("theia_detector_stage_seconds",
                     stage="partition")[1] == n0 + 1
    finally:
        mgr.close()


# -- jobs ----------------------------------------------------------------

def test_job_progress_stage_seconds_sum_to_the_run():
    before = {s: _hist("theia_job_stage_seconds", kind="unit",
                       stage=s)[1] for s in TAD_STAGES}
    with trace.span("job.run"):
        p = JobProgress("j1", TAD_STAGES, kind="unit")
        for s in TAD_STAGES:
            p.stage(s)
            time.sleep(0.01)
        p.done()
        assert p.snapshot()["state"] == "COMPLETED"
    rec = _span("job.run")
    st = rec["stagesMs"]
    assert list(st) == ["job." + s for s in TAD_STAGES]
    assert sum(st.values()) == pytest.approx(rec["durationMs"],
                                             rel=0.05)
    for s in TAD_STAGES:
        assert _hist("theia_job_stage_seconds", kind="unit",
                     stage=s)[1] == before[s] + 1


def test_tad_run_and_its_result_answer_are_timed_by_part():
    from theia_tpu.manager.jobs import JobController
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=16, points_per_series=24, seed=5)))
    ctl = JobController(db, workers=1)
    try:
        rec = ctl.create("tad", {"jobType": "EWMA"})
        deadline = time.time() + 120
        while rec.state not in ("COMPLETED", "FAILED") \
                and time.time() < deadline:
            time.sleep(0.05)
        assert rec.state == "COMPLETED", rec.status_dict()
        r0 = _hist("theia_job_result_seconds", kind="tad",
                   phase="rows")[1]
        rows = ctl.result_stats("tad", rec.name)
        assert rows
        assert _hist("theia_job_result_seconds", kind="tad",
                     phase="rows")[1] == r0 + 1
    finally:
        ctl.shutdown()
    run = _span("job.run")
    assert list(run["stagesMs"]) == ["job." + s for s in TAD_STAGES]
    assert sum(run["stagesMs"].values()) <= run["durationMs"]
    for s in TAD_STAGES:
        assert _hist("theia_job_stage_seconds", kind="tad",
                     stage=s)[1] >= 1
    # the parts of `score` lie inside it and take nothing from it
    parts = run["partsMs"]
    assert list(parts) == ["job.score." + p
                           for p in ("transfer", "kernel", "rows")]
    assert sum(parts.values()) <= run["stagesMs"]["job.score"]
    for p in ("transfer", "kernel", "rows"):
        assert _hist("theia_job_stage_part_seconds", kind="tad",
                     stage="score", part=p)[1] >= 1


def test_get_of_a_completed_job_is_timed_counted_and_makes_no_row(
        monkeypatch):
    """The served path: GET of a completed EWMA job by name. Each of
    the answer's three phases is observed once, its bytes and rows are
    counted, and no row dict is made on the way (to_rows may raise)."""
    import urllib.request

    from theia_tpu.manager import TheiaManagerServer
    from theia_tpu.schema.columnar import ColumnarBatch
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=16, points_per_series=24, seed=5)))
    srv = TheiaManagerServer(db, port=0, workers=1)
    srv.start_background()

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=60) as r:
            return r.status, r.headers["Content-Length"], r.read()

    try:
        rec = srv.controller.create("tad", {"jobType": "EWMA"})
        assert srv.controller.wait_all(120)
        assert rec.state == "COMPLETED", rec.status_dict()
        n_rows = len(db.tadetector.scan())
        assert n_rows
        path = ("/apis/intelligence.theia.antrea.io/v1alpha1/"
                "throughputanomalydetectors/" + rec.name)
        phases = ("rows", "encode", "send")
        n0 = {p: _hist("theia_job_result_seconds", kind="tad",
                       phase=p)[1] for p in phases}
        sent = metrics.REGISTRY.get(
            "theia_job_result_bytes_total").labels(kind="tad")
        carried = metrics.REGISTRY.get(
            "theia_job_result_rows_total").labels(kind="tad")
        bytes0, rows0 = sent.value(), carried.value()
        status, length, body = get(path)
        assert status == 200 and int(length) == len(body)
        doc = json.loads(body)
        assert doc["status"]["state"] == "COMPLETED"
        assert len(doc["stats"]) == n_rows
        # `send` is observed once the socket write has returned, which
        # a client on the same host can outrun: give it a moment
        deadline = time.time() + 5
        while _hist("theia_job_result_seconds", kind="tad",
                    phase="send")[1] == n0["send"] \
                and time.time() < deadline:
            time.sleep(0.01)
        for p in phases:
            assert _hist("theia_job_result_seconds", kind="tad",
                         phase=p)[1] == n0[p] + 1
        assert sent.value() == bytes0 + len(body)
        assert carried.value() == rows0 + n_rows
        assert doc["stats"] == srv.controller.tad_stats(rec.name)

        def no_rows(self, schema=None):
            raise AssertionError("the GET path made row dicts")
        monkeypatch.setattr(ColumnarBatch, "to_rows", no_rows)
        assert get(path) == (200, length, body)
        assert carried.value() == rows0 + 2 * n_rows
    finally:
        srv.shutdown()


def test_run_tad_without_a_controller_still_times_stages():
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=8, points_per_series=12, seed=6)))
    n0 = _hist("theia_job_stage_seconds", kind="", stage="score")[1] \
        if metrics.REGISTRY.get("theia_job_stage_seconds") else 0
    run_tad(db, "EWMA", TadQuerySpec(), tad_id="lib-1",
            progress=JobProgress("lib-1", TAD_STAGES))
    assert _hist("theia_job_stage_seconds", kind="",
                 stage="score")[1] == n0 + 1


# -- panels --------------------------------------------------------------

def test_a_panel_request_feeds_its_histogram_and_rows_counter():
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=32, points_per_series=12, service_fraction=0.3,
        protected_fraction=0.4, seed=17)))
    view_rows = len(db.views["flows_pod_view"].scan())
    _, n0 = _hist("theia_dashboard_panel_seconds", panel="pod_to_pod")
    rows0 = metrics.REGISTRY.get(
        "theia_dashboard_rows_scanned_total").value()
    stage0 = {s: _hist("theia_dashboard_stage_seconds", stage=s)[1]
              for s in ("scan", "aggregate", "encode")}
    raw = queries.panel_json(db, "pod_to_pod", {"k": "3", "limit": "9"})
    doc = json.loads(raw)
    assert doc["dashboard"] == "pod_to_pod" and doc["data"]["links"]
    assert doc["data"] == json.loads(json.dumps(
        queries.pod_to_pod(db, k=3), default=str))
    assert _hist("theia_dashboard_panel_seconds",
                 panel="pod_to_pod")[1] == n0 + 1
    # one request scanned the view once; the second call above (the
    # comparison) is counted too, outside any panel span
    assert metrics.REGISTRY.get(
        "theia_dashboard_rows_scanned_total").value() \
        == rows0 + 2 * view_rows
    for s, n in stage0.items():
        assert _hist("theia_dashboard_stage_seconds",
                     stage=s)[1] >= n + 1
    rec = _span("dashboard.panel")
    assert rec["panel"] == "pod_to_pod" and rec["rows"] == view_rows
    assert set(rec["stagesMs"]) == {"dash.scan", "dash.aggregate",
                                    "dash.encode"}
    with pytest.raises(KeyError):
        queries.panel_json(db, "no_such_panel", {})


# -- the capture ---------------------------------------------------------

def test_summarize_names_an_idle_gap_by_the_host_stage_open_in_it():
    ev = xplane.Event
    prog = (xplane.PROGRAM_STAT,)
    planes = {
        "/device:TPU:0": [xplane.Line(1, "XLA Ops", [
            ev("fusion.1", 0.000, 0.001, ()),
            # 0.400 s with nothing on the device, ended by a copy
            ev("copy-start.7", 0.401, 0.001, ()),
            ev("fusion.2", 0.402, 0.002, ()),
            ev("fusion.3", 0.500, 0.001, ())]),
            xplane.Line(2, "XLA Modules", [
                ev("jit_stream_update_sparse(1)", 0.401, 0.003, ())])],
        "/device:CUSTOM:Megascale Trace": [xplane.Line(3, "x", [
            ev("noise", 0.1, 0.2, ())])],
        "/host:CPU": [
            xplane.Line(11, "python", [
                ev("ingest.request", 0.000, 0.450, prog, "req-1"),
                ev("detector.remap", 0.010, 0.090, prog, "req-1"),
                ev("detector.plan", 0.100, 0.280, prog, "req-1"),
                ev("detector.dispatch", 0.380, 0.022, prog, "req-1"),
                ev("PjitFunction(f)", 0.381, 0.020, ())]),
            xplane.Line(12, "python", [
                ev("bg.gc", 0.300, 0.050, prog, "req-2")])],
    }
    doc = xplane.summarize_planes(planes)
    assert doc["devices"] == ["/device:TPU:0"]
    assert doc["deviceBusySeconds"] == pytest.approx(0.005)
    assert doc["deviceIdleShare"] == pytest.approx(1 - 0.005 / 0.501)
    gap = doc["idleGaps"][0]
    assert gap["seconds"] == pytest.approx(0.400)
    assert gap["endedBy"] == "copy-start.7"
    top = gap["host"][0]
    assert top["thread"] == "req-1"
    assert top["span"] == "ingest.request > detector.plan"
    assert top["share"] == pytest.approx(0.280 / 0.400)
    spans = {(h["thread"], h["span"]): h["share"] for h in gap["host"]}
    assert spans[("req-2", "bg.gc")] == pytest.approx(0.125)
    assert spans[("req-1", "ingest.request > detector.remap")] \
        == pytest.approx(0.090 / 0.400)
    # only the program's annotations name a gap
    assert not any("Pjit" in h["span"] for h in gap["host"])
    assert doc["annotations"]["detector.plan"]["calls"] == 1
    assert "idle gaps" in xplane.render(doc)


def _await(pm, timeout=240):
    deadline = time.time() + timeout
    while pm.status == "collecting" and time.time() < deadline:
        time.sleep(0.05)
    assert pm.status == "collected", pm.to_api()


def test_capture_holds_the_programs_stages_on_the_request_thread(
        tmp_path, capsys):
    batch, _ = _block()
    det = StreamingDetector()
    det.ingest(batch)                              # compiles
    pm = ProfileManager()
    doc = pm.create(duration_seconds=1.0)
    assert doc["pythonTracer"] is False
    stop = threading.Event()

    def requests():
        while not stop.is_set():
            with trace.ingress_span("ingest.request"):
                det.ingest(batch)
            time.sleep(0.01)

    t = threading.Thread(target=requests, name="req-thread-7")
    t.start()
    try:
        _await(pm)
    finally:
        stop.set()
        t.join(10)
    assert trace._annotate is None                 # off again
    status = pm.to_api()
    started, stopped = status["startedAt"], status["stoppedAt"]
    assert stopped["wall"] - started["wall"] >= 1.0
    assert stopped["monotonic"] - started["monotonic"] >= 1.0

    data = pm.data()
    tar = tarfile.open(fileobj=io.BytesIO(data), mode="r:gz")
    pb = next(m for m in tar.getmembers()
              if m.name.endswith(".xplane.pb"))
    capture = json.load(tar.extractfile(
        next(m for m in tar.getmembers()
             if m.name.endswith("capture.json"))))
    assert capture["pythonTracer"] is False
    planes, env = xplane.read_xspace(tar.extractfile(pb).read())
    host = [ln for name, lines in planes.items()
            if name.startswith("/host:") for ln in lines]
    plan_lines = [ln for ln in host
                  if any(e.name == "detector.plan" for e in ln.events)]
    assert len(plan_lines) == 1                    # one thread's line
    own = [e for e in plan_lines[0].events
           if xplane.PROGRAM_STAT in e.stats]
    assert {e.thread for e in own} == {"req-thread-7"}
    assert {"ingest.request", "detector.plan", "detector.dispatch",
            "detector.fetch"} <= {e.name for e in own}
    # the Python tracer is off: no interpreter frames in the trace
    names = {e.name for ln in host for e in ln.events}
    assert not any(n.startswith("$") for n in names), sorted(names)[:9]

    summary = pm.summary()
    assert summary is pm.summary()                 # computed once
    assert summary["annotations"]["detector.plan"]["calls"] >= 1
    assert summary["devices"] == ["cpu"]
    # the capture's start, as served, precedes the first device event
    first_ns = (summary["profileStartNs"]
                + summary["firstDeviceEventSeconds"] * 1e9)
    assert started["wall"] * 1e9 <= first_ns
    assert summary["startedAt"] == started
    stacks = {h["span"] for g in summary["idleGaps"] for h in g["host"]}
    assert any(s.startswith("ingest.request") for s in stacks)

    out = tmp_path / "p.tar.gz"
    out.write_bytes(data)
    cli_main(["profile", "--summarize", str(out)])
    text = capsys.readouterr().out
    assert "detector.plan" in text and "longest idle gaps" in text


def test_the_wire_reader_agrees_with_jaxs_own():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    import glob
    import tempfile
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("outer", theia="me"):
            f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
        mine, _ = xplane.read_xspace(open(path, "rb").read())
        theirs = ProfileData.from_file(path)
        for plane in theirs.planes:
            want = sorted((ev.name, round(ev.start_ns), round(
                ev.duration_ns)) for ln in plane.lines
                for ev in ln.events)
            got = sorted((e.name, round(e.start_s * 1e9),
                          round(e.duration_s * 1e9))
                         for ln in mine.get(plane.name, [])
                         for e in ln.events)
            assert got == want, plane.name
        assert xplane.summarize(d)["annotations"]["outer"]["calls"] == 1
