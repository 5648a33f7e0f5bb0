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
def _clean_ring(monkeypatch):
    trace.reset()
    # every span's stages are read, however short it is (the budget
    # has its own test below)
    monkeypatch.setattr(trace, "READING_BUDGET", 1.0)
    trace._local.read_after = 0.0
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


# -- what a stage's thread did with its wall ------------------------------

def _spin(seconds):
    """Burn `seconds` of this thread's CPU time (however long the
    machine takes to grant them)."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _touch(buf):
    """Write one byte of every 4 KiB page of `buf`."""
    memoryview(buf)[::4096] = b"\1" * (len(buf) // 4096)


#: a stage's CPU comes from the thread's rusage, which the kernel
#: brings up to date at its scheduler tick: one run is good to a tick
#: (1-4 ms on a plain kernel, 10 ms under a sandboxed one)
TICK_MS = 12.0

needs_rusage = pytest.mark.skipif(
    not trace.HAS_THREAD_RUSAGE,
    reason="no getrusage(RUSAGE_THREAD) on this platform")


def test_a_stage_that_spins_and_one_that_sleeps_report_their_cpu():
    series = trace.StageSeries("t_usage_seconds", "test", ("stage",))
    spin, sleep = series.labels(stage="spin"), series.labels(stage="sleep")
    with trace.span("usage"):
        with trace.stage("u.spin", spin) as st_spin:
            _spin(0.1)
        with trace.stage("u.sleep", sleep) as st_sleep:
            time.sleep(0.1)
    rec = _span("usage")
    wall, cpu = rec["stagesMs"], rec["stagesCpuMs"]
    assert set(cpu) == set(wall) == {"u.spin", "u.sleep"}
    assert 100.0 - TICK_MS <= cpu["u.spin"] <= wall["u.spin"] + TICK_MS
    assert cpu["u.sleep"] <= TICK_MS < 0.2 * wall["u.sleep"]
    # the same numbers on the stage, the series and the span
    assert st_spin.cpu_seconds * 1e3 == pytest.approx(cpu["u.spin"],
                                                      abs=0.01)
    assert st_sleep.seconds >= 0.1
    assert spin.cpu.sum() == pytest.approx(st_spin.cpu_seconds)
    assert spin.wall.count() == spin.cpu.count() == 1
    assert sleep.cpu.sum() < 0.2 * sleep.wall.sum()
    reg = metrics.REGISTRY
    assert reg.get("t_usage_cpu_seconds").labelnames == ("stage",)
    if trace.HAS_THREAD_RUSAGE:
        assert set(rec["stagesSysMs"]) == set(rec["stagesFaults"]) \
            == set(wall)
        assert rec["stagesSysMs"]["u.spin"] <= cpu["u.spin"]
        assert reg.get("t_usage_minor_faults_total").kind == "counter"
    else:       # absent, never 0
        assert "stagesFaults" not in rec and "stagesSysMs" not in rec
        assert spin.faults is None and st_spin.faults is None
    assert "stagesGcMs" not in rec      # nothing collected: left out


@needs_rusage
def test_a_fresh_mapping_faults_many_times_what_a_touched_one_does():
    import mmap
    series = trace.StageSeries("t_fault_seconds", "test", ("stage",))
    fresh, again = (series.labels(stage=s) for s in ("fresh", "again"))
    buf = mmap.mmap(-1, 64 << 20)
    try:
        with trace.span("faults"):
            with trace.stage("f.fresh", fresh):
                _touch(buf)
            with trace.stage("f.again", again):
                _touch(buf)
    finally:
        buf.close()
    flt = _span("faults")["stagesFaults"]
    # a ratio: pages are 4 KiB here and 2 MiB under transparent huge
    # pages, and the second pass faults next to nothing either way
    assert flt["f.fresh"] >= 16 and flt["f.fresh"] >= 8 * (
        flt["f.again"] + 1)
    assert fresh.faults.value() == flt["f.fresh"]
    assert again.faults.value() == flt["f.again"]


def test_nested_stages_account_cpu_and_faults_to_themselves():
    import mmap
    buf = mmap.mmap(-1, 16 << 20)
    try:
        with trace.span("nest"):
            with trace.stage("n.outer") as outer:
                _spin(0.05)
                with trace.stage("n.inner") as inner:
                    _spin(0.1)
                    _touch(buf)
    finally:
        buf.close()
    rec = _span("nest")
    cpu = rec["stagesCpuMs"]
    assert 100.0 - TICK_MS <= cpu["n.inner"] \
        <= rec["stagesMs"]["n.inner"] + TICK_MS
    # the outer stage keeps its own 50 ms, not the inner's 100
    assert 50.0 - TICK_MS <= cpu["n.outer"] <= 50.0 + 2 * TICK_MS
    assert cpu["n.outer"] + cpu["n.inner"] <= rec["durationMs"] + TICK_MS
    assert outer.cpu_seconds * 1e3 == pytest.approx(cpu["n.outer"],
                                                    abs=0.01)
    if trace.HAS_THREAD_RUSAGE:
        flt = rec["stagesFaults"]
        assert flt["n.inner"] >= 8 * (flt["n.outer"] + 1)
        assert inner.faults == flt["n.inner"]


def test_a_part_is_whole_and_leaves_its_stages_numbers_whole():
    series = trace.StageSeries("t_part_seconds", "test", ("part",))
    child = series.labels(part="p")
    with trace.span("parts"):
        with trace.stage("s.stage") as st:
            _spin(0.03)
            with trace.part("s.stage.p", child) as pt:
                _spin(0.06)
                with trace.stage("s.nested"):
                    _spin(0.03)
    rec = _span("parts")
    # the part holds the stage nested in it; the stage that encloses
    # the part loses the nested stage and keeps the part
    assert rec["partsMs"]["s.stage.p"] >= 90.0 - TICK_MS
    assert rec["partsCpuMs"]["s.stage.p"] >= 90.0 - TICK_MS
    assert rec["stagesMs"]["s.stage"] >= 90.0 - TICK_MS
    assert 90.0 - TICK_MS <= rec["stagesCpuMs"]["s.stage"]
    assert 30.0 - TICK_MS <= rec["stagesCpuMs"]["s.nested"] \
        <= 30.0 + 2 * TICK_MS
    assert sum(rec["stagesMs"].values()) <= rec["durationMs"]
    assert pt.cpu_seconds <= pt.seconds + TICK_MS / 1e3
    assert st.cpu_seconds * 1e3 == pytest.approx(
        rec["stagesCpuMs"]["s.stage"], abs=0.01)
    assert child.cpu.count() == 1
    if trace.HAS_THREAD_RUSAGE:
        assert set(rec["partsFaults"]) == {"s.stage.p"}


def test_neighbouring_edges_share_one_reading(monkeypatch):
    """A stage's exit and the next one's enter, a few microseconds
    apart on one thread, are one system call; edges further apart
    than the window are not."""
    calls = []
    real = trace._read_usage

    def counted():
        calls.append(threading.get_ident())
        return real()

    monkeypatch.setattr(trace, "_read_usage", counted)
    monkeypatch.setattr(trace, "SHARED_READING_SECONDS", 1e-3)

    def two(gap):
        time.sleep(0.005)               # past any earlier reading
        del calls[:]
        with trace.stage("near.a") as a:
            _spin(0.005)
        time.sleep(gap)
        with trace.stage("near.b") as b:
            _spin(0.005)
        return len(calls), a, b

    n, a, b = two(0.0)
    assert n == 3                       # a's enter, the shared edge, b's exit
    assert a.cpu_seconds + b.cpu_seconds >= 0.010 - TICK_MS / 1e3
    assert two(0.005)[0] == 4
    # another thread has its own reading: nothing is shared across
    t = threading.Thread(target=two, args=(0.0,))
    del calls[:]
    t.start()
    t.join(10)
    assert not t.is_alive() and len(set(calls)) == 1


def test_the_reading_budget_skips_spans_and_the_next_stands_for_them(
        monkeypatch):
    """A thread whose readings took more than READING_BUDGET of the
    time since its last read span began times the next spans by the
    wall alone; the first one read after that counts its CPU once for
    each of them, so the series' sums and counts stay whole."""
    series = trace.StageSeries("t_budget_seconds", "test", ("stage",))
    child = series.labels(stage="b")
    monkeypatch.setattr(trace, "_read_usage",
                        lambda real=trace._read_usage: (
                            time.sleep(0.01), real())[1])
    monkeypatch.setattr(trace, "READING_BUDGET", 0.05)

    def request():
        with trace.span("budget.req"):
            with trace.stage("b.stage", child) as st:
                _spin(0.005)
        return st

    first = request()                   # read: two readings, 20 ms
    assert first.cpu_seconds is not None
    # 20 ms at 5 % are paid off 400 ms after it began
    skipped = [request(), request()]
    assert [st.cpu_seconds for st in skipped] == [None, None]
    assert all(st.seconds >= 0.005 for st in skipped)
    time.sleep(0.5)
    last = request()
    assert last.cpu_seconds >= 0.0
    recs = [s for s in trace.recent(100) if s["op"] == "budget.req"]
    assert [r.get("usageWeight") for r in recs] == [3, None, None, 1]
    assert ["stagesCpuMs" in r for r in recs] == [True, False, False,
                                                  True]
    assert all("b.stage" in r["stagesMs"] for r in recs)
    assert child.wall.count() == 4      # every run, by the wall
    assert child.cpu.count() == 1 + 3   # the last one stands for three
    assert child.cpu.sum() == pytest.approx(
        first.cpu_seconds + 3 * last.cpu_seconds)
    # the exemplar is the slowest span that says what it did
    assert "usageWeight" in trace.slowest()["budget.req"]


def test_add_stage_reports_the_pool_threads_numbers():
    done = []

    def leg():
        with trace.stage("pool.leg") as st:     # no span on this thread
            _spin(0.06)
        done.append(st)

    t = threading.Thread(target=leg)
    with trace.span("request"):
        t.start()
        time.sleep(0.1)                         # this thread: no CPU
        t.join(10)
        assert not t.is_alive()
        trace.add_stage(done[0])
        trace.add_stage(done[0])                # a second leg adds up
    rec = _span("request")
    assert rec["stagesMs"]["pool.leg"] >= 120.0 - 2 * TICK_MS
    assert rec["stagesCpuMs"]["pool.leg"] >= 120.0 - 2 * TICK_MS
    assert rec["stagesCpuMs"]["pool.leg"] == pytest.approx(
        2e3 * done[0].cpu_seconds, abs=0.01)
    if trace.HAS_THREAD_RUSAGE:
        assert rec["stagesFaults"]["pool.leg"] == 2 * done[0].faults
    trace.add_stage(done[0])                    # outside a span: nothing


def test_a_young_collection_lands_on_the_open_stage_and_the_counters():
    def counters(gen):
        return tuple(metrics.REGISTRY.get(name).labels(
            generation=str(gen)).value() for name in (
            "theia_gc_pause_seconds_total", "theia_gc_collections_total"))

    trace.watch_gc()
    try:
        before = [counters(g) for g in range(3)]
        with trace.span("collected"):
            with trace.stage("gc.victim") as st:
                with trace.part("gc.victim.part"):
                    gc.collect(0)
            with trace.stage("gc.bystander"):
                pass
        after = [counters(g) for g in range(3)]
    finally:
        trace.unwatch_gc()
    rec = _span("collected")
    assert list(rec["stagesGcMs"]) == ["gc.victim"]
    assert 0 < rec["stagesGcMs"]["gc.victim"] <= rec["stagesMs"]["gc.victim"]
    assert st.gc_seconds * 1e3 == pytest.approx(
        rec["stagesGcMs"]["gc.victim"], abs=0.01)
    assert after[0][1] >= before[0][1] + 1
    assert after[0][0] - before[0][0] >= st.gc_seconds
    assert after[2] == before[2]                # no full collection
    assert not [s for s in trace.recent(100) if s["op"] == "bg.gc"]
    text = __import__("theia_tpu.obs.prom", fromlist=["render"]).render()
    assert 'theia_gc_collections_total{generation="0"}' in text


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
            for key, child in DETECTOR_STAGE.wall.children()}


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
        leg0 = ingest_mod._M_LEG_DET.wall.sum()
        cpu0 = ingest_mod._M_LEG_DET.cpu.count()
        out = mgr.ingest(_block(seed=4)[1], stream="s")
    finally:
        mgr.close()
    leg = ingest_mod._M_LEG_DET.wall.sum() - leg0
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
    assert ingest_mod._M_LEG_DET.cpu.count() == cpu0 + 1
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
    # the parts of a stage lie inside it and take nothing from it
    parts = run["partsMs"]
    named = {"tensorize": ("keys", "group", "decode"),
             "score": ("transfer", "kernel", "rows")}
    assert list(parts) == [f"job.{s}.{p}" for s in named
                           for p in named[s]]
    for s, ps in named.items():
        assert sum(parts[f"job.{s}.{p}"] for p in ps) \
            <= run["stagesMs"]["job." + s]
        for p in ps:
            assert _hist("theia_job_stage_part_seconds", kind="tad",
                         stage=s, part=p)[1] >= 1


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


@pytest.fixture(scope="module")
def wide_db():
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=2048, points_per_series=24, seed=9)))
    return db


@pytest.mark.parametrize("mode", ["", "pod"])
def test_tensorize_is_covered_by_its_three_parts(wide_db, mode):
    """keys + group + decode are the stage but for its bookkeeping,
    each observed once a job with its CPU beside it."""
    kind = "parts-" + (mode or "connection")
    with trace.span("job.run"):
        run_tad(wide_db, "EWMA", TadQuerySpec(agg_flow=mode),
                progress=JobProgress("p-" + mode, TAD_STAGES, kind=kind))
    rec = _span("job.run")
    names = ["job.tensorize." + p for p in ("keys", "group", "decode")]
    assert [k for k in rec["partsMs"] if "tensorize" in k] == names
    stage = rec["stagesMs"]["job.tensorize"]
    covered = sum(rec["partsMs"][n] for n in names)
    assert 0.85 * stage <= covered <= stage, rec["partsMs"]
    assert sum(rec["partsCpuMs"][n] for n in names) \
        <= rec["stagesCpuMs"]["job.tensorize"] + 1e-3
    for p in ("keys", "group", "decode"):
        labels = dict(kind=kind, stage="tensorize", part=p)
        assert _hist("theia_job_stage_part_seconds", **labels)[1] == 1
        assert _hist("theia_job_stage_part_cpu_seconds", **labels)[1] == 1
    assert _hist("theia_job_stage_cpu_seconds", kind=kind,
                 stage="tensorize")[1] == 1


#: every series this file's mechanism adds, by what makes it appear
STAGE_USAGE_SERIES = [
    f"theia_{family}_{kind}" for family in (
        "detector_stage", "ingest_stage", "job_stage", "job_stage_part",
        "job_result", "dashboard_stage", "checkpoint_stage")
    for kind in ("cpu_seconds_count", "minor_faults_total")
] + ["theia_gc_pause_seconds_total", "theia_gc_collections_total"]


def test_every_usage_series_is_served_after_one_of_each_request(
        tmp_path):
    """One ingest request, one job with its result fetched, one panel
    and one snapshot: /metrics then has a sample of every CPU
    histogram and fault counter beside its wall histogram, and the
    slowest spans carry the fields."""
    import urllib.request

    from theia_tpu.manager import TheiaManagerServer
    from theia_tpu.store import Checkpointer
    db = FlowDatabase()
    db.attach_wal(str(tmp_path / "wal"))
    srv = TheiaManagerServer(db, port=0, ingest_shards=2, workers=1)
    ck = Checkpointer(db, str(tmp_path / "db.npz"), interval=3600)
    ck.start()
    srv.attach_checkpointer(ck)
    srv.start_background()

    def call(path, data=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}{path}", data=data,
            method="GET" if data is None else "POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.read()

    try:
        call("/ingest?stream=s&seq=1", _block(n_series=48)[1])
        rec = srv.controller.create("tad", {"jobType": "EWMA"})
        assert srv.controller.wait_all(120)
        assert rec.state == "COMPLETED", rec.status_dict()
        call("/apis/intelligence.theia.antrea.io/v1alpha1/"
             "throughputanomalydetectors/" + rec.name)
        call("/dashboards/api/pod_to_pod")
        assert json.loads(call("/admin/checkpoint", b""))["rows"]
        text = call("/metrics").decode()
        slowest = json.loads(call("/debug/traces"))["slowest"]
    finally:
        ck.stop()
        srv.shutdown()
        db.close_wal()
    present = {line.split("{")[0].split(" ")[0]
               for line in text.splitlines() if line[:1] != "#"}
    wanted = [s for s in STAGE_USAGE_SERIES if trace.HAS_THREAD_RUSAGE
              or not s.endswith("_minor_faults_total")]
    assert not [s for s in wanted if s not in present]
    for family in ("detector_stage", "job_stage", "job_stage_part",
                   "job_result", "dashboard_stage", "checkpoint_stage"):
        wall = f"theia_{family}_seconds_count{{"
        cpu = f"theia_{family}_cpu_seconds_count{{"
        # the same label sets on both, so a reader changes one name
        assert sorted(ln.split("}")[0][len(cpu):]
                      for ln in text.splitlines() if ln.startswith(cpu)) \
            == sorted(ln.split("}")[0][len(wall):]
                      for ln in text.splitlines() if ln.startswith(wall))
    leg = 'theia_ingest_stage_cpu_seconds_count{stage="detector"} '
    assert [int(ln[len(leg):]) >= 1 for ln in text.splitlines()
            if ln.startswith(leg)] == [True]
    for op, name in (("ingest.request", "detector.plan"),
                     ("job.run", "job.tensorize"),
                     ("dashboard.panel", "dash.scan"),
                     ("bg.checkpoint", "checkpoint.hold")):
        span = slowest[op]
        assert name in span["stagesCpuMs"], (op, span)
        assert span["stagesCpuMs"][name] <= \
            span["stagesMs"][name] + TICK_MS
        if trace.HAS_THREAD_RUSAGE:
            assert name in span["stagesFaults"]
            assert name in span["stagesSysMs"]
    request = slowest["ingest.request"]
    assert "ingest.detector" in request["partsCpuMs"]
    assert "store.latch_wait" in request["stagesCpuMs"]
    assert "job.tensorize.keys" in slowest["job.run"]["partsCpuMs"]


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
