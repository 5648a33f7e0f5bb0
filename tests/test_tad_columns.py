"""A TAD job's result rows are born as one ColumnarBatch
(analytics/tad.py `detect_anomalies`) and inserted as one batch. They
are held here against the plain way, written out below: a dict a row
out of a Python loop over the anomalous points, `from_rows` after it."""

import numpy as np
import pytest

from theia_tpu.analytics import (TadQuerySpec, build_series, run_tad,
                                 score_series)
from theia_tpu.analytics.tad import effective_refit
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.manager.results import ResultColumns, select_job
from theia_tpu.obs import metrics, prom
from theia_tpu.parallel import job_mesh
from theia_tpu.schema import TADETECTOR_SCHEMA, ColumnarBatch
from theia_tpu.store import (FlowDatabase, ReplicatedFlowDatabase,
                             ShardedFlowDatabase)

NOW = 1727539200


def plain_rows(batch, algo, tad_id, now, refit_every=1):
    """The reference: what `detect_anomalies` did before its rows were
    columns."""
    refit = effective_refit(
        algo, refit_every,
        batch.values.shape[1] if batch.n_series else 0)
    filler = [{
        "sourceIP": "None", "sourceTransportPort": 0,
        "destinationIP": "None", "destinationTransportPort": 0,
        "protocolIdentifier": 0, "flowStartSeconds": int(now),
        "podNamespace": "None", "podLabels": "None", "podName": "None",
        "destinationServicePortName": "None", "direction": "None",
        "flowEndSeconds": 0, "throughputStandardDeviation": 0.0,
        "aggType": batch.agg_type, "algoType": algo, "algoCalc": 0.0,
        "throughput": 0.0, "anomaly": "NO ANOMALY DETECTED",
        "refitEvery": refit, "id": tad_id}]
    if batch.n_series == 0:
        return filler
    calc, std, anom = score_series(batch.values, batch.mask, algo,
                                   refit_every=refit if refit else 1,
                                   mesh=job_mesh())
    sidx, tidx = np.nonzero(anom)
    if sidx.size == 0:
        return filler
    std = np.nan_to_num(std, nan=0.0)
    rows = []
    for s, t in zip(sidx, tidx):
        row = {
            "aggType": batch.agg_type,
            "algoType": algo,
            "flowEndSeconds": int(batch.times[s, t]),
            "throughputStandardDeviation": float(std[s]),
            "algoCalc": float(calc[s, t]),
            "throughput": float(batch.values[s, t]),
            "anomaly": "true",
            "refitEvery": refit,
            "id": tad_id,
        }
        for key_name in batch.key_names:
            v = batch.keys[key_name][s]
            row[key_name] = v.item() if isinstance(v, np.generic) else v
        rows.append(row)
    return rows


def flows(**kw):
    cfg = dict(n_series=48, points_per_series=30, anomaly_fraction=0.3,
               anomaly_magnitude=50.0, external_fraction=0.3,
               service_fraction=0.4, seed=11)
    cfg.update(kw)
    return generate_flows(SynthConfig(**cfg))


def pod_label_of(batch):
    """A label value some destination pod of `batch` carries, and a pod
    name, for the filtered pod modes."""
    labels = [s for s in batch.strings("destinationPodLabels") if s]
    names = [s for s in batch.strings("destinationPodName") if s]
    return labels[0].split('"')[3], names[0]


# mode → (spec of a job with series, spec of one that selects none)
def _modes(batch):
    label, name = pod_label_of(batch)
    return {
        "connection": (TadQuerySpec(), TadQuerySpec(end_time=1)),
        "pod-label": (TadQuerySpec(agg_flow="pod", pod_label=label),
                      TadQuerySpec(agg_flow="pod",
                                   pod_label="no-such-label")),
        "pod-name": (TadQuerySpec(agg_flow="pod", pod_name=name),
                     TadQuerySpec(agg_flow="pod", pod_name="no-pod")),
        "external": (TadQuerySpec(agg_flow="external"),
                     TadQuerySpec(agg_flow="external",
                                  external_ip="192.0.2.255")),
        "svc": (TadQuerySpec(agg_flow="svc"),
                TadQuerySpec(agg_flow="svc", svc_port_name="no/svc")),
    }


MODES = ("connection", "pod-label", "pod-name", "external", "svc")
CASES = [(m, "EWMA", o) for m in MODES
         for o in ("anomalies", "no-anomalous-point", "no-series")]
CASES += [("connection", "ARIMA", "anomalies"),
          ("connection", "DBSCAN", "anomalies")]


def assert_tables_equal(got: ColumnarBatch, want: ColumnarBatch):
    assert list(got.column_names) == [c.name for c in TADETECTOR_SCHEMA]
    assert len(got) == len(want)
    for col in TADETECTOR_SCHEMA:
        g, w = got[col.name], want[col.name]
        assert g.dtype == w.dtype == np.dtype(col.host_dtype), col.name
        # the arrays as stored, row for row: a string column's codes
        # too, which are the WAL record's
        np.testing.assert_array_equal(g, w, err_msg=col.name)
        if col.is_string:
            assert got.dicts[col.name]._strings \
                == want.dicts[col.name]._strings, col.name
            assert got.strings(col.name).tolist() \
                == want.strings(col.name).tolist(), col.name


@pytest.mark.parametrize("mode,algo,outcome", CASES,
                         ids=["-".join(c) for c in CASES])
def test_table_after_run_tad_equals_the_plain_ways(mode, algo, outcome):
    kw = {}
    if algo == "ARIMA":
        kw = dict(n_series=12, points_per_series=24)
    elif algo == "DBSCAN":
        kw = dict(base_throughput=1e7, anomaly_magnitude=100.0)
    if outcome == "no-anomalous-point":
        # a series of one point has no deviation to exceed
        kw = dict(points_per_series=1)
    batch = flows(**kw)
    with_series, without = _modes(batch)[mode]
    spec = without if outcome == "no-series" else with_series
    db, plain = FlowDatabase(), FlowDatabase()
    db.insert_flows(batch)
    plain.insert_flows(batch)
    # two jobs, so that the second meets a table whose dictionaries
    # already hold most of its strings
    for job_id in ("job-a", "job-b"):
        run_tad(db, algo, spec, tad_id=job_id, now=NOW)
        rows = plain_rows(build_series(plain.flows.scan(), spec), algo,
                          job_id, NOW, spec.refit_every)
        assert plain.tadetector.insert_rows(rows) == len(rows)
    got, want = db.tadetector.scan(), plain.tadetector.scan()
    n_true = int((want.strings("anomaly") == "true").sum())
    if outcome == "anomalies":
        assert n_true == len(want) > 2
    else:
        assert n_true == 0 and len(want) == 2
        assert want.strings("anomaly").tolist() \
            == ["NO ANOMALY DETECTED"] * 2
    assert_tables_equal(got, want)
    # the polled answer's rows (manager/results.py), byte for byte
    for job_id in ("job-a", "job-b"):
        a, b = (ResultColumns(select_job(t, job_id), TADETECTOR_SCHEMA)
                .json_bytes('{"stats": ', "}") for t in (got, want))
        assert a == b and a.count(b'"id": "') == len(want) // 2


def test_run_tad_builds_no_row_dict(monkeypatch):
    def no_rows(*a, **kw):
        raise AssertionError("the job path made row dicts")
    monkeypatch.setattr(ColumnarBatch, "from_rows", no_rows)
    monkeypatch.setattr(ColumnarBatch, "to_rows", no_rows)
    db = FlowDatabase()
    db.insert_flows(flows())
    run_tad(db, "EWMA", TadQuerySpec(), tad_id="job-a", now=NOW)
    result = db.tadetector.scan()
    assert len(result) > 2
    assert set(result.strings("anomaly")) == {"true"}


def _sorted_cells(table: ColumnarBatch):
    cells = [table.strings(c.name).tolist() if c.is_string
             else table[c.name].tolist() for c in TADETECTOR_SCHEMA]
    return sorted(zip(*cells))


@pytest.mark.parametrize("make", [
    lambda: ShardedFlowDatabase(n_shards=2, seed=3),
    lambda: ReplicatedFlowDatabase(replicas=2)],
    ids=["sharded-2", "replicated-2"])
def test_store_facades_take_the_batch(make):
    batch = flows()
    single, db = FlowDatabase(), make()
    for store in (single, db):
        store.insert_flows(batch)
        run_tad(store, "EWMA", TadQuerySpec(agg_flow="pod"),
                tad_id="job-a", now=NOW)
    want = _sorted_cells(single.tadetector.scan())
    assert len(want) > 2
    assert _sorted_cells(db.tadetector.scan()) == want
    for replica in getattr(db, "replicas", ()):
        assert _sorted_cells(replica.tadetector.scan()) == want
    for shard in getattr(db, "shards", ()):
        assert 0 < len(shard.tadetector) < len(want)


def test_adoption_maps_stay_bounded_over_200_jobs():
    db = FlowDatabase()
    db.insert_flows(flows(n_series=6, points_per_series=16))
    for i in range(200):
        run_tad(db, "EWMA", TadQuerySpec(), tad_id=f"job-{i}", now=NOW)
    maps = db.tadetector._adopt_maps
    assert set(maps) == {c.name for c in TADETECTOR_SCHEMA
                         if c.is_string}
    for name, mapper in maps.items():
        assert 0 < len(mapper._maps) <= mapper.max_entries, name
    result = db.tadetector.scan()
    ids = result.strings("id")
    assert len(set(ids)) == 200
    # every job wrote what the first did, and the dictionaries grew by
    # the ids alone
    assert len(result) == 200 * int((ids == "job-0").sum())
    assert len(db.tadetector.dicts["id"]) == 201
    assert len(db.tadetector.dicts["sourceIP"]) <= 7


def test_rows_written_counter_is_the_answers_rows():
    """`theia_job_rows_written_total{kind="tad"}` rises by the rows the
    job's batch holds, `theia_job_bytes_written_total` by its columns'
    bytes, and the answer that carries the rows counts as many."""
    import urllib.request

    from theia_tpu.manager import TheiaManagerServer
    db = FlowDatabase()
    db.insert_flows(flows())
    srv = TheiaManagerServer(db, port=0, workers=1)
    srv.start_background()

    def value(name):
        return metrics.REGISTRY.get(name).labels(kind="tad").value()

    names = ("theia_job_rows_written_total",
             "theia_job_bytes_written_total",
             "theia_job_result_rows_total")
    try:
        before = [value(n) for n in names]
        rec = srv.controller.create("tad", {"jobType": "EWMA"})
        assert srv.controller.wait_all(120)
        assert rec.state == "COMPLETED", rec.status_dict()
        result = db.tadetector.scan()
        path = ("/apis/intelligence.theia.antrea.io/v1alpha1/"
                "throughputanomalydetectors/" + rec.name)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=60) as r:
            assert r.status == 200 and r.read()
        written, nbytes, carried = (
            value(n) - b for n, b in zip(names, before))
    finally:
        srv.shutdown()
    assert written == carried == len(result) > 2
    assert nbytes == sum(a.nbytes for a in result.columns.values())
    assert f'theia_job_rows_written_total{{kind="tad"}} {value(names[0]):g}' \
        in prom.render().replace(".0\n", "\n")


@pytest.mark.parametrize("mode", MODES)
def test_tensorize_rows_counter_names_the_path(monkeypatch, mode):
    """A TAD job through `JobProgress` raises
    `theia_job_tensorize_rows_total{kind="tad",path="columns"}` by the
    rows its filters kept (both sides' in the pod modes), and
    `{path="numpy"}` instead, and by as many, with the native builder
    away (as in a process without the library); a job that keeps no
    row raises neither."""
    from theia_tpu.analytics import series as series_mod
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.utils.native import build_padded_series

    batch = flows()
    spec, none = _modes(batch)[mode]
    db = FlowDatabase()
    db.insert_flows(batch)
    counter = metrics.REGISTRY.get("theia_job_tensorize_rows_total")
    points = np.count_nonzero(build_series(db.flows.scan(), spec).mask)

    def value(path):
        return counter.labels(kind="tad", path=path).value()

    def rise(path, other, job_spec):
        monkeypatch.setattr(
            series_mod, "build_padded_series",
            build_padded_series if path == "columns"
            else lambda parts, op, dtype: None)
        before, before_other = value(path), value(other)
        run_tad(db, "EWMA", job_spec, now=NOW,
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
        assert value(other) == before_other
        return value(path) - before

    rows = rise("columns", "numpy", spec)
    assert rows == rise("numpy", "columns", spec)
    # every kept row is a point or merges into one
    assert len(batch) * (2 if "pod" in mode else 1) >= rows >= points > 0
    if mode == "connection":
        assert rows == len(batch)
    assert rise("columns", "numpy", none) == 0
    assert rise("numpy", "columns", none) == 0
    text = prom.render()
    for path in ("columns", "numpy"):
        assert (f'theia_job_tensorize_rows_total{{kind="tad",'
                f'path="{path}"}} ') in text


@pytest.mark.parametrize("mode", MODES + ("pod",))
def test_tensorize_series_counter_names_the_way(monkeypatch, mode):
    """A TAD job through `JobProgress` raises
    `theia_job_tensorize_series_total{kind="tad",how=...}` by the
    series the native builder wrote each way: a connection's rows lie
    in time order, so a connection-mode job's series are all `cursor`
    and `cells` reads 0; a pod's connections follow one another, so
    its series with more than one go to `cells`; nothing here is
    `sorted`; the ways add up to the series built; and the numpy path
    (no builder) moves none of them."""
    from theia_tpu.analytics import series as series_mod
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress

    batch = flows()
    spec = (TadQuerySpec(agg_flow="pod") if mode == "pod"
            else _modes(batch)[mode][0])
    db = FlowDatabase()
    db.insert_flows(batch)
    counter = metrics.REGISTRY.get("theia_job_tensorize_series_total")
    n_series = build_series(db.flows.scan(), spec).n_series

    def rise():
        hows = ("cursor", "cells", "sorted")
        before = [counter.labels(kind="tad", how=h).value() for h in hows]
        run_tad(db, "EWMA", spec, now=NOW,
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
        return tuple(counter.labels(kind="tad", how=h).value() - b
                     for h, b in zip(hows, before))

    cursor, cells, in_sort = rise()
    assert cursor + cells == n_series > 0 and in_sort == 0
    if mode in ("connection", "external"):
        assert cells == 0
    else:
        assert cells > 0
    text = prom.render()
    for how in ("cursor", "cells", "sorted"):
        assert (f'theia_job_tensorize_series_total{{kind="tad",'
                f'how="{how}"}} ') in text
    monkeypatch.setattr(series_mod, "build_padded_series",
                        lambda parts, op, dtype: None)
    assert rise() == (0, 0, 0)
