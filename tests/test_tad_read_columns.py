"""A TAD job reads the columns its query names (analytics/series.py
`read_columns`) through the store's `select(columns=...)`, not the
table. Held here: for every mode and filter the projected batch gives
the tensors of the whole one, on the flat store and on a parts store
with sealed sorted parts and a memtable tail; `run_tad` never calls
`scan()`; `JobProgress.read` counts what the stage handed on."""

import numpy as np
import pytest

from tests.test_tad_columns import (NOW, assert_tables_equal, plain_rows,
                                    pod_label_of)
from theia_tpu.analytics import (TadQuerySpec, build_series, read_columns,
                                 run_tad)
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.obs import metrics
from theia_tpu.runner.progress import TAD_STAGES, JobProgress
from theia_tpu.schema import ColumnarBatch
from theia_tpu.store import FlowDatabase
from theia_tpu.store.parts import PART_FORMAT_SORTED

OTHER_CLUSTER = "8a6a2e0e-0000-4000-8000-000000000002"
CONNECTION_KEY = ("sourceIP", "sourceTransportPort", "destinationIP",
                  "destinationTransportPort", "protocolIdentifier",
                  "flowStartSeconds")


def _flows():
    """Two clusters' rows, shuffled so that a sorted part's order is
    not the insertion order."""
    kw = dict(n_series=40, points_per_series=12, anomaly_fraction=0.3,
              external_fraction=0.3, service_fraction=0.4)
    both = ColumnarBatch.concat([
        generate_flows(SynthConfig(seed=11, **kw)),
        generate_flows(SynthConfig(seed=12, cluster_uuid=OTHER_CLUSTER,
                                   **kw))])
    return both.take(np.random.default_rng(0).permutation(len(both)))


FLOWS = _flows()


def _flat():
    db = FlowDatabase()
    db.insert_flows(FLOWS)
    return db


def _parts():
    db = FlowDatabase(engine="parts", parts_config={
        "memtable_rows": 200,
        "sort_key": "timeInserted,destinationIP,sourceIP"})
    for lo in range(0, len(FLOWS), 110):
        db.insert_flows(FLOWS.take(np.arange(
            lo, min(lo + 110, len(FLOWS)))))
    sealed, mem = db.flows._snapshot_refs()
    assert len(sealed) > 2 and sum(map(len, mem)) > 0
    assert all(p.fmt >= PART_FORMAT_SORTED for p in sealed)
    return db


STORES = {"flat": _flat, "parts": _parts}


def _window():
    t = np.sort(np.asarray(FLOWS["flowEndSeconds"]))
    return dict(start_time=int(FLOWS["flowStartSeconds"].min()),
                end_time=int(t[len(t) * 2 // 3]))


IGNORED_NS = FLOWS.strings("sourcePodNamespace")[0]


def _passes_shared_filters():
    """Rows that the three shared filters below leave, so that a
    mode's own filter can name a value which all of them together
    still select."""
    ok = FLOWS.strings("clusterUUID") == OTHER_CLUSTER
    ok &= np.asarray(FLOWS["flowEndSeconds"]) < _window()["end_time"]
    for col in ("sourcePodNamespace", "destinationPodNamespace"):
        ok &= FLOWS.strings(col) != IGNORED_NS
    return ok


def _own(mode):
    """The mode's own filters, each a case of its own."""
    ok = _passes_shared_filters()

    def first(column, also=True):
        return next(s for s in FLOWS.strings(column)[ok & also] if s)

    pod = ok & (FLOWS.strings("destinationPodLabels") != "")
    label, name = pod_label_of(FLOWS.filter(pod))
    return {
        "connection": [],
        "pod": [("pod-label", dict(pod_label=label)),
                ("pod-name", dict(pod_name=name)),
                ("pod-namespace", dict(pod_namespace=FLOWS.strings(
                    "destinationPodNamespace")[pod][0]))],
        "external": [("external-ip", dict(external_ip=first(
            "destinationIP", np.asarray(FLOWS["flowType"]) == 3)))],
        "svc": [("svc-port-name", dict(
            svc_port_name=first("destinationServicePortName")))],
    }[mode]


def _filters(mode):
    shared = [
        ("no-filter", {}),
        ("ns-ignore", dict(ns_ignore_list=[IGNORED_NS])),
        ("cluster-uuid", dict(cluster_uuid=OTHER_CLUSTER)),
        ("window", _window()),
    ]
    every = {k: v for _, kw in shared + _own(mode) for k, v in kw.items()}
    return shared + _own(mode) + [("all-filters", every)]


CASES = [(mode, name, kw, store)
         for mode in ("connection", "pod", "external", "svc")
         for name, kw in _filters(mode) for store in STORES]


@pytest.mark.parametrize(
    "mode,kw,store", [(m, kw, s) for m, _, kw, s in CASES],
    ids=[f"{m}-{n}-{s}" for m, n, _, s in CASES])
def test_projected_batch_gives_the_whole_batchs_series(mode, kw, store):
    spec = TadQuerySpec(
        agg_flow="" if mode == "connection" else mode, **kw)
    columns = read_columns(spec)
    table = STORES[store]().flows
    projected = table.select(columns=columns)
    assert tuple(projected.column_names) == columns
    assert len(projected) == len(FLOWS)
    got = build_series(projected, spec)
    want = build_series(table.scan(), spec)
    # a filter that selected nothing would compare two empty batches
    assert want.n_series > 0 and want.mask.any()
    assert got.key_names == want.key_names
    assert got.agg_type == want.agg_type
    for name in want.key_names:
        assert got.keys[name].tolist() == want.keys[name].tolist(), name
    for field in ("values", "times", "mask"):
        g, w = getattr(got, field), getattr(want, field)
        assert g.dtype == w.dtype and g.shape == w.shape, field
        np.testing.assert_array_equal(g, w, err_msg=field)


@pytest.mark.parametrize("spec,want", [
    (TadQuerySpec(), CONNECTION_KEY + ("flowEndSeconds", "throughput")),
    (TadQuerySpec(end_time=5, refit_every=0),
     CONNECTION_KEY + ("flowEndSeconds", "throughput")),
    (TadQuerySpec(agg_flow="external", start_time=1),
     ("destinationIP", "flowType", "flowStartSeconds",
      "flowEndSeconds", "throughput")),
    (TadQuerySpec(agg_flow="svc", cluster_uuid="c"),
     ("destinationServicePortName", "flowEndSeconds", "throughput",
      "clusterUUID")),
    # the pod mode ignores the window, by upstream's rule
    (TadQuerySpec(agg_flow="pod", start_time=1, end_time=2,
                  ns_ignore_list=["kube-system"]),
     ("destinationPodNamespace", "destinationPodLabels",
      "sourcePodNamespace", "sourcePodLabels", "flowEndSeconds",
      "throughput")),
    (TadQuerySpec(agg_flow="pod", pod_name="p"),
     ("destinationPodNamespace", "destinationPodName",
      "sourcePodNamespace", "sourcePodName", "flowEndSeconds",
      "throughput")),
], ids=["empty", "end-time", "external-start", "svc-cluster",
        "pod-ignores-window", "pod-name"])
def test_read_columns_is_a_function_of_the_spec(spec, want):
    assert read_columns(spec) == want
    assert len(set(want)) == len(want)


@pytest.mark.parametrize("algo", ["EWMA", "DBSCAN"])
def test_run_tad_never_scans_the_table(algo, monkeypatch):
    db, plain = _parts(), FlowDatabase()
    plain.insert_flows(FLOWS)

    def no_scan():
        raise AssertionError("the job read the whole table")
    monkeypatch.setattr(db.flows, "scan", no_scan)
    spec = TadQuerySpec()
    run_tad(db, algo, spec, tad_id="job-a", now=NOW)
    rows = plain_rows(build_series(plain.flows.scan(), spec), algo,
                      "job-a", NOW)
    assert plain.tadetector.insert_rows(rows) == len(rows)
    assert_tables_equal(db.tadetector.scan(), plain.tadetector.scan())


def _read_counters():
    return [metrics.REGISTRY.get(f"theia_job_read_{what}_total")
            .labels(kind="tad").value()
            for what in ("rows", "columns", "bytes")]


@pytest.mark.parametrize("spec,n_columns", [
    (TadQuerySpec(), 8),
    (TadQuerySpec(agg_flow="svc"), 3),
    (TadQuerySpec(agg_flow="pod", cluster_uuid=OTHER_CLUSTER), 7),
], ids=["connection", "svc", "pod-one-cluster"])
def test_read_counters_rise_by_the_batch(spec, n_columns):
    db = _parts()
    batch = db.flows.select(columns=read_columns(spec))
    before = _read_counters()
    JobProgress("job-a", TAD_STAGES, kind="tad").read(batch)
    rows, columns, nbytes = (
        a - b for a, b in zip(_read_counters(), before))
    assert (rows, columns) == (len(FLOWS), n_columns)
    assert nbytes == sum(a.nbytes for a in batch.columns.values())
    assert 0 < nbytes <= 8 * n_columns * len(FLOWS)
    # and a job raises them by the same, through run_tad
    before = _read_counters()
    run_tad(db, "EWMA", spec, tad_id="job-b", now=NOW,
            progress=JobProgress("job-b", TAD_STAGES, kind="tad"))
    assert [a - b for a, b in zip(_read_counters(), before)] \
        == [rows, columns, nbytes]
