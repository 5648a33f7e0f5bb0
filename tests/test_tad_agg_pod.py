"""`run_tad(db, "DBSCAN", TadQuerySpec(agg_flow="pod"))` through the
store against the plain reference of upstream's pod query
(benchmarks/references/tad_agg_pod.py: numpy over the generator's own
rows, nothing of the program, the text the benchmark's check
`tad_agg_pod` reads).

The rows come from the benchmark's generator (`benchmarks/gen.py`, the
key law `spread_spikes`) as TBLK blocks, decoded and inserted as the
manager's ingest does: 64 connections x 32 points, several connections
a pod, so (key, second) cells collide and are summed. Held exactly: the
set of (podNamespace, podLabels or podName, direction, flowEndSeconds)
and `throughput` (an int64 sum below 2^53); the deviation to 1e-6;
`algoCalc` 0; `aggType` pod. On the native builder and on the numpy
fallback, unfiltered and under the pod filters."""

import numpy as np
import pytest

from benchmarks import extend, gen, manifest
from benchmarks.references import tad_agg_pod as ref
from theia_tpu.analytics import TadQuerySpec, run_tad
from theia_tpu.analytics import series as series_mod
from theia_tpu.obs import metrics, trace
from theia_tpu.runner.progress import TAD_STAGES, JobProgress
from theia_tpu.store import FlowDatabase, wire
from theia_tpu.utils.native import native_available

TRAFFIC = {"generator": {
    "law": "spread_spikes", "connections_per_producer": 64,
    "conns_per_block": 64, "points_per_conn": 8, "interval_seconds": 1,
    "base_throughput": 1e7, "spike_rate": 0.02,
    "spike_magnitude_low": 5.0, "spike_magnitude_high": 200.0}}
N_BLOCKS = 4
SEED = 2147489333
#: the population's hash spreads 64 connections over 1,024 pods and
#: nothing would collide: fold the pods onto eight a side
PODS = 8

FILTERS = {
    "unfiltered": {},
    "podLabel": {"pod_label": "APP-3-"},
    "podName+podNameSpace": {"pod_name": "pod-3-1",
                             "pod_namespace": "ns-3"},
}


@pytest.fixture
def few_pods(monkeypatch):
    """`Population` with every connection's pods folded onto `PODS` a
    side (one namespace each two pods), so that connections share
    pods; one connection in ten keeps its external destination."""
    init = gen.Population.__init__

    def folded(self, producer, n_conn, start_time=gen.DEFAULT_START):
        init(self, producer, n_conn, start_time)
        j = np.arange(n_conn)
        for side, pod in (("source", j % PODS),
                          ("destination", (j // 3) % PODS)):
            ns, p = pod // 2 + 2, pod % 2
            flat = ns * gen.PODS_PER_NAMESPACE + p
            for col, idx in ((f"{side}PodNamespace", ns),
                             (f"{side}PodName", flat),
                             (f"{side}PodLabels", flat)):
                table, was = self.strings[col]
                blank = was == len(table) - 1 if side == "destination" \
                    else np.zeros(n_conn, bool)
                self.strings[col] = (table, np.where(blank, was, idx))
    monkeypatch.setattr(gen.Population, "__init__", folded)


def database(stream):
    db = FlowDatabase()
    for b in range(N_BLOCKS):
        db.insert_flows(wire.decode_block(stream.block(b)[0]))
    return db


def answer(db, job_id):
    return [r for r in db.tadetector.scan().to_rows() if r["id"] == job_id]


@pytest.mark.parametrize("builder", ["native", "numpy"])
@pytest.mark.parametrize("case", sorted(FILTERS))
def test_run_tad_gives_the_references_rows(case, builder, few_pods,
                                           monkeypatch):
    if builder == "native" and not native_available():
        pytest.skip("native library unavailable")
    if builder == "numpy":          # as in a process without the library
        monkeypatch.setattr(series_mod, "build_padded_series",
                            lambda parts, op, dtype: None)
    extend.use(manifest.HERE)
    filters = FILTERS[case]
    db = database(gen.stream(TRAFFIC, SEED, 0))
    want = ref.pod_job([(gen.stream(TRAFFIC, SEED, 0), N_BLOCKS)],
                       **filters)
    merged = metrics.counter("theia_job_series_rows_merged_total", "",
                             ("kind", "agg")).labels(kind="tad", agg="pod")
    built = metrics.counter("theia_job_series_built_total", "",
                            ("kind", "agg")).labels(kind="tad", agg="pod")
    grouped = metrics.counter(
        "theia_job_tensorize_rows_total", "", ("kind", "path")).labels(
        kind="tad", path="columns" if builder == "native" else "numpy")
    before = merged.value(), built.value(), grouped.value()
    trace.reset()
    with trace.ingress_span("job.run", job="job", kind="tad"):
        run_tad(db, "DBSCAN", TadQuerySpec(agg_flow="pod", **filters),
                tad_id="job", mesh=None,
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
    rows = answer(db, "job")
    id_col = "podName" if "pod_name" in filters else "podLabels"
    got = {(r["podNamespace"], r[id_col], r["direction"],
            r["flowEndSeconds"]): r for r in rows}
    # the case says something: rows were summed, points were flagged
    assert want["merged"] > 0 and len(want["rows"]) > 3
    assert len(got) == len(rows) and set(got) == set(want["rows"])
    for point, (total, std) in want["rows"].items():
        r = got[point]
        assert r["throughput"] == total and total < 2 ** 53
        assert r["throughputStandardDeviation"] == pytest.approx(
            std, rel=1e-6)
        assert (r["aggType"], r["algoType"], r["algoCalc"],
                r["anomaly"]) == ("pod", "DBSCAN", 0.0, "true")
        assert (r["sourceIP"], r["destinationIP"],
                r["sourceTransportPort"], r["flowStartSeconds"],
                r["destinationServicePortName"]) == ("", "", 0, 0, "")
        assert r["podLabels" if id_col == "podName" else "podName"] == ""
    # the job's own account of its aggregation, and where it is told
    assert merged.value() - before[0] == want["merged"]
    assert built.value() - before[1] == want["series"]
    assert grouped.value() - before[2] == want["contributions"]
    (span,) = [s for s in trace.recent() if s["op"] == "job.run"]
    assert span["agg"] == "pod" and span["kind"] == "tad"
    assert {"job.tensorize.keys", "job.tensorize.group",
            "job.tensorize.decode"} <= set(span["partsMs"])


def test_a_connection_mode_job_merges_nothing():
    """Every row of a connection is a point of its own: the counter is
    there under `agg="None"` and stays at 0."""
    extend.use(manifest.HERE)
    db = database(gen.stream(TRAFFIC, SEED, 0))
    counter = metrics.counter("theia_job_series_rows_merged_total", "",
                              ("kind", "agg"))
    built = metrics.counter("theia_job_series_built_total", "",
                            ("kind", "agg")).labels(kind="tad", agg="None")
    before = built.value()
    run_tad(db, "DBSCAN", TadQuerySpec(), tad_id="conn", mesh=None,
            progress=JobProgress("conn", TAD_STAGES, kind="tad"))
    assert counter.labels(kind="tad", agg="None").value() == 0
    assert built.value() - before == 64
    from theia_tpu.obs import prom
    assert 'theia_job_series_rows_merged_total{kind="tad",agg="None"} 0' \
        in prom.render()


def test_the_empty_label_is_held_to_the_dictionary():
    """`labels <> ''` is `code != 0` because a dictionary's code 0 is
    '' by construction, whatever was ingested first: a store whose
    first row has labels on both sides keeps them."""
    extend.use(manifest.HERE)
    stream = gen.stream(TRAFFIC, SEED, 0)
    db = database(stream)
    flows = db.flows.scan()
    for col in ("sourcePodLabels", "destinationPodLabels"):
        assert flows.dicts[col].lookup("") == 0
        assert flows.dicts[col].decode_one(int(flows[col][0])) != ""
    series = series_mod.build_series(flows, TadQuerySpec(agg_flow="pod"))
    assert "" not in set(series.keys["podLabels"])
    pop = gen.Population(0, 64)
    table, idx = pop.strings["destinationPodLabels"]
    external = int((idx == len(table) - 1).sum())
    assert external > 0         # some rows do have no inbound side
