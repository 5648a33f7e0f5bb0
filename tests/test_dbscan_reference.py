"""`dbscan_scores` and the DBSCAN job's result rows against the plain
reference (tests/dbscan_reference.py: numpy, sorts where the program
tests pairs, nothing of the program; the same text the benchmark's
check reads as benchmarks/references/dbscan.py).

The suite runs in float64 over integer throughputs, where x + eps is
exact: program and reference decide every point alike, so decisions
are equal exactly and the deviations agree to REL = 1e-12 relative.

`dbscan_noise` sorts too (PR 40), but tests the rounded difference of
two values where the reference tests x + eps: it is held, bit for bit
in float32 and in float64, to the definition over all pairs
(`ref.noise_by_pairs`), which is what the program evaluated before."""

import json
import pathlib
import time
import urllib.request

import numpy as np
import pytest

from tests import dbscan_reference as ref
from theia_tpu.analytics import TadQuerySpec, build_series, run_tad
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ops.dbscan import (dbscan_noise, dbscan_scores, pair_tests,
                                  sorted_points)
from theia_tpu.schema import ColumnarBatch
from theia_tpu.store import FlowDatabase

REL = 1e-12
HERE = pathlib.Path(__file__).resolve().parent
#: (series, steps, spike rate): the cell's law at sizes a test holds
SHAPES = [(6, 700, 0.02), (3, 2304, 0.003), (4, 130, 0.05)]


def throughputs(n_series, n_steps, spike_rate, seed):
    """Integer throughputs as benchmarks/laws/spread_spikes.py draws
    them: a noisy base, and spikes of base x m, m log-uniform in
    [5, 200]."""
    rng = np.random.default_rng(seed)
    base = 1e7 * (0.5 + rng.random(n_series))[:, None]
    noise = np.clip(rng.normal(1, 0.05, (n_series, n_steps)), 0.1, None)
    spike = rng.random((n_series, n_steps)) < spike_rate
    height = np.exp(rng.uniform(np.log(5), np.log(200),
                                (n_series, n_steps)))
    x = (base * np.where(spike, height, noise)).astype(np.int64)
    return x.astype(np.float64), spike


def classes(x, mask, spike):
    """How many spikes the definition makes noise, border and core."""
    noise = border = core = 0
    for s in range(x.shape[0]):
        v = x[s, mask[s]]
        within = np.abs(v[:, None] - v[None, :]) <= ref.EPS
        is_core = within.sum(1) >= ref.MIN_SAMPLES
        is_noise = ref.noise_by_pairs(v)
        sp = spike[s, mask[s]]
        noise += int((sp & is_noise).sum())
        core += int((sp & is_core).sum())
        border += int((sp & ~is_core & ~is_noise).sum())
    return noise, border, core


def holes(shape):
    """The points a store keeps: all, but at 130 steps series 1 keeps
    two thirds of its points and series 2 three."""
    keep = np.ones(shape, bool)
    if shape[1] == 130:
        keep[1, 130 * 2 // 3:] = False
        keep[2, 3:] = False
    return keep


def database(n_series, n_steps, spike_rate, seed=23):
    """A store whose connections carry `throughputs`, and how many of
    their spikes are noise, border and core points; at 130 steps series
    1 keeps two thirds of its points and series 2 three."""
    flows = generate_flows(SynthConfig(
        n_series=n_series, points_per_series=n_steps, seed=seed))
    x, spike = throughputs(n_series, n_steps, spike_rate, seed)
    keep = holes(x.shape)
    cols = dict(flows.columns)
    cols["throughput"] = x.ravel().astype(cols["throughput"].dtype)
    rows = np.flatnonzero(keep.ravel())
    db = FlowDatabase()
    db.insert_flows(ColumnarBatch(
        {k: v[rows] for k, v in cols.items()}, flows.dicts))
    return db, classes(x, keep, spike)


def test_the_reference_is_the_definition_and_sklearns():
    """By sorting and over all pairs: the same flags, on series with
    ties, with pairs at eps exactly, and with 0 to 3 points."""
    rng = np.random.default_rng(4)
    levels = np.array([1e7, 2e8, 2.6e8, 5e8, 7.5e8, 1e9, 1.25e9])
    for n in list(range(0, 6)) + [17, 40, 90] * 20:
        x = (rng.choice(levels, n)
             + rng.integers(-2, 3, n) * rng.choice([0, 1, 1.25e8], n))
        np.testing.assert_array_equal(ref.noise_sorted(x),
                                      ref.noise_by_pairs(x))
    # a pair at eps exactly is within it: the fourth neighbour
    x = np.array([0, 1, 2, 2.5e8, 1e9])
    assert ref.noise_sorted(x).tolist() == [False] * 4 + [True]
    assert ref.noise_sorted(x[:3]).all()          # three points: no core
    x, spike = throughputs(5, 400, 0.05, 8)
    mask = np.ones(x.shape, bool)
    mask[1, 250:] = False
    calc, std, anom = ref.dbscan_scores(x, mask)
    assert not calc.any() and not anom[~mask].any()
    for s in range(5):
        np.testing.assert_array_equal(anom[s, mask[s]],
                                      ref.noise_by_pairs(x[s, mask[s]]))
        assert std[s] == pytest.approx(np.std(x[s, mask[s]], ddof=1),
                                       rel=REL)
    assert min(classes(x, mask, spike)) > 0
    cluster = pytest.importorskip("sklearn.cluster")
    for s in range(5):
        labels = cluster.DBSCAN(min_samples=4, eps=2.5e8).fit_predict(
            x[s, mask[s]].reshape(-1, 1))
        np.testing.assert_array_equal(anom[s, mask[s]], labels == -1)


def test_the_benchmarks_reference_is_this_one():
    """One text in two places: the benchmark's directory may import
    nothing of the repo's tests and the other way round (ROADMAP
    D15)."""
    assert (HERE / "dbscan_reference.py").read_bytes() == (
        HERE.parent / "benchmarks" / "references"
        / "dbscan.py").read_bytes()


@pytest.mark.parametrize("n_series,n_steps,spike_rate", SHAPES)
def test_scores_are_the_references(n_series, n_steps, spike_rate):
    """The kernel alone: its decisions are the reference's exactly."""
    x, spike = throughputs(n_series, n_steps, spike_rate, 23)
    mask = np.ones(x.shape, bool)
    mask[1, n_steps * 2 // 3:] = False
    mask[2, 3:] = n_steps != 130
    x = np.where(mask, x, 0)
    noise, border, core = classes(x, mask, spike)
    assert min(noise, border, core) > 0, (noise, border, core)
    _, std, anom = ref.dbscan_scores(x, mask)
    calc, got_std, got = (np.asarray(a) for a in
                          dbscan_scores(x, mask))
    np.testing.assert_array_equal(got, anom)
    np.testing.assert_allclose(got_std, std, rtol=REL)
    assert not calc.any()
    if n_steps == 130:
        assert anom[2, :3].all()     # three points: none core, all noise


def _law(n_series, n_steps, spike_rate):
    """The cell's law with the holes `database()` makes."""
    x, _ = throughputs(n_series, n_steps, spike_rate, 23)
    return x, holes(x.shape)


def _equal_runs():
    """Duplicates and runs of equal values, some of them long enough to
    be core on their own, at distances around eps from each other."""
    rng = np.random.default_rng(40)
    levels = np.array([1e7, 2e8, 2.6e8, 5.1e8, 7.6e8, 1.2e9, 3e9])
    x = rng.choice(levels, (5, 90), p=[.3, .2, .2, .1, .1, .05, .05])
    x[0] = 1e7                                  # one value, 90 times
    x[1, :3] = 3e9                              # a run of three far out
    return x, rng.random(x.shape) > 0.1


def _at_eps():
    """A quarter of every series' points moved to a distance of eps
    exactly (as exactly as the dtype holds it) from another point,
    below it or above it; the first three series get a point with one
    on both sides."""
    x, _ = throughputs(6, 160, 0.1, 41)
    rng = np.random.default_rng(41)
    for s in range(x.shape[0]):
        moved = rng.choice(160, 40, replace=False)
        x[s, moved] = (x[s, rng.integers(0, 160, 40)]
                       + ref.EPS * rng.choice([-1, 1], 40))
    x[:3, 1] = x[:3, 0] - ref.EPS
    x[:3, 2] = x[:3, 0] + ref.EPS
    return x, np.ones(x.shape, bool)


def _few_points():
    """Series of 0 to 7 valid points (all masked, one, fewer than
    `min_samples`, just enough), close together and apart."""
    x = np.tile(np.array([1e7, 1.1e7, 1.2e7, 9e8, 1.3e7, 1.4e7, 1.5e7,
                          1.6e7]), (9, 1))
    mask = np.arange(8)[None, :] < np.arange(9)[:, None]
    mask[8] = [False, True] * 4                 # every other point
    return x, mask


def _mask_holes():
    """Holes at the start, in the middle and at the end, over values
    that the holes would make neighbours of if they counted."""
    x, _ = throughputs(4, 96, 0.08, 42)
    mask = np.ones(x.shape, bool)
    mask[0, :30] = False
    mask[1, 20:70] = False
    mask[2, ::2] = False
    mask[3, 60:] = False
    x = np.where(mask, x, x[:, :1])         # garbage that is in reach
    return x, mask


def _leading_batch():
    """A leading batch shape [2, 3, T]."""
    x, _ = throughputs(6, 75, 0.1, 43)
    mask = np.random.default_rng(43).random(x.shape) > 0.15
    return x.reshape(2, 3, 75), mask.reshape(2, 3, 75)


NOISE_CASES = {
    "law-6x700": lambda: _law(*SHAPES[0]),
    "law-3x2304": lambda: _law(*SHAPES[1]),
    "law-4x130-holes": lambda: _law(*SHAPES[2]),
    "equal-runs": _equal_runs,
    "at-eps-exactly": _at_eps,
    "few-points": _few_points,
    "mask-holes": _mask_holes,
    "leading-batch": _leading_batch,
}


@pytest.mark.parametrize("min_samples", [1, 2, 4, 7])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", sorted(NOISE_CASES))
def test_noise_is_the_pairwise_definitions_bit_for_bit(
        case, dtype, min_samples):
    """`dbscan_noise` decides every point of every series as the
    definition over all pairs does in the same precision, whatever the
    ties, the pairs at eps exactly, the holes and `min_samples`; and
    answers in the shape and dtype it is given."""
    x, mask = NOISE_CASES[case]()
    x = x.astype(dtype)
    got = dbscan_noise(x, mask, min_samples=min_samples)
    assert got.shape == x.shape and got.dtype == bool
    got = np.asarray(got).reshape(-1, x.shape[-1])
    rows = zip(x.reshape(got.shape), mask.reshape(got.shape))
    want = np.zeros(got.shape, bool)
    for s, (v, m) in enumerate(rows):
        want[s, m] = ref.noise_by_pairs(v[m], min_samples=min_samples)
    np.testing.assert_array_equal(got, want)
    if case == "at-eps-exactly":
        # not vacuous: hundreds of pairs lie at eps as the dtype holds
        # it, and with 2 or 4 samples some decision hangs on the `<=`
        flat = x.reshape(got.shape)
        gaps = np.abs(flat[:, :, None] - flat[:, None, :])
        assert (gaps == dtype(ref.EPS)).sum() > 300
        under = np.nextafter(dtype(ref.EPS), dtype(0))
        strict = np.array([ref.noise_by_pairs(v, under, min_samples)
                           for v in flat])
        assert (strict != want).any() == (min_samples in (2, 4))


def test_the_traced_program_holds_nothing_quadratic():
    """No intermediate of `dbscan_noise` at T = 4,096 has more than a
    small multiple of S x T elements: the pairwise form's [S, T, T]
    (4,096 times S x T) fails this."""
    import jax

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (v.aval.size for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    n_series, n_steps = 2, 4096
    x = np.zeros((n_series, n_steps), np.float32)
    closed = jax.make_jaxpr(dbscan_noise)(x, x > 0)
    found = list(sizes(closed.jaxpr))
    assert len(found) > 20                      # the walk saw the body
    assert max(found) <= 2 * n_series * n_steps


def _rows_of(db, tad_id):
    return [r for r in db.tadetector.scan().to_rows() if r["id"] == tad_id]


def _decisions(series, rows):
    """{(series, step)} of result rows and the rows by that key."""
    index = {(series.keys["sourceIP"][s],
              int(series.keys["sourceTransportPort"][s])): s
             for s in range(series.n_series)}
    out = {}
    for r in rows:
        s = index[(r["sourceIP"], int(r["sourceTransportPort"]))]
        t = int(np.flatnonzero(
            series.times[s] == int(r["flowEndSeconds"]))[0])
        out[(s, t)] = r
    return out


@pytest.mark.parametrize("n_series,n_steps,spike_rate", SHAPES)
def test_job_rows_are_the_references_decisions_with_its_deviation(
        n_series, n_steps, spike_rate):
    """Through `run_tad` (what the REST job runs) on a real store:
    one row for each point the reference flags and no other, the
    reference's deviation at each, `algoCalc` 0; and the job counts
    the pairs one pass of the definition tests."""
    from theia_tpu.obs import metrics
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress

    db, (noise, border, core) = database(n_series, n_steps, spike_rate)
    assert min(noise, border, core) > 0, (noise, border, core)
    counter = metrics.REGISTRY.get("theia_job_dbscan_pair_tests_total")
    before = counter.value()
    run_tad(db, "EWMA", TadQuerySpec(), progress=JobProgress(
        "ewma", TAD_STAGES, kind="tad"))
    assert counter.value() == before
    tad_id = run_tad(db, "DBSCAN", TadQuerySpec(), now=int(time.time()),
                     progress=JobProgress("dbscan", TAD_STAGES,
                                          kind="tad"))
    series = build_series(db.flows.scan(), TadQuerySpec())
    lengths = series.mask.sum(1)
    assert series.values.shape == (n_series, n_steps)
    assert counter.value() - before == pair_tests(series.mask) \
        == int((lengths.astype(np.int64) ** 2).sum())
    if n_steps == 130:
        assert sorted(lengths) == [3, 86, 130, 130]
    _, std, anom = ref.dbscan_scores(series.values, series.mask)
    got = _decisions(series, _rows_of(db, tad_id))
    assert set(got) == set(zip(*(i.tolist() for i in np.nonzero(anom))))
    # a row for each noise spike, none for a border or a core one; the
    # three-point series' points are noise and no spikes
    assert len(got) == noise + 3 * (n_steps == 130)
    for (s, _), r in got.items():
        assert r["anomaly"] == "true" and r["algoType"] == "DBSCAN"
        assert r["algoCalc"] == 0.0 and r["refitEvery"] == 0
        assert r["throughputStandardDeviation"] == pytest.approx(
            std[s], rel=REL)


def test_a_job_counts_the_points_it_sorts():
    """A DBSCAN job through `run_tad` raises
    `theia_job_dbscan_sorted_points_total` by its valid points and
    `theia_job_dbscan_pair_tests_total` by the definition's pair
    tests, an EWMA job by nothing."""
    from theia_tpu.obs import metrics
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress

    def job(algo):
        run_tad(db, algo, TadQuerySpec(), now=int(time.time()),
                progress=JobProgress(algo, TAD_STAGES, kind="tad"))

    db, _ = database(*SHAPES[2])
    points = metrics.REGISTRY.get("theia_job_dbscan_sorted_points_total")
    pairs = metrics.REGISTRY.get("theia_job_dbscan_pair_tests_total")
    valid = 130 + 130 + 86 + 3
    before, pairs_before = points.value(), pairs.value()
    job("EWMA")
    assert (points.value(), pairs.value()) == (before, pairs_before)
    job("DBSCAN")
    assert points.value() - before == valid
    series = build_series(db.flows.scan(), TadQuerySpec())
    assert sorted_points(series.mask) == valid
    assert pairs.value() - pairs_before == pair_tests(series.mask)


def test_the_rest_path_answers_with_the_references_decisions():
    """POST {"jobType": "DBSCAN"}, thread dispatch, the polled answer's
    rows: the manager's normal path, held to the reference."""
    from theia_tpu.manager import STATE_COMPLETED, TheiaManagerServer

    db, _ = database(*SHAPES[0])
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    try:
        url = (f"http://127.0.0.1:{srv.port}/apis/intelligence.theia."
               f"antrea.io/v1alpha1/throughputanomalydetectors")
        req = urllib.request.Request(
            url, method="POST",
            data=json.dumps({"jobType": "DBSCAN"}).encode())
        with urllib.request.urlopen(req, timeout=10) as r:
            name = json.loads(r.read())["metadata"]["name"]
        assert srv.controller.wait_all()
        with urllib.request.urlopen(f"{url}/{name}", timeout=10) as r:
            answer = json.loads(r.read())
    finally:
        srv.shutdown()
    assert answer["status"]["state"] == STATE_COMPLETED
    series = build_series(db.flows.scan(), TadQuerySpec())
    _, std, anom = ref.dbscan_scores(series.values, series.mask)
    got = _decisions(series, answer["stats"])
    assert set(got) == set(zip(*(i.tolist() for i in np.nonzero(anom))))
    for (s, _), r in got.items():
        assert r["algoType"] == "DBSCAN" and float(r["algoCalc"]) == 0
        assert float(r["throughputStandardDeviation"]) == pytest.approx(
            std[s], rel=REL)


def test_a_job_that_finds_no_noise_writes_the_filler_row():
    """The built-in law's one spike height: a series' spikes are each
    other's neighbours, nothing is noise, and the job writes upstream's
    'NO ANOMALY DETECTED' row."""
    db = FlowDatabase()
    flows = generate_flows(SynthConfig(n_series=3, points_per_series=200,
                                       seed=2, base_throughput=1e7))
    rng = np.random.default_rng(2)
    x = flows.columns["throughput"].reshape(3, 200).copy()
    x[rng.random(x.shape) < 0.05] *= 50
    db.insert_flows(ColumnarBatch(
        {**flows.columns, "throughput": x.ravel()}, flows.dicts))
    tad_id = run_tad(db, "DBSCAN", TadQuerySpec(), now=1700000000)
    series = build_series(db.flows.scan(), TadQuerySpec())
    assert not ref.dbscan_scores(series.values, series.mask)[2].any()
    (row,) = _rows_of(db, tad_id)
    assert row["anomaly"] == "NO ANOMALY DETECTED"
    assert row["algoType"] == "DBSCAN" and row["algoCalc"] == 0.0
    assert row["flowStartSeconds"] == 1700000000
