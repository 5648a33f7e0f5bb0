"""`arima_scores` and the ARIMA job's result rows against the plain
float64 reference (tests/arima_reference.py: numpy, straight loops,
nothing of the program; the same text the benchmark's check reads as
benchmarks/references/arima.py).

The suite runs in float64, so program and reference do the same
arithmetic in another order: forecasts and deviations agree to
REL = 1e-9 relative, and decisions are equal wherever the point is not
within REL of its threshold."""

import pathlib
import time

import numpy as np
import pytest

from tests import arima_reference as ref
from theia_tpu.analytics import TadQuerySpec, build_series, run_tad
from theia_tpu.analytics.tad import effective_refit
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ops.arima import arima_scores
from theia_tpu.store import FlowDatabase

REL = 1e-9
HERE = pathlib.Path(__file__).resolve().parent


def batch(n_series=8, n_steps=300, seed=5, spike_rate=0.01):
    """Throughputs as the benchmark's generator draws them, with a
    masked tail, a series of 3 points, one of 4 and one that holds a
    non-positive value."""
    rng = np.random.default_rng(seed)
    base = 1e7 * (0.5 + rng.random(n_series))[:, None]
    x = base * np.clip(rng.normal(1, 0.05, (n_series, n_steps)), 0.1, None)
    x = np.where(rng.random(x.shape) < spike_rate, base * 50, x)
    x = x.astype(np.int64).astype(np.float64)
    mask = np.ones(x.shape, bool)
    mask[1, n_steps * 2 // 3:] = False
    mask[2, 3:] = False
    mask[4, 4:] = False
    x[3, 17] = 0
    return np.where(mask, x, 0), mask


def agree(got, want, x, mask):
    pred, std, anom = (np.asarray(a) for a in got)
    rpred, rstd, ranom = want
    np.testing.assert_allclose(pred, rpred, rtol=REL, atol=0)
    np.testing.assert_allclose(std, rstd, rtol=REL, equal_nan=True)
    with np.errstate(invalid="ignore"):
        on_threshold = np.abs(np.abs(x - rpred) - rstd[:, None]) \
            <= REL * rstd[:, None]
    assert not ((anom != ranom) & ~on_threshold).any()
    assert not anom[~mask].any() and not pred[~mask].any()


@pytest.mark.parametrize("refit_every", [1, 4, 0])
def test_scores_are_the_references(refit_every):
    x, mask = batch()
    k = effective_refit("ARIMA", refit_every, x.shape[1])
    assert k == (refit_every or 1)           # auto is 1 below 4,096 points
    want = ref.arima_scores(x, mask, k)
    agree(arima_scores(x, mask, refit_every=k), want, x, mask)
    pred, _, anom = want
    # too short (3 points) and non-positive: no forecast, no decision;
    # the shortest series that is scored has 4 points
    assert not pred[2].any() and not pred[3].any()
    assert not anom[2].any() and not anom[3].any()
    assert pred[4, 3] > 0
    np.testing.assert_allclose(pred[4, :3], x[4, :3], rtol=REL)
    assert anom.sum() > 20


def test_a_cadence_that_is_not_one_is_part_of_the_result():
    """The same series under k = 1 and k = 21 give other forecasts:
    the cadence a job ran with decides its rows."""
    x, mask = batch(n_series=5, n_steps=512, seed=9)
    one = ref.arima_scores(x, mask, 1)[0]
    grouped = ref.arima_scores(x, mask, 21)
    agree(arima_scores(x, mask, refit_every=21), grouped, x, mask)
    assert np.abs(one[0] / grouped[0][0] - 1)[50:].max() > 1e-4


def test_the_benchmarks_reference_is_this_one():
    """One text in two places: the benchmark's directory may import
    nothing of the repo's tests and the other way round."""
    assert (HERE / "arima_reference.py").read_bytes() == (
        HERE.parent / "benchmarks" / "references" / "arima.py").read_bytes()


def _rows_of(db, tad_id):
    return [r for r in db.tadetector.scan().to_rows() if r["id"] == tad_id]


@pytest.mark.parametrize("refit_every,n_steps", [(1, 160), (4, 160),
                                                 (0, 160), (0, 4200)])
def test_job_rows_carry_the_references_forecast_deviation_and_cadence(
        refit_every, n_steps):
    """Through `run_tad` (what the REST job runs): every result row is
    one of the reference's decisions with its forecast in `algoCalc`,
    its deviation and the cadence the job resolved; auto is 2 at 4,200
    points."""
    n_series = 6 if n_steps < 1000 else 2
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=n_series, points_per_series=n_steps, seed=11,
        base_throughput=1e7, anomaly_fraction=1.0,
        anomaly_magnitude=50.0)))
    tad_id = run_tad(db, "ARIMA", TadQuerySpec(refit_every=refit_every),
                     now=int(time.time()))
    series = build_series(db.flows.scan(), TadQuerySpec())
    k = effective_refit("ARIMA", refit_every, n_steps)
    assert k == (2 if n_steps == 4200 else refit_every or 1)
    pred, std, anom = ref.arima_scores(series.values, series.mask, k)
    rows = _rows_of(db, tad_id)
    assert len(rows) == int(anom.sum()) >= n_series
    index = {(series.keys["sourceIP"][s],
              int(series.keys["sourceTransportPort"][s])): s
             for s in range(series.n_series)}
    for r in rows:
        s = index[(r["sourceIP"], int(r["sourceTransportPort"]))]
        t = int(np.flatnonzero(series.times[s] == r["flowEndSeconds"])[0])
        assert anom[s, t] and r["anomaly"] == "true"
        assert r["algoCalc"] == pytest.approx(pred[s, t], rel=REL)
        assert r["throughputStandardDeviation"] == pytest.approx(
            std[s], rel=REL)
        assert r["refitEvery"] == k


@pytest.mark.parametrize("refit_every,n_steps,turns", [
    (1, 160, 160), (4, 160, 40), (0, 4200, 2100)])
def test_a_job_counts_its_fits_and_its_loops_turns(refit_every, n_steps,
                                                   turns):
    """`theia_job_arima_loop_iterations_total` rises by what
    `css_loop_iterations` says of the job's tensor and cadence, the
    function the kernel lays its loop out with; the fits as before,
    series x groups; an EWMA job moves neither."""
    from theia_tpu.obs import metrics
    from theia_tpu.ops.arima import css_loop_iterations
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress

    def read():
        return tuple(metrics.REGISTRY.get(name).value() for name in (
            "theia_job_arima_fits_total",
            "theia_job_arima_loop_iterations_total"))

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=2, points_per_series=n_steps, seed=11,
        base_throughput=1e7)))
    fits0, turns0 = read()
    run_tad(db, "EWMA", TadQuerySpec(), progress=JobProgress(
        "ewma", TAD_STAGES, kind="tad"))
    assert read() == (fits0, turns0)
    run_tad(db, "ARIMA", TadQuerySpec(refit_every=refit_every),
            progress=JobProgress("arima", TAD_STAGES, kind="tad"))
    k = effective_refit("ARIMA", refit_every, n_steps)
    assert css_loop_iterations(2, n_steps, k) == turns
    assert read() == (fits0 + 2 * -(-n_steps // k), turns0 + turns)
