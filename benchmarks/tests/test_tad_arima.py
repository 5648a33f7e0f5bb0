"""checks/tad_arima.py is sharp, and references/arima.py is one thing:
over rows made here the way the job's answer carries them, the
reference's own result is correct, float32 arithmetic in the program's
place is correct under the cell's limits, and one perturbed forecast,
deviation, cadence or decision, the bfloat16 control and a kernel that
returns the previous point as its forecast each give `correct: false`
by the number that names the fault. No manager is started: the check
reads records and a job's answer, and these are records and an answer."""

import json

import numpy as np
import pytest

from benchmarks import check as _check
from benchmarks import control, extend, gen, manifest
from benchmarks import reference as series
from benchmarks.checks import tad_arima as ta
from benchmarks.kernels import arima_scores as kernel
from benchmarks.references import arima as ref

BENCH = manifest.load()
CELL = "parts-fused-12h.tad-arima"
SHIPPED = BENCH.traffic(BENCH.cell(CELL)["traffic"])
#: the cell's law and limits at a size a test can hold: 6 connections x
#: 8 blocks of 64 points, a fit every 4 points, spikes often enough
TRAFFIC = {
    "name": "t", "limits": SHIPPED["limits"],
    "generator": {**SHIPPED["generator"], "connections_per_producer": 6,
                  "conns_per_block": 6, "points_per_conn": 64,
                  "spike_rate": 0.02},
    "workers": [{"role": "producer", "count": 1},
                {"role": "jobs", "count": 1, "job": {
                    "resource": "throughputanomalydetectors",
                    "spec": {"jobType": "ARIMA", "refitEvery": 4}}}],
}
SEED = 2147489333
N_BLOCKS = 8
REFIT = 4


def scored(precision="f64"):
    stream = gen.stream(TRAFFIC, SEED, 0)
    vals, times, mask = series.series_of(stream, N_BLOCKS)
    pred, std, anom = ref.arima_scores(vals, mask, REFIT, precision)
    return stream, vals, times, pred, std, anom


def answer(stream, times, pred, std, anom, refit=REFIT):
    """A COMPLETED job's answer: the anomalous points as the result
    rows' strings, keyed the way the generator lays connections out."""
    rows = []
    for c, t in zip(*np.nonzero(anom)):
        c = int(c)
        rows.append({
            "sourceIP": f"10.{stream.producer}.0.{c}",
            "sourceTransportPort": str(32768 + c % gen.PORT_SPAN),
            "flowStartSeconds": str(stream.start - 10 - c // gen.PORT_SPAN),
            "flowEndSeconds": str(int(times[c, t])),
            "algoCalc": repr(float(pred[c, t])),
            "throughputStandardDeviation": repr(float(std[c])),
            "refitEvery": str(refit), "anomaly": "true"})
    return rows


def ctx_of(rows, state="COMPLETED"):
    acked = [{"status": 200, "block": b} for b in range(N_BLOCKS)]
    return {
        "traffic": TRAFFIC, "seed": SEED,
        "specs": [{"role": "producer", "producer": 0}, {"role": "jobs"}],
        "preload": [{"records": acked}, {"records": []}],
        "warm": [{"records": []}] * 2, "probes": [{"records": []}] * 2,
        "results": [{"records": []},
                    {"records": [{"state": state}],
                     "last_result": json.dumps({"stats": rows})}],
    }


def failed(rows, **kw):
    rep = _check.Report()
    ta.check(ctx_of(rows, **kw), rep)
    doc = rep.doc()
    bad = sorted(k for k, v in doc["numbers"].items()
                 if v["value"] > v["limit"])
    assert doc["correct"] is (not bad)
    return bad


def test_the_references_own_rows_are_correct():
    stream, _, times, pred, std, anom = scored()
    assert anom.sum() > 20
    assert failed(answer(stream, times, pred, std, anom)) == []


def test_float32_in_the_programs_place_is_correct():
    stream, _, times, pred, std, anom = scored("f32")
    assert failed(answer(stream, times, pred, std, anom)) == []


def _perturbed(case):
    stream, vals, times, pred, std, anom = scored()
    pred, std, anom = pred.copy(), std.copy(), anom.copy()
    c, t = (int(i[3]) for i in np.nonzero(anom))
    if case == "one_forecast":
        # a spike's row, its forecast near the series' level
        c, t = (int(i) for i in np.argwhere(
            anom & (vals > 20 * pred))[3])
        pred[c, t] *= 1.002
    elif case == "one_deviation":
        std[c] *= 1.002
    elif case == "one_decision_left_out":
        anom[c, t] = False
    elif case == "one_decision_too_many":
        quiet = np.argwhere(~anom[:, 10:])[0]
        anom[quiet[0], 10 + quiet[1]] = True
    elif case == "previous_point_as_forecast":
        pred = np.concatenate([vals[:, :1], vals[:, :-1]], 1).astype(float)
        anom = np.abs(vals - pred) > std[:, None]
    rows = answer(stream, times, pred, std, anom)
    if case == "one_cadence":
        rows[2]["refitEvery"] = "1"
    return rows


CASES = {
    "one_forecast": ["arima_forecast_gap"],
    "one_deviation": ["arima_stddev_gap"],
    "one_cadence": ["arima_refit_gap"],
    "one_decision_left_out": ["arima_decision_mismatch"],
    "one_decision_too_many": ["arima_decision_mismatch"],
    # it flags the point after every spike as well, and forecasts the
    # noise of the point before
    "previous_point_as_forecast": ["arima_decision_mismatch",
                                   "arima_forecast_gap"],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_perturbed_answer_is_not_correct(case):
    assert failed(_perturbed(case)) == CASES[case]


def test_a_job_that_did_not_complete_and_an_answer_that_is_missing():
    stream, _, times, pred, std, anom = scored()
    rows = answer(stream, times, pred, std, anom)
    assert failed(rows, state="FAILED") == ["jobs_not_completed"]
    ctx = ctx_of(rows)
    ctx["results"][1]["last_result"] = None
    rep = _check.Report()
    ta.check(ctx, rep)
    assert not rep.correct and rep.numbers["arima_forecast_gap"] == {
        "value": 1.0, "limit": 0}


def test_the_bfloat16_control_fails_a_limit_and_float64_none():
    extend.use(BENCH.base)
    traffic = {**TRAFFIC, "checks": ["tad_arima"]}
    nums = control.control_numbers(traffic, SEED, N_BLOCKS)
    assert set(nums) == set(ta.limits)
    over = {k for k, v in nums.items() if v > TRAFFIC["limits"][k]}
    assert over >= {"arima_forecast_gap", "arima_stddev_gap"}
    same = control.control_numbers(traffic, SEED, N_BLOCKS, "f64")
    assert set(same.values()) == {0.0}


def test_forecasts_are_compared_on_the_scale_they_were_modelled_on():
    """Near the series' level the gap is the relative gap in levels;
    the point after a spike is not judged by its power of a number near
    0; beyond the transform's range the reference's 1e150 and float32's
    inf are one forecast, and a finite one against them is a gap."""
    p, gm, lam = (0, 0, 1), 1e7, -2.0
    def gap(got, want):
        return ta.compare({p: (got, 2.0)}, {p: (want, 2.0, lam, gm)},
                          10)["arima_forecast_gap"]
    assert gap(1.001e7, 1e7) == pytest.approx(1e-3, rel=0.01)
    # 30 x the level: 1 % in levels is 1e-5 on the model's scale
    assert gap(3.03e8, 3e8) == pytest.approx(0.01 / 30 ** 2, rel=0.02)
    assert gap(float("inf"), 1e157) == 0
    assert gap(3e7, 1e157) == pytest.approx(0.5 / 9, rel=1e-6)
    assert ta.compare({p: (1e7, 2.002)}, {p: (1e7, 2.0, lam, gm)},
                      10)["arima_stddev_gap"] == pytest.approx(1e-3)


def test_the_estimator_as_defined_and_by_running_sums_agree():
    rng = np.random.default_rng(3)
    d = rng.normal(0, 1, (3, 60))
    ns = np.array([1, 2, 5, 20, 59])
    phi, theta = ref.fits(d, ns)
    for s in range(3):
        for j, n in enumerate(ns):
            assert ref.fit(d[s], int(n)) == pytest.approx(
                (phi[s, j], theta[s, j]), abs=1e-10)


def test_the_cadence_a_job_resolves_to():
    assert ref.effective_refit(0, 43200) == 21 == 43200 // 2048
    assert ref.effective_refit(0, 512) == 1
    assert ref.effective_refit(7, 43200) == 7
    assert ta.spec_refit(SHIPPED) == 0 and ta.spec_refit(TRAFFIC) == 4


def test_the_kernels_bytes_and_steps_at_the_cells_shape():
    data = {"traffic": SHIPPED, "specs": [
        {"role": "producer", "preload_blocks": 27}, {"role": "jobs"}]}
    cells = 20 * 43200
    assert kernel.least(data) == {
        "bytes": cells * 10 + 20 * 4, "flops": 0} == {
        "bytes": 8640080, "flops": 0}
    assert kernel.recursion_steps(20, 43200, 21) == 20 * 2058 * 43199 \
        == 1778070840
    from benchmarks import roofline
    extend.use(BENCH.base)
    least = roofline.least_seconds("arima_scores", data,
                                   {"kind": "TPU v5 lite"})
    assert least == pytest.approx(8640080 / 819e9)
    # the measured call of 3.373 s (my chip runs, PR 34) is 3.1e-4 % of
    # that ceiling: above 0 and far from 100, because a call is 1.78 G
    # dependent steps and not 8.6 MB of traffic
    assert 100 * least / 3.373 == pytest.approx(3.13e-4, rel=0.01)
