"""checks/tad_dbscan.py is sharp, and references/dbscan.py is one thing:
over rows made here the way the job's answer carries them, the
reference's own result is correct, float32 arithmetic in the program's
place is correct under the cell's limits, and a kernel that leaves out
part of the algorithm (the reachability pass, the point itself in its
own count, `<` for `<=` where a pair lies at eps exactly), a dropped
row, a row too many, a perturbed deviation, a `algoCalc` that is not 0,
a FAILED job and the bfloat16 control each give `correct: false` by the
number that names the fault. No manager is started: the check reads
records and a job's answer, and these are records and an answer."""

import json

import numpy as np
import pytest

from benchmarks import check as _check
from benchmarks import control, extend, gen, manifest
from benchmarks import reference as series
from benchmarks.checks import tad_dbscan as td
from benchmarks.kernels import dbscan_noise as kernel
from benchmarks.laws import spread_spikes
from benchmarks.references import dbscan as ref

BENCH = manifest.load()
CELL = "parts-fused-12h-ns.tad-dbscan"
SHIPPED = BENCH.traffic(BENCH.cell(CELL)["traffic"])
#: the cell's law and limits at a size a test can hold: 6 connections x
#: 8 blocks of 64 points, spikes often enough for all three classes
TRAFFIC = {
    "name": "t", "limits": SHIPPED["limits"],
    "generator": {**SHIPPED["generator"], "connections_per_producer": 6,
                  "conns_per_block": 6, "points_per_conn": 64,
                  "spike_rate": 0.03},
    "workers": [{"role": "producer", "count": 1},
                {"role": "jobs", "count": 1, "job": {
                    "resource": "throughputanomalydetectors",
                    "spec": {"jobType": "DBSCAN"}}}],
}
SEED = 2147489333
N_BLOCKS = 8


def by_pairs(x, fault=None):
    """The definition over all pairs of one series, as a kernel would
    evaluate it, with one part of it left out."""
    d = np.abs(x[:, None] - x[None, :])
    within = d < ref.EPS if fault == "less_than_eps" else d <= ref.EPS
    count = within.sum(1) - (fault == "self_not_counted")
    core = count >= ref.MIN_SAMPLES
    reachable = (within & core[None, :]).any(1)
    if fault == "no_reachability_pass":
        reachable[:] = False
    return ~core & ~reachable


def scored(precision="f64", fault=None):
    stream = gen.stream(TRAFFIC, SEED, 0)
    vals, times, mask = series.series_of(stream, N_BLOCKS)
    _, std, anom = ref.dbscan_scores(vals, mask, precision=precision)
    if fault:
        anom = np.stack([by_pairs(v.astype(np.float64), fault)
                         for v in vals])
    return stream, times, std, anom


def answer(stream, times, std, anom):
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
            "algoType": "DBSCAN", "algoCalc": "0.0",
            "throughputStandardDeviation": repr(float(std[c])),
            "refitEvery": "0", "anomaly": "true"})
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
    td.check(ctx_of(rows, **kw), rep)
    doc = rep.doc()
    bad = sorted(k for k, v in doc["numbers"].items()
                 if v["value"] > v["limit"])
    assert doc["correct"] is (not bad)
    return bad


@pytest.fixture
def a_pair_at_eps_exactly(monkeypatch):
    """Connection 0's first four points are V, V + eps, V + eps and
    V + 2 eps, far above every spike: with `<=` the middle two have
    four neighbours and the outer two are their border points, nothing
    is noise; with `<` nobody has more than two and all four are."""
    values = spread_spikes.Stream.values

    def planted(self, b):
        v = values(self, b)
        if b == 0:
            eps = int(ref.EPS)
            v["thr"][0, :4] = 10 ** 10 + np.array([0, eps, eps, 2 * eps])
        return v
    monkeypatch.setattr(spread_spikes.Stream, "values", planted)


def test_the_references_own_rows_are_correct_and_hold_every_class():
    stream, times, std, anom = scored()
    vals, _, mask = series.series_of(stream, N_BLOCKS)
    spike = vals > 3 * np.median(vals, 1)[:, None]
    assert not anom[~spike].any()
    no_reach = scored(fault="no_reachability_pass")[3]
    noise, border = int(anom.sum()), int((no_reach & ~anom).sum())
    assert noise > 5 and border > 2 and spike.sum() - noise - border > 20
    assert failed(answer(stream, times, std, anom)) == []


def test_float32_in_the_programs_place_is_correct():
    stream, times, std, anom = scored("f32")
    assert failed(answer(stream, times, std, anom)) == []


def _perturbed(case):
    stream, times, std, anom = scored(
        fault=case if case in ("no_reachability_pass", "self_not_counted",
                               "less_than_eps") else None)
    std, anom = std.copy(), anom.copy()
    c, t = (int(i[3]) for i in np.nonzero(anom))
    if case == "one_deviation":
        std[c] *= 1.002
    elif case == "one_row_dropped":
        anom[c, t] = False
    elif case == "one_row_too_many":
        quiet = np.argwhere(~anom)[0]
        anom[quiet[0], quiet[1]] = True
    rows = answer(stream, times, std, anom)
    if case == "one_calc_not_zero":
        rows[2]["algoCalc"] = "1.5"
    elif case == "one_row_of_another_algorithm":
        rows[2]["algoType"] = "EWMA"
    return rows


CASES = {
    "no_reachability_pass": ["dbscan_decision_mismatch"],
    "self_not_counted": ["dbscan_decision_mismatch"],
    "one_deviation": ["dbscan_stddev_gap"],
    "one_row_dropped": ["dbscan_decision_mismatch"],
    "one_row_too_many": ["dbscan_decision_mismatch"],
    "one_calc_not_zero": ["dbscan_calc_gap"],
    "one_row_of_another_algorithm": ["dbscan_calc_gap"],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_perturbed_answer_is_not_correct(case):
    assert failed(_perturbed(case)) == CASES[case]


def test_less_than_for_at_most_loses_the_pair_at_eps(a_pair_at_eps_exactly):
    stream, times, std, anom = scored()
    assert not anom[0, :4].any()
    assert failed(answer(stream, times, std, anom)) == []
    wrong = scored(fault="less_than_eps")[3]
    assert wrong[0, :4].all() and (wrong ^ anom).sum() == 4
    assert failed(_perturbed("less_than_eps")) \
        == ["dbscan_decision_mismatch"]


def test_a_job_that_did_not_complete_and_an_answer_that_is_missing():
    stream, times, std, anom = scored()
    rows = answer(stream, times, std, anom)
    assert failed(rows, state="FAILED") == ["jobs_not_completed"]
    ctx = ctx_of(rows)
    ctx["results"][1]["last_result"] = None
    rep = _check.Report()
    td.check(ctx, rep)
    assert not rep.correct and rep.numbers["dbscan_stddev_gap"] == {
        "value": 1.0, "limit": 0}


def test_a_job_that_found_nothing_where_the_reference_did():
    """The filler row ('NO ANOMALY DETECTED') is no decision."""
    filler = [{"anomaly": "NO ANOMALY DETECTED", "algoType": "DBSCAN",
               "algoCalc": "0.0", "sourceIP": "None"}]
    assert failed(filler) == ["dbscan_decision_mismatch"]


def test_the_bfloat16_control_fails_a_limit_and_float64_none():
    extend.use(BENCH.base)
    traffic = {**TRAFFIC, "checks": ["tad_dbscan"]}
    nums = control.control_numbers(traffic, SEED, N_BLOCKS)
    assert set(nums) == set(td.limits)
    over = {k for k, v in nums.items() if v > TRAFFIC["limits"][k]}
    assert "dbscan_stddev_gap" in over
    same = control.control_numbers(traffic, SEED, N_BLOCKS, "f64")
    assert set(same.values()) == {0.0}


def test_the_kernels_bytes_and_pair_tests_at_the_cells_shape():
    data = {"traffic": SHIPPED, "specs": [
        {"role": "producer", "preload_blocks": 108}, {"role": "jobs"}]}
    cells = 80 * 43200
    assert kernel.least(data) == {
        "bytes": cells * 6 + 80 * 4, "flops": 0} == {
        "bytes": 20736320, "flops": 0}
    assert kernel.pair_tests(80, 43200) == 80 * 43200 ** 2 \
        == 149299200000
    from benchmarks import roofline
    extend.use(BENCH.base)
    least = roofline.least_seconds("dbscan_noise", data,
                                   {"kind": "TPU v5 lite"})
    assert least == pytest.approx(20736320 / 819e9)
