"""checks/tad_agg_pod.py is sharp, and references/tad_agg_pod.py is one
thing: over rows made here the way the job's answer carries them, the
reference's own result is correct, float32 arithmetic in the program's
place is correct under the cell's limits, and a job that reads one side
only, takes `max` for `sum`, decodes a source pod's labels through the
destination column's dictionary, counts a connection with an external
destination inbound under '', drops a row, sends one twice, answers a
row of another mode, FAILED, or ran in bfloat16 (the control) each
gives `correct: false` by the number that names the fault. No manager
is started: the check reads records, a job's answer and the counters'
text, and these are records, an answer and counters."""

import json

import pytest

from benchmarks import check as _check
from benchmarks import control, extend, gen, manifest
from benchmarks.checks import tad_agg_pod as ta
from benchmarks.kernels import dbscan_noise_aggpod as kernel
from benchmarks.references import tad_agg_pod as ref

BENCH = manifest.load()
CELL = "parts-fused-aggpod.tad-dbscan-pod"
SHIPPED = BENCH.traffic(BENCH.cell(CELL)["traffic"])
#: the cell's law and limits at a size a test can hold: the population's
#: first 512 connections (they fall on 400-odd pods a side, some shared)
#: x 8 blocks of 8 points, spikes often enough that every kind of
#: series has a decision
TRAFFIC = {
    "name": "t", "limits": SHIPPED["limits"],
    "generator": {**SHIPPED["generator"], "connections_per_producer": 512,
                  "conns_per_block": 512, "spike_rate": 0.01},
    "workers": [{"role": "producer", "count": 1},
                {"role": "jobs", "count": 1,
                 "job": SHIPPED["workers"][1]["job"]}],
}
SEED = 2147489333
N_BLOCKS = 8
JOBS = 3                # the warm-up's and two in the window


def streams():
    extend.use(BENCH.base)
    return [(gen.stream(TRAFFIC, SEED, 0), N_BLOCKS)]


def job(precision="f64", **query):
    return ref.pod_job(streams(), precision, **query)


def answer(rows):
    """A COMPLETED job's answer: the reference's rows as the result
    table's strings."""
    return [{
        "podNamespace": ns, "podLabels": labels, "direction": direction,
        "flowEndSeconds": str(t), "throughput": repr(float(total)),
        "throughputStandardDeviation": repr(std),
        "aggType": "pod", "algoType": "DBSCAN", "algoCalc": "0.0",
        "anomaly": "true", "refitEvery": "0", "podName": "",
        "sourceIP": "", "destinationIP": "", "sourceTransportPort": "0",
        "destinationTransportPort": "0", "protocolIdentifier": "0",
        "flowStartSeconds": "0", "destinationServicePortName": "", "id": "j"}
        for (ns, labels, direction, t), (total, std) in rows.items()]


def ctx_of(rows, counts=None, state="COMPLETED"):
    """A run whose last job answered `rows` and whose jobs each counted
    `counts` (the job dict of whatever program made the rows)."""
    acked = [{"status": 200, "block": b} for b in range(N_BLOCKS)]
    ctx = {
        "traffic": TRAFFIC, "seed": SEED,
        "specs": [{"role": "producer", "producer": 0}, {"role": "jobs"}],
        "preload": [{"records": acked}, {"records": []}],
        "warm": [{"records": []}, {"records": [{"state": "COMPLETED"}]}],
        "probes": [{"records": []}] * 2,
        "results": [{"records": []},
                    {"records": [{"state": state}] * (JOBS - 1),
                     "last_result": json.dumps({"stats": rows})}],
        "metrics_final": {},
    }
    if counts is not None:
        done = JOBS if state == "COMPLETED" else 1
        ctx["metrics_final"] = {
            ta.SERIES_BUILT: float(done * counts["series"]),
            ta.ROWS_MERGED: float(done * counts["merged"])}
    return ctx


def failed(rows, counts=None, **kw):
    rep = _check.Report()
    ta.check(ctx_of(rows, counts, **kw), rep)
    doc = rep.doc()
    bad = sorted(k for k, v in doc["numbers"].items()
                 if v["value"] > v["limit"])
    assert doc["correct"] is (not bad)
    return bad


def test_the_references_own_rows_are_correct_and_rows_were_summed():
    want = job()
    series = ref.pod_series(streams())
    assert (want["series"], want["contributions"], want["merged"]) == (
        len(series.keys), series.contributions, series.merged)
    per_point = series.contributions / series.mask.sum()
    assert want["merged"] > 0 and per_point > 1.05
    both = {k[2] for k in series.keys}
    assert both == {"inbound", "outbound"} and len(want["rows"]) > 20
    assert {p[2] for p in want["rows"]} == both
    # a sum is what its connections' rows add up to, second by second
    s = streams()[0][0]
    total = sum(int(s.values(b)["thr"].sum()) for b in range(N_BLOCKS))
    out = [i for i, k in enumerate(series.keys) if k[2] == "outbound"]
    assert int(series.values[out].sum()) == total
    assert failed(answer(want["rows"]), want) == []
    # a manager that exports neither counter: the rows are still held
    rep = _check.Report()
    ta.check(ctx_of(answer(want["rows"])), rep)
    assert rep.correct and "aggpod_series_gap" not in rep.numbers
    assert any("aggpod_series_gap: left out" in ln for ln in rep.lines)


def test_float32_in_the_programs_place_is_correct():
    got = job("f32")
    assert failed(answer(got["rows"]), got) == []


def through_the_other_dictionary(rows):
    """The outbound rows' labels as a program would print them that
    decoded a source column's code through the destination column's
    dictionary: each column's dictionary holds '' and then its strings
    in the order of ingest."""
    pop = gen.Population(0, TRAFFIC["generator"]["connections_per_producer"])
    dicts = {}
    for col in ("sourcePodLabels", "destinationPodLabels"):
        table, idx = pop.strings[col]
        dicts[col] = list(dict.fromkeys([""] + [table[int(i)] for i in idx]))
    src, dst = dicts["sourcePodLabels"], dicts["destinationPodLabels"]
    out = {}
    for (ns, labels, direction, t), v in rows.items():
        if direction == "outbound":
            labels = dst[src.index(labels) % len(dst)]
        out[(ns, labels, direction, t)] = v
    return out


def external_counted_inbound(monkeypatch):
    """The query without `destinationPodLabels <> ''`: a connection to
    an external address falls under ('', '', inbound)."""
    keys = ref.connection_keys

    def lax(population, **filters):
        arms = keys(population, **filters)
        arms[0] = [("", "", "inbound") if k is None else k
                   for k in arms[0]]
        return arms
    monkeypatch.setattr(ref, "connection_keys", lax)
    return job()


def _perturbed(case, monkeypatch):
    """(rows, the counts of the program that made them)."""
    want = job()
    if case == "one_side_left_out":
        got = job(sides=(1,))
        return answer(got["rows"]), got
    if case == "max_for_sum":
        got = job(op="max")
        return answer(got["rows"]), got
    if case == "external_counted_inbound":
        got = external_counted_inbound(monkeypatch)
        return answer(got["rows"]), got
    if case == "source_label_through_the_destination_dictionary":
        return answer(through_the_other_dictionary(want["rows"])), want
    rows = answer(want["rows"])
    if case == "one_row_dropped":
        del rows[3]
    elif case == "one_row_twice":
        rows.append(dict(rows[3]))
    elif case == "one_sum_off_by_one":
        rows[3]["throughput"] = repr(float(rows[3]["throughput"]) + 1)
    elif case == "one_deviation":
        rows[3]["throughputStandardDeviation"] = repr(
            float(rows[3]["throughputStandardDeviation"]) * 1.002)
    elif case == "one_row_of_agg_type_none":
        rows[3]["aggType"] = "None"
    elif case == "one_row_with_a_connections_column":
        rows[3]["sourceIP"] = "10.0.0.1"
    elif case == "one_calc_not_zero":
        rows[3]["algoCalc"] = "1.5"
    elif case == "a_series_too_many_counted":
        return rows, {**want, "series": want["series"] + 1}
    elif case == "rows_merged_not_counted":
        return rows, {**want, "merged": 0}
    return rows, want


CASES = {
    "one_side_left_out": ["aggpod_decision_mismatch",
                          "aggpod_rows_merged_gap", "aggpod_series_gap"],
    # a spike stands out of the largest connection as of the sum here:
    # no decision moves, every summed row's throughput and series do
    "max_for_sum": ["aggpod_stddev_gap", "aggpod_throughput_gap"],
    "source_label_through_the_destination_dictionary": [
        "aggpod_decision_mismatch"],
    "external_counted_inbound": ["aggpod_decision_mismatch",
                                 "aggpod_rows_merged_gap",
                                 "aggpod_series_gap"],
    "one_row_dropped": ["aggpod_decision_mismatch"],
    "one_row_twice": ["aggpod_decision_mismatch"],
    "one_sum_off_by_one": ["aggpod_throughput_gap"],
    "one_deviation": ["aggpod_stddev_gap"],
    "one_row_of_agg_type_none": ["aggpod_kind_gap"],
    "one_row_with_a_connections_column": ["aggpod_kind_gap"],
    "one_calc_not_zero": ["aggpod_kind_gap"],
    "a_series_too_many_counted": ["aggpod_series_gap"],
    "rows_merged_not_counted": ["aggpod_rows_merged_gap"],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_perturbed_answer_is_not_correct(case, monkeypatch):
    rows, counts = _perturbed(case, monkeypatch)
    monkeypatch.undo()          # the check's reference is the sound one
    assert failed(rows, counts) == CASES[case]


def test_a_job_that_did_not_complete_and_an_answer_that_is_missing():
    want = job()
    rows = answer(want["rows"])
    assert "jobs_not_completed" in failed(rows, want, state="FAILED")
    ctx = ctx_of(rows, want)
    ctx["results"][1]["last_result"] = None
    rep = _check.Report()
    ta.check(ctx, rep)
    assert not rep.correct and rep.numbers["aggpod_throughput_gap"] == {
        "value": 1.0, "limit": 0}


def test_a_job_that_found_nothing_where_the_reference_did():
    """The filler row ('NO ANOMALY DETECTED') is no decision."""
    filler = [{"anomaly": "NO ANOMALY DETECTED", "algoType": "DBSCAN",
               "aggType": "pod", "algoCalc": "0.0", "podNamespace": "None"}]
    assert failed(filler, job()) == ["aggpod_decision_mismatch"]


def test_the_bfloat16_control_fails_a_limit_and_float64_none():
    extend.use(BENCH.base)
    traffic = {**TRAFFIC, "checks": ["tad_agg_pod"]}
    nums = control.control_numbers(traffic, SEED, N_BLOCKS)
    assert set(nums) == set(ta.limits)
    over = {k for k, v in nums.items() if v > TRAFFIC["limits"][k]}
    assert "aggpod_stddev_gap" in over
    same = control.control_numbers(traffic, SEED, N_BLOCKS, "f64")
    assert set(same.values()) == {0.0}


def test_the_kernels_bytes_at_the_cells_shape_and_here():
    """S is the population's keys, not its connections; T what the
    producers preload."""
    data = {"traffic": SHIPPED, "specs": [
        {"role": "producer", "preload_blocks": 108, "seed": SEED,
         "producer": 0}, {"role": "jobs"}]}
    assert kernel.pod_series_shape(data) == {"series": 1985, "steps": 864}
    cells = 1985 * 864
    assert kernel.least(data) == {"bytes": cells * 6 + 1985 * 4,
                                  "flops": 0} == {"bytes": 10298180,
                                                  "flops": 0}
    from benchmarks import roofline
    extend.use(BENCH.base)
    assert roofline.series_shape(data) == {"series": 4000, "steps": 864}
    assert roofline.least_seconds(
        "dbscan_noise_aggpod", data, {"kind": "TPU v5 lite"}) \
        == pytest.approx(10298180 / 819e9)
    here = {"traffic": TRAFFIC, "specs": [
        {"role": "producer", "preload_blocks": N_BLOCKS}, {"role": "jobs"}]}
    series = ref.pod_series(streams())
    assert kernel.pod_series_shape(here) == {
        "series": len(series.keys), "steps": series.values.shape[1]}
