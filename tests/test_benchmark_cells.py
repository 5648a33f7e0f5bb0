"""Every cell of BENCHMARK.json resolves without a chip, and a cell
whose files cannot be found is refused before any child starts: the
benchmark's own refusal and sharpness cases (benchmarks/tests/) that
need no manager run here as tier-1 cases, so that a broken cell fails
this suite and not the driver's check."""

import os

import numpy as np
import pytest

from benchmarks import check, extend, harness, manifest
from benchmarks.tests.conftest import overlay            # noqa: F401
from benchmarks.tests.test_extension import (            # noqa: F401
    test_a_kernel_file_is_held_to_the_larger_bound,
    test_a_name_nothing_provides_is_a_broken_run,
    test_forgotten_overlay_is_read_anew,
    test_resolver,
)
from benchmarks.tests.test_retention_trim import (       # noqa: F401
    test_a_refused_request_counts_as_failed_and_no_answer_is_a_gap,
    test_a_round_that_does_something_else_is_not_correct,
    test_a_sound_round_is_correct,
    test_only_the_faults_own_numbers_move,
    test_the_references_views_are_the_programs_group_by,
)
from benchmarks.tests.test_snapshot_prefix import (      # noqa: F401
    test_a_perturbed_snapshot_or_log_is_not_correct,
    test_a_refused_request_counts_as_failed,
    test_a_sound_generation_pair_is_correct,
    test_a_window_with_two_snapshots_or_none,
    test_another_interval_than_the_configurations,
)

from benchmarks.tests.test_stage_usage_metrics import (  # noqa: F401
    test_counter_rise_per_sums_a_bare_name_and_scales,
    test_reader_gives_a_number_here_and_nothing_at_the_parent,
    test_tensorize_parts_cover_the_stage_in_the_recorded_pair,
    test_the_new_readers_are_the_ones_the_issue_lists,
)
from benchmarks.tests.test_tad_arima import (            # noqa: F401
    test_a_job_that_did_not_complete_and_an_answer_that_is_missing,
    test_a_perturbed_answer_is_not_correct,
    test_float32_in_the_programs_place_is_correct,
    test_forecasts_are_compared_on_the_scale_they_were_modelled_on,
    test_the_bfloat16_control_fails_a_limit_and_float64_none,
    test_the_cadence_a_job_resolves_to,
    test_the_estimator_as_defined_and_by_running_sums_agree,
    test_the_kernels_bytes_and_steps_at_the_cells_shape,
    test_the_references_own_rows_are_correct,
)
from benchmarks.tests.test_tad_dbscan import (           # noqa: F401
    a_pair_at_eps_exactly,
    test_a_job_that_did_not_complete_and_an_answer_that_is_missing
    as test_a_dbscan_job_that_did_not_complete,
    test_a_job_that_found_nothing_where_the_reference_did,
    test_a_perturbed_answer_is_not_correct
    as test_a_perturbed_dbscan_answer_is_not_correct,
    test_float32_in_the_programs_place_is_correct
    as test_float32_in_the_dbscan_kernels_place_is_correct,
    test_less_than_for_at_most_loses_the_pair_at_eps,
    test_the_bfloat16_control_fails_a_limit_and_float64_none
    as test_the_bfloat16_dbscan_control_fails_a_limit,
    test_the_kernels_bytes_and_pair_tests_at_the_cells_shape,
    test_the_references_own_rows_are_correct_and_hold_every_class,
)
from benchmarks.tests.test_npr_policies import (         # noqa: F401
    test_a_job_that_did_not_complete_and_an_answer_that_is_missing
    as test_an_npr_job_that_did_not_complete,
    test_a_perturbed_answer_is_not_correct
    as test_a_perturbed_npr_answer_is_not_correct,
    test_a_program_that_counts_other_rows_or_flows_is_not_correct,
    test_a_request_the_reference_cannot_stand_for_is_a_broken_run,
    test_the_kernels_bytes_at_the_cells_shape_and_here,
    test_the_references_own_documents_are_correct,
)
from benchmarks.tests.test_tad_agg_pod import (          # noqa: F401
    test_a_job_that_did_not_complete_and_an_answer_that_is_missing
    as test_an_aggpod_job_that_did_not_complete,
    test_a_job_that_found_nothing_where_the_reference_did
    as test_an_aggpod_job_that_found_nothing,
    test_a_perturbed_answer_is_not_correct
    as test_a_perturbed_aggpod_answer_is_not_correct,
    test_float32_in_the_programs_place_is_correct
    as test_float32_in_the_aggpod_jobs_place_is_correct,
    test_the_bfloat16_control_fails_a_limit_and_float64_none
    as test_the_bfloat16_aggpod_control_fails_a_limit,
    test_the_kernels_bytes_at_the_cells_shape_and_here
    as test_the_aggpod_kernels_bytes_at_the_cells_shape_and_here,
    test_the_references_own_rows_are_correct_and_rows_were_summed,
)

BENCH = manifest.load()
CELLS = [w["name"] for w in BENCH.doc["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_without_a_chip(cell):
    """Configuration, traffic, key law, roles, checks with their
    limits, reductions and kernel functions: all found by name."""
    w = BENCH.cell(cell)
    config = BENCH.config(w["config"])
    traffic = BENCH.traffic(w["traffic"])
    harness.resolve_all(BENCH, cell, traffic)
    assert w["chips"] in (1, 4)
    assert "--checkpoint-interval" in config["manager_args"]
    for fn in check.resolve_all(traffic):
        assert callable(fn)
    for section in ("end_to_end", "per_layer"):
        metrics = BENCH.metrics_of(cell, section)
        assert metrics, f"{cell} reports no {section} metric"
        for m in metrics:
            assert "reduce" in BENCH.reader(section, m["name"])
    assert "setup_s" in {m["name"] for m in
                         BENCH.metrics_of(cell, "end_to_end")}


@pytest.mark.parametrize("config", [c["name"]
                                    for c in BENCH.doc["configs"]])
def test_configuration_file_states_what_the_manifest_says(config):
    entry = next(c for c in BENCH.doc["configs"] if c["name"] == config)
    doc = BENCH.config(config)
    assert doc["name"] == config
    assert os.path.exists(os.path.join(manifest.ROOT, entry["file"]))
    assert any(w["config"] == config for w in BENCH.doc["workloads"])
    # a key that is `reduced` differs from the source's or the
    # program's own value, which the file states beside it
    for key in entry["reduced"]:
        assert key in doc
    interval = doc["manager_args"][
        doc["manager_args"].index("--checkpoint-interval") + 1]
    assert float(interval) == doc["checkpoint_interval_s"]
    assert ("checkpoint_interval_s" in entry["reduced"]) \
        == (doc["checkpoint_interval_s"] != 60)


def test_the_checkpoint_cell_is_saturate_with_the_snapshot_on():
    """The two cells differ in the snapshot and in nothing else of
    the producers' traffic: their gap is the snapshot's cost."""
    sat = BENCH.traffic("ingest-saturate")
    ckpt = BENCH.traffic("ingest-saturate-ckpt")
    assert ckpt["generator"] == sat["generator"]
    assert ckpt["limits"] == sat["limits"]
    assert ckpt["trace_seconds"] == sat["trace_seconds"] == 10
    producers, operator = ckpt["workers"]
    assert producers == {**sat["workers"][0], "preload_blocks": 8}
    assert operator == {"role": "operator", "count": 1, "offset_s": 3.0}
    assert ckpt["checks"] == ["snapshot_prefix"] + sat["checks"]
    rows = (producers["count"] * producers["preload_blocks"]
            * ckpt["generator"]["conns_per_block"]
            * ckpt["generator"]["points_per_conn"])
    cfg = BENCH.config("theia-default-ckpt-1x1")
    assert rows == cfg["retained_window_rows"] == 1024000
    base = BENCH.config("theia-default-1x1")
    assert [a for a in cfg["manager_args"] if a != "60"] \
        == [a for a in base["manager_args"] if a != "0"]
    assert (cfg["env"], cfg["expect"]) == (base["env"], base["expect"])
    e2e = {m["name"] for m in BENCH.metrics_of(
        "default-ckpt.ingest-saturate-ckpt", "end_to_end")}
    assert e2e == {"acked_rows_per_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(
        "default-ckpt.ingest-saturate-ckpt", "per_layer")}
    assert {m["name"] for m in BENCH.metrics_of(
        "default.ingest-saturate", "per_layer")} < layer
    assert len({n for n in layer if n.startswith("ckpt.")}) == 13


TRIM_CELL = "default-trim.ingest-saturate-trim"
TRIM_METRICS = {
    "trim.total_ms", "trim.boundary_ms", "trim.delete_flows_ms",
    "trim.delete_views_ms", "trim.rows_before", "trim.rows_deleted",
    "trim.bytes_freed", "trim.append_wait_ms_per_block",
    "trim.longest_ack_gap_ms", "trim.acked_rows_per_s_during",
    "trim.batches_cut", "trim.bytes_copied"}


def test_the_trim_cell_is_saturate_with_the_trim_and_nothing_else():
    """The two cells differ in the store's volume, the preload that
    brings it to 99 % of the trigger, and the one round: their gap on
    one commit is the trim."""
    sat = BENCH.traffic("ingest-saturate")
    trim = BENCH.traffic("ingest-saturate-trim")
    assert trim["generator"] == sat["generator"]
    assert trim["limits"] == sat["limits"]
    assert trim["trace_seconds"] == sat["trace_seconds"] == 10
    producers, trimmer = trim["workers"]
    assert producers == {**sat["workers"][0], "preload_blocks": 109,
                         "prepared_blocks": 237}
    # a producer encodes from block 0 on: the window's supply is
    # saturate's 128 behind the 109 preloaded
    assert producers["prepared_blocks"] \
        == sat["workers"][0]["prepared_blocks"] + 109
    assert trimmer == {"role": "trimmer", "count": 1, "offset_s": 3.0}
    # store_totals would hold the store to rows the deployment deleted
    assert trim["checks"] == ["retention_trim"] + [
        c for c in sat["checks"] if c != "store_totals"]
    assert not getattr(extend.module("check", "retention_trim"),
                       "limits", ())
    cfg = BENCH.config("theia-default-trim-1x1")
    base = BENCH.config("theia-default-1x1")
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    # the sibling's one deviation removed: the chart's volume, which is
    # the program's default
    assert cfg["manager_args"] == [
        "8589934592" if a == "68719476736" else a
        for a in base["manager_args"]]
    assert cfg["capacity_bytes"] == cfg["source_capacity_bytes"] \
        == 8 << 30
    assert (cfg["env"], cfg["expect"]) == (base["env"], base["expect"])
    assert "capacity_bytes" not in cfg["assumed"]
    assert cfg["monitor"]["threshold"] == 0.5 \
        and cfg["monitor"]["delete_percentage"] == 0.5 \
        and cfg["monitor"]["skip_rounds"] == 3 \
        and cfg["monitor"]["interval_s"] == 60
    assert set(base["guarantees"]) < set(cfg["guarantees"])
    assert "not promised" in cfg["guarantees"]["restart"]
    # 99.0 % of the trigger when the window opens, at 284 B a row
    rows = (producers["count"] * (producers["preload_blocks"]
                                  + producers["warm_blocks"]) * 32000)
    trigger = cfg["capacity_bytes"] * cfg["monitor"]["threshold"]
    assert rows == 14976000
    assert 0.985 < rows * 284 / trigger < 0.995
    assert cfg["retained_window_rows"] == int(trigger // 284) == 15123124
    cell = BENCH.cell(TRIM_CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert {m["name"] for m in BENCH.metrics_of(TRIM_CELL, "end_to_end")} \
        == {"acked_rows_per_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(TRIM_CELL, "per_layer")}
    old = {m["name"] for m in BENCH.metrics_of("default.ingest-saturate",
                                               "per_layer")}
    assert layer - old == TRIM_METRICS and old < layer
    for name in TRIM_METRICS:
        m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader("per_layer", name)
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "retention", "acked_rows_per_s", [TRIM_CELL])
        assert (reader["layer"], reader["moves"], reader["source"]) == (
            m["layer"], m["moves"], m["source"])
        # PR 39's readers are counted by the series they name
        assert "_cpu_seconds" not in str(reader)
        assert "_minor_faults_total" not in str(reader)


def test_the_trim_cell_is_rehearsed_on_the_cpu_backend():
    """The cell at a tiny size with no edit to it: manager child, the
    preload, the warm-up's idle round, the window's round, the checks,
    and every host-side reader of a traced run; plumbing only, no
    number of it is a result. The rehearsal's store (4 x 32 blocks of
    256 rows) gets a volume of its own size and stops just under its
    trigger, as the cell's store does under the chart's (99.9 % here
    where the cell has 99.0 %: the window's first block crosses it,
    however slowly a loaded machine acks the second)."""
    from benchmarks import rehearsal, selftest

    cell = BENCH.cell(TRIM_CELL)
    scale = rehearsal.scale_for(BENCH, cell)
    rows = 4 * scale["preload_blocks"] * 64 * 4
    scale["env"]["THEIA_STORE_CAPACITY_BYTES"] = str(
        int(2 * rows * 284 / 0.999))
    real = rehearsal.scale_for
    rehearsal.scale_for = lambda bench, c: scale
    try:
        plain, traced = selftest.rehearse(cell, trace=True)
    finally:
        rehearsal.scale_for = real
    for out in (plain, traced):
        assert out["correct"] and out["failed"] == 0
        assert {"trim_rounds_gap", "trim_boundary_gap",
                "trim_rows_deleted_gap", "trim_store_rows_gap",
                "trim_store_octets_gap", "trim_oldest_row_gap",
                "trim_view_rows_gap", "trim_detector_series_gap",
                "trim_ambiguous_blocks", "acks_not_whole",
                "detector_series_gap"} <= set(out["checks"])
        assert "store_rows_gap" not in out["checks"]
    assert set(plain["metrics"]) == {"acked_rows_per_s", "setup_s"}
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert TRIM_METRICS <= set(got)
    assert got["trim.rows_before"] > rows
    assert got["trim.rows_deleted"] * 2 <= got["trim.rows_before"]
    assert got["trim.bytes_freed"] == got["trim.rows_deleted"] * 284
    assert got["trim.total_ms"] >= got["trim.boundary_ms"] \
        + got["trim.delete_flows_ms"] + got["trim.delete_views_ms"] - 1e-6


def test_the_walk_metrics_reduce_to_a_rounds_figures():
    """`trim.batches_cut` and `trim.bytes_copied` (PR 49) read the
    program's own exposition around one round that trimmed: the
    batches of `flows` the round filtered under the table's lock and
    the bytes of their kept rows, as the round's record says them;
    nothing from a manager without the series (the parent). The trim
    cell's alone: no other cell runs a round that deletes."""
    from benchmarks import prom
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.schema import ColumnarBatch
    from theia_tpu.store import FlowDatabase, RetentionLoop

    readers = {}
    for name, unit in (("trim.batches_cut", "batches"),
                       ("trim.bytes_copied", "bytes")):
        m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        readers[name] = reader = BENCH.reader("per_layer", name)
        assert (m["layer"], m["moves"], m["workloads"], m["source"],
                m["unit"], m["better"]) == (
            "retention", "acked_rows_per_s", [TRIM_CELL],
            "program_counter", unit, "lower")
        assert (reader["layer"], reader["moves"], reader["source"],
                reader["reduce"], reader["per"]) == (
            m["layer"], m["moves"], m["source"], "counter_rise_per",
            'theia_retention_rounds_total{result="trimmed"}')
    # appended behind PR 48's last, the 127th and 128th
    assert [m["name"] for m in BENCH.doc["per_layer"][126:128]] \
        == list(readers)

    db = FlowDatabase()
    one = generate_flows(SynthConfig(n_series=8, points_per_series=4,
                                     seed=5))
    for i in range(6):      # blocks in time order, two seconds each
        cols = dict(one.columns)
        cols["timeInserted"] = (1_700_000_000 + 2 * i
                                + np.arange(32) // 16).astype(
            cols["timeInserted"].dtype)
        db.insert_flows(ColumnarBatch(cols, one.dicts))
    loop = RetentionLoop(db.monitor(capacity_bytes=db.flows.nbytes,
                                    delete_percentage=0.45),
                         interval=3600)
    before = prom.parse(exposition.render())
    assert loop.run_once() == 64 + 16
    rec = loop.last_round
    assert (rec["batchesDropped"], rec["batchesCut"], rec["batchesKept"],
            rec["bytesCopied"]) == (2, 1, 3, 16 * 284)
    data = {"metrics_before": before,
            "metrics_after": prom.parse(exposition.render())}

    def read(name):
        reader = readers[name]
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    for reader in readers.values():
        assert reader["series"] in data["metrics_after"]
        assert reader["per"] in data["metrics_after"]
    assert read("trim.batches_cut") == 1
    assert read("trim.bytes_copied") == 16 * 284
    for name, reader in readers.items():
        for side in data.values():
            side.pop(reader["series"], None)
        assert read(name) is None


def test_a_trimmer_no_manager_answers_ends_in_the_warm_up():
    """The parent commit has no /admin/retention (404), a manager with
    the loop off answers 409, one over its threshold already would
    trim in the warm-up: each must end the worker in set-up (exit 1
    for the run), never hang or go on."""
    import http.server
    import json
    import threading

    answers = []

    class Canned(http.server.BaseHTTPRequestHandler):
        def do_POST(self):                               # noqa: N802
            status, doc = answers.pop(0)
            body = json.dumps(doc).encode()
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), Canned)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        extend.use(manifest.HERE)
        role = extend.resolve("role", "trimmer")({
            "addr": f"http://127.0.0.1:{httpd.server_address[1]}",
            "offset_s": 3.0})
        assert role.handle(["preload"]) == {"event": "preloaded",
                                            "records": []}
        answers[:] = [(404, {}), (409, {"message": "no loop"}),
                      (200, {"result": "trimmed", "usageBefore": 0.51}),
                      (200, {"result": "idle", "usageBefore": 0.49,
                             "seconds": 0.001, "stagesMs": {"usage": 0.2}})]
        with pytest.raises(SystemExit, match="404"):
            role.handle(["warm"])
        with pytest.raises(SystemExit, match="409"):
            role.handle(["warm"])
        with pytest.raises(SystemExit, match="'trimmed' at usage 0.51"):
            role.handle(["warm"])
        (rec,) = role.handle(["warm"])["records"]
        assert (rec["result"], rec["usage_before"], rec["stages_ms"]) \
            == ("idle", 0.49, {"usage": 0.2})
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_the_arima_cell_holds_a_whole_retained_day_of_20_connections():
    """The time axis is whole (12 h at 1 s), the cut is in connections,
    and everything but the kernel is `parts-fused.tad-ewma`'s."""
    cfg = BENCH.config("theia-parts-fused-12h-1x1")
    sib = BENCH.config("theia-parts-fused-1x1")
    assert (cfg["env"], cfg["manager_args"], cfg["expect"]) \
        == (sib["env"], sib["manager_args"], sib["expect"])
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["retained_connections",
                                "checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    t = BENCH.traffic("tad-arima")
    ewma = BENCH.traffic("tad-ewma")
    g = t["generator"]
    for k in ("interval_seconds", "base_throughput", "spike_rate",
              "spike_magnitude"):
        assert g[k] == ewma["generator"][k]
    producer, jobs = t["workers"]
    points = producer["preload_blocks"] * g["points_per_conn"]
    assert points == cfg["points_per_connection"] == 43200 == 12 * 3600
    assert g["connections_per_producer"] == g["conns_per_block"] \
        == cfg["retained_connections"] == 20
    assert 20 * points == cfg["retained_window_rows"] == 864000
    assert cfg["source_retained_connections"] * points \
        == cfg["source_retained_window_rows"] == 172800000
    assert producer["window"] == "idle" and producer["warm_blocks"] == 0
    assert jobs["job"] == {**ewma["workers"][1]["job"],
                           "spec": {"jobType": "ARIMA", "refitEvery": 0}}
    assert t["checks"] == ["acks", "store_totals", "detector_series",
                           "tad_arima"]
    assert set(t["limits"]) == {"arima_decision_mismatch",
                                "arima_forecast_gap", "arima_stddev_gap"}
    cell = "parts-fused-12h.tad-arima"
    assert {m["name"] for m in BENCH.metrics_of(cell, "end_to_end")} \
        == {"job_turnaround_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(cell, "per_layer")}
    old = {m["name"] for m in BENCH.metrics_of("parts-fused.tad-ewma",
                                               "per_layer")}
    # a whole call of the kernel cannot be captured (the traffic
    # file's `trace_note`), so no device-trace metric of it is declared
    assert layer - old == {"job.arima_fits", "job.arima_loop_iterations"}
    assert old - layer == {"job.ewma_device_ms", "ewma_scores_roofline"}
    assert (t["trace_seconds"], t["trace_lead_seconds"]) == (1, 0)
    assert {"job.score_kernel_ms", "job.score_rows_ms"} <= layer & old


def test_the_dbscan_cell_holds_a_whole_retained_day_of_80_connections():
    """The time axis is whole (12 h at 1 s), the cut is in connections,
    the spikes are spread, and everything but the kernel, the law and
    the check is `parts-fused-12h.tad-arima`'s."""
    cfg = BENCH.config("theia-parts-fused-12h-ns-1x1")
    sib = BENCH.config("theia-parts-fused-12h-1x1")
    assert (cfg["env"], cfg["manager_args"], cfg["expect"]) \
        == (sib["env"], sib["manager_args"], sib["expect"])
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["retained_connections",
                                "checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "reference's decisions" in cfg["guarantees"]["job_result"]
    t = BENCH.traffic("tad-dbscan")
    arima = BENCH.traffic("tad-arima")
    assert t["generator"] == {
        "law": "spread_spikes", "connections_per_producer": 80,
        "conns_per_block": 80, "points_per_conn": 400,
        "interval_seconds": 1, "base_throughput": 1e7,
        "spike_rate": 2e-4, "spike_magnitude_low": 5,
        "spike_magnitude_high": 200}
    producer, jobs = t["workers"]
    assert producer == {**arima["workers"][0], "preload_blocks": 108,
                        "prepared_blocks": 108}
    points = producer["preload_blocks"] * t["generator"]["points_per_conn"]
    assert points == cfg["points_per_connection"] == 43200 == 12 * 3600
    assert cfg["retained_connections"] == 80
    assert 80 * points == cfg["retained_window_rows"] == 3456000
    assert 80 * t["generator"]["points_per_conn"] == 32000  # a block
    assert cfg["source_retained_connections"] * points \
        == cfg["source_retained_window_rows"] == 172800000
    assert jobs["job"] == {**arima["workers"][1]["job"],
                           "spec": {"jobType": "DBSCAN"}}
    assert t["checks"] == ["acks", "store_totals", "detector_series",
                           "tad_dbscan"]
    check_file = extend.module("check", "tad_dbscan")
    assert set(t["limits"]) == set(check_file.limits) == {
        "dbscan_decision_mismatch", "dbscan_stddev_gap"}
    assert set(t["limits_why"]) == set(t["limits"]) | {"exact"}
    assert (t["trace_seconds"], t["trace_lead_seconds"]) == (8, 4)
    cell = "parts-fused-12h-ns.tad-dbscan"
    assert BENCH.cell(cell)["chips"] == 1
    assert {m["name"] for m in BENCH.metrics_of(cell, "end_to_end")} \
        == {"job_turnaround_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(cell, "per_layer")}
    old = {m["name"] for m in BENCH.metrics_of(
        "parts-fused-12h.tad-arima", "per_layer")}
    assert layer - old == {"job.dbscan_pair_tests", "job.dbscan_device_ms",
                           "dbscan_noise_roofline",
                           "job.dbscan_sorted_points"}
    assert old - layer == {"job.arima_fits", "job.arima_loop_iterations"}
    for name in layer - old:
        m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        # the pod cell (PR 48) runs the same kernel: it reports the two
        # that do not take a series for a connection
        shared = name in ("job.dbscan_device_ms", "job.dbscan_sorted_points")
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "DBSCAN kernel", "job_turnaround_s",
            [cell, "parts-fused-aggpod.tad-dbscan-pod"] if shared
            else [cell])


def test_the_dbscan_cell_is_rehearsed_on_the_cpu_backend():
    """`benchmarks/selftest.py`'s rehearsal of the cell at a tiny size:
    manager child, preload, warm-up job, window, checks; plumbing only,
    no number of it is a result."""
    from benchmarks import selftest

    (out,) = selftest.rehearse(
        BENCH.cell("parts-fused-12h-ns.tad-dbscan"), trace=False)
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"job_turnaround_s", "setup_s"}
    assert {"dbscan_decision_mismatch", "dbscan_stddev_gap",
            "dbscan_calc_gap", "jobs_not_completed"} <= set(out["checks"])


def test_the_npr_cell_holds_the_documented_clusters_connections():
    """Connections are the job's shape and are whole (4,000, every one
    in every block, a block one 8 s commit); the cut is in time; the
    engines and so the job path are the TAD cells'."""
    cfg = BENCH.config("theia-parts-fused-npr-1x1")
    sib = BENCH.config("theia-parts-fused-1x1")
    assert (cfg["env"], cfg["manager_args"], cfg["expect"]) \
        == (sib["env"], sib["manager_args"], sib["expect"])
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["retained_window_rows",
                                "checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "reference's" in cfg["guarantees"]["job_result"]
    assert set(sib["guarantees"]) < set(cfg["guarantees"])
    assert "exclude_labels" in cfg["assumed"]
    t = BENCH.traffic("npr-initial")
    dbscan = BENCH.traffic("tad-dbscan")
    g = t["generator"]
    assert g == {"law": "slices", "connections_per_producer": 4000,
                 "conns_per_block": 4000, "points_per_conn": 8,
                 "interval_seconds": 1}
    producer, jobs = t["workers"]
    assert producer == dbscan["workers"][0]
    assert g["conns_per_block"] * g["points_per_conn"] == 32000  # a block
    rows = producer["preload_blocks"] * 32000
    assert rows == cfg["retained_window_rows"] == 3456000
    assert cfg["retained_connections"] \
        == cfg["source_retained_connections"] == 4000
    assert cfg["source_retained_window_rows"] == 4000 * 43200
    assert jobs["job"] == {
        "resource": "networkpolicyrecommendations",
        "spec": {"jobType": "initial", "policyType": "anp-deny-applied",
                 "excludeLabels": False},
        "poll_interval_s": 0.05}
    assert t["checks"] == ["acks", "store_totals", "detector_series",
                           "npr_policies"]
    assert t["limits"] == {} and set(t["limits_why"]) == {"exact"}
    assert not getattr(extend.module("check", "npr_policies"), "limits", ())
    cell = "parts-fused-npr.npr-initial"
    assert BENCH.cell(cell)["chips"] == 1
    assert {m["name"] for m in BENCH.metrics_of(cell, "end_to_end")} \
        == {"job_turnaround_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(cell, "per_layer")}
    old = {m["name"] for m in BENCH.metrics_of(
        "parts-fused-12h-ns.tad-dbscan", "per_layer")}
    new = layer - old
    assert new == {
        "npr.read_ms", "npr.recommend_ms", "npr.write_ms", "npr.scan_ms",
        "npr.keys_ms", "npr.distinct_ms", "npr.decode_ms",
        "npr.aggregate_ms", "npr.emit_ms", "npr.rows_sorted",
        "npr.distinct_flows", "npr.policies", "npr.read_columns",
        "npr.read_bytes", "npr.distinct_device_ms",
        "npr_distinct_roofline", "npr.documents_direct"}
    # of the TAD cells' readers the generic ones, none that names
    # kind="tad" or a TAD kernel
    assert layer & old == {
        "job.run_s", "job.outside_run_s", "job.device_idle_share",
        "job.window_compiles", "manager_start_s", "preload_s",
        "warmup_s", "compile_s", "setup_cache_hits"}
    kernel = {"npr.distinct_ms", "npr.rows_sorted", "npr.distinct_flows",
              "npr.distinct_device_ms", "npr_distinct_roofline"}
    for name in new:
        m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader("per_layer", name)
        layer_name = "distinct kernel" if name in kernel else "NPR job"
        assert (m["layer"], m["moves"], m["workloads"]) == (
            layer_name, "job_turnaround_s", [cell])
        assert (reader["layer"], reader["moves"], reader["source"]) == (
            m["layer"], m["moves"], m["source"])
        assert 'kind="tad"' not in str(reader)


AGGPOD_CELL = "parts-fused-aggpod.tad-dbscan-pod"
AGGPOD_METRICS = {"aggpod.series", "aggpod.rows_merged",
                  "aggpod.dbscan_noise_roofline"}


def test_the_aggpod_cell_is_the_npr_cells_store_under_the_pod_query():
    """Connections are whole (4,000, every one in every block, a block
    one 8 s commit) and the cut is in time, as in the NPR cell, whose
    store this is; the spikes are the DBSCAN cell's; the query, the
    poll, the check and three readers are this cell's own."""
    cfg = BENCH.config("theia-parts-fused-aggpod-1x1")
    sib = BENCH.config("theia-parts-fused-1x1")
    npr = BENCH.config("theia-parts-fused-npr-1x1")
    assert (cfg["env"], cfg["manager_args"], cfg["expect"]) \
        == (sib["env"], sib["manager_args"], sib["expect"])
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["retained_window_rows",
                                "checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("throughput-anomaly-detection.md", "--agg-flow pod",
                 "anomaly_detection.py:511-565"):
        assert word in cfg["source"]
    assert set(sib["guarantees"]) < set(cfg["guarantees"])
    assert "every one and no other" in cfg["guarantees"]["job_result"]
    assert {"spike_law", "poll", "connection_population"} \
        <= set(cfg["assumed"])
    for key in ("retained_window_rows", "source_retained_window_rows",
                "retained_connections", "source_retained_connections"):
        assert cfg[key] == npr[key]
    t = BENCH.traffic("tad-dbscan-pod")
    dbscan = BENCH.traffic("tad-dbscan")
    nprt = BENCH.traffic("npr-initial")
    assert t["generator"] == {
        **dbscan["generator"], "connections_per_producer": 4000,
        "conns_per_block": 4000, "points_per_conn": 8}
    assert {k: v for k, v in t["generator"].items()
            if k in nprt["generator"] and k != "law"} \
        == {k: v for k, v in nprt["generator"].items() if k != "law"}
    producer, jobs = t["workers"]
    assert producer == nprt["workers"][0]
    assert producer["preload_blocks"] * 32000 \
        == cfg["retained_window_rows"] == 3456000
    assert jobs["job"] == {
        "resource": "throughputanomalydetectors",
        "spec": {"jobType": "DBSCAN", "aggFlow": "pod"},
        "poll_interval_s": 0.01}
    assert "10 ms" in t["what"] and t["trace_seconds"] == 8
    assert t["checks"] == ["acks", "store_totals", "detector_series",
                           "tad_agg_pod"]
    check_file = extend.module("check", "tad_agg_pod")
    assert set(t["limits"]) == set(check_file.limits) == {
        "aggpod_decision_mismatch", "aggpod_stddev_gap"}
    assert set(t["limits_why"]) == set(t["limits"]) | {"exact"}
    cell = BENCH.cell(AGGPOD_CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert not any(w["chips"] == 4 for w in BENCH.doc["workloads"])
    assert {m["name"] for m in BENCH.metrics_of(AGGPOD_CELL, "end_to_end")} \
        == {"job_turnaround_s", "setup_s"}
    layer = {m["name"] for m in BENCH.metrics_of(AGGPOD_CELL, "per_layer")}
    old = {m["name"] for m in BENCH.metrics_of(
        "parts-fused-12h-ns.tad-dbscan", "per_layer")}
    assert layer - old == AGGPOD_METRICS
    # `roofline.series_shape` takes a series for a connection; pair
    # tests are what the answer would be worth, which the sibling says
    assert old - layer == {"dbscan_noise_roofline", "job.dbscan_pair_tests"}
    for name in AGGPOD_METRICS:
        m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader("per_layer", name)
        assert (m["moves"], m["workloads"]) == ("job_turnaround_s",
                                                [AGGPOD_CELL])
        assert (reader["layer"], reader["moves"], reader["source"]) == (
            m["layer"], m["moves"], m["source"])
    # entries are only ever appended: PR 48's last stays the 126th
    assert BENCH.doc["per_layer"][125]["name"] \
        == "aggpod.dbscan_noise_roofline"
    # appended behind the trim cell's, the ninth
    assert BENCH.doc["workloads"][8] == cell


def test_the_aggpod_cell_is_rehearsed_on_the_cpu_backend():
    """`benchmarks/selftest.py`'s rehearsal of the cell at a tiny size,
    with no edit to it: manager child, preload, warm-up job, window,
    checks, and every host-side reader of a traced run; plumbing only,
    no number of it is a result. 64 connections x 128 points fall on
    118 pod series and 384 of 15,488 row contributions are merged."""
    from benchmarks import selftest

    cell = BENCH.cell(AGGPOD_CELL)
    plain, traced = selftest.rehearse(cell, trace=True)
    for out in (plain, traced):
        assert out["correct"] and out["failed"] == 0
        assert {"aggpod_decision_mismatch", "aggpod_stddev_gap",
                "aggpod_throughput_gap", "aggpod_kind_gap",
                "aggpod_series_gap", "aggpod_rows_merged_gap",
                "jobs_not_completed", "detector_series_gap"} \
            <= set(out["checks"])
    assert set(plain["metrics"]) == {"job_turnaround_s", "setup_s"}
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    device_only = {"job.dbscan_device_ms", "aggpod.dbscan_noise_roofline"}
    assert set(got) == {m["name"] for m in BENCH.metrics_of(
        cell["name"], "per_layer")} - device_only
    assert got["aggpod.series"] == 118
    assert got["aggpod.rows_merged"] == 384
    assert got["job.tensorize_direct_rows"] == 15488
    assert got["job.dbscan_sorted_points"] == 15488 - 384
    # the two sides' namespace and labels, the seconds, the value
    assert got["job.read_columns"] == 6
    assert got["job.tensorize_ms"] >= got["job.tensorize_keys_ms"] \
        + got["job.tensorize_group_ms"] + got["job.tensorize_decode_ms"] \
        - 1e-6


def test_the_aggpod_cells_counters_reduce_to_a_jobs_figures():
    """`aggpod.series` and `aggpod.rows_merged` read the program's own
    exposition around one pod-mode job; a connection-mode job moves
    neither (its series count under `agg="None"`, its merged rows are
    0 there), and a manager without the counters (the parent) gives
    nothing. `aggpod.dbscan_noise_roofline` reads a trace's
    `module:jit_dbscan_noise` line with the bytes of the population's
    1,985 series."""
    import time

    from benchmarks import prom
    from theia_tpu.analytics import TadQuerySpec, build_series, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase

    traffic = BENCH.traffic("tad-dbscan-pod")
    harness.resolve_all(BENCH, AGGPOD_CELL, traffic)
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=40, points_per_series=12, n_namespaces=2,
        pods_per_namespace=3, seed=2)))
    flows = db.flows.scan()
    series = build_series(flows, TadQuerySpec(agg_flow="pod"))
    # a row counts once for each side that has labels (code 0 is '')
    both_sides = sum(int(np.count_nonzero(flows[f"{side}PodLabels"]))
                     for side in ("source", "destination"))

    def around(spec):
        before = prom.parse(exposition.render())
        run_tad(db, "DBSCAN", spec, now=int(time.time()),
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
        return {"metrics_before": before,
                "metrics_after": prom.parse(exposition.render())}

    def read(name, data):
        reader = BENCH.reader("per_layer", name)
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    pod = around(TadQuerySpec(agg_flow="pod"))
    assert read("aggpod.series", pod) == series.n_series <= 12
    merged = both_sides - int(series.mask.sum())
    assert read("aggpod.rows_merged", pod) == merged > 0
    conn = around(TadQuerySpec())
    assert read("aggpod.series", conn) == 0
    assert read("aggpod.rows_merged", conn) == 0
    none = 'theia_job_series_rows_merged_total{kind="tad",agg="None"}'
    assert conn["metrics_after"][none] == 0
    for name in ("aggpod.series", "aggpod.rows_merged"):
        key = BENCH.reader("per_layer", name)["series"]
        assert key in pod["metrics_after"]
        pod["metrics_after"].pop(key), pod["metrics_before"].pop(key, None)
        assert read(name, pod) is None

    traced = {
        "traffic": traffic, "device": {"kind": "TPU v5 lite"},
        "specs": [{"role": "producer", "preload_blocks": 108, "seed": 7,
                   "producer": 0}, {"role": "jobs"}],
        "trace": {"ops": [("module:jit_dbscan_noise(123)", 0.01, 2)],
                  "busy_s": 0.02, "window_s": 8.0}}
    assert read("job.dbscan_device_ms", traced) == pytest.approx(5.0)
    assert read("aggpod.dbscan_noise_roofline", traced) == pytest.approx(
        100 * (10298180 / 819e9) / 0.005)
    traced["trace"]["ops"] = []
    assert read("aggpod.dbscan_noise_roofline", traced) is None


def test_the_npr_cell_is_rehearsed_on_the_cpu_backend():
    """`benchmarks/selftest.py`'s rehearsal of the cell at a tiny size,
    with no edit to it: manager child, preload, warm-up job, window,
    checks, and every host-side reader of a traced run; plumbing only,
    no number of it is a result. 64 connections x 4 points x 32 blocks
    is under `_AUTO_THRESHOLD`: the counters count what went into
    `device_distinct` whichever path it took."""
    from benchmarks import selftest

    cell = BENCH.cell("parts-fused-npr.npr-initial")
    plain, traced = selftest.rehearse(cell, trace=True)
    for out in (plain, traced):
        assert out["correct"] and out["failed"] == 0
        assert {"npr_policies_missing", "npr_policies_unexpected",
                "npr_policy_kind_gap", "npr_distinct_flows_gap",
                "npr_rows_sorted_gap", "jobs_not_completed"} \
            <= set(out["checks"])
    assert set(plain["metrics"]) == {"job_turnaround_s", "setup_s"}
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    device_only = {"npr.distinct_device_ms", "npr_distinct_roofline"}
    assert set(got) == {m["name"] for m in BENCH.metrics_of(
        cell["name"], "per_layer")} - device_only
    # the client finds state, startTime and endTime before the YAML
    assert got["job.run_s"] > 0
    # the query's columns (PR 45), every one int32: 44 B a row
    assert got["npr.read_columns"] == 11
    assert got["npr.read_bytes"] == 44 * 64 * 4 * 32
    assert got["npr.rows_sorted"] % 4 == 0 and got["npr.rows_sorted"] > 0
    # an ANP and a reject ACNP a group, and the allow list's three
    assert got["npr.policies"] % 2 == 1 and got["npr.policies"] > 3
    # every scalar of the generator's population is plain
    assert got["npr.documents_direct"] == got["npr.policies"]
    assert got["npr.read_ms"] >= got["npr.scan_ms"] + got["npr.keys_ms"] \
        + got["npr.distinct_ms"] + got["npr.decode_ms"] - 1e-6
    assert got["npr.recommend_ms"] >= got["npr.aggregate_ms"] \
        + got["npr.emit_ms"] - 1e-6


def test_the_npr_cells_direct_documents_reduce_to_a_jobs_figures():
    """`npr.documents_direct` reads the program's own exposition around
    one NPR job: the documents `dump_yaml` wrote itself, a job, which
    over the synthetic population's plain names are all that
    `npr.policies` counts; a manager without the counter (the parent)
    gives nothing."""
    from benchmarks import prom
    from theia_tpu.analytics import run_npr
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.runner.progress import NPR_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=24, points_per_series=5, seed=2)))
    before = prom.parse(exposition.render())
    for job in ("one", "two"):
        run_npr(db, "initial", recommendation_id=job,
                progress=JobProgress(job, NPR_STAGES, kind="npr"))
    after = prom.parse(exposition.render())
    counters = {"metrics_before": before, "metrics_after": after}

    def read(name):
        reader = BENCH.reader("per_layer", name)
        return extend.resolve("reduction", reader["reduce"])(
            counters, reader)

    rows = db.recommendations.scan().to_rows()
    assert read("npr.documents_direct") == read("npr.policies") \
        == len(rows) / 2 > 3
    series = BENCH.reader("per_layer", "npr.documents_direct")["series"]
    assert series == "theia_job_npr_documents_direct_total" in after
    after.pop(series), before.pop(series, None)
    assert read("npr.documents_direct") is None


def test_an_operator_no_manager_answers_ends_in_the_warm_up(tmp_path):
    """The parent commit has no /admin/checkpoint: the role must end
    its worker in set-up (exit 1 for the run), never hang or go on."""
    import http.server
    import threading

    class NotFound(http.server.BaseHTTPRequestHandler):
        def do_POST(self):                               # noqa: N802
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *args):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), NotFound)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        extend.use(manifest.HERE)
        role = extend.resolve("role", "operator")({
            "addr": f"http://127.0.0.1:{httpd.server_address[1]}",
            "offset_s": 3.0})
        assert role.handle(["preload"]) == {"event": "preloaded",
                                            "records": []}
        with pytest.raises(SystemExit, match="404"):
            role.handle(["warm"])
    finally:
        httpd.shutdown()
        httpd.server_close()


@pytest.mark.parametrize("name,data,want", [
    ("record_field", {"p": {"role": "operator",
                            "field": "stages_ms.hold"}}, 1500.0),
    ("record_field", {"p": {"role": "operator", "field": "seconds",
                            "scale": 1000.0}}, 20000.0),
    ("record_field", {"p": {"role": "operator", "field": "absent"}},
     None),
    ("longest_ack_gap", {"p": {"role": "producer"}}, 2500.0),
    ("rate_between", {"p": {"role": "producer", "field": "rows",
                            "between": "operator"}}, 3 * 32000 / 20.0),
    # the trim cell's readers, over the same run with a trimmer's record
    ("record_field", {"role": "trimmer",
                      "p": {"role": "trimmer",
                            "field": "stages_ms.delete_flows"}}, 1500.0),
    ("record_field", {"role": "trimmer",
                      "p": {"role": "trimmer", "field": "seconds",
                            "scale": 1000.0}}, 20000.0),
    ("rate_between", {"role": "trimmer",
                      "p": {"role": "producer", "field": "rows",
                            "between": "trimmer"}}, 3 * 32000 / 20.0),
])
def test_reductions_the_cell_brings(name, data, want):
    extend.use(manifest.HERE)
    acks = [100.5, 101.0, 103.5, 104.0, 121.0, 140.0]
    run = {
        "t_open": 100.0, "seconds": 51.0, "clean": [0.0, float("inf")],
        "specs": [{"role": "producer"},
                  {"role": data.get("role", "operator")}],
        "results": [
            {"records": [{"ack": t, "status": 200, "rows": 32000}
                         for t in acks]},
            {"records": [{"send": 103.0, "ack": 123.0, "status": 200,
                          "seconds": 20.0,
                          "stages_ms": {"hold": 1500.0,
                                        "delete_flows": 1500.0}}]}],
    }
    got = extend.resolve("reduction", name)(run, data["p"])
    if name == "longest_ack_gap":
        # no ack between 121 and 140
        assert got == pytest.approx(19000.0)
        run["clean"] = [0.0, 105.0]      # a traced run: before the profiler
        got = extend.resolve("reduction", name)(run, data["p"])
    assert got == (want if want is None else pytest.approx(want))


def test_the_arima_cells_counters_reduce_to_a_jobs_figures():
    """`job.arima_loop_iterations` (PR 35) and `job.arima_fits` read
    the program's own exposition around one ARIMA job: the loop's
    turns and the fits of that job. A manager that does not export the
    counter (the parent of the PR that brought it) gives nothing."""
    import time

    from benchmarks import prom
    from theia_tpu.analytics import TadQuerySpec, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.ops.arima import css_loop_iterations
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase

    cell = "parts-fused-12h.tad-arima"
    harness.resolve_all(BENCH, cell, BENCH.traffic("tad-arima"))
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=3, points_per_series=48, seed=2)))
    before = prom.parse(exposition.render())
    run_tad(db, "ARIMA", TadQuerySpec(refit_every=4),
            now=int(time.time()),
            progress=JobProgress("job", TAD_STAGES, kind="tad"))
    after = prom.parse(exposition.render())

    def read(name, before, after):
        reader = BENCH.reader("per_layer", name)
        return extend.resolve("reduction", reader["reduce"])(
            {"metrics_before": before, "metrics_after": after}, reader)

    assert read("job.arima_fits", before, after) == 3 * 12
    assert read("job.arima_loop_iterations", before, after) == 12 \
        == css_loop_iterations(3, 48, 4)
    series = BENCH.reader("per_layer",
                          "job.arima_loop_iterations")["series"]
    assert series in after
    after.pop(series), before.pop(series, None)
    assert read("job.arima_loop_iterations", before, after) is None


def test_the_dbscan_cells_metrics_reduce_to_a_jobs_figures():
    """`job.dbscan_pair_tests` reads the program's own exposition
    around one DBSCAN job: the sum over series of (valid points)^2; a
    manager without the counter gives nothing. `job.dbscan_sorted_points`
    (PR 40) likewise: the valid points the job sorted, nothing from a
    manager without that counter (the parent). The two device metrics
    read a trace's `module:jit_dbscan_noise` line: ms a call, and the
    kernel file's bytes at the memory's peak over it."""
    import time

    from benchmarks import prom
    from benchmarks.kernels import dbscan_noise as kernel
    from theia_tpu.analytics import TadQuerySpec, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.ops.dbscan import dbscan_noise
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase

    cell = "parts-fused-12h-ns.tad-dbscan"
    traffic = BENCH.traffic("tad-dbscan")
    harness.resolve_all(BENCH, cell, traffic)
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=3, points_per_series=48, seed=2)))
    before = prom.parse(exposition.render())
    run_tad(db, "DBSCAN", TadQuerySpec(), now=int(time.time()),
            progress=JobProgress("job", TAD_STAGES, kind="tad"))
    after = prom.parse(exposition.render())

    def read(name, data):
        reader = BENCH.reader("per_layer", name)
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    counters = {"metrics_before": before, "metrics_after": after}
    assert read("job.dbscan_pair_tests", counters) == 3 * 48 * 48 \
        == kernel.pair_tests(3, 48)
    series = BENCH.reader("per_layer", "job.dbscan_pair_tests")["series"]
    assert series in after
    after.pop(series), before.pop(series, None)
    assert read("job.dbscan_pair_tests", counters) is None
    assert read("job.dbscan_sorted_points", counters) == 3 * 48
    series = BENCH.reader("per_layer", "job.dbscan_sorted_points")["series"]
    assert series == "theia_job_dbscan_sorted_points_total" in after
    after.pop(series), before.pop(series, None)
    assert read("job.dbscan_sorted_points", counters) is None

    # the jitted program's name is what the trace's line carries
    assert dbscan_noise.__name__ == "dbscan_noise"
    traced = {
        "traffic": traffic, "device": {"kind": "TPU v5 lite"},
        "specs": [{"role": "producer", "preload_blocks": 108},
                  {"role": "jobs"}],
        "trace": {"ops": [("module:jit_dbscan_noise(123)", 3.0, 2),
                          ("module:jit_masked_stddev", 0.001, 2)],
                  "busy_s": 3.1, "window_s": 8.0}}
    assert read("job.dbscan_device_ms", traced) == pytest.approx(1500.0)
    assert read("dbscan_noise_roofline", traced) == pytest.approx(
        100 * (20736320 / 819e9) / 1.5)
    traced["trace"]["ops"] = traced["trace"]["ops"][1:]
    assert read("job.dbscan_device_ms", traced) is None
    assert read("dbscan_noise_roofline", traced) is None


def test_the_series_ways_readers_reduce_to_a_jobs_series():
    """`job.tensorize_series_cells` and `job.tensorize_series_sorted`
    (PR 50; readers only: `per_layer` is at its 128 entries, so a
    `benchmark` PR declares them) read the program's own exposition
    around one job: a pod-mode job's series of more than one
    connection under `cells`, none under `sorted`; a connection-mode
    job 0 under both; nothing from a manager without the family (the
    parent)."""
    import time

    from benchmarks import prom
    from theia_tpu.analytics import TadQuerySpec, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase
    from theia_tpu.utils.native import native_available

    names = ("job.tensorize_series_cells", "job.tensorize_series_sorted")
    readers = {name: BENCH.reader("per_layer", name) for name in names}
    pattern = BENCH.reader("per_layer", "aggpod.series")
    for name, reader in readers.items():
        assert {k: v for k, v in reader.items() if k != "series"} \
            == {k: v for k, v in pattern.items() if k != "series"}
        assert reader["series"] == (
            'theia_job_tensorize_series_total{kind="tad",how="%s"}'
            % name.rsplit("_", 1)[1])
    if not native_available():
        pytest.skip("native library unavailable")

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=40, points_per_series=12, n_namespaces=2,
        pods_per_namespace=3, seed=2)))

    def around(spec):
        before = prom.parse(exposition.render())
        run_tad(db, "DBSCAN", spec, now=int(time.time()),
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
        return {"metrics_before": before,
                "metrics_after": prom.parse(exposition.render())}

    def read(name, data):
        reader = readers[name]
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    pod = around(TadQuerySpec(agg_flow="pod"))
    built = BENCH.reader("per_layer", "aggpod.series")
    built = extend.resolve("reduction", built["reduce"])(pod, built)
    assert 0 < read(names[0], pod) <= built
    assert read(names[1], pod) == 0
    conn = around(TadQuerySpec())
    assert read(names[0], conn) == read(names[1], conn) == 0
    for name in names:
        key = readers[name]["series"]
        assert key in pod["metrics_after"]
        pod["metrics_after"].pop(key), pod["metrics_before"].pop(key, None)
        assert read(name, pod) is None


TAD_CELLS = ["parts-fused.tad-ewma", "parts-fused-12h.tad-arima",
             "parts-fused-12h-ns.tad-dbscan"]


def test_the_direct_rows_metric_reduces_to_a_jobs_rows(monkeypatch):
    """`job.tensorize_direct_rows` (PR 46) reads the program's own
    exposition around one TAD job: the rows the native builder grouped
    from the batch's columns in place, all of a job's in the three TAD
    cells; 0 where the numpy path took them (its rows count under
    `path="numpy"`) and nothing from a manager without the counter
    (the parent)."""
    import time

    from benchmarks import prom
    from theia_tpu.analytics import TadQuerySpec, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.analytics import series as series_mod
    from theia_tpu.utils.native import native_available
    from theia_tpu.obs import prom as exposition
    from theia_tpu.runner.progress import TAD_STAGES, JobProgress
    from theia_tpu.store import FlowDatabase

    name = "job.tensorize_direct_rows"
    m = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
    reader = BENCH.reader("per_layer", name)
    assert (m["layer"], m["moves"], m["workloads"], m["source"]) == (
        "TAD host path", "job_turnaround_s",
        TAD_CELLS + ["parts-fused-aggpod.tad-dbscan-pod"], "program_counter")
    assert (reader["layer"], reader["moves"], reader["source"]) == (
        m["layer"], m["moves"], m["source"])
    if not native_available():
        pytest.skip("native library unavailable")

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=3, points_per_series=48, seed=2)))

    def around_a_job(native):
        if not native:      # as in a process without the library
            monkeypatch.setattr(series_mod, "build_padded_series",
                                lambda parts, op, dtype: None)
        before = prom.parse(exposition.render())
        run_tad(db, "EWMA", TadQuerySpec(), now=int(time.time()),
                progress=JobProgress("job", TAD_STAGES, kind="tad"))
        return {"metrics_before": before,
                "metrics_after": prom.parse(exposition.render())}

    def read(data):
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    data = around_a_job(True)
    assert reader["series"] in data["metrics_after"]
    assert read(data) == 3 * 48
    assert read(around_a_job(False)) == 0
    for side in data.values():
        side.pop(reader["series"], None)
    assert read(data) is None


VOL_CELL = "default-vol.dashboards-volume"
DASH_CELL = "default.dashboards-retained"


def _reader_panels(traffic):
    import urllib.parse
    out = {}
    for p in next(g for g in traffic["workers"]
                  if g["role"] == "reader")["panels"]:
        url = urllib.parse.urlsplit(p["path"])
        q = {k: int(v[0]) for k, v in
             urllib.parse.parse_qs(url.query).items()}
        out[p["name"]] = (url.path, p["closed"], q)
    return out


def test_the_volume_cell_is_the_dashboards_cell_on_the_volumes_store():
    """The two cells ask the same eight panels over as many rows each
    and take the same ingest; they differ in the store around a
    panel's range (13.6 M rows of four streams against 1.0 M of one)
    and in nothing else. The configuration is the trim cell's
    deployment, read."""
    from benchmarks.gen import DEFAULT_START

    dash = BENCH.traffic("dashboards-retained")
    vol = BENCH.traffic("dashboards-volume")
    assert vol["generator"] == {"law": "slices", **dash["generator"]}
    assert (vol["checks"], vol["limits"], vol["trace_seconds"]) == (
        dash["checks"], dash["limits"], dash["trace_seconds"])
    grafana, producers, reader = vol["workers"]
    # first, so that the harness reads its answer to a phase first
    assert grafana == {"role": "grafana", "count": 1}
    assert producers == {
        "role": "producer", "count": 4, "preload_blocks": 105,
        "warm_blocks": 1, "prepared_blocks": 116, "probe_blocks": 4,
        "schedule": {"period_s": 32.0, "stagger_s": 8.0}}
    # preload, warm-up, a window of up to 96 s, the probes
    assert producers["prepared_blocks"] >= 105 + 1 + 3 + 4
    # one block every 8 s at the manager: the documented 4,000 records/s
    old = dash["workers"][0]
    sched = producers["schedule"]
    assert sched["period_s"] / producers["count"] == sched["stagger_s"] \
        == old["schedule"]["period_s"] / old["count"] == 8.0
    assert 32000 / sched["stagger_s"] == 4000
    assert (reader["role"], reader["count"]) == ("reader", 1)
    # a data second holds 32,000 rows here and 8,000 there
    want = _reader_panels(dash)
    got = _reader_panels(vol)
    assert list(got) == list(want) == [
        "homepage", "flow_records", "pod_to_pod", "pod_to_service",
        "pod_to_external", "node_to_node", "networkpolicy",
        "network_topology"]
    offsets = {}
    for name, (path, closed, q) in got.items():
        old_path, old_closed, old_q = want[name]
        assert (path, closed) == (old_path, old_closed)
        assert {k: v for k, v in q.items() if k not in ("start", "end")} \
            == {k: v for k, v in old_q.items()
                if k not in ("start", "end")}
        if "start" in q:
            assert (q["end"] - q["start"]) * 32000 \
                == (old_q["end"] - old_q["start"]) * 8000
            offsets[name] = (q["start"] - DEFAULT_START,
                             q["end"] - DEFAULT_START)
    assert offsets == {
        "flow_records": (96, 128), "pod_to_pod": (388, 420),
        "pod_to_service": (404, 420), "pod_to_external": (66, 82),
        "node_to_node": (388, 420), "networkpolicy": (64, 96),
        "network_topology": (96, 104)}
    # every range lies inside the preloaded seconds; the old ones inside
    # the rehearsal's 128, and one of them cuts the 4 s parts
    assert all(0 <= a < b <= 4 * producers["preload_blocks"]
               for a, b in offsets.values())
    assert offsets["pod_to_external"][0] % 4 \
        and offsets["pod_to_external"][1] % 4

    cfg = BENCH.config("theia-default-vol-1x1")
    trim = BENCH.config("theia-default-trim-1x1")
    entry = next(c for c in BENCH.doc["configs"]
                 if c["name"] == cfg["name"])
    # appended: the ninth configuration, the tenth cell
    assert BENCH.doc["configs"][8] is entry
    assert entry["reduced"] == ["checkpoint_interval_s"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert (cfg["env"], cfg["manager_args"], cfg["expect"]) == (
        {}, trim["manager_args"], trim["expect"])
    assert cfg["monitor"] == {**trim["monitor"],
                              "note": cfg["monitor"]["note"]}
    assert cfg["capacity_bytes"] == cfg["source_capacity_bytes"] == 8 << 30
    assert {"exactly_once", "durability", "readable", "panel_exact",
            "range_contract"} == set(cfg["guarantees"])
    assert {"ttl", "capacity_counted_in", "data_clock", "streams",
            "panel_ranges", "refresh"} <= set(cfg["assumed"])
    # rows are not cut: 89.7 % of the trigger at the window's open and
    # 94.6 % after the last probe block, so no round trims in a run
    trigger = cfg["capacity_bytes"] * cfg["monitor"]["threshold"] // 284
    assert cfg["retained_window_rows"] == trigger == 15123124
    at_open = producers["count"] * 32000 * (
        producers["preload_blocks"] + producers["warm_blocks"])
    at_end = at_open + 32000 * (7 + producers["count"]
                                * producers["probe_blocks"])
    assert (at_open, at_end) == (13568000, 14304000)
    assert round(100 * at_open / trigger, 1) == 89.7
    assert round(100 * at_end / trigger, 1) == 94.6

    cell = BENCH.cell(VOL_CELL)
    assert BENCH.doc["workloads"][9] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "dashboards-volume", 1)
    assert len(cell["why"]) <= 200
    # appended to every list the present cell is on, and to no other;
    # `per_layer` is at its 128 entries, so the two new readers are
    # files without an entry
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in BENCH.metrics_of(VOL_CELL, section)] \
            == [m["name"] for m in BENCH.metrics_of(DASH_CELL, section)]
    for m in BENCH.doc["end_to_end"] + BENCH.doc["per_layer"]:
        if VOL_CELL in m.get("workloads", []):
            assert m["workloads"][-2:] == [DASH_CELL, VOL_CELL]
    assert len(BENCH.doc["per_layer"]) == 128
    assert not {"dash.parts_read", "dash.parts_pruned"} & {
        m["name"] for m in BENCH.doc["per_layer"]}


def test_the_volume_cell_is_rehearsed_on_the_cpu_backend():
    """The cell at `rehearsal.TINY_RETAINED` with no edit to it: the
    range contract held in the preload phase, four producers, the
    reader over the eight panels, the five checks (the three newest
    ranges lie beyond the rehearsal's 128 seconds and answer empty,
    equal to the reference's), every host-side reader of a traced run;
    plumbing only, no number of it is a result."""
    from benchmarks import selftest

    plain, traced = selftest.rehearse(BENCH.cell(VOL_CELL), trace=True)
    for out in (plain, traced):
        assert out["correct"] and out["failed"] == 0
        assert {"acks_not_whole", "store_rows_gap", "store_octets_gap",
                "detector_series_gap", "alert_probe_block_gap",
                "panel_requests_failed",
                "panels_changed_over_closed_range",
                "panels_differ_from_reference"} <= set(out["checks"])
    assert set(plain["metrics"]) == {"dashboard_panel_p50_ms", "setup_s"}
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert {"dash.slowest_panel_p50_ms", "dash.server_ms_networkpolicy",
            "dash.server_ms_pod_to_pod", "dash.scan_ms",
            "dash.aggregate_ms", "dash.encode_ms", "dash.rows_scanned",
            "dash.scan_cpu_ms", "dash.aggregate_cpu_ms",
            "dash.scan_faults", "preload_s", "warmup_s"} <= set(got)
    assert got["dash.rows_scanned"] > 0


def test_the_parts_readers_reduce_to_a_windows_figures():
    """`dash.parts_read` and `dash.parts_pruned` (PR 51; readers only:
    `per_layer` is at its 128 entries, so a `benchmark` PR declares
    them) read the program's own exposition around a window's panel
    requests: the batches of `flows` and the parts of the views that
    the reads opened and those they skipped by their bounds, over all
    tables; nothing from a manager without the family (the parent)."""
    from benchmarks import prom
    from theia_tpu.dashboards import queries
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.obs import prom as exposition
    from theia_tpu.store import FlowDatabase

    names = ("dash.parts_read", "dash.parts_pruned")
    readers = {name: BENCH.reader("per_layer", name) for name in names}
    pattern = BENCH.reader("per_layer", "dash.rows_scanned")
    for name, reader in readers.items():
        assert {k: reader[k] for k in ("source", "layer", "moves")} \
            == {k: pattern[k] for k in ("source", "layer", "moves")}
        assert (reader["reduce"], reader["series"], reader["where"]) == (
            "counter_rise_where", "theia_dashboard_parts_total",
            {"how": name.rsplit("_", 1)[1]})

    t0 = 1_700_000_000
    db = FlowDatabase()
    for i in range(20):         # twenty blocks of four seconds each
        db.insert_flows(generate_flows(SynthConfig(
            n_series=8, points_per_series=4, start_time=t0 + 4 * i,
            seed=1)))
    before = prom.parse(exposition.render())
    # a view over two blocks' seconds, flows over one block's and a
    # half, and the page without a range
    queries.panel_json(db, "pod_to_pod", {"start": str(t0 + 4),
                                          "end": str(t0 + 12)})
    queries.panel_json(db, "network_topology", {"start": str(t0 + 14),
                                                "end": str(t0 + 20)})
    queries.panel_json(db, "homepage", {})
    data = {"metrics_before": before,
            "metrics_after": prom.parse(exposition.render())}

    def read(name):
        reader = readers[name]
        return extend.resolve("reduction", reader["reduce"])(data, reader)

    assert read("dash.parts_read") == 2 + 2 + 20
    assert read("dash.parts_pruned") == 18 + 18 + 0
    rows = BENCH.reader("per_layer", "dash.rows_scanned")
    assert extend.resolve("reduction", rows["reduce"])(data, rows) \
        == 2 * 32 + 2 * 32 + 20 * 32
    for side in data.values():
        for key in [k for k in side
                    if k.startswith("theia_dashboard_parts_total")]:
            del side[key]
    assert read("dash.parts_read") is None
    assert read("dash.parts_pruned") is None


def test_an_export_that_sends_no_range_ends_the_run_in_the_preload():
    """The parent commit's export carries `urlPath` only: the role
    must end its worker in the preload phase with a message that names
    the panel, as it must for a dashboard the manager does not export
    and for a range on `homepage`; this commit's own export passes."""
    import http.server
    import json
    import threading

    from theia_tpu.dashboards import grafana_dashboard

    served = {}

    class Canned(http.server.BaseHTTPRequestHandler):
        def do_GET(self):                                # noqa: N802
            name = self.path.split("?")[0].rsplit("/", 1)[1]
            assert self.path.endswith("?format=grafana")
            status, doc = served.get(name, (404, {}))
            body = json.dumps(doc).encode()
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    def without_params(doc):
        doc = json.loads(json.dumps(doc))
        for panel in doc["panels"]:
            for target in panel["targets"]:
                target.pop("params", None)
        return doc

    httpd = http.server.HTTPServer(("127.0.0.1", 0), Canned)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        extend.use(manifest.HERE)
        traffic = BENCH.traffic("dashboards-volume")
        role = extend.resolve("role", "grafana")({
            "addr": f"http://127.0.0.1:{httpd.server_address[1]}",
            "traffic": traffic})
        assert role.ranged == {
            name: name != "homepage" for name in _reader_panels(traffic)}
        ours = {name: (200, grafana_dashboard(name))
                for name in role.ranged}
        served.update(ours)
        assert role.handle(["preload"]) == {
            "event": "preloaded", "records": [], "dashboards": 8,
            "targets": sum(len(p["targets"]) for _, d in ours.values()
                           for p in d["panels"])}
        assert role.handle(["warm"]) == {"event": "warmed", "records": []}
        assert role.handle(["run", "0", "1"]) == {"event": "done",
                                                 "records": []}
        assert role.handle(["probe", "4"]) == {"event": "probed",
                                               "records": []}
        # the parent's export
        served.update({n: (200, without_params(d))
                       for n, (_, d) in ours.items()})
        with pytest.raises(SystemExit, match="dashboard flow_records, "
                           "panel 'Flow records'.*start=None"):
            role.handle(["preload"])
        served.update(ours)
        half = json.loads(json.dumps(ours["pod_to_pod"][1]))
        half["panels"][1]["targets"][0]["params"][1][1] = "${__to}"
        served["pod_to_pod"] = (200, half)
        with pytest.raises(SystemExit, match="dashboard pod_to_pod, "
                           "panel 'Throughput'.*end='\\$\\{__to\\}'"):
            role.handle(["preload"])
        served.update(ours)
        home = json.loads(json.dumps(ours["homepage"][1]))
        home["panels"][0]["targets"][0]["params"] = [["start", "1"]]
        served["homepage"] = (200, home)
        with pytest.raises(SystemExit, match="dashboard homepage.*"
                           "carry start where the panel takes no range"):
            role.handle(["preload"])
        served.update(ours)
        del served["networkpolicy"]
        with pytest.raises(SystemExit, match="networkpolicy.*404"):
            role.handle(["preload"])
    finally:
        httpd.shutdown()
        httpd.server_close()
    # a traffic file that asks a panel with half a range is broken
    broken = json.loads(json.dumps(traffic))
    broken["workers"][2]["panels"][2]["path"] = \
        "/dashboards/api/pod_to_pod?start=1"
    with pytest.raises(SystemExit, match="pod_to_pod"):
        extend.resolve("role", "grafana")({"addr": "http://127.0.0.1:1",
                                           "traffic": broken})
