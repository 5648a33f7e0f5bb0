"""Job progress reporting, shaped like the Spark UI REST the reference
controllers scrape (pkg/controller/util.go:129-159 reads
/api/v1/applications/<id>/stages and surfaces completedStages/
totalStages into CRD status).

The runner updates a JSON document after every stage; it is written
atomically to a file (for the file-based manager/controller seam) and
kept in memory for in-process callers.

Every stage is also timed, here and nowhere else, for every job kind:
`theia_job_stage_seconds{kind,stage}`, the same seconds as `stagesMs`
on the enclosing `job.run` span, and a profiler annotation
`job.<stage>` while a capture runs (obs/trace.py StageMarks). A stage
may name its parts (`part`: `job.score.transfer`, `.kernel`, `.rows`),
which are timed the same way and leave the stage's seconds whole.
"""

from __future__ import annotations

import json
import threading
import time
from typing import List, Optional

from ..analysis.lockdep import named_lock
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..utils import atomic_write

_M_STAGE = _trace.StageSeries(
    "theia_job_stage_seconds",
    "Wall time of one stage of a job run (read, tensorize, score, "
    "write, ...: the stages JobProgress announces)",
    labelnames=("kind", "stage"))
_M_PART = _trace.StageSeries(
    "theia_job_stage_part_seconds",
    "Wall time of one named part of a job's stage (score: transfer, "
    "kernel, rows); the stage's own seconds include it",
    labelnames=("kind", "stage", "part"))
_M_SERIES_SCORED = _metrics.counter(
    "theia_job_series_scored_total",
    "Series a job's kernel scored", labelnames=("kind", "algo"))
_M_POINTS_SCORED = _metrics.counter(
    "theia_job_points_scored_total",
    "Valid points of those series", labelnames=("kind", "algo"))
_M_ARIMA_FITS = _metrics.counter(
    "theia_job_arima_fits_total",
    "Prefix fits of ARIMA jobs: series x refit groups, each one "
    "Hannan-Rissanen fit and one column of the residual recursion's "
    "carry")
_M_ARIMA_LOOP_ITERATIONS = _metrics.counter(
    "theia_job_arima_loop_iterations_total",
    "Trip count of the sequential loop of ARIMA jobs' residual "
    "recursion as compiled for the job's shape, summed over its slabs "
    "of series (ops.arima.css_loop_iterations)")
_M_DBSCAN_PAIR_TESTS = _metrics.counter(
    "theia_job_dbscan_pair_tests_total",
    "Pairs of points one pass of DBSCAN jobs' definition tests: the "
    "sum over series of (valid points)^2 (ops.dbscan.pair_tests), "
    "from the mask; what the answer is worth, not what the program "
    "does, which sorts (theia_job_dbscan_sorted_points_total)")
_M_DBSCAN_SORTED_POINTS = _metrics.counter(
    "theia_job_dbscan_sorted_points_total",
    "Valid points of the batches DBSCAN jobs gave to the sorting "
    "kernel (ops.dbscan.sorted_points): it decides each from its "
    "neighbours in sorted order")
_M_NPR_ROWS_SORTED = _metrics.counter(
    "theia_job_npr_rows_sorted_total",
    "Rows that passed a policy-recommendation job's WHERE clause and "
    "went into its DISTINCT (analytics.npr_device.device_distinct, "
    "whichever path it took)")
_M_NPR_DISTINCT_FLOWS = _metrics.counter(
    "theia_job_npr_distinct_flows_total",
    "Distinct flow 9-tuples that came out of it")
_M_NPR_POLICIES = _metrics.counter(
    "theia_job_npr_policies_total",
    "Policy documents a policy-recommendation job wrote, by the "
    "result table's kind (anp, acnp, acg, knp)", labelnames=("kind",))
_M_NPR_DOCUMENTS_DIRECT = _metrics.counter(
    "theia_job_npr_documents_direct_total",
    "Those of them whose YAML analytics.policy_gen.dump_yaml wrote "
    "itself; the rest held a scalar it leaves to PyYAML")
_M_READ_ROWS = _metrics.counter(
    "theia_job_read_rows_total",
    "Rows of the batch a job's read stage handed on",
    labelnames=("kind",))
_M_READ_COLUMNS = _metrics.counter(
    "theia_job_read_columns_total",
    "Columns of those batches: what the job's query names, not the "
    "table's 52", labelnames=("kind",))
_M_READ_BYTES = _metrics.counter(
    "theia_job_read_bytes_total",
    "Column bytes of those batches", labelnames=("kind",))
_M_TENSORIZE_ROWS = _metrics.counter(
    "theia_job_tensorize_rows_total",
    "Rows a job's tensorize stage grouped into series, by the path "
    "that grouped them: columns (the native builder, from the batch's "
    "columns in place) or numpy (the fallback and its key matrix)",
    labelnames=("kind", "path"))
_M_SERIES_BUILT = _metrics.counter(
    "theia_job_series_built_total",
    "Series a job's tensorize stage built, by the query's aggregation "
    "(None: a connection a series; pod, external, svc)",
    labelnames=("kind", "agg"))
_M_TENSORIZE_SERIES = _metrics.counter(
    "theia_job_tensorize_series_total",
    "Series the native builder wrote, by how: cursor (times never "
    "stepped back: written as the rows are met), cells (times out of "
    "order over dense seconds: each row reduced into its second's "
    "cell, nothing sorted) or sorted (out of order over a span far "
    "wider than the rows: gathered, sorted, merged); nothing from the "
    "numpy path",
    labelnames=("kind", "how"))
_M_SERIES_ROWS_MERGED = _metrics.counter(
    "theia_job_series_rows_merged_total",
    "Of the rows that stage grouped, those that fell into a (key, "
    "time) cell another row already held and were reduced into it "
    "(sum, or max over a connection's rows): rows grouped less the "
    "series' points",
    labelnames=("kind", "agg"))
_M_ROWS_WRITTEN = _metrics.counter(
    "theia_job_rows_written_total",
    "Result rows a job inserted into its result table as one batch",
    labelnames=("kind",))
_M_BYTES_WRITTEN = _metrics.counter(
    "theia_job_bytes_written_total",
    "Column bytes of those batches", labelnames=("kind",))


def _column_bytes(batch) -> int:
    return sum(a.nbytes for a in batch.columns.values())


class JobProgress:
    """Tracks named stages of one job run.

    States mirror the Spark application lifecycle the controllers map
    into CRD status (controller.go:458-500): RUNNING → COMPLETED/FAILED.
    """

    def __init__(self, job_id: str, stages: List[str],
                 path: Optional[str] = None, kind: str = "") -> None:
        self.job_id = job_id
        self.stages = list(stages)
        self.path = path
        self.kind = kind
        self._marks = _trace.StageMarks()
        self._completed = 0
        self._state = "RUNNING"
        self._error = ""
        self._current = ""
        self._started = time.time()
        self._lock = named_lock("runner.progress")
        self._flush()

    def stage(self, name: str) -> None:
        self._marks.mark("job." + name, _M_STAGE.labels(
            kind=self.kind, stage=name))
        with self._lock:
            if self._current:
                self._completed += 1
            self._current = name
        self._flush()

    def part(self, name: str):
        """Context manager around a named part of the stage that is
        running: `job.<stage>.<name>` on the span and in a capture,
        `theia_job_stage_part_seconds{kind,stage,part}`."""
        stage = self._current
        return _trace.part(
            f"job.{stage}.{name}",
            _M_PART.labels(kind=self.kind, stage=stage, part=name))

    def read(self, batch) -> None:
        """Count the batch the `read` stage hands on."""
        _M_READ_ROWS.labels(kind=self.kind).inc(len(batch))
        _M_READ_COLUMNS.labels(kind=self.kind).inc(len(batch.columns))
        _M_READ_BYTES.labels(kind=self.kind).inc(_column_bytes(batch))

    def tensorized(self, rows: int, path: str, agg: str, series: int,
                   points: int, ways) -> None:
        """Count the rows the `tensorize` stage grouped and the path
        (`columns` or `numpy`) that grouped them; under the query's
        aggregation `agg` (which the enclosing `job.run` span also
        gets), the series it built and the rows that were merged into
        a point another row already held; and the series by how the
        native builder wrote them (`ways`, its `SeriesWays`; None from
        numpy)."""
        _M_TENSORIZE_ROWS.labels(kind=self.kind, path=path).inc(rows)
        if ways is not None:
            for how, n in ways._asdict().items():
                _M_TENSORIZE_SERIES.labels(kind=self.kind, how=how).inc(n)
        _M_SERIES_BUILT.labels(kind=self.kind, agg=agg).inc(series)
        _M_SERIES_ROWS_MERGED.labels(kind=self.kind, agg=agg).inc(
            rows - points)
        span = _trace.current_span()
        if span is not None:
            span.attrs["agg"] = agg

    def scored(self, algo: str, series: int, points: int,
               fits: int = 0, loop_iterations: int = 0,
               pair_tests: int = 0, sorted_points: int = 0) -> None:
        """Count what the `score` stage's kernel was given."""
        _M_SERIES_SCORED.labels(kind=self.kind, algo=algo).inc(series)
        _M_POINTS_SCORED.labels(kind=self.kind, algo=algo).inc(points)
        if fits:
            _M_ARIMA_FITS.inc(fits)
            _M_ARIMA_LOOP_ITERATIONS.inc(loop_iterations)
        if pair_tests:
            _M_DBSCAN_PAIR_TESTS.inc(pair_tests)
        if sorted_points:
            _M_DBSCAN_SORTED_POINTS.inc(sorted_points)

    def distinct(self, rows_sorted: int, flows: int) -> None:
        """Count what a policy-recommendation job's DISTINCT was given
        and what it kept."""
        _M_NPR_ROWS_SORTED.inc(rows_sorted)
        _M_NPR_DISTINCT_FLOWS.inc(flows)

    def recommended(self, policies_by_kind, direct: int) -> None:
        """Count the policy documents the job wrote, {kind: number},
        and how many of them `dump_yaml` wrote without PyYAML."""
        for kind, n in policies_by_kind.items():
            _M_NPR_POLICIES.labels(kind=kind).inc(n)
        _M_NPR_DOCUMENTS_DIRECT.inc(direct)

    def wrote(self, batch) -> None:
        """Count the batch of result rows the `write` stage inserted."""
        _M_ROWS_WRITTEN.labels(kind=self.kind).inc(len(batch))
        _M_BYTES_WRITTEN.labels(kind=self.kind).inc(_column_bytes(batch))

    def done(self) -> None:
        self._marks.end()
        with self._lock:
            self._completed = len(self.stages)
            self._current = ""
            self._state = "COMPLETED"
        self._flush()

    def fail(self, error: str) -> None:
        self._marks.end()
        with self._lock:
            self._state = "FAILED"
            self._error = error
        self._flush()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "id": self.job_id,
                "state": self._state,
                "currentStage": self._current,
                "completedStages": self._completed,
                "totalStages": len(self.stages),
                "errorMsg": self._error,
                "startedAt": self._started,
            }

    def _flush(self) -> None:
        if not self.path:
            return
        snap = self.snapshot()

        def write(tmp: str) -> None:
            with open(tmp, "w") as f:
                json.dump(snap, f)

        atomic_write(self.path, write)


class FileProgress:
    """Read side of a runner's --progress-file: the manager's
    equivalent of the reference scraping the Spark UI REST into CRD
    status (pkg/controller/util.go:129-159). snapshot() re-reads the
    file and caches the last good document, so status stays correct
    after the job's scratch directory is cleaned up."""

    def __init__(self, job_id: str, stages: List[str],
                 path: str) -> None:
        self.job_id = job_id
        self.stages = list(stages)
        self.path = path
        self._last = {
            "id": job_id,
            "state": "RUNNING",
            "currentStage": "",
            "completedStages": 0,
            "totalStages": len(stages),
            "errorMsg": "",
            "startedAt": time.time(),
        }

    def snapshot(self) -> dict:
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and "completedStages" in doc:
                self._last = doc
        except (OSError, ValueError):
            pass   # mid-write/retired file: serve the cached snapshot
        return dict(self._last)

    def fail(self, error: str) -> None:
        """The runner process owns the file; just reflect the failure
        in the cached snapshot for status readers."""
        self._last = {**self._last, "state": "FAILED",
                      "errorMsg": error}


TAD_STAGES = ["read", "tensorize", "score", "write"]
NPR_STAGES = ["read", "recommend", "write"]
DD_STAGES = ["read", "tensorize", "score", "write"]
FPM_STAGES = ["read", "mine", "write"]
SPATIAL_STAGES = ["read", "embed", "score", "write"]
