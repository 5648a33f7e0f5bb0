"""Throughput Anomaly Detection job — the framework's flagship compute path.

Re-provides plugins/anomaly-detection/anomaly_detection.py end to end:
read a flow window from the store, build per-connection (or aggregated)
throughput series, score them with EWMA / ARIMA / DBSCAN, and write
anomalous points to the `tadetector` table (schema create_table.sh:363-384),
including the reference's "NO ANOMALY DETECTED" filler row when nothing
fires (:395-420).

The scoring step is one jitted XLA computation over the padded [S, T]
batch (kernels in theia_tpu.ops); the reference's per-row Python UDFs
(`plot_anomaly` :424-504) are replaced by `vmap`-batched scans.
"""

from __future__ import annotations

import functools
import time
import uuid
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import arima_scores, dbscan_scores, ewma_scores
from ..ops.arima import css_loop_iterations
from ..ops.dbscan import pair_tests, sorted_points
from ..schema import TADETECTOR_SCHEMA, ColumnarBatch, StringDictionary
from ..store import FlowDatabase
from ..utils import get_logger
from .series import (SeriesBatch, TadQuerySpec, build_series, job_part,
                     read_columns)

logger = get_logger("tad")

#: series length at which an under-populated mesh (fewer series than
#: devices) re-shards EWMA over TIME instead of running local — below
#: this the blockwise scan's collective overhead beats its win
LONG_SERIES_T = 4096

ALGORITHMS = ("EWMA", "ARIMA", "DBSCAN")


def effective_refit(algo: str, refit_every: int, n_steps: int) -> int:
    """Resolve the ARIMA refit cadence a job will actually run with.

    refit_every=1 is the reference's exact refit-per-step
    (anomaly_detection.py:246-253); 0 selects the auto heuristic
    max(1, T // 2048) that keeps 24h@1s series feasible. Non-ARIMA
    algorithms have no refit concept → 0."""
    if algo != "ARIMA":
        return 0
    if refit_every < 0:
        raise ValueError(f"refitEvery must be >= 0, got {refit_every}")
    return refit_every if refit_every else max(1, n_steps // 2048)


def _sharded_kernel(values, mask, algo, refit_every, mesh):
    """(place, kernel) of one algorithm over a device mesh:
    data-parallel over series (plus sequence-parallel over time for
    EWMA). The sharded kernels run the same per-series computation as
    the single-device path, so result rows are identical — this is the
    reference's `executorInstances` scale-out applied to the
    production job (SURVEY §2.7 row 1)."""
    from ..parallel import (cached_kernel, make_sharded_arima,
                            make_sharded_dbscan, make_sharded_ewma,
                            pad_to_multiple, shard_arrays)
    from ..parallel.mesh import SERIES_AXIS, TIME_AXIS
    from ..parallel.tad_sharded import make_series_sharded

    values, _ = pad_to_multiple(values, mesh.shape[SERIES_AXIS], axis=0)
    mask, _ = pad_to_multiple(mask, mesh.shape[SERIES_AXIS], axis=0)
    if algo == "EWMA" and mesh.shape.get(TIME_AXIS, 1) > 1:
        # Sequence-parallel scan over the mesh's time axis (its stddev
        # psum may differ from the local kernel in the last float bit;
        # the job path uses time_shards=1 meshes, which are exact).
        values, _ = pad_to_multiple(values, mesh.shape[TIME_AXIS],
                                    axis=1)
        mask, _ = pad_to_multiple(mask, mesh.shape[TIME_AXIS], axis=1)
        fn = cached_kernel(("ewma_time", mesh),
                           lambda: make_sharded_ewma(mesh))
    elif algo == "EWMA":
        fn = cached_kernel(
            ("ewma", mesh),
            lambda: make_series_sharded(mesh, ewma_scores))
    elif algo == "ARIMA":
        refit = effective_refit(algo, refit_every, values.shape[1])
        fn = cached_kernel(
            ("arima", mesh, refit),
            lambda: make_sharded_arima(mesh, refit_every=refit))
    else:
        from ..ops.dbscan import DEFAULT_EPS, DEFAULT_MIN_SAMPLES
        fn = cached_kernel(
            ("dbscan", mesh),
            lambda: make_sharded_dbscan(
                mesh, eps=DEFAULT_EPS,
                min_samples=DEFAULT_MIN_SAMPLES))
    return (lambda: shard_arrays(mesh, values, mask)), fn


def _local_kernel(values, mask, algo, refit_every):
    """(place, kernel) of one algorithm on the default device."""
    if algo == "EWMA":
        fn = ewma_scores
    elif algo == "ARIMA":
        refit = effective_refit(algo, refit_every, values.shape[1])
        if refit > 1:
            logger.info(
                "ARIMA grouped-refit approximation active: refitting "
                "every %d steps over T=%d (reference-exact is "
                "refitEvery=1)", refit, values.shape[1])
        elif values.shape[1] > 8192:
            logger.warning(
                "ARIMA exact refit-per-step over T=%d steps is "
                "O(T^2) — expect a long job; pass refitEvery=0 "
                "(auto) or k>1 for grouped refits", values.shape[1])
        fn = functools.partial(arima_scores, refit_every=refit)
    else:
        fn = dbscan_scores
    return (lambda: (jnp.asarray(values), jnp.asarray(mask))), fn


def _choose_kernel(values, mask, algo, refit_every, mesh):
    if algo not in ALGORITHMS:
        raise ValueError(
            f"algo must be one of {ALGORITHMS}, got {algo!r}")
    if mesh is not None and mesh.size > 1:
        if values.shape[0] >= mesh.size:
            return _sharded_kernel(values, mask, algo, refit_every,
                                   mesh)
        if algo == "EWMA" and values.shape[1] >= LONG_SERIES_T:
            # Few series, long T: series-DP would idle most devices,
            # so re-mesh the same devices sequence-parallel and scan
            # the TIME axis cooperatively (the long-time-series role
            # SURVEY §5 assigns to sequence sharding). The psum'd
            # stddev is bit-approximate vs the local kernel — anomaly
            # flags exactly ON the threshold can flip; worth it only
            # when T is long enough for the blockwise scan to pay.
            from ..parallel.mesh import make_mesh
            tmesh = make_mesh(devices=mesh.devices.flatten(),
                              time_shards=mesh.devices.size)
            logger.info(
                "EWMA over %d series x %d steps: sequence-parallel "
                "time sharding over %d devices (series-DP would idle "
                "%d of them)", values.shape[0], values.shape[1],
                tmesh.devices.size,
                mesh.devices.size - values.shape[0])
            return _sharded_kernel(values, mask, algo, refit_every,
                                   tmesh)
    return _local_kernel(values, mask, algo, refit_every)


def _score_on_device(values, mask, algo, refit_every, mesh, progress):
    """The batch to the device(s) and through one algorithm's kernel,
    each waited for, so that the two parts are the transfer's and the
    kernel's own time (dispatch until the results are ready). Returns
    the kernel's device arrays, still padded as the mesh needed."""
    place, kernel = _choose_kernel(values, mask, algo, refit_every, mesh)
    with job_part(progress, "transfer"):
        placed = jax.block_until_ready(place())
    with job_part(progress, "kernel"):
        return jax.block_until_ready(kernel(*placed))[:3]


def score_series(values: np.ndarray, mask: np.ndarray, algo: str,
                 refit_every: int = 1, mesh=None):
    """Run one algorithm over a padded [S, T] batch.

    Returns (algo_calc [S,T], stddev [S], anomaly [S,T]) as numpy.
    `refit_every` applies to ARIMA only (see `effective_refit`).
    With `mesh` (a jax.sharding.Mesh with >1 device), scoring shards
    over the mesh; results are identical to the local path for
    series-sharded meshes (time_shards=1 — the job_mesh() default).
    Time sharding engages in two cases, both bit-approximate in the
    psum-reduced stddev (anomaly flags exactly ON the threshold can
    differ): an explicitly time-sharded mesh, or automatically for
    EWMA when the batch has fewer series than devices and T ≥
    LONG_SERIES_T (sequence parallelism instead of idle devices).
    """
    S, T = values.shape
    calc, std, anom = _score_on_device(values, mask, algo, refit_every,
                                       mesh, None)
    return (np.asarray(calc)[:S, :T], np.asarray(std)[:S],
            np.asarray(anom)[:S, :T])


def run_tad(db: FlowDatabase, algo: str, spec: TadQuerySpec,
            tad_id: Optional[str] = None,
            now: Optional[int] = None,
            progress=None, mesh="auto") -> str:
    """Execute a full TAD job against the database; returns the job id.

    `mesh`: "auto" scores over every visible device (parallel.job_mesh;
    single-device hosts and THEIA_MESH=off keep the plain path), None
    forces single-device, or pass an explicit jax.sharding.Mesh.
    """
    if algo not in ALGORITHMS:
        raise ValueError(f"algo must be one of {ALGORITHMS}, got {algo!r}")
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(
                f"mesh must be 'auto', None or a Mesh, got {mesh!r} "
                f"(use THEIA_MESH=off to disable sharding)")
        from ..parallel import job_mesh
        mesh = job_mesh()
    tad_id = tad_id or str(uuid.uuid4())

    if progress:
        progress.stage("read")
    flows = db.flows.select(columns=read_columns(spec))

    if progress:
        progress.read(flows)
        progress.stage("tensorize")
    batch = build_series(flows, spec, progress=progress)

    if progress:
        progress.stage("score")
    result = detect_anomalies(batch, algo, tad_id, now=now,
                              refit_every=spec.refit_every, mesh=mesh,
                              progress=progress)

    if progress:
        progress.stage("write")
    db.tadetector.insert(result)
    if progress:
        progress.wrote(result)
        progress.done()
    return tad_id


def detect_anomalies(batch: SeriesBatch, algo: str, tad_id: str,
                     now: Optional[int] = None, refit_every: int = 1,
                     mesh=None, progress=None) -> ColumnarBatch:
    """Score a series batch and build its tadetector result rows, one
    for each anomalous point in `np.nonzero` order, as one batch made
    from the kernel's arrays: no row is a Python object. `progress`
    (the job's, in its `score` stage) times the transfer, the kernel
    and the rows as that stage's parts and counts what was scored."""
    n_steps = batch.values.shape[1] if batch.n_series else 0
    refit = effective_refit(algo, refit_every, n_steps)
    if not batch.n_series:
        return _result_batch(1, _no_anomaly_row(
            batch.agg_type, algo, tad_id, now, refit))
    # Pass the resolved cadence so the emitted refitEvery and the one
    # actually executed cannot drift (effective_refit is idempotent).
    scores = _score_on_device(batch.values, batch.mask, algo,
                              refit if refit else 1, mesh, progress)
    if progress:
        dbscan = algo == "DBSCAN"
        progress.scored(
            algo, batch.n_series, int(np.count_nonzero(batch.mask)),
            fits=batch.n_series * -(-n_steps // refit) if refit else 0,
            loop_iterations=css_loop_iterations(
                batch.n_series, n_steps, refit) if refit else 0,
            pair_tests=pair_tests(batch.mask) if dbscan else 0,
            sorted_points=sorted_points(batch.mask) if dbscan else 0)
    with job_part(progress, "rows"):
        return _result_rows(batch, scores, algo, tad_id, now, refit)


def _result_rows(batch: SeriesBatch, scores, algo: str, tad_id: str,
                 now: Optional[int], refit: int) -> ColumnarBatch:
    """Fetch the kernel's arrays and gather the anomalous points'
    rows."""
    S, T = batch.values.shape
    calc, std, anom = (np.asarray(a) for a in scores)
    calc, std, anom = calc[:S, :T], std[:S], anom[:S, :T]
    sidx, tidx = np.nonzero(anom)
    if sidx.size == 0:
        return _result_batch(1, _no_anomaly_row(
            batch.agg_type, algo, tad_id, now, refit))

    point = (sidx, tidx)
    values: Dict[str, object] = {
        "aggType": batch.agg_type,
        "algoType": algo,
        "flowEndSeconds": (batch.times, point),
        # stddev_samp is NULL (NaN) for 1-point series; those can't be
        # anomalous, but guard the cast anyway.
        "throughputStandardDeviation": (
            np.nan_to_num(std, nan=0.0), sidx),
        "algoCalc": (calc, point),
        "throughput": (batch.values, point),
        "anomaly": "true",
        "refitEvery": refit,
        "id": tad_id,
    }
    # Series key names coincide with tadetector column names; keys
    # not present for this agg mode default to ''/0 in the schema
    # (the reference emits a mode-specific column subset,
    # filter_df_with_true_anomalies :352-394). A key is taken from the
    # series that have a row, each row's place among them.
    used, place = np.unique(sidx, return_inverse=True)
    for key_name in batch.key_names:
        values[key_name] = (batch.keys[key_name][used], place)
    return _result_batch(sidx.size, values)


def _result_batch(n_rows: int,
                  values: Dict[str, object]) -> ColumnarBatch:
    """`n_rows` tadetector rows as one batch that carries dictionaries
    of its own (`Table.insert` adopts them, so every store facade
    takes it). A column of `values` is one value for every row, or
    `(source, index)` for rows that hold `source[index]`; a column it
    does not name holds its kind's default, 0 or ''.

    A string column's `source` is encoded once an entry (a series, not
    a point) and holds only values that some row has, so the batch's
    dictionary lists them in sorted order: the order in which
    `from_rows` over the same rows would hand the table's dictionary
    its new strings. The table's codes are the same either way."""
    cols: Dict[str, np.ndarray] = {}
    dicts: Dict[str, StringDictionary] = {}
    for col in TADETECTOR_SCHEMA:
        value = values.get(col.name, "" if col.is_string else 0)
        gathered = isinstance(value, tuple)
        if not col.is_string:
            cols[col.name] = (
                np.asarray(value[0][value[1]], col.host_dtype)
                if gathered else np.full(n_rows, value, col.host_dtype))
            continue
        d = dicts[col.name] = StringDictionary()
        if not gathered:
            cols[col.name] = np.full(n_rows, d.encode_one(value),
                                     np.int32)
            continue
        source, index = value
        cols[col.name] = d.encode(
            [str(v) for v in source.tolist()])[index]
    return ColumnarBatch(cols, dicts)


def _no_anomaly_row(agg_type: str, algo: str, tad_id: str,
                    now: Optional[int],
                    refit: int = 0) -> Dict[str, object]:
    """The reference's filler row (:401-419), a value a column: string
    identity columns get 'None', flowStartSeconds gets the wall clock,
    anomaly gets the sentinel text."""
    return {
        "sourceIP": "None",
        "sourceTransportPort": 0,
        "destinationIP": "None",
        "destinationTransportPort": 0,
        "protocolIdentifier": 0,
        "flowStartSeconds": int(now if now is not None else time.time()),
        "podNamespace": "None",
        "podLabels": "None",
        "podName": "None",
        "destinationServicePortName": "None",
        "direction": "None",
        "flowEndSeconds": 0,
        "throughputStandardDeviation": 0.0,
        "aggType": agg_type,
        "algoType": algo,
        "algoCalc": 0.0,
        "throughput": 0.0,
        "anomaly": "NO ANOMALY DETECTED",
        "refitEvery": refit,
        "id": tad_id,
    }
