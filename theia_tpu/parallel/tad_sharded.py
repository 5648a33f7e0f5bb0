"""Sharded TAD scoring: shard_map over the (series × time) mesh.

This is the multi-chip version of theia_tpu.ops scoring (SURVEY §2.7:
Spark's executor data-parallelism → shard_map over the series axis; the
per-task whole-series processing → a sequence-parallel associative scan
over the time axis). One jitted step computes, fully sharded:

  * EWMA via local `associative_scan` + cross-shard composition of the
    per-shard affine summaries (all_gather over the "time" axis — the
    classic parallel-scan block decomposition),
  * masked sample stddev via psum over the "time" axis,
  * the anomaly mask, and a global anomaly count via psum over both axes
    (the collective the reference's driver-side `count()` implies).

The outputs come back with the same [S, T] sharding as the inputs, so a
caller can keep them device-resident for the result-row gather.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.ewma import DEFAULT_ALPHA
from .mesh import SERIES_AXIS, TIME_AXIS, Mesh


def _local_scan(a, b):
    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2
    return jax.lax.associative_scan(combine, (a, b), axis=-1)


def _ewma_timeshard(x: jnp.ndarray, alpha: float,
                    n_time_shards: int) -> jnp.ndarray:
    """EWMA along a time-sharded axis: local scan + shard composition.

    Each time shard holds a contiguous [S_loc, T_loc] block. The affine
    summary (A_tot, B_tot) of every earlier shard is composed (in shard
    order) into an incoming state, then applied to the local cumulative
    scan: e = A_cum · e_in + B_cum.
    """
    a = jnp.full_like(x, 1.0 - alpha)
    b = alpha * x
    a_cum, b_cum = _local_scan(a, b)

    if n_time_shards == 1:
        return b_cum  # e_in = 0

    a_tot = a_cum[:, -1]
    b_tot = b_cum[:, -1]
    a_all = jax.lax.all_gather(a_tot, TIME_AXIS)  # [n_shards, S_loc]
    b_all = jax.lax.all_gather(b_tot, TIME_AXIS)
    my = jax.lax.axis_index(TIME_AXIS)

    e_in = jnp.zeros_like(a_tot)
    for j in range(n_time_shards):  # static, tiny (mesh axis size)
        take = j < my
        e_in = jnp.where(take, a_all[j] * e_in + b_all[j], e_in)
    return a_cum * e_in[:, None] + b_cum


def _sharded_step(x, mask, alpha: float, n_time_shards: int):
    xz = jnp.where(mask, x, 0.0)
    e = _ewma_timeshard(xz, alpha, n_time_shards)

    # Masked stddev_samp with cross-time-shard reductions.
    cnt = jax.lax.psum(jnp.sum(mask.astype(x.dtype), axis=-1), TIME_AXIS)
    total = jax.lax.psum(jnp.sum(xz, axis=-1), TIME_AXIS)
    mean = total / jnp.maximum(cnt, 1.0)
    ss = jax.lax.psum(
        jnp.sum(jnp.where(mask, (x - mean[:, None]) ** 2, 0.0), axis=-1),
        TIME_AXIS)
    var = ss / jnp.maximum(cnt - 1.0, 1.0)
    std = jnp.where(cnt >= 2, jnp.sqrt(var), jnp.nan)

    anomaly = (jnp.abs(xz - e) > std[:, None]) & mask
    count = jax.lax.psum(jnp.sum(anomaly.astype(jnp.int32)),
                         (SERIES_AXIS, TIME_AXIS))
    return e, std, anomaly, count


def make_sharded_ewma(mesh: Mesh, alpha: float = DEFAULT_ALPHA):
    """Build the jitted sharded scoring step for a mesh.

    Returns fn(x [S,T], mask [S,T]) → (ewma, stddev [S], anomaly, count)
    with S divisible by the series-axis size and T by the time-axis size.
    """
    n_time = mesh.shape[TIME_AXIS]
    step = functools.partial(_sharded_step, alpha=alpha,
                             n_time_shards=n_time)
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS, TIME_AXIS)),
        out_specs=(P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS),
                   P(SERIES_AXIS, TIME_AXIS), P()),
        check_vma=False)
    return jax.jit(mapped)


def shard_arrays(mesh: Mesh, x, mask) -> Tuple[jax.Array, jax.Array]:
    """device_put host arrays with the step's input sharding."""
    spec = NamedSharding(mesh, P(SERIES_AXIS, TIME_AXIS))
    return jax.device_put(x, spec), jax.device_put(mask, spec)


def make_series_sharded(mesh: Mesh, kernel):
    """Data parallelism over the series axis for any scoring kernel
    with the (x [S,T], mask [S,T]) → (calc, std [S], anomaly) shape.

    Per-series work is independent (SURVEY §2.7 row 2: Spark's
    per-series task parallelism → series sharding), so the sharded
    step is the single-device kernel applied to each chip's series
    slab — no collectives, and per-series outputs are BIT-IDENTICAL
    to the single-device kernel (same computation graph per series).
    The time axis of the mesh (if >1) replicates.
    """
    mapped = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(SERIES_AXIS, None), P(SERIES_AXIS, None)),
        out_specs=(P(SERIES_AXIS, None), P(SERIES_AXIS),
                   P(SERIES_AXIS, None)),
        check_vma=False)
    return jax.jit(mapped)


def make_sharded_arima(mesh: Mesh, refit_every: int = 1):
    """Sharded ARIMA scoring (series data parallelism; every
    (series, prefix) fit is independent — the walk-forward scan stays
    local to each shard)."""
    from ..ops.arima import arima_scores

    def step(x, mask):
        return arima_scores(x, mask, refit_every=refit_every)

    return make_series_sharded(mesh, step)


def make_sharded_dbscan(mesh: Mesh, eps: float, min_samples: int):
    """Sharded per-series DBSCAN noise scoring over the series axis.

    Each series is decided on its own (a sort along its time axis),
    so series shards run the single-device formulation,
    `ops.dbscan.dbscan_scores`, locally.
    """
    from ..ops.dbscan import dbscan_scores

    def step(x, mask):
        return dbscan_scores(x, mask, eps=eps, min_samples=min_samples)

    return make_series_sharded(mesh, step)


def make_sharded_points_dbscan(mesh: Mesh, eps: float,
                               min_samples: int = 4):
    """Sharded spatial DBSCAN over [N, F] point embeddings.

    The tiled two-pass of `ops.dbscan.dbscan_points_noise` shards over
    tile rows (mesh axis `rows`): each chip evaluates its row block
    against the full point set (one all_gather of the points), derives
    complete neighbor counts → local core flags, then a second
    all_gather shares the core flags for the reachability pass — the
    collective structure SURVEY §2.7 maps DBSCAN's region query onto.

    Returns fn(points [N, F] f32, valid [N] bool) → noise [N] bool,
    N divisible by the rows-axis size.
    """
    from .mesh import ROWS_AXIS

    eps2 = eps * eps

    def step(pts_loc, valid_loc):
        pts_all = jax.lax.all_gather(pts_loc, ROWS_AXIS)
        pts_all = pts_all.reshape(-1, pts_loc.shape[1])
        valid_all = jax.lax.all_gather(valid_loc, ROWS_AXIS).reshape(-1)
        t2 = (pts_loc * pts_loc).sum(-1)
        x2 = (pts_all * pts_all).sum(-1)
        d2 = t2[:, None] + x2[None, :] - 2.0 * jnp.matmul(
            pts_loc, pts_all.T, precision=jax.lax.Precision.HIGHEST)
        within = (d2 <= eps2) & valid_all[None, :] & valid_loc[:, None]
        counts = within.sum(-1)
        core_loc = (counts >= min_samples) & valid_loc
        core_all = jax.lax.all_gather(core_loc, ROWS_AXIS).reshape(-1)
        reach = (within & core_all[None, :]).any(-1)
        return valid_loc & ~core_loc & ~reach

    from jax.sharding import PartitionSpec as P2
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P2(ROWS_AXIS, None), P2(ROWS_AXIS)),
        out_specs=P2(ROWS_AXIS),
        check_vma=False)
    return jax.jit(mapped)
