"""Device meshes for the analytics jobs.

The reference scales by adding Spark executor pods (SURVEY §2.7;
pkg/controller/networkpolicyrecommendation/controller.go:573-675 copies
executorInstances into the SparkApplication spec). The TPU-native
equivalent is a `jax.sharding.Mesh` over the chips of a slice:

  * axis "series" — data parallelism over connections (the Spark
    executor axis): each chip scores an independent slab of series.
  * axis "time"   — sequence parallelism over long series (no reference
    equivalent; the reference materializes unbounded collect_list rows
    per task, SURVEY §5 long-context note): the EWMA recurrence is
    associative, so it scans locally per shard and composes shard
    summaries across the ICI ring.

Collectives ride ICI within a host and DCN across hosts; XLA inserts
them from the shard_map specs in tad_sharded.py.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

SERIES_AXIS = "series"
TIME_AXIS = "time"
ROWS_AXIS = "rows"


def make_mesh(n_devices: Optional[int] = None,
              time_shards: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """A (series × time) mesh over `n_devices` (default: all visible).

    time_shards must divide the device count; time_shards=1 degenerates
    to pure series data parallelism.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if n % time_shards != 0:
        raise ValueError(
            f"time_shards {time_shards} must divide device count {n}")
    grid = np.asarray(devs).reshape(n // time_shards, time_shards)
    return Mesh(grid, (SERIES_AXIS, TIME_AXIS))


def make_rows_mesh(n_devices: Optional[int] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D `rows` mesh — data parallelism over flow-record blocks
    (the NPR job's distinct/support-count shuffle axis)."""
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (ROWS_AXIS,))


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int,
                    fill=0) -> Tuple[np.ndarray, int]:
    """Pad `axis` up to a multiple; returns (padded, original size)."""
    size = arr.shape[axis]
    target = -(-size // multiple) * multiple if size else multiple
    if target == size:
        return arr, size
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, target - size)
    return np.pad(arr, pad, constant_values=fill), size
