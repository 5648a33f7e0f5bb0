"""The streaming detector's per-slot state and its one-tick update.

The EWMA recurrence is the batch kernel's (`ops/ewma.py`, reference
anomaly_detection.py:146-165); the stddev band is Welford's running
*sample* stddev over the points seen so far. Both callers scan this
update tick by tick over a gathered tile: `analytics/streaming.py`'s
`stream_update_sparse` and `ops/fused_detector.py`'s fused step.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp


class StreamState(NamedTuple):
    ewma: jnp.ndarray    # [S]
    count: jnp.ndarray   # [S] int32  points seen
    mean: jnp.ndarray    # [S]       running mean (Welford)
    m2: jnp.ndarray      # [S]       running sum of squared deviations


def _update(state: StreamState, x: jnp.ndarray, active: jnp.ndarray,
            alpha) -> Tuple[StreamState, jnp.ndarray]:
    """Elementwise detector recurrence (any shape): anomaly iff the
    slot is active, has seen ≥2 points, and |x − ewma| exceeds the
    running sample stddev (the streaming analogue of
    calculate_ewma_anomaly)."""
    xa = jnp.where(active, x, 0.0)
    count = state.count + active.astype(jnp.int32)
    delta = xa - state.mean
    mean = jnp.where(active,
                     state.mean + delta / jnp.maximum(count, 1),
                     state.mean)
    m2 = jnp.where(active, state.m2 + delta * (xa - mean), state.m2)
    ewma = jnp.where(active,
                     (1.0 - alpha) * state.ewma + alpha * xa,
                     state.ewma)
    std = jnp.sqrt(m2 / jnp.maximum(count - 1, 1))
    anomaly = active & (count >= 2) & (jnp.abs(xa - ewma) > std)
    return StreamState(ewma, count, mean, m2), anomaly
