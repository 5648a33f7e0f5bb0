"""EWMA anomaly scoring as a parallel (associative) scan.

Reference semantics (plugins/anomaly-detection/anomaly_detection.py:146-212):
    ewma_t = (1-α)·ewma_{t-1} + α·x_t,  ewma_{-1} = 0,  α = 0.5
    anomaly_t = |x_t − ewma_t| > stddev_samp(x)

TPU-first design: the recurrence is linear, so instead of the reference's
per-element Python loop it runs as `lax.associative_scan` over the time
axis — O(log T) depth, fully parallel across the [S, T] batch. The whole
scoring step (scan + stddev + threshold) is one fused XLA computation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .masked import masked_stddev_samp

DEFAULT_ALPHA = 0.5


def ewma(x: jnp.ndarray, alpha: float = DEFAULT_ALPHA) -> jnp.ndarray:
    """EWMA along the last axis with implicit zero initial state.

    Solves e_t = a·e_{t-1} + b_t (a = 1-α, b_t = α·x_t) by scanning the
    affine maps (A, B) under composition (A1,B1)∘(A2,B2) = (A1A2, A2B1+B2);
    with e_{-1}=0 the accumulated B is the answer.
    """
    a = jnp.full_like(x, 1.0 - alpha)
    b = alpha * x

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    with jax.named_scope("ewma_scan"):
        _, e = jax.lax.associative_scan(combine, (a, b), axis=-1)
    return e


@functools.partial(jax.jit, static_argnames=("alpha",))
def ewma_scores(x: jnp.ndarray, mask: jnp.ndarray,
                alpha: float = DEFAULT_ALPHA):
    """Full EWMA scoring for a padded series batch.

    Padding is squashed to 0 before the scan; because the reference also
    starts from ewma=0 and processes each series whole, leading valid
    points see exactly the reference recurrence as long as padding is
    trailing (the tensorizer guarantees that).

    Returns (ewma [S,T], stddev [S], anomaly [S,T] bool).
    """
    xz = jnp.where(mask, x, 0.0)
    e = ewma(xz, alpha)
    with jax.named_scope("stddev"):
        std = masked_stddev_samp(x, mask)
    # NaN stddev (fewer than 2 points) compares False, matching the
    # reference's "too few values" → not anomalous path (:198-201).
    anomaly = (jnp.abs(xz - e) > std[..., None]) & mask
    return e, std, anomaly
