"""Plain reference of the DBSCAN throughput-anomaly job: numpy in
float64, nothing imported from the program, no jax, and no pair of
points is ever tested.

What the job computes, per connection's throughput series x (upstream
plugins/anomaly-detection/anomaly_detection.py:325-349): sklearn's
`DBSCAN(min_samples=4, eps=250000000)` over the 1-D values of one
connection; the points labelled -1 (noise) are the anomalies. The
`algoCalc` column is a 0.0 placeholder (:312-322) and
`throughputStandardDeviation` is `stddev_samp` of the series, as for
the other two algorithms.

The definition (sklearn's, Ester et al. 1996), which the program
evaluates over all T x T pairs of a series:

    count_i     = |{j : |x_i - x_j| <= eps}|           (i itself included)
    core_i      = count_i >= min_samples
    reachable_i = exists j : core_j and |x_i - x_j| <= eps
    noise_i     = not core_i and not reachable_i

Which cluster a border point joins depends on the order of the points;
whether a point is noise does not, and that is all the job reads.

The values are one-dimensional, so the same answer comes from sorting
(`noise_sorted`): in sorted order the points within eps of x_i are a
contiguous run, so count_i is two binary searches, `searchsorted(x_i +
eps, right) - searchsorted(x_i - eps, left)`; and if any core point
lies within eps of x_i then so does the nearest core point below it or
the nearest above it, which a running maximum and a running minimum
over the sorted core values give. O(T log T) a series where the
definition is O(T^2). `noise_by_pairs` is the definition itself, for
the test that holds the two together at a small size.

In float64 over the integer throughputs of the generator x_i + eps and
x_i - eps are exact, so `x_j <= x_i + eps` is `x_j - x_i <= eps` to the
bit and a pair at a distance of eps exactly is within it, as `<=` says.

`precision` is "f64" (the reference), "f32", or "bf16": the input
rounded to bfloat16 and float32 arithmetic, the control of the check
(benchmarks/control.py), which the comparison has to fail.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

EPS = 2.5e8
MIN_SAMPLES = 4


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    as float32."""
    u = np.asarray(x, np.float32).view(np.uint32)
    rounded = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
               ) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def as_precision(x: np.ndarray, precision: str):
    if precision == "f64":
        return np.asarray(x, np.float64), np.float64
    if precision == "f32":
        return np.asarray(x, np.float32), np.float32
    if precision == "bf16":
        return to_bf16(np.asarray(x, np.float32)), np.float32
    raise ValueError(f"unknown precision {precision!r}")


def noise_sorted(x: np.ndarray, eps: float = EPS,
                 min_samples: int = MIN_SAMPLES) -> np.ndarray:
    """Noise flags of one series' valid points x [n], in x's order."""
    eps = x.dtype.type(eps)
    order = np.argsort(x, kind="stable")
    xs = x[order]
    count = (np.searchsorted(xs, xs + eps, side="right")
             - np.searchsorted(xs, xs - eps, side="left"))
    core = count >= min_samples
    inf = x.dtype.type(np.inf)
    below = np.maximum.accumulate(np.where(core, xs, -inf))
    above = np.minimum.accumulate(np.where(core, xs, inf)[::-1])[::-1]
    reachable = (below >= xs - eps) | (above <= xs + eps)
    noise = np.empty(x.size, bool)
    noise[order] = ~core & ~reachable
    return noise


def noise_by_pairs(x: np.ndarray, eps: float = EPS,
                   min_samples: int = MIN_SAMPLES) -> np.ndarray:
    """The definition over all pairs of one series' valid points: what
    `noise_sorted` is held to by its test, and never called by a check
    (it is n^2)."""
    within = np.abs(x[:, None] - x[None, :]) <= x.dtype.type(eps)
    core = within.sum(1) >= min_samples
    return ~core & ~(within & core[None, :]).any(1)


def stddev_samp(x: np.ndarray) -> float:
    """SQL stddev_samp of one series' valid points: NULL (nan) for
    fewer than two."""
    if x.size < 2:
        return float("nan")
    dev = x - x.sum() / x.dtype.type(x.size)
    return float(np.sqrt((dev * dev).sum() / x.dtype.type(x.size - 1)))


def dbscan_scores(vals: np.ndarray, mask: np.ndarray, eps: float = EPS,
                  min_samples: int = MIN_SAMPLES, precision: str = "f64"
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(algoCalc [S, T]: zeros, stddev_samp [S], anomaly [S, T]) of
    padded series, masked wherever: what the program's `dbscan_scores`
    returns."""
    x_all, dtype = as_precision(vals, precision)
    std = np.full(x_all.shape[0], np.nan, np.float64)
    anomaly = np.zeros(x_all.shape, bool)
    for s in range(x_all.shape[0]):
        valid = np.flatnonzero(mask[s])
        x = x_all[s, valid]
        std[s] = stddev_samp(x)
        anomaly[s, valid] = noise_sorted(x, eps, min_samples)
    return np.zeros(x_all.shape, dtype), std, anomaly
