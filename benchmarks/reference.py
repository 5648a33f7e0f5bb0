"""The plain reference: the same semantics in straightforward numpy.

It imports nothing of the program and takes nothing the program made:
its inputs are the generator's own rows (benchmarks/gen.py), its
arithmetic float64. Three references live here:

* the streaming detector's alert decisions (per connection: EWMA with
  α = 0.5 from 0, Welford's running sample standard deviation, alert
  iff the connection has ≥ 2 points and |x − ewma| > stddev; the
  recurrence of theia_tpu/analytics/streaming.py `_update`, upstream
  anomaly_detection.py:146-212);
* the TAD EWMA job's decisions (per connection over its whole series:
  the same EWMA, the *whole-series* sample stddev);
* the dashboards' panels over a closed time range.

`precision="bf16"` rounds the detector's input to bfloat16 and computes
in float32: the control of the contract (the nearest precision below
the float32 the program states), which the comparison has to fail.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

ALPHA = 0.5


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even),
    returned as float32."""
    u = np.asarray(x, np.float32).view(np.uint32)
    rounded = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
               ) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def _as_precision(thr: np.ndarray, precision: str):
    if precision == "f64":
        return np.asarray(thr, np.float64), np.float64
    if precision == "f32":
        return np.asarray(thr, np.float32), np.float32
    if precision == "bf16":
        return to_bf16(np.asarray(thr, np.float32)), np.float32
    raise ValueError(f"unknown precision {precision!r}")


class DetectorReference:
    """State of one producer's connections, advanced block by block."""

    def __init__(self, n_conn: int, precision: str = "f64") -> None:
        self.precision = precision
        _, dt = _as_precision(np.zeros(1), precision)
        self.ewma = np.zeros(n_conn, dt)
        self.mean = np.zeros(n_conn, dt)
        self.m2 = np.zeros(n_conn, dt)
        self.count = np.zeros(n_conn, np.int64)

    def advance(self, conn: np.ndarray, thr: np.ndarray) -> np.ndarray:
        """conn [U] distinct connections, thr [U, T] their successive
        points; returns the alert decisions [U, T]."""
        x_all, dt = _as_precision(thr, self.precision)
        e, m, m2, n = (self.ewma[conn], self.mean[conn], self.m2[conn],
                       self.count[conn])
        out = np.zeros(x_all.shape, bool)
        half = dt(ALPHA)
        for t in range(x_all.shape[1]):
            x = x_all[:, t]
            n = n + 1
            delta = x - m
            m = m + delta / n.astype(dt)
            m2 = m2 + delta * (x - m)
            e = (dt(1.0) - half) * e + half * x
            std = np.sqrt(m2 / np.maximum(n - 1, 1).astype(dt))
            out[:, t] = (n >= 2) & (np.abs(x - e) > std)
        self.ewma[conn], self.mean[conn] = e, m
        self.m2[conn], self.count[conn] = m2, n
        return out


def detector_alerts(stream, n_blocks: int, precision: str = "f64"
                    ) -> np.ndarray:
    """Alert count of each of a producer's first `n_blocks` blocks."""
    ref = DetectorReference(stream.n_conn, precision)
    counts = np.zeros(n_blocks, np.int64)
    for b in range(n_blocks):
        v = stream.values(b)
        counts[b] = int(ref.advance(v["conn"], v["thr"]).sum())
    return counts


def block_totals(stream, n_blocks: int) -> Tuple[int, int]:
    """(rows, sum of octetDeltaCount) of a producer's first blocks."""
    rows = octets = 0
    for b in range(n_blocks):
        v = stream.values(b)
        rows += v["thr"].size
        octets += int(v["thr"].sum()) * stream.interval
    return rows, octets


# -- TAD EWMA over the retained window -------------------------------------

def series_of(stream, n_blocks: int) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """The producer's retained rows as padded per-connection series:
    values [C, L] int64, times [C, L] int64, mask [C, L], in time
    order (blocks are generated in time order; a block's connections
    are distinct). Whatever the key law: only `values(b)` is asked."""
    blocks = [stream.values(b) for b in range(n_blocks)]
    filled = np.zeros(stream.n_conn, np.int64)
    for v in blocks:
        filled[v["conn"]] += v["thr"].shape[1]
    length = int(filled.max()) if blocks else 0
    vals = np.zeros((stream.n_conn, length), np.int64)
    times = np.zeros((stream.n_conn, length), np.int64)
    mask = np.zeros((stream.n_conn, length), bool)
    filled[:] = 0
    for v in blocks:
        conn = v["conn"][:, None]
        cols = filled[conn] + np.arange(v["thr"].shape[1])
        vals[conn, cols] = v["thr"]
        times[conn, cols] = v["flow_end"]
        mask[conn, cols] = True
        filled[v["conn"]] += v["thr"].shape[1]
    return vals, times, mask


def tad_ewma(vals: np.ndarray, mask: np.ndarray, precision: str = "f64"
             ) -> np.ndarray:
    """EWMA anomaly decisions [C, L] of padded series (trailing
    padding): anomaly iff |x − ewma| > stddev_samp(series), no decision
    for a series of fewer than 2 points."""
    x, dt = _as_precision(vals, precision)
    x = np.where(mask, x, dt(0))
    e = np.zeros(x.shape[0], dt)
    ewma = np.zeros_like(x)
    for t in range(x.shape[1]):
        e = dt(1 - ALPHA) * e + dt(ALPHA) * x[:, t]
        ewma[:, t] = e
    n = mask.sum(1)
    mean = x.sum(1) / np.maximum(n, 1).astype(dt)
    dev = np.where(mask, x - mean[:, None], dt(0))
    var = (dev * dev).sum(1) / np.maximum(n - 1, 1).astype(dt)
    std = np.where(n >= 2, np.sqrt(var), np.nan)
    with np.errstate(invalid="ignore"):
        return (np.abs(x - ewma) > std[:, None]) & mask
