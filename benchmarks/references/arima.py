"""Plain reference of the ARIMA throughput-anomaly job: float64 numpy,
straight loops, nothing imported from the program and no jax.

What it computes, per connection's throughput series x (upstream
plugins/anomaly-detection/anomaly_detection.py:215-309):

  1. a series of 3 points or fewer, or with a value <= 0, gives no
     forecast (0) and no anomaly (upstream's error paths :232-234,
     :260-264);
  2. Box-Cox with the likelihood's own lambda over (-2, 2), on x divided
     by its geometric mean (the likelihood is the same, the arithmetic
     better conditioned);
  3. walk-forward one-step forecasts of ARIMA(1,1,1): the first three
     points pass through, the forecast of point m >= 3 comes from a fit
     on the points before it;
  4. forecasts back to levels; anomaly iff |x - forecast| >
     stddev_samp(x) over the whole series.

The program's documented departures from upstream, which this file
shares because they define the job's result (docs/architecture.md,
theia_tpu/ops/arima.py's own text):

  * lambda by a grid of 161 points with one parabolic step through the
    best point and its neighbours, where scipy runs Brent on the same
    objective;
  * the ARMA(1,1) of the first differences is estimated by the
    two-stage regression of Hannan and Rissanen (ridge 1e-6, both
    coefficients clipped to +-0.99), where statsmodels maximises the
    likelihood at every step;
  * `refit_every` = k groups the refits: the points m in [g k, (g+1) k)
    are forecast with the fit on the first max(g k, 3) points; k = 1 is
    upstream's fit at every step. The job's "refitEvery: 0" resolves to
    k = max(1, T // 2048) (`effective_refit`);
  * residuals by the conditional-sum-of-squares recursion eps_t = d_t -
    phi d_(t-1) - theta eps_(t-1), eps_0 = 0.

`fit` is the estimator as defined, one series and one prefix at a time.
`fits` gives the same numbers for every prefix of many series at once
from running sums of the five products the estimator is made of, which
is what a 43,200-point series with 2,058 prefixes needs;
benchmarks/tests holds the two together.

`precision` is "f64" (the reference), "f32", or "bf16": the input
rounded to bfloat16 and float32 arithmetic, the control of the check
(benchmarks/control.py), which the comparison has to fail.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

MIN_POINTS = 4
RIDGE = 1e-6
CLIP = 0.99
LAMBDA_LO, LAMBDA_HI, LAMBDA_GRID = -2.0, 2.0, 161


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


# -- Box-Cox ---------------------------------------------------------------

def boxcox(x: np.ndarray, lam) -> np.ndarray:
    lam = np.asarray(lam, x.dtype)[..., None]
    zero = np.abs(lam) < 1e-12
    return np.where(zero, np.log(x), (x ** lam - 1) / np.where(zero, 1, lam))


def inv_boxcox(y: np.ndarray, lam) -> np.ndarray:
    """A forecast beyond the transform's range (lambda y + 1 <= 0) is
    held at 1e-300 before the power, as the program holds it: an
    astronomic level, anomalous whatever the point."""
    lam = np.asarray(lam, y.dtype)[..., None]
    zero = np.abs(lam) < 1e-12
    with np.errstate(over="ignore", divide="ignore"):
        return np.where(zero, np.exp(y),
                        np.maximum(lam * y + 1, y.dtype.type(1e-300))
                        ** (1 / np.where(zero, 1, lam)))


def boxcox_llf(lam: float, logx: np.ndarray) -> np.ndarray:
    """Profile log-likelihood of lambda for series [S, n] given their
    logarithms: (lambda - 1) sum(log x) - n/2 log var(y_lambda)."""
    dt = logx.dtype.type
    n = dt(logx.shape[-1])
    y = logx if abs(lam) < 1e-12 \
        else (np.exp(dt(lam) * logx) - 1) / dt(lam)
    var = ((y - y.mean(-1, keepdims=True)) ** 2).mean(-1)
    return (dt(lam) - 1) * logx.sum(-1) \
        - n / 2 * np.log(np.maximum(var, np.finfo(logx.dtype).tiny))


def boxcox_lambda(x: np.ndarray) -> np.ndarray:
    """The likelihood's lambda of each series of x [S, n] (positive):
    the best of the grid, moved by one parabolic step through it and
    its two neighbours; a best point on the grid's edge stays there."""
    dt = x.dtype.type
    grid = np.linspace(LAMBDA_LO, LAMBDA_HI, LAMBDA_GRID).astype(x.dtype)
    logx = np.log(x)
    llf = np.stack([boxcox_llf(float(g), logx) for g in grid])   # [G, S]
    best = llf.argmax(0)
    i = np.clip(best, 1, LAMBDA_GRID - 2)
    s = np.arange(x.shape[0])
    f_m, f_0, f_p = llf[i - 1, s], llf[i, s], llf[i + 1, s]
    denom = f_m - 2 * f_0 + f_p
    ok = np.abs(denom) > 1e-12
    shift = np.where(ok, (f_m - f_p) / np.where(ok, denom, 1) / 2, 0)
    step = dt((LAMBDA_HI - LAMBDA_LO) / (LAMBDA_GRID - 1))
    lam = grid[i] + np.clip(shift, -1, 1).astype(x.dtype) * step
    return np.where(best == i, lam, grid[best]).astype(x.dtype)


# -- the estimator -----------------------------------------------------------

def _solve(s11, s12, s22, b1, b2):
    det = s11 * s22 - s12 * s12
    det = np.where(np.abs(det) < 1e-30, 1e-30, det)
    phi = (s22 * b1 - s12 * b2) / det
    theta = (s11 * b2 - s12 * b1) / det
    return np.clip(phi, -CLIP, CLIP), np.clip(theta, -CLIP, CLIP)


def fit(d: np.ndarray, n: int) -> Tuple[float, float]:
    """Hannan-Rissanen on the differences d[0..n] of one series (the
    n + 1 differences of a prefix of n + 2 points): (phi, theta).

    Stage 1: regress d_t on d_(t-1), t = 1..n; residuals e_t.
    Stage 2: regress d_t on (d_(t-1), e_(t-1)), t = 1..n with e_0 = 0
    (so the second regressor enters from t = 2), ridge on the diagonal.
    """
    dt = d.dtype.type
    num = den = dt(0)
    for t in range(1, n + 1):
        num += d[t] * d[t - 1]
        den += d[t - 1] * d[t - 1]
    a = num / (den + dt(RIDGE))
    e = np.zeros(n + 1, d.dtype)
    for t in range(1, n + 1):
        e[t] = d[t] - a * d[t - 1]
    s12 = s22 = b2 = dt(0)
    for t in range(2, n + 1):
        s12 += d[t - 1] * e[t - 1]
        s22 += e[t - 1] * e[t - 1]
        b2 += e[t - 1] * d[t]
    phi, theta = _solve(den + dt(RIDGE), s12, s22 + dt(RIDGE), num, b2)
    return float(phi), float(theta)


def fits(d: np.ndarray, ns: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """`fit(d[s], n)` for every series s of d [S, L] and every n of ns
    [G], as (phi [S, G], theta [S, G]), from running sums: with e_t =
    d_t - a d_(t-1) the stage-2 moments are polynomials in a of five
    sums of products of d with itself shifted by one and two."""
    dt = d.dtype.type
    S, L = d.shape

    def upto(prod, first):
        """running[n] = sum of prod over t = first..n (0 below)."""
        full = np.zeros((S, L), d.dtype)
        full[:, first:] = prod
        return np.cumsum(full, 1)[:, ns]

    d0, d1, d2 = d[:, 2:], d[:, 1:-1], d[:, :-2]
    num = upto(d[:, 1:] * d[:, :-1], 1)          # sum d_t d_(t-1), t >= 1
    den = upto(d[:, :-1] * d[:, :-1], 1)         # sum d_(t-1)^2,   t >= 1
    a = num / (den + dt(RIDGE))
    q11 = upto(d1 * d1, 2)                       # the same sums from t = 2
    q12 = upto(d1 * d2, 2)
    q22 = upto(d2 * d2, 2)
    q01 = upto(d0 * d1, 2)
    q02 = upto(d0 * d2, 2)
    s12 = q11 - a * q12
    s22 = q11 - 2 * a * q12 + a * a * q22
    b2 = q01 - a * q02
    return _solve(den + dt(RIDGE), s12, s22 + dt(RIDGE), num, b2)


# -- walk-forward ------------------------------------------------------------

def walk_forward(y: np.ndarray, k: int) -> np.ndarray:
    """One-step forecasts [S, n] of Box-Cox series y [S, n] of one
    length n >= 4, refit every k points. The residual recursion runs
    over time once for all groups: group g (the points g k .. g k + k -
    1) reads eps at t = m - 2 of its points m only, so at time t the
    groups below (t + 2) // k are done and that group takes eps_t."""
    dt = y.dtype.type
    S, n = y.shape
    d = y[:, 1:] - y[:, :-1]                     # d[t] = y[t+1] - y[t]
    n_groups = -(-n // k)
    m_fit = np.maximum(np.arange(n_groups) * k, 3)
    phi, theta = fits(d, m_fit - 2)              # prefix of m_fit points
    pred = y.copy()
    eps = np.zeros((S, n_groups), d.dtype)       # eps_(t-1) per group
    for t in range(n - 2):
        g0 = (t + 2) // k                        # the group that reads eps_t
        if t == 0:
            eps[:, g0:] = dt(0)
        else:
            eps[:, g0:] = (d[:, t, None] - phi[:, g0:] * d[:, t - 1, None]
                           - theta[:, g0:] * eps[:, g0:])
        m = t + 2
        if m >= 3:
            pred[:, m] = y[:, m - 1] + phi[:, g0] * d[:, t] \
                + theta[:, g0] * eps[:, g0]
    return pred


def stddev_samp(x: np.ndarray) -> np.ndarray:
    n = x.shape[-1]
    if n < 2:
        return np.full(x.shape[:-1], np.nan, x.dtype)
    dev = x - x.mean(-1, keepdims=True)
    return np.sqrt((dev * dev).sum(-1) / x.dtype.type(n - 1))


def effective_refit(refit_every: int, n_steps: int) -> int:
    """The cadence a job runs with: its own, or for 0 the documented
    max(1, T // 2048) of the tensor's time axis."""
    return refit_every if refit_every else max(1, n_steps // 2048)


def arima_job(vals: np.ndarray, mask: np.ndarray, refit_every: int,
              precision: str = "f64") -> Dict[str, np.ndarray]:
    """The job over padded series (trailing padding), `refit_every` >=
    1: `pred` [S, T] forecasts in levels, `std` [S] stddev_samp,
    `anomaly` [S, T], and the scale each series was modelled on, `lam`
    [S] and `gm` [S] (nan for a series that was not scored). Series are
    taken length by length, each length as one batch."""
    x_all, dtype = as_precision(vals, precision)
    S, T = x_all.shape
    pred = np.zeros((S, T), dtype)
    std = np.full(S, np.nan, dtype)
    lam_all = np.full(S, np.nan, dtype)
    gm_all = np.full(S, np.nan, dtype)
    anomaly = np.zeros((S, T), bool)
    lengths = mask.sum(1)
    for n in np.unique(lengths):
        rows = np.flatnonzero(lengths == n)
        x = x_all[rows, :n]
        std[rows] = stddev_samp(x)
        ok = (x > 0).all(1) if n >= MIN_POINTS else np.zeros(len(rows), bool)
        if not ok.any():
            continue
        rows, x = rows[ok], x[ok]
        gm = np.exp(np.log(x).mean(1, keepdims=True))
        xs = x / gm
        lam = boxcox_lambda(xs)
        p = inv_boxcox(walk_forward(boxcox(xs, lam), refit_every), lam) * gm
        pred[rows, :n] = p
        lam_all[rows], gm_all[rows] = lam, gm[:, 0]
        with np.errstate(invalid="ignore"):
            anomaly[rows, :n] = np.abs(x - p) > std[rows, None]
    return {"pred": pred, "std": std, "anomaly": anomaly,
            "lam": lam_all, "gm": gm_all}


def arima_scores(vals: np.ndarray, mask: np.ndarray, refit_every: int,
                 precision: str = "f64"
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(forecast [S, T] in levels, stddev_samp [S], anomaly [S, T]):
    what the program's `arima_scores` returns."""
    job = arima_job(vals, mask, refit_every, precision)
    return job["pred"], job["std"], job["anomaly"]


def on_model_scale(level, lam, gm):
    """A forecast in levels on the scale its series was modelled on:
    Box-Cox of level / gm under that series' lambda, in float64. This
    is where the model forecasts and where an error of the arithmetic
    has one size whatever the level: back in levels a forecast near the
    transform's range (the point after a spike, under an early fit) is
    the power 1 / lambda of a number near 0, and beyond the range it is
    1e150 or float32's inf; on this scale those are all -1 / lambda."""
    z = np.asarray(level, np.float64) / np.asarray(gm, np.float64)
    lam = np.asarray(lam, np.float64)
    zero = np.abs(lam) < 1e-12
    with np.errstate(over="ignore", divide="ignore"):
        return np.where(zero, np.log(z),
                        (z ** lam - 1) / np.where(zero, 1, lam))
