"""Batched walk-forward ARIMA(1,1,1) forecasting.

Reference semantics (plugins/anomaly-detection/anomaly_detection.py:215-309):
for each connection's throughput series x (needs > 3 points, all positive):
  1. Box-Cox transform with MLE lambda           (scipy.stats.boxcox)
  2. train = y[:3]; for each later step t, fit ARIMA(1,1,1) on history
     y[:t] and forecast one step ahead           (statsmodels, re-fit per t)
  3. predictions = train + forecasts, inverse Box-Cox back to levels
  4. anomaly_t = |x_t − pred_t| > stddev_samp(x)
Series that are too short or fail the transform yield no anomalies
(:232-234, :260-264).

TPU-first design: the reference's per-step statsmodels MLE re-fit is the
system's hottest loop (SURVEY §3.5). Here every (series, prefix) pair is
fitted *simultaneously*:

  * Box-Cox lambda by dense grid + parabolic refinement of the profile
    log-likelihood (the same objective scipy optimizes with Brent).
  * ARIMA(1,1,1) = ARMA(1,1) on first differences, estimated per prefix
    with the Hannan–Rissanen two-stage regression — pure masked
    prefix-moment algebra (no iterative optimizer), vmapped over
    [series × prefix].
  * The MA residual recursion is a `lax.scan` over time under `vmap`.

Accuracy delta vs the reference (documented per SURVEY §7 hard-part b):
Hannan–Rissanen is a consistent estimator of the same model but not the
MLE, so individual forecasts differ from an MLE fit; on the synthetic
golden tests (tests/test_tad_golden.py, vs a scipy CSS-MLE fit of the
same model) injected spikes are flagged identically and the only
divergence is within the ≤3-step post-spike recovery window, where
predictions hinge on the estimated (phi, theta).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .masked import masked_count, masked_stddev_samp

MIN_POINTS = 4        # reference requires len > 3  (:232)
_RIDGE = 1e-6
_CLIP = 0.99


def boxcox_llf(lam: jnp.ndarray, x: jnp.ndarray,
               mask: jnp.ndarray) -> jnp.ndarray:
    """Profile log-likelihood of the Box-Cox parameter (scipy's
    boxcox_llf): llf = (λ−1)·Σ log x − n/2·log σ²(y_λ)."""
    n = jnp.maximum(masked_count(mask), 1)
    logx = jnp.where(mask, jnp.log(jnp.where(mask, x, 1.0)), 0.0)
    y = jnp.where(jnp.abs(lam) < 1e-12,
                  logx,
                  (jnp.exp(lam * logx) - 1.0) / jnp.where(
                      jnp.abs(lam) < 1e-12, 1.0, lam))
    y = jnp.where(mask, y, 0.0)
    mean = jnp.sum(y, axis=-1) / n
    var = jnp.sum(jnp.where(mask, (y - mean[..., None]) ** 2, 0.0),
                  axis=-1) / n
    return ((lam - 1.0) * jnp.sum(logx, axis=-1)
            - 0.5 * n * jnp.log(jnp.maximum(var, 1e-300)))


def boxcox_lambda(x: jnp.ndarray, mask: jnp.ndarray,
                  lo: float = -2.0, hi: float = 2.0,
                  n_grid: int = 161) -> jnp.ndarray:
    """MLE lambda per series via grid search + one parabolic refinement
    (scipy uses Brent on the same objective over (-2, 2))."""
    grid = jnp.linspace(lo, hi, n_grid)
    llf = jax.vmap(lambda g: boxcox_llf(g, x, mask))(grid)  # [G, S]
    idx = jnp.argmax(llf, axis=0)
    step = (hi - lo) / (n_grid - 1)
    i = jnp.clip(idx, 1, n_grid - 2)
    f_m1 = jnp.take_along_axis(llf, (i - 1)[None, :], axis=0)[0]
    f_0 = jnp.take_along_axis(llf, i[None, :], axis=0)[0]
    f_p1 = jnp.take_along_axis(llf, (i + 1)[None, :], axis=0)[0]
    denom = f_m1 - 2.0 * f_0 + f_p1
    shift = jnp.where(jnp.abs(denom) > 1e-12,
                      0.5 * (f_m1 - f_p1) / denom, 0.0)
    shift = jnp.clip(shift, -1.0, 1.0)
    lam = grid[i] + shift * step
    return jnp.where(idx == jnp.clip(idx, 1, n_grid - 2), lam, grid[idx])


def boxcox_transform(x, lam):
    lam = lam[..., None]
    safe = jnp.maximum(x, 1e-300)
    return jnp.where(jnp.abs(lam) < 1e-12,
                     jnp.log(safe),
                     (jnp.power(safe, lam) - 1.0) / jnp.where(
                         jnp.abs(lam) < 1e-12, 1.0, lam))


def inv_boxcox(y, lam):
    lam = lam[..., None]
    return jnp.where(jnp.abs(lam) < 1e-12,
                     jnp.exp(y),
                     jnp.power(jnp.maximum(lam * y + 1.0, 1e-300),
                               1.0 / jnp.where(jnp.abs(lam) < 1e-12,
                                               1.0, lam)))


def _fit_prefix(d: jnp.ndarray, w: jnp.ndarray):
    """Hannan–Rissanen ARMA(1,1) fit on one weighted (prefix-masked)
    difference series d [L]; returns (phi, theta).

    Stage 1: AR(1) OLS → provisional residuals.
    Stage 2: OLS of d_t on [d_{t-1}, resid_{t-1}] (2×2 normal equations).
    """
    d_lag = jnp.concatenate([jnp.zeros_like(d[:1]), d[:-1]])
    w_pair = w * jnp.concatenate([jnp.zeros_like(w[:1]), w[:-1]])
    # Stage 1
    a = (jnp.sum(w_pair * d * d_lag)
         / (jnp.sum(w_pair * d_lag * d_lag) + _RIDGE))
    eps1 = (d - a * d_lag) * w_pair  # resid_0 := 0
    e_lag = jnp.concatenate([jnp.zeros_like(eps1[:1]), eps1[:-1]])
    # Stage 2: X = [d_lag, e_lag], solve (XᵀWX + rI) β = XᵀW d
    s11 = jnp.sum(w_pair * d_lag * d_lag) + _RIDGE
    s12 = jnp.sum(w_pair * d_lag * e_lag)
    s22 = jnp.sum(w_pair * e_lag * e_lag) + _RIDGE
    b1 = jnp.sum(w_pair * d_lag * d)
    b2 = jnp.sum(w_pair * e_lag * d)
    det = s11 * s22 - s12 * s12
    det = jnp.where(jnp.abs(det) < 1e-30, 1e-30, det)
    phi = (s22 * b1 - s12 * b2) / det
    theta = (s11 * b2 - s12 * b1) / det
    return (jnp.clip(phi, -_CLIP, _CLIP),
            jnp.clip(theta, -_CLIP, _CLIP))


@functools.partial(jax.jit,
                   static_argnames=("refit_every", "group_chunk"))
def arima_walk_forward(y: jnp.ndarray, mask: jnp.ndarray,
                       refit_every: int = 1,
                       group_chunk: int = 512) -> jnp.ndarray:
    """Walk-forward one-step forecasts for a padded [S, T] Box-Cox batch.

    pred[:, :3] = y[:, :3] (the reference's train prefix is passed
    through, :241-255); pred[:, m] for m ≥ 3 comes from a fit on a
    prefix of y.

    `refit_every=k` groups prefixes: the fit for steps [g·k, (g+1)·k)
    uses the prefix of length max(g·k, 3), and one CSS residual
    recursion per group serves all its steps — k=1 is the reference's
    exact refit-per-step semantics; k>1 trades refit freshness for a
    k× compute cut on long series (the 24h@1s scale where per-step
    refits are infeasible for any implementation). Groups evaluate in
    `group_chunk`-sized chunks via lax.map, so peak memory is
    O(S · group_chunk · T) instead of the O(S · T²) a full vmap over
    prefixes would materialize.
    """
    S, T = y.shape
    k = refit_every
    n_groups = -(-T // k)
    y0 = jnp.where(mask, y, 0.0)

    def per_series(y_row):
        d = y_row[1:] - y_row[:-1]            # [T-1]
        idx = jnp.arange(T - 1)

        def group_preds(g):
            # Fit on the prefix available at the group's first step;
            # CSS recursion eps_t = d_t − φ d_{t-1} − θ eps_{t-1}
            # (eps_0 = 0) runs once with the group's params — eps_t for
            # t < m−1 doesn't depend on the prefix cutoff, so each step
            # m just reads eps[m−2].
            m_fit = jnp.maximum(g * k, 3)
            w = (idx < (m_fit - 1)).astype(y_row.dtype)
            with jax.named_scope("fit"):
                phi, theta = _fit_prefix(d, w)

            def step(eps_prev, t):
                d_prev = jnp.where(t >= 1, d[jnp.maximum(t - 1, 0)],
                                   0.0)
                eps_t = d[t] - phi * d_prev - theta * eps_prev
                eps_t = jnp.where(t == 0, 0.0, eps_t)
                return eps_t, eps_t

            with jax.named_scope("css"):
                _, eps = jax.lax.scan(
                    step, jnp.array(0.0, y_row.dtype), idx)
            ms = g * k + jnp.arange(k)
            last = jnp.clip(ms - 2, 0, T - 2)
            d_hat = phi * d[last] + theta * eps[last]
            return y_row[jnp.clip(ms - 1, 0, T - 1)] + d_hat

        gs = jnp.arange(n_groups)
        if n_groups <= group_chunk:
            preds = jax.vmap(group_preds)(gs).reshape(-1)[:T]
        else:
            pad = (-n_groups) % group_chunk
            gs = jnp.concatenate([gs, jnp.zeros(pad, gs.dtype)])
            preds = jax.lax.map(
                jax.vmap(group_preds),
                gs.reshape(-1, group_chunk)).reshape(-1)[:T]
        ms_all = jnp.arange(T)
        return jnp.where(ms_all < 3, y_row, preds)

    return jax.vmap(per_series)(y0)


@functools.partial(jax.jit, static_argnames=("refit_every",))
def arima_scores(x: jnp.ndarray, mask: jnp.ndarray,
                 refit_every: int = 1):
    """Full ARIMA scoring: (pred levels [S,T], stddev [S], anomaly [S,T]).

    Series with ≤ 3 points or any non-positive value produce no anomalies
    and zero algoCalc, matching the reference's error paths (:232-234,
    :260-264: scipy.boxcox raises on x ≤ 0 → caught → None → [False]).
    `refit_every` (see arima_walk_forward) defaults to the reference's
    exact refit-per-step; long-series callers raise it."""
    n = masked_count(mask)
    positive = jnp.all(jnp.where(mask, x > 0, True), axis=-1)
    ok = (n >= MIN_POINTS) & positive
    safe_x = jnp.where(mask & (x > 0), x, 1.0)

    # Normalize each series by its geometric mean before the transform.
    # Raw throughputs are ~1e6-1e9; when the MLE lambda is negative,
    # x^λ underflows the mantissa and (λ·y + 1) cancels — fatally in
    # float32 (the TPU path), noticeably even in float64. With x/gm ≈ 1
    # the transform is well-conditioned in both dtypes; predictions are
    # rescaled back to levels afterwards. (The reference transforms raw
    # values and simply inherits the float64 cancellation.)
    log_gm = jnp.sum(jnp.where(mask, jnp.log(safe_x), 0.0), axis=-1) \
        / jnp.maximum(n, 1)
    gm = jnp.exp(log_gm)[..., None]
    xs = safe_x / gm

    with jax.named_scope("boxcox"):
        lam = boxcox_lambda(xs, mask)
        y = boxcox_transform(xs, lam)
    # Auto-size the group chunk: each chunk materializes an
    # [S, chunk, T] f32 eps stack — budget it at ~256 MiB so 24h@1s
    # series fit alongside the rest of the working set.
    S, T = x.shape
    chunk = max(1, min(512, (256 << 20) // max(1, 4 * S * T)))
    preds_bc = arima_walk_forward(y, mask, refit_every=refit_every,
                                  group_chunk=chunk)
    preds = inv_boxcox(preds_bc, lam) * gm
    preds = jnp.where(ok[..., None] & mask, preds, 0.0)

    with jax.named_scope("stddev"):
        std = masked_stddev_samp(x, mask)
    anomaly = (jnp.abs(x - preds) > std[..., None]) & mask & ok[..., None]
    return preds, std, anomaly
