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
  * The MA residual recursion runs once over time for all prefixes of
    a slab of series, carrying eps[series, prefix] and keeping of every
    step the one residual a forecast reads (`_css_forecasts`).

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
# The CSS recursion's carry, eps[series, refit groups]: how many
# elements one slab of series may hold (16 MiB of float32; the loop
# reads and writes it once an iteration, and on a TPU v5e the
# recursion over 4,096 series x 2,048 groups took 182 ms as one slab
# of 2^23 elements, 99 ms as two of 2^22, 116 ms as eight of 2^20), and
# the most steps one loop iteration runs straight-line (an iteration
# costs 2.2 us + 0.19 us a step there; the bound keeps the compiled
# program small when a caller asks for a refit every thousands of
# steps).
CSS_CARRY_ELEMENTS = 1 << 22
CSS_BLOCK_STEPS = 64


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


def css_plan(n_series: int, n_steps: int, refit_every: int):
    """How the CSS recursion of an [n_series, n_steps] batch is laid
    out, from its static shapes alone: (slabs, series a slab, loop
    iterations a slab, steps an iteration).

    The recursion carries eps[series, groups] through time, so a slab
    takes as many series as keep that carry within CSS_CARRY_ELEMENTS.
    One loop iteration runs a block of steps straight-line: a group's
    whole window of `refit_every` steps or, where that is longer than
    CSS_BLOCK_STEPS, its largest divisor that is not (a block never
    spans two windows, so the column it records is fixed)."""
    k = refit_every
    n_groups = -(-n_steps // k)
    n_slabs = max(1, -(-n_series
                       // max(1, CSS_CARRY_ELEMENTS // n_groups)))
    block = max(b for b in range(1, min(k, CSS_BLOCK_STEPS) + 1)
                if k % b == 0)
    return (n_slabs, -(-n_series // n_slabs), n_groups * (k // block),
            block)


def css_loop_iterations(n_series: int, n_steps: int,
                        refit_every: int) -> int:
    """Trip count of the recursion's sequential loop as compiled for
    one call, summed over its slabs: what `arima_walk_forward` itself
    lays out (`css_plan`)."""
    n_slabs, _, n_blocks, _ = css_plan(n_series, n_steps, refit_every)
    return n_slabs * n_blocks


def _fit_groups(d_row: jnp.ndarray, k: int, n_groups: int,
                group_chunk: int):
    """(phi, theta), each [n_groups], of one series' differences
    d_row [T-1]: group g is fitted on the prefix available at its
    first step, max(g·k, 3) points. Groups evaluate in
    `group_chunk`-sized chunks via lax.map, because each fit's masked
    sums run over the whole row."""
    idx = jnp.arange(d_row.shape[0])

    def fit_group(g):
        with jax.named_scope("fit"):
            m_fit = jnp.maximum(g * k, 3)
            w = (idx < (m_fit - 1)).astype(d_row.dtype)
            return _fit_prefix(d_row, w)

    gs = jnp.arange(n_groups)
    if n_groups <= group_chunk:
        return jax.vmap(fit_group)(gs)
    pad = (-n_groups) % group_chunk
    gs = jnp.concatenate([gs, jnp.zeros(pad, gs.dtype)])
    phi, theta = jax.lax.map(jax.vmap(fit_group),
                             gs.reshape(-1, group_chunk))
    return phi.reshape(-1)[:n_groups], theta.reshape(-1)[:n_groups]


def _css_forecasts(d_cur, d_prev, phi, theta, block: int):
    """The forecast differences of one slab of series, [S, G·k]:
    column m is φ d_(m-2) + θ eps_(m-2) under the (φ, θ) [S, G] of
    group m // k, eps by the CSS recursion
    eps_t = d_t − φ d_(t-1) − θ eps_(t-1), eps_t = 0 for t ≤ 0.

    One pass over time for all groups. Group g reads eps only at
    t = g·k + j − 2, j < k, so the one column of the carry eps[S, G]
    that anybody reads at step t is (t + 2) // k. Loop iteration b
    runs the `block` steps from t = b·block − 2 straight-line on the
    carry, and the same steps on that column alone, whose forecasts it
    keeps. `d_cur` and `d_prev` [S, G·k] hold d_t and d_(t-1) in
    column t + 2 and 0 where t ≤ 0, so that no step needs a mask.

    Inside, series are the minor axis (the carry is [G, S], a step's
    differences a row), so that no array has `block` for its minor
    axis, which the device would pad to a whole tile."""
    S, G = phi.shape
    n_blocks = d_cur.shape[1] // block
    phi, theta = phi.T, theta.T

    def steps(eps, cur, prev, phi, theta):
        seen = []
        for j in range(block):
            eps = cur[j:j + 1] - phi * prev[j:j + 1] - theta * eps
            seen.append(eps)
        return seen

    def run_block(eps, xs):
        b, cur, prev = xs                               # [block, S]
        own = functools.partial(jax.lax.dynamic_slice_in_dim,
                                start_index=b // (n_blocks // G),
                                slice_size=1, axis=0)
        phi_g, theta_g = own(phi), own(theta)
        read = jnp.concatenate(steps(own(eps), cur, prev, phi_g, theta_g))
        return (steps(eps, cur, prev, phi, theta)[-1],
                phi_g * cur + theta_g * read)

    def by_block(a):                      # [S, G·k] → [n_blocks, block, S]
        return a.T.reshape(n_blocks, block, S)

    _, d_hat = jax.lax.scan(
        run_block, jnp.zeros((G, S), phi.dtype),
        (jnp.arange(n_blocks), by_block(d_cur), by_block(d_prev)))
    return d_hat.reshape(-1, S).T


@functools.partial(jax.jit, static_argnames=("refit_every",))
def arima_walk_forward(y: jnp.ndarray, mask: jnp.ndarray,
                       refit_every: int = 1) -> jnp.ndarray:
    """Walk-forward one-step forecasts for a padded [S, T] Box-Cox batch.

    pred[:, :3] = y[:, :3] (the reference's train prefix is passed
    through, :241-255); pred[:, m] for m ≥ 3 comes from a fit on a
    prefix of y.

    `refit_every=k` groups prefixes: the fit for steps [g·k, (g+1)·k)
    uses the prefix of length max(g·k, 3) — k=1 is the reference's
    exact refit-per-step semantics; k>1 trades refit freshness for a
    k× compute cut on long series (the 24h@1s scale where per-step
    refits are infeasible for any implementation). With G = ⌈T / k⌉
    groups: first every group's fit, [S, G] (`_fit_groups`, chunked
    over groups); then one CSS residual recursion over time for all
    groups at once, which keeps of each step the one residual a
    forecast reads and turns it into that forecast (`_css_forecasts`;
    eps_t for t < m−1 doesn't depend on the prefix cutoff, so step m
    just reads its group's eps_(m−2)), over slabs of series
    (`css_plan`)."""
    S, T = y.shape
    k = refit_every
    n_groups = -(-T // k)
    y0 = jnp.where(mask, y, 0.0)
    d = y0[:, 1:] - y0[:, :-1]                            # [S, T-1]

    # Each chunk of fits holds [S, chunk, T-1] masked products: budget
    # them at ~256 MiB of f32 so 24h@1s series fit alongside the rest
    # of the working set.
    chunk = max(1, min(512, (256 << 20) // max(1, 4 * S * T)))
    phi, theta = jax.vmap(
        lambda d_row: _fit_groups(d_row, k, n_groups, chunk))(d)

    def from_step_minus_2(a):      # d[:, 1:] or d[:, :-1] → [S, G·k]
        return jnp.pad(a, ((0, 0), (3, k)))[:, :n_groups * k]

    d_cur = from_step_minus_2(d[:, 1:])
    d_prev = from_step_minus_2(d[:, :-1])
    n_slabs, slab, _, block = css_plan(S, T, k)

    def slabs(a):                      # [S, n] → [n_slabs, slab, n]
        return jnp.pad(a, ((0, n_slabs * slab - S), (0, 0))).reshape(
            n_slabs, slab, -1)

    with jax.named_scope("css"):
        d_hat = jax.lax.map(
            lambda args: _css_forecasts(*args, block=block),
            tuple(slabs(a) for a in (d_cur, d_prev, phi, theta)))
    d_hat = d_hat.reshape(n_slabs * slab, -1)[:S, :T]
    preds = jnp.pad(y0, ((0, 0), (1, 0)))[:, :T] + d_hat
    return jnp.where(jnp.arange(T) < 3, y0, preds)


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
    preds_bc = arima_walk_forward(y, mask, refit_every=refit_every)
    preds = inv_boxcox(preds_bc, lam) * gm
    preds = jnp.where(ok[..., None] & mask, preds, 0.0)

    with jax.named_scope("stddev"):
        std = masked_stddev_samp(x, mask)
    anomaly = (jnp.abs(x - preds) > std[..., None]) & mask & ok[..., None]
    return preds, std, anomaly
