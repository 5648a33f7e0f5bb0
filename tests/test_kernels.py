"""Golden tests for the anomaly kernels against reference semantics.

Oracles: EWMA — the reference's recurrence re-run as a plain Python loop
(anomaly_detection.py:146-212); DBSCAN — sklearn itself (:325-349);
Box-Cox — scipy (:239). statsmodels is not installed in this image, so
ARIMA is validated behaviorally: spike recovery on synthetic series and
the reference's error paths (≤3 points / non-positive values → no
anomalies). Estimator deltas are documented in theia_tpu/ops/arima.py.
"""

import numpy as np
import pytest

from theia_tpu.ops import (
    arima_scores,
    boxcox_lambda,
    dbscan_noise,
    ewma_scores,
    masked_stddev_samp,
)


def _pad(series_list, dtype=np.float64):
    S = len(series_list)
    T = max(len(s) for s in series_list)
    x = np.zeros((S, T), dtype)
    m = np.zeros((S, T), bool)
    for i, s in enumerate(series_list):
        x[i, :len(s)] = s
        m[i, :len(s)] = True
    return x, m


def ref_ewma(values, alpha=0.5):
    prev, out = 0.0, []
    for v in values:
        prev = (1 - alpha) * prev + alpha * float(v)
        out.append(prev)
    return out


def test_ewma_matches_reference_loop(rng):
    series = [rng.uniform(1e5, 1e7, size=n) for n in (1, 2, 7, 60)]
    x, m = _pad(series)
    e, std, anom = ewma_scores(x, m)
    for i, s in enumerate(series):
        np.testing.assert_allclose(
            np.asarray(e)[i, :len(s)], ref_ewma(s), rtol=1e-12)
        expect_std = np.std(s, ddof=1) if len(s) >= 2 else None
        if expect_std is None:
            assert np.isnan(np.asarray(std)[i])
            assert not np.asarray(anom)[i].any()
        else:
            np.testing.assert_allclose(np.asarray(std)[i], expect_std)
            expect = [abs(v - w) > expect_std
                      for v, w in zip(s, ref_ewma(s))]
            assert list(np.asarray(anom)[i, :len(s)]) == expect


def test_ewma_detects_spike(rng):
    base = rng.normal(1e6, 3e4, size=50).clip(1e5)
    spiked = base.copy()
    spiked[37] = 2e7
    x, m = _pad([base, spiked])
    _, _, anom = ewma_scores(x, m)
    anom = np.asarray(anom)
    # (Exact parity with the reference loop — including its warmup-from-0
    # and 1-sigma-band noise flags — is covered by the oracle test above;
    # here just confirm the injected spike is caught.)
    assert anom[1, 37]
    # The spike inflates the sample stddev, so the spiked series flags
    # strictly fewer normal points than it does spike points by margin.
    assert anom[1].sum() <= anom[0].sum() + 1


def test_dbscan_matches_sklearn(rng):
    from sklearn.cluster import DBSCAN
    cases = [
        rng.uniform(0, 1e9, size=40),
        np.concatenate([rng.normal(1e8, 1e6, 30), [9.9e8]]),
        rng.normal(5e8, 1e5, size=8),
        np.array([1.0, 2.0, 3.0]),  # fewer points than min_samples
    ]
    x, m = _pad(cases)
    ours = np.asarray(dbscan_noise(x, m))
    for i, s in enumerate(cases):
        labels = DBSCAN(min_samples=4, eps=2.5e8).fit_predict(
            s.reshape(-1, 1))
        np.testing.assert_array_equal(ours[i, :len(s)], labels == -1)


def test_boxcox_lambda_close_to_scipy(rng):
    from scipy import stats
    series = [rng.lognormal(14, 0.3, size=60) for _ in range(4)]
    x, m = _pad(series)
    lam = np.asarray(boxcox_lambda(x, m))
    for i, s in enumerate(series):
        _, ref_lam = stats.boxcox(s)
        # Grid+parabolic vs Brent: the llf is flat near the optimum, so
        # compare achieved log-likelihood rather than raw lambda.
        ours = stats.boxcox_llf(lam[i], s)
        best = stats.boxcox_llf(ref_lam, s)
        assert ours >= best - abs(best) * 1e-4


def test_arima_recovers_spikes_and_error_paths(rng):
    quiet = rng.normal(1e6, 2e4, size=40).clip(1e5)
    spiked = quiet.copy()
    spiked[25] = 3e7
    short = np.array([1e6, 1.1e6, 0.9e6])        # len 3 → no anomalies
    nonpos = np.concatenate([quiet[:10], [0.0]])  # x ≤ 0 → no anomalies
    x, m = _pad([quiet, spiked, short, nonpos])
    preds, std, anom = map(np.asarray, arima_scores(x, m))
    # A 1-sigma band on one-step forecasts of white noise fires on a
    # minority of normal points by construction (the reference detector
    # has the same property); the spike must be flagged and the error
    # paths must stay silent.
    assert anom[0].mean() < 0.5
    assert anom[1, 25]
    assert not anom[2].any() and not anom[3].any()
    # train prefix passes through: first 3 predictions ≈ observations.
    # Tolerance is loose because the Box-Cox round trip itself loses
    # precision when the MLE lambda is strongly negative and x is large
    # ((λy+1) cancels to ~1e-12); scipy's round trip behaves the same.
    np.testing.assert_allclose(preds[0, :3], quiet[:3], rtol=5e-3)
    # forecasts track a stationary series to within a few stddevs
    track = np.abs(preds[0, 3:] - quiet[3:])
    assert np.median(track) < 3 * np.asarray(std)[0]


def test_masked_stddev_matches_numpy(rng):
    s = rng.uniform(0, 1e8, size=13)
    x, m = _pad([s])
    np.testing.assert_allclose(
        np.asarray(masked_stddev_samp(x, m))[0], np.std(s, ddof=1))


@pytest.mark.parametrize("algo", ["ewma", "dbscan"])
def test_kernels_all_padding_safe(rng, algo):
    # Garbage in padded region must not affect results.
    s = rng.uniform(1e5, 1e7, size=10)
    x1, m = _pad([s])
    x2 = x1.copy()
    x2[0, 10:] = 7.7e18 if x2.shape[1] > 10 else x2[0, 10:]
    x1 = np.pad(x1, ((0, 0), (0, 6)))
    x2 = np.pad(x2, ((0, 0), (0, 6)), constant_values=3.3e17)
    m = np.pad(m, ((0, 0), (0, 6)))
    fn = ewma_scores if algo == "ewma" else (
        lambda a, b: (None, None, dbscan_noise(a, b)))
    r1 = fn(x1, m)[2]
    r2 = fn(x2, m)[2]
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_arima_grouped_refit_long_series():
    """refit_every>1 (the 24h@1s-scale path) still flags spikes and
    matches the exact path closely away from refit boundaries; memory
    stays O(S*chunk*T) via lax.map chunking (an [S,T,T] vmap would OOM
    real deployments — round-9 probe)."""
    import numpy as np

    from theia_tpu.ops import arima_scores

    rng = np.random.default_rng(7)
    S, T = 4, 512
    x = rng.uniform(1e6, 2e6, (S, T))
    spikes = [(0, 300), (1, 100), (2, 450), (3, 256)]
    for s, t in spikes:
        x[s, t] = 5e7
    mask = np.ones((S, T), bool)
    _, _, exact = arima_scores(x, mask, refit_every=1)
    _, _, grouped = arima_scores(x, mask, refit_every=16)
    exact, grouped = np.asarray(exact), np.asarray(grouped)
    for s, t in spikes:
        assert grouped[s, t], f"spike ({s},{t}) missed by grouped refit"
    # grouped and exact agree almost everywhere (params drift only
    # within a refit window after a spike)
    agreement = (exact == grouped).mean()
    assert agreement > 0.98, f"agreement {agreement:.3f}"
