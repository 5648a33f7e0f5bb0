"""Sharded scoring on the 8-device virtual CPU mesh: sharded == local."""

import numpy as np

from theia_tpu.ops import ewma_scores
from theia_tpu.parallel import (
    make_mesh,
    make_sharded_ewma,
    pad_to_multiple,
    shard_arrays,
)


def _batch(rng, S=16, T=24):
    x = rng.uniform(1e5, 1e7, size=(S, T))
    mask = np.ones((S, T), bool)
    # make some series ragged
    mask[S // 4, (3 * T) // 4:] = False
    mask[S - 1, T // 4:] = False
    x[~mask] = 0.0
    return x, mask


def test_series_dp_matches_single_device(eight_devices, rng):
    mesh = make_mesh(8, time_shards=1)
    x, mask = _batch(rng)
    fn = make_sharded_ewma(mesh)
    xs, ms = shard_arrays(mesh, x, mask)
    e, std, anom, count = fn(xs, ms)
    e_ref, std_ref, anom_ref = ewma_scores(x, mask)
    np.testing.assert_allclose(np.asarray(e), np.asarray(e_ref),
                               rtol=1e-12)
    np.testing.assert_allclose(np.asarray(std), np.asarray(std_ref),
                               rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(anom), np.asarray(anom_ref))
    assert int(count) == int(np.asarray(anom_ref).sum())


def test_time_sharded_scan_matches_single_device(eight_devices, rng):
    # 4 series shards x 2 time shards: the cross-device scan composition
    # must reproduce the sequential recurrence exactly.
    mesh = make_mesh(8, time_shards=2)
    x, mask = _batch(rng, S=8, T=32)
    fn = make_sharded_ewma(mesh)
    xs, ms = shard_arrays(mesh, x, mask)
    e, std, anom, count = fn(xs, ms)
    e_ref, std_ref, anom_ref = ewma_scores(x, mask)
    np.testing.assert_allclose(np.asarray(e), np.asarray(e_ref),
                               rtol=1e-12)
    np.testing.assert_allclose(np.asarray(std), np.asarray(std_ref),
                               rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(anom), np.asarray(anom_ref))


def test_time_sharded_four_way(eight_devices, rng):
    mesh = make_mesh(8, time_shards=4)
    x, mask = _batch(rng, S=4, T=64)
    fn = make_sharded_ewma(mesh)
    e, _, _, _ = fn(*shard_arrays(mesh, x, mask))
    e_ref, _, _ = ewma_scores(x, mask)
    np.testing.assert_allclose(np.asarray(e), np.asarray(e_ref),
                               rtol=1e-12)


def test_pad_to_multiple():
    arr = np.ones((5, 3))
    padded, orig = pad_to_multiple(arr, 4, axis=0)
    assert padded.shape == (8, 3) and orig == 5
    assert padded[5:].sum() == 0


def test_sharded_arima_matches_single_device(eight_devices, rng):
    from theia_tpu.ops import arima_scores
    from theia_tpu.parallel import make_sharded_arima, shard_arrays

    mesh = make_mesh(8, time_shards=1)
    S, T = 16, 24
    x = np.maximum(
        1e6 + 1e5 * rng.standard_normal((S, T)).cumsum(axis=1), 1e3)
    x[2, 20] *= 30.0
    mask = np.ones((S, T), bool)
    mask[5, 18:] = False
    fn = make_sharded_arima(mesh, refit_every=1)
    calc, std, anom = fn(*shard_arrays(mesh, x, mask))
    c_ref, s_ref, a_ref = arima_scores(x, mask, refit_every=1)
    np.testing.assert_allclose(np.asarray(calc), np.asarray(c_ref),
                               rtol=1e-10)
    np.testing.assert_array_equal(np.asarray(anom), np.asarray(a_ref))


def test_sharded_dbscan_matches_single_device(eight_devices, rng):
    from theia_tpu.ops import dbscan_scores
    from theia_tpu.parallel import make_sharded_dbscan, shard_arrays

    mesh = make_mesh(8, time_shards=1)
    x = rng.uniform(1e6, 2e8, size=(8, 16))
    x[1, 3] = 9e9   # isolated outlier
    mask = np.ones(x.shape, bool)
    fn = make_sharded_dbscan(mesh, eps=2.5e8, min_samples=4)
    calc, std, anom = fn(*shard_arrays(mesh, x, mask))
    _, s_ref, a_ref = dbscan_scores(x, mask)
    np.testing.assert_array_equal(np.asarray(anom), np.asarray(a_ref))
    np.testing.assert_allclose(np.asarray(std), np.asarray(s_ref),
                               rtol=1e-12)
    assert np.asarray(anom)[1, 3]


def test_sharded_points_dbscan_matches_tiled(eight_devices, rng):
    from theia_tpu.ops.dbscan import dbscan_points_noise
    from theia_tpu.parallel import (make_rows_mesh,
                                    make_sharded_points_dbscan)

    mesh = make_rows_mesh(8)
    pts = rng.normal(0, 1, size=(64, 5)).astype(np.float32)
    pts[7] += 25.0
    valid = np.ones(64, bool)
    valid[-3:] = False
    noise_sh = np.asarray(
        make_sharded_points_dbscan(mesh, eps=1.2)(pts, valid))
    noise_ref = np.asarray(
        dbscan_points_noise(pts, valid, eps=1.2, block=16))
    np.testing.assert_array_equal(noise_sh, noise_ref)
    assert noise_sh[7] and not noise_sh[-1]


def test_score_series_mesh_pads_and_slices(eight_devices, rng):
    # S not divisible by the mesh: padding must not leak phantom rows.
    from theia_tpu.analytics.tad import score_series

    mesh = make_mesh(8, time_shards=1)
    S, T = 11, 13
    x = rng.uniform(1e5, 1e7, size=(S, T))
    mask = np.ones((S, T), bool)
    c_sh, s_sh, a_sh = score_series(x, mask, "EWMA", mesh=mesh)
    c_lo, s_lo, a_lo = score_series(x, mask, "EWMA")
    assert c_sh.shape == (S, T) and s_sh.shape == (S,)
    np.testing.assert_allclose(c_sh, c_lo, rtol=1e-12)
    np.testing.assert_array_equal(a_sh, a_lo)


def test_long_series_auto_time_sharding(eight_devices, rng):
    """Fewer series than devices + long T: EWMA re-shards over TIME
    (sequence parallelism) instead of falling back to one device —
    results match the local kernel up to the documented psum stddev
    approximation (Weak r4 #8: time sharding now has a production
    policy)."""
    from theia_tpu.analytics.tad import LONG_SERIES_T, score_series

    mesh = make_mesh(8, time_shards=1)
    S, T = 3, LONG_SERIES_T          # 3 series over 8 devices
    x = rng.uniform(1e5, 1e7, size=(S, T))
    mask = np.ones((S, T), bool)
    c_sh, s_sh, a_sh = score_series(x, mask, "EWMA", mesh=mesh)
    c_lo, s_lo, a_lo = score_series(x, mask, "EWMA")
    assert c_sh.shape == (S, T)
    np.testing.assert_allclose(c_sh, c_lo, rtol=1e-6)
    np.testing.assert_allclose(s_sh, s_lo, rtol=1e-6)
    # anomaly flags may flip only exactly on the threshold boundary
    assert (a_sh == a_lo).mean() > 0.999

    # below the threshold the local path still wins (no re-mesh)
    xs = rng.uniform(1e5, 1e7, size=(3, 64))
    ms = np.ones((3, 64), bool)
    c2, _, a2 = score_series(xs, ms, "EWMA", mesh=mesh)
    c2_lo, _, a2_lo = score_series(xs, ms, "EWMA")
    np.testing.assert_allclose(c2, c2_lo, rtol=1e-12)
    np.testing.assert_array_equal(a2, a2_lo)


def test_run_tad_sharded_rows_match_single_device(eight_devices):
    # The production job entry point over a mesh emits the same
    # tadetector rows as single-device (exact under the x64 conftest).
    from theia_tpu.analytics import TadQuerySpec, run_tad
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.store import FlowDatabase

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=16, points_per_series=24, anomaly_fraction=0.4,
        anomaly_magnitude=30.0, base_throughput=1e7)))
    mesh = make_mesh(8, time_shards=1)
    for algo in ("EWMA", "ARIMA", "DBSCAN"):
        run_tad(db, algo, TadQuerySpec(), tad_id=f"sh-{algo}",
                mesh=mesh)
        run_tad(db, algo, TadQuerySpec(), tad_id=f"lo-{algo}",
                mesh=None)
        data = db.tadetector.scan()
        ids = data.strings("id")
        sh = sorted(tuple(sorted((k, v) for k, v in r.items()
                                 if k != "id"))
                    for r in data.filter(ids == f"sh-{algo}").to_rows())
        lo = sorted(tuple(sorted((k, v) for k, v in r.items()
                                 if k != "id"))
                    for r in data.filter(ids == f"lo-{algo}").to_rows())
        assert sh == lo and sh, f"{algo} sharded != single-device"


def test_run_npr_sharded_policies_match_single_device(eight_devices):
    # An explicitly passed mesh opts into the sharded device distinct,
    # whatever the row count.
    from theia_tpu.analytics import run_npr
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.store import FlowDatabase

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=24, points_per_series=4)))
    mesh = make_mesh(8, time_shards=1)
    run_npr(db, recommendation_id="sh", mesh=mesh)
    run_npr(db, recommendation_id="lo", mesh=None)
    recs = db.recommendations.scan()
    ids = recs.strings("id")
    sh = sorted(zip(recs.filter(ids == "sh").strings("kind"),
                    recs.filter(ids == "sh").strings("policy")))
    lo = sorted(zip(recs.filter(ids == "lo").strings("kind"),
                    recs.filter(ids == "lo").strings("policy")))
    assert sh == lo and sh


def test_sharded_distinct_with_sentinel_padding(eight_devices, rng):
    # device_distinct pads row counts that don't divide the mesh with
    # the sentinel; results must match the host group_reduce exactly.
    from theia_tpu.analytics.npr_device import device_distinct
    from theia_tpu.parallel import make_rows_mesh

    mesh = make_rows_mesh(8)
    keys = list(rng.integers(0, 5, size=(4, 61)).astype(np.int64))
    u_sh, c_sh = device_distinct(keys, use_device=True, mesh=mesh)
    u_lo, c_lo = device_distinct(keys, use_device=False)
    np.testing.assert_array_equal(u_sh, u_lo)
    np.testing.assert_array_equal(c_sh, c_lo)


def test_job_mesh_env_switch(eight_devices, monkeypatch):
    from theia_tpu.parallel import job_mesh, reset_cache

    reset_cache()
    monkeypatch.setenv("THEIA_MESH", "off")
    assert job_mesh() is None
    monkeypatch.setenv("THEIA_MESH", "auto")
    m = job_mesh()
    assert m is not None and m.size == 8
    monkeypatch.setenv("THEIA_MESH", "4")
    assert job_mesh().size == 4
    reset_cache()
