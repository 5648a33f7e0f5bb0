"""Cross-engine parity suite: the fused device pipeline
(ingest/device_path.py + ops/fused_detector.py) must produce the SAME
alert stream as the sharded per-lock engine on the same per-shard
input order — plus unit coverage for the coalescing queue, staging
reuse, the admission pressure signal, and the saturation metrics.

Everything here runs CPU-green in tier-1; the `device`-marked cases at
the bottom need a real accelerator and auto-skip otherwise
(tests/conftest.py)."""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.ingest import BlockEncoder
from theia_tpu.manager.ingest import IngestManager
from theia_tpu.store import FlowDatabase
from theia_tpu.utils.native import native_available


def _strip(conn_alerts):
    """Connection alerts minus the latency measurement (a wall-clock
    observation, not detector output — the one field the parity
    contract excludes)."""
    return [{k: v for k, v in d.items() if k != "latency_s"}
            for d in conn_alerts]


def _assert_same_alerts(sharded_out, fused_out):
    hs, cs, ns = sharded_out
    hf, cf, nf = fused_out
    assert ns == nf
    assert hs == hf
    assert _strip(cs) == _strip(cf)


def _workload(seeds, n_series=150, points=8, anomaly=0.3):
    return [generate_flows(SynthConfig(
        n_series=n_series, points_per_series=points,
        anomaly_fraction=anomaly, seed=s)) for s in seeds]


def _pair(n_shards=4, **kwargs):
    return (IngestManager(FlowDatabase(), n_shards=n_shards, **kwargs),
            IngestManager(FlowDatabase(), n_shards=n_shards,
                          engine="fused", **kwargs))


# -- alert parity ---------------------------------------------------------

def test_parity_single_shard():
    im_s, im_f = _pair(n_shards=1)
    try:
        for b in _workload(range(3)):
            _assert_same_alerts(im_s.score_batch(b),
                                im_f.score_batch(b))
    finally:
        im_f.close()
        im_s.close()


@pytest.mark.parametrize("seed0", [0, 100, 200])
def test_parity_randomized_multi_shard(seed0):
    """Randomized multi-shard workloads, fed sequentially (the
    documented determinism contract: a producer that awaits each ack
    gets reproducible alerts) — alert streams must be identical,
    heavy-hitter and connection-anomaly both."""
    im_s, im_f = _pair(n_shards=4)
    try:
        rng = np.random.default_rng(seed0)
        for i in range(5):
            b = generate_flows(SynthConfig(
                n_series=int(rng.integers(20, 300)),
                points_per_series=int(rng.integers(2, 12)),
                anomaly_fraction=float(rng.uniform(0.0, 0.5)),
                seed=seed0 + i))
            _assert_same_alerts(im_s.score_batch(b),
                                im_f.score_batch(b))
    finally:
        im_f.close()
        im_s.close()


def test_parity_slot_overflow():
    """Capacity overflow (new series dropped, only existing slots keep
    scoring) must degrade identically in both engines, and both must
    count the same dropped series."""
    im_s, im_f = _pair(n_shards=2, streaming_capacity=40)
    try:
        for b in _workload(range(4), n_series=120):
            _assert_same_alerts(im_s.score_batch(b),
                                im_f.score_batch(b))
        drop_s = [s.streaming.dropped_series for s in im_s.shards]
        drop_f = [s.streaming.dropped_series for s in im_f.shards]
        assert drop_s == drop_f
        assert sum(drop_s) > 0   # the workload genuinely overflowed
    finally:
        im_f.close()
        im_s.close()


def test_parity_every_series_dropped():
    """A batch whose every NEW series is turned away still advances
    the heavy-hitter leg identically (the fused no-op streaming tile
    must not disturb state)."""
    im_s, im_f = _pair(n_shards=2, streaming_capacity=1)
    try:
        for b in _workload(range(3), n_series=60):
            _assert_same_alerts(im_s.score_batch(b),
                                im_f.score_batch(b))
    finally:
        im_f.close()
        im_s.close()


@pytest.mark.skipif(not native_available(),
                    reason="native codec unavailable")
@pytest.mark.parametrize("rung", ["sampled", "shed_detector"])
def test_parity_under_brownout(rung, monkeypatch):
    """Under a pinned brownout rung both engines must shed the SAME
    batches (the sampling credit accumulator is deterministic) and
    alert identically on the batches that are scored."""
    monkeypatch.setenv("THEIA_ADMISSION_FORCE_LEVEL", rung)
    db_s, db_f = FlowDatabase(), FlowDatabase()
    im_s = IngestManager(db_s, n_shards=2)
    im_f = IngestManager(db_f, n_shards=2, engine="fused")
    # mid-band pressure so the sampled rung's scoring fraction is a
    # real fraction (at zero pressure "sampled" still scores 100%)
    for im in (im_s, im_f):
        im.admission.add_signal("testPressure", lambda: 0.65, 1.0)
    try:
        enc_s, enc_f = BlockEncoder(), BlockEncoder()
        degraded = 0
        for i in range(6):
            b = generate_flows(SynthConfig(
                n_series=60, points_per_series=4,
                anomaly_fraction=0.4, seed=i), dicts=enc_s.dicts)
            b2 = generate_flows(SynthConfig(
                n_series=60, points_per_series=4,
                anomaly_fraction=0.4, seed=i), dicts=enc_f.dicts)
            out_s = im_s.ingest(enc_s.encode(b))
            out_f = im_f.ingest(enc_f.encode(b2))
            assert out_s["rows"] == out_f["rows"]
            assert out_s["alerts"] == out_f["alerts"]
            assert out_s.get("degraded") == out_f.get("degraded")
            degraded += bool(out_s.get("degraded"))
        assert degraded > 0          # the rung actually engaged
        assert len(db_s.flows) == len(db_f.flows)   # durability never shed
    finally:
        im_f.close()
        im_s.close()


# -- engine mechanics -----------------------------------------------------

def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        IngestManager(FlowDatabase(), n_shards=1, engine="warp")


def test_empty_batch_fast_path():
    im = IngestManager(FlowDatabase(), n_shards=2, engine="fused")
    try:
        b = _workload([1])[0]
        assert im.score_batch(b.take(np.zeros(0, np.int64))) \
            == ([], [], 0)
    finally:
        im.close()


def test_queue_signal_and_liveness_surface():
    """The fused queue feeds the admission pressure ladder and shows
    up in the liveness doc (→ /healthz ingest section + theia top)."""
    im = IngestManager(FlowDatabase(), n_shards=2, engine="fused")
    try:
        assert im.admission is not None
        assert "fusedQueue" in im.admission.signal_ratios()
        live = im.shard_liveness()
        eng = live["engine"]
        assert eng["name"] == "fused"
        assert eng["queueDepth"] == 0
        assert eng["queueCapacity"] > 0
        for s in live["perShard"]:
            assert "droppedSeries" in s and "capacity" in s
        im.score_batch(_workload([5])[0])
        assert im.shard_liveness()["engine"]["steps"] >= 1
    finally:
        im.close()

    im_sharded = IngestManager(FlowDatabase(), n_shards=2)
    try:
        assert im_sharded.shard_liveness()["engine"] == {
            "name": "sharded"}
        assert "fusedQueue" not in \
            im_sharded.admission.signal_ratios()
    finally:
        im_sharded.close()


def test_dropped_series_counter_metric():
    from theia_tpu.analytics.streaming import _M_DROPPED, \
        StreamingDetector
    det = StreamingDetector(capacity=2)
    before = _M_DROPPED.value()
    b = _workload([9], n_series=20, points=2)[0]
    det.ingest(b)
    assert det.dropped_series > 0
    assert _M_DROPPED.value() - before == det.dropped_series


def test_staging_buffers_reused_across_steps():
    im = IngestManager(FlowDatabase(), n_shards=2, engine="fused")
    try:
        # identical shapes step after step: after the two double-buffer
        # generations warm up, allocation stops
        for b in _workload(range(4), n_series=100, points=4):
            im.score_batch(b)
        pool = im._fused._staging
        misses_warm = pool.misses
        for b in _workload(range(4, 8), n_series=100, points=4):
            im.score_batch(b)
        assert pool.hits > 0
        assert pool.misses == misses_warm   # steady state: no allocs
    finally:
        im.close()


def test_concurrent_producers_coalesce_without_loss():
    """K threads scoring concurrently: every request resolves, rows
    are conserved shard-by-shard (n_series grows exactly as the union
    of keys), and the engine survives coalesced steps."""
    im = IngestManager(FlowDatabase(), n_shards=4, engine="fused")
    ref = IngestManager(FlowDatabase(), n_shards=4)
    try:
        batches = _workload(range(6), n_series=80, points=3,
                            anomaly=0.0)
        errs = []

        def feed(i):
            try:
                im.score_batch(batches[i])
            except Exception as e:   # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=feed, args=(i,))
                   for i in range(len(batches))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for b in batches:
            ref.score_batch(b)
        assert sorted(s.streaming.n_series for s in im.shards) \
            == sorted(s.streaming.n_series for s in ref.shards)
        eng = im.shard_liveness()["engine"]
        assert eng["coalescedBlocks"] == len(batches)
        assert eng["steps"] >= 1
    finally:
        im.close()
        ref.close()


def test_oversize_batch_exceeding_ring_rows():
    """A single block larger than the coalescing row cap still scores
    (the cap bounds coalescing, not batch size)."""
    im_s = IngestManager(FlowDatabase(), n_shards=2)
    im_f = IngestManager(FlowDatabase(), n_shards=2, engine="fused")
    im_f._fused.max_step_rows = 64
    try:
        b = _workload([3], n_series=100, points=4)[0]   # 400 rows
        _assert_same_alerts(im_s.score_batch(b), im_f.score_batch(b))
    finally:
        im_f.close()
        im_s.close()


def test_close_idempotent_and_post_close_errors():
    im = IngestManager(FlowDatabase(), n_shards=1, engine="fused")
    b = _workload([2])[0]
    im.score_batch(b)
    im.close()
    im.close()
    with pytest.raises(RuntimeError):
        im._fused.score(b, None)


@pytest.mark.parametrize("u", [64, 256])
def test_pallas_interpret_matches_jnp_scan(u):
    """The Pallas tile-scan kernel (interpret mode, so it runs on the
    CPU backend) must reproduce the lax.scan core bit for bit — also
    for the one 64-wide tile bucket, which the wrapper pads up to a
    full 128-lane block (it used to skip the kernel silently)."""
    import jax.numpy as jnp

    from theia_tpu.analytics.streaming import init_state
    from theia_tpu.ops import fused_detector as fd

    rng = np.random.default_rng(11)
    t, cap = 3, 512
    state = init_state(cap)
    slots = np.arange(u, dtype=np.int32)
    x = rng.normal(5.0, 2.0, size=(t, u)).astype(np.float32)
    active = rng.random((t, u)) < 0.8
    sub = type(state)(*(a[jnp.asarray(slots)] for a in state))
    ref_state, ref_anom = fd._scan_tile(sub, jnp.asarray(x),
                                        jnp.asarray(active), 0.5)
    pl_state, pl_anom = fd._scan_tile_pallas(
        sub, jnp.asarray(x), jnp.asarray(active), 0.5,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_anom),
                                  np.asarray(pl_anom))
    for a, b2 in zip(ref_state, pl_state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b2))


@pytest.mark.parametrize("ticks,kernels", [(4, 1), (64, 1), (128, 0),
                                           (2048, 0)])
def test_a_long_tile_takes_the_scan_not_the_unrolled_kernel(ticks,
                                                            kernels):
    """The Pallas kernel unrolls its tick loop at trace time: a block
    of 1,600 points a connection (a 2,048-tick tile, eight shards)
    kept a process's first request past its 120 s. Up to
    PALLAS_MAX_TICKS the kernel, beyond it the scan, whose alerts are
    the same (test_pallas_interpret_matches_jnp_scan)."""
    import jax
    import jax.numpy as jnp

    from theia_tpu.analytics.streaming import init_state
    from theia_tpu.ops import fused_detector as fd

    assert fd.PALLAS_MAX_TICKS == 64
    u = 64
    inp = fd.ShardInputs(
        slots=jnp.arange(u, dtype=jnp.int32),
        x=jnp.ones((ticks, u), jnp.float32),
        active=jnp.ones((ticks, u), bool),
        keys=None, vols=None, q=None, feats=None, valid=None)
    jaxpr = jax.make_jaxpr(
        lambda st, i: fd._stream_half(st, i, 0.5, True, True))(
            init_state(512), inp)
    assert str(jaxpr).count("pallas_call") == kernels


# -- accelerator-only ----------------------------------------------------

@pytest.mark.device
def test_fused_engine_on_accelerator():
    """Real-hardware smoke: the fused pipeline scores on a non-CPU
    backend and the two engines agree on alert counts (bitwise float
    parity is only promised per backend, so compare decisions, not
    bits, across the host/device boundary)."""
    assert jax.default_backend() != "cpu"
    im_f = IngestManager(FlowDatabase(), n_shards=2, engine="fused")
    try:
        for b in _workload(range(3)):
            hh, conn, n = im_f.score_batch(b)
            assert n == len(conn) or n > len(conn)
        assert im_f.shard_liveness()["engine"]["steps"] >= 1
    finally:
        im_f.close()
