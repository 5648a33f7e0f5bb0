"""FlowDatabase: inserts, views, TTL, retention, persistence, concat fix."""

import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.schema import FLOW_SCHEMA, ColumnarBatch
from theia_tpu.store import FlowDatabase
from theia_tpu.utils.native import (group_sum, group_sum_fast,
                                    native_available, native_group_sum)


def _db_with_flows(n_series=8, points=10, **kw):
    db = FlowDatabase()
    cfg = SynthConfig(n_series=n_series, points_per_series=points, **kw)
    batch = generate_flows(cfg)
    db.insert_flows(batch)
    return db, batch


def test_insert_and_scan_roundtrip():
    db, batch = _db_with_flows()
    scanned = db.flows.scan()
    assert len(scanned) == len(batch)
    # Store re-encodes against its own dictionaries; decoded strings match.
    np.testing.assert_array_equal(
        scanned.strings("sourcePodName"), batch.strings("sourcePodName"))
    np.testing.assert_array_equal(
        scanned["throughput"], batch["throughput"])


def test_time_window_select():
    db, batch = _db_with_flows(points=20)
    t0 = int(batch["flowEndSeconds"].min())
    sel = db.flows.select(end_time=t0 + 10, end_column="flowEndSeconds")
    assert len(sel) > 0
    assert sel["flowEndSeconds"].max() < t0 + 10


def test_concat_mixed_dictionaries_reencodes():
    # Two batches encoded with independent dictionaries must decode
    # correctly after concat (round-1 advisor finding).
    b1 = ColumnarBatch.from_rows(
        [{"sourcePodName": "alpha"}], FLOW_SCHEMA)
    b2 = ColumnarBatch.from_rows(
        [{"sourcePodName": "beta"}], FLOW_SCHEMA)
    merged = ColumnarBatch.concat([b1, b2])
    assert list(merged.strings("sourcePodName")) == ["alpha", "beta"]


def test_group_sum_matches_naive():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 4, size=(100, 3)).astype(np.int64)
    vals = rng.integers(0, 10, size=(100, 2)).astype(np.int64)
    gk, gv = group_sum(keys, vals)
    # naive dict-based check
    expect = {}
    for k, v in zip(map(tuple, keys), vals):
        expect[k] = expect.get(k, np.zeros(2, np.int64)) + v
    assert gk.shape[0] == len(expect)
    for k, v in zip(map(tuple, gk), gv):
        np.testing.assert_array_equal(expect[k], v)


@pytest.mark.parametrize("widths", [(np.int64,) * 3,
                                    (np.int32, np.int64, np.int32)],
                         ids=["int64", "stored"])
def test_a_views_block_group_bys_are_group_sum(widths):
    """What a view's insert takes, `native_group_sum` over the columns
    where the library loaded (it answers then, with whole groups) and
    `group_sum_fast` over their matrix, re-grouped as a read does:
    `group_sum` of the rows."""
    rng = np.random.default_rng(1)
    cols = [rng.integers(0, 4, size=500).astype(w) for w in widths]
    vals = [rng.integers(0, 10, size=500).astype(np.int64) for _ in "ab"]
    keys = np.stack([c.astype(np.int64) for c in cols], axis=1)
    want = group_sum(keys, np.stack(vals, axis=1))
    outs = [group_sum_fast(keys, np.stack(vals, axis=1))]
    if native_available():
        outs.append(native_group_sum(cols, vals))
        assert len(outs[-1][0]) == len(want[0])      # no group split
    for out in outs:
        for got, ref in zip(group_sum(*out), want):
            np.testing.assert_array_equal(got, ref)


def test_pod_view_aggregates_inserts():
    db, batch = _db_with_flows(n_series=4, points=5)
    view = db.views["flows_pod_view"].scan()
    # Sum of throughput over the view equals the sum over raw flows
    # (each (pod pair, flowEndSeconds) key sums its block rows).
    assert view["throughput"].sum() == batch["throughput"].sum()
    # Strings decode through the shared store dictionaries.
    pods = set(view.strings("sourcePodName"))
    assert pods <= set(batch.strings("sourcePodName"))


def test_view_collapses_duplicate_keys_across_blocks():
    db = FlowDatabase()
    cfg = SynthConfig(n_series=2, points_per_series=3, seed=1)
    batch = generate_flows(cfg)
    db.insert_flows(batch)
    db.insert_flows(batch)  # identical keys in a second block
    view = db.views["flows_node_view"]
    n_once = None
    db2 = FlowDatabase()
    db2.insert_flows(batch)
    n_once = len(db2.views["flows_node_view"])
    assert len(view) == n_once  # collapsed on merge, sums doubled
    assert (view.scan()["throughput"].sum()
            == 2 * db2.views["flows_node_view"].scan()["throughput"].sum())


def test_ttl_eviction():
    db = FlowDatabase(ttl_seconds=30)
    cfg = SynthConfig(n_series=2, points_per_series=60, interval_seconds=1)
    batch = generate_flows(cfg)
    db.insert_flows(batch)  # now = max(timeInserted)
    remaining = db.flows.scan()
    assert len(remaining) < len(batch)
    now = int(batch["timeInserted"].max())
    assert remaining["timeInserted"].min() >= now - 30
    # views trimmed to the same boundary
    v = db.views["flows_pod_view"].scan()
    assert v["timeInserted"].min() >= now - 30


def test_retention_monitor_trims_oldest_half():
    db, batch = _db_with_flows(n_series=4, points=50)
    mon = db.monitor(capacity_bytes=db.flows.nbytes,  # 100% full
                     threshold=0.5, delete_percentage=0.5, skip_rounds=3)
    n0 = len(db.flows)
    deleted = mon.tick()
    assert deleted > 0
    assert len(db.flows) <= n0 - deleted + 1
    # skip rounds honored
    assert mon.tick() == 0 and mon.tick() == 0 and mon.tick() == 0
    # after skip, another trim may fire if still over threshold
    assert mon._remaining_skip == 0


def test_empty_batch_insert_with_ttl_is_noop():
    db = FlowDatabase(ttl_seconds=3600)
    empty = ColumnarBatch.from_rows([], FLOW_SCHEMA, db.flows.dicts)
    assert db.insert_flows(empty) == 0
    assert len(db.flows) == 0


def test_save_load_roundtrip(tmp_path):
    db, batch = _db_with_flows(n_series=4, points=6)
    db.tadetector.insert_rows(
        [{"id": "x", "algoType": "EWMA", "throughput": 1.5,
          "anomaly": "true"}])
    path = str(tmp_path / "db.npz")
    db.save(path)
    db2 = FlowDatabase.load(path)
    assert len(db2.flows) == len(db.flows)
    np.testing.assert_array_equal(
        db2.flows.scan().strings("sourcePodName"),
        db.flows.scan().strings("sourcePodName"))
    rows = db2.tadetector.scan().to_rows()
    assert rows[0]["algoType"] == "EWMA" and rows[0]["anomaly"] == "true"


def test_view_regroups_lone_inexact_part():
    # A lone group_sum_fast part may contain split groups after a row
    # hash collision; scan() must still re-group exactly (views.py
    # promises read-time compaction even for a single part).
    from theia_tpu.store.views import ViewTable, ViewSpec
    vt = ViewTable("v", ViewSpec(("timeInserted", "k"), ("m",)), {})
    keys = np.array([[5, 1], [5, 1]], np.int64)
    values = np.array([[10], [32]], np.int64)
    vt._parts.append((keys, values, False))  # simulate collision split
    batch = vt.scan()
    assert len(batch) == 1
    assert int(np.asarray(batch["m"])[0]) == 42
    # Exact parts are returned as-is (no spurious re-group copies).
    gk, gv = vt._merged()
    assert len(vt._parts) == 1 and vt._parts[0][2] is True


def test_itemsets_rejects_negative_codes():
    from theia_tpu.analytics.itemsets import mine_frequent_patterns
    batch = ColumnarBatch(
        {"a": np.array([1, -1, 2], np.int64),
         "b": np.array([0, 1, 2], np.int64)}, {})
    with pytest.raises(ValueError, match="negative"):
        mine_frequent_patterns(batch, 1, columns=("a", "b"))
