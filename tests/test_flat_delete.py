"""`Table.delete_older_than` walks the flat table's batches as they lie
(store/flow_store.py): held here to the arithmetic it replaced, written
out below (concatenate, mask, filter), and to what the walk promises
beside the rows: kept batches untouched, the time column read only
where a batch straddles the boundary, `scan()`'s swap refused after a
delete, appends during a delete, TTL eviction that copies a batch and
not the table."""

import threading

import numpy as np
import pytest

from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.obs import metrics
from theia_tpu.schema import ColumnarBatch, StringDictionary
from theia_tpu.schema.flow_schema import Column, ColumnKind
from theia_tpu.store import FlowDatabase
from theia_tpu.store.flow_store import TRIM_WALK, Table

TIMED = (Column("timeInserted", ColumnKind.DATETIME),
         Column("bucketStart", ColumnKind.DATETIME),
         Column("v", ColumnKind.U64),
         Column("name", ColumnKind.STRING))
#: a table without the time column: no cached (min, max) at all
UNTIMED = TIMED[1:]
T0 = 1_700_000_000


def _nbytes(batch):
    return sum(v.nbytes for v in batch.columns.values())


def _block(schema, times, tag):
    """One block with its own dictionary; `v` numbers the rows of the
    whole test (`tag` + position), so order shows."""
    n = len(times)
    d = StringDictionary()
    cols = {"timeInserted": np.asarray(times, np.int64),
            # another clock: the same seconds, mirrored and coarser
            "bucketStart": (T0 + 1000 - np.asarray(times, np.int64)) // 3,
            "v": tag + np.arange(n, dtype=np.int64),
            "name": d.encode([f"n{(tag + i) % 7}" for i in range(n)])}
    return ColumnarBatch({c.name: cols[c.name] for c in schema},
                         {"name": d})


def _times(layout, rng):
    """timeInserted of each block, as the layout lays them."""
    if layout == "empty":
        return []
    sizes = rng.integers(1, 40, rng.integers(2, 9))
    if layout == "ordered":         # a producer's blocks, no overlap
        out, t = [], T0
        for n in sizes:
            out.append(np.sort(t + rng.integers(0, 6, n)))
            t = int(out[-1].max()) + 1
        return out
    if layout == "overlapping":     # several producers' blocks in turn
        return [np.sort(T0 + 4 * i + rng.integers(0, 12, n))
                for i, n in enumerate(sizes)]
    if layout == "one_second":      # every row shares one second
        return [np.full(n, T0 + 5) for n in sizes]
    # out of order between and inside the blocks
    return [T0 + rng.integers(0, 50, n) for n in sizes]


def _table(schema, layout, seed, compact=False):
    rng = np.random.default_rng([20261004, seed])
    t = Table("t", schema)
    tag = 0
    for times in _times(layout, rng):
        t.insert(_block(schema, times, tag))
        tag += len(times)
    if compact:
        t.scan()
        assert len(t._batches) <= 1
    return t


def _boundaries(t, column):
    """Below everything, above everything, each batch's min, each
    batch's max + 1, and values inside several batches."""
    if not t._batches:
        return [T0]
    cols = [np.asarray(b[column]) for b in t._batches]
    every = np.concatenate(cols)
    out = {int(every.min()) - 1, int(every.min()), int(every.max()) + 1,
           int(every.max()), int(np.median(every)),
           int(np.quantile(every, 0.25)), int(np.quantile(every, 0.9))}
    for c in cols:
        out |= {int(c.min()), int(c.max()) + 1}
    return sorted(out)


def old_delete(batches, boundary, column):
    """What the table did until PR 49: every batch concatenated, one
    mask, the kept rows filtered out. -> (deleted, kept, bytes freed)"""
    data = ColumnarBatch.concat(batches)
    mask = np.asarray(data[column]) < boundary
    kept = data.filter(~mask)
    return int(mask.sum()), kept, _nbytes(data) - _nbytes(kept)


LAYOUTS = ["ordered", "overlapping", "out_of_order", "one_second",
           "compacted", "empty"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("column,schema", [
    ("timeInserted", TIMED), ("bucketStart", TIMED),
    ("bucketStart", UNTIMED)], ids=["time", "other", "untimed"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_walk_deletes_what_the_concatenation_deleted(
        layout, column, schema, seed):
    def build():
        if layout == "compacted":
            return _table(schema, "overlapping", seed, compact=True)
        return _table(schema, layout, seed)

    for boundary in _boundaries(build(), column):
        t = build()
        before = list(t._batches)
        rows = len(t)
        if before:
            want_n, want, want_freed = old_delete(before, boundary, column)
        else:
            want_n, want, want_freed = 0, None, 0
        gen, trimmed = t.generation, t.bytes_trimmed_total
        assert t.delete_older_than(boundary, column) == want_n
        assert t.bytes_trimmed_total - trimmed == want_freed
        assert t.generation == gen + (1 if want_n else 0)
        assert len(t) == rows - want_n
        walk = t.last_walk()
        assert set(walk) == set(TRIM_WALK)
        assert walk["batchesDropped"] + walk["batchesCut"] \
            + walk["batchesKept"] == len(before)
        assert len(t._batches) == walk["batchesCut"] + walk["batchesKept"]
        assert all(len(b) for b in t._batches)
        # the cached pairs are what a fresh pass over the batches gives
        meta = list(t._batch_meta)
        assert len(meta) == (len(t._batches)
                             if t._time_column is not None else 0)
        t._refresh_meta_locked()
        assert meta == t._batch_meta
        # the same rows in the same order, strings through the table's
        # dictionary
        got = t.scan()
        if want is None:
            assert len(got) == 0
            continue
        for c in schema:
            np.testing.assert_array_equal(got[c.name], want[c.name])
        np.testing.assert_array_equal(got.strings("name"),
                                      want.strings("name"))


class CountedColumns(dict):
    """A batch's columns whose reads of the time column are counted
    (a `filter` iterates the items and reads through no name)."""

    def __init__(self, *a):
        super().__init__(*a)
        self.reads = 0

    def __getitem__(self, name):
        if name == "timeInserted":
            self.reads += 1
        return super().__getitem__(name)


def test_kept_batches_are_the_same_objects_and_only_straddlers_are_read():
    """Four producers' blocks in turn, each producer's in time order:
    the boundary straddles one block a producer at most."""
    t = Table("t", TIMED)
    tag = 0
    for step in range(12):
        for producer in range(4):
            lo = T0 + 10 * step + 2 * producer
            t.insert(_block(TIMED, np.arange(lo, lo + 10), tag))
            tag += 10
    for b in t._batches:
        b.columns = CountedColumns(b.columns)
    before = list(t._batches)
    metas = list(t._batch_meta)
    boundary = T0 + 63
    assert t.delete_older_than(boundary) == sum(
        int((np.arange(mn, mx + 1) < boundary).sum()) for mn, mx in metas)
    straddle = [b for b, (mn, mx) in zip(before, metas)
                if mn < boundary <= mx]
    above = [b for b, (mn, mx) in zip(before, metas) if mn >= boundary]
    assert len(straddle) == 4 and len(above) == 22
    assert t.last_walk() == {
        "batchesDropped": 22, "batchesCut": 4, "batchesKept": 22,
        "bytesCopied": sum(_nbytes(b) for b in t._batches
                           if not any(b is o for o in before))}
    # the kept batches are the objects they were, in their order
    kept = [b for b in t._batches if any(b is o for o in before)]
    assert len(kept) == 22 and all(a is b for a, b in zip(kept, above))
    assert len(t._batches) == 26
    for b in before:
        assert b.columns.reads == (1 if any(b is s for s in straddle)
                                   else 0)
    # a second round at the same boundary reads nothing and changes
    # nothing: no generation bump, the list as it is
    gen, now = t.generation, list(t._batches)
    assert t.delete_older_than(boundary) == 0
    assert t.generation == gen
    assert all(a is b for a, b in zip(t._batches, now))
    assert t.last_walk() == {"batchesDropped": 0, "batchesCut": 0,
                             "batchesKept": 26, "bytesCopied": 0}
    assert [b.columns.reads for b in before] \
        == [1 if any(b is s for s in straddle) else 0 for b in before]


def test_a_scan_that_raced_a_delete_does_not_bring_the_rows_back(
        monkeypatch):
    """`scan()` snapshots the list, merges outside the lock and swaps
    its copy in if nothing changed meanwhile. A delete that cuts one
    old batch and drops none leaves the list's length and its last
    batch as they were; the generation says that it happened."""
    t = Table("t", TIMED)
    for i in range(3):
        t.insert(_block(TIMED, np.arange(T0 + 10 * i, T0 + 10 * i + 10),
                        10 * i))
    last = t._batches[-1]
    real = ColumnarBatch.concat
    raced = []

    def concat_after_a_delete(batches):
        if not raced:               # between the scan's two locks
            raced.append(t.delete_older_than(T0 + 5))
        return real(batches)

    monkeypatch.setattr(ColumnarBatch, "concat",
                        staticmethod(concat_after_a_delete))
    stale = t.scan()
    assert raced == [5] and len(stale) == 30   # its own snapshot
    # no whole batch went, the last one is the object it was
    assert len(t._batches) == 3 and t._batches[-1] is last
    assert len(t) == 25
    again = t.scan()
    assert len(again) == 25 and int(again["timeInserted"].min()) == T0 + 5
    np.testing.assert_array_equal(again["v"], np.arange(5, 30))
    assert t._batch_meta == [(T0 + 5, T0 + 29)]     # this one swapped


def test_appends_during_a_delete_are_all_present_afterwards():
    """A second thread appends while a delete walks a few hundred
    batches; no timing is asserted, only that nothing was lost either
    way."""
    t = Table("t", TIMED)
    for i in range(400):
        t.insert(_block(TIMED, np.full(8, T0 + i), 8 * i))
    boundary = T0 + 250
    started = threading.Event()

    def append():
        for i in range(200):
            # above the boundary: none of them may go
            t.insert(_block(TIMED, np.full(8, T0 + 1000 + i),
                            100_000 + 8 * i))
            started.set()

    th = threading.Thread(target=append)
    th.start()
    started.wait(30)
    deleted = t.delete_older_than(boundary)
    th.join(60)
    assert not th.is_alive()
    assert deleted == 250 * 8 == 400 * 8 + 200 * 8 - len(t)
    got = t.scan()
    assert int(got["timeInserted"].min()) == boundary
    np.testing.assert_array_equal(
        np.sort(got["v"][got["v"] >= 100_000]),
        100_000 + np.arange(200 * 8))
    np.testing.assert_array_equal(got["v"][got["v"] < 100_000],
                                  np.arange(250 * 8, 400 * 8))


def _copied():
    return metrics.REGISTRY.get(
        "theia_retention_bytes_copied_total")._default.value()


def test_ttl_at_steady_state_copies_a_batch_and_not_the_table():
    """Blocks in time order under a TTL, as ingest appends them (no
    read compacts the table between them): every insert's eviction
    drops the batch wholly behind the TTL and cuts the one that
    straddles it, whatever the table holds; by the bytes it says it
    copied, not by the clock."""
    db = FlowDatabase(ttl_seconds=35)
    one = generate_flows(SynthConfig(n_series=10, points_per_series=4,
                                     seed=3))
    block_bytes = _nbytes(db.flows._adopt(one))
    sent = np.zeros(0, np.int64)
    for i in range(30):
        cols = dict(one.columns)
        # ten seconds a block, four rows a second
        cols["timeInserted"] = (T0 + 10 * i + np.arange(40) // 4).astype(
            cols["timeInserted"].dtype)
        sent = np.concatenate([sent, cols["timeInserted"]])
        copied0 = _copied()
        db.insert_flows(ColumnarBatch(cols, one.dicts))
        walk = db.flows.last_walk()
        if i < 3:       # nothing is old enough: the fast path, no walk
            assert walk == dict.fromkeys(TRIM_WALK, 0)
        else:
            assert walk["batchesCut"] == 1 and walk["batchesDropped"] <= 1
            assert 0 < walk["bytesCopied"] < block_bytes
            assert len(db.flows._batches) == 4
        assert _copied() - copied0 == walk["bytesCopied"]
        sent = sent[sent >= T0 + 10 * i + 9 - 35]
        np.testing.assert_array_equal(
            np.concatenate([b["timeInserted"]
                            for b in db.flows._batches]), sent)
    assert len(db.flows) == 4 * 36
    # after a read compacted the table, that one batch is what
    # straddles: each eviction cuts what is left of it (less each
    # time) until it is gone, the new blocks kept as they lie
    assert len(db.flows.scan()) == 4 * 36 and len(db.flows._batches) == 1
    for i in (30, 31):
        cols = dict(one.columns)
        cols["timeInserted"] = (T0 + 10 * i + np.arange(40) // 4).astype(
            cols["timeInserted"].dtype)
        db.insert_flows(ColumnarBatch(cols, one.dicts))
    assert db.flows.last_walk() == {
        "batchesDropped": 0, "batchesCut": 1, "batchesKept": 2,
        "bytesCopied": block_bytes // 40 * (4 * 36 - 80)}
    assert len(db.flows) == 4 * 36
