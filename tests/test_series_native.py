"""Native C++ series builder: bit parity with the numpy tensorize.

The builder takes the key columns where they lie (int32 or int64, any
stride), the time and the value column and an optional row mask; the
numpy path builds its [n, k] matrix from the same arguments and is the
reference."""

from __future__ import annotations

import numpy as np
import pytest

from theia_tpu.analytics import TadQuerySpec, build_series
from theia_tpu.analytics import series as series_mod
from theia_tpu.analytics.series import SeriesRows, _group_and_pad
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.utils.native import build_padded_series, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library unavailable")

#: the six connection-key columns as the store holds them: five int32
#: codes and ports, one int64 time
STORED = (np.int32, np.int32, np.int32, np.int32, np.int32, np.int64)
WIDTHS = {"int32": (np.int32,) * 6, "int64": (np.int64,) * 6,
          "stored": STORED}


def _random_rows(rng, n, k=5, card=7, t_card=12, widths=None):
    widths = widths or (np.int64,) * k
    keys = [rng.integers(0, card, size=n).astype(w) for w in widths[:k]]
    t = rng.integers(100, 100 + t_card, size=n).astype(np.int64)
    v = rng.integers(1, 10**9, size=n).astype(np.int64)
    return keys, t, v


def _numpy(monkeypatch, parts, op, dtype=np.float64):
    # the path a process without the library takes
    monkeypatch.setattr(series_mod, "build_padded_series",
                        lambda parts, op, dtype: None)
    res, path, ways = _group_and_pad(parts, op, dtype)
    assert path == "numpy" and ways is None
    return res


def _assert_same(got, want):
    assert got is not None
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("k", [1, 3, 6])
@pytest.mark.parametrize("mask", ["none", "random", "all_false"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("op", ["max", "sum"])
def test_native_matches_numpy_bitwise(monkeypatch, op, widths, mask, k):
    rng = np.random.default_rng(3)
    n = 2000
    keys, t, v = _random_rows(rng, n, k=k, widths=WIDTHS[widths])
    m = {"none": None, "random": rng.random(n) < 0.6,
         "all_false": np.zeros(n, bool)}[mask]
    parts = [SeriesRows(keys, t, v, m)]

    native = build_padded_series(parts, op)
    ref = _numpy(monkeypatch, parts, op)
    _assert_same(native, ref)
    assert native[0].shape[1] == k
    if mask == "all_false":
        assert native[1].shape == (0, 0)


@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.float16])
def test_native_fills_the_asked_dtype(monkeypatch, dtype):
    rng = np.random.default_rng(5)
    keys, t, v = _random_rows(rng, 500, k=2)
    parts = [SeriesRows(keys, t, v % 1000, None)]   # float16 holds sums
    _assert_same(build_padded_series(parts, "sum", dtype),
                 _numpy(monkeypatch, parts, "sum", dtype))


def test_native_empty_input():
    out = build_padded_series(
        [SeriesRows([np.zeros(0, np.int32)] * 4, np.zeros(0, np.int64),
                    np.zeros(0, np.int64), None)], "max")
    key_mat, values, times, mask, ways = out
    assert ways == (0, 0, 0)
    assert key_mat.shape == (0, 4)
    assert values.shape == times.shape == mask.shape == (0, 0)
    assert mask.dtype == bool


def test_native_single_group_duplicate_times():
    keys = [np.zeros(6, np.int64)] * 2
    t = np.array([5, 5, 5, 7, 7, 6], np.int64)
    v = np.array([10, 30, 20, 1, 2, 9], np.int64)
    parts = [SeriesRows(keys, t, v, None)]
    key_mat, values, times, mask, ways = build_padded_series(parts, "max")
    assert key_mat.shape == (1, 2)
    np.testing.assert_array_equal(times[0], [5, 6, 7])
    np.testing.assert_array_equal(values[0], [30.0, 9.0, 2.0])
    assert mask.all()

    _, values, _, _, _ = build_padded_series(parts, "sum")
    np.testing.assert_array_equal(values[0], [60.0, 9.0, 3.0])


@pytest.mark.parametrize("op", ["max", "sum"])
@pytest.mark.parametrize("arrival", ["increasing", "ties", "unsorted",
                                     "mixed"])
def test_native_orders_and_merges_however_times_arrive(
        monkeypatch, op, arrival):
    """Rows in insertion order take the pass that writes each point as
    it is met (a repeated time reduces into the cell before it); a
    series whose times arrive out of order must still be sorted, next
    to series that are not."""
    rng = np.random.default_rng(11)
    n_series, points = 9, 40
    key = np.repeat(np.arange(n_series), points)
    t = np.tile(np.arange(points), n_series)
    if arrival in ("ties", "mixed"):
        t = t // 3                       # each time three times running
    rows = rng.permutation(key.size)     # series interleaved ...
    rows = rows[np.argsort(t[rows], kind="stable")]
    key, t = key[rows], t[rows]          # ... and in time order
    if arrival in ("unsorted", "mixed"):
        late = key % 3 == 0              # every third series reversed
        t = np.where(late, t.max() - t, t)
    if arrival == "unsorted":
        assert np.unique(np.stack([key, t]), axis=1).shape[1] == key.size
    v = rng.integers(1, 10**9, key.size)
    parts = [SeriesRows([key.astype(np.int32), (key * 5).astype(np.int64)],
                        t.astype(np.int64), v, None)]
    native = build_padded_series(parts, op)
    _assert_same(native, _numpy(monkeypatch, parts, op))
    assert (np.diff(native[2], axis=1)[native[3][:, 1:]] > 0).all()


def _out_of_order(rng, lo, seconds, rows):
    """`rows` times that meet each of `seconds` after `lo`, shuffled
    until some step goes back."""
    t = lo + np.resize(seconds, rows)
    while not (np.diff(t) < 0).any():
        t = rng.permutation(t)
    return t


def _cells_case(case, rng, time_dtype):
    """(parts, ways) of a table whose out-of-order series the builder
    must sum into cells; `ways` is (cursor, cells, sorted)."""
    wide = time_dtype == np.int64
    lo = {"negative_lo": -2**62 if wide else -2**31,
          "large_lo": 2**62 if wide else 2**31 - 61}.get(case, 1000)
    n_series, rows = 6, 80
    if case == "one_second":
        # a repeated second alone never steps back (the cursor's);
        # two seconds taking turns are the narrowest span in cells
        key = np.repeat([0, 1], 6)
        t = np.array([7] * 6 + [5, 4, 5, 4, 4, 5], np.int64)
        v = rng.integers(1, 10**9, key.size)
        return [SeriesRows([key], t.astype(time_dtype), v, None)], (1, 1, 0)
    if case == "two_parts":
        # each part in order; the second starts over, so both parts'
        # rows meet in one cell
        parts = []
        for side in range(2):
            key = np.repeat(np.arange(n_series), 20).astype(np.int32)
            t = np.tile(np.arange(20) * 2 + lo, n_series)
            parts.append(SeriesRows(
                [key], t.astype(time_dtype),
                rng.integers(1, 10**9, key.size), None))
        return parts, (0, n_series, 0)
    # 60 seconds of which a series meets 35: the other cells stay unseen
    seconds = np.sort(rng.choice(60, size=35, replace=False))
    seconds[[0, -1]] = 0, 59
    key = np.repeat(np.arange(n_series), rows)
    t = np.concatenate([_out_of_order(rng, lo, seconds, rows)
                        for _ in range(n_series)])
    mask = None
    if case == "masked_widener":
        # a row years off that the filters drop: counted, it would
        # widen every span past the rule and send the series to the sort
        t[::rows] = lo + (2**30 if wide else 2**20)
        mask = np.ones(key.size, bool)
        mask[::rows] = False
    order = rng.permutation(key.size)        # the series interleaved
    order = order[np.argsort(key[order] // 2, kind="stable")]
    key, t = key[order], t[order]
    if mask is not None:
        mask = mask[order]
    v = rng.integers(-10**9, 10**9, key.size)
    return ([SeriesRows([key.astype(np.int32)], t.astype(time_dtype), v,
                        mask)], (0, n_series, 0))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("time_dtype", [np.int32, np.int64])
@pytest.mark.parametrize("case", ["gaps", "negative_lo", "large_lo",
                                  "one_second", "two_parts",
                                  "masked_widener"])
@pytest.mark.parametrize("op", ["max", "sum"])
def test_native_sums_out_of_order_series_into_cells(
        monkeypatch, op, case, time_dtype, dtype):
    """A series whose times step back over dense seconds is reduced
    into time-indexed cells and read out in order: the numpy path's
    tensors bit for bit, wherever the span lies, whatever it leaves
    unseen, and the counts say that no series was sorted."""
    parts, ways = _cells_case(case, np.random.default_rng(23), time_dtype)
    native = build_padded_series(parts, op, dtype)
    _assert_same(native, _numpy(monkeypatch, parts, op, dtype))
    assert native[4] == ways
    assert (np.diff(native[2], axis=1)[native[3][:, 1:]] > 0).all()
    if case == "gaps":
        assert native[3].sum(axis=1).tolist() == [35] * 6
    if case == "masked_widener":
        assert build_padded_series(
            [p._replace(mask=None) for p in parts], op, dtype)[4] \
            == (0, 0, 6)


@pytest.mark.parametrize("span", ["at_the_rule", "past_the_rule",
                                  "far_apart", "whole_int64"])
@pytest.mark.parametrize("op", ["max", "sum"])
def test_native_sorts_a_series_whose_span_is_far_over_its_rows(
        monkeypatch, op, span):
    """The rule is read off each series: nine rows take cells up to a
    span of 16 seconds (9 B a cell against 16 B a row) and the sort
    beyond, beside series that take cells and series in order, all
    three as numpy gives them; no cell of the benchmark enters the
    sort, so the counts hold it here."""
    rng = np.random.default_rng(29)
    last = {"at_the_rule": 15, "past_the_rule": 16, "far_apart": 10**12,
            "whole_int64": 2**63 - 1}[span]
    first = -2**63 if span == "whole_int64" else 0
    loose = np.array([3, last, 1, first, 3, 2, last, 4, 2], np.int64)
    dense = np.concatenate([_out_of_order(rng, 50, np.arange(30), 40)
                            for _ in range(3)])
    in_order = np.tile(np.arange(25) // 2, 4)
    key = np.concatenate([np.full(loose.size, 3), np.repeat([0, 2, 5], 40),
                          np.repeat([1, 4, 6, 7], 25)])
    t = np.concatenate([loose, dense, in_order])
    # the series interleaved, each in its own order of arrival
    turn, order = rng.permutation(key), np.empty(key.size, np.int64)
    for g in range(8):
        order[turn == g] = np.flatnonzero(key == g)
    key, t = key[order], t[order]
    v = rng.integers(1, 10**9, key.size)
    parts = [SeriesRows([key.astype(np.int32)], t, v, None)]
    native = build_padded_series(parts, op)
    _assert_same(native, _numpy(monkeypatch, parts, op))
    sorts = span != "at_the_rule"
    assert native[4] == (4, 4 - sorts, int(sorts))
    assert native[4]._fields == ("cursor", "cells", "sorted")


def test_native_orders_keys_as_int64_values(monkeypatch):
    """Negative keys sort before positive ones and a value over 2^31
    after every int32: lexicographic order of int64 values, not of
    bytes or of the low word."""
    a = np.array([-1, 2**31 + 5, 3, -2**40, 2**40, 0, 3, -1], np.int64)
    b = np.array([7, -7, 2**33, 1, -1, 0, -2**33, 7], np.int64)
    t = np.arange(a.size, dtype=np.int64) % 2
    v = np.arange(1, a.size + 1, dtype=np.int64)
    parts = [SeriesRows([a, b], t, v, None)]
    native = build_padded_series(parts, "sum")
    _assert_same(native, _numpy(monkeypatch, parts, "sum"))
    np.testing.assert_array_equal(
        native[0][:, 0], [-2**40, -1, 0, 3, 3, 2**31 + 5, 2**40])
    np.testing.assert_array_equal(native[0][3:5, 1], [-2**33, 2**33])


def test_native_reads_strided_and_constant_columns(monkeypatch):
    """A column is read by its stride: a matrix's column where it
    lies, a reversed view, a `broadcast_to` scalar as a constant."""
    rng = np.random.default_rng(13)
    mat = rng.integers(0, 4, size=(300, 3)).astype(np.int64)
    wide = rng.integers(0, 4, size=600).astype(np.int32)
    keys = [mat[:, 0], mat[:, 2], wide[::-2],
            np.broadcast_to(np.int64(-9), 300)]
    t = rng.integers(0, 20, size=(300, 2))[:, 1]
    v = rng.integers(1, 10**6, size=300)
    parts = [SeriesRows(keys, t, v, rng.random(300) < 0.8)]
    native = build_padded_series(parts, "max")
    _assert_same(native, _numpy(monkeypatch, parts, "max"))
    assert (native[0][:, 3] == -9).all()


def test_native_groups_several_parts_as_one_table(monkeypatch):
    """Two parts with their own columns and masks (the pod mode's
    sides) are one table: a key met in both is one series."""
    rng = np.random.default_rng(17)
    parts = []
    for side in range(2):
        keys, t, v = _random_rows(rng, 400, k=2, card=3, t_card=50,
                                  widths=(np.int32, np.int64))
        parts.append(SeriesRows(keys, np.sort(t), v,
                                rng.random(400) < 0.7 if side else None))
    native = build_padded_series(parts, "sum")
    _assert_same(native, _numpy(monkeypatch, parts, "sum"))
    assert native[0].shape[0] <= 9


@pytest.mark.parametrize("fault", ["float_key", "float_value",
                                   "short_key", "short_mask", "matrix"])
def test_native_refuses_what_it_cannot_read(fault):
    """Another dtype, shape or length: None from the builder, so the
    seam takes the numpy path, whose answer (or whose own refusal of a
    ragged table) the caller gets."""
    rng = np.random.default_rng(19)
    keys, t, v = _random_rows(rng, 200, k=3, widths=STORED)
    mask = rng.random(200) < 0.5
    if fault == "float_key":
        keys[1] = keys[1].astype(np.float64)
    elif fault == "float_value":
        v = v.astype(np.float64)
    elif fault == "short_key":
        keys[2] = keys[2][:-1]
    elif fault == "short_mask":
        mask = mask[:-1]
    else:
        keys[0] = np.stack([keys[0], keys[0]], axis=1)
    parts = [SeriesRows(keys, t, v, mask)]
    assert build_padded_series(parts, "max") is None

    if fault.startswith("float"):
        res, path, _ = _group_and_pad(parts, "max", np.float64)
        assert path == "numpy"
        whole = [SeriesRows([np.asarray(c, np.int64) for c in keys], t,
                            np.asarray(v, np.int64), mask)]
        _assert_same(res, build_padded_series(whole, "max"))
    else:
        with pytest.raises((ValueError, IndexError)):
            _group_and_pad(parts, "max", np.float64)


SPECS = {
    "connection": TadQuerySpec(),
    "pod": TadQuerySpec(agg_flow="pod"),
    "pod_name": TadQuerySpec(agg_flow="pod", pod_name="pod-2-7"),
    "external": TadQuerySpec(agg_flow="external"),
    "svc": TadQuerySpec(agg_flow="svc"),
}


def _filtered(spec, batch, filters):
    import dataclasses
    t0 = int(np.asarray(batch["flowStartSeconds"]).min())
    ns = batch.dicts["sourcePodNamespace"].decode_one(
        int(np.asarray(batch["sourcePodNamespace"])[0]))
    cluster = batch.dicts["clusterUUID"].decode_one(
        int(np.asarray(batch["clusterUUID"])[0]))
    return dataclasses.replace(spec, **{
        "none": {},
        "ns_ignore": {"ns_ignore_list": (ns,)},
        "cluster": {"cluster_uuid": cluster},
        "no_cluster": {"cluster_uuid": "no-such-cluster"},
        "time_range": {"start_time": t0 + 2, "end_time": t0 + 40},
    }[filters])


@pytest.fixture(scope="module")
def flows():
    return generate_flows(SynthConfig(
        n_series=24, points_per_series=10, anomaly_fraction=0.2, seed=4))


@pytest.mark.parametrize("filters", ["none", "ns_ignore", "cluster",
                                     "no_cluster", "time_range"])
@pytest.mark.parametrize("mode", sorted(SPECS))
def test_build_series_identical_on_both_paths(monkeypatch, flows, mode,
                                              filters):
    """Every group-key mode, bare and under each filter the query can
    set: the same SeriesBatch from the columns in place and from the
    numpy path's matrix."""
    spec = _filtered(SPECS[mode], flows, filters)

    def series(native):
        answered = []

        def builder(parts, op, dtype):
            res = build_padded_series(parts, op, dtype) if native else None
            answered.append(res is not None)
            return res

        monkeypatch.setattr(series_mod, "build_padded_series", builder)
        out = build_series(flows, spec)
        assert all(a == native for a in answered)
        return out

    a = series(True)
    b = series(False)
    assert a.key_names == b.key_names and a.agg_type == b.agg_type
    assert a.values.dtype == b.values.dtype
    np.testing.assert_array_equal(a.values, b.values)
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.mask, b.mask)
    for name in a.key_names:
        np.testing.assert_array_equal(a.keys[name], b.keys[name])
    if filters == "no_cluster":
        assert a.n_series == 0
    elif filters == "none":
        assert a.n_series > 0


@pytest.mark.parametrize("filters", ["none", "ns_ignore"])
@pytest.mark.parametrize("mode", sorted(SPECS))
def test_build_series_builds_no_key_matrix_on_the_native_path(
        monkeypatch, flows, mode, filters):
    """The [n, k] int64 matrix cannot come back unnoticed: on the
    native path nothing in `analytics.series` stacks or concatenates,
    and no key column is widened or masked on the way in (the builder
    is handed the batch's own arrays)."""
    spec = _filtered(SPECS[mode], flows, filters)

    def refuse(*a, **kw):
        raise AssertionError("a matrix on the native path")

    class NoMatrices:
        def __getattr__(self, name):
            return refuse if name in ("stack", "concatenate", "vstack",
                                      "hstack", "column_stack") \
                else getattr(np, name)

    handed = []

    def spy(parts, op, dtype=np.float64):
        handed.extend(parts)
        return build_padded_series(parts, op, dtype)

    monkeypatch.setattr(series_mod, "build_padded_series", spy)
    want = build_series(flows, spec)
    monkeypatch.setattr(series_mod, "np", NoMatrices())
    got = build_series(flows, spec)
    np.testing.assert_array_equal(got.values, want.values)

    own = {id(a): name for name, a in flows.columns.items()}
    for part in handed:
        cols = [*part.key_cols, part.times, part.values]
        stored = [c for c in cols if c.strides != (0,)]
        assert len(stored) >= len(cols) - 1      # the pod direction
        assert all(id(c) in own for c in stored)
        assert all(c.dtype == flows.columns[own[id(c)]].dtype
                   for c in stored)
        assert part.mask is None or part.mask.dtype == bool
    assert len(handed) == 2 * (2 if spec.agg_flow == "pod" else 1)
    if filters == "none" and not spec.agg_flow:
        # nothing filtered out of the connection mode: no mask at all
        assert handed[0].mask is None
