"""A view's exact re-group at read time (utils/native.py
`group_sum_exact` over native/groupsum.cc `gs_build_rows`): the same
groups and sums as the lexsort's, as row sets, with and without the
library; `ViewTable.scan()` / `select()` against a row-by-row
reference; a panel's ties independent of where the parts lay; the
counter that says which grouping ran."""

import numpy as np
import pytest

from benchmarks import prom
from theia_tpu.dashboards import queries
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.obs import prom as exposition
from theia_tpu.schema import FLOW_SCHEMA, ColumnarBatch
from theia_tpu.store import FlowDatabase
from theia_tpu.store.views import MATERIALIZED_VIEWS
from theia_tpu.utils import native
from theia_tpu.utils.native import (group_sum, group_sum_exact,
                                    group_sum_fast)

T0 = 1_700_000_000
VIEWS = sorted(MATERIALIZED_VIEWS)
LIBRARY = ["native", "numpy"]


@pytest.fixture
def how(request, monkeypatch):
    """Runs the test with the library (`hash`) or without (`sort`)."""
    if request.param == "numpy":
        monkeypatch.setattr(native, "_load_library", lambda: None)
        return "sort"
    if not native.native_available():
        pytest.skip("the native library did not build here")
    return "hash"


def _canonical(keys, sums):
    order = np.lexsort(keys.T[::-1])
    return keys[order].tolist(), sums[order].tolist()


def _reference(parts):
    """`group_sum` of the parts as one table: lexsorted already."""
    with np.errstate(over="ignore"):
        keys, sums = group_sum(np.concatenate([k for k, _ in parts]),
                               np.concatenate([v for _, v in parts]))
    return keys.tolist(), sums.tolist()


def _table(n, k, m, rng):
    """n rows over about n / 3 distinct keys: seconds, small codes,
    negative cells and cells beyond 2**31 and 2**40, a last column
    that alone tells some keys apart; sums that wrap int64."""
    pool = max(1, n // 3)
    distinct = rng.integers(0, 6, size=(pool, k)).astype(np.int64)
    distinct[:, 0] = T0 + rng.integers(0, 8, pool)
    distinct[:, 1] = rng.choice(
        np.array([-1, -2**40, 3_232_235_777, 2**31, 2**52 + 1]), pool)
    distinct[:, k - 1] = rng.integers(0, pool, pool)
    keys = distinct[rng.integers(0, pool, n)]
    values = rng.integers(-2**62, 2**62, size=(n, m)).astype(np.int64)
    return keys, values


@pytest.mark.parametrize("n", [0, 1, 200_000])
@pytest.mark.parametrize("k", [9, 15, 20])
@pytest.mark.parametrize("how", LIBRARY, indirect=True)
def test_group_sum_exact_is_group_sum_as_a_row_set(how, k, n):
    rng = np.random.default_rng([52, k, n])
    keys, values = _table(n, k, 8 if k > 15 else 6, rng)
    cuts = [0, n // 4, n // 4, n // 2, n]   # keys shared between parts,
    parts = [(keys[a:b].copy(), values[a:b].copy())     # one part empty
             for a, b in zip(cuts, cuts[1:])]
    gk, gv, said = group_sum_exact(parts)
    assert said == how
    assert gk.dtype == gv.dtype == np.int64
    assert gk.shape == (len(gk), k) and gv.shape == (len(gk), values.shape[1])
    assert _canonical(gk, gv) == _reference(parts)
    if n == 200_000:
        assert 0 < len(gk) < n // 2                    # groups collapsed
    # one part, and the same parts again: the order is deterministic
    one = group_sum_exact([(keys, values)])
    assert _canonical(*one[:2]) == _reference(parts)
    again = group_sum_exact(parts)
    assert np.array_equal(again[0], gk) and np.array_equal(again[1], gv)


def _last_column(rng):
    keys = np.tile(rng.integers(0, 2**40, 15), (6, 1)).astype(np.int64)
    keys[:, -1] = [0, 1, 0, 2, 1, 0]
    return [(keys[:3], np.arange(6).reshape(3, 2)),
            (keys[3:], np.arange(6, 12).reshape(3, 2))], 3


def _wide_cells(rng):
    cells = np.array([-1, -2**63, 2**63 - 1, 2**31, 2**31 - 1, 2**32,
                      -2**31, 0], np.int64)
    keys = cells[rng.integers(0, len(cells), size=(400, 9))]
    keys[200:] = keys[:200]                     # every key twice at least
    return [(keys[:150], np.ones((150, 1), np.int64)),
            (keys[150:], np.ones((250, 1), np.int64))], None


def _wrapping_sums(rng):
    keys = np.zeros((4, 20), np.int64)
    keys[2:, 7] = 1
    values = np.array([[2**63 - 1, 1], [1, -2**63], [2**62, 5],
                       [2**62, -7]], np.int64)
    return [(keys[:1], values[:1]), (keys[1:], values[1:])], 2


def _inexact_part(rng):
    """A `group_sum_fast` part in which a row-hash collision left one
    key on two rows, and the same key again in an exact part."""
    keys, values = _table(3000, 15, 6, rng)
    fk, fv = group_sum_fast(keys, values)
    split = (np.concatenate([fk, fk[:7]]),
             np.concatenate([fv, 3 * fv[:7]]))
    return [split, (keys[:500].copy(), values[:500].copy())], len(fk)


def _non_contiguous(rng):
    """What no native pass reads in place: a column-major value
    matrix (`values[:, [0, 2]]`), every second row of a key matrix."""
    keys, values = _table(2000, 9, 8, rng)
    return [(keys[::2], values[::2][:, [0, 2]]),
            (keys[1::2].copy(), values[1::2][:, [0, 2]])], None


def _stored_widths(rng):
    keys, values = _table(600, 9, 6, rng)
    keys[:, 1] = 7
    return [(keys.astype(np.int32), values)], None


EDGES = {"differs_in_its_last_column": (_last_column, None),
         "cells_negative_and_beyond_2**31": (_wide_cells, None),
         "sums_that_wrap": (_wrapping_sums, None),
         "a_fast_parts_split_key": (_inexact_part, None),
         "not_row_major": (_non_contiguous, "sort"),
         "not_int64": (_stored_widths, "sort")}


@pytest.mark.parametrize("edge", EDGES)
@pytest.mark.parametrize("how", LIBRARY, indirect=True)
def test_group_sum_exact_at_the_edges(how, edge):
    build, falls_back_to = EDGES[edge]
    parts, groups = build(np.random.default_rng([52, len(edge)]))
    gk, gv, said = group_sum_exact(parts)
    assert said == (falls_back_to or how)
    assert _canonical(gk, gv) == _reference(parts)
    if groups is not None:
        assert len(gk) == groups
    if edge == "sums_that_wrap":
        assert _canonical(gk, gv)[1] == [[-2**63, -2**63 + 1],
                                         [-2**63, -2]]


# -- a view ------------------------------------------------------------------

#: the second block is sent twice, the fourth reaches back over the
#: third; enough later blocks that two parts are under an eighth of a view
STARTS = (0, 4, 4, 8, 10) + tuple(range(16, 80, 4))


def _block(start, seed=3):
    return generate_flows(SynthConfig(
        n_series=24, points_per_series=4, start_time=T0 + start,
        service_fraction=0.3, external_fraction=0.2,
        protected_fraction=0.4, seed=seed))


def _row_by_row(db, spec, start=None, end=None):
    """The view by its definition: every inserted row, one at a time,
    summed under its key; sorted."""
    groups = {}
    for batch in db.flows._batches:
        cols = {c: np.asarray(batch[c], np.int64).tolist()
                for c in spec.key_columns + spec.sum_columns}
        for i in range(len(batch)):
            if start is not None and not (
                    start <= cols["flowEndSeconds"][i] < end):
                continue
            key = tuple(cols[c][i] for c in spec.key_columns)
            acc = groups.setdefault(key, [0] * len(spec.sum_columns))
            for j, c in enumerate(spec.sum_columns):
                acc[j] += cols[c][i]
    return sorted(key + tuple(acc) for key, acc in groups.items())


def _rows(batch, spec):
    return sorted(zip(*(np.asarray(batch[c], np.int64).tolist()
                        for c in spec.key_columns + spec.sum_columns)))


@pytest.mark.parametrize("name", VIEWS)
@pytest.mark.parametrize("how", LIBRARY, indirect=True)
def test_a_views_reads_are_its_rows_grouped_one_by_one(how, name):
    db = FlowDatabase()
    for start in STARTS:
        db.insert_flows(_block(start))
    view = db.views[name]
    assert all(exact == (how == "hash") for _, _, exact in view._parts)
    # a range of two parts of the 21: re-grouped where they lie
    got = view.select(T0 + 4, T0 + 8)
    seen = view.last_read()
    assert len(view._parts) == len(STARTS)
    assert (seen["read"], seen["how"]) == (2, how)
    assert seen["regrouped"] == seen["rows"] == 2 * len(view._parts[1][0])
    assert _rows(got, view.spec) == _row_by_row(db, view.spec,
                                                T0 + 4, T0 + 8)
    assert len(got) * 2 == seen["regrouped"]     # the block sent twice
    # the whole view: compacted, by the same grouping
    whole = view.scan()
    assert len(view._parts) == 1 and view._parts[0][2]
    assert _rows(whole, view.spec) == _row_by_row(db, view.spec)
    assert len(whole) == len(view)
    # one exact part: nothing left to re-group
    view.select(T0 + 4, T0 + 8)
    assert (view.last_read()["regrouped"], view.last_read()["how"]) \
        == (0, None)


# -- the panels ----------------------------------------------------------------

def _flow(src, dst, octets, second):
    return {"sourcePodName": src, "sourcePodNamespace": "ns",
            "destinationPodName": dst, "destinationPodNamespace": "ns",
            "octetDeltaCount": octets, "throughput": octets,
            "flowEndSeconds": T0 + second, "timeInserted": T0 + second}


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 2, 0)],
                         ids=["zeta_first", "alpha_first", "mid_first"])
@pytest.mark.parametrize("how", LIBRARY, indirect=True)
def test_sources_with_equal_totals_come_in_name_order(how, order):
    """`topSources` breaks a tie by name, so the answer does not depend
    on where the parts lay (nor on the codes the names were given)."""
    blocks = [[_flow("zeta", "b", 60, 1), _flow("zeta", "c", 40, 2)],
              [_flow("mid", "b", 100, 1), _flow("low", "b", 5, 3)],
              [_flow("alpha", "c", 100, 2)]]
    db = FlowDatabase()
    for i in order:
        db.insert_flows(ColumnarBatch.from_rows(blocks[i], FLOW_SCHEMA))
    data = queries.pod_to_pod(db, start=T0, end=T0 + 10)
    assert data["topSources"] == [
        {"name": "alpha", "value": 100}, {"name": "mid", "value": 100},
        {"name": "zeta", "value": 100}, {"name": "low", "value": 5}]
    assert db.views["flows_pod_view"].last_read()["how"] == how


VIEW_PANELS = {"pod_to_pod": "flows_pod_view",
               "pod_to_service": "flows_pod_view",
               "pod_to_external": "flows_pod_view",
               "node_to_node": "flows_node_view",
               "networkpolicy": "flows_policy_view"}


@pytest.mark.parametrize("span", [(4, 8), (0, 100)],
                         ids=["two_parts", "the_view_compacted"])
@pytest.mark.parametrize("panel", VIEW_PANELS)
@pytest.mark.parametrize("how", LIBRARY, indirect=True)
def test_the_regroup_counter_says_how_many_rows_and_how(how, panel, span):
    db = FlowDatabase()
    for start in STARTS:
        db.insert_flows(_block(start))
    table = VIEW_PANELS[panel]
    before = prom.parse(exposition.render())
    queries.panel_json(db, panel, {"start": str(T0 + span[0]),
                                   "end": str(T0 + span[1])})
    after = prom.parse(exposition.render())
    seen = db.views[table].last_read()

    def rise(way):
        series = ('theia_dashboard_regroup_rows_total{table="%s",how="%s"}'
                  % (table, way))
        return after.get(series, 0) - before.get(series, 0)

    other = {"hash": "sort", "sort": "hash"}[how]
    assert seen["how"] == how and seen["regrouped"] > 0
    assert (rise(how), rise(other)) == (seen["regrouped"], 0)
    if span == (0, 100):
        assert seen["regrouped"] == seen["rows"] >= len(STARTS) * 24
        # the next request finds the compacted part: nothing to count
        queries.panel_json(db, panel, {"start": str(T0), "end": str(T0 + 100)})
        assert db.views[table].last_read()["regrouped"] == 0
        final = prom.parse(exposition.render())
        assert all(final.get(s, 0) == after.get(s, 0) for s in final
                   if s.startswith("theia_dashboard_regroup_rows_total"))
