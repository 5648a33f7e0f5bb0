"""On-device NPR DISTINCT kernel: single-chip and sharded parity."""

from __future__ import annotations

import numpy as np
import pytest

from theia_tpu.analytics.npr_device import (
    device_distinct,
    distinct_rows,
    make_sharded_distinct,
)
from theia_tpu.parallel import make_rows_mesh
from theia_tpu.utils.native import group_reduce


def _random_keys(rng, n, k=9, card=17):
    return rng.integers(0, card, size=(n, k)).astype(np.int64)


def _columns(keys):
    """The K columns of an [N, K] matrix, as the program takes them."""
    return list(keys.T)


def _numpy_distinct(keys):
    uniq, counts = group_reduce(keys, np.ones((len(keys), 1), np.int64))
    return uniq, counts[:, 0]


def test_distinct_rows_matches_numpy():
    rng = np.random.default_rng(5)
    keys = _random_keys(rng, 513)   # odd size, guaranteed duplicates
    uniq, counts, n_unique = distinct_rows(keys.astype(np.int32))
    u = int(n_unique)
    ref_u, ref_c = _numpy_distinct(keys)
    assert u == len(ref_u)
    np.testing.assert_array_equal(np.asarray(uniq[:u]), ref_u)
    np.testing.assert_array_equal(np.asarray(counts[:u]), ref_c)
    assert int(np.asarray(counts[:u]).sum()) == len(keys)


def test_distinct_rows_all_unique_and_all_same():
    keys = np.arange(32, dtype=np.int32).reshape(32, 1)
    uniq, counts, n = distinct_rows(keys)
    assert int(n) == 32
    assert (np.asarray(counts[:32]) == 1).all()

    same = np.full((16, 3), 7, np.int32)
    uniq, counts, n = distinct_rows(same)
    assert int(n) == 1
    assert int(counts[0]) == 16
    np.testing.assert_array_equal(np.asarray(uniq[0]), [7, 7, 7])


def test_device_distinct_wrapper_parity_both_paths():
    rng = np.random.default_rng(6)
    keys = _random_keys(rng, 1000, k=4, card=9)
    ref_u, ref_c = _numpy_distinct(keys)
    for use_device in (False, True):
        u, c = device_distinct(_columns(keys), use_device=use_device)
        np.testing.assert_array_equal(u, ref_u)
        np.testing.assert_array_equal(c, ref_c)


def test_device_distinct_empty():
    u, c = device_distinct(_columns(np.zeros((0, 9), np.int64)),
                           use_device=True)
    assert u.shape == (0, 9) and c.shape == (0,)


def test_sharded_distinct_matches_single_device():
    import jax

    n_dev = len(jax.devices())
    assert n_dev >= 8, "conftest must provide the 8-device CPU mesh"
    mesh = make_rows_mesh(8)
    rng = np.random.default_rng(7)
    keys = _random_keys(rng, 8 * 64, k=5, card=13).astype(np.int32)

    fn = make_sharded_distinct(mesh)
    uniq, counts, n_unique = fn(keys)
    u = int(n_unique)
    ref_u, ref_c = _numpy_distinct(keys.astype(np.int64))
    assert u == len(ref_u)
    np.testing.assert_array_equal(np.asarray(uniq)[:u], ref_u)
    np.testing.assert_array_equal(np.asarray(counts)[:u], ref_c)


def test_sharded_distinct_with_empty_shards():
    """Shards whose local block is pure duplicates still merge right."""
    import jax

    mesh = make_rows_mesh(8)
    # every shard sees the same single row → global distinct of 1
    keys = np.full((8 * 16, 3), 42, np.int32)
    fn = make_sharded_distinct(mesh)
    uniq, counts, n_unique = fn(keys)
    assert int(n_unique) == 1
    assert int(np.asarray(counts)[0]) == 8 * 16
    np.testing.assert_array_equal(np.asarray(uniq)[0], [42, 42, 42])


def test_npr_job_unchanged_with_device_distinct(monkeypatch):
    """run_npr output is identical whichever distinct path executes."""
    from theia_tpu.analytics import run_npr
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.store import FlowDatabase

    from theia_tpu.analytics import npr_device

    def policies(threshold):
        monkeypatch.setattr(npr_device, "_AUTO_THRESHOLD", threshold)
        db = FlowDatabase()
        db.insert_flows(generate_flows(SynthConfig(
            n_series=16, points_per_series=4, seed=9)))
        run_npr(db, recommendation_id="e" * 32)
        rows = db.recommendations.scan()
        return sorted(zip(rows.strings("kind"),
                          rows.strings("policy")))

    # every row count reaches the device, then none does
    assert policies(0) == policies(1 << 62)


# -- packed keys, bucketed rows (PR 42) -----------------------------------

def _codes(rng, n, maxima):
    """[n, K] codes, column c uniform in [0, maxima[c]] with the
    maximum present, and duplicates of whole rows."""
    keys = np.stack([rng.integers(0, m + 1, size=n) for m in maxima],
                    axis=1).astype(np.int64)
    keys[n // 2:] = keys[:n - n // 2]          # every row twice or so
    keys[0] = maxima                           # every column's maximum
    return keys[rng.permutation(n)]


#: column maxima by the words their bits need, the job's nine among
#: them (73 bits); in "crosses" the second column lies across words
LAYOUTS = {
    "1-word": (15, 1023, 6, 3),
    "2-words": (65535, 65535, 1023, 255),
    "crosses": (1023, (1 << 31) - 1, 7),
    "3-words-the-jobs": (15, 1023, 1245, 16, 1024, 256, 5209, 6, 3),
    "4-words": ((1 << 31) - 1,) * 4,
    "a-column-of-zeros": (0, 5, 0, 9),
    "whole-words-of-ones": (65535, 65535),
}


@pytest.mark.parametrize("maxima", LAYOUTS.values(), ids=LAYOUTS.keys())
@pytest.mark.parametrize("n", [1, 7, 640, 641, 3000])
def test_packed_distinct_is_group_reduce_bit_for_bit(maxima, n):
    """640 is a bucket's edge (5 x 128), 641 one over it; every size
    is under `_AUTO_THRESHOLD` and forced onto the device."""
    from theia_tpu.analytics import npr_device

    assert n < npr_device._AUTO_THRESHOLD
    rng = np.random.default_rng(n)
    keys = _codes(rng, n, maxima)
    layout = npr_device.KeyLayout.of(_columns(keys))
    assert layout.bits == sum(int(m).bit_length() for m in maxima)
    assert layout.words == max(-(-layout.bits // 32), 1)
    u, c = device_distinct(_columns(keys), use_device=True)
    ref_u, ref_c = _numpy_distinct(keys)
    assert u.dtype == ref_u.dtype == c.dtype == np.int64
    np.testing.assert_array_equal(u, ref_u)
    np.testing.assert_array_equal(c, ref_c)


@pytest.mark.parametrize("keys", [
    np.full((700, 3), 9, np.int64),
    np.arange(2100, dtype=np.int64).reshape(700, 3),
    # rows that pack to all-ones words, as the padding does
    np.full((700, 2), 65535, np.int64),
], ids=["all-equal", "all-distinct", "all-ones"])
def test_packed_distinct_of_degenerate_tables(keys):
    u, c = device_distinct(_columns(keys), use_device=True)
    ref_u, ref_c = _numpy_distinct(keys)
    np.testing.assert_array_equal(u, ref_u)
    np.testing.assert_array_equal(c, ref_c)


def test_a_layout_packs_in_the_columns_order_and_back():
    from theia_tpu.analytics.npr_device import KeyLayout

    rng = np.random.default_rng(3)
    for maxima in LAYOUTS.values():
        keys = _codes(rng, 500, maxima)
        layout = KeyLayout.of(_columns(keys))
        words = layout.pack(_columns(keys), 512)
        assert words.shape == (layout.words, 512)
        assert words.dtype == np.uint32
        assert (words[:, 500:] == 0xFFFFFFFF).all()
        np.testing.assert_array_equal(layout.unpack(words[:, :500].T), keys)
        # the words' lexicographic order is the columns'
        by_words = np.lexsort(words[::-1, :500])
        by_columns = np.lexsort(keys.T[::-1])
        np.testing.assert_array_equal(keys[by_words], keys[by_columns])
    with pytest.raises(ValueError, match="codes"):
        KeyLayout.of(_columns(np.array([[1, -1]])))
    with pytest.raises(ValueError, match="codes"):
        KeyLayout.of(_columns(np.array([[1, 1 << 31]])))


def test_the_bucket_rule():
    from theia_tpu.analytics.npr_device import bucket_rows

    assert bucket_rows(3_119_904) == 3_145_728      # the cell's rows
    assert [bucket_rows(n) for n in (1, 2, 5, 8, 9, 640, 641)] \
        == [1, 2, 5, 8, 10, 640, 768]
    grown, n = set(), 65_536
    while n <= 172_800_000:
        b = bucket_rows(n)
        assert n <= b < 1.25 * n + 1
        grown.add(b)
        n = b + 1
    assert len(grown) == 47 and max(grown) == 201_326_592


def test_two_store_sizes_of_one_bucket_run_one_program(monkeypatch):
    """What the job pays for at a store size it has not seen: nothing,
    while the bucket and the number of key words stay. And the
    program sorts the packed words, three operands and not the nine
    columns."""
    import jax
    from theia_tpu.analytics import npr_device

    rng = np.random.default_rng(8)
    maxima = LAYOUTS["3-words-the-jobs"]
    traced = []
    jitted = npr_device.distinct_rows

    def counting(words, n_valid):
        traced.append((words.shape, jitted._cache_size()))
        return jitted(words, n_valid)

    monkeypatch.setattr(npr_device, "distinct_rows", counting)
    for n in (5200, 5500, 6144):                 # one bucket: 6,144
        keys = _codes(rng, n, maxima)
        u, c = device_distinct(_columns(keys), use_device=True)
        np.testing.assert_array_equal(u, _numpy_distinct(keys)[0])
    # another layout of three words, in the same bucket
    device_distinct(_columns(_codes(
        rng, 5999, LAYOUTS["crosses"] + (3, 1 << 20))), use_device=True)
    device_distinct(_columns(_codes(rng, 6145, maxima)), use_device=True)
    shapes = [s for s, _ in traced]
    assert shapes == [(6144, 3)] * 4 + [(7168, 3)]
    sizes = [k for _, k in traced] + [jitted._cache_size()]
    # the first call compiled one program, the next three none, the
    # next bucket one more
    assert [b - a for a, b in zip(sizes, sizes[1:])] == [1, 0, 0, 0, 1]

    text = jitted.lower(
        jax.ShapeDtypeStruct((6144, 3), np.uint32),
        jax.ShapeDtypeStruct((), np.int32)).as_text()
    sorts = [ln.split("stablehlo.sort", 1)[1].split(")", 1)[0].count("%")
             for ln in text.splitlines() if "stablehlo.sort" in ln]
    assert sorts == [3, 1]            # the rows' words, compact's starts
    assert "is_stable = false" in text and "is_stable = true" not in text


# -- the packer takes columns and a mask (PR 45) --------------------------

def _matrix_layout(keys):
    """`KeyLayout.of` as it stood before PR 45, over an [n, K] int64
    matrix of the rows that count: the packer's plain reference."""
    if not len(keys):
        return (0,) * keys.shape[1]
    largest = keys.max(axis=0)
    if keys.min() < 0 or largest.max() >= 1 << 31:
        raise ValueError("dictionary codes lie in [0, 2^31)")
    return tuple(int(m).bit_length() for m in largest)


def _matrix_pack(widths, keys, n_rows):
    """`KeyLayout.pack` as it stood before PR 45."""
    n_words = max(-(-sum(widths) // 32), 1)
    n = keys.shape[0]
    out = np.empty((n_words, n_rows), np.uint32)
    out[:, n:] = 0xFFFFFFFF
    acc = np.zeros(n, np.int64)
    fill, word = 0, n_words - 1
    for c in reversed(range(len(widths))):
        if not widths[c]:
            continue
        acc |= keys[:, c].astype(np.int64) << fill
        fill += widths[c]
        if fill >= 32:
            out[word, :n] = acc & 0xFFFFFFFF
            acc >>= 32
            fill -= 32
            word -= 1
    if fill:
        out[word, :n] = acc
        word -= 1
    out[:word + 1, :n] = 0
    return out


def _masked_columns(maxima, dtype, mask_kind, n=500):
    """K columns of `dtype` and a mask (None: every row counts). A row
    outside the mask holds what no row inside may: a negative code, or
    one wider than its column."""
    rng = np.random.default_rng(len(maxima) + n)
    keys = _codes(rng, n, maxima)
    mask = {"all": None, "all-true": np.ones(n, bool),
            "partial": rng.random(n) < 0.7,
            "none": np.zeros(n, bool)}[mask_kind]
    if mask is not None and not mask.all():
        keys[~mask] = np.where(rng.random((n, 1)) < 0.5, -7,
                               np.iinfo(dtype).max)[~mask]
    if mask_kind == "partial":
        keys[np.flatnonzero(mask)[0]] = maxima     # the widths' witness
    return [keys[:, c].astype(dtype) for c in range(len(maxima))], mask


PACKED = {
    "31-bits-in-one-word": ((1 << 31) - 1,),
    "73-bits-in-three-words": LAYOUTS["3-words-the-jobs"],
    "a-column-across-words": LAYOUTS["crosses"],
    "a-column-of-width-0": LAYOUTS["a-column-of-zeros"],
    "no-bits-at-all": (0, 0),
}


@pytest.mark.parametrize("block", [97, None], ids=["blocks-of-97", "one-block"])
@pytest.mark.parametrize("mask_kind", ["all", "all-true", "partial", "none"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=["int32", "int64"])
@pytest.mark.parametrize("maxima", PACKED.values(), ids=PACKED.keys())
def test_the_column_packer_is_the_matrix_packer_bit_for_bit(
        maxima, dtype, mask_kind, block, monkeypatch):
    from theia_tpu.analytics import npr_device
    from theia_tpu.analytics.npr_device import KeyLayout

    if block:
        monkeypatch.setattr(npr_device, "_PACK_ROWS", block)
    columns, mask = _masked_columns(maxima, dtype, mask_kind)
    counted = np.stack(
        [np.asarray(c if mask is None else c[mask], np.int64)
         for c in columns], axis=1)
    widths = _matrix_layout(counted)
    if mask_kind != "none":
        assert widths == tuple(int(m).bit_length() for m in maxima)
    layout = KeyLayout.of(columns, mask)
    assert layout.widths == widths
    for n_rows in (len(counted), 512):
        want = _matrix_pack(widths, counted, n_rows)
        got = layout.pack(columns, n_rows, mask)
        assert got.dtype == np.uint32 and got.flags.c_contiguous
        np.testing.assert_array_equal(got, want)
    # a matrix's strided columns are columns like any others
    assert KeyLayout.of(_columns(counted)) == layout
    np.testing.assert_array_equal(
        layout.pack(_columns(counted), 512), want)
    np.testing.assert_array_equal(
        layout.unpack(want[:, :len(counted)].T), counted)


@pytest.mark.parametrize("dtype, code", [
    (np.int32, -1), (np.int64, -1), (np.int64, 1 << 31),
    (np.int64, 1 << 40)], ids=["int32-negative", "int64-negative",
                               "2^31", "2^40"])
def test_a_code_out_of_range_under_the_mask_is_a_value_error(dtype, code):
    from theia_tpu.analytics.npr_device import KeyLayout

    columns = [np.arange(300, dtype=dtype), np.full(300, 5, dtype)]
    mask = np.arange(300) % 3 > 0
    columns[1][200] = code                       # 200 % 3 == 2: counted
    for m in (mask, None):
        with pytest.raises(ValueError, match="codes"):
            KeyLayout.of(columns, m)
        with pytest.raises(ValueError, match="codes"):
            device_distinct(columns, use_device=True, mask=m)
    columns[1][[200, 201]] = 5, code             # 201 % 3 == 0: not
    assert KeyLayout.of(columns, mask).widths == (9, 3)
    with pytest.raises(ValueError, match="codes"):
        KeyLayout.of(columns)


@pytest.mark.parametrize("use_device", [True, False, "8 shards"],
                         ids=["device", "host", "sharded"])
@pytest.mark.parametrize("mask_kind", ["all", "all-true", "partial", "none"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=["int32", "int64"])
def test_distinct_of_columns_under_a_mask_is_group_reduce_of_the_rows(
        dtype, mask_kind, use_device):
    mesh = None
    if use_device == "8 shards":
        use_device, mesh = True, make_rows_mesh(8)
    columns, mask = _masked_columns(
        LAYOUTS["3-words-the-jobs"], dtype, mask_kind, n=3000)
    counted = np.stack(
        [np.asarray(c if mask is None else c[mask], np.int64)
         for c in columns], axis=1)
    u, c = device_distinct(columns, use_device=use_device, mesh=mesh,
                           mask=mask)
    ref_u, ref_c = _numpy_distinct(counted)
    assert u.dtype == c.dtype == np.int64
    assert u.shape == ref_u.shape == (len(ref_u), 9)
    np.testing.assert_array_equal(u, ref_u)
    np.testing.assert_array_equal(c, ref_c)
    assert int(c.sum()) == len(counted)
