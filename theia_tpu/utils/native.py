"""The native (C++) library's loader and the host's group-by.

One shared object holds every native routine (`native/flowblock.cc`,
the wire decoders `ingest/native.py` drives; `native/seriesbuild.cc`,
`build_padded_series`; `native/groupsum.cc`, `native_group_sum` and
the native pass of `group_sum_exact`), loaded via ctypes (no pybind11
in the image) and compiled on first use with g++ -O3 into `_build/`
beside this file. How the host groups rows is one decision and lives
here, below the store: the native routines and their numpy twins
`group_reduce` / `group_sum` / `group_sum_fast`, which are the only
path without the library and the tests' reference. A materialized
view is grouped by `native_group_sum` (or `group_sum_fast`) an insert
block and re-grouped exactly at read time by `group_sum_exact`: one
hash pass with a full-key comparison where the library is loaded, the
lexsort otherwise — the same groups, in no stated order.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..analysis.lockdep import named_lock
from .logging import get_logger

logger = get_logger("native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "flowblock.cc")
_SRC_SERIES = os.path.join(_REPO_ROOT, "native", "seriesbuild.cc")
_SRC_GROUPSUM = os.path.join(_REPO_ROOT, "native", "groupsum.cc")
_ALL_SRCS = (_SRC, _SRC_SERIES, _SRC_GROUPSUM)
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_build")


def _so_path() -> str:
    """Content-hashed artifact name: a stale .so can never be picked up
    (and dlopen caches by pathname, so rebuilding under the SAME name
    would return the already-loaded stale handle — the name must
    change with the sources)."""
    import hashlib
    h = hashlib.sha1()
    for src in _ALL_SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"flowblock-{h.hexdigest()[:12]}.so")


_lib_lock = named_lock("native.lib")
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _load_library() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native decoder; None on failure."""
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            os.makedirs(_BUILD_DIR, exist_ok=True)
            so = _so_path()
            if not os.path.exists(so):
                _compile(so)
            _lib = _bind(ctypes.CDLL(so))
            return _lib
        except (OSError, subprocess.CalledProcessError,
                AttributeError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _build_error = f"native ingest unavailable: {detail}"
    # The pure-Python decoder / numpy tensorizer take over from here;
    # say so once, loudly (outside the lock) — the same fact is on the
    # entry points' start-up line and /healthz ingest.native.
    logger.error("%s — using the pure-Python decoder and the numpy "
                 "series builder", _build_error)
    return None


def _compile(so: str) -> None:
    # Per-process scratch name, atomically published: a concurrent
    # builder racing on a shared tmp path could otherwise publish a
    # half-written .so under the content-hashed (never-rebuilt) name.
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
             "-o", tmp, *_ALL_SRCS],
            check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.fb_new.restype = ctypes.c_void_p
    lib.fb_new.argtypes = [ctypes.c_int32,
                           ctypes.POINTER(ctypes.c_int32)]
    lib.fb_seed.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                            ctypes.c_char_p, ctypes.c_int64]
    lib.fb_decode.restype = ctypes.c_int64
    lib.fb_decode.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32)]
    lib.fb_decode_block2.restype = ctypes.c_int64
    lib.fb_decode_block2.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_void_p)]
    lib.fb_dict_size.restype = ctypes.c_int64
    lib.fb_dict_size.argtypes = [ctypes.c_void_p,
                                 ctypes.c_int32]
    lib.fb_dict_get.restype = ctypes.c_void_p
    lib.fb_dict_get.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.fb_free.argtypes = [ctypes.c_void_p]
    lib.sb_new.restype = ctypes.c_void_p
    lib.sb_new.argtypes = [ctypes.c_int32, ctypes.c_int32]
    lib.sb_add.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_void_p, ctypes.c_int64]
    lib.sb_finish.argtypes = [ctypes.c_void_p,
                              ctypes.POINTER(ctypes.c_int64),
                              ctypes.POINTER(ctypes.c_int64),
                              ctypes.POINTER(ctypes.c_int64)]
    lib.sb_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p]
    lib.sb_free.argtypes = [ctypes.c_void_p]
    lib.gs_build.restype = ctypes.c_void_p
    lib.gs_build.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32]
    lib.gs_build_rows.restype = ctypes.c_void_p
    lib.gs_build_rows.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32]
    lib.gs_dims.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_int64)]
    lib.gs_fill.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_int64),
                            ctypes.POINTER(ctypes.c_int64)]
    lib.gs_free.argtypes = [ctypes.c_void_p]
    return lib


def native_available() -> bool:
    return _load_library() is not None


def native_status() -> str:
    """One word for logs and health docs: `loaded`, or `unavailable`
    with the build/load error (the Python paths are in use)."""
    if native_available():
        return "loaded"
    return f"unavailable ({(_build_error or '').strip()[-200:]})"


_INT_DTYPES = (np.dtype(np.int32), np.dtype(np.int64))


class SeriesWays(NamedTuple):
    """How many series the native builder wrote by each way
    (native/seriesbuild.cc's header has the rule): as their rows were
    met, from time-indexed cells, after a sort."""
    cursor: int
    cells: int
    sorted: int


def build_padded_series(parts, op: str, dtype=np.float64):
    """Native tensorize: group rows by their integer key columns into
    padded per-series time arrays (native/seriesbuild.cc).

    `parts`: a sequence of (key_cols, times, values, mask): rows given
    as 1-D int32/int64 columns of one length, read where they lie in
    their stored width and stride (a `np.broadcast_to` scalar is a
    constant column), and an optional bool row mask (None = every
    row). Several parts (the pod mode's two sides) are grouped as one
    table; all have the same number of key columns.

    Returns (key_mat [S,k] int64, values [S,T] dtype, times [S,T] int64,
    mask [S,T] bool, ways) with series in lexicographic key order and
    points in time order — bit-identical to the numpy group_reduce +
    _pack_and_pad path in analytics/series.py — and `ways`, the
    `SeriesWays` that say how the builder wrote them. Duplicate (key,
    time) rows reduce with `op` ("max" or "sum"). Returns None (the caller
    falls back to numpy) when the native library is unavailable or a
    column is of another dtype, shape or length.
    """
    lib = _load_library()
    if lib is None:
        return None
    taken = []
    for key_cols, times, values, mask in parts:
        cols = [np.asarray(c) for c in (*key_cols, times, values)]
        n = len(cols[-1])
        if n >= 2 ** 31 or any(
                c.ndim != 1 or len(c) != n or c.dtype not in _INT_DTYPES
                for c in cols):
            return None
        if mask is not None:
            mask = np.ascontiguousarray(mask, bool)
            if mask.shape != (n,):
                return None
        taken.append((cols, mask))
    k = len(taken[0][0]) - 2
    if any(len(cols) != k + 2 for cols, _ in taken):
        raise ValueError("parts differ in their number of key columns")

    # values are written in the asked dtype where the builder has it
    fill = np.dtype(dtype)
    if fill not in (np.dtype(np.float32), np.dtype(np.float64)):
        fill = np.dtype(np.float64)
    handle = lib.sb_new(k, 0 if op == "max" else 1)
    try:
        for cols, mask in taken:
            lib.sb_add(
                handle,
                (ctypes.c_void_p * (k + 2))(*[c.ctypes.data for c in cols]),
                (ctypes.c_int32 * (k + 2))(*[c.itemsize for c in cols]),
                (ctypes.c_int64 * (k + 2))(*[c.strides[0] for c in cols]),
                None if mask is None else mask.ctypes.data,
                len(cols[-1]))
        S = ctypes.c_int64()
        T = ctypes.c_int64()
        ways = (ctypes.c_int64 * 3)()
        lib.sb_finish(handle, ctypes.byref(S), ctypes.byref(T), ways)
        s, t = S.value, T.value
        key_mat = np.empty((s, k), np.int64)
        vals = np.empty((s, t), fill)
        ts = np.empty((s, t), np.int64)
        out_mask = np.empty((s, t), bool)
        lib.sb_fill(handle, key_mat.ctypes.data, vals.ctypes.data,
                    fill.itemsize, ts.ctypes.data, out_mask.ctypes.data)
    finally:
        lib.sb_free(handle)
    return (key_mat, vals.astype(dtype, copy=False), ts, out_mask,
            SeriesWays(*ways))


def native_group_sum(key_cols, value_cols):
    """Native GROUP BY...SUM over column arrays (native/groupsum.cc):
    one hash pass, no sort, no row-major staging in Python — the
    materialized-view insert hot path. Group order is arbitrary
    (SummingMergeTree parts are re-grouped exactly at read time, by
    `group_sum_exact`).

    key_cols / value_cols: sequences of 1-D int32/int64 arrays of equal
    length. Returns (keys [g,k] int64, sums [g,m] int64), or None when
    the native library is unavailable.
    """
    lib = _load_library()
    if lib is None:
        return None
    key_cols = [np.ascontiguousarray(a) for a in key_cols]
    value_cols = [np.ascontiguousarray(a) for a in value_cols]
    for a in (*key_cols, *value_cols):
        if a.dtype not in (np.dtype(np.int32), np.dtype(np.int64)):
            return None   # unexpected dtype → numpy fallback
    n = len(key_cols[0]) if key_cols else 0
    for a in (*key_cols, *value_cols):
        if len(a) != n:  # C reads n cells per column — no OOB reads
            raise ValueError(
                f"column length mismatch: {len(a)} != {n}")
    k, m = len(key_cols), len(value_cols)
    kp = (ctypes.c_void_p * k)(*[a.ctypes.data for a in key_cols])
    kw = (ctypes.c_int32 * k)(*[a.dtype.itemsize for a in key_cols])
    vp = (ctypes.c_void_p * max(m, 1))(
        *[a.ctypes.data for a in value_cols])
    vw = (ctypes.c_int32 * max(m, 1))(
        *[a.dtype.itemsize for a in value_cols])
    return _gs_result(lib, lib.gs_build(kp, kw, n, k, vp, vw, m), k, m)


def _gs_result(lib, handle, k: int, m: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The groups of a `gs_build*` handle as (keys [g,k], sums [g,m]);
    frees the handle."""
    try:
        g = ctypes.c_int64()
        lib.gs_dims(handle, ctypes.byref(g))
        keys = np.empty((g.value, k), np.int64)
        sums = np.empty((g.value, m), np.int64)
        lib.gs_fill(
            handle,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            sums.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    finally:
        lib.gs_free(handle)
    return keys, sums


def _row_major_int64(a: np.ndarray, rows: int, cols: int) -> bool:
    return (a.dtype == np.int64 and a.shape == (rows, cols)
            and a.flags["C_CONTIGUOUS"])


def group_sum_exact(parts: Sequence[Tuple[np.ndarray, np.ndarray]]
                    ) -> Tuple[np.ndarray, np.ndarray, str]:
    """Exact GROUP BY...SUM of view parts at read time: `parts` is a
    non-empty sequence of (keys [n_i,k], values [n_i,m]) grouped as
    one table (equal keys of different parts collapse, a
    `group_sum_fast` part's hash-split key is rejoined). Returns
    (keys [g,k], sums [g,m], how).

    `how` is `hash` where the native pass ran (native/groupsum.cc
    `gs_build_rows`: the library is loaded and every array is a
    C-contiguous int64 matrix, read where it lies: no concatenation,
    no copy a column; groups in order of first appearance) and `sort`
    where `group_sum` did (the lexsort; groups in lexicographic
    order). The groups and their int64 sums (numpy's wrap-around) are
    the same either way; their order is not part of the contract."""
    k, m = parts[0][0].shape[1], parts[0][1].shape[1]
    lib = _load_library()
    if (lib is None or sum(len(keys) for keys, _ in parts) >= 2 ** 31
            or not all(_row_major_int64(keys, len(keys), k)
                       and _row_major_int64(values, len(keys), m)
                       for keys, values in parts)):
        keys, sums = group_sum(
            np.concatenate([keys for keys, _ in parts], axis=0),
            np.concatenate([values for _, values in parts], axis=0))
        return keys, sums, "sort"
    count = len(parts)
    handle = lib.gs_build_rows(
        (ctypes.c_void_p * count)(*[keys.ctypes.data for keys, _ in parts]),
        (ctypes.c_void_p * count)(*[values.ctypes.data
                                    for _, values in parts]),
        (ctypes.c_int64 * count)(*[len(keys) for keys, _ in parts]),
        count, k, m)
    # `parts` outlives the handle, which reads them until gs_fill
    return (*_gs_result(lib, handle, k, m), "hash")


def group_reduce(keys: np.ndarray, values: np.ndarray, op: str = "sum"
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized GROUP BY: `keys` [n,k] int64, `values` [n,m].

    `op` is "sum" or "max". Returns (unique_keys [g,k], reduced [g,m])
    with groups in lexicographic order. This is the host-side analogue of
    the on-device segment reductions the analytics jobs use; lexsort +
    reduceat keeps it allocation-lean.
    """
    n = keys.shape[0]
    if n == 0:
        return keys, values
    order = np.lexsort(keys.T[::-1])
    sk = keys[order]
    sv = values[order]
    boundary = np.empty(n, dtype=bool)
    boundary[0] = True
    boundary[1:] = np.any(sk[1:] != sk[:-1], axis=1)
    starts = np.flatnonzero(boundary)
    ufunc = np.add if op == "sum" else np.maximum
    reduced = ufunc.reduceat(sv, starts, axis=0)
    return sk[starts], reduced


def group_sum(keys: np.ndarray, values: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
    return group_reduce(keys, values, "sum")


def group_sum_fast(keys: np.ndarray, values: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-insert-block GROUP BY for the MV hot path: sort by a single
    64-bit row hash instead of lexsorting 15-20 key columns (~20x less
    sort work). Output group ORDER is arbitrary, and a hash collision
    between distinct keys may split a group into two rows — both are
    fine for a SummingMergeTree part: a read re-groups exactly
    (`group_sum_exact`), which is also where ClickHouse collapses part
    rows. Do NOT use where callers rely on lexicographic group order
    (use group_reduce)."""
    n = keys.shape[0]
    if n == 0:
        return keys, values
    h = np.full(n, 0xcbf29ce484222325, np.uint64)
    for i in range(keys.shape[1]):
        x = keys[:, i].astype(np.uint64)
        x *= np.uint64(0xff51afd7ed558ccd)
        x ^= x >> np.uint64(33)
        h ^= x
        h *= np.uint64(0x100000001b3)
    order = np.argsort(h, kind="stable")
    sk = keys[order]
    sv = values[order]
    boundary = np.empty(n, dtype=bool)
    boundary[0] = True
    # Full-row compare: equal keys are adjacent (equal hash); colliding
    # distinct keys interleaved in a run just produce extra boundaries.
    boundary[1:] = np.any(sk[1:] != sk[:-1], axis=1)
    starts = np.flatnonzero(boundary)
    return sk[starts], np.add.reduceat(sv, starts, axis=0)
