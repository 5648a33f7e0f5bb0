"""On-device DISTINCT + support counting for the NPR job.

The reference's NPR compute is a Spark `SELECT DISTINCT` over the flow
9-tuple followed by RDD reduceByKey shuffles
(policy_recommendation_job.py:785-802,621-712). Here the same kernel is
expressed TPU-natively, and so that a store which grew by a block runs
the program it ran before:

  * packed keys — the host knows every column's largest code, so it
    packs the K columns, most significant first, into W unsigned
    32-bit words whose lexicographic order is the columns' (`KeyLayout`:
    a function of the column maxima alone, never part of a compiled
    program; the job's nine columns need 73 bits, W = 3). The packer
    takes the K columns as they lie in the store's batch, int32 each,
    and a mask of the rows that count: `_PACK_ROWS` rows at a time
    through one 64-bit accumulator, each finished word written under
    the mask, so no column is copied whole and no [N, K] matrix is
    ever built;
  * bucketed rows — N is padded up to `bucket_rows(N)` with all-ones
    rows that sort last and weigh nothing, so a program is compiled
    once a bucket and once a number of words, not once a store size;
  * single chip — `distinct_rows`: one `lax.sort` of the W words as W
    key operands (XLA's lexicographic multi-operand sort, unstable:
    equal rows are alike), boundary detection, and the segment starts
    sorted to the front to produce the unique rows and their
    multiplicities ("support counts") in one jitted computation with
    static shapes. XLA's TPU sort pays its compile time by key and by
    operand, hardly by row (v5e host, 3.1 M rows: one key of one
    operand 4 s, these three keys 35-39 s, the nine stable keys of the
    form this replaces 260-296 s; a radix sort of one-operand passes
    compiles in 7.5 s and runs 477 ms where this runs 74, and its
    passes grow with the rows' position bits: PERF.md §6, PR 42);
  * multi chip — `shard_map` over a row-sharded mesh: each device
    dedupes its block locally, the padded local distincts ride one
    `all_gather` over ICI, and a second sort + segment-sum merges them
    into a replicated global distinct — the collective pattern that
    replaces the reference's executor shuffle (SURVEY §2.7).

Outputs are padded to the bucket (static shapes for XLA); the host
fetches a power-of-two prefix that holds `n_unique` rows and unpacks
it. Dictionary codes are below 2^31.
"""

from __future__ import annotations

from typing import Callable, Iterator, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import ROWS_AXIS
from ..utils.native import group_reduce

_U32 = jnp.uint32
_WORD = 32
_ALL_ONES = 0xFFFFFFFF

# The device path is for large inputs only: the host numpy lexsort
# wins under ~64k rows once transfer overhead is counted.
_AUTO_THRESHOLD = 65536

# Rows the packer handles at a time: its accumulator and the shifted
# column stay in the host's cache (1 MiB together) while the columns
# stream through once.
_PACK_ROWS = 1 << 16

#: K key columns: K arrays of N codes each
Keys = Sequence[np.ndarray]


def _blocks(n: int) -> Iterator[slice]:
    return (slice(lo, lo + _PACK_ROWS) for lo in range(0, n, _PACK_ROWS))


def bucket_rows(n: int) -> int:
    """The row count a program is compiled for: n rounded up to the
    next of four steps an octave (5, 6, 7, 8 x 2^e), so the padding is
    under a quarter of n (0.8 % at the documented store's 3,119,904
    unprotected rows: 3,145,728) where powers of two would sort up to
    twice the rows. A store growing from 65,536 to 172.8 M rows (12 h
    at 4,000 records/s) compiles 47 programs a number of key words,
    each once: the persistent compile cache keeps them."""
    step = 1 << max((n - 1).bit_length() - 3, 0)
    return -(-n // step) * step


class KeyLayout(NamedTuple):
    """How K columns of codes lie in W 32-bit words: `widths[c]` bits
    for column c (its largest code's bit length), most significant
    column first, the whole right-aligned in the W words."""
    widths: Tuple[int, ...]

    @classmethod
    def of(cls, columns: Keys, mask: np.ndarray | None = None
           ) -> "KeyLayout":
        """The layout of the rows of `columns` under `mask` (all of
        them without one): rows outside it neither widen a column nor
        fail the range check."""
        smallest, largest = 0, [0] * len(columns)
        for rows in _blocks(len(columns[0])):
            for c, column in enumerate(columns):
                # a row outside the mask counts as code 0
                part = column[rows] if mask is None \
                    else column[rows] * mask[rows]
                smallest = min(smallest, int(part.min()))
                largest[c] = max(largest[c], int(part.max()))
        if smallest < 0 or max(largest) >= 1 << 31:
            raise ValueError("dictionary codes lie in [0, 2^31)")
        return cls(tuple(m.bit_length() for m in largest))

    @property
    def bits(self) -> int:
        return sum(self.widths)

    @property
    def words(self) -> int:
        return max(-(-self.bits // _WORD), 1)

    def pack(self, columns: Keys, n_rows: int,
             mask: np.ndarray | None = None) -> np.ndarray:
        """[W, n_rows] uint32: the rows of `columns` under `mask` (all
        of them without one) packed in their order, then all-ones
        padding. `_PACK_ROWS` rows at a time, the columns go in from
        the least significant end through a 64-bit accumulator that
        is emptied a word at a time, so a column may cross a word;
        what a row outside the mask leaves in the accumulator is never
        written."""
        total = len(columns[0])
        n = total if mask is None else int(np.count_nonzero(mask))
        out = np.empty((self.words, n_rows), np.uint32)
        out[:, n:] = _ALL_ONES
        if not self.bits:
            out[:, :n] = 0              # no column reaches the one word
        acc = np.empty(min(total, _PACK_ROWS), np.int64)
        shifted = np.empty_like(acc)

        def put(word, a, keep, at):
            low = a.astype(np.uint32)           # the low 32 bits
            out[word, at] = low if keep is None else low[keep]

        done = 0
        for rows in _blocks(total):
            keep = None if mask is None else mask[rows]
            size = len(columns[0][rows])
            a, s = acc[:size], shifted[:size]
            a[:] = 0
            kept = size if keep is None else int(np.count_nonzero(keep))
            at = slice(done, done + kept)
            fill, word = 0, self.words - 1
            for c in reversed(range(len(self.widths))):
                if not self.widths[c]:
                    continue
                np.left_shift(columns[c][rows], fill, out=s, dtype=np.int64)
                a |= s
                fill += self.widths[c]
                if fill >= _WORD:
                    put(word, a, keep, at)
                    a >>= _WORD
                    fill -= _WORD
                    word -= 1
            if fill:
                put(word, a, keep, at)
            done += kept
        return out

    def unpack(self, words: np.ndarray) -> np.ndarray:
        """[U, K] int64 codes of packed rows [U, W]."""
        u = words.shape[0]
        out = np.zeros((u, len(self.widths)), np.int64)
        wide = words.astype(np.int64)
        lo = 0
        for c in reversed(range(len(self.widths))):
            b = self.widths[c]
            if not b:
                continue
            word, off = self.words - 1 - lo // _WORD, lo % _WORD
            v = wide[:, word] >> off
            if off + b > _WORD:
                v |= wide[:, word - 1] << (_WORD - off)
            out[:, c] = v & ((1 << b) - 1)
            lo += b
        return out


def _distinct(words_t: jnp.ndarray, n_valid=None, weights=None):
    """Sort, segment and compact `words_t` [W, N]: (uniq_t [W, N],
    counts [N] int32, n_unique []), the distinct rows in order at the
    front with their weights summed. A row weighs `weights[i]`, or
    without them 1 for the first `n_valid` rows and 0 for the rest
    (all-ones padding). A last segment that weighs nothing is padding
    and is not counted."""
    w, n = words_t.shape
    with jax.named_scope("sort"):
        # equal rows are alike in every word: ties may fall either way
        # (a stable sort carries an iota through every exchange and
        # takes twice as long to compile)
        operands = tuple(words_t) + (() if weights is None else (weights,))
        ordered = jax.lax.sort(operands, num_keys=w, is_stable=False)
        rows_t = jnp.stack(ordered[:w])
    with jax.named_scope("segments"):
        is_new = jnp.concatenate([
            jnp.ones((1,), bool),
            jnp.any(rows_t[:, 1:] != rows_t[:, :-1], axis=0)])
        n_unique = jnp.sum(is_new.astype(jnp.int32))
        if weights is None:
            # padding sorts last, so the rows before a sorted position
            # that count are those below n_valid
            total = jnp.int32(n_valid)

            def before(position):
                return jnp.minimum(position, total)
        else:
            running = jnp.cumsum(ordered[w])
            total = running[-1]

            def before(position):
                return jnp.take(running - ordered[w], position)
    with jax.named_scope("compact"):
        # a segment's first position, the segments' in order at the
        # front: sorted as one operand of unique keys, where a scatter
        # of N single elements is serial address work on a TPU (v5e,
        # 3.1 M rows: 74 ms a call against 162)
        position = jax.lax.iota(_U32, n)
        starts = jax.lax.sort(
            jnp.where(is_new, position, position + _U32(n)),
            is_stable=False)
        held = jax.lax.iota(jnp.int32, n) < n_unique
        starts = jnp.where(held, starts, _U32(0)).astype(jnp.int32)
        uniq_t = jnp.where(held[None, :],
                           jnp.take(rows_t, starts, axis=1), _U32(0))
        upto = jnp.where(held, before(starts), total)
        counts = jnp.where(
            held, jnp.concatenate([upto[1:], total[None]]) - upto, 0)
        last = jnp.maximum(n_unique - 1, 0)
        n_unique = jnp.where(counts[last] == 0, last, n_unique)
    return uniq_t, counts, n_unique


@jax.jit
def distinct_rows(keys: jnp.ndarray, n_valid=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """DISTINCT over [N, W] rows of 32-bit words with multiplicities.

    Returns (uniq [N, W], counts [N] int32, n_unique []): the first
    n_unique rows of `uniq` are the distinct rows in lexicographic
    order (the words compared as unsigned); `counts[i]` is how many of
    the first `n_valid` input rows equal `uniq[i]`. Rows from `n_valid`
    on are padding, all-ones (they sort last, and a segment of nothing
    but padding is not counted). `n_valid` is traced: it compiles no
    program. Default: every row.
    """
    n = keys.shape[0]
    # int32 counts: a single padded block never exceeds 2^31 rows
    # (hosts widen to int64); avoids the x64-disabled truncation
    # warning on TPU.
    uniq_t, counts, n_unique = _distinct(
        keys.astype(_U32).T, n if n_valid is None else n_valid)
    return uniq_t.T.astype(keys.dtype), counts, n_unique


def _sharded_distinct_step(keys: jnp.ndarray, n_valid):
    """Per-shard body: local dedupe → all_gather → global dedupe.

    keys: the local [N_loc, W] block of the padded rows, of which the
    first `n_valid` overall are genuine. Output is replicated
    (identical on every shard): (uniq [N, W], counts [N], n_unique)
    with N = N_loc * n_shards (the shard count is implicit in the
    all_gather output shape).
    """
    n_loc = keys.shape[0]
    mine = jnp.clip(n_valid - jax.lax.axis_index(ROWS_AXIS) * n_loc,
                    0, n_loc)
    uniq, counts, n_unique = distinct_rows(keys, mine)
    valid = jnp.arange(n_loc) < n_unique
    # Slots past the local distincts become padding: all-ones rows
    # that sort to the end and carry zero weight through the merge.
    uniq = jnp.where(valid[:, None], uniq.astype(_U32), _U32(_ALL_ONES))
    counts = jnp.where(valid, counts, 0)

    uniq_all = jax.lax.all_gather(uniq, ROWS_AXIS)       # [S, N_loc, W]
    counts_all = jax.lax.all_gather(counts, ROWS_AXIS)   # [S, N_loc]
    merged_t, total, n_uniq = _distinct(
        uniq_all.reshape(-1, keys.shape[1]).T,
        weights=counts_all.reshape(-1))
    return merged_t.T.astype(keys.dtype), total, n_uniq


def make_sharded_distinct(mesh: jax.sharding.Mesh):
    """Jitted multi-chip DISTINCT over a mesh with a `rows` axis.

    fn(keys [N, W], n_valid=None) with N divisible by the axis size and
    `distinct_rows`' arguments; returns replicated (uniq, counts,
    n_unique) padded to N.

    Precondition: no single distinct key's GLOBAL multiplicity reaches
    2^31 (counts merge in int32 because x64 is disabled on TPU; callers
    needing exact counts beyond that must sum per-shard results
    host-side).
    """
    from jax.sharding import PartitionSpec as P

    mapped = jax.shard_map(
        _sharded_distinct_step, mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)

    @jax.jit
    def sharded_distinct(keys, n_valid=None):
        return mapped(keys, jnp.int32(
            keys.shape[0] if n_valid is None else n_valid))

    return sharded_distinct


def _wants_device(n: int, use_device: bool | None) -> bool:
    return n >= _AUTO_THRESHOLD if use_device is None else use_device


def plan_distinct(columns: Keys,
                  use_device: bool | None = None,
                  mesh: jax.sharding.Mesh | None = None,
                  mask: np.ndarray | None = None
                  ) -> Callable[[], Tuple[np.ndarray, np.ndarray]]:
    """The host's half of `device_distinct`, done now: choose the
    path by the rows under `mask` and, for the device, lay the key
    columns out and pack them into a bucket's padded words. Returns
    the other half: call it for (uniq, counts) — the transfer, the
    jitted call until ready, the fetch of the distinct rows and their
    unpacking."""
    n = len(columns[0])
    if mask is not None:
        kept = int(np.count_nonzero(mask))
        if kept == n:
            mask = None
        n = kept
    if n == 0:
        return lambda: (np.zeros((0, len(columns)), np.int64),
                        np.zeros((0,), np.int64))
    if not _wants_device(n, use_device):
        def on_host():
            rows = np.stack([np.asarray(c if mask is None else c[mask],
                                        np.int64) for c in columns], axis=1)
            uniq, counts = group_reduce(rows, np.ones((n, 1), np.int64))
            return uniq, counts[:, 0]
        return on_host

    layout = KeyLayout.of(columns, mask)
    shards = mesh.size if mesh is not None and mesh.size > 1 \
        and n >= mesh.size else 1
    words = layout.pack(
        columns, bucket_rows(-(-n // shards)) * shards, mask).T
    if shards > 1:
        from ..parallel import cached_kernel

        fn = cached_kernel(("npr_distinct", mesh),
                           lambda: make_sharded_distinct(mesh))
    else:
        fn = distinct_rows

    def on_device():
        uniq, counts, n_unique = fn(words, np.int32(n))
        u = int(n_unique)
        # a prefix that holds them, of few lengths: a slice is a
        # program too
        head = min(max(1 << (u - 1).bit_length(), 1024), words.shape[0])
        return (layout.unpack(np.asarray(uniq[:head])[:u]),
                np.asarray(counts[:head])[:u].astype(np.int64))
    return on_device


def device_distinct(columns: Keys,
                    use_device: bool | None = None,
                    mesh: jax.sharding.Mesh | None = None,
                    mask: np.ndarray | None = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: DISTINCT + counts over K columns of int codes, of
    the rows under `mask` if one is given.

    Returns (uniq [U, K] int64, counts [U] int64) in lexicographic row
    order — bit-identical to the numpy group_reduce path. `use_device`
    None chooses by the row count (`_AUTO_THRESHOLD`), True / False
    force a side. With `mesh` (a rows-axis mesh with >1 device), the
    device path shards input rows over the mesh and merges per-chip
    distincts with the all_gather + segment-sum collective (production
    scale-out of the Spark shuffle, SURVEY §2.7).
    """
    return plan_distinct(columns, use_device, mesh, mask)()
