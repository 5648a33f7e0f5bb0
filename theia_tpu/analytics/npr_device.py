"""On-device DISTINCT + support counting for the NPR job.

The reference's NPR compute is a Spark `SELECT DISTINCT` over the flow
9-tuple followed by RDD reduceByKey shuffles
(policy_recommendation_job.py:785-802,621-712). Here the same kernel is
expressed TPU-natively:

  * single chip — `lax.sort` over the key columns (XLA's lexicographic
    multi-operand sort), boundary detection, and segment scatter/add to
    produce the unique rows and their multiplicities ("support counts")
    in one jitted computation with static shapes;
  * multi chip — `shard_map` over a row-sharded mesh: each device
    dedupes its block locally, the padded local distincts ride one
    `all_gather` over ICI, and a second sort + segment-sum merges them
    into a replicated global distinct — the collective pattern that
    replaces the reference's executor shuffle (SURVEY §2.7).

Outputs are padded to the input length with a validity mask (static
shapes for XLA); hosts slice by `n_unique`. Dictionary codes are int32
(dictionaries are far smaller than 2^31; INT32_MAX is reserved as the
cross-shard padding sentinel).
"""

from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import ROWS_AXIS

_SENTINEL = np.iinfo(np.int32).max

# Host-side switch: "auto" uses the device path for large inputs only
# (the host numpy lexsort wins under ~64k rows once transfer overhead is
# counted), "1"/"0" force it on/off.
_AUTO_THRESHOLD = 65536


def _boundaries(sk: jnp.ndarray) -> jnp.ndarray:
    """is_new[i] = row i differs from row i-1 (sorted input)."""
    head = jnp.ones((1,), bool)
    return jnp.concatenate(
        [head, jnp.any(sk[1:] != sk[:-1], axis=1)]) if sk.shape[0] > 1 \
        else jnp.ones((sk.shape[0],), bool)


def _dedupe_sorted(sk: jnp.ndarray, weights: jnp.ndarray):
    """Segment-reduce a sorted key matrix: unique rows scattered to the
    front, weights summed per segment. Returns (uniq, counts, n_unique)
    padded to len(sk)."""
    n = sk.shape[0]
    is_new = _boundaries(sk)
    seg = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    n_unique = seg[-1] + 1
    counts = jnp.zeros((n,), weights.dtype).at[seg].add(weights)
    uniq = jnp.zeros_like(sk).at[seg].set(sk)
    return uniq, counts, n_unique


@jax.jit
def distinct_rows(keys: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """DISTINCT over [N, K] int32 rows with multiplicities.

    Returns (uniq [N, K], counts [N] int32, n_unique []): the first
    n_unique rows of `uniq` are the distinct key rows in lexicographic
    order; `counts[i]` is how many input rows equal `uniq[i]`.
    """
    n, k = keys.shape
    ops = tuple(keys[:, i] for i in range(k))
    sorted_cols = jax.lax.sort(ops, num_keys=k)
    sk = jnp.stack(sorted_cols, axis=1)
    # int32 counts: a single padded block never exceeds 2^31 rows
    # (hosts widen to int64); avoids the x64-disabled truncation
    # warning on TPU.
    return _dedupe_sorted(sk, jnp.ones((n,), jnp.int32))


def _sharded_distinct_step(keys: jnp.ndarray):
    """Per-shard body: local dedupe → all_gather → global dedupe.

    keys: the local [N_loc, K] block. Output is replicated (identical
    on every shard): (uniq [N, K], counts [N], n_unique) with
    N = N_loc * n_shards (the shard count is implicit in the
    all_gather output shape).
    """
    n_loc, k = keys.shape
    uniq, counts, n_unique = distinct_rows(keys)
    valid = jnp.arange(n_loc) < n_unique
    # Pad invalid slots with the sentinel so they sort to the end and
    # carry zero weight through the merge.
    uniq = jnp.where(valid[:, None], uniq, _SENTINEL)
    counts = jnp.where(valid, counts, 0)

    uniq_all = jax.lax.all_gather(uniq, ROWS_AXIS)       # [S, N_loc, K]
    counts_all = jax.lax.all_gather(counts, ROWS_AXIS)   # [S, N_loc]
    flat_keys = uniq_all.reshape(-1, k)
    flat_counts = counts_all.reshape(-1)

    ops = tuple(flat_keys[:, i] for i in range(k)) + (flat_counts,)
    sorted_ = jax.lax.sort(ops, num_keys=k)
    sk = jnp.stack(sorted_[:k], axis=1)
    merged, total, n_uniq = _dedupe_sorted(sk, sorted_[k])
    # Drop the sentinel segment (present iff any shard had padding):
    # padding rows are _SENTINEL in EVERY column, so a genuine row can
    # only be misidentified if all K of its codes equal INT32_MAX —
    # excluded by the module precondition (codes < INT32_MAX).
    has_pad = jnp.all(merged[jnp.maximum(n_uniq - 1, 0)] == _SENTINEL)
    n_uniq = jnp.where(has_pad, n_uniq - 1, n_uniq)
    return merged, total, n_uniq


def make_sharded_distinct(mesh: jax.sharding.Mesh):
    """Jitted multi-chip DISTINCT over a mesh with a `rows` axis.

    fn(keys [N, K]) with N divisible by the axis size; returns
    replicated (uniq, counts, n_unique) padded to N.

    Preconditions: key codes < INT32_MAX (the padding sentinel), and
    no single distinct key's GLOBAL multiplicity reaches 2^31 (counts
    merge in int32 because x64 is disabled on TPU; callers needing
    exact counts beyond that must sum per-shard results host-side).
    """
    from jax.sharding import PartitionSpec as P

    mapped = jax.shard_map(
        _sharded_distinct_step, mesh=mesh,
        in_specs=(P(ROWS_AXIS, None),),
        out_specs=(P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)


def device_distinct(keys: np.ndarray,
                    use_device: str | bool | None = None,
                    mesh: jax.sharding.Mesh | None = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: DISTINCT + counts for an [N, K] int code matrix.

    Returns (uniq [U, K] int64, counts [U] int64) in lexicographic row
    order — bit-identical to the numpy group_reduce path. `use_device`
    defaults to the THEIA_NPR_DEVICE env switch ("auto"/"1"/"0").
    With `mesh` (a rows-axis mesh with >1 device), the device path
    shards input rows over the mesh and merges per-chip distincts with
    the all_gather + segment-sum collective (production scale-out of
    the Spark shuffle, SURVEY §2.7).
    """
    n = keys.shape[0]
    if n == 0:
        return (keys.astype(np.int64),
                np.zeros((0,), np.int64))
    if use_device is None:
        use_device = os.environ.get("THEIA_NPR_DEVICE", "auto")
    if use_device in ("0", False, "off", "false"):
        on_device = False
    elif use_device in ("1", True, "on", "true"):
        on_device = True
    else:
        on_device = n >= _AUTO_THRESHOLD
    if not on_device:
        from ..store.views import group_reduce

        uniq, counts = group_reduce(
            keys.astype(np.int64),
            np.ones((n, 1), np.int64))
        return uniq, counts[:, 0]

    if keys.max(initial=0) >= _SENTINEL:
        raise ValueError("dictionary code collides with the sentinel")
    if mesh is not None and mesh.size > 1 and n >= mesh.size:
        from ..parallel import cached_kernel
        from ..parallel.mesh import pad_to_multiple

        # Pad rows to the shard multiple with the sentinel; padding
        # rows sort to the end of the merge and the step drops the
        # trailing all-sentinel segment.
        padded, _ = pad_to_multiple(keys.astype(np.int32), mesh.size,
                                    axis=0, fill=_SENTINEL)
        fn = cached_kernel(("npr_distinct", mesh),
                           lambda: make_sharded_distinct(mesh))
        uniq, counts, n_unique = fn(padded)
    else:
        uniq, counts, n_unique = distinct_rows(keys.astype(np.int32))
    u = int(n_unique)
    return (np.asarray(uniq[:u]).astype(np.int64),
            np.asarray(counts[:u]).astype(np.int64))
