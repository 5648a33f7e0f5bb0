"""DBSCAN outlier scoring: one sort a series, then neighbours in order.

Reference semantics (plugins/anomaly-detection/anomaly_detection.py:325-349):
sklearn DBSCAN(min_samples=4, eps=2.5e8) over the 1-D throughput values of
one connection; points labeled -1 (noise) are anomalies. The algoCalc
column is a 0.0 placeholder (:312-322).

TPU-first design: general DBSCAN's cluster expansion is data-dependent
control flow, but *noise detection* — all the job needs — is closed-form:

    core_i   = |{j : |x_i − x_j| ≤ eps}| ≥ min_samples   (self included)
    noise_i  = ¬core_i ∧ ¬∃j (core_j ∧ |x_i − x_j| ≤ eps)

i.e. a point is noise iff it is neither a core point nor within eps of
one. That is exactly sklearn's label==-1 set. The values are
one-dimensional, so nothing of size [T, T] is needed to decide it: in
sorted order the points within eps of x_i are a contiguous run, and a
core point within eps of x_i, if there is one, is the nearest core
value below it or the nearest above (`dbscan_noise`). The definition
over all pairs stays, in numpy, as `noise_by_pairs` of
tests/dbscan_reference.py, which the tests hold this form to bit for
bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .masked import masked_stddev_samp

DEFAULT_EPS = 2.5e8
DEFAULT_MIN_SAMPLES = 4


def _ordered(bits: jnp.ndarray) -> jnp.ndarray:
    """Between a float's bits, as the signed integer of its width, and
    the integer whose order is the float's: the magnitude bits of a
    negative number flip, so the function is its own inverse. Sorting
    these integers is sorting the floats, NaN last, without the
    comparator that jax builds for float keys (on a v5e, PR 40: twice
    the sort's compile time and a third of its run time)."""
    info = jnp.iinfo(bits.dtype)
    return bits ^ ((bits >> (info.bits - 1)) & info.max)


@functools.partial(jax.jit, static_argnames=("eps", "min_samples"))
def dbscan_noise(x: jnp.ndarray, mask: jnp.ndarray,
                 eps: float = DEFAULT_EPS,
                 min_samples: int = DEFAULT_MIN_SAMPLES) -> jnp.ndarray:
    """Noise (= anomaly) flags for a padded [..., T] series batch, in
    O(T log T) a series and in the dtype it is given.

    Every decision is the rounded difference of two values of the
    series compared with eps, as the definition's |x_i - x_j| <= eps
    is, and rounding is monotone: between two sorted points within eps
    of x_i every point is within eps of x_i. So the flags are the
    pairwise definition's bit for bit in every precision. A point that
    is not there (masked, or beyond the row's ends) is NaN, and a
    difference with NaN is within nothing."""
    k = int(min_samples)
    dtype = jnp.result_type(x, float)
    bits = jnp.dtype(f"int{8 * dtype.itemsize}")
    nan = jnp.array(jnp.nan, dtype)
    inf = jnp.array(jnp.inf, dtype)
    axis = x.ndim - 1
    t = x.shape[axis]
    with jax.named_scope("sort"):
        key = jnp.where(
            mask, _ordered(jax.lax.bitcast_convert_type(
                x.astype(dtype), bits)),
            jnp.iinfo(bits).max)                  # a NaN, and the last
        pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
        # ties may fall either way: equal values are decided alike
        key, pos = jax.lax.sort((key, pos), dimension=axis, num_keys=1,
                                is_stable=False)
        xs = jax.lax.bitcast_convert_type(_ordered(key), dtype)
    with jax.named_scope("counts"):
        # k points within eps of xs[i], itself included, iff some window
        # of k consecutive sorted points that holds i has both its ends
        # within eps of it: below[d] is "xs[i-d] is", above[d] "xs[i+d]".
        edge = jnp.full(x.shape[:-1] + (k - 1,), nan, dtype)
        row = jnp.concatenate([edge, xs, edge], axis=axis)

        def shifted(d):
            return jax.lax.slice_in_dim(row, k - 1 + d, k - 1 + d + t,
                                        axis=axis)

        below = [xs - shifted(-d) <= eps for d in range(k)]
        above = [shifted(d) - xs <= eps for d in range(k)]
        core = functools.reduce(
            jnp.logical_or, (below[a] & above[k - 1 - a] for a in range(k)))
    with jax.named_scope("reach"):
        nearest_below = jax.lax.cummax(jnp.where(core, xs, -inf), axis=axis)
        nearest_above = jax.lax.cummin(jnp.where(core, xs, inf), axis=axis,
                                       reverse=True)
        reachable = ((xs - nearest_below <= eps)
                     | (nearest_above - xs <= eps))
    with jax.named_scope("unsort"):
        # the positions of a row are 0 .. T-1 once each: sorted with the
        # flag as their lowest bit they are the series' order again
        noise = ~core & ~reachable
        back = jax.lax.sort(2 * pos + noise.astype(jnp.int32),
                            dimension=axis, is_stable=False)
    return (back & 1).astype(bool) & mask


def pair_tests(mask) -> int:
    """Pairs the definition tests over a padded [S, T] batch, once
    for the neighbour counts: the sum over series of (valid points)^2.
    It says what the job's answer is worth in pair tests, not what the
    program does: `dbscan_noise` sorts and tests none of them
    (`sorted_points`)."""
    n = np.count_nonzero(np.asarray(mask), axis=-1).astype(np.int64)
    return int(np.sum(n * n))


def sorted_points(mask) -> int:
    """Valid points of a padded [S, T] batch: what `dbscan_scores`
    sends to `dbscan_noise`, which sorts them."""
    return int(np.count_nonzero(np.asarray(mask)))


def dbscan_scores(x: jnp.ndarray, mask: jnp.ndarray,
                  eps: float = DEFAULT_EPS,
                  min_samples: int = DEFAULT_MIN_SAMPLES):
    """(algoCalc placeholder zeros, stddev, anomaly) for DBSCAN, by
    `dbscan_noise` on every backend and length.

    stddev is still emitted to fill the tadetector row shape (the
    reference computes it in the groupby regardless of algorithm).
    """
    anomaly = dbscan_noise(x, mask, eps=eps, min_samples=min_samples)
    calc = jnp.zeros_like(x)
    with jax.named_scope("stddev"):
        std = masked_stddev_samp(x, mask)
    return calc, std, anomaly


# -- spatial DBSCAN over [N, F] point embeddings ------------------------
#
# The BASELINE north-star config 3 generalization: "DBSCAN spatial
# anomaly on (srcIP, dstIP, dstPort, bytes) embeddings". Same
# closed-form noise test as the per-series kernel, over euclidean
# distance in feature space, computed in [block, N] tiles so the full
# [N, N] distance matrix never materializes: two lax.scan passes
# (neighbor counts, then core-reachability), each tile one
# matmul-shaped distance evaluation on the MXU.


@functools.partial(jax.jit, static_argnames=("eps", "min_samples",
                                             "block"))
def dbscan_points_noise(points: jnp.ndarray, valid: jnp.ndarray,
                        eps: float, min_samples: int = DEFAULT_MIN_SAMPLES,
                        block: int = 1024) -> jnp.ndarray:
    """Noise flags for [N, F] float points (`valid` masks padding).
    Exact O(N^2) pairwise computation, O(N*block) memory."""
    points = points.astype(jnp.float32)
    n = points.shape[0]
    pad = (-n) % block
    if pad:
        points = jnp.concatenate(
            [points, jnp.zeros((pad, points.shape[1]), jnp.float32)])
        valid = jnp.concatenate([valid, jnp.zeros(pad, bool)])
    nb = points.shape[0] // block
    tiles = points.reshape(nb, block, -1)
    tile_valid = valid.reshape(nb, block)
    eps2 = eps * eps
    x2 = (points * points).sum(-1)

    def within(tile):             # [block, F] -> [block, Npad] bool
        t2 = (tile * tile).sum(-1)
        # HIGHEST precision: the default TPU bf16 matmul's absolute
        # error (~0.4% of the ~scale^2 dot products) would swamp eps^2
        # and corrupt the threshold test.
        d2 = t2[:, None] + x2[None, :] - 2.0 * jnp.matmul(
            tile, points.T, precision=jax.lax.Precision.HIGHEST)
        return d2 <= eps2

    def count_pass(_, tv):
        tile, tvalid = tv
        w = within(tile) & valid[None, :] & tvalid[:, None]
        return None, w.sum(-1)

    _, counts = jax.lax.scan(count_pass, None, (tiles, tile_valid))
    counts = counts.reshape(-1)
    core = (counts >= min_samples) & valid

    def reach_pass(_, tv):
        tile, tvalid = tv
        w = within(tile) & core[None, :] & tvalid[:, None]
        return None, w.any(-1)

    _, reachable = jax.lax.scan(reach_pass, None, (tiles, tile_valid))
    reachable = reachable.reshape(-1)
    noise = valid & ~core & ~reachable
    return noise[:n]
