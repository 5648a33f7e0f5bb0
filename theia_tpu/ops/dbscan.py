"""DBSCAN outlier scoring as dense pairwise-distance matrix ops.

Reference semantics (plugins/anomaly-detection/anomaly_detection.py:325-349):
sklearn DBSCAN(min_samples=4, eps=2.5e8) over the 1-D throughput values of
one connection; points labeled -1 (noise) are anomalies. The algoCalc
column is a 0.0 placeholder (:312-322).

TPU-first design: general DBSCAN's cluster expansion is data-dependent
control flow, but *noise detection* — all the job needs — is closed-form:

    core_i   = |{j : |x_i − x_j| ≤ eps}| ≥ min_samples   (self included)
    noise_i  = ¬core_i ∧ ¬∃j (core_j ∧ |x_i − x_j| ≤ eps)

i.e. a point is noise iff it is neither a core point nor within eps of
one. That is exactly sklearn's label==-1 set, computed as one [T,T]
masked distance matrix per series — batched matmul-shaped work instead of
sequential region growing.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .masked import masked_stddev_samp

DEFAULT_EPS = 2.5e8
DEFAULT_MIN_SAMPLES = 4


@functools.partial(jax.jit, static_argnames=("eps", "min_samples"))
def dbscan_noise(x: jnp.ndarray, mask: jnp.ndarray,
                 eps: float = DEFAULT_EPS,
                 min_samples: int = DEFAULT_MIN_SAMPLES) -> jnp.ndarray:
    """Noise (= anomaly) flags for a padded [S, T] series batch."""
    within = (jnp.abs(x[..., :, None] - x[..., None, :]) <= eps)
    pair_valid = mask[..., :, None] & mask[..., None, :]
    within &= pair_valid
    with jax.named_scope("counts"):
        neighbor_counts = jnp.sum(within, axis=-1)
    core = (neighbor_counts >= min_samples) & mask
    with jax.named_scope("reach"):
        reachable = jnp.any(within & core[..., None, :], axis=-1)
    return mask & ~core & ~reachable


def pair_tests(mask) -> int:
    """Pairs one pass of the definition tests over a padded [S, T]
    batch: the sum over series of (valid points)^2. `dbscan_noise`
    makes two such passes (`counts`, `reach`), whichever formulation
    runs them."""
    n = np.count_nonzero(np.asarray(mask), axis=-1).astype(np.int64)
    return int(np.sum(n * n))


def _interpret() -> bool:
    """Pallas interpreter mode: on for any backend that can't lower
    Mosaic (everything but real TPU)."""
    return jax.default_backend() != "tpu"


def _use_pallas(t: int) -> bool:
    """The dispatch rule for `dbscan_scores(use_pallas=None)`, decided
    from what the process can observe — never from a caught compile
    error: the Pallas kernel on a TPU backend for series that pad to
    at most `PALLAS_MAX_T` steps (the length its blocks fit VMEM
    for), the XLA formulation everywhere else. THEIA_TPU_PALLAS=1/0
    forces either side (1 off-TPU runs the interpreter)."""
    from .dbscan_pallas import PALLAS_MAX_T, padded_length

    flag = os.environ.get("THEIA_TPU_PALLAS", "auto").lower()
    if flag in ("0", "off", "false"):
        return False
    if flag in ("1", "on", "true"):
        return True
    return (jax.default_backend() == "tpu"
            and padded_length(t) <= PALLAS_MAX_T)


def dbscan_scores(x: jnp.ndarray, mask: jnp.ndarray,
                  eps: float = DEFAULT_EPS,
                  min_samples: int = DEFAULT_MIN_SAMPLES,
                  use_pallas: bool | None = None):
    """(algoCalc placeholder zeros, stddev, anomaly) for DBSCAN.

    stddev is still emitted to fill the tadetector row shape (the
    reference computes it in the groupby regardless of algorithm).

    use_pallas=None auto-selects per `_use_pallas`: the tiled Pallas
    kernel on TPU (no [S,T,T] HBM round-trip), the fused XLA
    formulation elsewhere and for series too long for the kernel.
    """
    if use_pallas is None:
        use_pallas = _use_pallas(x.shape[-1])
    if use_pallas:
        from .dbscan_pallas import dbscan_noise_pallas

        # Off-TPU, an explicit use_pallas=True runs the kernel in
        # interpreter mode (same code path, testable on the CPU mesh).
        anomaly = dbscan_noise_pallas(
            x, mask, eps=eps, min_samples=min_samples,
            interpret=_interpret())
    else:
        anomaly = dbscan_noise(x, mask, eps=eps,
                               min_samples=min_samples)
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
