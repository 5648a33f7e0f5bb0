"""Pallas TPU kernel for DBSCAN noise detection.

Same decisions as ops/dbscan.py (reference semantics:
plugins/anomaly-detection/anomaly_detection.py:325-349 — sklearn
DBSCAN(eps, min_samples) noise labels over 1-D throughput values), by
the definition itself: every pair of a series' points is tested, in
tiles that stream series blocks through VMEM and never write a
pairwise tensor back, so HBM traffic is O(S·T) where the work is
O(S·T²). `ops.dbscan.dbscan_noise` sorts instead and tests no pair.

Layout (what Mosaic accepts — the first version built a [BS, T, T]
cube with a minor-dim insert and sized BS from a VMEM budget, which
the TPU lowering refused for every T > 256: block rows of 3, 2, 1 are
neither a multiple of 8 nor the whole array): each grid step takes 128
series as a lane-aligned [128, T] row block plus the same values
transposed [T, 128], and walks the series one at a time. A series'
pairwise test is then plain 2-D work on [C, T] tiles — a column of
the transposed block (one-hot lane reduce, exact) against the series'
row — and because |x_i − x_j| is symmetric both reductions land in the
row layout the output wants, with no in-kernel transpose. Invalid
points travel as +inf: |inf − x| and |inf − inf| (NaN) both fail the
`<= eps` test, so the mask needs no separate operand.

T is padded to the 128-lane boundary; `PALLAS_MAX_T` bounds the padded
length the [128, T] blocks fit in scoped VMEM for (checked on a v5e).
Longer series are the caller's dispatch decision (ops/dbscan.py), not
an error path here.

On non-TPU backends the kernel runs in interpreter mode, so tests on
the CPU conftest (8 virtual devices) exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dbscan import DEFAULT_EPS, DEFAULT_MIN_SAMPLES

#: series per grid step (the transposed block's lane dimension)
_BLOCK_SERIES = 128
#: rows of the pairwise tile held live per inner step
_CHUNK = 128
#: longest padded series the kernel takes: three double-buffered
#: [128, T] f32 blocks plus the [_CHUNK, T] temporaries stay inside
#: the 16 MiB scoped-VMEM default up to here (T = 4096 needs 63 MiB)
PALLAS_MAX_T = 2048


def padded_length(t: int) -> int:
    """Series length after padding to the 128-lane boundary."""
    return -(-max(t, 1) // 128) * 128


def _dbscan_kernel(x_ref, xt_ref, out_ref, *, eps, min_samples):
    bs, t = x_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)

    def one_series(j, carry):
        row = x_ref[pl.ds(j, 1), :]                       # [1, T]
        counts = jnp.zeros((1, t), jnp.float32)
        reach = jnp.zeros((1, t), jnp.float32)
        for c0 in range(0, t, _CHUNK):
            # series j's values as a column: exact (one non-zero term)
            col = jnp.sum(
                jnp.where(lane == j, xt_ref[c0:c0 + _CHUNK, :], 0.0),
                axis=1, keepdims=True)                    # [C, 1]
            within = (jnp.abs(col - row) <= eps).astype(jnp.float32)
            # neighbor counts of the chunk's own points (complete:
            # the lane axis spans the whole series) → their core flags
            core = (jnp.sum(within, axis=1, keepdims=True)
                    >= min_samples).astype(jnp.float32)
            reach = jnp.maximum(
                reach, jnp.max(within * core, axis=0, keepdims=True))
            counts = counts + jnp.sum(within, axis=0, keepdims=True)
        noise = ((row < jnp.inf) & (counts < min_samples)
                 & (reach < 0.5))                # counts exact, T < 2^24
        out_ref[pl.ds(j, 1), :] = noise.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, bs, one_series, 0)


@functools.partial(
    jax.jit, static_argnames=("eps", "min_samples", "interpret"))
def dbscan_noise_pallas(x: jnp.ndarray, mask: jnp.ndarray,
                        eps: float = DEFAULT_EPS,
                        min_samples: int = DEFAULT_MIN_SAMPLES,
                        interpret: bool = False) -> jnp.ndarray:
    """Noise flags for a padded [S, T] batch via the Pallas kernel.

    Bit-identical to ops.dbscan.dbscan_noise (tested against it, and
    checked against it on the chip by chip_smoke.py's jobs phase).
    """
    s, t = x.shape
    t_pad = padded_length(t)
    if t_pad > PALLAS_MAX_T:
        raise ValueError(
            f"series length {t} pads to {t_pad} > PALLAS_MAX_T="
            f"{PALLAS_MAX_T}; use ops.dbscan.dbscan_noise")
    s_pad = -(-max(s, 1) // _BLOCK_SERIES) * _BLOCK_SERIES
    xp = jnp.full((s_pad, t_pad), jnp.inf, jnp.float32)
    xp = xp.at[:s, :t].set(
        jnp.where(mask, x.astype(jnp.float32), jnp.inf))

    out = pl.pallas_call(
        functools.partial(_dbscan_kernel, eps=eps,
                          min_samples=min_samples),
        out_shape=jax.ShapeDtypeStruct((s_pad, t_pad), jnp.int32),
        grid=(s_pad // _BLOCK_SERIES,),
        in_specs=[
            pl.BlockSpec((_BLOCK_SERIES, t_pad), lambda i: (i, 0)),
            pl.BlockSpec((t_pad, _BLOCK_SERIES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_SERIES, t_pad),
                               lambda i: (i, 0)),
        interpret=interpret,
    )(xp, xp.T)
    return out[:s, :t] != 0
