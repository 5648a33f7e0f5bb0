"""One-dispatch fused scoring step for the device-resident hot path.

The sharded ingest engine scores one micro-batch with (per shard) one
jitted heavy-hitter step (CMS scatter + query + k-means) plus one
jitted streaming step (EWMA/Welford gather-scan-scatter) — two
dispatches and two host↔device fetch round trips per shard per batch.
That fixed cost is paid 2 × shards times a block whatever its rows,
so this module fuses ALL of it — EWMA update + Welford band + CMS
heavy-hitter update + k-means shape outliers + alert thresholding —
across EVERY shard's coalesced slice into ONE jitted computation: one
dispatch, one fetch, per coalesced micro-batch.

Parity contract: the per-shard math is literally the sharded engine's
— the streaming scan applies `ops.stream_state._update` tick by
tick, and the heavy-hitter half composes the same
`ops.sketch.cms_update/cms_query/kmeans_step` helpers — so on the same
backend, the same per-shard input order produces bit-identical alert
decisions (tests/test_device_path.py holds both engines to that).

The T-tick scan over the [T, U] slot tile has a Pallas TPU kernel
(`THEIA_FUSED_PALLAS=auto|1|0|interpret`): one VMEM-resident pass per
128-lane slot block with the tick loop unrolled in-register, instead of
the lax.scan's per-tick HLO while-loop (tiles of at most
PALLAS_MAX_TICKS ticks; a longer one takes the scan, since unrolling
is paid at trace time). `auto` (the default) engages it
only on TPU backends; everywhere else — tier-1 CI included — the plain
jnp scan keeps the semantics on CPU. `interpret` runs the Pallas kernel
through the interpreter so its logic is testable without hardware.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .ewma import DEFAULT_ALPHA
from .sketch import CmsState, KMeansState, cms_query, cms_update, kmeans_step
from .stream_state import StreamState, _update as _stream_tick

#: Pallas lane width: the tile scan kernel blocks the slot axis by this.
#: Slot tiles arrive padded to powers of two >= 64; the one 64-wide
#: bucket is padded up to a full lane block inside the kernel wrapper.
PALLAS_BLOCK_U = 128
#: The kernel unrolls its tick loop, so tracing and compiling it grow
#: with the tile's ticks (at 2,048 ticks x 8 shards a process's first
#: block waited out the request's 120 s with every program in the
#: compile cache; my chip runs, PR 34). A tile with more ticks than
#: this, a block that carries hundreds of points a connection, takes
#: the `lax.scan`, whose one traced tick serves any length; the tiles
#: of 4-point blocks, coalesced or not, stay on the kernel.
PALLAS_MAX_TICKS = 64


class ShardInputs(NamedTuple):
    """One shard's coalesced micro-batch slice, host-staged and padded
    (streaming tile from StreamingDetector.build_plan, heavy-hitter
    arrays from heavy_hitters.build_hh_plan)."""
    slots: jnp.ndarray    # [U_pad] int32 state slots (capacity = pad)
    x: jnp.ndarray        # [T_pad, U_pad] float32 values
    active: jnp.ndarray   # [T_pad, U_pad] bool
    keys: jnp.ndarray     # [size] uint32 CMS keys
    vols: jnp.ndarray     # [size] float32 volumes
    q: jnp.ndarray        # [q_size] uint32 heavy-hitter query keys
    feats: jnp.ndarray    # [size, F] float32 k-means features
    valid: jnp.ndarray    # [size] bool


class ShardStepState(NamedTuple):
    """One shard's device-resident detector state between micro-batches."""
    stream: StreamState
    cms: CmsState
    km: KMeansState


class ShardOutputs(NamedTuple):
    anomaly: jnp.ndarray  # [T_pad, U_pad] bool streaming anomalies
    est: jnp.ndarray      # [q_size] float32 sketched volume per query
    total: jnp.ndarray    # scalar float32 post-update sketch total
    dist: jnp.ndarray     # [size] float32 distance to assigned centroid


def _scan_tile(sub: StreamState, x: jnp.ndarray, active: jnp.ndarray,
               alpha) -> Tuple[StreamState, jnp.ndarray]:
    """Reference tick scan: exactly stream_update_sparse's inner loop
    (analytics/streaming.py) over an already-gathered slot subset."""

    def step(carry, inp):
        x_t, act_t = inp
        new, anomaly = _stream_tick(carry, x_t, act_t, alpha)
        return new, anomaly

    return jax.lax.scan(step, sub, (x, active))


def _scan_tile_pallas(sub: StreamState, x: jnp.ndarray,
                      active: jnp.ndarray, alpha: float,
                      interpret: bool) -> Tuple[StreamState, jnp.ndarray]:
    """Pallas version of `_scan_tile`: grid over 128-lane slot blocks,
    the (small, static) tick loop unrolled with state held in
    registers/VMEM — no per-tick HLO loop, one pass over the tile.
    Math is kept line-for-line identical to stream_state._update."""
    from jax.experimental import pallas as pl

    t, u_in = x.shape
    pad = -u_in % PALLAS_BLOCK_U
    if pad:
        # inactive padding columns: their state is carried through
        # untouched and sliced away below
        sub = StreamState(*(jnp.pad(a, (0, pad)) for a in sub))
        x = jnp.pad(x, ((0, 0), (0, pad)))
        active = jnp.pad(active, ((0, 0), (0, pad)))
    u = u_in + pad
    alpha = float(alpha)
    one_minus = 1.0 - alpha

    def kernel(ewma_ref, count_ref, mean_ref, m2_ref, x_ref, act_ref,
               ewma_o, count_o, mean_o, m2_o, anom_o):
        ewma = ewma_ref[0, :]
        count = count_ref[0, :]
        mean = mean_ref[0, :]
        m2 = m2_ref[0, :]
        for tt in range(t):
            xv = x_ref[tt, :]
            act = act_ref[tt, :]
            xa = jnp.where(act, xv, 0.0)
            count = count + act.astype(jnp.int32)
            delta = xa - mean
            mean = jnp.where(act,
                             mean + delta / jnp.maximum(count, 1),
                             mean)
            m2 = jnp.where(act, m2 + delta * (xa - mean), m2)
            ewma = jnp.where(act, one_minus * ewma + alpha * xa, ewma)
            std = jnp.sqrt(m2 / jnp.maximum(count - 1, 1))
            anom_o[tt, :] = (act & (count >= 2)
                             & (jnp.abs(xa - ewma) > std))
        ewma_o[0, :] = ewma
        count_o[0, :] = count
        mean_o[0, :] = mean
        m2_o[0, :] = m2

    def vec():
        return pl.BlockSpec((1, PALLAS_BLOCK_U), lambda i: (0, i))

    def tile():
        return pl.BlockSpec((t, PALLAS_BLOCK_U), lambda i: (0, i))

    outs = pl.pallas_call(
        kernel,
        grid=(u // PALLAS_BLOCK_U,),
        in_specs=[vec(), vec(), vec(), vec(), tile(), tile()],
        out_specs=[vec(), vec(), vec(), vec(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct((1, u), sub.ewma.dtype),
            jax.ShapeDtypeStruct((1, u), sub.count.dtype),
            jax.ShapeDtypeStruct((1, u), sub.mean.dtype),
            jax.ShapeDtypeStruct((1, u), sub.m2.dtype),
            jax.ShapeDtypeStruct((t, u), jnp.bool_),
        ],
        interpret=interpret,
    )(sub.ewma[None, :], sub.count[None, :], sub.mean[None, :],
      sub.m2[None, :], x, active)
    state = StreamState(*(o[0, :u_in] for o in outs[:4]))
    return state, outs[4][:, :u_in]


def _stream_half(stream: StreamState, inp: ShardInputs, alpha,
                 use_pallas: bool, interpret: bool
                 ) -> Tuple[StreamState, jnp.ndarray]:
    """Gather-scan-scatter over one shard's slot tile (the
    stream_update_sparse shape, Pallas-optional scan core).
    Padding slots hold `capacity`: the gather clamps harmlessly and
    the scatter DROPS them (XLA's documented OOB semantics)."""
    with jax.named_scope("gather"):
        sub = StreamState(*(a[inp.slots] for a in stream))
    with jax.named_scope("scan"):
        if use_pallas and inp.x.shape[0] <= PALLAS_MAX_TICKS:
            sub, anomalies = _scan_tile_pallas(sub, inp.x, inp.active,
                                               alpha, interpret)
        else:
            sub, anomalies = _scan_tile(sub, inp.x, inp.active, alpha)
    with jax.named_scope("scatter"):
        new = StreamState(*(
            full.at[inp.slots].set(part, mode="drop")
            for full, part in zip(stream, sub)))
    return new, anomalies


def _shard_step(state: ShardStepState, inp: ShardInputs, alpha,
                use_pallas: bool, interpret: bool
                ) -> Tuple[ShardStepState, ShardOutputs]:
    new_stream, anomaly = _stream_half(state.stream, inp, alpha,
                                       use_pallas, interpret)
    with jax.named_scope("sketch"):
        cms = cms_update(state.cms, inp.keys, inp.vols)
        est = cms_query(cms, inp.q)
    with jax.named_scope("kmeans"):
        km, _, dist = kmeans_step(state.km, inp.feats, inp.valid)
    return (ShardStepState(new_stream, cms, km),
            ShardOutputs(anomaly, est, cms.total, dist))


@partial(jax.jit, static_argnames=("alpha", "use_pallas", "interpret"))
def fused_step(states: Tuple[ShardStepState, ...],
               inputs: Tuple[ShardInputs, ...],
               alpha: float = DEFAULT_ALPHA,
               use_pallas: bool = False,
               interpret: bool = False
               ) -> Tuple[Tuple[ShardStepState, ...],
                          Tuple[ShardOutputs, ...]]:
    """ONE device dispatch scoring every shard's coalesced slice:
    per-shard state in, per-shard (state', outputs) out. The host
    arrays in `inputs` ride the call (jit batches the transfers), and
    per-connection detector state never leaves the device between
    micro-batches. Retraces once per (shard subset, tile bucket)
    combination — tiles are padded to power-of-two buckets upstream."""
    pairs = tuple(_shard_step(s, i, alpha, use_pallas, interpret)
                  for s, i in zip(states, inputs))
    return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs)


@jax.jit
def gather_state(state: StreamState, slots: jnp.ndarray) -> StreamState:
    """Pull `slots` rows of per-connection state off the device in ONE
    dispatch — the working-set tier's eviction read
    (ingest/state_tier.py). Padding slots carry `capacity`; the gather
    clamps them to the last row (XLA OOB semantics) and the caller
    slices them away."""
    return StreamState(*(a[slots] for a in state))


@jax.jit
def restore_state(state: StreamState, slots: jnp.ndarray,
                  ewma: jnp.ndarray, count: jnp.ndarray,
                  mean: jnp.ndarray, m2: jnp.ndarray) -> StreamState:
    """Scatter promoted / freshly-zeroed state rows into `slots` in ONE
    dispatch — the working-set tier's promotion write. Padding slots
    carry `capacity`, which the scatter DROPS (XLA OOB semantics), so
    every eviction-batch size shares a handful of compiled shapes.
    Zero rows double as slot re-initialization: a reused slot must not
    leak its previous occupant's state."""
    part = (ewma, count, mean, m2)
    return StreamState(*(
        full.at[slots].set(p.astype(full.dtype), mode="drop")
        for full, p in zip(state, part)))


def pallas_mode() -> Tuple[bool, bool]:
    """(use_pallas, interpret) from THEIA_FUSED_PALLAS:
    'auto' (default) enables the Pallas scan on TPU backends only;
    '1' forces it on, '0' off; 'interpret' runs it through the Pallas
    interpreter (CPU testing of the kernel logic)."""
    raw = os.environ.get("THEIA_FUSED_PALLAS", "auto").strip().lower()
    if raw in ("0", "off", "false", "no"):
        return False, False
    if raw == "interpret":
        return True, True
    if raw in ("1", "force", "on", "yes"):
        return True, False
    return jax.default_backend() == "tpu", False
