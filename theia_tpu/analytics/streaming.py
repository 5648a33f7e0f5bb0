"""Streaming anomaly detection: micro-batch updates, sub-second alerts.

The reference's TAD is a batch job — minutes from `theia tad run` to a
result row (Spark submit + full table scan + per-row UDFs). This module
is the TPU-native streaming upgrade the BASELINE north star asks for
(sub-second p50 alert latency): per-connection detector state lives
device-resident and every ingest micro-batch advances it with one tiny
fused XLA step — no rescans, no job submission.

Semantics: the EWMA recurrence is exactly the batch kernel's
(ops/ewma.py, reference anomaly_detection.py:146-165); the stddev band
uses Welford's running *sample* stddev over the points seen so far,
where the batch job uses the whole window's stddev — the streaming
detector can't see the future. Alerts therefore fire with the
information available at arrival time (documented difference; the batch
path remains available for parity).

Slot model: a fixed-capacity state table indexed by slot; the host maps
connection keys (packed 6-tuples of dictionary codes) to slots on first
sight. Capacity overflow evicts nothing — new series beyond capacity
are dropped and counted, mirroring how a fixed-size flow cache degrades.

Sharding: a StreamingDetector is deliberately single-writer (callers
serialize updates). The manager's ingest path scales it by running N
independent instances, one per destination-hash shard, each behind its
own lock (manager/ingest.py) — the per-slot recurrence only ever reads
its own slot's state, so partitioning the key space partitions the
state with no cross-shard coupling.

Hot-path shape: one micro-batch is ONE jitted device step however many
rows it carries. The step gathers only the U slots present in the batch,
scans the (usually 1-2) ticks of duplicate points per connection over a
[T, U] tile, and scatters the updated state back — O(T·U) device work
instead of O(T·capacity) dense dispatches, with U ≤ rows.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..ops.ewma import DEFAULT_ALPHA
from ..ops.stream_state import StreamState, _update
from ..schema import ColumnarBatch

CONNECTION_KEY_COLUMNS = (
    "sourceIP", "sourceTransportPort", "destinationIP",
    "destinationTransportPort", "protocolIdentifier", "flowStartSeconds")

# Capacity overflow is silent at the data plane (new series simply stop
# being scored) — this counter is the operator's only line-rate signal
# that alerts are going missing before they do.
_M_DROPPED = _metrics.counter(
    "theia_detector_series_dropped_total",
    "New connection series dropped because every streaming-detector "
    "slot was taken (the series is never scored)")


# The detector leg of a request, by stage (self time; one observation
# per shard slice for the per-shard stages, so _sum / blocks is seconds
# a block and _count{stage="dispatch"} / blocks is dispatches a block).
# Declared here, the lowest module of the leg; manager/ingest.py takes
# the children of its own stages from it.
DETECTOR_STAGE = _trace.StageSeries(
    "theia_detector_stage_seconds",
    "Host time of the detector leg by stage: remap (dictionary remap "
    "incl. the wait for its lock), partition, lock_wait (blocked on a "
    "busy shard), heavy_hitters, plan (key to slot + tick tile), "
    "dispatch (tile to device + the step call returning), fetch "
    "(wait for the device + copy back), alerts",
    labelnames=("stage",))
_M_PLAN = DETECTOR_STAGE.labels(stage="plan")
_M_DISPATCH = DETECTOR_STAGE.labels(stage="dispatch")
_M_FETCH = DETECTOR_STAGE.labels(stage="fetch")
_M_ALERTS = DETECTOR_STAGE.labels(stage="alerts")
H2D_BYTES = _metrics.counter(
    "theia_detector_h2d_bytes_total",
    "Bytes of slots + x + active handed to the streaming detector's "
    "device step, power-of-two padding included")


def init_state(capacity: int, dtype=jnp.float32) -> StreamState:
    z = jnp.zeros(capacity, dtype)
    return StreamState(ewma=z, count=jnp.zeros(capacity, jnp.int32),
                       mean=z, m2=z)


@jax.jit
def stream_update(state: StreamState, x: jnp.ndarray,
                  active: jnp.ndarray,
                  alpha: float = DEFAULT_ALPHA
                  ) -> Tuple[StreamState, jnp.ndarray]:
    """Dense one-tick step: x [S] new values, active [S] validity."""
    return _update(state, x, active, alpha)


@jax.jit
def stream_update_sparse(state: StreamState, slots: jnp.ndarray,
                         x: jnp.ndarray, active: jnp.ndarray,
                         alpha: float = DEFAULT_ALPHA
                         ) -> Tuple[StreamState, jnp.ndarray]:
    """Gather-scan-scatter step for one micro-batch.

    slots [U] int32: the distinct state slots present in the batch;
    padding entries hold `capacity` (out of bounds), so the gather
    clamps harmlessly and the scatter DROPS them (XLA's documented
    OOB semantics) — padded columns never touch real state.
    x, active [T, U]: tick-major values; tick t carries each
    connection's t-th point in this batch, so the recurrence sees
    duplicate points in arrival order.

    Returns (new state, anomaly [T, U]).
    """
    with jax.named_scope("gather"):
        sub = StreamState(*(a[slots] for a in state))

    def step(carry, inp):
        x_t, act_t = inp
        new, anomaly = _update(carry, x_t, act_t, alpha)
        return new, anomaly

    with jax.named_scope("scan"):
        sub, anomalies = jax.lax.scan(step, sub, (x, active))
    with jax.named_scope("scatter"):
        new_state = StreamState(*(
            full.at[slots].set(part, mode="drop")
            for full, part in zip(state, sub)))
    return new_state, anomalies


def _pad_pow2(n: int, minimum: int) -> int:
    """Next power-of-two dispatch bucket so the jitted step compiles
    once per bucket, not once per distinct micro-batch shape."""
    size = minimum
    while size < n:
        size <<= 1
    return size


class StreamPlan(NamedTuple):
    """Host half of one micro-batch: the [T, U] tick tile plus the slot
    gather/scatter vector, ready for the jitted device step. Built by
    `StreamingDetector.build_plan` and consumed either by this module's
    `stream_update_sparse` (sharded engine) or by the fused engine's
    single cross-shard dispatch (ops/fused_detector.py)."""
    slots: np.ndarray     # [U_pad] int32; padding holds `capacity`
    x: np.ndarray         # [T_pad, U_pad] float32 values
    active: np.ndarray    # [T_pad, U_pad] bool validity
    row_idx: np.ndarray   # [T_pad, U_pad] int64 source row (-1 padding)
    present: np.ndarray   # [U] slot id per live column


def alert_record(slot: int, flow_end: int, value: float,
                 latency: float) -> Dict[str, object]:
    """The connection-anomaly alert record — ONE builder for both
    engines (this module's ingest path and the fused engine's
    device_path._finish) so the published shape cannot drift."""
    return {
        "slot": int(slot),
        "flowEndSeconds": int(flow_end),
        "throughput": float(value),
        "latency_s": latency,
    }


def plan_alerts(plan: StreamPlan, hits: np.ndarray, times: np.ndarray,
                values: np.ndarray,
                latency: float) -> List[Dict[str, object]]:
    """Alert records for the anomaly hits of one plan's device step
    (sharded engine; `row` is batch-local and popped before
    publication by describe_alert's caller)."""
    alerts: List[Dict[str, object]] = []
    for t, c in hits:
        i = int(plan.row_idx[t, c])
        rec = alert_record(plan.present[c], times[i], values[i],
                           latency)
        rec["row"] = i
        alerts.append(rec)
    return alerts


class StreamingDetector:
    """Host-side driver: key→slot mapping + device-resident state."""

    def __init__(self, capacity: int = 65536,
                 alpha: float = DEFAULT_ALPHA,
                 value_column: str = "throughput",
                 clock=time.perf_counter, tier=None) -> None:
        self.capacity = capacity
        self.alpha = alpha
        self.value_column = value_column
        #: injectable for deterministic latency_s in tests (the alert
        #: latency is a measurement, not detector state)
        self.clock = clock
        self.state = init_state(capacity)
        # packed key bytes → slot; dropped keys are remembered with
        # slot -1 so a series is only counted dropped once, however
        # many rows it keeps sending.
        self._slots: Dict[bytes, int] = {}
        self._slot_keys: List[Optional[bytes]] = []
        self._n_alloc = 0
        self.dropped_series = 0
        #: optional working-set tier (ingest/state_tier.WorkingSetTier):
        #: when attached, slot assignment goes through the tier —
        #: capacity overflow spills LRU state instead of dropping new
        #: series, and spilled state is restored exactly on re-arrival
        self.tier = tier
        if tier is not None:
            tier.attach(self)

    @property
    def n_series(self) -> int:
        return self._n_alloc

    @property
    def reads(self) -> Dict[str, type]:
        """The columns `ingest` and `describe_alert` read, each with
        the dtype `ingest` converts it to (the same declaration as
        `HeavyHitterDetector.reads`, per instance because of
        `value_column`)."""
        return {**dict.fromkeys(CONNECTION_KEY_COLUMNS, np.int64),
                self.value_column: np.float64,
                "flowEndSeconds": np.int64}

    def _slot_for(self, key: bytes) -> int:
        slot = self._slots.get(key)
        if slot is None:
            if self._n_alloc >= self.capacity:
                self._slots[key] = -1
                self.dropped_series += 1
                _M_DROPPED.inc()
                return -1
            slot = self._n_alloc
            self._n_alloc += 1
            self._slots[key] = slot
            self._slot_keys.append(key)
        return slot

    def build_plan(self, keys: np.ndarray, values: np.ndarray,
                   staging: Optional[Callable] = None
                   ) -> Optional[StreamPlan]:
        """Host half of `ingest`: key→slot mapping plus the [T, U]
        tick tile for one micro-batch, no device work.

        `keys` is the [N, 6] int64 connection-key matrix (in
        CONNECTION_KEY_COLUMNS order), `values` the [N] metric column.
        `staging(tag, shape, dtype)` returns a reusable array to fill
        — the fused engine's pinned ring; None allocates fresh arrays
        (this class's own path). Returns None when no row maps to a
        live slot.

        Python work is O(distinct NEW connections), not O(rows): keys
        are packed into 48-byte rows and deduplicated vectorized, and
        the Python dict is touched once per distinct key.
        """
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        packed = keys.view(np.dtype((np.void, keys.itemsize *
                                     keys.shape[1]))).ravel()
        uniq, inverse = np.unique(packed, return_inverse=True)
        if self.tier is not None:
            slots_u = self.tier.assign(self, uniq)
        else:
            slots_u = np.fromiter(
                (self._slot_for(k.tobytes()) for k in uniq),
                dtype=np.int64, count=len(uniq))
        slots = slots_u[inverse]
        ok = slots >= 0

        # Bucket duplicate slots into successive ticks (stable order).
        order = np.argsort(slots[ok], kind="stable")
        s_sorted = slots[ok][order]
        v_sorted = values[ok][order]
        idx_sorted = np.flatnonzero(ok)[order]
        # tick index = occurrence number of this slot within the batch:
        # position minus the start index of the slot's run.
        n = len(s_sorted)
        if n == 0:
            return None
        same = np.empty(n, bool)
        same[0] = False
        same[1:] = s_sorted[1:] == s_sorted[:-1]
        if not same.any():   # common case: one point per series
            tick = np.zeros(n, np.int64)
        else:
            idx = np.arange(n)
            run_start = np.maximum.accumulate(np.where(same, 0, idx))
            tick = idx - run_start
        n_ticks = int(tick.max()) + 1

        # [T, U] tile over the distinct slots present in this batch.
        present, col = np.unique(s_sorted, return_inverse=True)
        u = len(present)
        u_pad = _pad_pow2(u, 64)
        t_pad = _pad_pow2(n_ticks, 1)

        def _alloc(tag, shape, dtype, fill):
            if staging is None:
                return np.full(shape, fill, dtype)
            a = staging(tag, shape, dtype)
            a[...] = fill
            return a

        x = _alloc("x", (t_pad, u_pad), np.float32, 0)
        active = _alloc("active", (t_pad, u_pad), bool, False)
        row_idx = _alloc("row_idx", (t_pad, u_pad), np.int64, -1)
        x[tick, col] = v_sorted
        active[tick, col] = True
        row_idx[tick, col] = idx_sorted
        slots_pad = _alloc("slots", (u_pad,), np.int32, self.capacity)
        slots_pad[:u] = present
        return StreamPlan(slots_pad, x, active, row_idx, present)

    def ingest(self, batch: ColumnarBatch) -> List[Dict[str, object]]:
        """Advance state with one micro-batch; returns alert records.

        Rows are keyed by the 6-tuple connection columns; if a batch
        carries several points for one connection, each lands in a
        successive tick so the recurrence sees them in order. The
        whole batch is one jitted gather-scan-scatter device step.
        """
        if len(batch) == 0:
            return []
        t_arrival = self.clock()
        with _trace.stage("detector.plan", _M_PLAN):
            keys = np.stack(
                [np.asarray(batch[c], np.int64)
                 for c in CONNECTION_KEY_COLUMNS], axis=1)
            values = np.asarray(batch[self.value_column], np.float64)
            times = np.asarray(batch["flowEndSeconds"], np.int64)
            plan = self.build_plan(keys, values)
        if plan is None:
            return []
        with _trace.stage("detector.dispatch", _M_DISPATCH):
            H2D_BYTES.inc(plan.slots.nbytes + plan.x.nbytes
                          + plan.active.nbytes)
            self.state, anomaly = stream_update_sparse(
                self.state, jnp.asarray(plan.slots),
                jnp.asarray(plan.x), jnp.asarray(plan.active),
                self.alpha)
        with _trace.stage("detector.fetch", _M_FETCH):
            anomaly = np.asarray(anomaly)
        with _trace.stage("detector.alerts", _M_ALERTS):
            hits = np.argwhere(anomaly)
            if not hits.size:
                return []
            latency = self.clock() - t_arrival
            return plan_alerts(plan, hits, times, values, latency)

    def describe_alert(self, batch: ColumnarBatch,
                       alert: Dict[str, object]) -> Dict[str, object]:
        """Decode an alert's connection identity from its source row.
        Per-cell decode_one, NOT a whole-column decode — an alert
        burst would otherwise pay O(rows) string work per alert."""
        i = int(alert["row"])
        out = dict(alert)
        for c in CONNECTION_KEY_COLUMNS:
            d = batch.dicts.get(c)
            out[c] = (d.decode_one(int(batch[c][i])) if d is not None
                      else int(batch[c][i]))
        return out
