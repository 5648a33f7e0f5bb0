"""Device-resident scoring pipeline: cross-shard micro-batch
coalescing, one fused device step per micro-batch, double-buffered
host↔device transfer.

The sharded detector engine (manager/ingest.py) scores each request's
batch shard by shard under shard locks — per batch it pays N_shards ×
(2 dispatches + 2 fetches) and allocates fresh tile/feature arrays
every time. This engine replaces that hot loop with a pipeline:

  1. **Coalescing.** Score requests from all ingest shards land in a
     bounded queue; the scorer thread drains whatever is waiting (up
     to MAX_STEP_ROWS rows) and gathers the key/value columns
     of every pending block *directly from the decode output* into
     reused staging buffers — no per-shard ColumnarBatch copies (the
     sharded path slices all ~52 columns per shard; this path touches
     only the ~10 the detectors read).
  2. **One fused step.** The whole coalesced micro-batch — every
     shard's slice — is scored by ops/fused_detector.fused_step: EWMA
     update + Welford band + CMS heavy-hitter update + k-means shape
     outliers + alert thresholding in ONE jitted dispatch, with
     per-connection StreamState (and the CMS/centroid state) living on
     device between micro-batches instead of round-tripping.
  3. **Double buffering.** Staging buffers alternate between two
     generations and a dispatched step's results are fetched only
     after the NEXT step has been dispatched, so host staging/decode
     of batch N+1 overlaps device scoring of batch N. The queue is
     bounded: its depth is exported as a gauge and feeds the PR 5
     admission pressure ladder, so sustained device slowness browns
     out scoring instead of growing an invisible backlog.

Alert parity: the per-shard math is the sharded engine's own
(ops/fused_detector.py reuses stream_state._update and the sketch
helpers), the host-side slot mapping and tick bucketing are the same
code (StreamingDetector.build_plan), and shards are thresholded in
index order against the same eventually-consistent cross-shard totals
— so a producer that awaits each ack (one block per step, the
documented determinism contract) gets bit-identical alert streams from
either engine. Under concurrent producers, coalescing folds multiple
blocks into one statistical micro-batch for the heavy-hitter leg
(volumes sum once, centroids take one mini-batch step) while the
per-connection EWMA/Welford recurrence still sees every point in
per-shard arrival order, tick by tick.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..analytics import heavy_hitters as _hh
from ..analytics.streaming import (
    CONNECTION_KEY_COLUMNS,
    H2D_BYTES,
    StreamPlan,
    alert_record,
)
from ..obs import metrics as _metrics
from ..ops import fused_detector as _ops
from ..utils import get_logger

logger = get_logger("device_path")

_M_STEP = _metrics.histogram(
    "theia_fused_step_seconds",
    "One fused scoring step: staging + single-dispatch kernel over "
    "every shard's coalesced slice + result fetch")
_M_QDEPTH = _metrics.gauge(
    "theia_fused_queue_depth",
    "Score requests waiting for the fused pipeline (bounded queue; "
    "feeds the admission pressure ladder)")
_M_ROWS = _metrics.histogram(
    "theia_fused_batch_rows", "Rows per coalesced fused step")
_M_BLOCKS = _metrics.histogram(
    "theia_fused_coalesced_blocks",
    "Decoded blocks coalesced into one fused step")
_M_STEPS = _metrics.counter(
    "theia_fused_steps_total", "Fused scoring steps dispatched")

#: positions of the IP columns within CONNECTION_KEY_COLUMNS (the
#: heavy-hitter leg reads them out of the already-gathered key matrix
#: instead of gathering the batch columns a second time)
_KEY_SRC = CONNECTION_KEY_COLUMNS.index("sourceIP")
_KEY_DST = CONNECTION_KEY_COLUMNS.index("destinationIP")

#: decode-batch columns gathered besides the connection key (value /
#: time / heavy-hitter features) — everything the detectors read
_EXTRA_COLUMNS = ("flowEndSeconds", "octetDeltaCount",
                  "packetDeltaCount")

#: mirror of manager/ingest.py MAX_ALERTS (kept literal: the manager
#: imports this module, not the other way round) — only the newest
#: survive the ring, so only those are worth decoding
_MAX_DESCRIBED_ALERTS = 1000

#: score requests the bounded pipeline queue holds (backpressure)
QUEUE_CAPACITY = 8
#: most rows coalesced into one fused step (bounds the staging ring;
#: a bigger single block still scores alone)
MAX_STEP_ROWS = 131072
#: seconds a request waits for its fused step (queue put + result)
STEP_TIMEOUT = 120.0


class _StagingPool:
    """Reused host staging buffers, double-buffered by generation.

    `get` hands out the prefix view of a power-of-two-capacity buffer
    keyed by (tag, trailing shape, dtype) — a steady workload hits the
    same buckets every step and never allocates (the 'pinned, reused
    host staging arrays' the transfer leg needs; allocation was the
    bound, not the copy). Two generations alternate so the arrays
    staged for step N are not rewritten until step N+1 has been
    dispatched AND step N's results fetched — a backend that aliases
    host memory into device buffers (CPU XLA's zero-copy path) never
    sees a buffer mutate under a live computation.
    """

    def __init__(self, generations: int = 2) -> None:
        self._gens: List[Dict[tuple, np.ndarray]] = [
            {} for _ in range(generations)]
        self._live = 0
        self.hits = 0
        self.misses = 0

    def advance(self) -> None:
        self._live = (self._live + 1) % len(self._gens)

    def get(self, tag, shape, dtype) -> np.ndarray:
        shape = tuple(shape)
        cap = (_hh.pad_bucket(shape[0], minimum=8),) + shape[1:]
        key = (tag, cap[1:], np.dtype(dtype).str)
        pool = self._gens[self._live]
        arr = pool.get(key)
        if arr is None or arr.shape[0] < cap[0]:
            arr = pool[key] = np.empty(cap, dtype)
            self.misses += 1
        else:
            self.hits += 1
        return arr[:shape[0]]


class _ScoreItem:
    """One request's remapped batch waiting for (or riding) a step."""

    __slots__ = ("batch", "shard_rows", "future", "t_arrival", "rows")

    def __init__(self, batch, shard_rows: Dict[int, Optional[np.ndarray]],
                 t_arrival: float) -> None:
        self.batch = batch
        #: shard index -> row indices (None = every row of the batch)
        self.shard_rows = shard_rows
        self.future: Future = Future()
        self.t_arrival = t_arrival
        self.rows = len(batch)


class _ShardWork:
    """Host-side bookkeeping for one shard's slice of one step."""

    __slots__ = ("shard", "splan", "hplan", "times", "vals",
                 "item_of", "row_of", "segments", "dst", "n")

    def __init__(self, shard, splan, hplan, times, vals, item_of,
                 row_of, segments, dst, n) -> None:
        self.shard = shard
        self.splan = splan
        self.hplan = hplan
        self.times = times
        self.vals = vals
        self.item_of = item_of
        self.row_of = row_of
        #: [(item index, start, stop)] coalescing segments, item order
        self.segments = segments
        self.dst = dst
        self.n = n


class _Step:
    """A dispatched-but-unresolved fused step (the in-flight half of
    the double buffer)."""

    __slots__ = ("items", "work", "outputs", "t0")

    def __init__(self, items, work, outputs, t0) -> None:
        self.items = items
        self.work = work
        self.outputs = outputs
        self.t0 = t0


class FusedDetectorEngine:
    """Drop-in scoring engine behind IngestManager
    (THEIA_DETECTOR_ENGINE=fused): same DetectorShard state objects,
    same (hh_alerts, conn_alerts, n_conn) contract as the sharded
    score path, scored through the coalescing fused pipeline."""

    def __init__(self, shards: Sequence, shard_totals: np.ndarray,
                 on_scored: Optional[Callable[[int, int], None]] = None
                 ) -> None:
        if not shards:
            raise ValueError("fused engine needs at least one shard")
        alphas = {s.streaming.alpha for s in shards}
        vcols = {s.streaming.value_column for s in shards}
        if len(alphas) != 1 or len(vcols) != 1:
            raise ValueError(
                "fused engine requires a uniform detector config "
                f"across shards (alpha={alphas}, value={vcols})")
        self.shards = list(shards)
        self.alpha = float(next(iter(alphas)))
        self.value_column = next(iter(vcols))
        #: the injectable latency clock (tests pin it); alert latency
        #: is enqueue -> resolve, the whole pipeline a point traversed
        self.clock = self.shards[0].streaming.clock
        self._totals = shard_totals
        self._on_scored = on_scored
        #: an attribute so that a test can lower it on one engine
        self.max_step_rows = MAX_STEP_ROWS
        self._queue: _queue.Queue = _queue.Queue(QUEUE_CAPACITY)
        self._staging = _StagingPool()
        self._use_pallas, self._interpret = _ops.pallas_mode()
        self.steps = 0
        self.coalesced_blocks = 0
        self._closed = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="theia-fused-scorer")
        self._thread.start()

    # -- public surface --------------------------------------------------

    def queue_depth(self) -> int:
        """Live pipeline backlog — the admission pressure signal."""
        return self._queue.qsize()

    def stats(self) -> Dict[str, object]:
        """Operator doc for /healthz ingest.engine and `theia top`."""
        return {
            "queueDepth": self.queue_depth(),
            "queueCapacity": QUEUE_CAPACITY,
            "maxStepRows": self.max_step_rows,
            "steps": self.steps,
            "coalescedBlocks": self.coalesced_blocks,
            "pallas": bool(self._use_pallas),
            "stagingHits": self._staging.hits,
            "stagingMisses": self._staging.misses,
        }

    def score(self, scored, shard_ids: Optional[np.ndarray]
              ) -> Tuple[List, List[Dict[str, object]], int]:
        """Queue one globally-remapped batch for the next fused step
        and wait for its slice of the results. Same contract as the
        sharded path's score_batch tail: (heavy-hitter alerts,
        described connection alerts, raw connection-alert count)."""
        if self._closed.is_set():
            raise RuntimeError("fused scoring engine is closed")
        if len(scored) == 0:
            return [], [], 0
        if shard_ids is None:
            shard_rows: Dict[int, Optional[np.ndarray]] = {0: None}
        else:
            shard_rows = {}
            for s in range(len(self.shards)):
                idx = np.flatnonzero(shard_ids == s)
                if idx.size:
                    shard_rows[s] = (None if idx.size == len(scored)
                                     else idx)
        item = _ScoreItem(scored, shard_rows, self.clock())
        try:
            self._queue.put(item, timeout=STEP_TIMEOUT)
        except _queue.Full:
            raise RuntimeError(
                f"fused scoring queue stalled (capacity "
                f"{QUEUE_CAPACITY}, no step completed in "
                f"{STEP_TIMEOUT:.0f}s)")
        _M_QDEPTH.set(self._queue.qsize())
        deadline = time.monotonic() + STEP_TIMEOUT
        while True:
            try:
                # short poll instead of one long wait: an item that
                # slipped into the queue after the scorer's final
                # straggler drain (score/close race) must fail fast,
                # not sit out the whole step timeout
                return item.future.result(timeout=0.25)
            except _FutureTimeout:
                if not self._thread.is_alive() \
                        and not item.future.done():
                    raise RuntimeError(
                        "fused scoring engine closed")
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"fused scoring step not resolved within "
                        f"{STEP_TIMEOUT:.0f}s")

    def close(self, timeout: float = 10.0) -> None:
        """Stop the scorer (idempotent): queued work is still scored,
        then the thread exits; anything enqueued after close fails."""
        if self._closed.is_set() and not self._thread.is_alive():
            return
        try:
            self._queue.put_nowait(None)   # wake + mark closed
        except _queue.Full:
            self._closed.set()
        self._thread.join(timeout=timeout)
        self._closed.set()

    # -- scorer thread ---------------------------------------------------

    def _run(self) -> None:
        pending: Optional[_Step] = None
        while True:
            try:
                got = self._queue.get(timeout=0.05)
            except _queue.Empty:
                if pending is not None:
                    self._finish(pending)
                    pending = None
                if self._closed.is_set():
                    break
                continue
            if got is None:
                self._closed.set()
                continue
            items = [got]
            rows = got.rows
            # Coalesce whatever else is already waiting (bounded by
            # the ring row capacity) — cross-shard blocks from any
            # number of producers fold into ONE device step.
            while rows < self.max_step_rows:
                try:
                    nxt = self._queue.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:
                    self._closed.set()
                    break
                items.append(nxt)
                rows += nxt.rows
            _M_QDEPTH.set(self._queue.qsize())
            try:
                step = self._dispatch(items, rows)
            except Exception as e:   # noqa: BLE001 — fail the batch, not the loop
                logger.error("fused step dispatch failed: %s", e,
                             exc_info=True)
                for it in items:
                    if not it.future.done():
                        it.future.set_exception(e)
                if pending is not None:
                    # the failed dispatch already advanced the staging
                    # generation, so the NEXT successful dispatch
                    # would land back on the pending step's buffers —
                    # resolve it before that can happen
                    self._finish(pending)
                    pending = None
                continue
            # Double buffer: resolve the PREVIOUS step only after this
            # one is in flight — host staging of N+1 just overlapped
            # device scoring of N.
            if pending is not None:
                self._finish(pending)
            pending = step
            if self._queue.empty():
                # idle: don't sit on results waiting for traffic
                self._finish(pending)
                pending = None
        if pending is not None:
            self._finish(pending)
        # fail any stragglers enqueued after close
        while True:
            try:
                it = self._queue.get_nowait()
            except _queue.Empty:
                break
            if it is not None and not it.future.done():
                it.future.set_exception(
                    RuntimeError("fused scoring engine closed"))

    def _dispatch(self, items: List[_ScoreItem],
                  total_rows: int) -> Optional[_Step]:
        t0 = time.perf_counter()
        self._staging.advance()
        work: List[_ShardWork] = []
        states = []
        inputs = []
        for s, shard in enumerate(self.shards):
            segments: List[Tuple[int, Optional[np.ndarray], int, int]] = []
            n_s = 0
            for ii, it in enumerate(items):
                if s not in it.shard_rows:
                    continue
                idx = it.shard_rows[s]
                cnt = len(it.batch) if idx is None else len(idx)
                if cnt == 0:
                    continue
                segments.append((ii, idx, n_s, n_s + cnt))
                n_s += cnt
            if n_s == 0:
                continue

            def st(tag, shape, dtype, _s=s):
                return self._staging.get((_s, tag), shape, dtype)

            # Direct gather from the decode output into the staging
            # ring: only the columns the detectors read, no per-shard
            # ColumnarBatch copies.
            k6 = st("k6", (n_s, len(CONNECTION_KEY_COLUMNS)), np.int64)
            vals = st("vals", (n_s,), np.float64)
            times = st("times", (n_s,), np.int64)
            oct64 = st("oct", (n_s,), np.float64)
            pkt64 = st("pkt", (n_s,), np.float64)
            item_of = st("item", (n_s,), np.int32)
            row_of = st("row", (n_s,), np.int64)
            for ii, idx, a, b in segments:
                cols = items[ii].batch.columns
                for j, c in enumerate(CONNECTION_KEY_COLUMNS):
                    col = cols[c]
                    k6[a:b, j] = col if idx is None else col[idx]
                for buf, name in (
                        (vals, self.value_column),
                        (times, _EXTRA_COLUMNS[0]),
                        (oct64, _EXTRA_COLUMNS[1]),
                        (pkt64, _EXTRA_COLUMNS[2])):
                    col = cols[name]
                    buf[a:b] = col if idx is None else col[idx]
                item_of[a:b] = ii
                if idx is None:
                    row_of[a:b] = np.arange(b - a)
                else:
                    row_of[a:b] = idx
            splan = shard.streaming.build_plan(k6, vals, staging=st)
            if splan is None:
                # every row's series was dropped (capacity overflow):
                # the heavy-hitter half still advances, the streaming
                # half rides a no-op tile (all-padding slots gather-
                # clamp and scatter-drop, active all False)
                splan = StreamPlan(
                    slots=np.full(64, shard.streaming.capacity,
                                  np.int32),
                    x=np.zeros((1, 64), np.float32),
                    active=np.zeros((1, 64), bool),
                    row_idx=np.full((1, 64), -1, np.int64),
                    present=np.zeros(0, np.int64))
            hplan = _hh.build_hh_plan(
                k6[:, _KEY_DST], k6[:, _KEY_SRC], oct64, pkt64,
                staging=st)
            states.append(_ops.ShardStepState(
                shard.streaming.state, shard.heavy.cms,
                shard.heavy.kmeans))
            inputs.append(_ops.ShardInputs(
                slots=splan.slots, x=splan.x, active=splan.active,
                keys=hplan.keys, vols=hplan.vols, q=hplan.q,
                feats=hplan.feats, valid=hplan.valid))
            H2D_BYTES.inc(splan.slots.nbytes + splan.x.nbytes
                          + splan.active.nbytes)
            work.append(_ShardWork(shard, splan, hplan, times, vals,
                                   item_of, row_of, segments,
                                   k6[:, _KEY_DST], n_s))
        if not work:
            for it in items:
                if not it.future.done():
                    it.future.set_result(([], [], 0))
            return None
        new_states, outputs = self._call_kernel(tuple(states),
                                                tuple(inputs))
        # State stays device-resident between micro-batches: assign
        # the (possibly still-computing, async-dispatched) handles now.
        for w, ns in zip(work, new_states):
            w.shard.streaming.state = ns.stream
            w.shard.heavy.cms = ns.cms
            w.shard.heavy.kmeans = ns.km
        self.steps += 1
        self.coalesced_blocks += len(items)
        _M_STEPS.inc()
        _M_BLOCKS.observe(len(items))
        _M_ROWS.observe(total_rows)
        return _Step(items, work, outputs, t0)

    def _call_kernel(self, states, inputs):
        # No fallback between scan cores: a Pallas kernel that fails to
        # compile or run fails the step (and its requests) loudly —
        # which core runs is decided once, from the backend
        # (ops.fused_detector.pallas_mode), never from a caught error.
        return _ops.fused_step(states, inputs, alpha=self.alpha,
                               use_pallas=self._use_pallas,
                               interpret=self._interpret)

    def _finish(self, step: Optional[_Step]) -> None:
        if step is None:
            return
        items = step.items
        try:
            outs = jax.device_get(step.outputs)
            _M_STEP.observe(time.perf_counter() - step.t0)
            now = self.clock()
            per_hh: List[List] = [[] for _ in items]
            per_conn: List[List] = [[] for _ in items]
            per_n = [0] * len(items)
            dst_dict = None
            for it in items:
                d = it.batch.dicts.get("destinationIP")
                if d is not None:
                    dst_dict = d
                    break
            # Shards threshold in index order (work is built that
            # way): shard s sees this step's fresh totals for shards
            # < s and the previous totals for shards > s — the same
            # eventually-consistent discipline as the sharded path's
            # in-order visit.
            for w, out in zip(step.work, outs):
                if self._on_scored is not None:
                    self._on_scored(w.n, w.shard.index)
                extra = float(self._totals.sum()
                              - self._totals[w.shard.index])
                hits = w.shard.heavy.threshold(
                    w.hplan, out.est, out.total, out.dist, extra,
                    dst_dict)
                self._totals[w.shard.index] = \
                    w.shard.heavy.total_volume
                for alert, row, code in hits:
                    if row >= 0:
                        # shape outlier: row-scoped, exact attribution
                        per_hh[int(w.item_of[row])].append(alert)
                    else:
                        # heavy hitter: batch-scoped — attribute to
                        # every coalesced block that carried the
                        # destination (each would have alerted had it
                        # been scored alone; alerts are rare, the
                        # membership probe is per alert, not per row)
                        for ii, _, a, b in w.segments:
                            if np.any(w.dst[a:b] == code):
                                per_hh[ii].append(alert)
                anom = np.asarray(out.anomaly)
                if anom.any():
                    for t, c in np.argwhere(anom):
                        r = int(w.splan.row_idx[t, c])
                        if r < 0:
                            continue
                        ii = int(w.item_of[r])
                        per_n[ii] += 1
                        per_conn[ii].append(
                            (w, r, int(w.splan.present[c])))
            for ii, it in enumerate(items):
                latency = now - it.t_arrival
                conn: List[Dict[str, object]] = []
                # newest-survive cap, mirroring the sharded path's
                # per-request MAX_ALERTS decode bound
                for w, r, slot in per_conn[ii][-_MAX_DESCRIBED_ALERTS:]:
                    row = int(w.row_of[r])
                    d = alert_record(slot, w.times[r], w.vals[r],
                                     latency)
                    for c in CONNECTION_KEY_COLUMNS:
                        cd = it.batch.dicts.get(c)
                        code = int(it.batch[c][row])
                        d[c] = (cd.decode_one(code)
                                if cd is not None else code)
                    d["kind"] = "connection_anomaly"
                    conn.append(d)
                if not it.future.done():
                    it.future.set_result(
                        (per_hh[ii], conn, per_n[ii]))
        except Exception as e:   # noqa: BLE001 — fail the step's batches, not the loop
            logger.error("fused step resolve failed: %s", e,
                         exc_info=True)
            for it in items:
                if not it.future.done():
                    it.future.set_exception(e)
