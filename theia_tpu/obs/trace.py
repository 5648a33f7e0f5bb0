"""Tracing: trace-aware spans with cross-node context propagation, a
bounded ring of recent spans, and slowest-span exemplars per operation.

Three layers share one ring:

  * **Flight recorder** (PR 3): any code wraps itself in `span("op")`
    (context manager); finished spans land in a fixed-size ring
    (newest first on read) and the slowest span per operation is kept
    as an exemplar. Per-thread nesting links a span to the operation
    that enclosed it (`parent`).
  * **Distributed traces** (PR 11): each ingress — a producer
    `POST /ingest`, a coordinator `/query`, a job run, a replication
    ship — mints a W3C-traceparent-style context (128-bit trace id,
    64-bit span id, sampled flag) with `ingress_span(...)`, or adopts
    the one a remote caller stamped on the request
    (`traceparent: 00-<trace>-<span>-<flags>`). Every span that runs
    inside a traced ingress inherits the trace id and records its own
    span id plus its parent's, so the rings of every node in a cluster
    hold the pieces of one cross-node tree — `GET
    /debug/traces?trace=<id>` stitches them (manager/api.py).

  * **Stages** (PR 26): `stage(name, series)` brackets one part of
    the work INSIDE a span. At its two edges it reads `perf_counter`
    and the thread's `getrusage(RUSAGE_THREAD)` (CPU time = user +
    system, system time, minor page faults: one system call, which
    neighbouring edges share, `SHARED_READING_SECONDS`, and which a
    thread makes for at most `READING_BUDGET` of its time: a request
    in between is timed by the wall alone and the next one read
    stands for it), so a stage says what its thread did with its
    wall:
    wall − CPU is time OFF the CPU (waiting for the interpreter, a
    lock, the device, I/O); system time and faults are time ON the
    CPU but in the kernel (a fresh mapping paged in). Its self
    numbers (its own minus the stages nested in it) are summed into
    the enclosing span's `stagesMs[name]`, `stagesCpuMs`,
    `stagesSysMs`, `stagesFaults` (a stage that repeats, once per
    shard say, adds up) and observed on the series handed to it (a
    `StageSeries(...)` child: a wall histogram, a CPU histogram and
    a fault counter declared together; a plain histogram child takes
    the wall alone), so the slowest-span exemplar of an op carries
    the breakdown of its slowest request. `StageMarks` is the same
    for code that already announces its boundaries
    (`JobProgress.stage`). `part(name, series)` (PR 34) names a piece
    of the stage open around it, is reported whole (`partsMs`,
    `partsCpuMs`, `partsFaults`) and leaves that stage's own numbers
    whole. An ingress span also records `cpuMs` (the same clock over
    the request). `background(task)` is a span `bg.<task>` that also
    feeds `theia_background_seconds`, for the housekeeping that
    competes with requests (metrics-history tick, retention round,
    WAL segment roll, checkpoint, parts seal/merge). `watch_gc()`
    times the collector's pauses of every generation
    (`theia_gc_pause_seconds_total{generation}`,
    `theia_gc_collections_total{generation}`, and `stagesGcMs` on
    the stage open on the collecting thread) and reports generation
    2 as `bg.gc` spans as well.

While a profiler capture runs (manager/profiling.py hands this module
an annotation factory with `set_annotation_factory`), every span and
stage is ALSO a `jax.profiler.TraceAnnotation` of the same name, so
the program's spans sit on the host lines of the same `.xplane.pb`
as the device planes, on the profiler's clock (obs/xplane.py reads
them back and names each idle gap of the device by the host stage
open during it). With no capture the cost is one test of a module
attribute, and this package still imports no jax.

Sampling is **head-based and deterministic**: the mint-time decision
is a pure function of the trace id and `THEIA_TRACE_SAMPLE` (default
1.0 — sample everything), so the same trace id decides identically on
every node and every retry. An UNSAMPLED trace still times its spans
but retains nothing and stamps nothing on the wire — with
`THEIA_TRACE_SAMPLE=0` cluster traffic is byte-identical to a build
without tracing.

Span records are plain dicts (JSON-ready for GET /debug/traces):

    {"op", "startTime", "durationMs", "parent", "thread",
     # when stages ran inside it / on an ingress span:
     "stagesMs": {name: ms}, "partsMs": {name: ms}, "cpuMs",
     "stagesCpuMs", "stagesSysMs", "stagesFaults", "stagesGcMs",
     "partsCpuMs", "partsSysMs", "partsFaults",   # keys as in *Ms
     "usageWeight",     # the spans of the op this one was read for
     # present under a sampled trace context:
     "traceId", "spanId", "parentSpanId", "node", ...attrs}

Env knobs:

    THEIA_TRACE_RING     ring capacity (default 256; 0 disables
                         recording — span() still times, nothing is
                         kept, cluster-wide)
    THEIA_TRACE_SAMPLE   head-based sampling rate for ingress-minted
                         traces (default 1.0; 0 disables tracing —
                         no contexts, no wire headers)

Recording honors metrics.disable() (one kill switch for the whole obs
plane). Mutating an attr on the yielded span inside the `with` body
(`sp.attrs["rows"] = n`) annotates the record before it is published.
"""

from __future__ import annotations

import collections
import gc
import os
import random
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

from . import metrics as _metrics
from ..analysis.lockdep import named_lock

try:
    import resource as _resource
    _RUSAGE_THREAD: Optional[int] = _resource.RUSAGE_THREAD
    _resource.getrusage(_RUSAGE_THREAD)
except (ImportError, AttributeError, ValueError, OSError):
    _RUSAGE_THREAD = None
#: whether a stage can read its thread's system time and page faults
#: (Linux); without it those numbers are absent from spans and series
HAS_THREAD_RUSAGE = _RUSAGE_THREAD is not None
_perf_counter = time.perf_counter
#: a thread's reading is shared by the edges of its stages that fall
#: within this many seconds of it (one stage's exit and the next one's
#: enter, a stage that opens at once inside another): a reading is a
#: system call, 0.5 us on a plain Linux host and 6-25 us under a
#: sandboxed kernel, and about half of a request's stage edges are
#: such pairs. At most this much CPU moves between two neighbours.
SHARED_READING_SECONDS = 25e-6
#: the share of a thread's time its readings may take. A span that no
#: other encloses (a request, a job, a snapshot) has its stages read
#: if the readings of the last one that was read are paid off by the
#: time gone since it began; a span in between is timed by the wall
#: alone and the next one read stands for it too (its CPU and faults
#: are observed once for each). Where a reading is 0.5 us every span
#: that lasts a few milliseconds is read; under a sandboxed kernel
#: (20-50 us a reading, 46 a 435 ms ingest request) about one
#: request in ten is, one job in five to two in three, a snapshot
#: always.
READING_BUDGET = 5e-4

if HAS_THREAD_RUSAGE:
    def _read_usage() -> Tuple[float, Optional[float], Optional[int]]:
        """The calling thread's (CPU s, of it system s, minor faults)
        in one call. The kernel brings a running thread's times up to
        date at its scheduler tick, so one stage's CPU is good to a
        tick (1-10 ms) and a family's sum to much less."""
        ru = _resource.getrusage(_RUSAGE_THREAD)
        return ru[0] + ru[1], ru[1], ru[6]
else:
    def _read_usage() -> Tuple[float, Optional[float], Optional[int]]:
        return time.thread_time(), None, None


def _usage_at(now: float) -> Tuple[float, Optional[float], Optional[int]]:
    """The thread's reading for an edge at `now` (perf_counter): its
    last one if that is young enough, else a new one."""
    last = getattr(_local, "usage", None)
    if last is not None and now - last[0] < SHARED_READING_SECONDS:
        return last[1]
    used = _read_usage()
    _local.usage = (now, used)
    _local.read_cost = (getattr(_local, "read_cost", 0.0)
                        + _perf_counter() - now)
    return used


def _ring_capacity() -> int:
    raw = os.environ.get("THEIA_TRACE_RING", "")
    try:
        return max(0, int(raw)) if raw else 256
    except ValueError:
        return 256


def _sample_rate(env: Optional[str] = None) -> float:
    """THEIA_TRACE_SAMPLE, optionally overridden by a per-ingress env
    knob (e.g. THEIA_TRACE_SAMPLE_INGEST: high-rate ingresses get
    their own dial so turning them down does not blind the rest)."""
    raw = ""
    if env:
        raw = os.environ.get(env, "")
    if not raw:
        raw = os.environ.get("THEIA_TRACE_SAMPLE", "")
    try:
        return float(raw) if raw else 1.0
    except ValueError:
        return 1.0


#: distinct operations tracked for exemplars (bounds the dict; beyond
#: this, new op names are recorded in the ring but not as exemplars)
MAX_EXEMPLAR_OPS = 128

_lock = named_lock("trace.ring")
_ring: Deque[Dict[str, object]] = collections.deque(
    maxlen=_ring_capacity())
_slowest: Dict[str, Dict[str, object]] = {}
_local = threading.local()

#: set while a profiler capture runs (manager/profiling.py): name →
#: a context manager that writes the span into the profiler's trace
#: (jax.profiler.TraceAnnotation). None = no capture: nothing is built.
_annotate: Optional[Callable[[str], object]] = None

_M_BACKGROUND = _metrics.histogram(
    "theia_background_seconds",
    "Housekeeping that shares the interpreter with requests: one "
    "observation per run of a background task (also a span "
    "bg.<task>)", labelnames=("task",))

#: this process's node id, stamped on every trace-context span (set by
#: the manager when a cluster is configured; "" on standalone nodes)
_node_id = ""


def set_node_id(node_id: str) -> None:
    global _node_id
    _node_id = str(node_id or "")


def node_id() -> str:
    return _node_id


def set_annotation_factory(
        factory: Optional[Callable[[str], object]]) -> None:
    """Turn profiler annotations on (a factory) or off (None). The
    profiler's owner calls this around start_trace/stop_trace; spans
    and stages already open keep the state they started with."""
    global _annotate
    _annotate = factory


# -- trace context (W3C traceparent style) ---------------------------------

class TraceContext:
    """One position in a distributed trace: the 128-bit trace id, the
    current span's 64-bit id (what a child or remote callee records as
    its parent), and the head-based sampling decision."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str,
                 sampled: bool) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)


# ids need uniqueness and sampling spread, not crypto strength — and
# os.urandom is a syscall (~19us in sandboxed containers) paid per
# ingress on the query/ingest hot paths. One urandom-seeded PRNG
# (pid-mixed so forked workers diverge) mints ids at ~1us. CPython's
# getrandbits is C-level and GIL-atomic, so concurrent ingresses
# can't corrupt the generator state.
_id_rng = random.Random(int.from_bytes(os.urandom(16), "big")
                        ^ os.getpid())


def new_trace_id() -> str:
    return f"{_id_rng.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{_id_rng.getrandbits(64):016x}"


def sampled_for(trace_id: str, rate: Optional[float] = None) -> bool:
    """Deterministic head-based decision: a pure function of the trace
    id and the sampling rate, so every node (and every retry carrying
    the same id) decides identically."""
    if rate is None:
        rate = _sample_rate()
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    try:
        bits = int(trace_id[:8], 16)
    except ValueError:
        return False
    return bits / float(1 << 32) < rate


def format_traceparent(ctx: TraceContext) -> str:
    return (f"00-{ctx.trace_id}-{ctx.span_id}-"
            f"{'01' if ctx.sampled else '00'}")


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """`00-<32 hex>-<16 hex>-<2 hex>` → TraceContext, or None for
    anything malformed (a bad header from an old peer must degrade to
    a fresh trace, never to a 500)."""
    if not header:
        return None
    parts = str(header).strip().lower().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if (len(version) != 2 or len(trace_id) != 32
            or len(span_id) != 16 or len(flags) != 2):
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id, span_id,
                        sampled=bool(int(flags, 16) & 1))


def current_context() -> Optional[TraceContext]:
    """The innermost SAMPLED trace context on this thread (None outside
    any traced ingress, or when the trace is unsampled — callers use
    this to stamp outbound RPCs, and unsampled traces stamp nothing)."""
    stack = getattr(_local, "stack", None)
    if stack:
        ctx = stack[-1].context
        if ctx is not None and ctx.sampled:
            return ctx
    return None


def traceparent() -> Optional[str]:
    """The header value for the current sampled context, or None — the
    one call every outbound transport makes. No sampled context means
    NO header: with sampling off the wire is byte-identical to an
    untraced build."""
    ctx = current_context()
    return format_traceparent(ctx) if ctx is not None else None


class Span:
    """One in-flight operation; finished spans publish as dicts.

    Three flavors share this class:
      * `span(op)` — inherits the thread's context (legacy flight
        recorder when there is none: always published).
      * `ingress_span(op, traceparent=...)` — adopts the remote
        context or mints a fresh one (the trace root).
      * `child_span(op, ctx)` — continues an explicit context on
        another thread (pool workers running one request's fan-out).
    """

    __slots__ = ("op", "attrs", "_t0", "_start", "parent", "context",
                 "_parent_span_id", "_ingress", "_traceparent",
                 "_explicit_ctx", "_sample_env", "stages", "parts",
                 "stage_usage", "part_usage", "usage_weight", "_stage",
                 "_cpu0", "_ann", "_hist")

    def __init__(self, op: str, attrs: Dict[str, object],
                 ingress: bool = False,
                 traceparent: Optional[str] = None,
                 ctx: Optional[TraceContext] = None,
                 sample_env: Optional[str] = None) -> None:
        self.op = op
        self.attrs = attrs
        self.parent: Optional[str] = None
        self.context: Optional[TraceContext] = None
        self._parent_span_id: Optional[str] = None
        self._ingress = ingress
        self._traceparent = traceparent
        self._explicit_ctx = ctx
        self._sample_env = sample_env
        self._t0 = 0.0
        self._start = 0.0
        #: stage name → summed self seconds (None until one runs)
        self.stages: Optional[Dict[str, float]] = None
        #: part name → summed seconds, each also inside its stage's
        self.parts: Optional[Dict[str, float]] = None
        #: stage name → [CPU s, system s, minor faults, collector s],
        #: self numbers like `stages`; system and faults None without
        #: RUSAGE_THREAD. A stage added without them has no entry.
        self.stage_usage: Optional[Dict[str, List]] = None
        #: part name → the same (no collection is put on a part)
        self.part_usage: Optional[Dict[str, List]] = None
        #: how many spans of this op on this thread the usage of this
        #: one's stages stands for (itself and those not read since
        #: the last that was, READING_BUDGET); 0 = its stages are
        #: timed by the wall alone
        self.usage_weight = 1
        self._stage: Optional["Stage"] = None   # innermost open stage
        self._cpu0 = 0.0
        self._ann = None
        self._hist = None

    def _bind_context(self, enclosing: Optional["Span"]) -> None:
        if self._ingress:
            if _sample_rate(self._sample_env) <= 0.0:
                # tracing off is a LOCAL kill switch: no context
                # minted, nothing retained, no bytes on the wire —
                # even when a peer's sampled traceparent arrives
                self.context = TraceContext("", "", False)
                return
            remote = parse_traceparent(self._traceparent)
            if remote is not None:
                trace_id = remote.trace_id
                self._parent_span_id = remote.span_id
                sampled = remote.sampled
            else:
                trace_id = new_trace_id()
                sampled = sampled_for(trace_id,
                                      _sample_rate(self._sample_env))
            self.context = TraceContext(trace_id, new_span_id(),
                                        sampled)
            return
        parent_ctx = self._explicit_ctx
        if parent_ctx is None and enclosing is not None:
            parent_ctx = enclosing.context
        if parent_ctx is None:
            return                      # legacy span: no trace context
        if not parent_ctx.sampled:
            self.context = TraceContext(parent_ctx.trace_id, "", False)
            return
        self._parent_span_id = parent_ctx.span_id
        self.context = TraceContext(parent_ctx.trace_id, new_span_id(),
                                    True)

    def cpu_seconds(self) -> float:
        """Thread CPU time since an ingress span was entered."""
        return time.thread_time() - self._cpu0

    def __enter__(self) -> "Span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        enclosing = stack[-1] if stack else None
        self.parent = enclosing.op if enclosing is not None else None
        self._bind_context(enclosing)
        stack.append(self)
        factory = _annotate
        if factory is not None:
            self._ann = factory(self.op)
            self._ann.__enter__()
        self._start = time.time()
        if self._ingress:
            self._cpu0 = time.thread_time()
        t0 = self._t0 = time.perf_counter()
        if enclosing is not None:
            self.usage_weight = enclosing.usage_weight
        else:
            skipped = _local.__dict__.setdefault("skipped", {})
            if t0 >= getattr(_local, "read_after", 0.0):
                self.usage_weight = 1 + skipped.pop(self.op, 0)
                _local.read_cost = 0.0
            else:
                self.usage_weight = 0
                skipped[self.op] = skipped.get(self.op, 0) + 1
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        duration = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        stack = getattr(_local, "stack", None)
        if stack:
            stack.pop()
            if not stack and self.usage_weight:
                _local.read_after = self._t0 + getattr(
                    _local, "read_cost", 0.0) / READING_BUDGET
        if self._hist is not None:
            self._hist.observe(duration)
        if not _metrics.enabled():
            return
        if self.context is not None and not self.context.sampled:
            return   # unsampled trace: timed, never retained
        record: Dict[str, object] = {
            "op": self.op,
            "startTime": self._start,
            "durationMs": round(duration * 1e3, 4),
            "parent": self.parent,
            "thread": threading.current_thread().name,
        }
        if self.context is not None:
            record["traceId"] = self.context.trace_id
            record["spanId"] = self.context.span_id
            if self._parent_span_id:
                record["parentSpanId"] = self._parent_span_id
            record["node"] = _node_id
        if self._ingress:
            record["cpuMs"] = round(self.cpu_seconds() * 1e3, 4)
        if self.stages:
            record["stagesMs"] = _ms(self.stages)
            _usage_fields(record, "stages", self.stage_usage)
        if self.parts:
            record["partsMs"] = _ms(self.parts)
            _usage_fields(record, "parts", self.part_usage)
        if self.usage_weight and (self.stages or self.parts):
            # its stages were read, for this many spans of the op
            record["usageWeight"] = self.usage_weight
        if exc_type is not None:
            record["error"] = exc_type.__name__
        record.update(self.attrs)
        _publish(record)


def _ms(seconds: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v * 1e3, 4) for k, v in seconds.items()}


def _usage_fields(record: Dict[str, object], prefix: str,
                  usage: Optional[Dict[str, List]]) -> None:
    """`<prefix>CpuMs`, `SysMs`, `Faults` and, for stages, `GcMs`
    (only the stages a collection paused) beside `<prefix>Ms`."""
    if not usage:
        return
    record[prefix + "CpuMs"] = _ms({k: u[0] for k, u in usage.items()})
    if HAS_THREAD_RUSAGE:
        record[prefix + "SysMs"] = _ms(
            {k: u[1] for k, u in usage.items()})
        record[prefix + "Faults"] = {k: u[2] for k, u in usage.items()}
    paused = {k: u[3] for k, u in usage.items() if u[3]}
    if paused:
        record[prefix + "GcMs"] = _ms(paused)


class StageSeries:
    """The series of one family of stages, declared together: the
    wall histogram `<stem>_seconds`, the thread's CPU time of the same
    runs `<stem>_cpu_seconds` and its minor page faults
    `<stem>_minor_faults_total`, with the same labels. `labels(...)`
    gives what `stage()` / `part()` take. The family has labels (an
    unlabelled histogram is handed to `stage()` as it is and takes
    the wall alone)."""

    def __init__(self, name: str, help_text: str,
                 labelnames: Tuple[str, ...]) -> None:
        if not name.endswith("_seconds") or not labelnames:
            raise ValueError(f"{name}: a stage family is a labelled "
                             f"`<stem>_seconds`")
        stem = name[:-len("_seconds")]
        labelnames = tuple(labelnames)
        self.wall = _metrics.histogram(name, help_text, labelnames)
        self.cpu = _metrics.histogram(
            stem + "_cpu_seconds",
            f"Thread CPU time (user + system) of the runs {name} "
            f"times, self time like it: wall - CPU is time off the "
            f"CPU (interpreter, lock, device, I/O)", labelnames)
        self.faults = _metrics.counter(
            stem + "_minor_faults_total",
            f"Minor page faults of the thread in the runs {name} "
            f"times (RUSAGE_THREAD ru_minflt; no sample where the "
            f"platform lacks it)", labelnames)

    def labels(self, **labels) -> "StageUsage":
        return StageUsage(
            self.wall.labels(**labels), self.cpu.labels(**labels),
            self.faults.labels(**labels) if HAS_THREAD_RUSAGE else None)


class StageUsage:
    """One child of a `StageSeries`: where a stage's wall seconds, CPU
    seconds and faults go (`Stage.__exit__` observes the three;
    `faults` is None without RUSAGE_THREAD)."""

    __slots__ = ("wall", "cpu", "faults")

    def __init__(self, wall, cpu, faults) -> None:
        self.wall, self.cpu, self.faults = wall, cpu, faults


class Stage:
    """One timed part of the work inside the thread's innermost span
    (see the module docstring). Reusable only sequentially. With
    `within` it is a named part of the stage that is open around it:
    reported whole, and that stage keeps the part's numbers as its
    own. After a run `seconds`, `cpu_seconds`, `sys_seconds`, `faults`
    and `gc_seconds` hold its own numbers: system time and faults
    None without RUSAGE_THREAD, all three None in a span whose stages
    are not read (READING_BUDGET)."""

    __slots__ = ("name", "hist", "within", "seconds", "cpu_seconds",
                 "sys_seconds", "faults", "gc_seconds", "_span",
                 "_outer", "_t0", "_u0", "_weight", "_n_wall", "_n_cpu",
                 "_n_sys", "_n_flt", "_ann")

    def __init__(self, name: str, hist=None, within: bool = False) -> None:
        self.name = name
        self.hist = hist                # StageUsage or a histogram child
        self.within = within
        self.seconds = 0.0              # own numbers of the last run
        self.cpu_seconds: Optional[float] = None
        self.sys_seconds: Optional[float] = None
        self.faults: Optional[int] = None
        self.gc_seconds = 0.0           # collector pauses while innermost
        self._span: Optional[Span] = None
        self._outer: Optional["Stage"] = None
        # wall, CPU, system seconds and faults of the stages nested in
        # this run, which are theirs and not this stage's own
        self._n_wall = self._n_cpu = self._n_sys = 0.0
        self._n_flt = 0
        self._weight = 1                # of the span it runs in
        self._ann = None

    def __enter__(self) -> "Stage":
        stack = getattr(_local, "stack", None)
        if stack:
            sp = self._span = stack[-1]
            self._outer = sp._stage
            sp._stage = self
            self.gc_seconds = 0.0
            self._weight = sp.usage_weight
        else:
            self._weight = 1            # outside any span: always read
        factory = _annotate
        if factory is not None:
            self._ann = factory(self.name)
            self._ann.__enter__()
        # the thread's usage is read inside the wall clock's pair at
        # both edges, so a stage's wall holds what the reads cost
        t0 = self._t0 = _perf_counter()
        self._u0 = _usage_at(t0) if self._weight else None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._u0 is not None:
            cpu0, sys0, flt0 = self._u0
            cpu, sys_s, flt = _usage_at(_perf_counter())
            cpu -= cpu0
            if sys0 is not None:
                sys_s -= sys0
                flt -= flt0
        else:
            cpu = sys_s = sys0 = flt = None
        own = _perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        within = self.within
        if not within:                  # a part is reported whole
            # the enclosing stage that is not a part loses this run
            outer = _not_a_part(self._outer)
            if outer is not None:
                outer._n_wall += own
                if cpu is not None:
                    outer._n_cpu += cpu
                if sys0 is not None:
                    outer._n_sys += sys_s
                    outer._n_flt += flt
            if self._n_wall:            # stages ran nested in this one
                own -= self._n_wall
                if cpu is not None:
                    cpu -= self._n_cpu
                if sys0 is not None:
                    sys_s -= self._n_sys
                    flt -= self._n_flt
                self._n_wall = self._n_cpu = self._n_sys = 0.0
                self._n_flt = 0
        self.seconds, self.cpu_seconds = own, cpu
        self.sys_seconds, self.faults = sys_s, flt
        sp = self._span
        if sp is not None:
            sp._stage = self._outer
            self._span = self._outer = None
            if within:
                if sp.parts is None:
                    sp.parts, sp.part_usage = {}, {}
                seconds, usage = sp.parts, sp.part_usage
            else:
                if sp.stages is None:
                    sp.stages, sp.stage_usage = {}, {}
                seconds, usage = sp.stages, sp.stage_usage
            _sum_into(seconds, usage, self)
        hist = self.hist
        if hist is not None:
            if type(hist) is StageUsage:
                hist.wall.observe(own)
                if cpu is not None:     # for the runs not read as well
                    hist.cpu.observe(cpu, times=self._weight)
                    if flt:
                        hist.faults.inc(flt * self._weight)
            else:
                hist.observe(own)


def _not_a_part(st: Optional[Stage]) -> Optional[Stage]:
    """`st`, or the nearest open stage around it that is no part."""
    while st is not None and st.within:
        st = st._outer
    return st


def _sum_into(seconds: Dict[str, float], usage: Dict[str, List],
              st: Stage) -> None:
    """Add a finished stage's own numbers to a span's totals."""
    name = st.name
    seconds[name] = seconds.get(name, 0.0) + st.seconds
    if st.cpu_seconds is None:
        return
    have = usage.get(name)
    if have is None:
        usage[name] = [st.cpu_seconds, st.sys_seconds, st.faults,
                       st.gc_seconds]
        return
    have[0] += st.cpu_seconds
    if st.sys_seconds is not None:
        have[1] += st.sys_seconds
        have[2] += st.faults
    have[3] += st.gc_seconds


def stage(name: str, hist=None) -> Stage:
    """Context manager around one stage of the enclosing span:

        with stage("detector.plan", _M_PLAN):
            plan = self.build_plan(keys, values)

    `hist` is a `StageSeries(...)` child (wall, CPU and faults), a
    histogram child (wall alone) or None."""
    return Stage(name, hist)


def part(name: str, hist=None) -> Stage:
    """Context manager around a named part of the stage that is open:

        with part("job.score.kernel", _M_PART):
            out = jax.block_until_ready(kernel(x, mask))

    Timed and annotated like a stage, put whole on the span's
    `partsMs` (`partsCpuMs`, `partsFaults`) and taken from nothing:
    the enclosing stage's numbers still hold it, so `stagesMs` adds
    up to the span as before and the parts of a stage explain its
    time without changing it."""
    return Stage(name, hist, within=True)


def add_stage(st: Stage) -> None:
    """Put a stage that was timed on another thread (a pool worker
    running one leg of this request) on this thread's innermost span,
    with that thread's CPU and faults: it ran beside the span's own
    stages, so it is added as it was measured and taken from no
    enclosing stage."""
    sp = current_span()
    if sp is None:
        return
    if sp.stages is None:
        sp.stages, sp.stage_usage = {}, {}
    _sum_into(sp.stages, sp.stage_usage, st)


class StageMarks:
    """Stages for code that announces boundaries instead of nesting
    (`mark("job.read", h1) … mark("job.score", h2) … end()`): each
    mark closes the stage before it. The mark that closes a stage
    comes from the thread that opened it."""

    __slots__ = ("_open",)

    def __init__(self) -> None:
        self._open: Optional[Stage] = None

    def mark(self, name: str, hist=None) -> None:
        self.end()
        self._open = Stage(name, hist).__enter__()

    def end(self) -> None:
        st, self._open = self._open, None
        if st is not None:
            st.__exit__(None, None, None)


def _publish(record: Dict[str, object]) -> None:
    if _gc_done:
        _flush_gc()
    _retain(record)


def _exemplar_rank(record: Dict[str, object]) -> Tuple[bool, float]:
    """An op's exemplar is its slowest span whose stages were read,
    and its slowest span while none was (READING_BUDGET): the
    exemplar is there to say what the slow instance did."""
    return "usageWeight" in record, record["durationMs"]


def _retain(record: Dict[str, object]) -> None:
    # THEIA_TRACE_RING=0 promises NO span retention — exemplars are
    # retained state too (attrs carry stream ids and job names), so
    # the knob turns them off with the ring.
    if not _ring.maxlen:
        return
    op = str(record["op"])
    with _lock:
        _ring.append(record)
        best = _slowest.get(op)
        if best is None:
            if len(_slowest) < MAX_EXEMPLAR_OPS:
                _slowest[op] = record
        elif _exemplar_rank(record) > _exemplar_rank(best):
            _slowest[op] = record


def span(op: str, **attrs: object) -> Span:
    """Context manager timing one operation:

        with span("ingest.request", stream=sid) as sp:
            ...
            sp.attrs["rows"] = n
    """
    return Span(op, dict(attrs))


def ingress_span(op: str, traceparent: Optional[str] = None,
                 sample_env: Optional[str] = None,
                 **attrs: object) -> Span:
    """A request-boundary span: adopts the remote trace context from a
    `traceparent` header, or mints a fresh (deterministically sampled)
    one. Everything nested under it — including on other threads via
    child_span — shares the trace id. `sample_env` names an env knob
    that overrides THEIA_TRACE_SAMPLE for THIS ingress (high-rate
    paths get their own dial)."""
    return Span(op, dict(attrs), ingress=True, traceparent=traceparent,
                sample_env=sample_env)


def child_span(op: str, ctx: Optional[TraceContext],
               **attrs: object) -> Span:
    """Continue an explicit context on ANOTHER thread (a pool worker
    running one slice of a request captured with current_context()).
    ctx=None means the originating request was untraced/unsampled —
    the child span times but retains nothing."""
    if ctx is None:
        ctx = TraceContext("", "", False)
    return Span(op, dict(attrs), ctx=ctx)


def background(task: str, **attrs: object) -> Span:
    """Span `bg.<task>` around one run of a housekeeping task; its
    duration also lands in theia_background_seconds{task=...}."""
    sp = Span("bg." + task, dict(attrs))
    sp._hist = _M_BACKGROUND.labels(task=task)
    return sp


# -- garbage collections ------------------------------------------------------
# A collection stops whichever thread tripped it, mid-request and
# possibly while that thread holds this module's ring lock or a
# histogram's. So the callback takes no lock and allocates next to
# nothing: it adds the pause to two module-level lists (read by the
# two counters when they are collected) and to the stage open on the
# collecting thread, and notes a full collection on a deque, which the
# next span to publish (or reader of the ring) turns into a `bg.gc`
# span and a theia_background_seconds{task="gc"} observation.
_gc_t0: Optional[float] = None         # perf_counter at "start"
_gc_open: Optional[tuple] = None       # a full collection's (wall, ann)
_gc_done: Deque[tuple] = collections.deque(maxlen=64)
#: seconds paused and collections, by generation (one collection runs
#: at a time, so the callback is their only writer)
_gc_pause = [0.0, 0.0, 0.0]
_gc_count = [0, 0, 0]

_M_GC_PAUSE = _metrics.counter(
    "theia_gc_pause_seconds_total",
    "Seconds the cyclic collector stopped the thread that tripped "
    "it, by generation (timed once watch_gc() is on, as in the "
    "manager); the pause is also on the stage open on that thread "
    "(stagesGcMs)", labelnames=("generation",))
_M_GC_COLLECTIONS = _metrics.counter(
    "theia_gc_collections_total",
    "Collections of the cyclic collector, by generation",
    labelnames=("generation",))
for _gen in range(3):
    _M_GC_PAUSE.labels(generation=str(_gen)).set_callback(
        lambda g=_gen: _gc_pause[g])
    _M_GC_COLLECTIONS.labels(generation=str(_gen)).set_callback(
        lambda g=_gen: _gc_count[g])


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0, _gc_open
    gen = info.get("generation", 0)
    if phase == "start":
        if gen == 2:
            ann = None
            factory = _annotate
            if factory is not None:
                ann = factory("bg.gc")
                ann.__enter__()
            _gc_open = (time.time(), ann)
        _gc_t0 = time.perf_counter()
        return
    if _gc_t0 is None:                  # watched from mid-collection
        return
    duration = time.perf_counter() - _gc_t0
    _gc_t0 = None
    _gc_pause[gen] += duration
    _gc_count[gen] += 1
    stack = getattr(_local, "stack", None)
    if stack:
        st = _not_a_part(stack[-1]._stage)
        if st is not None:
            st.gc_seconds += duration
    if gen == 2 and _gc_open is not None:
        start, ann = _gc_open
        _gc_open = None
        if ann is not None:
            ann.__exit__(None, None, None)
        _gc_done.append((start, duration, info.get("collected", 0),
                         current_op(),
                         threading.current_thread().name))


def _flush_gc() -> None:
    hist = _M_BACKGROUND.labels(task="gc")
    while True:
        try:
            start, duration, collected, parent, thread = \
                _gc_done.popleft()
        except IndexError:
            return
        hist.observe(duration)
        if _metrics.enabled():
            _retain({"op": "bg.gc", "startTime": start,
                     "durationMs": round(duration * 1e3, 4),
                     "parent": parent, "thread": thread,
                     "collected": collected})


def watch_gc() -> None:
    """Time every collection's pause and report the full ones as
    `bg.gc` spans (the manager calls this once at start;
    idempotent)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def unwatch_gc() -> None:
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def current_span() -> Optional[Span]:
    """The innermost open span on this thread (None outside any)."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def current_op() -> Optional[str]:
    """The innermost span op on this thread (None outside any span)."""
    stack = getattr(_local, "stack", None)
    return stack[-1].op if stack else None


def recent(limit: int = 100) -> List[Dict[str, object]]:
    """Most recent finished spans, newest first."""
    _flush_gc()
    with _lock:
        out = list(_ring)
    out.reverse()
    return out[:max(0, limit)]


def spans_for_trace(trace_id: str) -> List[Dict[str, object]]:
    """Every retained span of one trace, oldest first — the local half
    of the cluster-stitched GET /debug/traces?trace=<id>."""
    tid = str(trace_id).strip().lower()
    with _lock:
        return [dict(rec) for rec in _ring
                if rec.get("traceId") == tid]


def slowest() -> Dict[str, Dict[str, object]]:
    """op → its slowest recorded span (the exemplar)."""
    _flush_gc()
    with _lock:
        return {op: dict(rec) for op, rec in sorted(_slowest.items())}


def reset() -> None:
    """Drop the ring and exemplars (tests)."""
    with _lock:
        _ring.clear()
        _slowest.clear()
