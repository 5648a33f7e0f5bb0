"""On-demand XLA profiler capture over the system API.

SURVEY §5/§7.7: the reference's only runtime introspection is
scraping the Spark UI REST and ClickHouse system tables
(pkg/apiserver/utils/stats/clickhouse_stats.go:92-117 dumps
system.stack_trace); it has no accelerator profiler at all. Here the
manager can capture a real XLA profile of whatever the engine is
doing — device kernels, host callbacks, transfers — and hand back the
trace directory as a tar.gz that loads straight into TensorBoard /
Perfetto / xprof.

    POST /apis/system.theia.antrea.io/v1alpha1/profiles
        body: {"durationSeconds": N,      (default 3, capped)
               "pythonTracer": false}     (default off, below)
    GET  .../profiles                  → {"status": ..., "size": ...,
                                          "startedAt", "stoppedAt"}
    GET  .../profiles/theia-manager/download → tar.gz
    GET  .../profiles/theia-manager/summary  → obs/xplane.py's summary
                                               (computed when asked)

While a capture runs, the program's own spans and stages (obs/trace.py)
are written into the profiler's trace as host annotations, so the
device's idle gaps can be named by what the host did in them
(obs/xplane.py). The Python function tracer is OFF unless the request
asks for it: it slows the host 2-7x while it runs and makes the export
take most of a minute, which distorts exactly the host time the
annotations are there to show. `startedAt` / `stoppedAt` are taken
right after the profiler started and right before it is stopped, on
the wall clock and on the monotonic clock (which a client on the same
host shares), so a capture can be placed on another process's
timeline.

One capture at a time (the profiler cannot nest); bearer-token
protected with the rest of the system group.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import tarfile
import tempfile
import threading
import time
from typing import Dict, Optional

from ..obs import trace as _trace
from ..obs import xplane as _xplane
from ..utils import get_logger
from .collect import AsyncCollector

logger = get_logger("profiling")

MAX_DURATION_SECONDS = 60.0


def _now() -> Dict[str, float]:
    return {"wall": time.time(), "monotonic": time.monotonic()}


class ProfileManager(AsyncCollector):
    """Async single-flight XLA trace collection."""

    kind = "Profile"

    def __init__(self) -> None:
        super().__init__()
        self.duration: float = 0.0
        self.python_tracer = False
        self.started_at: Optional[Dict[str, float]] = None
        self.stopped_at: Optional[Dict[str, float]] = None
        self._summary: Optional[Dict[str, object]] = None

    def create(self, duration_seconds: float = 3.0,
               python_tracer: bool = False) -> Dict[str, object]:
        self.duration = min(max(float(duration_seconds), 0.1),
                            MAX_DURATION_SECONDS)
        self.python_tracer = bool(python_tracer)
        return super().create(self.duration, self.python_tracer)

    def _extra_status(self) -> Dict[str, object]:
        return {"durationSeconds": self.duration,
                "pythonTracer": self.python_tracer,
                "startedAt": self.started_at,
                "stoppedAt": self.stopped_at}

    def summary(self) -> Optional[Dict[str, object]]:
        """obs/xplane.py's summary of the collected capture (None when
        there is none); computed on the first ask."""
        data = self.data()
        if data is None:
            return None
        if self._summary is None:
            self._summary = _xplane.summarize_archive(data)
        return self._summary

    def _collect(self, duration: float, python_tracer: bool) -> bytes:
        import jax

        self.started_at = self.stopped_at = self._summary = None
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        options.host_tracer_level = 2

        def annotation(name: str):
            return jax.profiler.TraceAnnotation(
                name, **{_xplane.PROGRAM_STAT:
                         threading.current_thread().name})

        tmpdir = tempfile.mkdtemp(prefix="theia-xprof-")
        try:
            jax.profiler.start_trace(tmpdir, profiler_options=options)
            try:
                self.started_at = _now()
                _trace.set_annotation_factory(annotation)
                time.sleep(duration)
            finally:
                _trace.set_annotation_factory(None)
                self.stopped_at = _now()
                jax.profiler.stop_trace()
            with open(os.path.join(tmpdir, "capture.json"), "w") as f:
                json.dump({"startedAt": self.started_at,
                           "stoppedAt": self.stopped_at,
                           "durationSeconds": duration,
                           "pythonTracer": python_tracer}, f)
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w:gz") as tar:
                for root, _dirs, files in os.walk(tmpdir):
                    for f in files:
                        full = os.path.join(root, f)
                        tar.add(full,
                                arcname=os.path.relpath(full, tmpdir))
            logger.v(1).info("profile captured: %.1fs", duration)
            return buf.getvalue()
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
