"""Runs one cell: manager child, worker children, phases, checks, result.

The parent (this process) never imports jax: a parent that has touched
JAX holds the chip and its children would fail. The manager child gets
`JAX_PLATFORMS=tpu`, so a machine without a chip is an error in JAX
itself and never a quiet CPU run; the harness then exits non-zero and
prints no result line. `platform="cpu"` exists only for selftest.py's
rehearsal, whose output is labelled a rehearsal and carries no metric.

Phases of a run (every run of a cell goes through the same ones, from
the same starting state):

  resolve    every check, key law, worker role, reduction and kernel
             function the cell's files name is found, built in or as a
             file (extend.py), and every tolerance a check declares is
             in the traffic file's `limits`: a name nothing provides
             ends the run here, before any child exists
  start      manager child up (compile cache at a fixed path inside the
             checkout), workers up with their inputs prepared — in
             parallel, since the manager's start is the longer
  preload    the retained window of the traffic file, acked
  warm-up    a fixed number of blocks / one job / one pass over the
             panels, acked: every device shape the window will use
  window     `--seconds` seconds from a common instant; nothing is
             compared with a reference in here, only recorded
  quiesce    workers stopped, acked == sent, store and engine settled
  probe      where the traffic file asks for it: every producer in turn
             sends its next blocks one at a time and reads the alert
             counters around each (check.check_detector_alerts)
  check      the plain reference over the generator's own rows against
             what the manager now answers (benchmarks/check.py)
  result     one JSON object on the last line of stdout
"""

from __future__ import annotations

import io
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tarfile
import time
import urllib.request
from typing import Dict, List, Optional

from . import check as _check
from . import extend as _extend
from . import gen as _gen
from . import manifest as _manifest
from . import prom as _prom
from . import reductions as _reductions
from .extend import RunFailed        # noqa: F401  (run.py and tests catch it here)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")
CACHE = os.path.join(HERE, ".cache")

SYSTEM = "/apis/system.theia.antrea.io/v1alpha1"
STATS = "/apis/stats.theia.antrea.io/v1alpha1"

BANNER = re.compile(
    r"theia-manager runtime: platform=(\S+) "
    r"device_kind='([^']*)' devices=(\d+) native=(\S+)")
COMPILED = re.compile(rb"Finished XLA compilation of (.*?) in ([0-9.]+) sec")
CACHE_HIT = re.compile(rb"Persistent compilation cache hit")


def log(msg: str) -> None:
    print(msg, flush=True)


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            lines = f.read().splitlines()
    except OSError:
        return f"(no log at {path})"
    return "\n".join("    | " + ln[:300] for ln in lines[-n:])


def http_get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def http_json(url: str, doc: Optional[Dict] = None, timeout: float = 60.0):
    data = json.dumps(doc).encode() if doc is not None else None
    req = urllib.request.Request(
        url, data=data, method="POST" if data is not None else "GET",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


# -- children ---------------------------------------------------------------

class Children:
    """Every process the run starts, so that all can be stopped and
    waited for whatever happens."""

    def __init__(self) -> None:
        self.procs: List[subprocess.Popen] = []

    def add(self, proc: subprocess.Popen) -> subprocess.Popen:
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
        for proc in self.procs:
            proc.wait()


#: load workers never share a core with the manager; more than this
#: many of them share these cores among themselves (a producer that
#: sends one pre-encoded block a second needs little of one)
MAX_WORKER_CORES = 4


def plan_cores(n_workers: int) -> Dict[str, Optional[List[int]]]:
    """Disjoint core sets where the host has them: up to
    MAX_WORKER_CORES cores from the top for the load workers, the rest
    for the manager (its request threads, insert pool, scorer and
    XLA's own). With fewer than two cores left for the manager nothing
    is pinned."""
    cores = sorted(os.sched_getaffinity(0))
    n = min(n_workers, MAX_WORKER_CORES)
    log(f"host: {len(cores)} cores usable, {n_workers} load workers on "
        f"{n} of them")
    if len(cores) - n < 2:
        log("host: too few cores to pin; manager and workers share them")
        return {"manager": None, "workers": None}
    return {"manager": cores[:len(cores) - n],
            "workers": cores[len(cores) - n:]}


def child_env(platform: str, extra: Dict[str, str]) -> Dict[str, str]:
    """The ambient environment minus every THEIA_* knob (a
    configuration states its own) and minus BENCH_RUN, which is the
    driver's and no business of the benchmark's."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("THEIA_") and k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = platform
    env["JAX_LOG_COMPILES"] = "1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if platform != "cpu" and not env.get("JAX_COMPILATION_CACHE_DIR"):
        # fixed path inside the checkout: the path is part of the key
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    env.update(extra)
    return env


class Manager:
    def __init__(self, children: Children, config: Dict, work: str,
                 platform: str) -> None:
        self.log = os.path.join(work, "manager.log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.addr = f"http://127.0.0.1:{port}"
        cmd = [sys.executable, "-m", "theia_tpu.manager",
               "--db", os.path.join(work, "db.npz"),
               "--wal-dir", os.path.join(work, "wal"),
               "--port", str(port)] + list(config.get("manager_args", []))
        self.t0 = time.monotonic()
        self._log_f = open(self.log, "wb")
        self.proc = children.add(subprocess.Popen(
            cmd, env=child_env(platform, config.get("env", {})), cwd=ROOT,
            stdout=self._log_f, stderr=subprocess.STDOUT,
            start_new_session=True))
        self.start_s = 0.0
        self.runtime: Dict[str, object] = {}

    def wait_ready(self, timeout: float = 900.0) -> None:
        while time.monotonic() - self.t0 < timeout:
            if self.proc.poll() is not None:
                raise RunFailed(f"manager exited {self.proc.returncode} "
                                f"at start\n{tail(self.log)}")
            with open(self.log, errors="replace") as f:
                m = BANNER.search(f.read())
            if m:
                try:
                    http_get(self.addr + "/healthz", timeout=5)
                except OSError:
                    pass
                else:
                    self.start_s = time.monotonic() - self.t0
                    self.runtime = {"platform": m.group(1),
                                    "kind": m.group(2),
                                    "count": int(m.group(3)),
                                    "native": m.group(4)}
                    return
            time.sleep(0.1)
        raise RunFailed(f"manager not ready in {timeout:.0f}s\n"
                        f"{tail(self.log)}")

    def get(self, path: str, timeout: float = 120.0) -> bytes:
        return http_get(self.addr + path, timeout)

    def json(self, path: str, doc: Optional[Dict] = None,
             timeout: float = 120.0):
        return http_json(self.addr + path, doc, timeout)

    def metrics(self) -> Dict[str, float]:
        return _prom.parse(self.get("/metrics").decode())

    def log_size(self) -> int:
        self._log_f.flush()
        return os.path.getsize(self.log)

    def log_bytes(self, start: int = 0, end: Optional[int] = None) -> bytes:
        with open(self.log, "rb") as f:
            f.seek(start)
            return f.read(None if end is None else end - start)

    def device(self) -> Dict[str, object]:
        """As JAX reports it inside the manager (stats API), with the
        peak bytes in use on the fullest chip."""
        infos = self.json(f"{STATS}/clickhouse/deviceInfo")["deviceInfos"]
        peaks = [int(d.get("memoryPeakBytesInUse", 0)) for d in infos]
        return {"platform": infos[0].get("platform"),
                "kind": infos[0].get("deviceKind"),
                "count": len(infos), "memory_peak_bytes": max(peaks)}


class Worker:
    #: the worker's entry; benchmarks/tests puts a broken one here
    module = "benchmarks.client"

    def __init__(self, children: Children, spec: Dict, work: str,
                 cores: Optional[List[int]]) -> None:
        self.spec = spec
        self.name = f"{spec['role']}-{spec['instance']}"
        self.out = spec["out"] = os.path.join(work, self.name + ".out.json")
        path = os.path.join(work, self.name + ".spec.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        self.err = os.path.join(work, self.name + ".err")
        self._err_f = open(self.err, "wb")
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = children.add(subprocess.Popen(
            [sys.executable, "-m", self.module, path], env=env,
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err_f, start_new_session=True))
        if cores:
            os.sched_setaffinity(self.proc.pid, cores)

    def send(self, line: str) -> None:
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()

    def expect(self, event: str) -> Dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed(f"worker {self.name} ended before '{event}'\n"
                            f"{tail(self.err)}")
        doc = json.loads(line)
        if doc.get("event") != event:
            raise RunFailed(f"worker {self.name}: expected '{event}', "
                            f"got {doc}")
        return doc

    def result(self) -> Dict:
        with open(self.out) as f:
            return json.load(f)


def worker_specs(traffic: Dict, seed: int, addr: str, scale: Dict,
                 base: str) -> List[Dict]:
    """One spec per worker process from the traffic file's `workers`:
    the group's own keys (whatever a role reads), and `addr`, `seed`,
    `traffic`, `base` (where extension files are looked for) and
    `instance` (which of its role's workers it is)."""
    specs = []
    producer = 0
    for group in traffic["workers"]:
        for i in range(int(group.get("count", 1))):
            spec = {k: v for k, v in group.items() if k != "count"}
            spec.update(addr=addr, seed=seed, traffic=traffic, base=base,
                        instance=sum(s["role"] == group["role"]
                                     for s in specs))
            if group["role"] == "producer":
                spec["producer"] = producer
                sched = group.get("schedule")
                if sched:
                    spec["schedule"] = {
                        "period_s": sched["period_s"],
                        "offset_s": producer * sched.get("stagger_s", 0.0)}
                for key in ("preload_blocks", "warm_blocks",
                            "prepared_blocks", "probe_blocks"):
                    if key in scale:
                        spec[key] = scale[key]
                producer += 1
            specs.append(spec)
    return specs


# -- phases -----------------------------------------------------------------

def all_do(workers: List[Worker], command: str, event: str) -> List[Dict]:
    for w in workers:
        w.send(command)
    for w in workers:
        w.expect(event)
    return [w.result() for w in workers]


def quiesce(mgr: Manager, acked_rows: int, timeout: float = 60.0) -> Dict:
    """Wait until every acked row is counted ingested and the store's
    and the engine's background state reads the same twice, half a
    second apart. Returns the last /healthz."""
    deadline = time.monotonic() + timeout
    last = None
    while True:
        h = mgr.json("/healthz")
        ing = h.get("ingest", {})
        store = h.get("store", {})
        state = (ing.get("rowsIngested"),
                 ing.get("engine", {}).get("queueDepth", 0),
                 store.get("flowRows"),
                 json.dumps(store.get("parts", {}), sort_keys=True),
                 h.get("wal", {}).get("lagRecords", 0),
                 h.get("jobs", {}).get("running", 0))
        settled = (state == last and ing.get("rowsIngested") == acked_rows
                   and not state[1] and not state[4] and not state[5])
        if settled or time.monotonic() > deadline:
            if not settled:
                log(f"quiesce: not settled after {timeout:.0f}s: {state}")
            return h
        last = state
        time.sleep(0.5)


#: the longest the profiler was seen to take from being asked to
#: running (1-2 s on the chip host), with room
TRACE_START_S = 3.0


def capture_trace(mgr: Manager, seconds: float) -> None:
    mgr.json(f"{SYSTEM}/profiles", {"durationSeconds": seconds})


def fetch_trace(mgr: Manager, work: str, platform: str,
                timeout: float = 120.0) -> Optional[Dict]:
    """Download the manager's profile, unpack it and reduce it in a
    child pinned to the CPU backend (reading an .xplane.pb needs jax;
    this parent stays off it, and by now the window is closed)."""
    deadline = time.monotonic() + timeout
    while True:
        st = mgr.json(f"{SYSTEM}/profiles")
        if st["status"] in ("collected", "failed"):
            break
        if time.monotonic() > deadline:
            raise RunFailed(f"profile still {st['status']}")
        time.sleep(0.25)
    if st["status"] != "collected":
        raise RunFailed(f"profile capture failed: {st.get('errorMsg')}")
    raw = mgr.get(f"{SYSTEM}/profiles/theia-manager/download")
    tdir = os.path.join(work, "trace")
    os.makedirs(tdir)
    with tarfile.open(fileobj=io.BytesIO(raw), mode="r:gz") as tar:
        tar.extractall(tdir, filter="data")
    out = os.path.join(work, "trace.json")
    env = child_env("cpu", {})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.tracered", tdir, out],
        env=env, cwd=ROOT, capture_output=True, timeout=300)
    if proc.returncode != 0:
        raise RunFailed("trace reduction failed:\n"
                        + proc.stderr.decode(errors="replace")[-2000:])
    with open(out) as f:
        return json.load(f)


def resolve_all(bench: _manifest.Bench, cell_name: str, traffic: Dict
                ) -> None:
    """Find everything the cell's files name (see `resolve` in the
    module's text), or raise RunFailed naming the file looked for."""
    _extend.use(bench.base)
    _check.resolve_all(traffic)
    _gen.law(traffic)
    for group in traffic["workers"]:
        _extend.resolve("role", group["role"])
    _reductions.resolve_all(bench, cell_name)


def compile_stats(text: bytes) -> Dict[str, float]:
    found = COMPILED.findall(text)
    return {"compiles": len(found),
            "compile_s": sum(float(s) for _, s in found),
            "cache_hits": len(CACHE_HIT.findall(text))}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, platform: str = "tpu",
             scale: Optional[Dict] = None,
             bench: Optional[_manifest.Bench] = None) -> Dict:
    """Drive one run; returns the result object (the caller prints it).
    `scale` shrinks block counts for the rehearsal."""
    bench = bench or _manifest.load()
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    if scale and "env" in scale:          # rehearsal: chip paths on the CPU
        config = dict(config, env={**config.get("env", {}), **scale["env"]})
    traffic = bench.traffic(cell["traffic"], scale)
    resolve_all(bench, cell_name, traffic)
    work = os.path.join(WORK, cell_name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(os.path.join(CACHE, "jax"), exist_ok=True)
    children = Children()
    phases: Dict[str, float] = {}
    try:
        mgr = Manager(children, config, work, platform)
        specs = worker_specs(traffic, seed, mgr.addr, scale or {},
                             bench.base)
        cores = plan_cores(len(specs))
        if cores["manager"]:
            os.sched_setaffinity(mgr.proc.pid, cores["manager"])
        wc = cores["workers"]
        workers = [Worker(children, s, work,
                          [wc[i % len(wc)]] if wc else None)
                   for i, s in enumerate(specs)]
        mgr.wait_ready()
        phases["manager_start_s"] = mgr.start_s
        log(f"manager: {mgr.runtime} start {mgr.start_s:.2f}s")
        if mgr.runtime["platform"] != platform:
            raise RunFailed(f"manager runs on {mgr.runtime}, this run "
                            f"needs platform={platform}")
        if platform != "cpu" and mgr.runtime["count"] < cell["chips"]:
            raise RunFailed(f"{mgr.runtime['count']} chips, the cell "
                            f"needs {cell['chips']}")
        health0 = mgr.json("/healthz")
        _check.config_preconditions(config, health0)
        ready = [w.expect("ready") for w in workers]
        phases["workers_ready_s"] = time.monotonic() - t_process
        phases["prepare_s"] = max(r["prepare_s"] for r in ready)

        t = time.monotonic()
        preload = all_do(workers, "preload", "preloaded")
        phases["preload_s"] = time.monotonic() - t
        t = time.monotonic()
        warm = all_do(workers, "warm", "warmed")
        phases["warmup_s"] = time.monotonic() - t
        sent_rows = sum(r.get("rows") or 0 for res in preload + warm
                        for r in res["records"] if "rows_sent" in r)
        quiesce(mgr, sent_rows)
        setup_log = mgr.log_size()
        m_before = mgr.metrics()
        trace_s = min(float(traffic.get("trace_seconds", 10.0)), seconds)
        t_open = time.monotonic() + 0.3
        setup_s = t_open - t_process
        run_cmd = f"run {t_open!r} {seconds!r}"
        # The profiler slows the host severalfold and its export keeps
        # the manager busy afterwards, so it takes the window's end:
        # the host-side per-layer numbers come from the window before
        # it is asked for (`clean`), the device's from it. It starts a
        # second or two after it is asked, so it is asked
        # TRACE_START_S before the window's last trace_s seconds: the
        # whole capture then lies inside the window, under the
        # window's load. The workers are told when it is asked for: a
        # job client lets no job run into it and starts its next
        # `trace_lead_seconds` later, so that the job's one kernel
        # call falls inside the capture.
        t_trace = max(t_open, t_open + seconds - trace_s - TRACE_START_S)
        if trace:
            lead = float(traffic.get("trace_lead_seconds", 0.0))
            run_cmd += f" {t_trace!r} {lead!r}"
        for w in workers:
            w.send(run_cmd)
        clean = [0.0, float("inf")]    # host-side numbers' part of the window
        if trace:
            time.sleep(max(0.0, t_trace - time.monotonic()))
            m_after = mgr.metrics()
            clean[1] = t_trace = time.monotonic()
            capture_trace(mgr, trace_s)
        for w in workers:
            w.expect("done")
        t_done = time.monotonic()
        window_log = mgr.log_size()
        if clean[1] == float("inf"):
            m_after = mgr.metrics()
        results = [w.result() for w in workers]

        def acked_rows(*phases_records) -> int:
            return sum(r.get("rows") or 0 for phase in phases_records
                       for res in phase for r in res["records"]
                       if "rows_sent" in r)

        t = time.monotonic()
        quiesce(mgr, acked_rows(preload, warm, results))
        phases["quiesce_s"] = time.monotonic() - t
        t = time.monotonic()
        probes = []
        for w in workers:               # one worker at a time
            n = int(w.spec.get("probe_blocks", 0))
            if n:
                probes.append(all_do([w], f"probe {n}", "probed")[0])
            else:
                probes.append({"records": []})
        for w in workers:
            w.send("exit")
        health = quiesce(mgr, acked_rows(preload, warm, results, probes))
        phases["probe_s"] = time.monotonic() - t
        m_final = mgr.metrics()
        ctx = {
            "cell": cell, "config": config, "traffic": traffic,
            "seed": seed, "seconds": seconds, "t_open": t_open,
            "specs": specs, "preload": preload, "warm": warm,
            "results": results, "probes": probes, "health": health,
            "metrics_final": m_final, "manager": mgr,
        }
        t = time.monotonic()
        report = _check.run_checks(ctx)
        phases["check_s"] = time.monotonic() - t
        for line in report["lines"]:
            log(line)

        device = mgr.device()
        window_compiles = compile_stats(mgr.log_bytes(setup_log, window_log))
        setup_compiles = compile_stats(mgr.log_bytes(0, setup_log))
        log(f"compiles: set-up {setup_compiles}, window {window_compiles}")
        trace_doc = None
        if trace:
            trace_doc = fetch_trace(mgr, work, platform)
            # The device is traced while the manager sleeps between
            # the profiler's start and stop: trace_s seconds, or what
            # the device's own events span if that is more. Where the
            # capture lies on this process's clock only the program
            # could say; certain is that the profiler ran from
            # TRACE_START_S after it was asked until trace_s after it
            # was asked: requests are counted there, as a rate, and
            # the rate is taken over the capture's length.
            sure = (t_trace + min(TRACE_START_S, trace_s / 2),
                    t_trace + trace_s)
            trace_doc.update(window_s=max(trace_s, trace_doc["span_s"]),
                             sure=sure)
            programs = [n for n, _, _ in trace_doc["ops"]
                        if n.startswith("module:")]
            log(f"trace: {trace_doc['devices_traced']} device plane(s), "
                f"busy {trace_doc['busy_s']:.6f}s of {trace_s:g}s asked "
                f"for {t_open + seconds - t_trace:.1f}s before the "
                f"window's end (device events span "
                f"{trace_doc['span_s']:.3f}s), programs {programs[:8]}")
            if platform != "cpu" and not trace_doc["busy_s"] > 0:
                raise RunFailed("the trace holds no device operation: "
                                "the profiler missed the window's work")
            device["busy_s"] = trace_doc["busy_s"]
            device["window_s"] = trace_doc["window_s"]
        data = {
            "cell": cell, "traffic": traffic, "seconds": seconds,
            "t_open": t_open, "t_done": t_done, "setup_s": setup_s,
            "clean": clean,
            "phases": phases, "results": results, "specs": specs,
            "metrics_before": m_before, "metrics_after": m_after,
            "setup_compiles": setup_compiles,
            "window_compiles": window_compiles, "trace": trace_doc,
            "device": device, "health": health,
        }
        metrics = _reductions.reduce_all(
            bench, cell_name, "per_layer" if trace else "end_to_end", data)
        log("phases: " + json.dumps({k: round(v, 3)
                                     for k, v in phases.items()}))
        out = {"correct": report["correct"],
               "attempted": report["attempted"],
               "failed": report["failed"], "metrics": metrics,
               "device": device}
        if trace_doc is not None:
            out["breakdown"] = trace_doc["breakdown"]
        out["checks"] = report["numbers"]     # each beside its limit; last
        return out
    finally:
        children.stop_all()
