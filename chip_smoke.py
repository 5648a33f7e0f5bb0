#!/usr/bin/env python3
"""chip_smoke.py — does theia-tpu still start, serve and score on the chip?

Drives the system's main path once, through the entry points a user
would call, on one accelerator, and checks what comes out:

  build           g++ build of native/*.cc on this machine (any copied
                  .so is removed first); fails unless it loads
  served-default  `python -m theia_tpu.manager --db … --wal-dir …` with no
                  THEIA_* set + the stock producer (`theia ingest`): TBLK
                  blocks of the full 52-column schema, 32,000 rows each
                  (the upstream deployment's ~4,000 records/s × 8 s
                  commitInterval, BASELINE.md), 32 blocks ≈ 1.0 M rows
                  over 8,000 connections; acks, /alerts, three `theia
                  query` group-bys against the producer's own ledger,
                  `clickhouse status --deviceInfo`
  jobs            in the same manager (thread dispatch shares the chip
                  in-process): `theia tad run -a EWMA|ARIMA|DBSCAN --wait`
                  and `theia pr run --wait` over the ingested rows
  reference       the same jobs by `python -m theia_tpu.runner` under
                  JAX_PLATFORMS=cpu on the manager's snapshot (a CPU
                  child is off the chip), decisions compared
  served-fused    a second manager with THEIA_DETECTOR_ENGINE=auto (→ the
                  fused engine + the Pallas tick scan on TPU), identical
                  traffic: per-ack rows/alerts must equal the default run's
  runner          `python -m theia_tpu.runner tad -a EWMA` as its own
                  process on the chip, after the managers have exited

One process holds the chip at a time, and this parent never imports jax
(a parent that has touched JAX holds the chip; its children would fail).
Children that need the chip get JAX_PLATFORMS=tpu, so a chip that cannot
be initialised is an error in JAX itself, never a quiet CPU run.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Any failed phase → non-zero exit and no such line. `--dry-run` runs the
same plumbing at a tiny size on the CPU (fused engine forced, Pallas in
interpret mode) and says so: its last line carries "dry_run": true and
no "ok" — a dry run is never a pass, and neither is a run cut short
with `--until PHASE`. On a host with several chips the children see
them all (the jobs then run over a mesh of every device) and the
phases print per-device memory high-water marks.

Timings printed here are a smoke's timings, not performance numbers.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke_work")

#: the upstream deployment's documented shape (BASELINE.md): ~4,000
#: records/s × 8 s commitInterval = 32,000 rows per block
FULL = {"batches": 32, "series": 8000, "points": 4}
DRY = {"batches": 4, "series": 64, "points": 4}

#: DBSCAN's fixed eps (2.5e8 bytes/s) only sees spikes from about 1e7 up
TRAFFIC = ["--base-throughput", "1e7", "--anomaly-magnitude", "50"]

#: Largest admitted share of scored points whose anomaly decision
#: differs between the chip and the CPU reference (f32 on both sides;
#: XLA:TPU and XLA:CPU round reductions and divisions differently, so
#: points sitting on a threshold can flip). Seen on a TPU v5e at the
#: full size, seed 0 (PR 22): EWMA 0, DBSCAN 0, NPR 0 and ARIMA 984 of
#: 1,024,000 points (9.6e-4 — its per-step least-squares refit is the
#: ill-conditioned one). ARIMA gets 5× what was seen, EWMA room for
#: a hundred flips; the threshold tests of DBSCAN and the set algebra
#: of NPR have no rounding to forgive.
TOLERANCE = {"EWMA": 1e-4, "ARIMA": 5e-3, "DBSCAN": 0.0, "NPR": 0.0}
JOBS = tuple(TOLERANCE)       # three TAD algorithms and NPR, in order

BANNER = re.compile(
    r"theia-(?:manager|runner) runtime: platform=(\S+) "
    r"device_kind='([^']*)' devices=(\d+) native=(\S+)")
COMPILED = re.compile(
    r"Finished XLA compilation of (.*) in ([0-9.]+) sec")
CACHE_HIT = re.compile(r"Persistent compilation cache hit")


class PhaseFailed(Exception):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise PhaseFailed(message)


# -- children -----------------------------------------------------------

_children: list = []


def child_env(platform: str, extra: dict | None = None) -> dict:
    """The environment of a child: the ambient one minus every THEIA_*
    knob (the served path runs on defaults), an explicit platform, and
    JAX's own compile log (the source of the compile times below)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("THEIA_")}
    env["JAX_PLATFORMS"] = platform
    env["JAX_LOG_COMPILES"] = "1"
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def run(cmd: list, env: dict, log: str, timeout: float) -> float:
    """Run one child to completion (stdout+stderr → `log`); returns
    wall seconds; a non-zero exit or a timeout fails the phase."""
    t0 = time.monotonic()
    with open(log, "wb") as f:
        proc = subprocess.Popen(cmd, env=env, cwd=HERE, stdout=f,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        _children.append(proc)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            kill(proc)
            raise PhaseFailed(
                f"{' '.join(cmd[:4])}… timed out after {timeout:.0f}s"
                f"\n{tail(log)}")
    check(rc == 0, f"{' '.join(cmd[:5])}… exited {rc}\n{tail(log)}")
    return time.monotonic() - t0


def kill(proc) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()


def kill_all() -> None:
    for proc in _children:
        kill(proc)


def tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            lines = [ln for ln in f.read().splitlines()
                     if "cpu_aot_loader" not in ln]
    except OSError:
        return f"(no log at {path})"
    return "\n".join("    | " + ln[:300] for ln in lines[-n:])


def read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def runtime_of(log_text: str, where: str) -> dict:
    """What a manager or runner says it ran on (its start-up line)."""
    m = BANNER.search(log_text)
    check(m is not None, f"{where}: no runtime line in its log")
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3)), "native": m.group(4)}


def compile_stats(log_text: str) -> dict:
    """From JAX's own compile log (JAX_LOG_COMPILES=1): how many
    programs the process compiled, the seconds that took, how many
    came from the persistent cache, and the slowest three."""
    found = [(float(s), name) for name, s in COMPILED.findall(log_text)]
    slowest = sorted(found, reverse=True)[:3]
    return {"compiles": len(found),
            "compile_s": round(sum(s for s, _ in found), 2),
            "cache_hits": len(CACHE_HIT.findall(log_text)),
            "slowest_compiles": ", ".join(
                f"{name} {s:.1f}s" for s, name in slowest)}


def get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


class Manager:
    """One `python -m theia_tpu.manager` child and its HTTP address."""

    def __init__(self, name: str, platform: str,
                 extra_env: dict | None = None) -> None:
        self.dir = os.path.join(WORK, name)
        os.makedirs(self.dir)
        self.log = os.path.join(self.dir, "manager.log")
        self.db = os.path.join(self.dir, "db.npz")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.addr = f"http://127.0.0.1:{port}"
        cmd = [sys.executable, "-m", "theia_tpu.manager",
               "--db", self.db, "--wal-dir",
               os.path.join(self.dir, "wal"), "--port", str(port)]
        self._t0 = time.monotonic()
        self._log_f = open(self.log, "wb")
        self.proc = subprocess.Popen(
            cmd, env=child_env(platform, extra_env), cwd=HERE,
            stdout=self._log_f,
            stderr=subprocess.STDOUT, start_new_session=True)
        _children.append(self.proc)
        self.start_s = self._wait_ready()

    def _wait_ready(self, timeout: float = 300.0) -> float:
        while time.monotonic() - self._t0 < timeout:
            check(self.proc.poll() is None,
                  f"manager exited {self.proc.returncode} at start\n"
                  f"{tail(self.log)}")
            # the runtime line is printed after the listener is up
            if BANNER.search(read(self.log)):
                try:
                    get_json(self.addr + "/healthz", timeout=5)
                    return time.monotonic() - self._t0
                except OSError:
                    pass
            time.sleep(0.25)
        raise PhaseFailed(f"manager not ready in {timeout:.0f}s\n"
                          f"{tail(self.log)}")

    def cli(self, args: list, log_name: str, timeout: float) -> tuple:
        """`theia <args>` against this manager; (path of its
        stdout+stderr, secs). The CLI never needs a device: it is
        pinned to the CPU so that it could not take the chip even by
        accident."""
        log = os.path.join(self.dir, log_name)
        secs = run([sys.executable, "-m", "theia_tpu.cli",
                    "--manager-addr", self.addr] + args,
                   child_env("cpu"), log, timeout)
        return log, secs

    def stop(self) -> None:
        """SIGTERM → drain → final snapshot at self.db."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=180)
            except subprocess.TimeoutExpired:
                kill(self.proc)
                raise PhaseFailed("manager did not stop on SIGTERM\n"
                                  + tail(self.log))
        self._log_f.close()
        check(self.proc.returncode == 0,
              f"manager exited {self.proc.returncode}\n{tail(self.log)}")


# -- phases -------------------------------------------------------------

def phase_build(ctx) -> dict:
    """Build the native library here, from native/*.cc as committed."""
    for so in glob.glob(os.path.join(
            HERE, "theia_tpu", "utils", "_build", "*.so")):
        os.remove(so)
    log = os.path.join(WORK, "build.log")
    secs = run([sys.executable, "-c",
                "import sys; from theia_tpu.utils.native import "
                "native_status; s = native_status(); print(s); "
                "sys.exit(0 if s == 'loaded' else 1)"],
               child_env("cpu"), log, 300)
    return {"native": read(log).strip().splitlines()[-1],
            "wall_s": round(secs, 1)}


def produce(mgr: Manager, ctx) -> dict:
    size = ctx["size"]
    log, secs = mgr.cli(
        ["ingest", "--stream", "smoke", "--json",
         "--batches", str(size["batches"]),
         "--series", str(size["series"]),
         "--points", str(size["points"]),
         "--seed", str(ctx["seed"])] + TRAFFIC,
        "producer.log", 900)
    ledger = json.loads(read(log).strip().splitlines()[-1])
    ledger["wall_s"] = secs
    return ledger


def served_checks(mgr: Manager, ledger: dict, ctx) -> dict:
    """What both served phases must show, whatever the engine."""
    size = ctx["size"]
    rows_block = size["series"] * size["points"]
    rows = rows_block * size["batches"]
    check(ledger["rowsSent"] == rows,
          f"producer sent {ledger['rowsSent']} rows, expected {rows}")
    check(ledger["rowsAcked"] == ledger["rowsSent"],
          f"acked {ledger['rowsAcked']} != sent {ledger['rowsSent']}")
    check(all(a["rows"] == rows_block for a in ledger["acks"]),
          f"an ack is not {rows_block} rows: "
          f"{[a['rows'] for a in ledger['acks']]}")
    check(ledger["duplicates"] == 0, "duplicate acks in a fresh run")
    alerts = sum(a["alerts"] for a in ledger["acks"])
    check(alerts > 0, "no alerts raised")

    ring = get_json(mgr.addr + "/alerts")["alerts"]
    conn = [a for a in ring if a.get("kind") == "connection_anomaly"]
    check(conn, f"/alerts holds no connection alerts ({len(ring)} "
                f"entries)")

    health = get_json(mgr.addr + "/healthz")
    ing = health["ingest"]
    check(ing["native"] is True, "/healthz ingest.native is not true")
    check(ing["rowsIngested"] == rows,
          f"/healthz rowsIngested {ing['rowsIngested']} != {rows}")
    series = sum(s["series"] for s in ing["perShard"])
    dropped = sum(s["droppedSeries"] for s in ing["perShard"])
    check(dropped == 0, f"{dropped} connection series dropped")
    check(series >= size["series"],
          f"{series} distinct connections < {size['series']} "
          f"(seed {ctx['seed']} collides; pick another)")
    ctx["query_kernel"] = health.get("query", {}).get("kernel")
    return {"rows": rows, "alerts": alerts, "connections": series,
            "alert_ring": len(ring), "engine": ing["engine"],
            "first_ack_s": round(ledger["acks"][0]["seconds"], 2),
            "median_ack_s": round(statistics.median(
                a["seconds"] for a in ledger["acks"][1:]), 3),
            "producer_wall_s": round(ledger["wall_s"], 1)}


def query_checks(mgr: Manager, ledger: dict) -> dict:
    """Three `theia query` group-bys: counts sum to the acked rows,
    sum(octetDeltaCount) equals the producer's own total."""
    took = []
    for group_by in ("sourcePodNamespace", "destinationTransportPort",
                     "flowType,sourceNodeName"):
        log, _ = mgr.cli(
            ["query", "--group-by", group_by, "--agg", "count",
             "--agg", "sum:octetDeltaCount", "-k", "0", "--json"],
            f"query-{group_by.replace(',', '+')}.log", 120)
        doc = json.loads(read(log))
        count = sum(r["count"] for r in doc["rows"])
        octets = sum(r["sum(octetDeltaCount)"] for r in doc["rows"])
        check(count == ledger["rowsAcked"],
              f"query by {group_by}: counts sum to {count}, acked "
              f"{ledger['rowsAcked']}")
        check(octets == ledger["octetsSent"],
              f"query by {group_by}: sum(octetDeltaCount) {octets} != "
              f"producer's {ledger['octetsSent']}")
        took.append((group_by, doc["groupCount"], doc["tookMs"]))
    return {"queries": [f"{g}: {n} groups {ms} ms"
                        for g, n, ms in took]}


def device_info(mgr: Manager, runtime: dict) -> dict:
    """`theia clickhouse status --deviceInfo` must agree with the
    manager's own start-up line and show memory in use on the device."""
    log, _ = mgr.cli(["clickhouse", "status", "--deviceInfo"],
                     "deviceinfo.log", 60)
    lines = [ln for ln in read(log).splitlines() if ln.strip()]
    head = lines.index("== deviceInfo ==")
    cols = re.split(r"\s{2,}", lines[head + 1].strip())
    devs = [dict(zip(cols, re.split(r"\s{2,}", ln.strip())))
            for ln in lines[head + 2:]]
    check(len(devs) == runtime["count"],
          f"deviceInfo lists {len(devs)} devices, runtime line says "
          f"{runtime['count']}")
    d = devs[0]
    check(d.get("platform") == runtime["platform"]
          and d.get("deviceKind") == runtime["kind"],
          f"deviceInfo {d} disagrees with the runtime line {runtime}")
    in_use = [int(x.get("memoryBytesInUse", 0)) for x in devs]
    peak = [int(x.get("memoryPeakBytesInUse", 0)) for x in devs]
    if runtime["platform"] != "cpu":   # the CPU backend reports none
        check(in_use[0] > 0, f"deviceInfo memoryBytesInUse={in_use}")
    return {"memoryBytesInUse": in_use, "memoryPeakBytesInUse": peak}


def phase_served_default(ctx) -> dict:
    mgr = ctx["mgr"] = Manager("default", ctx["platform"])
    runtime = ctx["runtime"] = runtime_of(read(mgr.log), "manager")
    check(runtime["native"] == "loaded",
          f"manager runs without the native library: {runtime}")
    ledger = ctx["ledger"] = produce(mgr, ctx)
    out = {**runtime, "start_s": round(mgr.start_s, 1)}
    out.update(served_checks(mgr, ledger, ctx))
    check(out["engine"]["name"] == "sharded",
          f"default engine is {out['engine']}, expected sharded")
    out.update(query_checks(mgr, ledger))
    out.update(device_info(mgr, runtime))
    return out


def phase_jobs(ctx) -> dict:
    """TAD ×3 and NPR through the CLI, in the manager that just took
    the ingest (thread dispatch: the jobs share its chip in-process)."""
    mgr = ctx["mgr"]
    out: dict = {}
    ctx["chip_ids"] = {}
    for algo in JOBS:
        job = ["pr", "run"] if algo == "NPR" else ["tad", "run", "-a", algo]
        log, secs = mgr.cli(job + ["--wait"], f"job-{algo}.log", 900)
        # the name is on the first line; the rest is the result table
        # (hundreds of thousands of rows at the full size)
        with open(log, errors="replace") as f:
            m = re.search(r"with name:? (?:tad|pr)-(\S+)", f.read(4096))
        check(m is not None, f"{algo}: no job name in CLI output")
        ctx["chip_ids"][algo] = m.group(1)
        out[f"{algo}_wall_s"] = round(secs, 1)
    jobs = get_json(mgr.addr + "/healthz")["jobs"]
    check(jobs["completed"] == len(JOBS) and jobs["failed"] == 0,
          f"jobs: {jobs}")
    # per-device high-water marks: on a multi-chip host the jobs run
    # over a mesh of every device, and each must have held shards
    mem = device_info(mgr, ctx["runtime"])
    out["memoryPeakBytesInUse_after_jobs"] = mem["memoryPeakBytesInUse"]
    if ctx["runtime"]["platform"] != "cpu":
        check(all(p > 0 for p in mem["memoryPeakBytesInUse"]),
              f"a device never held job data: {mem}")
    mgr.stop()     # SIGTERM → final snapshot, with the result tables
    out.update(compile_stats(read(mgr.log)))
    return out


def compare(mgr: Manager, log_name: str, refs: dict,
            chip_ids: dict) -> dict:
    """Run the comparison child (on the CPU: the parent stays off
    jax); {algo: {chip, ref, scored, mismatch, share, nan}}."""
    log = os.path.join(mgr.dir, log_name)
    run([sys.executable, os.path.abspath(__file__), "--compare-child",
         json.dumps({"snapshot": mgr.db, "refs": refs,
                     "chip_ids": chip_ids})],
        child_env("cpu"), log, 300)
    return json.loads(read(log).strip().splitlines()[-1])


def phase_reference(ctx) -> dict:
    """The same jobs on the CPU backend, then the comparison."""
    mgr = ctx["mgr"]
    refs = {}
    out: dict = {}
    for algo in JOBS:
        res = os.path.join(mgr.dir, f"cpu-{algo}.npz")
        refs[algo] = [res, f"cpu-{algo}"]
        job = (["npr"] if algo == "NPR" else ["tad", "-a", algo])
        log = os.path.join(mgr.dir, f"cpu-{algo}.log")
        secs = run([sys.executable, "-m", "theia_tpu.runner"] + job
                   + ["--db", mgr.db, "-i", f"cpu-{algo}",
                      "--out", res],
                   child_env("cpu"), log, 900)
        check(runtime_of(read(log), f"cpu {algo}")["platform"] == "cpu",
              f"the CPU reference for {algo} did not run on the CPU")
        out[f"{algo}_cpu_wall_s"] = round(secs, 1)
    report = compare(mgr, "compare.log", refs, ctx["chip_ids"])
    for algo, r in report.items():
        out[algo] = (f"{r['chip']} chip / {r['ref']} cpu decisions "
                     f"over {r['scored']}, {r['mismatch']} differ "
                     f"(share {r['share']:.3g})")
        check(not r["nan"], f"{algo}: NaN in the chip's result rows")
        check(0 < r["chip"] < r["scored"],
              f"{algo}: degenerate result, {r['chip']} of "
              f"{r['scored']}")
        check(r["share"] <= TOLERANCE[algo],
              f"{algo}: chip and CPU decisions differ on a share "
              f"{r['share']:.3g} > {TOLERANCE[algo]}")
    return out


def phase_served_fused(ctx) -> dict:
    """Same traffic into the engine `auto` resolves to on this
    backend; the repo's own contract is per-ack parity between
    engines on one backend. (On the CPU `auto` is the sharded engine,
    so the dry run names the fused engine and interprets the kernel.)"""
    knobs = ({"THEIA_DETECTOR_ENGINE": "auto"} if not ctx["dry"] else
             {"THEIA_DETECTOR_ENGINE": "fused",
              "THEIA_FUSED_PALLAS": "interpret"})
    mgr = Manager("fused", ctx["platform"], knobs)
    try:
        runtime = runtime_of(read(mgr.log), "fused manager")
        check(runtime == ctx["runtime"],
              f"fused manager runs on {runtime}, default ran on "
              f"{ctx['runtime']}")
        ledger = produce(mgr, ctx)
        out = {**runtime, "start_s": round(mgr.start_s, 1)}
        out.update(served_checks(mgr, ledger, ctx))
        eng = out["engine"]
        check(eng["name"] == "fused" and eng["pallas"] is True
              and eng["steps"] >= 1, f"engine block: {eng}")
        ours = [(a["rows"], a["alerts"]) for a in ledger["acks"]]
        theirs = [(a["rows"], a["alerts"])
                  for a in ctx["ledger"]["acks"]]
        check(ours == theirs,
              f"per-ack (rows, alerts) differ between engines:\n"
              f"    fused   {ours}\n    default {theirs}")
        out["per_ack_parity"] = f"{len(ours)} acks equal"
    finally:
        mgr.stop()
    log_text = read(mgr.log)
    check("falling back" not in log_text,
          "the fused manager's log says 'falling back'")
    out.update(compile_stats(log_text))
    return out


def phase_runner(ctx) -> dict:
    """The runner as its own chip-holding process (the managers are
    gone), over the first manager's snapshot; its decisions must be
    the in-manager EWMA job's (same kernels, same backend)."""
    mgr = ctx["mgr"]
    res = os.path.join(mgr.dir, "runner-EWMA.npz")
    log = os.path.join(mgr.dir, "runner-EWMA.log")
    secs = run([sys.executable, "-m", "theia_tpu.runner", "tad", "-a",
                "EWMA", "--db", mgr.db, "-i", "runner-EWMA",
                "--out", res], child_env(ctx["platform"]), log, 600)
    text = read(log)
    runtime = runtime_of(text, "runner")
    check(runtime == ctx["runtime"],
          f"runner ran on {runtime}, the manager on {ctx['runtime']}")
    r = compare(mgr, "compare-runner.log",
                {"EWMA": [res, "runner-EWMA"]},
                {"EWMA": ctx["chip_ids"]["EWMA"]})["EWMA"]
    check(r["mismatch"] == 0,
          f"runner vs in-manager EWMA on one backend: {r}")
    return {**runtime, "wall_s": round(secs, 1),
            "decisions": r["ref"], **compile_stats(text)}


# -- the comparison child (runs under JAX_PLATFORMS=cpu) ------------------

def compare_child(spec: dict) -> None:
    """Anomaly decisions of the chip's jobs (result rows in the
    manager's snapshot) against a reference run's, per algorithm; one
    JSON object on the last line."""
    import numpy as np

    from theia_tpu.analytics import TadQuerySpec, build_series
    from theia_tpu.store import FlowDatabase

    db = FlowDatabase.load(spec["snapshot"])
    scored = int(build_series(db.flows.scan(),
                              TadQuerySpec()).mask.sum())
    key = ("sourceIP", "sourceTransportPort", "destinationIP",
           "destinationTransportPort", "protocolIdentifier",
           "flowStartSeconds", "flowEndSeconds")

    def tad_decisions(table, job_id):
        rows = table.filter(table.strings("id") == job_id)
        rows = rows.filter(rows.strings("anomaly") == "true")
        cols = [rows.strings(c) if c in rows.dicts
                else np.asarray(rows[c]) for c in key]
        nan = bool(np.isnan(np.asarray(
            rows["throughputStandardDeviation"], float)).any()
            or np.isnan(np.asarray(rows["algoCalc"], float)).any())
        return set(zip(*(c.tolist() for c in cols))), nan

    def npr_policies(table, job_id):
        rows = table.filter(table.strings("id") == job_id)
        return set(zip(rows.strings("kind").tolist(),
                       rows.strings("policy").tolist())), False

    report = {}
    for algo, (ref_path, ref_id) in spec["refs"].items():
        ref_db = FlowDatabase.load(ref_path)
        if algo == "NPR":
            chip, nan = npr_policies(db.recommendations.scan(),
                                     spec["chip_ids"][algo])
            ref, _ = npr_policies(ref_db.recommendations.scan(),
                                  ref_id)
            total = len(chip | ref) + 1   # "scored" = candidate set
        else:
            chip, nan = tad_decisions(db.tadetector.scan(),
                                      spec["chip_ids"][algo])
            ref, _ = tad_decisions(ref_db.tadetector.scan(), ref_id)
            total = scored
        diff = len(chip ^ ref)
        report[algo] = {"chip": len(chip), "ref": len(ref),
                        "scored": total, "mismatch": diff,
                        "share": diff / max(total, 1), "nan": nan}
    print(json.dumps(report))


# -- driver -------------------------------------------------------------

PHASES = (("build", phase_build),
          ("served-default", phase_served_default),
          ("jobs", phase_jobs),
          ("reference", phase_reference),
          ("served-fused", phase_served_fused),
          ("runner", phase_runner))


def cache_entries() -> tuple:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or os.path.join(HERE, ".jax_cache"))
    return path, len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="traffic seed (default 0: 8,000 series give "
                        "8,000 distinct connections)")
    p.add_argument("--dry-run", action="store_true",
                   help="the same plumbing at a tiny size on the CPU; "
                        "never a pass")
    p.add_argument("--until", choices=[n for n, _ in PHASES],
                   help="stop after this phase (e.g. `jobs` on a "
                        "four-chip host); a partial run is never a "
                        "pass either")
    p.add_argument("--keep", action="store_true",
                   help="keep .smoke_work/ (logs, snapshots)")
    p.add_argument("--compare-child", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.compare_child:
        compare_child(json.loads(args.compare_child))
        return 0

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    ctx = {"dry": args.dry_run, "seed": args.seed,
           "size": DRY if args.dry_run else FULL,
           "platform": "cpu" if args.dry_run else "tpu"}
    cache_dir, cache_before = cache_entries()
    mode = ("DRY RUN on the CPU — not a pass" if ctx["dry"]
            else "on the accelerator JAX finds")
    print(f"chip_smoke: {mode}"
          f"; seed {ctx['seed']}; {ctx['size']['batches']} blocks × "
          f"{ctx['size']['series'] * ctx['size']['points']} rows; "
          f"compile cache {cache_dir} ({cache_before} entries)",
          flush=True)
    t_all = time.monotonic()
    failed = []
    try:
        for name, fn in PHASES:
            if failed:
                # every later phase consumes an earlier one's output
                print(f"[{name}] SKIPPED (after a failed phase)",
                      flush=True)
                failed.append(name)
                continue
            t0 = time.monotonic()
            try:
                result = fn(ctx)
                status = "ok"
            except PhaseFailed as e:
                result, status = {"error": str(e)}, "FAILED"
                failed.append(name)
            wall = time.monotonic() - t0
            print(f"[{name}] {status} wall={wall:.1f}s", flush=True)
            for k, v in result.items():
                if k == "queries":
                    for item in v:
                        print(f"    query {item}")
                else:
                    print(f"    {k}: {v}")
            sys.stdout.flush()
            if name == args.until:
                break
    finally:
        kill_all()
        if not args.keep and not failed:
            shutil.rmtree(WORK, ignore_errors=True)
    _, cache_after = cache_entries()
    print(f"total wall={time.monotonic() - t_all:.1f}s; compile cache "
          f"{cache_dir}: {cache_before} → {cache_after} entries "
          f"(+{cache_after - cache_before})")
    if "query_kernel" in ctx:
        print("note: /query ran on the host (query kernel "
              f"{ctx['query_kernel']!r}: `auto` means JAX only under "
              "x64, so a TPU process answers /query with numpy and "
              "does no device work there — ROADMAP D9 decides)")
    if failed:
        print(f"FAILED phases: {', '.join(failed)}; logs kept in "
              f"{WORK}", file=sys.stderr)
        return 1
    rt = ctx["runtime"]
    device = {"platform": rt["platform"], "kind": rt["kind"],
              "count": rt["count"]}
    if ctx["dry"] or args.until:
        print(json.dumps({"dry_run": ctx["dry"],
                          "until": args.until or PHASES[-1][0],
                          "plumbing_ok": True, "device": device}))
        return 0
    if device["platform"] == "cpu":     # a CPU run is never a pass
        print("FAILED: the phases ran on the CPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
