"""Load-generating worker processes: producer, dashboard reader, job client.

One worker is one process (`python3 -m benchmarks.client <spec.json>`)
that never imports jax, so it cannot hold the chip. The harness pins it
to cores of its own, sends it one command per line on stdin and reads
one JSON object per line from stdout:

    (start)  → {"event": "ready", ...}        inputs prepared
    preload  → {"event": "preloaded", ...}    retained window acked
    warm     → {"event": "warmed", ...}       warm-up work acked
    run T S [H L] → {"event": "done", ...}    window [T, T+S) on the
                                              monotonic clock, which
                                              Linux shares between
                                              processes; H, L: when a
                                              traced run's profiler
                                              starts, and a job
                                              client's lead after it
    probe N  → {"event": "probed", ...}       a producer's next N
                                              blocks, one at a time,
                                              the manager's alert
                                              counters read around each
    exit

Every request is recorded with its due time, send time and completion
time; the records go to the file the spec names (they can be large),
and the harness reduces them. A refused or failed request is recorded
as failed, never retried inside the window: a retry would hide it.

`ROLES` has the built-in roles. Any other `role` of a traffic file's
`workers` is a file benchmarks/roles/<name>.py (extend.py) with a class
`Role`: `Role(spec)` prepares, `handle(cmd)` answers `preload`, `warm`,
`run T S [H L]` and `probe N` with {"event": "preloaded" | "warmed" |
"done" | "probed", "records": [...], ...}; a record that a reduction
is to read carries `due`, `send`, `ack` and `status`.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import re
import sys
import time
import urllib.parse
from typing import Dict, List, Optional, Tuple

from . import extend

INTELLIGENCE = "/apis/intelligence.theia.antrea.io/v1alpha1"
ALERTS_TOTAL = "theia_ingest_alerts_total"
_STATE = re.compile(rb'"state":\s*"([A-Z_]+)"')


class Http:
    """One keep-alive connection; reconnects after an error."""

    def __init__(self, addr: str, timeout: float = 120.0) -> None:
        u = urllib.parse.urlsplit(addr)
        self.host, self.port, self.timeout = u.hostname, u.port, timeout
        self.conn: Optional[http.client.HTTPConnection] = None

    def request(self, method: str, path: str, body: Optional[bytes] = None,
                content_type: str = "application/octet-stream"
                ) -> Tuple[int, bytes]:
        """(status, body); status 0 is a transport failure."""
        headers = {"Content-Type": content_type} if body is not None else {}
        try:
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            self.conn.request(method, path, body=body, headers=headers)
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            return 0, repr(e).encode()

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def emit(doc: Dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.2) if d > 0.002 else 0)


# -- producer -------------------------------------------------------------

class Producer:
    """Sends its stream's blocks in order, one outstanding request at a
    time, as a Flow Aggregator commits: per-connection order is the
    stream's order whatever the schedule. `closed` sends the next block
    as soon as the ack is in; `open` sends block k at T + offset +
    k·period and times it from that due time, so a stall is charged to
    every block it delays."""

    def __init__(self, spec: Dict) -> None:
        from . import gen
        self.spec = spec
        self.http = Http(spec["addr"])
        self.stream = gen.stream(spec["traffic"], spec["seed"],
                                 spec["producer"])
        self.name = f"bench-{spec['producer']}"
        self.sent = 0
        self.ready: List[Tuple[bytes, Dict]] = []
        self.starved_s = 0.0
        t0 = time.monotonic()
        for _ in range(int(spec["prepared_blocks"])):
            self.ready.append(self.stream.block(len(self.ready)))
        self.prepare_s = time.monotonic() - t0

    def _payload(self, b: int) -> Tuple[bytes, Dict]:
        if b >= len(self.ready):
            t0 = time.monotonic()
            while b >= len(self.ready):
                self.ready.append(self.stream.block(len(self.ready)))
            self.starved_s += time.monotonic() - t0
        return self.ready[b]

    def send_next(self, due: Optional[float] = None) -> Dict:
        b = self.sent
        payload, meta = self._payload(b)
        self.sent += 1
        t_send = time.monotonic()
        status, body = self.http.request(
            "POST", f"/ingest?stream={self.name}&seq={b + 1}", payload)
        t_ack = time.monotonic()
        rec = {"block": b, "due": t_send if due is None else due,
               "send": t_send, "ack": t_ack, "status": status,
               "rows_sent": meta["rows"], "octets": meta["octets"]}
        if status == 200:
            try:
                ack = json.loads(body)
                rec["rows"] = ack.get("rows")
                rec["alerts"] = ack.get("alerts")
                if ack.get("duplicate"):
                    rec["duplicate"] = True
                if ack.get("degraded"):
                    rec["degraded"] = ack["degraded"]
            except ValueError:
                rec["malformed"] = body[:200].decode(errors="replace")
        else:
            rec["error"] = body[:200].decode(errors="replace")
        return rec

    def closed_loop(self, n: Optional[int] = None,
                    until: Optional[float] = None) -> List[Dict]:
        out = []
        while (n is None or len(out) < n) and \
                (until is None or time.monotonic() < until):
            out.append(self.send_next())
        return out

    def open_loop(self, t_open: float, seconds: float) -> List[Dict]:
        sched = self.spec["schedule"]
        period = float(sched["period_s"])
        offset = float(sched["offset_s"])
        out = []
        k = 0
        while True:
            due = t_open + offset + k * period
            if due >= t_open + seconds:
                return out
            sleep_until(due)
            out.append(self.send_next(due))
            k += 1

    def _alert_counters(self) -> Dict[str, int]:
        """The manager's alert counters by kind, now."""
        from . import prom
        status, body = self.http.request("GET", "/metrics")
        if status != 200:
            return {}
        return {k: int(v) for k, v in prom.parse(body.decode()).items()
                if k.startswith(ALERTS_TOTAL + "{")}

    def probe(self, n: int) -> List[Dict]:
        """The stream's next n blocks after the window, while nothing
        else is sent to the manager (the harness probes one producer
        at a time): the counters' rise around each ack is then that
        block's own, by kind. The ack counts all kinds together and
        the alert ring keeps the newest 1,000 of a block's thousands,
        so this is the only place where a single block's
        connection-anomaly decisions can be counted apart."""
        out = []
        before = self._alert_counters()
        for _ in range(n):
            rec = self.send_next()
            after = self._alert_counters()
            conn = ALERTS_TOTAL + '{kind="connection_anomaly"}'
            rec["conn_alerts"] = after.get(conn, 0) - before.get(conn, 0)
            rec["other_alerts"] = sum(
                v - before.get(k, 0) for k, v in after.items() if k != conn)
            rec["counters_read"] = bool(after)
            before = after
            out.append(rec)
        return out

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            recs = self.closed_loop(n=int(self.spec.get("preload_blocks", 0)))
            return {"event": "preloaded", "records": recs}
        if cmd[0] == "probe":
            return {"event": "probed", "records": self.probe(int(cmd[1]))}
        if cmd[0] == "warm":
            n = int(self.spec.get("warm_blocks", 0))
            if self.spec.get("schedule") and n:
                # an open-loop cell warms up on its own schedule, so
                # that the engine sees the window's arrival pattern
                # (and coalesces as it will) before the window opens
                period = float(self.spec["schedule"]["period_s"])
                recs = self.open_loop(time.monotonic() + 0.05, n * period)
            else:
                recs = self.closed_loop(n=n)
            return {"event": "warmed", "records": recs}
        t_open, seconds = float(cmd[1]), float(cmd[2])
        if self.spec.get("window") == "idle":     # preload only
            return {"event": "done", "records": []}
        sleep_until(t_open)
        if self.spec.get("schedule"):
            recs = self.open_loop(t_open, seconds)
        else:
            recs = self.closed_loop(until=t_open + seconds)
        return {"event": "done", "records": recs,
                "starved_ms": self.starved_s * 1e3}


# -- dashboard reader -----------------------------------------------------

class Reader:
    """Asks the panels of the spec round-robin, closed loop. Keeps a
    digest of every answer and the first answer's body per panel: over
    a closed range of immutable rows every answer of a panel must be
    the same bytes, and the harness compares one with the reference."""

    def __init__(self, spec: Dict) -> None:
        self.spec = spec
        self.http = Http(spec["addr"])
        self.panels = spec["panels"]       # [{"name", "path", "closed"}]
        self.bodies: Dict[str, str] = {}

    def ask(self, panel: Dict) -> Dict:
        t0 = time.monotonic()
        status, body = self.http.request("GET", panel["path"])
        t1 = time.monotonic()
        rec = {"panel": panel["name"], "due": t0, "send": t0, "ack": t1,
               "status": status, "bytes": len(body),
               "digest": hashlib.sha256(body).hexdigest()}
        if status == 200:
            self.bodies.setdefault(panel["name"], body.decode())
        else:
            rec["error"] = body[:200].decode(errors="replace")
        return rec

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            return {"event": "preloaded", "records": []}
        if cmd[0] == "warm":
            return {"event": "warmed",
                    "records": [self.ask(p) for p in self.panels]}
        t_open, seconds = float(cmd[1]), float(cmd[2])
        sleep_until(t_open)
        recs = []
        k = 0
        while time.monotonic() < t_open + seconds:
            recs.append(self.ask(self.panels[k % len(self.panels)]))
            k += 1
        return {"event": "done", "records": recs, "bodies": self.bodies}


# -- job client -----------------------------------------------------------

class JobClient:
    """Creates one job after another through the REST API and polls
    each as `theia tad run --wait` does (GET of the job by name, whose
    answer carries the result rows once COMPLETED), every
    `poll_interval_s`. Turn-around is POST sent → the answer that says
    COMPLETED fully read. The finished job is deleted before the next
    is created, outside its timed span, so that every job runs over the
    same store."""

    def __init__(self, spec: Dict) -> None:
        self.spec = spec
        self.http = Http(spec["addr"], timeout=600.0)
        self.resource = f"{INTELLIGENCE}/{spec['job']['resource']}"
        self.body = json.dumps(spec["job"]["spec"]).encode()
        self.poll = float(spec["job"].get("poll_interval_s", 0.05))
        self.last_result: Optional[str] = None
        self.longest_s = 0.0          # longest turn-around so far

    def one_job(self) -> Dict:
        t0 = time.monotonic()
        status, body = self.http.request("POST", self.resource, self.body,
                                         "application/json")
        t_created = time.monotonic()
        rec = {"due": t0, "send": t0, "created": t_created,
               "status": status, "polls": 0}
        if status != 201:
            rec["ack"] = t_created
            rec["error"] = body[:200].decode(errors="replace")
            return rec
        name = json.loads(body)["metadata"]["name"]
        rec["name"] = name
        state = b""
        while True:
            status, body = self.http.request(
                "GET", f"{self.resource}/{name}")
            rec["polls"] += 1
            m = _STATE.search(body[:4096]) if status == 200 else None
            state = m.group(1) if m else b""
            if status != 200 or state in (b"COMPLETED", b"FAILED"):
                break
            time.sleep(self.poll)
        rec["ack"] = time.monotonic()
        self.longest_s = max(self.longest_s, rec["ack"] - t0)
        rec["state"] = state.decode()
        rec["status"] = status if state == b"COMPLETED" else -1
        if state == b"COMPLETED":
            self.last_result = body.decode()
            m = re.search(rb'"startTime":\s*([0-9.]+).{0,40}?'
                          rb'"endTime":\s*([0-9.]+)', body[:4096], re.S)
            if m:
                rec["server_run_s"] = float(m.group(2)) - float(m.group(1))
        else:
            rec["error"] = body[:300].decode(errors="replace")
        t_del = time.monotonic()
        self.http.request("DELETE", f"{self.resource}/{name}")
        rec["delete_s"] = time.monotonic() - t_del
        return rec

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            return {"event": "preloaded", "records": []}
        if cmd[0] == "warm":
            return {"event": "warmed", "records": [self.one_job()]}
        t_open, seconds = float(cmd[1]), float(cmd[2])
        # a traced run: the profiler starts at `hold_at`; no job may run
        # into it (it would finish under the profiler, many times
        # slower), and the next starts `lead` seconds after it, so that
        # its kernel call falls inside the capture
        hold_at = float(cmd[3]) if len(cmd) > 3 else None
        lead = float(cmd[4]) if len(cmd) > 4 else 0.0
        sleep_until(t_open)
        recs = []
        while time.monotonic() < t_open + seconds:
            if hold_at is not None and \
                    time.monotonic() + 1.25 * self.longest_s > hold_at:
                sleep_until(hold_at + lead)
                hold_at = None
            recs.append(self.one_job())
        return {"event": "done", "records": recs,
                "last_result": self.last_result}


ROLES = {"producer": Producer, "reader": Reader, "jobs": JobClient}


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    extend.use(spec["base"])
    t0 = time.monotonic()
    worker = extend.resolve("role", spec["role"])(spec)
    emit({"event": "ready", "prepare_s": time.monotonic() - t0})
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "exit":
            break
        reply = worker.handle(cmd)
        # records can be megabytes: they go to the spec's file, the
        # pipe carries only the event
        with open(spec["out"], "w") as f:
            json.dump(reply, f)
        emit({"event": reply["event"], "n": len(reply["records"])})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
