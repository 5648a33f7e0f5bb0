"""Role `operator`: the person who runs the manager as documented
(`--db`, WAL, a snapshot every interval) and asks for a snapshot.

In the warm-up it asks for one snapshot (`POST /admin/checkpoint`, what
`theia checkpoint` sends) and waits for the answer: the first
generation, so that the window's snapshot rotates `db.npz.prev` and
collects log like every snapshot of a deployment but its first. In the
window it asks for one more `offset_s` seconds after the window opens
(at most half the window's length, so that a rehearsal's short window
holds it too) and waits for the answer. Nothing else is sent to the
manager but two reads of `/healthz`, at the window's open and at its
close: the number of snapshots written so far, for the check.

A manager that cannot be asked (an older commit: 404; no `--db` or
interval 0: 409) ends this worker in the warm-up with an error; the
harness then ends the run with exit 1 before the window opens.

The answer rides on the record (`stamp`, `rows`, `bytes`, `bytes_in`,
`seconds`, `stages_ms`, `generation`, `skipped`), so that the check
knows which acked blocks the snapshot must hold and a traced run
reports the snapshot's stages whenever it ends.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

from benchmarks.client import Http, sleep_until

PATH = "/admin/checkpoint"


class Role:
    def __init__(self, spec: Dict) -> None:
        self.spec = spec
        # a snapshot of millions of rows is compressed beside ingest
        self.http = Http(spec["addr"], timeout=600.0)

    def written(self) -> Optional[int]:
        """Snapshots written so far, as /healthz counts them."""
        status, body = self.http.request("GET", "/healthz")
        if status != 200:
            return None
        return json.loads(body).get("checkpoint", {}).get("written")

    def snapshot(self, due: float) -> Dict:
        t0 = time.monotonic()
        status, body = self.http.request("POST", PATH, b"")
        rec = {"due": due, "send": t0, "ack": time.monotonic(),
               "status": status}
        try:
            doc = json.loads(body)
        except ValueError:
            doc = {}
        if status == 200 and isinstance(doc, dict):
            rec.update(stamp=doc.get("stamp"), rows=doc.get("rows"),
                       bytes=doc.get("bytes"),
                       bytes_in=doc.get("bytesIn"),
                       seconds=doc.get("seconds"),
                       stages_ms=doc.get("stagesMs"),
                       generation=doc.get("generation"),
                       skipped=doc.get("skipped"))
        else:
            rec["error"] = body[:300].decode(errors="replace")
        return rec

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            return {"event": "preloaded", "records": []}
        if cmd[0] == "warm":
            rec = self.snapshot(time.monotonic())
            if rec["status"] != 200:
                raise SystemExit(
                    f"operator: POST {PATH} answered {rec['status']} "
                    f"{rec.get('error', '')}: this manager cannot be "
                    f"asked for a snapshot")
            return {"event": "warmed", "records": [rec]}
        t_open, seconds = float(cmd[1]), float(cmd[2])
        sleep_until(t_open)
        at_open = self.written()
        due = t_open + min(float(self.spec["offset_s"]), seconds / 2)
        sleep_until(due)
        rec = self.snapshot(due)
        sleep_until(t_open + seconds)
        return {"event": "done", "records": [rec],
                "written_at_open": at_open,
                "written_at_close": self.written()}
