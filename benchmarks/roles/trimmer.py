"""Role `trimmer`: the capacity monitor's round, asked for at a fixed
moment (`POST /admin/retention`), on a manager that runs its store at
the chart's volume.

In the warm-up it asks for one round and waits for the answer, which
must say `idle`: usage is still under the threshold, nothing is
deleted, and the round counts as the tick, so that no timer tick falls
between the store's crossing of the threshold and the window's round.
In the window it asks for one more `offset_s` seconds after the window
opens (at most half the window's length, so that a rehearsal's short
window holds it too) and waits for the answer (a round that copies
gigabytes under load: timeout 600 s). Nothing else is sent to the
manager but two reads each of `/healthz` and `/metrics`, at the
window's open and at its close: the rounds so far by result, for the
check.

A manager that cannot be asked (an older commit: 404; the loop off:
409) or whose warm-up round is not idle ends this worker in the
warm-up with an error; the harness then ends the run with exit 1
before the window opens.

The answer rides on the record (`result`, `usage_before`,
`rows_before`, `delete_n`, `boundary`, `rows_deleted`,
`view_rows_deleted`, `bytes_freed`, `rows_after`, `seconds`,
`stages_ms`), so that the check knows what the round says it did and a
traced run reports the round's stages whenever it ends.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

from benchmarks import prom
from benchmarks.client import Http, sleep_until

PATH = "/admin/retention"
ROUNDS = "theia_retention_rounds_total"
#: the answer's keys as the record carries them
FIELDS = {"result": "result", "usageBefore": "usage_before",
          "rowsBefore": "rows_before", "deleteN": "delete_n",
          "boundary": "boundary", "rowsDeleted": "rows_deleted",
          "viewRowsDeleted": "view_rows_deleted",
          "bytesFreed": "bytes_freed", "rowsAfter": "rows_after",
          "seconds": "seconds", "stagesMs": "stages_ms"}


class Role:
    def __init__(self, spec: Dict) -> None:
        self.spec = spec
        self.http = Http(spec["addr"], timeout=600.0)

    def rounds(self) -> Optional[Dict[str, int]]:
        """Rounds so far by result, as /metrics counts them, and in
        all as /healthz does (`healthz`); None where either is not
        answered."""
        status, body = self.http.request("GET", "/metrics")
        if status != 200:
            return None
        out = {k[len(ROUNDS) + 9:-2]: int(v)
               for k, v in prom.parse(body.decode()).items()
               if k.startswith(ROUNDS + '{result="')}
        status, body = self.http.request("GET", "/healthz")
        if status != 200:
            return None
        out["healthz"] = json.loads(body).get("retention", {}).get("rounds")
        return out

    def ask(self, due: float) -> Dict:
        t0 = time.monotonic()
        status, body = self.http.request("POST", PATH, b"")
        rec = {"due": due, "send": t0, "ack": time.monotonic(),
               "status": status}
        try:
            doc = json.loads(body)
        except ValueError:
            doc = {}
        if status == 200 and isinstance(doc, dict):
            rec.update({name: doc.get(key) for key, name in FIELDS.items()})
        else:
            rec["error"] = body[:300].decode(errors="replace")
        return rec

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            return {"event": "preloaded", "records": []}
        if cmd[0] == "warm":
            rec = self.ask(time.monotonic())
            if rec["status"] != 200:
                raise SystemExit(
                    f"trimmer: POST {PATH} answered {rec['status']} "
                    f"{rec.get('error', '')}: this manager cannot be "
                    f"asked for a retention round")
            if rec["result"] != "idle":
                raise SystemExit(
                    f"trimmer: the warm-up's round answered "
                    f"{rec['result']!r} at usage {rec['usage_before']}: "
                    f"the store must be under the threshold until the "
                    f"window opens")
            return {"event": "warmed", "records": [rec]}
        t_open, seconds = float(cmd[1]), float(cmd[2])
        sleep_until(t_open)
        at_open = self.rounds()
        due = t_open + min(float(self.spec["offset_s"]), seconds / 2)
        sleep_until(due)
        rec = self.ask(due)
        sleep_until(t_open + seconds)
        return {"event": "done", "records": [rec],
                "rounds_at_open": at_open,
                "rounds_at_close": self.rounds()}
