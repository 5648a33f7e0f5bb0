"""Role `grafana`: the range contract, held before a block is preloaded.

The panels a `reader` of this traffic file times are requests with a
time range (`?start=..&end=..`). They are requests Grafana sends only
if the dashboards it imported tell it to: a target of the JSON API
datasource fetches its `urlPath` with the `params` it carries and with
nothing else, so a target without `start` and `end` asks for the whole
store at every refresh, whatever the time picker says.

In the preload phase this worker fetches the manager's own export,
`GET /dashboards/api/<name>?format=grafana`, for every dashboard a
reader's panel names, and holds each target of each of its panels to
the reader's request: where the reader's path carries `start` and
`end`, the target's `params` must carry

    ["start", "${__from:date:seconds}"], ["end", "${__to:date:seconds}"]

(the dashboard's range in epoch seconds, which is what the panels'
`start` / `end` take), and where it carries neither (`homepage`), the
target must carry neither. Anything else ends this worker with a
message that names the dashboard and the panel; the harness then ends
the run with exit 1, seconds after the manager is up and before the
window opens (list this role first among `workers`: the harness reads
the phases' answers in list order). A manager that cannot be asked for
the export ends it the same way.

In the warm-up, the window and the probes it does nothing and returns
no records.
"""

from __future__ import annotations

import json
import urllib.parse
from typing import Dict, List

from benchmarks.client import Http

#: the query parameters of a ranged panel, and Grafana's variables
#: for the dashboard's range in epoch seconds
RANGE = {"start": "${__from:date:seconds}", "end": "${__to:date:seconds}"}


def ranged_panels(traffic: Dict) -> Dict[str, bool]:
    """{dashboard: whether the readers ask it with a range} over the
    panels of every `reader` group; a dashboard asked both ways, or
    with half a range, is a broken traffic file."""
    out: Dict[str, bool] = {}
    for group in traffic["workers"]:
        if group["role"] != "reader":
            continue
        for panel in group["panels"]:
            url = urllib.parse.urlsplit(panel["path"])
            name = url.path.rsplit("/", 1)[1]
            asked = set(urllib.parse.parse_qs(url.query)) & set(RANGE)
            if asked not in (set(), set(RANGE)) or \
                    out.setdefault(name, bool(asked)) != bool(asked):
                raise SystemExit(
                    f"grafana: the traffic file asks {name} with "
                    f"{sorted(asked) or 'no range'}: a panel carries "
                    f"start and end, or neither, every time")
    return out


def held(name: str, doc: Dict, ranged: bool) -> int:
    """Hold every target of one exported dashboard to the contract;
    returns how many targets it has."""
    targets = 0
    for panel in doc.get("panels", []):
        for target in panel.get("targets", []):
            targets += 1
            where = (f"dashboard {name}, panel {panel.get('title')!r} "
                     f"(target {target.get('refId')}, "
                     f"{target.get('urlPath')})")
            params = {p[0]: p[1] for p in target.get("params", [])}
            for key, value in RANGE.items():
                if ranged and params.get(key) != value:
                    raise SystemExit(
                        f"grafana: {where}: params carry "
                        f"{key}={params.get(key)!r}, not {value!r}: an "
                        f"imported dashboard would not send its time "
                        f"range, and every refresh would ask for the "
                        f"whole store")
                if not ranged and key in params:
                    raise SystemExit(
                        f"grafana: {where}: params carry {key} where "
                        f"the panel takes no range")
    if not targets:
        raise SystemExit(f"grafana: dashboard {name}: the export has "
                         f"no target to hold")
    return targets


class Role:
    def __init__(self, spec: Dict) -> None:
        self.spec = spec
        self.http = Http(spec["addr"])
        self.ranged = ranged_panels(spec["traffic"])

    def handle(self, cmd: List[str]) -> Dict:
        if cmd[0] == "preload":
            targets = 0
            for name, ranged in self.ranged.items():
                path = f"/dashboards/api/{name}?format=grafana"
                status, body = self.http.request("GET", path)
                if status != 200:
                    raise SystemExit(
                        f"grafana: GET {path} answered {status} "
                        f"{body[:200].decode(errors='replace')}: this "
                        f"manager exports no dashboard {name}")
                targets += held(name, json.loads(body), ranged)
            return {"event": "preloaded", "records": [],
                    "dashboards": len(self.ranged), "targets": targets}
        event = {"warm": "warmed", "probe": "probed"}.get(cmd[0], "done")
        return {"event": event, "records": []}
