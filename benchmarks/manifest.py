"""BENCHMARK.json and the data files its names point at.

A cell is an entry of `workloads`: it names a configuration
(benchmarks/configs/<name>.json) and a traffic mix
(benchmarks/traffic/<name>.json). A metric is an entry of `end_to_end`
or `per_layer`: its reader is benchmarks/end_to_end/<name>.json or
benchmarks/layer_metrics/<name>.json, which names one of the
reductions of reductions.py and its parameters. Adding a cell, a
configuration or a metric is adding files and manifest entries; no
code here knows a name."""

from __future__ import annotations

import copy
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    def __init__(self, doc: Dict, base: str = HERE) -> None:
        self.doc = doc
        self.base = base                       # the benchmark's directory
        self.root = os.path.dirname(base)      # what `file` is relative to

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _read(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str, scale: Optional[Dict] = None) -> Dict:
        doc = _read(os.path.join(self.base, "traffic", name + ".json"))
        doc = copy.deepcopy(doc)
        doc["name"] = name
        if scale and "generator" in scale:
            doc["generator"].update(scale["generator"])
        for key in ("trace_seconds", "trace_lead_seconds"):
            if scale and key in scale:
                doc[key] = scale[key]
        return doc

    def metrics_of(self, cell: str, section: str) -> List[Dict]:
        """The manifest's metrics of `section` that this cell reports:
        those that list it under `workloads`, and those without the
        key whose `moves` (or own name) the cell reports."""
        e2e = {m["name"]: m for m in self.doc["end_to_end"]}

        def reports(m: Dict) -> bool:
            return "workloads" not in m or cell in m["workloads"]

        if section == "end_to_end":
            return [m for m in self.doc["end_to_end"] if reports(m)]
        return [m for m in self.doc["per_layer"]
                if reports(m) and reports(e2e[m["moves"]])]

    def reader(self, section: str, name: str) -> Dict:
        sub = "end_to_end" if section == "end_to_end" else "layer_metrics"
        return _read(os.path.join(self.base, sub, name + ".json"))


def load(path: Optional[str] = None) -> Bench:
    return Bench(_read(path or os.path.join(ROOT, "BENCHMARK.json")))
