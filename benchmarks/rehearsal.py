"""The rehearsal's size: what selftest.py and benchmarks/tests shrink a
cell to so that it runs end to end on the CPU backend in seconds. A
rehearsal finds wrong paths, arguments and control flow; it is never a
result."""

from __future__ import annotations

import copy
from typing import Dict

#: ingest cells: 512 connections a producer, 256-row blocks
TINY = {
    "generator": {"connections_per_producer": 512, "conns_per_block": 64,
                  "points_per_conn": 4},
    "warm_blocks": 8, "prepared_blocks": 48, "trace_seconds": 1,
    "env": {"THEIA_FUSED_PALLAS": "interpret"},
}
#: cells with a retained window: 32 blocks of 64 connections x 4 points,
#: so that the 128 preloaded seconds the panels' ranges name exist
TINY_RETAINED = {
    "generator": {"connections_per_producer": 64, "conns_per_block": 64,
                  "points_per_conn": 4},
    "preload_blocks": 32, "warm_blocks": 0, "prepared_blocks": 40,
    "trace_seconds": 1, "trace_lead_seconds": 0.2,
    "env": {"THEIA_FUSED_PALLAS": "interpret"},
}


def scale_for(bench, cell: Dict) -> Dict:
    retained = any(w.get("preload_blocks")
                   for w in bench.traffic(cell["traffic"])["workers"])
    scale = copy.deepcopy(TINY_RETAINED if retained else TINY)
    if bench.config(cell["config"])["expect"].get(
            "detector_engine") == "fused":
        # `auto` resolves to the sharded engine on the CPU backend
        scale["env"]["THEIA_DETECTOR_ENGINE"] = "fused"
    return scale
