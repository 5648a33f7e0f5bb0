"""The control of the check: the reference put in the program's place,
computed in the nearest precision below the float32 the detector and
the EWMA job state (inputs rounded to bfloat16, arithmetic float32).
It has to come out as NOT correct against the float64 reference under
the cell's own limits, at the cell's own size.

    python3 -m benchmarks.control <cell> <blocks per producer> <seed>...

(blocks per producer: preload, warm-up and window together; the
traffic file's `probe_blocks` follow them) prints, per seed, each
number the cell's check compares with a tolerance, computed as
check.py computes it, as the control gives it, beside the limit. PERF.md records
the readings the limits were set from. benchmarks/tests keeps the same
control at a size a test run can hold.

A check that came as a file (benchmarks/checks/<name>.py) brings the
control of its own tolerances: `control(traffic, seed, n_blocks,
precision) -> {number: value}`, merged with the built-in ones here."""

from __future__ import annotations

import json
import sys
from typing import Dict, List

import numpy as np

from . import check, extend, gen, manifest
from . import reference as ref


def control_numbers(traffic: Dict, seed: int, n_blocks: int,
                    precision: str = "bf16") -> Dict[str, float]:
    checks = traffic["checks"]
    out: Dict[str, float] = {}
    want = got = points = probe_diff = probe_points = 0
    mism = scored = 0
    producer = 0
    for group in traffic["workers"]:
        if group["role"] != "producer":
            continue
        probes = int(group.get("probe_blocks", 0))
        for _ in range(int(group.get("count", 1))):
            s = gen.stream(traffic, seed, producer)
            producer += 1
            if "detector_alerts" in checks:
                # the whole run in the lower precision, the probe
                # blocks compared block by block (check_detector_alerts)
                a = ref.detector_alerts(s, n_blocks + probes, "f64")
                b = ref.detector_alerts(s, n_blocks + probes, precision)
                want += int(a.sum())
                got += int(b.sum())
                points += (n_blocks + probes) * s.rows
                probe_diff += int(np.abs(a - b)[n_blocks:].sum())
                probe_points += probes * s.rows
            if "jobs" in checks:
                vals, _, mask = ref.series_of(s, n_blocks)
                a = ref.tad_ewma(vals, mask, "f64")
                b = ref.tad_ewma(vals, mask, precision)
                mism += int((a ^ b).sum())
                scored += int(mask.sum())
    if points:
        out["alert_count_gap"] = abs(got - want) / points
        out["alert_probe_block_gap"] = probe_diff / max(probe_points, 1)
    if scored:
        out["tad_decision_mismatch"] = mism / scored
    for name in checks:
        mod = extend.module("check", name)
        if mod is not None and hasattr(mod, "control"):
            out.update(mod.control(traffic, seed, n_blocks, precision))
    return out


def main(argv: List[str]) -> int:
    bench = manifest.load()
    extend.use(bench.base)
    traffic = bench.traffic(bench.cell(argv[1])["traffic"])
    n_blocks = int(argv[2])
    failed_all = True
    for seed in map(int, argv[3:]):
        nums = control_numbers(traffic, seed, n_blocks)
        fails = {k: v > check.limit(traffic, k)
                 for k, v in nums.items()}
        print(json.dumps({"cell": argv[1], "seed": seed,
                          "blocks_per_producer": n_blocks,
                          "control": nums, "limits": traffic["limits"],
                          "control_not_correct": any(fails.values())}),
              flush=True)
        failed_all &= any(fails.values())
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
