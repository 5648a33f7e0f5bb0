"""`python3 -m benchmarks.selftest`: the rehearsal that costs no chip time.

1. The metric arithmetic: percentiles, spreads, roofline bytes from
   shapes, the peaks table's refusal of an unknown device.
2. The trace → busy/idle reduction on the small recorded trace in
   benchmarks/data/recorded_trace.json (a TAD EWMA job on a TPU v5e,
   the first events of every device line).
3. Every cell of BENCHMARK.json end to end at a tiny size under
   JAX_PLATFORMS=cpu (Pallas interpreted): wrong paths, arguments and
   control flow show here. It is a REHEARSAL: it prints whether each
   cell's plumbing and check hold and never a metric's value, because a
   number from the CPU backend says nothing about the chip.
4. Data-drivenness: a cell, a configuration and a per-layer metric
   added as files only (in a temporary overlay: the first Open question
   of PERF.md, `parts-fused.ingest-saturate`) run without any edit of
   the code.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys
import tempfile
import time

from . import harness, manifest, rehearsal, roofline, stats, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def check(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what, flush=True)
    if not cond:
        raise SystemExit(f"selftest failed: {what}")


def arithmetic() -> None:
    xs = list(range(1, 401))                      # 400 latencies
    check(abs(stats.percentile(xs, 50) - 200.5) < 1e-9, "median of 1..400")
    check(abs(stats.percentile(xs, 95) - 380.05) < 1e-9,
          "95th percentile of 1..400 (linear interpolation)")
    check(abs(stats.iqr_spread([100, 101, 102, 103, 104, 105])
              - 3.5 / 102.5) < 1e-9, "IQR spread as statistics.quantiles")
    check(abs(stats.range_spread([100, 102, 104]) - 4 / 102) < 1e-12,
          "gate statistic (max - min) / median")
    check(roofline.ewma_scores_bytes(8000, 128) == 8000 * 128 * 10 + 32000,
          "ewma_scores bytes: f32 in, bool mask in, f32 + bool out, f32 std")
    check(roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9,
          "v5e HBM peak 819 GB/s")
    try:
        roofline.peaks("TPU v9000")
        check(False, "an unknown device kind is an error")
    except KeyError:
        check(True, "an unknown device kind is an error")


def trace_reduction() -> None:
    check(abs(tracered.union_seconds([(0, 2), (1, 3), (5, 6)]) - 4.0)
          < 1e-12, "busy union of overlapping intervals")
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in ls.items()}
                  for p, ls in json.load(f).items()}
    red = tracered.reduce_planes(planes)
    ops = [e for ls in planes.values() for e in ls.get("XLA Ops", [])]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops))
    check(red["devices_traced"] == 1, "one device plane in the recording")
    every = [e for ls in planes.values() for evs in ls.values() for e in evs]
    check(abs(red["span_s"] - (max(s + d for _, s, d in every)
                               - min(s for _, s, _ in every))) < 1e-12,
          "span is the device events' first to last timestamp")
    check(0 < red["busy_s"] <= span + 1e-12,
          f"busy {red['busy_s']:.3e} s within the ops' span {span:.3e} s")
    check(abs(red["busy_s"] - tracered.union_seconds(
        (s, s + d) for _, s, d in ops)) < 1e-12, "busy is the ops' union")
    mods = [r for r in red["ops"] if r[0].startswith("module:")]
    check(any("ewma_scores" in r[0] for r in mods),
          "the EWMA program is found by its jitted name")
    check(len(red["breakdown"]["device_ops"]) <= 10
          and len(red["breakdown"]["idle_gaps"]) <= 10,
          "breakdown lists at most ten entries each")


def rehearse(cell: dict, bench=None, trace: bool = True) -> None:
    bench = bench or manifest.load()
    scale = rehearsal.scale_for(bench, cell)
    for tr in ([False, True] if trace else [False]):
        # a traced rehearsal needs room after the profiler's export,
        # which is slow on the CPU backend (its host trace is large)
        out = harness.run_cell(cell["name"], 7, 20.0 if tr else 5.0, tr,
                               time.monotonic(), platform="cpu",
                               scale=scale, bench=bench)
        want = {m["name"] for m in bench.metrics_of(
            cell["name"], "per_layer" if tr else "end_to_end")}
        device_only = {m["name"] for m in
                       bench.doc["per_layer"]
                       if m["source"] == "device_trace"}
        missing = want - set(out["metrics"]) - device_only
        check(out["correct"] and out["failed"] == 0,
              f"REHEARSAL {cell['name']} trace={int(tr)}: check holds "
              f"({out['attempted']} operations)")
        check(not missing, f"REHEARSAL {cell['name']} trace={int(tr)}: "
              f"every metric reported except device ones (missing "
              f"{sorted(missing)})")
        check(out["device"]["platform"] == "cpu",
              "REHEARSAL ran on the CPU backend and says so")


def files_only() -> None:
    """A later PR's cell, configuration and metric: files and manifest
    entries in an overlay, no edit of any code."""
    tmp = tempfile.mkdtemp(prefix="selftest-overlay-")
    try:
        base = os.path.join(tmp, "benchmarks")
        for sub in ("configs", "traffic", "end_to_end", "layer_metrics"):
            shutil.copytree(os.path.join(HERE, sub), os.path.join(base, sub))
        doc = copy.deepcopy(manifest.load().doc)
        cfg = json.load(open(os.path.join(
            base, "configs", "theia-parts-fused-1x1.json")))
        cfg["name"] = "theia-parts-fused-1x2"
        cfg["manager_args"] = cfg["manager_args"] + ["--workers", "3"]
        with open(os.path.join(base, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
        doc["configs"].append({
            "name": cfg["name"], "source": "selftest overlay",
            "file": f"benchmarks/configs/{cfg['name']}.json",
            "reduced": [], "why": "selftest overlay"})
        new = "parts-fused.ingest-saturate"
        doc["workloads"].append({
            "name": new, "config": cfg["name"],
            "traffic": "ingest-saturate", "chips": 1, "why": "overlay"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if "default.ingest-saturate" in m.get("workloads", []):
                m["workloads"].append(new)
        with open(os.path.join(base, "layer_metrics",
                               "store_parts_sealed.json"), "w") as f:
            json.dump({"reduce": "counter_delta",
                       "series": "theia_store_parts_sealed_total"}, f)
        doc["per_layer"].append({
            "name": "store_parts_sealed", "unit": "parts",
            "better": "lower", "source": "program_counter",
            "layer": "store", "moves": "acked_rows_per_s",
            "workloads": [new]})
        bench = manifest.Bench(doc, base=base)
        rehearse(bench.cell(new), bench=bench, trace=True)
        check(True, "a cell, a configuration and a per-layer metric "
              "were added as files only")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    print("selftest: a REHEARSAL on the CPU backend; no metric printed "
          "here is a result")
    arithmetic()
    trace_reduction()
    for cell in manifest.load().doc["workloads"]:
        rehearse(cell)
    files_only()
    print("selftest: all ok (rehearsal only)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
