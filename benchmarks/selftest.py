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
4. Data-drivenness: a deployment added as files only, in a temporary
   overlay: a configuration, a traffic mix and a cell that name a key
   law, a check, a worker role, a reduction and a kernel's roofline
   function which are files too (benchmarks/extend.py). The cell runs
   end to end with no edit of any code, `correct` true; the same check
   file with a perturbed expectation gives `correct` false.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys
import tempfile
import time

from . import (extend, harness, manifest, rehearsal, roofline, stats,
               tracered)

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


def rehearse(cell: dict, bench=None, trace: bool = True) -> list:
    """One untraced run and, with `trace`, one traced: their results."""
    bench = bench or manifest.load()
    scale = rehearsal.scale_for(bench, cell)
    outs = []
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
        outs.append(out)
    return outs


#: what a later PR's deployment brings, one file of each kind (step 4)
OVERLAY_FILES = {
    "laws/hot_slice.py": '''"""Key law `hot_slice`: of a producer's slices of connections, slice 0
comes three times in every round of visits and each other slice once.
The seed changes the values and the order of a round's visits; which
connections exist, and how often a slice comes, it does not."""
import numpy as np

from benchmarks.gen import ProducerStream


class Stream(ProducerStream):
    def __init__(self, traffic, seed, producer):
        super().__init__(traffic, seed, producer)
        rng = np.random.default_rng([self.seed, producer, 0x407])
        self.round = rng.permutation([0, 0] + list(range(self.n_slices)))

    def slice_of(self, b):
        return int(self.round[b % len(self.round)])
''',
    "checks/series_by_law.py": '''"""As many detector series as the key law sent distinct connections."""
from benchmarks import check as _check

limits = ("series_gap_share",)
EXPECTED_BESIDE_THE_LAW = 0


def check(ctx, rep):
    series = sum(s["series"] for s in ctx["health"]["ingest"]["perShard"])
    want = EXPECTED_BESIDE_THE_LAW
    for stream, n, _ in _check.streams(ctx):
        want += len(set().union(*(stream.conn_index(b).tolist()
                                  for b in range(n))))
    rep.compare("series_gap_share", abs(series - want) / max(want, 1),
                _check.limit(ctx["traffic"], "series_gap_share"),
                f"{series} series, {want} connections under the law")
''',
    "roles/health_probe.py": '''"""An operator's one request at a fixed offset in the window."""
import json
import time

from benchmarks.client import Http, sleep_until


class Role:
    def __init__(self, spec):
        self.spec = spec
        self.http = Http(spec["addr"])

    def ask(self, due):
        t0 = time.monotonic()
        status, body = self.http.request("GET", "/healthz")
        rec = {"due": due, "send": t0, "ack": time.monotonic(),
               "status": status}
        if status == 200:
            rec["flow_rows"] = json.loads(body)["store"]["flowRows"]
        return rec

    def handle(self, cmd):
        if cmd[0] == "preload":
            return {"event": "preloaded", "records": []}
        if cmd[0] == "warm":
            return {"event": "warmed",
                    "records": [self.ask(time.monotonic())]}
        due = float(cmd[1]) + float(self.spec["offset_s"])
        sleep_until(due)
        return {"event": "done", "records": [self.ask(due)]}
''',
    "reduce/counter_per_block.py": '''"""A counter's rise over the window per ingest block acked in it."""
from benchmarks import prom


def reduce(data, p):
    before, after = data["metrics_before"], data["metrics_after"]
    try:
        blocks = prom.delta(before, after, "theia_ingest_batches_total")
        rise = prom.delta(before, after, p["series"])
    except KeyError:
        return None
    return rise / blocks if blocks > 0 else None
''',
    "kernels/toy_step.py": '''"""A fixture, not a kernel of the program: 12 bytes and
`flops_per_point` operations for every point of one block."""


def least(data):
    g = data["traffic"]["generator"]
    points = g["conns_per_block"] * g["points_per_conn"]
    return {"bytes": 12 * points,
            "flops": int(g.get("flops_per_point", 0)) * points}
''',
}


def write(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def deployment_as_files() -> None:
    """A later PR's deployment: a configuration, a traffic mix, a cell,
    a check, a key law, a worker role, a reduction, a kernel's roofline
    function and three per-layer metrics, all as files and manifest
    entries in an overlay, with no edit of any file that is there."""
    tmp = tempfile.mkdtemp(prefix="selftest-overlay-")
    base = os.path.join(tmp, "benchmarks")
    try:
        for sub in ("configs", "traffic", "end_to_end", "layer_metrics"):
            shutil.copytree(os.path.join(HERE, sub), os.path.join(base, sub))
        for rel, text in OVERLAY_FILES.items():
            write(os.path.join(base, rel), text)
        doc = copy.deepcopy(manifest.load().doc)
        with open(os.path.join(base, "configs",
                               "theia-default-1x1.json")) as f:
            cfg = json.load(f)
        cfg["name"] = "overlay-default-1x1"
        write(os.path.join(base, "configs", cfg["name"] + ".json"),
              json.dumps(cfg))
        doc["configs"].append({
            "name": cfg["name"], "source": "selftest overlay",
            "file": f"benchmarks/configs/{cfg['name']}.json",
            "reduced": [], "why": "selftest overlay"})
        write(os.path.join(base, "traffic", "hot-ingest.json"), json.dumps({
            "generator": {"law": "hot_slice",
                          "connections_per_producer": 512,
                          "conns_per_block": 64, "points_per_conn": 4,
                          "spike_rate": 0.0, "flops_per_point": 8},
            "workers": [
                {"role": "producer", "count": 2, "warm_blocks": 10,
                 "prepared_blocks": 48, "probe_blocks": 2},
                {"role": "health_probe", "count": 1, "offset_s": 1.0}],
            "trace_seconds": 1,
            "checks": ["acks", "store_totals", "detector_series",
                       "detector_alerts", "series_by_law"],
            "limits": {"alert_count_gap": 1e-05,
                       "alert_probe_block_gap": 5e-05,
                       "series_gap_share": 0.0}}))
        new = "overlay.hot-ingest"
        doc["workloads"].append({
            "name": new, "config": cfg["name"], "traffic": "hot-ingest",
            "chips": 1, "why": "overlay"})
        # a new cell's name is appended to every metric it reports
        for m in doc["end_to_end"] + doc["per_layer"]:
            if "default.ingest-saturate" in m.get("workloads", []):
                m["workloads"].append(new)
        readers = {
            "detector.partition_bytes_per_block": (
                "bytes/block", "program_counter", "detector",
                {"reduce": "counter_per_block",
                 "series": "theia_detector_partition_bytes_total"}),
            "healthz_at_offset_ms": (
                "ms", "host_clock", "operator",
                {"reduce": "latency_percentile", "role": "health_probe",
                 "q": 50}),
            "toy_step_roofline": (
                "%", "device_trace", "detector",
                {"reduce": "roofline_share", "kernel": "toy_step",
                 "match": "module:jit_stream_update_sparse"}),
        }
        for name, (unit, source, layer, reader) in readers.items():
            write(os.path.join(base, "layer_metrics", name + ".json"),
                  json.dumps(reader))
            doc["per_layer"].append({
                "name": name, "unit": unit, "better": "lower",
                "source": source, "layer": layer,
                "moves": "acked_rows_per_s", "workloads": [new]})
        bench = manifest.Bench(doc, base=base)
        cell = bench.cell(new)
        plain, traced = rehearse(cell, bench=bench, trace=True)
        check("series_gap_share" in traced["checks"]
              and "alert_probe_block_gap" in traced["checks"],
              "the file's number stands beside the built-in ones, each "
              "with its limit, under the result's `checks`")
        check({"detector.partition_bytes_per_block",
               "healthz_at_offset_ms"} <= set(traced["metrics"]),
              "the file's reduction and the file's role's records are "
              "read into metrics")
        data = {"traffic": bench.traffic("hot-ingest")}
        pk = roofline.peaks("TPU v5 lite")
        check(roofline.least_seconds("toy_step", data, {"kind": "TPU v5 lite"})
              == 12 * 256 / pk["hbm_bytes_per_s"],
              "the file's kernel function: bound by its bytes")
        data["traffic"]["generator"]["flops_per_point"] = 10 ** 6
        check(roofline.least_seconds("toy_step", data, {"kind": "TPU v5 lite"})
              == 256e6 / pk["bf16_flops_per_s"],
              "the file's kernel function: bound by its operations")
        # the same check file with a perturbed expectation
        extend.forget(base)
        write(os.path.join(base, "checks", "series_by_law.py"),
              OVERLAY_FILES["checks/series_by_law.py"].replace(
                  "EXPECTED_BESIDE_THE_LAW = 0",
                  "EXPECTED_BESIDE_THE_LAW = 1000"))
        out = harness.run_cell(new, 7, 3.0, False, time.monotonic(),
                               platform="cpu",
                               scale=rehearsal.scale_for(bench, cell),
                               bench=bench)
        failed = [k for k, c in out["checks"].items()
                  if c["value"] > c["limit"]]
        check(out["correct"] is False and failed == ["series_gap_share"],
              "REHEARSAL the file-borne check with a perturbed "
              "expectation gives correct: false")
        check(True, "a deployment was added as files only: "
              + ", ".join(sorted(OVERLAY_FILES)))
    finally:
        extend.forget(base)
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    print("selftest: a REHEARSAL on the CPU backend; no metric printed "
          "here is a result")
    arithmetic()
    trace_reduction()
    for cell in manifest.load().doc["workloads"]:
        rehearse(cell)
    deployment_as_files()
    print("selftest: all ok (rehearsal only)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
