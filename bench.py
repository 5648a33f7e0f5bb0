#!/usr/bin/env python
"""Benchmark: TAD scoring throughput on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference's documented end-to-end capacity is ~4,000 flow
records/s (ClickHouse insert rate on the default deployment,
reference docs/network-flow-visibility.md:484-488; the Spark jobs then
re-scan those rows in minutes-long batches). Here the comparable number
is how many flow records per second the TPU engine scores through the
jitted EWMA anomaly step (scan + stddev + threshold over padded series).

Method: synthesize a small host batch once, tile it to a large
device-resident [S, T] batch (so the Python-bound generator is off the
measured path — VERDICT r1 note), then time steady-state jitted steps.
Each step scores S·T flow records. Secondary numbers (host tensorize
rate, device transfer) go to stderr.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_RECORDS_PER_SEC = 4000.0


def _run_child(env: dict, timeout_s: float):
    """Run the measurement in a child process (THEIA_BENCH_INNER=1):
    the orchestrator stays off JAX, so the child is the one process
    that holds the accelerator, and a hung child can be killed.
    Returns (stdout, failure_reason): stdout is the JSON line (b'' on
    failure); failure_reason is None, "timeout", "exit rc=N" or
    "no output (rc=0)"."""
    try:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env={**env, "THEIA_BENCH_INNER": "1"},
            stdout=subprocess.PIPE, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"bench child timed out after {timeout_s:.0f}s",
              file=sys.stderr)
        return b"", "timeout"
    if child.returncode != 0:
        return b"", f"exit rc={child.returncode}"
    out = child.stdout.strip()
    return out, (None if out else "no output (rc=0)")


def _parse_args(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        description="theia-tpu benchmark driver (one JSON result "
                    "line on stdout; non-zero exit if the child "
                    "fails)")
    p.add_argument("--out", default="",
                   help="write the result as a schema-versioned JSON "
                        "artifact (host metadata + per-leg values) to "
                        "this path; default: no artifact")
    return p.parse_args(argv)


def _write_artifact(path: str, result: dict) -> None:
    """Schema-versioned bench artifact: the result dict plus enough
    host metadata to interpret (or distrust) the numbers later."""
    import datetime
    import platform
    import socket
    doc = {
        "schemaVersion": 1,
        "createdAt": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "host": {
            "hostname": socket.gethostname(),
            "cpus": os.cpu_count(),
            "platform": platform.platform(),
            "python": platform.python_version(),
        },
        # knobs only, never credentials: the artifact is meant to be
        # committed/shared (THEIA_TOKEN / THEIA_AUTH_TOKEN carry the
        # deployment's service secret)
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("THEIA_", "JAX_"))
                and not any(s in k for s in
                            ("TOKEN", "SECRET", "KEY", "PASSWORD"))},
        "result": result,
    }
    try:
        import jax
        doc["host"]["jax"] = jax.__version__
    except Exception:
        pass
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    print(f"bench artifact written to {path}", file=sys.stderr)


def _leg_stats(times) -> dict:
    """best/median/spread for one timed leg's per-iteration seconds —
    recorded under result["leg_stats"] so the 2-core bench host's
    run-to-run noise (ROADMAP: 12-36k rows/s swings across identical
    runs) is visible IN the JSON artifact, not just changelog prose.
    spreadPct = (worst - best) / median."""
    ts = sorted(float(t) for t in times)
    med = ts[len(ts) // 2]
    return {
        "iterations": len(ts),
        "bestMs": round(ts[0] * 1e3, 3),
        "medianMs": round(med * 1e3, 3),
        "spreadPct": round((ts[-1] - ts[0]) / med * 100, 1)
        if med > 0 else 0.0,
    }


def main() -> None:
    """Prints one JSON result line on stdout. The orchestrator (this
    function) owns no JAX state; it runs the measurement once, in a
    child, on whatever backend JAX gives it. A child that fails or
    times out (THEIA_BENCH_TIMEOUT, default 420 s) makes the bench
    exit non-zero — there is no retry and no CPU fallback."""
    if os.environ.get("THEIA_BENCH_INNER") == "1":
        print(json.dumps(run_benchmarks()))
        return
    args = _parse_args()
    timeout_s = float(os.environ.get("THEIA_BENCH_TIMEOUT") or "420")
    out, why = _run_child(dict(os.environ), timeout_s)
    if not out:
        print(f"bench failed: {why}", file=sys.stderr)
        raise SystemExit(1)
    if args.out:
        try:
            _write_artifact(args.out, json.loads(out))
        except Exception as e:
            print(f"bench artifact write failed: {e}", file=sys.stderr)
    sys.stdout.buffer.write(out + b"\n")
    sys.stdout.flush()


def run_benchmarks() -> dict:
    import jax

    from theia_tpu.utils.device import enable_compile_cache
    enable_compile_cache()

    from theia_tpu.analytics import TadQuerySpec, build_series
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.ops.ewma import ewma_scores

    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr)

    # Host side: generate + tensorize a seed batch (measured separately).
    cfg = SynthConfig(n_series=256, points_per_series=128,
                      anomaly_fraction=0.1, seed=0)
    t0 = time.perf_counter()
    batch = generate_flows(cfg)
    t1 = time.perf_counter()
    series = build_series(batch, TadQuerySpec(), dtype=np.float32)
    tensorize_rate = 0.0
    for _ in range(3):   # warm best-of-3 (first call pays the .so load)
        t2 = time.perf_counter()
        series = build_series(batch, TadQuerySpec(), dtype=np.float32)
        tensorize_rate = max(tensorize_rate,
                             len(batch) / (time.perf_counter() - t2))
    print(f"host synth: {len(batch) / (t1 - t0):,.0f} rows/s; "
          f"tensorize: {tensorize_rate:,.0f} rows/s",
          file=sys.stderr)

    # Tile to a large device batch: 32768 series x 128 steps = 4.2M
    # records per step (~16 MiB fp32).
    reps = 32768 // series.values.shape[0]
    x = np.tile(series.values.astype(np.float32), (reps, 1))
    mask = np.tile(series.mask, (reps, 1))
    n_records = x.size

    t3 = time.perf_counter()
    xd = jax.device_put(x)
    md = jax.device_put(mask)
    jax.block_until_ready((xd, md))
    t4 = time.perf_counter()
    print(f"device transfer: {x.nbytes / (t4 - t3) / 1e9:.2f} GB/s",
          file=sys.stderr)

    # Warmup (compile) then steady-state timing.
    out = ewma_scores(xd, md)
    jax.block_until_ready(out)
    n_iters = 20
    t5 = time.perf_counter()
    for _ in range(n_iters):
        out = ewma_scores(xd, md)
    jax.block_until_ready(out)
    t6 = time.perf_counter()

    step_s = (t6 - t5) / n_iters
    records_per_sec = n_records / step_s
    print(f"step: {step_s * 1e3:.3f} ms for {n_records:,} records "
          f"({x.nbytes / step_s / 1e9:.1f} GB/s effective)",
          file=sys.stderr)

    # Secondary: ARIMA / DBSCAN steady-state device rates on a smaller
    # batch (ARIMA's walk-forward scan is far heavier than EWMA).
    # THEIA_BENCH_FAST (set on the CPU-fallback retry) skips them —
    # minutes of walk-forward ARIMA on a host core would starve the
    # stages that still say something useful about the pipeline.
    try:
        if os.environ.get("THEIA_BENCH_FAST") == "1":
            raise RuntimeError("THEIA_BENCH_FAST=1")
        from theia_tpu.ops import arima_scores, dbscan_scores
        xs, ms = xd[:4096], md[:4096]
        for name, fn in (("ARIMA", arima_scores),
                         ("DBSCAN", dbscan_scores)):
            jax.block_until_ready(fn(xs, ms))   # compile
            ta = time.perf_counter()
            for _ in range(5):
                out2 = fn(xs, ms)
            jax.block_until_ready(out2)
            rate = xs.size * 5 / (time.perf_counter() - ta)
            print(f"{name} scoring: {rate:,.0f} records/s "
                  f"({xs.shape[0]} series)", file=sys.stderr)
    except Exception as e:
        print(f"algo bench skipped: {e}", file=sys.stderr)

    # Secondary diagnostics (stderr): native ingest rate + streaming
    # alert latency on this chip.
    try:
        from theia_tpu.ingest import BlockEncoder, TsvDecoder, \
            encode_tsv, native_available
        if native_available():
            payload = encode_tsv(batch) * 8
            dec = TsvDecoder()
            dec.decode(payload)   # warm
            t7 = time.perf_counter()
            decoded = dec.decode(payload)
            t8 = time.perf_counter()
            print(f"native ingest (TSV): "
                  f"{len(decoded) / (t8 - t7):,.0f} rows/s",
                  file=sys.stderr)
            enc = BlockEncoder(dicts=batch.dicts)
            blocks = [enc.encode(batch) for _ in range(9)]
            bdec = TsvDecoder()
            bdec.decode_block(blocks[0])   # warm + dict delta
            t7 = time.perf_counter()
            n_blk = sum(len(bdec.decode_block(p)) for p in blocks[1:])
            t8 = time.perf_counter()
            print(f"native ingest (binary block): "
                  f"{n_blk / (t8 - t7):,.0f} rows/s", file=sys.stderr)
    except Exception as e:
        print(f"ingest bench skipped: {e}", file=sys.stderr)

    try:
        from theia_tpu.store import FlowDatabase
        host = generate_flows(SynthConfig(n_series=2000,
                                          points_per_series=30))
        FlowDatabase().insert_flows(host)   # warm native group-sum
        best = 0.0
        for _ in range(3):
            db = FlowDatabase()
            t9 = time.perf_counter()
            db.insert_flows(host)
            best = max(best, len(host) / (time.perf_counter() - t9))
        print(f"store insert (3 MV fan-out): {best:,.0f} rows/s",
              file=sys.stderr)
    except Exception as e:
        print(f"store bench skipped: {e}", file=sys.stderr)

    # Degraded-mode fan-out: replicated write throughput with one of
    # two replicas auto-quarantined by an injected per-replica write
    # fault — the number an operator sees between a replica failure
    # and its repair-loop re-admission.
    degraded_write = 0.0
    try:
        from theia_tpu.store import ReplicatedFlowDatabase
        from theia_tpu.utils import faults
        host2 = generate_flows(SynthConfig(n_series=2000,
                                           points_per_series=30))
        rdb = ReplicatedFlowDatabase(replicas=2)
        rdb.insert_flows(host2)   # warm both replicas
        faults.arm("replica.write:error@2")   # next fan-out, replica 1
        try:
            rdb.insert_flows(host2)
        finally:
            faults.disarm()
        if not rdb.membership()["quarantined"]:
            raise RuntimeError("injected fault did not quarantine")
        best = 0.0
        for _ in range(3):
            tq = time.perf_counter()
            rdb.insert_flows(host2)
            best = max(best,
                       len(host2) / (time.perf_counter() - tq))
        degraded_write = best
        print(f"degraded fan-out write (1 of 2 replicas "
              f"quarantined): {best:,.0f} rows/s", file=sys.stderr)
    except Exception as e:
        print(f"degraded-write bench skipped: {e}", file=sys.stderr)

    # End-to-end pipeline: wire bytes → stream decode → store insert
    # (3 MV fan-out, TTL check) → heavy-hitter + per-connection
    # streaming detectors → alert ring — the whole POST /ingest path
    # as one number (VERDICT r2 #2).
    e2e_rate = 0.0
    e2e_stages: dict = {}
    e2e_scaling: dict = {}
    det_shard_scaling: dict = {}
    try:
        from theia_tpu.ingest import BlockEncoder, TsvDecoder, \
            native_available
        from theia_tpu.manager.ingest import (IngestManager,
                                              default_ingest_shards)
        from theia_tpu.store import FlowDatabase

        if native_available():
            big = generate_flows(SynthConfig(n_series=2000,
                                             points_per_series=30))
            enc = BlockEncoder(dicts=big.dicts)
            blocks = [enc.encode(big) for _ in range(9)]
            # Headline: the real IngestManager path, one stream.
            # Best-of-2 passes: shared-host CPU steal makes single
            # passes noisy (observed 2-3x swings on idle RAM).
            im = IngestManager(FlowDatabase(ttl_seconds=12 * 3600))
            im.ingest(blocks[0])   # warm: dict deltas + jit
            dt = float("inf")
            for _ in range(2):
                t9 = time.perf_counter()
                n_e2e = sum(im.ingest(p)["rows"]
                            for p in blocks[1:])
                dt = min(dt, time.perf_counter() - t9)

            # Stage attribution: replicate the same pipeline with
            # per-stage stopwatches IN ONE LOOP (separate passes
            # skew — adoption/dict caches warm differently and the
            # remainder can go negative).
            from theia_tpu.analytics.heavy_hitters import \
                HeavyHitterDetector
            from theia_tpu.analytics.streaming import \
                StreamingDetector
            # Best-of-2 vs CPU steal: each pass rebuilds ALL state
            # (same workload both times — replaying into a grown
            # store / warmed detectors would measure a different
            # pipeline), and the kept stage triple comes from ONE
            # pass (independent per-stage minima could describe an
            # execution that never happened and mis-name the cap).
            t_dec = t_store = t_det = 0.0
            best_total = float("inf")
            stage_samples = {"decode": [], "store": [],
                             "detector": []}
            for _ in range(2):
                d2 = TsvDecoder()
                db2 = FlowDatabase(ttl_seconds=12 * 3600)
                hh2 = HeavyHitterDetector()
                sd2 = StreamingDetector()
                warm = d2.decode_block(blocks[0])
                db2.insert_flows(warm)
                hh2.update(warm)
                sd2.ingest(warm)
                s_dec = s_store = s_det = 0.0
                samples = {"decode": [], "store": [],
                           "detector": []}
                for p in blocks[1:]:
                    ta = time.perf_counter()
                    b = d2.decode_block(p)
                    tb = time.perf_counter()
                    db2.insert_flows(b)
                    tc = time.perf_counter()
                    hh2.update(b)
                    sd2.ingest(b)
                    td = time.perf_counter()
                    s_dec += tb - ta
                    s_store += tc - tb
                    s_det += td - tc
                    samples["decode"].append(tb - ta)
                    samples["store"].append(tc - tb)
                    samples["detector"].append(td - tc)
                total = s_dec + s_store + s_det
                if total < best_total:
                    best_total = total
                    t_dec, t_store, t_det = s_dec, s_store, s_det
                    stage_samples = samples

            def _p95_ms(xs):
                xs = sorted(xs)
                return round(
                    xs[min(len(xs) - 1,
                           int(round(0.95 * (len(xs) - 1))))] * 1e3,
                    2)
            e2e_rate = n_e2e / dt
            e2e_stages = {
                "decode_rows_per_sec": round(n_e2e / t_dec),
                "store_rows_per_sec": round(n_e2e / t_store),
                "detector_rows_per_sec": round(n_e2e / t_det),
                # per-block p95 latency per stage: mean rates hide the
                # tail (one slow MV fan-out or jit retrace per pass)
                "decode_p95_ms": _p95_ms(stage_samples["decode"]),
                "store_p95_ms": _p95_ms(stage_samples["store"]),
                "detector_p95_ms": _p95_ms(stage_samples["detector"]),
            }
            # The ingest path runs the store and detector legs
            # OVERLAPPED (manager/ingest.py pipelining), so the
            # steady-state ceiling is decode vs the SLOWER of the two
            # overlapped legs — not the sum of all three. The cap
            # names the stage that sets that pipelined floor.
            overlap_rate = n_e2e / max(t_store, t_det)
            e2e_stages["pipelined_floor_rows_per_sec"] = round(
                min(e2e_stages["decode_rows_per_sec"], overlap_rate))
            if e2e_stages["decode_rows_per_sec"] <= overlap_rate:
                cap = "decode_rows_per_sec"
            elif t_store >= t_det:
                cap = "store_rows_per_sec (overlapped)"
            else:
                cap = "detector_rows_per_sec (overlapped)"
            cores = os.cpu_count() or 1
            print(f"end-to-end ingest (wire->store+views->2 detectors"
                  f"->alerts, store||detector overlapped): "
                  f"{e2e_rate:,.0f} rows/s "
                  f"[decode {n_e2e / t_dec:,.0f}, store "
                  f"{n_e2e / t_store:,.0f}, "
                  f"detectors {n_e2e / t_det:,.0f} rows/s; "
                  f"pipelined floor "
                  f"{e2e_stages['pipelined_floor_rows_per_sec']:,} "
                  f"rows/s; cap: {cap}; host cores={cores}; "
                  f"{e2e_rate / cores:,.0f} rows/s/core, single "
                  f"stream]", file=sys.stderr)

            # Multi-stream scaling structure: k producer threads, one
            # IngestManager, distinct streams (decode parallelizes —
            # the native decoder and group-sum release the GIL; the
            # detector leg serializes on its lock). On a 1-core host
            # expect ~flat; the structure is what a multi-core v5e
            # host scales.
            import gc
            import threading

            # Drop the headline/attribution stores first: three live
            # ~200 MB databases push a small bench VM into swap and
            # the scaling numbers stop measuring the pipeline.
            del im, db2, hh2, sd2, warm
            gc.collect()

            from theia_tpu.schema import ColumnarBatch, \
                StringDictionary

            def reprefix_ips(batch, sid):
                """The same flow shapes moved into producer `sid`'s
                own address blocks (10.{sid}./203.{sid}.): distinct
                producers export distinct flow populations, so their
                detector keys — and shard assignments — differ the
                way real per-node exporters' do. Codes are preserved
                (entries re-encode in code order), only the strings
                move."""
                if sid == 0:
                    return batch
                dicts = dict(batch.dicts)
                for col in ("sourceIP", "destinationIP"):
                    nd = StringDictionary()
                    for s in batch.dicts[col].entries_since(0):
                        if s:
                            s = s.replace(
                                "10.0.", f"10.{sid}.", 1).replace(
                                "203.0.", f"203.{sid}.", 1)
                        nd.encode_one(s)
                    dicts[col] = nd
                return ColumnarBatch(dict(batch.columns), dicts)

            bigs = [reprefix_ips(big, sid) for sid in range(4)]
            for k in (1, 2, 4):
                imk = IngestManager(
                    FlowDatabase(ttl_seconds=12 * 3600))
                encs = [BlockEncoder(dicts=bigs[i].dicts)
                        for i in range(k)]
                payloads = [[encs[i].encode(bigs[i])
                             for _ in range(4)]
                            for i in range(k)]
                # warm each stream's dict chain + jit
                for i in range(k):
                    imk.ingest(payloads[i][0], stream=f"s{i}")

                def feed(i):
                    for p in payloads[i][1:]:
                        imk.ingest(p, stream=f"s{i}")

                best = float("inf")
                for _ in range(2):   # best-of-2 vs CPU steal
                    threads = [threading.Thread(target=feed,
                                                args=(i,))
                               for i in range(k)]
                    ts = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    best = min(best, time.perf_counter() - ts)
                rows = k * 3 * len(big)
                e2e_scaling[str(k)] = round(rows / best)
                del imk, payloads
                gc.collect()
            print("multi-stream e2e: " + ", ".join(
                f"{k} streams {v:,} rows/s"
                for k, v in e2e_scaling.items()), file=sys.stderr)

            # Detector-leg shard scaling: S shards, S feeder
            # threads, scoring only (no decode/insert) — isolates
            # what lifting the global detector lock buys. Each
            # feeder scores its own distinct flow population
            # (reprefix_ips), so S threads hold different shard
            # locks concurrently where cores exist.
            for s_count in (1, 2, 4):
                imd = IngestManager(FlowDatabase(),
                                    n_shards=s_count)
                for sid in range(s_count):   # warm jit+dicts
                    imd.score_batch(bigs[sid])

                def feed_det(sid, imd=imd):
                    for _ in range(8):
                        imd.score_batch(bigs[sid])

                best = float("inf")
                for _ in range(2):   # best-of-2 vs CPU steal
                    threads = [threading.Thread(target=feed_det,
                                                args=(sid,))
                               for sid in range(s_count)]
                    ts = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    best = min(best, time.perf_counter() - ts)
                rows = s_count * 8 * len(big)
                det_shard_scaling[str(s_count)] = round(
                    rows / best)
                imd.close()
                del imd
                gc.collect()
            print("detector shard scaling: " + ", ".join(
                f"{k} shards {v:,} rows/s"
                for k, v in det_shard_scaling.items()),
                file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"e2e bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Fused-engine legs (the device-resident scoring pipeline,
    # ingest/device_path.py). The engine-parity gate runs FIRST — the
    # same block sequence must yield the same alert stream from both
    # engines before any fused timing is trusted — then the fused
    # detector leg (the comparable to e2e_stages.detector_rows_per_sec:
    # same blocks, same single-shard detector state, but ONE fused
    # dispatch with reused staging buffers instead of two dispatches +
    # two fetches per block) and the fused end-to-end ingest number.
    # THEIA_BENCH_FAST=1 runs only the one-micro-batch parity smoke,
    # so a kernel regression fails fast without the full bench.
    fused_parity_ok = None
    fused_det_rate = 0.0
    sharded_det_2s = 0.0
    fused_e2e = 0.0
    try:
        import gc as _fgc

        from theia_tpu.ingest import BlockEncoder as _FEnc
        from theia_tpu.ingest import TsvDecoder as _FDec
        from theia_tpu.ingest import native_available as _f_native
        from theia_tpu.manager.ingest import IngestManager as _FIm
        from theia_tpu.store import FlowDatabase as _FDb

        if _f_native():
            fast = os.environ.get("THEIA_BENCH_FAST") == "1"


            cfgf = (SynthConfig(n_series=200, points_per_series=10)
                    if fast else
                    SynthConfig(n_series=2000, points_per_series=30))
            bigf = generate_flows(cfgf)
            encf = _FEnc(dicts=bigf.dicts)
            blocksf = [encf.encode(bigf)
                       for _ in range(3 if fast else 9)]
            decf = _FDec()
            batches = [decf.decode_block(p) for p in blocksf]

            def _strip(conn):
                return [{k: v for k, v in d.items()
                         if k != "latency_s"} for d in conn]

            # parity gate — before any timed window
            im_s = _FIm(_FDb(), n_shards=4)
            im_f = _FIm(_FDb(), n_shards=4, engine="fused")
            fused_parity_ok = True
            for b in batches[:3]:
                hs, cs, ns = im_s.score_batch(b)
                hf, cf, nf = im_f.score_batch(b)
                if not (hs == hf and ns == nf
                        and _strip(cs) == _strip(cf)):
                    fused_parity_ok = False
            im_f.close()
            im_s.close()
            print("fused engine parity: "
                  + ("ok" if fused_parity_ok else "MISMATCH"),
                  file=sys.stderr)
            _fgc.collect()

            if not fast and fused_parity_ok:
                # Detector-leg comparison at the pipeline's design
                # point: two concurrent producer streams (distinct
                # flow populations), so double-buffered staging
                # overlaps device scoring and coalescing can fold
                # blocks — the same structure for both engines so
                # the fused number is an apples win, not a
                # measurement artifact. Sequential single-stream
                # rates go to stderr for the record.
                import threading as _fthr

                stream_batches = []
                for sid in range(2):
                    bs = generate_flows(SynthConfig(
                        n_series=2000, points_per_series=30,
                        seed=sid))
                    es = _FEnc(dicts=bs.dicts)
                    ds = _FDec()
                    stream_batches.append(
                        [ds.decode_block(es.encode(bs))
                         for _ in range(9)])
                rows2 = sum(len(b) for st in stream_batches
                            for b in st[1:])

                def det_leg(engine_name):
                    imd = _FIm(_FDb(), n_shards=2,
                               engine=engine_name)
                    for st in stream_batches:   # warm jit + ring
                        imd.score_batch(st[0])
                    # sequential single-stream rate (diagnostic)
                    t0f = time.perf_counter()
                    for b in stream_batches[0][1:]:
                        imd.score_batch(b)
                    seq = (len(stream_batches[0][1:])
                           * len(stream_batches[0][0])
                           / (time.perf_counter() - t0f))

                    def feed(st):
                        for b in st[1:]:
                            imd.score_batch(b)
                    best = float("inf")
                    for _ in range(2):   # best-of-2 vs CPU steal
                        th = [_fthr.Thread(target=feed,
                                           args=(st,))
                              for st in stream_batches]
                        t0f = time.perf_counter()
                        for t in th:
                            t.start()
                        for t in th:
                            t.join()
                        best = min(best,
                                   time.perf_counter() - t0f)
                    imd.close()
                    del imd
                    _fgc.collect()
                    return rows2 / best, seq

                sharded_2s, sharded_seq = det_leg("sharded")
                sharded_det_2s = sharded_2s
                fused_det_rate, fused_seq = det_leg("fused")
                print(f"fused detector leg (2 streams): "
                      f"{fused_det_rate:,.0f} rows/s vs sharded "
                      f"{sharded_2s:,.0f} rows/s "
                      f"[sequential: fused {fused_seq:,.0f}, "
                      f"sharded {sharded_seq:,.0f}; e2e-leg "
                      f"attribution "
                      f"{e2e_stages.get('detector_rows_per_sec', 0):,}]",
                      file=sys.stderr)

                best = 0.0
                for _ in range(2):
                    enc2 = _FEnc(dicts=bigf.dicts)
                    payloads = [enc2.encode(bigf)
                                for _ in range(9)]
                    imf = _FIm(_FDb(ttl_seconds=12 * 3600),
                               engine="fused")
                    imf.ingest(payloads[0])   # warm dicts + jit
                    t0f = time.perf_counter()
                    nf2 = sum(imf.ingest(p)["rows"]
                              for p in payloads[1:])
                    best = max(best,
                               nf2 / (time.perf_counter() - t0f))
                    imf.close()
                    del imf, payloads
                    _fgc.collect()
                fused_e2e = best
                print(f"fused e2e ingest: {best:,.0f} rows/s",
                      file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"fused bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Working-set state-tier legs (ingest/state_tier.py): ≥1M distinct
    # 5-tuples (FAST: 100k) with Zipf re-arrival driven through a
    # deliberately small hot-slot budget. The parity gate runs FIRST
    # and is the tier's whole contract: ZERO
    # theia_detector_series_dropped_total, hot occupancy never above
    # the budget, zero transient overflow, and an alert stream
    # bit-identical to an unbounded-slots oracle over the same input —
    # only then is the tiered detector's throughput timed.
    working_set_parity_ok = None
    working_set_rate = 0.0
    working_set_times: list = []
    try:
        import gc as _wgc

        from theia_tpu.analytics.streaming import (
            StreamingDetector as _WDet)
        from theia_tpu.ingest.state_tier import (
            TierConfig as _WCfg, WorkingSetTier as _WTier)
        from theia_tpu.schema import ColumnarBatch as _WBatch

        fast_ws = os.environ.get("THEIA_BENCH_FAST") == "1"
        n_keys = 100_000 if fast_ws else 1_000_000
        budget = 8_192 if fast_ws else 32_768
        batch_rows = 4_096 if fast_ws else 16_384
        rng_ws = np.random.default_rng(7)
        # every key appears at least once (a permutation), then a
        # Zipf-distributed re-arrival tail exercises promote-on-
        # re-arrival against the long tail
        idx_stream = np.concatenate([
            rng_ws.permutation(n_keys),
            rng_ws.zipf(1.3, size=n_keys // 2).astype(np.int64)
            % n_keys])
        vals_stream = rng_ws.random(len(idx_stream)) * 1e3

        def _ws_batch(lo, hi):
            ix = idx_stream[lo:hi]
            n = len(ix)
            return _WBatch({
                "sourceIP": ix.astype(np.int64),
                "sourceTransportPort": np.full(n, 1234, np.int64),
                "destinationIP": (ix * 7).astype(np.int64),
                "destinationTransportPort": np.full(n, 80, np.int64),
                "protocolIdentifier": np.full(n, 6, np.int64),
                "flowStartSeconds": np.full(n, 1, np.int64),
                "throughput": vals_stream[lo:hi],
                "flowEndSeconds": np.full(n, 100, np.int64),
            }, {})

        def _ws_strip(alerts):
            return sorted(
                tuple(sorted((k, v) for k, v in a.items()
                             if k not in ("latency_s", "slot", "row")))
                for a in alerts)

        def _ws_run(det, tier=None):
            drained = []
            for lo in range(0, len(idx_stream), batch_rows):
                drained.append(_ws_strip(
                    det.ingest(_ws_batch(lo, lo + batch_rows))))
                if tier is not None and tier.n_hot > budget:
                    raise AssertionError(
                        f"hot occupancy {tier.n_hot} > budget {budget}")
            return drained

        # parity gate — before any timed window
        tier_g = _WTier(_WCfg(hot_watermark=0.9, evict_to=0.7,
                              age_out_seconds=0.0))
        det_t = _WDet(capacity=budget, tier=tier_g)
        det_o = _WDet(capacity=n_keys + 64)
        a_t = _ws_run(det_t, tier_g)
        a_o = _ws_run(det_o)
        working_set_parity_ok = (
            a_t == a_o and det_t.dropped_series == 0
            and tier_g.overflow == 0 and tier_g.n_hot <= budget)
        print(f"working-set parity ({n_keys:,} keys, budget "
              f"{budget:,}): "
              + ("ok" if working_set_parity_ok else "MISMATCH")
              + f" [evictions {tier_g.evictions:,}, promotions "
              f"{tier_g.promotions_warm + tier_g.promotions_cold:,}]",
              file=sys.stderr)
        del det_t, det_o, tier_g, a_t, a_o
        _wgc.collect()

        if working_set_parity_ok:
            for _ in range(1 if fast_ws else 2):  # best-of-2 vs steal
                det_w = _WDet(capacity=budget, tier=_WTier(
                    _WCfg(hot_watermark=0.9, evict_to=0.7,
                          age_out_seconds=0.0)))
                det_w.ingest(_ws_batch(0, batch_rows))  # warm jit
                t0w = time.perf_counter()
                for lo in range(batch_rows, len(idx_stream),
                                batch_rows):
                    det_w.ingest(_ws_batch(lo, lo + batch_rows))
                working_set_times.append(time.perf_counter() - t0w)
                del det_w
                _wgc.collect()
            rows_w = len(idx_stream) - batch_rows
            working_set_rate = rows_w / min(working_set_times)
            print(f"working-set detector leg: "
                  f"{working_set_rate:,.0f} rows/s "
                  f"({n_keys:,} distinct keys through "
                  f"{budget:,} hot slots)", file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"working-set bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # TBLK zero-copy wire format vs TFB2 on the ACKED e2e path at
    # interval:1 durability (the PR-16 tentpole's design point: the
    # ack is WAL-journaled, and the TBLK body journals VERBATIM).
    # The timed windows run only behind a byte-parity gate: same
    # rows through both formats must produce byte-identical WAL
    # streams and identical alert content first — a fast wrong
    # pipeline must not report a speedup. THEIA_BENCH_FAST runs only
    # the parity gate.
    tblk_parity_ok = None
    tblk_e2e = 0.0
    tfb2_e2e = 0.0
    tblk_leg_times: list = []
    tfb2_leg_times: list = []
    try:
        import gc as _tgc
        import tempfile as _ttmp

        from theia_tpu.ingest import BlockEncoder as _TEnc2
        from theia_tpu.ingest import TblkEncoder as _TEncB
        from theia_tpu.ingest import native_available as _t_native
        from theia_tpu.manager.ingest import IngestManager as _TIm
        from theia_tpu.store import FlowDatabase as _TDb
        from theia_tpu.store import wal as _twal

        if _t_native():
            fast_t = os.environ.get("THEIA_BENCH_FAST") == "1"


            cfgt = (SynthConfig(n_series=200, points_per_series=10)
                    if fast_t else
                    SynthConfig(n_series=2000, points_per_series=30))
            bigt = generate_flows(cfgt)
            n_blocks = 3 if fast_t else 9

            def wal_bodies(db):
                db._wal.sync()
                frames, _l, algo = db._wal.read_frames(0)
                return [bytes(b) for (_, _, b)
                        in _twal.iter_frames(frames, algo)]

            def alert_canon(im):
                return [
                    {k: v for k, v in a.items()
                     if k not in ("time", "latency_s")}
                    for a in im.recent_alerts(10_000)]

            # parity gate — before any timed window
            gate = {}
            for name, enc_cls in (("tblk", _TEncB),
                                  ("tfb2", _TEnc2)):
                with _ttmp.TemporaryDirectory() as wd:
                    enc = enc_cls(dicts=bigt.dicts)
                    dbp = _TDb()
                    dbp.attach_wal(wd, sync="always")
                    imp = _TIm(dbp, n_shards=1)
                    for i in range(3):
                        imp.ingest(enc.encode(bigt),
                                   stream="parity", seq=i)
                    gate[name] = (wal_bodies(dbp),
                                  alert_canon(imp))
                    imp.close()
                    dbp.close_wal()
                    del imp, dbp
                    _tgc.collect()
            tblk_parity_ok = gate["tblk"] == gate["tfb2"]
            print("tblk/tfb2 byte parity (WAL stream + alerts): "
                  + ("ok" if tblk_parity_ok else "MISMATCH"),
                  file=sys.stderr)

            if not fast_t and tblk_parity_ok:
                def e2e_wal_leg(enc_cls, leg_times):
                    # fresh db + WAL per pass: replaying into a
                    # grown store would measure a different
                    # pipeline; best-of-2 vs CPU steal
                    best = 0.0
                    for _ in range(2):
                        with _ttmp.TemporaryDirectory() as wd:
                            enc = enc_cls(dicts=bigt.dicts)
                            payloads = [enc.encode(bigt)
                                        for _ in range(n_blocks)]
                            dbw = _TDb(ttl_seconds=12 * 3600)
                            dbw.attach_wal(wd, sync="interval:1")
                            imw = _TIm(dbw)
                            imw.ingest(payloads[0],
                                       stream="b", seq=0)
                            t0t = time.perf_counter()
                            nw = sum(
                                imw.ingest(p, stream="b",
                                           seq=1 + i)["rows"]
                                for i, p in
                                enumerate(payloads[1:]))
                            dtw = time.perf_counter() - t0t
                            leg_times.append(dtw)
                            best = max(best, nw / dtw)
                            imw.close()
                            dbw.close_wal()
                            del imw, dbw, payloads
                            _tgc.collect()
                    return best

                tblk_e2e = e2e_wal_leg(_TEncB, tblk_leg_times)
                tfb2_e2e = e2e_wal_leg(_TEnc2, tfb2_leg_times)
                print(f"tblk e2e ingest (acked, WAL interval:1): "
                      f"{tblk_e2e:,.0f} rows/s vs tfb2 "
                      f"{tfb2_e2e:,.0f} rows/s "
                      f"({tblk_e2e / max(tfb2_e2e, 1e-9):.2f}x)",
                      file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"tblk bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Instrumentation overhead: the full IngestManager path with the
    # obs plane DISABLED vs ENABLED (THEIA_METRICS_DISABLED's runtime
    # switch), so the <3% overhead budget of the metrics subsystem is
    # tracked release-over-release instead of assumed.
    metrics_rate = 0.0
    metrics_overhead_pct = None
    try:
        from theia_tpu.ingest import BlockEncoder, native_available
        from theia_tpu.manager.ingest import IngestManager
        from theia_tpu.obs import metrics as obs_metrics
        from theia_tpu.store import FlowDatabase

        if native_available():
            bigm = generate_flows(SynthConfig(n_series=2000,
                                              points_per_series=30))

            def ingest_pass():
                imm = IngestManager(FlowDatabase(ttl_seconds=12 * 3600))
                encm = BlockEncoder(dicts=bigm.dicts)
                payloads = [encm.encode(bigm) for _ in range(9)]
                imm.ingest(payloads[0])   # warm dicts + jit
                tm = time.perf_counter()
                n = sum(imm.ingest(p)["rows"] for p in payloads[1:])
                dtm = time.perf_counter() - tm
                imm.close()
                return n / dtm

            # INTERLEAVED best-of-3 per mode: consecutive same-mode
            # passes would fold slow host drift (CPU steal, thermal)
            # into the A/B difference and report it as overhead.
            rates = {"disabled": 0.0, "enabled": 0.0}
            try:
                for _ in range(3):
                    obs_metrics.disable()
                    rates["disabled"] = max(rates["disabled"],
                                            ingest_pass())
                    obs_metrics.enable()
                    rates["enabled"] = max(rates["enabled"],
                                           ingest_pass())
            finally:
                obs_metrics.enable()
            metrics_rate = rates["enabled"]
            if rates["disabled"] > 0:
                metrics_overhead_pct = round(
                    (rates["disabled"] - rates["enabled"])
                    / rates["disabled"] * 100, 2)
            print(f"ingest with metrics: {metrics_rate:,.0f} rows/s "
                  f"(disabled: {rates['disabled']:,.0f}; overhead "
                  f"{metrics_overhead_pct}%)", file=sys.stderr)
    except Exception as e:
        print(f"metrics-overhead bench skipped: {e}", file=sys.stderr)

    # Distributed-tracing overhead: the SAME IngestManager A/B shape
    # as the metrics leg, flipping THEIA_TRACE_SAMPLE 0 ↔ 1 — with
    # sampling off no trace context is minted and no header ships, so
    # the delta is the whole cost of sampled tracing on the e2e
    # ingest path (the parity budget: within host noise, ≪ 3%).
    tracing_overhead_pct = None
    try:
        from theia_tpu.ingest import BlockEncoder, native_available
        from theia_tpu.manager.ingest import IngestManager
        from theia_tpu.store import FlowDatabase

        if native_available():
            bigt = generate_flows(SynthConfig(n_series=2000,
                                              points_per_series=30))

            def trace_pass():
                imt = IngestManager(FlowDatabase(ttl_seconds=12 * 3600))
                enct = BlockEncoder(dicts=bigt.dicts)
                payloads = [enct.encode(bigt) for _ in range(9)]
                imt.ingest(payloads[0])   # warm dicts + jit
                tt = time.perf_counter()
                n = sum(imt.ingest(p)["rows"] for p in payloads[1:])
                dtt = time.perf_counter() - tt
                imt.close()
                return n / dtt

            saved_sample = os.environ.get("THEIA_TRACE_SAMPLE")
            trates = {"off": 0.0, "sampled": 0.0}
            try:
                # interleaved best-of-3 (the metrics-leg rationale:
                # host drift must not masquerade as overhead)
                for _ in range(3):
                    os.environ["THEIA_TRACE_SAMPLE"] = "0"
                    trates["off"] = max(trates["off"],
                                        trace_pass())
                    os.environ["THEIA_TRACE_SAMPLE"] = "1"
                    trates["sampled"] = max(trates["sampled"],
                                            trace_pass())
            finally:
                if saved_sample is None:
                    os.environ.pop("THEIA_TRACE_SAMPLE", None)
                else:
                    os.environ["THEIA_TRACE_SAMPLE"] = saved_sample
            if trates["off"] > 0:
                tracing_overhead_pct = round(
                    (trates["off"] - trates["sampled"])
                    / trates["off"] * 100, 2)
            print(f"ingest with sampled tracing: "
                  f"{trates['sampled']:,.0f} rows/s "
                  f"(tracing off: {trates['off']:,.0f}; overhead "
                  f"{tracing_overhead_pct}%)", file=sys.stderr)
    except Exception as e:
        print(f"tracing-overhead bench skipped: {e}", file=sys.stderr)

    # Lockdep-witness overhead: the SAME IngestManager A/B shape,
    # flipping THEIA_LOCKDEP 0 <-> 1 around CONSTRUCTION (the witness
    # decision is made at lock creation, so each pass builds a fresh
    # engine; module-level locks keep whatever the process was born
    # with — instance locks dominate the ingest path, and the leg
    # honestly measures the armed-in-this-process cost an operator
    # pays turning the witness on for a deadlock hunt). Budget: <=3%
    # — the witness is a test-time gate, but it must stay cheap
    # enough to arm in production. THEIA_BENCH_FAST runs one
    # interleave instead of three.
    lockdep_rate = 0.0
    lockdep_overhead_pct = None
    lockdep_times = {"off": [], "on": []}
    try:
        from theia_tpu.ingest import BlockEncoder, native_available
        from theia_tpu.manager.ingest import IngestManager
        from theia_tpu.store import FlowDatabase

        if native_available():
            bigl = generate_flows(SynthConfig(n_series=2000,
                                              points_per_series=30))

            def lockdep_pass():
                iml = IngestManager(FlowDatabase(ttl_seconds=12 * 3600))
                encl = BlockEncoder(dicts=bigl.dicts)
                payloads = [encl.encode(bigl) for _ in range(9)]
                iml.ingest(payloads[0])   # warm dicts + jit
                tl = time.perf_counter()
                n = sum(iml.ingest(p)["rows"] for p in payloads[1:])
                dtl = time.perf_counter() - tl
                iml.close()
                return n / dtl, dtl

            saved_ld = os.environ.get("THEIA_LOCKDEP")
            lrates = {"off": 0.0, "on": 0.0}
            iters = (1 if os.environ.get("THEIA_BENCH_FAST") == "1"
                     else 3)
            try:
                # interleaved best-of-N with ALTERNATING order:
                # a fixed off-then-on order folds first-pass
                # warm-up (allocator, caches) into the SAME side
                # every interleave and reads as a systematic
                # bias, not noise — alternation cancels it
                for i in range(iters):
                    order = ("0", "1") if i % 2 == 0 else ("1",
                                                           "0")
                    for mode in order:
                        os.environ["THEIA_LOCKDEP"] = mode
                        r, dt = lockdep_pass()
                        key = "on" if mode == "1" else "off"
                        lrates[key] = max(lrates[key], r)
                        lockdep_times[key].append(dt)
            finally:
                if saved_ld is None:
                    os.environ.pop("THEIA_LOCKDEP", None)
                else:
                    os.environ["THEIA_LOCKDEP"] = saved_ld
            lockdep_rate = lrates["on"]
            if lrates["off"] > 0:
                lockdep_overhead_pct = round(
                    (lrates["off"] - lrates["on"])
                    / lrates["off"] * 100, 2)
            print(f"ingest with lockdep witness: "
                  f"{lockdep_rate:,.0f} rows/s "
                  f"(witness off: {lrates['off']:,.0f}; overhead "
                  f"{lockdep_overhead_pct}%)", file=sys.stderr)
    except Exception as e:
        print(f"lockdep-overhead bench skipped: {e}", file=sys.stderr)

    # WAL durability tax: e2e ingest throughput (the acceptance
    # surface — decode ∥ store+WAL ∥ detector, where spare cores can
    # absorb the journaling) per sync policy vs the WAL-off baseline,
    # plus bare store-insert rates (the worst case: nothing overlaps)
    # and replay throughput (how fast a crash recovers). Interleaved
    # best-of-3 per mode, same rationale as the metrics A/B:
    # consecutive same-mode passes fold host drift into the
    # difference.
    wal_rates = {}
    wal_store_rates = {}
    wal_recovery = 0.0
    try:
        import shutil
        import tempfile

        from theia_tpu.ingest import BlockEncoder as _WalEnc
        from theia_tpu.manager.ingest import IngestManager as _WalIm
        from theia_tpu.store import FlowDatabase as _WalDb

        bigw = generate_flows(SynthConfig(n_series=2000,
                                          points_per_series=30))

        def wal_store_pass(sync):
            tmpd = tempfile.mkdtemp(prefix="theia-wal-bench-")
            try:
                dbw = _WalDb(ttl_seconds=12 * 3600)
                if sync is not None:
                    dbw.attach_wal(os.path.join(tmpd, "wal"),
                                   sync=sync)
                dbw.insert_flows(bigw)   # warm adopt caches + jit
                tw = time.perf_counter()
                n = sum(dbw.insert_flows(bigw) for _ in range(8))
                dtw = time.perf_counter() - tw
                if sync is not None:
                    dbw.close_wal()
                return n / dtw
            finally:
                shutil.rmtree(tmpd, ignore_errors=True)

        def wal_e2e_pass(sync):
            tmpd = tempfile.mkdtemp(prefix="theia-wal-bench-")
            try:
                dbw = _WalDb(ttl_seconds=12 * 3600)
                if sync is not None:
                    dbw.attach_wal(os.path.join(tmpd, "wal"),
                                   sync=sync)
                imw = _WalIm(dbw)
                encw = _WalEnc(dicts=bigw.dicts)
                payloads = [encw.encode(bigw) for _ in range(9)]
                imw.ingest(payloads[0])   # warm dicts + jit
                tw = time.perf_counter()
                n = sum(imw.ingest(p)["rows"] for p in payloads[1:])
                dtw = time.perf_counter() - tw
                imw.close()
                if sync is not None:
                    dbw.close_wal()
                return n / dtw
            finally:
                shutil.rmtree(tmpd, ignore_errors=True)

        modes = [None, "never", "interval:1", "always"]
        best_e2e = {m: 0.0 for m in modes}
        best_store = {m: 0.0 for m in modes}
        for _ in range(3):
            for m in modes:
                best_e2e[m] = max(best_e2e[m], wal_e2e_pass(m))
                best_store[m] = max(best_store[m], wal_store_pass(m))
        wal_rates = {("off" if m is None else m): round(best_e2e[m])
                     for m in modes}
        wal_store_rates = {("off" if m is None else m):
                           round(best_store[m]) for m in modes}
        if best_e2e[None] > 0:
            wal_rates["interval1_overhead_pct"] = round(
                (best_e2e[None] - best_e2e["interval:1"])
                / best_e2e[None] * 100, 2)
        print("wal e2e ingest: " + ", ".join(
            f"{k} {v:,}" for k, v in wal_rates.items()),
            file=sys.stderr)
        print("wal store insert: " + ", ".join(
            f"{k} {v:,}" for k, v in wal_store_rates.items()),
            file=sys.stderr)

        tmpd = tempfile.mkdtemp(prefix="theia-wal-bench-")
        try:
            dbw = _WalDb()
            dbw.attach_wal(os.path.join(tmpd, "wal"), sync="never")
            for _ in range(8):
                dbw.insert_flows(bigw)
            dbw.wal_sync()
            dbw.close_wal()
            db2 = _WalDb()
            tr = time.perf_counter()
            st_rec = db2.attach_wal(os.path.join(tmpd, "wal"),
                                    sync="never")
            dtr = time.perf_counter() - tr
            wal_recovery = int(st_rec["recoveredRows"]) / dtr
            db2.close_wal()
            print(f"wal recovery: {wal_recovery:,.0f} rows/s "
                  f"({st_rec['recoveredRows']} rows replayed)",
                  file=sys.stderr)
        finally:
            shutil.rmtree(tmpd, ignore_errors=True)
    except Exception as e:
        print(f"wal bench skipped: {e}", file=sys.stderr)

    # Part-based storage engine (THEIA_STORE_ENGINE=parts): insert
    # throughput (seal/encode amortized on the ingest path), resident
    # bytes/row vs the flat engine's raw 284, min/max-pruned window
    # selects vs the flat full-scan+mask, and manifest-based recovery
    # vs wholesale snapshot recovery. The PARITY GATE runs before any
    # timed window (PR 6 playbook): byte-identical scan + pruned
    # select vs flat, or the legs don't report. THEIA_BENCH_FAST runs
    # a one-part smoke (parity + a single timed insert window).
    parts_bench: dict = {}
    parts_parity_ok = None
    try:
        import shutil
        import tempfile

        from theia_tpu.schema import ColumnarBatch as _PCB
        from theia_tpu.schema import FLOW_SCHEMA as _PSchema
        from theia_tpu.store import FlowDatabase as _PDb

        fastp = os.environ.get("THEIA_BENCH_FAST") == "1"
        n_windows = 1 if fastp else 12
        basep = generate_flows(SynthConfig(n_series=2000,
                                           points_per_series=30))

        def _shifted(i):
            cols = dict(basep.columns)
            for c in ("timeInserted", "flowStartSeconds",
                      "flowEndSeconds"):
                cols[c] = basep[c] + i * 3600
            return _PCB(cols, basep.dicts)

        windows = [_shifted(i) for i in range(n_windows)]
        t_lo = int(windows[0]["flowStartSeconds"].min())

        def _scan_equal(a, b) -> bool:
            if len(a) != len(b):
                return False
            for c in _PSchema:
                if not np.array_equal(np.asarray(a[c.name]),
                                      np.asarray(b[c.name])):
                    return False
                if c.is_string and not np.array_equal(
                        a.strings(c.name), b.strings(c.name)):
                    return False
            return True

        flatdb = _PDb(engine="flat")
        partsdb = _PDb(engine="parts")
        for w in windows:
            flatdb.insert_flows(w)
            partsdb.insert_flows(w)
        partsdb.flows.seal()
        # parity gate — before any timed window
        parts_parity_ok = _scan_equal(flatdb.flows.scan(),
                                      partsdb.flows.scan())
        if parts_parity_ok:
            sel_f = flatdb.flows.select(start_time=t_lo,
                                        end_time=t_lo + 1800)
            sel_p = partsdb.flows.select(start_time=t_lo,
                                         end_time=t_lo + 1800)
            parts_parity_ok = _scan_equal(sel_f, sel_p)
        print("parts engine parity: "
              + ("ok" if parts_parity_ok else "MISMATCH"),
              file=sys.stderr)
        if parts_parity_ok:
            n_rows = len(flatdb.flows)
            parts_bench["store_parts_bytes_per_row"] = round(
                partsdb.flows.nbytes / n_rows, 1)
            parts_bench["store_flat_bytes_per_row"] = round(
                flatdb.flows.nbytes / n_rows, 1)

            # insert throughput (includes seal + encode), best-of-3
            best_ins = 0.0
            for _ in range(1 if fastp else 3):
                dbi = _PDb(engine="parts")
                dbi.insert_flows(windows[0])   # warm adopt caches
                ti = time.perf_counter()
                n = sum(dbi.insert_flows(w) for w in windows)
                best_ins = max(best_ins,
                               n / (time.perf_counter() - ti))
            parts_bench["store_parts_insert_rows_per_sec"] = round(
                best_ins)

            # pruned out-of-window select vs flat full-scan+mask
            sel_args = dict(start_time=t_lo - 7200,
                            end_time=t_lo - 3600)
            best_f = best_p = float("inf")
            for _ in range(3):
                ts = time.perf_counter()
                flatdb.flows.select(**sel_args)
                best_f = min(best_f, time.perf_counter() - ts)
                ts = time.perf_counter()
                partsdb.flows.select(**sel_args)
                best_p = min(best_p, time.perf_counter() - ts)
            if best_p > 0:
                parts_bench["store_parts_select_pruned_vs_flat"] = \
                    round(best_f / best_p, 1)

            # recovery: manifest + WAL tail vs wholesale snapshot
            tmpp = tempfile.mkdtemp(prefix="theia-parts-bench-")
            try:
                dbr = _PDb(engine="parts",
                           parts_dir=os.path.join(tmpp, "parts"))
                dbr.attach_wal(os.path.join(tmpp, "wal"),
                               sync="never")
                for w in windows:
                    dbr.insert_flows(w)
                dbr.save(os.path.join(tmpp, "db.npz"))
                dbr.wal_sync()
                dbr.close_wal()
                # two honest numbers: time-to-SERVING (manifest
                # registered lazily + WAL tail — inserts ack, pruned
                # selects run; the parts engine's headline) and
                # time-to-full-materialization (forced whole-table
                # scan — the work-comparable figure vs the flat
                # engine, which materializes during load by
                # construction; both sides pay the scan). Best-of-2
                # like the other legs: a single pass is dominated by
                # host noise on a 2-core box.
                flatdb.save(os.path.join(tmpp, "flat.npz"))
                dt_parts = dt_parts_scan = float("inf")
                dt_flat = dt_flat_scan = float("inf")
                rows_rec = 0
                for _ in range(1 if fastp else 2):
                    tr = time.perf_counter()
                    db2 = _PDb.load(os.path.join(tmpp, "db.npz"))
                    db2.attach_wal(os.path.join(tmpp, "wal"),
                                   sync="never")
                    dt_parts = min(dt_parts,
                                   time.perf_counter() - tr)
                    rows_rec = len(db2.flows.scan())
                    dt_parts_scan = min(dt_parts_scan,
                                        time.perf_counter() - tr)
                    db2.close_wal()
                    tr = time.perf_counter()
                    db3 = _PDb.load(os.path.join(tmpp, "flat.npz"),
                                    engine="flat")
                    dt_flat = min(dt_flat, time.perf_counter() - tr)
                    assert len(db3.flows.scan()) == rows_rec
                    dt_flat_scan = min(dt_flat_scan,
                                       time.perf_counter() - tr)
                parts_bench["store_parts_recovery_rows_per_sec"] = \
                    round(rows_rec / dt_parts)
                parts_bench["store_parts_recovery_scan_rows_per_sec"] \
                    = round(rows_rec / dt_parts_scan)
                parts_bench["store_snapshot_recovery_rows_per_sec"] \
                    = round(rows_rec / dt_flat)
                parts_bench[
                    "store_snapshot_recovery_scan_rows_per_sec"] = \
                    round(rows_rec / dt_flat_scan)
            finally:
                shutil.rmtree(tmpp, ignore_errors=True)
            print("parts engine: " + ", ".join(
                f"{k.replace('store_', '')} {v:,}"
                for k, v in parts_bench.items()), file=sys.stderr)
    except Exception as e:
        print(f"parts bench skipped: {e}", file=sys.stderr)

    # Vectorized query engine over column parts (PR 8,
    # theia_tpu/query/): filtered group-by aggregation running
    # part-NATIVE (pruned, encoded-space filters, late-materializing
    # group keys) vs the decode-then-aggregate baseline (scan() to
    # table code space + the reference executor — what a job would
    # do). The query_parity_ok gate (parts engine == flat engine ==
    # pure-numpy reference, bit for bit) runs before ANY timed
    # window; legs: group-sum rows/s vs baseline, pruned-window
    # speedup, cold-tier scan rate (with a no-promotion check), and
    # cache-hit latency. THEIA_BENCH_FAST runs a one-window smoke.
    query_bench: dict = {}
    #: per-leg {bestMs, medianMs, spreadPct} for multi-iteration timed
    #: legs — lands in the --out artifact under result.leg_stats
    leg_stats: dict = {}
    query_parity_ok = None
    try:
        import shutil
        import tempfile

        from theia_tpu.query import (QueryEngine, parse_plan,
                                     reference_execute)
        from theia_tpu.schema import ColumnarBatch as _QCB
        from theia_tpu.store import FlowDatabase as _QDb

        fastq = os.environ.get("THEIA_BENCH_FAST") == "1"
        nq_windows = 1 if fastq else 12
        baseq = generate_flows(SynthConfig(n_series=2000,
                                           points_per_series=30))

        def _q_shifted(i):
            cols = dict(baseq.columns)
            for c in ("timeInserted", "flowStartSeconds",
                      "flowEndSeconds"):
                cols[c] = baseq[c] + i * 3600
            return _QCB(cols, baseq.dicts)

        qwindows = [_q_shifted(i) for i in range(nq_windows)]
        qflat = _QDb(engine="flat")
        qparts = _QDb(engine="parts")
        for w in qwindows:
            qflat.insert_flows(w)
            qparts.insert_flows(w)
        qparts.flows.seal()
        n_qrows = len(qflat.flows)
        q_lo = int(qwindows[0]["flowStartSeconds"].min())
        groupsum = parse_plan({
            "groupBy": "sourceIP",
            "aggregates": ["sum:octetDeltaCount", "count"], "k": 0})
        windowed = parse_plan({
            "groupBy": "sourceIP,destinationIP",
            "aggregates": ["sum:octetDeltaCount", "mean:throughput"],
            "start": q_lo, "end": q_lo + 1800,
            "filters": [{"column": "destinationTransportPort",
                         "op": ">=", "value": 1}], "k": 10})
        eng_p = QueryEngine(qparts)
        eng_f = QueryEngine(qflat)

        # parity gate — before any timed window
        query_parity_ok = True
        for qp in (groupsum, windowed):
            rp = eng_p.execute(qp, use_cache=False)
            rf = eng_f.execute(qp, use_cache=False)
            rref, gref, _ = reference_execute(
                qp, qflat.flows.scan(), qflat.flows.dicts)
            if not (rp["rows"] == rf["rows"] == rref
                    and rp["groupCount"] == rf["groupCount"] == gref):
                query_parity_ok = False
        print("query engine parity: "
              + ("ok" if query_parity_ok else "MISMATCH"),
              file=sys.stderr)
        if query_parity_ok:
            # group-sum through the engine vs decode-then-aggregate
            iters = 1 if fastq else 3
            t_q: list = []
            t_base: list = []
            for _ in range(iters):
                tq = time.perf_counter()
                eng_p.execute(groupsum, use_cache=False)
                t_q.append(time.perf_counter() - tq)
                tq = time.perf_counter()
                reference_execute(groupsum, qparts.flows.scan(),
                                  qparts.flows.dicts)
                t_base.append(time.perf_counter() - tq)
            best_q, best_base = min(t_q), min(t_base)
            leg_stats["query_groupsum"] = _leg_stats(t_q)
            leg_stats["query_baseline"] = _leg_stats(t_base)
            query_bench["query_groupsum_rows_per_sec"] = round(
                n_qrows / best_q)
            query_bench["query_baseline_rows_per_sec"] = round(
                n_qrows / best_base)
            query_bench["query_groupsum_vs_baseline"] = round(
                best_base / best_q, 1)

            # pruned narrow window vs the same query decoded
            t_qw: list = []
            t_bw: list = []
            for _ in range(iters):
                tq = time.perf_counter()
                eng_p.execute(windowed, use_cache=False)
                t_qw.append(time.perf_counter() - tq)
                tq = time.perf_counter()
                reference_execute(windowed, qparts.flows.scan(),
                                  qparts.flows.dicts)
                t_bw.append(time.perf_counter() - tq)
            best_qw, best_bw = min(t_qw), min(t_bw)
            leg_stats["query_pruned_window"] = _leg_stats(t_qw)
            if best_qw > 0:
                query_bench["query_pruned_window_speedup"] = round(
                    best_bw / best_qw, 1)

            # cold tier: demote everything, re-run group-sum through
            # the column-subset streaming path; the tier must not move
            tmpq = tempfile.mkdtemp(prefix="theia-query-bench-")
            try:
                qcold = _QDb(engine="parts",
                             parts_dir=os.path.join(tmpq, "parts"))
                for w in qwindows:
                    qcold.insert_flows(w)
                qcold.flows.seal()
                qcold.flows.demote_oldest(0)
                before_hot = qcold.flows.parts_stats()["hotBytes"]
                eng_c = QueryEngine(qcold)
                rc = eng_c.execute(groupsum, use_cache=False)
                best_c = float("inf")
                for _ in range(iters):
                    tq = time.perf_counter()
                    eng_c.execute(groupsum, use_cache=False)
                    best_c = min(best_c, time.perf_counter() - tq)
                after_hot = qcold.flows.parts_stats()["hotBytes"]
                query_bench["query_cold_tier_rows_per_sec"] = round(
                    n_qrows / best_c)
                query_bench["query_cold_no_promotion_ok"] = (
                    before_hot == after_hot == 0)
                if rc["rows"] != eng_p.execute(
                        groupsum, use_cache=False)["rows"]:
                    query_parity_ok = False
            finally:
                shutil.rmtree(tmpq, ignore_errors=True)

            # cache hit latency (same plan, unchanged fingerprint)
            eng_p.cache.clear()
            eng_p.execute(groupsum)
            hits = []
            for _ in range(5 if fastq else 20):
                tq = time.perf_counter()
                out = eng_p.execute(groupsum)
                hits.append(time.perf_counter() - tq)
                assert out["cache"] == "hit"
            query_bench["query_cache_hit_ms"] = round(
                sorted(hits)[len(hits) // 2] * 1e3, 3)
            leg_stats["query_cache_hit"] = _leg_stats(hits)

            # Sort-ordered parts + skip indexes (PR 12): a SELECTIVE
            # NON-TIME predicate (one tail destinationIP out of tens
            # of thousands) under a window covering the whole store —
            # the sparse primary index (destination-leading sort key)
            # prunes to a single granule — vs the identical rows in
            # unsorted v1 parts, which must scan everything in the
            # window (the pre-PR-12 behavior, reachable via
            # sort_key=""). Parity (sorted engine == unsorted engine
            # == pure-numpy reference) gates the timed windows;
            # ROADMAP item 2 targets >= 10x on this leg. Store size
            # matters here: the unsorted side scales linearly with
            # retention while the indexed side stays at per-query
            # fixed cost + one granule, so the leg uses a 1.2M-row
            # store (the earlier legs' 60k rows would mostly measure
            # the shared per-query overhead).
            sel_series = 2000 if fastq else 24000
            sel_points = 25 if fastq else 50
            sel_base = generate_flows(SynthConfig(
                n_series=sel_series, points_per_series=sel_points))
            db_sorted = _QDb(engine="parts", parts_config={
                "sort_key": "destinationIP,sourceIP,timeInserted",
                "granule_rows": 512,
                "memtable_rows": 1 << 22})
            db_unsorted = _QDb(engine="parts", parts_config={
                "sort_key": "",
                "memtable_rows": 1 << 22})
            for d in (db_sorted, db_unsorted):
                d.insert_flows(sel_base)
            db_sorted.flows.seal()
            db_unsorted.flows.seal()
            n_sel = len(db_sorted.flows)
            # the least frequent destination, straight from the synth
            # batch (a table scan here would decode 1.2M rows just to
            # pick the filter value) — "selective" must mean a tail
            # value, not the synth mix's heavy hitter
            import numpy as _np
            sel_codes, sel_counts = _np.unique(
                _np.asarray(sel_base["destinationIP"]),
                return_counts=True)
            dst = sel_base.dicts["destinationIP"].decode_one(
                int(sel_codes[_np.argmin(sel_counts)]))
            selective = parse_plan({
                "groupBy": "sourceIP",
                "aggregates": ["sum:octetDeltaCount", "count"],
                "start": int(sel_base["flowStartSeconds"].min()),
                "end": int(sel_base["flowEndSeconds"].max()) + 1,
                "filters": [{"column": "destinationIP", "op": "eq",
                             "value": dst}],
                "k": 0})
            eng_s = QueryEngine(db_sorted)
            eng_u = QueryEngine(db_unsorted)
            rs = eng_s.execute(selective, use_cache=False)
            ru = eng_u.execute(selective, use_cache=False)
            rref_s, gref_s, _ = reference_execute(
                selective, db_unsorted.flows.scan(),
                db_unsorted.flows.dicts)
            if not (rs["rows"] == ru["rows"] == rref_s
                    and rs["groupCount"] == gref_s):
                query_parity_ok = False
                print("selective-predicate parity: MISMATCH",
                      file=sys.stderr)
            else:
                sel_iters = 2 if fastq else 7
                t_sorted: list = []
                t_scan: list = []
                for _ in range(sel_iters):
                    tq = time.perf_counter()
                    eng_s.execute(selective, use_cache=False)
                    t_sorted.append(time.perf_counter() - tq)
                    tq = time.perf_counter()
                    eng_u.execute(selective, use_cache=False)
                    t_scan.append(time.perf_counter() - tq)
                best_s, best_u = min(t_sorted), min(t_scan)
                leg_stats["query_selective_predicate"] = \
                    _leg_stats(t_sorted)
                leg_stats["query_selective_scan"] = \
                    _leg_stats(t_scan)
                query_bench[
                    "query_selective_predicate_rows_per_sec"] = \
                    round(n_sel / best_s)
                query_bench["query_selective_scan_rows_per_sec"] = \
                    round(n_sel / best_u)
                query_bench["query_selective_predicate_speedup"] = \
                    round(best_u / best_s, 1)
                query_bench["query_selective_granules_skipped"] = \
                    int(rs.get("granulesSkipped") or 0)

            print("query engine: " + ", ".join(
                f"{k.replace('query_', '')} {v:,}"
                if isinstance(v, (int, float)) else f"{k} {v}"
                for k, v in query_bench.items()), file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"query bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Rollup views (PR 14): (A) the dashboard-speedup leg — a
    # long-window group-by answered from rollup tiers (1h folds over
    # cold history) vs the SAME plan forced down the raw cold-scan
    # path (`rollup=0`), parity-gated against the reference oracle
    # before any timed window; (B) the maintenance-overhead leg — A/B
    # ingest into identical parts stores with one declared view vs
    # the rollup plane inactive. THEIA_BENCH_FAST runs a one-view,
    # one-window smoke.
    rollup_bench: dict = {}
    rollup_parity_ok = None
    try:
        import json as _ru_json
        import shutil as _ru_shutil
        import tempfile as _ru_tempfile

        from theia_tpu.query import QueryEngine as _RuEng
        from theia_tpu.query import parse_plan as _ru_parse
        from theia_tpu.query import reference_execute as _ru_ref
        from theia_tpu.schema import ColumnarBatch as _RuCB
        from theia_tpu.store import FlowDatabase as _RuDb

        fast_ru = os.environ.get("THEIA_BENCH_FAST") == "1"
        ru_tmp = _ru_tempfile.mkdtemp(prefix="theia-rollup-bench-")
        ru_cfg = os.path.join(ru_tmp, "views.json")
        with open(ru_cfg, "w") as f:
            _ru_json.dump({"views": [{
                "name": "bench_per_source",
                "groupBy": ["sourceIP"],
                "aggregates": ["count", "sum:octetDeltaCount",
                               "mean:throughput"],
                "bucketSeconds": 60,
                "tiers": [{"resolutionSeconds": 3600,
                           "afterSeconds": 21600}],
            }]}, f)
        ru_saved = {k: os.environ.get(k) for k in
                    ("THEIA_ROLLUP_VIEWS", "THEIA_ROLLUP_DEFAULTS")}

        def _ru_env(on: bool) -> None:
            if on:
                os.environ["THEIA_ROLLUP_VIEWS"] = ru_cfg
            else:
                os.environ.pop("THEIA_ROLLUP_VIEWS", None)
            os.environ["THEIA_ROLLUP_DEFAULTS"] = "0"

        try:
            ru_base = generate_flows(SynthConfig(
                n_series=600 if fast_ru else 2000,
                points_per_series=30))
            ru_windows = 2 if fast_ru else 36
            ru_t0 = int(ru_base["timeInserted"].min())

            def _ru_shifted(i):
                # one hour of dashboard-shaped history per block:
                # timeInserted spread uniformly across the hour (the
                # synth generator clusters it in ~30 s, which would
                # leave 59 of 60 buckets empty)
                cols = dict(ru_base.columns)
                for c in ("flowStartSeconds", "flowEndSeconds"):
                    cols[c] = ru_base[c] + i * 3600
                rng = np.random.default_rng(1234 + i)
                cols["timeInserted"] = np.sort(rng.integers(
                    ru_t0 + i * 3600, ru_t0 + (i + 1) * 3600,
                    len(ru_base))).astype(np.int64)
                return _RuCB(cols, ru_base.dicts)

            ru_blocks = [_ru_shifted(i) for i in range(ru_windows)]

            # (A) dashboard speedup: cold month-shaped history,
            # folded to 1h tiers, one long unaligned window
            _ru_env(True)
            ru_db = _RuDb(engine="parts",
                          parts_dir=os.path.join(ru_tmp, "parts"))
            for b in ru_blocks:
                ru_db.insert_flows(b)
            ru_db.flows.seal()
            ru_lo = int(ru_blocks[0]["timeInserted"].min())
            ru_hi = int(ru_blocks[-1]["timeInserted"].max())
            # fold history older than 6h to 1h tiers (the realistic
            # cascade state: old coarse, recent at base resolution),
            # then demote all but the freshest ~10% of raw parts so
            # the forced-raw path pays the cold scans a month-scale
            # dashboard would while the ragged `now` edge stays hot
            ru_db.rollups.maintain(now=ru_hi + 60)
            ru_db.flows.demote_oldest(ru_db.flows.nbytes // 10)
            ru_eng = _RuEng(ru_db)
            # parity gate FIRST, on a fully-ragged window (stitched
            # head AND tail edges), against the forced-raw path and
            # the reference oracle
            gate_plan = _ru_parse({
                "groupBy": "sourceIP",
                "aggregates": ["count", "sum:octetDeltaCount",
                               "mean:throughput"],
                "start": ru_lo + 37, "end": ru_hi - 41,
                "timeColumn": "timeInserted",
                "endColumn": "timeInserted", "k": 0})
            served = ru_eng.execute(gate_plan, use_cache=False)
            forced = ru_eng.execute(gate_plan, use_cache=False,
                                    use_rollup=False)
            rrows, rgroups, _ = _ru_ref(gate_plan, ru_db.flows.scan(),
                                        ru_db.flows.dicts)
            rollup_parity_ok = bool(
                served.get("rollup")
                and served["rows"] == forced["rows"] == rrows
                and served["groupCount"] == rgroups)
            print("rollup parity: "
                  + ("ok" if rollup_parity_ok else "MISMATCH"),
                  file=sys.stderr)
            # the timed dashboard shape: hour-aligned start (a "last
            # N hours" panel), ragged `now` end
            ru_plan = _ru_parse({
                "groupBy": "sourceIP",
                "aggregates": ["count", "sum:octetDeltaCount",
                               "mean:throughput"],
                "start": ru_t0 // 3600 * 3600, "end": ru_hi - 41,
                "timeColumn": "timeInserted",
                "endColumn": "timeInserted", "k": 0})
            served = ru_eng.execute(ru_plan, use_cache=False)
            forced = ru_eng.execute(ru_plan, use_cache=False,
                                    use_rollup=False)
            rollup_parity_ok = bool(
                rollup_parity_ok and served.get("rollup")
                and served["rows"] == forced["rows"])
            if rollup_parity_ok:
                iters = 1 if fast_ru else 5
                t_served: list = []
                t_forced: list = []
                for _ in range(iters):
                    tq = time.perf_counter()
                    ru_eng.execute(ru_plan, use_cache=False)
                    t_served.append(time.perf_counter() - tq)
                    tq = time.perf_counter()
                    ru_eng.execute(ru_plan, use_cache=False,
                                   use_rollup=False)
                    t_forced.append(time.perf_counter() - tq)
                leg_stats["query_rollup_dashboard"] = \
                    _leg_stats(t_served)
                leg_stats["query_rollup_raw_scan"] = \
                    _leg_stats(t_forced)
                rollup_bench["query_rollup_dashboard_ms"] = round(
                    min(t_served) * 1000, 3)
                rollup_bench["query_rollup_raw_scan_ms"] = round(
                    min(t_forced) * 1000, 3)
                rollup_bench["query_rollup_dashboard_speedup"] = \
                    round(min(t_forced) / max(min(t_served), 1e-9), 1)
                rollup_bench["query_rollup_rows_scanned"] = int(
                    served["rowsScanned"])
                rollup_bench["query_rollup_raw_rows_scanned"] = int(
                    forced["rowsScanned"])

            # (B) maintenance overhead: A/B ingest, one declared view
            # vs rollup plane inactive, alternating reps to damp the
            # 2-core host's noise
            reps = 1 if fast_ru else 3
            ab_blocks = ru_blocks[:min(8, len(ru_blocks))]
            t_on: list = []
            t_off: list = []
            ratios: list = []
            for _ in range(reps):
                _ru_env(True)
                db_on = _RuDb(engine="parts")
                _ru_env(False)
                db_off = _RuDb(engine="parts")
                # warm both sides (native-kernel load, allocator)
                db_on.insert_flows(ab_blocks[0])
                db_off.insert_flows(ab_blocks[0])
                # paired, block-interleaved, order-alternated timing:
                # host drift on the 2-core bench box (tens of percent
                # across seconds) hits both members of a pair, and
                # alternating which side runs first cancels the
                # decaying-burst bias; the per-pair RATIO median is
                # the overhead estimator (outlier pairs — a GC or a
                # scheduler burst inside one member — drop out)
                for j, b in enumerate(ab_blocks):
                    order = ((db_on, t_on), (db_off, t_off)) \
                        if j % 2 else ((db_off, t_off), (db_on, t_on))
                    for side_db, sink in order:
                        tq = time.perf_counter()
                        side_db.insert_flows(b)
                        sink.append(time.perf_counter() - tq)
                    ratios.append((t_on[-1] - t_off[-1]) / t_off[-1])
            n_ru_rows = sum(len(b) for b in ab_blocks)
            leg_stats["query_rollup_ingest_on"] = _leg_stats(t_on)
            leg_stats["query_rollup_ingest_off"] = _leg_stats(t_off)
            ratios.sort()
            rollup_bench["query_rollup_maintenance_overhead_pct"] = \
                round(ratios[len(ratios) // 2] * 100, 2)
            rollup_bench["query_rollup_ingest_rows_per_sec"] = round(
                n_ru_rows * reps / sum(t_on))
            print("rollup views: " + ", ".join(
                f"{k.replace('query_rollup_', '')} {v:,}"
                if isinstance(v, (int, float)) else f"{k} {v}"
                for k, v in rollup_bench.items()), file=sys.stderr)
        finally:
            for k, v in ru_saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            _ru_shutil.rmtree(ru_tmp, ignore_errors=True)
    except Exception as e:
        import traceback
        print(f"rollup bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Metrics history (scrape-to-store, PR 13): (A) A/B ingest with a
    # REAL MetricsHistoryLoop thread scraping at a hot cadence vs the
    # plane disabled (THEIA_METRICS_SCRAPE_INTERVAL=0 semantics — no
    # loop at all), reporting the e2e ingest overhead of self-scrape
    # (budget: within host noise, well under the PR-3 3% bar); (B) a
    # 6h-window aggregation over a downsampled `__metrics__` store —
    # the p95-dashboard query shape (bucket series folded per metric/
    # labels) answered from rollup-tier parts — with a raw-vs-rolled
    # parity gate before the timed windows. THEIA_BENCH_FAST shrinks
    # both to a smoke.
    metrics_history_bench: dict = {}
    try:

        from theia_tpu.ingest import BlockEncoder as _MhEnc
        from theia_tpu.ingest import native_available as _mh_native
        from theia_tpu.manager.ingest import IngestManager as _MhIm
        from theia_tpu.obs import history as _mh_history
        from theia_tpu.query import QueryEngine as _MhEng
        from theia_tpu.query import parse_plan as _mh_parse
        from theia_tpu.schema import METRICS_SCHEMA as _MH_SCHEMA
        from theia_tpu.schema import ColumnarBatch as _MhCB
        from theia_tpu.store import FlowDatabase as _MhDb

        fast_mh = os.environ.get("THEIA_BENCH_FAST") == "1"
        if _mh_native():
            big_mh = generate_flows(SynthConfig(n_series=2000,
                                                points_per_series=30))
            n_payloads = 3 if fast_mh else 9

            def mh_ingest_pass(with_loop: bool) -> float:
                dbm = _MhDb(ttl_seconds=12 * 3600)
                imm = _MhIm(dbm)
                loop = None
                if with_loop:
                    # 1 s cadence — 15x hotter than the production
                    # default, so a ~1 s timed pass pays at least one
                    # real scrape+maintain tick without turning the
                    # leg into a scrape-throughput microbench
                    loop = _mh_history.MetricsHistoryLoop(
                        dbm, interval=1.0)
                    loop.start()
                encm = _MhEnc(dicts=big_mh.dicts)
                payloads = [encm.encode(big_mh)
                            for _ in range(n_payloads)]
                imm.ingest(payloads[0])   # warm dicts + jit
                tm = time.perf_counter()
                n = sum(imm.ingest(p)["rows"] for p in payloads[1:])
                dtm = time.perf_counter() - tm
                if loop is not None:
                    loop.stop()
                imm.close()
                return n / dtm

            # interleaved best-of-N (the metrics-overhead leg's
            # discipline): host drift must not masquerade as overhead
            rates_mh = {"off": 0.0, "on": 0.0}
            for _ in range(2 if fast_mh else 3):
                rates_mh["off"] = max(rates_mh["off"],
                                      mh_ingest_pass(False))
                rates_mh["on"] = max(rates_mh["on"],
                                     mh_ingest_pass(True))
            metrics_history_bench[
                "metrics_history_ingest_rows_per_sec"] = round(
                    rates_mh["on"])
            if rates_mh["off"] > 0:
                metrics_history_bench[
                    "metrics_history_overhead_pct"] = round(
                        (rates_mh["off"] - rates_mh["on"])
                        / rates_mh["off"] * 100, 2)
            print(f"ingest with metrics history: "
                  f"{rates_mh['on']:,.0f} rows/s (off: "
                  f"{rates_mh['off']:,.0f}; overhead "
                  f"{metrics_history_bench.get('metrics_history_overhead_pct')}%)",
                  file=sys.stderr)

        # (B) 6h-window history query from downsampled parts
        span = 1800 if fast_mh else 21600   # the "6h" window
        raw_mh, roll_mh = _MhDb(), _MhDb()
        hist_rng = np.random.default_rng(5)
        n_series_mh = 4 if fast_mh else 24
        totals = np.zeros(n_series_mh)
        rows_buf: list = []

        def flush_mh():
            for dmh in (raw_mh, roll_mh):
                tabm = _mh_history.metrics_table(dmh)
                tabm.insert(_MhCB.from_rows(
                    rows_buf, _MH_SCHEMA, tabm.dicts))
                tabm.seal()
            rows_buf.clear()

        for t in range(0, span, 15):
            totals += hist_rng.integers(0, 1000, n_series_mh)
            for s in range(n_series_mh):
                v = int(totals[s]) * 1_000_000
                rows_buf.append({
                    "timeInserted": t, "metric": "bench_lat_bucket",
                    "labels": f"le={s}", "node": "n0",
                    "kind": "bucket", "resolution": 15, "value": v,
                    "valueMin": v, "valueMax": v, "valueSum": v,
                    "valueCount": 1})
            if t % 900 == 0 and rows_buf:
                flush_mh()
        if rows_buf:   # the ticks after the last 900s boundary
            flush_mh()
        roll_loop = _mh_history.MetricsHistoryLoop(
            roll_mh, interval=15, retention_seconds=0,
            tiers=[(60, 600), (3600, 3600)])
        roll_loop.maintain(now=span)
        hist_plan = _mh_parse({
            "table": "__metrics__", "groupBy": "metric,labels",
            "agg": ["min:valueMin", "max:valueMax", "sum:valueSum",
                    "sum:valueCount"],
            "start": 0, "end": span, "k": 0})
        eng_raw_mh = _MhEng(raw_mh)
        eng_roll_mh = _MhEng(roll_mh)
        r_raw = eng_raw_mh.execute(hist_plan, use_cache=False)
        r_roll = eng_roll_mh.execute(hist_plan, use_cache=False)
        parity_mh = r_raw["rows"] == r_roll["rows"]
        metrics_history_bench["metrics_history_rollup_parity_ok"] = \
            parity_mh
        if parity_mh:
            t_hq: list = []
            for _ in range(3 if fast_mh else 9):
                tq = time.perf_counter()
                eng_roll_mh.execute(hist_plan, use_cache=False)
                t_hq.append(time.perf_counter() - tq)
            leg_stats["metrics_history_query"] = _leg_stats(t_hq)
            metrics_history_bench["metrics_history_query_ms"] = round(
                sorted(t_hq)[len(t_hq) // 2] * 1e3, 3)
            metrics_history_bench[
                "metrics_history_rollup_rows_scanned"] = \
                int(r_roll["rowsScanned"])
            metrics_history_bench[
                "metrics_history_raw_rows_scanned"] = \
                int(r_raw["rowsScanned"])
        print("metrics history: " + ", ".join(
            f"{k.replace('metrics_history_', '')} {v}"
            for k, v in metrics_history_bench.items()),
            file=sys.stderr)
    except Exception as e:
        import traceback
        print(f"metrics-history bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Overload behavior through a REAL manager (ephemeral port), two
    # phases: (A) flat-out exactly-once producers with admission
    # unlimited measure the HTTP-path capacity of this host; (B) the
    # admission row bucket is pinned to HALF that, so the same
    # producers now offer ~2x the admitted capacity — the 429 +
    # Retry-After path runs end to end while a prober samples
    # /healthz (the control plane must stay responsive while ingest
    # sheds). Reports acked goodput (should hold ≈ the admitted
    # capacity, not collapse), shed fraction (429s / attempts), and
    # /healthz p95.
    overload: dict = {}
    try:
        import gc as _gc
        import threading
        import urllib.request as _urlreq

        _gc.collect()   # drop earlier legs' stores before measuring

        from theia_tpu.ingest import BlockEncoder as _OvEnc
        from theia_tpu.ingest.client import IngestClient
        from theia_tpu.manager import TheiaManagerServer
        from theia_tpu.manager.admission import TokenBucket
        from theia_tpu.store import FlowDatabase as _OvDb

        saved_env = {k: os.environ.get(k) for k in
                     ("THEIA_RETENTION_INTERVAL",)}
        os.environ["THEIA_RETENTION_INTERVAL"] = "0"
        srv = None
        try:
            srv = TheiaManagerServer(
                _OvDb(ttl_seconds=12 * 3600), port=0, workers=1)
            srv.start_background()
            addr = f"http://127.0.0.1:{srv.port}"
            n_prod = 2
            t_end = [0.0]
            # Warm serially BEFORE any timed window: the first block
            # per detector shard pays jit compile (seconds), which
            # would otherwise be billed as shed capacity.
            producers = []
            for ci in range(n_prod):
                enc = _OvEnc()
                # small blocks (2k rows) keep the token-bucket
                # granularity error well under the admitted rate
                blk = generate_flows(SynthConfig(
                    n_series=200, points_per_series=10,
                    seed=10 + ci), dicts=enc.dicts)
                c = IngestClient(addr, stream=f"bench-{ci}",
                                 max_attempts=500,
                                 backoff_base=0.02,
                                 backoff_cap=0.25)
                c.send(enc.encode(blk))
                producers.append((enc, blk, c))
            clients = [c for _, _, c in producers]
            rows_per_block = len(producers[0][1])

            def reset_ledgers():
                for c in clients:
                    c.rows_acked = c.batches_acked = 0
                    c.rejected = c.retries = c.duplicates = 0

            def produce(ci):
                enc, blk, c = producers[ci]
                while time.monotonic() < t_end[0]:
                    try:
                        c.send(enc.encode(blk))
                    except Exception:
                        break

            def run_phase(seconds):
                reset_ledgers()
                t_end[0] = time.monotonic() + seconds
                threads = [threading.Thread(target=produce,
                                            args=(i,))
                           for i in range(n_prod)]
                t0p = time.monotonic()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                return time.monotonic() - t0p

            # Phase A: measured capacity of the whole HTTP path
            dt_a = run_phase(2.0)
            cap_http = sum(c.rows_acked for c in clients) / dt_a
            if cap_http <= 0:
                raise RuntimeError("no rows acked in capacity phase")
            # Reset the store so phase B's capacity matches phase A's
            # (a store grown by the capacity probe pays more per
            # insert, which would read as shed capacity).
            dbov = srv.controller.db
            dbov.flows.truncate()
            for v in dbov.views.values():
                v.truncate()
            _gc.collect()
            # Phase B: admit half of capacity → offered ≈ 2x admitted
            admit_rate = cap_http / 2
            srv.ingest.admission.rows = TokenBucket(
                admit_rate, max(2 * rows_per_block, admit_rate / 2))
            healthz_lat: list = []
            stop = threading.Event()

            def probe():
                while not stop.is_set():
                    t0q = time.monotonic()
                    try:
                        with _urlreq.urlopen(addr + "/healthz",
                                             timeout=5) as r:
                            r.read()
                        healthz_lat.append(time.monotonic() - t0q)
                    except Exception:
                        healthz_lat.append(float("inf"))
                    time.sleep(0.05)

            prober = threading.Thread(target=probe)
            prober.start()
            dt_b = run_phase(4.0)
            stop.set()
            prober.join()
            acked = sum(c.rows_acked for c in clients)
            n_429 = sum(c.rejected for c in clients)
            attempts = n_429 + sum(c.batches_acked for c in clients)
            lat_ok = sorted(x for x in healthz_lat
                            if x != float("inf"))
            p95 = (lat_ok[int(0.95 * (len(lat_ok) - 1))]
                   if lat_ok else float("nan"))
            overload = {
                "goodput_under_overload_rows_per_sec": round(
                    acked / dt_b),
                "shed_ratio_at_2x": round(n_429 / attempts, 3)
                if attempts else None,
                "overload_capacity_rows_per_sec": round(cap_http),
                "overload_admitted_rows_per_sec": round(admit_rate),
                "healthz_under_overload_p95_ms": round(p95 * 1e3, 1),
                "healthz_probe_failures": sum(
                    1 for x in healthz_lat if x == float("inf")),
            }
            print(f"overload: HTTP capacity {cap_http:,.0f} rows/s; "
                  f"at 2x offered vs {admit_rate:,.0f} admitted: "
                  f"goodput "
                  f"{overload['goodput_under_overload_rows_per_sec']:,}"
                  f" rows/s, shed ratio "
                  f"{overload['shed_ratio_at_2x']}, healthz p95 "
                  f"{overload['healthz_under_overload_p95_ms']}ms "
                  f"({len(healthz_lat)} probes, "
                  f"{overload['healthz_probe_failures']} failed)",
                  file=sys.stderr)
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            if srv is not None:
                srv.shutdown()
    except Exception as e:
        import traceback
        print(f"overload bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    # Cluster tier (docs/cluster.md) through REAL managers on
    # ephemeral ports: (1) WAL log-shipping replication throughput
    # with quorum vs leader-only acks, behind a CONSERVATION gate —
    # every row the producer was acknowledged for must be on the
    # follower; (2) failover: kill -9 the leader, promote the
    # follower, measure wall time until the producer's next ack on
    # the new leader, gated on zero acked-row loss + dedup-resolved
    # duplicates; (3) router forward rate on a 2-peer mesh, gated on
    # cluster-wide row conservation. THEIA_BENCH_FAST shrinks the
    # block counts to a smoke.
    cluster_bench: dict = {}
    try:
        import json as _cj
        import shutil as _cshutil
        import socket as _csocket
        import tempfile as _ctempfile
        import urllib.request as _curlreq

        from theia_tpu.ingest import BlockEncoder as _ClEnc
        from theia_tpu.ingest.client import IngestClient as _ClClient
        from theia_tpu.manager import TheiaManagerServer as _ClSrv
        from theia_tpu.store import FlowDatabase as _ClDb

        def _cl_port():
            s = _csocket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        fastc = os.environ.get("THEIA_BENCH_FAST") == "1"
        n_blocks = 3 if fastc else 30
        saved_env_c = {k: os.environ.get(k) for k in
                       ("THEIA_RETENTION_INTERVAL",
                        "THEIA_CLUSTER_HEARTBEAT",
                        "THEIA_CLUSTER_BOUNDS_INTERVAL")}
        os.environ["THEIA_RETENTION_INTERVAL"] = "0"
        tmpc = _ctempfile.mkdtemp(prefix="theia-cluster-bench-")
        try:
            # -- replication: quorum vs leader acks ------------------
            for policy in ("quorum", "leader"):
                p0, p1 = _cl_port(), _cl_port()
                peers = (f"n0=http://127.0.0.1:{p0},"
                         f"n1=http://127.0.0.1:{p1}")
                db0 = _ClDb()
                db0.attach_wal(os.path.join(tmpc, f"{policy}-w0"))
                db1 = _ClDb()
                db1.attach_wal(os.path.join(tmpc, f"{policy}-w1"))
                lead = _ClSrv(db0, port=p0, cluster_peers=peers,
                              cluster_self="n0", cluster_role="leader",
                              cluster_acks=policy)
                fol = _ClSrv(db1, port=p1, cluster_peers=peers,
                             cluster_self="n1",
                             cluster_role="follower")
                lead.start_background()
                fol.start_background()
                try:
                    import threading as _cthreading

                    # Concurrent producers: frames from several
                    # streams accumulate while a ship POST is in
                    # flight, so the batched shipping (up to
                    # THEIA_REPL_BATCH_BYTES per POST over the
                    # persistent peer connection) amortizes the
                    # follower roundtrip across streams instead of
                    # paying one per batch.
                    n_prod = 4
                    warm_enc = _ClEnc()
                    blk = generate_flows(SynthConfig(
                        n_series=200, points_per_series=10, seed=31),
                        dicts=warm_enc.dicts)
                    _ClClient(f"http://127.0.0.1:{p0}",
                              stream=f"repl-{policy}-warm").send(
                        warm_enc.encode(blk))   # jit warm, untimed
                    clients = []
                    errors = []

                    def _produce(i, window):
                        enc_i = _ClEnc()
                        blk_i = generate_flows(SynthConfig(
                            n_series=200, points_per_series=10,
                            seed=40 + i), dicts=enc_i.dicts)
                        cl_i = _ClClient(
                            f"http://127.0.0.1:{p0}",
                            stream=f"repl-{policy}-{window}-{i}")
                        clients.append(cl_i)
                        try:
                            for _ in range(n_blocks):
                                cl_i.send(enc_i.encode(blk_i))
                        except Exception as e:
                            errors.append(e)

                    # best-of-2 windows: the 2-core host's scheduling
                    # noise swings single windows by 2x (the PR-8
                    # query-leg discipline)
                    best_rate = 0.0
                    for window in range(1 if fastc else 2):
                        threads = [
                            _cthreading.Thread(target=_produce,
                                               args=(i, window))
                            for i in range(n_prod)]
                        t0c = time.perf_counter()
                        for t in threads:
                            t.start()
                        for t in threads:
                            t.join()
                        dt_c = time.perf_counter() - t0c
                        if errors:
                            raise errors[0]
                        best_rate = max(
                            best_rate,
                            (n_prod * n_blocks * len(blk)) / dt_c)
                    acked = sum(c.rows_acked for c in clients) \
                        + len(blk)
                    if policy == "leader":
                        # leader-only acks ship async: wait for drain
                        deadline = time.monotonic() + 30
                        while time.monotonic() < deadline and \
                                len(db1.flows) != len(db0.flows):
                            time.sleep(0.02)
                    conserved = (len(db1.flows) == len(db0.flows)
                                 == acked)
                    cluster_bench[
                        f"repl_ship_rows_per_sec_{policy}"] = round(
                        best_rate)
                    ok_key = "repl_conservation_ok"
                    cluster_bench[ok_key] = (
                        cluster_bench.get(ok_key, True) and conserved)
                    if not conserved:
                        print(f"replication CONSERVATION FAILED "
                              f"({policy}): leader {len(db0.flows)} "
                              f"follower {len(db1.flows)} acked "
                              f"{acked}", file=sys.stderr)
                finally:
                    lead.shutdown()
                    fol.shutdown()

            # -- failover recovery time ------------------------------
            # THREE nodes: with only two, a quorum-acks leader
            # promoted after its sole peer died can never meet quorum
            # again (majority of 2 is 2) and every post-failover ack
            # times out — the drill must leave a follower standing.
            fo_ports = [_cl_port() for _ in range(3)]
            peers = ",".join(
                f"n{i}=http://127.0.0.1:{p}"
                for i, p in enumerate(fo_ports))
            fo_dbs = []
            for i in range(3):
                db = _ClDb()
                db.attach_wal(os.path.join(tmpc, f"fo-w{i}"))
                fo_dbs.append(db)
            lead = _ClSrv(fo_dbs[0], port=fo_ports[0],
                          cluster_peers=peers, cluster_self="n0",
                          cluster_role="leader",
                          cluster_acks="quorum")
            fols = [_ClSrv(fo_dbs[i], port=fo_ports[i],
                           cluster_peers=peers, cluster_self=f"n{i}",
                           cluster_role="follower")
                    for i in (1, 2)]
            lead.start_background()
            for f in fols:
                f.start_background()
            try:
                enc = _ClEnc()
                blk = generate_flows(SynthConfig(
                    n_series=200, points_per_series=10, seed=32),
                    dicts=enc.dicts)
                cl = _ClClient(
                    [f"http://127.0.0.1:{p}" for p in fo_ports],
                    stream="fo", max_attempts=60,
                    backoff_base=0.02, backoff_cap=0.2)
                for _ in range(3 if fastc else 6):
                    cl.send(enc.encode(blk))
                acked_before = cl.rows_acked
                t0f = time.perf_counter()
                lead.httpd.shutdown()          # kill -9 equivalence:
                lead.httpd.server_close()      # no drain, no close
                lead.cluster.stop()
                # the runbook promotes the MOST ADVANCED follower at
                # its applied LSN (quorum writes intersect with it)
                best = max(
                    (1, 2),
                    key=lambda i: fo_dbs[i].wal_position() or 0)
                req = _curlreq.Request(
                    f"http://127.0.0.1:{fo_ports[best]}"
                    f"/cluster/promote",
                    data=_cj.dumps(
                        {"atLsn": fo_dbs[best].wal_position()}
                    ).encode(), method="POST")
                with _curlreq.urlopen(req, timeout=30) as r:
                    r.read()
                # the producer retries its LAST acked batch (the one
                # whose ack could have been lost on the wire), then
                # resumes with a fresh encoder chain on the new leader
                dup = cl.send(b"\x00", seq=cl.seq)
                enc2 = _ClEnc()
                blk2 = generate_flows(SynthConfig(
                    n_series=200, points_per_series=10, seed=33),
                    dicts=enc2.dicts)
                cl.send(enc2.encode(blk2))
                dt_fo = time.perf_counter() - t0f
                cluster_bench["failover_recovery_seconds"] = round(
                    dt_fo, 3)
                cluster_bench["failover_conservation_ok"] = bool(
                    dup.get("duplicate")
                    and len(fo_dbs[best].flows)
                    == acked_before + len(blk2))
            finally:
                for f in fols:
                    f.shutdown()

            # -- router forwarding -----------------------------------
            p0, p1 = _cl_port(), _cl_port()
            peers = (f"n0=http://127.0.0.1:{p0},"
                     f"n1=http://127.0.0.1:{p1}")
            db0, db1 = _ClDb(), _ClDb()
            s0 = _ClSrv(db0, port=p0, cluster_peers=peers,
                        cluster_self="n0", cluster_role="peer")
            s1 = _ClSrv(db1, port=p1, cluster_peers=peers,
                        cluster_self="n1", cluster_role="peer")
            s0.start_background()
            s1.start_background()
            try:
                enc = _ClEnc()
                blk = generate_flows(SynthConfig(
                    n_series=200, points_per_series=10, seed=34),
                    dicts=enc.dicts)
                cl = _ClClient(f"http://127.0.0.1:{p0}",
                               stream="mesh")
                cl.send(enc.encode(blk))   # warm both nodes' jit
                t0r = time.perf_counter()
                for _ in range(n_blocks):
                    cl.send(enc.encode(blk))
                dt_r = time.perf_counter() - t0r
                cluster_bench["router_forward_rows_per_sec"] = round(
                    (n_blocks * len(blk)) / dt_r)
                cluster_bench["router_conservation_ok"] = (
                    len(db0.flows) + len(db1.flows) == cl.rows_acked)
            finally:
                s0.shutdown()
                s1.shutdown()

            # -- distributed scatter-gather query --------------------
            # (docs/queries.md "Distributed execution") behind a
            # row-conservation PARITY gate: the cluster-wide group-sum
            # over router-spread ingest must be bit-identical —
            # groups, sums, means, top-K order — to the single-node
            # engine over the same rows, with bytes on the wire
            # proportional to surviving GROUPS (never rows).
            # THEIA_BENCH_FAST runs a two-node smoke.
            from theia_tpu.query import QueryEngine as _DqEngine
            from theia_tpu.query import parse_plan as _dq_parse
            from theia_tpu.store.wal import (
                RECORD_MAGIC as _DQ_MAGIC,
                encode_record_body as _dq_encode,
            )
            os.environ["THEIA_CLUSTER_HEARTBEAT"] = "0.1"
            os.environ["THEIA_CLUSTER_BOUNDS_INTERVAL"] = "0.05"
            n_nodes = 2 if fastc else 3
            dq_ports = [_cl_port() for _ in range(n_nodes)]
            dq_peers = ",".join(
                f"n{i}=http://127.0.0.1:{p}"
                for i, p in enumerate(dq_ports))
            dq_dbs = [_ClDb() for _ in range(n_nodes)]
            dq_srvs = [
                _ClSrv(dq_dbs[i], port=dq_ports[i],
                       cluster_peers=dq_peers, cluster_self=f"n{i}",
                       cluster_role="peer")
                for i in range(n_nodes)]
            for s in dq_srvs:
                s.start_background()
            oracle_db = _ClDb()
            try:
                # wave A: routed ingest through n0 (spread by
                # destination hash); the oracle holds the same rows
                enc = _ClEnc()
                cl = _ClClient(f"http://127.0.0.1:{dq_ports[0]}",
                               stream="dq")
                dq_rows = 0
                for i in range(2 if fastc else 8):
                    blk = generate_flows(SynthConfig(
                        n_series=300, points_per_series=10,
                        anomaly_fraction=0.0, seed=60 + i),
                        dicts=enc.dicts)
                    cl.send(enc.encode(blk))
                    oracle_db.insert_flows(blk)
                    dq_rows += len(blk)
                # wave B: per-node TREC placement with DISJOINT time
                # ranges ABOVE wave A's (TREC is never re-routed), so
                # a window over the LAST node's range proves every
                # other peer's flowStart maximum is below it
                from theia_tpu.data.synth import (
                    DEFAULT_START as _DQ_T0,
                )
                bases = [_DQ_T0 + (i + 1) * 30 * 86_400
                         for i in range(n_nodes)]
                for i, port in enumerate(dq_ports):
                    enc_b = _ClEnc()
                    blk_b = generate_flows(SynthConfig(
                        n_series=120, points_per_series=10,
                        anomaly_fraction=0.0, seed=80 + i,
                        start_time=bases[i]), dicts=enc_b.dicts)
                    _ClClient(f"http://127.0.0.1:{port}",
                              stream=f"dqp-n{i}").send(
                        _DQ_MAGIC + _dq_encode("flows", blk_b))
                    oracle_db.insert_flows(blk_b)
                    dq_rows += len(blk_b)
                assert sum(len(db.flows) for db in dq_dbs) == dq_rows
                # heartbeats must carry current fingerprints+bounds
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    if all(
                        (s.cluster.cmap.peer_info(o.cluster.cmap.self_id)
                         .get("store") or {}).get("fingerprint")
                        == o.queries.fingerprint_hash()
                        for s in dq_srvs for o in dq_srvs if s is not o):
                        break
                    time.sleep(0.05)
                plan_doc = {
                    "groupBy": "destinationIP",
                    "aggregates": ["sum:octetDeltaCount",
                                   "mean:throughput", "count"],
                    "k": 100,
                }
                oracle_doc = _DqEngine(oracle_db).execute(
                    _dq_parse(plan_doc), use_cache=False)

                def _dq_query(port, doc):
                    req = _curlreq.Request(
                        f"http://127.0.0.1:{port}/query",
                        data=_cj.dumps(doc).encode(), method="POST")
                    with _curlreq.urlopen(req, timeout=60) as r:
                        return _cj.load(r)

                got = _dq_query(dq_ports[1],
                                {**plan_doc, "cache": False})
                parity = (got["rows"] == oracle_doc["rows"]
                          and got["groupCount"]
                          == oracle_doc["groupCount"]
                          and not got["partial"])
                cluster_bench["distquery_parity_ok"] = parity
                if parity:
                    n_q = 3 if fastc else 12
                    t0q = time.perf_counter()
                    for _ in range(n_q):
                        got = _dq_query(dq_ports[1],
                                        {**plan_doc, "cache": False})
                    dt_q = time.perf_counter() - t0q
                    cluster_bench["distquery_groupsum_rows_per_sec"] \
                        = round(n_q * dq_rows / dt_q)
                    cluster_bench["distquery_bytes_shipped_per_group"] \
                        = round(got["bytesShipped"]
                                / max(got["groupCount"], 1), 1)
                    # tracing A/B on the distributed leg: the same
                    # queries with THEIA_TRACE_SAMPLE=0 (no contexts
                    # minted, no traceparent on the fan-out wire —
                    # every in-process node flips at once); the
                    # default-sampled loop above is the B side
                    saved_ts = os.environ.get("THEIA_TRACE_SAMPLE")
                    os.environ["THEIA_TRACE_SAMPLE"] = "0"
                    try:
                        t0n = time.perf_counter()
                        for _ in range(n_q):
                            _dq_query(dq_ports[1],
                                      {**plan_doc, "cache": False})
                        dt_n = time.perf_counter() - t0n
                    finally:
                        if saved_ts is None:
                            os.environ.pop("THEIA_TRACE_SAMPLE",
                                           None)
                        else:
                            os.environ["THEIA_TRACE_SAMPLE"] = \
                                saved_ts
                    if dt_n > 0:
                        cluster_bench[
                            "distquery_tracing_overhead_pct"] = round(
                            (dt_q - dt_n) / dt_n * 100, 2)
                    # pruned leg: window covering ONLY the last
                    # node's placed range — every other peer prunes
                    win = {"start": bases[-1] - 1000,
                           "end": bases[-1] + 86_000}
                    wdoc = {**plan_doc, **win, "cache": False}
                    worcle = _DqEngine(oracle_db).execute(
                        _dq_parse({**plan_doc, **win}),
                        use_cache=False)
                    wgot = _dq_query(dq_ports[-1], wdoc)
                    pruned_ok = (
                        wgot["rows"] == worcle["rows"]
                        and wgot["peers"]["pruned"] == n_nodes - 1)
                    cluster_bench["distquery_pruned_parity_ok"] = \
                        pruned_ok
                    if pruned_ok:
                        n_w = 3 if fastc else 12
                        t0w = time.perf_counter()
                        for _ in range(n_w):
                            _dq_query(dq_ports[-1], wdoc)
                        dt_w = time.perf_counter() - t0w
                        cluster_bench["distquery_peer_pruned_speedup"] \
                            = round((dt_q / n_q) / (dt_w / n_w), 1)
                else:
                    print("distributed query PARITY FAILED: "
                          f"cluster {got['groupCount']} groups vs "
                          f"oracle {oracle_doc['groupCount']} "
                          f"(partial={got.get('partial')})",
                          file=sys.stderr)
            finally:
                for s in dq_srvs:
                    s.shutdown()
            print("cluster: " + ", ".join(
                f"{k.replace('repl_', '').replace('router_', 'router ')}"
                f" {v:,}" if isinstance(v, int) else f"{k} {v}"
                for k, v in cluster_bench.items()), file=sys.stderr)
        finally:
            for k, v in saved_env_c.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            _cshutil.rmtree(tmpc, ignore_errors=True)
    except Exception as e:
        import traceback
        print(f"cluster bench skipped: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

    try:
        from theia_tpu.analytics.streaming import StreamingDetector
        det = StreamingDetector(capacity=1024)
        S, T = cfg.n_series, cfg.points_per_series
        idx = np.arange(len(batch)).reshape(S, T)
        lat = []
        for t in range(min(T, 40)):
            micro = batch.take(idx[:, t])
            t9 = time.perf_counter()
            det.ingest(micro)
            lat.append(time.perf_counter() - t9)
        p50 = sorted(lat)[len(lat) // 2]
        print(f"streaming micro-batch p50: {p50 * 1e3:.2f} ms "
              f"({S} series/batch)", file=sys.stderr)
        result_extra_p50 = p50
    except Exception as e:
        print(f"streaming bench skipped: {e}", file=sys.stderr)
        result_extra_p50 = None

    result = {
        "metric": "tad_ewma_scoring_records_per_sec",
        "value": round(records_per_sec),
        "unit": "records/s",
        "vs_baseline": round(records_per_sec / BASELINE_RECORDS_PER_SEC,
                             1),
        "platform": dev.platform,
        "e2e_ingest_rows_per_sec": round(e2e_rate),
        "degraded_write_rows_per_sec": round(degraded_write),
        "ingest_with_metrics_rows_per_sec": round(metrics_rate),
    }
    if metrics_overhead_pct is not None:
        result["ingest_metrics_overhead_pct"] = metrics_overhead_pct
    if tracing_overhead_pct is not None:
        result["ingest_tracing_overhead_pct"] = tracing_overhead_pct
    if lockdep_overhead_pct is not None:
        result["ingest_lockdep_rows_per_sec"] = round(lockdep_rate)
        result["lockdep_overhead_pct"] = lockdep_overhead_pct
        leg_stats["ingest_lockdep_on"] = _leg_stats(
            lockdep_times["on"])
        leg_stats["ingest_lockdep_off"] = _leg_stats(
            lockdep_times["off"])
    if wal_rates:
        result["wal_ingest_rows_per_sec"] = wal_rates
    if wal_store_rates:
        result["wal_store_insert_rows_per_sec"] = wal_store_rates
    if wal_recovery:
        result["wal_recovery_rows_per_sec"] = round(wal_recovery)
    if parts_parity_ok is not None:
        result["parts_parity_ok"] = parts_parity_ok
    if parts_bench:
        result.update(parts_bench)
    if query_parity_ok is not None:
        result["query_parity_ok"] = query_parity_ok
    if query_bench:
        result.update(query_bench)
    if rollup_parity_ok is not None:
        result["query_rollup_parity_ok"] = rollup_parity_ok
    if rollup_bench:
        result.update(rollup_bench)
    if metrics_history_bench:
        result.update(metrics_history_bench)
    if leg_stats:
        result["leg_stats"] = leg_stats
    if overload:
        result.update(overload)
    if cluster_bench:
        result.update(cluster_bench)
    if working_set_parity_ok is not None:
        result["working_set_parity_ok"] = working_set_parity_ok
    if working_set_rate:
        result["detector_working_set_rows_per_sec"] = round(
            working_set_rate)
    if working_set_times:
        leg_stats["detector_working_set"] = _leg_stats(
            working_set_times)
        result["leg_stats"] = leg_stats
    if fused_parity_ok is not None:
        result["fused_parity_ok"] = fused_parity_ok
    if fused_det_rate:
        result["fused_detector_rows_per_sec"] = round(fused_det_rate)
    if sharded_det_2s:
        # the same 2-stream structure on the sharded engine — the
        # apples comparable for fused_detector_rows_per_sec
        result["detector_2stream_rows_per_sec"] = round(sharded_det_2s)
    if fused_e2e:
        result["e2e_ingest_fused_rows_per_sec"] = round(fused_e2e)
    if tblk_parity_ok is not None:
        result["tblk_parity_ok"] = tblk_parity_ok
    if tblk_e2e:
        result["e2e_ingest_tblk_rows_per_sec"] = round(tblk_e2e)
        if tfb2_e2e:
            result["e2e_ingest_tblk_vs_tfb2_speedup"] = round(
                tblk_e2e / tfb2_e2e, 2)
        # honest-host caveat: the 2-core bench box's CPU steal swings
        # identical runs by 2-3x, so the speedup carries its per-leg
        # spread rather than pretending to a clean ratio
        leg_stats["e2e_tblk_wal"] = dict(
            _leg_stats(tblk_leg_times),
            caveat="2-core shared host; best-of-2 over CPU-steal "
                   "noise — compare spreads before trusting the "
                   "speedup ratio")
        leg_stats["e2e_tfb2_wal"] = _leg_stats(tfb2_leg_times)
        result["leg_stats"] = leg_stats
    if e2e_stages:
        result["e2e_stages"] = e2e_stages
    if e2e_scaling:
        result["e2e_multi_stream_rows_per_sec"] = e2e_scaling
        result["e2e_rows_per_sec_per_core"] = round(
            e2e_rate / (os.cpu_count() or 1))
    if det_shard_scaling:
        result["detector_shard_scaling_rows_per_sec"] = \
            det_shard_scaling
        result["ingest_detector_shards"] = \
            default_ingest_shards()
    if result_extra_p50 is not None:
        result["streaming_alert_p50_ms"] = round(
            result_extra_p50 * 1e3, 2)
    if dev.platform == "cpu":
        result["degraded"] = "cpu backend (not a device measurement)"
    return result


if __name__ == "__main__":
    main()
