"""checks/snapshot_prefix.py is sharp: over files made here the way the
program lays them out (a snapshot's numeric members, a log's frames),
a sound generation pair is correct, and each perturbed snapshot, log or
window gives `correct: false` by the number that names the fault. No
manager is started: the check reads files and records, and these are
files and records."""

import os
import struct

import numpy as np
import pytest

from benchmarks import check as _check
from benchmarks import gen
from benchmarks.checks import snapshot_prefix as sp

TRAFFIC = {"name": "t", "generator": {
    "connections_per_producer": 64, "conns_per_block": 16,
    "points_per_conn": 4, "spike_rate": 0.0}, "limits": {}}
SEED = 2147485777
N_BLOCKS = 6           # a producer's acked blocks
PREV_BLOCKS = 2        # ... of which in the warm-up snapshot
SNAP_BLOCKS = 4        # ... of which in the window's snapshot


def lsn_of(producer: int, b: int) -> int:
    """Two producers' blocks interleave in the log, and every third
    record is another table's (`__metrics__`): 1, 2, (3), 4, 5, ..."""
    return 3 * b + producer + 1


STAMP = lsn_of(1, SNAP_BLOCKS - 1)          # 11: four blocks of each
PREV_STAMP = lsn_of(1, PREV_BLOCKS - 1)     # 5: two blocks of each


def write_snapshot(path, stamp, blocks):
    """`blocks`: {producer: [block indices]}, written the way
    FlowDatabase.save names its members (clusterUUID as codes)."""
    code, end, octets = [], [], []
    for p, bs in blocks.items():
        stream = gen.stream(TRAFFIC, SEED, p)
        for b in bs:
            v = stream.values(b)
            code.append(np.full(stream.rows, p, np.int32))
            end.append(np.tile(v["flow_end"], stream.cpb))
            octets.append((v["thr"] * stream.interval).ravel())
    np.savez_compressed(
        path, **{"__wal__/lsns": np.asarray([stamp], np.int64),
                 "flows/clusterUUID": np.concatenate(code),
                 "flows/flowEndSeconds": np.concatenate(end),
                 "flows/octetDeltaCount": np.concatenate(octets),
                 # never read: a pickled member must not be touched
                 "flows/__dict__/clusterUUID": np.asarray(
                     ["a", "b"], dtype=object)})
    if not path.endswith(".npz"):
        os.replace(path + ".npz", path)


def write_log(wal_dir, records):
    """One segment of frames (lsn, table field), bodies of 64 bytes."""
    os.makedirs(wal_dir, exist_ok=True)
    first = records[0][0] if records else 1
    with open(os.path.join(wal_dir, f"wal-{first:016d}.log"), "wb") as f:
        f.write(sp.SEG_HEADER.pack(b"TWAL", 1, 2, 0, first))
        for lsn, table in records:
            name = table.encode()
            body = struct.pack("<H", len(name)) + name + b"\0" * 64
            f.write(sp.FRAME.pack(len(body), 0, lsn, 0) + body)


def deployment(tmp_path, snap=None, snap_stamp=STAMP, prev=True,
               drop_lsn=None):
    """db.npz, db.npz.prev and wal/ of a sound run, or a perturbed
    one; returns the check's ctx."""
    wal_dir = str(tmp_path / "wal")
    db = str(tmp_path / "db.npz")
    write_snapshot(db, snap_stamp, snap or {
        p: list(range(SNAP_BLOCKS)) for p in (0, 1)})
    if prev:
        write_snapshot(db + ".prev", PREV_STAMP,
                       {p: list(range(PREV_BLOCKS)) for p in (0, 1)})
    # what the log retains: every record above the previous stamp
    records = [(lsn_of(p, b), f"flows\x1fbench-{p}\x1f{b + 1}\x1f64")
               for b in range(PREV_BLOCKS, N_BLOCKS) for p in (0, 1)]
    records += [(3 * b, "__metrics__")
                for b in range(PREV_BLOCKS, N_BLOCKS + 1)]
    write_log(wal_dir, sorted(r for r in records if r[0] != drop_lsn))
    acked = [{"status": 200, "block": b} for b in range(N_BLOCKS)]
    answer = {"status": 200, "stamp": STAMP}
    return {
        "traffic": TRAFFIC, "seed": SEED,
        "config": {"checkpoint_interval_s": 60},
        "specs": [{"role": "producer", "producer": 0},
                  {"role": "producer", "producer": 1},
                  {"role": "operator"}],
        "preload": [{"records": []}] * 3,
        "warm": [{"records": []}, {"records": []},
                 {"records": [{"status": 200, "stamp": PREV_STAMP}]}],
        "results": [{"records": acked}, {"records": acked},
                    {"records": [answer], "written_at_open": 1,
                     "written_at_close": 2}],
        "probes": [{"records": []}] * 3,
        "health": {"wal": {"dir": wal_dir},
                   "checkpoint": {"intervalSeconds": 60.0}},
    }


def failed(ctx):
    rep = _check.Report()
    sp.check(ctx, rep)
    doc = rep.doc()
    bad = sorted(k for k, v in doc["numbers"].items()
                 if v["value"] > v["limit"])
    assert doc["correct"] is (not bad)
    return bad


def test_a_sound_generation_pair_is_correct(tmp_path):
    ctx = deployment(tmp_path)
    assert failed(ctx) == []
    log = sp.read_log(ctx["health"]["wal"]["dir"])
    assert log["first"] == PREV_STAMP + 1
    assert len(log["blocks"]) == 2 * (N_BLOCKS - PREV_BLOCKS)


FULL = list(range(SNAP_BLOCKS))
CASES = {
    # the snapshot lacks producer 0's last block at or below the stamp
    "short_by_one_block": (
        dict(snap={0: FULL[:-1], 1: FULL}),
        ["snapshot_octets_gap", "snapshot_rows_gap"]),
    # ... holds a later block of producer 1 and lacks an earlier one:
    # as many rows as it should have, not the right ones
    "later_block_for_an_earlier": (
        dict(snap={0: FULL, 1: [0, 1, 3, 4]}),
        ["snapshot_blocks_not_prefix", "snapshot_octets_gap"]),
    # ... holds one block more than its stamp says
    "one_block_beyond_the_stamp": (
        dict(snap={0: FULL + [SNAP_BLOCKS], 1: FULL}),
        ["snapshot_octets_gap", "snapshot_rows_gap"]),
    "stamp_one_too_high": (
        dict(snap_stamp=STAMP + 1), ["snapshot_stamp_gap"]),
    # without the previous generation the blocks below the log's
    # first record are nowhere but in the one snapshot
    "no_prev_generation": (
        dict(prev=False), ["prev_generation_missing", "wal_tail_missing"]),
    # the log lost a record above the previous stamp
    "log_lost_a_record": (
        dict(drop_lsn=lsn_of(0, PREV_BLOCKS)), ["wal_tail_missing"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_perturbed_snapshot_or_log_is_not_correct(tmp_path, case):
    perturb, names = CASES[case]
    assert failed(deployment(tmp_path, **perturb)) == names


@pytest.mark.parametrize("written,names", [
    ((1, 3), ["snapshots_in_window_gap"]),       # a timer tick as well
    ((1, 1), ["snapshots_in_window_gap"]),       # none
    ((None, 2), ["snapshots_in_window_gap"]),    # /healthz did not say
])
def test_a_window_with_two_snapshots_or_none(tmp_path, written, names):
    ctx = deployment(tmp_path)
    ctx["results"][2].update(written_at_open=written[0],
                             written_at_close=written[1])
    assert failed(ctx) == names


def test_another_interval_than_the_configurations(tmp_path):
    ctx = deployment(tmp_path)
    ctx["health"]["checkpoint"]["intervalSeconds"] = 0.0
    assert failed(ctx) == ["checkpoint_interval_gap"]
    del ctx["health"]["checkpoint"]
    assert failed(ctx) == ["checkpoint_interval_gap"]


def test_a_refused_request_counts_as_failed(tmp_path):
    ctx = deployment(tmp_path)
    ctx["results"][2]["records"][0] = {"status": 500, "error": "x"}
    rep = _check.Report()
    sp.check(ctx, rep)
    assert (rep.attempted, rep.failed, rep.correct) == (2, 1, False)
    assert rep.numbers["snapshot_stamp_gap"]["value"] == 1
