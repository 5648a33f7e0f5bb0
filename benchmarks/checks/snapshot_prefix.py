"""Check `snapshot_prefix`: the window's snapshot is an exact prefix of
the log, and the previous generation stays recoverable.

Run first of the cell's checks, after quiescence. It reads what
recovery would read, the files under the manager's work directory, and
nothing through the program's own loader:

  db.npz, db.npz.prev   `numpy.load`, numeric members only (no pickle:
                        the dictionaries are never touched): the stamp
                        `__wal__/lsns` and, of the flows table, the
                        clusterUUID codes, flowEndSeconds and
                        octetDeltaCount
  wal/wal-*.log         the frames' headers and the table field of
                        their bodies: an ingest block is journaled as
                        ONE record whose table field is
                        flows␟<stream>␟<seq>␟<rows> (store/wal.py
                        `pack_dedup_tag`), so the log itself says at
                        which LSN every acked block lies

against the operator's two answers (the stamps), the producers' records
(which blocks were acked) and the reference over the generator's own
rows (references/snapshot_prefix.py). The ack carries the same LSN as
`walLsn`, but the built-in producer role does not keep it, so the
positions are read from the log, which is what a recovery replays.

All exact (limit 0):

  snapshot_stamp_gap          db.npz's stamp against the answer's
  snapshot_rows_gap           per producer, rows in the file against
                              the rows of its blocks with LSN <= stamp
  snapshot_octets_gap         the same for sum(octetDeltaCount)
  snapshot_blocks_not_prefix  blocks out of place: in the log, a block
                              at or below the stamp behind one above
                              it; in the file, a partial block, a whole
                              one behind a gap, or rows of no producer
  wal_tail_missing            acked blocks that neither db.npz.prev nor
                              the retained log holds, and LSNs above
                              the previous stamp that the log lost
  prev_generation_missing     db.npz.prev exists and carries the
                              warm-up snapshot's stamp
  snapshots_in_window_gap     /healthz `checkpoint.written` at the
                              window's close minus at its open, less 1
  checkpoint_interval_gap     /healthz `checkpoint.intervalSeconds`
                              against the configuration's
                              `checkpoint_interval_s`
"""

from __future__ import annotations

import glob
import os
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks import check as _check
from benchmarks.references import snapshot_prefix as _ref

SEG_HEADER = struct.Struct("<4sBBHQ")     # TWAL, version, crc algo, 0, first
FRAME = struct.Struct("<IIQI")            # body length, crc, LSN, head crc
TAG_SEP = "\x1f"


def read_log(wal_dir: str) -> Dict:
    """{"first": the lowest LSN on disk or None, "blocks": {(stream,
    seq): LSN}} of every whole frame of every segment. Bodies are
    skipped but for their table field."""
    blocks: Dict[Tuple[str, int], int] = {}
    first: Optional[int] = None
    for path in sorted(glob.glob(os.path.join(wal_dir, "wal-*.log"))):
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            head = f.read(SEG_HEADER.size)
            if len(head) < SEG_HEADER.size or head[:4] != b"TWAL":
                continue
            off = SEG_HEADER.size
            while off + FRAME.size + 2 <= size:
                f.seek(off)
                blen, _, lsn, _ = FRAME.unpack(f.read(FRAME.size))
                if off + FRAME.size + blen > size:
                    break                  # a frame still being written
                (tlen,) = struct.unpack("<H", f.read(2))
                table = f.read(tlen).decode("utf-8", "replace")
                first = lsn if first is None else min(first, lsn)
                parts = table.split(TAG_SEP)
                if parts[0] == "flows" and len(parts) >= 4:
                    blocks[(TAG_SEP.join(parts[1:-2]),
                            int(parts[-2]))] = lsn
                off += FRAME.size + blen
    return {"first": first, "blocks": blocks}


def read_snapshot(path: str, stream) -> Optional[Dict]:
    """{"stamp", "groups": one per clusterUUID code: {"rows",
    "octets", "blocks": {b: (rows, octets)}}}; None without the file.
    Which producer a group is, the caller finds from its first block's
    octets: the dictionaries that would say are pickled."""
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        lsns = z["__wal__/lsns"] if "__wal__/lsns" in z.files else []
        code = np.asarray(z["flows/clusterUUID"], np.int64)
        block = _ref.block_of(z["flows/flowEndSeconds"], stream)
        octets = np.asarray(z["flows/octetDeltaCount"], np.int64)
    groups = []
    for c in np.unique(code):
        sel = code == c
        bs, inv = np.unique(block[sel], return_inverse=True)
        rows = np.bincount(inv, minlength=len(bs))
        octs = np.zeros(len(bs), np.int64)
        np.add.at(octs, inv, octets[sel])
        groups.append({"rows": int(sel.sum()),
                       "octets": int(octets[sel].sum()),
                       "blocks": {int(b): (int(r), int(o))
                                  for b, r, o in zip(bs, rows, octs)}})
    return {"stamp": int(lsns[0]) if len(lsns) else None,
            "groups": groups}


def group_of(groups: List[Dict], first_octets: int) -> Optional[Dict]:
    """The group whose block 0 sums to the producer's block 0."""
    for g in groups:
        if g["blocks"].get(0, (0, None))[1] == first_octets:
            return g
    return None


def operator_records(ctx: Dict) -> Tuple[Dict, Dict, Dict]:
    """(warm-up answer, window answer, the window's result) of the
    one operator."""
    for i, spec in enumerate(ctx["specs"]):
        if spec["role"] == "operator":
            return (ctx["warm"][i]["records"][0],
                    ctx["results"][i]["records"][0], ctx["results"][i])
    raise _check.RunFailed("check snapshot_prefix: the traffic file has "
                           "no `operator` worker")


def compare(rep, facts: Dict) -> None:
    """`facts`: `streams` [(stream, lsns of its acked blocks)], `snap`
    and `prev` (read_snapshot), `log_first`, `stamp` and `prev_stamp`
    (the answers'), `written` (at open, at close), `interval` (healthz,
    configuration)."""
    snap, prev = facts["snap"], facts["prev"]
    stamp, prev_stamp = facts["stamp"], facts["prev_stamp"]
    have = snap is not None and snap["stamp"] is not None \
        and stamp is not None
    rep.compare("snapshot_stamp_gap",
                abs(snap["stamp"] - stamp) if have else 1, 0,
                f"db.npz {snap and snap['stamp']}, answer {stamp}")
    rows_gap = octets_gap = misplaced = tail = 0
    claimed: List[int] = []
    for stream, lsns in facts["streams"]:
        want = _ref.prefix_at(stream, lsns,
                              stamp if stamp is not None else -1)
        first = int(want["block_octets"][0]) if len(lsns) else None
        g = group_of(snap["groups"], first) if snap else None
        claimed.append(id(g))
        got_blocks = g["blocks"] if g else {}
        rows_gap += abs((g["rows"] if g else 0) - want["rows"])
        octets_gap += abs((g["octets"] if g else 0) - want["octets"])
        top = max(list(got_blocks) + [len(lsns) - 1, 0])
        whole = [got_blocks.get(b, (0, 0))[0] == stream.rows
                 for b in range(top + 1)]
        partial = sum(0 < r < stream.rows or r > stream.rows
                      for r, _ in got_blocks.values())
        misplaced += want["not_prefix"] + _ref.out_of_place(whole, partial)
        # recoverable from the previous generation: in db.npz.prev, or
        # journaled in the log that is still there
        pg = group_of(prev["groups"], first) if prev else None
        n_prev = (pg["rows"] // stream.rows) if pg else 0
        tail += sum(lsn is None for lsn in lsns[n_prev:])
    strangers = [g for g in (snap["groups"] if snap else [])
                 if id(g) not in claimed]
    rows_gap += sum(g["rows"] for g in strangers)
    misplaced += sum(len(g["blocks"]) for g in strangers)
    if prev_stamp is not None and facts["log_first"] is not None:
        tail += max(0, facts["log_first"] - (prev_stamp + 1))
    held = sum(g["rows"] for g in snap["groups"]) if snap else 0
    rep.compare("snapshot_rows_gap", rows_gap, 0,
                f"snapshot at stamp {stamp}, {held} rows")
    rep.compare("snapshot_octets_gap", octets_gap, 0,
                "sum(octetDeltaCount) per producer at the stamp")
    rep.compare("snapshot_blocks_not_prefix", misplaced, 0,
                "per producer the snapshot's blocks are 0..k-1, whole")
    rep.compare("wal_tail_missing", tail, 0,
                f"log starts at LSN {facts['log_first']}, previous "
                f"stamp {prev_stamp}")
    ok = prev is not None and prev_stamp is not None \
        and prev["stamp"] == prev_stamp
    rep.compare("prev_generation_missing", 0 if ok else 1, 0,
                f"db.npz.prev {prev and prev['stamp']}, warm-up answer "
                f"{prev_stamp}")
    w0, w1 = facts["written"]
    rep.compare("snapshots_in_window_gap",
                abs(w1 - w0 - 1) if None not in (w0, w1) else 1, 0,
                f"written {w0} at the window's open, {w1} at its close")
    got, want_s = facts["interval"]
    rep.compare("checkpoint_interval_gap",
                abs(got - want_s) if got is not None else 1, 0,
                f"/healthz {got} s, configuration {want_s} s")


def check(ctx: Dict, rep) -> None:
    warm, asked, result = operator_records(ctx)
    rep.attempted += 2
    rep.failed += sum(r["status"] != 200 for r in (warm, asked))
    wal_dir = ctx["health"]["wal"]["dir"]
    db = os.path.join(os.path.dirname(wal_dir), "db.npz")
    log = read_log(wal_dir)
    streams = []
    any_stream = None
    for stream, n, _ in _check.streams(ctx):
        any_stream = stream
        streams.append((stream, [log["blocks"].get(
            (f"bench-{stream.producer}", b + 1)) for b in range(n)]))
    compare(rep, {
        "streams": streams,
        "snap": read_snapshot(db, any_stream),
        "prev": read_snapshot(db + ".prev", any_stream),
        "log_first": log["first"],
        "stamp": asked.get("stamp"), "prev_stamp": warm.get("stamp"),
        "written": (result.get("written_at_open"),
                    result.get("written_at_close")),
        "interval": (ctx["health"].get("checkpoint", {})
                     .get("intervalSeconds"),
                     ctx["config"]["checkpoint_interval_s"]),
    })
