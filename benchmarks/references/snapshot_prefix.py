"""What a snapshot stamped at log position L must hold: the plain
reference of checks/snapshot_prefix.py.

Numpy and the standard library, int64 only, over the generator's own
rows (`stream.values(b)`); nothing the program computed enters but the
positions themselves: `lsns[b]`, the LSN of the flows record that
carries producer block b, or None for a block whose record the log no
longer retains (it was collected, so it lies at or below the previous
snapshot's stamp, which the check verifies apart).

The law (theia_tpu/store/wal.py): a snapshot with stamp L holds
exactly the rows of the records with LSN <= L. A producer sends its
blocks in order, one outstanding at a time, so those are a prefix of
its stream: `blocks` of them, `rows` rows, `octets` =
sum(octetDeltaCount), and block by block `block_octets`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def block_octets(stream, n_blocks: int) -> np.ndarray:
    """sum(octetDeltaCount) of each of the stream's first blocks
    (octetDeltaCount = throughput x interval, gen.py `block`)."""
    return np.array([int(stream.values(b)["thr"].sum()) * stream.interval
                     for b in range(n_blocks)], np.int64)


def prefix_at(stream, lsns: Sequence[Optional[int]], stamp: int) -> Dict:
    """What the snapshot at `stamp` holds of this producer, whose
    block b was journaled at `lsns[b]`."""
    inside = [lsn is None or lsn <= stamp for lsn in lsns]
    k = sum(inside)
    octets = block_octets(stream, len(lsns))
    return {
        "blocks": k,
        "rows": k * stream.rows,
        "octets": int(octets[:k].sum()),
        "block_octets": octets,
        # blocks at or below the stamp that follow one above it
        "not_prefix": sum(inside[k:]),
    }


def block_of(flow_end: np.ndarray, stream) -> np.ndarray:
    """The block index of rows by their flowEndSeconds: block b
    carries the `points` seconds from start + b x points x interval."""
    return (np.asarray(flow_end, np.int64) - stream.start) \
        // (stream.points * stream.interval)


def out_of_place(whole: List[bool], partial: int) -> int:
    """Blocks of one producer that a whole-block prefix would not
    hold: partial ones, and whole ones behind a gap."""
    k = whole.index(False) if False in whole else len(whole)
    return partial + sum(whole[k:])
