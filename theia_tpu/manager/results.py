"""A job's result rows, by column.

The answer that carries a completed job's result rows (GET of a job by
name, manager/api.py) and the `*_stats` lists of JobController are made
here from the same per-column strings, so the two cannot drift: each
distinct value of a column is turned into its string once, and a row
is a gather. No Python object is made per cell on the way to the
socket. The bytes are those of `json.dumps(doc, default=str)` over the
document whose `stats` is the list of row dicts with `str()` a cell
(reference getTADetectorResult, rest.go:249-310, answers in strings).
"""

from __future__ import annotations

from json.encoder import encode_basestring_ascii as _json_str
from typing import Dict, List, Tuple

import numpy as np

from ..schema.columnar import ColumnarBatch
from ..schema.flow_schema import ColumnKind


def select_job(data: ColumnarBatch, job_id: str) -> ColumnarBatch:
    """The rows of a scanned result table whose `id` is `job_id`,
    chosen by the id's dictionary code: one lookup in place of a
    decode of the whole column. An id the dictionary has never seen
    has no rows. The dictionary is the scanned batch's own (a sharded
    table's scan merges its shards' dictionaries)."""
    code = data.dicts["id"].lookup(job_id)
    if code is None:
        return data.take(np.zeros(0, np.intp))
    return data.filter(np.asarray(data["id"]) == code)


def _distinct_strings(rows: ColumnarBatch, name: str,
                      kind: ColumnKind) -> Tuple[List[str], np.ndarray]:
    """(`str()` of each distinct value of the column as a row dict
    would hold it, the place of each row's value among them)."""
    arr = np.asarray(rows[name])
    if kind is ColumnKind.F64:
        # distinct by bit pattern: -0.0 is not 0.0, and every nan
        # reads "nan" whatever its payload
        arr = np.ascontiguousarray(arr, np.float64).view(np.int64)
    uniq, index = np.unique(arr, return_inverse=True)
    if kind is ColumnKind.STRING:
        # one by one: the codes in use, not the dictionary's length
        decode_one = rows.dicts[name].decode_one
        return [decode_one(c) for c in uniq.tolist()], index
    if kind is ColumnKind.F64:
        # str() of a Python float is its repr
        return list(map(repr, uniq.view(np.float64).tolist())), index
    return list(map(str, uniq.tolist())), index


def _gather(values: List[str], index: np.ndarray) -> List[str]:
    return np.asarray(values, dtype=object)[index].tolist()


class ResultColumns:
    """The string form of a job's result rows, column by column: for
    each column, in the batch's order, its name, the strings of its
    distinct values and which of them each row holds. The column's
    kind in the table's schema says how a value reads."""

    def __init__(self, rows: ColumnarBatch, schema) -> None:
        kinds = {c.name: c.kind for c in schema}
        self.n_rows = len(rows)
        self.columns = [
            (name, *_distinct_strings(rows, name, kinds[name]))
            for name in rows.column_names]

    def rows(self) -> List[Dict[str, str]]:
        """One dict a row, every value a string (CLI printing, tests,
        in-process callers)."""
        names = [name for name, _, _ in self.columns]
        cells = [_gather(values, index)
                 for _, values, index in self.columns]
        return [dict(zip(names, row)) for row in zip(*cells)]

    def json_bytes(self, head: str, tail: str) -> bytes:
        """`head` + the rows as a JSON list + `tail`, encoded: what
        `json.dumps` writes for `rows()` (", " and ": " separators,
        ensure_ascii escapes), joined from the columns' fragments."""
        if not self.n_rows:
            return (head + "[]" + tail).encode()
        # `fixed`: the text of a row since its last varying column. A
        # column with one value among the rows (the job's id, its
        # algoType) is part of that text and costs no gather.
        fixed = "{"
        parts = []
        for name, values, index in self.columns:
            pre = fixed + _json_str(name) + ": "
            frags = [pre + s for s in map(_json_str, values)]
            if len(frags) == 1:
                fixed = frags[0] + ", "
                continue
            parts.append(_gather(frags, index))
            fixed = ", "
        end = fixed[:-2] + "}"
        rows = (list(map("".join, zip(*parts))) if parts
                else [""] * self.n_rows)
        rows[0] = head + "[" + rows[0]
        rows[-1] += end + "]" + tail
        return (end + ", ").join(rows).encode()
