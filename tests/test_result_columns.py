"""A completed job's result rows go out by column (manager/results.py):
the answer's bytes and the `*_stats` lists are held against the plain
way, a dict a row with `str()` a cell, written out here."""

import json
import math
import urllib.request

import numpy as np
import pytest

from theia_tpu.manager import TheiaManagerServer
from theia_tpu.manager.api import GROUP_INTELLIGENCE, record_to_api
from theia_tpu.manager.jobs import (_NAME_PREFIX, _RESULT_TABLE,
                                    STATE_COMPLETED, JobRecord)
from theia_tpu.manager.results import select_job
from theia_tpu.schema.flow_schema import ColumnKind as K
from theia_tpu.store import FlowDatabase

_RESOURCE = {"tad": "throughputanomalydetectors",
             "dd": "trafficdropdetections",
             "fpm": "flowpatternminings",
             "sad": "spatialanomalydetections"}

JOB_MANY = "aaaaaaaa-0000-4000-8000-000000000001"
JOB_ONE = "aaaaaaaa-0000-4000-8000-000000000002"
JOB_DELETED = "aaaaaaaa-0000-4000-8000-000000000003"
JOB_UNSEEN = "aaaaaaaa-0000-4000-8000-000000000004"

# What a cell can hold that a shortcut gets wrong: JSON's escapes
# (podLabels is JSON text itself), floats whose repr is not "%g", the
# ends of each integer kind.
_AWKWARD = {
    K.STRING: ['he said "hi"', "back\\slash", "line\nbreak\ttab",
               "ctl\x01\x1f\x7f", "naïve 日本 \U0001f600",
               "", '{"app": "web", "tier": "db"}', "plain"],
    K.F64: [0.0, -0.0, 1 / 3, 1e16, 1e-7, 1.7976931348623157e308,
            math.nan, math.inf, -math.inf, 5e-324, 123456.789],
    K.U8: [0, 255, 6],
    K.U16: [0, 65535, 443],
    K.U64: [0, 2 ** 63 - 1, 1500],
    K.DATETIME: [1727539200, 0, 2 ** 63 - 1],
}


def _inserted_rows(schema):
    """31 rows of three jobs, interleaved: the value of a column moves
    through its kind's awkward values at the column's own pace, so
    the rows differ and every value meets every neighbour."""
    ids = [JOB_MANY, JOB_MANY, JOB_DELETED] * 10 + [JOB_ONE]
    rows = []
    for i, job_id in enumerate(ids):
        row = {}
        for j, col in enumerate(schema):
            pool = _AWKWARD[col.kind]
            row[col.name] = pool[(i * (j + 1) + j) % len(pool)]
        row["id"] = job_id
        rows.append(row)
    return rows


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as resp:
        return resp.status, resp.headers, resp.read()


@pytest.fixture(scope="module")
def served():
    """One manager whose four result tables hold `_inserted_rows`,
    put there through the tables' own insert; JOB_DELETED's rows are
    deleted again, so its id has a dictionary code and no row."""
    db = FlowDatabase()
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    inserted = {}
    for kind in _RESOURCE:
        table = db.result_tables[_RESULT_TABLE[kind]]
        inserted[kind] = _inserted_rows(table.schema)
        assert table.insert_rows(inserted[kind]) == 31
        assert table.delete_ids([JOB_DELETED]) == 10
        for job_id in (JOB_MANY, JOB_ONE, JOB_DELETED, JOB_UNSEEN):
            name = _NAME_PREFIX[kind] + job_id
            srv.controller._records[name] = JobRecord(
                name=name, kind=kind, spec={"jobType": "EWMA",
                                            "note": 'a "quoted" spec'},
                state=STATE_COMPLETED, start_time=1.5, end_time=2.5)
    yield srv, inserted
    srv.shutdown()


@pytest.mark.parametrize("job_id,n_rows", [
    (JOB_MANY, 20), (JOB_ONE, 1), (JOB_DELETED, 0), (JOB_UNSEEN, 0)],
    ids=["many-among-others", "one-row", "rows-deleted", "id-unseen"])
@pytest.mark.parametrize("kind", list(_RESOURCE))
def test_answer_is_json_dumps_of_the_row_dicts_byte_for_byte(
        served, kind, job_id, n_rows):
    srv, inserted = served
    ctl = srv.controller
    schema = srv.controller.db.result_tables[_RESULT_TABLE[kind]].schema
    # the reference: a dict a row, str() a cell, the table's order
    plain = [{col.name: str(row[col.name]) for col in schema}
             for row in inserted[kind]
             if row["id"] == job_id != JOB_DELETED]
    assert len(plain) == n_rows
    name = _NAME_PREFIX[kind] + job_id
    assert ctl.result_stats(kind, name) == plain
    record = ctl.get(name)
    doc = record_to_api(record, ctl, with_result=True)
    assert doc["stats"] == plain and list(doc)[-1] == "stats"
    status, headers, body = _get(
        srv.port, f"{GROUP_INTELLIGENCE}/{_RESOURCE[kind]}/{name}")
    assert status == 200
    assert int(headers["Content-Length"]) == len(body)
    assert body == json.dumps(doc, default=str).encode()
    if not n_rows:
        assert body.endswith(b'"stats": []}')


def test_the_established_stats_helpers_read_the_same_columns(served):
    ctl = served[0].controller
    assert ctl.tad_stats("tad-" + JOB_MANY) \
        == ctl.result_stats("tad", "tad-" + JOB_MANY)
    assert ctl.drop_detection_stats("dd-" + JOB_ONE) \
        == ctl.result_stats("dd", "dd-" + JOB_ONE)


def test_select_job_reads_the_scanned_batchs_own_dictionary():
    """A sharded table's scan re-codes its shards' strings into one
    merged dictionary: the job's code is looked up there."""
    from theia_tpu.store import ShardedFlowDatabase
    db = ShardedFlowDatabase(n_shards=3, seed=7)
    rows = _inserted_rows(db.spatialnoise.schema)
    db.spatialnoise.insert_rows(rows)
    data = db.spatialnoise.scan()
    mine = select_job(data, JOB_MANY)
    assert len(mine) == 20
    assert set(mine.strings("id")) == {JOB_MANY}
    assert sorted(np.asarray(mine["octetDeltaCount"]).tolist()) \
        == sorted(r["octetDeltaCount"] for r in rows
                  if r["id"] == JOB_MANY)
    assert len(select_job(data, JOB_UNSEEN)) == 0
