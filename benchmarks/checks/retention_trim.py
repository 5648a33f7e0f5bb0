"""Check `retention_trim`: the window's retention round deleted from
`flows` and from every materialized view exactly what upstream's
monitor deletes, no other row, and exactly once.

Run first of the cell's checks, after quiescence. It holds the
trimmer's two answers (the warm-up's idle round, the window's), the
counters it read at the window's open and close, and what the manager
then holds, against the reference over the generator's own rows
(references/retention_trim.py: `main.go`'s round by a full sort):

  flows   `/query?group_by=clusterUUID&agg=count,sum:octetDeltaCount,
          min:timeInserted` (as `store_totals` asks it), per producer
  views   `GET /debug/retention`: sum(octetDeltaCount) and the oldest
          timeInserted of each view, which do not depend on how the
          view's parts lie. `/query` resolves `flows` and the result
          tables, never a view; the views' other reads (a dashboard
          panel, the stats API's tableInfo) go through
          `ViewTable.scan()`, which first re-groups the whole view
          into one part (a concatenation and a lexsort over 9 to 20
          key columns of 16-22 M view rows, beside a manager that
          holds 36 GB of the host's memory by then; PERF.md section 4)
          and answers top-k links or merged row counts, not totals
  detector `/healthz` `ingest.perShard[].series`

Which blocks were in the store at the round follows from the answer's
`rowsBefore` and from the producers' records (a block acked before the
trimmer sent its request was in); the reference says when that cannot
be told (`trim_ambiguous_blocks`) and the check then fails rather than
guess.

All exact (limit 0):

  trim_rounds_gap          rounds that trimmed in the window (the rise
                           of theia_retention_rounds_total{result=
                           "trimmed"} between the trimmer's two reads)
                           less 1, plus those before the window, plus
                           failed rounds; the warm-up's answer `idle`
                           and the window's `trimmed`
  trim_boundary_gap        the answer's `boundary` against the
                           reference's
  trim_rows_deleted_gap    the answer's `rowsDeleted` and `deleteN`,
                           and the whole run's
                           theia_retention_rows_deleted_total, against
                           the reference's
  trim_store_rows_gap      per producer, rows in the store against the
                           acked rows at or above the boundary
  trim_store_octets_gap    the same for sum(octetDeltaCount)
  trim_oldest_row_gap      the store's smallest timeInserted against
                           the boundary: nothing older survives, the
                           boundary's own second does
  trim_view_rows_gap       per view: sum(octetDeltaCount) and oldest
                           timeInserted (neither depends on whether
                           the view's parts were merged) against the
                           reference's over the retained rows, and the
                           answer's `viewRowsDeleted`, which the round
                           counts itself as the parts held them then,
                           against the reference's
  trim_detector_series_gap the detector's series against every
                           connection sent: a trim takes no detector
                           state
  trim_ambiguous_blocks    blocks of which the reference cannot say
                           whether the round saw them
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks import check as _check
from benchmarks.gen import cluster_uuid
from benchmarks.references import retention_trim as _ref

DELETED = "theia_retention_rows_deleted_total"
QUERY = ("/query?group_by=clusterUUID&agg=count,sum:octetDeltaCount,"
         "min:timeInserted&k=0&cache=0")


def trimmer_records(ctx: Dict) -> Tuple[Dict, Dict, Dict]:
    """(warm-up answer, window answer, the window's result) of the
    one trimmer."""
    for i, spec in enumerate(ctx["specs"]):
        if spec["role"] == "trimmer":
            return (ctx["warm"][i]["records"][0],
                    ctx["results"][i]["records"][0], ctx["results"][i])
    raise _check.RunFailed("check retention_trim: the traffic file has "
                           "no `trimmer` worker")


def acked_before(recs: List[Dict], t: float) -> int:
    """Blocks of one producer acked before `t` (its records are in the
    order sent, one outstanding at a time)."""
    return sum(1 for r in recs if r["status"] == 200 and r["ack"] < t)


def _gap(a, b) -> int:
    """|a - b| of two whole numbers; 1 where either is missing."""
    return 1 if a is None or b is None else abs(int(a) - int(b))


def compare(rep, facts: Dict) -> None:
    """`facts`: `warm` and `asked` (the trimmer's records), `rounds`
    (by result, at the window's open and at its close), `deleted_total`
    (the counter after quiescence), `want` (references.round_of),
    `kept` (references.retained, or None without a boundary), `store`
    {producer: (rows, octets, oldest)}, `strangers` (rows of no
    producer), `views` (/debug/retention `views`), `series` (got,
    sent)."""
    warm, asked, want = facts["warm"], facts["asked"], facts["want"]
    r0, r1 = facts["rounds"]
    have = r0 is not None and r1 is not None
    rounds_gap = (
        (abs(r1.get("trimmed", 0) - r0.get("trimmed", 0) - 1)
         + r0.get("trimmed", 0) + r1.get("error", 0)) if have else 1)
    rounds_gap += (warm.get("result") != "idle") \
        + (asked.get("result") != "trimmed")
    rep.compare("trim_rounds_gap", rounds_gap, 0,
                f"rounds by result {r0} at the window's open, {r1} at "
                f"its close; answers {warm.get('result')!r}, "
                f"{asked.get('result')!r}; the window's round took "
                f"{asked.get('seconds')} s, stagesMs "
                f"{asked.get('stages_ms')}")
    boundary = want["boundary"]
    rep.compare("trim_boundary_gap", _gap(asked.get("boundary"), boundary),
                0, f"answer {asked.get('boundary')}, reference {boundary} "
                   f"(the {want['delete_n']}-th oldest of "
                   f"{asked.get('rows_before')} rows)")
    rep.compare("trim_rows_deleted_gap",
                _gap(asked.get("rows_deleted"), want["rows_deleted"])
                + _gap(asked.get("delete_n"), want["delete_n"])
                + _gap(facts["deleted_total"], want["rows_deleted"]), 0,
                f"answer {asked.get('rows_deleted')}, counter "
                f"{facts['deleted_total']}, reference "
                f"{want['rows_deleted']}")
    kept = facts["kept"]
    rows_gap = octets_gap = oldest_gap = view_gap = 0
    notes: List[str] = []
    if kept is None:
        rows_gap = octets_gap = oldest_gap = view_gap = 1
    else:
        store = dict(facts["store"])
        for p, (rows, octets) in kept["by_producer"].items():
            g = store.pop(p, (0, 0, None))
            rows_gap += abs(g[0] - rows)
            octets_gap += abs(g[1] - octets)
            if g[2] is not None and g[2] < boundary:
                oldest_gap += boundary - g[2]
        rows_gap += facts["strangers"]
        oldest = min((g[2] for g in facts["store"].values()
                      if g[2] is not None), default=None)
        oldest_gap += _gap(oldest, kept["oldest"])
        deleted = asked.get("view_rows_deleted") or {}
        for view, w in kept["views"].items():
            got = (facts["views"] or {}).get(view, {})
            gap = (sum(_gap(got.get(k), w[k]) for k in w)
                   + _gap(deleted.get(view),
                          want["view_rows_deleted"][view]))
            if gap:
                notes.append(f"{view}: {got}, reference {w}, deleted "
                             f"{deleted.get(view)} of "
                             f"{want['view_rows_deleted'][view]}")
            view_gap += gap
    rep.compare("trim_store_rows_gap", rows_gap, 0,
                "acked rows at or above the boundary, by clusterUUID")
    rep.compare("trim_store_octets_gap", octets_gap, 0,
                "their sum(octetDeltaCount)")
    rep.compare("trim_oldest_row_gap", oldest_gap, 0,
                f"the store's oldest timeInserted against {boundary}")
    rep.compare("trim_view_rows_gap", view_gap, 0,
                "; ".join(notes)[:400] or "octets and oldest row of the "
                "three views, and the rows the round deleted of each")
    got, sent = facts["series"]
    rep.compare("trim_detector_series_gap", abs(got - sent), 0,
                f"{got} series, {sent} distinct connections sent")
    rep.compare("trim_ambiguous_blocks", want["ambiguous_blocks"], 0,
                "; ".join(want["why"])[:300])


def check(ctx: Dict, rep) -> None:
    warm, asked, result = trimmer_records(ctx)
    rep.attempted += 2
    rep.failed += sum(r["status"] != 200 for r in (warm, asked))
    streams = [(s, n) for s, n, _ in _check.streams(ctx)]
    before = [acked_before(recs, asked["send"])
              for _, _, recs in _check.streams(ctx)]
    want = _ref.round_of(streams, before, int(asked.get("rows_before") or 0),
                         ctx["config"]["monitor"]["delete_percentage"])
    kept = None if want["boundary"] is None \
        else _ref.retained(streams, want["boundary"])
    mgr = ctx["manager"]
    by_uuid = {r["clusterUUID"]: (int(r["count"]),
                                  int(r["sum(octetDeltaCount)"]),
                                  int(r["min(timeInserted)"]))
               for r in mgr.json(QUERY, timeout=600.0)["rows"]}
    store = {s.producer: by_uuid.pop(cluster_uuid(s.producer))
             for s, _ in streams if cluster_uuid(s.producer) in by_uuid}
    shards = ctx["health"]["ingest"]["perShard"]
    sent = 0
    for s, n in streams:
        conns = [s.conn_index(b) for b in range(n)]
        sent += np.unique(np.concatenate(conns)).size if conns else 0
    compare(rep, {
        "warm": warm, "asked": asked,
        "rounds": (result.get("rounds_at_open"),
                   result.get("rounds_at_close")),
        "deleted_total": ctx["metrics_final"].get(DELETED),
        "want": want, "kept": kept, "store": store,
        "strangers": sum(g[0] for g in by_uuid.values()),
        "views": mgr.json("/debug/retention", timeout=600.0).get("views"),
        "series": (sum(sh["series"] for sh in shards), sent),
    })
