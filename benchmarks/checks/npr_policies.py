"""Check `npr_policies`: the policy documents of the last COMPLETED
NetworkPolicy Recommendation job are the reference's, every one and no
other, and the job counted the rows and flows the reference counts.

The answer of the last job (`status.recommendationOutcome`: the job's
policies as YAML, joined with `---`) is split into documents, each is
parsed and put in canonical form (references/npr.py `canonical`: the
name's five-character suffix cut, every list sorted), and the documents
are compared as multisets with the reference's over the generator's own
rows of every acked block:

  jobs_not_completed       every job of the run COMPLETED
  npr_policies_missing     documents of the reference the answer lacks
  npr_policies_unexpected  documents of the answer the reference lacks
                           (one sent twice is one too many)
  npr_policy_kind_gap      sum over the kinds anp / acnp / acg / knp of
                           |documents - the reference's|
  npr_distinct_flows_gap   |theia_job_npr_distinct_flows_total - jobs x
                           the reference's distinct 9-tuples|
  npr_rows_sorted_gap      |theia_job_npr_rows_sorted_total - jobs x the
                           rows under the reference's WHERE clause|

Every limit is 0: strings and integers have no rounding to forgive, so
there is no lower precision to hold the comparison against and
`python3 -m benchmarks.control` has nothing to print for this check;
what it has to catch is in benchmarks/tests/test_npr_policies.py. The
two counters are the program's account of its own work, read after
quiescence over the process's whole life (the warm-up's job and the
window's); a manager that exports neither (a commit before them) has
no account to compare and the two numbers are left out, as a reader
leaves out a metric it finds nothing for. The documents are compared
whatever the manager exports.

The YAML is parsed with PyYAML: a library the program did not write
and the image has, as numpy; it is not `theia_tpu` and not jax, so the
benchmark's parent stays off the chip.

The reference stands for the job with `excludeLabels: false` and no
interval: a traffic file that asks for anything else is a broken run.
"""

from __future__ import annotations

import collections
import json
from typing import Dict, Iterable, Iterator, List, Tuple

import yaml

from benchmarks import check as _check
from benchmarks import gen as _gen
from benchmarks.extend import RunFailed
from benchmarks.references import npr as _ref

_LOADER = getattr(yaml, "CSafeLoader", yaml.SafeLoader)
_READ = _ref.FLOW_COLUMNS + (
    "ingressNetworkPolicyName", "egressNetworkPolicyName",
    "flowStartSeconds")
ROWS_SORTED = "theia_job_npr_rows_sorted_total"
DISTINCT_FLOWS = "theia_job_npr_distinct_flows_total"
NUMBERS = ("npr_policies_missing", "npr_policies_unexpected",
           "npr_policy_kind_gap", "npr_distinct_flows_gap",
           "npr_rows_sorted_gap")


def connection_records(producer: int, n_conn: int, start: int
                       ) -> List[Dict]:
    """One flow record a connection of the generator's population:
    the columns this job reads, as the store holds them."""
    pop = _gen.Population(producer, n_conn, start)
    cols = {}
    for name in _READ:
        if name in pop.strings:
            table, idx = pop.strings[name]
            cols[name] = [table[int(i)] for i in idx]
        else:
            cols[name] = [int(v) for v in pop.static[name]]
    return [{name: cols[name][j] for name in _READ}
            for j in range(n_conn)]


def block_records(stream, n_blocks: int) -> Iterator[Tuple[Dict, int]]:
    """(record, rows it stands for) over a producer's first `n_blocks`
    blocks: a block carries `points` records of each of its
    connections, alike in every column the job reads."""
    recs = connection_records(stream.producer, stream.n_conn, stream.start)
    for b in range(n_blocks):
        for j in stream.conn_index(b):
            yield recs[int(j)], stream.points


def job_spec(traffic: Dict) -> Dict:
    """The one job request of the traffic file, which the reference
    has to be able to stand for."""
    specs = [g["job"]["spec"] for g in traffic["workers"]
             if g["role"] == "jobs"]
    if len(specs) != 1:
        raise RunFailed("npr_policies wants one `jobs` group")
    spec = specs[0]
    if spec.get("excludeLabels", True) or spec.get("startInterval") \
            or spec.get("endInterval") or spec.get("limit") \
            or spec.get("jobType", "initial") != "initial":
        raise RunFailed(
            "npr_policies holds `run --type initial` with excludeLabels "
            "false, no interval and no limit: under the label pass "
            "upstream keeps an arbitrary row of a label pair")
    return spec


def reference(records: Iterable[Tuple[Dict, int]], spec: Dict) -> Dict:
    """What the job has to answer and count over `records`."""
    flows, selected = _ref.distinct_unprotected(records)
    docs = _ref.recommend(
        flows, spec.get("policyType", "anp-deny-applied"),
        bool(spec.get("toServices", True)),
        spec.get("nsAllowList") or _ref.NAMESPACE_ALLOW_LIST)
    return {"documents": docs, "distinct_flows": len(flows),
            "rows_sorted": selected}


def documents_of(answer: str) -> List[Dict]:
    """The policy documents of a job's polled answer."""
    outcome = json.loads(answer).get("status", {}).get(
        "recommendationOutcome", "")
    return [d for d in yaml.load_all(outcome, Loader=_LOADER)
            if d is not None]


def compare(got: List[Dict], want: List[Dict]) -> Dict[str, int]:
    """The three numbers of the answer's documents against the
    reference's."""
    g = collections.Counter(_ref.canonical(d, named=True) for d in got)
    w = collections.Counter(_ref.canonical(d) for d in want)
    kinds_g = collections.Counter(_ref.policy_kind(d) for d in got)
    kinds_w = collections.Counter(_ref.policy_kind(d) for d in want)
    return {
        "npr_policies_missing": sum((w - g).values()),
        "npr_policies_unexpected": sum((g - w).values()),
        "npr_policy_kind_gap": sum(abs(kinds_g[k] - kinds_w[k])
                                   for k in kinds_g.keys() | kinds_w.keys()),
    }


def counted(metrics: Dict[str, float], jobs: int, want: Dict
            ) -> Dict[str, int]:
    """The two numbers of the program's counters, over `jobs` jobs;
    none where the manager exports neither."""
    if ROWS_SORTED not in metrics and DISTINCT_FLOWS not in metrics:
        return {}
    return {
        "npr_distinct_flows_gap": abs(
            int(metrics.get(DISTINCT_FLOWS, 0))
            - jobs * want["distinct_flows"]),
        "npr_rows_sorted_gap": abs(
            int(metrics.get(ROWS_SORTED, 0)) - jobs * want["rows_sorted"]),
    }


def check(ctx: Dict, rep) -> None:
    spec = job_spec(ctx["traffic"])
    bad = n = done = 0
    last = None
    for i, s in enumerate(ctx["specs"]):
        if s["role"] != "jobs":
            continue
        window = ctx["results"][i]["records"]
        for r in window:
            n += 1
            bad += r.get("state") != "COMPLETED"
        done += sum(r.get("state") == "COMPLETED"
                    for r in ctx["warm"][i]["records"] + window)
        last = ctx["results"][i].get("last_result") or last
    rep.attempted += n
    rep.failed += bad
    rep.compare("jobs_not_completed", bad, 0, f"{n} jobs")
    if last is None:
        for name in NUMBERS:
            rep.compare(name, 1, 0, "no job result")
        return
    want = reference(
        (rec for stream, k, _ in _check.streams(ctx)
         for rec in block_records(stream, k)), spec)
    got = documents_of(last)
    nums = compare(got, want["documents"])
    nums.update(counted(ctx["metrics_final"], done, want))
    detail = (f"{len(got)} documents, reference {len(want['documents'])}; "
              f"{want['distinct_flows']} distinct flows of "
              f"{want['rows_sorted']} rows; {done} jobs counted")
    for name in NUMBERS:
        if name in nums:
            rep.compare(name, nums[name], 0, detail)
        else:
            rep.lines.append(f"check {name}: left out, the manager "
                             f"exports no {ROWS_SORTED}")
