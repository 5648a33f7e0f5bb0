"""checks/npr_policies.py is sharp, and references/npr.py is one thing:
over an answer made here the way the job's carries it (YAML documents
joined with `---`, names with a suffix, rules in another order), the
reference's own documents are correct, and a block of a connection
that appears in no other left out, one protected connection admitted,
one destination port changed, a service flow read as pod-to-pod, one
document dropped, one sent twice, a counter off by one and a FAILED
job each give `correct: false` by the number that names the fault. No
manager is started: the check reads records, a job's answer and the
final `/metrics`, and these are records, an answer and a dict."""

import copy
import functools
import json

import pytest
import yaml

from benchmarks import check as _check
from benchmarks import gen, manifest
from benchmarks.checks import npr_policies as nc
from benchmarks.kernels import npr_distinct as kernel
from benchmarks.references import npr as ref

BENCH = manifest.load()
CELL = "parts-fused-npr.npr-initial"
SHIPPED = BENCH.traffic(BENCH.cell(CELL)["traffic"])
SPEC = SHIPPED["workers"][1]["job"]["spec"]
#: the cell's law and request at a size a test can hold: two slices of
#: 192 connections, one block each, so that the second block's
#: connections appear in no other
TRAFFIC = {
    "name": "t", "limits": {},
    "generator": {**SHIPPED["generator"], "connections_per_producer": 384,
                  "conns_per_block": 192, "points_per_conn": 4},
    "workers": [{"role": "producer", "count": 1},
                {"role": "jobs", "count": 1, "job": {
                    "resource": "networkpolicyrecommendations",
                    "spec": SPEC}}],
}
SEED = 2147489333
N_BLOCKS = 2
JOBS = 3                          # the warm-up's and two in the window


def records(n_blocks=N_BLOCKS, alter=None):
    """The generator's records of the first blocks, `alter(record)`
    applied to a copy of each."""
    stream = gen.stream(TRAFFIC, SEED, 0)
    out = []
    for rec, n in nc.block_records(stream, n_blocks):
        rec = dict(rec)
        if alter:
            alter(rec)
        out.append((rec, n))
    return out


def answer(docs):
    """A COMPLETED job's polled answer over `docs`, as the program
    writes them: a suffix on the names that get one, the rules the
    other way round, YAML joined with `---`."""
    docs = copy.deepcopy(docs)
    for d in docs:
        name = d["metadata"]["name"]
        if name in ref.SUFFIXED_NAMES \
                or name.startswith("recommend-allow-acnp-"):
            d["metadata"]["name"] = name + "-0a1b2"
        for rules in ("egress", "ingress"):
            if isinstance(d["spec"].get(rules), list):
                d["spec"][rules].reverse()
    return json.dumps({"status": {
        "state": "COMPLETED", "recommendationOutcome":
        "---\n".join(yaml.dump(d) for d in reversed(docs))}})


def counters(want, jobs=JOBS, off=0):
    return {nc.ROWS_SORTED: float(jobs * want["rows_sorted"]),
            nc.DISTINCT_FLOWS: float(jobs * want["distinct_flows"] + off)}


def ctx_of(body, metrics, state="COMPLETED"):
    acked = [{"status": 200, "block": b} for b in range(N_BLOCKS)]
    job = {"state": "COMPLETED"}
    return {
        "traffic": TRAFFIC, "seed": SEED, "metrics_final": metrics,
        "specs": [{"role": "producer", "producer": 0}, {"role": "jobs"}],
        "preload": [{"records": acked}, {"records": []}],
        "warm": [{"records": []}, {"records": [job]}],
        "probes": [{"records": []}] * 2,
        "results": [{"records": []},
                    {"records": [job, {"state": state}],
                     "last_result": body}],
    }


@functools.lru_cache(maxsize=None)
def sound():
    """What the reference answers and counts over the acked blocks."""
    return nc.reference(records(), SPEC)


def failed(docs, metrics=None, **kw):
    want = sound()
    rep = _check.Report()
    nc.check(ctx_of(answer(docs) if docs is not None else None,
                    counters(want) if metrics is None else metrics, **kw),
             rep)
    doc = rep.doc()
    bad = sorted(k for k, v in doc["numbers"].items()
                 if v["value"] > v["limit"])
    assert doc["correct"] is (not bad)
    return bad, doc


def documents(**kw):
    return (nc.reference(records(**kw), SPEC) if kw else sound()
            )["documents"]


def test_the_references_own_documents_are_correct():
    want = sound()
    assert want["distinct_flows"] > 300 and want["rows_sorted"] \
        == 4 * sum(1 for r, _ in records()
                   if not r["egressNetworkPolicyName"])
    kinds = {ref.policy_kind(d) for d in want["documents"]}
    assert kinds == {"anp", "acnp"}
    bad, doc = failed(want["documents"])
    assert bad == []
    assert set(doc["numbers"]) == {"jobs_not_completed"} | set(nc.NUMBERS)
    assert all(v["limit"] == 0 for v in doc["numbers"].values())


def keyed(alter):
    """`alter` applied to the first record it accepts, and to that
    connection's every later record."""
    chosen = []

    def apply(rec):
        key = tuple(rec[c] for c in ref.FLOW_COLUMNS)
        if chosen and chosen[0] != key:
            return
        if alter(rec):
            chosen[:] = [key]
    return apply


def admit_protected(rec):
    if rec["egressNetworkPolicyName"]:
        rec["egressNetworkPolicyName"] = ""
        rec["ingressNetworkPolicyName"] = ""
        return True


def another_port(rec):
    if not rec["egressNetworkPolicyName"] and rec["flowType"] != 3 \
            and not rec["destinationServicePortName"]:
        rec["destinationTransportPort"] += 1
        return True


def service_as_pod(rec):
    if not rec["egressNetworkPolicyName"] \
            and rec["destinationServicePortName"]:
        rec["destinationServicePortName"] = ""
        return True


@pytest.mark.parametrize("docs,bad", [
    # the second block's connections appear in no other block
    (lambda: documents(n_blocks=1),
     ["npr_policies_missing", "npr_policies_unexpected",
      "npr_policy_kind_gap"]),
    # its groups may be new (two documents each) or known (two altered)
    (lambda: documents(alter=keyed(admit_protected)),
     ["npr_policies_unexpected"]),
    (lambda: documents(alter=keyed(another_port)),
     ["npr_policies_missing", "npr_policies_unexpected"]),
    (lambda: documents(alter=keyed(service_as_pod)),
     ["npr_policies_missing", "npr_policies_unexpected"]),
    (lambda: documents()[:-1],
     ["npr_policies_missing", "npr_policy_kind_gap"]),
    (lambda: documents() + documents()[5:6],
     ["npr_policies_unexpected", "npr_policy_kind_gap"]),
], ids=["a-block-fewer", "a-protected-connection-admitted",
        "a-port-changed", "a-service-flow-as-pod-to-pod",
        "a-document-dropped", "a-document-twice"])
def test_a_perturbed_answer_is_not_correct(docs, bad):
    found = failed(docs())[0]
    assert set(bad) <= set(found) <= {
        "npr_policies_missing", "npr_policies_unexpected",
        "npr_policy_kind_gap"}
    if "kind_gap" in bad[-1]:        # a document more or fewer: exactly
        assert found == bad
    assert failed(documents())[0] == []


def test_a_program_that_counts_other_rows_or_flows_is_not_correct():
    want = sound()
    docs = want["documents"]
    assert failed(docs, counters(want, off=1))[0] \
        == ["npr_distinct_flows_gap"]
    short = nc.reference(records(n_blocks=1), SPEC)
    assert failed(docs, counters(short))[0] \
        == ["npr_distinct_flows_gap", "npr_rows_sorted_gap"]
    # one job fewer counted than completed
    assert failed(docs, counters(want, jobs=JOBS - 1))[0] \
        == ["npr_distinct_flows_gap", "npr_rows_sorted_gap"]
    # a manager before the counters has no account to hold: the two
    # numbers are left out, the documents still decide
    bad, doc = failed(docs, {})
    assert bad == [] and "npr_rows_sorted_gap" not in doc["numbers"]
    assert any("left out" in ln for ln in doc["lines"])
    assert failed(docs[:-1], {})[0] == ["npr_policies_missing",
                                        "npr_policy_kind_gap"]


def test_a_job_that_did_not_complete_and_an_answer_that_is_missing():
    want = sound()
    # the job that FAILED counted nothing
    account = counters(want, jobs=JOBS - 1)
    assert failed(documents(), account, state="FAILED")[0] \
        == ["jobs_not_completed"]
    bad, _ = failed(None, account, state="FAILED")
    assert bad == ["jobs_not_completed"] + sorted(nc.NUMBERS)


def test_a_request_the_reference_cannot_stand_for_is_a_broken_run():
    for spec in ({**SPEC, "excludeLabels": True},
                 {k: v for k, v in SPEC.items() if k != "excludeLabels"},
                 {**SPEC, "endInterval": 1700000000},
                 {**SPEC, "jobType": "subsequent"}):
        traffic = copy.deepcopy(TRAFFIC)
        traffic["workers"][1]["job"]["spec"] = spec
        with pytest.raises(_check.RunFailed, match="excludeLabels"):
            nc.job_spec(traffic)


def test_the_kernels_bytes_at_the_cells_shape_and_here():
    # 3,119,904 rows x 9 codes in, 3,607 rows x (9 codes + a count) out
    assert kernel.distinct_bytes(3119904, 3607) == 112460824
    want = sound()
    data = {"traffic": TRAFFIC, "specs": [
        {"role": "producer", "producer": 0, "seed": SEED,
         "preload_blocks": N_BLOCKS}, {"role": "jobs"}]}
    assert kernel.least(data) == {
        "bytes": kernel.distinct_bytes(want["rows_sorted"],
                                       want["distinct_flows"]),
        "flops": 0}
