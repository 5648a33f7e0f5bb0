"""`run_npr`, and the NPR job through the controller, against the plain
reference (tests/npr_reference.py: upstream's job over flow records as
dicts, policies as dicts, nothing of the program; the same text the
benchmark's check reads as benchmarks/references/npr.py).

Strings and integers: the documents are equal exactly, as multisets of
their canonical text (a name's suffix cut, every list sorted). The
reference stands for `excludeLabels: false`; under the default,
upstream keeps an arbitrary flow of each label pair, so there one
property is held: every label pair of the reference contributes
exactly one of its flows."""

import collections
import pathlib

import pytest
import yaml

from tests import npr_reference as ref
from theia_tpu.analytics import run_npr
from theia_tpu.analytics.npr import read_columns, read_distinct_flows
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.manager.jobs import (KIND_NPR, KIND_TAD, POLICY_TYPE_OPTION,
                                    JobController)
from theia_tpu.obs import metrics
from theia_tpu.store import FlowDatabase

HERE = pathlib.Path(__file__).resolve().parent
LATER = 1_700_000_000


def database(seed):
    """A store of two populations, the second of which joins later
    (an interval can cut it whole), a fifth of each under a policy."""
    kw = dict(n_series=150, points_per_series=5, protected_fraction=0.2,
              external_fraction=0.2, service_fraction=0.3,
              n_namespaces=5, pods_per_namespace=6)
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(seed=seed, **kw)))
    db.insert_flows(generate_flows(SynthConfig(
        seed=seed + 1, start_time=LATER, **kw)))
    return db


def records(db):
    """The store's flow records as plain dicts, each standing for
    itself."""
    return [(row, 1) for row in db.flows.scan().to_rows()]


def answer(db, job_id):
    """(kind, document) of the rows a job wrote."""
    rows = [r for r in db.recommendations.scan().to_rows()
            if r["id"] == job_id]
    return [(r["kind"], yaml.safe_load(r["policy"])) for r in rows]


def canon(docs, named=False):
    return collections.Counter(ref.canonical(d, named) for d in docs)


CASES = [
    dict(policy_type=p, to_services=s)
    for p in ref.POLICY_TYPES for s in (True, False)
] + [
    dict(policy_type="anp-deny-applied", to_services=False,
         ns_allow_list=["ns-1", "ns-3"]),
    dict(policy_type="k8s-np", to_services=True, ns_allow_list=["ns-0"]),
    dict(policy_type="anp-deny-applied", to_services=True,
         end_time=LATER - 1),
    dict(policy_type="anp-deny-all", to_services=False,
         start_time=LATER - 100, end_time=LATER + 3),
]


@pytest.mark.parametrize("seed", [31, 47])
@pytest.mark.parametrize(
    "case", CASES, ids=lambda c: "-".join(
        f"{k}={v}" for k, v in c.items()).replace("[", "").replace("]", ""))
def test_run_npr_gives_the_references_documents(case, seed):
    db = database(seed)
    allow = case.get("ns_allow_list")
    window = (case.get("start_time"), case.get("end_time"))
    run_npr(db, option=POLICY_TYPE_OPTION[case["policy_type"]],
            to_services=case["to_services"], ns_allow_list=allow,
            start_time=window[0], end_time=window[1], rm_labels=False,
            recommendation_id="job", mesh=None)
    flows, selected = ref.distinct_unprotected(records(db), *window)
    want = ref.recommend(flows, case["policy_type"], case["to_services"],
                         allow or ref.NAMESPACE_ALLOW_LIST)
    got = answer(db, "job")
    assert 0 < selected < len(db.flows) and flows
    assert canon([d for _, d in got], named=True) == canon(want)
    assert all(kind == ref.policy_kind(doc) for kind, doc in got)
    if any(window):
        # the interval did cut flows: the whole store gives more
        assert len(ref.distinct_unprotected(records(db))[0]) > len(flows)


def test_the_default_label_pass_keeps_one_flow_of_each_label_pair():
    db = database(31)
    flows, _ = ref.distinct_unprotected(records(db))
    kept = read_distinct_flows(db.flows.scan(), rm_labels=True)
    pairs = collections.Counter(
        (r["sourcePodLabels"], r["destinationPodLabels"]) for r in kept)
    assert set(pairs) == ref.label_pairs(flows)
    assert set(pairs.values()) == {1}
    assert len(pairs) < len(flows)         # the pass did drop flows
    assert {tuple(r[c] for c in ref.FLOW_COLUMNS[:8]) for r in kept} \
        <= {f[:8] for f in flows}


def test_a_name_keeps_what_is_not_a_suffix():
    """`canonical` cuts five characters from a name this job gives and
    from no other."""
    def name(n, named=True):
        return ref.canonical({"metadata": {"name": n}}, named)

    assert name("recommend-allow-anp-1a2b3") == name("recommend-allow-anp",
                                                     False)
    assert name("recommend-allow-acnp-kube-system-0f0f0") \
        == name("recommend-allow-acnp-kube-system", False)
    for kept in ("recommend-reject-all-acnp", "cg-ns-1-svc-12345",
                 "recommend-allow-anp", "other-policy-abcde"):
        assert name(kept) == name(kept, False)


def test_the_two_reference_files_are_one_text():
    assert (HERE / "npr_reference.py").read_text() == (
        HERE.parent / "benchmarks" / "references" / "npr.py").read_text()


# -- through the controller: the parts and the counters -------------------

def _counters():
    get = metrics.REGISTRY.get
    out = {name: get(f"theia_job_npr_{name}_total").value()
           for name in ("rows_sorted", "distinct_flows")}
    for kind in ("anp", "acnp", "acg", "knp"):
        out[kind] = get("theia_job_npr_policies_total").labels(
            kind=kind).value()
    for what in ("rows", "columns", "bytes"):
        out["read_" + what] = get(f"theia_job_read_{what}_total").labels(
            kind="npr").value()
    return out


def _part_counts():
    hist = metrics.REGISTRY.get("theia_job_stage_part_seconds")
    return {(stage, part): hist.labels(kind="npr", stage=stage,
                                       part=part).count()
            for stage, part in (
                ("read", "scan"), ("read", "keys"), ("read", "distinct"),
                ("read", "decode"), ("recommend", "aggregate"),
                ("recommend", "emit"))}


def _rise(after, before):
    return {k: after[k] - before[k] for k in after}


def test_an_npr_job_names_its_parts_and_counts_its_work():
    db = database(31)
    ctl = JobController(db, workers=1)
    try:
        counters, parts = _counters(), _part_counts()
        rec = ctl.create(KIND_NPR, {
            "jobType": "initial", "policyType": "anp-deny-applied",
            "toServices": False, "excludeLabels": False})
        assert ctl.wait_all(timeout=300)
        assert ctl.get(rec.name).state == "COMPLETED", \
            ctl.get(rec.name).error_msg
        flows, selected = ref.distinct_unprotected(records(db))
        want = collections.Counter(
            ref.policy_kind(d) for d in ref.recommend(
                flows, "anp-deny-applied", to_services=False))
        rise = _rise(_counters(), counters)
        assert rise["rows_sorted"] == selected
        assert rise["distinct_flows"] == len(flows)
        assert {k: rise[k] for k in ("anp", "acnp", "acg", "knp")} == {
            "anp": want["anp"], "acnp": want["acnp"],
            "acg": want["acg"], "knp": 0}
        assert want["acg"] > 0
        # the read is the query's 11 columns, not the table's 52
        batch = db.flows.select(columns=read_columns("initial"))
        assert (rise["read_rows"], rise["read_columns"]) == (
            len(db.flows.scan()), len(batch.columns)) == (len(batch), 11)
        assert rise["read_bytes"] == sum(
            a.nbytes for a in batch.columns.values())
        # every part once a job of `--type initial`
        assert set(_rise(_part_counts(), parts).values()) == {1}
        outcome = ctl.recommendation_outcome(rec.name)
        docs = [d for d in yaml.safe_load_all(outcome) if d]
        assert canon(docs, named=True) == canon(ref.recommend(
            flows, "anp-deny-applied", to_services=False))

        # a TAD job raises none of them
        counters, parts = _counters(), _part_counts()
        tad = ctl.create(KIND_TAD, {"jobType": "EWMA"})
        assert ctl.wait_all(timeout=300)
        assert ctl.get(tad.name).state == "COMPLETED"
        assert set(_rise(_counters(), counters).values()) == {0}
        assert set(_rise(_part_counts(), parts).values()) == {0}
    finally:
        ctl.shutdown()
