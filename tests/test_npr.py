"""NPR job: classification, peer aggregation, policy YAML, end-to-end.

Mirrors the reference job's unit suite style (golden YAML assertions on
hand-built flows, policy_recommendation_job_test.py) plus end-to-end runs
over the synthetic store.
"""

import pytest
import yaml

from theia_tpu.analytics.npr import (
    FLOW_TABLE_COLUMNS,
    aggregate_peers,
    get_flow_type,
    map_flow_to_egress,
    map_flow_to_ingress,
    read_columns,
    read_distinct_flows,
    recommend_policies_for_unprotected_flows,
    run_npr,
)
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.store import FlowDatabase


def _flow(**kw):
    base = {
        "sourcePodNamespace": "ns-a",
        "sourcePodLabels": '{"app": "client"}',
        "destinationIP": "10.0.0.5",
        "destinationPodNamespace": "ns-b",
        "destinationPodLabels": '{"app": "server"}',
        "destinationServicePortName": "",
        "destinationTransportPort": 8080,
        "protocolIdentifier": 6,
        "flowType": "pod_to_pod",
    }
    base.update(kw)
    return base


def test_get_flow_type_matches_reference_rules():
    assert get_flow_type(3, "x", "y") == "pod_to_external"
    assert get_flow_type(1, "ns/svc:http", "") == "pod_to_svc"
    assert get_flow_type(1, "", '{"a":"b"}') == "pod_to_pod"
    assert get_flow_type(1, "", "") == "pod_to_external"


def test_peer_mapping_shapes():
    src, dst = map_flow_to_egress(_flow())
    assert src == 'ns-a#{"app": "client"}'
    assert dst == 'ns-b#{"app": "server"}#8080#TCP'
    src, dst = map_flow_to_egress(
        _flow(flowType="pod_to_svc",
              destinationServicePortName="ns-b/web:http"))
    assert dst == "ns-b#web"
    src, dst = map_flow_to_egress(
        _flow(flowType="pod_to_svc",
              destinationServicePortName="ns-b/web:http"), k8s=True)
    assert dst == 'ns-b#{"app": "server"}#8080#TCP'
    dst, src = map_flow_to_ingress(_flow())
    assert dst == 'ns-b#{"app": "server"}'
    assert src == 'ns-a#{"app": "client"}#8080#TCP'


def test_option1_generates_anp_and_per_group_reject():
    flows = [_flow(),
             _flow(flowType="pod_to_external", destinationIP="8.8.8.8",
                   destinationPodNamespace="", destinationPodLabels="")]
    result = recommend_policies_for_unprotected_flows(flows, [], option=1)
    anps = [yaml.safe_load(p) for p in result["anp"]]
    acnps = [yaml.safe_load(p) for p in result["acnp"]]
    assert len(anps) == 2  # ns-a egress policy + ns-b ingress policy
    src_anp = next(a for a in anps
                   if a["metadata"]["namespace"] == "ns-a")
    egress = src_anp["spec"]["egress"]
    # pod-to-pod + external CIDR rules
    peer_kinds = {("ipBlock" in r["to"][0]) for r in egress}
    assert peer_kinds == {True, False}
    cidr_rule = next(r for r in egress if "ipBlock" in r["to"][0])
    assert cidr_rule["to"][0]["ipBlock"]["cidr"] == "8.8.8.8/32"
    assert cidr_rule["action"] == "Allow"
    assert src_anp["spec"]["tier"] == "Application"
    assert src_anp["spec"]["priority"] == 5
    # per-group baseline reject ACNPs (option 1): one per appliedTo group
    assert len(acnps) == 2
    assert all(a["spec"]["tier"] == "Baseline" for a in acnps)
    assert all(a["spec"]["egress"][0]["action"] == "Reject" for a in acnps)


def test_option2_generates_cluster_wide_reject():
    result = recommend_policies_for_unprotected_flows(
        [_flow()], [], option=2)
    rejects = [yaml.safe_load(p) for p in result["acnp"]]
    assert len(rejects) == 1
    assert rejects[0]["metadata"]["name"] == "recommend-reject-all-acnp"
    applied = rejects[0]["spec"]["appliedTo"][0]
    assert applied == {"podSelector": {}, "namespaceSelector": {}}


def test_option3_generates_k8s_np_without_deny():
    flows = [_flow(), _flow(flowType="pod_to_svc",
                            destinationServicePortName="ns-b/web:http")]
    result = recommend_policies_for_unprotected_flows(flows, [], option=3)
    assert set(result.keys()) == {"knp"}
    knps = [yaml.safe_load(p) for p in result["knp"]]
    assert all(p["apiVersion"] == "networking.k8s.io/v1" for p in knps)
    src = next(p for p in knps if p["metadata"]["namespace"] == "ns-a")
    # K8s policies never use toServices; svc flow becomes a pod rule
    assert "toServices" not in yaml.dump(src)
    assert src["spec"]["policyTypes"] == ["Egress"]
    dst = next(p for p in knps if p["metadata"]["namespace"] == "ns-b")
    assert dst["spec"]["policyTypes"] == ["Ingress"]
    peer = dst["spec"]["ingress"][0]["from"][0]
    assert peer["namespaceSelector"]["matchLabels"] == {"name": "ns-a"}


def test_to_services_rule_and_disabled_path():
    svc_flow = _flow(flowType="pod_to_svc",
                     destinationServicePortName="ns-b/web:http")
    with_ts = recommend_policies_for_unprotected_flows(
        [svc_flow], [], option=1, to_services=True)
    anp = yaml.safe_load(with_ts["anp"][0])
    assert anp["spec"]["egress"][0]["toServices"] == [
        {"namespace": "ns-b", "name": "web"}]
    assert with_ts["acg"] == []

    without_ts = recommend_policies_for_unprotected_flows(
        [svc_flow], [], option=1, to_services=False)
    cg = yaml.safe_load(without_ts["acg"][0])
    assert cg["kind"] == "ClusterGroup"
    assert cg["metadata"]["name"] == "cg-ns-b-web"
    assert cg["spec"]["serviceReference"] == {
        "name": "web", "namespace": "ns-b"}
    svc_acnp = next(
        yaml.safe_load(p) for p in without_ts["acnp"]
        if "svc-allow" in yaml.safe_load(p)["metadata"]["name"])
    assert svc_acnp["spec"]["egress"][0]["to"][0]["group"] == "cg-ns-b-web"


def test_ns_allow_list_skips_policies():
    flows = [_flow(sourcePodNamespace="kube-system")]
    result = recommend_policies_for_unprotected_flows(
        flows, ["kube-system"], option=1)
    # egress policy for kube-system suppressed; ingress side (ns-b) stays
    namespaces = [yaml.safe_load(p)["metadata"]["namespace"]
                  for p in result["anp"]]
    assert "kube-system" not in namespaces


def test_aggregate_peers_combines_ingress_and_egress():
    flows = [_flow(), _flow(destinationTransportPort=9090)]
    peers, svc = aggregate_peers(flows, k8s=False, to_services=True)
    applied = 'ns-b#{"app": "server"}'
    assert len(peers[applied]["ingress"]) == 2
    assert not svc


def test_read_distinct_flows_filters_and_dedupes():
    cfg = SynthConfig(n_series=16, points_per_series=10,
                      protected_fraction=0.5, seed=5)
    batch = generate_flows(cfg)
    db = FlowDatabase()
    db.insert_flows(batch)
    rows = read_distinct_flows(db.flows.scan(), rm_labels=False)
    # only unprotected flows (no egress/ingress NP verdicts) survive
    assert 0 < len(rows) < 16
    assert all(isinstance(r["flowType"], str) for r in rows)
    # distinct: far fewer rows than raw records
    assert len(rows) <= 16
    # rm_labels dedupe on the two label columns only
    rows_rm = read_distinct_flows(db.flows.scan(), rm_labels=True)
    assert len(rows_rm) <= len(rows)


def test_npr_end_to_end_initial_and_subsequent():
    cfg = SynthConfig(n_series=24, points_per_series=5, seed=2)
    db = FlowDatabase()
    db.insert_flows(generate_flows(cfg))
    rid = run_npr(db, "initial", option=1, recommendation_id="npr-1")
    assert rid == "npr-1"
    rows = db.recommendations.scan().to_rows()
    kinds = {r["kind"] for r in rows}
    assert "anp" in kinds and "acnp" in kinds
    assert all(r["type"] == "initial" for r in rows)
    # ns allow-list ACNPs present (3 defaults)
    allow = [r for r in rows if "recommend-allow-acnp" in r["policy"]]
    assert len(allow) >= 3
    # all YAML parses and every ANP applies to a real namespace
    for r in rows:
        doc = yaml.safe_load(r["policy"])
        assert doc["kind"] in ("NetworkPolicy", "ClusterNetworkPolicy",
                               "ClusterGroup")

    run_npr(db, "subsequent", option=1, recommendation_id="npr-2")
    rows2 = [r for r in db.recommendations.scan().to_rows()
             if r["id"] == "npr-2"]
    assert rows2
    assert all(r["type"] == "subsequent" for r in rows2)
    # subsequent jobs never include the ns-allow-list platform policies
    assert not any("tier: Platform" in r["policy"] for r in rows2)


# -- the read stays columnar (PR 45) --------------------------------------

LATER = 1_700_000_000


def _two_populations(engine, tmp_path):
    """A store of two populations, the second joining at LATER (a
    window can cut it whole): a fifth under a policy, a third of the
    rest trusted; the parts engine sealed into several parts."""
    db = FlowDatabase(engine=engine, parts_dir=str(tmp_path / "parts"),
                      parts_config={"memtable_rows": 128})
    for seed, start in ((4, None), (5, LATER)):
        kw = {} if start is None else {"start_time": start}
        batch = generate_flows(SynthConfig(
            n_series=60, points_per_series=4, protected_fraction=0.2,
            external_fraction=0.2, service_fraction=0.3, n_namespaces=4,
            pods_per_namespace=5, seed=seed, **kw))
        batch.columns["trusted"][::3] = 1
        db.insert_flows(batch)
    return db


def _job_rows(db, job_id):
    rows = db.recommendations.scan().to_rows()
    return [(r["type"], r["kind"], r["policy"]) for r in rows
            if r["id"] == job_id]


def test_read_columns_names_what_the_query_names():
    base = set(FLOW_TABLE_COLUMNS) | {
        "ingressNetworkPolicyName", "egressNetworkPolicyName"}
    assert len(read_columns()) == len(base) == 11
    assert set(read_columns("initial", 3)) == base
    for option in (1, 2):
        assert set(read_columns("subsequent", option)) == base | {"trusted"}
    # k8s-np never reads the trusted flows, nor does an initial job
    assert set(read_columns("subsequent", 3)) == base
    assert set(read_columns("initial", 1, start_time=5)) \
        == base | {"flowStartSeconds"}
    assert set(read_columns("initial", 1, end_time=9)) \
        == base | {"flowEndSeconds"}
    assert len(read_columns("initial", 2, 5, 9)) == 13
    assert len(read_columns("subsequent", 2, 5, 9)) == 14
    for names in (read_columns(), read_columns("subsequent", 1, 5, 9)):
        assert len(set(names)) == len(names)
        assert names[:9] == FLOW_TABLE_COLUMNS


@pytest.mark.parametrize("engine", ["flat", "parts"])
@pytest.mark.parametrize("window", [{}, {"start_time": LATER - 100,
                                         "end_time": LATER + 3}],
                         ids=["no-window", "window"])
@pytest.mark.parametrize("option", [1, 2, 3])
@pytest.mark.parametrize("kind", ["initial", "subsequent"])
def test_the_projected_read_recommends_what_the_scan_did(
        kind, option, window, engine, tmp_path, monkeypatch):
    """`run_npr` over `select(columns=read_columns(...))` leaves the
    rows that `read_distinct_flows` leaves when fed all 52 columns of
    `scan()`, the same strings in the same order."""
    db = _two_populations(engine, tmp_path)
    asked = []
    select = db.flows.select

    def projected(columns):
        asked.append(tuple(columns))
        return select(columns=columns)

    monkeypatch.setattr(db.flows, "select", projected)
    run_npr(db, kind, option=option, recommendation_id="projected",
            **window)
    assert asked == [read_columns(kind, option, **window)]
    monkeypatch.setattr(db.flows, "select",
                        lambda columns: db.flows.scan())
    run_npr(db, kind, option=option, recommendation_id="scanned",
            **window)
    got, want = _job_rows(db, "projected"), _job_rows(db, "scanned")
    assert got == want and len(got) > 3
    if kind == "subsequent" and option != 3:
        # the second read found trusted flows: the answer holds more
        # than an initial job's without its three namespace policies
        run_npr(db, "initial", option=option, recommendation_id="first",
                **window)
        assert len(got) > len(_job_rows(db, "first")) - 3


def test_the_read_is_counted_by_its_eleven_columns():
    from theia_tpu.obs import metrics
    from theia_tpu.runner.progress import JobProgress

    def counted(what):
        return metrics.REGISTRY.get(
            f"theia_job_read_{what}_total").labels(kind="npr").value()

    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=16, points_per_series=4, seed=9)))
    before = {w: counted(w) for w in ("rows", "columns", "bytes")}
    run_npr(db, progress=JobProgress(
        "npr-45", ["read", "recommend", "write"], kind="npr"))
    assert counted("rows") - before["rows"] == 64
    assert counted("columns") - before["columns"] == 11
    # every one an int32 column: 44 B a row where scan() hands on 284
    assert counted("bytes") - before["bytes"] == 64 * 44


def test_a_batch_without_a_queried_column_is_a_key_error():
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=8, points_per_series=2, seed=1)))
    names = read_columns("subsequent", 1, 5, 9)
    full = db.flows.select(columns=names)
    assert read_distinct_flows(full, start_time=5, end_time=2 ** 40)
    for missing, kw in (
            ("egressNetworkPolicyName", {}),
            ("destinationServicePortName", {}),
            ("trusted", {"unprotected": False}),
            ("flowStartSeconds", {"start_time": 5}),
            ("flowEndSeconds", {"end_time": 9})):
        batch = full.select([n for n in names if n != missing])
        with pytest.raises(KeyError, match=missing):
            read_distinct_flows(batch, **kw)
