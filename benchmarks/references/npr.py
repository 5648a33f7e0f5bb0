"""Plain reference of the NetworkPolicy Recommendation job (`theia
policy-recommendation run --type initial`): the standard library over
flow records as dicts of strings and integers. Nothing imported from
the program, no jax, no dictionary codes, no sort of the table, no
device; the policies are plain dicts, never YAML.

What the job computes (upstream plugins/policy-recommendation/
policy_recommendation_job.py, by its line numbers):

  :785-802  generate_sql_query: SELECT DISTINCT of nine columns
            (`FLOW_COLUMNS`) FROM flows WHERE
            ingressNetworkPolicyName == '' AND egressNetworkPolicyName
            == '' [AND flowStartSeconds >= start] [AND flowEndSeconds <
            end]: the flows no policy covers. Here: a `set()` of
            9-tuples.
  :83-91    get_flow_type: flowType 3 is pod_to_external; else a
            service port name makes pod_to_svc; else destination labels
            make pod_to_pod; else pod_to_external.
  :119-171  map_flow_to_egress / _egress_svc / _ingress: a flow gives
            its source group (namespace, labels) an egress peer and,
            unless external, its destination group an ingress peer.
  :621-712  the map / reduceByKey pipeline: peers per appliedTo group.
  :714-726  recommend_policies_for_unprotected_flows: by option, 1
            `anp-deny-applied` (allow ANPs and a reject ACNP a group),
            2 `anp-deny-all` (allow ANPs and one reject ACNP for the
            cluster), 3 `k8s-np` (K8s NetworkPolicies, no deny).
  :737-782  recommend_policies_for_ns_allow_list: one allow ACNP a
            namespace of the allow list (`--type initial` only).
  :253-618  the policy documents (generate_k8s_np, generate_anp, the
            service ClusterGroups and ACNPs, generate_reject_acnp).

Departures from upstream, each because an equal answer could not be
asked for otherwise:

  * a policy's name: upstream appends five random characters
    (generate_policy_name :244-250), the program five of a hash. The
    reference emits the name without any suffix and a comparison cuts
    the other side's (`NAME_SUFFIX`).
  * `excludeLabels` (read_flow_df :815-830) is not modelled: under it
    upstream rewrites the two label columns and then drops duplicates
    on those two columns alone, keeping whichever row Spark meets
    first, so two correct runs may differ. This reference is the job
    with `excludeLabels: false`. `label_pairs` gives what a property
    test can still hold under the option: the label pairs themselves.
  * the order of a policy's rules and of the policies is not part of
    the answer (upstream's comes out of reduceByKey): `canonical`
    sorts every list before two documents are compared.
"""

from __future__ import annotations

import ipaddress
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: the columns of the SELECT DISTINCT (:785-802), in its order
FLOW_COLUMNS = (
    "sourcePodNamespace", "sourcePodLabels", "destinationIP",
    "destinationPodNamespace", "destinationPodLabels",
    "destinationServicePortName", "destinationTransportPort",
    "protocolIdentifier", "flowType",
)
NAMESPACE_ALLOW_LIST = ("kube-system", "flow-aggregator", "flow-visibility")
POLICY_TYPES = ("anp-deny-applied", "anp-deny-all", "k8s-np")
PRIORITY = 5
#: what upstream (random) and the program (a hash) append to a name
NAME_SUFFIX = re.compile(r"^(recommend-.*)-[0-9a-z]{5}$")
#: the names that get one (and `recommend-allow-acnp-<namespace>`);
#: `recommend-reject-all-acnp` and a ClusterGroup's name get none
SUFFIXED_NAMES = ("recommend-allow-anp", "recommend-k8s-np",
                  "recommend-svc-allow-acnp", "recommend-reject-acnp")

Flow = Tuple[str, str, str, str, str, str, int, int, int]
Group = Tuple[str, str]                  # (namespace, labels as JSON)


def distinct_unprotected(records: Iterable[Tuple[Dict, int]],
                         start_time: Optional[int] = None,
                         end_time: Optional[int] = None
                         ) -> Tuple[Set[Flow], int]:
    """(the distinct 9-tuples, the records the WHERE clause let
    through) over `records`: pairs of a flow record (a dict by column
    name) and the number of identical records of the store it stands
    for (a connection's points of one block differ in nothing this job
    reads but `flowEndSeconds`: with an interval, hand each in alone)."""
    flows: Set[Flow] = set()
    selected = 0
    for rec, n in records:
        if rec["ingressNetworkPolicyName"] != "" \
                or rec["egressNetworkPolicyName"] != "":
            continue
        if start_time is not None and rec["flowStartSeconds"] < start_time:
            continue
        if end_time is not None and rec["flowEndSeconds"] >= end_time:
            continue
        selected += n
        flows.add(tuple(rec[c] for c in FLOW_COLUMNS))
    return flows, selected


def flow_type(flow: Flow) -> str:
    """:83-91"""
    if flow[8] == 3:
        return "pod_to_external"
    if flow[5] != "":
        return "pod_to_svc"
    if flow[4] != "":
        return "pod_to_pod"
    return "pod_to_external"


def protocol(number: int) -> str:
    return {6: "TCP", 17: "UDP"}.get(number, "UNKNOWN")


def service_of(port_name: str) -> Tuple[str, str]:
    """'ns/name:port' -> (ns, name)"""
    ns, name = port_name.partition(":")[0].split("/")
    return ns, name


def label_pairs(flows: Iterable[Flow]) -> Set[Tuple[str, str]]:
    """The (source labels, destination labels) pairs of the flows:
    under `excludeLabels` upstream keeps one flow of each."""
    return {(f[1], f[4]) for f in flows}


# -- peers per appliedTo group (:119-171, :621-712) -----------------------

class Peers:
    """What the flows give each appliedTo group, as sets of plain
    tuples: `ingress` {(ns, labels, port, protocol)}, `egress` the
    same for a pod, (ip, port, protocol) for an address, (svc ns, svc
    name) for a `toServices` rule; `svc_egress` {(service port name,
    port, protocol)} where services are allowed through ClusterGroups
    (Antrea policies with `toServices` off)."""

    def __init__(self, flows: Iterable[Flow], k8s: bool,
                 to_services: bool) -> None:
        self.ingress: Dict[Group, set] = {}
        self.egress: Dict[Group, set] = {}
        self.svc_egress: Dict[Group, set] = {}
        self.services: Set[str] = set()
        for f in flows:
            (src_ns, src_labels, dst_ip, dst_ns, dst_labels, svc, port,
             proto, _) = f
            kind = flow_type(f)
            src, dst = (src_ns, src_labels), (dst_ns, dst_labels)
            if kind != "pod_to_external":
                self.ingress.setdefault(dst, set()).add(
                    (src_ns, src_labels, port, protocol(proto)))
            if kind == "pod_to_svc":
                self.services.add(svc)
            if kind == "pod_to_svc" and not k8s and not to_services:
                self.svc_egress.setdefault(src, set()).add(
                    (svc, port, protocol(proto)))
            elif kind == "pod_to_external":
                self.egress.setdefault(src, set()).add(
                    (dst_ip, port, protocol(proto)))
            elif kind == "pod_to_svc" and not k8s:
                self.egress.setdefault(src, set()).add(service_of(svc))
            else:
                self.egress.setdefault(src, set()).add(
                    (dst_ns, dst_labels, port, protocol(proto)))

    def groups(self) -> List[Group]:
        return sorted(set(self.ingress) | set(self.egress))


# -- the documents (:253-618, :737-782) -----------------------------------

def _labels(text: str) -> Optional[Dict]:
    """A group's labels; None where they are no JSON object text (the
    job then recommends nothing for the group)."""
    try:
        return json.loads(text)
    except ValueError:
        return None


def _cidr(ip: str) -> str:
    return ip + ("/32" if ipaddress.ip_address(ip).version == 4
                 else "/128")


def _antrea_pod_peer(ns: str, labels: Dict) -> Dict:
    return {"namespaceSelector":
            {"matchLabels": {"kubernetes.io/metadata.name": ns}},
            "podSelector": {"matchLabels": labels}}


def _antrea_ports(port: int, proto: str) -> List[Dict]:
    return [{"protocol": proto, "port": port}]


def anp(group: Group, ingress: set, egress: set) -> Optional[Dict]:
    """:391-448; None for a group without a rule."""
    ns, labels_text = group
    labels = _labels(labels_text)
    if labels is None:
        return None
    egress_rules, ingress_rules = [], []
    for peer in egress:
        if len(peer) == 4:
            peer_labels = _labels(peer[1])
            if peer_labels is None:
                continue
            egress_rules.append({
                "action": "Allow",
                "to": [_antrea_pod_peer(peer[0], peer_labels)],
                "ports": _antrea_ports(peer[2], peer[3])})
        elif len(peer) == 3:
            egress_rules.append({
                "action": "Allow",
                "to": [{"ipBlock": {"cidr": _cidr(peer[0])}}],
                "ports": _antrea_ports(peer[1], peer[2])})
        else:
            egress_rules.append({
                "action": "Allow",
                "toServices": [{"namespace": peer[0], "name": peer[1]}]})
    for peer in ingress:
        peer_labels = _labels(peer[1])
        if peer_labels is None:
            continue
        ingress_rules.append({
            "action": "Allow",
            "from": [_antrea_pod_peer(peer[0], peer_labels)],
            "ports": _antrea_ports(peer[2], peer[3])})
    if not egress_rules and not ingress_rules:
        return None
    return {
        "apiVersion": "crd.antrea.io/v1alpha1",
        "kind": "NetworkPolicy",
        "metadata": {"name": "recommend-allow-anp", "namespace": ns},
        "spec": {
            "tier": "Application",
            "priority": PRIORITY,
            "appliedTo": [{"podSelector": {"matchLabels": labels}}],
            "egress": egress_rules,
            "ingress": ingress_rules,
        },
    }


def k8s_np(group: Group, ingress: set, egress: set) -> Optional[Dict]:
    """:253-296"""
    ns, labels_text = group

    def pod_peer(peer_ns: str, peer_labels: str) -> Dict:
        return {"namespaceSelector": {"matchLabels": {"name": peer_ns}},
                "podSelector": {"matchLabels": json.loads(peer_labels)}}

    egress_rules = [
        {"to": [pod_peer(p[0], p[1]) if len(p) == 4
                else {"ipBlock": {"cidr": _cidr(p[0])}}],
         "ports": [{"port": p[-2], "protocol": p[-1]}]}
        for p in egress]
    ingress_rules = [
        {"from": [pod_peer(p[0], p[1])],
         "ports": [{"port": p[2], "protocol": p[3]}]}
        for p in ingress]
    if not egress_rules and not ingress_rules:
        return None
    return {
        "apiVersion": "networking.k8s.io/v1",
        "kind": "NetworkPolicy",
        "metadata": {"name": "recommend-k8s-np", "namespace": ns},
        "spec": {
            "egress": egress_rules,
            "ingress": ingress_rules,
            "podSelector": {"matchLabels": json.loads(labels_text)},
            "policyTypes": (["Egress"] if egress_rules else [])
            + (["Ingress"] if ingress_rules else []),
        },
    }


def cluster_group_name(svc_ns: str, svc_name: str) -> str:
    return "-".join(["cg", svc_ns, svc_name])


def service_cluster_group(port_name: str) -> Dict:
    """:451-480"""
    svc_ns, svc_name = service_of(port_name)
    return {
        "apiVersion": "crd.antrea.io/v1alpha2",
        "kind": "ClusterGroup",
        "metadata": {"name": cluster_group_name(svc_ns, svc_name)},
        "spec": {"serviceReference": {"name": svc_name,
                                      "namespace": svc_ns}},
    }


def _cluster_applied_to(ns: str, labels: Dict) -> Dict:
    return {"podSelector": {"matchLabels": labels},
            "namespaceSelector":
                {"matchLabels": {"kubernetes.io/metadata.name": ns}}}


def service_acnp(group: Group, svc_egress: set) -> Optional[Dict]:
    """:483-549"""
    ns, labels_text = group
    labels = _labels(labels_text)
    if labels is None or not svc_egress:
        return None
    return {
        "apiVersion": "crd.antrea.io/v1alpha1",
        "kind": "ClusterNetworkPolicy",
        "metadata": {"name": "recommend-svc-allow-acnp"},
        "spec": {
            "tier": "Application",
            "priority": PRIORITY,
            "appliedTo": [_cluster_applied_to(ns, labels)],
            "egress": [
                {"action": "Allow",
                 "to": [{"group": cluster_group_name(*service_of(svc))}],
                 "ports": _antrea_ports(port, proto)}
                for svc, port, proto in svc_egress],
        },
    }


def reject_acnp(group: Optional[Group]) -> Optional[Dict]:
    """:552-618; `None` is the whole cluster (`anp-deny-all`)."""
    if group is None:
        name = "recommend-reject-all-acnp"
        applied = {"podSelector": {}, "namespaceSelector": {}}
    else:
        name = "recommend-reject-acnp"
        labels = _labels(group[1])
        if labels is None:
            return None
        applied = _cluster_applied_to(group[0], labels)
    return {
        "apiVersion": "crd.antrea.io/v1alpha1",
        "kind": "ClusterNetworkPolicy",
        "metadata": {"name": name},
        "spec": {
            "tier": "Baseline",
            "priority": PRIORITY,
            "appliedTo": [applied],
            "egress": [{"action": "Reject", "to": [{"podSelector": {}}]}],
            "ingress": [{"action": "Reject",
                         "from": [{"podSelector": {}}]}],
        },
    }


def namespace_allow_acnp(ns: str) -> Dict:
    """:737-782"""
    return {
        "apiVersion": "crd.antrea.io/v1alpha1",
        "kind": "ClusterNetworkPolicy",
        "metadata": {"name": f"recommend-allow-acnp-{ns}"},
        "spec": {
            "tier": "Platform",
            "priority": PRIORITY,
            "appliedTo": [{"namespaceSelector": {"matchLabels": {
                "kubernetes.io/metadata.name": ns}}}],
            "egress": [{"action": "Allow", "to": [{"podSelector": {}}]}],
            "ingress": [{"action": "Allow",
                         "from": [{"podSelector": {}}]}],
        },
    }


# -- the job (:714-726, :880-1017) ----------------------------------------

def recommend(flows: Iterable[Flow],
              policy_type: str = "anp-deny-applied",
              to_services: bool = True,
              ns_allow_list: Sequence[str] = NAMESPACE_ALLOW_LIST
              ) -> List[Dict]:
    """The documents of `run --type initial --policy-type <policy_type>`
    over the distinct unprotected flows: the allow list's ACNPs, then
    what the flows ask for. A group, or a service, of a namespace on
    the allow list gets nothing."""
    if policy_type not in POLICY_TYPES:
        raise ValueError(f"policy type {policy_type!r}")
    k8s = policy_type == "k8s-np"
    peers = Peers(flows, k8s, to_services)
    docs: List[Optional[Dict]] = [namespace_allow_acnp(ns)
                                  for ns in ns_allow_list]
    groups = [g for g in peers.groups() if g[0] not in ns_allow_list]
    make = k8s_np if k8s else anp
    docs += [make(g, peers.ingress.get(g, set()),
                  peers.egress.get(g, set())) for g in groups]
    if k8s:
        return [d for d in docs if d]
    if not to_services:
        docs += [service_cluster_group(svc)
                 for svc in sorted(peers.services)
                 if service_of(svc)[0] not in ns_allow_list]
        docs += [service_acnp(g, rules)
                 for g, rules in sorted(peers.svc_egress.items())
                 if g[0] not in ns_allow_list]
    if policy_type == "anp-deny-applied":
        docs += [reject_acnp(g)
                 for g in sorted(set(groups) | {
                     g for g in peers.svc_egress
                     if g[0] not in ns_allow_list})]
    else:
        docs.append(reject_acnp(None))
    return [d for d in docs if d]


def policy_kind(doc: Dict) -> str:
    """The result table's `kind` of a document (antrea_crd.py:789-793)."""
    if doc["kind"] == "ClusterGroup":
        return "acg"
    if doc["kind"] == "ClusterNetworkPolicy":
        return "acnp"
    return "knp" if doc["apiVersion"].startswith("networking.k8s.io") \
        else "anp"


def canonical(doc, named: bool = False) -> str:
    """One text for a document whatever the order of its lists: every
    list sorted by its elements' own canonical text. `named`: the
    document comes from a job, so its name carries a suffix that is
    cut (`NAME_SUFFIX`, and only from a name this job gives)."""
    def walk(x):
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        if isinstance(x, list):
            return sorted((walk(v) for v in x),
                          key=lambda v: json.dumps(v, sort_keys=True))
        return x

    doc = walk(doc)
    meta = doc.get("metadata") if isinstance(doc, dict) else None
    if named and isinstance(meta, dict) \
            and isinstance(meta.get("name"), str):
        m = NAME_SUFFIX.match(meta["name"])
        if m and (m.group(1) in SUFFIXED_NAMES
                  or m.group(1).startswith("recommend-allow-acnp-")):
            meta["name"] = m.group(1)
    return json.dumps(doc, sort_keys=True)
