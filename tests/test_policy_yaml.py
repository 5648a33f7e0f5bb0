"""`policy_gen.dump_yaml` writes a policy document's YAML itself and
gives `yaml.dump(doc)`'s text byte for byte, or declines the whole
document and calls `yaml.dump(doc)`: identity on both exits, for every
generator, for every scalar PyYAML would quote, fold or resolve to
another type, and over a seeded walk of nested documents; the job's
result rows are string for string what plain `yaml.dump` gives; the
counter `theia_job_npr_documents_direct_total` says how often the
first exit was taken."""

import collections
import copy
import datetime
import json
import random
from unittest import mock

import pytest
import yaml

from theia_tpu.analytics import policy_gen, run_npr
from theia_tpu.data.synth import SynthConfig, generate_flows
from theia_tpu.obs import metrics
from theia_tpu.runner.progress import NPR_STAGES, JobProgress
from theia_tpu.store import FlowDatabase

APPLIED = 'ns-a#{"app": "web"}'
POD_IN = 'ns-b#{"app": "client", "tier": "front-end_1"}#8080#TCP'
POD_OUT = 'ns-c#{"app": "db"}#5432#TCP'
EXTERNAL = "203.0.113.9#443#UDP"
SERVICE = "ns-c#svc-db"
SVC_PORT = "ns-c/svc-db:pg#5432#TCP"

# (generator, arguments) over inputs whose every scalar is plain
PLAIN = {
    "k8s_np": (policy_gen.generate_k8s_np,
               (APPLIED, [POD_IN], [POD_OUT, EXTERNAL])),
    "k8s_np_no_egress": (policy_gen.generate_k8s_np,
                         (APPLIED, [POD_IN], [])),
    "k8s_np_no_ingress": (policy_gen.generate_k8s_np,
                          (APPLIED, [], [EXTERNAL])),
    "anp": (policy_gen.generate_anp,
            (APPLIED, [POD_IN], [POD_OUT, EXTERNAL, SERVICE])),
    "anp_no_labels": (policy_gen.generate_anp,
                      ("ns-a#{}", [POD_IN], [])),
    "svc_cg": (policy_gen.generate_svc_cg, ("ns-c/svc-db:pg",)),
    "svc_acnp": (policy_gen.generate_svc_acnp, (APPLIED, [SVC_PORT])),
    "reject_acnp": (policy_gen.generate_reject_acnp, (APPLIED,)),
    "reject_all_acnp": (policy_gen.generate_reject_acnp, ("",)),
    "ns_allow_acnp": (policy_gen.generate_ns_allow_acnp, ("kube-system",)),
}

# strings PyYAML quotes, resolves to another type or lays out otherwise
HOSTILE = ["", "true", "No", "null", "~", "8080", "0x1f", "1e3", "1.20",
           "12:30", "a: b", "a #b", "-x", "x ", "é", "k" * 200]
STAND_IN = "stand-in"


def emitted(generator, *args):
    """(text, the document it was made from, documents written
    directly) of one generator call."""
    docs = []
    real = policy_gen.dump_yaml

    def spy(doc):
        docs.append(copy.deepcopy(doc))
        return real(doc)

    with mock.patch.object(policy_gen, "dump_yaml", spy), \
            policy_gen.count_direct() as direct:
        text = generator(*args)
    assert len(docs) == 1
    return text, docs[0], direct[0]


def dumped(doc):
    """(text, documents written directly) of `dump_yaml(doc)`."""
    with policy_gen.count_direct() as direct:
        return policy_gen.dump_yaml(doc), direct[0]


def renamed(node, old, new):
    """`node` with the string `old` replaced by `new`, keys too."""
    if isinstance(node, dict):
        return {renamed(k, old, new): renamed(v, old, new)
                for k, v in node.items()}
    if isinstance(node, list):
        return [renamed(v, old, new) for v in node]
    return new if node == old else node


# -- (a) every generator, plain inputs: the direct exit --------------------

@pytest.mark.parametrize("case", sorted(PLAIN))
def test_a_generator_over_plain_inputs_writes_yaml_dumps_text(case):
    generator, args = PLAIN[case]
    text, doc, direct = emitted(generator, *args)
    assert text == yaml.dump(doc)
    assert direct == 1
    assert yaml.safe_load(text) == doc


# -- (b) a hostile scalar anywhere: the fallback, the same text ------------

def anp_with(position, scalar):
    """`generate_anp`'s arguments with `scalar` at `position`."""
    if position == "label_value":
        return (f"ns-a#{json.dumps({'app': scalar})}", [POD_IN], [SERVICE])
    if position == "label_key":
        return (f"ns-a#{json.dumps({scalar: 'web'})}", [POD_IN], [SERVICE])
    if position == "namespace":
        return (f'{scalar}#{{"app": "web"}}', [POD_IN], [SERVICE])
    assert position == "service_name"
    return (APPLIED, [POD_IN], [f"ns-c#{scalar}"])


@pytest.mark.parametrize("position", ["label_value", "label_key",
                                      "namespace", "service_name"])
@pytest.mark.parametrize("scalar", HOSTILE, ids=[
    repr(s) if len(s) < 20 else "200-characters" for s in HOSTILE])
def test_a_hostile_scalar_sends_the_document_to_pyyaml(scalar, position):
    if policy_gen.ROW_DELIMITER in scalar:
        # a peer tuple cannot carry the delimiter: the generator's
        # document, the scalar put where a stand-in stood
        _, doc, direct = emitted(policy_gen.generate_anp,
                                 *anp_with(position, STAND_IN))
        assert direct == 1
        doc = renamed(doc, STAND_IN, scalar)
        text, direct = dumped(doc)
    else:
        text, doc, direct = emitted(policy_gen.generate_anp,
                                    *anp_with(position, scalar))
    assert scalar in json.dumps(doc, ensure_ascii=False)
    assert direct == 0
    assert text == yaml.dump(doc)
    assert yaml.safe_load(text) == doc


@pytest.mark.parametrize("generator", [policy_gen.generate_anp,
                                       policy_gen.generate_k8s_np])
def test_an_ipv6_peer_sends_the_document_to_pyyaml(generator):
    text, doc, direct = emitted(generator, APPLIED, [POD_IN],
                                ["fd00::1#443#TCP"])
    assert "fd00::1/128" in text
    assert direct == 0
    assert text == yaml.dump(doc)


@pytest.mark.parametrize("doc", [
    {"a": True}, {"a": 1.5}, {"a": None}, {1: "a"}, {"a": 1, 2: "b"},
    {"a": ("b",)}, {"a": collections.OrderedDict(b="c")}, {"a": b"b"},
    {}, [], ["a"], "a", 7,
], ids=repr)
def test_a_node_of_another_type_sends_the_document_to_pyyaml(doc):
    text, direct = dumped(doc)
    assert direct == 0
    assert text == yaml.dump(doc)


def test_a_container_met_twice_sends_the_document_to_pyyaml():
    """PyYAML anchors it (`&id001`) and refers to it (`*id001`)."""
    shared = {"matchLabels": {"app": "web"}}
    doc = {"from": [{"podSelector": shared}], "to": [{"podSelector": shared}]}
    text, direct = dumped(doc)
    assert direct == 0 and "&id001" in text and text == yaml.dump(doc)
    empty = []
    text, direct = dumped({"egress": empty, "ingress": empty})
    assert direct == 0 and text == yaml.dump({"egress": empty,
                                              "ingress": empty})
    loop = {"kind": "loop"}
    loop["self"] = [loop]
    text, direct = dumped(loop)
    assert direct == 0 and text == yaml.dump(loop)


# -- (c) the empty collections ---------------------------------------------

def test_empty_collections_are_written_in_flow_style():
    generator, args = PLAIN["k8s_np_no_egress"]
    text, doc, direct = emitted(generator, *args)
    assert direct == 1 and "  egress: []\n" in text
    assert doc["spec"]["egress"] == []
    text, doc, direct = emitted(policy_gen.generate_reject_acnp, "")
    assert direct == 1 and text == yaml.dump(doc)
    assert "  - namespaceSelector: {}\n    podSelector: {}\n" in text
    assert "    - podSelector: {}\n" in text


# -- (d) a seeded walk over nested documents -------------------------------

PLAIN_POOL = ["app", "web", "ns-3", "app-3-17", "TCP", "Allow",
              "crd.antrea.io/v1alpha1", "kubernetes.io/metadata.name",
              "203.0.113.9/32", "a_b.c-d/e", "Yes-man", "nullable", "x" * 100,
              0, 5, 8080, -1, 2 ** 70]
HOSTILE_POOL = HOSTILE + [
    "y", "N", "ON", "off", "NULL", "True", "1", "-5", "+1", "0o7", "1_000",
    ".5", "1.", ".inf", ".NaN", "2001-12-14", "<<", "=", "fd00::1/128",
    "999.1.1.1/32 ", "1.2.3/24", "a\nb", "a\tb", " a", "a:", ":a", "a,b",
    "[a]", "{a}", "&a", "*a", "!a", "|", ">", "'a'", '"a"', "%a", "@a",
    "`a`", "?", "? a", "-", "- a", "---", "...", "a" * 101, "a" * 130,
    "\u212aelvin", "\u017f", "\u0663", "nul\x00", "\ufeffa", "a\u2028b",
    True, False, None, 1.5]


def walk(rng, pool, depth=0):
    """A document of dicts and lists over `pool`; a dict's keys are
    the pool's strings."""
    roll = rng.random()
    if depth == 0 or (depth < 5 and roll < 0.45):
        keys = [k for k in rng.sample(pool, rng.randint(0 if depth else 1, 4))
                if isinstance(k, str)]
        return {k: walk(rng, pool, depth + 1) for k in keys}
    if depth < 5 and roll < 0.7:
        return [walk(rng, pool, depth + 1)
                for _ in range(rng.randint(0, 3))]
    return rng.choice(pool)


def all_plain(node):
    if isinstance(node, dict):
        return all(all_plain(k) and all_plain(v) for k, v in node.items())
    if isinstance(node, list):
        return all(map(all_plain, node))
    return node in PLAIN_POOL and type(node) is not bool


@pytest.mark.parametrize("seed", range(8))
def test_a_walk_of_nested_documents_is_written_as_yaml_dump_writes_it(seed):
    rng = random.Random(seed)
    exits = collections.Counter()
    for i in range(120):
        # one document in three meets hostile scalars, one in fifty each
        pool = PLAIN_POOL if i % 3 else PLAIN_POOL * 3 + HOSTILE_POOL
        doc = walk(rng, pool)
        text, direct = dumped(doc)
        assert text == yaml.dump(doc), doc
        assert yaml.safe_load(text) == doc
        if all_plain(doc) and doc:
            assert direct == 1, doc
        exits[direct] += 1
    assert exits[1] > 60 and exits[0] > 10


@pytest.mark.parametrize("scalar", [s for s in HOSTILE_POOL
                                    if isinstance(s, str)], ids=ascii)
def test_every_hostile_string_declines_as_key_and_as_value(scalar):
    for doc in ({"a": scalar}, {scalar: "a"}, {"a": [scalar]},
                {"a": [{scalar: 1}]}):
        text, direct = dumped(doc)
        assert direct == 0 and text == yaml.dump(doc)


def test_the_tally_is_the_block_s_own():
    """Nothing is counted outside a block, and a block inside another
    (another job, were it ever on this thread) keeps its own."""
    doc = {"kind": "NetworkPolicy"}
    assert policy_gen.dump_yaml(doc) == "kind: NetworkPolicy\n"
    with policy_gen.count_direct() as outer:
        policy_gen.dump_yaml(doc)
        with policy_gen.count_direct() as inner:
            policy_gen.dump_yaml(doc)
            policy_gen.dump_yaml({"kind": "true"})
        policy_gen.dump_yaml(doc)
    assert (outer[0], inner[0]) == (2, 1)


# -- (e) the job's rows, and its counters ----------------------------------

NOW = datetime.datetime(2024, 1, 1, tzinfo=datetime.timezone.utc)


def fixture_rows():
    """tests/test_npr.py's end-to-end store, as rows."""
    return generate_flows(SynthConfig(
        n_series=24, points_per_series=5, seed=2)).to_rows()


def database(rows):
    db = FlowDatabase()
    db.flows.insert_rows(rows)
    return db


def result_rows(db, **kw):
    run_npr(db, recommendation_id="job", now=NOW, **kw)
    return sorted((r["kind"], r["policy"], r["type"], r["timeCreated"])
                  for r in db.recommendations.scan().to_rows())


@pytest.mark.parametrize("kw", [
    dict(recommendation_type="initial", option=1),
    dict(recommendation_type="initial", option=2),
    dict(recommendation_type="initial", option=3),
    dict(recommendation_type="initial", option=1, to_services=False),
    dict(recommendation_type="initial", option=1, rm_labels=False),
    dict(recommendation_type="subsequent", option=1),
], ids=lambda kw: "-".join(f"{v}" for v in kw.values()))
def test_run_npr_gives_the_rows_plain_yaml_dump_gives(kw):
    rows = fixture_rows()
    got = result_rows(database(rows), **kw)
    with mock.patch.object(policy_gen, "dump_yaml", yaml.dump):
        want = result_rows(database(rows), **kw)
    assert got == want and len(got) > 1


def counters():
    get = metrics.REGISTRY.get
    return (get("theia_job_npr_documents_direct_total").value(),
            sum(get("theia_job_npr_policies_total").labels(kind=k).value()
                for k in ("anp", "acnp", "acg", "knp")))


def job(rows):
    """(direct's rise, policies' rise, the documents) of one job."""
    db = database(rows)
    direct, policies = counters()
    run_npr(db, "initial", option=1, rm_labels=False,
            progress=JobProgress("job", NPR_STAGES, kind="npr"))
    after = counters()
    docs = [r["policy"] for r in db.recommendations.scan().to_rows()]
    return after[0] - direct, after[1] - policies, docs


def test_the_counter_says_how_many_documents_were_written_directly():
    rows = fixture_rows()
    direct, policies, docs = job(rows)
    assert direct == policies == len(docs) > 3

    label = rows[0]["sourcePodLabels"]
    assert label == '{"app": "app-3-5"}'
    for row in rows:
        for col in ("sourcePodLabels", "destinationPodLabels"):
            if row[col] == label:
                row[col] = '{"app": "true"}'
    hostile_direct, hostile_policies, hostile_docs = job(rows)
    carrying = [d for d in hostile_docs if "app: 'true'" in d]
    # its own ANP and reject ACNP, and each peer's ANP that names it
    assert len(carrying) >= 3
    assert hostile_policies == policies == len(hostile_docs)
    assert hostile_direct == direct - len(carrying)
    for text in hostile_docs:
        assert text == yaml.dump(yaml.safe_load(text))
