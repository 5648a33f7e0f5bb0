"""Doc-drift gate: the metrics catalogue (docs/metrics.md) and the
process registry must name exactly the same metrics, and the doc's
environment-knob table must match the knobs the code reads (for the
env-var families this doc owns).

Direction 1 (undocumented): every metric the package registers — at
import time across every module, plus the scrape-time gauges a
fully-featured manager registers on its first /metrics render — must
have a row in docs/metrics.md. Direction 2 (stale docs): every metric
the catalogue names must actually be registered. A rename, removal,
or new metric that touches only one side fails tier-1 instead of
silently drifting. The same two directions hold for the observability
env vars (THEIA_METRICS_*, THEIA_TRACE_*, THEIA_ALERT_*,
THEIA_QUERY_SLOW_*): referenced-in-code ⇔ documented-in-table.
"""

import importlib
import pathlib
import re
import urllib.request

import pytest

from theia_tpu.obs import metrics

pytestmark = pytest.mark.obs

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE_DIR = REPO / "theia_tpu"
METRICS_MD = REPO / "docs" / "metrics.md"

#: docs table rows: `| `theia_foo_total` | counter | ... |`
_DOC_ROW = re.compile(r"^\|\s*`(theia_[a-z0-9_]+)`", re.MULTILINE)


def _all_modules():
    for path in sorted(PACKAGE_DIR.rglob("*.py")):
        rel = path.relative_to(REPO)
        parts = list(rel.with_suffix("").parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        name = ".".join(parts)
        # entrypoint modules parse argv / start servers on import
        # guards only — importable, but nothing registers there that
        # their siblings don't already
        if name.endswith("__main__"):
            continue
        yield name


def _register_scrape_time_gauges(monkeypatch, tmp_path):
    """Spin one maximal manager (parts engine, replicated store,
    retention on, 2-node cluster peer list) and render /metrics once:
    the gauges that register at scrape time — store size, job queue,
    replicas, parts tiers, retention usage — join the registry."""
    monkeypatch.setenv("THEIA_STORE_ENGINE", "parts")
    monkeypatch.setenv("THEIA_STORE_MEMTABLE_ROWS", "128")
    monkeypatch.setenv("THEIA_RETENTION_INTERVAL", "3600")
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.manager.api import TheiaManagerServer
    from theia_tpu.store import ReplicatedFlowDatabase
    db = ReplicatedFlowDatabase(replicas=1)
    db.insert_flows(generate_flows(SynthConfig(
        n_series=40, points_per_series=10, anomaly_fraction=0.0,
        seed=1)))
    srv = TheiaManagerServer(db, port=0)
    srv.start_background()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=30) as r:
            assert r.status == 200
    finally:
        srv.shutdown()


def test_metrics_docs_in_sync(monkeypatch, tmp_path):
    for name in _all_modules():
        try:
            importlib.import_module(name)
        except ModuleNotFoundError as e:
            # optional third-party dep absent in this environment
            # (e.g. manager/certs.py needs `cryptography`); a module
            # that cannot import cannot register metrics either
            if e.name and e.name.startswith("theia_tpu"):
                raise

    _register_scrape_time_gauges(monkeypatch, tmp_path)
    registered = {m.name for m in metrics.REGISTRY.collect()
                  if m.name.startswith("theia_")}
    documented = set(_DOC_ROW.findall(METRICS_MD.read_text()))
    undocumented = sorted(registered - documented)
    stale = sorted(documented - registered)
    assert not undocumented, (
        f"metrics registered but missing from docs/metrics.md: "
        f"{undocumented}")
    assert not stale, (
        f"docs/metrics.md names metrics nothing registers "
        f"(renamed or removed?): {stale}")


#: env-var families whose single source of documentation is
#: docs/metrics.md's knob table (other THEIA_* families are owned by
#: other docs — cluster.md, queries.md, ingest.md)
_ENV_PREFIXES = ("THEIA_METRICS_", "THEIA_TRACE_", "THEIA_ALERT_",
                 "THEIA_QUERY_SLOW_")

_ENV_REF = re.compile(r"THEIA_[A-Z0-9_]+")

#: knob-table rows: `| `THEIA_FOO` | default | meaning |`
_ENV_ROW = re.compile(r"^\|\s*`(THEIA_[A-Z0-9_]+)`", re.MULTILINE)


def test_metrics_env_knobs_in_sync():
    referenced = set()
    for path in sorted(PACKAGE_DIR.rglob("*.py")):
        for name in _ENV_REF.findall(path.read_text()):
            if name.startswith(_ENV_PREFIXES):
                referenced.add(name)
    documented = {name for name in
                  _ENV_ROW.findall(METRICS_MD.read_text())
                  if name.startswith(_ENV_PREFIXES)}
    undocumented = sorted(referenced - documented)
    stale = sorted(documented - referenced)
    assert not undocumented, (
        f"observability env vars read by code but missing from "
        f"docs/metrics.md's knob table: {undocumented}")
    assert not stale, (
        f"docs/metrics.md documents observability env vars nothing "
        f"reads (renamed or removed?): {stale}")


def test_all_theia_env_knobs_in_sync():
    """EVERY ``THEIA_*`` environment knob, both directions, driven by
    the analysis lint pass's AST extraction (docstrings and comments
    don't count as reads; knob names passed as data do — they are
    read through a variable later):

    1. every knob the code reads has a ``| `THEIA_X` |`` knob-table
       row in SOME docs/*.md — an operator can discover it;
    2. every knob any docs table documents is actually read — the doc
       cannot describe a removed or renamed knob.

    The per-family gate above keeps metrics.md the single home for
    the observability families; this one closes the other ~70 knobs
    that previously had no gate at all."""
    from theia_tpu.analysis.lint import (
        documented_env_knobs,
        extract_env_reads,
    )
    referenced = set(extract_env_reads(str(PACKAGE_DIR)))
    documented = set(documented_env_knobs(str(REPO / "docs")))
    undocumented = sorted(referenced - documented)
    stale = sorted(documented - referenced)
    assert not undocumented, (
        f"THEIA_* env vars read by code (theia_tpu/) with "
        f"no knob-table row in any docs/*.md: {undocumented}")
    assert not stale, (
        f"docs/*.md knob tables document THEIA_* vars nothing reads "
        f"(renamed or removed?): {stale}")


#: the count of ``THEIA_*`` names the package reads; it may fall,
#: never rise (PR 31 left 70, PR 47 67)
MAX_ENV_KNOBS = 67


def test_env_knob_count_does_not_grow():
    from theia_tpu.analysis.lint import extract_env_reads
    reads = extract_env_reads(str(PACKAGE_DIR))
    assert len(reads) <= MAX_ENV_KNOBS, (
        f"{len(reads)} THEIA_* names are read, {MAX_ENV_KNOBS} were: a "
        f"new option needs two callers that exist (a benchmark "
        f"configuration, a deploy manifest, chip_smoke.py: not tests) "
        f"with different values, or it is a constant. Lower "
        f"MAX_ENV_KNOBS when the count falls.")


#: a backticked path under one of the repo's directories, or a
#: root-level ``*.py``, optionally with a ``:line`` or ``::test`` tail
_DOC_PATH = re.compile(
    r"`((?:theia_tpu|tests|native|deploy|benchmarks)/[A-Za-z0-9_./-]+"
    r"|[A-Za-z0-9_]+\.py)(?::[^`]*)?`")


@pytest.mark.parametrize(
    "doc", [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))],
    ids=lambda p: p.name)
def test_doc_paths_exist(doc):
    """Every file a doc names in backticks is there: a doc cannot
    point at a module that was deleted or moved."""
    missing = sorted({m for m in _DOC_PATH.findall(doc.read_text())
                      if "*" not in m and "<" not in m
                      and not (REPO / m).exists()})
    assert not missing, f"{doc.name} names files that do not exist: " \
                        f"{missing}"
