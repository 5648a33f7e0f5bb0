"""The check is sharp: a sound run is correct, and each kind of fault
the contract names turns `correct` false — with the timed path broken
underneath (what is sent, or what comes back, altered where it enters
the harness), and with the control (the reference in bfloat16) in the
program's place. The faults live here, as wrappers around the worker's
entry and the harness's readers; the timed path carries no hook."""

import json

import pytest

from benchmarks import control, harness, manifest

SAT = "default.ingest-saturate"
TAD = "parts-fused.tad-ewma"
DASH = "default.dashboards-retained"


def failed_checks(capsys):
    return [ln.split()[1].rstrip(":") for ln in
            capsys.readouterr().out.splitlines()
            if ln.startswith("check ") and "FAILED" in ln]


def alter_results(monkeypatch, alter):
    """What a worker hands back goes through `alter(doc)` first."""
    result = harness.Worker.result

    def altered(self):
        doc = result(self)
        alter(doc)
        return doc
    monkeypatch.setattr(harness.Worker, "result", altered)


@pytest.mark.parametrize("cell", [SAT, DASH])
def test_sound_run_is_correct(rehearse, capsys, cell):
    out = rehearse(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert failed_checks(capsys) == []


def test_one_acked_row_perturbed(rehearse, capsys, broken_producer):
    """One row of one acked block carries one octet more than the
    generator says: exactly-once / readable-rows comparison fails."""
    broken_producer(octet_block=2)
    out = rehearse(SAT)
    assert out["correct"] is False
    assert failed_checks(capsys) == ["store_octets_gap"]


def test_bf16_rounded_detector_input(rehearse, capsys, broken_producer):
    """The detector is fed throughput rounded to bfloat16 (what a
    lower-precision scoring path would see) all through the run: the
    probe blocks' alert counts differ from the reference's. (More
    probe blocks than the cell's own: a tiny block has 256 points.)"""
    broken_producer(bf16_throughput=True)
    out = rehearse(SAT, probe_blocks=64)
    assert out["correct"] is False
    failed = failed_checks(capsys)
    assert "alert_probe_block_gap" in failed
    # rounding moves the octets too: the store comparison sees it as well
    assert "store_octets_gap" in failed


def test_probe_counters_unread(rehearse, capsys, monkeypatch):
    """A probe block whose counters could not be read is missing, not
    silently skipped."""
    def alter(doc):
        for r in doc.get("records", []):
            r.pop("counters_read", None)
    alter_results(monkeypatch, alter)
    out = rehearse(SAT)
    assert out["correct"] is False
    assert "probe_blocks_missing" in failed_checks(capsys)


def test_job_answer_altered(rehearse, capsys, monkeypatch):
    """Five result rows of the job's answer are lost on the way."""
    def alter(doc):
        if doc.get("last_result"):
            answer = json.loads(doc["last_result"])
            answer["stats"] = answer["stats"][5:]
            doc["last_result"] = json.dumps(answer)
    alter_results(monkeypatch, alter)
    out = rehearse(TAD)
    assert out["correct"] is False
    assert failed_checks(capsys) == ["tad_decision_mismatch"]


def test_one_panel_sum_perturbed(rehearse, capsys, monkeypatch):
    """One link of one panel's answer carries one octet more."""
    def alter(doc):
        body = doc.get("bodies", {}).get("pod_to_pod")
        if body:
            answer = json.loads(body)
            answer["data"]["links"][0]["value"] += 1
            doc["bodies"]["pod_to_pod"] = json.dumps(answer)
    alter_results(monkeypatch, alter)
    out = rehearse(DASH)
    assert out["correct"] is False
    assert failed_checks(capsys) == ["panels_differ_from_reference"]


def test_query_answer_altered(rehearse, capsys, monkeypatch):
    """The manager's /query answer reads one octet more than was sent."""
    read = harness.Manager.json

    def altered(self, path, doc=None, timeout=120.0):
        out = read(self, path, doc, timeout)
        if path.startswith("/query"):
            out["rows"][0]["sum(octetDeltaCount)"] += 1
        return out
    monkeypatch.setattr(harness.Manager, "json", altered)
    out = rehearse(TAD)
    assert out["correct"] is False
    assert failed_checks(capsys) == ["store_octets_gap"]


@pytest.mark.parametrize("cell,blocks", [(SAT, 16), (TAD, 8), (DASH, 8)])
def test_control_is_not_correct(cell, blocks):
    """The reference in the nearest precision below float32 fails at
    least one of the cell's tolerances (a test-sized copy of
    `python3 -m benchmarks.control`, which runs at the cell's size)."""
    bench = manifest.load()
    traffic = bench.traffic(bench.cell(cell)["traffic"])
    for seed in (1, 2, 3):
        nums = control.control_numbers(traffic, seed, blocks)
        assert any(v > traffic["limits"][k] for k, v in nums.items()), nums
        sound = control.control_numbers(traffic, seed, blocks, "f64")
        assert all(v == 0 for v in sound.values())
