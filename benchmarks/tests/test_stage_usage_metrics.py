"""The readers of what a stage's thread did with its wall (CPU,
faults, the collector's pauses, `tensorize` by its parts): each file
resolves, gives a number from a recorded `/metrics` pair of a manager
that exports the series, and nothing from a pair of its parent, which
does not. The pairs (`data/*.txt`: `_sum`, `_count` and counter lines
of the families the readers name) were recorded on the CPU backend
around three blocks, one EWMA job with its answer, two panels and one
snapshot; a CPU run gives no speed, so only the arithmetic is held."""

import os

import pytest

from benchmarks import extend, manifest, prom
from benchmarks.reduce import counter_rise_per

BENCH = manifest.load()
HERE = os.path.dirname(os.path.abspath(__file__))
#: the metrics this file is about: every per-layer reader that names
#: a CPU histogram, a fault counter, the collector or a tensorize part
NEW = sorted(
    m["name"] for m in BENCH.doc["per_layer"]
    if any(mark in str(BENCH.reader("per_layer", m["name"]))
           for mark in ("_cpu_seconds", "_minor_faults_total",
                        "theia_gc_", 'stage="tensorize",part'))
    # PR 26's two, which the parent exports too
    and m["name"] not in ("detector_cpu_ms_per_block",
                          "request_cpu_ms_per_block"))


def _pair(side):
    def read(when):
        with open(os.path.join(HERE, "data", f"{side}_{when}.txt")) as f:
            return prom.parse(f.read())
    return {"metrics_before": read("before"),
            "metrics_after": read("after")}


def _value(name, data):
    reader = BENCH.reader("per_layer", name)
    return extend.resolve("reduction", reader["reduce"])(data, reader)


def test_the_new_readers_are_the_ones_the_issue_lists():
    assert len(NEW) == 24
    assert sum(n.startswith("detector.") for n in NEW) == 9
    assert sum(n.startswith("job.") for n in NEW) == 11
    assert {n for n in NEW if n[:4] in ("dash", "ckpt")} == {
        "dash.scan_cpu_ms", "dash.aggregate_cpu_ms", "dash.scan_faults",
        "ckpt.hold_faults"}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_a_number_here_and_nothing_at_the_parent(name):
    entry = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
    reader = BENCH.reader("per_layer", name)
    # in its wall twin's layer, moving what the twin moves
    assert (reader["layer"], reader["moves"], reader["source"]) == (
        entry["layer"], entry["moves"], entry["source"])
    value = _value(name, _pair("change"))
    assert isinstance(value, float) and value >= 0.0
    assert _value(name, _pair("parent")) is None
    # CPU of a stage is at most its wall plus the kernel's tick, to
    # which one run's CPU is good; fault counts are whole
    if name.endswith("_cpu_ms") or name.endswith("_cpu_ms_per_block"):
        twin = name.replace("_cpu_ms", "_ms")
        assert value <= _value(twin, _pair("change")) + 12.0
    elif entry["unit"] == "faults":
        blocks, jobs, panels = 3, 1, 2
        assert (value * blocks * jobs * panels) % 1 == pytest.approx(
            0, abs=1e-9)


def test_tensorize_parts_cover_the_stage_in_the_recorded_pair():
    data = _pair("change")
    parts = sum(_value(f"job.tensorize_{p}_ms", data)
                for p in ("keys", "group", "decode"))
    stage = _value("job.tensorize_ms", data)
    assert 0.7 * stage <= parts <= stage


def test_counter_rise_per_sums_a_bare_name_and_scales():
    before = {'a_total{g="0"}': 1.0, 'a_total{g="1"}': 2.0, "n": 4.0,
              'h_count{p="x"}': 1.0, "a_total_other": 100.0}
    after = {'a_total{g="0"}': 2.0, 'a_total{g="1"}': 6.0, "n": 6.0,
             'h_count{p="x"}': 2.0, 'h_count{p="y"}': 4.0,
             "a_total_other": 900.0}
    data = {"metrics_before": before, "metrics_after": after}
    reduce = counter_rise_per.reduce
    assert reduce(data, {"series": "a_total", "per": "n"}) == 2.5
    assert reduce(data, {"series": 'a_total{g="1"}', "per": "h_count",
                         "scale": 1000.0}) == 800.0
    assert reduce(data, {"series": "a_total",
                         "per": 'h_count{p="x"}'}) == 5.0
    # a series nobody exports, a divisor that did not rise: nothing
    assert reduce(data, {"series": "b_total", "per": "n"}) is None
    assert reduce(data, {"series": "a_total", "per": "m"}) is None
    assert reduce({"metrics_before": after, "metrics_after": after},
                  {"series": "a_total", "per": "n"}) is None
