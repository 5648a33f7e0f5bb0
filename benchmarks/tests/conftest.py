"""Tests of the benchmark itself (`python3 -m pytest benchmarks/tests`).
They are not part of the repo's tier-1 suite (`tests/`): the ones that
drive a run start a manager on the CPU backend and take ~20 s each."""

import time

import pytest


@pytest.fixture
def rehearse():
    """Drive one cell end to end on the CPU backend: the harness's look
    for a chip is skipped (platform="cpu"), everything else is a run."""
    from benchmarks import harness, manifest, rehearsal

    def run(cell, seconds=3.0, seed=7, trace=False, **scale):
        bench = manifest.load()
        return harness.run_cell(
            cell, seed, seconds, trace, time.monotonic(), platform="cpu",
            scale={**rehearsal.scale_for(bench, bench.cell(cell)), **scale})
    return run


@pytest.fixture
def broken_producer(monkeypatch):
    """The producers send what tests/broken_client.py makes of the
    generator's blocks."""
    from benchmarks import harness

    def arm(**fault):
        import json
        monkeypatch.setenv("BENCH_TEST_FAULT", json.dumps(fault))
        monkeypatch.setattr(harness.Worker, "module",
                            "benchmarks.tests.broken_client")
    return arm


@pytest.fixture
def overlay(tmp_path):
    """A later PR's files in a directory of their own, beside copies of
    the data files that are there: `write(rel, text)` adds a file,
    `cell(traffic, **keys)` a traffic mix (a shipped one, altered) and
    a cell `overlay.cell` on the default configuration; `bench` is the
    manifest over it. What was imported from it is dropped afterwards."""
    import copy
    import json
    import os
    import shutil

    from benchmarks import extend, manifest

    base = str(tmp_path / "benchmarks")
    for sub in ("configs", "traffic", "end_to_end", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.HERE, sub),
                        os.path.join(base, sub))

    class Overlay:
        name = "overlay.cell"

        def __init__(self):
            self.base = base
            self.doc = copy.deepcopy(manifest.load().doc)
            self.bench = manifest.Bench(self.doc, base=base)

        def write(self, rel, text):
            path = os.path.join(base, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                f.write(text)
            return path

        def cell(self, like="ingest-saturate", **keys):
            with open(os.path.join(base, "traffic", like + ".json")) as f:
                traffic = json.load(f)
            for key, value in keys.items():
                if isinstance(value, dict):
                    traffic.setdefault(key, {}).update(value)
                else:
                    traffic[key] = value
            self.write("traffic/overlay-mix.json", json.dumps(traffic))
            was = next(w["name"] for w in self.doc["workloads"]
                       if w["traffic"] == like)
            self.doc["workloads"].append({
                "name": self.name, "config": "theia-default-1x1",
                "traffic": "overlay-mix", "chips": 1, "why": "overlay"})
            for m in self.doc["end_to_end"] + self.doc["per_layer"]:
                if was in m.get("workloads", []):
                    m["workloads"].append(self.name)
            return self.name

    yield Overlay()
    extend.forget(base)
