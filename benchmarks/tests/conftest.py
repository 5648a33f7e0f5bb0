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
