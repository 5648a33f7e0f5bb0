"""Tests for theia_tpu.utils: validation, logging ring buffer, env.

Reference behaviors: ParseRecommendationName (pkg/util/utils.go),
K8s quantity validation on job resource fields
(pkg/controller/networkpolicyrecommendation/controller.go:586-608),
klog -v levels, POD_NAMESPACE default (pkg/util/env/env.go).
"""

import io
import json
import tarfile
import uuid

import pytest

from theia_tpu.utils import (
    clear_logs,
    dump_logs,
    get_logger,
    get_theia_namespace,
    parse_job_name,
    parse_k8s_quantity,
    set_verbosity,
    split_job_name,
    validate_agg_flow,
    validate_algo,
    validate_k8s_quantity,
    validate_policy_type,
)


def test_parse_job_name_roundtrip():
    u = str(uuid.uuid4())
    assert parse_job_name(f"pr-{u}", "pr-") == u
    assert split_job_name(f"tad-{u}") == ("tad", u)
    with pytest.raises(ValueError):
        parse_job_name("pr-not-a-uuid", "pr-")
    with pytest.raises(ValueError):
        parse_job_name(f"tad-{u}", "pr-")
    with pytest.raises(ValueError):
        split_job_name("job-123")


@pytest.mark.parametrize("text,value", [
    ("200m", 0.2),
    ("512M", 512e6),
    ("1Gi", 2.0 ** 30),
    ("1.5", 1.5),
    ("2e3", 2000.0),
    ("100Ki", 102400.0),
    ("12E", 12e18),
])
def test_k8s_quantity_parse(text, value):
    assert parse_k8s_quantity(text) == pytest.approx(value)


@pytest.mark.parametrize("bad", ["", "abc", "1GiB", "--1", "1 Gi", "Mi"])
def test_k8s_quantity_rejects(bad):
    with pytest.raises(ValueError):
        parse_k8s_quantity(bad)
    with pytest.raises(ValueError):
        validate_k8s_quantity(bad, "--driver-memory")


def test_enum_validators():
    assert validate_algo("EWMA") == "EWMA"
    assert validate_agg_flow("pod") == "pod"
    assert validate_policy_type("k8s-np") == "k8s-np"
    for fn, bad in ((validate_algo, "KMEANS"),
                    (validate_agg_flow, "node"),
                    (validate_policy_type, "bogus")):
        with pytest.raises(ValueError):
            fn(bad)


def test_logging_ring_and_verbosity(capsys):
    clear_logs()
    set_verbosity(0)
    log = get_logger("t")
    log.info("always")
    log.v(2).info("debug-only %d", 7)
    text = dump_logs()
    assert "always" in text and "debug-only" not in text
    set_verbosity(2)
    log.v(2).info("debug-only %d", 7)
    assert "debug-only 7" in dump_logs()
    set_verbosity(0)
    clear_logs()


def test_env_namespace_default(monkeypatch):
    monkeypatch.delenv("POD_NAMESPACE", raising=False)
    assert get_theia_namespace() == "flow-visibility"
    monkeypatch.setenv("POD_NAMESPACE", "custom-ns")
    assert get_theia_namespace() == "custom-ns"


def test_support_bundle_includes_manager_logs():
    """The bundle tar must carry logs/theia-manager.log with recent
    lines (ManagerDumper parity, pkg/support/dump.go)."""
    from theia_tpu.manager.api import SupportBundleManager
    from theia_tpu.manager.jobs import JobController
    from theia_tpu.manager.stats import StatsProvider
    from theia_tpu.store import FlowDatabase

    clear_logs()
    get_logger("t").info("bundle-me")
    db = FlowDatabase()
    controller = JobController(db, workers=1)
    try:
        bundles = SupportBundleManager(
            controller, StatsProvider(db, capacity_bytes=1 << 20))
        bundles.create()
        for _ in range(100):
            if bundles.status == "collected":
                break
            import time
            time.sleep(0.05)
        assert bundles.status == "collected"
        with tarfile.open(fileobj=io.BytesIO(bundles.data()),
                          mode="r:gz") as tar:
            names = tar.getnames()
            assert "logs/theia-manager.log" in names
            raw = tar.extractfile("logs/theia-manager.log").read()
            assert b"bundle-me" in raw
            jobs = json.loads(tar.extractfile("jobs.json").read())
            assert jobs == []
    finally:
        controller.shutdown()
        clear_logs()


def test_retain_freed_memory_sets_the_allocators_thresholds():
    """On glibc the three `mallopt` calls are taken, a second time
    too; the pad is over one 64 MiB heap, which is what keeps a
    thread's heaps mapped, and the threshold is glibc's largest."""
    import ctypes

    from theia_tpu.utils import alloc

    if not hasattr(ctypes.CDLL(None), "mallopt"):
        pytest.skip("the C library has no mallopt")
    assert alloc.retain_freed_memory() is True
    assert alloc.retain_freed_memory() is True
    assert alloc.TOP_PAD > 64 << 20
    assert alloc.MMAP_THRESHOLD == 32 << 20


@pytest.mark.parametrize("library", ["missing", "no_mallopt", "refuses"])
def test_retain_freed_memory_says_when_it_could_not(monkeypatch, library):
    """No C library to open, one without `mallopt`, or one that
    refuses a value: False, and nothing raised."""
    from theia_tpu.utils import alloc

    class Refuses:
        def __init__(self):
            self.argtypes = self.restype = None

        def __call__(self, param, value):
            return 0

    def cdll(name):
        if library == "missing":
            raise OSError("no such library")
        lib = type("Lib", (), {})()
        if library == "refuses":
            lib.mallopt = Refuses()
        return lib

    monkeypatch.setattr(alloc.ctypes, "CDLL", cdll)
    assert alloc.retain_freed_memory() is False
