"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host-platform virtual devices (the driver separately
dry-run-compiles the multi-chip path via __graft_entry__.dryrun_multichip).
Environment variables must be set before the first jax import.
"""

import os

# Arm the runtime lockdep witness for the WHOLE suite (must happen
# before theia_tpu imports — lock wrapping is decided at creation):
# every test run doubles as a deadlock hunt. A session-scoped fixture
# below asserts zero observed lock-order inversions at teardown.
# THEIA_LOCKDEP=0 in the environment opts a run out.
os.environ.setdefault("THEIA_LOCKDEP", "1")

# Force CPU even if the ambient environment points JAX at an accelerator:
# tests validate numerics in float64 (golden comparisons) and sharding on
# 8 virtual devices, neither of which wants the single real chip.
# THEIA_TEST_DEVICE=1 opts OUT of the forcing so the `device`-marked
# hardware tests can actually reach the chip (run them selected:
# `THEIA_TEST_DEVICE=1 pytest -m device`); everything else in the suite
# assumes the CPU/x64 configuration and is not supported in that mode.
_device_mode = os.environ.get("THEIA_TEST_DEVICE") == "1"
if not _device_mode:
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _device_mode:
    jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Auto-skip `device`-marked tests when no accelerator backs JAX:
    tier-1 runs with JAX_PLATFORMS=cpu (forced above), so accelerator
    parity tests never flake CI and still run on real hardware."""
    if jax.default_backend() != "cpu":
        return
    skip = pytest.mark.skip(
        reason="requires a real accelerator (device marker; "
               "JAX is on the cpu backend)")
    for item in items:
        if "device" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session", autouse=True)
def _lockdep_zero_inversions():
    """The suite-wide deadlock hunt: every lock in the package runs
    witnessed (THEIA_LOCKDEP=1 above), and ANY observed lock-order
    inversion — even one that never deadlocked this run — fails the
    session at teardown. Tests that build deliberate inversions use
    lockdep.scoped() so fixtures don't trip this gate."""
    from theia_tpu.analysis import lockdep
    yield
    if not lockdep.enabled():
        return
    inv = lockdep.inversions()
    assert not inv, (
        "lockdep witnessed lock-order inversion(s) during the run "
        "(a deadlock waiting for the right interleaving):\n"
        + "\n".join(
            f"  cycle {' -> '.join(i['cycle'])} — new edge "
            f"{i['edge'][0]} -> {i['edge'][1]} at {i['site']} "
            f"(thread {i['thread']}); prior sites: {i['priorSites']}"
            for i in inv))


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(42)
