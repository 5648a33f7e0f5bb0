"""chip_smoke.py's plumbing on the CPU, and the process-level JAX
set-up it relies on (utils/device.py).

The smoke itself only passes on a chip (the driver runs it there);
what tier-1 can hold it to is the contract around that: the parent
stays off jax, a dry run is labelled a dry run and never a pass, no
accelerator means a non-zero exit and no result line, and the compile
cache lands where the operator put it or at one fixed place.
"""

import ast
import glob
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _env(**extra):
    """A child environment without the suite's own forcing (conftest
    pins JAX_PLATFORMS/XLA_FLAGS/THEIA_LOCKDEP in os.environ)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "THEIA_LOCKDEP",
                        "JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env.update(extra)
    return env


def _last_json(stdout: str):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_parent_has_no_jax_import_at_module_level():
    """The parent process must never touch jax: nothing at module
    level may import it, or anything of theia_tpu (whose analytics
    modules import jax) — only the comparison CHILD may."""
    tree = ast.parse(SMOKE.read_text())
    for node in tree.body:
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        for name in names:
            root = name.split(".")[0]
            assert root not in ("jax", "jaxlib", "theia_tpu", "numpy"), \
                f"chip_smoke.py imports {name} at module level"


def test_dry_run_is_labelled_and_parent_stays_off_jax():
    """The whole phase plumbing at a tiny size on the CPU: every phase
    ok, the last line says dry run and carries no "ok", and after all
    of it `jax` is not in the parent's sys.modules."""
    code = (
        "import sys, chip_smoke\n"
        "sys.argv = ['chip_smoke.py', '--dry-run']\n"
        "rc = chip_smoke.main()\n"
        "print('PARENT_IMPORTED_JAX', 'jax' in sys.modules, "
        "file=sys.stderr)\n"
        "sys.exit(rc)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_env(), capture_output=True, text=True,
                       timeout=800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "PARENT_IMPORTED_JAX False" in r.stderr
    assert "DRY RUN on the CPU — not a pass" in r.stdout
    for phase in ("build", "served-default", "jobs", "reference",
                  "served-fused", "runner"):
        assert f"[{phase}] ok" in r.stdout, r.stdout[-4000:]
    doc = _last_json(r.stdout)
    assert doc is not None and doc.get("dry_run") is True
    assert "ok" not in doc
    assert doc["device"]["platform"] == "cpu"


@pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")),
    reason="this host has an accelerator: the smoke would run for real")
def test_no_accelerator_fails_and_prints_no_result():
    """The real thing where JAX finds no accelerator (tier-1's own
    habitat): the first chip-needing child dies in JAX's backend
    init, the exit code is non-zero and no result line is printed."""
    r = subprocess.run([sys.executable, str(SMOKE)], cwd=REPO,
                       env=_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode != 0
    assert "[served-default] FAILED" in r.stdout
    assert "Unable to initialize backend 'tpu'" in r.stdout
    assert '"ok"' not in r.stdout
    shutil.rmtree(REPO / ".smoke_work", ignore_errors=True)


def test_without_the_repo_fails_and_prints_no_result(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo: non-zero exit, no result line."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    doc = _last_json(r.stdout)
    assert not (isinstance(doc, dict) and doc.get("ok"))
    assert '"ok"' not in r.stdout


# -- utils/device.py: the compile cache helper ---------------------------

_PROBE = """
import json, jax
from theia_tpu.utils import device
updates = []
real = jax.config.update
def spy(name, value):
    updates.append(name)
    return real(name, value)
jax.config.update = spy
if {fake_tpu!r}:
    jax.default_backend = lambda: "tpu"
ret = device.enable_compile_cache()
print(json.dumps({{"ret": ret, "dir": device.compile_cache_dir(),
                  "config": jax.config.jax_compilation_cache_dir,
                  "floor": jax.config
                  .jax_persistent_cache_min_compile_time_secs,
                  "updates": updates}}))
"""


def _probe(fake_tpu: bool, **env):
    r = subprocess.run(
        [sys.executable, "-c", _PROBE.format(fake_tpu=fake_tpu)],
        cwd=REPO, env=_env(JAX_PLATFORMS="cpu", **env),
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_dir_from_env_is_not_set_in_code(tmp_path):
    placed = str(tmp_path / "placed")
    doc = _probe(False, JAX_COMPILATION_CACHE_DIR=placed)
    assert doc["ret"] == doc["dir"] == doc["config"] == placed
    assert "jax_compilation_cache_dir" not in doc["updates"]
    # the sub-second steps of the served path must not be kept out
    assert doc["floor"] == 0.0


def test_cache_dir_default_is_fixed_and_in_the_checkout():
    a, b = _probe(True), _probe(True)      # two processes, one answer
    fixed = str(REPO / ".jax_cache")
    assert a["dir"] == b["dir"] == fixed
    assert a["ret"] == a["config"] == fixed
    assert a["floor"] == 0.0
    # on the CPU backend (tests, references) the cache stays off
    cpu = _probe(False)
    assert cpu["dir"] == fixed
    assert cpu["ret"] is None and cpu["config"] is None


def test_no_other_cache_directory_is_set_in_code():
    sources = [p for p in REPO.glob("*.py")]
    sources += list((REPO / "theia_tpu").rglob("*.py"))
    offenders = [
        str(p.relative_to(REPO)) for p in sources
        if p.name != "device.py"
        and any(needle in p.read_text() for needle in (
            "jax_compilation_cache_dir", "set_cache_dir",
            "initialize_cache"))]
    assert offenders == []
