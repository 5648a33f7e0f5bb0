"""Concurrency correctness tooling tests.

The hard gate: ``python -m theia_tpu.analysis`` must exit clean on
the repo (zero unwaived findings, zero stale waivers). Plus fixture
snippets pinning the two defect shapes the tooling was built for —
the PR-14 latch-inside-lock deadlock (caught by BOTH the static pass
and the runtime witness) and the PR-12 torn part-transition reader —
and unit coverage of the witness semantics (edges only for blocking
acquires, RLock reentrancy, Condition.wait held-set discipline,
disabled-mode zero-cost contract).
"""

import ast
import json
import pathlib
import threading
import time
import urllib.error
import urllib.request

import pytest

from theia_tpu.analysis import lockdep
from theia_tpu.analysis.base import (
    Finding,
    apply_waivers,
    validate_waivers,
)
from theia_tpu.analysis.lockgraph import LockGraph, analyze_source

pytestmark = pytest.mark.analysis

REPO = __file__.rsplit("/tests/", 1)[0]


# -- the tier-1 gate -----------------------------------------------------

def test_analysis_clean_at_head():
    """The static passes + waiver file = zero unwaived findings and
    zero stale waivers on the repo as committed. A new lock ordering,
    blocking call under a lock, undocumented THEIA_* knob, or
    unregistered fault site fails tier-1 here."""
    from theia_tpu.analysis.__main__ import run_all
    from theia_tpu.analysis.waivers import WAIVERS
    findings, _lg = run_all(REPO)
    problems = validate_waivers(WAIVERS)
    assert not problems, problems
    unwaived, _waived, stale = apply_waivers(findings, WAIVERS)
    assert not unwaived, (
        "unwaived analysis findings (fix, or waive with the "
        "invariant spelled out in analysis/waivers.py):\n"
        + "\n".join(f"  {f.check}: {f.key} @ {f.site}"
                    for f in unwaived))
    assert not stale, (
        "stale waivers (match nothing — the code they described "
        "changed):\n"
        + "\n".join(f"  {w['check']}:{w['match']}" for w in stale))


def test_analysis_main_exit_code():
    from theia_tpu.analysis.__main__ import main
    assert main(["--root", REPO]) == 0


def test_lockgraph_finds_real_locks():
    """The pass sees the package's actual lock population (the 50+
    adopted factory sites), including the latch and the WAL io lock."""
    lg = LockGraph(f"{REPO}/theia_tpu")
    lg.run()
    names = set(lg.locks.values())
    for expected in ("store.table", "wal.io", "ingest.shard",
                     "store.ingest_latch", "rollup.manager",
                     "cluster.node", "metrics.registry"):
        assert expected in names, f"{expected} not identified"
    assert len(names) >= 40


# -- the PR-14 shape: latch inside lock ----------------------------------

PR14_SRC = '''
import threading
from theia_tpu.analysis.lockdep import named_lock

class _Latch:
    def __init__(self, name): ...
    def read(self): ...
    def write(self): ...

class RollupManager:
    def __init__(self, db):
        self._lock = named_lock("rollup.manager")
        self._latch = _Latch("store.ingest_latch")

    def reload(self, cfg):
        with self._lock:                 # manager lock FIRST (the bug)
            with self._latch.write():    # latch inside the lock
                self._views = cfg

    def apply_block(self, batch):
        with self._latch.read():         # insert path: latch first
            with self._lock:             # then the manager lock
                self._fold(batch)
'''


def test_pr14_latch_inside_lock_caught_by_static_pass():
    findings = analyze_source(PR14_SRC)
    cycles = [f for f in findings if f.check == "lock-order-cycle"]
    assert cycles, "the PR-14 latch-inside-lock shape must be caught"
    assert "rollup.manager" in cycles[0].key
    assert "store.ingest_latch" in cycles[0].key


def test_pr14_fixed_order_is_clean():
    """The shipped (fixed) order — latch before lock on BOTH paths —
    produces no cycle: the gate fails the bug, not the fix."""
    fixed = PR14_SRC.replace(
        """        with self._lock:                 # manager lock FIRST (the bug)
            with self._latch.write():    # latch inside the lock
                self._views = cfg""",
        """        with self._latch.write():
            with self._lock:
                self._views = cfg""")
    findings = analyze_source(fixed)
    assert not [f for f in findings
                if f.check == "lock-order-cycle"]


def test_pr14_caught_by_runtime_witness():
    """The SAME shape at runtime: both orders observed (sequentially
    — no deadlock ever happens) flags the inversion. Uses a real WAL
    latch so the latch->lock integration is what's under test."""
    from theia_tpu.store.wal import _Latch
    if not lockdep.enabled():
        pytest.skip("witness disarmed (THEIA_LOCKDEP=0 run)")
    with lockdep.scoped():
        latch = _Latch("fixture.latch")
        lock = lockdep.named_lock("fixture.manager")

        def insert_path():
            with latch.read():
                with lock:
                    pass

        def reload_path():
            with lock:                    # the PR-14 bug order
                with latch.write():
                    pass

        t = threading.Thread(target=insert_path)
        t.start(); t.join()
        assert lockdep.inversions() == []
        t = threading.Thread(target=reload_path)
        t.start(); t.join()
        inv = lockdep.inversions()
        assert len(inv) == 1, inv
        assert set(inv[0]["cycle"]) == {"fixture.latch",
                                        "fixture.manager"}


# -- the PR-12 shape: torn multi-field transition ------------------------

PR12_SRC = '''
import threading

class Part:
    def __init__(self):
        self._lock = threading.Lock()
        self._chunks = None
        self._rowid = None

    def demote(self):
        with self._lock:
            self._chunks = None          # field 1
            self._rowid = None           # field 2: a reader between
                                         # the two sees a torn pair

    def scan(self):
        rid = self._rowid                # lock-free reader needs BOTH
        ch = self._chunks
        return ch, rid
'''


def test_pr12_torn_reader_caught_by_static_pass():
    findings = analyze_source(PR12_SRC)
    torn = [f for f in findings if f.check == "torn-read"]
    assert torn, "the PR-12 torn-reader shape must be caught"
    assert "_chunks" in torn[0].key and "_rowid" in torn[0].key


def test_locked_suffix_reader_exempt():
    """A reader named *_locked follows the repo convention (caller
    holds the lock) and is not a torn-read."""
    src = PR12_SRC.replace("def scan(self):", "def scan_locked(self):")
    findings = analyze_source(src)
    assert not [f for f in findings if f.check == "torn-read"]


# -- blocking-under-lock -------------------------------------------------

def test_blocking_call_under_lock_caught():
    src = '''
import os, threading, time

class Log:
    def __init__(self):
        self._io = threading.Lock()

    def sync(self):
        with self._io:
            os.fsync(3)

    def backoff(self):
        with self._io:
            time.sleep(1.0)
'''
    findings = analyze_source(src)
    keys = {f.key for f in findings
            if f.check == "blocking-under-lock"}
    assert any("os.fsync" in k for k in keys), keys
    assert any("time.sleep" in k for k in keys), keys


def test_multi_item_with_orders_left_to_right():
    """`with a, b:` takes b while a is held — the combined form must
    mint the same edge as the nested form, or an AB/BA deadlock
    written that way slips past the gate."""
    src = '''
import threading

class M:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._b, self._a:
            pass
'''
    findings = analyze_source(src)
    assert [f for f in findings if f.check == "lock-order-cycle"]


def test_trylock_adds_no_static_edge():
    """The ingest shards' opportunistic acquire must not read as an
    ordering commitment."""
    src = '''
import threading

class M:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            self._b.acquire(blocking=False)   # trylock: no edge
            self._b.release()

    def two(self):
        with self._b:
            with self._a:
                pass
'''
    findings = analyze_source(src)
    assert not [f for f in findings
                if f.check == "lock-order-cycle"]


# -- runtime witness unit semantics --------------------------------------

def _run(fn):
    t = threading.Thread(target=fn)
    t.start(); t.join()


@pytest.fixture(autouse=True)
def _skip_when_disarmed(request):
    if "witness" in request.node.name and not lockdep.enabled():
        pytest.skip("witness disarmed")
    yield


def test_witness_inversion_without_deadlock():
    with lockdep.scoped():
        a = lockdep.named_lock("fx.a")
        b = lockdep.named_lock("fx.b")
        _run(lambda: _nest(a, b))
        assert not lockdep.inversions()
        _run(lambda: _nest(b, a))
        inv = lockdep.inversions()
        assert len(inv) == 1
        assert inv[0]["edge"] == ["fx.b", "fx.a"]
        assert ("fx.a", "fx.b") in lockdep.order_edges()


def _nest(outer, inner):
    with outer:
        with inner:
            pass


def test_witness_consistent_order_stays_clean():
    with lockdep.scoped():
        a = lockdep.named_lock("fx.a")
        b = lockdep.named_lock("fx.b")
        for _ in range(3):
            _run(lambda: _nest(a, b))
        assert not lockdep.inversions()


def test_witness_trylock_records_no_edge():
    with lockdep.scoped():
        a = lockdep.named_lock("fx.a")
        b = lockdep.named_lock("fx.b")

        def one():
            with a:
                assert b.acquire(blocking=False)
                b.release()

        def two():
            with b:
                with a:
                    pass

        _run(one)
        _run(two)
        assert not lockdep.inversions(), lockdep.inversions()
        assert ("fx.a", "fx.b") not in lockdep.order_edges()


def test_witness_rlock_reentrancy_not_self_nesting():
    with lockdep.scoped():
        r = lockdep.named_rlock("fx.r")

        def go():
            with r:
                with r:
                    pass

        _run(go)
        doc = lockdep.stats_doc()
        assert doc["selfNesting"] == {}
        assert doc["stats"]["fx.r"]["acquires"] == 1


def test_witness_same_class_nesting_is_self_edge_not_inversion():
    with lockdep.scoped():
        t1 = lockdep.named_lock("fx.table")
        t2 = lockdep.named_lock("fx.table")

        def go():
            with t1:
                with t2:
                    pass

        _run(go)
        assert not lockdep.inversions()
        assert lockdep.stats_doc()["selfNesting"] == {"fx.table": 1}


def test_witness_condition_wait_drops_held_entry():
    with lockdep.scoped():
        c = lockdep.named_condition("fx.cond")
        seen = []

        def waiter():
            with c:
                c.wait(timeout=5.0)
                seen.append(tuple(lockdep.held_names()))

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with c:
            # the waiter released: this acquire succeeded while the
            # waiter is inside wait()
            c.notify()
        t.join()
        assert seen == [("fx.cond",)]


def test_witness_contention_stats():
    with lockdep.scoped():
        lk = lockdep.named_lock("fx.slow")
        started = threading.Event()

        def holder():
            with lk:
                started.set()
                time.sleep(0.05)

        t = threading.Thread(target=holder)
        t.start()
        started.wait()
        with lk:
            pass
        t.join()
        s = lockdep.stats()["fx.slow"]
        assert s["acquires"] == 2
        assert s["contended"] == 1
        assert s["waitTotalSeconds"] > 0.0
        assert s["holdTotalSeconds"] > 0.04


def test_witness_raise_mode_leaves_nothing_wedged(monkeypatch):
    """THEIA_LOCKDEP_RAISE=1 raises at the acquisition that closes a
    cycle — BEFORE the underlying lock/latch is taken, so the error
    propagates cleanly and every lock involved stays acquirable (a
    raise after the take would wedge the lock for every later
    acquirer, turning the hunt into a process-wide hang)."""
    from theia_tpu.store.wal import _Latch
    monkeypatch.setenv("THEIA_LOCKDEP_RAISE", "1")
    with lockdep.scoped():
        x = lockdep.named_lock("fx.rx")
        y = lockdep.named_lock("fx.ry")
        _run(lambda: _nest(x, y))
        raised = []

        def two():
            try:
                with y:
                    with x:
                        pass
            except RuntimeError as e:
                raised.append(str(e))

        _run(two)
        assert raised and "inversion" in raised[0]
        assert x.acquire(blocking=False)
        x.release()
        assert y.acquire(blocking=False)
        y.release()
    with lockdep.scoped():
        latch = _Latch("fx.rlatch")
        lk = lockdep.named_lock("fx.rlock")

        def a():
            with latch.read():
                with lk:
                    pass

        _run(a)
        raised = []

        def b():
            try:
                with lk:
                    with latch.write():
                        pass
            except RuntimeError:
                raised.append("raised")

        _run(b)
        assert raised == ["raised"]
        with latch.write():      # a wedged latch would hang here
            pass
        with latch.read():
            pass


def test_witness_latch_edge_site_names_the_caller():
    """The inversion report's closing site must point at the CALLER
    that took the latch — not wal.py's _Latch implementation — or the
    exact deadlock class this tool exists to localize becomes
    unactionable."""
    from theia_tpu.store.wal import _Latch
    if not lockdep.enabled():
        pytest.skip("witness disarmed")
    with lockdep.scoped():
        latch = _Latch("fx.site.latch")
        lock = lockdep.named_lock("fx.site.lock")

        def a():
            with latch.read():
                with lock:
                    pass

        def b():
            with lock:
                with latch.write():
                    pass

        _run(a)
        _run(b)
        inv = lockdep.inversions()
        assert len(inv) == 1
        assert "store/wal.py" not in inv[0]["site"], inv[0]
        assert "test_analysis" in inv[0]["site"], inv[0]


def test_scoped_merges_back_real_lock_observations():
    """A background thread's REAL ordering observation made while a
    fixture scope is active must survive the scope's teardown — the
    suite-wide zero-inversions gate would otherwise silently miss an
    inversion first witnessed during any scoped() window. Fixture
    locks (minted inside the scope) are still discarded."""
    if not lockdep.enabled():
        pytest.skip("witness disarmed")
    with lockdep.scoped():                 # isolate from the suite
        real_a = lockdep.named_lock("real.mb.a")
        real_b = lockdep.named_lock("real.mb.b")
        _run(lambda: _nest(real_a, real_b))   # real order known
        with lockdep.scoped():             # the fixture window
            fx = lockdep.named_lock("fx.mb")
            # a "background thread" closes the REAL cycle while the
            # window is active...
            _run(lambda: _nest(real_b, real_a))
            # ...and a fixture inversion happens too
            _run(lambda: _nest(fx, real_a))
            _run(lambda: _nest(real_a, fx))
        # after teardown: the real inversion survived the merge-back,
        # the fixture one (fx.mb was minted inside) did not
        inv = lockdep.inversions()
        assert len(inv) == 1, inv
        assert set(inv[0]["cycle"]) == {"real.mb.a", "real.mb.b"}
        assert ("real.mb.b", "real.mb.a") in lockdep.order_edges()
        assert "fx.mb" not in lockdep.lock_names()


def test_disabled_factory_returns_bare_primitives(monkeypatch):
    monkeypatch.setenv("THEIA_LOCKDEP", "0")
    lk = lockdep.named_lock("fx.off")
    assert type(lk) is type(threading.Lock())
    rl = lockdep.named_rlock("fx.off")
    assert type(rl) is type(threading.RLock())
    cond = lockdep.named_condition("fx.off")
    assert isinstance(cond, threading.Condition)
    assert type(cond._lock) is type(threading.RLock())


def test_latch_disabled_is_unwitnessed(monkeypatch):
    monkeypatch.setenv("THEIA_LOCKDEP", "0")
    from theia_tpu.store.wal import _Latch
    latch = _Latch("fx.latch.off")
    with lockdep.scoped():
        with latch.read():
            pass
        assert "fx.latch.off" not in lockdep.stats()


# -- waiver machinery ----------------------------------------------------

def test_waiver_requires_real_invariant():
    problems = validate_waivers([
        {"check": "torn-read", "match": "x*", "invariant": "is fine"}])
    assert problems and "invariant" in problems[0]


def test_waiver_unknown_check_rejected():
    problems = validate_waivers([
        {"check": "nonsense", "match": "x*",
         "invariant": "long enough invariant text that says why "
                      "this is safe in detail"}])
    assert problems and "unknown check" in problems[0]


def test_stale_waiver_reported():
    w = [{"check": "torn-read", "match": "torn-read:nowhere:*",
          "invariant": "a perfectly reasonable forty-plus character "
                       "invariant about nothing"}]
    unwaived, waived, stale = apply_waivers(
        [Finding(check="torn-read", key="torn-read:real:K:a,b",
                 message="m")], w)
    assert len(unwaived) == 1 and not waived and stale == w


# -- the package's layering ----------------------------------------------

#: the host's group-by: defined once, in ``theia_tpu/utils/native.py``
GROUP_BY = {"group_reduce", "group_sum", "group_sum_fast",
            "group_sum_exact"}

#: (importing package, imported package, the modules that may): an
#: arrow that points up. The one known exception is the store's two
#: hooks into ``query.rollup``: ROADMAP D14 (they go with D4's decision)
ARROWS = [
    ("ops", "analytics", ()),
    ("analytics", "ingest", ()),
    ("store", "ingest", ()),
    ("query", "ingest", ()),
    ("utils", "ingest", ()),
    ("store", "query", ("store/flow_store.py", "store/replicated.py")),
]


def _imports(path):
    """(line, module, names) of every import in a module of the
    package, function-level ones included, relative ones resolved."""
    package = list(path.relative_to(REPO).parent.parts)
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] \
                if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            yield node.lineno, module, tuple(a.name for a in node.names)


def _package_imports(sub=""):
    for path in sorted(pathlib.Path(REPO, "theia_tpu", sub).rglob("*.py")):
        rel = path.relative_to(pathlib.Path(REPO, "theia_tpu")).as_posix()
        for line, module, names in _imports(path):
            yield rel, line, module, names


@pytest.mark.parametrize("src,dst,may", ARROWS,
                         ids=[f"{s}->{d}" for s, d, _ in ARROWS])
def test_no_import_points_up(src, dst, may):
    target = f"theia_tpu.{dst}"
    found = [
        f"{rel}:{line} imports {module}"
        for rel, line, module, names in _package_imports(src)
        if rel not in may and any(
            m == target or m.startswith(target + ".")
            for m in (module, *(f"{module}.{n}" for n in names)))]
    assert not found, found


def test_the_hosts_group_by_has_one_home():
    """Nothing imports it from ``store.views`` (or through the
    ``store`` package), and nothing else defines it."""
    borrowed = [
        f"{rel}:{line} imports {sorted(GROUP_BY & set(names))} from {module}"
        for rel, line, module, names in _package_imports()
        if GROUP_BY & set(names) and module != "theia_tpu.utils.native"]
    assert not borrowed, borrowed
    homes = {
        path.relative_to(REPO).as_posix()
        for path in pathlib.Path(REPO, "theia_tpu").rglob("*.py")
        for node in ast.walk(ast.parse(path.read_text()))
        if isinstance(node, ast.FunctionDef) and node.name in GROUP_BY}
    assert homes == {"theia_tpu/utils/native.py"}


# -- lint fixtures -------------------------------------------------------

def test_lint_env_extraction(tmp_path):
    from theia_tpu.analysis.lint import extract_env_reads
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        '"""Doc mentions THEIA_IN_DOCSTRING only."""\n'
        "import os\n"
        "A = os.environ.get('THEIA_DIRECT', '')\n"
        "B = ('THEIA_AS_DATA', 1)\n")
    reads = extract_env_reads(str(pkg))
    assert "THEIA_DIRECT" in reads
    assert "THEIA_AS_DATA" in reads          # name passed as data
    assert "THEIA_IN_DOCSTRING" not in reads  # prose is not a read


def test_fault_site_registry_in_sync_with_code():
    from theia_tpu.analysis.lint import extract_fired_sites
    from theia_tpu.utils.faults import KNOWN_SITES
    fired = set(extract_fired_sites(f"{REPO}/theia_tpu"))
    assert fired == set(KNOWN_SITES)


def test_lint_bare_and_swallowed_except(tmp_path):
    from theia_tpu.analysis.lint import Lint
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        return 1\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "def ok():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n")
    checks = {f.check for f in
              Lint(str(pkg), str(tmp_path / "docs")).run()
              if "except" in f.check}
    assert checks == {"bare-except", "swallowed-except"}


def test_lint_raw_clock(tmp_path):
    from theia_tpu.analysis.lint import Lint
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import time\n"
        "def loop(clock=time.monotonic):\n"
        "    return clock()\n"
        "def bad():\n"
        "    return time.time()\n")
    raw = [f for f in Lint(str(pkg), str(tmp_path / "docs")).run()
           if f.check == "raw-clock"]
    assert len(raw) == 1 and "bad" in raw[0].key
    # a module with NO clock convention is exempt
    (pkg / "m.py").write_text(
        "import time\n"
        "def bad():\n"
        "    return time.time()\n")
    raw = [f for f in Lint(str(pkg), str(tmp_path / "docs")).run()
           if f.check == "raw-clock"]
    assert not raw


# -- /debug/locks HTTP surface -------------------------------------------

def test_debug_locks_http_and_auth_gate(tmp_path):
    from theia_tpu.data.synth import SynthConfig, generate_flows
    from theia_tpu.manager.api import TheiaManagerServer
    from theia_tpu.store import FlowDatabase
    db = FlowDatabase()
    db.insert_flows(generate_flows(SynthConfig(
        n_series=20, points_per_series=5, anomaly_fraction=0.0,
        seed=7)))
    srv = TheiaManagerServer(db, port=0, auth_token="sekrit")
    srv.start_background()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/debug/locks", timeout=10)
        assert ei.value.code == 401
        req = urllib.request.Request(
            f"{base}/debug/locks",
            headers={"Authorization": "Bearer sekrit"})
        doc = json.load(urllib.request.urlopen(req, timeout=10))
        if lockdep.enabled():
            assert doc["enabled"] is True
            assert "store.table" in doc["locks"]
            assert doc["inversions"] == []
            some = next(iter(doc["stats"].values()))
            assert {"acquires", "contended", "waitP95Seconds",
                    "holdP95Seconds"} <= set(some)
        else:
            assert doc == {"enabled": False}
    finally:
        srv.shutdown()
