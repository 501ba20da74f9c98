"""The concurrency contract (CONCURRENCY.md), both halves.

Static (tpudl.analysis.concurrency): per-rule positive/negative/
suppression fixtures proving each of the four interprocedural rules
LIVE, the seeded two-lock ABBA caught from source, the lock-registry
round-trip (every construction site in tpudl/ resolves to a
declaration and vice versa), and the repo self-lint.

Dynamic (tpudl.testing.tsan): the SAME seeded ABBA reproduced as a
real two-thread deadlock in a subprocess — the armed sanitizer
converts the hang into a loud DeadlockError + report, while the
unarmed control genuinely hangs until killed. Plus in-process
inversion/declared-order/lockset/self-deadlock detection and the
unarmed fast-path overhead guard.

Runtime regression: Heartbeat.beat() vs the snapshotting readers
(watchdog daemon / status writer) — the race this PR's sweep fixed.

The whole module is marked ``concurrency``: run-tests.sh re-runs it
with TPUDL_TSAN=1 (the armed pass) ahead of the full suite.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from tpudl.analysis import (CONCURRENCY_RULES, LOCK_NAMES, LOCKS,
                            analyze_concurrency, analyze_sources,
                            build_lock_graph, iter_python_files,
                            lock_order, registry_coverage,
                            render_lock_table)
from tpudl.testing import tsan

pytestmark = pytest.mark.concurrency

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_TARGETS = [os.path.join(REPO, "tpudl"), os.path.join(REPO, "tools")]


def rules_of(findings):
    return [f.rule for f in findings]


def only(src, rule, relpath="fix.py"):
    return [f for f in analyze_sources({relpath: src}, rules=[rule])
            if f.rule == rule]


@pytest.fixture
def armed():
    """Arm the sanitizer with a clean slate; restore the prior state
    (the TPUDL_TSAN=1 suite pass starts armed — keep it that way)."""
    prev = tsan.ENABLED
    tsan.reset()
    tsan.arm()
    yield
    tsan.ENABLED = prev
    tsan.reset()


# ---------------------------------------------------------------------------
# the seeded ABBA — ONE source, caught by BOTH halves
# ---------------------------------------------------------------------------

# also executable: the subprocess deadlock acceptance runs exactly this
ABBA_SRC = (
    "import threading\n"
    "\n"
    "from tpudl.testing import tsan\n"
    "\n"
    "LOCK_A = tsan.named_lock('fix.abba.a')\n"
    "LOCK_B = tsan.named_lock('fix.abba.b')\n"
    "_BARRIER = threading.Barrier(2)\n"
    "\n"
    "\n"
    "def forward():\n"
    "    with LOCK_A:\n"
    "        _BARRIER.wait()\n"
    "        with LOCK_B:\n"
    "            pass\n"
    "\n"
    "\n"
    "def backward():\n"
    "    with LOCK_B:\n"
    "        _BARRIER.wait()\n"
    "        with LOCK_A:\n"
    "            pass\n"
    "\n"
    "\n"
    "def run():\n"
    "    t1 = threading.Thread(target=forward)\n"
    "    t2 = threading.Thread(target=backward)\n"
    "    t1.start()\n"
    "    t2.start()\n"
    "    t1.join()\n"
    "    t2.join()\n"
)

ABBA_MAIN = (
    "\n"
    "if __name__ == '__main__':\n"
    "    import sys\n"
    "    run()\n"
    "    bad = [f for f in tsan.findings() if f['kind'] == 'deadlock']\n"
    "    tsan.write_report()\n"
    "    sys.exit(3 if bad else 0)\n"
)


class TestSeededABBA:
    def test_caught_statically(self):
        fs = only(ABBA_SRC, "lock-order")
        assert len(fs) == 1
        msg = fs[0].message
        assert "fix.LOCK_A" in msg and "fix.LOCK_B" in msg
        assert "witnesses" in msg

    def test_named_lock_sites_in_graph(self):
        g = build_lock_graph(sources={"fix.py": ABBA_SRC})
        names = {s.name for s in g.locks}
        assert names == {"fix.abba.a", "fix.abba.b"}
        # both acquired-under directions witnessed
        ids = {(a.split(".")[-1], b.split(".")[-1]) for a, b in g.edges}
        assert ("LOCK_A", "LOCK_B") in ids and ("LOCK_B", "LOCK_A") in ids

    def test_runtime_sanitizer_reports_the_deadlock(self, tmp_path):
        script = tmp_path / "abba.py"
        script.write_text(ABBA_SRC + ABBA_MAIN)
        env = dict(os.environ)
        env.update({"TPUDL_TSAN": "1", "TPUDL_TSAN_DEADLOCK_S": "0.4",
                    "TPUDL_FLIGHT_DIR": str(tmp_path),
                    "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
        proc = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=90)
        assert proc.returncode == 3, (proc.stdout, proc.stderr)
        assert "DeadlockError" in proc.stderr
        reports = list(tmp_path.glob("tpudl-tsan-*.json"))
        assert len(reports) == 1
        rep = json.loads(reports[0].read_text())
        kinds = [f["kind"] for f in rep["findings"]]
        assert "deadlock" in kinds
        dead = next(f for f in rep["findings"] if f["kind"] == "deadlock")
        assert set(dead["locks"]) == {"fix.abba.a", "fix.abba.b"}

    def test_unsanitized_control_hangs_then_killed(self, tmp_path):
        script = tmp_path / "abba.py"
        script.write_text(ABBA_SRC + ABBA_MAIN)
        env = dict(os.environ)
        env.pop("TPUDL_TSAN", None)  # unarmed: plain locks, true hang
        env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
        proc = subprocess.Popen([sys.executable, str(script)], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        try:
            with pytest.raises(subprocess.TimeoutExpired):
                proc.wait(timeout=20)
        finally:
            proc.kill()
            proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# rule: lock-order (fixtures beyond the seeded pair)
# ---------------------------------------------------------------------------

class TestLockOrderRule:
    def test_cycle_through_call_hops(self):
        src = (
            "import threading\n"
            "A = threading.Lock()\n"
            "B = threading.Lock()\n"
            "def takes_b():\n"
            "    with B:\n"
            "        pass\n"
            "def f():\n"
            "    with A:\n"
            "        takes_b()\n"
            "def g():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n")
        fs = only(src, "lock-order")
        assert len(fs) == 1
        assert "ABBA" in fs[0].message

    def test_consistent_order_is_clean(self):
        src = (
            "import threading\n"
            "A = threading.Lock()\n"
            "B = threading.Lock()\n"
            "def f():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n"
            "def g():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n")
        assert only(src, "lock-order") == []

    def test_suppression_at_witness_site(self):
        src = (
            "import threading\n"
            "A = threading.Lock()\n"
            "B = threading.Lock()\n"
            "def f():\n"
            "    with A:\n"
            "        # tpudl: ignore[lock-order] — test-only fixture\n"
            "        with B:\n"
            "            pass\n"
            "def g():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n")
        assert only(src, "lock-order") == []

    def test_reasonless_suppression_is_a_finding(self):
        src = (
            "import threading\n"
            "A = threading.Lock()\n"
            "B = threading.Lock()\n"
            "def f():\n"
            "    with A:\n"
            "        # tpudl: ignore[lock-order]\n"
            "        with B:\n"
            "            pass\n"
            "def g():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n")
        fs = only(src, "lock-order")
        assert len(fs) == 1
        assert "missing its required reason" in fs[0].message

    def test_same_lock_nested_is_a_finding(self):
        # a per-instance non-reentrant lock nested under itself: same
        # instance self-deadlocks, sibling instances are rank-equal —
        # either way the contract is violated
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "    def outer(self, other):\n"
            "        with self._lk:\n"
            "            with other._lk:\n"
            "                pass\n")
        fs = only(src, "lock-order")
        assert len(fs) == 1
        assert "same-lock nested acquisition" in fs[0].message

    def test_same_lock_nested_via_callee(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "    def leafy_grab(self):\n"
            "        with self._lk:\n"
            "            pass\n"
            "    def outer(self):\n"
            "        with self._lk:\n"
            "            self.leafy_grab()\n")
        fs = only(src, "lock-order")
        assert len(fs) == 1
        assert "same-lock nested acquisition" in fs[0].message
        assert "leafy_grab" in fs[0].message

    def test_closure_not_poisoned_by_cycle_memo(self):
        # q is processed FIRST and computes blocking_of(x) while y is
        # still on the DFS stack (the y->x->y cycle back-edge returns
        # {}); caching that truncated result would hide f's finding —
        # findings must not depend on definition order
        cyc = (
            "    x()\n"
            "def x():\n"
            "    y()\n"
            "def y():\n"
            "    import time\n"
            "    time.sleep(1)\n"
            "    x()\n")
        first = ("import threading\n"
                 "A = threading.Lock()\n"
                 "C = threading.Lock()\n"
                 "def q():\n"
                 "  with C:\n"
                 "    x()\n"
                 "def f():\n"
                 "  with A:\n" + cyc)
        second = ("import threading\n"
                  "A = threading.Lock()\n"
                  "C = threading.Lock()\n"
                  "def f():\n"
                  "  with A:\n"
                  "    x()\n"
                  "def q():\n"
                  "  with C:\n" + cyc)
        for src in (first, second):
            fs = only(src, "lock-held-blocking")
            held = {f.message.split(" held")[0] for f in fs}
            assert held == {"fix.A", "fix.C"}, (held, src)

    def test_same_rlock_nested_is_clean(self):
        # reentrancy is the POINT of an rlock
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.RLock()\n"
            "    def outer(self):\n"
            "        with self._lk:\n"
            "            with self._lk:\n"
            "                pass\n")
        assert only(src, "lock-order") == []

    def test_same_lock_nested_suppressible(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "    def outer(self, other):\n"
            "        with self._lk:\n"
            "            # tpudl: ignore[lock-order] — fixture\n"
            "            with other._lk:\n"
            "                pass\n")
        assert only(src, "lock-order") == []


# ---------------------------------------------------------------------------
# rule: lock-held-blocking
# ---------------------------------------------------------------------------

class TestLockHeldBlockingRule:
    def test_sleep_under_lock(self):
        src = (
            "import threading\n"
            "import time\n"
            "LOCK = threading.Lock()\n"
            "def slow():\n"
            "    with LOCK:\n"
            "        time.sleep(1.0)\n")
        fs = only(src, "lock-held-blocking")
        assert len(fs) == 1
        assert "time.sleep" in fs[0].message

    def test_blocking_reached_through_callee(self):
        src = (
            "import threading\n"
            "import time\n"
            "LOCK = threading.Lock()\n"
            "def helper():\n"
            "    time.sleep(0.5)\n"
            "def outer():\n"
            "    with LOCK:\n"
            "        helper()\n")
        fs = only(src, "lock-held-blocking")
        assert len(fs) == 1
        assert "reaches time.sleep" in fs[0].message

    def test_bounded_queue_put_and_argless_join(self):
        src = (
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def feed(work_queue, item, t):\n"
            "    with LOCK:\n"
            "        work_queue.put(item)\n"
            "        t.join()\n")
        msgs = [f.message for f in only(src, "lock-held-blocking")]
        assert any("bounded-queue put" in m for m in msgs)
        assert any("join" in m for m in msgs)

    def test_durable_io_in_a_combined_with_item(self):
        # `with LOCK, open(manifest, "w"):` — the IO item runs with
        # the earlier item's lock already held
        src = (
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def write(manifest_path, data):\n"
            "    with LOCK, open(manifest_path, 'w') as f:\n"
            "        f.write(data)\n")
        fs = only(src, "lock-held-blocking")
        assert len(fs) == 1
        assert "durable file IO" in fs[0].message

    def test_sleep_outside_lock_is_clean(self):
        src = (
            "import threading\n"
            "import time\n"
            "LOCK = threading.Lock()\n"
            "def ok():\n"
            "    with LOCK:\n"
            "        pass\n"
            "    time.sleep(1.0)\n")
        assert only(src, "lock-held-blocking") == []

    def test_suppression_on_def_line_covers_the_function(self):
        src = (
            "import threading\n"
            "import time\n"
            "LOCK = threading.Lock()\n"
            "# tpudl: ignore[lock-held-blocking] — fixture: the sleep\n"
            "# IS this function's job\n"
            "def slow():\n"
            "    with LOCK:\n"
            "        time.sleep(1.0)\n")
        assert only(src, "lock-held-blocking") == []


# ---------------------------------------------------------------------------
# rule: signal-lock
# ---------------------------------------------------------------------------

class TestSignalLockRule:
    def test_handler_reaching_a_lock_fires(self):
        src = (
            "import signal\n"
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def grab():\n"
            "    with LOCK:\n"
            "        pass\n"
            "def handler(signum, frame):\n"
            "    grab()\n"
            "def install():\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        fs = only(src, "signal-lock")
        assert len(fs) == 1
        assert "fix.LOCK" in fs[0].message
        assert "interrupted frame" in fs[0].message

    def test_flag_only_handler_is_clean(self):
        src = (
            "import signal\n"
            "import threading\n"
            "FLAG = threading.Event()\n"
            "def handler(signum, frame):\n"
            "    FLAG.set()\n"
            "def install():\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-lock") == []

    def test_suppression_on_handler_def(self):
        src = (
            "import signal\n"
            "import threading\n"
            "LOCK = threading.Lock()\n"
            "def grab():\n"
            "    with LOCK:\n"
            "        pass\n"
            "# tpudl: ignore[signal-lock] — fixture: assembled on a\n"
            "# bounded worker thread\n"
            "def handler(signum, frame):\n"
            "    grab()\n"
            "def install():\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-lock") == []


# ---------------------------------------------------------------------------
# rule: daemon-shared-write
# ---------------------------------------------------------------------------

class TestDaemonSharedWriteRule:
    def test_unguarded_attr_written_from_both_sides(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "    def bg(self):\n"
            "        self.n = compute()\n"
            "    def fg(self):\n"
            "        self.n = compute()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        fs = only(src, "daemon-shared-write")
        assert len(fs) == 1
        assert "C.n" in fs[0].message
        assert "no common lock" in fs[0].message

    def test_unguarded_global_written_from_both_sides(self):
        src = (
            "import threading\n"
            "_STATE = None\n"
            "def _bg():\n"
            "    global _STATE\n"
            "    _STATE = make()\n"
            "def fg_set():\n"
            "    global _STATE\n"
            "    _STATE = make()\n"
            "def start():\n"
            "    threading.Thread(target=_bg).start()\n")
        fs = only(src, "daemon-shared-write")
        assert len(fs) == 1
        assert "_STATE" in fs[0].message

    def test_tuple_unpacking_writes_fire(self):
        # `_A, _B = ...` rebinds both globals just as racily as the
        # single-name form (the PR 8 unlocked-global hardening, here)
        src = (
            "import threading\n"
            "_A = None\n"
            "_B = None\n"
            "def _bg():\n"
            "    global _A, _B\n"
            "    _A, _B = compute(), compute()\n"
            "def fg_set():\n"
            "    global _A, _B\n"
            "    _A, _B = compute(), compute()\n"
            "def start():\n"
            "    threading.Thread(target=_bg).start()\n")
        fs = only(src, "daemon-shared-write")
        assert len(fs) >= 1

    def test_augassign_is_not_a_const_store(self):
        # `self.n += 1` is a read-modify-write — the GIL-atomic
        # const-flag exemption must not swallow it (AugAssign.value is
        # the Constant OPERAND, not the stored value)
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "    def bg(self):\n"
            "        self.n += 1\n"
            "    def fg(self):\n"
            "        self.n += 1\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        fs = only(src, "daemon-shared-write")
        assert len(fs) == 1
        assert "C.n" in fs[0].message

    def test_tuple_global_every_name_checked(self):
        # bg writes `_A, _B = ...`; fg writes only _A — the finding
        # must fire on _A even though it is not the first flattened
        # name of the tuple write
        src = (
            "import threading\n"
            "_A = None\n"
            "_B = None\n"
            "def _bg():\n"
            "    global _A, _B\n"
            "    _A, _B = compute(), compute()\n"
            "def fg_set():\n"
            "    global _A\n"
            "    _A = compute()\n"
            "def start():\n"
            "    threading.Thread(target=_bg).start()\n")
        fs = only(src, "daemon-shared-write")
        assert len(fs) == 1
        assert "_A" in fs[0].message

    def test_annotation_only_statement_is_not_a_write(self):
        # `self.mode: str` performs no store — it must not produce a
        # phantom race
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.mode = ''\n"
            "    def bg(self):\n"
            "        self.mode: str\n"
            "    def fg(self):\n"
            "        self.mode: str\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        assert only(src, "daemon-shared-write") == []

    def test_common_lock_is_clean(self):
        src = (
            "import threading\n"
            "class D:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "        self.n = 0\n"
            "    def bg(self):\n"
            "        with self._lk:\n"
            "            self.n = compute()\n"
            "    def fg(self):\n"
            "        with self._lk:\n"
            "            self.n = compute()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        assert only(src, "daemon-shared-write") == []

    def test_constant_flag_store_is_exempt(self):
        # GIL-atomic flag stores are the house idiom (checker.py)
        src = (
            "import threading\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self.stop = False\n"
            "    def bg(self):\n"
            "        self.stop = True\n"
            "    def fg(self):\n"
            "        self.stop = False\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        assert only(src, "daemon-shared-write") == []

    def test_suppression_at_a_write_site(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "    def bg(self):\n"
            "        # tpudl: ignore[daemon-shared-write] — fixture\n"
            "        self.n = compute()\n"
            "    def fg(self):\n"
            "        self.n = compute()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.bg).start()\n")
        assert only(src, "daemon-shared-write") == []


# ---------------------------------------------------------------------------
# the lock registry round-trip (the coverage acceptance)
# ---------------------------------------------------------------------------

class TestLockRegistry:
    def test_registry_round_trip(self):
        cov = registry_coverage([os.path.join(REPO, "tpudl")], root=REPO)
        assert cov["undeclared"] == [], (
            "named_lock sites missing a LockDecl: " + str(cov["undeclared"]))
        assert cov["unconstructed"] == [], (
            "LockDecls with no construction site: "
            + str(cov["unconstructed"]))
        assert cov["named"] == set(LOCK_NAMES)
        # raw construction is allowed ONLY inside the sanitizer itself
        assert cov["anonymous"], "the sanitizer's own lock should be here"
        assert all(a.startswith("tpudl/testing/tsan.py")
                   for a in cov["anonymous"]), cov["anonymous"]

    def test_raw_lock_ctors_only_in_the_sanitizer(self):
        pat = re.compile(r"threading\.(Lock|RLock|Condition)\(")
        offenders = []
        for path in iter_python_files([os.path.join(REPO, "tpudl")]):
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            if rel == "tpudl/testing/tsan.py":
                continue  # the sanitizer's internals stay raw (recursion)
            with open(path, encoding="utf-8") as f:
                if pat.search(f.read()):
                    offenders.append(rel)
        assert offenders == [], (
            "raw threading.Lock outside the sanitizer — use "
            "tsan.named_lock + a LockDecl: " + str(offenders))

    def test_declarations_are_wellformed(self):
        assert len({d.name for d in LOCKS}) == len(LOCKS)
        for d in LOCKS:
            assert d.kind in ("lock", "rlock", "condition")
            assert d.scope in ("module", "instance")
            assert d.guards
            assert d.module.startswith("tpudl.")
        # rank sanity: leaf metric locks above the registry lock
        assert lock_order("obs.metrics.counter") > \
            lock_order("obs.metrics.registry")
        assert lock_order("nope.such.lock") is None

    def test_concurrency_md_table_matches_registry(self):
        doc = open(os.path.join(REPO, "CONCURRENCY.md"),
                   encoding="utf-8").read()
        for line in render_lock_table().splitlines()[2:]:
            assert line in doc, f"CONCURRENCY.md missing lock row: {line}"

    def test_repo_graph_edges_respect_declared_ranks(self):
        # the declared order is not vestigial: every acquired-under
        # edge between two NAMED locks in the real tree climbs ranks
        g = build_lock_graph([os.path.join(REPO, "tpudl")], root=REPO)
        by_id = {s.lock_id: s for s in g.locks}
        for (a, b), w in g.edges.items():
            sa, sb = by_id.get(a), by_id.get(b)
            if sa is None or sb is None or not sa.name or not sb.name:
                continue
            ra, rb = lock_order(sa.name), lock_order(sb.name)
            assert rb > ra, (
                f"edge {sa.name} (rank {ra}) -> {sb.name} (rank {rb}) "
                f"violates the declared order at {w['file']}:{w['line']}")


# ---------------------------------------------------------------------------
# the repo self-lint (the sweep's acceptance)
# ---------------------------------------------------------------------------

class TestSelfLint:
    def test_repo_tree_concurrency_clean_and_fast(self):
        t0 = time.perf_counter()
        findings, errors = analyze_concurrency(CHECK_TARGETS, root=REPO)
        dt = time.perf_counter() - t0
        assert errors == []
        assert findings == [], "\n".join(f.render() for f in findings)
        assert dt < 30.0, f"concurrency analysis took {dt:.1f}s"


# ---------------------------------------------------------------------------
# the CLI additions: --rules and --json
# ---------------------------------------------------------------------------

class TestCLI:
    def _run(self, *args, cwd=REPO):
        return subprocess.run(
            [sys.executable, "-m", "tools.tpudl_check", *args],
            cwd=cwd, capture_output=True, text=True, timeout=120)

    @pytest.fixture
    def bad_tree(self, tmp_path):
        (tmp_path / "bad.py").write_text(
            "import os\n"
            "import threading\n"
            "import time\n"
            "V = os.environ.get('TPUDL_NOT_A_KNOB')\n"
            "LOCK = threading.Lock()\n"
            "def slow():\n"
            "    with LOCK:\n"
            "        time.sleep(1.0)\n")
        return tmp_path

    def test_rules_selects_one_rule(self, bad_tree):
        p = self._run("--rules", "undeclared-knob", str(bad_tree))
        assert p.returncode == 2
        assert "TPUDL_NOT_A_KNOB" in p.stderr
        assert "lock-held-blocking" not in p.stderr

    def test_rules_concurrency_only(self, bad_tree):
        p = self._run("--rules", "lock-held-blocking", str(bad_tree))
        assert p.returncode == 2
        assert "time.sleep" in p.stderr
        assert "TPUDL_NOT_A_KNOB" not in p.stderr

    def test_rules_filters_to_clean(self, bad_tree):
        p = self._run("--rules", "lock-order", str(bad_tree))
        assert p.returncode == 0

    def test_unknown_rule_id_is_rc1(self, bad_tree):
        # the suppression-typo contract: a typo must not gate nothing
        p = self._run("--rules", "lock-ordr", str(bad_tree))
        assert p.returncode == 1
        assert "unknown rule id" in p.stderr

    def test_json_findings_are_machine_readable(self, bad_tree):
        p = self._run("--json", str(bad_tree))
        assert p.returncode == 2
        doc = json.loads(p.stdout)
        assert doc["schema"] == "tpudl-check-findings"
        assert doc["files"] == 1
        rules = {f["rule"] for f in doc["findings"]}
        assert "undeclared-knob" in rules
        assert "lock-held-blocking" in rules
        for f in doc["findings"]:
            assert set(f) == {"file", "line", "col", "rule", "message",
                              "hint"}
            assert f["line"] >= 1

    def test_json_clean_tree_rc0(self, tmp_path):
        (tmp_path / "ok.py").write_text("X = 1\n")
        p = self._run("--json", str(tmp_path))
        assert p.returncode == 0
        assert json.loads(p.stdout)["findings"] == []

    def test_cross_module_resolution_is_cwd_independent(self, tmp_path):
        # absolute path args from an unrelated cwd: module identity is
        # package-derived, so the cross-module ABBA still resolves —
        # a cwd-relative fallback would report a false clean
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "locks.py").write_text(
            "import threading\n"
            "A = threading.Lock()\n"
            "B = threading.Lock()\n")
        (pkg / "one.py").write_text(
            "from pkg.locks import A, B\n"
            "def f():\n"
            "    with A:\n"
            "        with B:\n"
            "            pass\n")
        (pkg / "two.py").write_text(
            "from pkg.locks import A, B\n"
            "def g():\n"
            "    with B:\n"
            "        with A:\n"
            "            pass\n")
        p = self._run("--rules", "lock-order", str(pkg))
        assert p.returncode == 2, (p.stdout, p.stderr)
        assert "pkg.locks.A" in p.stderr and "pkg.locks.B" in p.stderr

    def test_list_rules_covers_both_halves(self):
        p = self._run("--list-rules")
        assert p.returncode == 0
        for rule in CONCURRENCY_RULES:
            assert rule in p.stdout
        assert "interprocedural" in p.stdout


# ---------------------------------------------------------------------------
# the runtime sanitizer, in-process
# ---------------------------------------------------------------------------

class TestTsanRuntime:
    def test_inversion_observed(self, armed):
        a = tsan.named_lock("fix.inv.a")
        b = tsan.named_lock("fix.inv.b")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        inv = [f for f in tsan.findings() if f["kind"] == "inversion"]
        assert len(inv) == 1
        assert set(inv[0]["edge"]) == {"fix.inv.a", "fix.inv.b"}

    def test_consistent_order_no_findings(self, armed):
        a = tsan.named_lock("fix.ok.a")
        b = tsan.named_lock("fix.ok.b")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert tsan.findings() == []

    def test_declared_order_violation(self, armed):
        # real registry names: counter is rank 30, registry rank 28 —
        # acquiring the LOWER rank while holding the higher violates
        # the declared order even before any inversion exists
        hi = tsan.named_lock("obs.metrics.counter")
        lo = tsan.named_lock("obs.metrics.registry")
        with hi:
            with lo:
                pass
        kinds = [f["kind"] for f in tsan.findings()]
        assert "declared-order" in kinds

    def test_self_deadlock_raises(self, armed):
        lk = tsan.named_lock("fix.self")
        with pytest.raises(tsan.DeadlockError):
            with lk:
                lk.acquire()
        kinds = [f["kind"] for f in tsan.findings()]
        assert "deadlock" in kinds

    def test_equal_rank_sibling_instances_nesting_flagged(self, armed):
        # two INSTANCES of one named per-instance class share a rank;
        # nesting them is a declared-order violation even though no
        # cross-name edge exists (the Heartbeat.beat() regression
        # class: the parent chain must re-arm one lock at a time)
        a = tsan.named_lock("obs.watchdog.heartbeat")
        b = tsan.named_lock("obs.watchdog.heartbeat")
        with a:
            with b:
                pass
        bad = [f for f in tsan.findings() if f["kind"] == "declared-order"]
        assert len(bad) == 1
        assert "equal-rank nesting" in bad[0]["message"]

    def test_equal_rank_different_names_nesting_flagged(self, armed):
        # strictly-higher-only: equal declared ranks never nest even
        # across different names (both registries are rank 24)
        a = tsan.named_lock("obs.metrics.registry")
        b = tsan.named_lock("obs.watchdog.registry")
        with a:
            with b:
                pass
        bad = [f for f in tsan.findings() if f["kind"] == "declared-order"]
        assert len(bad) == 1
        assert "equal ranks never nest" in bad[0]["message"]

    def test_failed_trylock_records_no_edge(self, armed):
        # `acquire(blocking=False)` backoff is the standard
        # deadlock-AVOIDANCE idiom: an acquisition that never happened
        # must not put an edge in the order graph or fire findings
        a = tsan.named_lock("obs.metrics.registry")
        b = tsan.named_lock("obs.metrics.counter")
        holder_has_b = threading.Event()
        release_b = threading.Event()

        def holder():
            with b:
                holder_has_b.set()
                release_b.wait(timeout=10)

        t = threading.Thread(target=holder)
        t.start()
        holder_has_b.wait(timeout=5)
        with a:
            assert b.acquire(blocking=False) is False  # backoff
        release_b.set()
        t.join(timeout=5)
        assert tsan.findings() == []
        assert all(e["from"] != "obs.metrics.registry"
                   for e in tsan.report()["edges"])
        with a:  # a SUCCESSFUL nested acquire still notes the edge
            with b:
                pass
        assert any(e["from"] == "obs.metrics.registry" and
                   e["to"] == "obs.metrics.counter"
                   for e in tsan.report()["edges"])

    def test_trylock_by_own_holder_returns_false(self, armed):
        # only an UNBOUNDED blocking reacquire is a guaranteed hang: a
        # non-blocking/bounded probe by the holder must behave like
        # the plain lock (stdlib Condition's _is_owned probes this way)
        lk = tsan.named_lock("fix.probe")
        with lk:
            assert lk.acquire(blocking=False) is False
            assert lk.acquire(True, 0.01) is False
        assert tsan.findings() == []

    def test_condition_wrapping_a_named_lock_works_armed(self, armed):
        # the pattern _check_kind's error message recommends
        cv = threading.Condition(tsan.named_lock("fix.cv"))
        with cv:
            cv.notify_all()
            assert cv.wait(timeout=0.01) is False
        assert tsan.findings() == []

    def test_disarm_mid_hold_does_not_leak_held_entry(self, armed):
        # disarm() between acquire and release must still clean the
        # per-thread held list: a stale entry tripped a spurious
        # self-deadlock on the next armed acquisition
        lk = tsan.named_lock("fix.disarm")
        lk.acquire()
        tsan.disarm()
        lk.release()
        tsan.ENABLED = True  # re-arm the SAME state (no reset)
        with lk:  # must not raise DeadlockError
            pass
        assert [f for f in tsan.findings()
                if f["kind"] == "deadlock"] == []

    def test_condition_kind_is_rejected_loudly(self, armed):
        # a silent plain-Lock stand-in would AttributeError at the
        # first wait()/notify() — in production, on the unarmed path
        with pytest.raises(ValueError, match="condition"):
            tsan.named_lock("fix.cond", kind="condition")
        tsan.disarm()
        try:
            with pytest.raises(ValueError, match="condition"):
                tsan.named_lock("fix.cond", kind="condition")
        finally:
            tsan.ENABLED = True

    def test_rlock_reentry_is_fine(self, armed):
        r = tsan.named_lock("fix.re", kind="rlock")
        with r:
            with r:
                pass
        assert tsan.findings() == []

    def test_slow_holder_is_not_a_deadlock(self, armed, monkeypatch):
        monkeypatch.setenv("TPUDL_TSAN_DEADLOCK_S", "0.1")
        lk = tsan.named_lock("fix.slow")
        started = threading.Event()

        def holder():
            with lk:
                started.set()
                time.sleep(0.4)

        t = threading.Thread(target=holder)
        t.start()
        started.wait(timeout=5)
        with lk:  # waits past several slices, then succeeds
            pass
        t.join(timeout=5)
        assert [f for f in tsan.findings()
                if f["kind"] == "deadlock"] == []

    def test_lockset_identity_check_catches_sibling_instance(self, armed):
        # holding a SIBLING instance's lock of the same registry name
        # must NOT satisfy an identity-checked lockset probe — that is
        # the cross-instance race the check exists to catch
        a = tsan.named_lock("obs.metrics.registry")
        b = tsan.named_lock("obs.metrics.registry")
        with a:
            tsan.check_guarded("obs.metrics.registry", "map", lock=a)
        assert [f for f in tsan.findings()
                if f["kind"] == "lockset"] == []
        with a:
            tsan.check_guarded("obs.metrics.registry", "map", lock=b)
        bad = [f for f in tsan.findings() if f["kind"] == "lockset"]
        assert len(bad) == 1

    def test_check_guarded_muted_during_reporting_hop(self, armed):
        """The sanitizer never reports its own reporting path:
        _file_finding's metrics hop may REGISTER a fresh tsan.* counter
        (mutating the metrics registry map) while the registry's guard
        is a pre-arming plain Lock that held() cannot see — that probe
        must be muted, or the process's FIRST lockset finding grows a
        spurious metrics-registry sibling (order-dependent: whichever
        test module armed the registry first)."""
        st = tsan._state()
        tsan.named_lock("obs.metrics.registry")  # name known to st
        st.tls.reporting = True
        try:
            tsan.check_guarded("obs.metrics.registry", "map")
        finally:
            st.tls.reporting = False
        assert [f for f in tsan.findings()
                if f["kind"] == "lockset"] == []
        # and outside the hop the same probe still fires
        tsan.check_guarded("obs.metrics.registry", "map")
        assert len([f for f in tsan.findings()
                    if f["kind"] == "lockset"]) == 1

    def test_lockset_violation_and_pass(self, armed):
        lk = tsan.named_lock("fix.guard")
        with lk:
            tsan.check_guarded("fix.guard", "guarded structure")
        assert tsan.findings() == []
        tsan.check_guarded("fix.guard", "guarded structure")
        bad = [f for f in tsan.findings() if f["kind"] == "lockset"]
        assert len(bad) == 1
        assert "without holding" in bad[0]["message"]

    def test_product_lockset_checks_fire_when_unguarded(self, armed):
        # the real wiring: mutating the pipeline ring without its
        # declared guard is flagged (check_guarded at the product site)
        tsan.named_lock("obs.pipeline.ring")  # registers the guard name
        tsan.check_guarded("obs.pipeline.ring", "pipeline-report ring")
        bad = [f for f in tsan.findings() if f["kind"] == "lockset"]
        assert len(bad) == 1

    def test_report_schema_and_atomic_write(self, armed, tmp_path):
        a = tsan.named_lock("fix.rep.a")
        with a:
            pass
        out = tsan.write_report(str(tmp_path / "t.json"))
        assert out is not None
        rep = json.loads(open(out, encoding="utf-8").read())
        assert rep["schema"] == "tpudl-tsan-report"
        assert rep["armed"] is True
        assert "fix.rep.a" in rep["locks_seen"]
        assert rep["hold_times"]["fix.rep.a"]["n"] == 1
        assert list(tmp_path.glob("*.tmp.*")) == []

    def test_hold_times_accumulate(self, armed):
        lk = tsan.named_lock("fix.hold")
        with lk:
            time.sleep(0.05)
        rep = tsan.report()
        h = rep["hold_times"]["fix.hold"]
        assert h["n"] == 1 and h["max_s"] >= 0.04


# ---------------------------------------------------------------------------
# the unarmed fast path (<5% overhead guard)
# ---------------------------------------------------------------------------

class TestUnarmedOverhead:
    @pytest.fixture
    def unarmed(self):
        prev = tsan.ENABLED
        tsan.disarm()
        yield
        tsan.ENABLED = prev

    def test_unarmed_named_lock_is_a_plain_lock(self, unarmed):
        # the strongest possible guarantee: not "cheap wrapper", but
        # literally the stdlib type — zero added bytes per acquisition
        assert type(tsan.named_lock("obs.pipeline.ring")) \
            is type(threading.Lock())
        assert type(tsan.named_lock("x", kind="rlock")) \
            is type(threading.RLock())

    def test_unarmed_acquisitions_leave_no_record(self, unarmed):
        # the unarmed lock IS the stdlib type (the test above), so a
        # timing of one against the other measures the machine. What
        # the unarmed path must not do is book-keep: nested
        # acquisitions in both orders and an unguarded-mutation probe,
        # each of which the armed sanitizer records, leave its report
        # as it was.
        def records():
            rep = tsan.report()
            return {k: rep[k] for k in ("findings", "edges",
                                        "locks_seen", "hold_times")}

        def inversion_and_unguarded_probe():
            a = tsan.named_lock("obs.pipeline.ring")
            b = tsan.named_lock("obs.metrics.registry")
            for _ in range(100):
                with a, b:
                    pass
                with b, a:
                    pass
                tsan.check_guarded("obs.pipeline.ring", "ring")

        before = records()
        inversion_and_unguarded_probe()
        assert records() == before
        tsan.arm()  # the same calls, armed, are all recorded
        try:
            tsan.reset()
            inversion_and_unguarded_probe()
            armed = records()
        finally:
            tsan.disarm()
            tsan.reset()
        assert {"obs.pipeline.ring",
                "obs.metrics.registry"} <= set(armed["locks_seen"])
        assert armed["edges"] and armed["hold_times"]
        assert {f["kind"] for f in armed["findings"]} >= {"inversion",
                                                          "lockset"}

    def test_unarmed_check_guarded_is_one_flag_read(self, unarmed):
        t0 = time.perf_counter()
        for _ in range(200000):
            tsan.check_guarded("obs.pipeline.ring", "ring")
        dt = time.perf_counter() - t0
        # 200k disarmed checks in well under a second: nothing beyond
        # the ENABLED read happens on the unarmed path
        assert dt < 1.0, f"200k unarmed check_guarded took {dt:.2f}s"


# ---------------------------------------------------------------------------
# the Heartbeat.beat() race regression (the sweep's known race)
# ---------------------------------------------------------------------------

class TestHeartbeatRace:
    def test_beat_vs_snapshotting_readers(self):
        from tpudl.obs import watchdog as wd

        reg = wd.HeartbeatRegistry()
        stop = threading.Event()
        errors: list = []
        with reg.start("outer") as parent, \
                reg.start("hammer", n=-1) as hb:
            assert hb.parent is parent  # the chain the writer re-arms

            def writer():
                i = 0
                while not stop.is_set():
                    # beats and info["n"] move together under _iflock:
                    # a reader must never observe one without the other
                    # (pre-fix, the two assignments interleaved)
                    hb.beat(n=i, **{f"k{i % 53}": i})
                    i += 1

            def reader():
                try:
                    while not stop.is_set():
                        d = hb.describe()
                        json.dumps(d["info"])
                        if "n" in d["info"]:
                            # the atomic-pair invariant: beat() sets
                            # beats and n in ONE critical section (the
                            # pre-fix code interleaved them)
                            assert d["beats"] == d["info"]["n"] + 1, d
                        assert d["age_s"] >= -0.01
                        reg.describe()  # the status writer's view
                except Exception as e:  # noqa: BLE001 - reported below
                    errors.append(e)

            threads = [threading.Thread(target=writer)] + \
                [threading.Thread(target=reader) for _ in range(2)]
            for t in threads:
                t.start()
            time.sleep(0.6)
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert errors == [], errors

    def test_inflight_age_clamped_nonnegative(self):
        # a stage_enter() can land between a reader's `now` capture
        # and inflight()'s lock acquisition — ages are clamped just
        # like describe()'s age_s (a status consumer may assume >= 0)
        from tpudl.obs import watchdog as wd

        reg = wd.HeartbeatRegistry()
        with reg.start("hb") as hb:
            hb.stage_enter("prepare")
            try:
                snap = hb.inflight(now=0.0)  # `now` before t0
                assert snap["prepare"]["age_s"] == 0.0
                d = hb.describe()
                assert d["in_flight"]["prepare"]["age_s"] >= 0.0
            finally:
                hb.stage_exit("prepare")

    def test_parent_chain_rearm_under_hammer(self):
        from tpudl.obs import watchdog as wd

        reg = wd.HeartbeatRegistry()
        with reg.start("parent") as parent, reg.start("child") as child:
            parent.last_beat -= 100.0  # parent looks long-stalled
            child.beat(step=1)
            assert parent.age() < 1.0  # child progress re-armed it

    def test_watchdog_scan_uses_locked_snapshot(self):
        from tpudl.obs import watchdog as wd

        reg = wd.HeartbeatRegistry()
        dog = wd.Watchdog(reg, stall_s=0.05, interval=10.0)
        stop = threading.Event()
        with reg.start("stally", phase="warm") as hb:
            def mutate():
                i = 0
                while not stop.is_set():
                    hb.info[f"m{i % 29}"] = i  # daemon-side dict churn
                    i += 1

            t = threading.Thread(target=mutate, daemon=True)
            t.start()
            try:
                time.sleep(0.1)  # age past stall_s while info churns
                for _ in range(50):
                    hb.stalled = False
                    flagged = dog.scan()
                    if flagged:
                        assert flagged[0]["name"] == "stally"
            finally:
                stop.set()
                t.join(timeout=5)


# ---------------------------------------------------------------------------
# the armed pass itself: product structures under TPUDL_TSAN=1
# ---------------------------------------------------------------------------

class TestArmedProductFlow:
    def test_metrics_and_rings_clean_under_armed_sanitizer(self, armed):
        # fresh instrumented instances of the registered structures,
        # driven through their public APIs: the declared guards hold,
        # so the sanitizer stays silent
        from tpudl.obs.metrics import MetricsRegistry
        from tpudl.obs.pipeline import PipelineReport

        m = MetricsRegistry()
        m.counter("train.steps").inc()
        m.gauge("train.last_step").set(3)
        m.histogram("train.step_seconds").observe(0.01)
        r = PipelineReport()
        with r.stage("prepare"):
            pass
        r.progress(4)
        bad = [f for f in tsan.findings()
               if f["kind"] in ("lockset", "inversion", "deadlock")]
        assert bad == [], bad
