"""tpudl.analysis: the AST invariant checker, the knob/metric
registries, and the tools/tpudl_check.py CLI (ANALYSIS.md).

Four layers, mirroring the other validator suites:

1. per-rule fixtures — every rule is proven LIVE by a positive snippet
   that fires it, kept honest by a negative snippet that doesn't, and
   a suppression snippet that silences it (with the required reason);
2. the self-lint — the repo's own tree is clean, which is the
   acceptance criterion (`python -m tools.tpudl_check tpudl tools`
   exits 0);
3. registry round-trips — every declared knob/metric is used, every
   used one is declared (deleting a knob's last read without deleting
   its declaration fails here, and vice versa);
4. the CLI contract — exit 0 clean / 2 findings / 1 error, importable
   like the five runtime validators, and under the 20 s budget of a
   gate that runs before every test run.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from tpudl.analysis import (RULES, check_paths, check_source,
                            collect_usage, is_declared_metric,
                            KNOB_NAMES, KNOBS, METRIC_NAMES,
                            render_knob_table, render_metric_table,
                            unknown_metric_names)
from tpudl.analysis.metric_names import matches_pattern_prefix

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_TARGETS = [os.path.join(REPO, "tpudl"), os.path.join(REPO, "tools")]


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "tpudl_check", os.path.join(REPO, "tools", "tpudl_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rules_of(src: str, relpath: str = "pkg/mod.py") -> list[str]:
    return [f.rule for f in check_source(src, relpath, relpath)]


def only(src: str, rule: str, relpath: str = "pkg/mod.py"):
    """Findings of one rule (the fixture may legitimately trip none)."""
    return [f for f in check_source(src, relpath, relpath)
            if f.rule == rule]


# ---------------------------------------------------------------------------
# rule: hot-sync
# ---------------------------------------------------------------------------

class TestHotSync:
    def test_marked_function_block_until_ready_fires(self):
        src = (
            "def drain(x):  # tpudl: hot-path\n"
            "    import jax\n"
            "    jax.block_until_ready(x)\n")
        fs = only(src, "hot-sync")
        assert len(fs) == 1 and fs[0].line == 3
        assert "block_until_ready" in fs[0].message

    def test_stage_block_asarray_fires(self):
        src = (
            "import numpy as np\n"
            "def run(report, arr):\n"
            "    with report.stage('dispatch'):\n"
            "        h = np.asarray(arr)\n"
            "    return h\n")
        fs = only(src, "hot-sync")
        assert len(fs) == 1 and fs[0].line == 4

    def test_item_and_device_get_fire(self):
        src = (
            "def step(loss):  # tpudl: hot-path\n"
            "    import jax\n"
            "    a = loss.item()\n"
            "    b = jax.device_get(loss)\n"
            "    return a, b\n")
        assert [f.line for f in only(src, "hot-sync")] == [3, 4]

    def test_future_result_in_dispatch_stage_fires(self):
        """ISSUE 10: a bare .result() on an in-flight future inside
        ``report.stage('dispatch')`` blocks the dispatch loop exactly
        like block_until_ready — the async-window helpers must wait in
        their own (non-hot) dispatch_wait stage instead."""
        src = (
            "def run(report, futs):\n"
            "    with report.stage('dispatch'):\n"
            "        out = futs.popleft().result()\n"
            "    return out\n")
        fs = only(src, "hot-sync")
        assert len(fs) == 1 and fs[0].line == 3
        assert ".result()" in fs[0].message

    def test_future_wait_in_hot_marked_fn_fires(self):
        src = (
            "def drain(fut):  # tpudl: hot-path\n"
            "    fut.wait()\n")
        fs = only(src, "hot-sync")
        assert len(fs) == 1 and ".wait()" in fs[0].message

    def test_result_in_dispatch_wait_stage_is_clean(self):
        """The executor's own window wait lives in ``dispatch_wait`` —
        deliberately NOT a hot stage (it IS the accounted residue)."""
        src = (
            "def pop(report, futs):\n"
            "    with report.stage('dispatch_wait'):\n"
            "        return futs.popleft().result()\n")
        assert only(src, "hot-sync") == []

    def test_result_with_timeout_arg_is_clean(self):
        """.result(timeout)/.wait(timeout) are bounded probes, not the
        unbounded block the rule targets."""
        src = (
            "def run(report, fut, ev):\n"
            "    with report.stage('dispatch'):\n"
            "        a = fut.result(5.0)\n"
            "        b = ev.wait(timeout=1.0)\n"
            "    return a, b\n")
        assert only(src, "hot-sync") == []

    def test_result_suppressible_with_reason(self):
        src = (
            "def run(report, fut):\n"
            "    with report.stage('dispatch'):\n"
            "        return fut.result()  "
            "# tpudl: ignore[hot-sync] — drain IS this stage's point\n")
        assert only(src, "hot-sync") == []

    def test_cold_function_is_clean(self):
        src = (
            "import numpy as np\n"
            "def summarize(x):\n"
            "    return np.asarray(x).sum()\n")
        assert only(src, "hot-sync") == []

    def test_prepare_stage_is_not_hot(self):
        src = (
            "import numpy as np\n"
            "def run(report, arr):\n"
            "    with report.stage('prepare'):\n"
            "        return np.asarray(arr)\n")
        assert only(src, "hot-sync") == []

    def test_nested_def_does_not_inherit_hot(self):
        src = (
            "def outer():  # tpudl: hot-path\n"
            "    import numpy as np\n"
            "    def pack(b):\n"
            "        return np.asarray(b)\n"
            "    return pack\n")
        assert only(src, "hot-sync") == []

    def test_inline_suppression_with_reason(self):
        src = (
            "import numpy as np\n"
            "def drain(r):  # tpudl: hot-path\n"
            "    return np.asarray(r)  "
            "# tpudl: ignore[hot-sync] — this fetch IS the d2h stage\n")
        assert only(src, "hot-sync") == []

    def test_suppression_line_above(self):
        src = (
            "import numpy as np\n"
            "def drain(r):  # tpudl: hot-path\n"
            "    # tpudl: ignore[hot-sync] — this fetch IS d2h\n"
            "    return np.asarray(r)\n")
        assert only(src, "hot-sync") == []


# ---------------------------------------------------------------------------
# rule: atomic-write
# ---------------------------------------------------------------------------

class TestAtomicWrite:
    def test_open_w_durable_path_fires(self):
        src = (
            "import json\n"
            "def save(d, m):\n"
            "    with open(d + '/manifest.json', 'w') as f:\n"
            "        json.dump(m, f)\n")
        fs = only(src, "atomic-write")
        assert len(fs) == 1 and fs[0].line == 3
        assert "os.replace" in fs[0].hint

    def test_np_save_checkpoint_fires(self):
        src = (
            "import numpy as np\n"
            "def save(d, arr):\n"
            "    np.save(d + '/checkpoint.npy', arr)\n")
        assert len(only(src, "atomic-write")) == 1

    def test_tmp_plus_replace_idiom_is_clean(self):
        src = (
            "import json, os\n"
            "def save(path, m):\n"
            "    tmp = path + '.tmp.%d' % os.getpid()\n"
            "    with open(tmp, 'w') as f:\n"
            "        json.dump(m, f)\n"
            "    os.replace(tmp, path)\n")
        assert only(src, "atomic-write") == []

    def test_non_durable_path_is_clean(self):
        src = (
            "def note(d):\n"
            "    with open(d + '/notes.txt', 'w') as f:\n"
            "        f.write('hi')\n")
        assert only(src, "atomic-write") == []

    def test_read_mode_is_clean(self):
        src = (
            "import json\n"
            "def load(d):\n"
            "    with open(d + '/manifest.json') as f:\n"
            "        return json.load(f)\n")
        assert only(src, "atomic-write") == []

    def test_suppression(self):
        src = (
            "import json\n"
            "def save(d, m):\n"
            "    # tpudl: ignore[atomic-write] — scratch file, torn OK\n"
            "    with open(d + '/manifest.json', 'w') as f:\n"
            "        json.dump(m, f)\n")
        assert only(src, "atomic-write") == []


# ---------------------------------------------------------------------------
# rule: signal-handler
# ---------------------------------------------------------------------------

class TestSignalHandler:
    def test_nontrivial_handler_fires(self):
        src = (
            "import signal\n"
            "def cleanup():\n"
            "    pass\n"
            "def install():\n"
            "    def handler(signum, frame):\n"
            "        cleanup()\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        fs = only(src, "signal-handler")
        assert len(fs) == 1 and fs[0].line == 6
        assert "signal context" in fs[0].message

    def test_flag_only_handler_is_clean(self):
        src = (
            "import signal\n"
            "_STOP = False\n"
            "def install():\n"
            "    def handler(signum, frame):\n"
            "        global _STOP\n"
            "        _STOP = True\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-handler") == []

    def test_chaining_and_os_write_are_clean(self):
        src = (
            "import os, signal\n"
            "def install(prev):\n"
            "    def handler(signum, frame, _prev=prev):\n"
            "        os.write(2, b'sig\\n')\n"
            "        _prev(signum, frame)\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-handler") == []

    def test_allowlist_is_dotted_not_bare_attr(self):
        # logfile.write()/pool.kill() must NOT ride the os.* pass: a
        # buffered .write() takes interpreter/IO locks in signal
        # context — the exact hazard this rule exists to catch
        src = (
            "import signal\n"
            "def install(logfile, pool):\n"
            "    def handler(signum, frame):\n"
            "        logfile.write('dying')\n"
            "        pool.kill()\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert [f.line for f in only(src, "signal-handler")] == [4, 5]

    def test_event_set_flag_idiom_is_clean(self):
        src = (
            "import signal, threading\n"
            "_STOP = threading.Event()\n"
            "def install():\n"
            "    def handler(signum, frame):\n"
            "        _STOP.set()\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-handler") == []

    def test_suppression_on_def_covers_handler_body(self):
        src = (
            "import signal\n"
            "def dump():\n"
            "    pass\n"
            "def install():\n"
            "    # tpudl: ignore[signal-handler] — dump() runs on a\n"
            "    # bounded worker thread, then the process exits\n"
            "    def handler(signum, frame):\n"
            "        dump()\n"
            "    signal.signal(signal.SIGTERM, handler)\n")
        assert only(src, "signal-handler") == []


# ---------------------------------------------------------------------------
# rule: adhoc-retry
# ---------------------------------------------------------------------------

class TestAdhocRetry:
    def test_sleep_in_except_fires(self):
        src = (
            "import time\n"
            "def fetch(read, log):\n"
            "    for i in range(3):\n"
            "        try:\n"
            "            return read()\n"
            "        except OSError as e:\n"
            "            log(e)\n"
            "            time.sleep(2 ** i)\n")
        fs = only(src, "adhoc-retry")
        assert len(fs) == 1 and fs[0].line == 8
        assert "RetryPolicy" in fs[0].hint

    def test_sleep_in_try_inside_loop_fires(self):
        src = (
            "import time\n"
            "def poll(ready):\n"
            "    while True:\n"
            "        try:\n"
            "            if ready():\n"
            "                return\n"
            "            time.sleep(0.1)\n"
            "        except OSError as e:\n"
            "            raise e\n")
        assert len(only(src, "adhoc-retry")) == 1

    def test_plain_pacing_sleep_is_clean(self):
        src = (
            "import time\n"
            "def warmup():\n"
            "    time.sleep(0.5)\n")
        assert only(src, "adhoc-retry") == []

    def test_retry_module_itself_is_exempt(self):
        src = (
            "import time\n"
            "def call(fn):\n"
            "    for i in range(3):\n"
            "        try:\n"
            "            return fn()\n"
            "        except OSError as e:\n"
            "            raise e\n"
            "            time.sleep(1)\n")
        assert only(src, "adhoc-retry",
                    relpath="tpudl/jobs/retry.py") == []

    def test_suppression(self):
        src = (
            "import time\n"
            "def restart(log):\n"
            "    for i in range(3):\n"
            "        try:\n"
            "            return 1\n"
            "        except OSError as e:\n"
            "            log(e)\n"
            "            # tpudl: ignore[adhoc-retry] — pacing comes\n"
            "            # from the shared RetryPolicy\n"
            "            time.sleep(1)\n")
        assert only(src, "adhoc-retry") == []


# ---------------------------------------------------------------------------
# rule: swallowed-except
# ---------------------------------------------------------------------------

class TestSwallowedExcept:
    def test_bare_except_fires(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    except:\n"
            "        pass\n")
        fs = only(src, "swallowed-except")
        assert len(fs) == 1 and "bare except" in fs[0].message

    def test_broad_silent_except_fires(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass\n")
        fs = only(src, "swallowed-except")
        assert len(fs) == 1 and "swallows silently" in fs[0].message

    def test_narrow_except_is_clean(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    except ValueError:\n"
            "        pass\n")
        assert only(src, "swallowed-except") == []

    def test_breadcrumb_call_is_clean(self):
        src = (
            "def f(g, log):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception as e:\n"
            "        log(e)\n")
        assert only(src, "swallowed-except") == []

    def test_reraise_is_clean(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        raise\n")
        assert only(src, "swallowed-except") == []

    def test_suppression(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    # tpudl: ignore[swallowed-except] — best-effort probe\n"
            "    except Exception:\n"
            "        pass\n")
        assert only(src, "swallowed-except") == []


# ---------------------------------------------------------------------------
# rule: undeclared-knob
# ---------------------------------------------------------------------------

class TestUndeclaredKnob:
    def test_unknown_knob_fires(self):
        src = ("import os\n"
               "v = os.environ.get('TPUDL_NOT_A_REAL_KNOB', '')\n")
        fs = only(src, "undeclared-knob")
        assert len(fs) == 1
        assert "TPUDL_NOT_A_REAL_KNOB" in fs[0].message
        assert "knobs.py" in fs[0].hint

    def test_declared_knob_is_clean(self):
        src = ("import os\n"
               "v = os.environ.get('TPUDL_WIRE_CODEC', '')\n")
        assert only(src, "undeclared-knob") == []

    def test_docstring_mention_is_clean(self):
        src = ('def f():\n'
               '    """Honors TPUDL_TOTALLY_UNDECLARED when set."""\n')
        assert only(src, "undeclared-knob") == []

    def test_registry_module_itself_is_exempt(self):
        src = "KNOB = 'TPUDL_SOME_NEW_THING'\n"
        assert only(src, "undeclared-knob",
                    relpath="tpudl/analysis/knobs.py") == []

    def test_suppression(self):
        src = ("import os\n"
               "# tpudl: ignore[undeclared-knob] — test-only escape\n"
               "v = os.environ.get('TPUDL_NOT_A_REAL_KNOB', '')\n")
        assert only(src, "undeclared-knob") == []


# ---------------------------------------------------------------------------
# rule: undeclared-metric
# ---------------------------------------------------------------------------

class TestUndeclaredMetric:
    def test_unknown_literal_fires(self):
        src = ("from tpudl.obs import metrics\n"
               "metrics.counter('nope.not.declared').inc()\n")
        fs = only(src, "undeclared-metric")
        assert len(fs) == 1 and "nope.not.declared" in fs[0].message

    def test_declared_literal_is_clean(self):
        src = ("from tpudl.obs import metrics\n"
               "metrics.counter('data.cache.hits').inc()\n")
        assert only(src, "undeclared-metric") == []

    def test_declared_fstring_family_is_clean(self):
        src = ("from tpudl.obs import metrics\n"
               "def bump(name):\n"
               "    metrics.counter(f'frame.stage.{name}.seconds')"
               ".inc()\n")
        assert only(src, "undeclared-metric") == []

    def test_unknown_fstring_family_fires(self):
        src = ("from tpudl.obs import metrics\n"
               "def bump(name):\n"
               "    metrics.counter(f'nope.{name}.things').inc()\n")
        fs = only(src, "undeclared-metric")
        assert len(fs) == 1 and "nope.*" in fs[0].message

    def test_subfamily_under_declared_pattern_is_clean(self):
        # f"retry.io.{op}" expands only to names the declared retry.*
        # pattern already covers — no redundant registry entry needed
        src = ("from tpudl.obs import metrics\n"
               "def bump(op):\n"
               "    metrics.counter(f'retry.io.{op}').inc()\n")
        assert only(src, "undeclared-metric") == []

    def test_fully_dynamic_name_is_plumbing(self):
        # obs-internal helpers pass the name through a variable; the
        # declaration site is the caller's literal, not the plumbing
        src = ("from tpudl.obs import metrics\n"
               "def bump(name):\n"
               "    metrics.counter(name).inc()\n")
        assert only(src, "undeclared-metric") == []

    def test_suppression(self):
        src = ("from tpudl.obs import metrics\n"
               "# tpudl: ignore[undeclared-metric] — fixture metric\n"
               "metrics.counter('nope.not.declared').inc()\n")
        assert only(src, "undeclared-metric") == []


# ---------------------------------------------------------------------------
# rule: unlocked-global
# ---------------------------------------------------------------------------

class TestUnlockedGlobal:
    def test_unlocked_rebind_in_threaded_module_fires(self):
        src = (
            "import threading\n"
            "_STATE = None\n"
            "def start(run):\n"
            "    global _STATE\n"
            "    t = threading.Thread(target=run)\n"
            "    t.start()\n"
            "    _STATE = t\n")
        fs = only(src, "unlocked-global")
        assert len(fs) == 1 and "_STATE" in fs[0].message

    def test_tuple_target_rebind_fires(self):
        # `_A, _B = a, b` rebinds both globals just as racily as the
        # single-name form — the swap idiom must not slip through
        src = (
            "import threading\n"
            "_A = _B = None\n"
            "def start(run):\n"
            "    global _A, _B\n"
            "    threading.Thread(target=run).start()\n"
            "    _A, _B = run, None\n")
        fs = only(src, "unlocked-global")
        assert len(fs) == 1 and "_A" in fs[0].message

    def test_locked_rebind_is_clean(self):
        src = (
            "import threading\n"
            "_LOCK = threading.Lock()\n"
            "_STATE = None\n"
            "def start(run):\n"
            "    global _STATE\n"
            "    threading.Thread(target=run).start()\n"
            "    with _LOCK:\n"
            "        _STATE = 1\n")
        assert only(src, "unlocked-global") == []

    def test_unthreaded_module_is_clean(self):
        src = (
            "_STATE = None\n"
            "def set_state(v):\n"
            "    global _STATE\n"
            "    _STATE = v\n")
        assert only(src, "unlocked-global") == []

    def test_locked_suffix_contract_is_clean(self):
        src = (
            "import threading\n"
            "_STATE = None\n"
            "def _reset_locked(run):\n"
            "    global _STATE\n"
            "    threading.Thread(target=run).start()\n"
            "    _STATE = None\n")
        assert only(src, "unlocked-global") == []

    def test_suppression(self):
        src = (
            "import threading\n"
            "_STATE = None\n"
            "def start(run):\n"
            "    global _STATE\n"
            "    threading.Thread(target=run).start()\n"
            "    # tpudl: ignore[unlocked-global] — single writer\n"
            "    _STATE = run\n")
        assert only(src, "unlocked-global") == []


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------

class TestSuppressionContract:
    def test_reasonless_ignore_is_itself_a_finding(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:  # tpudl: ignore[swallowed-except]\n"
            "        pass\n")
        fs = check_source(src, "pkg/mod.py", "pkg/mod.py")
        assert len(fs) == 1
        assert "required reason" in fs[0].message

    def test_unknown_rule_id_is_flagged(self):
        src = "x = 1  # tpudl: ignore[no-such-rule] — whatever\n"
        fs = check_source(src, "pkg/mod.py", "pkg/mod.py")
        assert len(fs) == 1 and fs[0].rule == "bad-suppression"

    def test_typod_rule_id_does_not_suppress_anything(self):
        # an all-unknown ignore must NOT become a suppress-everything:
        # the line's genuine finding stays visible next to the
        # bad-suppression pointing at the typo
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    # tpudl: ignore[swallowedexcept] — typo'd rule id\n"
            "    except Exception:\n"
            "        pass\n")
        rules = sorted(f.rule for f in check_source(src, "p.py", "p.py"))
        assert rules == ["bad-suppression", "swallowed-except"]

    def test_mixed_known_unknown_suppresses_only_the_known(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    # tpudl: ignore[swallowed-except, bogus-rule] — probe\n"
            "    except Exception:\n"
            "        pass\n")
        rules = [f.rule for f in check_source(src, "p.py", "p.py")]
        assert rules == ["bad-suppression"]  # the real finding IS hidden

    def test_suppression_is_rule_scoped(self):
        # an ignore[adhoc-retry] must NOT silence a swallowed-except
        # on the same line
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    # tpudl: ignore[adhoc-retry] — wrong rule\n"
            "    except Exception:\n"
            "        pass\n")
        assert [f.rule for f in check_source(src, "p.py", "p.py")] == \
            ["swallowed-except"]

    def test_every_rule_has_hint_and_description(self):
        assert set(RULES) == {
            "hot-sync", "atomic-write", "signal-handler", "adhoc-retry",
            "swallowed-except", "undeclared-knob", "undeclared-metric",
            "unlocked-global",
            # the interprocedural concurrency rules (CONCURRENCY.md)
            "lock-order", "lock-held-blocking", "signal-lock",
            "daemon-shared-write",
            # the jit-boundary trace rules (ANALYSIS.md, traceguard)
            "trace-time-effect", "host-op-on-traced", "traced-branch",
            "donation-reuse", "jit-cache-churn",
            # the gate's suppression self-audit (tools/tpudl_check.py)
            "stale-suppression"}
        for rule, desc in RULES.items():
            assert desc, rule


# ---------------------------------------------------------------------------
# the self-lint: the acceptance criterion
# ---------------------------------------------------------------------------

class TestSelfLint:
    def test_repo_tree_is_clean_and_fast(self):
        t0 = time.perf_counter()
        findings, errors = check_paths(CHECK_TARGETS, root=REPO)
        dt = time.perf_counter() - t0
        assert errors == []
        assert findings == [], "\n".join(f.render() for f in findings)
        # the CI budget: the gate runs before every test run
        assert dt < 20.0, f"self-lint took {dt:.1f}s (budget 20s)"

    def test_registries_round_trip(self):
        cli = _load_cli()
        drift = cli.registry_audit(CHECK_TARGETS, root=REPO)
        assert drift == [], "\n".join(drift)

    def test_knob_declarations_do_not_self_count_as_uses(self):
        # the registry file's own literals are declarations, not reads:
        # counting them would make 'declared but never read' dead code
        usage = collect_usage(
            [os.path.join(REPO, "tpudl", "analysis", "knobs.py")],
            root=REPO)
        assert usage["knobs"] == set()

    def test_usage_scan_sees_known_anchors(self):
        usage = collect_usage(CHECK_TARGETS, root=REPO)
        # anchors that existed for several PRs: the scan itself works
        assert "TPUDL_WIRE_CODEC" in usage["knobs"]
        assert "TPUDL_WATCHDOG_STALL_S" in usage["knobs"]
        assert "data.cache.hits" in usage["metrics"]
        assert "train.steps" in usage["metrics"]
        assert ("frame.stage.", ".seconds") in usage["metric_patterns"]


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

class TestRegistries:
    def test_knob_names_are_schema_shaped(self):
        assert KNOB_NAMES
        for k in KNOBS:
            assert k.name.startswith("TPUDL_")
            assert k.kind in ("int", "float", "bool", "str", "enum",
                              "path", "json")
            assert k.subsystem in ("frame", "data", "obs", "jobs",
                                   "train", "zoo", "compile", "serve",
                                   "text")
            assert k.help
        assert len(KNOB_NAMES) == len(KNOBS)  # no duplicate names

    def test_metric_declarations_are_wellformed(self):
        assert METRIC_NAMES
        assert is_declared_metric("data.cache.hits")
        assert is_declared_metric("frame.stage.dispatch.seconds")
        assert not is_declared_metric("nope.not.declared")
        assert matches_pattern_prefix("frame.stage.", ".seconds")
        assert not matches_pattern_prefix("nope.", ".things")
        assert unknown_metric_names(
            ["train.steps", "bogus.metric"]) == ["bogus.metric"]

    def test_rendered_tables_cover_the_registries(self):
        ktable = render_knob_table()
        for k in KNOBS:
            assert f"`{k.name}`" in ktable
        mtable = render_metric_table()
        assert "`data.cache.hits`" in mtable
        assert "`frame.stage.*.seconds`" in mtable

    def test_analysis_md_knob_table_matches_registry(self):
        # the docs' knob/metric tables are GENERATED from the
        # registries; a hand-edit that drifts fails here
        doc = open(os.path.join(REPO, "ANALYSIS.md")).read()
        for line in render_knob_table().splitlines()[2:]:
            assert line in doc, f"ANALYSIS.md missing knob row: {line}"
        for line in render_metric_table().splitlines()[2:]:
            assert line in doc, f"ANALYSIS.md missing metric row: {line}"

    def test_validate_metrics_shares_the_registry(self):
        spec = importlib.util.spec_from_file_location(
            "validate_metrics", os.path.join(REPO, "tools",
                                             "validate_metrics.py"))
        vm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vm)
        assert vm.unknown_sink_names(
            {"train.steps": 1, "bogus.metric": 2}) == ["bogus.metric"]


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

class TestCLI:
    def _run(self, *args, cwd=REPO):
        return subprocess.run(
            [sys.executable, "-m", "tools.tpudl_check", *args],
            cwd=cwd, capture_output=True, text=True, timeout=120)

    @pytest.mark.slow
    def test_clean_tree_exits_0(self):
        p = self._run("tpudl", "tools")
        assert p.returncode == 0, p.stderr + p.stdout
        assert "0 finding(s)" in p.stdout

    def test_findings_exit_2(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def f(g):\n"
                       "    try:\n"
                       "        g()\n"
                       "    except Exception:\n"
                       "        pass\n")
        p = self._run(str(bad))
        assert p.returncode == 2
        assert "[swallowed-except]" in p.stderr
        assert "hint:" in p.stderr

    def test_unparseable_file_exits_1(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n")
        p = self._run(str(bad))
        assert p.returncode == 1
        assert "ERROR" in p.stderr

    def test_non_utf8_file_is_an_error_line_not_a_traceback(self, tmp_path):
        enc = tmp_path / "latin.py"
        enc.write_bytes("# coding: latin-1\n# caf\xe9\nx = 1\n"
                        .encode("latin-1"))
        p = self._run(str(enc))
        assert p.returncode == 1
        assert "ERROR" in p.stderr
        assert "Traceback" not in p.stderr

    def test_missing_path_exits_1(self):
        p = self._run("/no/such/dir")
        assert p.returncode == 1

    def test_typod_flag_exits_1(self):
        # a typo'd --registry-adit must not silently run a plain lint
        # and let CI believe the audit passed
        p = self._run("--registry-adit", "tpudl")
        assert p.returncode == 1
        assert "unknown option" in p.stderr

    def test_non_python_file_arg_exits_1(self, tmp_path):
        sh = tmp_path / "gate.sh"
        sh.write_text("echo hi\n")
        p = self._run(str(sh))
        assert p.returncode == 1
        assert "not python" in p.stderr

    def test_no_args_exits_1_with_usage(self):
        p = self._run()
        assert p.returncode == 1
        assert "usage" in p.stderr

    def test_list_rules(self):
        p = self._run("--list-rules")
        assert p.returncode == 0
        for rule in RULES:
            assert rule in p.stdout

    def test_registry_audit_flags_drift(self, tmp_path):
        # a knob nobody declared → audit exits 2 with a DRIFT line
        f = tmp_path / "drifty.py"
        f.write_text("import os\n"
                     "# tpudl: ignore[undeclared-knob] — audit fixture\n"
                     "v = os.environ.get('TPUDL_AUDIT_FIXTURE_ONLY')\n")
        p = self._run("--registry-audit", str(f))
        assert p.returncode == 2
        assert "TPUDL_AUDIT_FIXTURE_ONLY" in p.stderr

    def test_importable_like_the_validators(self):
        cli = _load_cli()
        findings, errors = cli.run_check(
            CHECK_TARGETS, root=REPO, out=open(os.devnull, "w"))
        assert findings == [] and errors == []
