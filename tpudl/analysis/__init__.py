"""tpudl.analysis — static enforcement of the codebase's contracts.

Three pieces (ANALYSIS.md):

- :mod:`tpudl.analysis.checker`: the AST invariant checker — eight
  rules distilled from PRs 2–7 (atomic writes, flag-only signal
  handlers, the one RetryPolicy, no hot-path device syncs, no silent
  excepts, declared knobs/metrics, locked globals), with
  ``# tpudl: ignore[rule] — reason`` suppressions;
- :mod:`tpudl.analysis.knobs`: the registry of every ``TPUDL_*`` env
  knob (the docs' knob tables render from it);
- :mod:`tpudl.analysis.metric_names`: the registry of every
  ``tpudl.obs`` metric name (shared with tools/validate_metrics.py);
- :mod:`tpudl.analysis.concurrency`: the INTERPROCEDURAL half
  (CONCURRENCY.md) — the whole-tree lock graph and the four
  concurrency rules (lock-order, lock-held-blocking, signal-lock,
  daemon-shared-write);
- :mod:`tpudl.analysis.locks`: the registry of every product lock
  (name / module / guards / declared rank) — feeds the lock graph,
  the runtime sanitizer (:mod:`tpudl.testing.tsan`), and the
  CONCURRENCY.md inventory table;
- :mod:`tpudl.analysis.traceguard`: the JIT-BOUNDARY half — which
  functions are traced (jit/scan/_fused_wrapper/CodecPlan.wrap/
  device_fn= entries, plus transitively everything they call) and the
  five trace rules (trace-time-effect, host-op-on-traced,
  traced-branch, donation-reuse, jit-cache-churn). Runtime twin:
  :mod:`tpudl.testing.traceck` (``TPUDL_TRACECK=1`` recompile-storm
  sentinel).

CLI: ``python -m tools.tpudl_check tpudl tools``
(exit 0 clean / 2 findings / 1 error; ``--rules`` / ``--json`` for
selective machine-readable runs). Wired into run-tests.sh and tier-1
via tests/test_analysis.py + tests/test_concurrency.py.
"""

from .checker import (Finding, RULES, Suppression, check_file,
                      check_paths, check_source, collect_usage,
                      iter_python_files)
from .concurrency import (CONCURRENCY_RULES, LockGraph, LockSite,
                          analyze as analyze_concurrency,
                          analyze_sources, build_lock_graph,
                          registry_coverage)
from .traceguard import (TRACE_RULES, TracedFn,
                         analyze as analyze_trace,
                         analyze_sources as analyze_trace_sources,
                         traced_functions)
from .knobs import KNOBS, KNOB_NAMES, Knob, render_knob_table
from .locks import (LOCKS, LOCK_NAMES, LockDecl, lock_order,
                    render_lock_table)
from .metric_names import (METRIC_NAMES, METRIC_PATTERNS, METRICS,
                           Metric, is_declared_metric,
                           render_metric_table, unknown_metric_names)

__all__ = [
    "Finding", "RULES", "Suppression", "check_file", "check_paths",
    "check_source", "collect_usage", "iter_python_files",
    "CONCURRENCY_RULES", "LockGraph", "LockSite",
    "analyze_concurrency", "analyze_sources", "build_lock_graph",
    "registry_coverage",
    "TRACE_RULES", "TracedFn", "analyze_trace",
    "analyze_trace_sources", "traced_functions",
    "Knob", "KNOBS", "KNOB_NAMES", "render_knob_table",
    "LockDecl", "LOCKS", "LOCK_NAMES", "lock_order",
    "render_lock_table",
    "Metric", "METRICS", "METRIC_NAMES", "METRIC_PATTERNS",
    "is_declared_metric", "render_metric_table",
    "unknown_metric_names",
]
