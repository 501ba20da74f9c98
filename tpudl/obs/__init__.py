"""Observability: host-span tracing, process-wide metrics, device traces.

SURVEY.md §5.1/§5.5: the reference had NO first-party tracing or metrics
(observability was inherited from the Spark UI). This package is the
run-wide subsystem that replaces it (OBSERVABILITY.md is the operator
guide), three pillars:

- :mod:`tpudl.obs.tracer` — host-span tracer: ``obs.span("stage")``
  records spans with an id, a parent and an epoch-nanosecond start into
  a bounded ring, exportable as Chrome trace-event JSON;
- :mod:`tpudl.obs.metrics` — process-wide metrics registry: thread-safe
  counters/gauges/bounded-histograms with ``snapshot()`` and an opt-in
  JSONL sink (``TPUDL_METRICS_FILE``);
- :mod:`tpudl.obs.trace` — jax.profiler capture, its device planes, and
  the host spans beside them on the clock the profiler stamps its
  session with: ``python -m tpudl.obs trace <dir>`` renders both on one
  timeline with a combined summary (device busy %, host stage totals,
  device idle by host span, queue lead).

Per-run executor reports (:class:`PipelineReport`) live in
:mod:`tpudl.obs.pipeline`, kept in a bounded ring keyed by run id.

The black-box layer (OBSERVABILITY.md "Failure forensics"):

- :mod:`tpudl.obs.flight` — always-on bounded flight recorder;
  ``obs.dump()`` (or an unhandled exception / SIGTERM / SIGQUIT after
  ``obs.flight.install()``) writes a self-contained
  ``tpudl-dump-<pid>.json.gz``;
- :mod:`tpudl.obs.watchdog` — heartbeat registry + stall daemon
  (``TPUDL_WATCHDOG_STALL_S``); stalls snapshot every thread's stack
  into the recorder and bump ``obs.watchdog.stalls``;
- :mod:`tpudl.obs.doctor` — ``python -m tpudl.obs doctor <dump|dir>``
  merges per-host dumps and classifies the failure.

The live ops plane (OBSERVABILITY.md "Live ops plane"):

- :mod:`tpudl.obs.roofline` — per-run roofline attribution:
  ``obs.analyze_roofline()`` decomposes achieved vs achievable
  throughput across prepare/wire/dispatch/d2h, publishes
  ``obs.roofline.*`` gauges, and the knob advisor recommends concrete
  ``fuse_steps``/``prefetch_depth``/``prepare_workers``/``wire_codec``
  settings with predicted gain;
- :mod:`tpudl.obs.live` — every instrumented process writes an atomic
  ``tpudl-status-<pid>.json`` (``TPUDL_STATUS_DIR``);
  ``python -m tpudl.obs top <dir>`` renders the refreshing live view.

The attribution plane (OBSERVABILITY.md "Attribution plane"):

- :mod:`tpudl.obs.attribution` — ``obs.scope(tenant=..., job=...,
  run=...)`` tags every publish on the calling thread (carried across
  the executor/serve/HPO pools), and the bounded per-scope resource
  ledger answers WHO used the bytes/rows/tokens/seconds; per-scope
  sums + ``unattributed`` reconcile EXACTLY against the global
  counters (``python -m tpudl.obs ledger <dir>`` offline).
"""

from __future__ import annotations

from tpudl.obs.attribution import (Scope, carry, charge, current_scope,
                                   get_ledger, ledger_snapshot,
                                   ledger_totals, reconcile,
                                   reset_ledger, scope)
from tpudl.obs.flight import dump, get_recorder, record_error
from tpudl.obs.live import (ensure_status_writer, start_status_writer,
                            stop_status_writer, write_status)
from tpudl.obs.roofline import RooflineReport, advise, autotune_seed
from tpudl.obs.roofline import analyze as analyze_roofline
from tpudl.obs.metrics import (Meter, counter, flush_metrics, gauge,
                               get_registry, histogram, snapshot, timed)
from tpudl.obs.watchdog import heartbeat, start_watchdog
from tpudl.obs.pipeline import (PipelineReport, get_pipeline_report,
                                last_pipeline_report, pipeline_reports,
                                set_last_pipeline)
from tpudl.obs.trace import (align, attribute_idle, declared_scopes,
                             device_account, load_device_planes,
                             load_host_spans, load_host_trace_events,
                             merge_trace_events, named_scope, profile,
                             profile_window, queue_lead, summarize_merged)
from tpudl.obs.tracer import (children, export_chrome_trace, get_tracer,
                              self_ns, span)

__all__ = [
    # attribution plane (scoped ledgers)
    "Scope", "scope", "current_scope", "carry", "charge",
    "get_ledger", "reset_ledger", "ledger_snapshot", "ledger_totals",
    "reconcile",
    # tracer
    "span", "get_tracer", "export_chrome_trace", "children", "self_ns",
    # metrics
    "counter", "gauge", "histogram", "snapshot", "flush_metrics",
    "get_registry", "timed", "Meter",
    # device traces + merge
    "profile", "named_scope", "declared_scopes", "device_account",
    "load_host_trace_events", "merge_trace_events", "summarize_merged",
    "load_device_planes", "profile_window", "load_host_spans", "align",
    "attribute_idle", "queue_lead",
    # per-run pipeline reports
    "PipelineReport", "last_pipeline_report", "set_last_pipeline",
    "pipeline_reports", "get_pipeline_report",
    # failure forensics (flight recorder + watchdog)
    "dump", "get_recorder", "record_error", "heartbeat",
    "start_watchdog",
    # live ops plane (roofline + status files)
    "RooflineReport", "analyze_roofline", "advise",
    "ensure_status_writer", "start_status_writer",
    "stop_status_writer", "write_status",
]
