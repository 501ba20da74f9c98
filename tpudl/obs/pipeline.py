"""Per-run pipeline reports: stage times, gauges, and the report ring.

``PipelineReport`` is ONE ``Frame.map_batches`` run's stage accounting
(PIPELINE.md has the reading guide). This module also owns the ring of
recent reports — keyed by run id, bounded at ``TPUDL_PIPELINE_RING``
(default 16) — which replaces the old single racy ``_LAST_PIPELINE``
global: two concurrent runs (HPO trials in threads) each keep their own
retrievable, internally-consistent report, and
``last_pipeline_report()`` stays the newest entry for every existing
caller. On ``finish()`` a report ALSO publishes its totals into the
process-wide metrics registry (:mod:`tpudl.obs.metrics`), so run-level
stage seconds accumulate across a whole process alongside every other
layer's metrics.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import time
from collections import deque

from tpudl.obs import metrics as _metrics
from tpudl.obs import tracer as _tracer
from tpudl.testing import tsan as _tsan

__all__ = ["PipelineReport", "last_pipeline_report", "set_last_pipeline",
           "pipeline_reports", "get_pipeline_report"]

# per-gauge retained samples; running aggregates keep mean/max exact
# over ALL samples (a long streaming run must not grow without bound)
GAUGE_SAMPLE_CAP = 4096

_run_counter = itertools.count()


def _next_run_id() -> str:
    return f"{os.getpid()}-{next(_run_counter)}"


class PipelineReport:
    """Per-stage wall time + gauges for ONE ``Frame.map_batches`` run.

    The stage-time model (PIPELINE.md has the reading guide):

    - ``prepare``: worker-thread seconds in decode/pack (summed across
      the prepare pool — N workers can make this exceed wall time);
    - ``h2d``: the explicit pad + sharded-transfer ENQUEUE on the mesh
      path (``mesh.transfer_batch`` is async since ISSUE 11 — the
      copies themselves ride under later dispatches, so this stage
      measures the enqueue/pad cost, not the wire; on the mesh=None
      path the transfer rides the dispatch, see map_batches);
    - ``dispatch``: seconds in ``fn(...)`` — on the serial path these
      are consumer-thread seconds (enqueue only for async device fns,
      enqueue+compute for host fns); under the D-deep async dispatch
      window they are POOL-SUMMED across the dispatch threads and may
      exceed wall time (like ``prepare``) — the consumer-visible cost
      is ``dispatch_wait``;
    - ``dispatch_wait``: consumer seconds blocked on the in-flight
      dispatch window (async executor only) — the UNHIDDEN dispatch
      residue, the round-trip time depth D failed to hide (the
      ``infeed_wait`` analogue of the dispatch side; the roofline model
      reads this, not the pool-summed ``dispatch``, when present);
    - ``d2h``: device→host fetch time (windowed drain + the acc-mode
      final fetch — the copies themselves start at dispatch, so this
      measures only the unoverlapped tail);
    - ``infeed_wait``: consumer seconds blocked on the infeed queue —
      the UNHIDDEN remainder of prepare, and the numerator of
      ``overlap_efficiency``.

    Gauges (``gauge``) keep a bounded ring of samples (last
    ``GAUGE_SAMPLE_CAP``) plus running count/sum/max, so the reported
    mean/max stay exact over ALL samples at O(cap) memory
    (``queue_depth`` is sampled at each consumer take: depth K means the
    pool is keeping the device fed). Thread-safe: prepare workers and
    the consumer thread write concurrently.

    Each stage() block also lands on the host-span tracer (named
    ``frame.<stage>``, tagged with this run's id), so an exported host
    trace shows the executor's stages on the merged timeline.
    """

    def __init__(self):
        self.run_id = _next_run_id()
        self.stages: dict[str, float] = {}
        self.calls: dict[str, int] = {}
        self.gauges: dict[str, _metrics.Histogram] = {}
        self.wall_seconds = 0.0
        self.config: dict = {}
        # live progress (fed by the executor's dispatch loop, which
        # knows batch counts — the live monitor and its ETA read these
        # instead of inferring progress from counters); rows_total
        # arrives via config["rows"], rows_done via progress()
        self.rows_done = 0
        self.finished = False
        self._t0 = time.perf_counter()
        # the executor's watchdog heartbeat (set by map_batches): every
        # stage ENTRY beats it with the stage name, so a freeze inside
        # any stage leaves "last progress = entering <stage>" as the
        # stall's suspect (tpudl.obs.watchdog)
        self.heartbeat = None
        self._lock = _tsan.named_lock("obs.pipeline.report")

    @contextlib.contextmanager
    def stage(self, name: str):
        # enter/exit (not a bare beat): the stage stays IN FLIGHT on
        # the heartbeat until it returns, so a freeze inside dispatch
        # is still the suspect after prepare workers beat afterwards
        hb = self.heartbeat
        if hb is not None:
            hb.stage_enter(name)
        with _tracer.span(f"frame.{name}", run=self.run_id):
            t0 = time.perf_counter()
            try:
                yield
            except BaseException as e:
                # fault-taxonomy hook (tpudl.frame.supervisor): tag the
                # escaping exception with the INNERMOST stage it left —
                # outer stage blocks see the tag set and keep it, so a
                # mesh-transfer fault inside prepare's nested h2d block
                # classifies as a transfer fault, not a prepare one
                if getattr(e, "tpudl_stage", None) is None:
                    try:
                        e.tpudl_stage = name
                    # tpudl: ignore[swallowed-except] — exceptions with
                    # __slots__/immutable attrs just stay untagged; the
                    # classifier falls back to type/message anchoring
                    except Exception:
                        pass
                raise
            finally:
                self.add(name, time.perf_counter() - t0)
                if hb is not None:
                    hb.stage_exit(name)

    def add(self, name: str, seconds: float):
        with self._lock:
            self.stages[name] = self.stages.get(name, 0.0) + seconds
            self.calls[name] = self.calls.get(name, 0) + 1

    def count(self, name: str, k: int = 1):
        with self._lock:
            self.calls[name] = self.calls.get(name, 0) + k

    def progress(self, rows: int):
        """``rows`` more rows finished dispatching — the executor calls
        this per handled batch so the run's rows_done/rows_total pair is
        authoritative (ETA = remaining rows / observed rate)."""
        with self._lock:
            self.rows_done += int(rows)

    def gauge(self, name: str, value):
        with self._lock:
            h = self.gauges.get(name)
            if h is None:
                # one authority for "bounded samples + exact running
                # aggregates": the registry's Histogram (unregistered —
                # these samples are per-run, not process-wide)
                h = self.gauges[name] = _metrics.Histogram(
                    cap=GAUGE_SAMPLE_CAP)
        h.observe(value)

    def dispatch_overlap_s(self) -> float | None:
        """Dispatch seconds HIDDEN from the consumer by the in-flight
        window: pool-summed ``dispatch`` minus the consumer's
        ``dispatch_wait``. On the async executor this is the round-trip
        time that rode under other dispatches — the ROADMAP-2 win as
        one number (published as the ``frame.dispatch.overlap_s``
        gauge). None for serial runs (no window, nothing overlapped);
        clamped at 0 so measurement jitter never reports negative
        overlap."""
        with self._lock:
            if "dispatch_wait" not in self.stages:
                return None
            return max(0.0, self.stages.get("dispatch", 0.0)
                       - self.stages.get("dispatch_wait", 0.0))

    def overlap_efficiency(self) -> float | None:
        """Fraction of host prepare work hidden under device compute:
        1 - infeed_wait/prepare, clamped to [0, 1]. 1.0 = the consumer
        never waited (prepare fully overlapped); 0.0 = fully serial.
        None when nothing was prepared (empty frame / no prefetch)."""
        prep = self.stages.get("prepare", 0.0)
        if prep <= 0.0:
            return None
        wait = self.stages.get("infeed_wait", 0.0)
        return max(0.0, min(1.0, 1.0 - wait / prep))

    def finish(self, wall_seconds: float | None = None):
        """Close out the run: record wall time and publish totals into
        the process-wide metrics registry (map_batches runs/rows
        counters, per-stage seconds, wall-time histogram). Called by the
        executor; idempotent enough for tests (re-publishing would
        double-count, so the executor calls it exactly once)."""
        if wall_seconds is not None:
            self.wall_seconds = wall_seconds
        self.finished = True
        _metrics.counter("frame.map_batches.runs").inc()
        rows = self.config.get("rows")
        if rows:
            _metrics.counter("frame.map_batches.rows").inc(rows)
        _metrics.histogram("frame.map_batches.wall_seconds").observe(
            self.wall_seconds)
        with self._lock:
            stages = dict(self.stages)
            dispatches = self.calls.get("dispatch", 0)
        if dispatches:
            _metrics.counter("frame.map_batches.batches").inc(dispatches)
        for name, secs in stages.items():
            _metrics.counter(f"frame.stage.{name}.seconds").inc(secs)
        eff = self.overlap_efficiency()
        if eff is not None:
            _metrics.gauge("frame.overlap_efficiency").set(eff)
        # the async dispatch window's run-level truth (ROADMAP 2):
        # mean in-flight depth + the seconds the window actually hid
        overlap = self.dispatch_overlap_s()
        if overlap is not None:
            _metrics.gauge("frame.dispatch.overlap_s").set(overlap)
        with self._lock:
            inflight = self.gauges.get("dispatch_inflight")
        if inflight is not None:
            _metrics.gauge("frame.dispatch.inflight").set(
                inflight.to_dict()["mean"])
        # mesh-path waste accounting (ISSUE 11): rows of SPMD padding
        # this run shipped and computed only to throw away — the
        # roofline reads these
        if self.config.get("mesh"):
            with self._lock:
                pad = int(self.calls.get("pad_rows", 0))
            _metrics.gauge("frame.mesh.pad_rows").set(pad)
            if rows:
                _metrics.gauge("frame.mesh.pad_overhead_pct").set(
                    100.0 * pad / (int(rows) + pad))
            # 2-D grid truth (ISSUE 16): the model-axis size the run
            # actually executed under — 1 on a data-parallel mesh, >1
            # when tensor-parallel params were resident. obs top
            # reads this to prove the second axis was armed, not
            # silently collapsed to 1-D.
            _metrics.gauge("frame.mesh.model_axis").set(
                int(self.config["mesh"].get("model") or 1))
        # serve-session truth (ISSUE 17): a serve run's report commits
        # the session-mean slot occupancy (the saturation SLO) and the
        # sustained token rate — obs top's serve line and the roofline
        # read these, and the per-step gauge's last value must not
        # stand in for the whole session
        if self.config.get("serve"):
            with self._lock:
                occ = self.gauges.get("slot_occupancy")
                toks = int(self.calls.get("tokens", 0))
            if occ is not None and occ.to_dict()["mean"] is not None:
                _metrics.gauge("serve.batch_occupancy").set(
                    occ.to_dict()["mean"])
            if toks and self.wall_seconds:
                _metrics.gauge("serve.tokens_per_s").set(
                    toks / self.wall_seconds)
        _metrics.get_registry().maybe_flush()

    def report(self) -> dict:
        with self._lock:
            out = {
                "run_id": self.run_id,
                "wall_seconds": round(self.wall_seconds, 4),
                "stage_seconds": {k: round(v, 4)
                                  for k, v in sorted(self.stages.items())},
                "stage_calls": dict(sorted(self.calls.items())),
                # live-progress triple: rows_done climbs per handled
                # batch; age_s is wall-so-far for UNFINISHED runs (the
                # committed wall_seconds stays finish()-only)
                "rows_done": self.rows_done,
                "finished": self.finished,
                "age_s": round(time.perf_counter() - self._t0, 4),
            }
            for name, h in sorted(self.gauges.items()):
                d = h.to_dict()
                out[f"{name}_mean"] = round(d["mean"], 2)
                out[f"{name}_max"] = d["max"]
            out.update(self.config)
        eff = self.overlap_efficiency()
        if eff is not None:
            out["overlap_efficiency"] = round(eff, 3)
        overlap = self.dispatch_overlap_s()
        if overlap is not None:
            out["dispatch_overlap_s"] = round(overlap, 4)
        return out


def _ring_size() -> int:
    try:
        return max(1, int(os.environ.get("TPUDL_PIPELINE_RING", "") or 16))
    except ValueError:
        return 16


_REPORTS: deque = deque(maxlen=_ring_size())
_REPORTS_LOCK = _tsan.named_lock("obs.pipeline.ring")


def set_last_pipeline(report: PipelineReport | None):
    """Filed by ``Frame.map_batches`` at the start of every run, so the
    caller above any transformer stack (``benchmark/adapters/``, a
    notebook) can read
    the executor's stage breakdown without threading a handle through
    the transformer APIs. Reports live in a bounded ring keyed by run
    id — concurrent runs no longer clobber each other (each stays
    retrievable via :func:`get_pipeline_report` /
    :func:`pipeline_reports`)."""
    if report is None:
        return
    with _REPORTS_LOCK:
        if _tsan.ENABLED:
            _tsan.check_guarded("obs.pipeline.ring",
                                "pipeline-report ring",
                                lock=_REPORTS_LOCK)
        _REPORTS.append(report)


def last_pipeline_report() -> dict | None:
    """Stage breakdown of the most recent map_batches run (or None)."""
    with _REPORTS_LOCK:
        newest = _REPORTS[-1] if _REPORTS else None
    return newest.report() if newest is not None else None


def pipeline_reports() -> dict[str, dict]:
    """``{run_id: report_dict}`` for the ring's runs, oldest→newest."""
    with _REPORTS_LOCK:
        reports = list(_REPORTS)
    return {r.run_id: r.report() for r in reports}


def get_pipeline_report(run_id: str) -> dict | None:
    """One ring entry by run id (None once evicted)."""
    # snapshot under the ring lock, render outside it — like the two
    # accessors above (report() takes the report's own lock and does
    # real work; holding the ring across it is needless contention)
    with _REPORTS_LOCK:
        match = next((r for r in _REPORTS if r.run_id == run_id), None)
    return match.report() if match is not None else None
