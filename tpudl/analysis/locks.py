"""The machine-readable registry of every product lock.

One declaration per lock the codebase constructs (via
:func:`tpudl.testing.tsan.named_lock` — the name literal at the
construction site IS the registry key). Consumers (CONCURRENCY.md):

1. the static concurrency analyzer
   (:mod:`tpudl.analysis.concurrency`): the interprocedural lock graph
   resolves every construction site to a declaration, and the coverage
   round-trip test (tests/test_concurrency.py) fails when a
   ``threading.Lock``/``RLock``/``Condition`` appears in ``tpudl/``
   without one (or a declaration loses its construction site);
2. the runtime sanitizer (:mod:`tpudl.testing.tsan`): armed runs check
   observed acquisition order against the declared ranks and name
   locks in inversion/deadlock/lockset findings;
3. the docs: CONCURRENCY.md's lock inventory table renders from this
   module (:func:`render_lock_table`) — drift fails a test, the
   ANALYSIS.md pattern.

**Declared order**: ``order`` is a rank — a thread holding a lock may
only acquire locks of a STRICTLY HIGHER rank (outer/coarse locks are
low, leaf scalar locks are high). Equal ranks must never nest (the
per-instance locks of one class share a rank for exactly this reason).
The ranks document the intended global order; the static ``lock-order``
rule checks the real call graph for cycles regardless, and the armed
sanitizer reports rank violations it actually observes.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["LockDecl", "LOCKS", "LOCK_NAMES", "lock_order",
           "render_lock_table"]


@dataclass(frozen=True)
class LockDecl:
    name: str       # the named_lock(...) literal, dotted lowercase
    module: str     # owning module (dotted, under tpudl)
    kind: str       # lock | rlock | condition. A condition is built by
                    # WRAPPING a named lock in stdlib Condition —
                    # named_lock itself refuses kind="condition" so a
                    # plain Lock can never stand in for one silently.
    scope: str      # "module" (one per process) | "instance" (per obj)
    order: int      # rank: may only acquire strictly higher while held
    guards: str     # one line: the state this lock protects


LOCKS: tuple[LockDecl, ...] = (
    # -- rank 10: coarse outer locks (held across whole operations) ----
    LockDecl("data.shards.manifest", "tpudl.data.shards", "lock",
             "instance", 10,
             "ShardCache shard map + verified set + manifest file IO"),
    LockDecl("jobs.runtime.manifest", "tpudl.jobs.runtime", "lock",
             "instance", 10,
             "JobRuntime resume-manifest read/modify/write"),
    # -- rank 12: checkpoint store (acquired under an estimator trial's
    #    save lock when a trial persists its result) ------------------
    LockDecl("train.checkpoint.manifest", "tpudl.train.checkpoint",
             "lock", "instance", 12,
             "CheckpointManager manifest + checkpoint store IO"),
    LockDecl("native.build", "tpudl.native", "lock", "module", 10,
             "one-shot native decoder build (cc subprocess) + dlopen"),
    LockDecl("ml.estimator.save", "tpudl.ml.estimator", "lock",
             "instance", 10,
             "shared keras model write-back across trial threads"),
    # -- rank 15 -------------------------------------------------------
    LockDecl("ml.estimator.step_cache", "tpudl.ml.estimator", "lock",
             "instance", 15,
             "compiled-train-step cache shared across trials"),
    LockDecl("image.lazyfile.transform", "tpudl.image.imageIO", "lock",
             "instance", 15,
             "LazyFileColumn serial-decode contract (non-thread-safe "
             "transforms run one batch at a time)"),
    # -- rank 16: obs singleton start/stop (their start/stop paths may
    #    reach the wire probe (20) and the report rings) --------------
    LockDecl("obs.live.writer", "tpudl.obs.live", "lock", "module", 16,
             "status-writer singleton start/stop"),
    LockDecl("obs.watchdog.daemon", "tpudl.obs.watchdog", "lock",
             "module", 16, "watchdog daemon singleton start/stop"),
    LockDecl("data.device_cache.singleton", "tpudl.data.device_cache",
             "lock", "module", 16,
             "process-wide DeviceBatchCache create/reset (construction "
             "publishes the budget gauges — metrics locks are higher)"),
    # -- rank 18 -------------------------------------------------------
    LockDecl("data.codec.plan", "tpudl.data.codec", "lock", "instance",
             18, "CodecPlan per-column codec resolution/adoption"),
    # -- rank 20 -------------------------------------------------------
    LockDecl("data.codec.wire_probe", "tpudl.data.codec", "lock",
             "module", 20,
             "process-wide H2D wire-bandwidth probe cache (one probe, "
             "ever)"),
    LockDecl("testing.faults.arm", "tpudl.testing.faults", "lock",
             "module", 20, "fault-plan arm/disarm singleton"),
    LockDecl("testing.traceck", "tpudl.testing.traceck", "lock",
             "module", 20,
             "traceck per-fn-identity trace counts + storm findings "
             "(metrics/flight reporting happens AFTER release)"),
    LockDecl("ml.hpo.slices", "tpudl.ml.hpo", "lock", "module", 20,
             "free device-slice list under the trial thread pool "
             "(function-local; module scope = one per run_parallel "
             "call)"),
    LockDecl("image.lazyfile.memo", "tpudl.image.imageIO", "lock",
             "instance", 20, "LazyFileColumn small-access decode memo"),
    LockDecl("obs.pipeline.ring", "tpudl.obs.pipeline", "lock",
             "module", 20, "bounded ring of recent PipelineReports"),
    LockDecl("data.device_cache", "tpudl.data.device_cache", "lock",
             "instance", 20,
             "DeviceBatchCache entry map + LRU order + resident-byte "
             "and pin accounting (metrics published outside the lock)"),
    LockDecl("serve.queue", "tpudl.serve.queue", "lock", "instance",
             20,
             "RequestQueue deque + payload-byte ledger (admission "
             "decision; metrics and reject raise happen outside the "
             "lock)"),
    LockDecl("serve.registry", "tpudl.serve.registry", "lock",
             "instance", 20,
             "serve ModelRegistry name→entry map (serve.models gauge "
             "published outside the lock)"),
    LockDecl("serve.loadgen", "tpudl.serve.loadgen", "lock", "module",
             20,
             "closed-loop client tallies: request counter + latency/"
             "TTFT/reject/shed lists (function-local; module scope = "
             "one per run_closed_loop call; never held across a "
             "submit/result wait)"),
    # -- rank 24: the two registries (their armed lockset checks file
    #    breadcrumbs into the flight recorder (25); they never nest
    #    with each other) ---------------------------------------------
    LockDecl("obs.metrics.registry", "tpudl.obs.metrics", "lock",
             "instance", 24,
             "MetricsRegistry name→metric map + flush throttle"),
    LockDecl("obs.watchdog.registry", "tpudl.obs.watchdog", "lock",
             "instance", 24,
             "HeartbeatRegistry active set (the watchdog's scan list)"),
    # -- rank 25 -------------------------------------------------------
    LockDecl("testing.faults.plan", "tpudl.testing.faults", "lock",
             "instance", 25,
             "FaultPlan rule counters + fired list (the hot fire() "
             "hook)"),
    LockDecl("obs.pipeline.report", "tpudl.obs.pipeline", "lock",
             "instance", 25,
             "PipelineReport stages/calls/gauges/progress (prepare "
             "workers + consumer write concurrently)"),
    LockDecl("obs.flight.recorder", "tpudl.obs.flight", "lock",
             "instance", 25,
             "FlightRecorder evidence rings (batches/errors/stalls/"
             "ticks/requests/restarts/events) + dumped-paths list"),
    LockDecl("obs.slo.engine", "tpudl.obs.slo", "lock", "instance", 25,
             "SloEngine windowed stamp ring + cached median + publish "
             "throttle (gauges and exemplar writes happen outside "
             "the lock)"),
    LockDecl("obs.attribution.ledger", "tpudl.obs.attribution", "lock",
             "instance", 26,
             "ScopeLedger scope table + unattributed bucket (LRU "
             "bookkeeping and folds under the lock; the eviction "
             "counter publishes after release — charges nest under "
             "any caller lock but acquire nothing themselves)"),
    # -- rank 30: leaf scalar locks (never acquire anything under) -----
    LockDecl("obs.metrics.counter", "tpudl.obs.metrics", "lock",
             "instance", 30, "one Counter's running value"),
    LockDecl("obs.metrics.gauge", "tpudl.obs.metrics", "lock",
             "instance", 30, "one Gauge's value/count/total/max"),
    LockDecl("obs.metrics.histogram", "tpudl.obs.metrics", "lock",
             "instance", 30,
             "one Histogram's sample ring + running aggregates"),
    LockDecl("obs.watchdog.heartbeat", "tpudl.obs.watchdog", "lock",
             "instance", 30,
             "Heartbeat beat fields (info/last_beat/beats/stalled) + "
             "in-flight stage map"),
    LockDecl("obs.tracer.ring", "tpudl.obs.tracer", "lock", "instance",
             30, "host-span tracer ring + dropped counter"),
    LockDecl("image.lazyfile.reads", "tpudl.image.imageIO", "lock",
             "instance", 30, "LazyFileColumn read counter"),
    LockDecl("data.device_cache.token_memo", "tpudl.data.device_cache",
             "lock", "module", 30,
             "array_token memo map (concurrent estimator trial "
             "threads share it; pure dict ops under the lock)"),
)

LOCK_NAMES = frozenset(d.name for d in LOCKS)


def lock_order(name: str) -> int | None:
    for d in LOCKS:
        if d.name == name:
            return d.order
    return None


def render_lock_table() -> str:
    """Markdown lock-inventory table (CONCURRENCY.md embeds the output
    verbatim; the drift test re-renders and compares)."""
    lines = ["| order | lock | module | scope | guards |",
             "|---|---|---|---|---|"]
    for d in sorted(LOCKS, key=lambda d: (d.order, d.name)):
        lines.append(f"| {d.order} | `{d.name}` | `{d.module}` "
                     f"| {d.scope} | {d.guards} |")
    return "\n".join(lines)
