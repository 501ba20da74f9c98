"""Device traces, and the host spans beside them on one clock.

The device half of the observability subsystem: capture a jax.profiler
trace (:func:`profile`), read its device planes
(:func:`load_device_planes`) and the session's own start and stop
(:func:`profile_window`), and put the host-span tracer's spans
(:mod:`tpudl.obs.tracer`) beside them (:func:`align`). On that clock
every device idle gap has a host span to answer for it
(:func:`attribute_idle`), every run of a step program has the dispatch
that enqueued it (:func:`queue_lead`), and one Chrome trace
(:func:`merge_trace_events`) and one summary (:func:`summarize_merged`)
hold both sides. The device's own time has an account too: every
operation of a traced step filed once, under a ``jax.named_scope`` the
program declared (:func:`named_scope`, :func:`declared_scopes`) or as the
remainder (:func:`record_device_scopes` writes it into the span ring,
:func:`device_account` returns the table a person reads). ``python -m
tpudl.obs trace <dir>`` drives all of this from the command line.

The clock: the profiler stamps every ``xplane.pb`` with
``profile_start_time`` / ``profile_stop_time`` in epoch nanoseconds (the
plane ``Task Environment``) and counts device events in nanoseconds
since that start. A span's ``start_ns`` is ``time.time_ns()``, so
``start_ns - profile_start_time`` is its place on the device's clock: one
subtraction, no stream is zeroed on its own first event.
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import glob
import gzip
import json
import os
import re
import statistics
from collections import Counter

from tpudl.obs import metrics as _metrics
from tpudl.obs.tracer import Span, children, get_tracer

__all__ = ["profile", "named_scope", "declared_scopes",
           "load_host_trace_events", "find_trace_files",
           "load_device_planes", "profile_window", "load_host_spans",
           "align", "attribute_idle", "queue_lead", "traced_fit",
           "merge_trace_events", "summarize_merged", "load_device_op_scopes",
           "scope_ns_by_run", "record_device_scopes", "device_account"]

HOST_PID = 0  # merged-trace pid for the host lane (device pids count up)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"
TASK_PLANE = "Task Environment"
NO_SPAN = "(no span)"
UNSCOPED = "unscoped"  # the account's remainder: ``device.unscoped``
_DECLARED: set = set()


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block, device planes
    only: the host and Python tracers are off (with them on, the host's
    runtime threads alone made a 413 MB trace that took 58 s to write),
    and the program's own spans are the host side. The trace carries its
    own start and stop (:func:`profile_window`), so
    ``export_chrome_trace(path_in_log_dir, window="profile")`` exports
    exactly the spans this block covered."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def named_scope(name: str):
    """Label pipeline stages inside jitted code (jax.named_scope; jax
    imported lazily so host-only Frame pipelines — which report into
    this module every map_batches call — never pay the jax import), and
    declare the name: the device account files operations under the
    scopes the program said it has (:func:`declared_scopes`). A set
    insertion while a program is TRACED; nothing when it runs."""
    import jax

    _DECLARED.add(name)
    return jax.named_scope(name)


def declared_scopes() -> frozenset:
    """Every name this process has opened a :func:`named_scope` under."""
    return frozenset(_DECLARED)


def load_host_trace_events(path: str) -> list[dict]:
    """Events from a host-span tracer export (plain or gzipped JSON)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        tr = json.load(f)
    return tr["traceEvents"] if isinstance(tr, dict) else tr


def _newest(trace_dir: str, *patterns: str) -> str | None:
    paths = [p for pat in patterns for p in glob.glob(
        os.path.join(trace_dir, "**", pat), recursive=True)]
    return max(paths, key=os.path.getmtime) if paths else None


def find_trace_files(trace_dir: str) -> dict:
    """Locate the newest host export and device trace under a directory:
    ``{"host": path|None, "device": path|None}``. Host exports are the
    tracer's ``*.host.trace.json`` (optionally ``.gz``); the device trace
    is the profiler's ``*.xplane.pb``."""
    return {"host": _newest(trace_dir, "*.host.trace.json",
                            "*.host.trace.json.gz"),
            "device": _newest(trace_dir, "*.xplane.pb")}


def _xspace(trace_dir: str):
    import jax

    path = _newest(trace_dir, "*.xplane.pb")
    if path is None:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(path)


def load_device_planes(trace_dir: str) -> dict:
    """``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` for the
    ``/device:TPU:<n>`` planes of the newest ``*.xplane.pb`` under
    ``trace_dir``, lines ``XLA Modules`` (one event per program run) and
    ``XLA Ops``; times are nanoseconds since the session's start. ``{}``
    when the trace has no device plane (a CPU backend)."""
    planes = {}
    for plane in _xspace(trace_dir).planes:
        if DEVICE_PLANE.match(plane.name):
            planes[plane.name] = {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines if line.name in (MODULES, OPS)}
    return planes


def profile_window(trace_dir: str) -> tuple[int, int]:
    """``(profile_start_time, profile_stop_time)`` of the newest
    ``*.xplane.pb`` under ``trace_dir``, epoch nanoseconds, from the
    stats of its ``Task Environment`` plane."""
    for plane in _xspace(trace_dir).planes:
        if plane.name == TASK_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return (int(stats["profile_start_time"]),
                        int(stats["profile_stop_time"]))
    raise ValueError(
        f"the trace under {trace_dir} has no {TASK_PLANE!r} plane with "
        "profile_start_time: host spans cannot be placed on its clock")


def load_host_spans(path: str) -> list[Span]:
    """The spans of a host-span tracer export, on the epoch clock. An
    export written before spans had an identity gets fresh ids, no
    parents, and times from its float microseconds."""
    spans = []
    for e in load_host_trace_events(path):
        if e.get("ph") != "X":
            continue
        a = dict(e.get("args") or {})
        start, dur = a.pop("start_ns", None), a.pop("dur_ns", None)
        spans.append(Span(
            e["name"],
            round(e["ts"] * 1e3) if start is None else start,
            round(e.get("dur", 0) * 1e3) if dur is None else dur,
            id=a.pop("id", None), parent=a.pop("parent", None),
            root=a.pop("root", None), tid=e.get("tid", 0),
            attrs=a or None))
    return spans


def align(spans, start_ns: int) -> list[Span]:
    """``spans`` in nanoseconds since ``start_ns`` (a session's
    ``profile_start_time``): the device events' own unit and origin."""
    return [s.shifted(start_ns) for s in spans]


def _merged(intervals) -> list[tuple[float, float]]:
    """Coalesce possibly-overlapping intervals — the ONE sweep behind
    both union and intersection (diverging copies would skew
    device_busy_ns vs overlap_ns)."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union(intervals) -> float:
    """Total covered time of possibly-overlapping intervals."""
    return sum(e - s for s, e in _merged(intervals))


def _intersection(a, b) -> float:
    """Covered time where union(a) and union(b) overlap."""
    am, bm = _merged(a), _merged(b)
    i = j = 0
    total = 0
    while i < len(am) and j < len(bm):
        s = max(am[i][0], bm[j][0])
        e = min(am[i][1], bm[j][1])
        if s < e:
            total += e - s
        if am[i][1] < bm[j][1]:
            i += 1
        else:
            j += 1
    return total


def _interval(span) -> tuple[int, int]:
    return span.start_ns, span.start_ns + span.dur_ns


def _program(event_name: str) -> str:
    """``jit_step(2287243686015180859)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def _op(event_name: str) -> str:
    """On the TPU an ``XLA Ops`` event is named by its whole HLO
    instruction: ``%fusion.7 = (bf16[256]{...}) fusion(...)`` ->
    ``%fusion.7``."""
    return event_name.split(" = ", 1)[0]


def _gaps(busy, lo, hi) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi]`` that the coalesced ``busy`` leaves."""
    out, end = [], lo
    for s, e in busy:
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def _dispatching_tid(spans):
    """The thread that enqueues device work: the one with the most
    ``*.dispatch`` spans, or with the most spans when there is none."""
    tids = ([s.tid for s in spans if s.name.endswith(".dispatch")]
            or [s.tid for s in spans])
    return Counter(tids).most_common(1)[0][0] if tids else None


def attribute_idle(modules, spans, window=None) -> dict:
    """Every device idle gap with the host span that answers for it.

    ``modules`` are one plane's ``XLA Modules`` events and ``spans`` are
    :func:`align`-ed, so both count nanoseconds from the session's start.
    A gap is a part of ``window`` (default: the first program's start to
    the last one's end) in which no program ran. At each instant of a gap
    the dispatching thread has one innermost open span, or none; the gap
    goes to the span that is innermost for most of it, or to ``"(no
    span)"``. Returns ``{"gaps": [{"start_ns", "dur_ns", "span", "id"}],
    "by_span": {name: idle_ns}, "idle_ns": total}``.
    """
    busy = _merged((s, s + d) for _, s, d in modules)
    if window is None:
        window = (busy[0][0], busy[-1][1]) if busy else (0, 0)
    tid = _dispatching_tid(spans)
    thread = sorted((s for s in spans if s.tid == tid),
                    key=lambda s: s.dur_ns)  # a child before its parent
    names = {s.id: s.name for s in thread}
    gaps, by_span = [], {}
    for a, b in _gaps(busy, *window):
        open_, claims = [(a, b)], {}
        for s in thread:
            lo, hi = _interval(s)
            if hi <= a or lo >= b or not open_:
                continue
            rest = []
            for x, y in open_:
                cut = min(y, hi) - max(x, lo)
                if cut <= 0:
                    rest.append((x, y))
                    continue
                claims[s.id] = claims.get(s.id, 0) + cut
                if x < lo:
                    rest.append((x, lo))
                if hi < y:
                    rest.append((hi, y))
            open_ = rest
        best = max(claims, key=claims.get, default=None)
        if best is None or sum(y - x for x, y in open_) > claims[best]:
            name, best = NO_SPAN, None
        else:
            name = names[best]
        gaps.append({"start_ns": a, "dur_ns": b - a, "span": name,
                     "id": best})
        by_span[name] = by_span.get(name, 0) + b - a
    return {"gaps": gaps, "by_span": by_span,
            "idle_ns": sum(g["dur_ns"] for g in gaps)}


def _paired(modules, spans, program):
    runs = sorted((s, d) for name, s, d in modules
                  if _program(name) == program)
    dispatches = sorted((s for s in spans
                         if s.name == "train.step.dispatch"),
                        key=lambda s: s.start_ns)
    if len(runs) != len(dispatches):
        raise ValueError(
            f"{len(runs)} runs of {program!r} on the device against "
            f"{len(dispatches)} train.step.dispatch spans: not paired")
    return list(zip(dispatches, runs))


def queue_lead(modules, spans, program: str) -> list[int]:
    """How far the host runs ahead of the device, per step: the start of
    the i-th run of ``program`` minus the end of the i-th
    ``train.step.dispatch`` span, nanoseconds (both on the session's
    clock, ``spans`` being one fit's). Near 0 when the device starves;
    as many step times as the runtime's queue holds programs when the
    host only fills it. Unequal counts raise ``ValueError``: a pairing by position
    means nothing then."""
    return [run[0] - _interval(d)[1]
            for d, run in _paired(modules, spans, program)]


def traced_fit(spans, n_steps: int | None = None) -> dict | None:
    """The newest ``train.fit`` span of ``spans`` (with exactly
    ``n_steps`` ``train.step`` children, when given) in numbers:
    ``fit`` and its ``steps`` in order, ``step_host_ns`` (median over the
    steps of the step's duration less its ``train.step.dispatch`` child:
    data, placement and bookkeeping), ``dispatch_ns`` (median dispatch
    duration) and ``start_ns`` (first step's start minus the fit's: what
    ``fit`` does before its first step). None when there is no such
    fit."""
    by_parent: dict = {}
    for s in spans:
        by_parent.setdefault(s.parent, []).append(s)
    for fit in sorted((s for s in spans if s.name == "train.fit"),
                      key=lambda s: -s.start_ns):
        steps = sorted((s for s in by_parent.get(fit.id, ())
                        if s.name == "train.step"),
                       key=lambda s: s.start_ns)
        if not steps or n_steps not in (None, len(steps)):
            continue
        dispatch = [sum(c.dur_ns for c in by_parent.get(step.id, ())
                        if c.name == "train.step.dispatch")
                    for step in steps]
        return {"fit": fit, "steps": steps,
                "step_host_ns": statistics.median(
                    step.dur_ns - d for step, d in zip(steps, dispatch)),
                "dispatch_ns": statistics.median(dispatch),
                "start_ns": steps[0].start_ns - fit.start_ns}
    return None


def _scope_parts(op_name: str) -> list[str]:
    """The ``jax.named_scope`` parts of an operation's ``op_name``
    (``jit(step)/jit(main)/transpose(jvp(moe.experts))/ragged_dot`` ->
    ``[..., "moe.experts", ...]``): transformations wrap a scope's name
    in ``jvp(...)``, ``transpose(...)``, ``checkpoint``/``rematted`` and
    the like, so every part is stripped to its innermost name."""
    parts = []
    for part in op_name.split("/"):
        while "(" in part and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        parts.append(part)
    return parts


@functools.lru_cache(maxsize=1)
def _xspace_subset():
    """A message class for the part of ``xplane.proto`` that carries an
    operation's ``op_name``: the profiler files it (as ``tf_op``) under
    the EVENT METADATA's statistics, which ``jax.profiler.ProfileData``
    does not expose. Fields are declared by the numbers of the published
    schema; everything else in the file is skipped as unknown."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    pkg = "tpudl.xplane_subset"
    file = descriptor_pb2.FileDescriptorProto(
        name="tpudl_xplane_subset.proto", package=pkg, syntax="proto3")

    def message(name, *fields, parent=None):
        m = (parent.nested_type if parent is not None
             else file.message_type).add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        label=(F.LABEL_REPEATED if repeated
                               else F.LABEL_OPTIONAL),
                        type_name=type_name and f".{pkg}.{type_name}")
        return m

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("str_value", 5, F.TYPE_STRING, False, None),
            ("ref_value", 7, F.TYPE_UINT64, False, None))
    message("XEventMetadata", ("name", 2, F.TYPE_STRING, False, None),
            ("stats", 5, F.TYPE_MESSAGE, True, "XStat"))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, False, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("offset_ps", 2, F.TYPE_INT64, False, None),
            ("duration_ps", 3, F.TYPE_INT64, False, None))
    message("XLine", ("name", 2, F.TYPE_STRING, False, None),
            ("timestamp_ns", 3, F.TYPE_INT64, False, None),
            ("events", 4, F.TYPE_MESSAGE, True, "XEvent"))
    plane = message(
        "XPlane", ("name", 2, F.TYPE_STRING, False, None),
        ("lines", 3, F.TYPE_MESSAGE, True, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, True,
         "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, True,
         "XPlane.StatMetadataEntry"))
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        message(entry, ("key", 1, F.TYPE_INT64, False, None),
                ("value", 2, F.TYPE_MESSAGE, False, value),
                parent=plane).options.map_entry = True
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, True, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _load_ops(trace_dir: str) -> dict:
    """The first chip's plane of the newest trace under ``trace_dir``,
    parsed once: ``{"modules": [(name, start_ns, dur_ns)], "ops":
    [(metadata id, start_ns, dur_ns)], "meta": {metadata id: (name,
    op_name, source, category)}}``, times in nanoseconds since the
    session's start. ``op_name`` is the ``tf_op`` statistic of the
    event's metadata, ``source`` its ``file:line`` and ``category`` its
    ``hlo_category``, each ``""`` where the compiler left none. ``{}``
    without a device plane or without protobuf."""
    path = _newest(trace_dir, "*.xplane.pb")
    if path is None:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    try:
        space = _xspace_subset()()
    except ImportError:
        return {}
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = [pl for pl in space.planes if DEVICE_PLANE.match(pl.name)]
    if not planes:
        return {}
    plane = min(planes, key=lambda pl: pl.name)
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    read = {"tf_op": 1, "source": 2, "hlo_category": 3}

    def described(meta):
        out = [_op(meta.name), "", "", ""]
        for stat in meta.stats:
            at = read.get(stat_names.get(stat.metadata_id))
            if at:
                out[at] = stat.str_value or stat_names.get(stat.ref_value, "")
        return tuple(out)

    out = {"modules": [], "ops": [],
           "meta": {k: described(m) for k, m in plane.event_metadata.items()}}
    for line in plane.lines:
        if line.name not in (MODULES, OPS):
            continue
        base = line.timestamp_ns * 1000
        for e in line.events:
            start, dur = (base + e.offset_ps) // 1000, e.duration_ps // 1000
            if line.name == MODULES:
                out["modules"].append((
                    out["meta"].get(e.metadata_id, ("",))[0], start, dur))
            else:
                out["ops"].append((e.metadata_id, start, dur))
    return out


def _filed(op_name: str, wanted, kernels) -> str | None:
    """The outermost of ``wanted`` on an operation's path, or the scope
    of the ``kernels`` entry its ``op_name`` starts with, or None."""
    for part in _scope_parts(op_name):
        if part in wanted:
            return part
    return next((scope for head, scope in kernels.items()
                 if op_name.startswith(head)), None)


def load_device_op_scopes(trace_dir: str, scopes, kernels=None) -> dict:
    """The first chip's plane of the newest trace under ``trace_dir``
    with every operation filed under one of ``scopes`` (named scopes the
    program was traced with): ``{"modules": [(name, start_ns, dur_ns)],
    "ops": [(scope | None, start_ns, dur_ns)]}``, times in nanoseconds
    since the session's start. An operation's scope is the outermost of
    ``scopes`` on its ``op_name`` path (the ``tf_op`` statistic of its
    event metadata). ``kernels`` maps the start of an ``op_name`` to a
    scope, for kernels the compiler itself puts in and names (XLA's
    grouped matrix product is ``ragged-dot-…``, under no scope of the
    program). ``{}`` without a device plane or without protobuf."""
    loaded = _load_ops(trace_dir)
    return _by_scope(loaded, scopes, kernels) if loaded else {}


def _by_scope(loaded, scopes, kernels) -> dict:
    wanted, kernels = set(scopes), dict(kernels or {})
    by_id = {k: _filed(m[1], wanted, kernels)
             for k, m in loaded["meta"].items()}
    return {"modules": loaded["modules"],
            "ops": [(by_id[k], s, d) for k, s, d in loaded["ops"]]}


def _by_run(modules, ops, program: str) -> list[tuple]:
    """``((start_ns, end_ns), [op, ...])`` per run of ``program`` (its
    events on ``XLA Modules``, in order) with the ``ops`` ``(x, start_ns,
    dur_ns)`` that started inside it; the rest are dropped."""
    runs = sorted((s, s + d) for name, s, d in modules
                  if _program(name) == program)
    starts = [s for s, _ in runs]
    inside: list[list] = [[] for _ in runs]
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < runs[i][1]:
            inside[i].append(op)
    return list(zip(runs, inside))


def scope_ns_by_run(modules, ops, program: str) -> list[dict]:
    """Per run of ``program`` (its events on ``XLA Modules``, in order),
    ``{"start_ns", "dur_ns", "scopes": {scope: device ns}}`` of the
    operations that started inside the run: per scope the UNION of their
    intervals, because the ``XLA Ops`` line carries a loop and the
    operations of its body as events that overlap. ``None`` is the time
    of operations filed under no scope that no scoped operation covers:
    a scanned run of layers is one unscoped loop around scoped bodies."""
    out = []
    for (s, e), events in _by_run(modules, ops, program):
        found: dict = {}
        for scope, start, d in events:
            found.setdefault(scope, []).append((start, start + d))
        scopes = {scope: _union(iv) for scope, iv in found.items()}
        if None in found:
            scopes[None] -= _intersection(found[None], [
                iv for scope, ivs in found.items() if scope is not None
                for iv in ivs])
        out.append({"start_ns": s, "dur_ns": e - s, "scopes": scopes})
    return out


def record_device_scopes(trace_dir: str, program: str, scopes,
                         parent: Span | None = None,
                         kernels=None) -> list[dict]:
    """Put what a device trace says of the program's named scopes into
    the span ring: one ``device.<scope>`` span per run of ``program``
    and scope, its duration the device time of that scope's operations
    in that run, its start the run's start on the epoch clock
    (:func:`profile_window`), children of ``parent`` (the traced
    ``train.fit`` span). Readers then find device time by scope where
    they find the host loop's. Returns :func:`scope_ns_by_run`'s list;
    ``[]`` without a device plane.

    The FIRST call for a ``parent`` also writes the rest of the run's
    account beside them, one span a run each, so that every nanosecond
    of a traced step has a name: ``device.step`` (the run itself),
    ``device.<scope>`` for every declared scope (:func:`declared_scopes`)
    outside ``scopes`` that claims operations no scope of ``scopes``
    claims (the outermost declared scope on the operation's path;
    attribute ``ops``, their count), and ``device.unscoped``, what is
    left (``ops``). A later call for the same parent (an adapter's second
    pass, for scopes nested inside the first's) adds its own spans and
    nothing more. The scopes of the first call, the other declared
    scopes and the remainder should make up the step: the gauge
    ``obs.trace.account_gap_ns`` takes, per run, what they leave of it
    or claim beyond it."""
    loaded = _load_ops(trace_dir)
    if not loaded:
        return []
    try:
        epoch = profile_window(trace_dir)[0]
    except ValueError:
        epoch = 0
    filed = _by_scope(loaded, scopes, kernels)
    runs = scope_ns_by_run(filed["modules"], filed["ops"], program)
    tracer = get_tracer()
    first = parent is None or not any(
        s.name == "device.step" and s.parent == parent.id
        for s in tracer.spans())
    for i, run in enumerate(runs):
        for scope in scopes:
            tracer.record(f"device.{scope}", epoch + run["start_ns"],
                          run["scopes"].get(scope, 0), parent=parent,
                          run=i)
    if first:
        _record_account(loaded, program, scopes, kernels, runs, epoch,
                        parent)
    return runs


def _account_runs(loaded, program: str, scopes, kernels,
                  declared) -> list[dict]:
    """Per run of ``program``, every operation that started inside it
    filed ONCE: ``{"start_ns", "dur_ns", "ns": {label: device ns}, "ops":
    {label: count}, "rows": {label: {(source, category): ns}}}``. An
    operation's own label is its scope by ``scopes`` and ``kernels`` as
    :func:`load_device_op_scopes` files it, else the outermost of
    ``declared`` on its path, else None (the remainder). The ``XLA Ops``
    line carries a loop and the operations of its body as events that
    nest, so each operation is charged the time none of the operations
    inside it covers, under the label that answers for it: its own where
    ``scopes`` gave one, else the label of the loop around it (a scoped
    loop covers the compiler's unnamed copies inside it, as the union of
    a scope's intervals does), else its own. ``source`` is the
    operation's ``file:line`` cut to its last two parts, or the
    operation's name without its number where the compiler left none."""
    wanted, kernels = set(scopes), dict(kernels or {})
    others = set(declared) - wanted
    label, row = {}, {}
    for key, (name, op_name, source, category) in loaded["meta"].items():
        own = _filed(op_name, wanted, kernels)
        label[key] = ((own, True) if own is not None
                      else (_filed(op_name, others, {}), False))
        row[key] = ("/".join(source.split("/")[-2:]) if source
                    else name.lstrip("%").split(".")[0], category)
    out = []
    for (lo, hi), events in _by_run(loaded["modules"], loaded["ops"],
                                    program):
        open_: list = []    # (end, label, requested, index), innermost last
        charged = []        # [label, key, own ns], one an operation
        # a loop before the operations of its body that start with it
        for s, neg, key in sorted((s, -d, key) for key, s, d in events):
            while open_ and open_[-1][0] <= s:
                open_.pop()
            own, requested = label[key]
            if open_:
                _, around, around_requested, at = open_[-1]
                charged[at][2] += neg
                if not requested and around is not None:
                    own, requested = around, around_requested
            open_.append((s - neg, own, requested, len(charged)))
            charged.append([own, key, -neg])
        run = {"start_ns": lo, "dur_ns": hi - lo, "ns": {}, "ops": {},
               "rows": {}}
        for own, key, ns in charged:
            run["ns"][own] = run["ns"].get(own, 0) + ns
            run["ops"][own] = run["ops"].get(own, 0) + 1
            rows = run["rows"].setdefault(own, {})
            rows[row[key]] = rows.get(row[key], 0) + ns
        out.append(run)
    return out


def _record_account(loaded, program, scopes, kernels, runs, epoch, parent):
    """The spans :func:`record_device_scopes` adds on its first call for
    a parent, and the gauge."""
    account = _account_runs(loaded, program, scopes, kernels,
                            declared_scopes())
    found = sorted({scope for run in account for scope in run["ns"]
                    if scope is not None and scope not in scopes})
    tracer, gap = get_tracer(), _metrics.gauge("obs.trace.account_gap_ns")
    for i, (run, filed) in enumerate(zip(account, runs)):
        start = epoch + run["start_ns"]
        tracer.record("device.step", start, run["dur_ns"], parent=parent,
                      run=i)
        for scope in found:
            tracer.record(f"device.{scope}", start, run["ns"].get(scope, 0),
                          parent=parent, run=i,
                          ops=run["ops"].get(scope, 0))
        tracer.record(f"device.{UNSCOPED}", start, run["ns"].get(None, 0),
                      parent=parent, run=i, ops=run["ops"].get(None, 0))
        gap.set(run["dur_ns"] - run["ns"].get(None, 0)
                - sum(filed["scopes"].get(scope, 0) for scope in scopes)
                - sum(run["ns"].get(scope, 0) for scope in found))


# jax's own words on an operation's path: control flow, rematerialisation
# and calls. A part that is none of them, nor a ``jit(...)`` call, nor the
# path's last (the primitive), is a named scope of the program.
_JAX_PARTS = frozenset({"while", "body", "body_pred", "cond", "checkpoint",
                        "rematted_computation", "closed_call", "core_call",
                        "custom_jvp_call", "custom_vjp_call", ""})


def _path_scopes(op_names) -> set:
    """The named scopes on the paths ``op_names``, for a reader that
    traced no program and so has none declared."""
    found = set()
    for op_name in op_names:
        for part in op_name.split("/")[:-1]:
            while "(" in part and part.endswith(")"):
                head, part = part[:part.index("(")], part[
                    part.index("(") + 1:-1]
                if head in ("jit", "pjit"):
                    part = ""
            if part not in _JAX_PARTS and not part.startswith("branch_"):
                found.add(part)
    return found


def device_account(trace_dir: str, program: str | None = None,
                   kernels=None) -> dict:
    """The device's time in the newest trace under ``trace_dir`` as the
    table a person reads: ``{"program", "runs", "step_ms", "filed_ms",
    "scopes": [{"scope", "ms", "share", "ops", "rows": [{"source",
    "category", "ms"}]}]}``. One entry per top-level scope (the outermost
    declared scope on an operation's path, :func:`declared_scopes`; a
    reader that declared none takes the scopes the paths themselves
    name) in order of time, and last ``"unscoped"``, the remainder: the
    median device ms a run of ``program`` (default: the program with most
    device time), its share of the median run, the median count of
    operations, and the five ``(source file:line, hlo_category)`` rows
    with most time inside it, ms a run. ``filed_ms`` is the sum of the
    entries' ``ms``: it should be ``step_ms``. Every operation is filed
    once (:func:`_account_runs`); the compiler's own estimates of FLOPs
    and bytes, which the trace also carries, are not read: they are 0
    for a Pallas kernel and count every row of a grouped product.
    ``{}`` without a device plane."""
    loaded = _load_ops(trace_dir)
    if not loaded or not loaded["modules"]:
        return {}
    if program is None:
        total: dict = {}
        for name, _, d in loaded["modules"]:
            total[_program(name)] = total.get(_program(name), 0) + d
        program = max(total, key=total.get)
    declared = declared_scopes() or _path_scopes(
        m[1] for m in loaded["meta"].values())
    runs = _account_runs(loaded, program, (), kernels, declared)
    if not runs:
        return {}
    step = statistics.median(r["dur_ns"] for r in runs)
    scopes = []
    for scope in {scope for r in runs for scope in r["ns"]}:
        rows: dict = {}
        for r in runs:
            for key, ns in r["rows"].get(scope, {}).items():
                rows[key] = rows.get(key, 0) + ns
        ns = statistics.median(r["ns"].get(scope, 0) for r in runs)
        scopes.append({
            "scope": UNSCOPED if scope is None else scope, "ms": ns / 1e6,
            "share": ns / step if step else 0.0,
            "ops": int(statistics.median(r["ops"].get(scope, 0)
                                         for r in runs)),
            "rows": [{"source": source, "category": category,
                      "ms": total / len(runs) / 1e6}
                     for (source, category), total in sorted(
                         rows.items(), key=lambda kv: -kv[1])[:5]]})
    scopes.sort(key=lambda e: (e["scope"] == UNSCOPED, -e["ms"]))
    return {"program": program, "runs": len(runs), "step_ms": step / 1e6,
            "filed_ms": sum(e["ms"] for e in scopes), "scopes": scopes}


def merge_trace_events(spans, planes: dict) -> list[dict]:
    """One Chrome trace with the host-span lane alongside the device
    lanes, both in microseconds since the session's start (``spans``
    :func:`align`-ed, ``planes`` from :func:`load_device_planes`). Host
    events take ``pid=HOST_PID``; device planes count up from 1."""
    merged = [{"ph": "M", "pid": HOST_PID, "name": "process_name",
               "args": {"name": "tpudl host"}}]
    merged.extend(s.to_event(HOST_PID) for s in spans)
    for pid, plane in enumerate(sorted(planes), start=1):
        merged.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": plane}})
        for tid, line in enumerate(sorted(planes[plane]), start=1):
            merged.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": line}})
            merged.extend(
                {"ph": "X", "pid": pid, "tid": tid, "name": _op(name),
                 "ts": s / 1e3, "dur": d / 1e3}
                for name, s, d in planes[plane][line])
    return merged


def _fit_facts(fit, spans, busy, window) -> dict:
    """One ``train.fit`` span against the device: idle inside it (its
    duration, cut to the session's window, minus the union of program
    runs inside it) and the steps whose dispatch paid for a
    compilation."""
    lo, hi = _interval(fit)
    if window:
        lo, hi = max(lo, window[0]), min(hi, window[1])
    inside = sum(min(e, hi) - max(s, lo) for s, e in busy
                 if e > lo and s < hi)
    by_id = {s.id: s for s in spans}
    compiled = []
    for s in spans:
        if s.name != "compile.program":
            continue
        up, step = s, None
        while up is not None and up.id != fit.id:
            if up.name == "train.step":
                step = (up.attrs or {}).get("step")
            up = by_id.get(up.parent)
        if up is not None:
            compiled.append(step)
    return {"id": fit.id, "start_ns": fit.start_ns, "dur_ns": fit.dur_ns,
            "steps": sum(1 for s in children(fit, spans)
                         if s.name == "train.step"),
            "device_idle_ns": max(hi - lo, 0) - inside,
            "compilations": len(compiled), "compiled_in_steps": compiled}


def summarize_merged(spans, planes: dict, window=None) -> dict:
    """The merged-timeline summary behind ``python -m tpudl.obs trace``.

    ``spans`` and ``planes`` are on one clock (:func:`align`,
    :func:`load_device_planes`); ``window`` is the session's ``(0,
    stop - start)`` when a trace gave one. Device numbers are of the
    first chip's plane. All times in nanoseconds.

    - ``device_busy_ns`` / ``device_busy_frac``: union of ``XLA Modules``
      intervals, over the first program's start to the last one's end;
    - ``host_stage_ns`` / ``host_stage_calls``: totals per span name;
      ``host_busy_ns``: union of all host spans; ``overlap_ns``: time in
      which a host span and a program run were both open;
    - ``idle_by_span``: :func:`attribute_idle` over the window;
    - ``queue_lead``: :func:`queue_lead` of the newest fit whose
      dispatch count some program's run count equals, as ``median_ns`` /
      ``min_ns``, with ``after_dispatch_start`` (device start minus
      dispatch START, which causality keeps above 0), or ``refused``;
    - ``fits``: per ``train.fit`` span, device idle inside it and the
      compilations its steps paid for;
    - ``top_ops``: the five operations with the most device time.
    """
    first = planes[min(planes)] if planes else {}
    modules = first.get(MODULES, [])
    busy = _merged((s, s + d) for _, s, d in modules)
    host_iv = [_interval(s) for s in spans]
    stage_ns: dict = {}
    stage_calls: dict = {}
    for s in spans:
        stage_ns[s.name] = stage_ns.get(s.name, 0) + s.dur_ns
        stage_calls[s.name] = stage_calls.get(s.name, 0) + 1
    device_busy = _union(busy)
    dev_wall = busy[-1][1] - busy[0][0] if busy else 0
    host_busy = _union(host_iv)
    overlap = _intersection(host_iv, busy)
    edges = [x for iv in host_iv + busy for x in iv]
    ops: dict = {}
    for name, _, d in first.get(OPS, []):
        rec = ops.setdefault(_op(name), {"ns": 0, "count": 0})
        rec["ns"] += d
        rec["count"] += 1
    out = {
        "planes": len(planes),
        "module_count": len(modules),
        "device_busy_ns": device_busy,
        "device_busy_frac": (round(device_busy / dev_wall, 4)
                             if dev_wall > 0 else None),
        "host_stage_ns": dict(sorted(stage_ns.items())),
        "host_stage_calls": dict(sorted(stage_calls.items())),
        "host_busy_ns": host_busy,
        "overlap_ns": overlap,
        "host_overlap_frac": (round(overlap / host_busy, 4)
                              if host_busy > 0 else None),
        "wall_ns": (window[1] - window[0] if window
                    else max(edges) - min(edges) if edges else 0),
        "top_ops": [{"name": k, **v} for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1]["ns"])[:5]],
        "fits": [_fit_facts(s, spans, busy, window) for s in spans
                 if s.name == "train.fit"],
    }
    if modules and spans:
        idle = attribute_idle(modules, spans, window)
        out["idle_ns"] = idle["idle_ns"]
        out["idle_by_span"] = dict(sorted(idle["by_span"].items(),
                                          key=lambda kv: -kv[1]))
        out["queue_lead"] = _lead_facts(modules, spans)
    return out


def _lead_facts(modules, spans) -> dict | None:
    fit = traced_fit(spans)
    if fit is None:
        return None
    steps = {s.id for s in fit["steps"]}
    own = [s for s in spans if s.parent in steps]
    n = sum(1 for s in own if s.name == "train.step.dispatch")
    runs: dict = {}
    for name, _, d in modules:
        rec = runs.setdefault(_program(name), [0, 0])
        rec[0] += 1
        rec[1] += d
    programs = [p for p, (count, _) in runs.items() if count == n]
    if not programs:
        return {"refused": f"{n} train.step.dispatch spans in the newest "
                f"fit; runs by program: "
                f"{ {p: c for p, (c, _) in sorted(runs.items())} }"}
    program = max(programs, key=lambda p: runs[p][1])
    pairs = _paired(modules, own, program)
    lead = [run[0] - _interval(d)[1] for d, run in pairs]
    after = [run[0] - d.start_ns for d, run in pairs]
    return {"program": program, "pairs": len(pairs),
            "median_ns": statistics.median(lead), "min_ns": min(lead),
            "after_dispatch_start": {
                "median_ns": statistics.median(after),
                "min_ns": min(after)}}
