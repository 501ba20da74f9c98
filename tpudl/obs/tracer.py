"""Host-span tracer: spans with a parent, on the epoch-nanosecond clock,
in a bounded ring.

The host half of the merged timeline (OBSERVABILITY.md): any layer
wraps work in ``obs.span("stage", **attrs)`` and the span lands in a
process-wide ring buffer with its id, the span that was open on the
same thread when it started (``parent``), the outermost span of that
stack (``root``), thread id/name and attributes.
``export_chrome_trace`` writes the ring as Chrome trace-event JSON, and
:mod:`tpudl.obs.trace` puts the spans beside the device planes of a
profiler trace.

Clock model: a span's start is ``time.time_ns()`` read once at entry,
its duration a ``time.perf_counter_ns()`` difference. The profiler
stamps its session with ``profile_start_time`` on the same epoch clock
and counts device events in nanoseconds since it, so one subtraction
(:func:`tpudl.obs.trace.align`) lands a span on the device trace's
clock.

Hot-loop discipline: a span is one small object, two clock reads at
entry, one at exit and a lock-guarded deque append; the ring
(``TPUDL_TRACE_RING`` spans, default 65536) never grows past its cap,
so tracing stays on in production. There is no switch: "off" is
recording into the ring with no profiler and no export.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

from tpudl.testing import tsan as _tsan

__all__ = ["Span", "Tracer", "get_tracer", "span", "export_chrome_trace",
           "children", "self_ns"]

_DEFAULT_RING = 65536
_ids = itertools.count(1)  # process-wide: ids never collide across tracers


class Span:
    """One host span, and the context manager that records it.

    ``start_ns`` is epoch nanoseconds; ``dur_ns`` is None while the span
    is open. ``parent`` is the id of the span that was open on the same
    thread at entry (None at the top), ``root`` the id of the outermost
    span of that stack. ``set(k=v)`` adds attributes while it is open.
    """

    __slots__ = ("name", "id", "parent", "root", "start_ns", "dur_ns",
                 "tid", "thread_name", "attrs", "_tracer", "_t0", "_stack")

    def __init__(self, name, start_ns, dur_ns, *, id=None, parent=None,
                 root=None, tid=0, thread_name="", attrs=None, tracer=None):
        self.name = name
        self.id = next(_ids) if id is None else id
        self.parent = parent
        self.root = self.id if root is None else root
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.thread_name = thread_name
        self.attrs = attrs
        self._tracer = tracer  # the ring it goes to when it closes
        self._stack = None
        self._t0 = 0

    # epoch microseconds: what flight dumps and the doctor have always read
    @property
    def ts_us(self) -> float:
        return self.start_ns / 1e3

    @property
    def dur_us(self) -> float:
        return (self.dur_ns or 0) / 1e3

    def set(self, **attrs) -> None:
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        stack, self.tid, self.thread_name = self._tracer._thread()
        if stack and self.parent is None:
            top = stack[-1]
            self.parent, self.root = top.id, top.root
        stack.append(self)
        self._stack = stack
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self._t0
        # raising inside the block still records the span (the failing
        # span is usually the interesting one)
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        stack, self._stack = self._stack, None
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._append(self)
        return False

    def shifted(self, by_ns: int) -> "Span":
        """A copy whose start is ``by_ns`` earlier (same id and parent)."""
        return Span(self.name, self.start_ns - by_ns, self.dur_ns,
                    id=self.id, parent=self.parent, root=self.root,
                    tid=self.tid, thread_name=self.thread_name,
                    attrs=self.attrs)

    def to_event(self, pid: int) -> dict:
        args = dict(self.attrs) if self.attrs else {}
        # integers beside the float microseconds: an epoch time in
        # microseconds has a quarter of one left in a double
        args.update(id=self.id, parent=self.parent, root=self.root,
                    start_ns=self.start_ns, dur_ns=self.dur_ns)
        return {"ph": "X", "name": self.name, "pid": pid, "tid": self.tid,
                "ts": self.ts_us, "dur": self.dur_us, "args": args}


def children(span: Span, spans) -> list[Span]:
    """The spans of ``spans`` whose parent is ``span``."""
    return [s for s in spans if s.parent == span.id]


def self_ns(span: Span, spans) -> int:
    """``span``'s duration minus the union of its children's intervals
    (each cut to the span's own): the time it spent in no child."""
    lo, hi = span.start_ns, span.start_ns + span.dur_ns
    covered, end = 0, lo
    for s, e in sorted((c.start_ns, c.start_ns + c.dur_ns)
                       for c in children(span, spans)):
        s, e = max(s, end), min(e, hi)
        if e > s:
            covered += e - s
            end = e
    return span.dur_ns - covered


class Tracer:
    """Bounded, thread-safe span ring.

    ``with tracer.span("decode", batch=3) as s:`` records one span on
    exit, a child of whatever span the thread has open; raising inside
    the block still records it with ``error`` set in its attrs. Work
    handed to another thread names its parent: ``tracer.span("x",
    parent=s)``.
    """

    def __init__(self, ring: int | None = None):
        if ring is None:
            try:
                ring = int(os.environ.get("TPUDL_TRACE_RING", "")
                           or _DEFAULT_RING)
            except ValueError:
                ring = _DEFAULT_RING
        self._spans: deque[Span] = deque(maxlen=max(1, int(ring)))
        self._lock = _tsan.named_lock("obs.tracer.ring")
        self._local = threading.local()
        self.dropped = 0  # spans pushed out of the ring

    def _thread(self) -> tuple:
        """The calling thread's ``(stack of open spans, ident, name)``."""
        try:
            return self._local.ctx
        except AttributeError:
            th = threading.current_thread()
            self._local.ctx = ctx = ([], th.ident or 0, th.name)
            return ctx

    def _append(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def span(self, name: str, parent: Span | None = None, **attrs) -> Span:
        if parent is None:
            return Span(name, 0, None, attrs=attrs or None, tracer=self)
        return Span(name, 0, None, attrs=attrs or None, tracer=self,
                    parent=parent.id, root=parent.root)

    def current(self) -> Span | None:
        """The innermost span open on the calling thread."""
        stack = self._thread()[0]
        return stack[-1] if stack else None

    def record(self, name: str, start_ns: int, dur_ns: int,
               parent: Span | None = None, **attrs) -> Span:
        """Put a span that was timed elsewhere into the ring (a
        ``jax.monitoring`` duration, a hand-written fixture). Its parent
        is ``parent`` or the span open on the calling thread."""
        stack, tid, thread_name = self._thread()
        if parent is None and stack:
            parent = stack[-1]
        s = Span(name, int(start_ns), int(dur_ns), tid=tid,
                 thread_name=thread_name, attrs=attrs or None,
                 parent=parent.id if parent else None,
                 root=parent.root if parent else None)
        self._append(s)
        return s

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self):
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def to_events(self, window: tuple[int, int] | None = None,
                  ) -> list[dict]:
        """Chrome trace-event list: process/thread metadata + one "X"
        event per span, epoch-µs timestamps, ``id`` / ``parent`` /
        ``root`` / ``start_ns`` / ``dur_ns`` in ``args``.
        ``window=(start_ns, end_ns)``, epoch nanoseconds, keeps only
        spans overlapping it: the ring outlives any one capture."""
        pid = os.getpid()
        spans = self.spans()
        if window is not None:
            w0, w1 = window
            spans = [s for s in spans
                     if s.start_ns + s.dur_ns >= w0 and s.start_ns <= w1]
        events = [{"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": "tpudl host"}}]
        seen_tids = {}
        for s in spans:
            if s.tid not in seen_tids:
                seen_tids[s.tid] = s.thread_name
        for tid, tname in sorted(seen_tids.items()):
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        events.extend(s.to_event(pid) for s in spans)
        return events

    def export_chrome_trace(self, path: str,
                            window: object = None) -> str:
        """Write the ring as ``{"traceEvents": [...]}`` JSON. Name the
        file ``*.host.trace.json`` so the CLI's directory scan finds it
        next to the profiler's ``*.xplane.pb``.

        ``window="profile"`` keeps only spans overlapping the profiler
        session whose trace lies in ``path``'s directory, by the start
        and stop the trace itself carries
        (:func:`tpudl.obs.trace.profile_window`); an explicit
        ``(start_ns, end_ns)`` tuple windows arbitrarily; None exports
        everything."""
        if window == "profile":
            from tpudl.obs.trace import profile_window

            window = profile_window(os.path.dirname(os.path.abspath(path)))
        payload = {"traceEvents": self.to_events(window=window),
                   "displayTimeUnit": "ms",
                   "metadata": {"tpudl": "host-span-tracer",
                                "dropped_spans": self.dropped}}
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, parent: Span | None = None, **attrs) -> Span:
    """``with obs.span("ml.Featurizer.transform", rows=n):`` — record a
    host span on the process-wide tracer."""
    return _TRACER.span(name, parent=parent, **attrs)


def export_chrome_trace(path: str, window: object = None) -> str:
    return _TRACER.export_chrome_trace(path, window=window)
