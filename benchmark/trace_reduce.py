"""From a profiler trace to the numbers the per-layer readers use.

Input is the ``xplane.pb`` the JAX profiler writes; on a TPU each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per
program run (``jit_step(<id>)``) and whose line ``XLA Ops`` has one per
operation. Everything below the loader is a pure function over lists of
``(name, start_ns, duration_ns)``, so it is tested on hand-written lists.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"


def load_planes(trace_dir: str) -> dict:
    """``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` for the device
    planes of the newest ``*.xplane.pb`` under ``trace_dir``; ``{}`` when
    the trace has no device plane (a CPU rehearsal)."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}
    planes = {}
    for plane in jax.profiler.ProfileData.from_file(files[-1]).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        planes[plane.name] = {
            line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            for line in plane.lines if line.name in (MODULES, OPS)}
    return planes


def merged(intervals) -> list:
    """Coalesce possibly overlapping ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union(intervals) -> float:
    return sum(e - s for s, e in merged(intervals))


def program_name(event_name: str) -> str:
    """``jit_step(2287243686015180859)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def clip(events, lo, hi):
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            out.append((name, s2, e2 - s2))
    return out


def program_span(planes: dict, program: str):
    """First start and last end, over all planes, of ``program``'s runs."""
    runs = [(s, s + d) for lines in planes.values()
            for name, s, d in lines.get(MODULES, ())
            if program_name(name) == program]
    if not runs:
        return None
    return min(s for s, _ in runs), max(e for _, e in runs)


def reduce(planes: dict, program: str, window_ns: float | None = None,
           top: int = 10) -> dict | None:
    """The traced window in numbers.

    ``window_ns`` None: the window is ``program``'s first start to its last
    end on the device's own clock and everything is cut to it. Given (a
    host-clock wall time around the traced call): nothing is cut and busy
    time is set against that length. Returns None without a device plane.
    """
    if not planes:
        return None
    span = None
    if window_ns is None:
        span = program_span(planes, program)
        if span is None:
            return None
        window_ns = span[1] - span[0]
    busy, gaps, ops, runs = [], [], {}, []
    for i, plane in enumerate(sorted(planes)):
        lines = planes[plane]
        mods, plane_ops = lines.get(MODULES, []), lines.get(OPS, [])
        if span is not None:
            mods, plane_ops = clip(mods, *span), clip(plane_ops, *span)
        busy.append(union((s, s + d) for _, s, d in mods))
        if i:
            continue  # programs, operations and gaps: first chip only
        runs = [d for name, _, d in lines.get(MODULES, [])
                if program_name(name) == program]
        for name, _, d in plane_ops:
            ops[name] = ops.get(name, 0.0) + d
        # a host-clock window starts with the trace, whose clock starts at
        # 0: the wait before the first program and after the last are gaps
        ends = max((s + d for _, s, d in mods), default=0.0)
        edges = span is None and 0.0 < ends <= window_ns  # same clock
        end, before = (0.0, "trace start") if edges else (None, None)
        for name, s, d in sorted(mods, key=lambda ev: ev[1]):
            if end is not None and s > end:
                gaps.append((f"{program_name(before)} -> "
                             f"{program_name(name)}", s - end))
            if end is None or s + d > end:
                end, before = s + d, name
        if edges and window_ns > end:
            gaps.append((f"{program_name(before)} -> trace end",
                         window_ns - end))
    named: dict = {}
    for name, g in gaps:
        named[name] = max(named.get(name, 0.0), g)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": statistics.fmean(busy) / 1e9,
        "busy_s_per_plane": [b / 1e9 for b in busy],
        "first_start_s": min((s for ls in planes.values() for _, s, _ in
                              ls.get(MODULES, ())), default=0.0) / 1e9,
        "program_runs": len(runs),
        "program_ms": statistics.median(runs) / 1e6 if runs else None,
        "device_ops": [[n[:120], s / 1e9] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n[:120], s / 1e9] for n, s in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gap_count": len(gaps),
        "idle_gap_total_s": sum(g for _, g in gaps) / 1e9,
    }
