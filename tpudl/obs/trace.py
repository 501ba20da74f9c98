"""Device traces + the host/device merged timeline.

The device half of the observability subsystem: capture a jax.profiler
trace (:func:`profile`), parse the trace-viewer JSON it writes
(:func:`load_trace_events`), aggregate the XLA Modules/Ops lanes
(:func:`summarize_device_trace`) — and MERGE the host-span tracer's
export (:mod:`tpudl.obs.tracer`) with the device lanes into one Chrome
trace (:func:`merge_trace_events`) plus one summary
(:func:`summarize_merged`): device busy %, host stage totals, and how
much host work was hidden under device compute. ``python -m tpudl.obs
trace <dir>`` drives all of this from the command line.

Time bases: the profiler's trace-viewer events use an opaque device
time base; host spans are epoch µs. The merge normalizes EACH stream to
its own first event, so the combined timeline is stream-relative — the
right call when both streams cover the same window (the
``obs.profile`` + tracer pattern), and stated in the summary either way.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os

__all__ = ["profile", "named_scope", "load_trace_events",
           "summarize_device_trace", "load_host_trace_events",
           "find_trace_files", "merge_trace_events", "summarize_merged"]

HOST_PID = 0  # merged-trace pid for the host lane (device pids re-number up)


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block; view with
    tensorboard-plugin-profile or xprof against ``log_dir``, or parse
    programmatically with :func:`load_trace_events` +
    :func:`summarize_device_trace`. The capture window is recorded on
    the host-span tracer, so ``export_chrome_trace(path,
    window="profile")`` exports exactly the spans this block covered —
    the merged-timeline pairing."""
    import time

    import jax

    from tpudl.obs import tracer as _tracer_mod

    t0_us = time.time() * 1e6
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        _tracer_mod.get_tracer().last_profile_window = (t0_us,
                                                        time.time() * 1e6)


def named_scope(name: str):
    """Label pipeline stages inside jitted code (jax.named_scope; jax
    imported lazily so host-only Frame pipelines — which report into
    this module every map_batches call — never pay the jax import)."""
    import jax

    return jax.named_scope(name)


def load_trace_events(trace_dir: str) -> list[dict]:
    """Events from the newest trace-viewer JSON under ``trace_dir``
    (written by :func:`profile`; the TPU PJRT plugin populates real
    device lanes, a CPU backend none)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    with gzip.open(max(paths, key=os.path.getmtime)) as f:
        tr = json.load(f)
    return tr["traceEvents"] if isinstance(tr, dict) else tr


def load_host_trace_events(path: str) -> list[dict]:
    """Events from a host-span tracer export (plain or gzipped JSON)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        tr = json.load(f)
    return tr["traceEvents"] if isinstance(tr, dict) else tr


def find_trace_files(trace_dir: str) -> dict:
    """Locate the newest host export and device trace under a directory:
    ``{"host": path|None, "device": path|None}``. Host exports are the
    tracer's ``*.host.trace.json`` (optionally ``.gz``); device traces
    are the profiler's ``*.trace.json.gz`` (host exports excluded)."""
    host = [p for pat in ("**/*.host.trace.json", "**/*.host.trace.json.gz")
            for p in glob.glob(os.path.join(trace_dir, pat), recursive=True)]
    dev = [p for p in glob.glob(os.path.join(trace_dir, "**/*.trace.json.gz"),
                                recursive=True)
           if not p.endswith(".host.trace.json.gz")]
    newest = lambda ps: max(ps, key=os.path.getmtime) if ps else None  # noqa: E731
    return {"host": newest(host), "device": newest(dev)}


def summarize_device_trace(events: list[dict]) -> dict:
    """Aggregate DEVICE-side time from a trace-viewer event list.

    Returns ``{"module_us": total_us_across_XLA-Module_executions,
    "module_count": n, "ops": {name: {us, count, category, long_name,
    bytes}}}``. The "XLA Modules" lane is the compiled program's
    on-device wall time — the honest chip-side throughput denominator,
    independent of host dispatch latency; the "XLA Ops" lane is
    the per-fusion attribution (SURVEY.md §5.1). Empty summary (count 0)
    when the trace has no TPU lanes (CPU backend)."""
    procs, lanes = _trace_metadata(events)
    device_pids = {p for p, n in procs.items() if "TPU" in (n or "")}
    module_us, module_count = 0.0, 0
    ops: dict[str, dict] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = lanes.get((e["pid"], e["tid"]), "")
        if lane == "XLA Modules":
            module_us += e.get("dur", 0.0)
            module_count += 1
        elif lane == "XLA Ops":
            a = e.get("args", {})
            rec = ops.setdefault(e["name"], {
                "us": 0.0, "count": 0, "category": "", "long_name": "",
                "bytes": 0})
            rec["us"] += e.get("dur", 0.0)
            rec["count"] += 1
            rec["category"] = a.get("hlo_category", rec["category"])
            rec["long_name"] = a.get("long_name", rec["long_name"])
            rec["bytes"] += int(a.get("bytes_accessed", 0) or 0)
    return {"module_us": module_us, "module_count": module_count,
            "ops": ops}


def _trace_metadata(events):
    """(pid → process name, (pid, tid) → lane name) from "M" events."""
    procs, lanes = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"].get("name", "")
        elif e.get("name") == "thread_name":
            lanes[(e["pid"], e["tid"])] = e["args"].get("name", "")
    return procs, lanes


def _durations(events, keep) -> list[tuple[float, float]]:
    """(start, end) µs intervals of "X" events passing ``keep(e)``."""
    out = []
    for e in events:
        if e.get("ph") == "X" and keep(e):
            ts = float(e.get("ts", 0.0))
            out.append((ts, ts + float(e.get("dur", 0.0))))
    return out


def _merged(intervals) -> list[tuple[float, float]]:
    """Coalesce possibly-overlapping intervals — the ONE sweep behind
    both union and intersection (diverging copies would skew
    device_busy_us vs overlap_us)."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union_us(intervals) -> float:
    """Total covered time of possibly-overlapping intervals."""
    return sum(e - s for s, e in _merged(intervals))


def _intersection_us(a, b) -> float:
    """Covered time where union(a) and union(b) overlap."""
    am, bm = _merged(a), _merged(b)
    i = j = 0
    total = 0.0
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


def _normalize(events) -> list[dict]:
    """Shift a stream's "X" timestamps so its first event starts at 0
    (metadata events pass through untouched)."""
    xs = [float(e["ts"]) for e in events
          if e.get("ph") == "X" and "ts" in e]
    if not xs:
        return list(events)
    base = min(xs)
    out = []
    for e in events:
        if e.get("ph") == "X" and "ts" in e:
            e = dict(e)
            e["ts"] = float(e["ts"]) - base
        out.append(e)
    return out


def merge_trace_events(host_events: list[dict],
                       device_events: list[dict]) -> list[dict]:
    """One Chrome trace with the host-span lane alongside the device
    lanes. Each stream is normalized to its own start (time bases are
    incompatible: host = epoch µs, device = profiler-internal); host
    events take ``pid=HOST_PID`` and device pids are renumbered from 1
    upward so the lanes can never collide."""
    host = _normalize(host_events)
    dev = _normalize(device_events)
    merged = []
    for e in host:
        e = dict(e)
        e["pid"] = HOST_PID
        merged.append(e)
    pid_map: dict = {}
    for e in device_events:
        if "pid" in e and e["pid"] not in pid_map:
            pid_map[e["pid"]] = len(pid_map) + 1
    for e in dev:
        e = dict(e)
        if "pid" in e:
            e["pid"] = pid_map[e["pid"]]
        merged.append(e)
    return merged


def summarize_merged(host_events: list[dict],
                     device_events: list[dict]) -> dict:
    """The merged-timeline summary behind ``python -m tpudl.obs trace``.

    - ``device``: :func:`summarize_device_trace` of the device stream;
    - ``device_busy_us`` / ``device_busy_frac``: union of XLA-Modules
      intervals over the stream's wall window — the chip's duty cycle;
    - ``host_stage_us``: per-span-name host totals (the run-wide
      generalization of PipelineReport's stage_seconds);
    - ``host_busy_us``: union of all host spans;
    - ``overlap_us`` / ``host_overlap_frac``: host-busy time that
      coincides with device-busy time, on each stream's own normalized
      clock — the run-level overlap-efficiency twin. Both streams must
      cover the same window for this to mean overlap (the
      ``obs.profile`` + tracer capture pattern does).
    """
    procs, lanes = _trace_metadata(device_events)
    device_pids = {p for p, n in procs.items() if "TPU" in (n or "")}
    dev_norm = _normalize(device_events)
    host_norm = _normalize(host_events)
    mod_iv = _durations(
        dev_norm, lambda e: e.get("pid") in device_pids
        and lanes.get((e["pid"], e.get("tid")), "") == "XLA Modules")
    host_iv = _durations(host_norm, lambda e: True)
    host_stage_us: dict[str, float] = {}
    host_stage_calls: dict[str, int] = {}
    for e in host_norm:
        if e.get("ph") == "X":
            host_stage_us[e["name"]] = (host_stage_us.get(e["name"], 0.0)
                                        + float(e.get("dur", 0.0)))
            host_stage_calls[e["name"]] = host_stage_calls.get(e["name"],
                                                               0) + 1
    xs = [x for s, e in mod_iv + host_iv for x in (s, e)]
    wall_us = (max(xs) - min(xs)) if xs else 0.0
    dev_xs = [x for s, e in mod_iv for x in (s, e)]
    dev_wall = (max(dev_xs) - min(dev_xs)) if dev_xs else 0.0
    device_busy = _union_us(mod_iv)
    host_busy = _union_us(host_iv)
    overlap = _intersection_us(host_iv, mod_iv)
    summary = summarize_device_trace(device_events)
    top = sorted(summary["ops"].items(), key=lambda kv: -kv[1]["us"])[:5]
    return {
        "device": summary,
        "device_busy_us": round(device_busy, 1),
        "device_busy_frac": (round(device_busy / dev_wall, 4)
                             if dev_wall > 0 else None),
        "host_stage_us": {k: round(v, 1)
                          for k, v in sorted(host_stage_us.items())},
        "host_stage_calls": dict(sorted(host_stage_calls.items())),
        "host_busy_us": round(host_busy, 1),
        "overlap_us": round(overlap, 1),
        "host_overlap_frac": (round(overlap / host_busy, 4)
                              if host_busy > 0 else None),
        "wall_us": round(wall_us, 1),
        "top_ops": [{"name": k, "us": round(v["us"], 1),
                     "count": v["count"], "category": v["category"]}
                    for k, v in top],
    }
