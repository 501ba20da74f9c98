"""Trace parsing, the host spans beside the device planes on one clock,
and the ``python -m tpudl.obs trace`` CLI (ISSUE 3 pillar 1; ISSUE 25:
the shared clock, idle attribution, queue lead, the spans of ``fit``).

Fixtures: hand-written ``xplane.pb`` files (a text proto serialized by
jax's own ProfileData) with a ``/device:TPU:0`` plane and the ``Task
Environment`` plane that carries the session's start and stop; a
trace-viewer dump (gzipped JSON, as the jax.profiler also writes) lies
beside them where a test shows that nothing reads it.
"""

import gzip
import json
import os
import subprocess
import sys

import statistics
import time

import pytest

from tpudl.obs import trace as T
from tpudl.obs.tracer import Span, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_events(base=1000.0):
    """Synthetic TPU trace: 2 module executions + 3 op events + a host
    process that must be ignored. Times in µs from ``base``."""
    return [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 3, "tid": 2, "name": "jit_step",
         "ts": base, "dur": 50.0},
        {"ph": "X", "pid": 3, "tid": 2, "name": "jit_step",
         "ts": base + 120.0, "dur": 60.0},
        {"ph": "X", "pid": 3, "tid": 3, "name": "fusion.1",
         "ts": base, "dur": 30.0,
         "args": {"hlo_category": "convolution fusion",
                  "bytes_accessed": "100"}},
        {"ph": "X", "pid": 3, "tid": 3, "name": "fusion.1",
         "ts": base + 120.0, "dur": 30.0,
         "args": {"hlo_category": "convolution fusion",
                  "bytes_accessed": "100"}},
        {"ph": "X", "pid": 3, "tid": 3, "name": "copy.2",
         "ts": base + 150.0, "dur": 10.0,
         "args": {"bytes_accessed": "0"}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "jit_step",
         "ts": base, "dur": 9e9},  # host lane: never counted
    ]


def _host_events(base=1000.0):
    """Host spans on the tracer's export shape: prepare [0,100],
    d2h [150,200] relative to ``base``."""
    return [
        {"ph": "M", "pid": 42, "name": "process_name",
         "args": {"name": "tpudl host"}},
        {"ph": "M", "pid": 42, "tid": 1, "name": "thread_name",
         "args": {"name": "MainThread"}},
        {"ph": "X", "pid": 42, "tid": 1, "name": "frame.prepare",
         "ts": base, "dur": 100.0},
        {"ph": "X", "pid": 42, "tid": 1, "name": "frame.d2h",
         "ts": base + 150.0, "dur": 50.0},
    ]


def _write_device_gz(trace_dir, events, name="x.trace.json.gz"):
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, name)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def _write_host_json(trace_dir, events, name="y.host.trace.json"):
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, name)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path


START_NS = 1_790_000_000_000_000_000   # the fixture session's start
STOP_NS = START_NS + 1_000_000         # ... and its stop, 1 ms later

# the device side of the fixtures, ns since the session's start: two
# runs of jit_step, [100k, 150k] and [220k, 280k], and their operations
MODULES = [("jit_step(123)", 100_000, 50_000),
           ("jit_step(123)", 220_000, 60_000)]
FUSION = "%fusion.1 = (bf16[256]{0:T(256)}) fusion(bf16[256]{0} %copy.2)"
OPS = [(FUSION, 100_000, 30_000), (FUSION, 220_000, 30_000),
       ("copy.2", 250_000, 10_000)]


def _task_plane(start=None, stop=None):
    """The ``Task Environment`` plane with a session's start and stop."""
    start, stop = START_NS if start is None else start, (
        STOP_NS if stop is None else stop)
    return ('planes { name: "Task Environment"\n'
            f"stats {{ metadata_id: 1 uint64_value: {start} }}\n"
            f"stats {{ metadata_id: 2 uint64_value: {stop} }}\n"
            'stat_metadata { key: 1 value { id: 1 '
            'name: "profile_start_time" } }\n'
            'stat_metadata { key: 2 value { id: 2 '
            'name: "profile_stop_time" } }\n}\n')


def _events_line(title, events):
    """A line of ``(metadata id, start_ns, dur_ns)`` events."""
    body = "".join(
        f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}\n" for m, s, d in events)
    return f'lines {{ name: "{title}" timestamp_ns: 0\n{body}}}\n'


def _write_xspace(trace_dir, name, text):
    """``text`` (a text-proto XSpace) as ``trace_dir/name``."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, name)
    with open(path, "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            text))
    return path


def _write_xplane(trace_dir, modules=MODULES, ops=OPS, start=START_NS,
                  stop=STOP_NS, name="host.xplane.pb", task_plane=True):
    """A hand-written ``xplane.pb``: one ``/device:TPU:0`` plane (when
    ``modules`` is not None), a host plane that must be ignored, and the
    ``Task Environment`` plane with the session's start and stop."""
    text = ""
    if modules is not None:
        ids = {n: i + 1 for i, n in enumerate(
            dict.fromkeys(n for n, _, _ in list(modules) + list(ops)))}

        def line(title, events):
            return _events_line(title, [(ids[n], s, d)
                                        for n, s, d in events])

        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        text += ('planes { name: "/device:TPU:0"\n'
                 + line("XLA Modules", modules) + line("XLA Ops", ops)
                 + line("Steps", modules)  # a line nobody asked for
                 + meta + "}\n")
    text += 'planes { name: "/host:CPU" }\n'
    if task_plane:
        text += _task_plane(start, stop)
    return _write_xspace(trace_dir, name, text)


def _host_tracer(base=START_NS):
    """Host spans on the epoch clock: frame.prepare [0, 100k] and
    frame.d2h [150k, 200k] ns after ``base``."""
    tr = Tracer(ring=16)
    tr.record("frame.prepare", base, 100_000)
    tr.record("frame.d2h", base + 150_000, 50_000)
    return tr


def _write_host_export(trace_dir, tracer=None, name="run.host.trace.json"):
    os.makedirs(trace_dir, exist_ok=True)
    return (tracer or _host_tracer()).export_chrome_trace(
        os.path.join(trace_dir, name))


def sp(name, start, dur, id, parent=None, tid=1, **attrs):
    return Span(name, start, dur, id=id, parent=parent, root=1, tid=tid,
                attrs=attrs or None)


class TestTraceParsing:
    def test_find_trace_files(self, tmp_path):
        d = str(tmp_path)
        assert T.find_trace_files(d) == {"host": None, "device": None}
        # the trace-viewer JSON beside it is not the device trace
        _write_device_gz(os.path.join(d, "plugins"), _device_events())
        dev = _write_xplane(os.path.join(d, "plugins"))
        host = _write_host_export(d)
        assert T.find_trace_files(d) == {"host": host, "device": dev}

    def test_load_device_planes_and_profile_window(self, tmp_path):
        d = str(tmp_path)
        old = _write_xplane(d, modules=MODULES[:1], ops=(),
                            name="old.xplane.pb", start=1, stop=2)
        os.utime(old, (1, 1))
        _write_xplane(os.path.join(d, "plugins", "profile"))
        planes = T.load_device_planes(d)  # the newest file, TPU planes only
        assert list(planes) == ["/device:TPU:0"]
        assert planes["/device:TPU:0"] == {"XLA Modules": MODULES,
                                           "XLA Ops": OPS}
        assert T.profile_window(d) == (START_NS, STOP_NS)

    def test_no_device_plane_and_no_task_plane(self, tmp_path):
        d = str(tmp_path / "cpu")
        _write_xplane(d, modules=None)
        assert T.load_device_planes(d) == {}
        assert T.profile_window(d) == (START_NS, STOP_NS)
        bare = str(tmp_path / "bare")
        _write_xplane(bare, task_plane=False)
        with pytest.raises(ValueError, match="Task Environment"):
            T.profile_window(bare)
        with pytest.raises(FileNotFoundError, match="xplane.pb"):
            T.profile_window(str(tmp_path / "nothing"))

    def test_profile_window_of_a_real_profile_is_bracketed(self, tmp_path):
        """The clock the whole merge rests on: a real CPU profile taken
        with the host and Python tracers off carries its own start, and
        that start lies between two reads of time.time_ns() around
        start_trace."""
        import jax
        import numpy as np

        d = str(tmp_path / "real")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        before = time.time_ns()
        jax.profiler.start_trace(d, profiler_options=opts)
        started = time.time_ns()
        try:
            jax.block_until_ready(jax.jit(lambda x: x + 1)(np.zeros(4)))
        finally:
            stopping = time.time_ns()
            jax.profiler.stop_trace()
        stopped = time.time_ns()
        start, stop = T.profile_window(d)
        assert before <= start <= started
        assert stopping <= stop <= stopped

    def test_export_window_profile_reads_the_trace_beside_it(self, tmp_path):
        d = str(tmp_path)
        _write_xplane(os.path.join(d, "plugins"))
        tr = Tracer(ring=8)
        tr.record("before", START_NS - 5_000, 1_000)
        tr.record("inside", START_NS + 5_000, 1_000)
        tr.record("straddles", STOP_NS - 500, 1_000)
        tr.record("after", STOP_NS + 5_000, 1_000)
        path = tr.export_chrome_trace(
            os.path.join(d, "run.host.trace.json"), window="profile")
        assert [s.name for s in T.load_host_spans(path)] == [
            "inside", "straddles"]

    def test_host_spans_survive_the_export_exactly(self, tmp_path):
        tr = Tracer(ring=8)
        with tr.span("train.step", step=3) as step:
            with tr.span("train.step.dispatch"):
                pass
        path = _write_host_export(str(tmp_path), tr)
        dispatch, loaded = T.load_host_spans(path)
        assert (loaded.id, loaded.parent, loaded.root) == (
            step.id, None, step.id)
        assert (loaded.start_ns, loaded.dur_ns) == (step.start_ns,
                                                    step.dur_ns)
        assert loaded.attrs == {"step": 3} and loaded.tid == step.tid
        assert dispatch.parent == step.id and dispatch.attrs is None


    def test_export_without_identity_still_loads(self, tmp_path):
        """An export written before spans had ids (no args): fresh ids,
        no parents, nanoseconds from the float microseconds."""
        path = _write_host_json(str(tmp_path), _host_events(base=5000.0))
        prepare, d2h = T.load_host_spans(path)
        assert (prepare.name, prepare.start_ns, prepare.dur_ns) == (
            "frame.prepare", 5_000_000, 100_000)
        assert (d2h.start_ns, d2h.dur_ns, d2h.tid) == (5_150_000, 50_000, 1)
        assert prepare.parent is None and prepare.id != d2h.id
        assert prepare.attrs is None


class TestSharedClock:
    def test_align_is_one_subtraction(self):
        spans = [sp("a", START_NS + 40, 10, 1), sp("b", START_NS - 5, 7, 2,
                                                   parent=1, step=4)]
        a, b = T.align(spans, START_NS)
        assert (a.start_ns, a.dur_ns, b.start_ns, b.dur_ns) == (40, 10, -5, 7)
        assert (b.id, b.parent, b.attrs) == (2, 1, {"step": 4})
        assert spans[0].start_ns == START_NS + 40  # the ring's are untouched

    def test_attribute_idle_names_the_innermost_covering_span(self):
        """Gaps: [150k,220k] between the runs; with the session's window
        also [0,100k] before the first and [280k,400k] after the last."""
        spans = [
            sp("train.fit", 60_000, 300_000, 1),
            sp("train.step", 140_000, 100_000, 2, parent=1),
            sp("train.step.data", 150_000, 10_000, 3, parent=2),
            sp("train.step.place", 160_000, 55_000, 4, parent=2),
            sp("train.step.dispatch", 215_000, 20_000, 5, parent=2),
            # another thread's span covers everything and answers nothing
            sp("frame.prepare", 0, 400_000, 6, tid=2),
        ]
        got = T.attribute_idle(MODULES, spans)
        assert got["gaps"] == [{"start_ns": 150_000, "dur_ns": 70_000,
                                "span": "train.step.place", "id": 4}]
        assert got["by_span"] == {"train.step.place": 70_000}
        assert got["idle_ns"] == 70_000
        whole = T.attribute_idle(MODULES, spans, window=(0, 400_000))
        assert [(g["start_ns"], g["dur_ns"], g["span"]) for g in
                whole["gaps"]] == [
            (0, 100_000, "(no span)"),          # [0,60k] bare, [60k,100k] fit
            (150_000, 70_000, "train.step.place"),
            (280_000, 120_000, "train.fit")]    # [280k,360k] fit, 40k bare
        assert whole["by_span"] == {"(no span)": 100_000,
                                    "train.step.place": 70_000,
                                    "train.fit": 120_000}
        assert whole["idle_ns"] == 290_000

    def test_attribute_idle_without_spans_or_without_programs(self):
        bare = T.attribute_idle(MODULES, [])
        assert bare["by_span"] == {"(no span)": 70_000}
        assert T.attribute_idle([], [sp("x", 0, 10, 1)]) == {
            "gaps": [], "by_span": {}, "idle_ns": 0}

    def test_queue_lead_pairs_runs_with_dispatches(self):
        spans = [
            sp("train.step.dispatch", 10_000, 20_000, 2),    # ends 30k
            sp("train.step.dispatch", 40_000, 200_000, 3),   # ends 240k
            sp("train.step.data", 35_000, 1_000, 4),
        ]
        mods = MODULES + [("jit_eval(9)", 300_000, 5_000)]
        # run 0 starts 70k after dispatch 0 ended: the host ran ahead;
        # run 1 starts 20k BEFORE dispatch 1 returned: the host waited
        assert T.queue_lead(mods, spans, "jit_step") == [70_000, -20_000]
        with pytest.raises(ValueError, match="1 runs of 'jit_eval'.*2 train"):
            T.queue_lead(mods, spans, "jit_eval")
        with pytest.raises(ValueError, match="not paired"):
            T.queue_lead(mods, spans[:1], "jit_step")

    def test_traced_fit_medians_and_selection(self):
        spans = [sp("train.fit", 0, 900, 1)]
        for i, (dur, dispatch) in enumerate([(100, 70), (120, 80), (90, 75)]):
            spans.append(sp("train.step", 200 + 150 * i, dur, 10 + i,
                            parent=1, step=i))
            spans.append(sp("train.step.dispatch", 210 + 150 * i, dispatch,
                            20 + i, parent=10 + i))
        spans.append(sp("train.fit", 5_000, 50, 2))  # newer, no steps
        got = T.traced_fit(spans)
        assert got["fit"].id == 1 and [s.id for s in got["steps"]] == [
            10, 11, 12]
        assert got["step_host_ns"] == statistics.median([30, 40, 15])
        assert got["dispatch_ns"] == 75 and got["start_ns"] == 200
        assert T.traced_fit(spans, 3)["fit"].id == 1
        assert T.traced_fit(spans, 4) is None
        assert T.traced_fit([]) is None


def _fit_spans():
    """Two steps of a fit beside MODULES, ns since the session's start:
    dispatch 0 [60k,90k] -> run 0 [100k,150k]; dispatch 1 [160k,215k]
    -> run 1 [220k,280k]; a compilation inside dispatch 1."""
    return [
        sp("train.fit", 20_000, 300_000, 1, steps=2),
        sp("train.fit.place", 21_000, 30_000, 2, parent=1),
        sp("train.step", 55_000, 40_000, 3, parent=1, step=0),
        sp("train.step.data", 56_000, 2_000, 4, parent=3),
        sp("train.step.dispatch", 60_000, 30_000, 5, parent=3),
        sp("train.step", 155_000, 62_000, 6, parent=1, step=1),
        sp("train.step.data", 156_000, 2_000, 7, parent=6),
        sp("train.step.dispatch", 160_000, 55_000, 8, parent=6),
        sp("compile.program", 170_000, 40_000, 9, parent=8, cache_hit=False),
        sp("train.fit.drain", 290_000, 25_000, 10, parent=1),
    ]


class TestMerge:
    def test_merge_places_both_streams_on_the_session_clock(self):
        """Neither stream is zeroed on its own first event: a host span
        that started 40 us into the session sits at ts 40, and the first
        program run at ts 100, where the trace put it."""
        spans = T.align(_host_tracer(base=START_NS + 40_000).spans(),
                        START_NS)
        merged = T.merge_trace_events(
            spans, {"/device:TPU:0": {"XLA Modules": MODULES,
                                      "XLA Ops": OPS}})
        host_x = [e for e in merged
                  if e.get("ph") == "X" and e["pid"] == T.HOST_PID]
        assert [(e["name"], e["ts"], e["dur"]) for e in host_x] == [
            ("frame.prepare", 40.0, 100.0), ("frame.d2h", 190.0, 50.0)]
        dev_x = [e for e in merged
                 if e.get("ph") == "X" and e["pid"] != T.HOST_PID]
        assert min(e["ts"] for e in dev_x) == 100.0
        # an operation goes by its name, not by its whole HLO text
        assert {e["name"] for e in dev_x} == {"jit_step(123)", "%fusion.1",
                                              "copy.2"}
        # device planes count up from 1 — never colliding with the host
        assert {e["pid"] for e in dev_x} == {1}
        lanes = {(e["pid"], e["args"]["name"]) for e in merged
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert lanes == {(1, "XLA Modules"), (1, "XLA Ops")}
        procs = {e["pid"]: e["args"]["name"] for e in merged
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert procs == {0: "tpudl host", 1: "/device:TPU:0"}

    def test_summarize_merged_overlap_math(self):
        # on the session's clock: host busy [0,100k]+[150k,200k], device
        # programs [100k,150k]+[220k,280k] -> they never overlap; shifted
        # 100 us later the host covers [100k,200k]+[250k,300k] -> overlap
        # [100k,150k]+[250k,280k]
        planes = {"/device:TPU:0": {"XLA Modules": MODULES, "XLA Ops": OPS}}
        s = T.summarize_merged(T.align(_host_tracer().spans(), START_NS),
                               planes, (0, 1_000_000))
        assert s["host_busy_ns"] == 150_000
        assert s["host_stage_ns"] == {"frame.d2h": 50_000,
                                      "frame.prepare": 100_000}
        assert s["host_stage_calls"] == {"frame.d2h": 1, "frame.prepare": 1}
        assert s["device_busy_ns"] == 110_000 and s["module_count"] == 2
        assert s["overlap_ns"] == 0
        assert s["device_busy_frac"] == pytest.approx(110 / 180, abs=1e-4)
        assert s["wall_ns"] == 1_000_000
        assert s["top_ops"][0] == {"name": "%fusion.1", "ns": 60_000,
                                   "count": 2}
        later = T.summarize_merged(
            T.align(_host_tracer(START_NS + 100_000).spans(), START_NS),
            planes, (0, 1_000_000))
        assert later["overlap_ns"] == 80_000
        assert later["host_overlap_frac"] == pytest.approx(80 / 150,
                                                           abs=1e-4)
        # one thread, two spans: it is the dispatching thread, and the
        # gap [150k,220k] has frame.d2h [250k,300k] nowhere near it
        assert later["idle_by_span"]["frame.prepare"] == 70_000

    def test_summarize_merged_host_only_and_device_only(self):
        s = T.summarize_merged(_host_tracer().spans(), {})
        assert s["device_busy_ns"] == 0 and s["device_busy_frac"] is None
        assert s["host_busy_ns"] == 150_000 and s["overlap_ns"] == 0
        assert s["wall_ns"] == 200_000 and "idle_by_span" not in s
        s2 = T.summarize_merged(
            [], {"/device:TPU:0": {"XLA Modules": MODULES}})
        assert s2["host_busy_ns"] == 0 and s2["host_overlap_frac"] is None
        assert s2["device_busy_ns"] == 110_000 and s2["fits"] == []

    def test_summary_of_a_fit(self):
        planes = {"/device:TPU:0": {"XLA Modules": MODULES, "XLA Ops": OPS}}
        s = T.summarize_merged(_fit_spans(), planes, (0, 400_000))
        lead = s["queue_lead"]
        assert (lead["program"], lead["pairs"]) == ("jit_step", 2)
        assert lead["median_ns"] == 7_500 and lead["min_ns"] == 5_000
        assert lead["after_dispatch_start"] == {"median_ns": 50_000,
                                                "min_ns": 40_000}
        # gaps: [0,100k] (place covers most), [150k,220k] (inside dispatch
        # 1 the compilation is innermost for 40k), [280k,400k] (past the
        # fit's end: 80k under no span, 25k drain, 15k fit)
        assert s["idle_by_span"] == {"(no span)": 120_000,
                                     "train.fit.place": 100_000,
                                     "compile.program": 70_000}
        (fit,) = s["fits"]
        assert fit["steps"] == 2 and fit["dur_ns"] == 300_000
        assert fit["device_idle_ns"] == 300_000 - 110_000
        assert (fit["compilations"], fit["compiled_in_steps"]) == (1, [1])

    def test_queue_lead_refused_when_no_program_matches(self):
        spans = [s for s in _fit_spans() if s.id != 8]  # one dispatch lost
        s = T.summarize_merged(
            spans, {"/device:TPU:0": {"XLA Modules": MODULES}}, (0, 400_000))
        assert "1 train.step.dispatch" in s["queue_lead"]["refused"]
        assert "'jit_step': 2" in s["queue_lead"]["refused"]

    def test_tracer_export_feeds_merge(self, tmp_path):
        """The real producer path: Tracer.export_chrome_trace output is
        loadable and summarizes beside a device fixture."""
        tr = Tracer(ring=16)
        with tr.span("frame.prepare"):
            pass
        path = _write_host_export(str(tmp_path), tr)
        (span,) = tr.spans()
        spans = T.align(T.load_host_spans(path), span.start_ns - 1_000)
        assert spans[0].start_ns == 1_000
        s = T.summarize_merged(
            spans, {"/device:TPU:0": {"XLA Modules": MODULES}})
        assert "frame.prepare" in s["host_stage_ns"]
        assert s["module_count"] == 2


class TestCLI:
    def test_trace_cli_end_to_end_on_fixtures(self, tmp_path):
        """``python -m tpudl.obs trace <dir>`` on a dir holding a
        host-span export AND an xplane prints the merged summary on the
        trace's clock (device busy, host stage totals, overlap, idle by
        span, queue lead, idle and compilations inside train.fit) and
        writes the merged Chrome trace."""
        d = str(tmp_path)
        _write_xplane(os.path.join(d, "plugins", "profile", "x"))
        tr = Tracer(ring=32)
        for s in _fit_spans():
            tr.record(s.name, START_NS + s.start_ns, s.dur_ns,
                      parent=next((p for p in tr.spans()
                                   if p.attrs and p.attrs.get("was") ==
                                   s.parent), None),
                      was=s.id, **(s.attrs or {}))
        _write_host_export(d, tr)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "tpudl.obs", "trace", d],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = proc.stdout
        assert f"profile_start_time {START_NS}" in out
        assert "device busy:" in out and "110 us" in out
        assert "across 2 module executions" in out
        assert "host stages:" in out and "train.step.dispatch" in out
        assert "host/device overlap:" in out
        assert "device idle by host span (890 us idle" in out
        assert "queue lead:         median 8 us, min 5 us" in out
        assert "2 runs of jit_step" in out
        assert "device idle inside train.fit: 190 us" in out
        assert "compilations inside train.fit: 1 (steps 1)" in out
        assert "top device ops:" in out and "fusion.1" in out
        merged_path = os.path.join(d, "merged.trace.json")
        with open(merged_path) as f:
            doc = json.load(f)
        xs = {e["name"]: e for e in doc["traceEvents"]
              if e.get("ph") == "X"}
        assert {"train.fit", "jit_step(123)"} <= set(xs)
        # one clock: the fit began 20 us into the session, the first run
        # at 100 us
        assert xs["train.fit"]["ts"] == 20.0

    def test_trace_cli_newest_xplane_and_gz_host_export(self, tmp_path,
                                                        capsys):
        """The device stream is the newest xplane.pb, whatever else the
        profiler wrote beside it (its trace.json.gz, an older xplane);
        a gzipped host export is read too."""
        import gzip as _gzip

        from tpudl.obs.__main__ import main

        d = str(tmp_path)
        old = _write_xplane(d, modules=MODULES[:1], ops=(),
                            name="old.xplane.pb")
        os.utime(old, (1, 1))
        _write_xplane(d)
        _write_device_gz(d, _device_events())
        plain = _write_host_export(d)
        with open(plain) as f, _gzip.open(
                os.path.join(d, "later.host.trace.json.gz"), "wt") as g:
            g.write(f.read())
        os.utime(plain, (1, 1))
        assert main(["trace", d]) == 0
        out = capsys.readouterr().out
        assert "2 module executions" in out
        assert "later.host.trace.json.gz" in out
        assert "frame.prepare" in out

    def test_trace_cli_empty_dir_fails_cleanly(self, tmp_path):
        from tpudl.obs.__main__ import main

        assert main(["trace", str(tmp_path)]) == 2

    def test_trace_cli_host_only_inprocess(self, tmp_path, capsys):
        from tpudl.obs.__main__ import main

        d = str(tmp_path)
        _write_host_export(d)
        assert main(["trace", d]) == 0
        out = capsys.readouterr().out
        assert "host stages:" in out and "frame.d2h" in out
        assert "queue lead" not in out and "device idle by" not in out

    def test_metrics_cli_validates_file(self, tmp_path, capsys):
        from tpudl.obs.__main__ import main

        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps(
                {"ts": 1.0, "event": "final", "pid": 1,
                 "metrics": {"a.b": {"type": "counter",
                                     "value": 3}}}) + "\n")
        assert main(["metrics", path]) == 0
        out = capsys.readouterr().out
        assert "a.b" in out and "OK" in out
        with open(path, "a") as f:
            f.write("garbage\n")
        assert main(["metrics", path]) == 1


class TestFitSpans:
    """The spans of ``Trainer.fit`` (ISSUE 25): names, tree, and what
    reads them."""

    @staticmethod
    def _fit(steps, scale=0.1, **trainer_kw):
        import jax.numpy as jnp
        import numpy as np
        import optax

        from tpudl import obs
        from tpudl.train.runner import Trainer

        def loss(p, x, y):
            return jnp.mean((x @ p["w"] * scale - y) ** 2)

        x = np.ones((8, 4), np.float32)
        y = np.ones((8, 1), np.float32)
        trainer = Trainer(loss, optax.sgd(0.1), **trainer_kw)
        hist = obs.histogram("train.step_seconds")
        count0 = hist.count
        mark = obs.get_tracer().record("test.mark", 0, 0).id
        trainer.fit({"w": np.zeros((4, 1), np.float32)},
                    lambda step: (x, y), steps=steps)
        spans = [s for s in obs.get_tracer().spans() if s.id > mark]
        return spans, hist.count - count0

    def test_four_step_fit_yields_the_span_tree(self):
        spans, observed = self._fit(4)
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        (fit,) = by_name["train.fit"]
        assert fit.attrs == {"steps": 4, "start": 0, "devices": 1}
        assert fit.parent is None
        steps = by_name["train.step"]
        assert [s.attrs["step"] for s in steps] == [0, 1, 2, 3]
        assert {s.parent for s in steps} == {fit.id}
        for step in steps:
            kids = [c.name for c in spans if c.parent == step.id]
            assert kids == ["train.step.data", "train.step.dispatch"]
        assert len(by_name["train.fit.place"]) == 1
        assert len(by_name["train.fit.drain"]) == 1
        assert {s.parent for s in by_name["train.fit.place"]
                + by_name["train.fit.drain"]} == {fit.id}
        # no data axis to shard over, no checkpoint directory
        assert "train.step.place" not in by_name
        assert "train.fit.restore" not in by_name
        assert "train.step.checkpoint" not in by_name
        assert {s.root for s in spans
                if s.name.startswith("train.")} == {fit.id}
        # one histogram sample per step, and it IS the span's duration
        assert observed == 4
        got = T.traced_fit(spans, 4)
        assert got["fit"] is fit and got["steps"] == steps
        assert 0 < got["start_ns"] < fit.dur_ns
        assert got["step_host_ns"] > 0 and got["dispatch_ns"] > 0
        assert sum(s.dur_ns for s in steps) <= fit.dur_ns

    def test_step_seconds_is_fed_from_the_step_span(self):
        from tpudl import obs

        spans, _ = self._fit(3, scale=0.2)
        longest = max(s.dur_ns for s in spans if s.name == "train.step")
        # the histogram keeps its maximum over all samples ever seen
        assert obs.histogram("train.step_seconds").max >= longest / 1e9

    def test_mesh_fit_has_place_spans_with_bytes(self):
        from tpudl import mesh as M

        spans, _ = self._fit(2, scale=0.3, mesh=M.build_mesh())
        place = [s for s in spans if s.name == "train.step.place"]
        assert len(place) == 2
        assert all(s.attrs == {"bytes": 8 * 4 * 4 + 8 * 4} for s in place)
        steps = [s for s in spans if s.name == "train.step"]
        assert [s.parent for s in place] == [s.id for s in steps]
        (fit,) = [s for s in spans if s.name == "train.fit"]
        assert fit.attrs["devices"] == 8

    def test_checkpoint_and_restore_spans(self, tmp_path):
        from tpudl import obs

        saves = obs.histogram("train.checkpoint_save_seconds")
        restores = obs.histogram("train.checkpoint_restore_seconds")
        s0, r0 = saves.count, restores.count
        spans, _ = self._fit(4, scale=0.4, checkpoint_dir=str(tmp_path),
                             save_every=2)
        by_id = {s.id: s for s in spans}
        ck = [s for s in spans if s.name == "train.step.checkpoint"]
        # one at step 2 (inside the loop), one forced in the drain; step
        # 4 is the last and is the drain's
        assert [by_id[s.parent].name for s in ck] == ["train.step",
                                                      "train.fit.drain"]
        assert by_id[ck[0].parent].attrs == {"step": 1}
        (restore,) = [s for s in spans if s.name == "train.fit.restore"]
        assert restore.attrs is None  # nothing to resume from
        assert saves.count - s0 == 2
        assert restores.count - r0 == 0
        again, observed = self._fit(6, scale=0.4,
                                    checkpoint_dir=str(tmp_path),
                                    save_every=2)
        (restore,) = [s for s in again if s.name == "train.fit.restore"]
        assert restore.attrs == {"resumed_at": 4}
        (fit,) = [s for s in again if s.name == "train.fit"]
        assert fit.attrs["start"] == 4 and observed == 2
        assert restores.count - r0 == 1
        assert restores.max >= restore.dur_ns / 1e9

    def test_compilation_is_a_child_of_the_dispatch_that_paid(
            self, tmp_path, monkeypatch):
        import jax

        from tpudl.compile import cache as ccache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert ccache.enable_compilation_cache(str(tmp_path / "jc"))
            ccache.enable_compilation_cache(str(tmp_path / "jc"))  # once
            spans, _ = self._fit(3, scale=0.5)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        by_id = {s.id: s for s in spans}
        compiled = [s for s in spans if s.name == "compile.program"]
        in_dispatch = [s for s in compiled
                       if by_id[s.parent].name == "train.step.dispatch"]
        # the step program compiles inside the FIRST step's dispatch,
        # once (a second listener would record it twice)
        assert len(in_dispatch) == 1
        (paid,) = in_dispatch
        step = by_id[by_id[paid.parent].parent]
        assert step.name == "train.step" and step.attrs == {"step": 0}
        assert paid.attrs["cache_hit"] in (False, None)
        dispatch = by_id[paid.parent]
        assert dispatch.start_ns <= paid.start_ns + 2_000_000
        assert paid.dur_ns <= dispatch.dur_ns
        # the summary hangs it under its fit and names the step
        (fit,) = T.summarize_merged(spans, {})["fits"]
        assert fit["compiled_in_steps"].count(0) >= 1
        assert all(s == 0 or s is None for s in fit["compiled_in_steps"])


# ---- device time by named scope (PR 28) -----------------------------------
def _write_scoped_xplane(trace_dir, name="scoped.xplane.pb"):
    """One TPU plane whose operations carry their ``op_name`` where the
    profiler puts it: the ``tf_op`` statistic of the EVENT METADATA, as a
    string or as a reference into the statistics' own names. Two runs of
    jit_step; a loop and an operation of its body overlap."""
    ops = {
        1: ("jit_step(9)", None),
        2: ("%fusion.1", "jit(step)/jvp(lm.conv_op)/dot_general:"),
        3: ("%ragged.2", "ragged-dot-none:"),
        4: ("%fusion.3", "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                         "rematted_computation/moe.route/gather:"),
        5: ("%while.4", "jit(step)/transpose(jvp(lm.head))/while"),
        6: ("%fusion.5", "jit(step)/transpose(jvp(lm.head))/while/body/"
                         "dot_general:"),
        7: ("%add.6", "jit(step)/add:"),
        8: ("%copy.7", None),
    }
    meta = ""
    for key, (op, tf_op) in ops.items():
        stat = ""
        if tf_op is not None and key != 4:
            stat = f'stats {{ metadata_id: 3 str_value: "{tf_op}" }}'
        elif tf_op is not None:       # by reference
            stat = "stats { metadata_id: 3 ref_value: 4 }"
        meta += (f'event_metadata {{ key: {key} value {{ id: {key} '
                 f'name: "{op}" {stat} }} }}\n')
    meta += ('stat_metadata { key: 3 value { id: 3 name: "tf_op" } }\n'
             f'stat_metadata {{ key: 4 value {{ id: 4 name: "{ops[4][1]}" '
             '} }\n')

    modules = [(1, 1_000, 1_000), (1, 3_000, 1_000)]
    # run 1: conv 100 + ragged 200 + route 50 + loop [1500, 1900) whose
    # body op [1600, 1800) overlaps it + add 20 + an unnamed copy 10
    run = [(2, 0, 100), (3, 100, 200), (4, 300, 50), (5, 500, 400),
           (6, 600, 200), (7, 900, 20), (8, 950, 10)]
    events = [(m, 1_000 + s, d) for m, s, d in run]
    events += [(m, 3_000 + s, 2 * d if m == 3 else d) for m, s, d in run]
    events.append((2, 5_000, 999))          # after the last run: dropped
    return _write_xspace(trace_dir, name, (
        'planes { name: "/device:TPU:0"\n'
        + _events_line("XLA Modules", modules)
        + _events_line("XLA Ops", events) + meta + "}\n"
        'planes { name: "/host:CPU" }\n' + _task_plane()))


SCOPES = ("lm.conv_op", "moe.route", "moe.experts", "lm.head", "lm.attention")


class TestDeviceScopes:
    @pytest.mark.parametrize("op_name, parts", [
        ("jit(step)/jvp(lm.conv_op)/dot_general:",
         ["step", "lm.conv_op", "dot_general:"]),
        ("jit(step)/transpose(jvp(moe.experts))/mul",
         ["step", "moe.experts", "mul"]),
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
         "lm.attention/jit(flash_attention)/pallas_call:",
         ["step", "", "checkpoint", "rematted_computation", "lm.attention",
          "flash_attention", "pallas_call:"]),
        ("dot_general[dims=((1,), (0,))]", ["dot_general[dims=((1,), (0,))]"]),
        ("", [""])])
    def test_scope_parts_strip_transformations(self, op_name, parts):
        assert T._scope_parts(op_name) == parts

    def test_operations_are_filed_by_scope_from_event_metadata(self,
                                                               tmp_path):
        _write_scoped_xplane(str(tmp_path))
        loaded = T.load_device_op_scopes(
            str(tmp_path), SCOPES, kernels={"ragged-dot": "moe.experts"})
        assert [m[0] for m in loaded["modules"]] == ["jit_step(9)"] * 2
        first = [scope for scope, s, _ in loaded["ops"] if s < 3_000]
        assert first == ["lm.conv_op", "moe.experts", "moe.route",
                         "lm.head", "lm.head", None, None]
        # without the kernel's name the grouped product is nobody's
        bare = T.load_device_op_scopes(str(tmp_path), SCOPES)
        assert [scope for scope, s, _ in bare["ops"] if s < 3_000][1] is None

    def test_scope_ns_by_run_takes_the_union_inside_each_run(self, tmp_path):
        _write_scoped_xplane(str(tmp_path))
        loaded = T.load_device_op_scopes(
            str(tmp_path), SCOPES, kernels={"ragged-dot": "moe.experts"})
        runs = T.scope_ns_by_run(loaded["modules"], loaded["ops"],
                                 "jit_step")
        assert [(r["start_ns"], r["dur_ns"]) for r in runs] == [
            (1_000, 1_000), (3_000, 1_000)]
        # the loop [500, 900) and its body [600, 800) count once
        assert runs[0]["scopes"] == {"lm.conv_op": 100, "moe.experts": 200,
                                     "moe.route": 50, "lm.head": 400,
                                     None: 30}
        assert runs[1]["scopes"]["moe.experts"] == 400
        assert T.scope_ns_by_run(loaded["modules"], loaded["ops"],
                                 "jit_other") == []
        # an unscoped loop around scoped bodies (a scanned run of layers)
        # is charged only what its bodies leave uncovered
        scanned = T.scope_ns_by_run(
            [("jit_step(1)", 0, 1_000)],
            [(None, 0, 900), ("moe.experts", 100, 300),
             ("moe.route", 400, 100), (None, 950, 20)], "jit_step")
        assert scanned[0]["scopes"] == {"moe.experts": 300, "moe.route": 100,
                                        None: 520}

    def test_record_device_scopes_writes_one_span_a_run_and_scope(
            self, tmp_path):
        _write_scoped_xplane(str(tmp_path))
        tracer = T_tracer()
        fit = tracer.record("train.fit", START_NS, 10_000, steps=2)
        runs = T.record_device_scopes(
            str(tmp_path), "jit_step", SCOPES, parent=fit,
            kernels={"ragged-dot": "moe.experts"})
        assert len(runs) == 2
        wanted = {"device." + scope for scope in SCOPES}
        mine = [s for s in tracer.spans()
                if s.parent == fit.id and s.name in wanted]
        assert len(mine) == 2 * len(SCOPES)
        experts = [s for s in mine if s.name == "device.moe.experts"]
        assert [s.dur_ns for s in experts] == [200, 400]
        assert [s.start_ns for s in experts] == [START_NS + 1_000,
                                                 START_NS + 3_000]
        assert [s.attrs["run"] for s in experts] == [0, 1]
        # a scope the trace never saw still gets its span, of no length
        assert [s.dur_ns for s in mine
                if s.name == "device.lm.attention"] == [0, 0]

    def test_no_device_plane_records_nothing(self, tmp_path):
        _write_xplane(str(tmp_path), modules=None)
        assert T.load_device_op_scopes(str(tmp_path), SCOPES) == {}
        assert T.record_device_scopes(str(tmp_path), "jit_step",
                                      SCOPES) == []
        with pytest.raises(FileNotFoundError):
            T.load_device_op_scopes(str(tmp_path / "none"), SCOPES)


def T_tracer():
    """The process-wide tracer record_device_scopes writes to."""
    from tpudl.obs import get_tracer

    return get_tracer()


# ---- the device account (PR 36) -------------------------------------------
ACCOUNT_SCOPES = ("lm.attention", "moe.experts", "lm.head")
ACCOUNT_KERNELS = {"ragged-dot": "moe.experts"}
# what the fixture's program "declares" beside them
ACCOUNT_DECLARED = ("lm.attention.latent", "lm.norm", "train.update")
# key: (name, op_name, source, hlo_category); None = no such statistic
ACCOUNT_OPS = {
    1: ("jit_step(9)", None, None, None),
    # a scanned run of layers: one unscoped loop ...
    2: ("%while.1", None, "/x/tpudl/zoo/decoder.py:458", "while"),
    # ... around scoped bodies, one of them under a nested declared scope
    3: ("%fusion.2", "jit(step)/while/body/lm.attention/dot_general:",
        "/x/tpudl/zoo/lm_blocks.py:110", "convolution fusion"),
    4: ("%fusion.3", "jit(step)/while/body/lm.attention/"
                     "lm.attention.latent/dot_general:",
        "/x/tpudl/zoo/lm_blocks.py:134", "convolution fusion"),
    5: ("%fusion.4", "jit(step)/while/body/jvp(lm.norm)/add:",
        "/x/tpudl/zoo/decoder.py:420", "loop fusion"),
    6: ("%copy.5", None, None, "data formatting"),     # the compiler's own
    7: ("%ragged-dot-none.6", "ragged-dot-none:", None, "custom-call"),
    8: ("%fusion.7", "jit(step)/train.update/add:",
        "/x/tpudl/train/step.py:89", "loop fusion"),
    # an unscoped operation with a source line
    9: ("%fusion.8", "jit(step)/add:", "/x/tpudl/train/step.py:45",
        "loop fusion"),
    # a scoped loop around an operation the compiler left unnamed
    10: ("%while.9", "jit(step)/jvp(lm.head)/while", None, "while"),
    11: ("%copy.10", None, None, "data formatting"),
    # a declared scope inside a declared scope: the outermost files it
    12: ("%fusion.11", "jit(step)/train.update/lm.norm/mul:",
         "/x/tpudl/train/step.py:90", "loop fusion"),
}
# (metadata key, start, duration) from the run's start: the account of a
# run is attention 500, experts 200, head 600 (the requested), norm 100,
# update 350 (declared), and 150 left: the loop's own 50, the unnamed
# copy inside it 50 and the add with a source line 50. 1,900 in all
ACCOUNT_RUN = [(2, 0, 700), (3, 0, 300), (4, 300, 200), (5, 500, 100),
               (6, 600, 50), (7, 700, 200), (8, 900, 300), (12, 1_200, 50),
               (9, 1_250, 50), (10, 1_300, 600), (11, 1_400, 100)]
ACCOUNT_NS = {"lm.attention": 500, "moe.experts": 200, "lm.head": 600,
              "lm.norm": 100, "train.update": 350, "unscoped": 150}


def _write_account_xplane(trace_dir, name="account.xplane.pb"):
    """Two runs of jit_step with ``ACCOUNT_RUN``'s operations: the first
    as long as they are, the second with 100 ns at its end in which
    nothing runs."""
    stats = {3: "tf_op", 5: "source", 6: "hlo_category"}
    meta = ""
    for key, (op, *values) in ACCOUNT_OPS.items():
        stat = "".join(
            f'stats {{ metadata_id: {at} str_value: "{value}" }} '
            for at, value in zip(stats, values) if value is not None)
        meta += (f'event_metadata {{ key: {key} value {{ id: {key} '
                 f'name: "{op}" {stat}}} }}\n')
    meta += "".join(f'stat_metadata {{ key: {at} value {{ id: {at} '
                    f'name: "{stat}" }} }}\n' for at, stat in stats.items())

    modules = [(1, 1_000, 1_900), (1, 4_000, 2_000)]
    events = [(m, base + s, d) for base in (1_000, 4_000)
              for m, s, d in ACCOUNT_RUN]
    events.append((9, 7_000, 999))          # after the last run: dropped
    return _write_xspace(trace_dir, name, (
        'planes { name: "/device:TPU:0"\n'
        + _events_line("XLA Modules", modules)
        + _events_line("XLA Ops", events) + meta + "}\n" + _task_plane()))


@pytest.fixture(scope="module")
def account(tmp_path_factory):
    """A traced fit's two passes over the fixture, as the hybrid and
    latent-attention adapters make them: the requested scopes, then a
    scope nested inside one of them, for one parent."""
    from tpudl import obs

    d = str(tmp_path_factory.mktemp("account"))
    _write_account_xplane(d)
    for scope in (*ACCOUNT_SCOPES, *ACCOUNT_DECLARED):
        T.named_scope(scope)     # declared as a traced program declares
    tracer = T_tracer()
    fit = tracer.record("train.fit", START_NS, 10_000, steps=2)
    runs = T.record_device_scopes(d, "jit_step", ACCOUNT_SCOPES, parent=fit,
                                  kernels=ACCOUNT_KERNELS)
    first = [s for s in tracer.spans() if s.parent == fit.id]
    gap = obs.snapshot()["obs.trace.account_gap_ns"]
    again = T.record_device_scopes(d, "jit_step", ("lm.attention.latent",),
                                   parent=fit)
    second = [s for s in tracer.spans() if s.parent == fit.id][len(first):]
    return {"dir": d, "runs": runs, "first": first, "gap": gap,
            "again": again, "second": second}


def _durations(spans, name):
    return [s.dur_ns for s in spans if s.name == name]


def _account_closes(a):
    """Per run, the top-level spans and the remainder make up the step,
    and the gauge holds what they leave of it."""
    names = {"device." + scope for scope in ACCOUNT_NS}
    for i, step in enumerate(_durations(a["first"], "device.step")):
        filed = sum(s.dur_ns for s in a["first"]
                    if s.name in names and s.attrs["run"] == i)
        assert filed == 1_900 and step - filed == (0, 100)[i]
    assert a["gap"]["value"] == 100 and a["gap"]["count"] >= 2


def _account_files_each_operation_once(a):
    table = T.device_account(a["dir"], kernels=ACCOUNT_KERNELS)
    assert table["program"] == "jit_step" and table["runs"] == 2
    assert sum(e["ops"] for e in table["scopes"]) == len(ACCOUNT_RUN)
    assert table["filed_ms"] == pytest.approx(1_900 / 1e6)
    assert table["step_ms"] == pytest.approx(1_950 / 1e6)
    by_span = {s.name[len("device."):]: s.attrs["ops"] for s in a["first"]
               if "ops" in (s.attrs or {}) and s.attrs["run"] == 0}
    assert by_span == {"lm.norm": 1, "train.update": 2, "unscoped": 3}


def _account_second_call_adds_its_own_spans_only(a):
    assert [s.name for s in a["second"]] == ["device.lm.attention.latent"] * 2
    assert [s.dur_ns for s in a["second"]] == [200, 200]
    assert a["again"][0]["scopes"]["lm.attention.latent"] == 200
    assert len(_durations(a["first"], "device.step")) == 2


def _account_requested_scopes_read_as_before(a):
    loaded = T.load_device_op_scopes(a["dir"], ACCOUNT_SCOPES,
                                     ACCOUNT_KERNELS)
    assert a["runs"] == T.scope_ns_by_run(loaded["modules"], loaded["ops"],
                                          "jit_step")
    # the None of the return value is the remainder before it was named
    assert a["runs"][0]["scopes"] == {
        **{scope: ACCOUNT_NS[scope] for scope in ACCOUNT_SCOPES}, None: 600}
    for scope in ACCOUNT_SCOPES:
        spans = [s for s in a["first"] if s.name == "device." + scope]
        assert [s.dur_ns for s in spans] == [ACCOUNT_NS[scope]] * 2
        assert [s.attrs for s in spans] == [{"run": 0}, {"run": 1}]
        assert [s.start_ns for s in spans] == [START_NS + 1_000,
                                               START_NS + 4_000]


def _account_declared_scopes_get_their_spans(a):
    assert _durations(a["first"], "device.lm.norm") == [100, 100]
    # the norm under train.update is the outermost scope's
    assert _durations(a["first"], "device.train.update") == [350, 350]
    # a declared scope inside a requested one claims nothing of its own
    assert _durations(a["first"], "device.lm.attention.latent") == []
    assert _durations(a["first"], "device.step") == [1_900, 2_000]


def _account_remainder_is_none_narrowed(a):
    assert _durations(a["first"], "device.unscoped") == [150, 150]
    for run in a["runs"]:
        assert run["scopes"][None] - 150 == (ACCOUNT_NS["lm.norm"]
                                            + ACCOUNT_NS["train.update"])


def _account_table_names_the_remainder(a):
    table = T.device_account(a["dir"], "jit_step", ACCOUNT_KERNELS)
    scopes = [e["scope"] for e in table["scopes"]]
    assert scopes[-1] == "unscoped" and scopes[0] == "lm.head"
    by_scope = {e["scope"]: e for e in table["scopes"]}
    assert {e["scope"]: round(e["ms"] * 1e6) for e in table["scopes"]} \
        == ACCOUNT_NS
    assert by_scope["lm.attention"]["share"] == pytest.approx(500 / 1_950)
    rows = {(r["source"], r["category"]): round(r["ms"] * 1e6)
            for r in by_scope["unscoped"]["rows"]}
    assert rows == {("train/step.py:45", "loop fusion"): 50,
                    ("copy", "data formatting"): 50,
                    ("zoo/decoder.py:458", "while"): 50}
    # the scoped loop answers for the unnamed copy inside it
    assert {(r["source"], r["category"]): round(r["ms"] * 1e6)
            for r in by_scope["lm.head"]["rows"]} == {
        ("while", "while"): 500, ("copy", "data formatting"): 100}
    # without the kernel's name the grouped product is the remainder's
    bare = {e["scope"]: e for e in T.device_account(a["dir"])["scopes"]}
    assert "moe.experts" not in bare
    assert bare["unscoped"]["rows"][0] == {
        "source": "ragged-dot-none", "category": "custom-call",
        "ms": pytest.approx(200 / 1e6)}


def _account_named_scope_declares(a):
    from tpudl import obs

    assert "test.account.declared" not in T.declared_scopes()
    with obs.named_scope("test.account.declared"):
        pass
    assert "test.account.declared" in obs.declared_scopes()
    assert set(ACCOUNT_DECLARED) <= T.declared_scopes()


def _account_paths_name_their_scopes(a):
    """What a reader that traced nothing files by."""
    assert T._path_scopes(
        op_name for _, op_name, *_ in ACCOUNT_OPS.values() if op_name) == {
        "lm.attention", "lm.attention.latent", "lm.norm", "train.update",
        "lm.head"}
    assert T._path_scopes([
        "jit(step)/jit(main)/transpose(jvp())/checkpoint/"
        "rematted_computation/closed_call/while/body/cond/branch_1_fun/"
        "jvp(jit(_roll_static))/mul:"]) == set()


def _account_cli_prints_the_table(a, capsys):
    from tpudl.obs.__main__ import main

    assert main(["trace", a["dir"], "--out",
                 os.path.join(a["dir"], "merged.json")]) == 0
    out = capsys.readouterr().out
    assert "device account of jit_step (2 runs" in out
    assert "unscoped" in out and "train/step.py:45" in out


@pytest.mark.parametrize("holds", [
    _account_closes, _account_files_each_operation_once,
    _account_second_call_adds_its_own_spans_only,
    _account_requested_scopes_read_as_before,
    _account_declared_scopes_get_their_spans,
    _account_remainder_is_none_narrowed,
    _account_table_names_the_remainder, _account_named_scope_declares,
    _account_paths_name_their_scopes, _account_cli_prints_the_table],
    ids=lambda f: f.__name__.removeprefix("_account_"))
def test_device_account(account, capsys, holds):
    if holds is _account_cli_prints_the_table:
        holds(account, capsys)
    else:
        holds(account)


def test_device_account_of_a_kept_chip_trace_closes():
    """On a trace a builder kept from the chip (``chiprun_out/`` is no
    part of a checkout): every operation filed once makes up the step to
    within 1%."""
    d = os.path.join(REPO, "chiprun_out", "xplane")
    if not (os.path.isdir(d) and T.find_trace_files(d)["device"]):
        pytest.skip("no kept chip trace under chiprun_out/xplane")
    table = T.device_account(d)
    assert table["runs"] >= 1 and len(table["scopes"]) > 2
    assert abs(table["filed_ms"] - table["step_ms"]) < 0.01 * table["step_ms"]
    assert table["scopes"][-1]["scope"] == "unscoped"
