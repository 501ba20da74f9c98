"""Host-span tracer tests (ISSUE 3 tentpole pillar 1; ISSUE 25: spans
with an id, a parent and a start on the epoch-nanosecond clock)."""

import json
import threading
import time

import pytest

from tpudl.obs.tracer import Span, Tracer, children, self_ns


def test_span_records_name_duration_thread_attrs():
    tr = Tracer(ring=16)
    with tr.span("decode", batch=3, run="r1"):
        pass
    (s,) = tr.spans()
    assert s.name == "decode"
    assert s.dur_ns >= 0 and s.dur_us == s.dur_ns / 1e3
    assert s.tid == threading.current_thread().ident
    assert s.attrs == {"batch": 3, "run": "r1"}


def test_ring_is_bounded_and_counts_drops():
    tr = Tracer(ring=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == 8
    # newest survive, oldest dropped
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    assert tr.dropped == 12


def test_error_span_still_recorded_with_error_attr():
    tr = Tracer(ring=8)
    with pytest.raises(ValueError):
        with tr.span("boom", k=1):
            raise ValueError("x")
    (s,) = tr.spans()
    assert s.name == "boom"
    assert s.attrs["error"] == "ValueError"
    assert s.attrs["k"] == 1


def test_threads_get_distinct_tids_and_names():
    tr = Tracer(ring=32)

    def work():
        with tr.span("worker"):
            pass

    t = threading.Thread(target=work, name="obs-test-worker")
    t.start()
    t.join()
    with tr.span("main"):
        pass
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["worker"].tid != by_name["main"].tid
    assert by_name["worker"].thread_name == "obs-test-worker"


def test_export_chrome_trace_format(tmp_path):
    tr = Tracer(ring=8)
    with tr.span("prepare", run="r0"):
        pass
    with tr.span("dispatch"):
        pass
    path = str(tmp_path / "x.host.trace.json")
    tr.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    procs = [e for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"]
    assert procs and procs[0]["args"]["name"] == "tpudl host"
    xs = [e for e in events if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["prepare", "dispatch"]
    for e in xs:
        assert e["ts"] > 0 and e["dur"] >= 0 and "pid" in e and "tid" in e
    # attributes, then the span's identity and its integer nanoseconds
    first, second = tr.spans()
    assert xs[0]["args"] == {"run": "r0", "id": first.id, "parent": None,
                             "root": first.id, "start_ns": first.start_ns,
                             "dur_ns": first.dur_ns}
    assert xs[1]["args"]["id"] == second.id != first.id
    # spans are on one epoch clock: ordering survives the export
    assert xs[0]["ts"] <= xs[1]["ts"]
    assert xs[0]["ts"] == pytest.approx(first.start_ns / 1e3)


def test_export_window_filters_spans(tmp_path):
    """window=(start_ns, end_ns) exports only overlapping spans — a
    long-lived ring must not pollute a capture's merge."""
    tr = Tracer(ring=16)
    with tr.span("before"):
        pass
    time.sleep(0.002)
    w0 = time.time_ns()
    with tr.span("inside"):
        pass
    w1 = time.time_ns()
    time.sleep(0.002)
    with tr.span("after"):
        pass
    names = [e["name"] for e in tr.to_events(window=(w0, w1))
             if e.get("ph") == "X"]
    assert names == ["inside"]
    path = str(tmp_path / "w.host.trace.json")
    tr.export_chrome_trace(path, window=(w0, w1))
    with open(path) as f:
        doc = json.load(f)
    assert [e["name"] for e in doc["traceEvents"]
            if e.get("ph") == "X"] == ["inside"]
    # "profile" reads the window from the trace beside the export, and
    # says so when there is none (no silent full export)
    with pytest.raises(FileNotFoundError, match="xplane.pb"):
        tr.export_chrome_trace(path, window="profile")


def test_span_start_is_epoch_ns_read_at_entry():
    tr = Tracer(ring=4)
    before = time.time_ns()
    with tr.span("timed") as s:
        assert s.dur_ns is None  # still open
        time.sleep(0.003)
    after = time.time_ns()
    assert before <= s.start_ns <= after
    assert s.start_ns + s.dur_ns <= after + 1_000_000
    assert 3_000_000 <= s.dur_ns < after - before + 1_000_000
    assert s.ts_us == s.start_ns / 1e3


def test_nested_and_sibling_spans_have_parent_and_root():
    tr = Tracer(ring=16)
    with tr.span("fit") as fit:
        with tr.span("step") as step0:
            with tr.span("data") as data:
                pass
            with tr.span("dispatch") as dispatch:
                pass
        with tr.span("step") as step1:
            pass
    with tr.span("next") as nxt:
        pass
    ids = [s.id for s in (fit, step0, data, dispatch, step1, nxt)]
    assert len(set(ids)) == 6 and ids == sorted(ids)
    assert fit.parent is None and fit.root == fit.id
    assert step0.parent == step1.parent == fit.id
    assert data.parent == dispatch.parent == step0.id
    assert {s.root for s in (step0, data, dispatch, step1)} == {fit.id}
    assert nxt.parent is None and nxt.root == nxt.id  # the stack emptied
    assert tr.current() is None
    spans = tr.spans()
    assert [s.name for s in children(fit, spans)] == ["step", "step"]
    assert [s.name for s in children(step0, spans)] == ["data", "dispatch"]
    assert children(nxt, spans) == []


def test_error_unwinds_the_stack():
    tr = Tracer(ring=8)
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("x")
    assert tr.current() is None
    inner, outer = tr.spans()
    assert inner.parent == outer.id
    assert inner.attrs == outer.attrs == {"error": "ValueError"}


def test_parent_across_threads_is_explicit():
    """A thread starts with an empty stack; work handed to it names its
    parent, and what that work nests follows from there."""
    tr = Tracer(ring=16)
    seen = {}

    def work(parent):
        with tr.span("orphan") as o:
            pass
        with tr.span("handed", parent=parent) as h:
            with tr.span("nested") as n:
                pass
        seen.update(orphan=o, handed=h, nested=n)

    with tr.span("owner") as owner:
        t = threading.Thread(target=work, args=(owner,))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert seen["orphan"].parent is None
    assert seen["orphan"].root == seen["orphan"].id
    assert seen["handed"].parent == owner.id
    assert seen["handed"].root == owner.id
    assert seen["nested"].parent == seen["handed"].id
    assert seen["nested"].root == owner.id
    assert seen["handed"].tid != owner.tid


def test_record_takes_the_open_span_as_parent():
    tr = Tracer(ring=8)
    with tr.span("dispatch") as d:
        got = tr.record("compile.program", d.start_ns, 5_000, cache_hit=False)
    alone = tr.record("compile.program", 1, 2)
    assert (got.parent, got.root) == (d.id, d.id)
    assert got.attrs == {"cache_hit": False} and got.dur_ns == 5_000
    assert alone.parent is None and alone.root == alone.id
    assert [s.name for s in tr.spans()] == ["compile.program", "dispatch",
                                            "compile.program"]


def test_self_ns_on_a_hand_written_tree():
    """Duration minus the UNION of the children's intervals, each cut
    to the span's own."""
    def sp(name, start, dur, id, parent=None):
        return Span(name, start, dur, id=id, parent=parent, root=1)

    step = sp("step", 1_000, 1_000, 1)
    spans = [
        step,
        sp("data", 1_100, 200, 2, parent=1),       # [1100, 1300]
        sp("place", 1_250, 150, 3, parent=1),      # overlaps: [1250, 1400]
        sp("dispatch", 1_500, 700, 4, parent=1),   # runs past the end
        sp("early", 900, 150, 5, parent=1),        # starts before: [1000,1050]
        sp("compile", 1_550, 100, 6, parent=4),    # a grandchild: not ours
        sp("elsewhere", 1_000, 1_000, 7),          # no child of step
    ]
    # covered: [1000,1050] + [1100,1400] + [1500,2000] = 850
    assert self_ns(step, spans) == 150
    assert self_ns(spans[3], spans) == 600
    assert self_ns(spans[1], spans) == 200  # a leaf is all self time
    assert [s.name for s in children(step, spans)] == [
        "data", "place", "dispatch", "early"]


def test_clear_resets_ring():
    tr = Tracer(ring=4)
    with tr.span("a"):
        pass
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_module_level_span_lands_on_default_tracer():
    from tpudl import obs

    before = len(obs.get_tracer().spans())
    with obs.span("module.level"):
        pass
    spans = obs.get_tracer().spans()
    assert len(spans) >= before  # ring may wrap, but the newest is ours
    assert spans[-1].name == "module.level"
