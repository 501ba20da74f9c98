"""Serve telemetry tests (ISSUE 18): ReqTrace bounded-stamp semantics,
the segment-sum contract (queue_wait + batching + prefill + decode ≈
end-to-end latency on a real engine run), the windowed SLO engine
(window expiry, burn/availability math, gauge publication, tail
exemplars), windowed-p99 agreement with the loadgen's own ground
truth, the ``slo_burn`` doctor rule on synthetic single- and
multi-host fixtures (rule order pinned against ``overload_shed`` and
the stall rules), the extended validators (dump request ring, status
slo section), the ``obs top`` fleet merge row, the shared-percentile
consolidation, and the <5% armed-tracing overhead guard."""

import gzip
import importlib.util
import json
import os
import statistics
import time
import types

import numpy as np
import pytest

from tpudl.obs import doctor as obs_doctor
from tpudl.obs import flight as _flight
from tpudl.obs import live as obs_live
from tpudl.obs import metrics as _metrics
from tpudl.obs import slo as _slo
from tpudl.obs.metrics import percentile
from tpudl.serve import (ModelRegistry, ReqTrace, RequestQueue, Server,
                         ServeRequest, run_closed_loop)
from tpudl.serve import reqtrace as _reqtrace
from tpudl.testing import faults as _faults
from tpudl.zoo.transformer import TinyCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the segment-sum tolerance: segments and latency_s share the
# monotonic clock but latency_s starts at the ``submitted`` attribute
# (top of __init__) while the "submit" stamp lands after prompt
# validation — tens of microseconds apart, never milliseconds
SUM_TOL_S = 0.005


@pytest.fixture(autouse=True)
def _clean_telemetry_state(monkeypatch):
    monkeypatch.delenv(_faults.PLAN_ENV, raising=False)
    _faults.disarm()
    _metrics.get_registry().reset()
    _flight.get_recorder().reset()
    _slo.reset_slo_engine()
    yield
    _faults.disarm()
    _metrics.get_registry().reset()
    _flight.get_recorder().reset()
    _slo.reset_slo_engine()


def _metric(name):
    entry = _metrics.get_registry().snapshot().get(name)
    return entry.get("value") if entry else None


def _tiny_lm():
    lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2, max_len=64)
    return lm, lm.init(0)


@pytest.fixture(scope="module")
def lm_params():
    return _tiny_lm()


def _prompt(rng, n):
    return rng.integers(1, 64, size=n).astype(np.int32)


def _server(lm, params, slots=2, cap=32):
    reg = ModelRegistry()
    reg.add_model("default", lm, params, slots=slots, cache_len=32,
                  warm=False)
    return Server(reg, RequestQueue(cap=cap))


def _drain(srv):
    srv._stop.set()
    try:
        return srv.run()
    finally:
        srv._stop.clear()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_req(latency_s, trace=None, model="default"):
    """The duck-typed view SloEngine.record()/exemplar capture needs."""
    return types.SimpleNamespace(latency_s=latency_s, model=model,
                                 trace=trace)


def _trace_with_cuts(queue_wait=0.0, batching=0.0, prefill=0.0,
                     decode=0.0):
    """A ReqTrace whose segments() returns exactly the given widths."""
    tr = ReqTrace()
    t = 1000.0
    tr.events = [("submit", t),
                 ("queue_wait_end", t + queue_wait),
                 ("rung_pack", t + queue_wait + batching),
                 ("first_token", t + queue_wait + batching + prefill),
                 ("complete",
                  t + queue_wait + batching + prefill + decode)]
    return tr


# ---------------------------------------------------------------------------
# ReqTrace: bounded stamps, terminal reserve, arming gate
# ---------------------------------------------------------------------------

class TestReqTrace:
    def test_stamps_are_bounded_with_terminal_reserve(self,
                                                      monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_TRACE_EVENTS", "12")
        tr = ReqTrace()
        for i in range(100):
            tr.stamp(f"decode_{i}")
        # cadence stamps stop early: 4 slots stay reserved...
        assert len(tr.events) == 12 - 4
        # ...so the terminal stamp ALWAYS lands, even after a long
        # decode filled the non-reserved region
        tr.stamp("complete", force=True)
        assert tr.t("complete") is not None
        # and even force stamps never breach the hard cap
        for _ in range(100):
            tr.stamp("fail", force=True)
        assert len(tr.events) == 12

    def test_t_returns_last_stamp(self):
        tr = ReqTrace()
        tr.events = [("queue_wait_end", 1.0), ("queue_wait_end", 2.0)]
        # a requeued request waits twice; the LAST wait fed the slot
        assert tr.t("queue_wait_end") == 2.0
        assert tr.t("missing") is None

    def test_segments_none_until_terminal(self):
        tr = ReqTrace()
        tr.stamp("submit")
        tr.stamp("queue_wait_end")
        assert tr.segments() is None  # no pack/first/terminal cuts yet

    def test_segments_exact_widths_and_fail_terminal(self):
        tr = _trace_with_cuts(queue_wait=1.0, batching=0.25,
                              prefill=0.5, decode=2.0)
        segs = tr.segments()
        assert segs == {"queue_wait": 1.0, "batching": 0.25,
                        "prefill": 0.5, "decode": 2.0}
        # a failed (evicted/shed) request decomposes off its fail stamp
        tr.events[-1] = ("fail", tr.events[-1][1])
        assert tr.segments()["decode"] == 2.0

    def test_disarmed_requests_carry_no_trace(self, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_TRACE", "0")
        assert _reqtrace.new_trace() is None
        req = ServeRequest([1, 2, 3], 4)
        assert req.trace is None
        # the flight descriptor still forms (trace-less, no segments)
        rec = _reqtrace.request_record(req)
        assert rec["trace_id"] is None
        assert rec["segments"] is None
        assert rec["prompt_len"] == 3

    def test_trace_ids_are_unique(self):
        ids = {ReqTrace().trace_id for _ in range(50)}
        assert len(ids) == 50

    def test_decode_cadence_env(self, monkeypatch):
        assert _reqtrace.decode_cadence() == 16
        monkeypatch.setenv("TPUDL_SERVE_TRACE_CADENCE", "3")
        assert _reqtrace.decode_cadence() == 3
        monkeypatch.setenv("TPUDL_SERVE_TRACE_CADENCE", "0")
        assert _reqtrace.decode_cadence() == 1  # floor: never div-zero


# ---------------------------------------------------------------------------
# the segment-sum contract on a REAL engine run
# ---------------------------------------------------------------------------

class TestSegmentSums:
    def test_segments_sum_to_latency(self, lm_params):
        """THE ISSUE-18 stamp-consistency acceptance: every completed
        request decomposes into four non-negative segments whose sum
        IS its measured end-to-end latency (shared clock, shared cut
        points)."""
        lm, params = lm_params
        srv = _server(lm, params, slots=2)
        rng = np.random.default_rng(18)
        reqs = [srv.submit(_prompt(rng, n), 5)
                for n in (3, 5, 7, 11, 2, 9)]
        _drain(srv)
        for req in reqs:
            req.result(timeout=1)
            assert req.trace is not None
            segs = req.trace.segments()
            assert segs is not None, req.trace.events
            assert set(segs) == set(_reqtrace.SEGMENTS)
            assert all(v >= 0.0 for v in segs.values()), segs
            assert sum(segs.values()) == pytest.approx(
                req.latency_s, abs=SUM_TOL_S)

    def test_lifecycle_stamp_order(self, lm_params):
        lm, params = lm_params
        srv = _server(lm, params, slots=1)
        rng = np.random.default_rng(19)
        req = srv.submit(_prompt(rng, 4), 4)
        _drain(srv)
        req.result(timeout=1)
        names = [n for n, _ in req.trace.events]
        for a, b in zip(("submit", "admit", "queue_wait_end",
                         "slot_insert", "rung_pack", "first_token",
                         "complete"),
                        ("admit", "queue_wait_end", "slot_insert",
                         "rung_pack", "first_token", "complete", None)):
            assert a in names
            if b is not None:
                assert names.index(a) < names.index(b), names
        times = [t for _, t in req.trace.events]
        assert times == sorted(times)

    def test_decode_cadence_stamps(self, lm_params, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_TRACE_CADENCE", "2")
        lm, params = lm_params
        srv = _server(lm, params, slots=1)  # cadence read at init
        rng = np.random.default_rng(20)
        req = srv.submit(_prompt(rng, 4), 6)
        _drain(srv)
        req.result(timeout=1)
        cadence = [n for n, _ in req.trace.events
                   if n.startswith("decode_")]
        assert cadence  # every 2nd token stamped
        assert all(int(n.split("_")[1]) % 2 == 0 for n in cadence)

    def test_typed_reject_is_stamped(self):
        from tpudl.serve import AdmissionError

        q = RequestQueue(cap=1)
        q.submit(ServeRequest([1], 2))
        doomed = ServeRequest([2], 2)
        with pytest.raises(AdmissionError):
            q.submit(doomed)
        assert any(n == "reject:queue_full"
                   for n, _ in doomed.trace.events)

    def test_request_record_is_descriptors_only(self, lm_params):
        lm, params = lm_params
        srv = _server(lm, params, slots=1)
        rng = np.random.default_rng(21)
        req = srv.submit(_prompt(rng, 6), 4)
        _drain(srv)
        req.result(timeout=1)
        rec = _reqtrace.request_record(req)
        assert rec["outcome"] == "complete"
        assert rec["prompt_len"] == 6 and rec["max_new"] == 4
        assert rec["latency_ms"] == pytest.approx(
            req.latency_s * 1000.0, abs=0.01)
        assert sum(rec["segments"].values()) == pytest.approx(
            rec["latency_ms"], abs=SUM_TOL_S * 1000.0)
        # the never-content contract, at the source
        for k in ("prompt", "tokens", "text"):
            assert k not in rec
        assert not any(isinstance(v, (list, np.ndarray))
                       for v in rec.values())


# ---------------------------------------------------------------------------
# SLO engine: window math, burn, gauges, exemplars
# ---------------------------------------------------------------------------

class TestSloEngine:
    def test_burn_and_availability_math(self, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_SLO_P99_MS", "100")
        eng = _slo.reset_slo_engine()
        now = time.monotonic()
        for ms in (50.0, 50.0, 150.0, 150.0):
            eng._stamps.append((now, ms))
        view = eng.compute(now)
        assert view["window_n"] == 4
        assert view["availability"] == 0.5
        # 50% of requests over target / 1% budget = burn 50x
        assert view["burn_short"] == pytest.approx(50.0)
        assert view["window_p50_ms"] == 150.0  # nearest-rank idx 2
        assert view["window_p99_ms"] == 150.0

    def test_window_expiry_short_vs_long(self, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_SLO_WINDOW_S", "30")
        monkeypatch.setenv("TPUDL_SERVE_SLO_P99_MS", "100")
        eng = _slo.reset_slo_engine()
        now = time.monotonic()
        eng._stamps.append((now - 100.0, 500.0))  # long window only
        eng._stamps.append((now - 5.0, 10.0))     # both windows
        view = eng.compute(now)
        assert view["window_n"] == 1              # the spike aged out
        assert view["burn_short"] == 0.0
        assert view["burn_long"] == pytest.approx(50.0)
        # stamps older than the long window count nowhere
        eng2 = _slo.reset_slo_engine()
        eng2._stamps.append((now - 400.0, 500.0))
        assert eng2.compute(now)["burn_long"] is None

    def test_empty_engine_has_no_status_section(self):
        eng = _slo.reset_slo_engine()
        assert eng.status_section() is None
        view = eng.compute()
        assert view["window_n"] == 0
        assert view["burn_short"] is None
        assert view["window_p99_ms"] is None

    def test_publish_sets_gauges(self, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_SLO_P99_MS", "100")
        eng = _slo.reset_slo_engine()
        for _ in range(4):
            eng.record(_fake_req(0.150))
        view = eng.publish(force=True)
        assert view is not None
        assert _metric("serve.slo.target_ms") == 100.0
        assert _metric("serve.slo.window_p99_ms") == pytest.approx(150.0)
        assert _metric("serve.slo.availability") == 0.0
        assert _metric("serve.slo.burn_short") == pytest.approx(100.0)

    def test_publish_is_throttled(self):
        eng = _slo.reset_slo_engine()
        now = time.monotonic()
        assert eng.publish(now=now) is not None
        assert eng.publish(now=now + 0.01) is None      # throttled
        assert eng.publish(force=True, now=now) is not None

    def test_tail_exemplar_captured_with_dominant_segment(
            self, monkeypatch):
        monkeypatch.setenv("TPUDL_SERVE_SLO_TAIL_K", "2")
        eng = _slo.reset_slo_engine()
        for _ in range(8):
            eng.record(_fake_req(0.010))
        eng.compute()  # cache the windowed median (10 ms)
        tr = _trace_with_cuts(queue_wait=0.080, batching=0.002,
                              prefill=0.008, decode=0.010)
        eng.record(_fake_req(0.100, trace=tr))  # 100 ms > 2 x 10 ms
        assert _metric("serve.slo.exemplars") == 1
        errs = [e for e in _flight.get_recorder().snapshot()["errors"]
                if e.get("kind") == "serve.slo.exemplar"]
        assert len(errs) == 1
        ex = errs[0]
        assert ex["dominant_segment"] == "queue_wait"
        assert ex["queue_wait_ms"] == pytest.approx(80.0)
        assert ex["trace_id"] == tr.trace_id
        assert ex["window_median_ms"] == pytest.approx(10.0)
        # fast requests below the k x median bar never become exemplars
        eng.record(_fake_req(0.015))
        assert _metric("serve.slo.exemplars") == 1


# ---------------------------------------------------------------------------
# windowed percentiles vs the loadgen's own ground truth
# ---------------------------------------------------------------------------

class TestWindowedVsLoadgen:
    def test_windowed_p99_matches_loadgen(self, lm_params):
        """The SLO engine's windowed percentiles and the loadgen's
        summary are computed over the SAME completed-request latencies
        with the SAME shared nearest-rank percentile — on a run that
        fits inside one window they must agree."""
        lm, params = lm_params
        srv = _server(lm, params, slots=2).start_async()
        rng = np.random.default_rng(22)
        try:
            summary = run_closed_loop(
                srv, lambda i: _prompt(rng, 3 + (i % 5)),
                requests=10, clients=2, max_new=4, timeout=120)
        finally:
            srv.close(timeout=120)
        assert summary["completed"] == 10
        assert summary["rejected"] == 0
        view = _slo.get_slo_engine().compute()
        assert view["window_n"] == 10
        assert view["window_p99_ms"] == pytest.approx(
            summary["p99_ms"], abs=0.01)
        assert view["window_p50_ms"] == pytest.approx(
            summary["p50_ms"], abs=0.01)
        assert view["window_qps"] > 0
        assert 0.0 <= view["availability"] <= 1.0
        assert len(view["window_samples_ms"]) == 10


# ---------------------------------------------------------------------------
# doctor: slo_burn classification + rule order
# ---------------------------------------------------------------------------

def _payload(**over):
    base = {"schema": "tpudl-flight-dump", "version": 1,
            "reason": "manual", "ts": time.time(), "pid": 1000,
            "process_index": 0, "process_count": 1, "argv": ["job.py"],
            "python": "3.11.0", "backend": {"jax_loaded": False},
            "env": {}, "error": None, "batches": [], "errors": [],
            "stalls": [], "metric_ticks": [], "restarts": [],
            "events": [], "metrics": {}, "pipeline_reports": {},
            "spans": [], "heartbeats": {}}
    base.update(over)
    return base


def _counter(v):
    return {"type": "counter", "value": float(v)}


def _gauge(v):
    return {"type": "gauge", "value": float(v)}


def _stall(stage, name="serve.loop", age=12.0):
    return {"ts": time.time(), "name": name, "info": {"stage": stage},
            "beats": 5, "age_s": age, "stall_s": 5.0, "active": [name],
            "stacks": {"1:MainThread": ["  File x, line 1"]}}


def _exemplar(queue_wait=400.0, batching=5.0, prefill=20.0,
              decode=30.0):
    seg = {"queue_wait_ms": queue_wait, "batching_ms": batching,
           "prefill_ms": prefill, "decode_ms": decode}
    dominant = max(seg, key=seg.get)[:-3]
    return {"ts": time.time(), "kind": "serve.slo.exemplar",
            "type": "str", "message": "tail request",
            "latency_ms": sum(seg.values()), "trace_id": "1000-1",
            "dominant_segment": dominant, **seg}


def _write_dump(path, payload):
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(payload, f)
    return str(path)


_BURN_METRICS = {"serve.slo.burn_short": _gauge(6.0),
                 "serve.slo.target_ms": _gauge(100.0),
                 "serve.slo.window_p99_ms": _gauge(450.0),
                 "serve.requests": _counter(200),
                 "serve.completed": _counter(195)}


class TestDoctorSloBurn:
    def test_slo_burn_names_dominant_segment(self, tmp_path):
        """THE ISSUE-18 forensics acceptance: a death while the burn
        gauge reads >= 1 with enough tail exemplars is classified
        ``slo_burn``, the dominant slow segment is named, and the
        remedy points at it."""
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", metrics=dict(_BURN_METRICS),
            errors=[_exemplar() for _ in range(4)]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "slo_burn"
        assert diag["suspect_stage"] == "queue_wait"
        head = diag["evidence"][0]
        assert "p99 burn" in head and "450ms" in head
        assert "burn 6.0x" in head and "queue_wait" in head
        assert any(e.startswith("tail time by segment:")
                   for e in diag["evidence"])
        assert any("TPUDL_SERVE_SLOTS" in e for e in diag["evidence"])

    def test_overload_shed_outranks_slo_burn(self, tmp_path):
        """Rule order, pinned: typed rejects are the louder fact —
        when the plane was BOTH shedding and burning, the shed story
        wins."""
        metrics = dict(_BURN_METRICS)
        metrics["serve.rejects"] = _counter(30)
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", metrics=metrics,
            errors=[_exemplar() for _ in range(4)]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "overload_shed"

    def test_slo_burn_outranks_stall_rules(self, tmp_path):
        """A burning-but-live serve loop that also logged a watchdog
        stall classifies slo_burn (slow, not stuck) — with the stall
        kept as history evidence."""
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", metrics=dict(_BURN_METRICS),
            errors=[_exemplar() for _ in range(4)],
            stalls=[_stall("dispatch")]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "slo_burn"
        assert any("history: watchdog flagged" in e
                   for e in diag["evidence"])

    def test_below_gates_is_not_slo_burn(self, tmp_path):
        # too few exemplars: an anecdote, not statistics
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", metrics=dict(_BURN_METRICS),
            errors=[_exemplar() for _ in range(2)]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "clean_external_kill"
        # burn below 1.0: the budget was NOT burning at death
        metrics = dict(_BURN_METRICS)
        metrics["serve.slo.burn_short"] = _gauge(0.5)
        p = _write_dump(tmp_path / "tpudl-dump-1001.json.gz", _payload(
            reason="signal:15", pid=1001, metrics=metrics,
            errors=[_exemplar() for _ in range(4)]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "clean_external_kill"

    def test_multi_host_names_burning_host(self, tmp_path):
        _write_dump(tmp_path / "tpudl-dump-host0-1.json.gz", _payload(
            reason="signal:15", process_index=0, process_count=2,
            metrics={"serve.requests": _counter(100)}))
        _write_dump(tmp_path / "tpudl-dump-host1-2.json.gz", _payload(
            reason="signal:15", process_index=1, process_count=2,
            pid=2000, metrics=dict(_BURN_METRICS),
            errors=[_exemplar(queue_wait=5.0, decode=600.0)
                    for _ in range(3)]))
        merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert merged["n_hosts"] == 2
        assert diag["classification"] == "slo_burn"
        assert diag["suspect_host"] == "1"
        assert diag["suspect_stage"] == "decode"


# ---------------------------------------------------------------------------
# validators: dump request ring (v2), status slo section
# ---------------------------------------------------------------------------

def _req_rec(**over):
    base = {"ts": 1.0, "trace_id": "1000-1", "model": "default",
            "prompt_len": 5, "max_new": 4, "outcome": "complete",
            "ttft_ms": 2.5, "latency_ms": 12.5, "events": 7,
            "segments": {"queue_wait": 1.0, "batching": 0.1,
                         "prefill": 4.0, "decode": 7.4}}
    base.update(over)
    return base


class TestValidateDumpRequests:
    @pytest.fixture(scope="class")
    def vd(self):
        return _load_tool("validate_dump")

    def test_v2_request_ring_valid(self, vd):
        payload = _payload(version=2, requests=[_req_rec()])
        assert vd.validate_payload(payload) == []

    def test_v1_dump_without_requests_still_valid(self, vd):
        assert vd.validate_payload(_payload()) == []

    def test_v2_dump_must_carry_the_ring(self, vd):
        errs = vd.validate_payload(_payload(version=2))
        assert any("requests" in e and "missing" in e for e in errs)

    def test_prompt_content_is_a_leak(self, vd):
        payload = _payload(version=2, requests=[
            _req_rec(prompt=[1, 2, 3])])
        errs = vd.validate_payload(payload)
        assert any("must not carry prompt/token content" in e
                   for e in errs)
        payload = _payload(version=2, requests=[
            _req_rec(extra=list(range(100)))])
        errs = vd.validate_payload(payload)
        assert any("descriptors must not carry data" in e
                   for e in errs)

    def test_bad_segment_values_flagged(self, vd):
        payload = _payload(version=2, requests=[
            _req_rec(segments={"queue_wait": "slow"})])
        errs = vd.validate_payload(payload)
        assert any("segments.queue_wait" in e for e in errs)

    def test_real_dump_round_trip(self, vd, lm_params, monkeypatch,
                                  tmp_path):
        """End-to-end: a real serve run dumps a schema-valid payload
        whose request ring decomposes every completed request."""
        monkeypatch.setenv("TPUDL_FLIGHT_DIR", str(tmp_path))
        lm, params = lm_params
        srv = _server(lm, params, slots=2)
        rng = np.random.default_rng(23)
        reqs = [srv.submit(_prompt(rng, n), 4) for n in (3, 6, 9)]
        _drain(srv)
        for r in reqs:
            r.result(timeout=1)
        path = _flight.dump(reason="telemetry-test")
        assert path is not None
        assert vd.validate_dump(path) == []
        payload = json.load(gzip.open(path, "rt", encoding="utf-8"))
        assert payload["version"] >= 2
        ring = payload["requests"]
        assert len(ring) == len(reqs)
        for rec in ring:
            assert rec["outcome"] == "complete"
            assert sum(rec["segments"].values()) == pytest.approx(
                rec["latency_ms"], abs=SUM_TOL_S * 1000.0)


def _status_payload(serve):
    return {"schema": "tpudl-status", "version": 1, "ts": time.time(),
            "pid": 1234, "host": "h0", "argv": ["job.py"],
            "interval_s": 1.0, "alive": True, "runs": [],
            "heartbeats": {}, "metrics": {}, "roofline": None,
            "serve": serve}


def _slo_section(**over):
    base = {"target_ms": 500.0, "window_s": 30.0,
            "long_window_s": 300.0, "window_n": 10, "window_qps": 0.3,
            "window_p50_ms": 12.0, "window_p99_ms": 40.0,
            "availability": 1.0, "burn_short": 0.0, "burn_long": 0.0,
            "window_samples_ms": [12.0] * 10}
    base.update(over)
    return base


def _serve_status(**over):
    base = {"requests": 10, "rejects": 0, "completed": 10,
            "queue_depth": 0, "queue_cap": 64, "deadline_sheds": 0,
            "evictions": 0, "occupancy": 0.5, "tokens_per_s": 100.0,
            "p50_ms": 12.0, "p99_ms": 40.0, "models": 1,
            "slo": _slo_section()}
    base.update(over)
    return base


class TestValidateStatusSlo:
    @pytest.fixture(scope="class")
    def vs(self):
        return _load_tool("validate_status")

    def test_slo_section_valid(self, vs):
        assert vs.validate_payload(
            _status_payload(_serve_status())) == []
        # slo is optional (pre-ISSUE-18 status files stay valid)
        assert vs.validate_payload(
            _status_payload(_serve_status(slo=None))) == []

    def test_slo_section_invalids(self, vs):
        errs = vs.validate_payload(_status_payload(_serve_status(
            slo=_slo_section(availability=2.0))))
        assert any("availability" in e for e in errs)
        errs = vs.validate_payload(_status_payload(_serve_status(
            slo=_slo_section(window_p50_ms="slow"))))
        assert any("window_p50_ms" in e for e in errs)
        errs = vs.validate_payload(_status_payload(_serve_status(
            slo=_slo_section(window_samples_ms=[1.0] * 300))))
        assert any("window_samples_ms" in e for e in errs)
        slo = _slo_section()
        del slo["target_ms"]
        errs = vs.validate_payload(_status_payload(_serve_status(
            slo=slo)))
        assert any("target_ms" in e for e in errs)

    def test_live_serve_section_passes_validator(self, vs, lm_params):
        """The section the status writer actually emits after a real
        run satisfies the validator's slo schema."""
        lm, params = lm_params
        srv = _server(lm, params, slots=2)
        rng = np.random.default_rng(24)
        reqs = [srv.submit(_prompt(rng, n), 4) for n in (3, 7)]
        _drain(srv)
        for r in reqs:
            r.result(timeout=1)
        section = obs_live._serve_section(
            _metrics.get_registry().snapshot())
        assert section is not None
        assert section["slo"]["window_n"] == len(reqs)
        assert vs.validate_payload(_status_payload(section)) == []


# ---------------------------------------------------------------------------
# obs top: the fleet merge row
# ---------------------------------------------------------------------------

class TestFleetRow:
    def _status(self, pid, serve):
        st = _status_payload(serve)
        st["pid"] = pid
        return st

    def test_fleet_row_merges_samples_not_p99s(self):
        """The merged w_p99 is computed over the CONCATENATED sample
        tails — a single outlier that IS one process's nearest-rank
        p99 must not become the fleet's."""
        a = [10.0] * 60 + [100.0]   # this proc's p99 = 100
        b = [10.0] * 61             # this proc's p99 = 10
        serve_a = _serve_status(requests=40, completed=38, slo=(
            _slo_section(window_samples_ms=a, window_p99_ms=100.0,
                         window_qps=2.0, burn_short=3.0)))
        serve_b = _serve_status(requests=60, completed=59, slo=(
            _slo_section(window_samples_ms=b, window_p99_ms=10.0,
                         window_qps=1.5, burn_short=0.5)))
        out = obs_live.render([self._status(1, serve_a),
                               self._status(2, serve_b)])
        merged = percentile(sorted(a + b), 0.99)
        assert merged == 10.0  # != max-of-p99s (100): a REAL merge
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("fleet serve"))
        assert "fleet serve (2 procs)" in line
        assert "req 100" in line and "done 97" in line
        assert f"w_p99 {merged:.0f}ms" in line
        assert "qps 3.5" in line
        assert "burn 3.0x" in line  # worst process's burn

    def test_single_process_has_no_fleet_row(self):
        out = obs_live.render([self._status(1, _serve_status())])
        assert "fleet serve" not in out

    def test_windowed_p99_on_the_process_line(self):
        out = obs_live.render([self._status(1, _serve_status())])
        assert "w_p50 12ms" in out and "w_p99 40ms" in out
        # lifetime fallback when the slo section is absent
        out = obs_live.render([self._status(
            1, _serve_status(slo=None))])
        assert "p99 40ms" in out and "w_p99" not in out


# ---------------------------------------------------------------------------
# percentile consolidation: ONE nearest-rank implementation
# ---------------------------------------------------------------------------

class TestPercentileConsolidation:
    def test_shared_semantics(self):
        assert percentile([], 0.99) is None
        assert percentile([5.0], 0.99) == 5.0
        assert percentile([1, 2, 3, 4], 0.50) == 3  # nearest-rank
        assert percentile(list(range(100)), 0.99) == 99

    def test_loadgen_delegates(self):
        from tpudl.serve import loadgen

        xs = [3.0, 1.0, 2.0, 9.0, 4.0]
        for q in (0.5, 0.9, 0.99):
            assert loadgen._percentile(xs, q) == percentile(sorted(xs),
                                                            q)

    def test_histogram_delegates(self):
        h = _metrics.histogram("telemetry.test.hist")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        snap = h.to_dict()
        assert snap["p50"] == percentile([1.0, 2.0, 3.0, 4.0], 0.50)
        assert snap["p99"] == percentile([1.0, 2.0, 3.0, 4.0], 0.99)


# ---------------------------------------------------------------------------
# the armed-overhead guard: tracing must stay <5% of the serve loop
# ---------------------------------------------------------------------------

class TestTracingOverhead:
    def test_armed_tracing_under_five_percent(self, lm_params,
                                              monkeypatch):
        """The ISSUE-18 overhead acceptance: the full serve drain with
        tracing + SLO recording armed vs TPUDL_SERVE_TRACE=0, median
        of repeated runs, 5% + 10ms jitter allowance."""
        lm, params = lm_params
        srv = _server(lm, params, slots=2, cap=64)
        rng = np.random.default_rng(25)

        def one_run():
            t0 = time.perf_counter()
            reqs = [srv.submit(_prompt(rng, 3 + (i % 5)), 4)
                    for i in range(8)]
            _drain(srv)
            for r in reqs:
                r.result(timeout=10)
            return time.perf_counter() - t0

        one_run()  # warm the programs out of the measurement
        plain, armed = [], []
        for _ in range(4):
            monkeypatch.setenv("TPUDL_SERVE_TRACE", "0")
            plain.append(one_run())
            monkeypatch.setenv("TPUDL_SERVE_TRACE", "1")
            armed.append(one_run())
        med_plain = statistics.median(plain)
        med_armed = statistics.median(armed)
        assert med_armed <= med_plain * 1.05 + 0.010, (
            f"armed {med_armed:.4f}s vs plain {med_plain:.4f}s")
