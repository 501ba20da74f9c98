"""tpudl.serve tests (ISSUE 17): admission-controlled queue semantics,
slot-decoder edge cases (evict-while-decoding, all-slots-full typed
reject, deadline expiry mid-decode, slot-reuse bitwise parity against
fresh-cache serial decode), rung-batched UDF dispatch, warm-start
registry, the traceck-armed zero-retrace serve loop acceptance, and
the overload-chaos acceptance (burst past queue capacity → typed
rejects, bounded queue, schema-valid dump classified
``overload_shed``)."""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from tpudl.obs import metrics as _metrics
from tpudl.serve import (AdmissionError, DeadlineExceeded, Evicted,
                         ModelRegistry, RequestQueue, RungBatcher,
                         Server, ServeRequest)
from tpudl.testing import faults as _faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_serve_state(monkeypatch):
    monkeypatch.delenv(_faults.PLAN_ENV, raising=False)
    _faults.disarm()
    _metrics.get_registry().reset()
    yield
    _faults.disarm()
    _metrics.get_registry().reset()


def _metric(name):
    entry = _metrics.get_registry().snapshot().get(name)
    return entry.get("value") if entry else None


# ---------------------------------------------------------------------------
# queue: typed admission, deadlines, the zero-hangs result contract
# ---------------------------------------------------------------------------

class TestRequestQueue:
    def test_queue_full_typed_reject(self):
        q = RequestQueue(cap=2)
        q.submit(ServeRequest([1, 2], 4))
        q.submit(ServeRequest([3], 4))
        with pytest.raises(AdmissionError) as ei:
            q.submit(ServeRequest([4], 4))
        assert ei.value.reason == "queue_full"
        assert _metric("serve.rejects") == 1
        assert _metric("serve.requests") == 2
        assert q.depth() == 2  # bounded: the reject really kept it out

    def test_hbm_budget_typed_reject(self):
        # ~1 KB budget: one 200-row int32 prompt fits, a second does not
        q = RequestQueue(cap=64, hbm_budget_mb=1e-3)
        q.submit(ServeRequest(np.ones(200, np.int32), 4))
        with pytest.raises(AdmissionError) as ei:
            q.submit(ServeRequest(np.ones(200, np.int32), 4))
        assert ei.value.reason == "hbm_budget"

    def test_deadline_shed_before_dispatch(self):
        q = RequestQueue(cap=8)
        dead = q.submit(ServeRequest([1, 2, 3], 4, deadline_s=0.0))
        live = q.submit(ServeRequest([4, 5], 4, deadline_s=60.0))
        time.sleep(0.005)
        assert q.take(4) == [live]
        assert _metric("serve.deadline_sheds") == 1
        with pytest.raises(DeadlineExceeded, match="before dispatch"):
            dead.result(timeout=0.5)

    def test_result_timeout_is_typed(self):
        req = ServeRequest([1], 2)
        with pytest.raises(TimeoutError):
            req.result(timeout=0.05)

    def test_requeue_front_preserves_order(self):
        q = RequestQueue(cap=8)
        a, b, c = [q.submit(ServeRequest([i], 2)) for i in (1, 2, 3)]
        taken = q.take(2)
        assert taken == [a, b]
        q.requeue_front(taken)
        assert q.take(3) == [a, b, c]

    def test_fail_all_unblocks_clients(self):
        q = RequestQueue(cap=8)
        req = q.submit(ServeRequest([1], 2))
        n = q.fail_all(RuntimeError("server died"))
        assert n == 1 and q.depth() == 0
        with pytest.raises(RuntimeError, match="server died"):
            req.result(timeout=0.5)


# ---------------------------------------------------------------------------
# rung batcher: ragged payloads, one padded dispatch, exact fan-out
# ---------------------------------------------------------------------------

class TestRungBatcher:
    def test_ragged_payloads_exact_split(self):
        calls = []

        def spy(x):
            calls.append(int(x.shape[0]))
            return np.asarray(x) * 2.0

        rb = RungBatcher(spy, buckets=True)
        payloads = [np.full((n, 3), n, np.float32) for n in (3, 5, 2)]
        outs = rb.run(payloads)
        assert calls == [rb.rung_for(10)]  # ONE padded dispatch
        for p, o in zip(payloads, outs):
            assert o.shape == p.shape
            np.testing.assert_array_equal(o, p * 2.0)
        assert _metric("serve.batches") == 1
        occ = _metric("serve.batch_occupancy")
        assert occ == pytest.approx(10 / rb.rung_for(10))

    def test_empty_and_single(self):
        rb = RungBatcher(lambda x: np.asarray(x) + 1, buckets=True)
        assert rb.run([]) == []
        (out,) = rb.run([np.ones((4, 2), np.float32)])
        assert out.shape == (4, 2)
        np.testing.assert_array_equal(out, np.full((4, 2), 2.0))


# ---------------------------------------------------------------------------
# slot decoder: the churn edge cases, bitwise against serial decode
# ---------------------------------------------------------------------------

def _tiny_lm():
    from tpudl.zoo.transformer import TinyCausalLM

    lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2, max_len=64)
    return lm, lm.init(0)


@pytest.fixture(scope="module")
def lm_params():
    return _tiny_lm()


def _prompt(rng, n):
    return rng.integers(1, 64, size=n).astype(np.int32)


def _serial(lm, params, prompt, max_new):
    return np.asarray(lm.generate(params, np.asarray(prompt)[None, :],
                                  max_new))[0]


def _engine(lm, params, slots):
    reg = ModelRegistry()
    return reg.add_model("m", lm, params, slots=slots, cache_len=32).engine


class TestSlotDecoder:
    def test_all_slots_full_typed_reject(self, lm_params):
        lm, params = lm_params
        eng = _engine(lm, params, slots=2)
        rng = np.random.default_rng(0)
        for i in range(2):
            eng.insert(ServeRequest(_prompt(rng, 3 + i), 4))
        with pytest.raises(AdmissionError) as ei:
            eng.insert(ServeRequest(_prompt(rng, 5), 4))
        assert ei.value.reason == "slots_full"

    def test_evict_while_decoding_peer_unaffected(self, lm_params):
        """Evicting one mid-decode slot fails its request typed and
        leaves the surviving slot's stream bitwise-intact."""
        lm, params = lm_params
        eng = _engine(lm, params, slots=2)
        rng = np.random.default_rng(1)
        keep_req = ServeRequest(_prompt(rng, 5), 6)
        drop_req = ServeRequest(_prompt(rng, 7), 6)
        eng.insert(keep_req)
        s_drop = eng.insert(drop_req)
        eng.step()  # both mid-decode now
        eng.evict(s_drop, Evicted("request cancelled mid-decode"))
        with pytest.raises(Evicted):
            drop_req.result(timeout=0.5)
        assert s_drop in eng.free()
        assert _metric("serve.evictions") == 1
        while not (done := eng.pop_completed()):
            eng.step()
        ((req, toks),) = done
        assert req is keep_req
        np.testing.assert_array_equal(
            toks, _serial(lm, params, keep_req.prompt[0], 6))

    def test_slot_reuse_bitwise_parity_after_churn(self, lm_params):
        """The cache-hygiene claim: a reused slot's stream is bitwise
        equal to a fresh-cache serial decode — the full-row prefill
        write really retires the previous occupant's state."""
        lm, params = lm_params
        eng = _engine(lm, params, slots=1)
        rng = np.random.default_rng(2)
        for plen in (9, 4, 13):  # 3 occupancies of the ONE slot
            req = ServeRequest(_prompt(rng, plen), 5)
            eng.insert(req)
            while not (done := eng.pop_completed()):
                eng.step()
            ((_, toks),) = done
            np.testing.assert_array_equal(
                toks, _serial(lm, params, req.prompt[0], 5))

    def test_cancel_by_request(self, lm_params):
        lm, params = lm_params
        eng = _engine(lm, params, slots=2)
        rng = np.random.default_rng(7)
        req = ServeRequest(_prompt(rng, 4), 8)
        eng.insert(req)
        assert eng.cancel(req) is True
        assert eng.cancel(req) is False  # no longer resident
        with pytest.raises(Evicted, match="cancelled"):
            req.result(timeout=0.5)

    def test_rung_overflow_is_typed(self, lm_params):
        lm, params = lm_params
        eng = _engine(lm, params, slots=1)
        with pytest.raises(ValueError, match="exceeds the"):
            eng.rung_for(30, 8)  # 38 > cache_len 32


# ---------------------------------------------------------------------------
# server: serial-drain parity with churn, mid-decode deadline expiry
# ---------------------------------------------------------------------------

def _drain(srv):
    """Deterministic synchronous drain of everything queued."""
    srv._stop.set()
    try:
        return srv.run()
    finally:
        srv._stop.clear()


class TestServer:
    def test_ragged_churn_parity(self, lm_params):
        """8 ragged prompts through 2 slots: >= 3 insert/evict cycles
        of churn per slot, every token stream bitwise-equal to the
        serial batch-1 generate of the same prompt."""
        lm, params = lm_params
        reg = ModelRegistry()
        reg.add_model("default", lm, params, slots=2, cache_len=32)
        srv = Server(reg, RequestQueue(cap=16))
        rng = np.random.default_rng(3)
        reqs = [srv.submit(_prompt(rng, n), 6)
                for n in (3, 5, 7, 11, 2, 9, 13, 4)]
        summary = _drain(srv)
        assert summary["completed"] == len(reqs)
        for req in reqs:
            np.testing.assert_array_equal(
                req.result(timeout=1),
                _serial(lm, params, req.prompt[0], 6))
            assert req.ttft_s is not None and req.latency_s is not None
        assert _metric("serve.inserts") == len(reqs)
        assert _metric("serve.completed") == len(reqs)

    def test_deadline_expiry_mid_decode(self, lm_params):
        """A delayed tick ages an in-flight request past its deadline
        MID-decode: the sweep evicts it typed, the peer finishes
        bitwise-clean. The delay fires at tick 2 — both requests are
        admitted on tick 1, so the expiry is unambiguously mid-decode."""
        lm, params = lm_params
        reg = ModelRegistry()
        reg.add_model("default", lm, params, slots=2, cache_len=32)
        srv = Server(reg, RequestQueue(cap=16))
        rng = np.random.default_rng(4)
        doomed = srv.submit(_prompt(rng, 5), 20, deadline_s=0.25)
        ok = srv.submit(_prompt(rng, 8), 20)
        _faults.arm(_faults.FaultPlan([{
            "point": "serve.dispatch", "action": "delay",
            "seconds": 0.4, "at_call": 2}]))
        try:
            _drain(srv)
        finally:
            _faults.disarm()
        with pytest.raises(DeadlineExceeded, match="mid-decode"):
            doomed.result(timeout=1)
        assert doomed.tokens is None
        np.testing.assert_array_equal(
            ok.result(timeout=1), _serial(lm, params, ok.prompt[0], 20))
        assert _metric("serve.deadline_sheds") == 1
        assert _metric("serve.evictions") == 1

    def test_unknown_model_is_immediate(self, lm_params):
        lm, params = lm_params
        reg = ModelRegistry()
        reg.add_model("default", lm, params, slots=1, cache_len=32)
        with pytest.raises(KeyError, match="nope"):
            Server(reg).submit([1, 2], 4, model="nope")

    def test_threaded_lifecycle_and_close(self, lm_params):
        lm, params = lm_params
        reg = ModelRegistry()
        reg.add_model("default", lm, params, slots=2, cache_len=32)
        srv = Server(reg).start_async()
        rng = np.random.default_rng(5)
        reqs = [srv.submit(_prompt(rng, n), 4) for n in (3, 6, 10)]
        outs = [r.result(timeout=120) for r in reqs]
        summary = srv.close()
        assert summary["completed"] >= len(reqs)
        for req, out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, _serial(lm, params, req.prompt[0], 4))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_get_unknown_lists_names(self, lm_params):
        lm, params = lm_params
        reg = ModelRegistry()
        reg.add_model("a", lm, params, slots=1, cache_len=32)
        with pytest.raises(KeyError, match="not registered"):
            reg.get("b")
        assert reg.names() == ["a"]


# ---------------------------------------------------------------------------
# acceptance: traceck-armed serve loop — zero retraces through churn
# ---------------------------------------------------------------------------

_ZERO_RETRACE_SCRIPT = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tpudl.testing import traceck
from tpudl.serve import ModelRegistry, RequestQueue, Server
from tpudl.zoo.transformer import TinyCausalLM

lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2, max_len=64)
params = lm.init(0)
plens = [3, 5, 7, 11, 14, 18]   # 6 distinct ragged admission shapes

def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 64, size=n).astype(np.int32)
            for n in plens]

baseline = {p.tobytes(): np.asarray(
    lm.generate(params, p[None, :], 6))[0] for p in prompts()}

reg = ModelRegistry()
reg.add_model("default", lm, params, slots=2, cache_len=32)
srv = Server(reg, RequestQueue(cap=32))

def drain(reqs):
    srv._stop.set()
    try:
        srv.run()
    finally:
        srv._stop.clear()
    return [np.asarray(r.result(timeout=1)) for r in reqs]

# warmup: every prefill rung + the step program traces once
drain([srv.submit(p, 6) for p in prompts()])
warm_traces = sum(traceck.counts().values())

# steady state: same 6 ragged shapes through 2 slots => 3 full
# insert/complete churn cycles per slot — and ZERO (re)traces
traceck.reset()
reqs = [srv.submit(p, 6) for p in prompts()]
outs = drain(reqs)
counts = traceck.counts()
parity = all(
    np.array_equal(out, baseline[req.prompt[0].tobytes()])
    for req, out in zip(reqs, outs))
json.dump({
    "warm_traces": warm_traces,
    "steady_traces": sum(counts.values()),
    "steady_retraces": sum(max(0, v - 1) for v in counts.values()),
    "distinct_shapes": len(plens),
    "churn_cycles": len(plens) // 2,
    "parity": bool(parity),
}, open(sys.argv[1], "w"))
"""


class TestZeroRetraceServe:
    def test_serve_loop_zero_retraces_bitwise(self, tmp_path):
        """THE ISSUE-17 acceptance: a traceck-armed serve loop admits
        >= 6 distinct ragged shapes across >= 3 insert/evict churn
        cycles with ZERO retraces after warmup, tokens bitwise-equal
        to serial ``generate``."""
        out_path = str(tmp_path / "serve_traceck.json")
        script = str(tmp_path / "serve_traceck.py")
        with open(script, "w") as f:
            f.write(_ZERO_RETRACE_SCRIPT)
        env = dict(os.environ)
        env["TPUDL_TRACECK"] = "1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop(_faults.PLAN_ENV, None)
        r = subprocess.run([sys.executable, script, out_path],
                           capture_output=True, text=True, env=env,
                           timeout=420, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        got = json.load(open(out_path))
        assert got["distinct_shapes"] >= 6
        assert got["churn_cycles"] >= 3
        assert got["parity"] is True
        assert got["steady_traces"] == 0, got
        assert got["steady_retraces"] == 0, got
        assert got["warm_traces"] >= 1  # the shim really was counting


# ---------------------------------------------------------------------------
# acceptance: overload chaos — burst past capacity, typed rejects,
# bounded queue, dump classified overload_shed
# ---------------------------------------------------------------------------

_OVERLOAD_SCRIPT = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tpudl import obs
from tpudl.serve import (ModelRegistry, RequestQueue, Server,
                         run_closed_loop)
from tpudl.testing import faults
from tpudl.zoo.transformer import TinyCausalLM
faults.install_from_env()

lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2, max_len=64)
params = lm.init(0)
reg = ModelRegistry()
reg.add_model("default", lm, params, slots=2, cache_len=32)
queue = RequestQueue(cap=4)
srv = Server(reg, queue).start_async()
depth_high_water = [0]

def make_prompt(i):
    depth_high_water[0] = max(depth_high_water[0], queue.depth())
    return np.random.default_rng(i).integers(
        1, 64, size=3 + (i % 5)).astype(np.int32)

# chaos window: the armed burst rule floods admission; a typed
# reject is instant, so clients may burn through every index while
# the queue is clogged — that IS the load-shedding contract
chaos = run_closed_loop(srv, make_prompt, requests=12, clients=3,
                        max_new=4, timeout=120)
# let the spike drain (bounded wait — the zero-hangs contract means
# the admitted extras MUST complete), then prove service resumes
import time
t_limit = time.monotonic() + 120
while queue.depth() > 0 and time.monotonic() < t_limit:
    time.sleep(0.05)
recovery = run_closed_loop(srv, make_prompt, requests=12, clients=3,
                           max_new=4, timeout=120)
srv.close(timeout=120)
snap = obs.snapshot()

def val(name):
    return (snap.get(name) or {}).get("value") or 0

dump_path = obs.dump(reason="overload-chaos")
json.dump({
    "chaos": chaos,
    "recovery": recovery,
    "rejects": val("serve.rejects"),
    "requests": val("serve.requests"),
    "queue_depth_final": val("serve.queue_depth"),
    "depth_high_water": depth_high_water[0],
    "queue_cap": 4,
    "dump_path": dump_path,
}, open(sys.argv[1], "w"))
"""


class TestOverloadChaos:
    def test_burst_past_capacity_sheds_typed(self, tmp_path):
        """THE ISSUE-17 overload acceptance: a ``burst`` fault plan
        drives admission past queue capacity — clients get TYPED
        rejects (not hangs), the queue never grows past its cap, and
        the flight dump classifies ``overload_shed``."""
        from tpudl.obs import doctor as obs_doctor

        out_path = str(tmp_path / "overload.json")
        script = str(tmp_path / "overload.py")
        with open(script, "w") as f:
            f.write(_OVERLOAD_SCRIPT)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["TPUDL_FLIGHT_DIR"] = str(tmp_path)
        # the first 4 client ticks each burst 12 extra submits at a
        # cap-4 queue served by 2 slots: deterministic overload, well
        # past the doctor's >= 8-reject / >= 10%-of-offered bar
        env[_faults.PLAN_ENV] = _faults.FaultPlan([{
            "point": "serve.tick", "action": "burst", "count": 12,
            "first_calls": 4}]).to_env()
        r = subprocess.run([sys.executable, script, out_path],
                           capture_output=True, text=True, env=env,
                           timeout=420, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        got = json.load(open(out_path))
        # typed rejects happened, nothing hung (the script's bounded
        # waits all resolved), the queue stayed within its cap, and
        # service RESUMED once the spike drained
        assert got["rejects"] >= 8, got
        assert got["chaos"]["rejected"] >= 8, got["chaos"]
        assert got["recovery"]["completed"] >= 1, got["recovery"]
        assert got["depth_high_water"] <= got["queue_cap"]
        assert got["queue_depth_final"] == 0
        # the black box: schema-valid, classified overload_shed
        spec = importlib.util.spec_from_file_location(
            "validate_dump",
            os.path.join(REPO, "tools", "validate_dump.py"))
        vd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vd)
        assert vd.validate_dump(got["dump_path"]) == []
        _merged, diag = obs_doctor.diagnose(got["dump_path"])
        assert diag["classification"] == "overload_shed"
        assert diag["suspect_stage"] == "admission"
        assert any("typed rejects" in e for e in diag["evidence"])
