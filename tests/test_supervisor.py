"""Fault-contained executor tests (ISSUE 14): typed taxonomy, the
degradation ladder, OOM evict-and-retry, shared-RetryPolicy transfer
routing, exhaustion → typed error + schema-valid dump → ``obs doctor``
``degraded_run`` — and THE chaos matrix: every ``faults.py`` plan
across stage × fault-kind × topology either recovers bitwise-identical
to the fault-free run or raises a typed ``tpudl`` error with a
schema-valid flight dump; never a hang, never a wrong answer. The
matrix subset is pytest-marked ``chaos`` (run-tests.sh runs it
explicitly ahead of the full suite), and the unarmed-supervisor
executor overhead guard rides at the bottom."""

import glob
import importlib.util
import json
import os
import statistics
import time

import jax
import numpy as np
import pytest

from tpudl import obs
from tpudl.data import device_cache as dcache
from tpudl.frame import Frame
from tpudl.frame import supervisor as sup
from tpudl.obs import doctor as obs_doctor
from tpudl.obs import flight
from tpudl.obs import watchdog as obs_watchdog
from tpudl.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ONE jitted fn for every executor run in this module: the chaos matrix
# re-runs map_batches dozens of times and must not pay a fresh
# trace/compile per case (the fused/donating variants cache on the fn)
N_ROWS, BATCH = 64, 16  # 4 batches; batch % 8 == 0 keeps mesh fusion on
_JFN = jax.jit(lambda b: (b.reshape(b.shape[0], -1) * 2.0).sum(axis=1))


def _frame() -> Frame:
    x = np.arange(N_ROWS * 6, dtype=np.float32).reshape(N_ROWS, 6)
    return Frame({"x": x})


@pytest.fixture(scope="module")
def baseline():
    """The fault-free truth the whole matrix compares against (plain
    serial executor — every config's parity anchor)."""
    out = _frame().map_batches(_JFN, ["x"], ["y"], batch_size=BATCH)
    return np.asarray(out["y"])


@pytest.fixture()
def clean(monkeypatch, tmp_path):
    """Disarmed faults, clean recorder/metrics/watchdog/device-cache,
    dumps + near-zero retry backoff into tmp_path."""
    monkeypatch.setenv("TPUDL_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("TPUDL_RETRY_IO_BACKOFF_S", "0.001")
    monkeypatch.delenv("TPUDL_WATCHDOG_STALL_S", raising=False)
    monkeypatch.delenv("TPUDL_FRAME_DEGRADE", raising=False)
    faults.disarm()
    obs_watchdog.stop_watchdog()
    obs_watchdog.get_registry().clear()
    flight.get_recorder().reset()
    obs.get_registry().reset()
    dcache.reset_device_cache()
    yield tmp_path
    faults.disarm()
    obs_watchdog.stop_watchdog()
    obs_watchdog.get_registry().clear()
    flight.get_recorder().reset()
    obs.get_registry().reset()
    dcache.reset_device_cache()


def _load_dump_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_dump", os.path.join(REPO, "tools", "validate_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_typed_with_dump(excinfo, tmp_path):
    """The exhaustion contract: a typed taxonomy error chained to the
    original fault, plus a schema-valid flight dump on disk."""
    e = excinfo.value
    assert isinstance(e, sup.FaultError)
    assert e.__cause__ is not None
    dumps = glob.glob(os.path.join(str(tmp_path), "tpudl-dump-*"))
    assert dumps, "exhaustion must leave a flight dump"
    vd = _load_dump_validator()
    for d in dumps:
        assert vd.validate_dump(d) == []


# -- taxonomy --------------------------------------------------------------
class TestTaxonomy:
    def test_oom_anchoring(self):
        assert sup.classify_exception(
            faults.oom_error(123)) == "oom"
        assert sup.classify_exception(
            RuntimeError("RESOURCE_EXHAUSTED: thingy")) == "oom"
        # bare OOM wording on a NON-XLA type is not a device OOM: a
        # user library's 'CUDA out of memory' must not evict the
        # process-wide HBM cache (generic ladder instead)
        assert sup.classify_exception(
            RuntimeError("CUDA out of memory"),
            stage="dispatch") == "stage"

    def test_oom_error_is_xla_shaped(self):
        e = faults.oom_error(4096, point="frame.dispatch call 1")
        assert type(e).__name__ == "XlaRuntimeError"
        assert "RESOURCE_EXHAUSTED" in str(e)
        assert "4096 bytes" in str(e)

    def test_transfer_by_stage_and_by_type(self):
        assert sup.classify_exception(
            RuntimeError("sharding failed"), stage="h2d") == "transfer"
        assert sup.classify_exception(OSError("flaky NFS")) == "transfer"
        assert sup.classify_exception(
            TimeoutError("link")) == "transfer"

    def test_fatal_never_retried(self):
        assert sup.classify_exception(TypeError("bug")) == "fatal"
        assert sup.classify_exception(KeyError("col")) == "fatal"
        assert sup.classify_exception(MemoryError()) == "fatal"
        pre = RuntimeError("preempted")
        pre.tpudl_fatal = True  # the jobs-layer contract
        assert sup.classify_exception(pre) == "fatal"

    def test_storm_flag_beats_generic_stage(self):
        e = RuntimeError("slow dispatch")
        assert sup.classify_exception(e, stage="dispatch",
                                      storm=True) == "recompile_storm"
        assert sup.classify_exception(e, stage="dispatch") == "stage"

    def test_typed_errors_carry_kind_and_fatal_contract(self):
        assert sup.DeviceOOM("x").kind == "oom"
        assert sup.TransferError("x").kind == "transfer"
        assert not getattr(sup.StageFault("x"), "tpudl_fatal", False)
        assert sup.Fatal("x").tpudl_fatal  # no retry layer fights it

    def test_fault_plan_oom_round_trips_env(self):
        plan = faults.FaultPlan.oom("frame.dispatch", at_call=2,
                                    nbytes=777)
        spec = faults.FaultPlan(json.loads(plan.to_env())).rules[0]
        assert spec.action == "oom" and spec.nbytes == 777
        assert spec.at_call == 2


# -- ladder order ----------------------------------------------------------
class TestLadderOrder:
    def _sup_with_config(self, config):
        s = sup.Supervisor()

        class _FakeReport:
            def __init__(self, cfg):
                self.config = cfg

            def report(self):
                return {"stage_calls": {}}

        s.note_report(_FakeReport(dict(config)))
        return s

    def test_ladder_halves_depth_then_fuse_then_donate_then_serial(self):
        s = self._sup_with_config(
            {"dispatch_depth": 4, "fuse_steps": 4, "donate": True})
        labels = [s._next_ladder_rung() for _ in range(6)]
        assert labels == ["dispatch_depth=2", "dispatch_depth=1",
                          "fuse_steps=1", "donate=off", "serial", None]
        # the applied overrides accumulate into the conservative arm
        assert s.overrides["prefetch"] is False
        assert s.overrides["dispatch_depth"] == 1
        assert s.overrides["donate"] is False
        assert s.overrides["fuse_steps"] == 1

    def test_noop_rungs_are_skipped(self):
        s = self._sup_with_config(
            {"dispatch_depth": 1, "fuse_steps": 1, "donate": False})
        assert s._next_ladder_rung() == "serial"
        assert s._next_ladder_rung() is None

    def test_max_rungs_bounds_the_ladder(self, clean):
        frame = _frame()
        plan = faults.FaultPlan(
            [{"point": "frame.dispatch", "action": "raise"}])
        with plan.armed(), pytest.raises(sup.StageFault) as ei:
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, dispatch_depth=8)
        # 8 -> 4 -> 2 -> 1, fuse skip (already 1), donate, serial = 5;
        # the serial last resort may exceed the budget by exactly one
        assert len(ei.value.rungs) <= sup.Supervisor().max_rungs + 1
        assert ei.value.rungs[-1] == "serial"

    def test_serial_guaranteed_even_when_budget_spent(self, clean):
        """The last-resort rung is never left untried: an eviction +
        deep halving sequence that consumes the whole budget still
        gets ONE serial attempt before the typed raise."""
        frame = _frame()
        plan = faults.FaultPlan(
            [{"point": "frame.dispatch", "action": "oom"}])  # persistent
        with plan.armed(), pytest.raises(sup.DeviceOOM) as ei:
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, dispatch_depth=8,
                              fuse_steps=2, donate=True)
        # evict_hbm + 3 halvings + fuse + donate = the full 6-rung
        # budget — serial still ran as rung 7
        assert ei.value.rungs[0] == "evict_hbm"
        assert ei.value.rungs[-1] == "serial"
        assert len(ei.value.rungs) == sup.Supervisor().max_rungs + 1


# -- halving actually reads the resolved config ----------------------------
def test_depth_halving_reads_resolved_config(clean, baseline):
    frame = _frame()
    plan = faults.FaultPlan(
        [{"point": "frame.dispatch", "action": "raise",
          "first_calls": 2}])
    with plan.armed():
        out = frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                                supervise=True, dispatch_depth=4,
                                fuse_steps=1)
    assert np.array_equal(np.asarray(out["y"]), baseline)
    rep = obs.last_pipeline_report()
    assert rep["degraded_to"].startswith("dispatch_depth=")
    assert rep["dispatch_depth"] < 4  # the rung actually applied
    assert rep["recovered_batches"] >= 1


# -- recovery shapes (in-process, fast) ------------------------------------
class TestRecovery:
    def test_unarmed_propagates_raw_error_once(self, clean, baseline):
        frame = _frame()
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed(), pytest.raises(faults.FaultInjected):
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH)
        assert len(plan.fired) == 1  # no retries happened
        snap = obs.snapshot()
        assert "frame.degraded.rungs" not in snap

    def test_transient_dispatch_recovers_bitwise(self, clean, baseline):
        frame = _frame()
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed():
            out = frame.map_batches(_JFN, ["x"], ["y"],
                                    batch_size=BATCH, supervise=True,
                                    dispatch_depth=2)
        assert np.array_equal(np.asarray(out["y"]), baseline)
        rep = obs.last_pipeline_report()
        assert rep["degraded_to"] is not None
        assert rep["recovered_batches"] == -(-N_ROWS // BATCH)
        snap = obs.snapshot()
        assert snap["frame.degraded.rungs"]["value"] >= 1
        assert snap["frame.degraded.recovered_batches"]["value"] >= 1
        # the rung left its forensic trail in the error ring
        errs = flight.get_recorder().snapshot()["errors"]
        assert any(e["kind"] == "frame.degraded" for e in errs)

    def test_oom_evicts_unpinned_hbm_and_retries(self, clean, baseline):
        frame = _frame()
        # park a stale entry in the device cache: the OOM rung must
        # evict it (unpinned) before retrying
        cache = dcache.get_device_cache()
        arr = jax.device_put(np.zeros((8, 8), np.float32))
        pin = cache.put(("stale-run", 0), [arr])
        pin.release()
        assert cache.bytes_resident > 0
        plan = faults.FaultPlan.oom("frame.dispatch", at_call=1)
        with plan.armed():
            out = frame.map_batches(_JFN, ["x"], ["y"],
                                    batch_size=BATCH, supervise=True)
        assert np.array_equal(np.asarray(out["y"]), baseline)
        assert obs.last_pipeline_report()["degraded_to"] == "evict_hbm"
        assert cache.bytes_resident == 0  # the rung freed the HBM tier
        assert obs.snapshot()["data.hbm.evictions"]["value"] >= 1

    def test_transfer_faults_ride_the_one_retry_policy(self, clean,
                                                       baseline):
        frame = _frame()
        plan = faults.FaultPlan(
            [{"point": "frame.prepare", "action": "raise",
              "exc": "OSError", "first_calls": 1}])
        with plan.armed():
            out = frame.map_batches(_JFN, ["x"], ["y"],
                                    batch_size=BATCH, supervise=True)
        assert np.array_equal(np.asarray(out["y"]), baseline)
        snap = obs.snapshot()
        # the shared policy's counters, not a private retry loop
        assert snap["retry.frame.transfer"]["value"] >= 1
        assert snap["retry.attempts"]["value"] >= 1
        # an IO retry is NOT a degradation: config untouched, and the
        # frame.degraded.* trail untouched too (the registry contract
        # — retry.frame.transfer is the retry's whole record)
        rep = obs.last_pipeline_report()
        assert rep.get("degraded_to") is None
        assert rep.get("recovered_batches") is None
        assert "frame.degraded.rungs" not in snap
        assert "frame.degraded.recovered_batches" not in snap

    def test_exhaustion_raises_typed_with_schema_valid_dump(
            self, clean, baseline):
        frame = _frame()
        plan = faults.FaultPlan(
            [{"point": "frame.dispatch", "action": "raise"}])
        with plan.armed(), pytest.raises(sup.StageFault) as ei:
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, dispatch_depth=2)
        _assert_typed_with_dump(ei, clean)
        assert ei.value.stage == "dispatch"
        assert obs.snapshot()["frame.degraded.exhausted"]["value"] == 1
        # the kwarg-collision regression (PR 7 class): the exhaustion
        # ring entry must carry its fault kind under fault_kind
        errs = flight.get_recorder().snapshot()["errors"]
        ex = [e for e in errs
              if e["kind"] == "frame.degraded.exhausted"]
        assert ex and ex[-1]["fault_kind"] == "stage"

    def test_env_armed_supervision(self, clean, baseline, monkeypatch):
        monkeypatch.setenv("TPUDL_FRAME_DEGRADE", "1")
        frame = _frame()
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed():
            out = frame.map_batches(_JFN, ["x"], ["y"],
                                    batch_size=BATCH)
        assert np.array_equal(np.asarray(out["y"]), baseline)
        # explicit kwarg wins over env
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed(), pytest.raises(faults.FaultInjected):
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=False)

    def test_programming_error_in_fn_reraises_unwrapped(self, clean):
        frame = _frame()

        def bad(b):
            raise TypeError("a bug, not a fault")

        with pytest.raises(TypeError):
            frame.map_batches(bad, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, device_fn=False)
        assert "frame.degraded.rungs" not in obs.snapshot()


# -- doctor ----------------------------------------------------------------
class TestDoctorDegradedRun:
    def test_degraded_then_killed_classifies_degraded_run(self, clean):
        frame = _frame()
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed():
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, dispatch_depth=2)
        # the driver kills the (healthy, but degraded) run from outside
        obs.dump(reason="signal:15")
        merged, diag = obs_doctor.diagnose(str(clean))
        assert diag["classification"] == "degraded_run"
        assert any("rung" in ev for ev in diag["evidence"])

    def test_exhausted_dump_classifies_degraded_run(self, clean):
        frame = _frame()
        plan = faults.FaultPlan(
            [{"point": "frame.dispatch", "action": "raise"}])
        with plan.armed(), pytest.raises(sup.StageFault):
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True)
        merged, diag = obs_doctor.diagnose(str(clean))
        assert diag["classification"] == "degraded_run"
        assert diag["suspect_stage"] == "dispatch"

    def test_degradation_free_dumps_keep_their_classes(self, clean):
        # rule-order guard: no degradation evidence -> the existing
        # classes still win (here: a clean external kill)
        obs.dump(reason="signal:15")
        merged, diag = obs_doctor.diagnose(str(clean))
        assert diag["classification"] == "clean_external_kill"

    def test_stale_degradation_does_not_reroute_later_deaths(
            self, clean):
        """Recency gate: a fault absorbed (and fully recovered) EARLY
        in a process's life must not reclassify a later unrelated
        death — the cumulative counters alone are not evidence that
        the dying run was degraded."""
        frame = _frame()
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
        with plan.armed():  # degrade + recover, long ago
            frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                              supervise=True, dispatch_depth=2)
        # a NEWER, healthy, unsupervised run finishes after it
        frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH)
        obs.dump(reason="signal:15")  # then the driver kills cleanly
        merged, diag = obs_doctor.diagnose(str(clean))
        assert diag["classification"] == "clean_external_kill"

    def test_live_supervisor_heartbeat_alone_is_not_degradation(
            self, clean):
        """The heartbeat leg of the recency gate reads the rungs INFO
        field, not mere presence: under process-wide
        TPUDL_FRAME_DEGRADE=1 every supervised run registers a
        frame.supervisor heartbeat, and a stale recovered fault plus a
        live-but-undegraded supervised run must not classify as
        degraded_run."""
        # stale degradation evidence from an earlier, recovered run
        obs.counter("frame.degraded.rungs").inc()
        flight.record_error("frame.degraded", RuntimeError("old"),
                            rung="dispatch_depth=1", stage="dispatch")
        # newest report: a healthy run (no degraded_to — the report leg
        # of the gate must not fire either)
        _frame().map_batches(_JFN, ["x"], ["y"], batch_size=BATCH)
        # a LIVE supervised run, zero rungs applied (mid-first-attempt)
        hb = obs_watchdog.get_registry().start("frame.supervisor")
        try:
            hb.beat(attempt=1, rungs=0)
            obs.dump(reason="signal:15")
            merged, diag = obs_doctor.diagnose(str(clean))
            assert diag["classification"] != "degraded_run"
            # ...but the SAME heartbeat with rungs applied IS current
            hb.beat(attempt=2, rungs=1)
            obs.dump(reason="signal:15")
            merged, diag = obs_doctor.diagnose(str(clean))
            assert diag["classification"] == "degraded_run"
        finally:
            hb.__exit__(None, None, None)

    def test_preempted_still_beats_degraded(self, clean):
        flight.get_recorder().record_event(
            "job.preempted", manifest="/tmp/job-manifest.json")
        obs.counter("frame.degraded.rungs").inc()
        flight.record_error("frame.degraded", RuntimeError("x"),
                            rung="serial", stage="dispatch")
        obs.dump(reason="preempted_resumable")
        merged, diag = obs_doctor.diagnose(str(clean))
        assert diag["classification"] == "preempted_resumable"


# -- device-cache satellites -----------------------------------------------
class TestDeviceCacheFaults:
    def test_evict_unpinned_spares_pinned(self, clean):
        cache = dcache.DeviceBatchCache(budget=1 << 20)
        a = jax.device_put(np.zeros((16, 16), np.float32))
        pinned = cache.put(("r1", 0), [a])
        released = cache.put(("r2", 0), [a])
        released.release()
        n, freed = cache.evict_unpinned()
        assert (n, freed) == (1, a.nbytes)
        assert cache.bytes_resident == a.nbytes  # the pinned one stays
        pinned.release()
        n, freed = cache.evict_unpinned()
        assert n == 1 and cache.bytes_resident == 0

    def test_evict_unpinned_run_filter(self, clean):
        cache = dcache.DeviceBatchCache(budget=1 << 20)
        a = jax.device_put(np.zeros((8, 8), np.float32))
        cache.put(("r1", 0), [a]).release()
        cache.put(("r2", 0), [a]).release()
        n, freed = cache.evict_unpinned(run="r1")  # scoped eviction
        assert (n, freed) == (1, a.nbytes)
        assert cache.bytes_resident == a.nbytes
        assert cache.get(("r2", 0)) is not None  # the other run stays

    def test_put_failure_leaves_tallies_consistent(self, clean):
        cache = dcache.DeviceBatchCache(budget=1 << 20)

        class _Poisoned:
            @property
            def nbytes(self):  # a device_put that died mid-placement
                raise RuntimeError("buffer was never materialized")

        before = cache.bytes_resident
        assert cache.put(("r", 0), [_Poisoned()]) is None
        assert cache.bytes_resident == before
        assert len(cache) == 0
        assert obs.snapshot()["data.hbm.put_failed"]["value"] == 1
        # the cache still works after the failed put
        a = jax.device_put(np.zeros((4, 4), np.float32))
        assert cache.put(("r", 1), [a]) is not None

    def test_executor_counts_put_failed_on_placement_raise(
            self, clean, baseline, monkeypatch):
        # device_put dies mid-placement on the populate path: the
        # supervisor's OOM rung evicts + retries, residency degrades
        # to plain wire, tallies stay consistent
        calls = {"n": 0}
        real_put = jax.device_put

        def flaky_put(x, *a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise faults.oom_error(1 << 30, point="device_put")
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", flaky_put)
        frame = _frame()
        out = frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                                supervise=True, device_cache=True,
                                cache_key="sup-putfail",
                                fuse_steps=1)
        assert np.array_equal(np.asarray(out["y"]), baseline)
        snap = obs.snapshot()
        assert snap["data.hbm.put_failed"]["value"] >= 1
        cache = dcache.get_device_cache()
        # accounting consistent: resident bytes equal the summed
        # entries, nothing leaked by the mid-placement throw
        assert cache.bytes_resident >= 0


# -- THE chaos matrix ------------------------------------------------------
def _plan_for(point: str, kind: str) -> faults.FaultPlan:
    if kind == "oom":
        return faults.FaultPlan.oom(point, at_call=1)
    if kind == "transient":
        return faults.FaultPlan(
            [{"point": point, "action": "raise", "first_calls": 2}])
    if kind == "persistent":
        return faults.FaultPlan([{"point": point, "action": "raise"}])
    if kind == "delay":
        return faults.FaultPlan.delay(point, seconds=0.02,
                                      first_calls=2)
    raise AssertionError(kind)


KINDS = ("oom", "transient", "persistent", "delay")
# fast-path configs the matrix sweeps: the async+fused+donating arm and
# the plain default arm
CONFIGS = (
    {"dispatch_depth": 2, "fuse_steps": 2, "donate": True},
    {},
)


@pytest.mark.chaos
@pytest.mark.parametrize("cfg", CONFIGS, ids=("fastpath", "default"))
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("stage", ("prepare", "dispatch", "d2h"))
def test_chaos_single_chip(stage, kind, cfg, clean, baseline):
    """Single-chip arm: every executor stage × every fault kind ×
    both fast-path configs either recovers bitwise or exits typed with
    a dump. (h2d has no single-chip fault point: mesh=None ships args
    through the runtime's own transfer inside dispatch — the mesh arm
    below owns that stage.)"""
    frame = _frame()
    plan = _plan_for(f"frame.{stage}", kind)
    with plan.armed():
        if kind == "persistent":
            with pytest.raises(sup.FaultError) as ei:
                frame.map_batches(_JFN, ["x"], ["y"],
                                  batch_size=BATCH, supervise=True,
                                  **cfg)
            _assert_typed_with_dump(ei, clean)
            return
        out = frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                                supervise=True, **cfg)
    assert plan.fired, "the plan must actually have injected"
    assert np.array_equal(np.asarray(out["y"]), baseline)


@pytest.mark.chaos
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("stage", ("prepare", "h2d", "dispatch",
                                   "d2h"))
def test_chaos_mesh8(stage, kind, clean, baseline, mesh8):
    """Mesh arm: the sharded executor (fused + windowed) under the
    same matrix, h2d included (the explicit pad+transfer stage exists
    only under a mesh). Outputs must stay bitwise-identical to the
    single-chip fault-free baseline after unpadding."""
    frame = _frame()
    plan = _plan_for(f"frame.{stage}", kind)
    kw = dict(batch_size=BATCH, mesh=mesh8, supervise=True,
              fuse_steps=2, dispatch_depth=2)
    with plan.armed():
        if kind == "persistent":
            with pytest.raises(sup.FaultError) as ei:
                frame.map_batches(_JFN, ["x"], ["y"], **kw)
            _assert_typed_with_dump(ei, clean)
            if stage == "h2d":
                # the taxonomy names the transfer edge
                assert isinstance(ei.value, sup.TransferError)
            return
        out = frame.map_batches(_JFN, ["x"], ["y"], **kw)
    assert plan.fired
    assert np.array_equal(np.asarray(out["y"]), baseline)


@pytest.mark.chaos
@pytest.mark.parametrize("kind", ("transient", "persistent"))
def test_chaos_mesh_transfer_edge(kind, clean, baseline, mesh8):
    """The ONE mesh transfer edge (mesh.transfer_batch) under
    injection: transient faults ride the shared RetryPolicy and
    recover; persistent ones exhaust into a typed TransferError."""
    frame = _frame()
    plan = _plan_for("mesh.transfer", kind)
    kw = dict(batch_size=BATCH, mesh=mesh8, supervise=True)
    with plan.armed():
        if kind == "persistent":
            with pytest.raises(sup.TransferError) as ei:
                frame.map_batches(_JFN, ["x"], ["y"], **kw)
            _assert_typed_with_dump(ei, clean)
            return
        out = frame.map_batches(_JFN, ["x"], ["y"], **kw)
    assert np.array_equal(np.asarray(out["y"]), baseline)
    assert obs.snapshot()["retry.frame.transfer"]["value"] >= 1


@pytest.mark.chaos
def test_chaos_device_cache_oom_path(clean, baseline):
    """OOM during a device-cache run: the evict rung frees the HBM
    tier and the retry recovers bitwise with residency intact for the
    batches that fit."""
    frame = _frame()
    plan = faults.FaultPlan.oom("frame.dispatch", at_call=2)
    with plan.armed():
        out = frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                                supervise=True, device_cache=True,
                                cache_key="sup-oom-dc")
    assert np.array_equal(np.asarray(out["y"]), baseline)
    assert obs.last_pipeline_report()["degraded_to"] == "evict_hbm"


# -- supervised retry vs the watchdog --------------------------------------
def test_supervisor_heartbeat_covers_backoff(clean, monkeypatch):
    """The supervisor's own heartbeat is re-armed through every rung
    and backoff slice: a retrying run never reads as a stall (the
    test_obs_flight.py regression pins the watchdog side; this one
    pins the beat plumbing)."""
    monkeypatch.setenv("TPUDL_RETRY_IO_BACKOFF_S", "0.2")
    frame = _frame()
    beats = []
    real_start = obs_watchdog.HeartbeatRegistry.start

    def spy(self, name, **info):
        hb = real_start(self, name, **info)
        if name == "frame.supervisor":
            beats.append(hb)
        return hb

    monkeypatch.setattr(obs_watchdog.HeartbeatRegistry, "start", spy)
    plan = faults.FaultPlan(
        [{"point": "frame.prepare", "action": "raise",
          "exc": "OSError", "first_calls": 1}])
    with plan.armed():
        frame.map_batches(_JFN, ["x"], ["y"], batch_size=BATCH,
                          supervise=True)
    assert beats, "the supervisor registers its own heartbeat"
    # the 0.2s backoff was slept in slices with a beat per slice:
    # far more beats than the two attempt boundaries alone
    assert beats[0].beats >= 4


# -- overhead guard (acceptance) -------------------------------------------
def test_unarmed_supervisor_overhead_under_5pct(clean):
    """ISSUE 14 acceptance: the unarmed supervisor (default) adds one
    env read per run; armed-but-fault-free adds a heartbeat + a
    try/except. Both stay inside the same <5% envelope as the
    recorder+watchdog guard (interleaved arms + medians + absolute
    slack for CI stability)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 256)).astype(np.float32)
    w = rng.normal(size=(256, 256)).astype(np.float32) * 0.05

    def fn(b):
        acc = b @ w
        for _ in range(8):
            acc = np.tanh(acc @ w)
        return acc.sum(axis=1)

    frame = Frame({"x": x})

    def run_once(supervise):
        t0 = time.perf_counter()
        frame.map_batches(fn, ["x"], ["y"], batch_size=16,
                          supervise=supervise)
        return time.perf_counter() - t0

    run_once(None)
    run_once(True)  # warm both paths outside the timed trials
    armed, plain = [], []
    for t in range(5):
        for arm in (("armed", "plain") if t % 2 == 0
                    else ("plain", "armed")):
            if arm == "armed":
                armed.append(run_once(True))
            else:
                plain.append(run_once(None))
    med_armed = statistics.median(armed)
    med_plain = statistics.median(plain)
    assert med_armed <= med_plain * 1.05 + 0.010, (
        f"supervisor overhead too high: {med_armed:.4f}s armed vs "
        f"{med_plain:.4f}s unarmed (trials {armed} vs {plain})")
