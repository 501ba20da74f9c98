"""Preemption-survivable job runtime (ISSUE 7): JobSpec fingerprinting,
JobRuntime resume state + SIGTERM checkpoint-then-exit, the shared
RetryPolicy at every layer (gang restarts, shard/image IO, HPO
trials), the hardened CheckpointManager (atomic + checksummed +
newest-VALID fallback), the fault-injection harness that proves it all
(tpudl.testing.faults), the shard-cache eviction race, doctor's
``preempted_resumable`` class, and ``tools/validate_job.py`` (tier-1
wiring).

The acceptance path is the kill-mid-epoch subprocess round-trip: a
SIGTERM'd JobRuntime run exits RC_PREEMPTED, a relaunch of the SAME
spec resumes and produces BIT-IDENTICAL final params to an
uninterrupted run, with zero re-decodes for already-prepared batches.
"""

import gzip
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from tpudl import obs
from tpudl.jobs import (JobPreempted, JobRuntime, JobSpec, RC_PREEMPTED,
                        RetryPolicy, load_manifest)
from tpudl.jobs.retry import is_fatal
from tpudl.obs import doctor as obs_doctor
from tpudl.obs import flight
from tpudl.testing import faults
from tpudl.train import Trainer
from tpudl.train.checkpoint import CheckpointManager
from tpudl.train.runner import Preempted, RestartsExhausted

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _optax():
    return pytest.importorskip("optax")


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_job", os.path.join(REPO, "tools", "validate_job.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_obs():
    faults.disarm()
    flight.get_recorder().reset()
    obs.get_registry().reset()
    yield
    faults.disarm()
    flight.get_recorder().reset()
    obs.get_registry().reset()


def _toy():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(256, 4)).astype(np.float32)
    y = X @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32) + 0.1

    def data_fn(step, batch=32):
        i = (step * batch) % (len(X) - batch + 1)
        return X[i:i + batch], y[i:i + batch]

    def loss_fn(p, x, t):
        return jnp.mean((x @ p["w"] + p["b"] - t) ** 2)

    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(())}
    return data_fn, loss_fn, params


def _metric(name):
    return obs.snapshot().get(name, {}).get("value", 0)


# -- RetryPolicy -----------------------------------------------------------
class TestRetryPolicy:
    def test_transient_recovers_after_k(self):
        calls = {"n": 0}
        sleeps = []

        def flaky():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise OSError("transient")
            return "ok"

        pol = RetryPolicy(max_attempts=4, backoff_s=0.01, jitter=0,
                          sleep=sleeps.append, seed=0)
        assert pol.call(flaky, kind="t") == "ok"
        assert calls["n"] == 3
        assert len(sleeps) == 2
        assert sleeps[1] > sleeps[0]  # exponential
        assert _metric("retry.attempts") == 2
        assert _metric("retry.t") == 2
        # every retry left a sample in the flight recorder's error ring
        errs = flight.get_recorder().snapshot()["errors"]
        assert sum(1 for e in errs if e["kind"] == "retry.t") == 2

    def test_budget_exhaustion_reraises_original(self):
        pol = RetryPolicy(max_attempts=3, backoff_s=0, jitter=0,
                          sleep=lambda s: None)
        with pytest.raises(OSError, match="always"):
            pol.call(lambda: (_ for _ in ()).throw(OSError("always")),
                     kind="t")

    def test_non_transient_fails_immediately(self):
        calls = {"n": 0}

        def bad():
            calls["n"] += 1
            raise ValueError("permanent")

        pol = RetryPolicy(max_attempts=5, sleep=lambda s: None)
        with pytest.raises(ValueError):
            pol.call(bad)
        assert calls["n"] == 1

    def test_fatal_never_retried_even_with_transient_all(self):
        calls = {"n": 0}

        def preempted():
            calls["n"] += 1
            raise Preempted(7)

        pol = RetryPolicy(max_attempts=5, transient="all",
                          sleep=lambda s: None)
        with pytest.raises(Preempted):
            pol.call(preempted)
        assert calls["n"] == 1
        assert is_fatal(Preempted(7))
        assert is_fatal(JobPreempted("/m", {}))
        assert not is_fatal(OSError())

    def test_backoff_caps_and_jitters_deterministically(self):
        pol = RetryPolicy(backoff_s=1.0, backoff_factor=10.0,
                          max_backoff_s=5.0, jitter=0.5, seed=42)
        pol2 = RetryPolicy(backoff_s=1.0, backoff_factor=10.0,
                           max_backoff_s=5.0, jitter=0.5, seed=42)
        for a in (1, 2, 3):
            b = pol.backoff_s(a)
            assert b == pol2.backoff_s(a)  # seeded: reproducible
            assert b <= 5.0 * 1.5  # cap + jitter headroom


# -- fault harness ---------------------------------------------------------
class TestFaultHarness:
    def test_raise_in_dispatch_stage(self):
        from tpudl.frame import Frame

        f = Frame({"x": np.arange(32, dtype=np.float32)})
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=2)
        with plan.armed():
            with pytest.raises(faults.FaultInjected, match="frame.dispatch"):
                f.map_batches(lambda x: x * 2, ["x"], ["y"], batch_size=8,
                              prefetch=False)
        assert plan.fired and plan.fired[0]["point"] == "frame.dispatch"
        # the injected fault left the same forensic trail a real one
        # would
        errs = flight.get_recorder().snapshot()["errors"]
        assert any(e["kind"] == "fault.injected" for e in errs)

    @pytest.mark.parametrize("stage", ["prepare", "d2h"])
    def test_raise_in_other_stages(self, stage):
        from tpudl.frame import Frame

        f = Frame({"x": np.arange(64, dtype=np.float32)})
        with faults.FaultPlan.raise_in_stage(stage, at_call=1).armed():
            with pytest.raises(faults.FaultInjected):
                # host fn returns arrays -> window mode drains in d2h
                f.map_batches(lambda x: np.asarray(x) * 2, ["x"], ["y"],
                              batch_size=8, prefetch=False)

    def test_transient_io_recovery_after_k(self, tmp_path):
        """First K reads fail, then recover: the shared IO retry policy
        absorbs the fault — the rows decode, no decode_errors."""
        from tpudl.image.imageIO import LazyFileColumn

        paths = []
        for i in range(4):
            p = tmp_path / f"f{i}.bin"
            p.write_bytes(b"payload-%d" % i)
            paths.append(str(p))
        col = LazyFileColumn(paths, io_workers=1)
        plan = faults.FaultPlan.transient_io(first_calls=2)
        with plan.armed():
            out = col[0:4]
        assert [bytes(o) for o in out] == [b"payload-0", b"payload-1",
                                           b"payload-2", b"payload-3"]
        assert len(plan.fired) == 2
        assert _metric("retry.imageio.read") == 2
        assert _metric("imageio.decode_errors") == 0

    def test_transient_io_beyond_budget_propagates(self, tmp_path,
                                                   monkeypatch):
        from tpudl.image.imageIO import LazyFileColumn

        monkeypatch.setenv("TPUDL_RETRY_IO_ATTEMPTS", "2")
        monkeypatch.setenv("TPUDL_RETRY_IO_BACKOFF_S", "0")
        p = tmp_path / "f.bin"
        p.write_bytes(b"x")
        col = LazyFileColumn([str(p)], io_workers=1)
        with faults.FaultPlan.transient_io(first_calls=5).armed():
            with pytest.raises(OSError):
                col[0:1]

    def test_plan_env_round_trip(self, monkeypatch):
        plan = faults.FaultPlan.kill_at_step(13)
        monkeypatch.setenv(faults.PLAN_ENV, plan.to_env())
        got = faults.FaultPlan.from_env()
        assert got.rules[0].point == "train.step"
        assert got.rules[0].action == "sigterm"
        assert got.rules[0].when == {"step": 13}
        faults.disarm()


# -- CheckpointManager hardening -------------------------------------------
class TestCheckpointHardening:
    def test_atomic_checksummed_roundtrip(self, tmp_path):
        state = {"params": {"w": jnp.arange(4.0), "b": jnp.float32(2.5)},
                 "step": np.asarray(7, np.int64)}
        with CheckpointManager(str(tmp_path / "c"), save_every=1) as mgr:
            assert mgr.save(7, state, force=True)
            got = mgr.restore(like=state)
        np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                      np.arange(4.0))
        assert np.asarray(got["params"]["b"]).shape == ()  # 0-d survives
        assert int(got["step"]) == 7
        # no stray tmp files: every write landed via os.replace
        assert not [f for f in os.listdir(tmp_path / "c") if ".tmp." in f]

    def test_bfloat16_roundtrip_exact(self, tmp_path):
        state = {"w": jnp.arange(6.0).astype(jnp.bfloat16)}
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(1, state, force=True)
        got = mgr.restore(like=state)
        assert got["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got["w"], np.float32), np.arange(6.0))

    def test_bit_flip_falls_back_to_newest_valid(self, tmp_path):
        """The satellite contract: a bit-flipped LATEST checkpoint is
        dropped (counter + error sample) and restore returns the
        previous valid step instead of crashing."""
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(5, {"v": jnp.ones(3)}, force=True)
        mgr.save(10, {"v": jnp.full(3, 9.0)}, force=True)
        path = mgr._file_for(10)
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]))
        got = mgr.restore(like={"v": jnp.zeros(3)})
        np.testing.assert_array_equal(np.asarray(got["v"]), np.ones(3))
        assert mgr.latest_step() == 5  # the corrupt step was dropped
        assert _metric("train.checkpoint.corrupt") == 1
        errs = flight.get_recorder().snapshot()["errors"]
        assert any(e["kind"] == "train.checkpoint.corrupt" for e in errs)

    def test_truncated_latest_falls_back(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(3, {"v": jnp.ones(2)}, force=True)
        mgr.save(6, {"v": jnp.full(2, 2.0)}, force=True)
        path = mgr._file_for(6)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        got = mgr.restore(like={"v": jnp.zeros(2)})
        np.testing.assert_array_equal(np.asarray(got["v"]), np.ones(2))

    def test_all_corrupt_returns_none(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(1, {"v": jnp.ones(2)}, force=True)
        with open(mgr._file_for(1), "w") as f:
            f.write("garbage")
        assert mgr.restore(like={"v": jnp.zeros(2)}) is None

    def test_explicit_step_corruption_raises(self, tmp_path):
        from tpudl.train.checkpoint import CheckpointCorruption

        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(1, {"v": jnp.ones(2)}, force=True)
        with open(mgr._file_for(1), "w") as f:
            f.write("garbage")
        with pytest.raises(CheckpointCorruption):
            mgr.restore(1, like={"v": jnp.zeros(2)})

    def test_orphan_file_without_manifest_entry_restorable(self, tmp_path):
        """A crash between the checkpoint replace and the manifest write
        leaves a durable orphan — it must still be a restore
        candidate."""
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1)
        mgr.save(4, {"v": jnp.full(2, 4.0)}, force=True)
        os.unlink(os.path.join(str(tmp_path / "c"), "ckpt-manifest.json"))
        mgr2 = CheckpointManager(str(tmp_path / "c"), save_every=1)
        assert mgr2.latest_step() == 4
        got = mgr2.restore(like={"v": jnp.zeros(2)})
        np.testing.assert_array_equal(np.asarray(got["v"]),
                                      np.full(2, 4.0))

    def test_max_to_keep_prunes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"), save_every=1,
                                max_to_keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"v": jnp.full(2, float(s))}, force=True)
        assert mgr._candidate_steps() == [4, 3]
        files = [f for f in os.listdir(tmp_path / "c")
                 if f.startswith("ckpt-0")]
        assert len(files) == 2


# -- shard-cache eviction race ---------------------------------------------
class TestShardEvictionRace:
    def _cache(self, tmp_path):
        from tpudl.data.shards import ShardCache

        c = ShardCache(str(tmp_path), "k1")
        c.put(0, [np.arange(8, dtype=np.float32)])
        return c

    def test_deleted_between_check_and_load_is_miss(self, tmp_path):
        """The concurrent-eviction race, pinned deterministically: the
        shard file vanishes BETWEEN the manifest/stat check and
        np.load — a miss + re-prepare, counted as eviction, NOT as
        corruption (no false storm evidence for the doctor)."""
        c = self._cache(tmp_path)
        with faults.FaultPlan([{"point": "shards.read",
                                "action": "unlink"}]).armed():
            assert c.get(0) is None
        assert _metric("data.cache.evicted") == 1
        assert _metric("data.cache.misses") >= 1
        assert _metric("data.cache.corrupt") == 0
        errs = flight.get_recorder().snapshot()["errors"]
        assert not any(e["kind"] == "data.cache.corrupt" for e in errs)
        # re-prepare path: a fresh put over the same index works
        c.put(0, [np.arange(8, dtype=np.float32)])
        assert c.get(0) is not None

    def test_deleted_before_get_is_miss(self, tmp_path):
        c = self._cache(tmp_path)
        entry = c._shards["0"]["files"][0]["name"]
        os.unlink(os.path.join(c.dir, entry))
        assert c.get(0) is None
        assert _metric("data.cache.evicted") == 1
        assert _metric("data.cache.corrupt") == 0

    def test_bit_flip_still_counts_corrupt(self, tmp_path):
        """The corruption path keeps its classification (regression
        guard for the eviction split)."""
        c = self._cache(tmp_path)
        with faults.FaultPlan.corrupt_on_read().armed():
            assert c.get(0) is None
        assert _metric("data.cache.corrupt") == 1
        assert _metric("data.cache.evicted") == 0


# -- HorovodRunner retry integration ---------------------------------------
@pytest.fixture()
def fake_mesh(monkeypatch):
    """HorovodRunner without jax.sharding.set_mesh (absent in this jax):
    a 1-wide fake mesh + no-op use_mesh, enough to drive the restart
    loop."""
    import contextlib

    from tpudl import mesh as M
    from tpudl.train import runner as R

    class _FakeMesh:
        shape = {M.DATA_AXIS: 1}

    monkeypatch.setattr(R.HorovodRunner, "_build_mesh",
                        lambda self: _FakeMesh())
    monkeypatch.setattr(M, "use_mesh",
                        lambda mesh: contextlib.nullcontext())
    return _FakeMesh()


class TestHorovodRunnerRetry:
    def test_backoff_between_restarts_and_typed_exhaustion(self,
                                                           fake_mesh):
        from tpudl.train import HorovodRunner

        sleeps = []
        pol = RetryPolicy(max_attempts=3, backoff_s=0.01, jitter=0,
                          transient="all", sleep=sleeps.append)

        def main(ctx):
            raise RuntimeError("always fails")

        runner = HorovodRunner(np=1, max_restarts=2, retry_policy=pol)
        import time as _time

        orig_sleep = _time.sleep
        slept = []
        try:
            _time.sleep = lambda s: slept.append(s)
            with pytest.raises(RestartsExhausted,
                               match="always fails") as ei:
                runner.run(main)
        finally:
            _time.sleep = orig_sleep
        assert ei.value.attempts == 3
        assert isinstance(ei.value.last_cause, RuntimeError)
        assert isinstance(ei.value.__cause__, RuntimeError)
        assert len(slept) == 2  # backoff between restarts, not after
        assert slept[1] > slept[0]  # exponential
        assert _metric("train.restarts") == 2
        hist = obs.snapshot().get("train.restart_backoff_s", {})
        assert hist.get("count") == 2
        # forensics: every restart recorded
        snap = flight.get_recorder().snapshot()
        assert len(snap["restarts"]) == 3

    def test_preempted_is_not_restarted(self, fake_mesh):
        from tpudl.train import HorovodRunner

        calls = {"n": 0}

        def main(ctx):
            calls["n"] += 1
            raise Preempted(5)

        runner = HorovodRunner(np=1, max_restarts=3)
        with pytest.raises(Preempted):
            runner.run(main)
        assert calls["n"] == 1  # no gang restart fought the preemption


# -- Trainer cooperative stop ----------------------------------------------
class TestTrainerPreempt:
    def test_stop_checkpoints_then_raises(self, tmp_path):
        optax = _optax()
        data_fn, loss_fn, params0 = _toy()
        t = Trainer(loss_fn, optax.adam(0.05),
                    checkpoint_dir=str(tmp_path / "ck"), save_every=100)
        seen = {"s": 0}

        def data(step):
            seen["s"] = step
            return data_fn(step)

        with pytest.raises(Preempted) as ei:
            t.fit(params0, data, 20, stop=lambda: seen["s"] >= 13)
        assert ei.value.step == 14
        assert ei.value.saved
        mgr = CheckpointManager(str(tmp_path / "ck"), save_every=100)
        assert mgr.latest_step() == 14

    def test_preempt_resume_bit_identical(self, tmp_path):
        """20 straight steps == 14 + preempt + resume-to-20, BITWISE."""
        optax = _optax()
        data_fn, loss_fn, params0 = _toy()
        p_ref, _, _ = Trainer(loss_fn, optax.adam(0.05)).fit(
            params0, data_fn, 20)
        d = str(tmp_path / "ck")
        t1 = Trainer(loss_fn, optax.adam(0.05), checkpoint_dir=d,
                     save_every=5)
        seen = {"s": 0}

        def data(step):
            seen["s"] = step
            return data_fn(step)

        with pytest.raises(Preempted):
            t1.fit(params0, data, 20, stop=lambda: seen["s"] >= 13)
        t2 = Trainer(loss_fn, optax.adam(0.05), checkpoint_dir=d,
                     save_every=5)
        p_res, _, _ = t2.fit(params0, data_fn, 20)
        for k in ("w", "b"):
            a, b = np.asarray(p_ref[k]), np.asarray(p_res[k])
            assert a.shape == b.shape
            assert np.array_equal(a, b), f"params[{k}] not bit-identical"

    def test_stop_without_checkpoint_dir_flags_unsaved(self):
        optax = _optax()
        data_fn, loss_fn, params0 = _toy()
        t = Trainer(loss_fn, optax.adam(0.05))
        with pytest.raises(Preempted) as ei:
            t.fit(params0, data_fn, 20, stop=lambda: True)
        assert not ei.value.saved


# -- JobSpec ---------------------------------------------------------------
class TestJobSpec:
    def test_fingerprint_stable_and_sensitive(self, tmp_path):
        a = JobSpec("fit", str(tmp_path), material={"knobs": {"lr": 0.1},
                                                    "model": "m"})
        b = JobSpec("fit", str(tmp_path / "elsewhere"),
                    material={"model": "m", "knobs": {"lr": 0.1}})
        assert a.fingerprint() == b.fingerprint()  # workdir/order-free
        c = JobSpec("fit", str(tmp_path), material={"knobs": {"lr": 0.2},
                                                    "model": "m"})
        assert a.fingerprint() != c.fingerprint()
        d = JobSpec("hpo", str(tmp_path), material={"knobs": {"lr": 0.1},
                                                    "model": "m"})
        assert a.fingerprint() != d.fingerprint()

    def test_json_round_trip(self, tmp_path):
        a = JobSpec("featurize", str(tmp_path), material={"x": 1},
                    save_every=7, name="feat")
        b = JobSpec.from_json(a.to_json())
        assert b.fingerprint() == a.fingerprint()
        assert (b.kind, b.save_every, b.name) == ("featurize", 7, "feat")

    def test_frame_material(self, tmp_path):
        from tpudl.frame import Frame
        from tpudl.jobs import fingerprint_material

        f = Frame({"x": np.arange(8, dtype=np.float32)})
        m1 = fingerprint_material(frame=f, input_cols=["x"],
                                  knobs={"lr": 1e-3})
        f2 = Frame({"x": np.arange(8, dtype=np.float32) + 1})
        m2 = fingerprint_material(frame=f2, input_cols=["x"],
                                  knobs={"lr": 1e-3})
        assert m1["frame"] != m2["frame"]  # content re-keys the job


# -- JobRuntime ------------------------------------------------------------
class TestJobRuntime:
    def test_preempt_persists_resume_state(self, tmp_path):
        optax = _optax()
        data_fn, loss_fn, params0 = _toy()
        spec = JobSpec("fit", str(tmp_path / "job"),
                       material={"model": "toy"}, save_every=5)
        rt = JobRuntime(spec, install_signals=False)
        holder = {}

        def payload(ctx):
            holder["ctx"] = ctx
            seen = {"s": 0}

            def data(step):
                seen["s"] = step
                if step >= 13:
                    ctx.request_stop()
                return data_fn(step)

            t = Trainer(loss_fn, optax.adam(0.05),
                        checkpoint_dir=ctx.checkpoint_dir, save_every=5)
            return t.fit(params0, data, 20, stop=ctx.stop_requested)

        # Trainer raises Preempted AFTER the triggering step completes
        with pytest.raises(JobPreempted) as ei:
            rt.run(payload)
        # the forensic breadcrumbs actually landed (the recording calls
        # are wrapped in a bare except — a signature drift would
        # otherwise silently drop them)
        ev_kinds = [e["kind"] for e in
                    flight.get_recorder().snapshot()["events"]]
        assert "job.start" in ev_kinds
        assert "job.preempted" in ev_kinds
        m = load_manifest(spec.workdir)
        assert m["status"] == "preempted"
        assert m["cursor"]["step"] == m["checkpoint"]["step"]
        assert m["fingerprint"] == spec.fingerprint()
        assert ei.value.manifest_path == rt.manifest_path()
        # the workdir dump classifies as preempted_resumable
        res = obs_doctor.diagnose(spec.workdir)
        assert res is not None
        _, diag = res
        assert diag["classification"] == "preempted_resumable"
        assert diag["resume_manifest"] == rt.manifest_path()
        # audit clean
        vj = _load_validator()
        assert vj.validate_manifest(spec.workdir) == []
        # resume completes and flips status to done
        rt2 = JobRuntime(spec, install_signals=False)

        def payload2(ctx):
            t = Trainer(loss_fn, optax.adam(0.05))
            return t.fit(params0, data_fn, 20, stop=ctx.stop_requested)

        rt2.run_fit(Trainer(loss_fn, optax.adam(0.05)), params0,
                    data_fn, 20)
        m2 = load_manifest(spec.workdir)
        assert m2["status"] == "done"
        assert m2["attempt"] == 2
        assert m2["cursor"]["step"] == 20
        assert vj.validate_manifest(spec.workdir) == []

    def test_foreign_fingerprint_refused(self, tmp_path):
        spec_a = JobSpec("fit", str(tmp_path / "job"),
                         material={"model": "A"})
        rt = JobRuntime(spec_a, install_signals=False)
        rt.run(lambda ctx: "ok")
        spec_b = JobSpec("fit", str(tmp_path / "job"),
                         material={"model": "B"})
        with pytest.raises(ValueError, match="DIFFERENT job"):
            JobRuntime(spec_b, install_signals=False).run(
                lambda ctx: "never")

    def test_failed_status_on_exception(self, tmp_path):
        spec = JobSpec("custom", str(tmp_path / "job"))
        rt = JobRuntime(spec, install_signals=False)
        with pytest.raises(RuntimeError, match="boom"):
            rt.run(lambda ctx: (_ for _ in ()).throw(RuntimeError("boom")))
        m = load_manifest(spec.workdir)
        assert m["status"] == "failed"
        assert "boom" in m["error"]

    def test_iter_batches_cursor_and_zero_reprepare(self, tmp_path):
        """Kill mid-epoch at batch k; resume prepares each batch exactly
        ONCE across both runs (zero re-decodes past the cursor) and a
        second epoch replays fully from the shard cache."""
        from tpudl.data import Dataset
        from tpudl.frame import Frame

        frame = Frame({"x": np.arange(64, dtype=np.float32)})
        prepares = {"n": 0}

        def counting_pack(sl):
            prepares["n"] += 1
            return np.asarray(sl)

        counting_pack.cache_token = "counting-pack-v1"

        def make_ds():
            return Dataset(frame, ["x"], batch_size=8,
                           cache_dir=str(tmp_path / "cache"),
                           pack=counting_pack)

        spec = JobSpec("featurize", str(tmp_path / "job"),
                       material={"frame": frame.fingerprint(["x"])})
        rt = JobRuntime(spec, install_signals=False)

        def payload(ctx):
            ds = make_ds()
            got = []
            for epoch, b, batch in ctx.iter_batches(ds, epochs=2):
                got.append((epoch, b))
                if (epoch, b) == (0, 4):
                    ctx.request_stop()
            return got

        with pytest.raises(JobPreempted) as ei:
            rt.run(payload)
        assert ei.value.cursor == {"epoch": 0, "batch": 5}
        assert prepares["n"] == 5  # batches 0..4 prepared once
        m = load_manifest(spec.workdir)
        assert m["bounds"] == {"epochs": 2, "batches_per_epoch": 8}

        rt2 = JobRuntime(spec, install_signals=False)

        def payload2(ctx):
            ds = make_ds()
            return [(e, b) for e, b, _ in ctx.iter_batches(ds, epochs=2)]

        got = rt2.run(payload2)
        # resume picks up at (0, 5); epoch 1 replays from cache
        assert got[0] == (0, 5)
        assert got[-1] == (1, 7)
        assert len(got) == 3 + 8
        # the cursor bound: batches 5..7 prepare once; epoch 1 and the
        # pre-cursor batches are pure cache hits — ZERO re-prepares
        assert prepares["n"] == 8
        assert load_manifest(spec.workdir)["status"] == "done"
        vj = _load_validator()
        assert vj.validate_manifest(spec.workdir) == []

    def test_run_trials_ledger_skips_done(self, tmp_path):
        spec = JobSpec("hpo", str(tmp_path / "job"),
                       material={"grid": [1, 2, 3]})
        rt = JobRuntime(spec, install_signals=False)
        ran = []

        def payload(ctx):
            def trial(i, item, devs):
                ran.append(i)
                return item * 10

            return sorted(ctx.run_trials([1, 2, 3], trial))

        out = rt.run(payload)
        assert out == [(0, 10), (1, 20), (2, 30)]
        assert sorted(ran) == [0, 1, 2]
        # second run over the same spec: ledger says all done
        rt2 = JobRuntime(spec, install_signals=False)
        ran2 = []

        def payload2(ctx):
            assert ctx.trials_done() == {0, 1, 2}
            def trial(i, item, devs):
                ran2.append(i)
                return item

            return list(ctx.run_trials([1, 2, 3], trial))

        assert rt2.run(payload2) == []
        assert ran2 == []
        vj = _load_validator()
        assert vj.validate_manifest(spec.workdir) == []


# -- TrialScheduler retry --------------------------------------------------
class TestTrialRetry:
    def test_transient_trial_retries_on_slice(self):
        from tpudl.ml.hpo import TrialScheduler

        attempts = {}

        def trial(i, item, devs):
            attempts[i] = attempts.get(i, 0) + 1
            if i == 1 and attempts[i] == 1:
                raise OSError("flaky trial IO")
            return item

        pol = RetryPolicy(max_attempts=2, backoff_s=0,
                          sleep=lambda s: None)
        out = sorted(TrialScheduler(devices=[object()]).run(
            ["a", "b", "c"], trial, retry=pol))
        assert out == [(0, "a"), (1, "b"), (2, "c")]
        assert attempts[1] == 2
        assert _metric("hpo.trial_retries") == 1
        assert _metric("hpo.trials_failed") == 0

    def test_default_no_retry_preserved(self):
        from tpudl.ml.hpo import TrialScheduler

        def trial(i, item, devs):
            raise OSError("fails")

        with pytest.raises(OSError):
            list(TrialScheduler(devices=[object()]).run(["a"], trial))
        assert _metric("hpo.trials_failed") == 1


# -- doctor: preempted_resumable vs clean_external_kill --------------------
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


def _write_dump(path, payload):
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(payload, f)
    return str(path)


class TestDoctorPreempted:
    def test_preempted_resumable_single_host(self, tmp_path):
        _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="preempted_resumable",
            events=[{"ts": time.time(), "kind": "job.preempted",
                     "manifest": "/w/job-manifest.json",
                     "cursor": '{"step": 14}'}]))
        _merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert diag["classification"] == "preempted_resumable"
        assert diag["resume_manifest"] == "/w/job-manifest.json"
        assert any("job-manifest.json" in e for e in diag["evidence"])

    def test_clean_external_kill_unchanged_without_manifest(self,
                                                            tmp_path):
        """A SIGTERM dump WITHOUT resume state keeps its existing
        class: the kill was terminal, not resumable."""
        _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15"))
        _merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert diag["classification"] == "clean_external_kill"

    def test_multi_host_any_member_resumable(self, tmp_path):
        """In a gang, ONE member persisting resume state makes the
        death resumable — the signal-killed peer must not downgrade
        it."""
        _write_dump(tmp_path / "tpudl-dump-host0-1.json.gz", _payload(
            process_index=0, process_count=2, ts=time.time() - 1,
            reason="preempted_resumable",
            events=[{"ts": time.time(), "kind": "job.preempted",
                     "manifest": "/w/job-manifest.json"}]))
        _write_dump(tmp_path / "tpudl-dump-host1-2.json.gz", _payload(
            process_index=1, process_count=2, pid=2000,
            reason="signal:15"))
        _merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert diag["classification"] == "preempted_resumable"

    def test_preempted_outranks_stall_history(self, tmp_path):
        """Rule order: a preempted dump whose RING still holds an old
        (recovered-from) stall must classify preempted_resumable — the
        relaunch instruction outranks history; the stall rides along
        as evidence."""
        _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="preempted_resumable",
            stalls=[{"ts": time.time() - 300, "name":
                     "frame.map_batches", "age_s": 31.0,
                     "in_flight": {"prepare": {"age_s": 31.0}}}],
            events=[{"ts": time.time(), "kind": "job.preempted",
                     "manifest": "/w/job-manifest.json"}]))
        _merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert diag["classification"] == "preempted_resumable"
        assert diag["resume_manifest"] == "/w/job-manifest.json"
        assert any("stall" in e for e in diag["evidence"])

    def test_cli_prints_preempted(self, tmp_path, capsys):
        from tpudl.obs.__main__ import main as obs_main

        _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="preempted_resumable",
            events=[{"ts": time.time(), "kind": "job.preempted",
                     "manifest": "/w/job-manifest.json"}]))
        assert obs_main(["doctor", str(tmp_path)]) == 0
        assert "preempted_resumable" in capsys.readouterr().out


# -- tools/validate_job.py (tier-1 wiring) ---------------------------------
class TestValidateJob:
    def _make_job(self, tmp_path):
        optax = _optax()
        data_fn, loss_fn, params0 = _toy()
        spec = JobSpec("fit", str(tmp_path / "job"),
                       material={"model": "toy"}, save_every=5)
        rt = JobRuntime(spec, install_signals=False)
        rt.run_fit(Trainer(loss_fn, optax.adam(0.05)), params0,
                   data_fn, 10)
        return spec

    def test_clean_workdir_passes(self, tmp_path):
        spec = self._make_job(tmp_path)
        vj = _load_validator()
        assert vj.validate_manifest(spec.workdir) == []
        assert vj.main(["validate_job.py", spec.workdir]) == 0

    def test_cursor_past_bounds_detected(self, tmp_path):
        spec = self._make_job(tmp_path)
        p = os.path.join(spec.workdir, "job-manifest.json")
        m = json.load(open(p))
        m["cursor"]["step"] = 999
        json.dump(m, open(p, "w"))
        vj = _load_validator()
        errs = vj.validate_manifest(spec.workdir)
        assert any("exceeds bounds.steps" in e for e in errs)

    def test_checkpoint_ahead_of_cursor_detected(self, tmp_path):
        spec = self._make_job(tmp_path)
        p = os.path.join(spec.workdir, "job-manifest.json")
        m = json.load(open(p))
        m["cursor"]["step"] = 3  # behind the recorded checkpoint (10)
        json.dump(m, open(p, "w"))
        vj = _load_validator()
        errs = vj.validate_manifest(spec.workdir)
        assert any("AHEAD of cursor" in e for e in errs)

    def test_corrupt_checkpoint_payload_detected(self, tmp_path):
        spec = self._make_job(tmp_path)
        ckpt = os.path.join(spec.workdir, "checkpoints",
                            "ckpt-00000010.npz")
        size = os.path.getsize(ckpt)
        with open(ckpt, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]))
        vj = _load_validator()
        errs = vj.validate_manifest(spec.workdir)
        assert any("crc32 mismatch" in e for e in errs)

    def test_schema_violations_detected(self, tmp_path):
        spec = self._make_job(tmp_path)
        p = os.path.join(spec.workdir, "job-manifest.json")
        m = json.load(open(p))
        m["status"] = "zombie"
        m["fingerprint"] = "nothex"
        m["trials"]["done"]["0"] = {}
        m["trials"]["pending"] = [0]
        json.dump(m, open(p, "w"))
        vj = _load_validator()
        errs = vj.validate_manifest(spec.workdir)
        assert any("status" in e for e in errs)
        assert any("fingerprint" in e for e in errs)
        assert any("overlap" in e for e in errs)

    def test_cli_rc_contract(self, tmp_path):
        vj = _load_validator()
        assert vj.main(["validate_job.py"]) == 2
        assert vj.main(["validate_job.py", str(tmp_path)]) == 1  # empty


# -- the acceptance path: kill-mid-epoch subprocess round-trip -------------
_JOB_SCRIPT = """
import os, signal, sys
import numpy as np
import jax.numpy as jnp
import optax
from tpudl.testing import faults
from tpudl.jobs import JobRuntime, JobSpec
from tpudl.train import Trainer

faults.install_from_env()
workdir, out = sys.argv[1], sys.argv[2]
sigkill_at = int(sys.argv[3]) if len(sys.argv) > 3 else None
rng = np.random.default_rng(0)
X = rng.normal(size=(256, 4)).astype(np.float32)
y = X @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32) + 0.1

def data_fn(step, batch=32):
    with open(out + ".steps", "a") as f:
        f.write(f"{step}\\n")
    if step == sigkill_at:  # no handler runs, no boundary is reached
        os.kill(os.getpid(), signal.SIGKILL)
    i = (step * batch) % (len(X) - batch + 1)
    return X[i:i + batch], y[i:i + batch]

def loss_fn(p, x, t):
    return jnp.mean((x @ p["w"] + p["b"] - t) ** 2)

params0 = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(())}
spec = JobSpec("fit", workdir, material={"model": "toy", "lr": 0.05},
               save_every=5)
rt = JobRuntime(spec)
p, _o, _h = rt.run_fit(Trainer(loss_fn, optax.adam(0.05)), params0,
                       data_fn, 20, exit_on_preempt=True)
np.savez(out, w=np.asarray(p["w"]), b=np.asarray(p["b"]))
print("DONE")
"""


def _run_job(tmp_path, workdir, out, env_extra=None, timeout=120,
             sigkill_at=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               **(env_extra or {}))
    env.pop("TPUDL_FAULT_PLAN", None) if env_extra is None else None
    r = subprocess.run(
        [sys.executable, "-c", _JOB_SCRIPT, str(workdir), str(out)]
        + ([str(sigkill_at)] if sigkill_at is not None else []),
        capture_output=True, text=True, env=env, timeout=timeout)
    return r


class TestKillMidEpochAcceptance:
    @pytest.fixture(scope="class")
    def ref_params(self, tmp_path_factory):
        """Final params of the uninterrupted 20-step job."""
        d = tmp_path_factory.mktemp("ref")
        ref = _run_job(d, d / "ref_job", d / "ref")
        assert ref.returncode == 0, ref.stderr[-800:]
        return dict(np.load(str(d / "ref.npz")))

    def test_sigterm_relaunch_bit_identical(self, tmp_path, ref_params):
        """THE acceptance test: SIGTERM-at-step-13 (injected
        deterministically by the fault plan) → rc 75 → relaunch of the
        identical spec → final params BIT-IDENTICAL to an uninterrupted
        run; the dump in the workdir classifies preempted_resumable and
        the manifest passes the audit."""
        plan = faults.FaultPlan.kill_at_step(13)
        killed = _run_job(tmp_path, tmp_path / "job", tmp_path / "kill",
                          env_extra={"TPUDL_FAULT_PLAN": plan.to_env()})
        assert killed.returncode == RC_PREEMPTED, (
            killed.returncode, killed.stderr[-800:])
        assert not os.path.exists(str(tmp_path / "kill.npz"))
        m = load_manifest(str(tmp_path / "job"))
        assert m["status"] == "preempted"
        # checkpoint-then-exit: cursor == checkpoint step, rework 0
        assert m["cursor"]["step"] == m["checkpoint"]["step"]
        assert 13 <= m["cursor"]["step"] <= 15

        resumed = _run_job(tmp_path, tmp_path / "job", tmp_path / "kill")
        assert resumed.returncode == 0, resumed.stderr[-800:]
        b = np.load(str(tmp_path / "kill.npz"))
        for k in ("w", "b"):
            assert np.array_equal(ref_params[k], b[k]), (
                f"params[{k}] differ after preempt+resume")

        res = obs_doctor.diagnose(str(tmp_path / "job"))
        assert res is not None
        _, diag = res
        assert diag["classification"] == "preempted_resumable"
        assert "job-manifest.json" in str(diag["resume_manifest"])
        vj = _load_validator()
        assert vj.validate_manifest(str(tmp_path / "job")) == []
        final = load_manifest(str(tmp_path / "job"))
        assert final["status"] == "done"
        assert final["attempt"] == 2

    def test_sigkill_relaunch_reworks_at_most_save_every_steps(
            self, tmp_path, ref_params):
        """The hard kill: SIGKILL inside step 13's ``data_fn`` (no
        handler, no boundary) → the relaunch of the identical spec
        starts from the last periodic save, re-executes at most
        ``save_every`` = 5 of the steps the killed run had finished,
        and ends BIT-IDENTICAL to an uninterrupted run."""
        def steps_seen():
            with open(str(tmp_path / "kill.steps")) as f:
                return [int(x) for x in f.read().split()]

        killed = _run_job(tmp_path, tmp_path / "job", tmp_path / "kill",
                          sigkill_at=13)
        assert killed.returncode == -signal.SIGKILL, killed.stderr[-800:]
        assert steps_seen() == list(range(14))  # steps 0..12 finished
        resumed = _run_job(tmp_path, tmp_path / "job", tmp_path / "kill")
        assert resumed.returncode == 0, resumed.stderr[-800:]
        resumed_steps = steps_seen()[14:]
        start = resumed_steps[0]
        assert resumed_steps == list(range(start, 20))
        assert 0 < 13 - start <= 5, f"resumed at step {start}"
        b = np.load(str(tmp_path / "kill.npz"))
        for k in ("w", "b"):
            assert np.array_equal(ref_params[k], b[k]), (
                f"params[{k}] differ after hard kill + resume")


# -- executor overhead guard (fault hooks must stay free) ------------------
class TestFaultHookOverhead:
    def test_unarmed_fire_is_cheap(self):
        t0 = time.perf_counter()
        for _ in range(100_000):
            faults.fire("frame.dispatch", index=0)
        dt = time.perf_counter() - t0
        assert dt < 0.5  # 5µs/call ceiling — a None-check + kwargs
