"""Async dispatch-hiding executor (ISSUE 10 tentpole).

The acceptance surface, all tier-1 fast:

1. OVERLAP — with a fault-harness-injected per-dispatch latency (a
   deterministic slow round-trip), the depth-D executor sustains ≥ 1.8× the
   blocking executor's throughput, and batch N+1 provably dispatches
   while batch N's d2h drain is still in progress;
2. BOUND — the in-flight window never exceeds D (gauge max AND a live
   concurrency counter inside fn);
3. BIT-IDENTITY — depth 1 vs depth D, donation on vs off, fused and
   codec-wrapped paths: byte-equal outputs;
4. DONATION SAFETY — shard-cache-hit (memoized) batches feed donating
   programs as writable copies; the cache replays uncorrupted;
5. AUTOTUNE — with no env knobs set, the executor's chosen
   fuse_steps/dispatch_depth match ``obs.analyze_roofline()``'s advice
   over the previous report, and ``TPUDL_FRAME_PREFETCH=0`` still
   yields the fully serial executor (the baseline arm of an A/B).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from tpudl import obs
from tpudl.frame import Frame
import tpudl.frame.frame as frame_mod
from tpudl.testing import faults


DELAY = 0.06  # injected per-dispatch round-trip (seconds)


def _clean_env(monkeypatch):
    """Pin the executor knobs the suite asserts on to their defaults —
    an outer environment (or CI) must not leak into the A/B."""
    for var in ("TPUDL_FRAME_PREFETCH", "TPUDL_FRAME_PREFETCH_DEPTH",
                "TPUDL_FRAME_PREPARE_WORKERS", "TPUDL_FRAME_FUSE_STEPS",
                "TPUDL_FRAME_DISPATCH_DEPTH", "TPUDL_FRAME_DONATE",
                "TPUDL_FRAME_AUTOTUNE", "TPUDL_WIRE_CODEC",
                "TPUDL_DATA_CACHE_DIR", "TPUDL_WIRE_MBPS",
                "TPUDL_DEVICE_MS_PER_STEP"):
        monkeypatch.delenv(var, raising=False)


class TestOverlap:
    def test_depth_d_hides_injected_dispatch_latency(self, monkeypatch):
        """THE acceptance bar: per-dispatch latency L over N batches
        costs the blocking executor ~N·L; the D-deep window overlaps
        the round-trips and must sustain ≥ 1.8× the blocking
        throughput (expected ~3× at D=4 with 8 batches)."""
        import jax

        _clean_env(monkeypatch)
        n_batches, batch = 8, 4
        x = np.arange(n_batches * batch * 2,
                      dtype=np.float32).reshape(n_batches * batch, 2)
        f = Frame({"x": x})
        jfn = jax.jit(lambda b: b * 2.0)
        f.map_batches(jfn, ["x"], ["y"], batch_size=batch,
                      dispatch_depth=1)  # compile outside timing

        def run(depth):
            # fresh plan per arm: rule call counters are stateful
            plan = faults.FaultPlan.delay("frame.dispatch", DELAY)
            with plan.armed():
                t0 = time.perf_counter()
                out = f.map_batches(jfn, ["x"], ["y"], batch_size=batch,
                                    dispatch_depth=depth, fuse_steps=1,
                                    autotune=False)
            assert len(plan.fired) == n_batches
            return time.perf_counter() - t0, out

        blocking_s, blocking_out = run(1)
        async_s, async_out = run(4)
        assert blocking_s >= n_batches * DELAY * 0.9  # it really blocked
        speedup = blocking_s / async_s
        assert speedup >= 1.8, (
            f"depth-4 executor only {speedup:.2f}x over blocking "
            f"({async_s:.3f}s vs {blocking_s:.3f}s) — round-trips did "
            f"not overlap")
        np.testing.assert_array_equal(
            np.asarray(list(blocking_out["y"]), np.float32),
            np.asarray(list(async_out["y"]), np.float32))
        rep = obs.last_pipeline_report()
        assert rep["dispatch_depth"] == 4
        assert "dispatch_wait" in rep["stage_seconds"]
        # the window HID most of the injected latency: pool dispatch
        # seconds ≈ N·L, consumer wait ≪ that
        assert rep["dispatch_overlap_s"] >= n_batches * DELAY * 0.5

    def test_next_batch_dispatches_during_prior_d2h(self, monkeypatch):
        """Batch N+1's dispatch must START while batch N's d2h drain is
        still in progress: fn records its own start times (it runs ON
        the dispatch threads), a spy around the windowed drain records
        each d2h interval, and at least one dispatch start must land
        INSIDE a drain interval."""
        _clean_env(monkeypatch)
        starts: dict[int, float] = {}
        drains: list[tuple[float, float]] = []
        lock = threading.Lock()

        def fn(b):  # host fn on the dispatch threads (device_fn=True)
            with lock:
                starts[int(np.asarray(b)[0, 0])] = time.perf_counter()
            time.sleep(0.01)  # a visible dispatch round-trip
            return np.asarray(b) * 2

        orig_drain = frame_mod._drain

        def slow_drain(entry, outputs):
            t0 = time.perf_counter()
            time.sleep(0.03)  # a visible d2h drain
            orig_drain(entry, outputs)
            with lock:
                drains.append((t0, time.perf_counter()))

        monkeypatch.setattr(frame_mod, "_drain", slow_drain)
        n_batches, batch = 8, 4
        x = np.repeat(np.arange(n_batches, dtype=np.float32),
                      batch)[:, None]
        out = Frame({"x": x}).map_batches(
            fn, ["x"], ["y"], batch_size=batch, device_fn=True,
            dispatch_depth=3, fuse_steps=1, autotune=False)
        np.testing.assert_array_equal(
            np.stack(list(out["y"])).astype(np.float32), x * 2)
        assert drains, "windowed outfeed never drained"
        overlapped = [i for i, t in starts.items()
                      if any(s < t < e for s, e in drains)]
        assert overlapped, (
            f"no dispatch started during any d2h drain — the executor "
            f"serialized d2h against dispatch (starts={starts}, "
            f"drains={drains})")

    def test_accumulated_fetch_starts_all_copies_first(self, monkeypatch):
        """The acc-mode d2h fix (ISSUE 10 satellite): every pending
        chunk's ``copy_to_host_async`` is armed BEFORE any blocking
        ``np.asarray`` conversion, so the copies cross concurrently
        even at depth 1."""
        calls = []

        class FakeChunk:
            def __init__(self, v):
                self.v = v
                self.ndim = 1
                self.shape = (2,)

            def copy_to_host_async(self):
                calls.append(("copy", self.v))

            def __array__(self, dtype=None, copy=None):
                calls.append(("convert", self.v))
                return np.full(2, self.v, dtype=np.float32)

        acc = [[FakeChunk(0), FakeChunk(1)], [FakeChunk(2)]]
        outputs = [[], []]
        frame_mod._fetch_accumulated(acc, [(2, 0), (2, 0)], outputs)
        copies = [c for c in calls if c[0] == "copy"]
        first_convert = calls.index(("convert", 0))
        assert len(copies) == 3
        assert all(calls.index(c) < first_convert for c in copies), (
            f"a conversion ran before all copies started: {calls}")
        np.testing.assert_array_equal(
            outputs[0][0], np.array([0, 0, 1, 1], np.float32))


class TestDepthBound:
    def test_in_flight_never_exceeds_depth(self, monkeypatch):
        """Never more than D dispatches in flight: the report gauge's
        max AND a live concurrency counter inside fn agree."""
        _clean_env(monkeypatch)
        depth = 3
        live = {"cur": 0, "max": 0}
        lock = threading.Lock()

        def fn(b):
            with lock:
                live["cur"] += 1
                live["max"] = max(live["max"], live["cur"])
            time.sleep(0.01)
            with lock:
                live["cur"] -= 1
            return np.asarray(b) + 1

        x = np.arange(48, dtype=np.float32)[:, None]
        Frame({"x": x}).map_batches(fn, ["x"], ["y"], batch_size=4,
                                    device_fn=True, dispatch_depth=depth,
                                    fuse_steps=1, autotune=False)
        rep = obs.last_pipeline_report()
        assert rep["dispatch_inflight_max"] <= depth
        assert live["max"] <= depth, (
            f"{live['max']} dispatches ran concurrently at depth {depth}")
        assert live["max"] >= 2, "window never actually overlapped"

    def test_dispatch_error_propagates_and_pool_unwinds(self, monkeypatch):
        _clean_env(monkeypatch)
        plan = faults.FaultPlan.raise_in_stage("dispatch", at_call=3)
        x = np.arange(64, dtype=np.float32)

        with plan.armed(), pytest.raises(faults.FaultInjected):
            Frame({"x": x}).map_batches(
                lambda b: b * 2, ["x"], ["y"], batch_size=8,
                device_fn=True, dispatch_depth=4, autotune=False)
        deadline = time.perf_counter() + 5.0
        alive = []
        while time.perf_counter() < deadline:
            alive = [t for t in threading.enumerate()
                     if t.name.startswith("tpudl-dispatch")
                     and t.is_alive()]
            if not alive:
                break
            time.sleep(0.05)
        assert not alive, f"dispatch threads lingered: {alive}"


class TestBitIdentity:
    def _frame(self):
        rng = np.random.default_rng(7)
        return Frame({"x": rng.integers(
            0, 256, size=(40, 6)).astype(np.float32)})

    def test_depth_and_donation_matrix_bitwise_identical(self, monkeypatch):
        """depth ∈ {1, 4} × donate ∈ {off, on} × fuse ∈ {1, 4}: every
        cell byte-equal to the serial reference (the fused-dispatch
        bit-identity guarantee survives the async window + donation)."""
        import jax

        _clean_env(monkeypatch)
        f = self._frame()
        jfn = jax.jit(lambda b: (b * 3.0 + 0.5).sum(axis=1))
        ref = f.map_batches(jfn, ["x"], ["y"], batch_size=4,
                            prefetch=False, dispatch_depth=1,
                            donate=False, autotune=False)
        ref_y = np.asarray(list(ref["y"]), np.float32)
        for depth in (1, 4):
            for donate in (False, True):
                for fuse in (1, 4):
                    out = f.map_batches(
                        jfn, ["x"], ["y"], batch_size=4,
                        dispatch_depth=depth, donate=donate,
                        fuse_steps=fuse, autotune=False)
                    np.testing.assert_array_equal(
                        np.asarray(list(out["y"]), np.float32), ref_y,
                        err_msg=f"depth={depth} donate={donate} "
                                f"fuse={fuse}")

    def test_codec_path_donation_bitwise_identical(self, monkeypatch):
        """u8 wire codec (encoded uint8 inputs, donating wrapped
        program) restores bit-identically with donation on and off."""
        import jax

        _clean_env(monkeypatch)
        f = self._frame()
        jfn = jax.jit(lambda b: b.sum(axis=1))
        outs = {}
        for donate in (False, True):
            out = f.map_batches(jfn, ["x"], ["y"], batch_size=4,
                                wire_codec="u8", donate=donate,
                                dispatch_depth=2, autotune=False)
            outs[donate] = np.asarray(list(out["y"]), np.float32)
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_donation_safe_on_shard_cache_hits(self, tmp_path,
                                               monkeypatch):
        """Memoized (cache-hit) batches feed donating programs as
        writable COPIES: the warm replay's outputs equal the cold
        run's, the shards survive byte-for-byte (no corruption counter
        movement), and a THIRD donation-off replay still agrees."""
        import jax

        _clean_env(monkeypatch)
        f = self._frame()
        jfn = jax.jit(lambda b: b.sum(axis=1))
        kw = dict(batch_size=4, wire_codec="u8",
                  cache_dir=str(tmp_path), cache_key="donate-safety",
                  autotune=False)
        cold = f.map_batches(jfn, ["x"], ["y"], donate=True,
                             dispatch_depth=2, **kw)
        before = obs.snapshot()
        warm = f.map_batches(jfn, ["x"], ["y"], donate=True,
                             dispatch_depth=4, **kw)
        replay = f.map_batches(jfn, ["x"], ["y"], donate=False,
                               dispatch_depth=1, **kw)
        after = obs.snapshot()

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        assert delta("data.cache.hits") >= 20  # both replays hit
        assert delta("data.cache.corrupt") == 0
        cold_y = np.asarray(list(cold["y"]), np.float32)
        np.testing.assert_array_equal(
            np.asarray(list(warm["y"]), np.float32), cold_y)
        np.testing.assert_array_equal(
            np.asarray(list(replay["y"]), np.float32), cold_y)


def _dispatch_bound_prior_report(batch_size=256):
    """File a finished round-4/5-shaped (dispatch-bound) report into
    the ring — the 'previous run' the autotuner seeds from.
    ``batch_size`` must match the NEXT run's: the seed's workload guard
    refuses a report from a different batch geometry."""
    rep = obs.PipelineReport()
    rep.stages = {"prepare": 1.5, "infeed_wait": 0.12, "dispatch": 1.9,
                  "d2h": 0.1}
    rep.calls = {"dispatch": 4, "prepare": 4,
                 "bytes_prepared": int(1024 * 0.0685 * 2**20)}
    rep.rows_done = 1024
    rep.wall_seconds = 2.3
    rep.finished = True
    rep.config = {"rows": 1024, "batch_size": int(batch_size),
                  "fuse_steps": 1, "dispatch_depth": 1,
                  "prefetch_depth": 2, "prepare_workers": 2,
                  "wire_codec": "u8", "executor": "pipelined"}
    obs.set_last_pipeline(rep)
    return rep


class TestAutotune:
    def test_seeds_match_roofline_advice(self, monkeypatch):
        """ISSUE 10 acceptance: with NO env knobs set, the executor's
        report shows autotune-chosen fuse_steps/dispatch_depth equal to
        ``obs.analyze_roofline()``'s recommendations over the previous
        report."""
        import jax

        _clean_env(monkeypatch)
        monkeypatch.setenv("TPUDL_WIRE_MBPS", "140")
        monkeypatch.setenv("TPUDL_DEVICE_MS_PER_STEP", "34.26")
        _dispatch_bound_prior_report(batch_size=4)
        rr = obs.analyze_roofline(obs.last_pipeline_report(),
                                  publish=False)
        advice = {r["knob"]: r["recommended"] for r in rr.advice}
        assert advice.get("dispatch_depth", 0) > 1
        assert advice.get("fuse_steps", 0) > 1

        x = np.arange(256, dtype=np.float32).reshape(64, 4)
        out = Frame({"x": x}).map_batches(
            jax.jit(lambda b: b * 2), ["x"], ["y"], batch_size=4)
        rep = obs.last_pipeline_report()
        assert rep["autotune"] is True
        assert rep["dispatch_depth"] == advice["dispatch_depth"]
        assert rep["fuse_steps"] == advice["fuse_steps"]
        assert set(rep["autotuned"]) >= {"dispatch_depth", "fuse_steps"}
        np.testing.assert_array_equal(
            np.stack(list(out["y"])).astype(np.float32), x * 2)

    def test_explicit_knobs_beat_autotune(self, monkeypatch):
        import jax

        _clean_env(monkeypatch)
        monkeypatch.setenv("TPUDL_WIRE_MBPS", "140")
        monkeypatch.setenv("TPUDL_DEVICE_MS_PER_STEP", "34.26")
        _dispatch_bound_prior_report(batch_size=8)
        x = np.arange(64, dtype=np.float32)
        Frame({"x": x}).map_batches(jax.jit(lambda b: b), ["x"], ["y"],
                                    batch_size=8, fuse_steps=2,
                                    dispatch_depth=3)
        rep = obs.last_pipeline_report()
        assert rep["fuse_steps"] == 2
        assert rep["dispatch_depth"] == 3
        assert rep["autotuned"] == []

    def test_env_knobs_beat_autotune(self, monkeypatch):
        import jax

        _clean_env(monkeypatch)
        monkeypatch.setenv("TPUDL_WIRE_MBPS", "140")
        monkeypatch.setenv("TPUDL_DEVICE_MS_PER_STEP", "34.26")
        monkeypatch.setenv("TPUDL_FRAME_DISPATCH_DEPTH", "2")
        monkeypatch.setenv("TPUDL_FRAME_FUSE_STEPS", "1")
        _dispatch_bound_prior_report(batch_size=8)
        x = np.arange(64, dtype=np.float32)
        Frame({"x": x}).map_batches(jax.jit(lambda b: b), ["x"], ["y"],
                                    batch_size=8)
        rep = obs.last_pipeline_report()
        assert rep["dispatch_depth"] == 2
        assert rep["fuse_steps"] == 1
        assert "dispatch_depth" not in rep["autotuned"]
        assert "fuse_steps" not in rep["autotuned"]

    def test_mismatched_batch_size_never_seeds(self, monkeypatch):
        """The workload guard: a prior report from a DIFFERENT batch
        geometry must not tune this run (a process alternating a big
        featurizer and a tiny scorer would otherwise cross-tune)."""
        import jax

        _clean_env(monkeypatch)
        monkeypatch.setenv("TPUDL_WIRE_MBPS", "140")
        monkeypatch.setenv("TPUDL_DEVICE_MS_PER_STEP", "34.26")
        _dispatch_bound_prior_report(batch_size=256)
        x = np.arange(64, dtype=np.float32)
        Frame({"x": x}).map_batches(jax.jit(lambda b: b), ["x"], ["y"],
                                    batch_size=8)
        rep = obs.last_pipeline_report()
        assert rep["autotuned"] == []
        assert rep["dispatch_depth"] == 2  # defaults, not the seed
        assert rep["fuse_steps"] == 1

    def test_kill_switch_yields_fully_serial_executor(self, monkeypatch):
        """The pre-existing A/B kill switch still produces the serial
        baseline arm: no prefetch, no fusion, no dispatch window, no
        autotune, no donation."""
        import jax

        _clean_env(monkeypatch)
        monkeypatch.setenv("TPUDL_FRAME_PREFETCH", "0")
        _dispatch_bound_prior_report()
        x = np.arange(16, dtype=np.float32)
        out = Frame({"x": x}).map_batches(jax.jit(lambda b: b * 2),
                                          ["x"], ["y"], batch_size=4)
        rep = obs.last_pipeline_report()
        assert rep["executor"] == "serial"
        assert rep["dispatch_depth"] == 1
        assert rep["fuse_steps"] == 1
        assert rep["donate"] is False
        assert rep["autotune"] is False
        assert "dispatch_wait" not in rep["stage_seconds"]
        np.testing.assert_array_equal(
            np.asarray(out["y"], np.float32), x * 2)

    def test_host_fns_never_async(self, monkeypatch):
        """A host fn's dispatch stays on the consumer thread (depth is
        forced to 1) — its numpy inputs and in-place mutations keep
        today's serial semantics."""
        _clean_env(monkeypatch)
        names = []

        def fn(b):
            names.append(threading.current_thread().name)
            return np.asarray(b) + 1

        x = np.arange(16, dtype=np.float32)
        Frame({"x": x}).map_batches(fn, ["x"], ["y"], batch_size=4)
        rep = obs.last_pipeline_report()
        assert rep["dispatch_depth"] == 1
        assert not any(n.startswith("tpudl-dispatch") for n in names)


class TestReportSurface:
    def test_async_run_reports_window_gauges(self, monkeypatch):
        """The new observability contract: dispatch_inflight gauge,
        dispatch_wait stage, dispatch_overlap_s on the report, and the
        frame.dispatch.* process gauges move."""
        import jax

        _clean_env(monkeypatch)
        x = np.arange(96, dtype=np.float32)[:, None]
        Frame({"x": x}).map_batches(jax.jit(lambda b: b * 2), ["x"],
                                    ["y"], batch_size=8,
                                    dispatch_depth=3, autotune=False)
        rep = obs.last_pipeline_report()
        assert rep["executor"] == "pipelined"
        assert rep["dispatch_depth"] == 3
        assert 1 <= rep["dispatch_inflight_max"] <= 3
        assert "dispatch_wait" in rep["stage_seconds"]
        assert rep["dispatch_overlap_s"] >= 0.0
        snap = obs.snapshot()
        assert "frame.dispatch.inflight" in snap
        assert "frame.dispatch.overlap_s" in snap

    def test_serial_run_has_no_window_keys(self, monkeypatch):
        _clean_env(monkeypatch)
        x = np.arange(16, dtype=np.float32)
        Frame({"x": x}).map_batches(lambda b: b + 1, ["x"], ["y"],
                                    batch_size=4)
        rep = obs.last_pipeline_report()
        assert "dispatch_wait" not in rep["stage_seconds"]
        assert "dispatch_overlap_s" not in rep
        assert "dispatch_inflight_max" not in rep
