"""Roofline attribution + knob advisor (tpudl.obs.roofline).

ISSUE 6 acceptance: on bench-round-4/5-shaped fixtures the report must
attribute ≥ 80% of the device-vs-e2e gap to dispatch+wire, NAME
dispatch as the bottleneck, and the advisor must recommend a concrete
``fuse_steps`` increase with a predicted gain. Plus: the wire-bound
shape recommends a codec, the prepare-bound shape recommends workers,
gauges publish, and a REAL map_batches run feeds the model end-to-end.
"""

from __future__ import annotations

import numpy as np
import pytest

from tpudl import obs
from tpudl.obs import roofline


def round45_report(**over) -> dict:
    """A PipelineReport dict shaped like the bench's judged featurize
    runs in rounds 4–5 (July 2026): 1024 rows in 4 × 256-row
    dispatches, the chip at 34.26 ms/step (~7,470 img/s) while e2e
    wall-clock sits near ~445 img/s, u8 pixels on the wire, no fusion.
    The residual is the blocking per-dispatch round-trip."""
    rep = {
        "run_id": "fixture-r45",
        "wall_seconds": 2.3,
        "finished": True,
        "stage_seconds": {"prepare": 1.5, "infeed_wait": 0.12,
                          "dispatch": 1.9, "d2h": 0.1},
        "stage_calls": {"dispatch": 4, "prepare": 4,
                        "bytes_prepared": int(1024 * 0.0685 * 2**20)},
        "rows": 1024, "rows_done": 1024,
        "batch_size": 256, "fuse_steps": 1,
        "prefetch_depth": 2, "prepare_workers": 2,
        "wire_codec": "u8", "executor": "pipelined",
    }
    rep.update(over)
    return rep


# the round-4 capture's wire + device numbers
WIRE_MBPS = 140.0       # effective in-stream delivery during the run
DEVICE_MS = 34.26       # July 2026 "XLA Modules" lane, batch 256


class TestRound45Attribution:
    def test_dispatch_named_and_gap_attributed(self):
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr is not None
        # achieved ~445 img/s vs achievable ~7,470 img/s
        assert rr.achieved_rows_per_s == pytest.approx(1024 / 2.3,
                                                       rel=1e-3)
        assert rr.achievable_rows_per_s == pytest.approx(7472, rel=0.01)
        # the acceptance bar: ≥ 80% of the device-vs-e2e gap lands on
        # dispatch + wire, and dispatch is THE bottleneck
        assert rr.bottleneck == "dispatch"
        assert rr.dispatch_plus_wire_frac() >= 0.80
        # attribution fractions are sane and bounded
        total = sum(rr.gap_attribution.values())
        assert 0.95 <= total <= 1.01
        assert all(0.0 <= v <= 1.0 for v in rr.gap_attribution.values())

    def test_dispatch_depth_is_top_recommendation(self):
        """ISSUE 10: on the dispatch-bound round-4/5 shape the async
        in-flight window is THE recommendation — it overlaps the
        round-trips (and the d2h drain) without recompiling, so it must
        outrank fusion; fuse_steps rides second (the two compose)."""
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.advice, "dispatch-bound run must produce advice"
        top = rr.advice[0]
        assert top["knob"] == "dispatch_depth"
        assert top["recommended"] > top["current"] == 1
        assert top["recommended"] <= roofline.KNOB_CAPS["dispatch_depth"]
        assert top["predicted_gain_pct"] > 20
        assert "dispatch_depth" in rr.verdict and "dispatch" in rr.verdict

    def test_advisor_recommends_fuse_steps_with_gain(self):
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        fuse = next(r for r in rr.advice if r["knob"] == "fuse_steps")
        assert fuse["recommended"] > fuse["current"] == 1
        assert fuse["recommended"] <= roofline.KNOB_CAPS["fuse_steps"]
        assert fuse["predicted_gain_pct"] > 20

    def test_verdict_consumable_by_async_executor(self):
        """The ROADMAP-2 contract: the advice entries carry exactly the
        knob names map_batches accepts, as numbers (or codec strings)
        — directly settable, no parsing (the autotuner consumes
        fuse_steps/dispatch_depth/prefetch_depth verbatim)."""
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        valid = {"fuse_steps", "dispatch_depth", "prefetch_depth",
                 "prepare_workers", "wire_codec", "device_cache"}
        for rec in rr.advice:
            assert rec["knob"] in valid
            assert "recommended" in rec and "predicted_gain_pct" in rec

    def test_autotune_seed_matches_advice(self):
        """autotune_seed() returns exactly the advisor's recommended
        numbers for the executor-seedable knobs, capped."""
        rep = round45_report()
        rr = roofline.analyze(rep, h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        advice = {r["knob"]: r["recommended"] for r in rr.advice}
        import os

        os.environ["TPUDL_WIRE_MBPS"] = str(WIRE_MBPS)
        os.environ["TPUDL_DEVICE_MS_PER_STEP"] = str(DEVICE_MS)
        try:
            seeds = roofline.autotune_seed(rep)
        finally:
            del os.environ["TPUDL_WIRE_MBPS"]
            del os.environ["TPUDL_DEVICE_MS_PER_STEP"]
        assert seeds["dispatch_depth"] == advice["dispatch_depth"]
        assert seeds["fuse_steps"] == advice["fuse_steps"]
        assert set(seeds) <= set(roofline.AUTOTUNE_KNOBS)
        for k, v in seeds.items():
            assert v <= roofline.KNOB_CAPS[k]

    def test_async_report_attributes_dispatch_wait_not_pool_sum(self):
        """A report from the async executor carries pool-summed
        ``dispatch`` seconds (can exceed wall) plus the consumer's
        ``dispatch_wait``: the model must attribute the WAIT — the
        unhidden residue — not re-charge time the window already hid."""
        rep = round45_report(
            wall_seconds=0.8,
            stage_seconds={"prepare": 1.5, "infeed_wait": 0.05,
                           "dispatch": 1.9,       # pool-summed
                           "dispatch_wait": 0.25,  # consumer residue
                           "d2h": 0.05},
            dispatch_depth=8)
        rr = roofline.analyze(rep, h2d_mbps=10_000.0,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr is not None
        # residue ≈ 0.25 - 0.137 compute; never the pool-summed 1.9
        assert rr.dispatch_overhead_s <= 0.25
        assert rr.inputs["dispatch_depth"] == 8
        total = sum(rr.gap_attribution.values())
        assert total <= 1.0001


class TestOtherBottlenecks:
    def test_wire_bound_recommends_codec(self):
        """A round-5 slow link (8 MB/s) with identity-shipped float32:
        the wire owns the dispatch window; advisor says codec."""
        rep = round45_report(
            wall_seconds=36.0,
            stage_seconds={"prepare": 1.5, "infeed_wait": 0.1,
                           "dispatch": 35.3, "d2h": 0.2},
            stage_calls={"dispatch": 4, "prepare": 4,
                         "bytes_prepared": int(1024 * 0.274 * 2**20)},
            wire_codec="identity")
        rr = roofline.analyze(rep, h2d_mbps=8.0,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.bottleneck == "wire_h2d"
        assert rr.dispatch_plus_wire_frac() >= 0.80
        knobs = [r["knob"] for r in rr.advice]
        assert "wire_codec" in knobs
        rec = next(r for r in rr.advice if r["knob"] == "wire_codec")
        assert rec["recommended"] == "auto"
        assert "wire-bound" in rr.verdict

    def test_prepare_bound_recommends_workers(self):
        """Unhidden decode: infeed_wait dominates → grow the pool (and
        the queue to feed it)."""
        rep = round45_report(
            wall_seconds=8.0,
            stage_seconds={"prepare": 7.5, "infeed_wait": 6.0,
                           "dispatch": 1.0, "d2h": 0.1},
            stage_calls={"dispatch": 4, "prepare": 4,
                         "bytes_prepared": 4 << 20},
            prepare_workers=1, prefetch_depth=1)
        rr = roofline.analyze(rep, h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.bottleneck == "prepare"
        knobs = [r["knob"] for r in rr.advice]
        assert "prepare_workers" in knobs
        w = next(r for r in rr.advice if r["knob"] == "prepare_workers")
        assert w["recommended"] == 2 and w["current"] == 1
        assert "prefetch_depth" in knobs  # companion rec rides along

    def test_device_bound_is_healthy(self):
        """When the chip owns ≥ 80% of wall, the verdict says so and no
        knob fiddling is advised as the headline."""
        rep = round45_report(
            wall_seconds=0.16,
            stage_seconds={"prepare": 0.01, "infeed_wait": 0.001,
                           "dispatch": 0.145, "d2h": 0.005},
            stage_calls={"dispatch": 4, "prepare": 4,
                         "bytes_prepared": 4 << 20})
        rr = roofline.analyze(rep, h2d_mbps=2000.0,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.verdict.startswith("device-bound")


class TestModelEdges:
    def test_no_device_time_still_attributes(self):
        """Without a device ms/step the dispatch stage is attributed
        whole (un-split) — achievable stays None, nothing crashes."""
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              publish=False)
        assert rr is not None
        assert rr.achievable_rows_per_s is None
        assert rr.device_compute_s is None
        assert rr.gap_attribution["dispatch"] > 0.4

    def test_wire_model_clamped_to_dispatch_window(self):
        """A probe taken over a slower link must not 'explain' more
        dispatch time than the stage measured: modeled wire is clamped
        into dispatch − compute."""
        rep = round45_report()
        rr = roofline.analyze(rep, h2d_mbps=1.0,  # absurdly slow probe
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.wire_h2d_s <= rep["stage_seconds"]["dispatch"] + 1e-9
        assert rr.dispatch_overhead_s >= 0.0

    def test_mesh_path_explicit_h2d_not_subtracted_from_dispatch(self):
        """On the mesh path the transfer has its OWN measured stage —
        the model must not also subtract it from dispatch (that would
        double-count the wire and understate the round-trip). And
        because that stage is POOL-SUMMED worker time largely hidden
        under dispatch, it may only claim the gap's unexplained
        remainder — fractions can never sum past 1."""
        rep = round45_report(
            stage_seconds={"prepare": 1.5, "infeed_wait": 0.12,
                           "h2d": 0.5, "dispatch": 1.9, "d2h": 0.1})
        rr = roofline.analyze(rep, h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        # dispatch residue = 1.9 - 0.137 compute, NOT another -0.5 wire
        assert rr.dispatch_overhead_s == pytest.approx(1.9 - 0.137,
                                                       abs=1e-3)
        # gap remainder after consumer-wall components = ~0.18s: the
        # 0.5s pool-summed h2d claims only what nothing else explains
        assert rr.wire_h2d_s == pytest.approx(0.18, abs=1e-2)
        assert sum(rr.gap_attribution.values()) <= 1.0001

    def test_sharded_report_gets_ranked_advice_and_mesh_inputs(self):
        """ISSUE 11 acceptance: a data-sharded (mesh) report still gets
        a RANKED knob verdict — dispatch_depth and fuse_steps both
        recommended on a dispatch-bound shape (a mesh multiplies
        compute, not the per-dispatch round-trip) — and the inputs
        carry the topology + the measured sharded-transfer stage."""
        rep = round45_report(
            stage_seconds={"prepare": 1.5, "infeed_wait": 0.12,
                           "h2d": 0.5, "dispatch": 1.9, "d2h": 0.1})
        rep["mesh"] = {"data": 8, "model": 1}
        rep["stage_calls"]["pad_rows"] = 24
        rr = roofline.analyze(rep, h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr.inputs["mesh"] == {"data": 8, "model": 1}
        assert rr.inputs["h2d_s"] == pytest.approx(0.5)
        assert rr.inputs["pad_rows"] == 24
        knobs = [r["knob"] for r in rr.advice]
        assert knobs[0] in ("dispatch_depth", "fuse_steps")
        assert {"dispatch_depth", "fuse_steps"} <= set(knobs)
        assert rr.advice[0]["predicted_gain_pct"] > 0
        # ranked: gains are non-increasing down the list
        gains = [r["predicted_gain_pct"] for r in rr.advice]
        assert gains == sorted(gains, reverse=True)

    def test_empty_and_meaningless_reports(self):
        assert roofline.analyze({}, publish=False) is None
        assert roofline.analyze({"stage_calls": {"dispatch": 0},
                                 "rows": 0, "wall_seconds": 0},
                                publish=False) is None

    def test_unfinished_run_uses_age(self):
        """A LIVE (unfinished) report is attributable mid-run off its
        age_s and rows_done — what the status plane ticks on."""
        rep = round45_report(wall_seconds=0.0, finished=False,
                             rows_done=512)
        rep["age_s"] = 1.15
        rr = roofline.analyze(rep, h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        assert rr is not None
        assert rr.achieved_rows_per_s == pytest.approx(512 / 1.15,
                                                       rel=1e-3)

    def test_env_device_ms_fallback(self, monkeypatch):
        monkeypatch.setenv("TPUDL_DEVICE_MS_PER_STEP", str(DEVICE_MS))
        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              publish=False)
        assert rr.achievable_rows_per_s == pytest.approx(7472, rel=0.01)


class TestGaugesAndIntegration:
    def test_publishes_obs_roofline_gauges(self):
        roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                         device_ms_per_dispatch=DEVICE_MS, publish=True)
        snap = obs.snapshot()
        assert "obs.roofline.achieved_rows_per_s" in snap
        assert "obs.roofline.achievable_rows_per_s" in snap
        assert snap["obs.roofline.gap_frac.dispatch"]["value"] > 0.4
        assert snap["obs.roofline.predicted_gain_pct"]["value"] > 20

    def test_real_map_batches_run_feeds_model(self, monkeypatch):
        """End-to-end: a real executor run's report (bytes_prepared +
        rows_done recorded by the executor itself) analyzes without any
        hand-fed numbers except the wire figure."""
        from tpudl.frame import Frame

        monkeypatch.setenv("TPUDL_WIRE_MBPS", "100")
        rng = np.random.default_rng(0)
        f = Frame({"x": rng.normal(size=(512, 32)).astype(np.float32)})
        f.map_batches(lambda a: a.sum(axis=1), ["x"], ["y"],
                      batch_size=64)
        rep = obs.last_pipeline_report()
        assert rep["rows_done"] == 512 and rep["finished"]
        assert rep["stage_calls"]["bytes_prepared"] == 512 * 32 * 4
        rr = obs.analyze_roofline(rep, publish=False)
        assert rr is not None
        assert rr.achieved_rows_per_s > 0
        assert rr.inputs["h2d_mbps"] == 100.0

    def test_to_dict_round_trips_json(self):
        import json

        rr = roofline.analyze(round45_report(), h2d_mbps=WIRE_MBPS,
                              device_ms_per_dispatch=DEVICE_MS,
                              publish=False)
        d = json.loads(json.dumps(rr.to_dict()))
        assert d["bottleneck"] == "dispatch"
        assert d["advice"][0]["knob"] == "dispatch_depth"
