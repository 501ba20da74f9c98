"""Metrics-registry tests (ISSUE 3 tentpole pillar 2) + the
instrumentation sweep across frame/imageIO/ml/hpo/udf/train, the
``TPUDL_METRICS_FILE`` JSONL contract (schema-checked by
tools/validate_metrics.py), Meter edge cases, and what the armed sink
costs the executor (a count of appended lines)."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from tpudl import obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_metrics", os.path.join(REPO, "tools",
                                         "validate_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def registry():
    reg = obs.get_registry()
    reg.reset()
    yield reg
    reg.reset()


# -- registry semantics ----------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_snapshot(self, registry):
        obs.counter("a.calls").inc()
        obs.counter("a.calls").inc(2)
        obs.gauge("a.depth").set(3)
        obs.gauge("a.depth").set(7)
        obs.gauge("a.depth").set(5)
        obs.histogram("a.lat").observe(1.0)
        obs.histogram("a.lat").observe(3.0)
        s = obs.snapshot()
        assert s["a.calls"] == {"type": "counter", "value": 3.0}
        g = s["a.depth"]
        assert (g["value"], g["count"], g["max"], g["mean"]) == (5.0, 3,
                                                                 7.0, 5.0)
        h = s["a.lat"]
        assert h["count"] == 2 and h["sum"] == 4.0 and h["mean"] == 2.0
        assert h["min"] == 1.0 and h["max"] == 3.0

    def test_histogram_bounded_memory_exact_aggregates(self, registry):
        h = obs.histogram("big", cap=100)
        for i in range(10_000):
            h.observe(float(i))
        assert len(h.samples) == 100  # ring bounded
        d = h.to_dict()
        # mean/min/max exact over ALL 10k samples despite the cap
        assert d["count"] == 10_000
        assert d["mean"] == pytest.approx(4999.5)
        assert d["min"] == 0.0 and d["max"] == 9999.0
        # percentiles come from the ring (newest window)
        assert 9900 <= d["p50"] <= 9999

    def test_name_pins_kind(self, registry):
        obs.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            obs.gauge("x")

    def test_timed_context_observes(self, registry):
        with obs.timed("t.secs"):
            time.sleep(0.005)
        d = obs.snapshot()["t.secs"]
        assert d["count"] == 1 and d["min"] >= 0.004

    def test_threaded_updates_consistent(self, registry):
        import threading

        c = obs.counter("thr")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value == 4000


# -- JSONL sink + schema ---------------------------------------------------
class TestMetricsSink:
    def test_flush_writes_schema_valid_jsonl(self, registry, tmp_path,
                                             monkeypatch):
        path = str(tmp_path / "metrics.jsonl")
        monkeypatch.setenv("TPUDL_METRICS_FILE", path)
        obs.counter("k.n").inc(5)
        obs.histogram("k.lat").observe(0.25)
        obs.gauge("k.g").set(1.5)
        assert obs.flush_metrics() is True
        assert obs.flush_metrics(event="final") is True
        vm = _load_validator()
        errors, n, last = vm.validate_metrics_file(path)
        assert errors == []
        assert n == 2 and last["event"] == "final"
        assert last["metrics"]["k.n"]["value"] == 5.0

    def test_no_sink_no_write(self, registry, monkeypatch):
        monkeypatch.delenv("TPUDL_METRICS_FILE", raising=False)
        obs.counter("x").inc()
        assert obs.flush_metrics() is False

    def test_periodic_flush_throttles(self, registry, tmp_path,
                                      monkeypatch):
        path = str(tmp_path / "m.jsonl")
        monkeypatch.setenv("TPUDL_METRICS_FILE", path)
        monkeypatch.setenv("TPUDL_METRICS_FLUSH_S", "3600")
        registry.maybe_flush()  # first call flushes and arms the timer
        for _ in range(50):
            registry.maybe_flush()  # all inside the window: throttled
        with open(path) as f:
            assert len(f.readlines()) == 1

    def test_validator_rejects_malformed(self, tmp_path):
        vm = _load_validator()
        bad = tmp_path / "bad.jsonl"
        bad.write_text(
            json.dumps({"ts": "notanumber", "event": "snapshot",
                        "pid": 1, "metrics": {}}) + "\n"
            + "not json at all\n"
            + json.dumps({"ts": 1.0, "event": "snapshot", "pid": 2,
                          "metrics": {"m": {"type": "warble"}}}) + "\n")
        errors, n, _ = vm.validate_metrics_file(str(bad))
        assert n == 3 and len(errors) == 3


# -- instrumentation sweep -------------------------------------------------
class TestInstrumentationSweep:
    def test_frame_executor_publishes(self, registry):
        from tpudl.frame import Frame

        x = np.arange(64, dtype=np.float32)
        Frame({"x": x}).map_batches(lambda b: b + 1, ["x"], ["y"],
                                    batch_size=8)
        s = obs.snapshot()
        assert s["frame.map_batches.runs"]["value"] == 1.0
        assert s["frame.map_batches.rows"]["value"] == 64.0
        assert s["frame.map_batches.wall_seconds"]["count"] == 1
        assert s["frame.stage.dispatch.seconds"]["value"] > 0.0

    def test_imageio_counters(self, registry, tmp_path):
        from PIL import Image

        from tpudl.image.imageIO import readImages

        for i in range(3):
            Image.fromarray(
                np.full((8, 8, 3), 40 * i, np.uint8)).save(
                    tmp_path / f"im{i}.png")
        (tmp_path / "junk.png").write_bytes(b"not an image")
        frame = readImages(str(tmp_path))
        col = frame["image"]
        col[0:4]  # one batch: 4 reads, 3 decodes ok, 1 null row
        s = obs.snapshot()
        assert s["imageio.files_read"]["value"] == 4.0
        assert s["imageio.bytes_read"]["value"] > 0.0
        assert s["imageio.decode_errors"]["value"] == 1.0
        col[0:4]  # small-access memo: served without new reads
        s = obs.snapshot()
        assert s["imageio.memo_hits"]["value"] == 1.0
        assert s["imageio.files_read"]["value"] == 4.0

    def test_ml_transformer_rows_and_seconds(self, registry):
        from tpudl.frame import Frame
        from tpudl.ml.pipeline import Transformer

        class Doubler(Transformer):
            def _transform(self, frame):
                return frame.with_column("y", frame["x"] * 2)

        out = Doubler().transform(Frame({"x": np.arange(5.0)}))
        assert len(out) == 5
        s = obs.snapshot()
        assert s["ml.Doubler.transforms"]["value"] == 1.0
        assert s["ml.Doubler.rows_in"]["value"] == 5.0
        assert s["ml.Doubler.rows_out"]["value"] == 5.0
        assert s["ml.Doubler.transform_seconds"]["count"] == 1
        # the transform landed on the host-span tracer too
        names = [sp.name for sp in obs.get_tracer().spans()]
        assert "ml.Doubler.transform" in names

    def test_hpo_trial_metrics(self, registry):
        from tpudl.ml.hpo import TrialScheduler

        sched = TrialScheduler()
        got = dict(sched.run([10, 20, 30],
                             lambda i, item, devs: item + 1))
        assert got == {0: 11, 1: 21, 2: 31}
        s = obs.snapshot()
        assert s["hpo.trials_started"]["value"] == 3.0
        assert s["hpo.trials_completed"]["value"] == 3.0
        assert "hpo.trials_failed" not in s
        assert s["hpo.trial_seconds"]["count"] == 3

    def test_hpo_failed_trial_counted(self, registry):
        from tpudl.ml.hpo import TrialScheduler

        def boom(i, item, devs):
            raise RuntimeError("trial dies")

        with pytest.raises(RuntimeError):
            list(TrialScheduler().run([1], boom))
        assert obs.snapshot()["hpo.trials_failed"]["value"] == 1.0

    def test_udf_call_metrics(self, registry):
        import jax.numpy as jnp

        from tpudl.frame import Frame
        from tpudl.ingest.builder import GraphFunction
        from tpudl.udf import makeGraphUDF

        gf = GraphFunction(lambda a: jnp.tanh(a), ["x"], ["y"])
        udf = makeGraphUDF(gf, "obs_udf", register=False)
        data = np.linspace(-1, 1, 12).astype(np.float32)
        udf(Frame({"x": data}))
        udf(Frame({"x": data}))
        s = obs.snapshot()
        assert s["udf.obs_udf.calls"]["value"] == 2.0
        assert s["udf.obs_udf.rows"]["value"] == 24.0
        assert s["udf.obs_udf.seconds"]["count"] == 2

    def test_trainer_step_and_checkpoint_metrics(self, registry, tmp_path):
        optax = pytest.importorskip("optax")

        import jax.numpy as jnp

        from tpudl.train import Trainer

        def loss_fn(p, x, y):
            return jnp.mean((x @ p["w"] - y) ** 2)

        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        Y = (X @ np.ones((4, 1), np.float32)).astype(np.float32)
        data = lambda step: (X, Y)  # noqa: E731
        params = {"w": jnp.zeros((4, 1))}
        ckdir = str(tmp_path / "ck")
        tr = Trainer(loss_fn, optax.sgd(0.1), checkpoint_dir=ckdir,
                     save_every=2)
        tr.fit(params, data, steps=4)
        s = obs.snapshot()
        assert s["train.steps"]["value"] == 4.0
        assert s["train.examples"]["value"] == 256.0
        assert s["train.step_seconds"]["count"] == 4
        assert s["train.checkpoint_save_seconds"]["count"] >= 1
        # resume path observes a restore duration
        tr2 = Trainer(loss_fn, optax.sgd(0.1), checkpoint_dir=ckdir,
                      save_every=2)
        tr2.fit(params, data, steps=6)
        s = obs.snapshot()
        assert s["train.checkpoint_restore_seconds"]["count"] == 1
        assert s["train.steps"]["value"] == 6.0  # 4 + (6 - 4 resumed)

    def test_trainer_failed_run_counts_executed_steps_only(self, registry):
        optax = pytest.importorskip("optax")

        import jax.numpy as jnp

        from tpudl.train import Trainer

        def loss_fn(p, x, y):
            return jnp.mean((x @ p["w"] - y) ** 2)

        X = np.ones((8, 4), np.float32)
        Y = np.ones((8, 1), np.float32)

        def data(step):
            if step == 2:
                raise RuntimeError("input pipeline dies at step 2")
            return X, Y

        with pytest.raises(RuntimeError):
            Trainer(loss_fn, optax.sgd(0.1)).fit(
                {"w": jnp.zeros((4, 1))}, data, steps=100)
        s = obs.snapshot()
        # 2 steps ran, not the 100 planned — a failed run must not
        # report its plan as fact
        assert s["train.steps"]["value"] == 2.0
        assert s["train.examples"]["value"] == 16.0

    def test_horovod_restart_counter(self, registry):
        from tpudl.train import HorovodRunner

        state = {"tries": 0}

        def main(ctx):
            state["tries"] += 1
            if state["tries"] == 1:
                raise RuntimeError("first attempt dies")
            return "ok"

        result = HorovodRunner(np=1, max_restarts=1).run(main)
        assert result == "ok"
        assert obs.snapshot()["train.restarts"]["value"] == 1.0


# -- acceptance: end-to-end JSONL emission ---------------------------------
class TestEndToEndEmission:
    def test_featurizer_and_trainer_emit_schema_valid_jsonl(
            self, registry, tmp_path, monkeypatch):
        """ISSUE 3 acceptance: with TPUDL_METRICS_FILE set, a
        DeepImageFeaturizer.transform + a Trainer run emit JSONL that
        tools/validate_metrics.py accepts, carrying both layers'
        metrics."""
        optax = pytest.importorskip("optax")

        import jax.numpy as jnp

        from tpudl.frame import Frame
        from tpudl.image import imageIO
        from tpudl.ml import DeepImageFeaturizer
        from tpudl.train import Trainer

        path = str(tmp_path / "run_metrics.jsonl")
        monkeypatch.setenv("TPUDL_METRICS_FILE", path)

        rng = np.random.default_rng(0)
        structs = [imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(40, 40, 3), dtype=np.uint8))
            for _ in range(4)]
        feat = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                   modelName="ResNet50", batchSize=4)
        out = feat.transform(Frame({"image": structs}))
        assert len(out) == 4

        def loss_fn(p, x, y):
            return jnp.mean((x @ p["w"] - y) ** 2)

        X = rng.normal(size=(32, 4)).astype(np.float32)
        Y = (X @ np.ones((4, 1), np.float32)).astype(np.float32)
        Trainer(loss_fn, optax.sgd(0.1)).fit(
            {"w": jnp.zeros((4, 1))}, lambda s: (X, Y), steps=3)

        assert obs.flush_metrics(event="final") is True
        vm = _load_validator()
        errors, n, last = vm.validate_metrics_file(path)
        assert errors == [], errors[:5]
        assert n >= 1
        m = last["metrics"]
        assert m["ml.DeepImageFeaturizer.rows_in"]["value"] == 4.0
        assert m["train.steps"]["value"] == 3.0
        assert m["frame.map_batches.runs"]["value"] >= 1.0


# -- Meter edge cases (satellite) ------------------------------------------
class TestMeterEdgeCases:
    def test_skip_beyond_batches_clamps_and_surfaces(self):
        m = obs.Meter(skip=5)
        with m.batch(10):
            pass
        with m.batch(20):
            pass
        r = m.report()
        # clamp keeps the LAST batch instead of silently reporting 0
        assert r["examples"] == 20
        assert r["skipped"] == 1
        assert r["batches"] == 2

    def test_negative_skip_counts_everything(self):
        m = obs.Meter(skip=-3)
        with m.batch(10):
            pass
        r = m.report()
        assert r["examples"] == 10 and r["skipped"] == 0

    def test_empty_meter_reports_zeros(self):
        r = obs.Meter(skip=2).report()
        assert r["examples"] == 0
        assert r["examples_per_sec"] == 0.0
        assert r["cold_examples_per_sec"] == 0.0
        assert r["skipped"] == 0 and r["batches"] == 0

    def test_zero_seconds_and_zero_chips_guarded(self):
        m = obs.Meter(n_chips=0)
        assert m.n_chips == 1  # clamped: /0 is impossible
        m._batches.append((10, 0.0))  # pathological zero-duration batch
        r = m.report()
        assert r["examples_per_sec"] == 0.0
        assert r["examples_per_sec_per_chip"] == 0.0

    def test_normal_skip_unchanged(self):
        m = obs.Meter(n_chips=2, skip=1)
        with m.batch(10):
            pass
        with m.batch(10):
            pass
        r = m.report()
        assert r["examples"] == 10 and r["skipped"] == 1
        assert r["examples_per_sec_per_chip"] * 2 == pytest.approx(
            r["examples_per_sec"], rel=1e-4)


# -- what the armed sink costs the executor (acceptance) -------------------
def test_instrumented_executor_flushes_per_window_not_per_batch(
        registry, tmp_path, monkeypatch):
    """ISSUE 3 acceptance, as a count: with the JSONL sink armed the
    executor's hot loop appends one line per flush window
    (``TPUDL_METRICS_FLUSH_S``), however many batches and runs fall in
    it, and the line passes the validator; with the sink unset nothing
    is written. A wall-clock ratio of the two arms on the CPU rig says
    nothing about the chip and flaps under parallel workers."""
    from tpudl.frame import Frame

    frame = Frame({"x": np.arange(256 * 4, dtype=np.float32).reshape(256, 4)})
    sink = tmp_path / "sink.jsonl"

    def three_runs_of_16_batches():
        for _ in range(3):
            frame.map_batches(lambda b: b.sum(axis=1), ["x"], ["y"],
                              batch_size=16)

    monkeypatch.delenv("TPUDL_METRICS_FILE", raising=False)
    three_runs_of_16_batches()
    assert os.listdir(tmp_path) == []
    monkeypatch.setenv("TPUDL_METRICS_FILE", str(sink))
    monkeypatch.setenv("TPUDL_METRICS_FLUSH_S", "3600")
    registry.reset()  # re-arms the throttle: the next flush is due
    three_runs_of_16_batches()
    errors, n, last = _load_validator().validate_metrics_file(str(sink))
    assert errors == [] and n == 1
    assert last["metrics"]["frame.map_batches.runs"]["value"] >= 1.0
