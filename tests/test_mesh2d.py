"""tpudl 2-D mesh tensor parallelism (ISSUE 16).

The acceptance surface of the GSPMD model-sharded fast path: the
``TPUDL_MESH_MODEL`` knob + idle-device rail, Megatron param layouts
across {8x1, 4x2, 2x4} grids, the transfer_batch pass-through for
model-resident leaves, the generate/executor parity matrix, the HLO
collective pin (ZERO all-gathers of param shards), the capacity proof
(params that only fit sharded), the roofline ``collective`` component,
and the validate_job topology audit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudl import mesh as M
from tpudl import obs
from tpudl.frame import Frame
from tpudl.frame.supervisor import DeviceOOM
from tpudl.obs import metrics as obs_metrics
from tpudl.zoo.transformer import TinyCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def registry():
    obs_metrics.get_registry().reset()
    yield
    obs_metrics.get_registry().reset()


def _metric(name):
    return obs.snapshot().get(name, {}).get("value")


def _clean_env(monkeypatch):
    for var in ("TPUDL_FRAME_PREFETCH", "TPUDL_FRAME_PREFETCH_DEPTH",
                "TPUDL_FRAME_PREPARE_WORKERS", "TPUDL_FRAME_FUSE_STEPS",
                "TPUDL_FRAME_DISPATCH_DEPTH", "TPUDL_FRAME_DONATE",
                "TPUDL_FRAME_AUTOTUNE", "TPUDL_MESH_FAST_PATH",
                "TPUDL_WIRE_CODEC", "TPUDL_DATA_CACHE_DIR",
                "TPUDL_MESH_MODEL", "TPUDL_DATA_HBM_BUDGET_MB"):
        monkeypatch.delenv(var, raising=False)


@pytest.fixture(scope="module")
def mesh2x4():
    return M.build_mesh(n_data=2, n_model=4)


@pytest.fixture(scope="module")
def lm():
    # heads=4 and 4*dim=64 divide every model-axis size under test
    return TinyCausalLM(vocab=32, dim=16, heads=4, layers=2, max_len=64)


@pytest.fixture(scope="module")
def lm_params(lm):
    return lm.init(0)


@pytest.fixture(scope="module")
def job_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_job", os.path.join(REPO, "tools", "validate_job.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# satellite: TPUDL_MESH_MODEL knob + idle-device rail
# ---------------------------------------------------------------------------

class TestMeshKnob:
    def test_model_axis_size_env(self, monkeypatch):
        monkeypatch.delenv("TPUDL_MESH_MODEL", raising=False)
        assert M.model_axis_size() == 1
        monkeypatch.setenv("TPUDL_MESH_MODEL", "2")
        assert M.model_axis_size() == 2
        monkeypatch.setenv("TPUDL_MESH_MODEL", "garbage")
        assert M.model_axis_size() == 1  # invalid never crashes a build
        monkeypatch.setenv("TPUDL_MESH_MODEL", "0")
        assert M.model_axis_size() == 1  # floor 1

    def test_build_mesh_defaults_fold_model_axis(self, monkeypatch):
        monkeypatch.setenv("TPUDL_MESH_MODEL", "2")
        m = M.build_mesh()
        assert dict(m.shape) == {"data": 4, "model": 2}
        monkeypatch.delenv("TPUDL_MESH_MODEL")
        assert dict(M.build_mesh().shape) == {"data": 8, "model": 1}

    def test_idle_devices_warn_once_and_gauge(self, monkeypatch):
        monkeypatch.setattr(M, "_warned_idle_devices", False)
        with pytest.warns(RuntimeWarning, match="IDLE"):
            M.build_mesh(n_data=2, n_model=2)
        assert _metric("frame.mesh.idle_devices") == 4
        # once per process: the second undersized build stays silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            M.build_mesh(n_data=2, n_model=2)
        # a full-width grid clears the gauge (it tracks the LAST build)
        M.build_mesh(n_data=4, n_model=2)
        assert _metric("frame.mesh.idle_devices") == 0


# ---------------------------------------------------------------------------
# satellite: param_shardings / shard_params across the grid matrix
# ---------------------------------------------------------------------------

GRIDS = [(8, 1), (4, 2), (2, 4)]


class TestParamShardings:
    @pytest.mark.parametrize("n_data,n_model", GRIDS)
    def test_every_leaf_on_declared_sharding(self, lm, lm_params,
                                             n_data, n_model):
        mesh = M.build_mesh(n_data=n_data, n_model=n_model)
        plan = lm.param_shardings(mesh)
        placed = lm.shard_params(lm_params, mesh)
        flat_p = jax.tree_util.tree_leaves_with_path(placed)
        flat_s = jax.tree.leaves(plan)
        assert len(flat_p) == len(flat_s)
        for (path, leaf), sh in zip(flat_p, flat_s):
            assert leaf.sharding == sh, (path, leaf.sharding, sh)
        # Megatron layout: column-parallel wq splits its OUTPUT dim
        wq = placed["block_0"]["wq"]
        assert wq.addressable_shards[0].data.shape == \
            (lm.dim, lm.dim // n_model)
        # row-parallel w_down splits its INPUT dim
        wd = placed["block_0"]["w_down"]
        assert wd.addressable_shards[0].data.shape == \
            (4 * lm.dim // n_model, lm.dim)
        # embedding/norms replicate
        assert placed["embed"]["table"].sharding.spec == P()

    def test_divisibility_refusal(self):
        lm2 = TinyCausalLM(vocab=16, dim=16, heads=2, layers=1)
        mesh = M.build_mesh(n_data=2, n_model=4)
        with pytest.raises(ValueError, match="divide"):
            lm2.param_shardings(mesh)

    def test_bytes_per_device_shrink(self, lm, lm_params):
        mesh = M.build_mesh(n_data=4, n_model=2)
        plan = lm.param_shardings(mesh)
        rep = M.bytes_per_device(lm_params)
        tp = M.bytes_per_device(lm_params, plan)
        assert tp < rep  # the whole point: each chip holds a slice
        # exact arithmetic: every col/row-parallel matrix + b_up halves
        halved = sum(
            int(np.prod(np.shape(lm_params[f"block_{i}"][k]))) * 4 // 2
            for i in range(lm.layers)
            for k in ("wq", "wk", "wv", "wo", "w_up", "w_down", "b_up"))
        full = sum(
            int(np.prod(np.shape(lm_params[f"block_{i}"][k]))) * 4
            for i in range(lm.layers)
            for k in ("wq", "wk", "wv", "wo", "w_up", "w_down", "b_up"))
        assert rep - tp == full - halved


# ---------------------------------------------------------------------------
# satellite: transfer_batch pass-through for model-resident leaves
# ---------------------------------------------------------------------------

class TestTransferPassThrough:
    def test_mixed_tree_batch_ships_weights_stay(self, mesh4x2):
        w = jax.device_put(np.ones((16, 16), np.float32),
                           NamedSharding(mesh4x2, P(None, "model")))
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        out = M.transfer_batch({"x": x, "w": w}, mesh4x2)
        # the model-sharded leaf is the SAME array object: zero wire
        # bytes, and crucially no host gather of the param shard
        assert out["w"] is w
        assert out["x"].sharding == M.batch_sharding(mesh4x2, ndim=2)
        np.testing.assert_array_equal(np.asarray(out["x"]), x)

    def test_exact_data_resident_leaf_passes_through(self, mesh4x2):
        sh = M.batch_sharding(mesh4x2, ndim=2)
        x = jax.device_put(np.ones((8, 4), np.float32), sh)
        out = M.transfer_batch({"x": x}, mesh4x2)
        assert out["x"] is x

    def test_foreign_mesh_leaf_reships(self, mesh4x2, mesh2x4):
        # model-sharded on ANOTHER mesh: residency must not be assumed
        w = jax.device_put(np.ones((8, 16), np.float32),
                           NamedSharding(mesh2x4, P(None, "model")))
        out = M.transfer_batch({"w": w}, mesh4x2)
        assert out["w"] is not w
        assert out["w"].sharding.mesh == mesh4x2


# ---------------------------------------------------------------------------
# acceptance: TinyCausalLM tensor-parallel generate parity
# ---------------------------------------------------------------------------

class TestGenerateParity:
    @pytest.fixture(scope="class")
    def prompt(self):
        return np.array([[3, 1, 4, 1, 5, 9], [2, 6, 5, 3, 5, 8]],
                        np.int32)

    @pytest.fixture(scope="class")
    def baseline(self, lm, lm_params, prompt):
        greedy = np.asarray(lm.generate(lm_params, prompt, 8))
        sampled = np.asarray(lm.generate(
            lm_params, prompt, 8, temperature=1.0,
            rng=jax.random.PRNGKey(7)))
        return greedy, sampled

    @pytest.mark.parametrize("n_data,n_model", [(4, 2), (2, 4)])
    def test_tp_generate_matches_1d(self, lm, lm_params, prompt,
                                    baseline, n_data, n_model):
        """Token-exact parity: the model-axis all-reduces change only
        float summation ORDER inside each layer, and argmax/categorical
        over the resulting logits picks identical tokens for this
        model/geometry (ints compare bitwise — the strongest parity
        the partitioned program admits)."""
        mesh = M.build_mesh(n_data=n_data, n_model=n_model)
        placed = lm.shard_params(lm_params, mesh)
        got_g = np.asarray(lm.generate(placed, prompt, 8,
                                       mesh=mesh, tp=True))
        np.testing.assert_array_equal(got_g, baseline[0])
        got_s = np.asarray(lm.generate(
            placed, prompt, 8, temperature=1.0,
            rng=jax.random.PRNGKey(7), mesh=mesh, tp=True))
        np.testing.assert_array_equal(got_s, baseline[1])

    def test_gen_program_cache_keys_on_topology(self, lm, mesh4x2):
        lm._gen_jits.clear()
        lm._gen_program(2, 4, 2, 0.0)
        assert len(lm._gen_jits) == 1
        # same geometry, 2-D topology: a DIFFERENT executable
        lm._gen_program(2, 4, 2, 0.0, mesh=mesh4x2, tp=True)
        assert len(lm._gen_jits) == 2
        lm._gen_jits.clear()

    def test_tp_requires_model_axis(self, lm, lm_params):
        with pytest.raises(ValueError, match="model"):
            lm.generate(lm_params, np.ones((1, 4), np.int32), 2,
                        tp=True)


# ---------------------------------------------------------------------------
# acceptance: executor parity matrix — 2-D mesh vs 8x1, fast path armed
# ---------------------------------------------------------------------------

def _megatron_pair(mesh):
    """A col-parallel + row-parallel matmul pair closed over
    model-sharded weights — the executor-level shape of a TP layer."""
    rng = np.random.default_rng(11)
    w1 = (rng.standard_normal((12, 32)) * 0.1).astype(np.float32)
    w2 = (rng.standard_normal((32, 6)) * 0.1).astype(np.float32)
    if mesh is not None and mesh.shape["model"] > 1:
        d1 = jax.device_put(w1, NamedSharding(mesh, P(None, "model")))
        d2 = jax.device_put(w2, NamedSharding(mesh, P("model", None)))
    else:
        d1, d2 = jax.device_put(w1), jax.device_put(w2)
    fn = jax.jit(lambda b: jnp.tanh(b @ d1) @ d2)
    return fn, w1, w2


class TestExecutorParityMatrix:
    # documented tolerance: the row-parallel matmul becomes a partial
    # matmul + model-axis all-reduce, reassociating the K-dim float
    # reduction (DATA.md caveat class). Everything else is bitwise.
    RTOL, ATOL = 1e-5, 1e-6

    @pytest.mark.parametrize("fuse", [1, 4])
    @pytest.mark.parametrize("donate", [False, True])
    @pytest.mark.parametrize("depth", [1, 4])
    def test_4x2_matches_host_math(self, monkeypatch, depth, donate,
                                   fuse):
        _clean_env(monkeypatch)
        mesh = M.build_mesh(n_data=4, n_model=2)
        fn, w1, w2 = _megatron_pair(mesh)
        x = np.random.default_rng(5).standard_normal(
            (64, 12)).astype(np.float32)
        ref = np.tanh(x @ w1) @ w2
        out = Frame({"x": x}).map_batches(
            fn, ["x"], ["y"], batch_size=16, mesh=mesh,
            dispatch_depth=depth, donate=donate, fuse_steps=fuse,
            autotune=False)
        got = np.stack(list(out["y"]))
        np.testing.assert_allclose(got, ref, rtol=self.RTOL,
                                   atol=self.ATOL)
        rep = obs.last_pipeline_report()
        assert rep["mesh"] == {"data": 4, "model": 2}
        assert rep["fuse_steps"] == fuse

    def test_2x4_matches_8x1(self, monkeypatch, mesh8, mesh2x4):
        _clean_env(monkeypatch)
        x = np.random.default_rng(6).standard_normal(
            (32, 12)).astype(np.float32)
        outs = {}
        for mesh in (mesh8, mesh2x4):
            fn, _, _ = _megatron_pair(mesh)
            out = Frame({"x": x}).map_batches(
                fn, ["x"], ["y"], batch_size=16, mesh=mesh,
                autotune=False)
            outs[dict(mesh.shape)["model"]] = np.stack(list(out["y"]))
        np.testing.assert_allclose(outs[4], outs[1], rtol=self.RTOL,
                                   atol=self.ATOL)

    def test_featurizer_across_grids(self, monkeypatch, mesh8,
                                     mesh4x2):
        """DeepImageFeaturizer replicates its params over the mesh, so
        a 2-D grid runs it pure-data-parallel over the ``data`` axis.
        The data-axis WIDTH differs between grids (8 vs 4), so XLA
        tiles the per-row conv reductions differently — measured
        ~3.5e-4 relative, the same f32-reassociation class the 1-D
        mesh parity test documents; the pin is that tolerance. (The
        bitwise leg of the matrix is generate's integer tokens.)"""
        _clean_env(monkeypatch)
        from tpudl.image import imageIO
        from tpudl.ml import DeepImageFeaturizer

        rng = np.random.default_rng(3)
        structs = [imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(32, 32, 3), dtype=np.uint8))
            for _ in range(8)]
        frame = Frame({"image": structs})
        feats = {}
        for mesh in (mesh8, mesh4x2):
            f = DeepImageFeaturizer(inputCol="image", outputCol="f",
                                    modelName="ResNet50", batchSize=8,
                                    mesh=mesh)
            feats[dict(mesh.shape)["model"]] = np.stack(
                list(f.transform(frame)["f"]))
        np.testing.assert_allclose(feats[2], feats[1], rtol=1e-3,
                                   atol=1e-5)
        assert obs.last_pipeline_report()["mesh"] == \
            {"data": 4, "model": 2}


# ---------------------------------------------------------------------------
# acceptance: HLO collective pin — the identity rail of the TP program
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
# the Megatron contract: model-axis sums may be all-reduce (or the
# reduce-scatter spelling); NOTHING may gather a param shard
ALLOWED = {"all-reduce", "reduce-scatter"}


def _collective_lines(hlo: str) -> dict[str, list[str]]:
    found: dict[str, list[str]] = {}
    for line in hlo.splitlines():
        for op in COLLECTIVES:
            if re.search(rf"\b{op}(?:-start|-done)?\(", line):
                found.setdefault(op, []).append(line.strip())
    return found


def _tp_generate_hlo(lm, mesh) -> str:
    fn = lm._gen_program(2, 4, 2, 0.0, mesh=mesh, tp=True)
    plan = lm.param_shardings(mesh)
    p_avals = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(
            np.shape(s), np.asarray(s).dtype, sharding=sh),
        lm.init(0), plan)
    key = jax.random.PRNGKey(0)
    avals = (p_avals,
             jax.ShapeDtypeStruct((2, 4), jnp.int32),
             jax.ShapeDtypeStruct(jnp.shape(key),
                                  jnp.asarray(key).dtype),
             jax.ShapeDtypeStruct((), jnp.int32))
    return fn.lower(*avals).compile().as_text()


class TestHLOPin:
    def test_collective_set_pinned(self, lm, mesh4x2):
        found = _collective_lines(_tp_generate_hlo(lm, mesh4x2))
        for op, lines in sorted(found.items()):
            assert op in ALLOWED, (
                f"forbidden collective {op!r} in the TP generate "
                f"program ({len(lines)} site(s)) — a param shard is "
                f"being gathered; first site:\n  {lines[0][:200]}")
        # sensitivity control: the pin is ALIVE — the partitioned
        # program really does reduce over the model axis
        assert found.get("all-reduce"), (
            "no all-reduce in the TP program: GSPMD did not partition "
            "the matmuls (shardings lost?) — the pin would never fire")
        assert "all-gather" not in found

    def test_pin_catches_a_gather(self, mesh4x2):
        """The pin's own detector fires on a program that DOES gather:
        re-replicating a model-sharded operand forces an all-gather —
        exactly the op the TP generate program must never contain."""
        @jax.jit
        def f(w):
            # the multiply keeps XLA from eliding the reshard as an
            # input-layout change — the gather must be an instruction
            return jax.lax.with_sharding_constraint(
                w * 2.0, NamedSharding(mesh4x2, P()))

        hlo = f.lower(
            jax.ShapeDtypeStruct(
                (16, 16), np.float32,
                sharding=NamedSharding(mesh4x2, P("model", None)))
        ).compile().as_text()
        found = _collective_lines(hlo)
        assert found.get("all-gather"), sorted(found)


# ---------------------------------------------------------------------------
# acceptance: capacity proof — params that only fit model-sharded
# ---------------------------------------------------------------------------

class TestCapacityProof:
    def test_budget_admits_4x2_refuses_8x1(self, monkeypatch, lm,
                                           lm_params):
        _clean_env(monkeypatch)
        mesh42 = M.build_mesh(n_data=4, n_model=2)
        mesh81 = M.build_mesh(n_data=8, n_model=1)
        prompt = np.array([[7, 2, 9]], np.int32)
        want = np.asarray(lm.generate(lm_params, prompt, 4))
        plan42 = lm.param_shardings(mesh42)
        shard_b = M.bytes_per_device(lm_params, plan42)
        full_b = M.bytes_per_device(lm_params)
        assert shard_b < full_b
        # a budget the sharded layout fits and the replicated one busts
        budget_mb = (shard_b + full_b) / 2 / 2**20
        monkeypatch.setenv("TPUDL_DATA_HBM_BUDGET_MB", f"{budget_mb:.6f}")
        with pytest.raises(DeviceOOM, match="model"):
            M.replicate(lm_params, mesh81)
        with pytest.raises(DeviceOOM, match="model"):
            # a 1-wide model axis shards NOTHING: same typed refusal
            lm.shard_params(lm_params, mesh81)
        placed = lm.shard_params(lm_params, mesh42)  # fits
        got = np.asarray(lm.generate(placed, prompt, 4,
                                     mesh=mesh42, tp=True))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# obs: roofline `collective` component + run-line topology
# ---------------------------------------------------------------------------

def _report(**over) -> dict:
    rep = {
        "run_id": "fixture-2d", "wall_seconds": 2.3, "finished": True,
        "stage_seconds": {"prepare": 1.5, "infeed_wait": 0.12,
                          "dispatch": 1.9, "d2h": 0.1},
        "stage_calls": {"dispatch": 4, "prepare": 4,
                        "bytes_prepared": int(1024 * 0.0685 * 2**20)},
        "rows": 1024, "rows_done": 1024,
        "batch_size": 256, "fuse_steps": 1,
        "prefetch_depth": 2, "prepare_workers": 2,
        "wire_codec": "u8", "executor": "pipelined",
        "mesh": {"data": 4, "model": 2},
    }
    rep.update(over)
    return rep


class TestRooflineCollective:
    def test_collective_carved_from_dispatch(self):
        from tpudl.obs import roofline

        rr = roofline.analyze(_report(), h2d_mbps=140.0,
                              device_ms_per_dispatch=34.26,
                              collective_ms_per_dispatch=50.0,
                              publish=False)
        assert rr.collective_s == pytest.approx(4 * 50.0 / 1e3)
        assert rr.gap_attribution["collective"] > 0
        base = roofline.analyze(_report(), h2d_mbps=140.0,
                                device_ms_per_dispatch=34.26,
                                publish=False)
        # the component is CARVED OUT of dispatch, not added on top
        assert rr.gap_attribution["dispatch"] < \
            base.gap_attribution["dispatch"]

    def test_model_axis_1_ignores_collective_time(self):
        from tpudl.obs import roofline

        rr = roofline.analyze(_report(mesh={"data": 8, "model": 1}),
                              h2d_mbps=140.0,
                              device_ms_per_dispatch=34.26,
                              collective_ms_per_dispatch=50.0,
                              publish=False)
        assert not rr.collective_s
        assert rr.gap_attribution.get("collective", 0) == 0

    def test_gauge_published(self):
        from tpudl.obs import roofline

        roofline.analyze(_report(), h2d_mbps=140.0,
                         device_ms_per_dispatch=34.26,
                         collective_ms_per_dispatch=50.0)
        assert _metric("obs.roofline.collective_s") == \
            pytest.approx(0.2)


class TestObsTopology:
    def test_run_entry_carries_mesh(self):
        from tpudl.obs import live

        entry = live._run_entry(_report())
        assert entry["config"]["mesh"] == {"data": 4, "model": 2}

    def test_render_shows_grid(self):
        from tpudl.obs import live

        status = {"pid": 1, "alive": True, "ts": 0.0, "interval_s": 1.0,
                  "argv": ["job.py"], "host": "h", "runs": [
                      live._run_entry(_report())]}
        out = live.render([status], now=1.0)
        assert "mesh=4x2" in out

    def test_model_axis_gauge_from_executor_run(self, monkeypatch,
                                                mesh4x2):
        _clean_env(monkeypatch)
        fn = jax.jit(lambda b: b * 2.0)
        out = Frame({"x": np.ones((16, 3), np.float32)}).map_batches(
            fn, ["x"], ["y"], batch_size=8, mesh=mesh4x2,
            autotune=False)
        np.stack(list(out["y"]))
        assert _metric("frame.mesh.model_axis") == 2


# ---------------------------------------------------------------------------
# satellite: validate_job resume-topology
# ---------------------------------------------------------------------------

class TestResumeTopology:
    def test_parse_mesh_arg(self, job_validator):
        assert job_validator.parse_mesh_arg("data=4,model=2") == \
            {"data": 4, "model": 2}
        assert job_validator.parse_mesh_arg("") == {}
        with pytest.raises(ValueError):
            job_validator.parse_mesh_arg("data=four")

    def _workdir(self, tmp_path, mesh):
        wd = tmp_path / "job"
        wd.mkdir(exist_ok=True)
        (wd / "job-manifest.json").write_text(json.dumps(
            {"mesh": mesh}))
        return str(wd)

    def test_2d_manifest_refused_on_1d_mesh(self, tmp_path,
                                            job_validator):
        wd = self._workdir(tmp_path, {"data": 4, "model": 2})
        errs = job_validator.check_resume_topology(wd, {"data": 8})
        assert len(errs) == 1 and "different grid" in errs[0]
        assert job_validator.check_resume_topology(
            wd, "data=4,model=2") == []

    def test_size_1_axes_are_topology_neutral(self, tmp_path,
                                              job_validator):
        wd = self._workdir(tmp_path, {"data": 8, "model": 1})
        assert job_validator.check_resume_topology(wd, {"data": 8}) == []

    def test_pre_topology_manifest_passes(self, tmp_path,
                                          job_validator):
        wd = self._workdir(tmp_path, None)
        assert job_validator.check_resume_topology(
            wd, {"data": 4, "model": 2}) == []


# ---------------------------------------------------------------------------
# train/zoo plumbing: HorovodRunner grid fold + Trainer TP fit
# ---------------------------------------------------------------------------

class TestRunner2D:
    def test_build_mesh_folds_model_axis(self, monkeypatch):
        from tpudl.train.runner import HorovodRunner

        monkeypatch.setenv("TPUDL_MESH_MODEL", "2")
        r = HorovodRunner(np=8)
        assert dict(r._build_mesh().shape) == {"data": 4, "model": 2}

    def test_non_dividing_np_refused(self, monkeypatch):
        from tpudl.train.runner import HorovodRunner

        monkeypatch.setenv("TPUDL_MESH_MODEL", "3")
        with pytest.raises(ValueError, match="TPUDL_MESH_MODEL"):
            HorovodRunner(np=8)._build_mesh()

    def test_trainer_fit_with_model_sharded_params(self, monkeypatch,
                                                   mesh4x2):
        optax = pytest.importorskip("optax")
        from tpudl.train import Trainer

        _clean_env(monkeypatch)
        rng = np.random.default_rng(0)
        params = {"w": (rng.standard_normal((12, 8)) * 0.1).astype(
            np.float32)}
        plan = {"w": NamedSharding(mesh4x2, P(None, "model"))}
        x = rng.standard_normal((16, 12)).astype(np.float32)
        y = rng.standard_normal((16, 8)).astype(np.float32)

        def loss_fn(p, xb, yb):
            return jnp.mean((xb @ p["w"] - yb) ** 2)

        t = Trainer(loss_fn, optax.sgd(0.1), mesh=mesh4x2,
                    param_shardings=plan, log_every=1)
        p1, _, hist = t.fit(params, lambda step: (x, y), 20)
        assert hist[-1]["loss"] < hist[0]["loss"]
        # params lived (and remain) model-sharded for the whole fit
        assert p1["w"].sharding.spec == P(None, "model")
        assert p1["w"].addressable_shards[0].data.shape == (12, 4)
