"""tpudl.compile — the persistent compilation cache and shape buckets.

Covers the compilation-cache module (placement by
JAX_COMPILATION_CACHE_DIR, fixed in-checkout default, loud failure),
warm starts through that cache (two fresh processes share one cache
directory: the second compiles nothing at ten entry points and matches
the first bitwise; damaged entries recompile), the bucket ladder, the
executor wiring (bucketed-vs-exact bitwise parity across
depth×donate×fuse×mesh8), the traceck-armed zero-retrace ragged sweep,
LM prompt bucketing, the roofline cold-start attribution and the
obs-top compile line.
"""

import importlib.util
import json
import os
import random
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax

from tpudl import obs
from tpudl.compile import buckets as bk
from tpudl.compile import cache as ccache
from tpudl.frame import Frame
from tpudl.obs import metrics as obs_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def registry():
    obs_metrics.get_registry().reset()
    yield
    obs_metrics.get_registry().reset()


def _metric(name):
    return obs.snapshot().get(name, {}).get("value")


# ---------------------------------------------------------------------------
# satellite: enable_compilation_cache — placement, loudness
# ---------------------------------------------------------------------------

class TestCompilationCache:
    """Placement is decided outside the code: JAX_COMPILATION_CACHE_DIR
    when set (jax reads it itself — nothing is set here), else ONE
    fixed in-checkout path."""

    def test_jax_env_wins_and_config_is_untouched(self, tmp_path,
                                                  monkeypatch):
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        before = jax.config.jax_compilation_cache_dir
        assert ccache.enable_compilation_cache() == placed
        # an explicit path does not override the operator's placement
        assert ccache.enable_compilation_cache(
            str(tmp_path / "arg")) == placed
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "arg").exists()

    def test_unset_env_uses_fixed_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fixed = os.path.join(REPO, ".jax_cache")
        assert ccache.DEFAULT_CACHE_DIR == fixed
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert ccache.enable_compilation_cache() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_explicit_path_when_env_unset(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            got = ccache.enable_compilation_cache(str(tmp_path / "arg"))
            assert got == str(tmp_path / "arg")
            assert os.path.isdir(got)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_failure_is_loud_warn_once_plus_counter(self, tmp_path,
                                                    monkeypatch):
        """The old bare `except Exception: return None` swallowed a
        read-only fs silently — now: one RuntimeWarning per process,
        a compile.cache_disabled count per occurrence."""
        blocker = tmp_path / "blocker"
        blocker.write_text("a file where the cache dir path needs a "
                           "directory")
        bad = str(blocker / "sub")  # makedirs → NotADirectoryError
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        ccache._reset_warned_for_tests()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert ccache.enable_compilation_cache(bad) is None
            assert ccache.enable_compilation_cache(bad) is None
        loud = [w for w in rec if "compilation cache DISABLED"
                in str(w.message)]
        assert len(loud) == 1  # warn-once
        assert _metric("compile.cache_disabled") == 2  # count-always


# ---------------------------------------------------------------------------
# warm starts through the persistent cache: two fresh processes, one cache
# ---------------------------------------------------------------------------

# One script, run in two fresh processes over one cache directory. Per
# entry point it records the jax.monitoring cache hit/miss events (a
# miss is a compile written to the cache) and a digest of the outputs.
# Every entry point compiles at least one program no earlier one did,
# so a warm process must show a hit at each.
_WARM_SCRIPT = r"""
import hashlib, json, os, sys
# two cores at most: these processes share the machine with the test
# workers, some of which time themselves
if hasattr(os, "sched_setaffinity"):
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.monitoring
import jax.numpy as jnp
from tpudl import compile as C
from tpudl.compile import cache as ccache

cache_dir, out_path, workdir = sys.argv[1], sys.argv[2], sys.argv[3]
only = sys.argv[4].split(",") if len(sys.argv) > 4 else None
assert C.enable_compilation_cache(cache_dir) == cache_dir

events = {}
current = [None]


def on_event(event, **_kw):
    if current[0] is None:
        return
    rec = events.setdefault(current[0], {"hits": 0, "misses": 0})
    if event == ccache._CACHE_HIT:
        rec["hits"] += 1
    elif event == ccache._CACHE_MISS:
        rec["misses"] += 1


jax.monitoring.register_event_listener(on_event)


def digest(*arrays):
    h = hashlib.sha256()
    for a in jax.tree.leaves(arrays):
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def map_rows(frame, fn, **kw):
    out = frame.map_batches(fn, ["x"], ["y"], autotune=False, **kw)
    return np.stack(list(out["y"]))


def frame_exact():
    from tpudl.frame import Frame

    x = np.random.default_rng(1).standard_normal((40, 6)).astype(np.float32)
    fn = jax.jit(lambda b: jnp.tanh(b) * 2.0 + 1.0)
    return digest(map_rows(Frame({"x": x}), fn, batch_size=16,
                           buckets=False))


def frame_bucketed():
    from tpudl.frame import Frame

    fn = jax.jit(lambda b: jnp.sin(b) * 3.0)
    outs = []
    for n in (33, 40, 45, 50, 57, 63):
        x = np.random.default_rng(n).standard_normal((n, 5)).astype(
            np.float32)
        outs.append(map_rows(Frame({"x": x}), fn, batch_size=64,
                             buckets="pow2"))
    return digest(*outs)


def frame_mesh8():
    from tpudl import mesh as M
    from tpudl.frame import Frame

    mesh = M.build_mesh()
    assert mesh.devices.size == 8, mesh
    x = np.random.default_rng(2).standard_normal((48, 7)).astype(np.float32)
    fn = jax.jit(lambda b: b * b - 0.5)
    return digest(map_rows(Frame({"x": x}), fn, batch_size=16, mesh=mesh))


def _lm():
    from tpudl.zoo.transformer import TinyCausalLM

    lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2, max_len=64)
    return lm, lm.init(0)


def lm_generate():
    lm, params = _lm()
    prompt = np.random.default_rng(3).integers(1, 64, (2, 9)).astype(
        np.int32)
    return digest(lm.generate(params, prompt, 6))


def lm_generate_buckets():
    lm, params = _lm()
    rng = np.random.default_rng(4)
    outs = [lm.generate(params, rng.integers(1, 64, (2, n)).astype(
        np.int32), 5, prompt_buckets="pow2") for n in (10, 11, 13)]
    return digest(*outs)


def lm_generate_tp():
    from tpudl import mesh as M

    lm, params = _lm()
    mesh = M.build_mesh(n_data=4, n_model=2)
    placed = lm.shard_params(params, mesh)
    prompt = np.random.default_rng(5).integers(1, 64, (1, 7)).astype(
        np.int32)
    return digest(lm.generate(placed, prompt, 4, mesh=mesh, tp=True))


def serve_request():
    from tpudl.serve import ModelRegistry, RequestQueue, Server

    lm, params = _lm()
    reg = ModelRegistry()
    reg.add_model("default", lm, params, slots=2, cache_len=32)
    srv = Server(reg, RequestQueue(cap=4))
    prompt = np.random.default_rng(6).integers(1, 64, 5).astype(np.int32)
    req = srv.submit(prompt, 4)
    srv._stop.set()  # deterministic synchronous drain
    srv.run()
    return digest(req.result(timeout=60))


def image_warmup():
    from tpudl.frame import Frame
    from tpudl.image import imageIO
    from tpudl.ml import TFImageTransformer

    rng = np.random.default_rng(7)
    frame = Frame({"image": [imageIO.imageArrayToStruct(
        rng.integers(0, 256, (24, 24, 3), dtype=np.uint8),
        origin=f"img{i}") for i in range(4)]})
    g = lambda x: jnp.tanh(x.reshape(x.shape[0], -1)  # noqa: E731
                           @ jnp.ones((24 * 24 * 3, 5)) * 1e-3)
    t = TFImageTransformer(inputCol="image", outputCol="y", graph=g,
                           batchSize=4)
    t.warmup(24, 24)
    return digest(np.stack(list(t.transform(frame)["y"])))


def _toy(width):
    rng = np.random.default_rng(width)
    X = rng.normal(size=(64, width)).astype(np.float32)
    y = (X @ rng.normal(size=(width, 1)).astype(np.float32)) + 0.1

    def data_fn(step, batch=16):
        i = (step * batch) % (len(X) - batch + 1)
        return X[i:i + batch], y[i:i + batch]

    def loss_fn(p, x, t):
        return jnp.mean((x @ p["w"] + p["b"] - t) ** 2)

    params = {"w": jnp.zeros((width, 1)), "b": jnp.zeros(())}
    return data_fn, loss_fn, params


def trainer_fit():
    import optax
    from tpudl.train import Trainer

    data_fn, loss_fn, params = _toy(4)
    params, _, _ = Trainer(loss_fn, optax.sgd(0.05)).fit(params, data_fn, 3)
    return digest(params)


def job_resume():
    import optax
    from tpudl.jobs import JobPreempted, JobRuntime, JobSpec
    from tpudl.train import Trainer

    data_fn, loss_fn, params = _toy(6)
    spec = JobSpec("fit", workdir, material={"model": "toy6"},
                   save_every=2)

    def killed(ctx):
        def data(step):
            if step >= 3:
                ctx.request_stop()
            return data_fn(step)

        t = Trainer(loss_fn, optax.adam(0.05),
                    checkpoint_dir=ctx.checkpoint_dir, save_every=2)
        return t.fit(params, data, 6, stop=ctx.stop_requested)

    try:
        JobRuntime(spec, install_signals=False).run(killed)
        raise AssertionError("the first attempt was not preempted")
    except JobPreempted:
        pass
    out = JobRuntime(spec, install_signals=False).run_fit(
        Trainer(loss_fn, optax.adam(0.05)), params, data_fn, 6)
    return digest(out[0])


ENTRIES = [frame_exact, frame_bucketed, frame_mesh8, lm_generate,
           lm_generate_buckets, lm_generate_tp, serve_request,
           image_warmup, trainer_fit, job_resume]
result = {}
for fn in ENTRIES:
    if only is not None and fn.__name__ not in only:
        continue
    current[0] = fn.__name__
    d = fn()
    current[0] = None
    result[fn.__name__] = dict(events.get(fn.__name__,
                                          {"hits": 0, "misses": 0}),
                               digest=d)
with open(out_path, "w") as f:
    json.dump(result, f)
"""

WARM_ENTRIES = ("frame_exact", "frame_bucketed", "frame_mesh8",
                "lm_generate", "lm_generate_buckets", "lm_generate_tp",
                "serve_request", "image_warmup", "trainer_fit",
                "job_resume")


def _warm_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    # the cache directory is the script's argument, never the caller's
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _run_warm_script(root, cache, tag, only=None):
    """One fresh process over ``cache``; returns ({entry: {hits,
    misses, digest}}, stderr)."""
    script = os.path.join(root, "two_starts.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(_WARM_SCRIPT)
    out = os.path.join(root, f"{tag}.json")
    argv = [sys.executable, script, cache, out,
            os.path.join(root, f"job-{tag}")]
    if only:
        argv.append(",".join(only))
    r = subprocess.run(argv, capture_output=True, text=True,
                       env=_warm_env(), timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    with open(out) as f:
        return json.load(f), r.stderr


@pytest.fixture(scope="module")
def warm_runs(tmp_path_factory):
    """(cold, warm): the same script in two fresh processes sharing one
    cache directory, the first against an empty cache."""
    root = str(tmp_path_factory.mktemp("two_starts"))
    cache = os.path.join(root, "cache")
    cold, _ = _run_warm_script(root, cache, "cold")
    warm, _ = _run_warm_script(root, cache, "warm")
    return cold, warm


@pytest.mark.parametrize("entry", WARM_ENTRIES)
class TestWarmStartThroughCache:
    """A fresh process that shares the cache directory compiles
    nothing at any of these entry points, and computes the same bits."""

    def test_warm_process_hits_and_never_misses(self, warm_runs, entry):
        cold, warm = warm_runs
        assert cold[entry]["misses"] >= 1, cold[entry]  # it compiled
        assert warm[entry]["misses"] == 0, warm[entry]
        assert warm[entry]["hits"] >= 1, warm[entry]

    def test_outputs_bitwise_equal_to_cold(self, warm_runs, entry):
        cold, warm = warm_runs
        assert warm[entry]["digest"] == cold[entry]["digest"]


def _damage(path, how, rng):
    with open(path, "rb") as f:
        blob = f.read()
    if how == "emptied":
        blob = b""
    elif how == "truncated":
        blob = blob[:len(blob) // 2]
    else:
        blob = bytes(rng.getrandbits(8) for _ in range(len(blob)))
    with open(path, "wb") as f:
        f.write(blob)


@pytest.mark.parametrize("how", ["emptied", "truncated", "random_bytes"])
def test_damaged_cache_entry_recompiles_and_matches(tmp_path, how):
    """Every entry a cold process wrote is damaged one way; the next
    process warns, recompiles, does not raise, and returns the cold
    outputs bitwise. The cache key includes the directory, so each
    case makes its own cold cache in place rather than copying one."""
    only = ("frame_bucketed", "lm_generate", "trainer_fit")
    cache = str(tmp_path / "cache")
    cold, _ = _run_warm_script(str(tmp_path), cache, "cold", only)
    rng = random.Random(0)
    names = sorted(os.listdir(cache))
    assert names
    for name in names:
        _damage(os.path.join(cache, name), how, rng)
    again, err = _run_warm_script(str(tmp_path), cache, "again", only)
    assert "Error reading persistent compilation cache entry" in err
    for entry in only:
        assert again[entry]["hits"] == 0, again[entry]
        assert again[entry]["misses"] >= 1, again[entry]
        assert again[entry]["digest"] == cold[entry]["digest"], entry


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

class TestBucketLadder:
    def test_pow2ish_picks(self):
        lad = bk.BucketLadder("pow2ish")
        assert [lad.pick(n) for n in (1, 2, 3, 4, 5, 6, 7, 8, 9, 13,
                                      33, 49)] == \
            [1, 2, 3, 4, 6, 6, 8, 8, 12, 16, 48, 64]
        assert lad.rungs_up_to(16) == [1, 2, 3, 4, 6, 8, 12, 16]

    def test_pow2_picks(self):
        lad = bk.BucketLadder("pow2")
        assert [lad.pick(n) for n in (1, 3, 5, 33, 64)] == \
            [1, 4, 8, 64, 64]

    def test_explicit_rungs_exact_past_top(self):
        lad = bk.resolve_ladder("8,16,32")
        assert lad.pick(5) == 8 and lad.pick(17) == 32
        assert lad.pick(100) == 100  # past the declared top: exact
        assert lad.is_rung(16) and not lad.is_rung(17)

    def test_resolution_rules(self, monkeypatch):
        monkeypatch.delenv("TPUDL_COMPILE_BUCKETS", raising=False)
        assert bk.resolve_ladder(None) is None  # unset env = off
        monkeypatch.setenv("TPUDL_COMPILE_BUCKETS", "pow2")
        assert bk.resolve_ladder(None).spec == "pow2"
        assert bk.resolve_ladder(False) is None  # kwarg beats env
        assert bk.resolve_ladder(True).spec == "pow2ish"
        monkeypatch.setenv("TPUDL_COMPILE_BUCKETS", "off")
        assert bk.resolve_ladder(None) is None
        with pytest.raises(ValueError):
            bk.resolve_ladder("not-a-ladder")

    def test_pad_to_repeats_row0_and_strip_roundtrip(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        p = bk.pad_to(a, 5)
        assert p.shape == (5, 4)
        np.testing.assert_array_equal(p[:3], a)
        np.testing.assert_array_equal(p[3], a[0])
        np.testing.assert_array_equal(p[4], a[0])
        assert bk.pad_to(a, 3) is a  # already at target: untouched


# ---------------------------------------------------------------------------
# executor wiring: bitwise parity matrix
# ---------------------------------------------------------------------------

def _run(frame, fn, **kw):
    out = frame.map_batches(fn, ["x"], ["y"], autotune=False, **kw)
    return np.stack(list(out["y"]))


class TestExecutorBuckets:
    @pytest.mark.parametrize("depth", [1, 4])
    @pytest.mark.parametrize("donate", [False, True])
    @pytest.mark.parametrize("fuse", [1, 4])
    def test_bucketed_vs_exact_bitwise_single_chip(self, depth, donate,
                                                   fuse):
        rng = np.random.default_rng(0)
        frame = Frame({"x": rng.standard_normal((70, 6)).astype(
            np.float32)})
        fn = jax.jit(lambda b: jax.numpy.tanh(b) * 2.0)
        kw = dict(batch_size=16, dispatch_depth=depth, donate=donate,
                  fuse_steps=fuse)
        exact = _run(frame, fn, buckets=False, **kw)
        bucketed = _run(frame, fn, buckets="pow2ish", **kw)
        np.testing.assert_array_equal(exact, bucketed)
        rep = obs.last_pipeline_report()
        assert rep["buckets"] == "pow2ish"
        # ragged tail: 70 % 16 = 6 rows → rung 6 (pow2ish) = no pad;
        # force a pad with pow2 to assert the counter
        obs_metrics.get_registry().reset()
        bucketed2 = _run(frame, fn, buckets="pow2", **kw)
        np.testing.assert_array_equal(exact, bucketed2)
        assert _metric("compile.bucket_pad_rows") == 2  # 6 → 8

    @pytest.mark.parametrize("donate", [False, True])
    @pytest.mark.parametrize("fuse", [1, 4])
    def test_bucketed_vs_exact_bitwise_mesh8(self, mesh8, donate, fuse):
        rng = np.random.default_rng(1)
        frame = Frame({"x": rng.standard_normal((70, 6)).astype(
            np.float32)})
        fn = jax.jit(lambda b: jax.numpy.tanh(b) * 2.0)
        kw = dict(batch_size=16, dispatch_depth=4, donate=donate,
                  fuse_steps=fuse, mesh=mesh8)
        exact = _run(frame, fn, buckets=False, **kw)
        bucketed = _run(frame, fn, buckets="pow2ish", **kw)
        np.testing.assert_array_equal(exact, bucketed)

    def test_unbucketed_batch_size_drops_fusion(self):
        """batch_size 20 is no pow2 rung: every full batch pads, so a
        fused (m, B, ...) stack would interleave pad rows — fusion must
        fall back to per-batch dispatch (the mesh-fusion rule)."""
        frame = Frame({"x": np.ones((80, 4), np.float32)})
        fn = jax.jit(lambda b: b + 1)
        _run(frame, fn, batch_size=20, fuse_steps=4, buckets="pow2")
        rep = obs.last_pipeline_report()
        assert rep["fuse_steps"] == 1
        assert (rep.get("stage_calls") or {}).get("bucket_pad_rows")

    def test_rung_batch_size_keeps_fusion(self):
        frame = Frame({"x": np.ones((64, 4), np.float32)})
        fn = jax.jit(lambda b: b + 1)
        _run(frame, fn, batch_size=16, fuse_steps=4, buckets="pow2")
        rep = obs.last_pipeline_report()
        assert rep["fuse_steps"] == 4

    def test_host_fn_and_kill_switch_never_bucket(self, monkeypatch):
        frame = Frame({"x": np.ones((10, 4), np.float32)})
        _run(frame, lambda b: b + 1, batch_size=8, buckets="pow2")
        assert obs.last_pipeline_report()["buckets"] == "off"
        monkeypatch.setenv("TPUDL_FRAME_PREFETCH", "0")
        _run(frame, jax.jit(lambda b: b + 1), batch_size=8,
             buckets="pow2")
        assert obs.last_pipeline_report()["buckets"] == "off"


# ---------------------------------------------------------------------------
# acceptance: traceck-armed ragged sweep — ZERO retraces through the shim
# ---------------------------------------------------------------------------

_SWEEP_SCRIPT = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tpudl.testing import traceck
from tpudl.frame import Frame

fn = jax.jit(lambda b: jax.numpy.tanh(b) * 2.0)
sizes = [33, 40, 45, 50, 57, 63]

def run(n, buckets):
    rng = np.random.default_rng(n)
    frame = Frame({"x": rng.standard_normal((n, 5)).astype(np.float32)})
    out = frame.map_batches(fn, ["x"], ["y"], batch_size=64,
                            autotune=False, buckets=buckets)
    return np.stack(list(out["y"]))

# serial unbucketed baseline outputs (each size traces its own shape)
baseline = {n: run(n, False) for n in sizes}
# warm the ONE bucket program (rung 64) ...
traceck.reset()
run(64, "pow2")
warm_counts = traceck.counts()
# ... then the ragged sweep must be trace-FREE: 6 distinct batch sizes,
# zero traces, zero retraces, bitwise-identical to the serial baseline
traceck.reset()
parity = True
for n in sizes:
    parity = parity and bool(np.array_equal(baseline[n], run(n, "pow2")))
counts = traceck.counts()
json.dump({
    "warm_traces": sum(warm_counts.values()),
    "sweep_traces": sum(counts.values()),
    "sweep_retraces": sum(max(0, v - 1) for v in counts.values()),
    "distinct_sizes": len(sizes),
    "parity": parity,
}, open(sys.argv[1], "w"))
"""


class TestZeroRetraceSweep:
    def test_ragged_sweep_zero_retraces_bitwise(self, tmp_path):
        """THE ISSUE-15 acceptance: >= 6 distinct ragged batch sizes
        through the armed traceck shim perform ZERO (re)traces once the
        one bucket program is warm, with outputs bitwise-identical to
        the unbucketed serial baseline."""
        out_path = str(tmp_path / "sweep.json")
        script = str(tmp_path / "sweep.py")
        open(script, "w").write(_SWEEP_SCRIPT)
        env = dict(os.environ)
        env["TPUDL_TRACECK"] = "1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, script, out_path],
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        got = json.load(open(out_path))
        assert got["distinct_sizes"] >= 6
        assert got["parity"] is True
        assert got["sweep_traces"] == 0, got
        assert got["sweep_retraces"] == 0, got
        assert got["warm_traces"] >= 1  # the shim really was counting


# ---------------------------------------------------------------------------
# LM: prompt bucketing
# ---------------------------------------------------------------------------

class TestLMBuckets:
    def _lm(self):
        from tpudl.zoo.transformer import TinyCausalLM

        return TinyCausalLM(vocab=64, dim=32, heads=4, layers=2,
                            max_len=128)

    def test_bucketed_generate_matches_exact_one_program(self):
        lm = self._lm()
        params = lm.init(0)
        rng = np.random.default_rng(0)
        for plen in (9, 10, 11, 13, 14, 16):
            prompt = rng.integers(1, 64, size=(2, plen)).astype(np.int32)
            exact = np.asarray(lm.generate(params, prompt, 8))
            bucketed = np.asarray(lm.generate(params, prompt, 8,
                                              prompt_buckets="pow2"))
            np.testing.assert_array_equal(exact, bucketed)
        # the six ragged lengths share ONE padded-16 program
        assert sum(1 for k in lm._gen_jits if k[1] == 16) == 1

    def test_unarmed_generate_unchanged(self):
        """Without prompt_buckets the prompt runs at its exact length:
        one program for that geometry, keyed by the unpadded length."""
        lm = self._lm()
        params = lm.init(0)
        prompt = np.ones((1, 4), np.int32)
        out = np.asarray(lm.generate(params, prompt, 4))
        assert out.shape == (1, 4)
        assert list(lm._gen_jits) == [(1, 4, 4, 0.0, None)]


# ---------------------------------------------------------------------------
# roofline: cold-start attribution
# ---------------------------------------------------------------------------

def _cold_report():
    return {
        "run_id": "r", "rows": 4096, "rows_done": 4096,
        "wall_seconds": 80.0, "finished": True,
        "stage_seconds": {"dispatch": 70.0, "infeed_wait": 0.5,
                          "d2h": 1.0},
        "stage_calls": {"dispatch": 16, "bytes_prepared": 1e6,
                        "first_dispatch_s": 61.0},
        "fuse_steps": 1, "dispatch_depth": 1, "prefetch_depth": 2,
        "prepare_workers": 2, "wire_codec": "off", "batch_size": 256,
        "mesh": None,
    }


class TestRooflinePrecompile:
    def test_cold_start_attributed_and_precompile_recommended(self):
        from tpudl.obs import roofline

        rr = roofline.analyze(_cold_report(), h2d_mbps=1000.0,
                              publish=False, allow_probe=False)
        # first dispatch 61s vs steady (70-61)/15 = 0.6s → cold ~60s
        assert rr.inputs["cold_start_s"] == pytest.approx(60.4, abs=0.5)
        # the cold start is attributed, not advised on: there is no
        # knob that removes it (the persistent cache already does, on
        # the next process that shares its directory)
        assert not [r for r in rr.advice if r["knob"] == "precompile"]


# ---------------------------------------------------------------------------
# obs top: the compile status line
# ---------------------------------------------------------------------------

class TestObsTopCompileLine:
    def test_compile_section_and_render(self, tmp_path, monkeypatch):
        from tpudl.obs import live

        obs_metrics.counter("compile.bucket_pad_rows").inc(7)
        obs_metrics.counter("compile.cache_disabled").inc()
        payload = live.collect_status(roofline=False)
        comp = payload.get("compile")
        assert comp == {"bucket_pad_rows": 7, "cache_disabled": 1}
        text = live.render([payload])
        assert "compile:" in text
        assert "pad_rows 7" in text
        assert "CACHE-DISABLED" in text
        # the written status file still passes the status validator
        monkeypatch.setenv("TPUDL_STATUS_DIR", str(tmp_path))
        path = live.write_status(str(tmp_path), payload)
        spec = importlib.util.spec_from_file_location(
            "validate_status", os.path.join(REPO, "tools",
                                            "validate_status.py"))
        vs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vs)
        assert vs.validate_payload(json.load(open(path))) == []

    def test_no_compile_metrics_no_section(self):
        from tpudl.obs import live

        payload = live.collect_status(roofline=False)
        assert "compile" not in payload
