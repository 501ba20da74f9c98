"""tpudl.compile — shape-bucketed AOT program store (ISSUE 15).

Covers the compilation-cache module (placement by
JAX_COMPILATION_CACHE_DIR, fixed in-checkout default, loud failure),
the bucket ladder, the program store (manifest
round trip, serialized-executable restore, corruption recovery), the
executor wiring (bucketed-vs-exact bitwise parity across
depth×donate×fuse×mesh8, AOT hit/miss accounting), the traceck-armed
zero-retrace ragged sweep, the kill-mid-precompile fault-plan case,
the LM prompt bucketing + precompile, the roofline `precompile` rec,
the obs-top compile line, and the tools/validate_programs audit
(tier-1-wired here, the validate_shards pattern).
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax

from tpudl import compile as C
from tpudl import obs
from tpudl.compile import buckets as bk
from tpudl.compile import cache as ccache
from tpudl.compile import store as cstore
from tpudl.frame import Frame
from tpudl.obs import metrics as obs_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def registry():
    obs_metrics.get_registry().reset()
    C.reset_program_store()
    yield
    obs_metrics.get_registry().reset()
    C.reset_program_store()


@pytest.fixture(scope="module")
def validator():
    spec = importlib.util.spec_from_file_location(
        "validate_programs", os.path.join(REPO, "tools",
                                          "validate_programs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric(name):
    return obs.snapshot().get(name, {}).get("value")


# ---------------------------------------------------------------------------
# satellite: enable_compilation_cache — placement, loudness
# ---------------------------------------------------------------------------

class TestCompilationCache:
    """Placement is decided outside the code: JAX_COMPILATION_CACHE_DIR
    when set (jax reads it itself — nothing is set here), else ONE
    fixed in-checkout path. The program store's default follows."""

    def test_jax_env_wins_and_config_is_untouched(self, tmp_path,
                                                  monkeypatch):
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        monkeypatch.delenv("TPUDL_COMPILE_AOT", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert ccache.enable_compilation_cache() == placed
        # an explicit path does not override the operator's placement
        assert ccache.enable_compilation_cache(
            str(tmp_path / "arg")) == placed
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "arg").exists()
        assert C.store_dir() == os.path.join(placed, "programs")

    def test_unset_env_uses_fixed_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("TPUDL_COMPILE_AOT", raising=False)
        fixed = os.path.join(REPO, ".jax_cache")
        assert ccache.DEFAULT_CACHE_DIR == fixed
        prev = jax.config.jax_compilation_cache_dir
        try:
            assert ccache.enable_compilation_cache() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        assert C.store_dir() == os.path.join(fixed, "programs")

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
# fn fingerprints
# ---------------------------------------------------------------------------

class TestFingerprint:
    def test_same_code_same_closures_same_fp(self):
        w = np.ones((4,), np.float32)

        def mk():
            return jax.jit(lambda x: x * w)

        fp1, p1 = cstore.fn_fingerprint(mk())
        fp2, p2 = cstore.fn_fingerprint(mk())
        assert fp1 == fp2 and p1 and p2

    def test_changed_closure_weights_rekey(self):
        def mk(w):
            return jax.jit(lambda x: x * w)

        fp1, _ = cstore.fn_fingerprint(mk(np.ones((4,), np.float32)))
        fp2, _ = cstore.fn_fingerprint(mk(np.full((4,), 2.0,
                                                  np.float32)))
        assert fp1 != fp2

    def test_jax_array_closure_is_non_portable(self):
        w = jax.numpy.ones((4,))
        fp, portable = cstore.fn_fingerprint(jax.jit(lambda x: x * w))
        assert fp is not None and not portable

    def test_aot_token_wins_and_is_portable(self):
        """A closure reaching device weights ONLY through a token-
        carrying owner (the TinyCausalLM pattern) stays portable: the
        token IS the owner's content identity, so the jax arrays behind
        it are never walked."""
        class Owner:
            aot_token = "model:v1:crc123"

            def __init__(self):
                self.w = jax.numpy.ones((4,))

        owner = Owner()
        fp, portable = cstore.fn_fingerprint(
            jax.jit(lambda x: x * owner.w))
        assert portable and fp is not None
        # two owners with different tokens re-key
        owner2 = Owner()
        owner2.aot_token = "model:v2:crc456"
        fp2, _ = cstore.fn_fingerprint(jax.jit(lambda x: x * owner2.w))
        assert fp2 != fp


# ---------------------------------------------------------------------------
# program store: manifest round trip, restore, corruption
# ---------------------------------------------------------------------------

def _store_with_one_program(root):
    st = cstore.ProgramStore(str(root))
    f = jax.jit(lambda x: x * 3.0)
    x = np.ones((8, 4), np.float32)
    out = st.call(f, [x])
    st.drain(60)
    return st, f, x, np.asarray(out)


class TestProgramStore:
    def test_miss_records_compiles_persists_then_restores(self, tmp_path):
        st, f, x, out = _store_with_one_program(tmp_path / "s")
        entries = st.entries()
        assert len(entries) == 1
        e = list(entries.values())[0]
        assert e["exe"] and e["portable"] and e["compile_s"] is not None
        assert e["crc"] == cstore._entry_crc(e)
        # fresh-process simulation: a NEW instance restores the
        # serialized executable and the same call HITS, bitwise
        st2 = cstore.ProgramStore(str(tmp_path / "s"))
        assert st2.ensure_restored(block=True) == 1
        out2 = np.asarray(st2.call(f, [x]))
        np.testing.assert_array_equal(out, out2)
        assert _metric("compile.hits") == 1
        assert _metric("compile.programs_restored") == 1

    def test_restore_skips_foreign_backend(self, tmp_path):
        st, f, x, out = _store_with_one_program(tmp_path / "s")
        mpath = os.path.join(str(tmp_path / "s"), cstore.MANIFEST_NAME)
        with open(mpath) as fh:
            m = json.load(fh)
        for e in m["entries"].values():
            e["backend"] = {"platform": "tpu", "device_kind": "v5e",
                            "n_devices": 8, "jax": "9.9.9"}
        with open(mpath, "w") as fh:
            json.dump(m, fh)
        st2 = cstore.ProgramStore(str(tmp_path / "s"))
        assert st2.ensure_restored(block=True) == 0  # not ours: skipped

    def test_corrupt_manifest_quarantines_and_starts_empty(self,
                                                           tmp_path):
        root = tmp_path / "s"
        _store_with_one_program(root)
        mpath = os.path.join(str(root), cstore.MANIFEST_NAME)
        with open(mpath, "w") as fh:
            fh.write("{ torn json")
        st2 = cstore.ProgramStore(str(root))
        assert st2.entries() == {}
        assert os.path.exists(mpath + ".corrupt")
        assert _metric("compile.store_corrupt") == 1

    def test_corrupt_exe_is_skipped_never_fatal(self, tmp_path):
        st, f, x, out = _store_with_one_program(tmp_path / "s")
        e = list(st.entries().values())[0]
        epath = os.path.join(str(tmp_path / "s"), e["exe"])
        blob = bytearray(open(epath, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(epath, "wb").write(bytes(blob))
        st2 = cstore.ProgramStore(str(tmp_path / "s"))
        assert st2.ensure_restored(block=True) == 0
        assert _metric("compile.store_corrupt") == 1
        # the jit path still serves the program (miss, not crash)
        np.testing.assert_array_equal(out, np.asarray(st2.call(f, [x])))

    def test_compile_signature_no_execution(self, tmp_path):
        """The warmup contract: declared-aval compile runs NO data —
        a fn that would fail on real zeros still AOT-compiles."""
        st = cstore.ProgramStore(str(tmp_path / "s"))
        f = jax.jit(lambda x: x * 2.0)
        aval = jax.ShapeDtypeStruct((16, 3), np.float32)
        assert st.compile_signature(f, [aval], block=True)
        assert st.programs() == 1
        x = np.ones((16, 3), np.float32)
        np.asarray(st.call(f, [x]))
        assert _metric("compile.hits") == 1
        assert _metric("compile.misses") is None


# ---------------------------------------------------------------------------
# tools/validate_programs.py — the seventh validator (tier-1-wired)
# ---------------------------------------------------------------------------

class TestValidator:
    def test_clean_store_validates(self, tmp_path, validator):
        _store_with_one_program(tmp_path / "s")
        errs, n, n_exe = validator.validate_store_dir(str(tmp_path / "s"))
        assert errs == [] and n == 1 and n_exe == 1

    def test_tampered_entry_fails_checksum(self, tmp_path, validator):
        _store_with_one_program(tmp_path / "s")
        mpath = os.path.join(str(tmp_path / "s"), cstore.MANIFEST_NAME)
        m = json.load(open(mpath))
        list(m["entries"].values())[0]["donate"] = True  # hand edit
        json.dump(m, open(mpath, "w"))
        errs, _, _ = validator.validate_store_dir(str(tmp_path / "s"))
        assert any("checksum" in e for e in errs)

    def test_inflight_persist_orphan_tolerated_and_swept(self, tmp_path,
                                                         validator):
        """A crash between a bin's publish and its manifest seal leaves
        the entry at exe=null beside the bin: the validator must read
        that as in-flight (not corruption), and the next store open
        sweeps it once it ages."""
        st, f, x, out = _store_with_one_program(tmp_path / "s")
        key, e = list(st.entries().items())[0]
        mpath = os.path.join(str(tmp_path / "s"), cstore.MANIFEST_NAME)
        m = json.load(open(mpath))
        entry = m["entries"][key]
        entry["exe"] = entry["exe_crc32"] = entry["exe_nbytes"] = None
        entry["crc"] = cstore._entry_crc(entry)
        json.dump(m, open(mpath, "w"))
        errs, _, n_exe = validator.validate_store_dir(str(tmp_path / "s"))
        assert errs == [] and n_exe == 0  # bin present but unreferenced
        # aged past the cross-process guard, the next open sweeps it
        bin_path = os.path.join(str(tmp_path / "s"), e["exe"])
        os.utime(bin_path, (1, 1))
        cstore.ProgramStore(str(tmp_path / "s"))
        assert not os.path.exists(bin_path)

    def test_stale_executable_flagged(self, tmp_path, validator):
        _store_with_one_program(tmp_path / "s")
        open(os.path.join(str(tmp_path / "s"),
                          "prog-deadbeef.bin"), "wb").write(b"orphan")
        errs, _, _ = validator.validate_store_dir(str(tmp_path / "s"))
        assert any("stale executable" in e for e in errs)

    def test_truncated_exe_flagged(self, tmp_path, validator):
        st, *_ = _store_with_one_program(tmp_path / "s")
        e = list(st.entries().values())[0]
        epath = os.path.join(str(tmp_path / "s"), e["exe"])
        open(epath, "wb").write(open(epath, "rb").read()[:-10])
        errs, _, _ = validator.validate_store_dir(str(tmp_path / "s"))
        assert any("size" in e or "truncated" in e for e in errs)

    def test_bucket_ladder_consistency(self, tmp_path, validator):
        """A bucketed entry whose leading dim is not a rung of the
        manifest's declared ladder is a store bug."""
        st = cstore.ProgramStore(str(tmp_path / "s"))
        st.note_ladder(bk.BucketLadder("pow2"))
        f = jax.jit(lambda x: x + 1)
        st.call(f, [np.ones((7, 2), np.float32)], bucketed=True)
        st.drain(60)
        errs, _, _ = validator.validate_store_dir(str(tmp_path / "s"))
        assert any("not a rung" in e for e in errs)
        # the same shape at a rung size audits clean
        st.call(f, [np.ones((8, 2), np.float32)], bucketed=True)
        st.drain(60)
        errs2, _, _ = validator.validate_store_dir(str(tmp_path / "s"))
        assert errs2 == errs  # only the 7-row entry flagged

    def test_cli_contract(self, tmp_path, validator):
        _store_with_one_program(tmp_path / "s")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "validate_programs.py"),
             str(tmp_path / "s")], capture_output=True, text=True)
        assert r.returncode == 0 and "OK" in r.stdout
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "validate_programs.py")],
            capture_output=True, text=True)
        assert r2.returncode == 2  # usage


# ---------------------------------------------------------------------------
# executor wiring: bitwise parity matrix + AOT accounting
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


class TestExecutorAOT:
    def test_warm_process_first_dispatch_hits(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("TPUDL_COMPILE_AOT", str(tmp_path / "s"))
        rng = np.random.default_rng(2)
        frame = Frame({"x": rng.standard_normal((100, 8)).astype(
            np.float32)})
        fn = jax.jit(lambda b: b * 2.0)
        exact = _run(frame, fn, batch_size=32, buckets="pow2")
        # the background pool may legitimately finish a signature's
        # compile BETWEEN dispatches (same-process hits are design),
        # so only the total and the first miss are deterministic
        hits0 = int(_metric("compile.hits") or 0)
        misses0 = int(_metric("compile.misses") or 0)
        assert misses0 >= 1 and hits0 + misses0 == 4
        rep = obs.last_pipeline_report()
        assert rep["aot"] is True
        calls = rep.get("stage_calls") or {}
        assert (calls.get("aot_hits", 0) + calls["aot_misses"]) == 4
        assert calls.get("first_dispatch_s")
        # a miss compiles ONCE, inline (the jit path never traces): the
        # table already holds both signatures before any drain, and
        # exactly one compile per signature was paid
        assert C.get_program_store().programs() == 2
        assert _metric("compile.programs_compiled") == 2
        C.get_program_store().drain(60)
        # "fresh process": drop the singleton (its table dies with it)
        C.reset_program_store()
        obs_metrics.get_registry().reset()
        assert C.warm_start(block=True) == 2  # 32-rung + 4-tail
        warm = _run(frame, fn, batch_size=32, buckets="pow2")
        np.testing.assert_array_equal(exact, warm)
        assert _metric("compile.hits") == 4
        assert _metric("compile.misses") is None
        assert (obs.last_pipeline_report().get("stage_calls")
                or {}).get("aot_hits") == 4

    def test_aot_off_by_default(self, monkeypatch):
        monkeypatch.delenv("TPUDL_COMPILE_AOT", raising=False)
        frame = Frame({"x": np.ones((8, 4), np.float32)})
        _run(frame, jax.jit(lambda b: b + 1), batch_size=8)
        assert obs.last_pipeline_report()["aot"] is False
        assert _metric("compile.misses") is None


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
        env.pop("TPUDL_COMPILE_AOT", None)
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
# acceptance: kill mid-precompile — manifest stays valid, next start resumes
# ---------------------------------------------------------------------------

_KILL_SCRIPT = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tpudl import compile as C
from tpudl.frame import Frame
from tpudl.testing import faults

faults.install_from_env()  # the cross-process fault-plan contract

frame = Frame({"x": np.ones((80, 4), np.float32)})   # 2 programs:
fn = jax.jit(lambda b: b * 2.0)                      # 64-full + 16-tail
out = frame.map_batches(fn, ["x"], ["y"], batch_size=64, autotune=False,
                        aot=True, buckets="pow2")
np.stack(list(out["y"]))
C.get_program_store().drain(120)   # the armed plan SIGTERMs in here
print("DRAINED-CLEAN")             # only reached when no plan is armed
"""


class TestKillMidPrecompile:
    def test_manifest_valid_after_kill_and_next_start_resumes(
            self, tmp_path, validator):
        store_dir = str(tmp_path / "store")
        script = str(tmp_path / "kill.py")
        open(script, "w").write(_KILL_SCRIPT)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["TPUDL_COMPILE_AOT"] = store_dir
        env["TPUDL_FAULT_PLAN"] = json.dumps(
            [{"point": "compile.precompile", "action": "sigterm",
              "at_call": 2}])
        r = subprocess.run([sys.executable, script],
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=REPO)
        assert r.returncode in (-signal.SIGTERM, 128 + signal.SIGTERM,
                                143), (r.returncode, r.stderr[-500:])
        assert "DRAINED-CLEAN" not in r.stdout  # really died mid-drain
        # the manifest survived the kill VALID (atomic writes only)
        errs, n_entries, n_exe = validator.validate_store_dir(store_dir)
        assert errs == [], errs
        assert n_entries == 2
        assert n_exe < 2  # at least one compile was killed away
        # relaunch WITHOUT the plan: the same run resumes compiling the
        # missing programs and the store completes
        env2 = dict(env)
        env2.pop("TPUDL_FAULT_PLAN")
        r2 = subprocess.run([sys.executable, script],
                            capture_output=True, text=True, env=env2,
                            timeout=300, cwd=REPO)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "DRAINED-CLEAN" in r2.stdout
        errs, n_entries, n_exe = validator.validate_store_dir(store_dir)
        assert errs == [] and n_entries == 2 and n_exe == 2


# ---------------------------------------------------------------------------
# LM: prompt bucketing + precompile_generate
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

    def test_precompile_generate_then_hit(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPUDL_COMPILE_AOT", str(tmp_path / "s"))
        lm = self._lm()
        params = lm.init(0)
        assert lm.precompile_generate(params, 2, 13, 8,
                                      prompt_buckets="pow2")
        prompt = np.random.default_rng(0).integers(
            1, 64, size=(2, 13)).astype(np.int32)
        out = np.asarray(lm.generate(params, prompt, 8,
                                     prompt_buckets="pow2"))
        assert _metric("compile.hits") == 1
        # fresh process: a NEW model instance over a restored store
        C.get_program_store().drain(60)
        C.reset_program_store()
        obs_metrics.get_registry().reset()
        lm2 = self._lm()
        assert C.warm_start(block=True) >= 1
        out2 = np.asarray(lm2.generate(params, prompt, 8,
                                       prompt_buckets="pow2"))
        np.testing.assert_array_equal(out, out2)
        assert _metric("compile.hits") == 1

    def test_unarmed_generate_unchanged(self, monkeypatch):
        monkeypatch.delenv("TPUDL_COMPILE_AOT", raising=False)
        lm = self._lm()
        params = lm.init(0)
        prompt = np.ones((1, 4), np.int32)
        out = np.asarray(lm.generate(params, prompt, 4))
        assert out.shape == (1, 4)
        assert _metric("compile.misses") is None


# ---------------------------------------------------------------------------
# warmup as an AOT warm call
# ---------------------------------------------------------------------------

class TestWarmupAOT:
    def test_warmup_compiles_declared_signature_without_execution(
            self, tmp_path, monkeypatch):
        from tpudl.ml.tf_image import ImageBatchWarmup

        monkeypatch.setenv("TPUDL_COMPILE_AOT", str(tmp_path / "s"))

        class W(ImageBatchWarmup):
            batchSize = 16
            mesh = None
            fuseSteps = 1

            def _get_jfn(self):
                return jax.jit(
                    lambda b: b.astype(jax.numpy.float32).mean(
                        axis=(1, 2, 3)))

        w = W()
        w.warmup(8, 8, 3)
        st = C.get_program_store()
        assert st.programs() >= 1
        assert _metric("compile.programs_compiled") >= 1
        # the executor's dispatch hits the exact warmed key
        frame = Frame({"x": np.zeros((16, 8, 8, 3), np.uint8)})
        _run(frame, w._get_jfn(), batch_size=16)
        assert _metric("compile.hits") == 1


# ---------------------------------------------------------------------------
# jobs: resume warm-starts the store
# ---------------------------------------------------------------------------

class TestJobsWarmStart:
    def test_manifest_records_store_and_resume_restores(self, tmp_path,
                                                        monkeypatch):
        from tpudl.jobs import JobRuntime, JobSpec

        monkeypatch.setenv("TPUDL_COMPILE_AOT", str(tmp_path / "s"))
        _store_with_one_program(tmp_path / "s")
        C.reset_program_store()
        spec = JobSpec("featurize", str(tmp_path / "job"),
                       material={"k": 1})
        JobRuntime(spec, install_signals=False).run(lambda ctx: 1)
        from tpudl.jobs.runtime import load_manifest

        m = load_manifest(str(tmp_path / "job"))
        assert m["program_store"] == str(tmp_path / "s")
        # relaunch = resume: the warm start restores before the payload
        obs_metrics.get_registry().reset()
        C.reset_program_store()
        JobRuntime(spec, install_signals=False).run(lambda ctx: 2)
        assert _metric("compile.programs_restored") == 1


# ---------------------------------------------------------------------------
# roofline: cold-start attribution + the precompile rec
# ---------------------------------------------------------------------------

def _cold_report(aot=False, hits=0, misses=4):
    return {
        "run_id": "r", "rows": 4096, "rows_done": 4096,
        "wall_seconds": 80.0, "finished": True,
        "stage_seconds": {"dispatch": 70.0, "infeed_wait": 0.5,
                          "d2h": 1.0},
        "stage_calls": {"dispatch": 16, "bytes_prepared": 1e6,
                        "first_dispatch_s": 61.0,
                        "aot_hits": hits, "aot_misses": misses},
        "fuse_steps": 1, "dispatch_depth": 1, "prefetch_depth": 2,
        "prepare_workers": 2, "wire_codec": "off", "batch_size": 256,
        "aot": aot, "mesh": None,
    }


class TestRooflinePrecompile:
    def test_cold_start_attributed_and_precompile_recommended(self):
        from tpudl.obs import roofline

        rr = roofline.analyze(_cold_report(), h2d_mbps=1000.0,
                              publish=False, allow_probe=False)
        # first dispatch 61s vs steady (70-61)/15 = 0.6s → cold ~60s
        assert rr.inputs["cold_start_s"] == pytest.approx(60.4, abs=0.5)
        rec = [r for r in rr.advice if r["knob"] == "precompile"]
        assert rec and rec[0]["recommended"] == "on"
        assert rec[0]["predicted_gain_pct"] > 100  # 80s run, 60s cold

    def test_armed_store_suppresses_the_rec(self):
        from tpudl.obs import roofline

        rr = roofline.analyze(_cold_report(aot=True, hits=4),
                              h2d_mbps=1000.0, publish=False,
                              allow_probe=False)
        assert not [r for r in rr.advice if r["knob"] == "precompile"]


# ---------------------------------------------------------------------------
# obs top: the compile status line
# ---------------------------------------------------------------------------

class TestObsTopCompileLine:
    def test_compile_section_and_render(self, tmp_path, monkeypatch):
        from tpudl.obs import live

        obs_metrics.counter("compile.hits").inc(7)
        obs_metrics.counter("compile.misses").inc(2)
        obs_metrics.counter("compile.programs_restored").inc(3)
        obs_metrics.counter("compile.cache_disabled").inc()
        payload = live.collect_status(roofline=False)
        comp = payload.get("compile")
        assert comp == {"hits": 7, "misses": 2, "programs_restored": 3,
                        "programs_compiled": 0, "aot_s": 0.0,
                        "bucket_pad_rows": 0, "cache_disabled": 1}
        text = live.render([payload])
        assert "compile:" in text
        assert "hits 7" in text and "restored 3" in text
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
