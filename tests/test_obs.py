"""Observability tests (SURVEY.md §5.1/§5.5)."""

import json

import numpy as np
import pytest

import jax

from tpudl.obs import Meter, named_scope, profile


def test_meter_report_and_json_line():
    m = Meter(n_chips=2, skip=1)
    with m.batch(10):
        pass
    with m.batch(10):
        pass
    r = m.report()
    assert r["examples"] == 10  # first (warmup) batch skipped
    assert r["batches"] == 2
    assert r["examples_per_sec_per_chip"] * 2 == pytest.approx(
        r["examples_per_sec"], rel=1e-4)
    line = json.loads(m.json_line("images/sec/chip (test)", baseline=None))
    assert line["unit"] == "images/sec/chip"
    assert line["vs_baseline"] is None
    line2 = json.loads(m.json_line("x", baseline=r["examples_per_sec_per_chip"]))
    assert line2["vs_baseline"] == 1.0


def test_named_scope_composes_with_jit():
    @jax.jit
    def f(x):
        with named_scope("decode"):
            y = x * 2
        with named_scope("apply"):
            return y + 1

    np.testing.assert_array_equal(np.asarray(f(np.arange(3.0))),
                                  [1.0, 3.0, 5.0])


def test_profile_writes_trace(tmp_path):
    import os
    import time

    from tpudl import obs

    d = str(tmp_path / "trace")
    before = time.time_ns()
    with profile(d):
        with obs.span("test.profiled"):
            jax.block_until_ready(jax.jit(lambda x: x + 1)(np.zeros(4)))
    after = time.time_ns()
    files = [os.path.join(r, f) for r, _d, fs in os.walk(d) for f in fs]
    assert files, "profiler produced no trace files"
    # the trace carries its own window, on the clock spans start on:
    # nothing is written on the tracer for window="profile" exports
    start, stop = obs.profile_window(d)
    assert before <= start <= stop <= after
    assert not hasattr(obs.get_tracer(), "last_profile_window")
    path = obs.export_chrome_trace(os.path.join(d, "run.host.trace.json"),
                                   window="profile")
    inside = obs.align(obs.load_host_spans(path), start)
    assert "test.profiled" in [s.name for s in inside]
    assert all(s.start_ns + s.dur_ns >= 0 and s.start_ns <= stop - start
               for s in inside)


def test_persistent_compilation_cache_round_trip(tmp_path, monkeypatch):
    """compilation_cache: second process-equivalent compile of the same
    program must be served from the on-disk cache (observable: cache dir
    gains entries, and a fresh jit of the same HLO hits it).

    Order-independence (the PR-5 flake): jax's persistent-cache layer is
    a process-wide singleton initialized at first use — a test earlier
    in the session may have armed it against a different (or no) dir,
    after which this test's ``jax_compilation_cache_dir`` update alone
    does not re-point it. ``reset_cache()`` forces re-initialization
    against THIS test's tmp dir (before AND after: leave no armed cache
    behind). The program also embeds a per-run nonce so its HLO can
    never be served by any in-memory executable another test compiled,
    and every config knob touched is restored."""
    import jax
    import jax.numpy as jnp

    from tpudl.compile import enable_compilation_cache

    def _reset_persistent_cache():
        try:
            from jax._src import compilation_cache as _cc

            _cc.reset_cache()
        except Exception:  # private API drift: best effort
            pass

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min_time = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_min_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    _reset_persistent_cache()
    d = str(tmp_path / "xla_cache")
    got = enable_compilation_cache(d)
    assert got == d
    # the production threshold (1s) skips toy programs; force-persist here
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        nonce = float(np.random.default_rng().integers(1, 1 << 30))

        @jax.jit
        def f(x):
            return jnp.tanh(x) * 3.0 + x**2 + nonce

        x = np.arange(64, dtype=np.float32)
        np.testing.assert_allclose(
            np.asarray(f(x)), np.tanh(x) * 3.0 + x**2 + nonce, rtol=1e-6)
        import os as _os

        entries = [p for p in _os.listdir(d)]
        assert entries, "no cache entries written"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min_time)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev_min_size)
        _reset_persistent_cache()


