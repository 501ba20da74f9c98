"""Persistent XLA compilation cache wiring (the bottom tier of the
compile subsystem — COMPILE.md has the operator guide).

The reference pays Spark task-dispatch overhead per stage; our analogous
fixed cost is XLA compilation — a minute or more for InceptionV3, paid
again every process start. JAX's persistent compilation cache
(serialized executables keyed by HLO+flags+topology) removes the
*compile* for repeat runs; the AOT program store
(:mod:`tpudl.compile.store`) sits above it and removes the *trace* too.
This module turns the JAX cache on; ``benchmark/run.py`` and
``chip_smoke.py`` call it, library code never does.

Where the cache lives is decided OUTSIDE the code: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it and this
module sets no directory at all. When it is unset the cache goes to ONE
fixed path inside the checkout (:data:`DEFAULT_CACHE_DIR`, git-ignored)
— the directory is part of the cache's key, so it is never built from
``$HOME``, a temporary name, a pid or the clock.

Cache safety: entries are keyed by backend+topology, so a cache shared
between the CPU-mesh test runs and the TPU chip never cross-serves.

Failure is LOUD: a read-only filesystem used to be swallowed silently —
a whole fleet could cold start on every process with nothing in any
log. Now the first failure warns once per process, counts
``compile.cache_disabled``, and files a flight-recorder breadcrumb, so
``python -m tpudl.obs doctor`` and the metrics sink both show WHY the
fleet is cold.

Every compilation is a span: enabling the cache registers ONE
``jax.monitoring`` listener, and each ``backend_compile_duration`` event
(a compile or a load from the cache) becomes a ``compile.program`` span
in the host-span ring, a child of whatever span the compiling thread had
open, so a recompilation inside a fit hangs under the
``train.step.dispatch`` that paid for it (OBSERVABILITY.md).
"""

from __future__ import annotations

import os
import threading
import time
import warnings

__all__ = ["enable_compilation_cache", "cache_dir", "DEFAULT_CACHE_DIR"]

_JAX_ENV = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_warned_disabled = False

# jax.monitoring event names (jax/_src/dispatch.py, compilation_cache.py)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_listening = False
_last_lookup = threading.local()  # .hit: this thread's last cache lookup


def cache_dir() -> str:
    """Where the persistent cache lives: ``$JAX_COMPILATION_CACHE_DIR``
    when the operator set it, else the fixed in-checkout path. The ONE
    resolution the program store's default directory shares."""
    return os.environ.get(_JAX_ENV) or DEFAULT_CACHE_DIR


def _note_disabled(path: str, exc: BaseException) -> None:
    """The diagnosable-cold-fleet breadcrumb: warn once per process,
    count every occurrence, leave flight evidence (all best-effort —
    cache setup must never take the run down)."""
    global _warned_disabled
    try:
        from tpudl.obs import metrics as _m

        _m.counter("compile.cache_disabled").inc()
        from tpudl.obs import flight as _flight

        _flight.record_error(
            "compile.cache_disabled",
            f"persistent compilation cache disabled at {path!r}: "
            f"{exc!r} — every process start pays full XLA compile",
            path=path)
    # tpudl: ignore[swallowed-except] — the breadcrumb channel itself
    # is best-effort: obs may be unimportable in a minimal subprocess,
    # and the warning below still fires
    except Exception:
        pass
    if not _warned_disabled:
        _warned_disabled = True
        warnings.warn(
            f"tpudl: persistent XLA compilation cache DISABLED "
            f"({path!r}: {exc!r}) — cold starts will pay full compile "
            f"time; fix the directory or set {_JAX_ENV}",
            RuntimeWarning, stacklevel=3)


def _on_cache_event(event, **_kw) -> None:
    if event == _CACHE_HIT:
        _last_lookup.hit = True
    elif event == _CACHE_MISS:
        _last_lookup.hit = False


def _on_compile_duration(event, duration, **_kw) -> None:
    """The event arrives when the compile (or cache load) has ended, on
    the thread that asked for it: start = now - duration."""
    if event != _BACKEND_COMPILE:
        return
    from tpudl.obs import tracer as _tracer

    dur_ns = int(duration * 1e9)
    _tracer.get_tracer().record(
        "compile.program", time.time_ns() - dur_ns, dur_ns,
        cache_hit=getattr(_last_lookup, "hit", None))
    _last_lookup.hit = None


def _listen_for_compiles() -> None:
    """Once per process: jax.monitoring has no public way to take a
    listener back."""
    global _listening
    if _listening:
        return
    import jax.monitoring as mon

    mon.register_event_listener(_on_cache_event)
    mon.register_event_duration_secs_listener(_on_compile_duration)
    _listening = True


def enable_compilation_cache(path: str | None = None) -> str | None:
    """Enable JAX's persistent compilation cache and return its
    directory (None when it could not be enabled).

    ``$JAX_COMPILATION_CACHE_DIR`` wins over everything: jax already
    points at it, so no directory is set here and ``path`` is ignored.
    Otherwise the cache goes to ``path`` or the fixed in-checkout
    :data:`DEFAULT_CACHE_DIR`."""
    import jax

    env = os.environ.get(_JAX_ENV)
    target = env or path or DEFAULT_CACHE_DIR
    try:
        os.makedirs(target, exist_ok=True)
        if not env:
            jax.config.update("jax_compilation_cache_dir", target)
        # cache EVERY program. A compile-time floor makes a program
        # whose compile straddles it a miss on every other run (on the
        # v5e six ~1 s LM serve programs were written only by the
        # second of two identical runs under jax's 1 s default), and
        # the small entries are nothing next to one CNN executable
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _listen_for_compiles()
        return target
    except OSError as e:  # read-only / not-a-directory: loud, never fatal
        _note_disabled(str(target), e)
        return None


def _reset_warned_for_tests() -> None:
    global _warned_disabled
    _warned_disabled = False
