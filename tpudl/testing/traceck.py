"""tpudl.testing.traceck — the opt-in recompile-storm sentinel.

The runtime twin of the static jit-boundary analyzer
(:mod:`tpudl.analysis.traceguard`), the same static+runtime-twin shape
as tpudl-check's concurrency rules and :mod:`tpudl.testing.tsan`:
the analyzer PREDICTS cache churn from the source (per-call closures,
jit-in-loop, unhashable static args); this module MEASURES it — every
retrace of the same function identity, in this process, right now.

``TPUDL_TRACECK=1`` arms the sentinel (``tpudl/__init__`` installs it
before any product module touches jax). :func:`install` replaces
``jax.jit`` with a counting shim: the function handed to jit is
wrapped so that each execution of its body — which, under jit, happens
exactly once per TRACE — bumps a per-identity counter. Identity is the
code object's ``file:line:qualname``, NOT the function object: a fresh
lambda built per call (the churn pattern the static rule flags)
collapses onto one identity and its retraces pile up where a per-object
key would hide them.

Findings:

- every trace bumps ``traceck.traces``; a second-or-later trace of one
  identity bumps ``traceck.retraces``;
- an identity tracing **more than** ``TPUDL_TRACECK_STORM`` times
  (default 3) is a **recompile storm**: one finding per identity into
  the flight error ring (kind ``traceck.recompile_storm``) +
  ``traceck.storms`` — on the real chip a recompile costs ~60 s
  (ROADMAP item 3's measured cold start), so a storm is a silent
  order-of-magnitude throughput loss that looks like a dispatch
  slowdown from the outside. ``python -m tpudl.obs doctor`` classifies
  a dump carrying this evidence as ``recompile_storm``, ranked beside
  ``dispatch_slowdown``.

Like the lock sanitizer, the armed sentinel taxes the numbers (every
trace takes the bookkeeping hop): a measured run keeps it off.

Unarmed — the default — this module is never imported by product code
and ``jax.jit`` is untouched: the hot path pays literally nothing.

Stdlib-only at import (jax and the obs reporting surface load lazily
inside :func:`install` and the finding path), mirroring tsan's
lowest-layer import contract.
"""

from __future__ import annotations

import functools
import os
import weakref

from tpudl.testing.tsan import named_lock

__all__ = ["ENABLED", "DEFAULT_STORM", "arm", "disarm", "enabled",
           "install", "uninstall", "installed", "counts", "findings",
           "reset", "storm_threshold"]

#: armed at import when TPUDL_TRACECK=1 (tpudl/__init__ then installs);
#: :func:`arm`/:func:`disarm` flip it in-process for unit tests.
ENABLED = os.environ.get("TPUDL_TRACECK", "0") == "1"

DEFAULT_STORM = 3

_LOCK = named_lock("testing.traceck")
_COUNTS: dict[str, int] = {}
_FINDINGS: list[dict] = []
_REAL_JIT = None


def enabled() -> bool:
    """Is the sentinel armed right now?"""
    return ENABLED


def storm_threshold() -> int:
    """Traces of one identity beyond which the storm finding files."""
    try:
        v = int(os.environ.get("TPUDL_TRACECK_STORM", "") or
                DEFAULT_STORM)
    except ValueError:
        return DEFAULT_STORM
    return max(1, v)


def _identity(fun) -> str:
    """A fn's identity by CODE LOCATION, not object: per-call lambdas
    (the churn pattern) share one identity so their retraces pile up
    visibly instead of hiding behind fresh ids."""
    seen = set()
    while id(fun) not in seen:
        seen.add(id(fun))
        code = getattr(fun, "__code__", None)
        if code is not None:
            qual = getattr(fun, "__qualname__",
                           getattr(fun, "__name__", "<fn>"))
            return f"{code.co_filename}:{code.co_firstlineno}:{qual}"
        inner = getattr(fun, "__wrapped__", None) or \
            getattr(fun, "func", None)
        if inner is None or inner is fun:
            break
        fun = inner
    t = type(fun)
    return f"<{t.__module__}.{t.__qualname__}> " \
           f"{getattr(fun, '__name__', repr(type(fun)))}"


def _note_trace(ident: str):
    fire_retrace = False
    storm_count = None
    with _LOCK:
        n = _COUNTS.get(ident, 0) + 1
        _COUNTS[ident] = n
        fire_retrace = n >= 2
        if n == storm_threshold() + 1:
            storm_count = n
            entry = {"kind": "recompile_storm", "fn": ident,
                     "traces": n, "threshold": storm_threshold()}
            _FINDINGS.append(entry)
            del _FINDINGS[:-256]   # bounded even under a churn loop
    # metrics + flight hop AFTER release: the breadcrumb channel takes
    # its own (higher-ranked) product locks, and the sentinel must
    # never hold its lock across them (lock-held-blocking)
    try:
        from tpudl.obs import metrics as _m

        _m.counter("traceck.traces").inc()
        if fire_retrace:
            _m.counter("traceck.retraces").inc()
        if storm_count is not None:
            _m.counter("traceck.storms").inc()
            from tpudl.obs import flight as _f

            _f.record_error(
                "traceck.recompile_storm",
                f"recompile storm: {ident} traced {storm_count} times "
                f"(> TPUDL_TRACECK_STORM={storm_threshold()}) — each "
                f"retrace recompiles (~60 s on the real chip); check "
                f"for per-call closures, jit-in-loop, or cache-key "
                f"churn (the static jit-cache-churn rule names the "
                f"site)", fn=ident, traces=storm_count)
    # tpudl: ignore[swallowed-except] — the sentinel's breadcrumb
    # channel is best-effort: obs may be unimportable in a minimal
    # subprocess, and counts()/findings() still carry the evidence
    except Exception:
        pass


def _jit_disabled() -> bool:
    """Under ``jax.disable_jit()`` the wrapped body re-executes EAGERLY
    on every call — those are not traces, and counting them would file
    false storms that bury a dump's real failure cause."""
    try:
        import jax

        return bool(jax.config.jax_disable_jit)
    # config-surface drift means we cannot tell; counting (the
    # pre-check behavior) is the safe default and the report still
    # carries honest per-identity counts
    except Exception:
        return False


_SHIM_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _shim(fun):
    """Wrap the fn handed to jax.jit: under jit, the body runs exactly
    once per trace, so one shim call == one (re)trace.

    MEMOIZED per fn object: jax's trace cache keys on fn identity, so
    a fresh wrapper per ``jax.jit(f)`` call would make the benign
    ``jax.jit(f)(x)``-in-a-loop pattern over a STABLE f — one trace
    unarmed — retrace per call and file a storm the sentinel itself
    manufactured. Same fn object in, same wrapper object out."""
    try:
        with _LOCK:
            cached = _SHIM_MEMO.get(fun)
    except TypeError:
        cached = None   # unweakrefable/unhashable fn: uncached shim
    if cached is not None:
        return cached

    @functools.wraps(fun)
    def traced(*a, **k):
        if ENABLED and not _jit_disabled():
            _note_trace(ident)
        return fun(*a, **k)

    ident = _identity(fun)
    # wraps() copied fun.__dict__ — including any _tpudl_fused /
    # _tpudl_codec_wrap retention caches. Those must key on the REAL
    # fn object, not the shim (a shared reference here is harmless:
    # the wrappers cache on the object they were handed).
    try:
        with _LOCK:
            winner = _SHIM_MEMO.get(fun)
            if winner is not None:
                # two threads raced the build: ONE wrapper identity
                # must win, or jax compiles the same program once per
                # wrapper and the sentinel manufactures the very
                # retraces it reports
                return winner
            _SHIM_MEMO[fun] = traced
    except TypeError:
        pass
    return traced


def install():
    """Replace ``jax.jit`` with the counting shim (idempotent). Called
    by ``tpudl/__init__`` when ``TPUDL_TRACECK=1`` — before product
    modules bind ``jax.jit`` into decorators/partials."""
    global _REAL_JIT
    import jax

    if getattr(jax.jit, "_tpudl_traceck", False):
        return
    real = jax.jit
    _REAL_JIT = real

    def traceck_jit(fun=None, *args, **kwargs):
        if fun is None:
            # kwargs-only decorator form: jax.jit(static_argnums=...)
            return lambda f: traceck_jit(f, *args, **kwargs)
        # the CLOSED-OVER real jit, never the module global: a module
        # that bound `jit = jax.jit` while armed keeps a working jit
        # after uninstall() clears _REAL_JIT
        return real(_shim(fun), *args, **kwargs)

    traceck_jit._tpudl_traceck = True
    traceck_jit.__wrapped__ = real
    jax.jit = traceck_jit


def installed() -> bool:
    try:
        import jax
    except Exception:
        return False
    return bool(getattr(jax.jit, "_tpudl_traceck", False))


def uninstall():
    """Restore the real ``jax.jit`` (tests)."""
    global _REAL_JIT
    if _REAL_JIT is None:
        return
    import jax

    if getattr(jax.jit, "_tpudl_traceck", False):
        jax.jit = _REAL_JIT
    _REAL_JIT = None


def arm():
    """Arm in-process AND install the shim (tests; production arms via
    TPUDL_TRACECK=1 at import, before jax.jit is bound anywhere)."""
    global ENABLED
    ENABLED = True
    install()


def disarm():
    """Stop counting (the shim stays installed but its fast path
    re-checks ENABLED — already-wrapped programs keep working)."""
    global ENABLED
    ENABLED = False


def reset():
    """Drop every count/finding (tests)."""
    with _LOCK:
        _COUNTS.clear()
        _FINDINGS.clear()


def counts() -> dict[str, int]:
    """Per-identity trace counts observed so far."""
    with _LOCK:
        return dict(_COUNTS)


def findings() -> list[dict]:
    """Storm findings filed so far (one per storming identity)."""
    with _LOCK:
        return list(_FINDINGS)


if ENABLED:
    # armed via env: install as soon as anything imports the sentinel
    # (tpudl/__init__ does, exactly once, before product jax use)
    install()
