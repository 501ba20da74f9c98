"""Deterministic fault injection — the harness that PROVES recovery.

The robustness claims of the job runtime (JOBS.md) are only claims
until a test can kill, corrupt, and starve the pipeline on demand and
watch it recover. This module is that demand side:

- production code exposes **fault points**: ``faults.fire("frame.
  dispatch", index=i)`` at the top of each executor stage, per train
  step, per shard-cache read, per file read. Unarmed (the default,
  always in production), ``fire`` is a global ``None``-check — the
  executor overhead guard in tests/test_obs_flight.py already pins the
  whole observer stack at <5%, and this is far cheaper than a metric
  increment;
- a :class:`FaultPlan` is a list of RULES, each naming a point, a
  deterministic trigger (the Nth call, the first K calls, or a ctx
  match like ``step == 13``), and an action:

  - ``raise`` — raise a chosen exception type (stage faults,
    transient IO errors with recovery-after-K via ``first_calls``);
  - ``oom`` — raise a realistic device-OOM: the REAL
    ``XlaRuntimeError`` type when jaxlib is importable (a message-
    compatible stand-in otherwise), with the ``RESOURCE_EXHAUSTED: Out
    of memory while trying to allocate N bytes.`` text the supervisor's
    taxonomy anchors on — so OOM recovery (evict-and-retry,
    FAULTS.md) is testable without a real device;
  - ``sigterm`` — SIGTERM-to-self (the preemption kill, delivered at
    an exact step instead of a racy external timer);
  - ``corrupt`` — flip one byte of the file named by the firing's
    ``path`` ctx (shard/checkpoint bit-rot on the read path);
  - ``delay`` — sleep ``seconds`` on the firing thread: the
    deterministic stand-in for a high-latency dispatch round-trip
    (the async-executor overlap acceptance tests inject a per-dispatch
    latency this way and measure how much of it the D-deep window
    hides). ``slow_client`` wraps it for the serve plane's
    ``serve.client`` point: a stalling client whose requests age in
    the queue exercises the deadline-shed path;
  - ``burst`` — inject ``count`` extra requests in one serve tick:
    the firing site (the serve load generator at ``serve.tick``)
    receives the count as ``fire``'s return value and submits that
    many requests back-to-back, driving admission control past queue
    capacity deterministically (the ``overload_shed`` chaos
    acceptance).

Plans arm process-locally (``with plan.armed(): ...``) or across a
process boundary via ``TPUDL_FAULT_PLAN`` (JSON; the kill-mid-epoch
subprocess tests use this — ``install_from_env()`` in the child).
Every triggered fault is appended to ``plan.fired`` and filed into the
flight recorder's error ring (kind ``fault.injected``), so the forensic
trail of an injected death looks exactly like a real one.
"""

from __future__ import annotations

import builtins
import json
import os
import signal
import time

from tpudl.testing import tsan as _tsan

__all__ = ["FaultPlan", "FaultInjected", "arm", "disarm", "fire",
           "install_from_env", "oom_error", "PLAN_ENV"]

PLAN_ENV = "TPUDL_FAULT_PLAN"

_PLAN: "FaultPlan | None" = None
_ARM_LOCK = _tsan.named_lock("testing.faults.arm")


class FaultInjected(RuntimeError):
    """Default exception for ``raise`` rules that don't name one."""


class _StandInXlaRuntimeError(RuntimeError):
    """Stand-in mirroring jaxlib's XlaRuntimeError when jaxlib is not
    importable: classifiers anchor on the type NAME + the
    RESOURCE_EXHAUSTED message, both preserved here."""


_StandInXlaRuntimeError.__name__ = "XlaRuntimeError"
_StandInXlaRuntimeError.__qualname__ = "XlaRuntimeError"
_OOM_TYPE: list = []  # resolved lazily; faults.py sits on the frame
#                       import chain and must not pull jaxlib in early


def _xla_runtime_error_type():
    if not _OOM_TYPE:
        try:
            # the REAL runtime-error type XLA raises on device OOM — an
            # ``oom`` fault is then type-identical to production, not
            # just message-identical
            from jaxlib.xla_extension import XlaRuntimeError
            _OOM_TYPE.append(XlaRuntimeError)
        # jaxlib absent/renamed: the message-compatible stand-in keeps
        # the harness usable on host-only installs
        except Exception:  # pragma: no cover - jaxlib absent/renamed
            _OOM_TYPE.append(_StandInXlaRuntimeError)
    return _OOM_TYPE[0]


def oom_error(nbytes: int = 2 << 30, point: str = "") -> BaseException:
    """One realistic device-OOM exception (the ``oom`` action's
    payload), exactly message-shaped like a real allocator failure."""
    suffix = f" [{point}]" if point else ""
    return _xla_runtime_error_type()(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        f"{int(nbytes)} bytes.{suffix}")


def _resolve_exc(name: str | None):
    """Exception class by builtin name (allowlist: must actually be an
    exception type); anything unknown falls back to FaultInjected so a
    typo'd plan still injects a failure instead of silently passing."""
    if not name:
        return FaultInjected
    cls = getattr(builtins, str(name), None)
    if isinstance(cls, type) and issubclass(cls, BaseException) \
            and not issubclass(cls, (SystemExit, KeyboardInterrupt)):
        return cls
    return FaultInjected


class _Rule:
    """One deterministic fault rule (see module docstring)."""

    def __init__(self, spec: dict):
        self.point = str(spec["point"])
        self.action = str(spec.get("action", "raise"))
        if self.action not in ("raise", "sigterm", "corrupt", "unlink",
                               "delay", "oom", "burst"):
            raise ValueError(f"unknown fault action {self.action!r}")
        self.seconds = float(spec.get("seconds", 0.0))
        self.nbytes = int(spec.get("bytes", 0) or 0)  # oom: alloc size
        self.count = int(spec.get("count", 0) or 0)   # burst: extra reqs
        # triggers — all optional, all must match when present:
        self.at_call = spec.get("at_call")        # exactly the Nth call
        self.first_calls = spec.get("first_calls")  # calls 1..K
        self.when = dict(spec.get("when") or {})  # ctx equality
        self.exc = spec.get("exc")
        self.message = spec.get("message") or (
            f"injected fault at {self.point}")
        self.calls = 0       # firings seen at this point
        self.triggered = 0   # firings that took the action

    def matches(self, ctx: dict) -> bool:
        if self.at_call is not None and self.calls != int(self.at_call):
            return False
        if self.first_calls is not None \
                and self.calls > int(self.first_calls):
            return False
        for k, v in self.when.items():
            if ctx.get(k) != v:
                return False
        return True

    def to_dict(self) -> dict:
        d = {"point": self.point, "action": self.action}
        for k in ("at_call", "first_calls", "exc", "message"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.seconds:
            d["seconds"] = self.seconds
        if self.nbytes:
            d["bytes"] = self.nbytes
        if self.count:
            d["count"] = self.count
        if self.when:
            d["when"] = self.when
        return d


class FaultPlan:
    """A deterministic set of fault rules, armed process-globally."""

    def __init__(self, rules):
        self._lock = _tsan.named_lock("testing.faults.plan")
        self.rules = [r if isinstance(r, _Rule) else _Rule(dict(r))
                      for r in rules]
        self.fired: list[dict] = []  # every TRIGGERED fault, for asserts

    # -- construction ------------------------------------------------------
    @classmethod
    def kill_at_step(cls, step: int, point: str = "train.step",
                     ) -> "FaultPlan":
        """SIGTERM-to-self the Nth time ``point`` fires with
        ``step == N`` — the deterministic preemption kill."""
        return cls([{"point": point, "action": "sigterm",
                     "when": {"step": int(step)}}])

    @classmethod
    def raise_in_stage(cls, stage: str, at_call: int = 1,
                       exc: str | None = None) -> "FaultPlan":
        """Raise inside one executor stage (prepare/h2d/dispatch/d2h)
        on its ``at_call``-th entry."""
        return cls([{"point": f"frame.{stage}", "action": "raise",
                     "at_call": int(at_call), "exc": exc}])

    @classmethod
    def transient_io(cls, first_calls: int, point: str = "io.read",
                     exc: str = "OSError") -> "FaultPlan":
        """Fail the first K firings of an IO point, then recover — the
        retry-policy acceptance shape (recovery-after-K)."""
        return cls([{"point": point, "action": "raise",
                     "first_calls": int(first_calls), "exc": exc,
                     "message": f"injected transient IO error "
                                f"(first {first_calls} calls)"}])

    @classmethod
    def delay(cls, point: str, seconds: float,
              first_calls: int | None = None) -> "FaultPlan":
        """Sleep ``seconds`` at every firing of ``point`` (or only its
        first K) — the deterministic per-dispatch latency the
        overlap acceptance tests inject (``frame.dispatch``): a D-deep
        window must hide all but ~1/D of it, a blocking executor pays
        it per batch."""
        rule: dict = {"point": point, "action": "delay",
                      "seconds": float(seconds)}
        if first_calls is not None:
            rule["first_calls"] = int(first_calls)
        return cls([rule])

    @classmethod
    def burst(cls, count: int, point: str = "serve.tick",
              at_call: int | None = None) -> "FaultPlan":
        """Inject ``count`` extra requests in ONE serve tick (every
        firing of ``point``, or only its ``at_call``-th): the firing
        site receives the count as the return value and submits that
        many requests back-to-back — the deterministic overload spike
        the admission-control acceptance drives past queue capacity."""
        rule: dict = {"point": point, "action": "burst",
                      "count": int(count)}
        if at_call is not None:
            rule["at_call"] = int(at_call)
        return cls([rule])

    @classmethod
    def slow_client(cls, seconds: float, point: str = "serve.client",
                    first_calls: int | None = None) -> "FaultPlan":
        """A client that stalls ``seconds`` before each submit (or only
        its first K) — the deadline-shed path's chaos shape: requests
        age in the queue while the slow client dribbles load."""
        return cls.delay(point, seconds, first_calls=first_calls)

    @classmethod
    def oom(cls, point: str = "frame.dispatch", at_call: int = 1,
            nbytes: int = 2 << 30) -> "FaultPlan":
        """Raise a realistic ``XlaRuntimeError``-shaped
        ``RESOURCE_EXHAUSTED`` at one firing of ``point`` — the
        device-OOM recovery shape (the supervisor evicts unpinned HBM
        entries and retries; FAULTS.md)."""
        return cls([{"point": point, "action": "oom",
                     "at_call": int(at_call), "bytes": int(nbytes)}])

    @classmethod
    def corrupt_on_read(cls, point: str = "shards.read",
                        at_call: int = 1) -> "FaultPlan":
        """Bit-flip the file a read point is about to open (the firing
        must pass ``path=`` ctx)."""
        return cls([{"point": point, "action": "corrupt",
                     "at_call": int(at_call)}])

    @classmethod
    def from_env(cls) -> "FaultPlan | None":
        raw = os.environ.get(PLAN_ENV)
        if not raw:
            return None
        spec = json.loads(raw)
        if isinstance(spec, dict):
            spec = [spec]
        return cls(spec)

    def to_env(self) -> str:
        """JSON for ``TPUDL_FAULT_PLAN`` (subprocess arming)."""
        return json.dumps([r.to_dict() for r in self.rules])

    # -- the hot hook ------------------------------------------------------
    def fire(self, point: str, **ctx):
        matched = None
        with self._lock:
            for rule in self.rules:
                if rule.point != point:
                    continue
                rule.calls += 1
                if rule.matches(ctx):
                    rule.triggered += 1
                    matched = rule
                    self.fired.append(
                        {"point": point, "action": rule.action,
                         "call": rule.calls, **ctx})
                    break
        if matched is None:
            return
        try:  # forensics: an injected death must leave the same trail
            from tpudl.obs import flight as _flight

            _flight.record_error(
                "fault.injected", matched.message, point=point,
                action=matched.action, call=matched.calls,
                **{k: v for k, v in ctx.items()
                   if isinstance(v, (int, float, str, bool, type(None)))})
        # tpudl: ignore[swallowed-except] — guards the fault
        # breadcrumb; the injected fault below must still fire
        except Exception:
            pass
        if matched.action == "delay":
            # on the FIRING thread deliberately: a delayed dispatch
            # stage blocks its dispatch-window thread exactly like a
            # slow dispatch round-trip would, so overlap tests measure
            # the executor, not the harness
            time.sleep(matched.seconds)
            return None
        if matched.action == "burst":
            # chaos input, not a failure: the COUNT is returned to the
            # firing site (the serve load generator submits that many
            # extra requests in the same tick) so admission control is
            # tested by pressure, not by mocking the queue
            return matched.count
        if matched.action == "oom":
            raise oom_error(matched.nbytes or (2 << 30),
                            point=f"{point} call {matched.calls}")
        if matched.action == "sigterm":
            os.kill(os.getpid(), signal.SIGTERM)
            return  # the handler decides what dies; the firing returns
        if matched.action == "corrupt":
            path = ctx.get("path")
            if path:
                _flip_one_byte(str(path))
            return
        if matched.action == "unlink":
            # the concurrent-eviction race, made deterministic: delete
            # the file between the caller's manifest read and its open
            path = ctx.get("path")
            if path:
                try:
                    os.unlink(str(path))
                except OSError:
                    pass
            return
        raise _resolve_exc(matched.exc)(
            f"{matched.message} [{point} call {matched.calls}]")

    # -- arming ------------------------------------------------------------
    def armed(self):
        return _Armed(self)


class _Armed:
    def __init__(self, plan: FaultPlan):
        self._plan = plan

    def __enter__(self):
        arm(self._plan)
        return self._plan

    def __exit__(self, *exc):
        disarm()


def _flip_one_byte(path: str):
    """In-place single-byte flip at mid-file (deliberately NOT atomic —
    this IS the bit-rot being simulated)."""
    try:
        size = os.path.getsize(path)
        if size == 0:
            return
        at = size // 2
        with open(path, "r+b") as f:
            f.seek(at)
            b = f.read(1)
            f.seek(at)
            f.write(bytes([b[0] ^ 0xFF]))
    except OSError:
        pass


def arm(plan: FaultPlan):
    global _PLAN
    with _ARM_LOCK:
        _PLAN = plan
    return plan


def disarm():
    global _PLAN
    with _ARM_LOCK:
        _PLAN = None


def install_from_env() -> FaultPlan | None:
    """Arm the ``TPUDL_FAULT_PLAN`` plan, if any (subprocess entry)."""
    plan = FaultPlan.from_env()
    if plan is not None:
        arm(plan)
    return plan


def fire(point: str, **ctx):
    """The production-side hook: a no-op global check unless a plan is
    armed (never add work on this line — it sits on executor and train
    hot paths). Returns the matched rule's payload for data-bearing
    actions (``burst`` → its count), else ``None``."""
    plan = _PLAN
    if plan is not None:
        return plan.fire(point, **ctx)
    return None
