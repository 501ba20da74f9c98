"""Shape-bucket ladders: snap ragged batch/sequence lengths to O(log n)
compiled program signatures.

Every novel leading-dim shape a jitted program sees costs one retrace +
one XLA compile — 20–40 s per CNN program on a v5e (chip_smoke.py,
PERF.md). The traceck sentinel *detects* that storm (PR 13);
a :class:`BucketLadder` *prevents* it: a batch of ``n`` rows pads up to
the smallest ladder rung ≥ ``n`` (repeating row 0, the bitwise-honest
``mesh.pad_batch`` discipline — pad rows are stripped from the outputs
before the caller sees them), so a workload of arbitrary ragged sizes
runs through a handful of precompiled programs instead of one compile
per novel shape.

Ladders (``TPUDL_COMPILE_BUCKETS``, or the ``buckets=`` kwarg on
``Frame.map_batches``):

- ``pow2ish`` (the ``1``/``auto`` default): powers of two plus the
  3·2^k midpoints — 1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, … —
  bounded pad waste ≤ 1/3 of the batch, ~2·log2(n) rungs;
- ``pow2``: pure powers of two (pad waste ≤ ~1/2, log2(n) rungs —
  the tightest program count, the zero-retrace sweep's pick);
- an explicit comma list (``"8,16,32,64"``): serving deployments that
  declared their shapes; sizes past the top rung stay EXACT (honest:
  an undeclared giant batch gets its own program, never silent
  truncation);
- ``0`` / ``off`` / unset: bucketing disabled (every shape exact —
  today's behavior).

Numpy-only at import: the ladder runs on the executor's prepare path
and in the offline validator, neither of which may drag jax in.
"""

from __future__ import annotations

import math
import os

import numpy as np

__all__ = ["BucketLadder", "resolve_ladder", "pad_to", "count_pad_rows",
           "DEFAULT_SPEC"]

DEFAULT_SPEC = "pow2ish"

_OFF = ("", "0", "off", "none", "false")


class BucketLadder:
    """One bucket ladder: ``pick(n)`` → the dispatch size for an
    ``n``-row batch. Generated specs (``pow2ish``/``pow2``) are
    closed-form and unbounded; explicit rung lists return ``n`` itself
    past their top rung (exact dispatch, never a lie)."""

    def __init__(self, spec: str = DEFAULT_SPEC,
                 rungs=None):
        if rungs is not None:
            rungs = sorted({int(r) for r in rungs})
            if not rungs or rungs[0] < 1:
                raise ValueError(f"bucket rungs must be >= 1: {rungs}")
            self.spec = ",".join(str(r) for r in rungs)
            self.rungs: tuple[int, ...] | None = tuple(rungs)
            return
        if spec not in ("pow2", "pow2ish"):
            raise ValueError(
                f"unknown bucket-ladder spec {spec!r} (want 'pow2', "
                f"'pow2ish', or an explicit comma list)")
        self.spec = spec
        self.rungs = None

    def pick(self, n: int) -> int:
        """Smallest rung ≥ ``n`` (``n`` itself past an explicit
        ladder's top rung; ``n <= 0`` is returned unchanged)."""
        n = int(n)
        if n <= 0:
            return n
        if self.rungs is not None:
            for r in self.rungs:
                if r >= n:
                    return r
            return n  # past the declared top: exact, honest
        p = 1 << max(0, math.ceil(math.log2(n)))
        if self.spec == "pow2ish" and p >= 4 and n <= (3 * p) // 4:
            return (3 * p) // 4
        return p

    def is_rung(self, n: int) -> bool:
        return int(n) > 0 and self.pick(int(n)) == int(n)

    def rungs_up_to(self, n: int) -> list[int]:
        """Every distinct rung the ladder can emit for sizes 1..n —
        the declared-signature set precompilation walks."""
        out, seen = [], set()
        for i in range(1, int(n) + 1):
            r = self.pick(i)
            if r not in seen:
                seen.add(r)
                out.append(r)
        return out

    def to_meta(self) -> dict:
        """JSON-shippable identity (the program manifest persists it so
        the validator can audit shapes↔ladder consistency)."""
        return {"spec": self.spec,
                "rungs": list(self.rungs) if self.rungs else None}

    def __repr__(self):
        return f"BucketLadder({self.spec!r})"


def resolve_ladder(value=None) -> BucketLadder | None:
    """The one resolution rule: explicit value beats the
    ``TPUDL_COMPILE_BUCKETS`` env, and ``None`` means *consult the
    env* (unset env = bucketing OFF — opt-in, like the AOT store).
    Accepts a :class:`BucketLadder`, a spec string, ``True`` (the
    default ladder) or ``False``/``"off"``."""
    if isinstance(value, BucketLadder):
        return value
    if value is None:
        value = os.environ.get("TPUDL_COMPILE_BUCKETS", "")
    if value is True:
        return BucketLadder(DEFAULT_SPEC)
    if value is False:
        return None
    spec = str(value).strip().lower()
    if spec in _OFF:
        return None
    if spec in ("1", "auto", "default", "pow2ish"):
        return BucketLadder("pow2ish")
    if spec == "pow2":
        return BucketLadder("pow2")
    try:
        rungs = [int(s) for s in spec.split(",") if s.strip()]
    except ValueError:
        raise ValueError(
            f"TPUDL_COMPILE_BUCKETS={value!r} is neither a known ladder "
            f"spec (pow2, pow2ish, 1, off) nor a comma list of rungs")
    return BucketLadder(rungs=rungs)


def pad_to(arr: np.ndarray, target: int) -> np.ndarray:
    """Pad the leading dim up to ``target`` rows by repeating row 0 —
    the exact ``mesh.pad_batch`` discipline (realistic dtype/scale for
    compiled kernels, bitwise-honest: real rows are untouched and pad
    rows are stripped downstream via the executor's ``n_pad``
    plumbing)."""
    n = int(arr.shape[0])
    if n >= int(target):
        return arr
    pad = np.repeat(
        arr[:1] if n else np.zeros_like(arr, shape=(1, *arr.shape[1:])),
        int(target) - n, axis=0)
    return np.concatenate([arr, pad], axis=0)


def count_pad_rows(n: int) -> None:
    """Publish bucket padding into the process registry
    (``compile.bucket_pad_rows``) — the operator's measure of what the
    O(log n) program count costs in shipped rows."""
    from tpudl.obs import metrics as _m

    _m.counter("compile.bucket_pad_rows").inc(int(n))
