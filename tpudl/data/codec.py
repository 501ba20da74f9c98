"""Wire codecs: shrink the host→device representation of prepared batches.

Where the featurize executor sits ON the H2D wire (the July 2026
diagnosis; not re-measured on the current machine), every byte a batch
does not ship is throughput. A :class:`WireCodec` is the two-sided contract that makes
shipping fewer bytes safe:

- ``encode(arr)`` runs HOST-side in the executor's prepare stage and
  returns the smaller wire representation (uint8 pixels, bfloat16, ...);
- ``prologue(x)`` is a jax-traceable device-side restore that the
  executor fuses IN FRONT of the user's jitted fn (one program — XLA
  folds the cast/scale into the model's first conv, exactly like the
  reference spliced its spImageConverter fragment into the GraphDef).

Codecs are bit-controlled: ``u8`` with ``offset == 0`` reproduces the
float32 path EXACTLY (``float32(u8) * float32(scale)`` is one IEEE f32
multiply on either side of the wire), and refuses any batch it cannot
encode losslessly; ``bf16`` is lossy by declaration (relative error
≤ 2⁻⁸ per element, the bfloat16 mantissa).

Selection: pass a :class:`WireCodec`, a name (``"u8"``, ``"bf16"``,
``"identity"``), or ``"auto"`` — auto picks from the first packed
batch's DTYPE, never its values (the pick is pinned for the run):
uint8 → ``u8``; float32 → ``bf16`` on a slow wire, identity on a fast
one, judged by :func:`probe_wire_mbps`, a bare ``device_put`` of one
buffer (threshold ``TPUDL_DATA_BF16_WIRE_MBPS``).
``"u8"`` by name infers its scale from the first batch and REFUSES
non-exact batches — strictness by request. ``TPUDL_WIRE_CODEC`` is the
process-wide default ``Frame.map_batches`` falls back to.
"""

from __future__ import annotations

import os
import time
import warnings

import numpy as np

from tpudl.testing import tsan as _tsan

__all__ = [
    "CodecError",
    "WireCodec",
    "filter_unusable_donation_warning",
    "IdentityCodec",
    "U8Codec",
    "BF16Codec",
    "resolve_codec",
    "codec_from_key",
    "probe_wire_mbps",
    "CodecPlan",
]


class CodecError(ValueError):
    """A codec cannot represent this batch losslessly (caller falls back
    or surfaces the misconfiguration — never silent value drift)."""


class WireCodec:
    """One host→device wire representation. Subclasses implement
    ``encode`` (host, numpy → numpy), ``prologue`` (device, jittable
    restore to float32 semantics) and ``key`` (a JSON-serializable
    identity tuple — shard manifests persist it so a warm cache replay
    reconstructs the exact prologue, see tpudl.data.shards)."""

    name = "abstract"

    def encode(self, arr: np.ndarray) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    def decode_array(self, arr: np.ndarray) -> np.ndarray:
        """Host-side inverse of ``encode`` (tests, host-fn fallback);
        MUST apply the same op sequence as ``prologue`` so host and
        device restores agree bitwise where exactness is promised."""
        raise NotImplementedError  # pragma: no cover

    def prologue(self, x):  # pragma: no cover - abstract
        raise NotImplementedError

    def key(self) -> tuple:
        return (self.name,)

    def dense_nbytes(self, encoded: np.ndarray) -> int:
        """Bytes of the float32 tensor ``prologue`` reconstitutes — the
        counterfactual the wire would carry without this codec (the
        ``data.wire.bytes_dense`` counter's contribution)."""
        return int(encoded.size) * 4

    def __repr__(self):
        return f"{type(self).__name__}({self.key()!r})"


class IdentityCodec(WireCodec):
    """Ship the packed batch as-is (today's behavior, the fallback)."""

    name = "identity"

    def encode(self, arr: np.ndarray) -> np.ndarray:
        return np.asarray(arr)

    def decode_array(self, arr: np.ndarray) -> np.ndarray:
        return np.asarray(arr)

    def prologue(self, x):
        return x

    def dense_nbytes(self, encoded: np.ndarray) -> int:
        return int(encoded.nbytes)  # no shrink claimed


class U8Codec(WireCodec):
    """uint8 pixels + (scale, offset) — 4× fewer wire bytes than the
    float32 the loaders used to ship, restored on device as
    ``f32(u8) * scale + offset`` fused into the model program.

    Exactness: with ``offset == 0`` (the default) the restore is ONE
    IEEE-754 f32 multiply — numpy host-side and XLA device-side produce
    bit-identical results, so the RESTORED PIXELS match the float32
    path at atol=0 for uint8-sourced images (tests pin this). Two
    caveats, both documented in DATA.md: a nonzero offset may fuse to
    an FMA on device (≤1 ulp), and a downstream program jitted TOGETHER
    with the prologue may be reassociated by XLA across the boundary
    (e.g. a scalar multiply hoisted out of a reduction) — elementwise-
    identical inputs, f32-rounding-level output drift (~1e-7 relative,
    measured).

    ``encode`` of a float32 batch INVERTS the loader's normalize and
    verifies losslessness by re-applying the restore host-side and
    comparing bitwise; any mismatch raises :class:`CodecError` rather
    than shipping drifted values. uint8 batches pass straight through.
    """

    name = "u8"

    def __init__(self, scale: float = 1.0, offset: float = 0.0):
        # pinned to f32 so host verify and device prologue use the SAME
        # constant (a float64 scale would round differently on device)
        self.scale = float(np.float32(scale))
        self.offset = float(np.float32(offset))
        if self.scale == 0.0:
            raise CodecError("u8 codec scale must be nonzero")

    def key(self) -> tuple:
        return (self.name, self.scale, self.offset)

    def _restore_np(self, q8: np.ndarray) -> np.ndarray:
        # mirror prologue op-for-op (skip no-op affine terms so the
        # exactness claim covers the same instruction sequence)
        y = q8.astype(np.float32)
        if self.scale != 1.0:
            y = y * np.float32(self.scale)
        if self.offset != 0.0:
            y = y + np.float32(self.offset)
        return y

    def encode(self, arr: np.ndarray) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:
            return arr
        if arr.dtype != np.float32:
            raise CodecError(
                f"u8 codec encodes uint8/float32 batches, got {arr.dtype}")
        q = np.rint((arr.astype(np.float64) - self.offset) / self.scale)
        if q.size and (q.min() < 0 or q.max() > 255):
            raise CodecError(
                f"u8 codec: values outside u8×{self.scale}+{self.offset} "
                f"range (min {q.min()}, max {q.max()})")
        q8 = q.astype(np.uint8)
        if not np.array_equal(self._restore_np(q8), arr):
            raise CodecError(
                "u8 codec cannot losslessly encode this batch (values are "
                f"not exactly uint8 × {self.scale} + {self.offset}); use "
                "'bf16' or 'identity', or fix the loader to emit raw uint8 "
                "(imageIO.createNativeImageLoader(output_dtype='uint8'))")
        return q8

    def decode_array(self, arr: np.ndarray) -> np.ndarray:
        return self._restore_np(np.asarray(arr))

    def prologue(self, x):
        import jax.numpy as jnp

        y = x.astype(jnp.float32)
        if self.scale != 1.0:
            y = y * jnp.float32(self.scale)
        if self.offset != 0.0:
            y = y + jnp.float32(self.offset)
        return y

    @classmethod
    def infer(cls, arr: np.ndarray) -> "U8Codec | None":
        """The codec that losslessly encodes ``arr``: raw uint8 → scale
        1; float32 tries the loader conventions — ``scale=1/255``
        FIRST when the batch's range says 'normalized' (max ≤ 1: a
        degenerate integral batch, e.g. all-black images, encodes
        under BOTH scales, and pinning scale=1 there would make every
        later generic /255 batch raise mid-run), ``scale=1`` first
        otherwise. Inference is a first-batch heuristic by nature; a
        loader that declares ``wire_scale`` or an explicit
        ``U8Codec(scale=...)`` is the unambiguous spelling."""
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:
            return cls(1.0)
        if arr.dtype != np.float32:
            return None
        normalized = arr.size == 0 or float(np.max(np.abs(arr))) <= 1.0
        scales = ((1.0 / 255.0, 1.0) if normalized
                  else (1.0, 1.0 / 255.0))
        for scale in scales:
            codec = cls(scale)
            try:
                codec.encode(arr)
                return codec
            except CodecError:
                continue
        return None


class BF16Codec(WireCodec):
    """bfloat16 on the wire — 2× fewer bytes for float32 batches that
    are NOT exact uint8 multiples (augmented/whitened inputs). Lossy by
    declaration: bfloat16 keeps 8 significand bits, so each element's
    relative error is ≤ 2⁻⁸ (and integers up to 256 are exact). The
    documented test tolerance is rtol=2⁻⁷ (one rounding on encode, one
    representable-value cast back). uint8 batches pass through (already
    smaller than bf16)."""

    name = "bf16"
    RTOL = 2.0 ** -7  # documented round-trip tolerance

    def _bf16(self):
        import ml_dtypes  # ships with jax

        return ml_dtypes.bfloat16

    def encode(self, arr: np.ndarray) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:
            return arr
        if arr.dtype != np.float32:
            raise CodecError(
                f"bf16 codec encodes uint8/float32 batches, got {arr.dtype}")
        return arr.astype(self._bf16())

    def decode_array(self, arr: np.ndarray) -> np.ndarray:
        return np.asarray(arr).astype(np.float32)

    def prologue(self, x):
        import jax.numpy as jnp

        return x.astype(jnp.float32)

    def dense_nbytes(self, encoded: np.ndarray) -> int:
        return int(encoded.size) * 4


_WIRE_MBPS_CACHE: dict = {}
_WIRE_MBPS_LOCK = _tsan.named_lock("data.codec.wire_probe")


def probe_wire_mbps(mb: int = 4) -> float | None:
    """H2D bandwidth of the default backend in MB/s — a bare
    ``device_put`` of one contiguous buffer, waited for,
    sized small (4 MB) and cached per process so 'auto' codec selection
    costs one probe, ever. ``TPUDL_WIRE_MBPS`` overrides (tests, and
    operators who already know their link). None when probing fails —
    callers must treat that as 'unknown', not 'fast'."""
    env = os.environ.get("TPUDL_WIRE_MBPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    with _WIRE_MBPS_LOCK:
        if "mbps" in _WIRE_MBPS_CACHE:
            return _WIRE_MBPS_CACHE["mbps"]
        try:
            import jax

            x = np.zeros(mb << 20, dtype=np.uint8)
            # tpudl: ignore[lock-held-blocking] — the probe IS the
            # blocking op: the lock serializes "one probe, ever", and
            # concurrent probes would skew each other's timing (waiters
            # get the cached result the moment it exists)
            jax.block_until_ready(jax.device_put(x[: 1 << 20]))  # warm
            t0 = time.perf_counter()
            # tpudl: ignore[lock-held-blocking] — see above: the timed
            # transfer must run under the probe lock
            jax.block_until_ready(jax.device_put(x))
            mbps = mb / (time.perf_counter() - t0)
        # tpudl: ignore[swallowed-except] — no backend / wedged RPC
        # means UNKNOWN wire speed; None makes every caller treat the
        # wire as not-fast (the conservative codec pick)
        except Exception:
            mbps = None
        _WIRE_MBPS_CACHE["mbps"] = mbps
        return mbps


def _bf16_wire_threshold() -> float:
    try:
        return float(os.environ.get("TPUDL_DATA_BF16_WIRE_MBPS", "")
                     or 1000.0)
    except ValueError:
        return 1000.0


def _auto_pick(arr: np.ndarray) -> WireCodec:
    """Auto selection for one packed column — STRUCTURAL only (dtype,
    never sample values): the pick is pinned from the first batch, so
    a value-dependent choice (e.g. 'batch 0 happened to be exactly
    uint8×scale') would crash batch N when augmented floats stop being
    exact. uint8 columns ship as u8 (every batch of a uint8 column is
    uint8 — lossless by construction); float32 columns ship bf16 when
    the measured wire is slower than ``TPUDL_DATA_BF16_WIRE_MBPS``
    (default 1000 MB/s), identity when the wire is fast or
    unknown (never trade accuracy for a link that was not measured to
    need it). Exact-u8 float encoding is the explicit ``'u8'`` /
    ``U8Codec(scale=...)`` contract, which documents its strictness."""
    arr = np.asarray(arr)
    if arr.dtype == np.uint8:
        return U8Codec(1.0)
    if arr.dtype == np.float32:
        mbps = probe_wire_mbps()
        if mbps is not None and mbps < _bf16_wire_threshold():
            return BF16Codec()
    return IdentityCodec()


def resolve_codec(spec) -> "WireCodec | str | None":
    """Codec spec → instance, or a deferred sentinel string resolved
    per column from the first packed batch by :class:`CodecPlan`:
    ``"auto"`` (pick freely) and ``"u8"`` (infer the scale — raw uint8,
    exact ``u8×1`` or exact ``u8/255`` floats — and REFUSE anything
    else; an explicit ``U8Codec(scale=...)`` pins the scale instead)."""
    if spec is None:
        return None
    if isinstance(spec, WireCodec):
        return spec
    if spec in ("auto", "u8"):
        return spec
    if spec == "identity":
        return IdentityCodec()
    if spec == "bf16":
        return BF16Codec()
    if spec == "tokens":
        # lazy: tpudl.text.codec imports this module, so the dependency
        # must stay one-way at import time
        from tpudl.text.codec import TokenCodec

        return TokenCodec()
    if isinstance(spec, str):
        raise CodecError(
            f"unknown wire codec {spec!r}; known: "
            "['auto', 'bf16', 'identity', 'tokens', 'u8']")
    raise CodecError(f"wire codec must be a name or WireCodec, got "
                     f"{type(spec).__name__}")


def codec_from_key(key) -> WireCodec:
    """Inverse of ``WireCodec.key()`` — how a shard manifest's persisted
    codec identity becomes the prologue for a warm replay."""
    key = tuple(key)
    name = key[0]
    if name == "identity":
        return IdentityCodec()
    if name == "u8":
        return U8Codec(*key[1:])
    if name == "bf16":
        return BF16Codec()
    if name == "tokens":
        from tpudl.text.codec import TokenCodec

        pad_id, vocab_size, wire = key[1:]
        return TokenCodec(pad_id=pad_id, vocab_size=vocab_size,
                          wire_dtype=wire)
    raise CodecError(f"unknown codec key {key!r}")


def spec_token(spec) -> str:
    """Stable string identity of a codec spec, for cache keys."""
    if spec is None:
        return "none"
    if isinstance(spec, WireCodec):
        return repr(spec.key())
    return str(spec)


_DONATION_WARNING_MSG = "Some donated buffers were not usable"


def filter_unusable_donation_warning():
    """XLA warns (once per compile) when a donated buffer cannot be
    reused — routine on codec paths whose encoded inputs are smaller
    than any output (a u8 wire buffer can never alias an f32 feature
    map), and harmless: an unusable donation is simply ignored. The
    executor owns every donating jit it builds, so it installs ONE
    message-anchored ignore when a donating wrapper is built. The
    presence check keeps ``warnings.filters`` from growing a duplicate
    entry per program (and re-installs after a test harness restored
    the filter state, where a module latch would go stale)."""
    for f in warnings.filters:
        if f[0] == "ignore" and f[1] is not None \
                and getattr(f[1], "pattern", None) == _DONATION_WARNING_MSG:
            return
    warnings.filterwarnings("ignore", message=_DONATION_WARNING_MSG)


_warned_host_codec = False


def warn_host_fn_codec_once():
    global _warned_host_codec
    if _warned_host_codec:
        return
    _warned_host_codec = True
    warnings.warn(
        "wire_codec requested but fn is a HOST function — the device "
        "prologue cannot run, so the codec is disabled for this call. "
        "Pass device_fn=True if fn wraps a jitted call.",
        RuntimeWarning, stacklevel=4)


class CodecPlan:
    """Per-``map_batches``-run codec state: one resolved codec per input
    column, the wrapped device fn, and the wire-byte accounting.

    Thread-safe where it must be: ``encode`` runs on the executor's
    prepare-pool threads for DIFFERENT batches concurrently; per-column
    resolution ('auto') happens once under a lock on whichever batch
    arrives first (every batch of a column packs to the same dtype, so
    the choice is order-independent). ``wrap`` is called on the consumer
    thread after at least one batch was prepared, so resolution is
    always complete by then; the wrapped jit is cached ON the user's fn
    keyed by the resolved codec keys (the ``_fused_wrapper`` retention
    pattern — the wrapper lives exactly as long as fn does).

    Counters (process-wide, :mod:`tpudl.obs.metrics`):

    - ``data.wire.bytes_shipped`` — encoded bytes actually crossing;
    - ``data.wire.bytes_dense``  — the float32-equivalent bytes the
      prologue reconstitutes (the no-codec counterfactual);
    - ``data.wire.bytes_saved``  — dense − shipped;
    - ``data.codec.encode_seconds`` — host encode cost (histogram);
    - ``data.codec.<name>.batches`` — per-codec batch counts.
    """

    def __init__(self, spec, n_cols: int, report=None):
        base = resolve_codec(spec)
        self._deferred = base if isinstance(base, str) else None
        self._codecs: list[WireCodec | None] = [
            None if self._deferred else base for _ in range(n_cols)]
        self._lock = _tsan.named_lock("data.codec.plan")
        self._report = report

    # -- resolution --------------------------------------------------------
    def _resolve_one(self, arr: np.ndarray) -> WireCodec:
        if self._deferred == "auto":
            return _auto_pick(arr)
        # "u8": infer the scale but NEVER fall back silently — the user
        # asked for the 4× wire shrink, a quiet identity would fake it
        codec = U8Codec.infer(arr)
        if codec is None:
            raise CodecError(
                "wire_codec='u8': batch is not losslessly uint8-encodable "
                f"(dtype {np.asarray(arr).dtype}); pass U8Codec(scale=...) "
                "for a custom normalize, or 'bf16'/'auto'")
        return codec

    def _codec_for(self, col: int, arr: np.ndarray) -> WireCodec:
        c = self._codecs[col]
        if c is not None:
            return c
        with self._lock:
            if self._codecs[col] is None:
                self._codecs[col] = self._resolve_one(arr)
            return self._codecs[col]

    def resolved(self) -> bool:
        return all(c is not None for c in self._codecs)

    def keys(self) -> list:
        """JSON-serializable per-column codec keys (shard-manifest
        form); requires resolution."""
        return [list(c.key()) for c in self._codecs]

    def adopt(self, keys) -> None:
        """Pin the plan to a persisted resolution (a warm shard cache's
        manifest meta) — the replay MUST restore with the codecs the
        shards were encoded with, not a fresh auto pick."""
        codecs = [codec_from_key(k) for k in keys]
        if len(codecs) != len(self._codecs):
            raise CodecError(
                f"cached codec count {len(codecs)} != input columns "
                f"{len(self._codecs)}")
        with self._lock:
            self._codecs = codecs

    # -- host side ---------------------------------------------------------
    def encode(self, col: int, arr: np.ndarray) -> np.ndarray:
        from tpudl.obs import metrics as _m

        codec = self._codec_for(col, arr)
        t0 = time.perf_counter()
        enc = codec.encode(arr)
        _m.histogram("data.codec.encode_seconds").observe(
            time.perf_counter() - t0)
        _m.counter(f"data.codec.{codec.name}.batches").inc()
        return enc

    def record_shipped(self, arrays) -> None:
        """Wire-byte accounting for one prepared batch — called for
        encoded AND cache-hit batches (a replayed shard still crosses
        the wire)."""
        from tpudl.obs import attribution as _attr
        from tpudl.obs import metrics as _m

        shipped = dense = 0
        for col, arr in enumerate(arrays):
            codec = self._codecs[col] or IdentityCodec()
            shipped += int(np.asarray(arr).nbytes)
            dense += codec.dense_nbytes(np.asarray(arr))
        _m.counter("data.wire.bytes_shipped").inc(shipped)
        # attribution pairing (tpudl.obs.attribution): the SAME amount
        # as the global counter, so per-scope sums + unattributed
        # reconcile exactly against data.wire.bytes_shipped
        _attr.charge("wire_bytes", shipped)
        _m.counter("data.wire.bytes_dense").inc(dense)
        if dense > shipped:
            _m.counter("data.wire.bytes_saved").inc(dense - shipped)
        if self._report is not None:
            self._report.gauge("wire_batch_bytes", shipped)

    # -- device side -------------------------------------------------------
    def wrap(self, fn, donate: bool = False):
        """``fn`` with the per-column prologues fused in front, as ONE
        jitted program. Identity-only plans return ``fn`` untouched (no
        extra jit layer, bit-for-bit today's path — which also means no
        donation: the executor never re-jits a user's fn just to carry
        ``donate_argnums``). With ``donate=True`` every wire input is
        donated (``jax.jit(..., donate_argnums=...)``): XLA may reuse
        the staged buffers for outputs/temps so steady-state dispatch
        allocates nothing extra. Donation changes no values (the u8
        atol=0 restore guarantee is pinned donation-on and -off); a
        donated buffer that cannot alias any output (a u8 wire batch
        restoring to f32) is simply ignored by XLA. The caller
        (Frame.map_batches) hands donating programs writable COPIES of
        shard-cache hits, never the cache's read-only mmap. The wrapper
        is cached on ``fn`` itself keyed by the resolved codec keys +
        the donate flag, so repeated transforms reuse one compiled
        program."""
        codecs = list(self._codecs)
        if any(c is None for c in codecs):
            raise CodecError("codec plan not resolved (no batch encoded "
                             "and no cache meta adopted)")
        if all(c.name == "identity" for c in codecs):
            return fn
        cache_key = (tuple(c.key() for c in codecs), bool(donate))
        per_fn = getattr(fn, "_tpudl_codec_wrap", None)
        if per_fn is not None and cache_key in per_fn:
            return per_fn[cache_key]
        import jax

        def wrapped(*xs):
            return fn(*[c.prologue(x) for c, x in zip(codecs, xs)])

        if donate:
            filter_unusable_donation_warning()
            wrapped = jax.jit(
                wrapped, donate_argnums=tuple(range(len(codecs))))
        else:
            wrapped = jax.jit(wrapped)

        try:
            if per_fn is None:
                per_fn = fn._tpudl_codec_wrap = {}
            per_fn[cache_key] = wrapped
        except (AttributeError, TypeError):  # fn rejects attrs: uncached
            pass
        return wrapped

    def names(self) -> list[str]:
        return [c.name if c is not None else "auto" for c in self._codecs]
