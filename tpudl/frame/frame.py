"""Minimal columnar batch abstraction — the Spark DataFrame stand-in.

SURVEY.md §7.1 item 3: "intentionally small — transport, not a query
engine". A Frame is an ordered dict of equal-length named columns. Numeric
columns are numpy arrays; ragged/struct/string columns are object arrays.
``map_batches`` is the executor: it packs host batches, pads and shards
them over the mesh's data axis, runs ONE jitted function per batch (the
reference's one-native-call-per-block invariant, SURVEY.md §3.2), and
appends the outputs as new columns.

The reference equivalent is the Spark DataFrame + TensorFrames MapBlocks
path (ref: sparkdl graph/tensorframes_udf.py, tf_image.py:_transform).
"""

from __future__ import annotations

import os
import sys
import time
import warnings
from collections import deque
from collections.abc import Callable, Iterator, Mapping, Sequence
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from tpudl.testing import faults as _faults

__all__ = ["Frame", "LazyColumn", "concat"]


class LazyColumn:
    """A deferred column: elements materialize per access, so host RAM in
    ``map_batches`` is O(batch) no matter the row count — the lazy input
    plane replacing the reference's ``sc.binaryFiles`` partitioned RDD
    (ref: sparkdl imageIO.py filesToDF ~L200; SURVEY.md §5.8). Concrete
    sources implement ``__len__`` and ``_get(indices) -> object ndarray``
    (see tpudl.image.imageIO.LazyFileColumn)."""

    dtype = np.dtype(object)

    def __len__(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def _get(self, indices: np.ndarray) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    def __getitem__(self, idx):
        n = len(self)
        if isinstance(idx, slice):
            return self._get(np.arange(*idx.indices(n)))
        arr = np.asarray(idx)
        if arr.ndim == 0:
            return self._get(np.array([int(arr)]))[0]
        if arr.dtype == bool:
            arr = np.nonzero(arr)[0]
        return self._get(arr.astype(np.intp))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def subset(self, indices) -> "LazyColumn":
        """A LAZY row-subset view (used by Frame.filter_rows/dropna):
        keeps only the index mapping, so filtering a million-file column
        costs O(rows) indices, not O(dataset) decoded payloads."""
        return _SubsetLazyColumn(self, np.asarray(indices, dtype=np.intp))

    def validity_mask(self):
        """Optional cheap per-row validity (True = row is not null)
        WITHOUT materializing values — lets ``null_mask`` skip the
        decode scan entirely, so ``dropna().map_batches(...)`` decodes
        each surviving row exactly once (round-3 verdict weak #4).
        Returns None when unknown (caller falls back to a value scan);
        sources that can probe override (LazyFileColumn)."""
        return None

    def fingerprint(self) -> str | None:
        """Optional cheap content identity WITHOUT materializing values
        — the prepared-batch cache (``map_batches(cache_dir=...)``)
        keys on it so a changed source re-prepares instead of replaying
        stale shards. None = unknown (the caller must supply an
        explicit ``cache_key``); file-backed sources override
        (LazyFileColumn hashes paths + sizes + mtimes)."""
        return None


class _SubsetLazyColumn(LazyColumn):
    def __init__(self, base: LazyColumn, indices: np.ndarray):
        self._base = base
        self._indices = indices

    def __len__(self) -> int:
        return len(self._indices)

    def _get(self, indices: np.ndarray) -> np.ndarray:
        return self._base._get(self._indices[indices])

    def validity_mask(self):
        base = self._base.validity_mask()
        return None if base is None else base[self._indices]

    def fingerprint(self):
        base = self._base.fingerprint()
        if base is None:
            return None
        import hashlib

        return hashlib.sha1(
            base.encode() + self._indices.tobytes()).hexdigest()


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class _PipelineInfeed:
    """K-deep bounded infeed fed by an N-worker prepare pool: up to
    ``depth`` batches are packed/decoded (and, on the mesh path,
    host→device-transferred) in flight, by up to ``workers`` concurrent
    threads, while the consumer dispatches compute (the tf.data
    parallel-prepare + prefetch design, Murray et al. 2021; replaces the
    round-5 one-deep single-worker double buffer whose serialized PIL
    decode gated the whole executor). Futures are consumed in submission
    order, so batch order — and therefore output row order — is
    preserved no matter which worker finishes first.

    Backpressure: at most ``depth`` prepared batches exist at once, so
    host RAM stays O(depth · batch) at any dataset size."""

    def __init__(self, prepare: Callable, spans: Sequence[tuple[int, int]],
                 depth: int = 2, workers: int = 2, report=None):
        self._prepare = prepare
        self._spans = spans
        self._depth = max(1, int(depth))
        self._ex = ThreadPoolExecutor(
            max_workers=max(1, min(int(workers), self._depth)),
            thread_name_prefix="tpudl-infeed")
        self._futs: deque = deque()
        self._next = 0
        self._report = report
        while self._next < min(self._depth, len(spans)):
            self._submit()

    def _submit(self):
        from tpudl.obs import attribution as _attr

        # the submitter's attribution scope rides onto the worker: a
        # contextvar does not cross the pool boundary by itself, and
        # the prepare path publishes wire/row charges that must land
        # in the SUBMITTING run's ledger row (OBSERVABILITY.md)
        self._futs.append(self._ex.submit(
            _attr.carry(self._prepare), *self._spans[self._next]))
        self._next += 1

    def get(self, i: int):
        fut = self._futs.popleft()
        if self._report is not None:
            # ready-batch count at the moment the consumer takes one:
            # a depth pinned at 0 means the pool can't keep up (host-
            # bound); pinned at depth-1 means the device is the gate
            self._report.gauge("queue_depth",
                               int(fut.done())
                               + sum(f.done() for f in self._futs))
        t0 = time.perf_counter()
        try:
            out = fut.result()
        except BaseException:
            self.close()
            raise  # the worker's original exception, not a pool wrapper
        if self._report is not None:
            self._report.add("infeed_wait", time.perf_counter() - t0)
        if self._next < len(self._spans):
            self._submit()
        elif not self._futs:
            self._ex.shutdown(wait=False)
        return out

    def close(self):
        """Release the pool even when the consumer loop unwinds early
        (fn raised mid-batch) — queued prepares are cancelled and the
        non-daemon workers exit as soon as any in-flight prepare
        finishes, so nothing lingers reading/transferring."""
        for f in self._futs:
            f.cancel()
        self._futs.clear()
        self._ex.shutdown(wait=False, cancel_futures=True)


class _DispatchWindow:
    """D-deep in-flight dispatch window — the futures-not-syncs executor
    core (ROADMAP item 2). The consumer SUBMITS dispatch calls onto a
    small pool and only blocks once ``depth`` results are already in
    flight, so the blocking per-dispatch host round-trip for batch N
    rides under the dispatches of N+1..N+D instead of serializing the
    loop. Results are consumed strictly in submission order (the output
    row order is untouched, and bit-identity with depth 1 is structural:
    the same per-batch programs run, only their round-trips overlap).

    The consumer's blocked time lands in the ``dispatch_wait`` stage —
    the UNHIDDEN dispatch residue, the analogue of ``infeed_wait`` on
    the prepare side — while the pool threads' ``dispatch`` stage
    seconds become pool-summed (like ``prepare``, they may exceed wall
    time; tpudl.obs.roofline reads ``dispatch_wait`` when present so
    overlapped time is not attributed twice). ``dispatch_inflight`` is
    gauged at every submit; its max can never exceed ``depth``.

    The first dispatch runs alone (the window stays at 1 until the
    first result is consumed): one thread traces/compiles the program,
    and the outfeed mode is picked before the window floods."""

    def __init__(self, depth: int, report):
        self._depth = max(1, int(depth))
        self._ex = ThreadPoolExecutor(max_workers=self._depth,
                                      thread_name_prefix="tpudl-dispatch")
        self._futs: deque = deque()
        self._report = report
        self._primed = False

    def __len__(self) -> int:
        return len(self._futs)

    def full(self) -> bool:
        if not self._primed:
            return bool(self._futs)  # warmup: one dispatch at a time
        return len(self._futs) >= self._depth

    def submit(self, call):
        from tpudl.obs import attribution as _attr

        # carry the consumer's attribution scope onto the dispatch
        # thread (dispatch_s and compile_s charges happen there)
        self._futs.append(self._ex.submit(_attr.carry(call)))
        self._report.gauge("dispatch_inflight", len(self._futs))

    def pop(self):
        """Oldest in-flight dispatch's (result, n_pad), in submission
        order. Blocks only when that dispatch is still in its round
        trip — the wait IS the unhidden residue, accounted as its own
        ``dispatch_wait`` stage (deliberately NOT ``dispatch``: the
        pool already timed the call there)."""
        fut = self._futs.popleft()
        self._primed = True
        with self._report.stage("dispatch_wait"):
            try:
                out = fut.result()
            except BaseException:
                self.close()
                raise  # the dispatch thread's original exception
        return out

    def close(self):
        """Release the pool on every exit path (mirrors
        _PipelineInfeed.close): queued dispatches are cancelled and the
        workers exit as soon as any in-flight call returns."""
        for f in self._futs:
            f.cancel()
        self._futs.clear()
        self._ex.shutdown(wait=False, cancel_futures=True)


def _start_host_copies(result) -> None:
    """Start the device→host copy of every output of one dispatch, ON
    the thread that issued it — D2H of batch N then overlaps the
    dispatch of N+1..N+D (and, at depth 1, the next batch's prepare),
    for BOTH outfeed modes: the windowed drain's ``np.asarray`` and the
    accumulated fetch both find their copies already in flight. Host
    arrays (host fns) have no async copy and need none."""
    for r in result:
        if hasattr(r, "copy_to_host_async"):
            r.copy_to_host_async()


def _is_device_fn(fn) -> bool:
    """Jitted/device-fn detection: any ``jax.stages.Wrapped`` (jit,
    pjit, AOT wrappers) counts, plus the legacy ``lower`` probe for
    compiled executables. A plain-python wrapper AROUND a jitted call
    is still undetectable — ``map_batches(device_fn=True)`` is the
    explicit override (and the executor warns once when outputs come
    back as device arrays anyway)."""
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            if isinstance(fn, jax.stages.Wrapped):
                return True
        # tpudl: ignore[swallowed-except] — jax API drift guard: an
        # exotic jax falls through to the hasattr heuristic below
        except Exception:  # pragma: no cover - jax API drift
            pass
    return hasattr(fn, "lower")


_warned_device_outputs = False


def _warn_device_outputs_once():
    global _warned_device_outputs
    if _warned_device_outputs:
        return
    _warned_device_outputs = True
    warnings.warn(
        "map_batches classified fn as a HOST function (prefetch and "
        "fused dispatch disabled) but its outputs are device arrays — "
        "fn likely wraps a jitted call the heuristic cannot see. Pass "
        "device_fn=True (or prefetch=True) to enable the pipelined "
        "executor.", RuntimeWarning, stacklevel=3)


def _fused_wrapper(fn: Callable, m: int, *, n_args: int | None = None,
                   donate: bool = False) -> Callable:
    """ONE compiled program that runs ``m`` microbatches per dispatch:
    inputs are stacked (m, B, ...), a ``lax.scan`` applies ``fn`` to
    each microbatch on-device, outputs come back flattened (m·B, ...).
    The host pays one dispatch round-trip per m batches instead of
    per batch (GPipe-style multi-step fusion, Huang et al. 2019). The
    July 2026 record's 485 vs 7,472 img/s gap was almost entirely that
    per-step round-trip; its share on the current machine is not
    measured.

    ``donate=True`` marks every stacked input as donated
    (``jax.jit(..., donate_argnums=...)``): XLA may reuse the staged
    input buffers for outputs/temps, so steady-state fused dispatch
    allocates nothing extra device-side. Safe by construction here —
    the stacked arrays are freshly ``np.stack``-built host batches the
    executor never reads again (donation changes no values; the
    depth-1/donation-off bit-identity tests pin this).

    The wrapper is cached ON fn itself (``fn._tpudl_fused[key]``): the
    fused program — whose closure pins fn and, transitively, its model
    weights — then lives exactly as long as fn does; the fn↔wrapper
    reference cycle is an ordinary gc-collectible cycle, so a discarded
    transformer frees both (a module-level cache keyed by fn would keep
    the pair alive forever: the wrapper's closure references its own
    key)."""
    donate = bool(donate and n_args)
    key = (int(m), donate)
    per_fn = getattr(fn, "_tpudl_fused", None)
    if per_fn is not None and key in per_fn:
        return per_fn[key]
    import jax

    def fused(*stacked):
        def body(carry, xs):
            r = fn(*xs)
            if not isinstance(r, (tuple, list)):
                r = (r,)
            return carry, tuple(r)

        _, ys = jax.lax.scan(body, None, tuple(stacked))
        return tuple(
            y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]) for y in ys)

    if donate:
        from tpudl.data import codec as _codec

        _codec.filter_unusable_donation_warning()
        fused = jax.jit(fused, donate_argnums=tuple(range(int(n_args))))
    else:
        fused = jax.jit(fused)

    try:
        if per_fn is None:
            per_fn = fn._tpudl_fused = {}
        per_fn[key] = fused
    except (AttributeError, TypeError):  # fn rejects attributes: uncached
        pass
    return fused


def mesh_fuse_ok(batch_size: int, mesh) -> bool:
    """Can the fused multi-step program run under ``mesh`` at this
    batch geometry? THE one rule — shared by the executor's fuse gate
    and ``ImageBatchWarmup`` (which must warm exactly the program
    variant the timed transform will run): the fast path must be armed
    and full batches must shard evenly over the data axis — a fused
    group stacks M padded microbatches into ``(M, B_pad, ...)``, and
    per-microbatch padding would leave pad rows INTERLEAVED in the
    flattened output. Pick ``batch_size % data-axis == 0`` to enable
    mesh fusion; the ragged TAIL batch always pads + dispatches
    per-batch either way. ``mesh=None`` imposes no constraint.

    On a 2-D ``(data, model)`` grid only the DATA-axis size gates:
    batches shard over ``data`` while the model axis holds parameter
    shards (which never ride the transfer edge — transfer_batch passes
    model-resident leaves through untouched), so a 4×2 mesh fuses at
    any ``batch_size % 4 == 0``, not ``% 8``."""
    if mesh is None:
        return True
    if os.environ.get("TPUDL_MESH_FAST_PATH", "1") == "0":
        return False
    from tpudl import mesh as M

    return int(batch_size) % mesh.shape[M.DATA_AXIS] == 0


def _as_column(values) -> np.ndarray:
    if isinstance(values, LazyColumn):
        return values  # deferred source; materializes per access
    if isinstance(values, np.ndarray):
        return values
    values = list(values)
    if values and isinstance(values[0], (dict, bytes, str, type(None))):
        col = np.empty(len(values), dtype=object)
        col[:] = values
        return col
    try:
        return np.asarray(values)
    except Exception:
        col = np.empty(len(values), dtype=object)
        col[:] = values
        return col


class Frame:
    """Ordered named columns of equal length."""

    def __init__(self, columns: Mapping[str, object], num_partitions: int | None = None):
        self._cols: dict[str, np.ndarray] = {}
        n = None
        for name, values in columns.items():
            col = _as_column(values)
            if n is None:
                n = len(col)
            elif len(col) != n:
                raise ValueError(
                    f"column {name!r} has length {len(col)}, expected {n}"
                )
            self._cols[str(name)] = col
        self._n = n or 0
        self.num_partitions = num_partitions

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_files(cls, path, num_partitions: int | None = None,
                   host_sharded: bool = False) -> "Frame":
        """Streaming file source: columns (filePath, fileData) where the
        bytes column is LAZY — paths only in RAM, reads deferred to the
        accessed batch (the ``sc.binaryFiles`` contract; delegates to
        :func:`tpudl.image.imageIO.filesToFrame`)."""
        from tpudl.image.imageIO import filesToFrame

        return filesToFrame(path, numPartitions=num_partitions,
                            host_sharded=host_sharded, lazy=True)

    # -- schema/access ----------------------------------------------------
    @property
    def columns(self) -> list[str]:
        return list(self._cols)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, name: str) -> np.ndarray:
        return self._cols[name]

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __repr__(self) -> str:
        cols = ", ".join(f"{k}:{v.dtype}" for k, v in self._cols.items())
        return f"Frame[{self._n} rows]({cols})"

    # -- relational-lite --------------------------------------------------
    def select(self, *names: str) -> "Frame":
        missing = [n for n in names if n not in self._cols]
        if missing:
            raise KeyError(f"unknown columns {missing}; have {self.columns}")
        return Frame({n: self._cols[n] for n in names}, self.num_partitions)

    def with_column(self, name: str, values) -> "Frame":
        col = _as_column(values)
        if len(col) != self._n:
            raise ValueError(f"column length {len(col)} != frame length {self._n}")
        out = dict(self._cols)
        out[name] = col
        return Frame(out, self.num_partitions)

    def with_column_renamed(self, old: str, new: str) -> "Frame":
        if new != old and new in self._cols:
            raise ValueError(f"cannot rename {old!r} to existing column {new!r}")
        return Frame(
            {new if k == old else k: v for k, v in self._cols.items()},
            self.num_partitions,
        )

    def drop(self, *names: str) -> "Frame":
        return Frame(
            {k: v for k, v in self._cols.items() if k not in names},
            self.num_partitions,
        )

    def filter_rows(self, mask) -> "Frame":
        mask = np.asarray(mask, dtype=bool)
        idx = np.nonzero(mask)[0]
        return Frame(
            {k: (v.subset(idx) if isinstance(v, LazyColumn) else v[mask])
             for k, v in self._cols.items()},
            self.num_partitions)

    def take(self, indices) -> "Frame":
        """Rows by integer index, in the GIVEN order (duplicates
        allowed) — the ORDER BY backbone; filter_rows is the boolean
        sibling."""
        idx = np.asarray(indices, dtype=int)
        return Frame(
            {k: (v.subset(idx) if isinstance(v, LazyColumn) else v[idx])
             for k, v in self._cols.items()},
            self.num_partitions)

    def dropna(self, subset: Sequence[str] | None = None) -> "Frame":
        """Drop rows with None/NaN in ``subset`` (default: all columns).
        On a LazyColumn nullness comes from the column's cheap
        ``validity_mask`` probe when it has one (NO decode at all — see
        ``null_mask``); otherwise the scan streams in chunks (O(chunk)
        held payloads, decoded once for the scan). Either way the result
        keeps a lazy subset VIEW — filtering a huge readImages() frame
        stays O(batch) in host RAM."""
        names = list(subset) if subset else self.columns
        mask = np.ones(self._n, dtype=bool)
        for n in names:
            mask &= ~null_mask(self._cols[n])
        return self.filter_rows(mask)

    def head(self, n: int = 5) -> "Frame":
        # LazyColumns keep a lazy subset VIEW (like filter_rows) so
        # 'SELECT path FROM t LIMIT n' never reads bytes the projection
        # doesn't use; np.arange(len)[:n] preserves python slice
        # semantics (incl. negative n) so lazy/eager columns agree
        return Frame(
            {k: (v.subset(np.arange(len(v))[:n])
                 if isinstance(v, LazyColumn) else v[:n])
             for k, v in self._cols.items()}, self.num_partitions)

    def limit(self, n: int) -> "Frame":
        return self.head(n)

    def to_dict(self) -> dict[str, np.ndarray]:
        return dict(self._cols)

    def fingerprint(self, cols: Sequence[str] | None = None) -> str:
        """Content identity of the named columns (sha1 hex) — the
        prepared-batch cache's key material (``map_batches(cache_dir=
        ...)``), so a changed input re-prepares instead of replaying
        stale shards. Lazy columns answer via their cheap
        ``fingerprint`` probe (LazyFileColumn: paths + sizes + mtimes —
        NO reads, NO decodes); eager columns hash their bytes (only
        paid when caching is on). A lazy column without a fingerprint
        raises — pass ``cache_key`` explicitly for such sources."""
        import hashlib

        h = hashlib.sha1()
        for name in (list(cols) if cols is not None else self.columns):
            col = self._cols[name]
            h.update(f"col:{name}\n".encode())
            if isinstance(col, LazyColumn):
                fp = col.fingerprint()
                if fp is None:
                    raise ValueError(
                        f"lazy column {name!r} has no content "
                        "fingerprint; pass an explicit cache_key= to "
                        "map_batches/Dataset to enable caching")
                h.update(str(fp).encode())
            elif col.dtype == object:
                for v in col:
                    _hash_value(h, v)
            else:
                h.update(f"{col.dtype}{col.shape}".encode())
                h.update(np.ascontiguousarray(col).tobytes())
        return h.hexdigest()

    def rows(self) -> Iterator[dict]:
        for i in range(self._n):
            yield {k: v[i] for k, v in self._cols.items()}

    def collect(self) -> list[dict]:
        return list(self.rows())

    # -- executor ---------------------------------------------------------
    def iter_batches(self, batch_size: int) -> Iterator[tuple[int, int]]:
        for start in range(0, self._n, batch_size):
            yield start, min(start + batch_size, self._n)

    def map_batches(self, fn: Callable, input_cols: Sequence[str],
                    output_cols: Sequence[str], *,
                    supervise: bool | None = None,
                    **kwargs) -> "Frame":
        """Run ``fn`` over the frame in device-sized batches; append outputs.

        ``fn`` maps packed input arrays → one array or a tuple matching
        ``output_cols``. ``pack`` converts a column slice (object arrays
        included) to a stacked numpy batch; defaults to ``np.stack``-like
        coercion. When ``mesh`` is given, batches are padded to the
        data-axis size and transferred as ONE batched async
        ``device_put`` under ``NamedSharding(P('data'))``
        (``tpudl.mesh.transfer_batch`` — the infeed edge); outputs are
        fetched and unpadded, and the SAME fast path below (fusion,
        async window, donation, codec, autotune) stays armed — no
        parallel-only code path (``TPUDL_MESH_FAST_PATH=0`` is the
        conservative pre-ISSUE-11 escape hatch). This is the rebuild of
        the reference's per-partition TensorFrames MapBlocks execution,
        minus the JVM.

        A 2-D ``(data, model)`` mesh works identically (ISSUE 16):
        batches still ride the one transfer edge sharded over ``data``,
        while ``fn``'s model-sharded closures/params stay device-
        resident under their ``P(None, 'model')``-family shardings
        (transfer_batch passes them through without gathering) — every
        gate keys on the DATA-axis size, so the full fast path stays
        armed at ``n_model > 1``.

        ``batch_size`` defaults to the frame's ``num_partitions`` hint
        (``ceil(rows / num_partitions)`` — the Spark-side meaning of a
        partition as the unit of executor dispatch), else 256.

        The executor is a staged pipeline (PIPELINE.md has the stage-time
        model; every stage reports into ``tpudl.obs.last_pipeline_report``):

        1. ``prepare`` pool — up to ``prepare_workers`` threads
           (``TPUDL_FRAME_PREPARE_WORKERS``, default 2) pack/decode
           batches concurrently, so a 256-image PIL decode no longer
           serializes with compute;
        2. a ``prefetch_depth``-deep bounded infeed queue
           (``TPUDL_FRAME_PREFETCH_DEPTH``, default 2) — host RAM stays
           O(depth · batch);
        3. multi-step fused dispatch — when ``fn`` is a jitted device fn
           and batches are full-size, ``fuse_steps``
           (``TPUDL_FRAME_FUSE_STEPS``, default 1 = off) microbatches are
           stacked and executed by ONE compiled ``lax.scan`` program, so
           the host pays one dispatch round-trip per M batches (how
           much of wall time that round-trip is on the current machine
           is not measured). Under a ``mesh`` the stacked
           group transfers once with ``NamedSharding(P(None, 'data'))``
           and each scanned microbatch runs data-sharded (fusion needs
           ``batch_size % data-axis == 0`` there — see PIPELINE.md
           "Mesh-native execution");
        4. a ``dispatch_depth``-deep ASYNC dispatch window
           (``TPUDL_FRAME_DISPATCH_DEPTH``, default 2; device fns —
           sharded mesh outputs are async futures too) — up to D
           dispatches stay in flight as futures, so the blocking
           per-dispatch round-trip of batch N rides under the
           dispatches of N+1..N+D; the hot loop never calls
           ``block_until_ready``/``np.asarray`` on a device result.
           With ``donate`` (``TPUDL_FRAME_DONATE``, default on), fused
           and codec-wrapped programs donate their input buffers
           (``jax.jit(..., donate_argnums=...)``) so steady-state
           dispatch allocates nothing extra device-side; shard-cache
           hits are handed to donating programs as writable COPIES,
           never the cache's read-only mmap;
        5. the windowed/accumulated async outfeed — the device→host
           copy of every output starts AT dispatch
           (``copy_to_host_async``), in both outfeed modes, so D2H of
           batch N overlaps later dispatches.

        ``autotune`` (``TPUDL_FRAME_AUTOTUNE``, default on): any of
        ``fuse_steps``/``dispatch_depth``/``prefetch_depth`` left unset
        (no kwarg, no env) is SEEDED from the knob advisor's ranked
        recommendations over the previous run's PipelineReport
        (``obs.analyze_roofline()`` — wire probe + device ms/step +
        report gauges; PIPELINE.md "Async dispatch"). The chosen values
        land on the report's config (``autotuned`` names the seeded
        knobs); explicit kwargs/env always win.

        ``prefetch`` defaults to on for device fns, off for host fns
        (whose inputs must stay numpy). ``device_fn`` overrides the
        detection — the heuristic recognizes ``jax.stages.Wrapped``
        (jit/pjit) and ``.lower()``-bearing executables, but NOT a
        plain-python wrapper around a jitted call; the executor warns
        once when a "host" fn returns device arrays.
        ``TPUDL_FRAME_PREFETCH=0`` force-disables the whole pipelined
        executor — prefetch AND fusion (the serial arm of an A/B).

        The ``tpudl.data`` knobs (DATA.md has the operator guide):

        - ``wire_codec`` (env ``TPUDL_WIRE_CODEC``): a codec name
          ('u8', 'bf16', 'identity', 'auto') or a
          :class:`tpudl.data.WireCodec` — prepared batches are
          wire-ENCODED host-side and a restoring prologue is fused in
          front of ``fn`` on device, so an image batch ships as uint8
          + scale instead of float32 (4× fewer H2D bytes). Device fns
          only; a host fn gets a warn-once and the identity path.
        - ``cache_dir`` (env ``TPUDL_DATA_CACHE_DIR``): prepared
          (packed + encoded) batches persist to a checksummed sharded
          cache keyed by the frame's content ``fingerprint`` — repeat
          runs and epochs ≥ 2 over the same inputs skip decode/pack
          entirely. ``cache_key`` overrides the fingerprint for frames
          whose columns cannot self-identify (raises otherwise).
        - ``device_cache`` (env ``TPUDL_DATA_DEVICE_CACHE``): pin the
          prepared, wire-ENCODED batches in device memory (HBM) under
          the ``TPUDL_DATA_HBM_BUDGET_MB`` budget — the top tier of the
          cache hierarchy (DATA.md "Cache hierarchy"). A hit bypasses
          prepare, codec encode and the H2D transfer entirely and feeds
          the dispatch window a resident buffer; epochs ≥ 2 of a
          fitting run ship ZERO wire bytes. Entries are keyed by the
          same fingerprint identity as the shard cache plus the mesh
          topology (a shard resident under one ``NamedSharding`` is a
          key miss on any other mesh). Resident buffers are never
          donated (hits route through the non-donating program —
          ``data.hbm.donation_blocked`` counts the fallback), and
          residency forces ``fuse_steps`` to 1: fusion amortizes the
          per-dispatch round-trip by re-stacking HOST batches, which
          would defeat the residency it rides with. Device fns only.
        The ``tpudl.compile`` knobs (COMPILE.md):

        - ``buckets`` (env ``TPUDL_COMPILE_BUCKETS``, default off): a
          bucket-ladder spec (``"pow2"``, ``"pow2ish"``/``"1"``, an
          explicit ``"8,16,32"`` list, or a
          :class:`tpudl.compile.BucketLadder`). Ragged dispatch shapes
          pad up to the smallest ladder rung (repeating row 0, pad
          rows stripped from the outputs — the mesh-pad discipline),
          so an arbitrary mix of batch sizes runs through O(log n)
          compiled programs instead of one retrace per novel shape.
          If the primary ``batch_size`` itself is not a rung, fusion
          drops to per-batch dispatch (a fused stack would interleave
          the pad rows).
        - ``aot`` (env ``TPUDL_COMPILE_AOT``, default off): consult
          the AOT program store at dispatch — a hit executes a
          precompiled executable (restored from disk on process start:
          zero trace, zero compile); a miss runs the jitted path
          unchanged and background-compiles the signature so the NEXT
          process starts warm. ``compile.{hits,misses}`` count both.
        ``supervise`` (env ``TPUDL_FRAME_DEGRADE``, default OFF): arm
        the fault-containment supervisor (FAULTS.md,
        :mod:`tpudl.frame.supervisor`). Classified executor faults
        retry the run down a bounded degradation ladder — device OOM
        evicts unpinned HBM-cache entries and retries, transient
        transfer/IO faults ride the ONE shared RetryPolicy, repeated
        stage faults halve ``dispatch_depth``, then drop ``fuse_steps``
        to 1, then disable donation, then fall back to the conservative
        serial arm — every rung bitwise-identical to a healthy run of
        that config, recorded as a ``frame.degraded`` flight event and
        on the report (``degraded_to``, ``recovered_batches``).
        Exhaustion (``TPUDL_FRAME_DEGRADE_MAX_RUNGS``) writes a flight
        dump and raises a TYPED taxonomy error (``DeviceOOM`` /
        ``TransferError`` / ``RecompileStorm`` / ``StageFault``)
        chained to the original — never a raw pool-unwind error.
        """
        from tpudl.frame import supervisor as _sup

        if not _sup.enabled(supervise):
            # unarmed: ONE env read, straight into the executor (the
            # overhead guard in tests/test_supervisor.py pins this)
            return self._map_batches_impl(fn, input_cols, output_cols,
                                          **kwargs)
        sup = _sup.Supervisor()

        def attempt(overrides):
            kw = dict(kwargs)
            kw.update(overrides)  # rung knobs beat the caller's
            return self._map_batches_impl(fn, input_cols, output_cols,
                                          _supervisor=sup, **kw)

        return sup.supervise(attempt)

    def _map_batches_impl(
        self,
        fn: Callable,
        input_cols: Sequence[str],
        output_cols: Sequence[str],
        *,
        batch_size: int | None = None,
        mesh=None,
        pack: Callable | None = None,
        check_finite: bool = False,
        prefetch: bool | None = None,
        prefetch_depth: int | None = None,
        prepare_workers: int | None = None,
        fuse_steps: int | None = None,
        dispatch_depth: int | None = None,
        donate: bool | None = None,
        autotune: bool | None = None,
        device_fn: bool | None = None,
        wire_codec=None,
        cache_dir: str | None = None,
        cache_key: str | None = None,
        device_cache: bool | None = None,
        buckets=None,
        aot: bool | None = None,
        _supervisor=None,
    ) -> "Frame":
        """One executor attempt: the full staged pipeline (the
        public :meth:`map_batches` carries the user-facing contract
        and, when supervision is armed, retries this body down the
        degradation ladder — ``_supervisor`` is its ladder-state
        handle)."""
        if batch_size is None:
            if self.num_partitions:
                batch_size = max(1, -(-self._n // int(self.num_partitions)))
            else:
                batch_size = 256
        heuristic = device_fn is None
        device_flag = ((mesh is not None or _is_device_fn(fn))
                       if heuristic else bool(device_fn))
        # the fast-path gates (fusion / window / donation / autotune)
        # need fn to REALLY be a device fn: under a mesh device_flag is
        # forced True (sharded inputs make prefetch/codec routing right
        # even for host fns), but jitting a numpy fn into a fused scan
        # would crash at trace time, and a host fn must never run
        # concurrently on the window's pool threads (mesh=None already
        # enforces this via device_flag — same rule, same heuristic)
        device_fn_real = (_is_device_fn(fn) if heuristic
                          else bool(device_fn))
        if prefetch is None:
            prefetch = device_flag
        killed = os.environ.get("TPUDL_FRAME_PREFETCH", "1") == "0"
        if killed:
            prefetch = False
        # -- mesh fast path (ISSUE 11) ------------------------------------
        # the mesh executor runs the SAME fast path as single-chip:
        # fused multi-step dispatch, the async dispatch window, buffer
        # donation, codec fusion and autotune all stay armed under a
        # mesh. TPUDL_MESH_FAST_PATH=0 reverts to the pre-ISSUE-11
        # conservative mesh executor (serial blocking dispatch,
        # per-batch transfer) — the A/B arm and the escape hatch.
        mesh_fast = (mesh is not None
                     and os.environ.get("TPUDL_MESH_FAST_PATH", "1")
                     != "0")
        mesh_slow = mesh is not None and not mesh_fast
        # -- autotune: seed unset executor knobs from the advisor ---------
        # (ROADMAP 2's closed loop: fuse_steps / dispatch_depth /
        # prefetch_depth come from obs.analyze_roofline()'s ranked recs
        # over the PREVIOUS run's report + the wire probe + device
        # ms/step, instead of hand-set env knobs. Explicit kwargs and
        # env settings always win; the serial kill switch and host fns
        # never autotune.)
        autotune_on = (
            (bool(autotune) if autotune is not None
             else os.environ.get("TPUDL_FRAME_AUTOTUNE", "1") != "0")
            and not killed and device_fn_real and not mesh_slow)
        seeds: dict = {}
        seeded: list[str] = []

        def _resolve(kwarg, env_name, seed_key, default):
            if kwarg is not None:
                return int(kwarg)
            if os.environ.get(env_name, "") != "":
                return _env_int(env_name, default)
            if seed_key in seeds:
                seeded.append(seed_key)
                return int(seeds[seed_key])
            return default

        if autotune_on and any(
                k is None and os.environ.get(e, "") == ""
                for k, e in ((fuse_steps, "TPUDL_FRAME_FUSE_STEPS"),
                             (dispatch_depth, "TPUDL_FRAME_DISPATCH_DEPTH"),
                             (prefetch_depth, "TPUDL_FRAME_PREFETCH_DEPTH"))):
            # read the PREVIOUS run's report before this run files its
            # own into the ring below; never probe the wire from here
            # (the cached probe / TPUDL_WIRE_MBPS is consumed if known).
            # batch_size + mesh shape are the workload guard: the
            # advisor's numbers are per-dispatch quantities at that
            # batch geometry AND topology — a process alternating a
            # sharded featurizer and a single-chip scorer must not
            # cross-tune them
            from tpudl.obs import roofline as _roofline

            seeds = _roofline.autotune_seed(
                allow_probe=False,
                match={"batch_size": int(batch_size),
                       "mesh": (dict(mesh.shape) if mesh is not None
                                else None)})
        depth = _resolve(prefetch_depth, "TPUDL_FRAME_PREFETCH_DEPTH",
                         "prefetch_depth", 2)
        workers = (int(prepare_workers) if prepare_workers is not None
                   else _env_int("TPUDL_FRAME_PREPARE_WORKERS", 2))
        d_depth = max(1, _resolve(dispatch_depth,
                                  "TPUDL_FRAME_DISPATCH_DEPTH",
                                  "dispatch_depth", 2))
        if killed or mesh_slow or not device_fn_real:
            # the async window needs a REAL device fn returning futures
            # (sharded jax arrays are futures too — ISSUE 11); host fns
            # stay serial (their in-place mutations would race on the
            # pool), and the kill switches must yield the serial
            # executor (the serial arm of an A/B)
            d_depth = 1
        donate_flag = (bool(donate) if donate is not None
                       else os.environ.get("TPUDL_FRAME_DONATE", "1")
                       != "0")
        if killed or mesh_slow or not device_fn_real:
            donate_flag = False
        if d_depth > 1 and prefetch and prefetch_depth is None and \
                os.environ.get("TPUDL_FRAME_PREFETCH_DEPTH", "") == "" \
                and "prefetch_depth" not in seeds:
            # a D-deep dispatch window drains prepared batches D at a
            # time: the DEFAULT infeed must be able to feed it (explicit
            # kwarg/env/seeded depths are respected as set)
            depth = max(depth, d_depth)
        if (prepare_workers is None
                and "TPUDL_FRAME_PREPARE_WORKERS" not in os.environ
                and pack is not None
                and not getattr(pack, "thread_safe", False)):
            # a user-supplied pack never promised thread-safety (same
            # contract as LazyFileColumn's decode_workers=1 default):
            # run it single-worker unless the caller opted in — via the
            # kwarg/env, or by marking the callable ``pack.thread_safe
            # = True`` (the first-party packs are marked)
            workers = 1
        fuse = max(1, _resolve(fuse_steps, "TPUDL_FRAME_FUSE_STEPS",
                               "fuse_steps", 1))
        if killed or mesh_slow or not device_fn_real:
            # fusion traces fn into one jitted scan program: it needs a
            # REAL device fn (a numpy fn would crash at trace time),
            # and the A/B kill switches must yield the serial executor
            fuse = 1
        if mesh is not None:
            from tpudl import mesh as M  # jax import only on the mesh path

            multiple = mesh.shape[M.DATA_AXIS]
            if fuse > 1 and not mesh_fuse_ok(batch_size, mesh):
                fuse = 1
                if "fuse_steps" in seeded:
                    # an autotune seed this geometry can never engage
                    # must not be REPORTED as applied (the `autotuned`
                    # contract: listed knobs carry the advisor's values)
                    seeded.remove("fuse_steps")
        missing = [c for c in input_cols if c not in self._cols]
        if missing:
            raise KeyError(f"unknown input columns {missing}")

        from tpudl import obs  # deferred: host-only frames stay light
        from tpudl.obs import attribution as _attr
        from tpudl.obs import flight as _flight

        report = obs.PipelineReport()

        # -- tpudl.data: wire codec + sharded prepared-batch cache -------
        if wire_codec is None:
            wire_codec = os.environ.get("TPUDL_WIRE_CODEC") or None
        if cache_dir is None:
            cache_dir = os.environ.get("TPUDL_DATA_CACHE_DIR") or None
        dc_flag = (bool(device_cache) if device_cache is not None
                   else os.environ.get("TPUDL_DATA_DEVICE_CACHE", "0")
                   == "1")
        # the HBM tier needs a REAL device fn (resident jax arrays
        # would break a host fn's numpy contract) and the fast path
        # armed; the serial kill switch and the conservative mesh arm
        # stay residency-free (their A/B role is the un-cached wire)
        dc_flag = (dc_flag and device_fn_real and not killed
                   and not mesh_slow)
        plan = cache = None
        dcache = dkey = None
        if wire_codec is not None or cache_dir is not None or dc_flag:
            from tpudl.data import codec as _codec

            if wire_codec is not None and not device_flag:
                # a host fn's inputs must stay restored numpy — the
                # device prologue can never run, so shipping encoded
                # bytes would hand fn the wrong values
                _codec.warn_host_fn_codec_once()
                wire_codec = None
            if wire_codec is not None:
                plan = _codec.CodecPlan(wire_codec, len(input_cols),
                                        report=report)
            material = None
            pack_token = None
            if cache_dir is not None or dc_flag:
                from tpudl.data import shards as _shards

                material = cache_key
                if material is None:
                    try:
                        material = self.fingerprint(input_cols)
                    except ValueError:
                        # a lazy column with no content fingerprint:
                        # EXPLICITLY-requested caching (cache_dir, or
                        # device_cache=True as a kwarg) keeps the
                        # clear pass-cache_key error — but the
                        # process-wide TPUDL_DATA_DEVICE_CACHE=1
                        # accelerator must never turn a working
                        # uncached run into a crash; residency just
                        # disarms (plain wire transfer, the device
                        # cache's degrade-never-error contract)
                        if cache_dir is not None or device_cache:
                            raise
                        dc_flag = False
            if cache_dir is not None or dc_flag:
                # the pack is part of the prepared bytes' identity: a
                # different pack (e.g. a loader with another geometry)
                # must re-key, not replay. A pack without an explicit
                # ``cache_token`` keys by repr — object address, so the
                # cache is only reused by the SAME pack object (never
                # stale: two lambdas at one code location, or an edited
                # function body, share a qualname but not an address).
                # First-party packs carry tokens; set one on a custom
                # pack to opt into cross-run reuse (DATA.md).
                pack_token = ("default" if pack is None else
                              getattr(pack, "cache_token", None)
                              or repr(pack))
                key_str = _shards.cache_key(
                    material,
                    cols=",".join(input_cols),
                    batch=int(batch_size),
                    codec=_codec.spec_token(wire_codec),
                    pack=pack_token,
                    # the sanitizer runs on the MISS
                    # path only; a run asking for it
                    # must not warm-skip the check
                    finite=bool(check_finite),
                    layout="map_batches_v1")
            if cache_dir is not None:
                cache = _shards.ShardCache(cache_dir, key_str)
                if plan is not None and cache.meta.get("codecs"):
                    # warm replay MUST restore with the codecs the
                    # shards were encoded with, not a fresh auto pick
                    plan.adopt(cache.meta["codecs"])
            if dc_flag:
                from tpudl.data import device_cache as _dc

                # SAME key material as the shard cache + the mesh
                # topology: a resident shard sharded for one mesh is a
                # key MISS on any other (never resharded in place)
                dkey = _dc.run_key(key_str, mesh)
                dcache = _dc.get_device_cache()
                if fuse > 1:
                    # residency replaces fusion: the fused program
                    # re-stacks HOST microbatches (np.stack), which
                    # would force resident buffers back through the
                    # wire — and under a mesh, fuse==1 is what routes
                    # the sharded transfer through prepare where the
                    # populated buffers are born. Round-trips stay
                    # hidden by the dispatch window.
                    fuse = 1
                    if "fuse_steps" in seeded:
                        # an autotune seed residency disarms must not
                        # be REPORTED as applied (the `autotuned`
                        # contract — same rule as the mesh gate)
                        seeded.remove("fuse_steps")

        # -- tpudl.compile: shape buckets + AOT program store -------------
        # (COMPILE.md; PIPELINE.md "Bucket pick & AOT dispatch".) The
        # ladder snaps ragged dispatch shapes onto O(log n) rungs; the
        # program store serves precompiled executables at dispatch and
        # records misses for the next process. Both are opt-in
        # (TPUDL_COMPILE_BUCKETS / TPUDL_COMPILE_AOT or the kwargs),
        # device fns only, and the serial kill switch disarms them like
        # every other fast-path stage.
        ladder = None
        store = None
        if device_fn_real and not killed:
            from tpudl.compile import buckets as _bk

            ladder = _bk.resolve_ladder(buckets)
            from tpudl.compile import store as _aot_store

            if _aot_store.aot_enabled(aot):
                store = _aot_store.get_program_store()
                # fresh-process warm start: deserialize persisted
                # executables on the background pool so the first
                # batches can already hit (idempotent per process)
                store.ensure_restored()
                store.note_ladder(ladder)
        bucket_full = False
        if ladder is not None:
            # does the PRIMARY batch size itself snap to a rung? If it
            # pads, every full batch carries pad rows — and a fused
            # (m, B, ...) stack would interleave them in the flattened
            # output (the same rule that gates mesh fusion), so fusion
            # drops to per-batch dispatch
            target_full = ladder.pick(int(batch_size))
            if mesh is not None:
                target_full = -(-target_full // multiple) * multiple
            bucket_full = target_full != int(batch_size)
            if bucket_full and fuse > 1:
                fuse = 1
                if "fuse_steps" in seeded:
                    seeded.remove("fuse_steps")

        report.config = {
            "executor": ("pipelined" if (prefetch or fuse > 1
                                         or d_depth > 1)
                         else "serial"),
            "prefetch": bool(prefetch),
            "prefetch_depth": int(depth) if prefetch else 0,
            "prepare_workers": (max(1, min(workers, depth))
                                if prefetch else 0),
            "fuse_steps": fuse,
            "dispatch_depth": int(d_depth),
            "donate": bool(donate_flag),
            "autotune": bool(autotune_on),
            "autotuned": sorted(seeded),
            "batch_size": int(batch_size),
            "rows": self._n,
            # mesh topology on the report: the live monitor, the
            # roofline model and the autotune workload guard all read
            # it; None = single-chip
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "wire_codec": (plan.names()[0] if plan is not None
                           else "off"),
            "batch_cache": bool(cache is not None),
            "device_cache": bool(dcache is not None),
            # tpudl.compile (COMPILE.md): the bucket ladder in force
            # ("off" = exact shapes) and whether dispatch consults the
            # AOT program store — the roofline's cold-start attribution
            # and the live monitor's compile line both read these
            "buckets": ladder.spec if ladder is not None else "off",
            "aot": bool(store is not None),
        }
        obs.set_last_pipeline(report)
        if _supervisor is not None:
            # fault containment (frame.supervisor): the ladder reads
            # the RESOLVED config off this report (what to halve) and
            # recovery stamps degraded_to/recovered_batches onto it
            _supervisor.note_report(report)

        # mesh transfer placement, captured ONCE: fuse==1 runs transfer
        # on the prepare pool (copies start as early as possible and
        # ride under earlier dispatches); fused runs keep host arrays
        # and transfer the stacked (M, B, ...) group on the dispatch
        # thread (handle()'s window-mode fallback only ever LOWERS fuse
        # mid-run when it started > 1, so this flag never flips)
        transfer_in_prepare = mesh is not None and fuse == 1

        def prepare(start, stop):
            """Pack (and, on the prefetch path, transfer) one batch.
            Runs on a prepare-pool thread when prefetching: jax dispatch
            is thread-safe and transfers release the GIL, so this
            overlaps the main thread's compute dispatch. The pool runs
            ``pack`` for DIFFERENT batches concurrently only when the
            pack opted in (see the workers resolution above).

            With a shard cache, a verified hit replaces the whole
            pack/decode/encode path by a memory-mapped read; a miss (or
            a corrupt shard) prepares as usual and persists the result.
            Wire encoding happens AFTER pack and the finite check (the
            check must see restored float values, not wire bytes).

            With a DEVICE cache (DATA.md "Cache hierarchy"), an HBM hit
            short-circuits everything above — no pack, no decode, no
            encode, no transfer: the resident (already encoded, already
            sharded) buffers feed the dispatch window directly, pinned
            until their dispatch returns. Returns
            ``(arrays, n_pad, pin-or-None)`` — a non-None pin marks the
            batch RESIDENT (the consumer routes it through the
            non-donating program and releases the pin after
            dispatch)."""
            with report.stage("prepare"):
                bidx = start // batch_size
                # executor-stage fault points (tpudl.testing.faults):
                # the robustness suite raises/kills inside an exact
                # stage at an exact batch; unarmed this is a None-check
                _faults.fire("frame.prepare", index=bidx)
                # attribution: rows entering the pipeline, charged in
                # the submitting run's scope (carried onto this pool
                # thread by _PipelineInfeed._submit)
                _attr.charge("rows_in", stop - start)
                if dcache is not None:
                    pin = dcache.get((dkey, bidx))
                    # an all-hits replay still needs resolved codecs
                    # for the device prologue (same guard as the shard
                    # cache below) — entries persist their codec keys
                    if pin is not None and (
                            plan is None or plan.resolved()
                            or pin.codecs):
                        if plan is not None and not plan.resolved():
                            plan.adopt(pin.codecs)
                        pins.add(pin)
                        # `bytes_prepared` keeps meaning "bytes fed to
                        # dispatch"; `bytes_hbm_hit` is the resident
                        # share the roofline subtracts from its wire
                        # model (these bytes never crossed the link,
                        # and data.wire.bytes_shipped stays untouched)
                        report.count("bytes_prepared", pin.nbytes)
                        report.count("bytes_hbm_hit", pin.nbytes)
                        report.count("hbm_hits")
                        _flight.record_batch(
                            "prepare", bidx, pin.arrays,
                            rows=stop - start, cache_hit=True,
                            hbm_hit=True, run=report.run_id)
                        return list(pin.arrays), pin.n_pad, pin
                    if pin is not None:
                        pin.release()  # unusable hit: codecs unknown
                packed = None
                cache_hit = False
                if cache is not None:
                    hit = cache.get(bidx)
                    # an all-hits replay still needs resolved codecs for
                    # the device prologue; a cache written by a run that
                    # died before persisting its codec meta re-prepares
                    if hit is not None and (plan is None
                                            or plan.resolved()):
                        report.count("cache_hits")
                        # device fns only read their numpy inputs, so
                        # they keep the zero-copy read-only mmap; a
                        # host fn may mutate in place (legal on the
                        # cold path's fresh arrays), so warm batches
                        # must be writable copies or cold/warm diverge.
                        # DONATING programs also get writable copies: a
                        # donated buffer hands XLA write access, and on
                        # a backend that zero-copies host numpy that
                        # would be the shard file itself (DATA.md). The
                        # only donating program that can SEE these hit
                        # buffers is the codec wrapper's per-batch path
                        # (plan is not None); the fused path re-stacks
                        # into fresh arrays, and without a plan no
                        # wrapper exists to carry donate_argnums — the
                        # default (donate on, no codec) keeps zero-copy
                        # mmap replay. Under a mesh the transfer edge
                        # (mesh.transfer_batch) always COPIES host
                        # buffers into device shards, so a donating
                        # program can never see the mmap there either.
                        donate_sees_hit = (donate_flag
                                           and plan is not None
                                           and mesh is None)
                        packed = (list(hit)
                                  if device_flag and not donate_sees_hit
                                  else [np.array(a) for a in hit])
                        cache_hit = True
                if packed is None:
                    if cache is not None:
                        report.count("cache_misses")
                    packed = []
                    for ci, c in enumerate(input_cols):
                        sl = self._cols[c][start:stop]
                        arr = pack(sl) if pack is not None else _default_pack(sl)
                        if check_finite and np.issubdtype(arr.dtype, np.floating):
                            # input-pipeline sanitizer (SURVEY.md §5.2):
                            # catch bad rows host-side before they enter
                            # a fused program
                            bad = ~np.isfinite(arr).reshape(arr.shape[0], -1).all(1)
                            if bad.any():
                                rows = (np.nonzero(bad)[0][:8] + start).tolist()
                                raise ValueError(
                                    f"non-finite values in column {c!r}, rows "
                                    f"{rows} (batch {start}:{stop})")
                        if plan is not None:
                            arr = plan.encode(ci, arr)
                        packed.append(arr)
                    if cache is not None:
                        cache.put(bidx, packed)
                        if (plan is not None and plan.resolved()
                                and not cache.meta.get("codecs")):
                            cache.set_meta({"codecs": plan.keys()})
                if plan is not None:
                    plan.record_shipped(packed)
                # wire-byte accounting for the roofline model
                # (tpudl.obs.roofline): what this batch will put on the
                # H2D link — nbytes reads a header field, no data touch
                report.count("bytes_prepared",
                             int(sum(int(getattr(a, "nbytes", 0))
                                     for a in packed)))
                # black-box descriptor: shapes/dtypes/fingerprint only
                # (never data) — a dump shows what the last batches
                # looked like (tpudl.obs.flight)
                _flight.record_batch("prepare", bidx, packed,
                                     rows=stop - start,
                                     cache_hit=cache_hit,
                                     run=report.run_id)
                n_pad = 0
                if ladder is not None and packed:
                    # bucket pick (COMPILE.md): snap this batch's
                    # dispatch shape onto the ladder — pad rows repeat
                    # row 0 (the mesh.pad_batch discipline) and are
                    # stripped from the outputs via the same n_pad
                    # plumbing the mesh path uses, so values for real
                    # rows are bitwise-identical to exact dispatch.
                    # Under a mesh the rung rounds up to the data-axis
                    # multiple so SPMD padding never pads twice.
                    rows_b = int(packed[0].shape[0])
                    target = ladder.pick(rows_b)
                    if mesh is not None:
                        target = -(-target // multiple) * multiple
                    if target > rows_b:
                        packed = [_bk.pad_to(a, target) for a in packed]
                        n_pad = target - rows_b
                        report.count("bucket_pad_rows", n_pad)
                        _bk.count_pad_rows(n_pad)
                if mesh is not None:
                    # every column slices the same rows, so one pad count
                    # serves
                    with report.stage("h2d"):
                        _faults.fire("frame.h2d", index=bidx)
                        padded = [M.pad_batch(arr, multiple) for arr in packed]
                        mesh_pad = padded[0][1] if padded else 0
                        packed = [p for p, _ in padded]
                        if mesh_pad:
                            report.count("pad_rows", mesh_pad)
                        report.gauge("mesh_pad_rows", mesh_pad)
                        n_pad += mesh_pad
                        if transfer_in_prepare:
                            # ONE batched ASYNC device_put for every
                            # column (mesh.transfer_batch) — no barrier:
                            # the sharded arrays are futures, and the
                            # copies land while the consumer keeps
                            # dispatching (the old per-batch
                            # block_until_ready serialized the pool on
                            # the wire; the dispatch window now hides
                            # any residual wait as dispatch_wait).
                            # Fused runs skip this: the consumer stacks
                            # M HOST microbatches and transfers the
                            # (M, B, ...) group at dispatch.
                            packed = M.transfer_batch(packed, mesh)
                            if mesh_slow and prefetch:
                                import jax

                                # tpudl: ignore[hot-sync] — the
                                # TPUDL_MESH_FAST_PATH=0 escape hatch
                                # keeps the pre-ISSUE-11 barrier: the
                                # copy lands ON this prepare-pool
                                # thread, so the A/B arm isolates the
                                # new async transfer edge instead of
                                # silently exercising it too
                                jax.block_until_ready(packed)
                if dcache is not None:
                    # populate the HBM tier: the batch becomes resident
                    # NOW and the resident buffers themselves feed this
                    # dispatch — the bytes cross the wire exactly once.
                    # Mesh path (fuse==1 → transfer_in_prepare): packed
                    # is already the sharded device tree; single-chip:
                    # one batched async device_put, budget-gated so an
                    # over-budget batch never ships a doomed copy.
                    codecs = (plan.keys()
                              if plan is not None and plan.resolved()
                              else None)
                    pin = None
                    if mesh is not None:
                        pin = dcache.put((dkey, bidx), packed,
                                         n_pad=n_pad, codecs=codecs)
                    elif dcache.would_fit(
                            sum(int(getattr(a, "nbytes", 0))
                                for a in packed), run=dkey):
                        import jax

                        try:
                            packed = jax.device_put(list(packed))
                        except BaseException:
                            # a placement that dies mid-way (device OOM
                            # is likeliest right here) never touched
                            # the cache tallies — count it and let the
                            # error propagate to the supervisor, whose
                            # OOM rung evicts and retries
                            _dc.count_put_failed()
                            raise
                        pin = dcache.put((dkey, bidx), packed,
                                         n_pad=n_pad, codecs=codecs)
                    if pin is not None:
                        pins.add(pin)
                        return list(pin.arrays), n_pad, pin
                # mesh=None: host arrays go straight into the jitted fn even
                # when prefetching — the runtime's own arg transfer
                # pipelines with the dispatch, where an explicit
                # device_put is one more blocking call per batch (not
                # re-measured on the current machine). The
                # prefetch win here is the pack/decode work riding under
                # compute; the transfer stays on the dispatch path (so
                # ``h2d`` shows up inside ``dispatch`` on this path).
                return packed, n_pad, None

        # device-cache pin tokens currently OUTSTANDING (hits +
        # populates awaiting their dispatch): the dispatch path
        # releases AND discards each token, so the set — and, through
        # Pin._entry, the device buffers of entries another run may
        # have evicted meanwhile — stays bounded by the in-flight
        # window, not the whole run. The outer-finally sweep catches
        # only tokens an unwind stranded (cancelled window futures);
        # release is idempotent per token, so the double call is safe.
        # set add/discard are single GIL-atomic ops (prepare-pool and
        # dispatch threads touch it concurrently); the sweep iterates
        # a snapshot.
        pins: set = set()

        outputs: list[list[np.ndarray]] = [[] for _ in output_cols]
        acc: list[list] = [[] for _ in output_cols]  # device-resident results
        segs: list[tuple[int, int]] = []  # (padded_len, n_pad) per dispatch
        pending: list[tuple[tuple, int]] = []
        mode = None  # "acc" (fetch once at end) or "window" (bounded drain)

        def handle(result, n_pad):
            """Route one dispatch's result into the outfeed (acc/window)."""
            nonlocal mode, fuse
            if not isinstance(result, (tuple, list)):
                result = (result,)
            if len(result) != len(output_cols):
                raise ValueError(
                    f"fn returned {len(result)} outputs, expected "
                    f"{len(output_cols)}")
            if mode is None:
                # keyed on device_fn_real, not device_flag: under a
                # mesh device_flag is forced True, but a misclassified
                # jitted WRAPPER still loses the fast path — the hint
                # to pass device_fn=True matters there most
                if (heuristic and not device_fn_real and all(
                        hasattr(r, "copy_to_host_async") for r in result)):
                    _warn_device_outputs_once()
                mode = _pick_fetch_mode(result, max(1, self._n))
                if mode == "window" and fuse > 1:
                    # window mode exists to bound device memory at
                    # O(window · batch); a fused entry holds fuse× that,
                    # so big-output runs fall back to per-batch dispatch
                    fuse = 1
            # rows finished dispatching: the live monitor's progress/ETA
            # source (rows_done/rows_total on the status file)
            done_rows = (int(result[0].shape[0]) if result[0].ndim else 1)
            report.progress(max(0, done_rows - n_pad))
            _attr.charge("rows_out", max(0, done_rows - n_pad))
            if mode == "acc":
                # Keep results device-resident and fetch ONCE per column
                # at the end: every device→host fetch is a blocking
                # round-trip with a fixed cost, so per-batch fetching
                # serializes the pipeline (round-1 bottleneck).
                for i, r in enumerate(result):
                    acc[i].append(r)
                segs.append((int(result[0].shape[0]), n_pad))
            else:
                # Large outputs (e.g. outputMode='image'): bounded
                # window so device memory stays O(window · batch). The
                # host copy already started AT dispatch
                # (_start_host_copies on the dispatching thread), so the
                # drain below blocks only on the oldest entry's
                # in-flight copy.
                pending.append((tuple(result), n_pad))
                if len(pending) > _PIPELINE_WINDOW:
                    with report.stage("d2h"):
                        _faults.fire("frame.d2h")
                        _drain(pending.pop(0), outputs)

        spans = list(self.iter_batches(batch_size))
        # only the leading run of full-size batches is fusable (the
        # ragged tail would change the compiled (m, B, ...) signature)
        n_full = sum(1 for s, e in spans if e - s == batch_size)
        # watchdog supervision: ONE heartbeat for the whole run, beaten
        # at every stage entry (PipelineReport.stage) — a freeze inside
        # prepare/h2d/dispatch/d2h surfaces as a stall NAMING that
        # stage. Registered before the infeed so the prepare pool's
        # first batches are already supervised; deregistered on every
        # exit path below (finished work cannot false-flag).
        hb_run = obs.heartbeat("frame.map_batches", run=report.run_id,
                               rows=self._n)
        report.heartbeat = hb_run
        infeed = (_PipelineInfeed(prepare, spans, depth, workers, report)
                  if prefetch else None)
        consumed = 0

        def next_prepared():
            nonlocal consumed
            out = (infeed.get(consumed) if infeed
                   else prepare(*spans[consumed]))
            consumed += 1
            return out

        run_fn = fn if plan is None else None
        run_fn_direct = fn if plan is None else None

        def _run_fn():
            """``fn`` with the codec prologues fused in front (ONE jit
            program, see CodecPlan.wrap) — bindable only after the
            first batch prepared ('auto' codecs pick from it), hence
            the lazy bind; identity plans return ``fn`` itself. This is
            the NON-donating variant the fused wrapper traces inline
            (donation belongs to the outermost jit only)."""
            nonlocal run_fn
            if run_fn is None:
                run_fn = plan.wrap(fn)
            return run_fn

        def _run_fn_direct():
            """The per-batch dispatch program: donates its inputs when
            donation is armed and the codec wrapper exists to carry the
            ``donate_argnums`` (a bare user fn is never re-jitted just
            to donate — donation rides the wrappers the executor
            already owns)."""
            nonlocal run_fn_direct
            if run_fn_direct is None:
                run_fn_direct = plan.wrap(fn, donate=donate_flag)
            return run_fn_direct

        window = (_DispatchWindow(d_depth, report) if d_depth > 1
                  else None)

        # the roofline's cold-start evidence: the first dispatch's wall
        # time (trace + compile ride inside it on a cold process); the
        # flag list keeps the record single-shot (the first dispatch
        # runs alone — window warmup — so no second writer races it)
        first_dispatched: list = []

        def dispatch(call_fn, args, idx, n_pad, fused=False, pin=None,
                     donate_key=False):
            """Issue one dispatch: directly on the consumer (serial /
            depth 1) or onto the in-flight window. The dispatch stage
            itself — fault point, fn call, and starting the outputs'
            device→host copies — runs on whichever thread executes it;
            results are handled strictly in issue order. ``pin`` is the
            batch's device-cache pin token, released once the dispatch
            has consumed the resident buffers (eviction accounting must
            not drop bytes still feeding an in-flight program)."""
            def run():
                try:
                    call_args = args
                    if mesh is not None and call_args \
                            and isinstance(call_args[0], np.ndarray):
                        # mesh batches still host-side (fused groups,
                        # the ragged tail of a fused run, shape-drift
                        # fallbacks): ONE batched async transfer under
                        # the group's NamedSharding — P(None, data,
                        # ...) for a stacked (M, B, ...) group,
                        # P(data, ...) per batch — on the dispatching
                        # thread, so the copy rides inside the window
                        # like every other round-trip
                        with report.stage("h2d"):
                            call_args = M.transfer_batch(
                                list(call_args), mesh,
                                batch_dim=1 if fused else 0)
                    t_disp = time.perf_counter()
                    with report.stage("dispatch"):
                        _faults.fire("frame.dispatch", index=idx)
                        if store is not None:
                            # AOT program store (COMPILE.md): a hit
                            # executes a precompiled (possibly
                            # restored-from-disk) program — no trace
                            # possible; a miss runs the jitted path
                            # unchanged and background-compiles the
                            # signature for the next process. Only
                            # pure-rung per-batch shapes are marked
                            # bucketed (the validator's shapes↔ladder
                            # audit): a fused stack leads with M, and
                            # a mesh target rounds the rung up to the
                            # data-axis multiple.
                            result = store.call(
                                call_fn, call_args, donate=donate_key,
                                bucketed=(ladder is not None
                                          and not fused
                                          and mesh is None),
                                report=report)
                        else:
                            result = call_fn(*call_args)
                    # attribution: device seconds this scope consumed
                    # (the quota broker's currency, ROADMAP item 5)
                    _attr.charge("dispatch_s",
                                 time.perf_counter() - t_disp)
                    if not first_dispatched:
                        first_dispatched.append(True)
                        report.count("first_dispatch_s",
                                     time.perf_counter() - t_disp)
                    if not isinstance(result, (tuple, list)):
                        result = (result,)
                    # D2H starts NOW, at dispatch, for both outfeed
                    # modes — batch idx's copy overlaps the next
                    # dispatches
                    _start_host_copies(result)
                    return result, n_pad
                finally:
                    if pin is not None:
                        pin.release()
                        pins.discard(pin)

            if fused:
                report.count("fused_dispatches")
            if window is None:
                handle(*run())
                return
            while window.full():
                handle(*window.pop())
            window.submit(run)

        t_wall = time.perf_counter()
        try:
            try:
                while consumed < len(spans):
                    if fuse > 1 and window is not None and mode is None \
                            and len(window):
                        # resolve the outfeed mode BEFORE stacking the
                        # next fused group: if the first result picks
                        # window mode, handle() drops fuse to 1 and the
                        # O(window · batch) device-memory bound must
                        # not be multiplied by an already-stacked group
                        handle(*window.pop())
                        continue
                    if fuse > 1 and consumed + fuse <= n_full:
                        group = [next_prepared() for _ in range(fuse)]
                        try:
                            stacked = [np.stack([g[0][j] for g in group])
                                       for j in range(len(input_cols))]
                        except ValueError:
                            # shapes drifted between microbatches
                            # (variable-geometry pack): dispatch this
                            # group per-batch
                            for packed, n_pad, pin in group:
                                dispatch(_run_fn_direct(), packed,
                                         consumed, n_pad, pin=pin,
                                         donate_key=(donate_flag
                                                     and plan
                                                     is not None))
                            continue
                        fused_fn = _fused_wrapper(
                            _run_fn(), fuse, n_args=len(input_cols),
                            donate=donate_flag)
                        dispatch(fused_fn, stacked, consumed, 0,
                                 fused=True,
                                 donate_key=bool(donate_flag
                                                 and input_cols))
                    else:
                        packed, n_pad, pin = next_prepared()
                        if pin is not None:
                            # RESIDENT batch: never hand a donating
                            # program the cached buffers (XLA would
                            # reuse them, corrupting every later
                            # replay) — the non-donating wrapper
                            # variant runs instead. Only a codec
                            # wrapper can carry donate_argnums on the
                            # per-batch path, so only that combination
                            # counts as a blocked donation.
                            if donate_flag and plan is not None:
                                _dc.count_donation_blocked()
                            dispatch(_run_fn(), packed, consumed,
                                     n_pad, pin=pin)
                        else:
                            dispatch(_run_fn_direct(), packed,
                                     consumed, n_pad,
                                     donate_key=(donate_flag
                                                 and plan is not None))
                while window is not None and len(window):
                    handle(*window.pop())
            finally:
                if window is not None:
                    window.close()
                if infeed is not None:
                    infeed.close()
                if cache is not None:
                    cache.flush()  # persist throttled manifest entries
            while pending:
                with report.stage("d2h"):
                    _faults.fire("frame.d2h")
                    _drain(pending.pop(0), outputs)
            if mode == "acc":
                with report.stage("d2h"):
                    _faults.fire("frame.d2h")
                    _fetch_accumulated(acc, segs, outputs)
        finally:
            # the final d2h drain runs supervised too (a wedged fetch
            # IS the interesting stall); only now does the run's
            # heartbeat leave the watchdog's scan list
            hb_run.__exit__(None, None, None)
            # sweep device-cache pins an unwind stranded (a cancelled
            # window future whose run() never started still holds its
            # batch's pin) — release is idempotent per token; snapshot
            # first, dispatch threads may still be discarding
            for p in list(pins):
                p.release()
            pins.clear()
        # close out the run: wall time + publish totals into the
        # process-wide metrics registry (obs.snapshot() / JSONL sink)
        if plan is not None and plan.resolved():
            # deferred specs ('auto'/'u8') now know their pick — the
            # report shows what actually ran, not what was asked for
            report.config["wire_codec"] = plan.names()[0]
        report.finish(time.perf_counter() - t_wall)
        out = self
        for name, chunks in zip(output_cols, outputs):
            col = np.concatenate(chunks, axis=0) if chunks else np.empty((0,))
            if col.ndim > 1:
                obj = np.empty(len(col), dtype=object)
                obj[:] = list(col)
                col = obj
            out = out.with_column(name, col)
        return out


_PIPELINE_WINDOW = 2  # in-flight device batches retained before fetch
_ACC_FETCH_CAP = 512 * 1024 * 1024  # max bytes held on device in "acc" mode


def _pick_fetch_mode(result, est_total_rows: int) -> str:
    """Device-resident accumulation for small outputs (features, scores),
    windowed drain for big ones (image-sized tensors) or host results.
    Sized per ROW (not per dispatch) so fused multi-step dispatches —
    whose results are fuse_steps× bigger — estimate the same total."""
    if not all(hasattr(r, "copy_to_host_async") for r in result):
        return "window"  # fn returned host arrays; drain is free
    rows = max(1, int(result[0].shape[0]) if result[0].ndim else 1)
    per_row = sum(r.nbytes for r in result) / rows
    return "acc" if per_row * est_total_rows <= _ACC_FETCH_CAP else "window"


def _fetch_accumulated(acc, segs, outputs):  # tpudl: hot-path
    """Fetch the accumulated device results: start (or re-arm)
    ``copy_to_host_async`` on EVERY pending array first, so all the
    copies cross the link concurrently, THEN convert each chunk —
    each ``np.asarray`` blocks only on its own already-in-flight copy
    instead of issuing one serialized round-trip at a time (the
    round-10 d2h fix; dispatch normally armed these copies already —
    re-arming a finished copy is a no-op). Concatenation happens
    host-side; per-batch mesh padding is stripped per segment."""
    for chunks in acc:
        for r in chunks:
            if hasattr(r, "copy_to_host_async"):
                r.copy_to_host_async()
    for i, chunks in enumerate(acc):
        if not chunks:
            continue
        # tpudl: ignore[hot-sync] — this fetch IS the d2h stage: every
        # chunk's copy is already in flight (armed above + at dispatch),
        # so each conversion awaits its own copy, nothing else
        parts = [np.asarray(r) for r in chunks]
        host = np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        if any(n_pad for _, n_pad in segs):
            out, pos = [], 0
            for padded_len, n_pad in segs:
                out.append(host[pos: pos + padded_len - n_pad])
                pos += padded_len
            outputs[i].extend(out)
        else:
            outputs[i].append(host)


def _drain(entry, outputs):  # tpudl: hot-path
    (result, n_pad) = entry
    for i, r in enumerate(result):
        # tpudl: ignore[hot-sync] — this fetch IS the d2h stage; the
        # copy was started async at dispatch (copy_to_host_async), so
        # this blocks only on the oldest window entry
        r = np.asarray(r)  # device→host; blocks until this batch is done
        outputs[i].append(r[: r.shape[0] - n_pad] if n_pad else r)


def null_mask(col) -> np.ndarray:
    """Per-row null flags: object ``None`` and float ``NaN`` count as
    null, everything else does not. The ONE definition of nullness —
    shared by ``Frame.dropna`` and SQL ``IS NULL`` so the two can never
    disagree. A LazyColumn answers via its cheap ``validity_mask`` probe
    when it has one (no decode at all); otherwise the scan streams in
    CHUNKS (parallel reads, O(chunk) held payloads, each discarded
    before the next chunk)."""
    if isinstance(col, LazyColumn):
        valid = col.validity_mask()
        if valid is not None:
            return ~np.asarray(valid, dtype=bool)
        flags = np.empty(len(col), dtype=bool)
        for start in range(0, len(col), 256):
            stop = min(start + 256, len(col))
            chunk = col[start:stop]
            flags[start:stop] = [v is None for v in chunk]
        return flags
    if col.dtype == object:
        return np.array([v is None for v in col], dtype=bool)
    if np.issubdtype(col.dtype, np.floating):
        return np.isnan(col)
    return np.zeros(len(col), dtype=bool)


def _hash_value(h, v) -> None:
    """One object-column row into a running hash — covers the column
    shapes this frame actually stores (image structs, raw bytes,
    ndarrays, scalars/strings, None); anything else contributes its
    repr (best effort, documented in DATA.md)."""
    if v is None:
        h.update(b"\x00none")
    elif isinstance(v, bytes):
        h.update(b"\x00b")
        h.update(v)
    elif isinstance(v, dict):
        for k in sorted(v):
            h.update(f"\x00k{k}=".encode())
            _hash_value(h, v[k])
    elif isinstance(v, np.ndarray):
        h.update(f"\x00a{v.dtype}{v.shape}".encode())
        h.update(np.ascontiguousarray(v).tobytes())
    else:
        h.update(f"\x00r{v!r}".encode())


def _default_pack(sl: np.ndarray) -> np.ndarray:
    if sl.dtype == object:
        return np.stack([np.asarray(v) for v in sl])
    return np.asarray(sl)


def concat(frames: Sequence[Frame]) -> Frame:
    if not frames:
        raise ValueError("concat of zero frames")
    names = frames[0].columns
    for i, f in enumerate(frames[1:], start=1):
        if set(f.columns) != set(names):
            raise ValueError(
                f"concat schema mismatch: frame 0 has {names}, "
                f"frame {i} has {f.columns}"
            )
    out = {}
    for n in names:
        cols = [f[n] for f in frames]
        if any(c.dtype == object for c in cols):
            merged = np.empty(sum(len(c) for c in cols), dtype=object)
            i = 0
            for c in cols:
                # a LazyColumn materializes here: concat is an explicit
                # whole-frame operation, not the streaming path
                merged[i : i + len(c)] = c[:] if isinstance(c, LazyColumn) else c
                i += len(c)
            out[n] = merged
        else:
            out[n] = np.concatenate(cols, axis=0)
    return Frame(out, frames[0].num_partitions)
