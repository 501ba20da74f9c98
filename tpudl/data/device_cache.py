"""HBM-tier device-resident batch cache: epoch ≥ 2 ships zero wire bytes.

The top of the cache hierarchy (DATA.md "Cache hierarchy"): disk shards
(PR 4) killed the re-DECODE, this module kills the re-SHIP. The July
2026 record's decomposition says when it matters: the chip did ~5,144
img/s with the input already device-resident vs 89.6 img/s end-to-end,
because every epoch re-crossed an 8–22 MB/s H2D wire with the same
bytes (the v5e host's own link measures 590–670 MB/s, PERF.md). The
paper-shaped workloads — featurize-then-fit, multi-epoch estimator
fitting, repeat batch inference over one table — re-ship *identical*
bytes every pass, so a :class:`DeviceBatchCache` pins the prepared,
codec-ENCODED (u8-on-wire) batches in device memory once and replays
them for free thereafter.

Contracts (each one load-bearing):

- **identity** — entries are keyed by the SAME fingerprint material as
  the shard cache (frame fingerprint/cache_key + input columns + batch
  size + codec spec + pack token) **plus the mesh topology**
  (:func:`run_key`): a shard stored as sharded arrays under
  ``NamedSharding(P('data'))`` on one mesh is never replayed onto a
  different mesh — a different topology is a key MISS, not a reshard;
- **budget** — ``TPUDL_DATA_HBM_BUDGET_MB`` caps total resident bytes
  (default: a conservative fraction of the device's reported memory,
  or :data:`DEFAULT_BUDGET_BYTES` when the backend reports none). LRU
  entries evict to make room; an entry that cannot fit even after
  evicting everything unpinned is simply not stored (the batch stays a
  plain wire transfer — never an error);
- **pinning** — a batch handed to an in-flight dispatch is pinned via
  its :class:`Pin` token until the dispatch returns, so mid-flight
  entries are never evicted out of the byte accounting while their
  buffers are still live on device (the budget stays honest);
- **donation** — resident buffers must NEVER be donated: a donating
  program would hand XLA write access to (or outright invalidate) the
  cached buffer, corrupting every later replay. The frame executor
  routes resident batches through the NON-donating wrapper variant and
  counts ``data.hbm.donation_blocked`` (DATA.md "Donation caveat");
- **restart = cold** — this cache is process-local by nature (device
  buffers die with the client); a relaunch falls back to the PR-4 disk
  shards (zero decodes, bytes re-shipped exactly once) and re-pins.

Observability: ``data.hbm.bytes_resident`` / ``budget_bytes`` gauges,
``hits`` / ``misses`` / ``puts`` / ``evictions`` / ``bytes_served`` /
``donation_blocked`` counters — the roofline model subtracts
``bytes_served`` from its wire attribution and ``obs top`` renders the
residency/budget line live (OBSERVABILITY.md).
"""

from __future__ import annotations

import os
import zlib
from collections import OrderedDict

import numpy as np

from tpudl.testing import tsan as _tsan

__all__ = ["DeviceBatchCache", "Pin", "get_device_cache",
           "reset_device_cache", "run_key", "budget_bytes",
           "bulk_resident", "array_token", "count_donation_blocked",
           "count_put_failed",
           "DEFAULT_BUDGET_BYTES", "DEFAULT_BUDGET_FRACTION"]

# when the backend reports no memory figure (CPU simulation, exotic
# PJRT plugins), stay conservative: enough for the bench/test datasets,
# far below any real HBM
DEFAULT_BUDGET_BYTES = 256 << 20
# fraction of the device's reported bytes_limit the cache may own when
# no explicit TPUDL_DATA_HBM_BUDGET_MB is set — the model, activations
# and the executor's in-flight batches need the rest
DEFAULT_BUDGET_FRACTION = 0.25

_BUDGET_CACHE: dict = {}


def budget_bytes(allow_device: bool = True) -> int | None:
    """The resident-byte budget. ``TPUDL_DATA_HBM_BUDGET_MB`` wins
    (an explicit ``0`` means ZERO — residency forbidden, never
    silently replaced by the default); otherwise
    :data:`DEFAULT_BUDGET_FRACTION` of the first local device's
    reported ``bytes_limit`` (cached per process), falling back to
    :data:`DEFAULT_BUDGET_BYTES` when the backend reports nothing.
    ``allow_device=False`` reads the env/cache WITHOUT ever importing
    jax or touching a device — the roofline/status-thread contract
    (returns None when the budget was never derived)."""
    env = os.environ.get("TPUDL_DATA_HBM_BUDGET_MB")
    if env:
        try:
            return max(0, int(float(env) * (1 << 20)))
        except ValueError:
            pass
    if "bytes" in _BUDGET_CACHE:
        return _BUDGET_CACHE["bytes"]
    if not allow_device:
        return None
    derived = DEFAULT_BUDGET_BYTES
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        limit = (stats or {}).get("bytes_limit")
        if limit:
            derived = int(limit * DEFAULT_BUDGET_FRACTION)
    # tpudl: ignore[swallowed-except] — backends without memory_stats
    # (CPU simulation, older PJRT) keep the conservative default; an
    # unknown budget must never crash the executor's setup path
    except Exception:
        pass
    _BUDGET_CACHE["bytes"] = derived
    return derived


def run_key(material_key: str, mesh=None) -> str:
    """One run's device-cache namespace: the shard-cache key string
    (fingerprint material + cols + batch + codec + pack — see
    ``tpudl.data.shards.cache_key``) extended with the MESH TOPOLOGY
    **and device identity**, so resident shards stored under one
    ``NamedSharding`` are a key miss on any other mesh — including a
    same-shape mesh over a DIFFERENT device slice, whose replay would
    silently run on the wrong devices (the PR-11 topology-guard
    contract, here at the buffer level)."""
    if mesh is None:
        topo = "single"
    else:
        topo = (",".join(f"{k}={v}"
                         for k, v in sorted(dict(mesh.shape).items()))
                + "|dev="
                + ",".join(str(getattr(d, "id", d))
                           for d in mesh.devices.flat))
    return f"{material_key}|mesh={topo}"


# array_token memo: the estimator calls it per TRIAL on the same X/y
# objects — re-hashing a multi-GB dataset 16× per sweep (under the GIL,
# across concurrent trial threads) would cost more than the cache
# saves. Keyed by id(), validated by weakref identity (a recycled id
# after gc can never serve a stale token) AND a head+tail sample crc
# (an IN-PLACE mutation of a memoized array — X[:] = normalize(X) —
# must re-key, not replay the pre-mutation device buffers). Guarded by
# its own leaf lock: concurrent trial threads share the memo.
_TOKEN_MEMO: dict = {}
_TOKEN_MEMO_CAP = 32
_TOKEN_MEMO_LOCK = _tsan.named_lock("data.device_cache.token_memo")
_PROBE_ELEMS = 16384


def _probe_crc(carr: np.ndarray) -> int:
    """crc32 over the first+last ``_PROBE_ELEMS`` elements of a
    C-contiguous array — O(64KB) no matter the array size (reshape of
    a contiguous array is a view)."""
    flat = carr.reshape(-1)
    return zlib.crc32(flat[-_PROBE_ELEMS:].tobytes(),
                      zlib.crc32(flat[:_PROBE_ELEMS].tobytes()))


def array_token(arr) -> str:
    """Cheap content identity of one host array (the estimator's bulk
    residency key): crc32 over the raw bytes + shape/dtype, memoized
    per live array object. A changed dataset — a new object OR an
    in-place rewrite of the same one — re-keys instead of replaying
    stale device buffers (the memo hit re-probes a 64KB head+tail
    sample; a mutation the sample misses everywhere is the same
    residual risk class as any sampling fingerprint, documented
    here)."""
    import weakref

    contiguous = (getattr(arr, "flags", None) is not None
                  and arr.flags.c_contiguous)
    if contiguous:
        with _TOKEN_MEMO_LOCK:
            memo = _TOKEN_MEMO.get(id(arr))
        if memo is not None and memo[0]() is arr \
                and _probe_crc(arr) == memo[2]:
            return memo[1]
    carr = np.ascontiguousarray(arr)
    token = f"{carr.dtype}{carr.shape}:{zlib.crc32(carr) & 0xFFFFFFFF:08x}"
    if not contiguous:
        return token  # the probe view needs the original's layout
    try:
        ref = weakref.ref(arr)
    except TypeError:  # non-weakrefable input (rare): skip the memo
        return token
    probe = _probe_crc(arr)
    with _TOKEN_MEMO_LOCK:
        if len(_TOKEN_MEMO) >= _TOKEN_MEMO_CAP:
            _TOKEN_MEMO.pop(next(iter(_TOKEN_MEMO)), None)
        _TOKEN_MEMO[id(arr)] = (ref, token, probe)
    return token


class Pin:
    """One acquisition's pin on one entry. ``release()`` is idempotent
    per token — checked-and-flipped UNDER the cache lock, so the
    executor's dispatch-path release and its unwind sweep can race on
    the same token (window.close() is shutdown(wait=False)) without
    double-decrementing a pin another concurrent run still holds."""

    __slots__ = ("_entry", "_cache", "_released")

    def __init__(self, cache: "DeviceBatchCache", entry: "_Entry"):
        self._cache = cache
        self._entry = entry
        self._released = False

    @property
    def arrays(self) -> tuple:
        return self._entry.arrays

    @property
    def n_pad(self) -> int:
        return self._entry.n_pad

    @property
    def nbytes(self) -> int:
        return self._entry.nbytes

    @property
    def codecs(self):
        return self._entry.codecs

    def release(self) -> None:
        self._cache._release(self)


class _Entry:
    __slots__ = ("key", "arrays", "n_pad", "codecs", "nbytes", "pins",
                 "resident", "owner")

    def __init__(self, key, arrays, n_pad, codecs):
        self.key = key
        self.arrays = tuple(arrays)
        self.n_pad = int(n_pad)
        self.codecs = codecs
        self.nbytes = int(sum(int(getattr(a, "nbytes", 0))
                              for a in self.arrays))
        self.pins = 0
        # False once evicted/cleared: an outstanding Pin's late release
        # must not adjust tallies for an entry no longer in the map
        self.resident = False
        # attribution scope key charged for these bytes at put() — an
        # eviction from ANY run/thread credits this owner, so the
        # per-scope HBM ledger never leaks an evicted entry's bytes
        # onto whoever happened to trigger the eviction
        self.owner = None

    @property
    def run(self):
        return self.key[0] if isinstance(self.key, tuple) else self.key


class DeviceBatchCache:
    """LRU cache of device-resident prepared batches under a byte
    budget. Keys are ``(run_key, batch_index)`` tuples; values hold the
    encoded device arrays + their mesh pad count + the resolved codec
    keys (so an all-hits replay can still reconstruct the device
    prologue via ``CodecPlan.adopt``).

    The caller places arrays on device (``jax.device_put`` /
    ``mesh.transfer_batch``) BEFORE ``put`` — this class only owns
    residency accounting, LRU order, pinning and eviction; it never
    issues a device op itself (and therefore never blocks under its
    lock)."""

    def __init__(self, budget: int | None = None):
        if budget is None:
            budget = budget_bytes()  # an explicit env 0 stays 0
        self._budget = int(budget if budget is not None
                           else DEFAULT_BUDGET_BYTES)
        self._entries: OrderedDict = OrderedDict()
        self._bytes = 0
        # running tallies so would_fit()/put() admission is O(1) under
        # the lock instead of an O(entries) scan per batch (the prepare
        # pool contends on this lock): pinned bytes total + unpinned
        # bytes per run (evictable-for-run-r = unpinned − unpinned[r])
        self._pinned_bytes = 0
        self._unpinned_by_run: dict = {}
        self._lock = _tsan.named_lock("data.device_cache")
        from tpudl.obs import metrics as _m

        _m.gauge("data.hbm.budget_bytes").set(self._budget)
        _m.gauge("data.hbm.bytes_resident").set(0)

    @property
    def budget(self) -> int:
        return self._budget

    @property
    def bytes_resident(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def _pin_locked(self, entry: _Entry) -> None:
        if entry.pins == 0 and entry.resident:
            self._pinned_bytes += entry.nbytes
            self._run_unpinned_locked(entry.run, -entry.nbytes)
        entry.pins += 1

    def _run_unpinned_locked(self, run, delta: int) -> None:
        v = self._unpinned_by_run.get(run, 0) + delta
        if v <= 0:
            self._unpinned_by_run.pop(run, None)
        else:
            self._unpinned_by_run[run] = v

    def _admissible_locked(self, nbytes: int, run) -> bool:
        free = self._budget - self._bytes
        evictable = ((self._bytes - self._pinned_bytes)
                     - self._unpinned_by_run.get(run, 0))
        return nbytes <= free + max(0, evictable)

    def get(self, key) -> Pin | None:
        """The pinned entry for ``key`` (LRU-touched), or None. The
        caller MUST ``release()`` the returned :class:`Pin` once the
        batch's in-flight dispatch completes."""
        from tpudl.obs import metrics as _m

        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self._pin_locked(entry)
        if entry is None:
            _m.counter("data.hbm.misses").inc()
            return None
        _m.counter("data.hbm.hits").inc()
        _m.counter("data.hbm.bytes_served").inc(entry.nbytes)
        return Pin(self, entry)

    def would_fit(self, nbytes: int, run=None) -> bool:
        """Could an ``nbytes`` entry for ``run`` be admitted by
        :meth:`put` (free room, or room after evicting unpinned
        entries of OTHER runs — a scan never evicts itself, see put)?
        The executor checks this BEFORE paying the device_put, so a
        batch the cache would refuse never ships a doomed copy. O(1):
        running tallies, no entry scan under the contended lock."""
        with self._lock:
            return self._admissible_locked(int(nbytes), run)

    def put(self, key, arrays, n_pad: int = 0, codecs=None) -> Pin | None:
        """Make one batch resident (arrays must already live on
        device). Returns a pinned :class:`Pin` on success, None when
        the entry cannot fit (the batch simply stays un-cached).

        Two deliberate non-obvious rules:

        - an entry ALREADY resident under ``key`` is returned pinned
          instead of being replaced — keys derive from content
          fingerprints, so same key = same bytes, and popping a
          predecessor another run still has in flight would deduct
          bytes whose device buffers are still live (the budget would
          under-count);
        - eviction to make room skips entries of the SAME run
          (``key[0]``): a sequential scan bigger than the budget must
          not LRU-thrash itself (tail evicts head, epoch 2 misses
          everything, every epoch pays the wire PLUS churn — strictly
          worse than cache-off). The prefix that fits stays resident;
          the tail stays a plain wire transfer. Cross-run reclaim
          (stale entries of a previous dataset) still evicts."""
        from tpudl.obs import attribution as _attr
        from tpudl.obs import metrics as _m

        try:
            entry = _Entry(key, arrays, n_pad, codecs)
        # a batch whose arrays cannot even describe themselves (a
        # device_put that failed mid-placement leaves buffers whose
        # metadata probes raise) must not become resident OR touch the
        # byte tallies: counted, and the batch stays a plain wire
        # transfer
        except Exception:
            count_put_failed()
            return None
        # owner resolved BEFORE the entry becomes visible in the map,
        # so a concurrent eviction always finds the right scope to
        # credit (the charge itself happens after the lock)
        sc = _attr.current_scope()
        entry.owner = sc.key if sc is not None else None
        run = entry.run
        evicted = 0
        victims: list = []
        stored = dedup = False
        with self._lock:
            old = self._entries.get(key)
            if old is not None:
                self._entries.move_to_end(key)
                self._pin_locked(old)
                entry = old
                stored = dedup = True
            elif self._admissible_locked(entry.nbytes, run):
                # feasibility FIRST: an entry that can never fit must
                # not evict other runs' residency on the way to
                # discovering that (the churn would make THEIR warm
                # epochs re-ship for nothing)
                while (self._bytes + entry.nbytes > self._budget
                       and (victim := self._evictable_locked(run))
                       is not None):
                    del self._entries[victim.key]
                    victim.resident = False
                    self._bytes -= victim.nbytes
                    self._run_unpinned_locked(victim.run,
                                              -victim.nbytes)
                    victims.append(victim)
                    evicted += 1
                if self._bytes + entry.nbytes <= self._budget:
                    entry.resident = True
                    entry.pins = 1
                    self._entries[key] = entry
                    self._bytes += entry.nbytes
                    self._pinned_bytes += entry.nbytes
                    stored = True
            resident = self._bytes
        # the Pin exists BEFORE any metric publication: once the entry
        # is stored+pinned under the lock, nothing between here and the
        # return may raise, or the pin would strand in the tallies
        # forever (bytes pinned that no caller can ever release)
        pin = Pin(self, entry) if stored else None
        try:
            if evicted:
                _m.counter("data.hbm.evictions").inc(evicted)
            _m.gauge("data.hbm.bytes_resident").set(resident)
            # attribution pairing: the ledger mirrors the resident
            # gauge EXACTLY — each victim's bytes credit its owner
            # (create=False: a folded/evicted scope's credit lands in
            # unattributed, where its debits went), the stored entry's
            # bytes charge its owner
            for v in victims:
                _attr.charge("hbm_bytes", -v.nbytes, key=v.owner,
                             create=False)
            if stored and not dedup:
                _m.counter("data.hbm.puts").inc()
                _attr.charge("hbm_bytes", entry.nbytes,
                             key=entry.owner)
        # tpudl: ignore[swallowed-except] — the observer must never
        # strand a pinned entry: accounting consistency beats a lost
        # metric tick
        except Exception:
            pass
        return pin

    def _evictable_locked(self, incoming_run):
        """Oldest unpinned entry NOT belonging to ``incoming_run`` (see
        put: a scan never evicts its own entries). Only runs when an
        eviction actually happens — admission itself is O(1)."""
        for e in self._entries.values():
            if e.pins <= 0 and e.run != incoming_run:
                return e
        return None

    def _release(self, pin: Pin) -> None:
        # token idempotence checked UNDER the lock: the dispatch-path
        # release and the unwind sweep may race on one token
        with self._lock:
            if pin._released:
                return
            pin._released = True
            e = pin._entry
            e.pins = max(0, e.pins - 1)
            if e.pins == 0 and e.resident:
                self._pinned_bytes -= e.nbytes
                self._run_unpinned_locked(e.run, e.nbytes)

    def evict_unpinned(self, run=None) -> tuple[int, int]:
        """Evict EVERY unpinned entry (all runs — or only ``run``'s
        when given), returning ``(entries, bytes_freed)``. The device
        OOM recovery rung (FAULTS.md): before retrying an allocation
        that just failed, hand the allocator back everything the cache
        holds speculatively. Pinned entries — buffers an in-flight
        dispatch still reads — stay, so the budget stays honest."""
        from tpudl.obs import attribution as _attr
        from tpudl.obs import metrics as _m

        freed = count = 0
        with self._lock:
            victims = [e for e in self._entries.values()
                       if e.pins <= 0
                       and (run is None or e.run == run)]
            for e in victims:
                del self._entries[e.key]
                e.resident = False
                self._bytes -= e.nbytes
                self._run_unpinned_locked(e.run, -e.nbytes)
                freed += e.nbytes
                count += 1
            resident = self._bytes
        if count:
            _m.counter("data.hbm.evictions").inc(count)
        _m.gauge("data.hbm.bytes_resident").set(resident)
        for e in victims:
            # credit each victim's OWNING scope (put() pairing)
            _attr.charge("hbm_bytes", -e.nbytes, key=e.owner,
                         create=False)
        return count, freed

    def clear(self) -> None:
        from tpudl.obs import attribution as _attr
        from tpudl.obs import metrics as _m

        with self._lock:
            dropped = [(e.owner, e.nbytes)
                       for e in self._entries.values()]
            for e in self._entries.values():
                e.resident = False
            self._entries.clear()
            self._bytes = 0
            self._pinned_bytes = 0
            self._unpinned_by_run.clear()
        _m.gauge("data.hbm.bytes_resident").set(0)
        for owner, nbytes in dropped:
            _attr.charge("hbm_bytes", -nbytes, key=owner, create=False)


_CACHE: DeviceBatchCache | None = None
_CACHE_LOCK = _tsan.named_lock("data.device_cache.singleton")


def get_device_cache() -> DeviceBatchCache:
    """The process-wide cache (one budget, shared by every consumer —
    frame executor, Dataset, estimator bulk residency)."""
    global _CACHE
    with _CACHE_LOCK:
        if _CACHE is None:
            _CACHE = DeviceBatchCache()
        return _CACHE


def reset_device_cache() -> None:
    """Drop the process-wide cache (tests, and the restart-semantics
    simulation: a fresh process = a fresh, COLD cache)."""
    global _CACHE
    with _CACHE_LOCK:
        if _CACHE is not None:
            _CACHE.clear()
        _CACHE = None


def count_put_failed() -> None:
    """One batch failed to become resident because its device placement
    (or its metadata probe) threw mid-way — the tallies stayed
    consistent and the batch fell back to the plain wire path; this
    counter is the operator's evidence that residency is degrading."""
    from tpudl.obs import metrics as _m

    _m.counter("data.hbm.put_failed").inc()


def count_donation_blocked() -> None:
    """One resident batch was routed away from a donating program (the
    donation caveat above) — the fallback is correct and silent for the
    user, loud for the operator."""
    from tpudl.obs import metrics as _m

    _m.counter("data.hbm.donation_blocked").inc()


def bulk_resident(key, arrays, device=None) -> Pin | None:
    """Whole-dataset residency for the estimator's multi-epoch bulk
    path: place ``arrays`` (e.g. the full X, y) on ``device`` ONCE
    under the shared budget and index batches on-device thereafter —
    every epoch past the first ships only gather indices. Returns a
    pinned :class:`Pin` (``.arrays`` are the device buffers), or None
    when the bulk doesn't fit (caller keeps the per-step host
    transfer).

    The CALLER must ``release()`` the pin when its fit/trial completes:
    the pin keeps the bulk un-evictable (budget-honest) while batches
    gather from it, and the release makes a finished dataset's bulk
    ordinary LRU prey for the NEXT dataset — a process fitting dataset
    A then dataset B must not strand A's dead buffers in the budget
    forever. Re-fits over the same data re-hit (and re-pin) the entry.
    Include a content token (:func:`array_token`) in ``key`` — and
    keep it in the RUN component (``key[0]``) so different datasets'
    bulks can evict each other (a run never evicts its own entries)."""
    cache = get_device_cache()
    hit = cache.get(key)
    if hit is not None:
        return hit
    nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
    if not cache.would_fit(nbytes,
                           run=key[0] if isinstance(key, tuple)
                           else key):
        return None
    import jax

    placed = (jax.device_put(list(arrays), device) if device is not None
              else jax.device_put(list(arrays)))
    return cache.put(key, placed, n_pad=0, codecs=None)
