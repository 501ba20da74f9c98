"""Slot-based continuous batch decoding on a fixed-geometry KV cache.

The LM serving engine (SERVE.md): ONE compiled decode-step program of
static geometry ``(slots, cache_len)`` serves a churning request mix.
Requests are INSERTED into free slots (a bucketed prefill program
scans the prompt on a fresh batch-1 row cache, then writes the whole
row into the slot cache at a TRACED slot index — the full-row write
wipes any stale state of the slot's previous occupant) and EVICTED by
pure host-side bookkeeping: the device program never changes shape, so
after warmup the serve loop performs ZERO retraces no matter how
requests churn (traceck-pinned in tests/test_serve.py).

Correctness contract, validated bitwise: each slot's token stream
equals a serial batch-1 ``generate`` of the same prompt — per-slot
traced positions mask dead cache lanes to ``-inf`` before the softmax
and per-lane zero padding keeps reductions exact, so neighbors and
stale occupants are invisible. Sampling folds each slot's key with its
OWN generation-step index, matching ``_gen_program``'s per-step
``fold_in``.

Host state (tok/pos/steps/keys) lives in writable numpy arrays — the
engine copies device outputs before mutating (device views are
read-only). The engine is single-consumer (the server thread); no lock.
"""

from __future__ import annotations

import os

import numpy as np

from tpudl.obs import metrics as _metrics
from tpudl.serve import reqtrace as _reqtrace
from tpudl.serve.queue import AdmissionError, Evicted

__all__ = ["SlotDecoder"]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class SlotDecoder:
    """Continuous-batching decode engine for one model's params.

    ``slots`` defaults to ``TPUDL_SERVE_SLOTS``; ``cache_len`` (the
    fixed per-slot KV length) defaults to the model's ``max_len``;
    ``prompt_buckets`` resolves through
    :func:`tpudl.compile.resolve_ladder` so ragged prompt lengths share
    O(log n) prefill programs. ``mesh``/``tp`` thread straight into the
    model's ``_tp_hooks`` — the slot programs are topology-keyed in
    ``_gen_jits`` like every generate program."""

    def __init__(self, model, params, *, slots: int | None = None,
                 cache_len: int | None = None, temperature: float = 0.0,
                 prompt_buckets=True, mesh=None, tp: bool = False):
        import jax
        import jax.numpy as jnp

        from tpudl.compile import resolve_ladder

        self.model = model
        self.params = params
        self.slots = (int(slots) if slots is not None
                      else _env_int("TPUDL_SERVE_SLOTS", 8))
        self.cache_len = int(cache_len if cache_len is not None
                             else model.max_len)
        self.temperature = float(temperature)
        self.mesh = mesh
        self.tp = bool(tp)
        self._ladder = resolve_ladder(prompt_buckets)
        dtype = jnp.asarray(params["embed"]["table"]).dtype
        self._cache = model.init_cache(self.slots, self.cache_len,
                                       dtype=dtype, mesh=mesh, tp=tp)
        self._tok = np.zeros(self.slots, dtype=np.int32)
        self._pos = np.zeros(self.slots, dtype=np.int32)
        self._steps = np.zeros(self.slots, dtype=np.int32)
        key0 = np.asarray(jax.random.PRNGKey(0))
        self._keys = np.stack([key0] * self.slots)
        # per-slot occupant: {"request", "tokens": [ints], "trace"}
        # or None
        self._meta: list[dict | None] = [None] * self.slots
        # decode-cadence stamp stride, resolved once (the step loop is
        # the hot path — no env read per token)
        self._trace_cadence = _reqtrace.decode_cadence()

    # -- host-side bookkeeping --------------------------------------------
    def free(self) -> list:
        return [s for s, m in enumerate(self._meta) if m is None]

    def active(self) -> list:
        return [s for s, m in enumerate(self._meta) if m is not None]

    def occupants(self) -> list:
        """``[(slot, request), ...]`` for every occupied slot — the
        server's mid-decode deadline sweep walks this."""
        return [(s, m["request"]) for s, m in enumerate(self._meta)
                if m is not None]

    def occupancy(self) -> float:
        return len(self.active()) / max(self.slots, 1)

    def rung_for(self, plen: int, max_new: int) -> int:
        """Padded prompt length for one admission: bucketed UP the
        ladder but never past what the fixed cache can hold alongside
        ``max_new`` decode steps (past the cap the exact length is
        used — honest, one extra program for an outlier)."""
        plen, max_new = int(plen), int(max_new)
        if plen + max_new > self.cache_len:
            raise ValueError(
                f"prompt ({plen}) + max_new ({max_new}) exceeds the "
                f"slot cache length {self.cache_len}")
        if self._ladder is None:
            return plen
        return max(plen, min(self._ladder.pick(plen),
                             self.cache_len - max_new))

    def _normalize_key(self, rng):
        import jax

        if rng is None:
            return np.asarray(jax.random.PRNGKey(0))
        if isinstance(rng, (int, np.integer)):
            return np.asarray(jax.random.PRNGKey(int(rng)))
        return np.asarray(rng)

    def _call(self, fn, args):
        from tpudl.compile import aot_enabled, get_program_store

        if aot_enabled():
            return get_program_store().call(fn, args)
        return fn(*args)

    # -- the three verbs ---------------------------------------------------
    def insert(self, request) -> int:
        """Prefill ``request``'s prompt into a free slot; returns the
        slot index with the first token already decoded (the request's
        TTFT moment — the server observes it). Raises the typed
        :class:`AdmissionError` (``slots_full``) when no slot is free:
        direct engine users get the same typed answer the queue gives."""
        import jax.numpy as jnp

        free = self.free()
        if not free:
            raise AdmissionError(
                f"all {self.slots} decode slots occupied; raise "
                f"TPUDL_SERVE_SLOTS or queue the request",
                reason="slots_full")
        slot = free[0]
        trace = getattr(request, "trace", None)
        if trace is not None:
            trace.stamp("slot_insert")
        plen = int(request.prompt.shape[1])
        rung = self.rung_for(plen, request.max_new)
        padded = np.zeros((1, rung), dtype=np.int32)
        padded[:, :plen] = request.prompt
        if trace is not None:
            trace.stamp("rung_pack")
        key = self._normalize_key(request.rng)
        fill = self.model._slot_prefill_program(
            rung, self.slots, self.cache_len, self.temperature,
            mesh=self.mesh, tp=self.tp)
        first, self._cache = self._call(fill, (
            self.params, self._cache, jnp.asarray(padded),
            jnp.asarray(key), jnp.asarray(plen, jnp.int32),
            jnp.asarray(slot, jnp.int32)))
        first_tok = int(np.asarray(first)[0])
        if trace is not None:
            trace.stamp("first_token")
        self._tok[slot] = first_tok
        self._pos[slot] = plen
        self._steps[slot] = 1
        self._keys[slot] = key
        self._meta[slot] = {"request": request, "tokens": [first_tok],
                            "trace": trace}
        _metrics.counter("serve.inserts").inc()
        return slot

    def step(self) -> int:
        """One decode step for EVERY active slot through the single
        compiled step program; returns the number of tokens emitted
        (0 = nothing active, no dispatch). Inactive slots ride along as
        dead lanes (their writes land at pos 0 and are overwritten by
        the next insert's full-row write)."""
        import jax.numpy as jnp

        active = self.active()
        if not active:
            return 0
        step_fn = self.model._slot_step_program(
            self.slots, self.cache_len, self.temperature,
            mesh=self.mesh, tp=self.tp)
        nxt, self._cache = self._call(step_fn, (
            self.params, self._cache, jnp.asarray(self._tok),
            jnp.asarray(self._pos), jnp.asarray(self._keys),
            jnp.asarray(self._steps)))
        nxt = np.asarray(nxt).copy()  # device views are read-only
        cad = self._trace_cadence
        for s in active:
            meta = self._meta[s]
            meta["tokens"].append(int(nxt[s]))
            trace = meta["trace"]
            if trace is not None:
                n = len(meta["tokens"])
                if n % cad == 0:
                    trace.stamp(f"decode_{n}")
        self._tok = nxt.astype(np.int32)
        self._pos[active] += 1
        self._steps[active] += 1
        _metrics.counter("serve.steps").inc()
        _metrics.counter("serve.tokens").inc(len(active))
        _metrics.gauge("serve.batch_occupancy").set(self.occupancy())
        return len(active)

    def evict(self, slot: int, error: BaseException | None = None):
        """Free ``slot`` NOW (host bookkeeping only — the next insert's
        full-row write retires the stale cache state). Returns the
        evicted request; when ``error`` is given the request is failed
        with it (typed: deadline shed, cancel), else the caller owns
        the disposition (e.g. requeue for a supervised retry)."""
        meta = self._meta[int(slot)]
        if meta is None:
            raise KeyError(f"slot {slot} is not occupied")
        self._meta[int(slot)] = None
        _metrics.counter("serve.evictions").inc()
        req = meta["request"]
        trace = meta.get("trace")
        if trace is not None:
            trace.stamp("evict")
        if error is not None:
            req.fail(error)
        return req

    def evict_all(self, error: BaseException | None = None) -> list:
        """Evict every occupant (supervised-retry reset / teardown)."""
        return [self.evict(s, error) for s in self.active()]

    def pop_completed(self) -> list:
        """Harvest ``[(request, tokens), ...]`` for every slot whose
        occupant has emitted ``max_new`` tokens, freeing the slots.
        Completion is NOT an eviction: ``serve.evictions`` counts only
        early removals."""
        out = []
        for s in self.active():
            meta = self._meta[s]
            req = meta["request"]
            if len(meta["tokens"]) >= req.max_new:
                self._meta[s] = None
                out.append((req, np.asarray(meta["tokens"],
                                            dtype=np.int32)))
        return out

    def cancel(self, request) -> bool:
        """Evict ``request`` mid-decode, failing it typed
        :class:`Evicted`; ``False`` when it occupies no slot."""
        for s, req in self.occupants():
            if req is request:
                self.evict(s, Evicted("request cancelled mid-decode"))
                return True
        return False
