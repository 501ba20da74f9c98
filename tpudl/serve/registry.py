"""Multi-model serve registry with warm-start from the program store.

Registration is where TTFT is won (SERVE.md): ``add_model`` builds the
model's :class:`~tpudl.serve.slots.SlotDecoder` and — when the AOT
store is armed — restores the persisted program table
(``ensure_restored(block=True)``) and submits every serve-loop
signature through ``precompile_serve``. A previously-served model's
first token is then a DESERIALIZATION away, not a 60-second jit (not
measured on the chip).

One instance lock (``serve.registry``) guards the name→entry map;
the ``serve.models`` gauge publishes outside it.
"""

from __future__ import annotations

import time

from tpudl.obs import metrics as _metrics
from tpudl.serve.slots import SlotDecoder
from tpudl.testing import tsan as _tsan

__all__ = ["ModelEntry", "ModelRegistry"]


class ModelEntry:
    """One registered model: its engine plus warm-start forensics.
    ``tokenizer`` is set for text-serving entries (``add_generator``)
    so the request path can encode prompts / decode completions with
    the exact vocab the model was trained against."""

    __slots__ = ("name", "model", "params", "engine",
                 "warm_signatures", "warm_s", "tokenizer")

    def __init__(self, name: str, model, params, engine: SlotDecoder,
                 warm_signatures: int, warm_s: float, tokenizer=None):
        self.name = name
        self.model = model
        self.params = params
        self.engine = engine
        self.warm_signatures = warm_signatures
        self.warm_s = warm_s
        self.tokenizer = tokenizer


class ModelRegistry:
    """Name → :class:`ModelEntry` map shared by one server."""

    def __init__(self):
        self._lock = _tsan.named_lock("serve.registry")
        self._entries: dict[str, ModelEntry] = {}

    def add_model(self, name: str, model, params, *,
                 slots: int | None = None,
                 cache_len: int | None = None,
                 temperature: float = 0.0, prompt_buckets=True,
                 prompt_rungs=None, mesh=None, tp: bool = False,
                 warm: bool = True, tokenizer=None) -> ModelEntry:
        """Build the engine for ``model`` and (``warm=True``, store
        armed) AOT-warm its serve programs. ``prompt_rungs`` overrides
        the warmed prefill signature set; default is every ladder rung
        the fixed cache can admit (an over-approximation costs compile
        time once, never correctness — a rung missed here compiles on
        first use like any store miss)."""
        engine = SlotDecoder(model, params, slots=slots,
                             cache_len=cache_len,
                             temperature=temperature,
                             prompt_buckets=prompt_buckets, mesh=mesh,
                             tp=tp)
        warm_n, warm_s = 0, 0.0
        if warm:
            t0 = time.perf_counter()
            if prompt_rungs is None:
                prompt_rungs = (
                    engine._ladder.rungs_up_to(engine.cache_len - 1)
                    if engine._ladder else [])
            if prompt_rungs:
                warm_n = model.precompile_serve(
                    params, slots=engine.slots,
                    cache_len=engine.cache_len,
                    prompt_rungs=prompt_rungs,
                    temperature=engine.temperature, mesh=mesh, tp=tp,
                    block=True)
            warm_s = time.perf_counter() - t0
        entry = ModelEntry(str(name), model, params, engine, warm_n,
                           warm_s, tokenizer=tokenizer)
        with self._lock:
            self._entries[entry.name] = entry
            count = len(self._entries)
        _metrics.gauge("serve.models").set(count)
        return entry

    def add_generator(self, name: str, generator, *,
                      slots: int | None = None,
                      cache_len: int | None = None,
                      warm: bool = True) -> ModelEntry:
        """Register an :class:`~tpudl.ml.lm.LMGenerator`'s signature for
        online serving: the transformer already binds the model, the
        weights, the sampling temperature, the prompt bucket ladder,
        and the TOKENIZER — this unwraps them into :meth:`add_model`
        (so the registered entry decodes through the continuous-
        batching queue with exactly the offline stage's programs) and
        files the tokenizer on the entry for the request path."""
        missing = [k for k in ("model", "weights", "tokenizer")
                   if getattr(generator, k, None) is None]
        if missing:
            raise ValueError(
                f"add_generator needs a fully-bound LMGenerator "
                f"(missing {missing})")
        return self.add_model(
            str(name), generator.model, generator.weights,
            slots=slots, cache_len=cache_len,
            temperature=float(generator.temperature),
            prompt_buckets=(generator.promptBuckets
                            if generator.promptBuckets is not None
                            else True),
            mesh=generator.mesh, tp=bool(generator.tp), warm=warm,
            tokenizer=generator.tokenizer)

    def get(self, name: str) -> ModelEntry:
        with self._lock:
            try:
                return self._entries[name]
            except KeyError:
                raise KeyError(
                    f"model {name!r} not registered (have: "
                    f"{sorted(self._entries)})") from None

    def names(self) -> list:
        with self._lock:
            return sorted(self._entries)

    def entries(self) -> list:
        with self._lock:
            return list(self._entries.values())
