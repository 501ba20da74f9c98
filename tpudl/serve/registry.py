"""Multi-model serve registry (SERVE.md).

``add_model`` builds the model's :class:`~tpudl.serve.slots.SlotDecoder`;
registration compiles nothing — each serve program compiles on its
first request, from the persistent XLA cache when a previous process
with the same ``JAX_COMPILATION_CACHE_DIR`` compiled it (COMPILE.md).

One instance lock (``serve.registry``) guards the name→entry map;
the ``serve.models`` gauge publishes outside it.
"""

from __future__ import annotations

from tpudl.obs import metrics as _metrics
from tpudl.serve.slots import SlotDecoder
from tpudl.testing import tsan as _tsan

__all__ = ["ModelEntry", "ModelRegistry"]


class ModelEntry:
    """One registered model and its engine. ``tokenizer`` is set for
    text-serving entries (``add_generator``) so the request path can
    encode prompts / decode completions with the exact vocab the model
    was trained against."""

    __slots__ = ("name", "model", "params", "engine", "tokenizer")

    def __init__(self, name: str, model, params, engine: SlotDecoder,
                 tokenizer=None):
        self.name = name
        self.model = model
        self.params = params
        self.engine = engine
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
                 mesh=None, tp: bool = False,
                 tokenizer=None) -> ModelEntry:
        """Build the engine for ``model`` and file it under ``name``."""
        engine = SlotDecoder(model, params, slots=slots,
                             cache_len=cache_len,
                             temperature=temperature,
                             prompt_buckets=prompt_buckets, mesh=mesh,
                             tp=tp)
        entry = ModelEntry(str(name), model, params, engine,
                           tokenizer=tokenizer)
        with self._lock:
            self._entries[entry.name] = entry
            count = len(self._entries)
        _metrics.gauge("serve.models").set(count)
        return entry

    def add_generator(self, name: str, generator, *,
                      slots: int | None = None,
                      cache_len: int | None = None) -> ModelEntry:
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
            mesh=generator.mesh, tp=bool(generator.tp),
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
