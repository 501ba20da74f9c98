"""LM transformers over STRING columns — the text face of the ml API.

The paper's pitch applied to sequences (ROADMAP item 4): a language
model as a pipeline stage over a DataFrame column, with tokenization,
packing, wire coding, and compiled-program reuse all owned by the
framework. Three stages, same spellings a sparkdl user would guess from
DeepImageFeaturizer/DeepImagePredictor:

- :class:`LMFeaturizer` — string column → mean-pooled final-norm hidden
  states (the transfer-learning feature vector). Rides the FULL
  map_batches fast path: :func:`~tpudl.text.codec.tokenize_pack` on the
  prepare pool, :class:`~tpudl.text.codec.TokenCodec` ids on the wire,
  the pad-mask restore fused into the one compiled program.
- :class:`LMClassifier` — string column → label string, scored as the
  last-position logits gathered at the classes' leading token ids (the
  verbalizer pattern); same fast path, int32 on the wire both ways.
- :class:`LMGenerator` — string column → completion string. Generation
  is host-orchestrated (per-row output lengths), but every device call
  snaps to the PR-15 bucket ladders on BOTH axes — prompts pad to a
  sequence rung inside ``TinyCausalLM.generate`` (real length traced),
  chunks pad to a batch rung here (rows are independent in decode, so
  pad rows change nothing bitwise) — which is what the traceck-armed
  ragged sweep in tests/test_text.py proves: zero retraces across a
  ragged prompt mix after the rung programs are warm.

All three take ``model=`` (a :class:`~tpudl.zoo.transformer.TinyCausalLM`
or compatible), ``weights=`` (its param pytree — named to stay clear of
the ml Params machinery), and ``tokenizer=`` (a fingerprintable
:class:`~tpudl.text.tokenizer.Tokenizer`); they are Transformers, not
Estimators — training stays with tpudl.train (see examples/generate_text.py).
"""

from __future__ import annotations

import numpy as np

from tpudl.ml.params import HasInputCol, HasOutputCol, keyword_only
from tpudl.ml.pipeline import Transformer
from tpudl.obs import metrics as _obs_metrics
from tpudl.text.codec import TokenCodec, pad_mask, tokenize_pack
from tpudl.text.tokenizer import EOS_ID

__all__ = ["LMFeaturizer", "LMGenerator", "LMClassifier"]

_LM_ATTRS = ("model", "weights", "tokenizer", "maxLen", "maxNew",
             "temperature", "seed", "classes", "promptBuckets",
             "batchSize", "mesh", "tp")


class _LMStage(Transformer, HasInputCol, HasOutputCol):
    """Shared ctor plumbing: the LM trio's model/tokenizer/geometry are
    plain attributes (they parameterize the executor and the compiled
    programs, not the Param map — the batchSize/mesh precedent), and
    only inputCol/outputCol go through ``_set``."""

    def _init_lm(self):
        kwargs = dict(self._input_kwargs)
        for k in _LM_ATTRS:
            kwargs.pop(k, None)
        self._set_pipeline_opts(kwargs)
        self._set(**kwargs)

    def _require(self):
        missing = [k for k in ("model", "weights", "tokenizer")
                   if getattr(self, k, None) is None]
        if missing:
            raise ValueError(
                f"{type(self).__name__} needs {missing} — pass the "
                "TinyCausalLM (model=), its param pytree (weights=), and "
                "a tpudl.text Tokenizer (tokenizer=)")
        return self.model, self.weights, self.tokenizer

    def _hidden_mesh(self):
        """The mesh handed to the model's forward: only under ``tp``
        (heads/MLP sharded over the mesh's ``model`` axis, PR-16).
        Without tp, ``self.mesh`` still reaches ``map_batches`` for
        data-parallel batch sharding, but the forward stays dense —
        the ring/SP spelling is a training concern."""
        return self.mesh if self.tp else None

    def _codec_opts(self) -> dict:
        opts = self._pipeline_opts()
        if opts.get("wire_codec") is None:
            opts["wire_codec"] = TokenCodec(
                vocab_size=self.tokenizer.vocab_size)
        return opts


class LMFeaturizer(_LMStage):
    """String column → pooled hidden-state feature vectors [dim]."""

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, model=None,
                 weights=None, tokenizer=None, maxLen=None,
                 promptBuckets="pow2", batchSize=32, mesh=None,
                 tp=False, prefetchDepth=None, prepareWorkers=None,
                 fuseSteps=None, dispatchDepth=None, wireCodec=None,
                 cacheDir=None, deviceCache=None):
        super().__init__()
        self.model = model
        self.weights = weights
        self.tokenizer = tokenizer
        self.maxLen = maxLen
        self.promptBuckets = promptBuckets
        self.batchSize = int(batchSize)
        self.mesh = mesh
        self.tp = bool(tp)
        self._init_lm()

    def _transform(self, frame):
        import jax.numpy as jnp

        model, w, tok = self._require()
        pack = tokenize_pack(tok, seq_len=self.maxLen,
                             buckets=self.promptBuckets, bos=True)
        mesh, tp = self._hidden_mesh(), self.tp

        def build():
            def fn(tokens):
                mask = pad_mask(tokens)                    # [B, S]
                h = model.hidden(w, tokens, mesh=mesh, tp=tp)
                pooled = (h * mask[..., None]).sum(axis=1)
                return pooled / jnp.maximum(
                    mask.sum(axis=1, keepdims=True), 1.0)
            return fn

        jfn = self._cached_jit(
            (model.arch_token, id(w), "featurize", self.tp), build)
        out = frame.map_batches(
            jfn, [self.getInputCol()], [self.getOutputCol()],
            batch_size=self.batchSize, pack=pack, **self._codec_opts())
        _obs_metrics.counter("lm.embed.rows").inc(len(frame))
        return out


class LMClassifier(_LMStage):
    """String column → label string: last-real-position logits gathered
    at each class's LEADING token id (classes must therefore start with
    distinct tokens under the given tokenizer — checked loudly)."""

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, model=None,
                 weights=None, tokenizer=None, classes=None, maxLen=None,
                 promptBuckets="pow2", batchSize=32, mesh=None,
                 tp=False, prefetchDepth=None, prepareWorkers=None,
                 fuseSteps=None, dispatchDepth=None, wireCodec=None,
                 cacheDir=None, deviceCache=None):
        super().__init__()
        self.model = model
        self.weights = weights
        self.tokenizer = tokenizer
        self.classes = list(classes) if classes else None
        self.maxLen = maxLen
        self.promptBuckets = promptBuckets
        self.batchSize = int(batchSize)
        self.mesh = mesh
        self.tp = bool(tp)
        self._init_lm()

    def _class_ids(self, tok) -> list:
        if not self.classes:
            raise ValueError("LMClassifier needs classes=[...] (label "
                             "strings)")
        ids = []
        for c in self.classes:
            enc = tok.encode(c)
            if enc.size == 0:
                raise ValueError(f"class {c!r} tokenizes to nothing "
                                 f"under {tok!r}")
            ids.append(int(enc[0]))
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"classes {self.classes} do not start with distinct "
                f"token ids under {tok!r} (leading ids {ids}); pick "
                "distinguishable label strings")
        return ids

    def _transform(self, frame):
        import jax.numpy as jnp

        model, w, tok = self._require()
        class_ids = self._class_ids(tok)
        pack = tokenize_pack(tok, seq_len=self.maxLen,
                             buckets=self.promptBuckets, bos=True)
        mesh, tp = self._hidden_mesh(), self.tp

        def build():
            def fn(tokens):
                mask = pad_mask(tokens)
                logits = model.apply(w, tokens, mesh=mesh, tp=tp)
                last = jnp.maximum(
                    mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                row = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1)[:, 0, :]
                cls = row[:, jnp.asarray(class_ids, jnp.int32)]
                return jnp.argmax(cls, axis=-1).astype(jnp.int32)
            return fn

        jfn = self._cached_jit(
            (model.arch_token, id(w), "classify", tuple(class_ids),
             self.tp), build)
        out_col = self.getOutputCol()
        out = frame.map_batches(
            jfn, [self.getInputCol()], [out_col],
            batch_size=self.batchSize, pack=pack, check_finite=False,
            **self._codec_opts())
        labels = np.array(self.classes, dtype=object)[
            np.asarray(out[out_col], dtype=np.int64)]
        _obs_metrics.counter("lm.classify.rows").inc(len(frame))
        return out.drop(out_col).with_column(out_col, list(labels))


class LMGenerator(_LMStage):
    """String column → generated completion string (decoded, cut at the
    first EOS). Host-orchestrated batching: rows group by EXACT prompt
    length (``generate``'s traced real length is one scalar per batch),
    chunks pad up to a batch-ladder rung, prompts pad to a sequence
    rung inside ``generate`` — O(log B · log S) compiled programs for
    any ragged workload."""

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, model=None,
                 weights=None, tokenizer=None, maxNew=16,
                 temperature=0.0, seed=0, promptBuckets="pow2",
                 batchSize=8, mesh=None, tp=False):
        super().__init__()
        self.model = model
        self.weights = weights
        self.tokenizer = tokenizer
        self.maxNew = int(maxNew)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.promptBuckets = promptBuckets
        self.batchSize = max(1, int(batchSize))
        self.mesh = mesh
        self.tp = bool(tp)
        self._init_lm()

    def _transform(self, frame):
        import jax

        from tpudl.compile import resolve_ladder

        model, w, tok = self._require()
        texts = list(frame[self.getInputCol()])
        # bos=True guarantees plen >= 1 (generate refuses an empty
        # prompt — the logits carry would never see the model)
        prompts = tok.encode_batch(texts, bos=True)
        ladder = resolve_ladder(
            self.promptBuckets if self.promptBuckets is not None
            else "pow2")
        groups: dict = {}
        for i, p in enumerate(prompts):
            groups.setdefault(len(p), []).append(i)
        key = (jax.random.PRNGKey(self.seed)
               if self.temperature > 0 else None)
        out_rows: list = [None] * len(texts)
        n_new = 0
        for plen in sorted(groups):
            idxs = groups[plen]
            for c0 in range(0, len(idxs), self.batchSize):
                chunk = idxs[c0:c0 + self.batchSize]
                arr = np.stack([prompts[i] for i in chunk])
                b = len(chunk)
                brung = (min(self.batchSize, max(b, ladder.pick(b)))
                         if ladder is not None else b)
                if brung > b:
                    # decode rows are independent (the per-row softmax
                    # never mixes rows), so repeated pad rows leave the
                    # real rows' tokens bitwise unchanged
                    arr = np.concatenate(
                        [arr, np.repeat(arr[:1], brung - b, axis=0)])
                rng = (jax.random.fold_in(key, plen * 8191 + c0)
                       if key is not None else None)
                toks = model.generate(
                    w, arr, self.maxNew, temperature=self.temperature,
                    rng=rng, prompt_buckets=ladder,
                    mesh=self._hidden_mesh(), tp=self.tp)
                toks = np.asarray(toks)[:b]
                for row, i in zip(toks, chunk):
                    stop = np.flatnonzero(row == EOS_ID)
                    if stop.size:
                        row = row[: stop[0]]
                    out_rows[i] = row
                    n_new += int(row.size)
        _obs_metrics.counter("lm.generate.requests").inc(len(texts))
        _obs_metrics.counter("lm.generate.tokens").inc(n_new)
        completions = [tok.decode(r) for r in out_rows]
        return frame.with_column(self.getOutputCol(), completions)
