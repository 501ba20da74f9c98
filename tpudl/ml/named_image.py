"""DeepImageFeaturizer / DeepImagePredictor — pretrained named models.

Rebuild of ref: python/sparkdl/transformers/named_image.py
(DeepImageFeaturizer ~L40, DeepImagePredictor ~L120,
_NamedImageTransformer internal) and its JVM fast path
src/main/scala/com/databricks/sparkdl/DeepImageFeaturizer.scala. The
reference's "fast path" is graph surgery + TensorFrames JNI; ours is one
jit-fused XLA program per batch: resize → channel-order fix → imagenet
preprocess → zoo forward pass, data-parallel over the mesh. This is the
benchmark path (BASELINE.json configs[0]).

Weights: ``weights="random"`` (seeded, offline-friendly),
``"imagenet"`` (converted from keras.applications when its cache exists),
or a path to a .keras/.h5 model or an .npz param dump.
"""

from __future__ import annotations

import os

import numpy as np

import jax

from tpudl.image import ops as image_ops
from tpudl.ml.params import (HasInputCol, HasOutputCol, Param,
                             TypeConverters, keyword_only)
from tpudl.ml.pipeline import Transformer
from tpudl.ml.tf_image import ImageBatchWarmup, _pack_image_structs
from tpudl.zoo.preprocessing import decode_predictions
from tpudl.zoo.registry import SUPPORTED_MODELS, getKerasApplicationModel

__all__ = ["DeepImageFeaturizer", "DeepImagePredictor"]

_PARAMS_CACHE: dict[tuple[str, str], dict] = {}


def load_named_params(model_name: str, weights: str = "random") -> dict:
    """Resolve a named model's param pytree. The symbolic sources
    ("random", "imagenet") are cached per model — the moral equivalent of
    the reference broadcasting one GraphDef per model (Models.scala
    packaged .pb resources). Path sources are re-read on every call here;
    note the transformer layer above additionally caches its compiled
    program keyed on (path, mtime), so a rewrite within mtime granularity
    can still serve the previous compile (see _apply_batches)."""
    cacheable = weights in ("random", "imagenet")
    key = (model_name, weights)
    if cacheable and key in _PARAMS_CACHE:
        return _PARAMS_CACHE[key]
    model = getKerasApplicationModel(model_name)
    if weights == "random":
        # host fast path: numpy init, zero device dispatches (a device
        # init dispatches one small kernel per layer)
        params = model.init(0)
    elif weights == "imagenet":
        # offline artifact first when $TPUDL_WEIGHTS_DIR is set (see
        # zoo.convert.save_named_params) — no keras download attempt on
        # egress-less hosts; else the live keras cache/download.
        wdir = os.environ.get("TPUDL_WEIGHTS_DIR")
        art = os.path.join(wdir, f"{model_name}.npz") if wdir else None
        if art and os.path.exists(art):
            from tpudl.zoo.convert import load_params_npz

            params = load_params_npz(art)
        else:
            try:
                from tpudl.zoo.convert import params_from_keras

                kmodel = model.keras_builder()(weights="imagenet")
                params = params_from_keras(kmodel)
            except Exception as e:
                raise RuntimeError(
                    f"imagenet weights unavailable (keras download failed: "
                    f"{e!r}) and no offline artifact at "
                    f"{art or '$TPUDL_WEIGHTS_DIR/' + model_name + '.npz'!r}."
                    f" Run tpudl.zoo.convert.save_named_params("
                    f"{model_name!r}, '<dir>/{model_name}.npz') once on a "
                    "networked host and set TPUDL_WEIGHTS_DIR=<dir>.") from e
    elif weights.endswith(".npz"):
        from tpudl.zoo.convert import load_params_npz

        # an explicitly-named artifact is the user vouching for the file,
        # so legacy pickled layouts stay loadable here; only the
        # TPUDL_WEIGHTS_DIR auto-discovery path above refuses them
        params = load_params_npz(weights, allow_legacy_pickle=True)
    else:
        from tpudl.zoo.convert import load_keras_model, params_from_keras

        params = params_from_keras(load_keras_model(weights))
    if cacheable:
        _PARAMS_CACHE[key] = params
    return params


def _model_geometry_pack(height: int, width: int):
    """The named-model pack: image-struct slice → stacked uint8 batch.
    A slice whose rows share one size is stacked as it is (the fused
    program resizes on device). A RAGGED slice — any real image
    directory — is first host-resized row by row to the model's own
    input geometry, as the reference does before its graph runs
    (ref: DeepImageFeaturizer.scala resizes in the JVM); the generic
    TFImageTransformer has no geometry to resize to and keeps
    refusing mixed shapes."""
    from tpudl.image import imageIO

    def pack(sl):
        sizes = {(r["height"], r["width"]) for r in sl
                 if isinstance(r, dict)}
        if len(sizes) > 1:
            sl = [imageIO.resizeImage(r, height, width)
                  if isinstance(r, dict) else r for r in sl]
        return _pack_image_structs(sl)

    pack.thread_safe = True  # pure function of its slice
    pack.cache_token = (f"{_pack_image_structs.cache_token}"
                        f":ragged->{height}x{width}")
    return pack


_COMPUTE_DTYPES = ("float32", "bfloat16", "float16")


def _check_compute_dtype(value: str) -> str:
    if value not in _COMPUTE_DTYPES:
        raise ValueError(
            f"computeDtype must be one of {_COMPUTE_DTYPES}, got {value!r}")
    return value


class _NamedImageTransformer(ImageBatchWarmup, Transformer, HasInputCol,
                             HasOutputCol):
    """Shared engine (ref: named_image.py _NamedImageTransformer): packs
    the image column, runs ONE fused program —
    uint8 batch → float → resize(model geometry) → preprocess → net.
    ``warmup(h, w)`` (ImageBatchWarmup) compiles without fetching."""

    modelName = Param(None, "modelName", "named model from the zoo registry",
                      TypeConverters.supportedNameConverter(SUPPORTED_MODELS))

    def setModelName(self, value):
        return self.set(self.modelName, value)

    def getModelName(self):
        return self.getOrDefault(self.modelName)

    def _head_fn(self, model, params):  # pragma: no cover - abstract
        raise NotImplementedError

    def _get_jfn(self):
        """The fused jitted program (cached per (model, weights, dtype)):
        uint8 batch → float → resize(model geometry) → preprocess → net."""
        name = self.getModelName()
        dtype = self.computeDtype

        def build():
            import jax.numpy as jnp

            model = getKerasApplicationModel(name)
            params = load_named_params(name, self.weights)
            if dtype != "float32":
                # MXU-native precision: bf16 params+activations, fp32 in
                # the decode/preprocess prologue and the output epilogue.
                from tpudl.zoo.registry import cast_params

                params = cast_params(params, dtype)
            # one transfer for the whole tree, replicated over the mesh if
            # one is set (the Spark-broadcast analogue)
            if self.mesh is not None:
                from tpudl import mesh as M

                params = M.replicate(params, self.mesh)
            else:
                params = jax.device_put(params)
            h, w = model.input_size
            head = self._head_fn(model, params)

            def fn(batch):
                x = image_ops.to_model_input(batch, h, w, "BGR", "RGB")
                x = model.preprocess(x)
                y = head(x.astype(dtype))
                return y.astype(jnp.float32)

            return fn

        if self.weights in ("random", "imagenet"):
            key = (name, self.weights, dtype)
        else:  # file-backed weights may be rewritten between calls
            key = (name, self.weights, dtype, os.path.getmtime(self.weights))
        return self._cached_jit(key, build)

    def _apply_batches(self, frame, out_col):
        jfn = self._get_jfn()
        h, w = getKerasApplicationModel(self.getModelName()).input_size
        return frame.map_batches(
            jfn, [self.getInputCol()], [out_col],
            batch_size=self.batchSize, pack=_model_geometry_pack(h, w),
            **self._pipeline_opts())


class DeepImageFeaturizer(_NamedImageTransformer):
    """Penultimate-layer feature vectors for transfer learning
    (ref: named_image.py ~L40; Scala DeepImageFeaturizer.transform ~L80).
    """

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, modelName=None,
                 weights="random", batchSize=64, mesh=None,
                 computeDtype="float32", prefetchDepth=None,
                 prepareWorkers=None, fuseSteps=None, dispatchDepth=None,
                 wireCodec=None, cacheDir=None, deviceCache=None):
        super().__init__()
        self.weights = weights
        self.batchSize = int(batchSize)
        self.mesh = mesh
        self.computeDtype = _check_compute_dtype(computeDtype)
        kwargs = dict(self._input_kwargs)
        for k in ("weights", "batchSize", "mesh", "computeDtype"):
            kwargs.pop(k, None)
        self._set_pipeline_opts(kwargs)
        self._set(**kwargs)

    def _head_fn(self, model, params):
        return lambda x: model.featurize(params, x)

    def _transform(self, frame):
        return self._apply_batches(frame, self.getOutputCol())


class DeepImagePredictor(_NamedImageTransformer):
    """ImageNet class predictions, optionally decoded to (wnid, label,
    score) topK rows (ref: named_image.py ~L120 — pipes through
    TFImageTransformer + keras decode_predictions)."""

    decodePredictions = Param(None, "decodePredictions",
                              "decode scores to (wnid,label,score) topK",
                              TypeConverters.toBoolean)
    topK = Param(None, "topK", "how many predictions to keep",
                 TypeConverters.toInt)

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, modelName=None,
                 decodePredictions=False, topK=5, weights="random",
                 batchSize=64, mesh=None, computeDtype="float32",
                 prefetchDepth=None, prepareWorkers=None, fuseSteps=None,
                 dispatchDepth=None, wireCodec=None, cacheDir=None,
                 deviceCache=None):
        super().__init__()
        self._setDefault(decodePredictions=False, topK=5)
        self.weights = weights
        self.batchSize = int(batchSize)
        self.mesh = mesh
        self.computeDtype = _check_compute_dtype(computeDtype)
        kwargs = dict(self._input_kwargs)
        for k in ("weights", "batchSize", "mesh", "computeDtype"):
            kwargs.pop(k, None)
        self._set_pipeline_opts(kwargs)
        self._set(**kwargs)

    def _head_fn(self, model, params):
        return lambda x: model.predict(params, x)

    def _transform(self, frame):
        out_col = self.getOutputCol()
        out = self._apply_batches(frame, out_col)
        if self.getOrDefault(self.decodePredictions):
            scores = np.stack(list(out[out_col]))
            decoded = decode_predictions(scores, top=self.getOrDefault(self.topK))
            col = np.empty(len(decoded), dtype=object)  # keep tuples un-coerced
            col[:] = decoded
            out = out.drop(out_col).with_column(out_col, col)
        return out
