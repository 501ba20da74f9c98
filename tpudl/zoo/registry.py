"""Named pretrained-model registry — the zoo's public surface.

TPU-native rebuild of sparkdl's named-model registry
(ref: python/sparkdl/transformers/keras_applications.py —
KerasApplicationModel base ~L30, InceptionV3Model/XceptionModel/
ResNet50Model/VGG16Model/VGG19Model ~L60-200, getKerasApplicationModel;
JVM twin src/main/scala/com/databricks/sparkdl/Models.scala). Each entry
couples architecture, input geometry, preprocessing mode, and featurize
semantics (penultimate-layer output, like the reference's graph cut).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.zoo import (densenet, efficientnet, inception_v3, mobilenet_v2,
                       resnet, vgg, xception)
from tpudl.zoo.core import Store
from tpudl.zoo.preprocessing import preprocess_input

__all__ = ["NamedModel", "SUPPORTED_MODELS", "getKerasApplicationModel",
           "cast_params"]


def cast_params(params, dtype):
    """Cast the floating leaves of a param pytree to ``dtype`` host-side
    (numpy handles bf16 via ml_dtypes, so the cast is free and the tree
    crosses host→device once, after casting). Non-float leaves are kept."""
    return jax.tree.map(
        lambda p: np.asarray(p).astype(dtype)
        if jnp.issubdtype(np.asarray(p).dtype, jnp.floating) else p,
        params)


@dataclasses.dataclass(frozen=True)
class NamedModel:
    name: str
    build_fn: Callable
    input_size: tuple[int, int]
    feature_dim: int
    preprocess_mode: str
    classes: int = 1000

    @property
    def keras_module(self) -> str:
        """keras.applications submodule name (its preprocess_input is the
        golden-generation oracle)."""
        return {
            "InceptionV3": "inception_v3",
            "Xception": "xception",
            "ResNet50": "resnet50",
            "VGG16": "vgg16",
            "VGG19": "vgg19",
            "MobileNetV2": "mobilenet_v2",
            "DenseNet121": "densenet",
            "ResNet101": "resnet",
            "ResNet152": "resnet",
            "EfficientNetB0": "efficientnet",
        }[self.name]

    @property
    def feature_cut(self) -> str:
        """Keras layer whose output IS the DeepImageFeaturizer vector —
        the ONE definition the golden generator and the harness
        self-check must both cut at (post-relu fc2 for VGG, avg_pool for
        the conv nets; mirrors :meth:`featurize`). A 4-D cut output
        (MobileNetV2's out_relu — its keras pool layer is auto-named,
        so unstable to cut at) gets a GlobalAveragePooling2D appended by
        the consumers."""
        return {"VGG16": "fc2", "VGG19": "fc2",
                "MobileNetV2": "out_relu"}.get(self.name, "avg_pool")

    def feature_cut_model(self, km):
        """keras Model emitting THE featurizer vector from ``km`` — the
        single definition of the oracle cut, shared by the golden
        generator and the harness self-check so they can never drift: a
        4-D cut output (MobileNetV2) gets global average pooling
        appended, matching :meth:`featurize`."""
        import keras

        cut = km.get_layer(self.feature_cut).output
        if len(cut.shape) == 4:
            cut = keras.layers.GlobalAveragePooling2D()(cut)
        return keras.Model(km.input, cut)

    # -- params ----------------------------------------------------------
    def init(self, rng, *, image_size: tuple[int, int] | None = None,
             include_top: bool = True) -> dict:
        """Random-init param pytree (Keras initializers).

        ``rng`` may be a jax PRNG key (traced under jit: one compile, params
        land on the default device) or an int seed / ``np.random.Generator``
        (host fast path: shapes are inferred abstractly via ``eval_shape``
        while the initializers draw concrete numpy arrays — zero device
        dispatches, milliseconds instead of one dispatched init kernel per
        layer)."""
        h, w = image_size or self.input_size

        if isinstance(rng, (int, np.random.Generator)):
            gen = np.random.default_rng(rng) if isinstance(rng, int) else rng
            s = Store(rng=gen)
            jax.eval_shape(
                lambda x: self.build_fn(s, x, include_top=include_top,
                                        classes=self.classes),
                jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32))
            return s.params

        def _init(key):
            s = Store(rng=key)
            self.build_fn(s, jnp.zeros((1, h, w, 3), jnp.float32),
                          include_top=include_top, classes=self.classes)
            return s.params

        # tpudl: ignore[jit-cache-churn] — params init is a deliberate
        # one-shot program (once per model build); retaining it would
        # pin a throwaway init graph for the process lifetime
        return jax.jit(_init)(rng)

    # -- pure apply fns (jit at call sites) ------------------------------
    def apply(self, params: dict, x, *, include_top=True, pooling=None,
              train: bool = False):
        """Forward pass. x: float RGB in [0,255] BEFORE preprocessing is
        NOT assumed — caller preprocesses (see preprocess)."""
        s = Store(params=params, train=train)
        y = self.build_fn(s, x, include_top=include_top, pooling=pooling,
                          classes=self.classes)
        if train:
            return y, s.bn_updates
        return y

    def preprocess(self, x):
        """float RGB [0,255] → model input domain."""
        return preprocess_input(x, self.preprocess_mode)

    def featurize(self, params: dict, x):
        """Penultimate-layer features (the DeepImageFeaturizer vector)."""
        s = Store(params=params)
        if self.build_fn in (vgg.build_vgg16, vgg.build_vgg19):
            return self.build_fn(s, x, include_top="features")
        return self.build_fn(s, x, include_top=False, pooling="avg")

    def predict(self, params: dict, x):
        """Softmax class scores (the DeepImagePredictor path)."""
        return self.apply(params, x, include_top=True)

    def keras_builder(self):
        """The matching keras.applications constructor (loader-only use:
        pretrained-weight conversion and parity tests)."""
        import keras

        return {
            "InceptionV3": keras.applications.InceptionV3,
            "Xception": keras.applications.Xception,
            "ResNet50": keras.applications.ResNet50,
            "VGG16": keras.applications.VGG16,
            "VGG19": keras.applications.VGG19,
            "MobileNetV2": keras.applications.MobileNetV2,
            "DenseNet121": keras.applications.DenseNet121,
            "ResNet101": keras.applications.ResNet101,
            "ResNet152": keras.applications.ResNet152,
            "EfficientNetB0": keras.applications.EfficientNetB0,
        }[self.name]


SUPPORTED_MODELS: dict[str, NamedModel] = {
    m.name: m
    for m in [
        NamedModel("InceptionV3", inception_v3.build, inception_v3.INPUT_SIZE,
                   inception_v3.FEATURE_DIM, inception_v3.PREPROCESS_MODE),
        NamedModel("Xception", xception.build, xception.INPUT_SIZE,
                   xception.FEATURE_DIM, xception.PREPROCESS_MODE),
        NamedModel("ResNet50", resnet.build, resnet.INPUT_SIZE,
                   resnet.FEATURE_DIM, resnet.PREPROCESS_MODE),
        NamedModel("VGG16", vgg.build_vgg16, vgg.INPUT_SIZE, 4096,
                   vgg.PREPROCESS_MODE),
        NamedModel("VGG19", vgg.build_vgg19, vgg.INPUT_SIZE, 4096,
                   vgg.PREPROCESS_MODE),
        # beyond the reference registry (which stops at the 5 above)
        NamedModel("MobileNetV2", mobilenet_v2.build,
                   mobilenet_v2.INPUT_SIZE, mobilenet_v2.FEATURE_DIM,
                   mobilenet_v2.PREPROCESS_MODE),
        NamedModel("DenseNet121", densenet.build, densenet.INPUT_SIZE,
                   densenet.FEATURE_DIM, densenet.PREPROCESS_MODE),
        NamedModel("ResNet101", resnet.build_resnet101, resnet.INPUT_SIZE,
                   resnet.FEATURE_DIM, resnet.PREPROCESS_MODE),
        NamedModel("ResNet152", resnet.build_resnet152, resnet.INPUT_SIZE,
                   resnet.FEATURE_DIM, resnet.PREPROCESS_MODE),
        NamedModel("EfficientNetB0", efficientnet.build,
                   efficientnet.INPUT_SIZE, efficientnet.FEATURE_DIM,
                   efficientnet.PREPROCESS_MODE),
    ]
}


def getKerasApplicationModel(name: str) -> NamedModel:
    if name not in SUPPORTED_MODELS:
        raise ValueError(
            f"unsupported model {name!r}; supported: {sorted(SUPPORTED_MODELS)}"
        )
    return SUPPORTED_MODELS[name]
