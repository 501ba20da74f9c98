"""Product-layer tests — the rebuild of the reference's transformer suites
(python/tests/transformers/*_test.py, SURVEY.md §4): each transformer's
Frame path compared against the plain local oracle (zoo apply / keras
predict), plus params machinery and negative converter tests
(python/tests/param/test_converters.py pattern).
"""

import numpy as np
import pytest

import jax

from tpudl.frame import Frame
from tpudl.image import imageIO


def _image_frame(n=6, h=32, w=28, seed=0):
    rng = np.random.default_rng(seed)
    structs = []
    for i in range(n):
        arr = rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
        structs.append(imageIO.imageArrayToStruct(arr, origin=f"img{i}"))
    return Frame({"image": structs})


# -- params machinery ------------------------------------------------------
class TestParams:
    def test_keyword_only_and_defaults(self):
        from tpudl.ml import TFImageTransformer

        t = TFImageTransformer(inputCol="image", outputCol="out",
                               graph=lambda x: x)
        assert t.getInputCol() == "image"
        assert t.getOutputMode() == "vector"  # default
        assert t.getOrDefault(t.channelOrder) == "RGB"

    def test_copy_extra_overrides_without_mutating(self):
        from tpudl.ml import TFImageTransformer

        t = TFImageTransformer(inputCol="image", outputCol="out",
                               graph=lambda x: x)
        t2 = t.copy({t.outputCol: "other"})
        assert t2.getOutputCol() == "other"
        assert t.getOutputCol() == "out"

    def test_type_converters_reject(self):
        from tpudl.ml import TFImageTransformer, TFTransformer

        with pytest.raises(TypeError, match="channelOrder"):
            TFImageTransformer(inputCol="i", outputCol="o",
                               graph=lambda x: x, channelOrder="XYZ")
        with pytest.raises(TypeError, match="TFInputGraph"):
            TFTransformer(tfInputGraph=42)
        with pytest.raises(TypeError, match="str"):
            TFTransformer(inputMapping={1: "x"})

    def test_output_mode_validated_via_transform_params(self):
        # regression: copy(extra)/transform(frame, params) must validate too
        from tpudl.ml import TFImageTransformer

        t = TFImageTransformer(inputCol="image", outputCol="o",
                               graph=lambda x: x)
        with pytest.raises(TypeError, match="outputMode"):
            t.transform(_image_frame(2), {t.outputMode: "vectr"})

    def test_trainable_graph_in_image_transformer(self):
        keras = pytest.importorskip("keras")
        from tpudl.ingest import TFInputGraph
        from tpudl.ml import TFImageTransformer

        keras.utils.set_random_seed(0)
        m = keras.Sequential([
            keras.layers.Input((32, 28, 3)),
            keras.layers.GlobalAveragePooling2D(),
        ])
        gin = TFInputGraph.fromKerasTrainable(m)
        frame = _image_frame(3)
        out = TFImageTransformer(inputCol="image", outputCol="f",
                                 graph=gin).transform(frame)
        assert np.stack(list(out["f"])).shape == (3, 3)

    def test_positional_args_rejected(self):
        from tpudl.ml import DeepImageFeaturizer

        with pytest.raises(TypeError, match="keyword"):
            DeepImageFeaturizer("image")

    def test_unsupported_model_name(self):
        from tpudl.ml import DeepImageFeaturizer

        with pytest.raises(TypeError, match="unsupported"):
            DeepImageFeaturizer(inputCol="image", outputCol="f",
                                modelName="NotANet")

    def test_explain_params(self):
        from tpudl.ml import DeepImagePredictor

        p = DeepImagePredictor(inputCol="image", outputCol="p",
                               modelName="ResNet50")
        text = p.explainParams()
        assert "topK" in text and "modelName" in text


# -- TFImageTransformer ----------------------------------------------------
class TestTFImageTransformer:
    def test_identity_graph_vector_mode(self):
        from tpudl.ml import TFImageTransformer

        frame = _image_frame()
        t = TFImageTransformer(inputCol="image", outputCol="flat",
                               graph=lambda x: x, channelOrder="RGB")
        out = t.transform(frame)
        # oracle: struct → array (BGR) → RGB flip → float flatten
        row0 = imageIO.imageStructToArray(frame["image"][0])
        want = row0[:, :, ::-1].astype(np.float32).reshape(-1)
        np.testing.assert_allclose(np.asarray(out["flat"][0]), want)

    def test_channel_order_bgr_passthrough(self):
        from tpudl.ml import TFImageTransformer

        frame = _image_frame()
        t = TFImageTransformer(inputCol="image", outputCol="flat",
                               graph=lambda x: x, channelOrder="BGR")
        out = t.transform(frame)
        row0 = imageIO.imageStructToArray(frame["image"][0])
        np.testing.assert_allclose(
            np.asarray(out["flat"][0]),
            row0.astype(np.float32).reshape(-1))

    def test_image_output_mode_restructs(self):
        from tpudl.ml import TFImageTransformer

        frame = _image_frame(n=3)
        t = TFImageTransformer(inputCol="image", outputCol="img2",
                               graph=lambda x: x / 2.0, channelOrder="BGR",
                               outputMode="image")
        out = t.transform(frame)
        s = out["img2"][0]
        assert s["mode"] == imageIO.imageTypeByName("CV_32FC3").ord
        orig = imageIO.imageStructToArray(frame["image"][0])
        np.testing.assert_allclose(
            imageIO.imageStructToArray(s), orig.astype(np.float32) / 2.0)

    def test_tfinputgraph_as_graph(self):
        tf = pytest.importorskip("tensorflow")
        from tpudl.ingest import TFInputGraph
        from tpudl.ml import TFImageTransformer

        g = tf.Graph()
        with g.as_default():
            x = tf.compat.v1.placeholder(tf.float32, [None, 32, 28, 3],
                                         name="x")
            y = tf.reduce_mean(x, axis=[1, 2], name="y")
        gin = TFInputGraph.fromGraphDef(g.as_graph_def(), ["x"], ["y"])
        frame = _image_frame()
        t = TFImageTransformer(inputCol="image", outputCol="m", graph=gin,
                               channelOrder="RGB")
        out = t.transform(frame)
        row0 = imageIO.imageStructToArray(frame["image"][0])[:, :, ::-1]
        want = row0.astype(np.float32).mean(axis=(0, 1))
        np.testing.assert_allclose(np.asarray(out["m"][0]), want, rtol=1e-5)

    def test_mesh_path_matches_single_device(self, mesh8):
        from tpudl.ml import TFImageTransformer

        frame = _image_frame(n=11)  # non-divisible → padding path
        t_plain = TFImageTransformer(inputCol="image", outputCol="f",
                                     graph=lambda x: x.mean(axis=(1, 2)))
        t_mesh = TFImageTransformer(inputCol="image", outputCol="f",
                                    graph=lambda x: x.mean(axis=(1, 2)),
                                    mesh=mesh8, batchSize=8)
        a = np.stack(list(t_plain.transform(frame)["f"]))
        b = np.stack(list(t_mesh.transform(frame)["f"]))
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_mixed_shapes_error(self):
        from tpudl.ml import TFImageTransformer

        rng = np.random.default_rng(0)
        structs = [
            imageIO.imageArrayToStruct(
                rng.integers(0, 255, size=(16, 16, 3), dtype=np.uint8)),
            imageIO.imageArrayToStruct(
                rng.integers(0, 255, size=(8, 8, 3), dtype=np.uint8)),
        ]
        t = TFImageTransformer(inputCol="image", outputCol="f",
                               graph=lambda x: x)
        with pytest.raises(ValueError, match="mixed image shapes"):
            t.transform(Frame({"image": structs}))


# -- named models ----------------------------------------------------------
class TestNamedImage:
    def test_ragged_slice_is_resized_to_model_geometry(self):
        """A named model knows its input geometry, so its pack resizes
        a mixed-size slice (any real image directory) on the host; a
        uniform slice is stacked untouched for the on-device resize."""
        from tpudl.ml.named_image import _model_geometry_pack

        rng = np.random.default_rng(0)

        def struct(h, w):
            return imageIO.imageArrayToStruct(
                rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8))

        pack = _model_geometry_pack(24, 32)
        ragged = pack([struct(16, 16), struct(40, 20), struct(24, 32)])
        assert ragged.shape == (3, 24, 32, 3) and ragged.dtype == np.uint8
        uniform = [struct(16, 20), struct(16, 20)]
        out = pack(uniform)
        assert out.shape == (2, 16, 20, 3)
        np.testing.assert_array_equal(
            out[1], imageIO.imageStructToArray(uniform[1]))

    def test_featurizer_matches_zoo_oracle(self):
        from tpudl.ml import DeepImageFeaturizer
        from tpudl.ml.named_image import load_named_params
        from tpudl.zoo.registry import getKerasApplicationModel
        from tpudl.image import ops as image_ops

        frame = _image_frame(n=4, h=40, w=40, seed=1)
        feat = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                   modelName="ResNet50", batchSize=4)
        out = feat.transform(frame)
        got = np.stack(list(out["features"]))
        model = getKerasApplicationModel("ResNet50")
        params = load_named_params("ResNet50", "random")
        batch = np.stack([imageIO.imageStructToArray(s)
                          for s in frame["image"]])
        x = image_ops.to_model_input(jax.numpy.asarray(batch), 224, 224,
                                     "BGR", "RGB")
        want = np.asarray(model.featurize(params, model.preprocess(x)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert got.shape == (4, 2048)

    def test_tf_image_transformer_warmup(self):
        """The generic graph transformer shares the no-fetch warm path
        (ImageBatchWarmup): warmup then transform matches cold."""
        import jax.numpy as jnp

        from tpudl.ml import TFImageTransformer

        frame = _image_frame(n=4, h=24, w=24, seed=9)
        g = lambda x: jnp.tanh(x.reshape(x.shape[0], -1) @  # noqa: E731
                               jnp.ones((24 * 24 * 3, 5)) * 1e-3)
        warm = TFImageTransformer(inputCol="image", outputCol="y",
                                  graph=g, batchSize=4)
        assert warm.warmup(24, 24) is warm
        got = np.stack(list(warm.transform(frame)["y"]))
        cold = TFImageTransformer(inputCol="image", outputCol="y",
                                  graph=g, batchSize=4)
        want = np.stack(list(cold.transform(frame)["y"]))
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_warmup_no_fetch_then_transform_matches(self):
        """``warmup`` compiles+executes WITHOUT any device→host read
        and a subsequent transform reuses the warmed program and matches
        the unwarmed transformer's output."""
        from tpudl.ml import DeepImageFeaturizer

        frame = _image_frame(n=4, h=36, w=36, seed=3)
        warm = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                   modelName="ResNet50", batchSize=4)
        ret = warm.warmup(36, 36)
        assert ret is warm  # chainable
        jfn_after_warm = warm._get_jfn()
        got = np.stack(list(warm.transform(frame)["features"]))
        # same cached program object — warmup did not fork a new jit
        assert warm._get_jfn() is jfn_after_warm
        cold = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                   modelName="ResNet50", batchSize=4)
        want = np.stack(list(cold.transform(frame)["features"]))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    def test_predictor_decode_topk(self):
        from tpudl.ml import DeepImagePredictor

        frame = _image_frame(n=3, h=40, w=40, seed=2)
        pred = DeepImagePredictor(inputCol="image", outputCol="preds",
                                  modelName="ResNet50",
                                  decodePredictions=True, topK=4)
        out = pred.transform(frame)
        decoded = out["preds"][0]
        assert len(decoded) == 4
        wnid, label, score = decoded[0]
        assert isinstance(score, float)
        scores = [s for (_w, _l, s) in decoded]
        assert scores == sorted(scores, reverse=True)

    def test_predictor_raw_scores_sum_to_one(self):
        from tpudl.ml import DeepImagePredictor

        frame = _image_frame(n=2, h=36, w=36, seed=3)
        pred = DeepImagePredictor(inputCol="image", outputCol="p",
                                  modelName="ResNet50")
        out = pred.transform(frame)
        s = np.stack(list(out["p"]))
        np.testing.assert_allclose(s.sum(axis=1), 1.0, rtol=1e-4)


# -- tensor transformers ---------------------------------------------------
class TestTensorTransformers:
    def test_tf_transformer_mapping(self):
        tf = pytest.importorskip("tensorflow")
        from tpudl.ingest import TFInputGraph
        from tpudl.ml import TFTransformer

        g = tf.Graph()
        with g.as_default():
            x = tf.compat.v1.placeholder(tf.float64, [None, 3], name="x")
            z = tf.identity(3.0 * x + 1.0, name="z")
        gin = TFInputGraph.fromGraphDef(g.as_graph_def(), ["x"], ["z"])
        X = np.random.default_rng(0).normal(size=(9, 3))
        frame = Frame({"feats": X})
        t = TFTransformer(tfInputGraph=gin,
                          inputMapping={"feats": "x"},
                          outputMapping={"z": "preds"})
        out = t.transform(frame)
        got = np.stack(list(out["preds"]))
        np.testing.assert_allclose(got, 3.0 * X + 1.0, rtol=1e-5)

    def test_keras_transformer_vs_predict(self, tmp_path):
        keras = pytest.importorskip("keras")
        from tpudl.ml import KerasTransformer

        keras.utils.set_random_seed(0)
        m = keras.Sequential([
            keras.layers.Input((5,)),
            keras.layers.Dense(7, activation="tanh"),
            keras.layers.Dense(2),
        ])
        path = str(tmp_path / "mlp.keras")
        m.save(path)
        X = np.random.default_rng(1).normal(size=(13, 5)).astype(np.float32)
        frame = Frame({"x": X})
        t = KerasTransformer(inputCol="x", outputCol="y", modelFile=path)
        out = t.transform(frame)
        got = np.stack(list(out["y"]))
        np.testing.assert_allclose(got, m.predict(X, verbose=0),
                                   rtol=1e-5, atol=1e-6)


# -- image-file transformer ------------------------------------------------
class TestKerasImageFile:
    def test_uri_loading_path(self, tmp_path):
        keras = pytest.importorskip("keras")
        PIL = pytest.importorskip("PIL")
        from PIL import Image
        from tpudl.ml import KerasImageFileTransformer

        rng = np.random.default_rng(0)
        uris = []
        for i in range(5):
            arr = rng.integers(0, 255, size=(20, 20, 3), dtype=np.uint8)
            p = str(tmp_path / f"im{i}.png")
            Image.fromarray(arr).save(p)
            uris.append(p)

        keras.utils.set_random_seed(0)
        m = keras.Sequential([
            keras.layers.Input((8, 8, 3)),
            keras.layers.Conv2D(2, 3, padding="same"),
            keras.layers.Flatten(),
        ])
        mpath = str(tmp_path / "cnn.keras")
        m.save(mpath)

        def loader(uri):
            img = Image.open(uri).convert("RGB").resize((8, 8),
                                                        Image.BILINEAR)
            return np.asarray(img, dtype=np.float32) / 255.0

        t = KerasImageFileTransformer(inputCol="uri", outputCol="feat",
                                      modelFile=mpath, imageLoader=loader,
                                      batchSize=2)
        out = t.transform(Frame({"uri": np.array(uris, dtype=object)}))
        got = np.stack(list(out["feat"]))
        X = np.stack([loader(u) for u in uris])
        np.testing.assert_allclose(got, m.predict(X, verbose=0),
                                   rtol=1e-4, atol=1e-5)


# -- pipeline composition --------------------------------------------------
class TestPipeline:
    def test_featurizer_in_pipeline(self):
        from tpudl.ml import DeepImageFeaturizer, Pipeline, Transformer

        class Scaler(Transformer):
            def _transform(self, frame):
                col = np.stack(list(frame["features"]))
                norm = col / (np.linalg.norm(col, axis=1, keepdims=True) + 1e-9)
                return frame.with_column("scaled", list(norm))

        frame = _image_frame(n=3, h=36, w=36)
        pipe = Pipeline([
            DeepImageFeaturizer(inputCol="image", outputCol="features",
                                modelName="ResNet50", batchSize=4),
            Scaler(),
        ])
        model = pipe.fit(frame)
        out = model.transform(frame)
        norms = np.linalg.norm(np.stack(list(out["scaled"])), axis=1)
        np.testing.assert_allclose(norms, 1.0, rtol=1e-5)


def test_cached_jit_retains_multiple_configs():
    """Round-1 weak item: the one-slot jit cache retraced every call when
    two configs alternated on one instance."""
    from tpudl.ml.pipeline import Transformer

    class T(Transformer):
        def _transform(self, frame):
            return frame

    t = T()
    builds = []

    def make(tag):
        def build():
            builds.append(tag)
            return lambda x: x
        return build

    for _ in range(3):  # alternate two keys; each must compile once
        t._cached_jit(("a",), make("a"))
        t._cached_jit(("b",), make("b"))
    assert builds == ["a", "b"]
    # eviction at capacity: oldest key rebuilt after overflow
    for i in range(T._JIT_CACHE_SIZE):
        t._cached_jit(("k", i), make(f"k{i}"))
    t._cached_jit(("a",), make("a2"))  # "a" was evicted → rebuilt
    assert builds[-1] == "a2"
