"""Zoo numerical-parity tests vs Keras-CPU (the reference-oracle pattern,
SURVEY.md §4: run the same model both ways on the same inputs, allclose).

Keras builds use weights=None (no network in CI); random weights exercise
the exact same conversion + arithmetic as pretrained ones. Small input
sizes keep the oracle cheap; the conversion/naming logic is size-blind.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpudl import obs
from tpudl.zoo import (
    SUPPORTED_MODELS,
    getKerasApplicationModel,
    params_from_keras,
    preprocess_input,
    decode_predictions,
)
from tpudl.zoo.core import Store

keras = pytest.importorskip("keras")

# smallest legal input per architecture (keeps the CPU oracle fast)
_SMALL = {"InceptionV3": 75, "Xception": 71, "ResNet50": 32, "VGG16": 32,
          "VGG19": 32, "MobileNetV2": 32, "DenseNet121": 32,
          "ResNet101": 32, "ResNet152": 32, "EfficientNetB0": 32}


@pytest.fixture(scope="module")
def x_small(rng):
    return (rng.normal(size=(2, 1, 1, 3)).astype(np.float32) * 0)  # placeholder


def _rand(rng, hw):
    return (rng.normal(size=(2, hw, hw, 3)) * 50).astype(np.float32)


@pytest.mark.parametrize("name", sorted(SUPPORTED_MODELS))
def test_features_match_keras(name, rng):
    hw = _SMALL[name]
    m = getKerasApplicationModel(name)
    km = m.keras_builder()(weights=None, include_top=False,
                           input_shape=(hw, hw, 3))
    params = params_from_keras(km)
    x = _rand(rng, hw)
    ref = km.predict(x, verbose=0)
    ours = np.asarray(m.apply(params, jnp.asarray(x), include_top=False))
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)


def test_mobilenetv2_featurize_is_pooled_1280(rng):
    """MobileNetV2 featurize == keras no-top pooling='avg' (the
    1280-d out_relu global average — the DeepImageFeaturizer vector)."""
    m = getKerasApplicationModel("MobileNetV2")
    km = m.keras_builder()(weights=None, include_top=False,
                           pooling="avg", input_shape=(64, 64, 3))
    params = params_from_keras(km)
    x = _rand(rng, 64)
    ref = km.predict(x, verbose=0)
    ours = np.asarray(m.featurize(params, jnp.asarray(x)))
    assert ours.shape == (2, 1280)
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)


def test_resnet50_top_matches_keras(rng):
    m = getKerasApplicationModel("ResNet50")
    km = m.keras_builder()(weights=None, include_top=True,
                           input_shape=(64, 64, 3), classes=1000)
    params = params_from_keras(km)
    x = _rand(rng, 64)
    ref = km.predict(x, verbose=0)
    ours = np.asarray(m.predict(params, jnp.asarray(x)))
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ours.sum(axis=1), 1.0, rtol=1e-5)


def test_vgg16_featurize_is_fc2(rng):
    m = getKerasApplicationModel("VGG16")
    km = m.keras_builder()(weights=None, include_top=True,
                           input_shape=(32, 32, 3), classes=10)
    sub = keras.Model(km.input, km.get_layer("fc2").output)
    # our classes param is fixed at 1000; build featurize-only params from
    # the keras model (predictions layer shape mismatch doesn't matter —
    # featurize never touches it)
    params = params_from_keras(km)
    x = _rand(rng, 32)
    ref = sub.predict(x, verbose=0)
    ours = np.asarray(m.featurize(params, jnp.asarray(x)))
    assert ours.shape == (2, 4096)
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)


def test_preprocess_parity_tf_and_caffe(rng):
    from keras.src.applications.imagenet_utils import preprocess_input as kpre

    x = (rng.random(size=(2, 8, 8, 3)) * 255).astype(np.float32)
    for mode in ("tf", "caffe", "torch"):
        ref = kpre(x.copy(), data_format="channels_last", mode=mode)
        ours = np.asarray(preprocess_input(jnp.asarray(x), mode))
        np.testing.assert_allclose(ours, ref, rtol=1e-6, atol=1e-5)


def test_decode_predictions_offline_fallback(rng):
    preds = rng.random(size=(2, 1000)).astype(np.float32)
    out = decode_predictions(preds, top=3)
    assert len(out) == 2 and len(out[0]) == 3
    top1 = out[0][0]
    assert top1[2] == pytest.approx(float(preds[0].max()))
    with pytest.raises(ValueError):
        decode_predictions(preds[:, :10])


def test_init_shapes_match_keras_conversion(rng):
    import jax

    m = getKerasApplicationModel("ResNet50")
    params = m.init(jax.random.PRNGKey(0), image_size=(32, 32))
    km = m.keras_builder()(weights=None, include_top=True,
                           input_shape=(32, 32, 3), classes=1000)
    kp = params_from_keras(km)
    assert set(params) == set(kp)
    for lname in params:
        assert set(params[lname]) == set(kp[lname]), lname
        for k in params[lname]:
            assert params[lname][k].shape == kp[lname][k].shape, (lname, k)


def test_train_mode_returns_bn_updates(rng):
    import jax

    m = getKerasApplicationModel("ResNet50")
    params = m.init(jax.random.PRNGKey(0), image_size=(32, 32))
    x = jnp.asarray(_rand(rng, 32))
    y, updates = m.apply(params, x, include_top=True, train=True)
    assert y.shape == (2, 1000)
    assert updates, "train mode must collect BN moving-stat updates"
    lname = next(iter(updates))
    assert set(updates[lname]) == {"moving_mean", "moving_var"}
    # moving stats must actually move
    assert not np.allclose(np.asarray(updates[lname]["moving_mean"]),
                           np.asarray(params[lname]["moving_mean"]))


def test_normalization_rescaling_fold(rng):
    """convert.params_from_keras folds a per-channel Rescaling that
    directly follows a weighted Normalization into its variance (the
    keras EfficientNet imagenet-graph workaround), and ONLY then: an
    intervening weighted layer or nonzero offset must leave params
    untouched."""
    import keras

    def build(with_rescale, intervene=False, intervene_weightless=False):
        x = inp = keras.Input((8, 8, 3))
        # no explicit mean/variance: that path stores them as weights,
        # exactly how keras EfficientNet's normalization layer is built
        norm = keras.layers.Normalization(axis=-1)
        x = norm(x)
        if intervene:
            x = keras.layers.Conv2D(3, 1, use_bias=False)(x)
        if intervene_weightless:
            x = keras.layers.Activation("relu")(x)
        if with_rescale:
            x = keras.layers.Rescaling([0.5, 0.5, 0.5])(x)
        x = keras.layers.Conv2D(2, 1)(x)
        model = keras.Model(inp, x)
        norm.set_weights([np.array([1.0, 2.0, 3.0], np.float32),
                          np.array([4.0, 4.0, 4.0], np.float32),
                          np.array(1, np.int64)])
        return model

    from tpudl.zoo.convert import params_from_keras

    plain = params_from_keras(build(False))
    np.testing.assert_allclose(plain["normalization"]["variance"],
                               [4.0, 4.0, 4.0])
    folded = params_from_keras(build(True))
    # (x-m)/sqrt(v) * 0.5 == (x-m)/sqrt(v/0.25) → variance 16
    np.testing.assert_allclose(folded["normalization"]["variance"],
                               [16.0, 16.0, 16.0])
    untouched = params_from_keras(build(True, intervene=True))
    np.testing.assert_allclose(untouched["normalization"]["variance"],
                               [4.0, 4.0, 4.0])
    # a weightLESS transforming layer (Activation) between them must
    # ALSO close the fold window: relu then *s does not commute into
    # the variance (ADVICE.md — the non-EfficientNet-graph mis-fold)
    weightless = params_from_keras(
        build(True, intervene_weightless=True))
    np.testing.assert_allclose(weightless["normalization"]["variance"],
                               [4.0, 4.0, 4.0])


# -- Store.conv_bn: moving-statistics batch norm folded into its conv ------

_RESNET_PAIRS = {"ResNet50": 53, "ResNet101": 104, "ResNet152": 155}


class _UnfoldedStore(Store):
    """The pair as it was written before the fold: ``nn.conv2d`` then
    ``nn.batch_norm`` on the same parameters (the reference the folded
    path is held to)."""

    def conv_bn(self, x, filters, kernel_size, *, strides=(1, 1),
                padding="SAME", epsilon=1e-3, conv_name=None, bn_name=None):
        x = self.conv(x, filters, kernel_size, strides=strides,
                      padding=padding, name=conv_name)
        return self.bn(x, epsilon=epsilon, name=bn_name)


def _busy_params(m, hw, seed=4):
    """``init`` leaves batch norm at the identity and biases at zero, where
    a wrong fold would still pass: draw every such leaf away from it. The
    head's kernel is scaled down so that the softmax does not saturate and
    a cross-entropy loss has a gradient to compare."""
    gen = np.random.default_rng(seed)
    params = m.init(seed, image_size=(hw, hw))
    draw = {"gamma": lambda s: gen.uniform(0.5, 1.5, s),
            "moving_var": lambda s: gen.uniform(0.5, 1.5, s),
            "beta": lambda s: gen.normal(0, 0.1, s),
            "moving_mean": lambda s: gen.normal(0, 0.1, s),
            "bias": lambda s: gen.normal(0, 0.1, s)}
    params = {lname: {k: (draw[k](v.shape).astype(np.float32) if k in draw
                          else v) for k, v in p.items()}
              for lname, p in params.items()}
    params["predictions"]["kernel"] = params["predictions"]["kernel"] * 0.01
    return params, jnp.asarray(_rand(gen, hw))


def _conv_bn_counts():
    snap = obs.snapshot("zoo.conv_bn.")
    return tuple(snap.get(f"zoo.conv_bn.{k}", {}).get("value", 0)
                 for k in ("folded", "unfolded"))


@pytest.mark.parametrize("name", sorted(_RESNET_PAIRS))
def test_resnet_folded_forward_matches_unfolded_pair(name):
    """Class probabilities to rtol 1e-5 (measured: 3e-7 to 1.8e-6), and the
    pooled features, everything the fold touches, to 1e-5 of their scale."""
    m = getKerasApplicationModel(name)
    params, x = _busy_params(m, _SMALL[name])
    folded0, unfolded0 = _conv_bn_counts()
    probs = np.asarray(m.predict(params, x))
    assert _conv_bn_counts() == (folded0 + _RESNET_PAIRS[name], unfolded0)
    unfolded = _UnfoldedStore(params=params)
    ref = np.asarray(m.build_fn(unfolded, x, include_top=True,
                                classes=m.classes))
    np.testing.assert_allclose(probs, ref, rtol=1e-5, atol=0)
    ref = np.asarray(m.build_fn(unfolded, x, include_top=False,
                                pooling="avg"))
    np.testing.assert_allclose(np.asarray(m.featurize(params, x)), ref,
                               rtol=1e-5, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize("name", sorted(_RESNET_PAIRS))
def test_resnet_folded_gradients_match_unfolded_pair(name):
    """Plain autodiff through the fold gives every leaf its gradient:
    kernel, bias, gamma, beta, moving_mean, moving_var and the head, each
    to rel-l2 1e-5 (measured: worst leaf 1.9e-6). The seed is fixed: where
    a ReLU's input rounds across zero (seed 3 has one in
    ``conv2_block2_1``) a small layer's gradient moves by per cents, which
    is the activation's doing and not the fold's (it is gone in
    float64)."""
    import jax

    m = getKerasApplicationModel(name)
    params, x = _busy_params(m, _SMALL[name])
    y = np.eye(m.classes, dtype=np.float32)[[3, 7]]

    def loss(store):
        def f(p):
            probs = m.build_fn(store(params=p), x, include_top=True,
                               classes=m.classes)
            return -jnp.mean(jnp.sum(y * jnp.log(jnp.clip(probs, 1e-7, 1.0)),
                                     axis=-1))
        return f

    got = jax.jit(jax.grad(loss(Store)))(params)
    ref = jax.jit(jax.grad(loss(_UnfoldedStore)))(params)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    worst = {}
    for lname, leaves in ref.items():
        for k, r in leaves.items():
            r, g = np.asarray(r), np.asarray(got[lname][k])
            assert np.linalg.norm(r) > 0, (lname, k)
            worst[lname, k] = np.linalg.norm(g - r) / np.linalg.norm(r)
    bad = {k: v for k, v in worst.items() if not v <= 1e-5}
    assert not bad, bad


@pytest.mark.parametrize("name", sorted(_RESNET_PAIRS))
def test_resnet_init_is_bitwise_the_unfolded_builders(name):
    import jax

    m = getKerasApplicationModel(name)
    hw = _SMALL[name]
    s = _UnfoldedStore(rng=np.random.default_rng(11))
    jax.eval_shape(lambda x: m.build_fn(s, x, include_top=True,
                                        classes=m.classes),
                   jax.ShapeDtypeStruct((1, hw, hw, 3), jnp.float32))
    params = m.init(11, image_size=(hw, hw))
    assert list(params) == list(s.params)
    for lname, p in s.params.items():
        assert list(params[lname]) == list(p), lname
        for k, v in p.items():
            assert params[lname][k].dtype == v.dtype, (lname, k)
            assert np.array_equal(params[lname][k], v), (lname, k)


@pytest.mark.parametrize("name", sorted(_RESNET_PAIRS))
def test_resnet_batch_statistics_stay_unfolded(name, rng):
    m = getKerasApplicationModel(name)
    hw = _SMALL[name]
    params = m.init(0, image_size=(hw, hw))
    folded0, unfolded0 = _conv_bn_counts()
    _, updates = m.apply(params, jnp.asarray(_rand(rng, hw)), train=True)
    assert len(updates) == _RESNET_PAIRS[name]
    assert _conv_bn_counts() == (folded0, unfolded0 + _RESNET_PAIRS[name])


def test_folded_block_backward_keeps_no_raw_conv_output():
    """The mechanism itself, as a count: the backward of one shortcut block
    in bf16 keeps four activation-sized residuals fewer than the unfolded
    pair, one raw convolution output per pair. An edit that brings them
    back fails here and not on the chip."""
    import jax

    from tpudl.zoo import resnet

    n, side, filters = 5, 8, 8
    x = jnp.ones((n, side, side, 2 * filters), jnp.bfloat16)
    s = Store(rng=np.random.default_rng(0))
    resnet._block(s, x, filters, name="b")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16), s.params)

    def activation_residuals(store):
        def f(p, x):
            return jnp.sum(resnet._block(store(params=p), x, filters,
                                         name="b").astype(jnp.float32))
        _, vjp = jax.eval_shape(lambda p, x: jax.vjp(f, p, x), params, x)
        return [r for r in jax.tree.leaves(vjp)
                if r.ndim == 4 and r.shape[:3] == (n, side, side)]

    folded = activation_residuals(Store)
    unfolded = activation_residuals(_UnfoldedStore)
    assert len(unfolded) - len(folded) == 4, (len(unfolded), len(folded))
