"""InceptionV3 as a pure JAX build function.

Architecture follows keras.applications.inception_v3 exactly (layer
creation order included, so canonical auto-names line up for weight
conversion). Reference consumer: sparkdl transformers/keras_applications.py
InceptionV3Model (~L60) — 299×299 input, 'tf' preprocessing, 2048-d
featurize vector (avg-pooled minus-top output).

All conv+bn pairs are unnamed in the Keras source → canonical names
conv2d/conv2d_N + batch_normalization/batch_normalization_N. BN uses
scale=False, epsilon defaults (1e-3).
"""

from __future__ import annotations

import jax.numpy as jnp

from tpudl.zoo import nn
from tpudl.zoo.core import Store

NAME = "InceptionV3"
INPUT_SIZE = (299, 299)
FEATURE_DIM = 2048
PREPROCESS_MODE = "tf"


def _conv2d_bn(s: Store, x, filters, num_row, num_col, *, padding="SAME",
               strides=(1, 1)):
    x = s.conv(x, filters, (num_row, num_col), strides=strides,
               padding=padding, use_bias=False)
    x = s.bn(x, scale=False)
    return nn.relu(x)


def _use_s2d_stem(s: Store, x) -> bool:
    """Inference-apply only: init must CREATE the canonical params, and
    train-mode BN computes per-channel batch stats that differ in the
    4×-tiled s2d layout. Odd H/W is the InceptionV3 VALID geometry the
    transform is derived for.

    Default OFF: measured 40.83 ms/step vs the canonical stem's
    34.26 ms on the real v5e chip (July 2026 profile, ROADMAP.md A8
    — the s2d reshuffles cost ~4.4 ms of HBM copies and XLA's conv
    already contracts over kh·kw·ci, so 3×3×32 = 288 taps was never
    lane-starved). Kept because the transform is exact and tested; a
    future backend where skinny convs DO underfill can flip it on."""
    import os

    return (not s.initializing and not s.train
            and os.environ.get("TPUDL_S2D_STEM", "0") == "1"
            and x.shape[1] % 2 == 1 and x.shape[2] % 2 == 1
            and x.shape[1] >= 7 and x.shape[2] >= 7)


def _stem_s2d(s: Store, x):
    """The three stem conv+BN+ReLU layers in space-to-depth form
    (tpudl.zoo.s2d — measured SLOWER than the canonical stem on v5e;
    see _use_s2d_stem above). Reads the SAME
    canonically-named params the plain stem uses, advancing the Namer
    identically, so checkpoints/conversion are unaffected."""
    from tpudl.zoo.s2d import inception_stem_s2d

    pairs = [(s.name("conv2d"), s.name("batch_normalization"))
             for _ in range(3)]
    (c1, b1), (c2, b2), (c3, b3) = pairs

    def bn_apply(t, p):
        return nn.batch_norm(t, p, train=False, epsilon=1e-3)

    return inception_stem_s2d(
        x, s.params[c1], s.params[b1], s.params[c2], s.params[b2],
        s.params[c3], s.params[b3], bn_apply=bn_apply, relu=nn.relu)


def build(s: Store, x, *, include_top=True, pooling=None, classes=1000):
    if _use_s2d_stem(s, x):
        x = _stem_s2d(s, x)
    else:
        x = _conv2d_bn(s, x, 32, 3, 3, strides=(2, 2), padding="VALID")
        x = _conv2d_bn(s, x, 32, 3, 3, padding="VALID")
        x = _conv2d_bn(s, x, 64, 3, 3)
    x = nn.max_pool(x, (3, 3), strides=(2, 2))

    x = _conv2d_bn(s, x, 80, 1, 1, padding="VALID")
    x = _conv2d_bn(s, x, 192, 3, 3, padding="VALID")
    x = nn.max_pool(x, (3, 3), strides=(2, 2))

    # mixed 0, 1, 2: 35 x 35
    for pool_filters in (32, 64, 64):
        branch1x1 = _conv2d_bn(s, x, 64, 1, 1)
        branch5x5 = _conv2d_bn(s, x, 48, 1, 1)
        branch5x5 = _conv2d_bn(s, branch5x5, 64, 5, 5)
        branch3x3dbl = _conv2d_bn(s, x, 64, 1, 1)
        branch3x3dbl = _conv2d_bn(s, branch3x3dbl, 96, 3, 3)
        branch3x3dbl = _conv2d_bn(s, branch3x3dbl, 96, 3, 3)
        branch_pool = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        branch_pool = _conv2d_bn(s, branch_pool, pool_filters, 1, 1)
        x = jnp.concatenate(
            [branch1x1, branch5x5, branch3x3dbl, branch_pool], axis=-1)

    # mixed 3: 17 x 17
    branch3x3 = _conv2d_bn(s, x, 384, 3, 3, strides=(2, 2), padding="VALID")
    branch3x3dbl = _conv2d_bn(s, x, 64, 1, 1)
    branch3x3dbl = _conv2d_bn(s, branch3x3dbl, 96, 3, 3)
    branch3x3dbl = _conv2d_bn(s, branch3x3dbl, 96, 3, 3, strides=(2, 2),
                              padding="VALID")
    branch_pool = nn.max_pool(x, (3, 3), strides=(2, 2))
    x = jnp.concatenate([branch3x3, branch3x3dbl, branch_pool], axis=-1)

    # mixed 4: 17 x 17, 128-wide 7x7 factorized
    x = _mixed_7x7(s, x, 128)
    # mixed 5, 6: 160-wide
    for _ in range(2):
        x = _mixed_7x7(s, x, 160)
    # mixed 7: 192-wide
    x = _mixed_7x7(s, x, 192)

    # mixed 8: 8 x 8
    branch3x3 = _conv2d_bn(s, x, 192, 1, 1)
    branch3x3 = _conv2d_bn(s, branch3x3, 320, 3, 3, strides=(2, 2),
                           padding="VALID")
    branch7x7x3 = _conv2d_bn(s, x, 192, 1, 1)
    branch7x7x3 = _conv2d_bn(s, branch7x7x3, 192, 1, 7)
    branch7x7x3 = _conv2d_bn(s, branch7x7x3, 192, 7, 1)
    branch7x7x3 = _conv2d_bn(s, branch7x7x3, 192, 3, 3, strides=(2, 2),
                             padding="VALID")
    branch_pool = nn.max_pool(x, (3, 3), strides=(2, 2))
    x = jnp.concatenate([branch3x3, branch7x7x3, branch_pool], axis=-1)

    # mixed 9, 10: 8 x 8 x 2048
    for _ in range(2):
        branch1x1 = _conv2d_bn(s, x, 320, 1, 1)
        branch3x3 = _conv2d_bn(s, x, 384, 1, 1)
        branch3x3_1 = _conv2d_bn(s, branch3x3, 384, 1, 3)
        branch3x3_2 = _conv2d_bn(s, branch3x3, 384, 3, 1)
        branch3x3 = jnp.concatenate([branch3x3_1, branch3x3_2], axis=-1)
        branch3x3dbl = _conv2d_bn(s, x, 448, 1, 1)
        branch3x3dbl = _conv2d_bn(s, branch3x3dbl, 384, 3, 3)
        branch3x3dbl_1 = _conv2d_bn(s, branch3x3dbl, 384, 1, 3)
        branch3x3dbl_2 = _conv2d_bn(s, branch3x3dbl, 384, 3, 1)
        branch3x3dbl = jnp.concatenate([branch3x3dbl_1, branch3x3dbl_2],
                                          axis=-1)
        branch_pool = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        branch_pool = _conv2d_bn(s, branch_pool, 192, 1, 1)
        x = jnp.concatenate(
            [branch1x1, branch3x3, branch3x3dbl, branch_pool], axis=-1)

    if include_top:
        x = nn.global_avg_pool(x)
        x = s.dense(x, classes, name="predictions")
        return nn.softmax(x)
    if pooling == "avg":
        return nn.global_avg_pool(x)
    if pooling == "max":
        return nn.global_max_pool(x)
    return x


def _mixed_7x7(s: Store, x, width):
    branch1x1 = _conv2d_bn(s, x, 192, 1, 1)
    branch7x7 = _conv2d_bn(s, x, width, 1, 1)
    branch7x7 = _conv2d_bn(s, branch7x7, width, 1, 7)
    branch7x7 = _conv2d_bn(s, branch7x7, 192, 7, 1)
    branch7x7dbl = _conv2d_bn(s, x, width, 1, 1)
    branch7x7dbl = _conv2d_bn(s, branch7x7dbl, width, 7, 1)
    branch7x7dbl = _conv2d_bn(s, branch7x7dbl, width, 1, 7)
    branch7x7dbl = _conv2d_bn(s, branch7x7dbl, width, 7, 1)
    branch7x7dbl = _conv2d_bn(s, branch7x7dbl, 192, 1, 7)
    branch_pool = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
    branch_pool = _conv2d_bn(s, branch_pool, 192, 1, 1)
    return jnp.concatenate(
        [branch1x1, branch7x7, branch7x7dbl, branch_pool], axis=-1)
