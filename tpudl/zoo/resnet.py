"""ResNet50 (v1) as a pure JAX build function.

Architecture follows keras.applications.resnet.ResNet50 exactly, with the
stable semantic Keras layer names (conv1_conv, conv2_block1_1_conv, ...)
as param keys. Reference consumer: sparkdl transformers/
keras_applications.py ResNet50Model (~L120) — 224×224 input, 'caffe'
preprocessing, 2048-d featurize vector. Also the HorovodRunner training
config (BASELINE.json configs[3]) — train mode exercises BN batch stats.

Conv/BN details from the Keras source: conv1 is 7×7 s2 VALID after a
(3,3) zero-pad, all convs use bias, BN epsilon 1.001e-5; stacks
conv2(64×3, s1), conv3(128×4, s2), conv4(256×6, s2), conv5(512×3, s2);
block shortcut is a 1×1 VALID conv at stride s.

Every convolution here is followed directly by batch norm, so all five
sites go through ``Store.conv_bn``. On moving statistics (``apply`` with
``train=False``: inference, and every training path that calls
``predict``) the norm's per-channel scale and shift are folded into the
convolution's kernel and bias inside the traced program; the parameter
tree keeps its six leaves per pair and autodiff through the fold gives
all six their gradients, without the backward pass storing and re-reading
the raw convolution output. The fold does not apply on batch statistics
(``train=True``: the scale depends on the data) and not where the norm
comes before its convolution or something sits between them (DenseNet,
the other zoo models keep ``conv`` + ``bn``).
"""

from __future__ import annotations

import jax.numpy as jnp

from tpudl.zoo import nn
from tpudl.zoo.core import Store

NAME = "ResNet50"
INPUT_SIZE = (224, 224)
FEATURE_DIM = 2048
PREPROCESS_MODE = "caffe"

_EPS = 1.001e-5


def _block(s: Store, x, filters, *, stride=1, conv_shortcut=True, name=""):
    def conv_bn(x, filters, kernel_size, i, **kw):
        return s.conv_bn(x, filters, kernel_size, epsilon=_EPS,
                         conv_name=f"{name}_{i}_conv", bn_name=f"{name}_{i}_bn",
                         **kw)

    if conv_shortcut:
        shortcut = conv_bn(x, 4 * filters, 1, 0, strides=(stride, stride),
                           padding="VALID")
    else:
        shortcut = x
    x = conv_bn(x, filters, 1, 1, strides=(stride, stride), padding="VALID")
    x = nn.relu(x)
    x = conv_bn(x, filters, 3, 2, padding="SAME")
    x = nn.relu(x)
    x = conv_bn(x, 4 * filters, 1, 3, padding="VALID")
    return nn.relu(shortcut + x)


def _stack(s: Store, x, filters, blocks, *, stride1=2, name=""):
    x = _block(s, x, filters, stride=stride1, name=f"{name}_block1")
    for i in range(2, blocks + 1):
        x = _block(s, x, filters, conv_shortcut=False, name=f"{name}_block{i}")
    return x


def _build_resnet(s: Store, x, stacks, *, include_top=True, pooling=None,
                  classes=1000):
    """Shared v1 bottleneck skeleton; ``stacks`` = blocks per
    conv2..conv5 stage (keras.applications.resnet: ResNet50 (3,4,6,3),
    ResNet101 (3,4,23,3), ResNet152 (3,8,36,3))."""
    x = nn.zero_pad(x, ((3, 3), (3, 3)))
    x = s.conv_bn(x, 64, 7, strides=(2, 2), padding="VALID", epsilon=_EPS,
                  conv_name="conv1_conv", bn_name="conv1_bn")
    x = nn.relu(x)
    x = nn.zero_pad(x, ((1, 1), (1, 1)))
    x = nn.max_pool(x, (3, 3), strides=(2, 2))

    for i, (filters, blocks) in enumerate(zip((64, 128, 256, 512), stacks)):
        x = _stack(s, x, filters, blocks, stride1=1 if i == 0 else 2,
                   name=f"conv{i + 2}")

    if include_top:
        x = nn.global_avg_pool(x)
        x = s.dense(x, classes, name="predictions")
        return nn.softmax(x)
    if pooling == "avg":
        return nn.global_avg_pool(x)
    if pooling == "max":
        return nn.global_max_pool(x)
    return x


def build(s: Store, x, *, include_top=True, pooling=None, classes=1000):
    return _build_resnet(s, x, (3, 4, 6, 3), include_top=include_top,
                         pooling=pooling, classes=classes)


def build_resnet101(s: Store, x, *, include_top=True, pooling=None,
                    classes=1000):
    """keras.applications.resnet.ResNet101: stacks (3, 4, 23, 3)."""
    return _build_resnet(s, x, (3, 4, 23, 3), include_top=include_top,
                         pooling=pooling, classes=classes)


def build_resnet152(s: Store, x, *, include_top=True, pooling=None,
                    classes=1000):
    """keras.applications.resnet.ResNet152: stacks (3, 8, 36, 3)."""
    return _build_resnet(s, x, (3, 8, 36, 3), include_top=include_top,
                         pooling=pooling, classes=classes)
