"""Functional NN ops with exact TF/Keras numerical semantics.

The zoo models (ref: sparkdl transformers/keras_applications.py — the
InceptionV3/ResNet50/Xception/VGG registry) are pure JAX functions over
param pytrees; these are their building blocks. Semantics parity notes:

- conv SAME padding: jax ``lax`` SAME == TF SAME (asymmetric on stride>1).
- average pooling with SAME padding **excludes** padded cells from the
  divisor (TF AvgPool behavior, verified empirically) — implemented as a
  sum window divided by a ones-count window.
- batch norm follows Keras: inference uses moving stats; train mode uses
  per-replica batch stats (Horovod-style non-synced BN) and returns updated
  moving averages.

Everything here is shape-static and jit/pjit-friendly: no data-dependent
Python control flow, so XLA fuses these into the surrounding model program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "conv2d",
    "depthwise_conv2d",
    "separable_conv2d",
    "dense",
    "batch_norm",
    "moving_scale_shift",
    "conv2d_bn_folded",
    "max_pool",
    "avg_pool",
    "global_avg_pool",
    "global_max_pool",
    "zero_pad",
    "relu",
    "relu6",
    "softmax",
]


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def conv2d(x, kernel, bias=None, *, strides=(1, 1), padding="SAME"):
    """NHWC conv with HWIO kernel (the Keras Conv2D weight layout)."""
    dn = lax.conv_dimension_numbers(x.shape, kernel.shape, ("NHWC", "HWIO", "NHWC"))
    y = lax.conv_general_dilated(
        x, kernel.astype(x.dtype), _pair(strides), padding, dimension_numbers=dn
    )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def depthwise_conv2d(x, kernel, bias=None, *, strides=(1, 1), padding="SAME"):
    """Depthwise conv. ``kernel`` is Keras layout (kh, kw, cin, mult);
    lax wants grouped HWIO (kh, kw, 1, cin*mult) with cin groups — the
    row-major reshape maps keras's [c, m] to group-major channel c*mult+m,
    matching TF DepthwiseConv2dNative output ordering."""
    kh, kw, cin, mult = kernel.shape
    k = kernel.reshape(kh, kw, 1, cin * mult)
    dn = lax.conv_dimension_numbers(x.shape, k.shape, ("NHWC", "HWIO", "NHWC"))
    y = lax.conv_general_dilated(
        x, k.astype(x.dtype), _pair(strides), padding,
        feature_group_count=cin, dimension_numbers=dn,
    )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def separable_conv2d(x, depth_kernel, point_kernel, bias=None, *,
                     strides=(1, 1), padding="SAME"):
    """Keras SeparableConv2D == depthwise then 1x1 pointwise (+bias)."""
    y = depthwise_conv2d(x, depth_kernel, strides=strides, padding=padding)
    return conv2d(y, point_kernel, bias, strides=(1, 1), padding="VALID")


def dense(x, kernel, bias=None):
    y = x @ kernel.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def moving_scale_shift(p: dict, epsilon: float):
    """Batch norm on its moving statistics as one per-channel float32
    ``(scale, shift)``: ``y = x * scale + shift``."""
    inv = lax.rsqrt(p["moving_var"].astype(jnp.float32) + epsilon)
    if p.get("gamma") is not None:
        inv = inv * p["gamma"].astype(jnp.float32)
    shift = -p["moving_mean"].astype(jnp.float32) * inv
    if p.get("beta") is not None:
        shift = shift + p["beta"].astype(jnp.float32)
    return inv, shift


def conv2d_bn_folded(x, kernel, bias, p: dict, *, epsilon: float,
                     strides=(1, 1), padding="SAME"):
    """``batch_norm(conv2d(x, kernel, bias), p)`` on moving statistics as
    ONE convolution: the scale is per output channel, so it folds into
    the kernel and the bias exactly. Autodiff then takes the scale's
    gradient from a kernel-sized product and never needs the raw
    convolution output: the backward pass stores and reads one
    activation-sized tensor fewer per pair."""
    inv, shift = moving_scale_shift(p, epsilon)
    kernel = (kernel.astype(jnp.float32) * inv).astype(x.dtype)
    bias = (bias.astype(jnp.float32) * inv + shift).astype(x.dtype)
    return conv2d(x, kernel, bias, strides=strides, padding=padding)


def batch_norm(x, p: dict, *, train: bool = False, epsilon: float = 1e-3,
               momentum: float = 0.99):
    """Keras BatchNormalization over the channel (last) axis.

    ``p`` holds ``gamma`` (may be None for scale=False, e.g. InceptionV3),
    ``beta``, ``moving_mean``, ``moving_var``. Inference folds stats into
    one scale+shift (XLA fuses it into the preceding conv). Train mode
    returns ``(y, new_stats)`` with Keras's moving-average update.
    """
    gamma = p.get("gamma")
    beta = p.get("beta")
    if not train:
        inv, shift = moving_scale_shift(p, epsilon)
        return x * inv.astype(x.dtype) + shift.astype(x.dtype)
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    inv = lax.rsqrt(var + epsilon)
    if gamma is not None:
        inv = inv * gamma.astype(jnp.float32)
    y = (xf - mean) * inv
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    new_stats = {
        "moving_mean": p["moving_mean"] * momentum + mean * (1 - momentum),
        "moving_var": p["moving_var"] * momentum + var * (1 - momentum),
    }
    return y.astype(x.dtype), new_stats


def max_pool(x, window, *, strides, padding="VALID"):
    w, s = _pair(window), _pair(strides)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return lax.reduce_window(
        x, init, lax.max, (1, *w, 1), (1, *s, 1), padding
    )


def avg_pool(x, window, *, strides, padding="VALID"):
    """TF-semantics average pool: SAME padding excludes padded cells."""
    w, s = _pair(window), _pair(strides)
    sums = lax.reduce_window(
        x, jnp.array(0, x.dtype), lax.add, (1, *w, 1), (1, *s, 1), padding
    )
    if padding == "VALID":
        return sums / (w[0] * w[1])
    ones = jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype)
    counts = lax.reduce_window(
        ones, jnp.array(0, x.dtype), lax.add, (1, *w, 1), (1, *s, 1), padding
    )
    return sums / counts


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


def global_max_pool(x):
    return jnp.max(x, axis=(1, 2))


def zero_pad(x, pad):
    """Keras ZeroPadding2D: pad = ((top, bottom), (left, right))."""
    (t, b), (l, r) = pad
    return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)))


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    """Keras ReLU(6.0) — the MobileNet activation."""
    return jnp.minimum(jax.nn.relu(x), 6.0)


def softmax(x):
    return jax.nn.softmax(x, axis=-1)
