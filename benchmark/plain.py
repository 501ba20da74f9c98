"""Plain float32 layer arithmetic for the configurations' references.

Straight ``jax.lax`` in float32 with no kernels, cache, batching or mixed
precision. The references in ``configs/<name>.py`` are written against
these and the Keras layer names of the published models, independently of
``tpudl.zoo``. Callers run them under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv(x, p, stride=1, padding="SAME"):
    """NHWC x HWIO (the Keras Conv2D layout), optional bias."""
    y = lax.conv_general_dilated(
        x, jnp.asarray(p["kernel"], jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "bias" in p:
        y = y + jnp.asarray(p["bias"], jnp.float32)
    return y


def bn(x, p, eps):
    """Keras BatchNormalization at inference: moving statistics."""
    y = (x - p["moving_mean"]) * lax.rsqrt(
        jnp.asarray(p["moving_var"], jnp.float32) + eps)
    if "gamma" in p:
        y = y * p["gamma"]
    return y + p["beta"]


def dense(x, p):
    return x @ jnp.asarray(p["kernel"], jnp.float32) + p["bias"]


def max_pool(x, size, stride):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, size, size, 1),
                             (1, stride, stride, 1), "VALID")


def avg_pool_same(x, size):
    """Stride 1, SAME, padded cells excluded from the mean (TF semantics)."""
    dims, strides = (1, size, size, 1), (1, 1, 1, 1)
    sums = lax.reduce_window(x, 0.0, lax.add, dims, strides, "SAME")
    ones = jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype)
    return sums / lax.reduce_window(ones, 0.0, lax.add, dims, strides, "SAME")


def pad(x, n):
    return jnp.pad(x, ((0, 0), (n, n), (n, n), (0, 0)))


relu = jax.nn.relu
