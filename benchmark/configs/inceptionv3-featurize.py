"""Plain float32 reference of ``inceptionv3-featurize``.

keras.applications.InceptionV3(include_top=False, pooling="avg") as its
source describes it: conv (no bias) + batch norm (no gamma, eps 1e-3) +
ReLU units wired into the stem and the eleven mixed blocks, global average
pool to 2048 features. Parameters are the Keras-named dict the program
uses (``conv2d``, ``conv2d_1`` ... in order of creation); kernel sizes and
widths are read from their shapes, the wiring is written here.
"""

import itertools

import jax.numpy as jnp

from benchmark import plain as P


def forward(params, x):
    """``x``: float32 RGB pixels in [0, 255], (N, 299, 299, 3) -> (N, 2048)."""
    x = x / 127.5 - 1.0
    count = itertools.count()

    def unit(x, stride=1, padding="SAME"):
        i = next(count)
        sfx = f"_{i}" if i else ""
        x = P.conv(x, params["conv2d" + sfx], stride, padding)
        return P.relu(P.bn(x, params["batch_normalization" + sfx], 1e-3))

    def cat(*branches):
        return jnp.concatenate(branches, axis=-1)

    x = unit(unit(unit(x, 2, "VALID"), 1, "VALID"))
    x = P.max_pool(x, 3, 2)
    x = unit(unit(x, 1, "VALID"), 1, "VALID")
    x = P.max_pool(x, 3, 2)
    for _ in range(3):      # mixed 0-2, 35 x 35
        x = cat(unit(x), unit(unit(x)), unit(unit(unit(x))),
                unit(P.avg_pool_same(x, 3)))
    x = cat(unit(x, 2, "VALID"), unit(unit(unit(x)), 2, "VALID"),
            P.max_pool(x, 3, 2))                        # mixed 3
    for _ in range(4):      # mixed 4-7, 17 x 17, factorised 7 x 7
        x = cat(unit(x), unit(unit(unit(x))),
                unit(unit(unit(unit(unit(x))))),
                unit(P.avg_pool_same(x, 3)))
    x = cat(unit(unit(x), 2, "VALID"),
            unit(unit(unit(unit(x))), 2, "VALID"),
            P.max_pool(x, 3, 2))                        # mixed 8
    for _ in range(2):      # mixed 9-10, 8 x 8
        b1 = unit(x)
        b3 = unit(x)
        b3 = cat(unit(b3), unit(b3))
        bd = unit(unit(x))
        bd = cat(unit(bd), unit(bd))
        x = cat(b1, b3, bd, unit(P.avg_pool_same(x, 3)))
    return jnp.mean(x, axis=(1, 2))
