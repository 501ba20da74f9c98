"""Plain float32 reference of ``resnet50-sgd``.

keras.applications.ResNet50 (v1, 1000 classes) as its source describes it:
7x7/2 stem, four stages of (3, 4, 6, 3) bottleneck blocks with biased convs
and batch norm at eps 1.001e-5 on moving statistics, global average pool,
dense softmax. ``loss`` is the categorical cross-entropy the cell trains,
its gradient the thing the first update is held against.
"""

import jax
import jax.numpy as jnp

from benchmark import plain as P

_EPS = 1.001e-5


def forward(params, x):
    """``x``: float32 pixels in [0, 255], (N, H, W, 3) -> class probabilities."""
    x = (x - 127.5) / 127.5

    def cbn(x, name, stride=1, padding="VALID"):
        x = P.conv(x, params[name + "_conv"], stride, padding)
        return P.bn(x, params[name + "_bn"], _EPS)

    x = P.relu(cbn(P.pad(x, 3), "conv1", 2))
    x = P.max_pool(P.pad(x, 1), 3, 2)
    for stage, blocks in enumerate((3, 4, 6, 3), start=2):
        for b in range(1, blocks + 1):
            name = f"conv{stage}_block{b}"
            stride = 2 if b == 1 and stage > 2 else 1
            shortcut = cbn(x, name + "_0", stride) if b == 1 else x
            y = P.relu(cbn(x, name + "_1", stride))
            y = P.relu(cbn(y, name + "_2", 1, "SAME"))
            x = P.relu(shortcut + cbn(y, name + "_3"))
    x = jnp.mean(x, axis=(1, 2))
    return jax.nn.softmax(P.dense(x, params["predictions"]), axis=-1)


def loss(params, x, y):
    """Mean categorical cross-entropy of one-hot ``y`` on uint8 ``x``."""
    probs = forward(params, x.astype(jnp.float32))
    return -jnp.mean(jnp.sum(y * jnp.log(jnp.clip(probs, 1e-7, 1.0)), axis=-1))
