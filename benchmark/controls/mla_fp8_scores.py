"""Control of the ``lm_train_mla`` cells: the cell with latent attention's
score product on fp8 operands, the nearest precision below the
configuration's bfloat16. It has to print ``correct: false``; its readings
are the upper ones that the limits of ``configs/<config>.json`` "check"
stand under.

    python3 benchmark/controls/mla_fp8_scores.py --workload <cell> --seed <n> --seconds 30 --trace 0

``tpudl.zoo.lm_blocks.flash_attention`` is replaced, for this process, by
the same kernels on queries and keys rounded to e4m3 (4 exponent bits, 3
mantissa bits) under one scale a tensor (the largest magnitude lands on
224, under the format's largest finite value), as an fp8 attention kernel
takes them; the values, the softmax and every accumulation stay as they
are, and the scales are taken off again. The rounding is a straight-through
one: the backward kernels see the rounded operands and hand their
cotangents to the unrounded ones. ``reduce_precision`` is a rounding XLA
may not elide (a cast to float8 and back was: ``lm_fp8_experts.py``).
"""

import os
import runpy
import sys

import jax
import jax.numpy as jnp


def e4m3(x):
    """``x`` rounded to e4m3 under a per-tensor scale, in ``x``'s dtype,
    with the gradient of the identity."""
    x32 = x.astype(jnp.float32)
    top = jnp.max(jnp.abs(x32))
    scale = jnp.where(top > 0, top / 224.0, 1.0)
    rounded = (jax.lax.reduce_precision(
        x32 / scale, exponent_bits=4, mantissa_bits=3) * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from tpudl.zoo import lm_blocks

    flash = lm_blocks.flash_attention
    lm_blocks.flash_attention = lambda q, k, v, **kw: flash(
        e4m3(q), e4m3(k), v, **kw)
    sys.argv = [os.path.join(root, "benchmark", "run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
