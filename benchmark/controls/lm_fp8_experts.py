"""Control of the ``lm_train`` cells: the cell with the experts' grouped
products in fp8, the nearest precision below the configuration's bfloat16.
It has to print ``correct: false``; its readings are the upper ones that
the limits of ``configs/<config>.json`` "check" stand under.

    python3 benchmark/controls/lm_fp8_experts.py --workload <cell> --seed <n> --seconds 30 --trace 0

``jax.lax.ragged_dot`` is replaced, for this process, by a product whose
operands, and whose cotangent in the backward pass, are rounded to e4m3
(4 exponent bits, 3 mantissa bits) under one scale a tensor (the largest
magnitude lands on 224, under the format's largest finite value), as an
fp8 training recipe does; accumulation stays float32 and the scales are
taken off again. Rows past the groups' end belong to no expert: the
grouped product leaves them undefined, so they are zeroed before a scale
is taken (the program masks them after the products). ``reduce_precision`` is a rounding XLA may not elide: a
cast to float8 and back was (PR 28's first probe printed the sound runs'
readings). Unscaled, the cotangents underflow e4m3 and the experts' group
reads 1.0: a dead backward, not fp8's rounding.
"""

import os
import runpy
import sys

import jax
import jax.numpy as jnp

_ragged_dot = jax.lax.ragged_dot


def e4m3(x, rows=None):
    """``x`` rounded to e4m3 under a per-tensor scale, in ``x``'s dtype;
    of its rows only the first ``rows`` count."""
    x32 = x.astype(jnp.float32)
    if rows is not None:
        x32 = jnp.where(jnp.arange(x.shape[0])[:, None] < rows, x32, 0.0)
    top = jnp.max(jnp.abs(x32))
    scale = jnp.where(top > 0, top / 224.0, 1.0)
    return (jax.lax.reduce_precision(x32 / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(x.dtype)


@jax.custom_vjp
def fp8_ragged_dot(lhs, rhs, group_sizes):
    return _ragged_dot(e4m3(lhs, group_sizes.sum()), e4m3(rhs), group_sizes)


def _fwd(lhs, rhs, group_sizes):
    lhs, rhs = e4m3(lhs, group_sizes.sum()), e4m3(rhs)
    return _ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(saved, g):
    lhs, rhs, group_sizes = saved
    _, pull = jax.vjp(lambda a, b: _ragged_dot(a, b, group_sizes), lhs, rhs)
    return (*pull(e4m3(g, group_sizes.sum())), None)


fp8_ragged_dot.defvjp(_fwd, _bwd)

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.lax.ragged_dot = lambda lhs, rhs, group_sizes, **kw: fp8_ragged_dot(
        lhs, rhs, group_sizes)
    sys.argv = [os.path.join(root, "benchmark", "run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
