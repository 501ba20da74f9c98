"""Required floating-point operations, counted from shapes.

The plain float32 reference of a configuration (``configs/<name>.py``) is
traced at batch 1 with ``jax.make_jaxpr`` and every ``conv_general_dilated``
and ``dot_general`` contributes 2 x its multiply-adds, read from operand and
result shapes. Nothing runs; it takes seconds on the CPU. The count is of
what the algorithm needs, so a change to the program (a fused stem, a
recomputation) does not move it.

Convention, stated because it is one: a training example costs ``passes`` = 3
forward passes (forward, gradient w.r.t. activations, gradient w.r.t.
weights); recomputed operations do not count.
"""

from __future__ import annotations

import math


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        out = eqn.outvars[0].aval.shape
        out_ch_dim = eqn.params["dimension_numbers"].rhs_spec[0]
        # per output element: kernel taps x input channels of its group
        return 2 * math.prod(out) * math.prod(rhs) // rhs[out_ch_dim]
    if name == "dot_general":
        lhs = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        return 2 * math.prod(out) * math.prod(lhs[d] for d in lhs_contract)
    return 0


def jaxpr_flops(jaxpr) -> int:
    """Sum over a jaxpr and every jaxpr nested in its equations."""
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += jaxpr_flops(inner)
    return total


def forward_flops(fn, *args) -> int:
    """FLOPs of one call of ``fn(*args)`` (args may be ShapeDtypeStructs)."""
    import jax

    return jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)


def per_example(forward, params, input_shape, passes: int = 1) -> float:
    """FLOPs one example needs: ``passes`` x the reference ``forward(params,
    x)`` traced at batch 1 on a float32 ``input_shape`` (H, W, C) input."""
    import jax
    import jax.numpy as jnp

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
    x = jax.ShapeDtypeStruct((1, *input_shape), jnp.float32)
    return float(passes * forward_flops(forward, shapes, x))
