"""Mesh-parallel training step — the NCCL-allreduce equivalent.

The reference's distributed-training capability is HorovodRunner's MPI +
NCCL ring allreduce (SURVEY.md §3.6/§5.8, Databricks distribution). The
TPU-native translation: ONE jitted SPMD program over the mesh — batch
sharded on the ``data`` axis, params replicated — in which XLA lowers
the gradient reduction onto ICI collectives automatically. There is no
hand-written ring: the sharding annotations ARE the communication spec
(scaling-book recipe: pick a mesh, annotate, let XLA insert collectives).
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudl import mesh as M
from tpudl.obs.trace import named_scope

__all__ = ["make_train_step", "make_eval_step", "with_compute_dtype"]


def with_compute_dtype(loss_fn, dtype, keep=()):
    """Mixed precision the TPU way: fp32 MASTER params, ``dtype``
    (bf16) compute. Wraps ``loss_fn`` so float32 param leaves are cast
    to ``dtype`` for the forward/backward pass while the optimizer
    updates the fp32 originals.

    Why this exists: training directly in bf16 silently STALLS once
    updates shrink below the parameter's 8-bit-mantissa ULP —
    ``bf16(1.0 + 1e-6) == 1.0``, so SGD steps round to nothing (the
    ResNet50 convergence bench plateaued exactly this way). The cast is
    free on the MXU path (XLA fuses it into the consuming matmul), and
    grads come back fp32 because the masters are fp32.

    ``keep`` names leaves that stay float32, by the END of the last key
    on their path (``(".A_log", ".dt_bias")``): scalars of a recurrence,
    whose rounding to 8 bits compounds over a sequence.
    """
    import jax.numpy as jnp

    target, keep = jnp.dtype(dtype), tuple(keep)

    def cast(path, leaf):
        name = str(getattr(path[-1], "key", path[-1])) if path else ""
        return (leaf.astype(target)
                if hasattr(leaf, "dtype") and leaf.dtype == jnp.float32
                and not name.endswith(keep) else leaf)

    def wrapped(params, *batch):
        with named_scope("train.cast"):
            compute = jax.tree_util.tree_map_with_path(cast, params)
        return loss_fn(compute, *batch)

    return wrapped


def make_train_step(loss_fn, optimizer, mesh=None, donate=True,
                    param_shardings=None):
    """Build ``step(params, opt_state, *batch) -> (params, opt_state,
    loss)``, jit-compiled as one SPMD program.

    ``loss_fn(params, *batch) -> scalar`` must be the *global-batch mean*
    loss (the usual formulation): because the mean over a sharded batch
    already contracts over the data axis, the backward pass's reduction
    IS the allreduce — XLA emits the psum over ICI, replacing
    hvd.DistributedOptimizer's NCCL ring.

    ``param_shardings`` (a pytree of NamedSharding matching ``params``)
    overrides the default fully-replicated param constraint — the
    tensor-parallel hook: pass the model's ``param_shardings(mesh)`` and
    params, grads, and optimizer state all stay sharded over the
    ``model`` axis through the whole step (grads inherit the param
    sharding through AD; XLA keeps the update local to each shard).
    """

    def step(params, opt_state, *batch):
        if mesh is not None:
            batch = tuple(
                jax.lax.with_sharding_constraint(
                    b, NamedSharding(mesh, P(M.DATA_AXIS,
                                             *([None] * (b.ndim - 1)))))
                for b in batch)
            params = (jax.lax.with_sharding_constraint(params,
                                                       param_shardings)
                      if param_shardings is not None else
                      jax.lax.with_sharding_constraint(
                          params, NamedSharding(mesh, P())))
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        with named_scope("train.update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_eval_step(apply_fn, mesh=None):
    """Build ``eval(params, *batch) -> outputs`` sharded like the train
    step (for validation passes between epochs)."""

    def step(params, *batch):
        if mesh is not None:
            batch = tuple(
                jax.lax.with_sharding_constraint(
                    b, NamedSharding(mesh, P(M.DATA_AXIS,
                                             *([None] * (b.ndim - 1)))))
                for b in batch)
        return apply_fn(params, *batch)

    return jax.jit(step)
