"""Ring attention — sequence/context parallelism over the mesh.

The reference has no long-context capability (SURVEY.md §5.7 records it
absent upstream), but tpudl's charter makes long-context first-class:
sequences too large for one chip's HBM are sharded over a mesh axis and
attention runs as a RING — each device holds its Q shard and the K/V
shards ROTATE around the axis via ``jax.lax.ppermute`` (one hop per
step, riding ICI neighbor links, never materializing the full [S, S]
score matrix or the full K/V on any chip).

Numerics: flash-style online softmax — running max ``m``, normalizer
``l`` and weighted accumulator per Q row are updated as each K/V block
arrives, so the result is bit-consistent with dense softmax(QKᵀ)V up to
float re-association. Causal masking uses global positions derived from
``lax.axis_index``, so it stays correct as blocks rotate.

The implementation is ``shard_map`` over the existing :mod:`tpudl.mesh`
axes — the same mesh that carries data-parallel training; XLA schedules
the ppermute collectives on ICI. Differentiable end-to-end (jax.grad
through shard_map), jit-compatible, size-agnostic from the 8-device CPU
test mesh to a pod slice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from tpudl import mesh as M

__all__ = ["ring_attention", "attention_reference", "shard_sequence"]


def attention_reference(q, k, v, causal: bool = False):
    """Dense single-device softmax attention oracle: ``softmax(QKᵀ/√d)V``.
    q, k, v: [batch, seq, heads, head_dim]."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def shard_sequence(tree, mesh, axis: str = M.DATA_AXIS):
    """Place [B, S, ...] arrays with the SEQUENCE dim sharded over
    ``axis`` — the long-context infeed edge (batch replicated)."""
    def _put(x):
        spec = P(None, axis, *([None] * (x.ndim - 2)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(_put, tree)


def ring_attention(q, k, v, mesh, *, axis: str = M.DATA_AXIS,
                   head_axis: str | None = None,
                   causal: bool = False, use_pallas: bool = False,
                   pallas_block: int = 128,
                   pallas_interpret: bool | None = None):
    """Sequence-parallel attention over ``mesh[axis]``.

    q, k, v: [batch, seq, heads, head_dim] with ``seq`` sharded over
    ``axis`` (``shard_sequence`` produces the right placement; unsharded
    inputs are accepted and constrained). ``seq`` must divide evenly by
    the axis size. Returns [batch, seq, heads, head_dim] with the same
    sequence sharding.

    ``head_axis`` additionally shards the HEADS dim over that mesh axis
    — the tensor-parallel composition (SP ring × TP heads): heads are
    embarrassingly parallel in attention, so the ring body runs
    unchanged on its head shard and no extra collective is needed
    inside; ``heads`` must divide by the axis size.

    Communication: n-1 neighbor ``ppermute`` hops of the local K/V block
    (each hop overlaps the block's score/accumulate compute in XLA's
    schedule); memory: O(S/n) K/V per device, O((S/n)²·n → S·S/n) scores
    peak, never the full matrix.

    ``use_pallas=True`` computes each ring step with the Pallas flash
    kernel (:func:`tpudl.pallas_ops.flash_attention`): forward AND
    backward are tiled kernels (the custom VJP launches ONE backward
    kernel from the saved log-sum-exp), so neither direction
    materializes an (S/n)² matrix per device, and strictly-future
    hops/tiles are skipped under causal masking. Partials merge exactly
    via their log-sum-exps (the standard ring/flash-decoding merge).
    ``pallas_interpret`` is :func:`flash_attention`'s ``interpret``:
    None compiles the kernel on TPU and interprets it elsewhere.
    """
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by ring size {n}")
    if head_axis is not None and q.shape[2] % mesh.shape[head_axis]:
        raise ValueError(
            f"heads {q.shape[2]} not divisible by mesh axis "
            f"{head_axis!r} size {mesh.shape[head_axis]}")
    vary_axes = (axis,) if head_axis is None else (axis, head_axis)
    seq_spec = P(None, axis, head_axis, None)
    if use_pallas:
        return _ring_attention_pallas(q, k, v, mesh, axis, n, seq_spec,
                                      causal, pallas_block,
                                      pallas_interpret, vary_axes)

    def local(qb, kb, vb):
        # qb/kb/vb: [B, S/n, H, D] — this device's blocks
        idx = jax.lax.axis_index(axis)
        s_loc = qb.shape[1]
        scale = 1.0 / jnp.sqrt(qb.shape[-1]).astype(jnp.float32)
        q32 = qb.astype(jnp.float32)
        q_pos = idx * s_loc + jnp.arange(s_loc)

        m = jnp.full(qb.shape[:2] + (qb.shape[2],), -jnp.inf, jnp.float32)
        m = jnp.moveaxis(m, -1, 1)                     # [B, H, Sq]
        l = jnp.zeros_like(m)                          # [B, H, Sq]
        acc = jnp.zeros(
            (qb.shape[0], qb.shape[2], s_loc, qb.shape[3]), jnp.float32)
        # the carry becomes device-varying after one step (it mixes in the
        # rotating K/V); mark the initial values varying so scan's carry
        # types line up under shard_map's varying-axis tracking
        m, l, acc = (_mark_varying(t, vary_axes) for t in (m, l, acc))

        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, s):
            m, l, acc, kc, vc = carry
            # block s originated on device (idx - s) mod n
            src = (idx - s) % n
            k_pos = src * s_loc + jnp.arange(s_loc)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q32,
                                kc.astype(jnp.float32)) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            # exp(-inf - -inf) guard: rows with no visible keys yet keep
            # m_new == -inf; make their correction factor 0, not NaN
            corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_new))
            p = jnp.exp(scores - m_new[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
            kc, vc = _rotate_unless_last(kc, vc, s, n, axis, perm)
            return (m_new, l, acc, kc, vc), None

        (m, l, acc, _k, _v), _ = jax.lax.scan(
            step, (m, l, acc, kb, vb), jnp.arange(n))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]  # [B,H,Sq,D]
        return jnp.moveaxis(out, 1, 2).astype(qb.dtype)     # [B,Sq,H,D]

    fn = shard_map(local, mesh=mesh,
                   in_specs=(seq_spec, seq_spec, seq_spec),
                   out_specs=seq_spec)
    return fn(q, k, v)


def _ring_attention_pallas(q, k, v, mesh, axis, n, seq_spec, causal,
                           block, interpret, vary_axes=None):
    """Ring loop where each step is one Pallas flash-attention call over
    the local Q shard and the rotating K/V block; partials merge via
    log-sum-exp weights (exact — same math as the in-kernel online
    softmax, applied across blocks)."""
    from tpudl.pallas_ops import _NEG_INF, flash_attention

    def local(qb, kb, vb):
        idx = jax.lax.axis_index(axis)
        s_loc = qb.shape[1]
        q_off = idx * s_loc
        o0 = jnp.zeros(qb.shape, jnp.float32)
        lse0 = jnp.full((qb.shape[0], s_loc, qb.shape[2]), _NEG_INF,
                        jnp.float32)
        o0, lse0 = (_mark_varying(t, vary_axes or (axis,))
                    for t in (o0, lse0))
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, s):
            o, lse, kc, vc = carry
            src = (idx - s) % n

            def live(args):
                kc, vc = args
                return flash_attention(
                    qb, kc, vc, causal=causal, q_offset=q_off,
                    k_offset=src * s_loc, block_q=block, block_k=block,
                    interpret=interpret, return_lse=True)

            def future(args):
                return (jnp.zeros(qb.shape, qb.dtype),
                        jnp.full(lse0.shape, _NEG_INF, jnp.float32))

            if causal:
                # a hop whose K block is strictly in this shard's future
                # contributes weight exp(-inf); skip the whole launch
                ob, lb = jax.lax.cond(src <= idx, live, future, (kc, vc))
            else:
                ob, lb = live((kc, vc))
            m = jnp.maximum(lse, lb)
            w_prev, w_blk = jnp.exp(lse - m), jnp.exp(lb - m)
            denom = w_prev + w_blk
            safe = jnp.where(denom == 0.0, 1.0, denom)
            o = (o * w_prev[..., None]
                 + ob.astype(jnp.float32) * w_blk[..., None]) / safe[..., None]
            lse = m + jnp.log(safe)
            kc, vc = _rotate_unless_last(kc, vc, s, n, axis, perm)
            return (o, lse, kc, vc), None

        (o, _lse, _k, _v), _ = jax.lax.scan(
            step, (o0, lse0, kb, vb), jnp.arange(n))
        return o.astype(qb.dtype)

    # check_vma off: pallas_call's out_shape carries no varying-axis
    # annotation, so the tracker cannot type the kernel's outputs
    fn = shard_map(local, mesh=mesh,
                   in_specs=(seq_spec, seq_spec, seq_spec),
                   out_specs=seq_spec, check_vma=False)
    return fn(q, k, v)


def _rotate_unless_last(kc, vc, s, n, axis, perm):
    """Rotate the K/V blocks one ring hop — except on the final scan step,
    whose rotated blocks would be discarded (n-1 hops suffice for n
    blocks; the predicate is the uniform scan counter, so every device
    takes the same branch and the collective stays matched)."""
    if n == 1:
        return kc, vc
    return jax.lax.cond(
        s < n - 1,
        lambda kv: (jax.lax.ppermute(kv[0], axis, perm),
                    jax.lax.ppermute(kv[1], axis, perm)),
        lambda kv: kv,
        (kc, vc))


def _mark_varying(t, axes):
    """Mark ``t`` device-varying over ``axes`` (a name or tuple of
    names) under shard_map's varying-axis type tracking."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return jax.lax.pcast(t, axes, to="varying")
