"""Decoder blocks as pure functions over a flat parameter dict.

What a config-driven decoder (:mod:`tpudl.zoo.decoder`) is assembled from:
RMSNorm, rotary positions, grouped-query attention with a norm on every
query and key head, LFM2's double-gated short convolution, and the gated
SiLU feed-forward. Every function takes the dict ``p``, the ``name`` its
leaves are filed under (``layers.3.attn`` -> ``layers.3.attn.q_proj``),
and activations ``x`` of shape ``[B, S, D]``; projections are bias-free
and stored ``[in, out]``. Activations keep the dtype the parameters were
cast to (``with_compute_dtype``); norms, rotations and the convolution's
three taps are computed in float32 and cast back.

``init_*`` build the leaves of one block from a ``numpy`` generator, on
the host: a model is initialised once per process and placed by
``Trainer.fit``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs.trace import named_scope
from tpudl.pallas_ops import flash_attention

__all__ = ["rms_norm", "rotary", "conv_op", "attention_op", "gated_ff",
           "init_conv", "init_attention", "init_ff", "normal"]


def normal(rng, *shape, fan_in=None):
    """float32 N(0, 1/fan_in) leaves (``fan_in`` defaults to the
    second-to-last axis: the ``in`` of an ``[in, out]`` projection)."""
    fan_in = shape[-2] if fan_in is None else fan_in
    return (rng.standard_normal(shape, dtype=np.float32)
            / np.float32(np.sqrt(fan_in)))


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta: float):
    """Rotary positions on ``x`` ``[B, S, H, d]``: position ``t`` turns
    the half-split pair ``(x_i, x_{i+d/2})`` by ``t · theta^(-2i/d)``."""
    s, d = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


def conv_op(p, name: str, x):
    """LFM2's double-gated short convolution: ``[B, C, u] = split₃(x
    W_in)``; ``v_t = Σ_j w_j ⊙ (B ⊙ u)_{t-K+1+j}`` (depthwise, causal,
    zeros before the sequence); ``out = (C ⊙ v) W_out``. No activation
    inside. The kernel is ``[K, D]``, ``K = conv_L_cache``."""
    with named_scope("lm.conv_op"):
        b, c, u = jnp.split(x @ p[name + ".in_proj"], 3, axis=-1)
        taps = p[name + ".kernel"].astype(jnp.float32)
        k, s = taps.shape[0], x.shape[1]
        y = jnp.pad((b * u).astype(jnp.float32),
                    ((0, 0), (k - 1, 0), (0, 0)))
        v = sum(taps[j] * y[:, j:j + s] for j in range(k))
        return (c * v.astype(x.dtype)) @ p[name + ".out_proj"]


def attention_op(p, name: str, x, *, heads: int, kv_heads: int, eps: float,
                 theta: float):
    """Causal grouped-query attention: ``heads`` query heads over
    ``kv_heads`` key/value heads, an RMSNorm over each query and key head
    before the rotation, scale ``1/√head_dim``, through
    :func:`tpudl.pallas_ops.flash_attention` (compiled by Mosaic on a
    TPU, interpreted elsewhere; the kernels derive their tile shapes
    from these shapes)."""
    with named_scope("lm.attention"):
        bsz, s, _ = x.shape
        d = p[name + ".q_norm"].shape[0]
        q = (x @ p[name + ".q_proj"]).reshape(bsz, s, heads, d)
        k = (x @ p[name + ".k_proj"]).reshape(bsz, s, kv_heads, d)
        v = (x @ p[name + ".v_proj"]).reshape(bsz, s, kv_heads, d)
        q = rotary(rms_norm(q, p[name + ".q_norm"], eps), theta)
        k = rotary(rms_norm(k, p[name + ".k_norm"], eps), theta)
        out = flash_attention(q, k, v, causal=True)
        return out.reshape(bsz, s, heads * d) @ p[name + ".o_proj"]


def gated_ff(p, name: str, x):
    """``W₂(silu(W₁x) ⊙ W₃x)``."""
    with named_scope("lm.dense_ff"):
        gate = jax.nn.silu(x @ p[name + ".w1"]) * (x @ p[name + ".w3"])
        return gate @ p[name + ".w2"]


def init_conv(rng, name: str, dim: int, taps: int) -> dict:
    return {name + ".in_proj": normal(rng, dim, 3 * dim),
            name + ".kernel": normal(rng, taps, dim, fan_in=taps),
            name + ".out_proj": normal(rng, dim, dim)}


def init_attention(rng, name: str, dim: int, heads: int, kv_heads: int,
                   head_dim: int) -> dict:
    return {name + ".q_proj": normal(rng, dim, heads * head_dim),
            name + ".k_proj": normal(rng, dim, kv_heads * head_dim),
            name + ".v_proj": normal(rng, dim, kv_heads * head_dim),
            name + ".o_proj": normal(rng, heads * head_dim, dim),
            name + ".q_norm": np.ones((head_dim,), np.float32),
            name + ".k_norm": np.ones((head_dim,), np.float32)}


def init_ff(rng, name: str, dim: int, width: int) -> dict:
    return {name + ".w1": normal(rng, dim, width),
            name + ".w3": normal(rng, dim, width),
            name + ".w2": normal(rng, width, dim)}
