"""Decoder blocks as pure functions over a flat parameter dict.

What a config-driven decoder (:mod:`tpudl.zoo.decoder`) is assembled from:
RMSNorm, rotary positions (half-split or interleaved pairs),
grouped-query attention (with a norm on every query and key head and a
rotation, or with neither), latent attention (MLA: low-rank queries, keys
and values expanded from one compressed latent, one rotated key shared by
all heads), LFM2's double-gated short convolution, the Mamba-2 mixer with
its chunked selective scan, and the gated SiLU and relu² feed-forwards.
Every function takes the dict ``p``, the ``name`` its leaves are filed under (``layers.3.attn`` ->
``layers.3.attn.q_proj``), and activations ``x`` of shape ``[B, S, D]``;
projections are bias-free and stored ``[in, out]``. Activations keep the
dtype the parameters were cast to (``with_compute_dtype``); norms,
rotations, the convolutions' taps and the recurrence's decays, sums and
state are computed in float32 and cast back.

``init_*`` build the leaves of one block from a ``numpy`` generator, on
the host: a model is initialised once per process and placed by
``Trainer.fit``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs import metrics as _metrics
from tpudl.obs.trace import named_scope
from tpudl import pallas_ops
from tpudl.pallas_ops import flash_attention

__all__ = ["rms_norm", "rotary", "conv_op", "attention_op", "mla_op",
           "gated_ff", "relu2_ff", "mamba2_op", "ssd_scan", "init_conv",
           "init_attention", "init_mla", "init_ff", "init_mamba2", "normal",
           "MAMBA2_FLOAT32"]

# the recurrence's per-head scalars: a step casts every other leaf to its
# compute dtype, these stay float32 (``with_compute_dtype(keep=)``)
MAMBA2_FLOAT32 = (".A_log", ".dt_bias", ".D")
# what the recurrence's decays, cumulative sums and carried state are held
# in (benchmark/controls/hybrid_bf16_scan.py sets bfloat16, and has to fail)
SCAN_DTYPE = jnp.float32


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


def rotary(x, theta: float, interleaved: bool = False, *, axis: int = 1,
           first: int = 0):
    """Rotary positions on ``x`` ``[..., d]`` whose positions lie along
    ``axis`` (``[B, S, H, d]`` or ``[B, S, d]`` as it stands; ``axis=2``
    for a head-major ``[B, H, S, d]``): position ``t`` turns the
    half-split pair ``(x_i, x_{i+d/2})`` by ``t · theta^(-2i/d)``, or
    with ``interleaved`` the neighbouring pair ``(x_{2i}, x_{2i+1})``,
    each staying where it lies (no reshape to pairs). With ``first``
    (interleaved pairs only) the columns before it carry no position and
    pass as they are, cos 1 and sin 0, in the same one pass that turns
    the columns from it on (latent attention's query: 128 without
    positions, then 64 with)."""
    if first and not interleaved:
        raise ValueError("first= is for interleaved pairs")
    s, d = x.shape[axis], x.shape[-1] - first
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    widen = ((lambda a: jnp.repeat(a, 2, -1)) if interleaved
             else (lambda a: jnp.concatenate([a] * 2, -1)))
    others = tuple(i for i in range(x.ndim - 1) if i != axis)

    def table(fn, before):
        t = widen(fn(angle))
        if first:
            t = jnp.pad(t, ((0, 0), (first, 0)), constant_values=before)
        return jnp.expand_dims(t, others)

    cos, sin = table(jnp.cos, 1.0), table(jnp.sin, 0.0)
    if interleaved:
        return _turn_pairs(x, cos, sin, first)
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turn_pairs(x, cos, sin, first: int):
    """``x · cos + partner(x) · sin`` in float32, where the partner of the
    neighbouring pair ``(x_{2i}, x_{2i+1})`` from column ``first`` on is
    ``(−x_{2i+1}, x_{2i})``. The partner is a product with the 0 / ±1
    matrix of that signed permutation: exact in any dtype, and the float32
    arithmetic around it is the product's epilogue, ONE pass over ``x``. A
    shift of one lane either way, which is what it is, XLA's TPU backend
    writes out as two float32 arrays of ``x``'s size before the pass that
    reads them (PERF.md §6, PR 37). ``sin`` is the same on both columns of
    a pair, so the transpose is the turn by the opposite angle, one such
    pass over the cotangent as it comes (autodiff's would put the product
    on the float32 ``g · sin``)."""
    width = x.shape[-1]
    turn = np.zeros((width, width), np.float32)
    even = np.arange(first, width, 2)
    turn[even + 1, even], turn[even, even + 1] = -1.0, 1.0
    partner = jnp.einsum("...d,de->...e", x, jnp.asarray(turn, x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)


_turn_pairs.defvjp(
    lambda x, cos, sin, first: (_turn_pairs(x, cos, sin, first), (cos, sin)),
    lambda first, tables, g: (_turn_pairs(g, tables[0], -tables[1], first),
                              None, None))


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
                 theta):
    """Causal grouped-query attention: ``heads`` query heads over
    ``kv_heads`` key/value heads, scale ``1/√head_dim``, through
    :func:`tpudl.pallas_ops.flash_attention` (compiled by Mosaic on a
    TPU, interpreted elsewhere; the kernels derive their tile shapes
    from these shapes). With a ``theta``, an RMSNorm over each query and
    key head and then the rotation (LFM2); with ``theta=None`` neither
    (``nemotron_h``: the state-space layers carry the positions)."""
    with named_scope("lm.attention"):
        bsz, s, _ = x.shape
        d = p[name + ".q_proj"].shape[1] // heads
        q = (x @ p[name + ".q_proj"]).reshape(bsz, s, heads, d)
        k = (x @ p[name + ".k_proj"]).reshape(bsz, s, kv_heads, d)
        v = (x @ p[name + ".v_proj"]).reshape(bsz, s, kv_heads, d)
        if theta is not None:
            q = rotary(rms_norm(q, p[name + ".q_norm"], eps), theta)
            k = rotary(rms_norm(k, p[name + ".k_norm"], eps), theta)
        out = flash_attention(q, k, v, causal=True)
        return out.reshape(bsz, s, heads * d) @ p[name + ".o_proj"]


def mla_op(p, name: str, x, *, heads: int, nope: int, rope: int,
           eps: float, theta: float):
    """Causal multi-head latent attention in its expanded (training)
    form: ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` a
    head; ``[c_kv | k_r] = x W_kva``, ``[k_nope | v] = RMSNorm(c_kv)
    W_kvb`` a head. ``q_rope`` and the ONE ``k_r`` that every head
    shares are rotated on interleaved pairs; scores ``(q_nope·k_nope +
    q_rope·k_r) / √(nope + rope)``; ``W_o``. Per-head keys and values are
    materialised from the latent (the absorbed form is decoding's, and is
    not built).

    Nothing is copied between a projection and a kernel: the products
    write ``q`` ``[B, H, S, nope + rope]``, ``k_nope`` ``[B, H, S, nope]``
    and ``v`` ``[B, H, S, v]`` head-major (the last two from the two
    column halves of ``W_kvb``, each a product of its own), which is how
    :func:`tpudl.pallas_ops.flash_attention` (``layout="bhsd"``) takes
    them; ``q``'s last ``rope`` columns are turned in one pass over it;
    ``k_r`` ``[B, S, rope]`` goes to the kernels as ``k_shared``, the one
    row a batch entry that every head reads in place; the output goes
    into ``W_o`` head-major. The four low-rank projections and their two
    norms lie under the inner scope ``lm.attention.latent``."""
    def heads_first(c, w):
        # [B, S, C] x [C, H, d] -> [B, H, S, d]. The transpose is the
        # product's output layout, no copy; asked of einsum itself
        # ("->bhsd") XLA turns the product round and copies (PERF.md §6)
        return jnp.einsum("bsc,chd->bshd", c, w).transpose(0, 2, 1, 3)

    with named_scope("lm.attention"):
        with named_scope("lm.attention.latent"):
            c_q = rms_norm(x @ p[name + ".q_a_proj"], p[name + ".q_a_norm"],
                           eps)
            q = heads_first(c_q, p[name + ".q_b_proj"].reshape(
                -1, heads, nope + rope))
            w_kv = p[name + ".kv_b_proj"]
            w_kv = w_kv.reshape(w_kv.shape[0], heads, -1)
            c_kv, k_r = jnp.split(x @ p[name + ".kv_a_proj"],
                                  [w_kv.shape[0]], axis=-1)
            c_kv = rms_norm(c_kv, p[name + ".kv_a_norm"], eps)
            k_nope = heads_first(c_kv, w_kv[..., :nope])
            v = heads_first(c_kv, w_kv[..., nope:])
        q = rotary(q, theta, interleaved=True, axis=2, first=nope)
        k_r = rotary(k_r, theta, interleaved=True)
        out = flash_attention(q, k_nope, v, causal=True, layout="bhsd",
                              k_shared=k_r)
        return jnp.einsum("bhsd,hdo->bso", out, p[name + ".o_proj"].reshape(
            heads, -1, x.shape[2]))


def gated_ff(p, name: str, x, scope: str = "lm.dense_ff"):
    """``W₂(silu(W₁x) ⊙ W₃x)``; a shared expert of this form is filed
    under the ``scope`` ``lm.shared_ff``."""
    with named_scope(scope):
        gate = jax.nn.silu(x @ p[name + ".w1"]) * (x @ p[name + ".w3"])
        return gate @ p[name + ".w2"]


def relu2_ff(p, name: str, x):
    """``W₂ relu(W₁x)²``: two products, no gate (``nemotron_h``'s
    ``relu2``; its shared expert, which every token passes)."""
    with named_scope("lm.shared_ff"):
        return jnp.square(jax.nn.relu(x @ p[name + ".w1"])) @ p[name + ".w2"]


def ssd_scan(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence of ONE sequence, ``H_t =
    exp(Δ_t A) H_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = H_t C_t``, in chunks
    of ``chunk`` positions (Mamba-2's state-space duality):
    :func:`tpudl.pallas_ops.ssd_scan`, a Pallas kernel with its backward
    (compiled by Mosaic on a TPU, interpreted elsewhere).

    ``x`` ``[S, H, P]``, ``dt`` ``[S, H]`` (Δ, after the softplus), ``a``
    ``[H]`` (negative), ``b`` / ``c`` ``[S, G, N]``; head ``h`` uses
    group ``h // (H / G)``. Returns ``y`` ``[S, H, P]`` float32. Inside
    a chunk the masked product ``(L ∘ C Bᵀ)(Δ x)``, ``L_ij = exp(Σ_{j<s≤i}
    Δ_s A)``, and what the state entering the chunk adds, ``exp(Σ_{s≤i}
    Δ_s A) C_i · H``; the state goes from chunk to chunk in VMEM. Δ A, its
    running sums, ``L`` and the carried state are ``SCAN_DTYPE`` (float32;
    read when the program is traced, and handed over as ``a``'s dtype);
    the products take operands in ``x``'s dtype and accumulate in float32.
    A length that ``chunk`` does not divide is padded with Δ = 0: no
    decay, no input."""
    return pallas_ops.ssd_scan(x, dt, a.astype(SCAN_DTYPE), b, c,
                               chunk=chunk)


def mamba2_op(p, name: str, x, *, heads: int, groups: int, state: int,
              chunk: int, eps: float):
    """The Mamba-2 mixer: ``[z | xBC | dt] = x W_in``; ``xBC ← silu(conv(
    xBC) + b)`` (depthwise, causal, zeros before the sequence); ``Δ =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the recurrence
    (:func:`ssd_scan`); ``y + D x``; ``GroupRMSNorm(y ⊙ silu(z))`` over
    ``groups`` groups of channels; ``W_out``. ``A_log``, ``dt_bias`` and
    ``D`` are read as float32 whatever the other leaves were cast to.

    A sequence at a time (``lax.map``), each rematerialised: what is
    live is one sequence's projections and, in its backward, the scan's
    chunk states, a quarter of the cell's batch. Sets the gauges
    ``lm.ssm.chunk`` / ``lm.ssm.chunks`` while a program is TRACED."""
    s, f32 = x.shape[1], jnp.float32
    d_in = p[name + ".out_proj"].shape[0]
    conv_dim = d_in + 2 * groups * state
    _metrics.gauge("lm.ssm.chunk").set(chunk)
    _metrics.gauge("lm.ssm.chunks").set(-(-s // chunk))

    def mixer(u):                                          # [S, D]
        z, xbc, dt = jnp.split(u @ p[name + ".in_proj"],
                               [d_in, d_in + conv_dim], axis=-1)
        taps = p[name + ".conv_kernel"].astype(f32)
        k = taps.shape[0]
        padded = jnp.pad(xbc.astype(f32), ((k - 1, 0), (0, 0)))
        xbc = jax.nn.silu(
            sum(taps[j] * padded[j:j + s] for j in range(k))
            + p[name + ".conv_bias"].astype(f32)).astype(u.dtype)
        xs, b, c = jnp.split(xbc, [d_in, d_in + groups * state], axis=-1)
        xs = xs.reshape(s, heads, d_in // heads)
        dt = jax.nn.softplus(dt.astype(f32)
                             + p[name + ".dt_bias"].astype(f32))
        with named_scope("lm.ssm.scan"):
            y = ssd_scan(xs, dt, -jnp.exp(p[name + ".A_log"].astype(f32)),
                         b.reshape(s, groups, state),
                         c.reshape(s, groups, state), chunk)
        y = y + p[name + ".D"].astype(f32)[:, None] * xs.astype(f32)
        y = y.reshape(s, d_in) * jax.nn.silu(z.astype(f32))
        y = y.reshape(s, groups, d_in // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        y = y.reshape(s, d_in) * p[name + ".norm"].astype(f32)
        return y.astype(u.dtype) @ p[name + ".out_proj"]

    with named_scope("lm.ssm"):
        return jax.lax.map(jax.checkpoint(mixer), x)


def init_conv(rng, name: str, dim: int, taps: int) -> dict:
    return {name + ".in_proj": normal(rng, dim, 3 * dim),
            name + ".kernel": normal(rng, taps, dim, fan_in=taps),
            name + ".out_proj": normal(rng, dim, dim)}


def init_attention(rng, name: str, dim: int, heads: int, kv_heads: int,
                   head_dim: int, head_norms: bool = True) -> dict:
    out = {name + ".q_proj": normal(rng, dim, heads * head_dim),
           name + ".k_proj": normal(rng, dim, kv_heads * head_dim),
           name + ".v_proj": normal(rng, dim, kv_heads * head_dim),
           name + ".o_proj": normal(rng, heads * head_dim, dim)}
    if head_norms:
        out[name + ".q_norm"] = np.ones((head_dim,), np.float32)
        out[name + ".k_norm"] = np.ones((head_dim,), np.float32)
    return out


def init_mla(rng, name: str, dim: int, heads: int, q_rank: int,
             kv_rank: int, nope: int, rope: int, v_dim: int) -> dict:
    return {name + ".q_a_proj": normal(rng, dim, q_rank),
            name + ".q_a_norm": np.ones((q_rank,), np.float32),
            name + ".q_b_proj": normal(rng, q_rank, heads * (nope + rope)),
            name + ".kv_a_proj": normal(rng, dim, kv_rank + rope),
            name + ".kv_a_norm": np.ones((kv_rank,), np.float32),
            name + ".kv_b_proj": normal(rng, kv_rank, heads * (nope + v_dim)),
            name + ".o_proj": normal(rng, heads * v_dim, dim)}


def init_ff(rng, name: str, dim: int, width: int, gated: bool = True) -> dict:
    out = {name + ".w1": normal(rng, dim, width)}
    if gated:
        out[name + ".w3"] = normal(rng, dim, width)
    out[name + ".w2"] = normal(rng, width, dim)
    return out


def init_mamba2(rng, name: str, dim: int, heads: int, head_dim: int,
                groups: int, state: int, taps: int, dt_range) -> dict:
    """``A_log = log U[1, 16]``; ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly from ``dt_range`` = (min, max, floor) and
    floored; ``D`` and the norm at 1; the convolution's bias at 0."""
    d_in, lo, hi = heads * head_dim, *np.log(dt_range[:2])
    conv_dim = d_in + 2 * groups * state
    step = np.maximum(np.exp(rng.uniform(lo, hi, heads)), dt_range[2])
    return {
        name + ".in_proj": normal(rng, dim, d_in + conv_dim + heads),
        name + ".conv_kernel": normal(rng, taps, conv_dim, fan_in=taps),
        name + ".conv_bias": np.zeros((conv_dim,), np.float32),
        name + ".A_log": np.log(rng.uniform(1.0, 16.0, heads)).astype(
            np.float32),
        name + ".dt_bias": (step + np.log(-np.expm1(-step))).astype(
            np.float32),
        name + ".D": np.ones((heads,), np.float32),
        name + ".norm": np.ones((d_in,), np.float32),
        name + ".out_proj": normal(rng, d_in, dim)}
