"""Pallas TPU kernels for the hot attention op.

Flash attention in Pallas: tiled ``softmax(QKᵀ/√d)·V`` that never
materializes the full score matrix — Q/K/V tiles stream HBM→VMEM per
grid step, scores hit the MXU via ``jnp.dot(..,
preferred_element_type=f32)``, and the online-softmax state (running
max, normalizer, weighted accumulator) lives in VMEM scratch that
persists across the innermost (K-tile) grid dimension. Peak VMEM is
O(block_q·block_k + block·d) instead of O(S²).

The kernel also returns the per-row **log-sum-exp**, which makes it
ring-composable: :func:`tpudl.attention.ring_attention` with
``use_pallas=True`` runs this kernel on each rotating K/V block and
combines the per-block (out, lse) pairs exactly — the standard
ring/flash-decoding partial-softmax merge.

``q_offset``/``k_offset`` are the blocks' global sequence positions, so
causal masking stays correct when the caller holds only a shard of the
sequence (the ring case).

CPU/tests run the same kernel with ``interpret=True`` (pure jax
semantics, no tiling constraints). Compiled, every block is a multiple
of the 128-lane tile: a sequence that is not is PADDED up to the next
block multiple and the padded keys are masked in-kernel, so an awkward
length (S=2047) costs one partial tile, never a whole-sequence block
that outgrows VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30  # finite -inf stand-in: exp(x - _NEG_INF) never NaNs


def _tile_live(causal, qoff_ref, koff_ref, iq, ik, block_q, block_k):
    """Whether this (Q, K) tile has ANY visible pair under causal
    masking — the shared tile-skip predicate for all three kernels."""
    if not causal:
        return jnp.bool_(True)
    return (koff_ref[0] + ik * block_k
            <= qoff_ref[0] + (iq + 1) * block_q - 1)


def _masked_scores(q_ref, k_ref, qoff_ref, koff_ref, iq, ik, *, causal,
                   scale, block_q, block_k, kv_len, precision):
    """QKᵀ·scale with the global-position causal mask applied — the ONE
    definition of the score tile shared by forward, dq and dkv kernels.
    ``kv_len`` (static; None when the keys were not padded) masks the
    pad keys past the real sequence end by their LOCAL index."""
    # operands go to the MXU in their own dtype (bf16 stays one pass);
    # the product is accumulated, scaled and masked in float32
    q, k = q_ref[0], k_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                precision=precision) * scale
    if causal or kv_len is not None:
        k_idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
    if causal:
        q_pos = (qoff_ref[0] + iq * block_q
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
        s = jnp.where(q_pos >= koff_ref[0] + k_idx, s, _NEG_INF)
    if kv_len is not None:
        s = jnp.where(k_idx < kv_len, s, _NEG_INF)
    return q, k, s


def _bwd_p(s, lse):
    """Reconstruct softmax weights from the saved log-sum-exp, zeroing
    rows that saw no key (f32 multiplicand: a bool minor-dim insertion
    is unsupported in Mosaic for non-32-bit types)."""
    alive = (lse > _NEG_INF * 0.5).astype(jnp.float32)[:, None]
    return jnp.exp(s - lse[:, None]) * alive


def _bwd_ds(p, do, v, dlt, scale, precision):
    """The shared score gradient ``p ⊙ (dO Vᵀ − δ + dlse) · scale``."""
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32,
                 precision=precision)
    return p * (dp - dlt[:, None]) * scale


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *, causal: bool, scale: float,
                  block_q: int, block_k: int, kv_len, precision):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    iq = pl.program_id(1)
    # a K tile strictly in the future of every row of this Q tile
    # contributes nothing; skip BOTH MXU passes (≈2x for long causal)
    live = _tile_live(causal, qoff_ref, koff_ref, iq, ik, block_q, block_k)

    @pl.when(live)
    def _compute():
        _q, _k, s = _masked_scores(
            q_ref, k_ref, qoff_ref, koff_ref, iq, ik, causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            kv_len=kv_len, precision=precision)

        m_prev = m_scr[:, 0]                          # [TQ]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])               # [TQ, TK]
        # a row with NO visible key yet has m_new == _NEG_INF and
        # exp(0)==1 for every masked entry; zero it so l stays 0 and
        # finalize reports the row as fully masked, not mean(V)
        p = jnp.where((m_new <= _NEG_INF * 0.5)[:, None], 0.0, p)
        l_new = l_scr[:, 0] * corr + p.sum(axis=1)
        v = v_ref[0]
        acc_scr[:] = (acc_scr[:] * corr[:, None]
                      + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32,
                                precision=precision))
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        # lse = m + log(l); fully-masked rows (l==0) get -inf-equivalent.
        # The row vector is broadcast over an 8-sublane dim purely to
        # satisfy the TPU (8, 128) output-tile rule; callers read row 0.
        lse = jnp.where(l == 0.0, _NEG_INF, m_scr[:, 0] + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, dlt_ref, dq_ref, dq_scr, *, causal: bool,
                   scale: float, block_q: int, block_k: int, kv_len,
                   precision):
    """dq = Σ_k  p ⊙ (dOVᵀ − δ + dlse) · scale @ K, accumulated over the
    innermost K-tile grid dim — same tiling discipline as the forward,
    no S² materialization. δ = rowsum(dO ⊙ O), and ``p = exp(s − lse)``
    reconstructs the softmax weights from the saved log-sum-exp."""
    iq, ik, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _tile_live(causal, qoff_ref, koff_ref, iq, ik, block_q, block_k)

    @pl.when(live)
    def _compute():
        q, k, s = _masked_scores(
            q_ref, k_ref, qoff_ref, koff_ref, iq, ik, causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            kv_len=kv_len, precision=precision)
        p = _bwd_p(s, lse_ref[0, 0])
        ds = _bwd_ds(p, do_ref[0], v_ref[0], dlt_ref[0, 0], scale,
                     precision)
        dq_scr[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32,
                             precision=precision)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, dlt_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    causal: bool, scale: float, block_q: int,
                    block_k: int, kv_len, precision, q_tiles: int):
    """dk = Σ_q (p ⊙ (dOVᵀ − δ + dlse) · scale)ᵀ @ Q ; dv = Σ_q pᵀ @ dO —
    grid over K tiles with the Q-tile dim innermost. Under grouped
    queries the innermost dim runs over every query head of this K/V
    head's group in turn (``q_tiles`` tiles each), so the group's sum
    is accumulated in float32 in the same scratch."""
    ik, j, nj = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    iq = j % q_tiles

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _tile_live(causal, qoff_ref, koff_ref, iq, ik, block_q, block_k)

    @pl.when(live)
    def _compute():
        q, k, s = _masked_scores(
            q_ref, k_ref, qoff_ref, koff_ref, iq, ik, causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            kv_len=kv_len, precision=precision)
        p = _bwd_p(s, lse_ref[0, 0])
        do = do_ref[0]
        dv_scr[:] += jnp.dot(p.T.astype(do.dtype), do,
                             preferred_element_type=jnp.float32,
                             precision=precision)
        ds = _bwd_ds(p, do, v_ref[0], dlt_ref[0, 0], scale, precision)
        dk_scr[:] += jnp.dot(ds.T.astype(q.dtype), q,
                             preferred_element_type=jnp.float32,
                             precision=precision)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_flash_bwd(qh, kh, vh, out, lse, qoff, koff, do, dlse, *,
                      causal, block_q, block_k, kv_len, interpret,
                      precision, group=1):
    """Tiled flash backward: (dq, dk, dv) without any S² tensor.

    The lse cotangent folds in analytically: ∂lse_i/∂s_ij = p_ij, so the
    shared score gradient is ds = p ⊙ (dOVᵀ − δ + dlse) with
    δ = rowsum(dO ⊙ O) − the δ and dlse terms combine into one per-row
    constant fed to both kernels. ``group`` query rows share each K/V
    row (row ``bh`` reads K/V row ``bh // group``)."""
    bh_n, s_q, d = qh.shape
    s_k = kh.shape[1]
    scale = 1.0 / (d ** 0.5)
    do32 = do.astype(jnp.float32)
    # per-row constant: −δ + dlse, folded so the kernels need ONE vector
    dlt = (jnp.sum(do32 * out.astype(jnp.float32), axis=-1)
           - dlse.astype(jnp.float32))
    # broadcast row vectors over an 8-sublane dim (TPU input tiling)
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh_n, 8, s_q))
    dlt8 = jnp.broadcast_to(dlt[:, None, :], (bh_n, 8, s_q))
    kernel_kw = dict(causal=causal, scale=scale, block_q=block_q,
                     block_k=block_k, kv_len=kv_len, precision=precision)

    # dq: grid (BH, Sq/TQ, Sk/TK) — q tile fixed per row, K innermost
    def qi_q(bh, iq, ik):
        return (bh, iq, 0)

    def qi_k(bh, iq, ik):
        return (bh // group, ik, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kernel_kw),
        grid=(bh_n, s_q // block_q, s_k // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), qi_q),
            pl.BlockSpec((1, block_k, d), qi_k),
            pl.BlockSpec((1, block_k, d), qi_k),
            pl.BlockSpec((1, block_q, d), qi_q),
            pl.BlockSpec((1, 8, block_q), lambda bh, iq, ik: (bh, 0, iq)),
            pl.BlockSpec((1, 8, block_q), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), qi_q),
        out_shape=jax.ShapeDtypeStruct((bh_n, s_q, d), qh.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qoff, koff, qh, kh, vh, do, lse8, dlt8)

    # dk/dv: grid (B·Hkv, Sk/TK, group · Sq/TQ) — k tile fixed per row,
    # the group's query heads and their Q tiles innermost
    q_tiles = s_q // block_q

    def ki_k(bkv, ik, j):
        return (bkv, ik, 0)

    def ki_q(bkv, ik, j):
        return (bkv * group + j // q_tiles, j % q_tiles, 0)

    def ki_row(bkv, ik, j):
        return (bkv * group + j // q_tiles, 0, j % q_tiles)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_tiles=q_tiles, **kernel_kw),
        grid=(bh_n // group, s_k // block_k, group * q_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), ki_q),
            pl.BlockSpec((1, block_k, d), ki_k),
            pl.BlockSpec((1, block_k, d), ki_k),
            pl.BlockSpec((1, block_q, d), ki_q),
            pl.BlockSpec((1, 8, block_q), ki_row),
            pl.BlockSpec((1, 8, block_q), ki_row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), ki_k),
            pl.BlockSpec((1, block_k, d), ki_k),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kh.shape, kh.dtype),
            jax.ShapeDtypeStruct(vh.shape, vh.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qoff, koff, qh, kh, vh, do, lse8, dlt8)
    return dq, dk, dv


@functools.lru_cache(maxsize=32)
def _flash_fn(causal: bool, block_q: int, block_k: int, kv_len,
              interpret: bool, precision, group: int = 1):
    """One custom-VJP'd head-major flash fn per static config: forward
    AND backward are Pallas kernels (pallas_call has no generic
    autodiff), so neither direction materializes an S² tensor."""

    def fwd_impl(qh, kh, vh, qoff, koff):
        return _pallas_flash_bh(qh, kh, vh, qoff, koff, causal=causal,
                                block_q=block_q, block_k=block_k,
                                kv_len=kv_len, interpret=interpret,
                                precision=precision, group=group)

    f = jax.custom_vjp(fwd_impl)

    def fwd(qh, kh, vh, qoff, koff):
        out, lse = fwd_impl(qh, kh, vh, qoff, koff)
        return (out, lse), (qh, kh, vh, out, lse, qoff, koff)

    def bwd(res, cots):
        qh, kh, vh, out, lse, qoff, koff = res
        do, dlse = cots
        dq, dk, dv = _pallas_flash_bwd(
            qh, kh, vh, out, lse, qoff, koff, do, dlse, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
            interpret=interpret, precision=precision, group=group)
        return dq, dk, dv, None, None

    f.defvjp(fwd, bwd)
    return f


def _fit_block(block: int, s: int, align: int) -> tuple[int, int]:
    """``(block, padded_s)`` for a requested block over a length-``s``
    axis: the request clipped to the (aligned) sequence and rounded
    down to ``align``, and ``s`` rounded up to a multiple of it."""
    block = max(align, min(block, s + (-s % align)) // align * align)
    return block, s + (-s % block)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "return_lse", "precision"))
def flash_attention(q, k, v, *, causal: bool = False, q_offset=0,
                    k_offset=0, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None,
                    return_lse: bool = False, precision=None):
    """Tiled flash attention. q: [B, Sq, H, D], k/v: [B, Sk, Hkv, D] →
    out [B, Sq, H, D] (and, with ``return_lse``, lse [B, Sq, H] —
    ``logsumexp(scores)`` per query row, for ring partial merges).

    Grouped queries: ``Hkv`` divides ``H`` and K/V head ``j`` serves the
    query heads ``j·H/Hkv … (j+1)·H/Hkv − 1``. The kernels read the
    shared K/V tiles in place (no repeated copy), and dk/dv come back
    with ``Hkv`` heads, summed over each group in float32.

    ``q_offset``/``k_offset`` are the blocks' GLOBAL sequence positions
    for causal masking; they may be traced values (each ring device
    passes its rotating source position). Block sizes are advisory: a
    compiled block is a multiple of the 128-lane tile, and a sequence
    that is not a block multiple is zero-padded up to one (pad keys
    masked in-kernel, pad query rows dropped), so any length works
    with a bounded VMEM footprint.

    ``interpret=None`` picks from the process's default backend: the
    compiled Mosaic kernel on TPU, the Pallas interpreter anywhere else
    (Mosaic has no other target)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"{h} query heads cannot share {h_kv} key / "
                         f"{v.shape[2]} value heads")
    align = 1 if interpret else 128
    block_q, pad_q = _fit_block(block_q, s_q, align)
    block_k, pad_k = _fit_block(block_k, s_k, align)

    # head-major [B*H, S, D]: each grid row owns one (batch, head) pair
    def to_bh(x, padded):
        x = jnp.pad(x, ((0, 0), (0, padded - x.shape[1]), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(-1, padded, d)

    qh, kh, vh = to_bh(q, pad_q), to_bh(k, pad_k), to_bh(v, pad_k)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    out, lse = _flash_fn(causal, block_q, block_k,
                         s_k if pad_k != s_k else None, interpret,
                         precision, h // h_kv)(qh, kh, vh, qoff, koff)
    out = out.reshape(b, h, pad_q, d).transpose(0, 2, 1, 3)[:, :s_q]
    if not return_lse:
        return out
    lse = lse.reshape(b, h, pad_q).transpose(0, 2, 1)[:, :s_q]
    return out, lse


def _pallas_flash_bh(qh, kh, vh, qoff, koff, *, causal, block_q, block_k,
                     kv_len, interpret, precision=None, group=1):
    """The raw kernel launch, head-major [BH, S, D] → (out, lse[BH, S])."""
    bh_n, s_q, d = qh.shape
    s_k = kh.shape[1]
    grid = (bh_n, s_q // block_q, s_k // block_k)
    out, lse8 = _launch(qh, kh, vh, qoff, koff, grid=grid, causal=causal,
                        block_q=block_q, block_k=block_k, kv_len=kv_len,
                        interpret=interpret, precision=precision,
                        group=group)
    return out, lse8[:, 0, :]


def _launch(qh, kh, vh, qoff, koff, *, grid, causal, block_q, block_k,
            kv_len, interpret, precision=None, group=1):
    bh_n, s_q, d = qh.shape
    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=1.0 / (d ** 0.5),
        block_q=block_q, block_k=block_k, kv_len=kv_len,
        precision=precision)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q global offset
            pl.BlockSpec(memory_space=pltpu.SMEM),  # k global offset
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 8, block_q), lambda bh, iq, ik: (bh, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_n, s_q, d), qh.dtype),
            # lse rides an 8-sublane broadcast dim for TPU output tiling
            jax.ShapeDtypeStruct((bh_n, 8, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running norm l
            pltpu.VMEM((block_q, d), jnp.float32),    # weighted acc
        ],
        interpret=interpret,
    )(qoff, koff, qh, kh, vh)
