"""Pallas TPU kernels for the hot operators: flash attention, and the
selective scan of a Mamba-2 mixer in chunks (:func:`ssd_scan`, at the end
of the file: a forward and a backward kernel that keep a chunk's decays,
masked scores and the state carried between chunks in VMEM).

Flash attention in Pallas: tiled ``softmax(QKᵀ/√d)·V`` that never
materializes the full score matrix — Q/K/V tiles stream HBM→VMEM per
grid step, scores hit the MXU via ``dot_general(..,
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

**A tile does only what its position requires.** The offsets ride in
SMEM (scalar prefetch), and each (Q, K) tile is one of three classes,
decided by a scalar predicate from them (:func:`_tile_class`), so a
traced offset takes the same path as a static one:

* *dead* — no visible pair under the causal mask: no compute, and no
  DMA either, because the index maps clamp a dead step to the tile the
  neighbouring live step already holds;
* *crossing* — the diagonal passes through it, or it holds padded keys:
  the masked body (iota, compare, select, the no-key guard);
* *interior* — every pair visible: matrix products, max, exp and the
  accumulation, nothing else.

**What the shapes allow is decided from the shapes** (never by a
caller): the scale rides on the query rows, outside the kernels, when
it is a power of two (exact in any float type), the forward's row sum
rides in the spare MXU output columns of ``p @ v`` when the value head
is narrower than a lane tile, a value head may be narrower than the
query/key head (latent attention's 128 beside 192: every operand keeps
its own width, nothing is padded to the other's), the query heads that
share a K/V head are taken ``heads`` at a time in one grid step against
one K/V tile, and the tile shapes and the backward's dq span come from
:func:`tile_shapes`.

**The backward is ONE kernel** (:func:`_bwd_kernel`): on a live tile
the transposed score tile ``k qᵀ``, ``pᵀ = exp(sᵀ − lse)`` and ``dsᵀ =
pᵀ ∘ (v dOᵀ − δ + dlse)`` are formed once and give ``dv += pᵀ dO``,
``dk += dsᵀ q`` and ``dqᵀ += kᵀ dsᵀ``: five products a tile where a dq
and a dk/dv kernel ran seven, and no ``[block_q, block_k]`` plane is
ever transposed. The grid walks K tiles with Q tiles innermost; dk and
dv of a K tile sit in scratch while its Q tiles pass, and **dq of every
Q tile of a span of rows sits in float32 VMEM scratch while the K tiles
pass** and leaves once, as its last live K tile is done. The span is
derived (the whole padded sequence at every shape a cell runs; a
sequence whose dq outgrows VMEM is swept in several spans), never asked
for. Where a K/V head's dk and dv come in parts (more spans than one;
more rows of ``heads`` query heads than one) the parts leave in float32
and are summed outside, as the shared key's cotangent always is.

**Operands come as their producers wrote them.** A caller says which
axis its heads lie on (``layout``: the kernels work head-major, and a
head-major operand reaches them without a copy), and a key column block
that every head of a batch entry shares (latent attention's ONE rotated
key) is an operand of its own, ``k_shared``: each head's grid steps read
its tile in place through an index map, as grouped queries read a shared
K/V tile, and it joins the head's own key tile in VMEM. Nothing is
broadcast or concatenated in HBM; its cotangent comes back a head and is
summed outside the kernels.

**The forward kernel runs once under rematerialisation.** Its output and
row statistics carry the ``checkpoint_name`` :data:`SAVED` where they are
the backward kernel's residuals. A ``jax.checkpoint`` whose policy saves
that name (``zoo/decoder.py``'s does) keeps the two from the forward pass
and recomputes only what feeds the kernels; for every other caller the
name compiles to nothing.

CPU/tests run the same kernel with ``interpret=True`` (pure jax
semantics, no tiling constraints). Compiled, every block is a multiple
of the 128-lane tile: a sequence that is not is PADDED up to the next
block multiple and the padded keys are masked in-kernel, so an awkward
length (S=2047) costs one partial tile, never a whole-sequence block
that outgrows VMEM.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.obs import metrics as _metrics

__all__ = ["flash_attention", "tile_shapes", "tile_counts", "SAVED",
           "ssd_scan", "scan_tiles"]

# checkpoint_name of the forward kernel's output and row statistics, as the
# backward kernel takes them: a ``jax.checkpoint`` whose policy saves this
# name does not launch the forward kernel again to get them back
SAVED = "pallas.flash.saved"

_NEG_INF = -1e30  # finite -inf stand-in: exp(x - _NEG_INF) never NaNs
_LANES = 128
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T, which the MXU takes as it stands
_MAX_HEADS_A_STEP = 4
_TILE = 1024
# three quarters of the 128 MiB of VMEM a v5e / v6e core has, the chips
# tile_shapes was swept on; a core with less needs a smaller _TILE too
_VMEM_ASK_MAX = 96 << 20
# score-plane elements (heads x block_q x block_k) one grid step of the
# backward may form: at 4 Mi it runs 2.2 times slower (PERF.md §6, PR 39)
_BWD_PLANES = 2 << 20


class Tiles(NamedTuple):
    """What :func:`tile_shapes` derives: the forward's tile, the query
    heads of one K/V head taken in one grid step, the backward's Q block
    (its K block is the forward's) and the rows of Q whose dq the
    backward holds in VMEM while the K tiles pass."""
    block_q: int
    block_k: int
    heads: int
    bwd_block_q: int
    dq_span: int

    def count(self, pad_q, pad_k, causal, q_offset, k_offset, kv_len,
              head_dims):
        """Bump ``pallas.flash.*`` for one call of ``flash_attention``,
        which under ``jax.jit`` is once per TRACE of the caller's
        program, on purpose (as ``zoo.conv_bn.folded`` is): the shape
        chosen, the heads' widths ``(query/key, value, the key columns a
        shared key brings)`` and, where the offsets are known now, the
        tiles a head by class."""
        _metrics.counter("pallas.flash.launches").inc()
        if head_dims[2]:
            _metrics.counter("pallas.flash.shared_key").inc()
        _metrics.gauge("pallas.flash.block_q").set(self.block_q)
        _metrics.gauge("pallas.flash.block_k").set(self.block_k)
        _metrics.gauge("pallas.flash.heads_a_step").set(self.heads)
        _metrics.gauge("pallas.flash.bwd_block_q").set(self.bwd_block_q)
        _metrics.gauge("pallas.flash.dq_span").set(self.dq_span)
        _metrics.gauge("pallas.flash.dq_spans").set(pad_q // self.dq_span)
        _metrics.gauge("pallas.flash.head_dim_qk").set(head_dims[0])
        _metrics.gauge("pallas.flash.head_dim_v").set(head_dims[1])
        _metrics.gauge("pallas.flash.head_dim_shared").set(head_dims[2])
        try:
            q_offset, k_offset = int(q_offset), int(k_offset)
        except TypeError:   # a tracer (the ring's): known at run time only
            return
        for cls, n in tile_counts(pad_q, pad_k, self.block_q, self.block_k,
                                  causal=causal, q_offset=q_offset,
                                  k_offset=k_offset, kv_len=kv_len).items():
            _metrics.counter(f"pallas.flash.tiles.{cls}").inc(n)


# --- what the shapes decide ------------------------------------------------
def _fit_block(block: int, s: int, align: int) -> int:
    """A requested block over a length-``s`` axis: clipped to the
    (aligned) sequence, rounded down to ``align``."""
    return max(align, min(block, s + (-s % align)) // align * align)


def tile_shapes(s_q: int, s_k: int, group: int, head_dim: int,
                itemsize: int, *, block_q: int | None = None,
                block_k: int | None = None, align: int = _LANES) -> Tiles:
    """The shapes the kernels run at, from what the code can see. The
    forward's tile is 1,024 x 1,024 clipped to the (aligned) sequence
    (``block_q`` / ``block_k``: a caller's wish in their place, clipped
    the same way): the largest tile that stays in VMEM without spills
    was the fastest at every shape a caller runs (swept on the v5e at
    ``S`` = 8,192 and 2,048, ``head_dim`` 64 and 128, groups of 1 and 4,
    bfloat16 and float32: PERF.md §6, PR 31; 2,048 is twice as slow). A
    K/V head's query heads go up to four a grid step: one K/V fetch and
    one step's overhead for the four. **The backward's Q block is the
    forward's halved until the score planes of a grid step,** ``heads ×
    block_q × block_k``, **are at most** ``_BWD_PLANES`` (its K block is
    the forward's): 1,024 x 1,024 for one or two heads a step, 512 x
    1,024 for four (PERF.md §6, PR 39: at four heads of 1,024 x 1,024
    the one kernel runs 2.2 times slower, at 512 x 2,048 too; 512 x 512
    is within 1%, 256 x 1,024 7% slower, 2,048 x 1,024 at one head 8%).
    The rows of Q the backward holds dq for, from ``head_dim`` (the wider
    of the two widths) and the operands' ``itemsize``: :func:`_dq_span`."""
    heads = max(h for h in range(1, _MAX_HEADS_A_STEP + 1) if group % h == 0)
    block_q = _fit_block(_TILE if block_q is None else block_q, s_q, align)
    block_k = _fit_block(_TILE if block_k is None else block_k, s_k, align)
    bwd_q = block_q
    while heads * bwd_q * block_k > _BWD_PLANES and bwd_q % (2 * align) == 0:
        bwd_q //= 2
    pad_q = s_q + -s_q % block_q
    return Tiles(block_q, block_k, heads, bwd_q, _dq_span(
        pad_q // bwd_q, heads, bwd_q, block_k, head_dim, itemsize))


def _vmem_need(heads: int, block_q: int, block_k: int, d: int,
               item: int) -> int:
    """Bytes of VMEM a kernel's tile takes, whichever of the two: its
    blocks, double buffered; float32 scratch a tile; four score planes.
    ``d`` is the wider of the two head widths."""
    return (2 * item * d * (3 * heads * block_q + 4 * block_k)
            + 4 * heads * block_q * (3 * _LANES + d)
            + 4 * 4 * block_q * block_k)


def _dq_bytes(heads: int, rows: int, d: int) -> int:
    """``heads`` float32 accumulators of ``rows`` rows, held transposed:
    the rows along lanes, the width along sublanes."""
    return 4 * heads * rows * (d + -d % 8)


def _dq_span(q_tiles: int, heads: int, block_q: int, block_k: int, d: int,
             item: int) -> int:
    """The rows of Q the backward kernel holds dq for while the K tiles
    pass: as many whole Q blocks as ``_VMEM_ASK_MAX`` leaves room for
    beside twice the tile's own need (what :func:`_params` asks for),
    and a divisor of the padded sequence's ``q_tiles``. Every shape a
    cell runs fits whole (8,192 rows: 6.3 MB a head at 192 wide, 8.4 MB
    for four heads of 64, 16.8 MB of 128); a longer sequence is swept in
    several spans."""
    room = _VMEM_ASK_MAX - 2 * _vmem_need(heads, block_q, block_k, d, item)
    fit = max(1, room // _dq_bytes(heads, block_q, d))
    return block_q * max(n for n in range(1, q_tiles + 1)
                         if q_tiles % n == 0 and n <= fit)


def tile_counts(s_q: int, s_k: int, block_q: int, block_k: int, *,
                causal: bool, q_offset: int = 0, k_offset: int = 0,
                kv_len: int | None = None) -> dict:
    """Tiles a head by class, for static offsets: the same predicate as
    :func:`_tile_class`, in Python integers."""
    out = {"interior": 0, "crossing": 0, "dead": 0}
    for iq in range(-(-s_q // block_q)):
        for ik in range(-(-s_k // block_k)):
            live, interior = _tile_class(
                causal, kv_len, q_offset, k_offset, iq, ik, block_q,
                block_k)
            out["interior" if interior else
                "crossing" if live else "dead"] += 1
    return out


# --- the per-tile predicate and the index maps that follow from it ---------
def _tile_class(causal, kv_len, qoff, koff, iq, ik, block_q, block_k):
    """``(live, interior)`` of tile ``(iq, ik)``: *live* has a visible
    pair, *interior* has nothing but (no pair above the diagonal, no
    padded key). The ONE predicate of both kernels; Python bools
    where nothing depends on a position."""
    unpadded = True if kv_len is None else (ik + 1) * block_k <= kv_len
    if not causal:
        return True, unpadded
    q_lo, k_lo = qoff + iq * block_q, koff + ik * block_k
    live = k_lo <= q_lo + block_q - 1
    interior = k_lo + block_k - 1 <= q_lo
    return live, (interior & unpadded if kv_len is not None else interior)


def _by_class(live, interior, body):
    """Run ``body(masked=False)`` on an interior tile, ``body(True)`` on
    a crossing one, nothing on a dead one."""
    if interior is True:
        body(False)
        return
    pl.when(interior)(lambda: body(False))
    crossing = jnp.logical_not(interior)
    if live is not True:
        crossing = jnp.logical_and(live, crossing)
    pl.when(crossing)(lambda: body(True))


def _last_live_k(causal, qoff, koff, iq, ik, block_q, block_k):
    """The forward walks K tiles innermost: past the row's last live
    tile the index stays there, so a dead step fetches nothing."""
    if not causal:
        return ik
    reach = jnp.maximum(qoff - koff + (iq + 1) * block_q - 1, 0)
    return jnp.minimum(ik, jax.lax.div(reach, jnp.int32(block_k)))


def _dead_before(qoff, koff, ik, block_q, block_k):
    """Under the causal mask, the Q tiles before this one see nothing of
    K tile ``ik``, nor of any after it."""
    start = jnp.maximum(koff - qoff + ik * block_k, 0)
    return jax.lax.div(start, jnp.int32(block_q))


def _first_live_q(causal, qoff, koff, iq, ik, block_q, block_k, q_end):
    """The backward walks Q tiles innermost, up to ``q_end``: before the
    column's first live tile the index already points at it."""
    if not causal:
        return iq
    return jnp.maximum(iq, jnp.minimum(
        _dead_before(qoff, koff, ik, block_q, block_k), q_end - 1))


def _dq_done(causal, qoff, koff, ik, k_tiles, block_q, block_k, span,
             q_tiles):
    """``(first, end)``: of the Q tiles of sweep ``span``, ``q_tiles`` of
    them, ``[first, end)`` meet their LAST live K tile at ``ik`` (a tile
    no K tile is live for meets it at 0, and leaves as the zeros it was
    filled with) and those before ``first`` have met it before. Both
    rise with ``ik`` and ``end`` is the next ``first``, so in the order
    the grid walks, the tiles are completed in their own order: what lets
    ONE output block follow them."""
    lo, hi = span * q_tiles, (span + 1) * q_tiles
    if not causal:
        return lo, jnp.where(ik == k_tiles - 1, hi, lo)

    def dead_before(ik):
        return jnp.clip(_dead_before(qoff, koff, ik, block_q, block_k),
                        lo, hi)

    return (jnp.where(ik == 0, lo, dead_before(ik)),
            jnp.where(ik == k_tiles - 1, hi, dead_before(ik + 1)))


def _mask(s, qoff, koff, iq, ik, *, causal, kv_len, block_q, block_k,
          q_axis):
    """The global-position causal mask and the padded keys' (by LOCAL
    index, ``kv_len`` static) on a score tile whose query rows lie along
    ``q_axis``: a crossing tile's work."""
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                    1 - q_axis)
    if causal:
        q_pos = (qoff + iq * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
        s = jnp.where(q_pos >= koff + k_idx, s, _NEG_INF)
    if kv_len is not None:
        s = jnp.where(k_idx < kv_len, s, _NEG_INF)
    return s


def _scaled(x, scale):
    """``x · scale``; ``scale`` is None where the query rows came in
    scaled (:func:`_scale_rides_on_q`)."""
    return x if scale is None else x * scale


def _scale_rides_on_q(head_dim: int) -> bool:
    """``1/√d`` a power of two (d = 16, 64, 256): multiplying the query
    rows by it changes an exponent and no mantissa bit, in any float
    type, so it is done once outside the kernels (where XLA folds it
    into whatever produced the rows) and dq, dk follow by the chain
    rule. Any other scale is applied to the float32 scores."""
    return math.frexp(head_dim ** -0.5)[0] == 0.5


def _dot(a, b, dims, precision):
    # operands go to the MXU in their own dtype (bf16 stays one pass);
    # the product is accumulated in float32
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _key_tile(k_ref):
    """The K tile of a grid step. With a shared key the operand is the
    pair (the head's own columns, the batch entry's shared ones): the two
    tiles side by side, joined in VMEM (the first ends on a lane-tile
    boundary)."""
    if isinstance(k_ref, tuple):
        return jnp.concatenate([ref[0] for ref in k_ref], axis=-1)
    return k_ref[0]


# --- kernels ---------------------------------------------------------------
def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, acc_scr, side_scr, *, causal: bool, scale,
                  block_q: int, block_k: int, kv_len, precision,
                  heads: int, head_dim: int):
    """Online softmax over the K tiles of one Q tile, for ``heads`` query
    heads against the one K/V tile (``head_dim`` is the VALUE head's
    width, the accumulator's; the score product contracts over whatever
    width ``q`` and ``k`` have). With a value head narrower than its lane
    tile the accumulator has spare columns: ``side_scr`` then holds V
    beside columns of ones, and the row sum comes out of the product
    ``p @ [V | 1]`` that was needed anyway (``acc[:, head_dim]``: the
    same MXU passes, no cross-lane sum, and the normaliser adds the
    weights the numerator saw: with bfloat16 operands the returned lse
    is the log of the sum of the bfloat16-rounded weights, 1e-3 from the
    float32 sum's and what the backward and the ring's merge divide by;
    with float32 operands it is the float32 sum). Otherwise ``side_scr``
    is the running sum itself."""
    iq, ik, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    sum_on_mxu = acc_scr.shape[-1] > head_dim

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if sum_on_mxu:
            side_scr[...] = jnp.ones_like(side_scr)
        else:
            side_scr[...] = jnp.zeros_like(side_scr)

    qoff, koff = qoff_ref[0], koff_ref[0]
    live, interior = _tile_class(causal, kv_len, qoff, koff, iq, ik,
                                 block_q, block_k)

    def body(masked):
        k = _key_tile(k_ref)
        if sum_on_mxu:
            side_scr[:, :head_dim] = v_ref[0]
            v = side_scr[...]
        else:
            v = v_ref[0]
        for h in range(heads):
            s = _scaled(_dot(q_ref[0, h], k, _NT, precision), scale)
            if masked:
                s = _mask(s, qoff, koff, iq, ik, causal=causal,
                          kv_len=kv_len, block_q=block_q, block_k=block_k,
                          q_axis=0)
            m_prev = m_scr[h][:, :1]                        # [TQ, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked:
                # a row with NO visible key yet has m_new == _NEG_INF
                # and exp(0)==1 for every masked entry; zero it so l
                # stays 0 and finalize reports the row as fully masked,
                # not mean(V)
                p = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, p)
            if not sum_on_mxu:
                side_scr[h] = (side_scr[h] * corr
                               + p.sum(axis=1, keepdims=True))
            acc_scr[h] = acc_scr[h] * corr + _dot(p.astype(v.dtype), v,
                                                  _NN, precision)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    _by_class(live, interior, body)

    @pl.when(ik == nk - 1)
    def _finalize():
        for h in range(heads):
            acc = acc_scr[h]
            l = (acc[:, head_dim:head_dim + 1] if sum_on_mxu
                 else side_scr[h][:, :1])
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc[:, :head_dim] / safe_l).astype(o_ref.dtype)
            # lse = m + log(l); fully-masked rows (l==0) get
            # -inf-equivalent. The row vector is broadcast over an
            # 8-sublane dim purely to satisfy the TPU (8, 128)
            # output-tile rule; callers read row 0.
            lse = jnp.where(l == 0.0, _NEG_INF,
                            m_scr[h][:, :1] + jnp.log(safe_l))
            lse_ref[0, h] = jnp.broadcast_to(lse[:, 0][None, :],
                                             lse_ref.shape[2:])


def _bwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dlt_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                causal: bool, scale, block_q: int, block_k: int, kv_len,
                precision, heads: int, q_tiles: int):
    """dq = Σ_k ds K · scale, dk = Σ_q dsᵀ Q · scale, dv = Σ_q pᵀ dO with
    ``p = exp(s − lse)`` rebuilt from the saved log-sum-exp and ``ds = p ⊙
    (dO Vᵀ − δ + dlse)``, δ = rowsum(dO ⊙ O): every live tile's ``p`` and
    ``ds`` are formed ONCE and give all three, five products a tile.

    The grid walks K tiles with the Q tiles of a span innermost, on the
    TRANSPOSED score tile ``K Qᵀ``: ``lse`` and δ already lie along
    lanes, and ``pᵀ`` / ``dsᵀ`` are the left operands of dv's and dk's
    products as they stand and the RIGHT operand of dq's, which is formed
    transposed, ``dqᵀ += Kᵀ dsᵀ`` (no ``[block_q, block_k]`` plane is
    ever transposed; the K tile is, ``head_dim`` rows of it). dk and dv
    of a K tile are summed over the Q tiles (and the ``heads`` query
    heads of the step) in float32 scratch and leave when its last Q tile
    is done. **dqᵀ of every Q tile of the span stays in float32 scratch
    while the K tiles pass**: filled with zeros at the first, and turned
    and written out once, at the Q tile's last live K tile
    (:func:`_dq_done`; the output's index map holds still until then).
    The scale is applied once, to the float32 sums. With a shared key
    ``k_ref`` and ``dk_ref`` are pairs: ``dk`` is accumulated whole and
    its two column blocks leave by their own outputs."""
    span, ik, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    iq = span * q_tiles + j

    @pl.when(j == 0)
    def _a_k_tile_begins():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(ik == 0)
    def _a_q_tile_begins():
        dq_scr[j] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    qoff, koff = qoff_ref[0], koff_ref[0]
    live, interior = _tile_class(causal, kv_len, qoff, koff, iq, ik,
                                 block_q, block_k)

    def body(masked):
        k, v = _key_tile(k_ref), v_ref[0]
        kt = k.T                                            # [D, TK]
        for h in range(heads):
            q, do = q_ref[0, h], do_ref[0, h]
            st = _scaled(_dot(k, q, _NT, precision), scale)  # [TK, TQ]
            lse = lse_ref[0, h, :1, :]                      # [1, TQ]
            if masked:
                st = _mask(st, qoff, koff, iq, ik, causal=causal,
                           kv_len=kv_len, block_q=block_q,
                           block_k=block_k, q_axis=1)
            pt = jnp.exp(st - lse)
            if masked:
                # rows that saw no key at all: exp(-inf - -inf) is 1
                pt = pt * (lse > _NEG_INF * 0.5).astype(jnp.float32)
            dv_scr[...] += _dot(pt.astype(do.dtype), do, _NN, precision)
            dpt = _dot(v, do, _NT, precision)
            dst = (pt * (dpt - dlt_ref[0, h, :1, :])).astype(q.dtype)
            dk_scr[...] += _dot(dst, q, _NN, precision)
            dq_scr[j, h] += _dot(kt, dst, _NN, precision)    # [D, TQ]

    _by_class(live, interior, body)

    @pl.when(j == q_tiles - 1)
    def _a_k_tile_ends():
        dk = _scaled(dk_scr[...], scale)
        if isinstance(dk_ref, tuple):   # (the head's own, the shared key's)
            own = dk_ref[0].shape[-1]
            dk_ref[0][0] = dk[:, :own].astype(dk_ref[0].dtype)
            dk_ref[1][0] = dk[:, own:].astype(dk_ref[1].dtype)
        else:
            dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    first, end = _dq_done(causal, qoff, koff, ik, pl.num_programs(2),
                          block_q, block_k, span, q_tiles)

    @pl.when(jnp.logical_and(iq >= first, iq < end))
    def _a_q_tile_ends():
        for h in range(heads):
            dq_ref[0, h] = _scaled(dq_scr[j, h].T, scale).astype(
                dq_ref.dtype)


# --- launches --------------------------------------------------------------
def _params(semantics: tuple, need: int, held: int = 0):
    """Mosaic's scoped-VMEM default is 16 MiB; ask for twice what
    :func:`_vmem_need` counts when that is more (the count is of the
    operands, not of what the compiler adds), and for all of ``held``,
    the backward's dq accumulator, whose size is exact: up to
    ``_VMEM_ASK_MAX``."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=(None if need + held <= 12 << 20
                          else min(2 * need + held, _VMEM_ASK_MAX)))


def _q_major_maps(causal, block_q, block_k, per_kv):
    """Index maps of the forward's grid ``(row, iq, ik)``: the Q
    tile (and what is shaped like it), the row vectors beside it, and
    the K/V tile of the ``per_kv`` rows that share a K/V head."""
    def q_map(r, iq, ik, *_):
        return (r, 0, iq, 0)

    def row_map(r, iq, ik, *_):
        return (r, 0, 0, iq)

    def k_map(r, iq, ik, qoff_ref, koff_ref):
        return (r // per_kv,
                _last_live_k(causal, qoff_ref[0], koff_ref[0], iq, ik,
                             block_q, block_k), 0)

    return q_map, row_map, k_map


def _key_specs(kh, block_k, k_map):
    """Block specs of the key operand (or its cotangent) under ``k_map``,
    a map to ``(K/V row, K tile, 0)``. With a shared key ``kh`` is the
    pair ``([B·Hkv, Sk, D − Ds], [B, Sk, Ds])`` and so are the specs: the
    shared tile is the batch entry's, row ``// Hkv``, read in place by
    every head. ``Hkv`` is the ratio of the pair's own row counts: 1 for
    the cotangents, which come back a head."""
    if not isinstance(kh, tuple):
        return pl.BlockSpec((1, block_k, kh.shape[2]), k_map)
    h_kv = kh[0].shape[0] // kh[1].shape[0]

    def shared_map(*at):
        row, ik, _ = k_map(*at)
        return (row // h_kv, ik, 0)

    return (pl.BlockSpec((1, block_k, kh[0].shape[2]), k_map),
            pl.BlockSpec((1, block_k, kh[1].shape[2]), shared_map))


def _pallas_flash_fwd(qg, kh, vh, qoff, koff, *, causal, tiles: Tiles,
                      kv_len, interpret, precision, group, scale):
    """The forward launch. Head-major: ``qg`` ``[R, heads, Sq, D]`` (the
    ``heads`` query heads one grid step takes are neighbours), ``kh``
    ``[B·Hkv, Sk, D]`` or, with a shared key, the pair ``([B·Hkv, Sk, D −
    Ds], [B, Sk, Ds])``, ``vh`` ``[B·Hkv, Sk, Dv]`` → (out ``[R, heads,
    Sq, Dv]``, lse ``[R, heads, Sq]``)."""
    rows, heads, s_q, d = qg.shape
    s_k, d_v = vh.shape[1:]
    block_q, block_k = tiles.block_q, tiles.block_k
    acc_w = d_v + (-d_v % _LANES)
    q_map, row_map, k_map = _q_major_maps(causal, block_q, block_k,
                                          group // heads)
    side = (pltpu.VMEM((block_k, acc_w), vh.dtype) if acc_w > d_v
            else pltpu.VMEM((heads, block_q, _LANES), jnp.float32))
    out, lse8 = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
            precision=precision, heads=heads, head_dim=d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,     # the q and k global offsets
            grid=(rows, s_q // block_q, s_k // block_k),
            in_specs=[
                pl.BlockSpec((1, heads, block_q, d), q_map),
                _key_specs(kh, block_k, k_map),
                pl.BlockSpec((1, block_k, d_v), k_map),
            ],
            out_specs=[
                pl.BlockSpec((1, heads, block_q, d_v), q_map),
                pl.BlockSpec((1, heads, 8, block_q), row_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),  # max
                pltpu.VMEM((heads, block_q, acc_w), jnp.float32),   # acc
                side,
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads, s_q, d_v), qg.dtype),
            # lse rides an 8-sublane broadcast dim for TPU output tiling
            jax.ShapeDtypeStruct((rows, heads, 8, s_q), jnp.float32),
        ],
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary"),
            _vmem_need(heads, block_q, block_k, max(d, d_v),
                       qg.dtype.itemsize)),
        interpret=interpret,
    )(qoff, koff, qg, kh, vh)
    return out, lse8[:, :, 0, :]


def _pallas_flash_bwd(qg, kh, vh, out, lse, qoff, koff, do, dlse, *,
                      causal, tiles: Tiles, kv_len, interpret, precision,
                      group, scale):
    """Tiled flash backward, ONE launch: (dq, dk, dv) without any S²
    tensor, every live score tile formed once (:func:`_bwd_kernel`).

    The lse cotangent folds in analytically: ∂lse_i/∂s_ij = p_ij, so the
    score gradient is ds = p ⊙ (dOVᵀ − δ + dlse) with δ = rowsum(dO ⊙
    O): the δ and dlse terms combine into one per-row constant. ``group``
    query rows share each K/V row (row ``bh`` reads K/V row ``bh //
    group``).

    Grid ``(row of heads, dq span, K tile, Q tile of the span)``. A row's
    dk and dv are one PART of its K/V head's: the head has ``group //
    heads`` rows, and a sequence longer than ``tiles.dq_span`` is swept a
    span of Q rows at a time against all K tiles. Where a K/V head has
    more parts than one they leave the kernel in float32 and are summed
    here, as the shared key's cotangent, a head, always is."""
    rows, heads, s_q, d = qg.shape
    s_k, d_v = vh.shape[1:]
    block_q, block_k = tiles.bwd_block_q, tiles.block_k
    per_kv, spans = group // heads, s_q // tiles.dq_span
    q_tiles, k_tiles = tiles.dq_span // block_q, s_k // block_k
    # per-row constant: −δ + dlse, folded so the kernel needs ONE vector
    dlt = (jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
           - dlse.astype(jnp.float32))
    # broadcast row vectors over an 8-sublane dim (TPU input tiling)
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (rows, heads, 8, s_q))
    dlt8 = jnp.broadcast_to(dlt[:, :, None, :], (rows, heads, 8, s_q))

    def q_tile(span, ik, j, qoff_ref, koff_ref):
        return _first_live_q(causal, qoff_ref[0], koff_ref[0],
                             span * q_tiles + j, ik, block_q, block_k,
                             (span + 1) * q_tiles)

    def q_map(r, *at):
        return (r, 0, q_tile(*at), 0)

    def row_map(r, *at):
        return (r, 0, 0, q_tile(*at))

    def k_map(r, span, ik, j, *_):
        return (r // per_kv, ik, 0)

    def part_map(r, span, ik, j, *_):
        return (r * spans + span, ik, 0)

    def dq_map(r, span, ik, j, qoff_ref, koff_ref):
        # the next Q tile to be completed: the block stays in VMEM, not
        # written, until the step that completes it, and moves on then
        first, end = _dq_done(causal, qoff_ref[0], koff_ref[0], ik, k_tiles,
                              block_q, block_k, span, q_tiles)
        return (r, 0, jnp.minimum(jnp.clip(span * q_tiles + j, first, end),
                                  (span + 1) * q_tiles - 1), 0)

    # a K/V head's cotangents in parts (and the shared key's a head): in
    # float32 where they are summed here
    part = jnp.float32 if per_kv * spans > 1 else None
    dk_shape, dv_shape = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((rows * spans, s_k, x.shape[2]),
                                       part or x.dtype), (kh, vh))
    need = _vmem_need(heads, block_q, block_k, max(d, d_v),
                      qg.dtype.itemsize)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, kv_len=kv_len, precision=precision,
            heads=heads, q_tiles=q_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, spans, k_tiles, q_tiles),
            in_specs=[
                pl.BlockSpec((1, heads, block_q, d), q_map),
                _key_specs(kh, block_k, k_map),
                pl.BlockSpec((1, block_k, d_v), k_map),
                pl.BlockSpec((1, heads, block_q, d_v), q_map),
                pl.BlockSpec((1, heads, 8, block_q), row_map),
                pl.BlockSpec((1, heads, 8, block_q), row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, heads, block_q, d), dq_map),
                _key_specs(dk_shape, block_k, part_map),
                pl.BlockSpec((1, block_k, d_v), part_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((q_tiles, heads, d, block_q), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d_v), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct(qg.shape, qg.dtype), dk_shape,
                   dv_shape],
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary", "arbitrary"), need,
            _dq_bytes(heads, tiles.dq_span, d)),
        interpret=interpret,
    )(qoff, koff, qg, kh, vh, do, lse8, dlt8)

    def summed(x, like):
        if x.shape[0] == like.shape[0]:
            return x
        return x.reshape(like.shape[0], -1, *x.shape[1:]).sum(1).astype(
            like.dtype)

    return dq, jax.tree.map(summed, dk, kh), summed(dv, vh)


@functools.lru_cache(maxsize=32)
def _flash_fn(causal: bool, tiles: Tiles, kv_len, interpret: bool,
              precision, group: int, scale):
    """One custom-VJP'd head-major flash fn per static config: forward
    AND backward are Pallas kernels (pallas_call has no generic
    autodiff), so neither direction materializes an S² tensor. The
    forward's two outputs carry the ``checkpoint_name`` ``SAVED`` where
    they are the backward's residuals: inside the ``fwd`` rule, because a
    name put on the public output is a value AFTER the residual and a
    policy that saves it would still recompute the kernel."""
    kw = dict(causal=causal, tiles=tiles, kv_len=kv_len,
              interpret=interpret, precision=precision, group=group,
              scale=scale)

    def fwd_impl(qg, kh, vh, qoff, koff):
        return _pallas_flash_fwd(qg, kh, vh, qoff, koff, **kw)

    f = jax.custom_vjp(fwd_impl)

    def fwd(qg, kh, vh, qoff, koff):
        out, lse = (checkpoint_name(x, SAVED)
                    for x in fwd_impl(qg, kh, vh, qoff, koff))
        return (out, lse), (qg, kh, vh, out, lse, qoff, koff)

    def bwd(res, cots):
        qg, kh, vh, out, lse, qoff, koff = res
        do, dlse = cots
        dq, dk, dv = _pallas_flash_bwd(qg, kh, vh, out, lse, qoff, koff,
                                       do, dlse, **kw)
        return dq, dk, dv, None, None

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, *, causal: bool = False, q_offset=0,
                    k_offset=0, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    return_lse: bool = False, precision=None,
                    layout: str = "bshd", k_shared=None):
    """Tiled flash attention. q: [B, Sq, H, D], k: [B, Sk, Hkv, D], v:
    [B, Sk, Hkv, Dv] → out [B, Sq, H, Dv] (and, with ``return_lse``, lse
    [B, Sq, H] — ``logsumexp(scores)`` per query row, for ring partial
    merges). ``Dv`` may differ from ``D`` (latent attention: 192-wide
    queries and keys, 128-wide values); the scale is ``1/√D``.

    ``layout`` says what the operands ARE: ``"bshd"`` as above, or
    ``"bhsd"`` for q ``[B, H, Sq, D]``, k ``[B, Hkv, Sk, D]``, v ``[B,
    Hkv, Sk, Dv]`` → out ``[B, H, Sq, Dv]``, lse ``[B, H, Sq]``. The
    kernels work head-major: a ``"bhsd"`` operand reaches them as it
    stands, a ``"bshd"`` one through a transposed copy each way.

    ``k_shared`` ``[B, Sk, Ds]`` is a block of key columns that every head
    of a batch entry shares (latent attention's ONE rotated key): ``k``
    then holds the first ``D − Ds`` columns a head and a score is ``q[:D −
    Ds]·k + q[D − Ds:]·k_shared``, scaled by ``1/√D``. The kernels read
    its tiles in place (never broadcast over heads, never concatenated in
    HBM), and its cotangent is the sum over the heads.

    Grouped queries: ``Hkv`` divides ``H`` and K/V head ``j`` serves the
    query heads ``j·H/Hkv … (j+1)·H/Hkv − 1``. The kernels read the
    shared K/V tiles in place (no repeated copy), and dk/dv come back
    with ``Hkv`` heads, summed over each group in float32.

    Under a gradient ONE backward kernel runs: every live score tile is
    formed once and gives dq, dk and dv; dq of a span of Q rows (the
    whole padded sequence wherever it fits VMEM: ``tile_shapes(...)
    .dq_span``) is held in float32 scratch while the K tiles pass and
    written once. Nothing about it is asked for: tile, span and the
    parts a K/V head's cotangents are summed from follow from the shapes.

    ``q_offset``/``k_offset`` are the blocks' GLOBAL sequence positions
    for causal masking; they may be traced values (each ring device
    passes its rotating source position). ``block_q`` / ``block_k`` are
    None for the shape :func:`tile_shapes` derives; a value (tests that
    want many small tiles; the ring's shard block) is advisory: a
    compiled block is a multiple of the 128-lane tile, and a sequence
    that is not a block multiple is zero-padded up to one (pad keys
    masked in-kernel, pad query rows dropped), so any length works with
    a bounded VMEM footprint.

    ``interpret=None`` picks from the process's default backend: the
    compiled Mosaic kernel on TPU, the Pallas interpreter anywhere else
    (Mosaic has no other target).

    Counts ``pallas.flash.*`` once per call, which under ``jax.jit`` is
    once per TRACE of the caller's program (the gauges ``.dq_span`` and
    ``.dq_spans`` say what the backward holds and in how many sweeps)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"layout {layout!r} is neither 'bshd' nor 'bhsd'")
    seq = layout.index("s")
    s_q, h, d = q.shape[seq], q.shape[3 - seq], q.shape[3]
    s_k, h_kv = k.shape[seq], k.shape[3 - seq]
    if h % h_kv or v.shape[3 - seq] != h_kv:
        raise ValueError(f"{h} query heads cannot share {h_kv} key / "
                         f"{v.shape[3 - seq]} value heads")
    d_shared = 0 if k_shared is None else k_shared.shape[2]
    if k_shared is not None and k_shared.shape[:2] != (k.shape[0], s_k):
        raise ValueError(f"a shared key of shape {k_shared.shape} beside "
                         f"{k.shape[0]} x {s_k} keys a head")
    if k.shape[3] + d_shared != d:
        raise ValueError(f"queries {d} wide against keys "
                         f"{k.shape[3] + d_shared} wide")
    group, align = h // h_kv, 1 if interpret else _LANES
    tiles = tile_shapes(s_q, s_k, group, max(d, v.shape[3]),
                        q.dtype.itemsize, block_q=block_q, block_k=block_k,
                        align=align)
    pad_q, pad_k = s_q + (-s_q % tiles.block_q), s_k + (-s_k % tiles.block_k)
    kv_len = s_k if pad_k != s_k else None
    tiles.count(pad_q, pad_k, causal, q_offset, k_offset, kv_len,
                (d, v.shape[3], d_shared))
    return _flash_call(q, k, v, k_shared, q_offset, k_offset, causal=causal,
                       tiles=tiles, pads=(pad_q, pad_k), layout=layout,
                       interpret=interpret, return_lse=return_lse,
                       precision=precision)


def _flash_traced(q, k, v, k_shared, q_offset, k_offset, *, causal, tiles,
                  pads, layout, interpret, return_lse, precision):
    seq = layout.index("s")
    b, s_q, h, d = q.shape[0], q.shape[seq], q.shape[3 - seq], q.shape[3]
    s_k, h_kv = k.shape[seq], k.shape[3 - seq]
    pad_q, pad_k = pads

    # head-major: K/V [B·Hkv, Sk, D], a row a (batch, head) pair; Q
    # [B·H/heads, heads, Sq, D], a row the heads one grid step takes. Of
    # a "bhsd" operand that is a view
    def to_bh(x, padded):
        widths = [(0, 0)] * 4
        widths[seq] = (0, padded - x.shape[seq])
        x = jnp.pad(x, widths)
        if seq == 1:
            x = x.transpose(0, 2, 1, 3)
        return x.reshape(-1, padded, x.shape[3])

    scale = d ** -0.5
    if _scale_rides_on_q(d):
        q, scale = q * jnp.asarray(scale, q.dtype), None
    qg = to_bh(q, pad_q).reshape(-1, tiles.heads, pad_q, d)
    kh, vh = to_bh(k, pad_k), to_bh(v, pad_k)
    if k_shared is not None:
        kh = (kh, jnp.pad(k_shared, ((0, 0), (0, pad_k - s_k), (0, 0))))
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    out, lse = _flash_fn(causal, tiles, s_k if pad_k != s_k else None,
                         interpret, precision, h // h_kv,
                         scale)(qg, kh, vh, qoff, koff)
    out = out.reshape(b, h, pad_q, -1)
    out = (out.transpose(0, 2, 1, 3)[:, :s_q] if seq == 1
           else out[:, :, :s_q])
    if not return_lse:
        return out
    lse = lse.reshape(b, h, pad_q)
    lse = lse.transpose(0, 2, 1)[:, :s_q] if seq == 1 else lse[:, :, :s_q]
    return out, lse


# the traced name is what device traces file these kernels under
# (``jit(flash_attention)/pallas_call``, the ``flash_attention.N`` rows)
_flash_traced.__name__ = _flash_traced.__qualname__ = "flash_attention"
_flash_call = jax.jit(_flash_traced, static_argnames=(
    "causal", "tiles", "pads", "layout", "interpret", "return_lse",
    "precision"))


# === the selective scan in chunks ==========================================
# No checkpoint name here: the caller (``lm_blocks.mamba2_op``)
# rematerialises a whole mixer a sequence at a time, so the chunk states the
# forward saves for the backward live for one sequence's turn only.
# positions a grid step holds, whole chunks (512, 1,024 and 2,048 ran equal to
# 0.5% on the v5e at the hybrid cell's shape: PERF.md §6, PR 38)
_SCAN_ROWS = 1024


class ScanTiles(NamedTuple):
    """What the scan kernels' shapes are derived to: the chunk, the chunks
    one grid step holds (a divisor of the sequence's chunks, at most
    ``_SCAN_ROWS`` positions), and the heads that share one lane tile (a
    64-wide head is half a tile: two heads' columns are worked on as ONE
    128-lane block, each taking its own lanes of a product the MXU makes
    128 wide either way)."""
    chunk: int
    chunks_a_step: int
    heads_a_tile: int


def scan_tiles(s: int, per: int, width: int, chunk: int) -> ScanTiles:
    """From the shapes alone: ``s`` positions, ``per`` heads a group of
    ``width`` columns each."""
    chunks = -(-s // chunk)
    most = max(1, _SCAN_ROWS // chunk)
    a_step = max(n for n in range(1, most + 1) if chunks % n == 0)
    fill = _LANES // width if width < _LANES and _LANES % width == 0 else 1
    return ScanTiles(chunk, a_step, fill if per % fill == 0 else 1)


def _held(v, dtype):
    """``v`` as a value HELD in ``dtype``: rounded to it, and float32 again
    for the arithmetic (the VPU's own type; nothing when ``dtype`` is)."""
    return v if dtype == jnp.float32 else v.astype(dtype).astype(jnp.float32)


def _running_sum(x, reverse: bool = False):
    """Inclusive running sum along the lanes of ``[R, Q]`` (from the far
    end with ``reverse``): a shift-and-add ladder of ⌈log₂ Q⌉ float32
    steps on the VPU. A product with a triangle of ones would round the
    log-decays to the MXU's default bfloat16 operands."""
    q = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < q:
        if reverse:
            x = x + jnp.where(lane < q - shift,
                              pltpu.roll(x, q - shift, 1), 0.0)
        else:
            x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0.0)
        shift *= 2
    return x


# What a chunk's decays are staged as, once a grid step for all its chunks:
# with heads along sublanes, ``[R, rows]`` (ONE vector register a quantity
# and chunk at the cell's shape: everything is computed there), and, for
# what has to be spread over a head's lanes, turned to ``[rows, R]``.
# ``run`` is Σ_{s<=i} Δ_s A inside the chunk, ``grow`` exp(run) (what an
# entering state keeps; its last position is the chunk's whole decay),
# ``to_end`` exp(run_last − run), ``pull`` Δ ∘ to_end.
_BY_ROW = ("run", "grow", "to_end")
_BY_COL = ("dt", "run", "grow", "pull")


def _stage_decays(dt_ref, a, held, chunk: int, chunks: int, by_row, by_col):
    """The decays of every chunk of the grid step into VMEM scratch
    (``by_row`` ``[3, R, rows]``, ``by_col`` ``[4, rows, R]``), before the
    loop over the chunks. A chunk's are a chain of small dependent steps
    (a ladder of seven shifts, two exponentials, four ``[R, Q]``
    transposes) that occupies no unit and waits on every one: inside the
    loop it cost 0.9 µs a turn, 45% of the forward; here the chunks'
    chains are independent and overlap (PERF.md §6, PR 38). Every decay is
    a value held in the scan's dtype, the running sum float32
    arithmetic."""
    for j in range(chunks):
        at = slice(j * chunk, (j + 1) * chunk)
        dt = dt_ref[0, :, at]                                # [R, Q]
        run = held(_running_sum(held(held(dt) * a)))
        grow = held(jnp.exp(run))
        to_end = held(jnp.exp(held(run[:, -1:] - run)))
        for i, rows in enumerate((run, grow, to_end)):
            by_row[i, :, at] = rows
        for i, rows in enumerate((dt, run, grow, dt * to_end)):
            by_col[i, at, :] = rows.T


class _Lanes:
    """The lanes of one tile of ``fill`` heads, ``width`` columns each: the
    masks are built ONCE a kernel, outside its loops."""

    def __init__(self, rows: int, fill: int, width: int):
        self.fill, self.width = fill, width
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, fill * width), 1)
        self.upto = [lane < (k + 1) * width for k in range(fill)]
        self.own = [m if k == 0 else m & ~self.upto[k - 1]
                    for k, m in enumerate(self.upto)]

    def take(self, parts):
        """``[rows, fill · width]`` taking head ``k``'s lanes from
        ``parts[k]``."""
        out = parts[-1]
        for k in reversed(range(self.fill - 1)):
            out = jnp.where(self.upto[k][:out.shape[0]], parts[k], out)
        return out

    def over(self, v, tile: int):
        """Column ``tile · fill + k`` of ``v`` ``[rows, R]`` over the
        lanes of head ``k`` of the tile."""
        shape = (v.shape[0], self.fill * self.width)
        at = tile * self.fill
        return self.take([jnp.broadcast_to(v[:, at + k:at + k + 1], shape)
                          for k in range(self.fill)])

    def row_over(self, v, tile: int):
        """The same of ONE row ``[1, R]``. Its last select is kept where
        nothing is left to choose: a lone ``[1, 1]`` spread over lanes and
        then over rows folds, in Mosaic's canonical form, into one
        broadcast along both axes, which it does not lower."""
        shape = (1, self.fill * self.width)
        out = jnp.zeros(shape, v.dtype)
        for k in reversed(range(self.fill)):
            col = v[:, tile * self.fill + k:tile * self.fill + k + 1]
            out = jnp.where(self.upto[k][:1], jnp.broadcast_to(col, shape),
                            out)
        return out

    def only(self, v, k: int):
        """``v`` with every lane but head ``k``'s at zero."""
        return v if self.fill == 1 else jnp.where(
            self.own[k][:v.shape[0]], v, jnp.zeros_like(v))


def _causal(chunk: int, transposed: bool = False):
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return row <= col if transposed else row >= col


def _scan_fwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, y_ref, *rest,
                     tiles: ScanTiles, width: int, held_in):
    """A grid step is ``chunks_a_step`` chunks of one group's heads; the
    state ``[N, R · P]`` is carried from chunk to chunk, and from step to
    step, in VMEM scratch (zero before the first). Before the loop, every
    chunk's running sum of Δ·A and its decays (:func:`_stage_decays`). A
    chunk: ``C Bᵀ`` once for the group; a head's ``L`` and ``(C Bᵀ ∘ L ∘
    Δ) x`` (Δ rides on the masked scores' columns: ``x`` goes to the MXU
    as it came); what the entering state adds, ``exp(run) ∘ C H``, as one
    product a lane tile; the state's update ``exp(run_last) H + Bᵀ (Δ ∘
    exp(run_last − run) ∘ x)``. With ``states_ref`` the state ENTERING
    each chunk is written out, in the operands' dtype, as the products
    read it: the backward's residual."""
    *states_ref, h_scr, by_row, by_col = rest
    chunk, fill = tiles.chunk, tiles.heads_a_tile
    wide, dtype, f32 = fill * width, x_ref.dtype, jnp.float32
    held = functools.partial(_held, dtype=held_in)
    a = a_ref[0].astype(f32)                                 # [R, 1]
    lanes, seen = _Lanes(chunk, fill, width), _causal(chunk)
    row, col = _BY_ROW.index, _BY_COL.index

    @pl.when(pl.program_id(1) == 0)
    def _first():
        h_scr[...] = jnp.zeros_like(h_scr)

    _stage_decays(dt_ref, a, held, chunk, tiles.chunks_a_step, by_row,
                  by_col)

    def a_chunk(j, carry):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        dt_rows, run_rows = dt_ref[0, :, at], by_row[row("run"), :, at]
        run, grow = by_col[col("run"), at, :], by_col[col("grow"), at, :]
        b, c = b_ref[at, :], c_ref[at, :]
        b_t = b.T
        if states_ref:
            states_ref[0][j, 0] = h_scr[...].astype(dtype)
        scores = _dot(c, b, _NT, None)                       # [Q, Q]
        for tile in range(a.shape[0] // fill):
            cols = slice(tile * wide, (tile + 1) * wide)
            x = x_ref[at, cols]
            inside = []
            for k in range(fill):
                r = tile * fill + k
                decay = held(jnp.exp(jnp.where(seen, held(
                    run[:, r:r + 1] - run_rows[r:r + 1, :]), _NEG_INF)))
                inside.append(_dot(
                    (scores * decay * dt_rows[r:r + 1, :]).astype(dtype),
                    x, _NN, None))
            h = h_scr[:, cols]
            y_ref[at, cols] = lanes.take(inside) + lanes.over(
                grow, tile) * _dot(c, h.astype(dtype), _NN, None)
            pull = lanes.over(by_col[col("pull"), at, :], tile)
            ends = _dot(b_t, (pull * x.astype(f32)).astype(dtype), _NN,
                        None)                                # [N, wide]
            # the chunk's whole decay is the last row of what a state keeps
            h_scr[:, cols] = held(lanes.row_over(grow[-1:, :], tile) * h
                                  + held(ends))
        return carry

    jax.lax.fori_loop(0, tiles.chunks_a_step, a_chunk, None)


def _scan_bwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, states_ref, dy_ref,
                     da_ref, ddt_ref, dx_ref, db_ref, dc_ref, dh_scr, by_row,
                     by_col, drun_scr, *, tiles: ScanTiles, width: int,
                     held_in):
    """The chunks in reverse, the cotangent of the state LEAVING a chunk
    carried in VMEM scratch. Everything works on the TRANSPOSED score tile
    ``B Cᵀ`` ``[j, i]`` (as dk/dv does): ``Lᵀ`` and ``(B Cᵀ ∘ Lᵀ ∘ Δ)`` are
    the left operands of ``dx``'s product as they stand, and the group's
    ``G = Σ_heads (x dyᵀ) ∘ Lᵀ ∘ Δ`` gives ``dB = G C`` and ``dC = Gᵀ B``.

    The running sum's cotangent is kept with heads along sublanes, ``[R,
    Q]``, one register: a head's ``K = (x dyᵀ) ∘ Lᵀ ∘ B Cᵀ`` gives its sums
    over ``j`` as they stand and those over ``i`` from ``Kᵀ`` (a sum along
    lanes costs a tree of rotations a register, a transpose and a sum
    along sublanes a fraction); both come from the ONE matrix, so the pairs
    that do not straddle a position cancel exactly in the reverse running
    sum that follows. What the states add (``dy · exp(run) C H``, ``x · Bᵀ
    dH``) is summed over a head's columns the same way, before the decays
    multiply it. The reverse running sum itself, one more chain of small
    steps a chunk, is taken for all the step's chunks after the loop."""
    chunk, fill = tiles.chunk, tiles.heads_a_tile
    wide, dtype, f32 = fill * width, x_ref.dtype, jnp.float32
    held = functools.partial(_held, dtype=held_in)
    a = a_ref[0].astype(f32)                                 # [R, 1]
    per = a.shape[0]
    lanes, seen_t = _Lanes(chunk, fill, width), _causal(chunk, True)
    row, col = _BY_ROW.index, _BY_COL.index
    head_row = jax.lax.broadcasted_iota(jnp.int32, (per, chunk), 0)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, per), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, per), 0) == chunk - 1

    @pl.when(pl.program_id(1) == 0)
    def _first():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    def of_head(r, vector):     # [1, Q] on head r's sublane, zero elsewhere
        return jnp.where(head_row == r, vector, 0.0)

    _stage_decays(dt_ref, a, held, chunk, tiles.chunks_a_step, by_row,
                  by_col)

    def a_chunk(back, carry):
        j = tiles.chunks_a_step - 1 - back
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        dt_rows, run_rows = dt_ref[0, :, at], by_row[row("run"), :, at]
        grow_rows = by_row[row("grow"), :, at]
        to_end_rows = by_row[row("to_end"), :, at]
        pull_rows = dt_rows * to_end_rows
        dt_cols, run = by_col[col("dt"), at, :], by_col[col("run"), at, :]
        grow = by_col[col("grow"), at, :]
        b, c = b_ref[at, :], c_ref[at, :]
        c_t = c.T
        scores_t = _dot(b, c, _NT, None)                     # [j, i]
        g_sum = jnp.zeros_like(scores_t)
        drun = jnp.zeros((per, chunk), f32)     # of the running sum, by row
        ddt = jnp.zeros((per, chunk), f32)      # of Δ where it is a factor
        dwhole = jnp.zeros((1, per), f32)       # of exp(run_last), a head
        db = jnp.zeros(b.shape, f32)
        dc = jnp.zeros(c.shape, f32)
        for tile in range(per // fill):
            cols = slice(tile * wide, (tile + 1) * wide)
            x, dy = x_ref[at, cols], dy_ref[at, cols]
            x32, dy_in = x.astype(f32), dy.astype(dtype)
            h_in = states_ref[j, 0, :, cols]
            dh = dh_scr[:, cols]
            dh_in = dh.astype(dtype)
            inside = []
            for k in range(fill):
                r = tile * fill + k
                dt_col = dt_cols[:, r:r + 1]
                decay_t = held(jnp.exp(jnp.where(seen_t, held(
                    run_rows[r:r + 1, :] - run[:, r:r + 1]), _NEG_INF)))
                inside.append(_dot((scores_t * decay_t * dt_col).astype(
                    dtype), dy_in, _NN, None))
                g = _dot(lanes.only(x32, k).astype(dtype), dy_in, _NT,
                         None) * decay_t
                pairs = g * scores_t             # K without its factor Δ_j
                g_sum = g_sum + g * dt_col
                by_i = jnp.sum(pairs * dt_col, axis=0, keepdims=True)
                by_j = jnp.sum(pairs.T, axis=0, keepdims=True)
                drun = drun + of_head(
                    r, by_i - dt_rows[r:r + 1, :] * by_j)
                ddt = ddt + of_head(r, by_j)
            # the state leaving the chunk: Σ_j pull_j B_j ⊗ x_j + whole H
            from_dh = _dot(b, dh_in, _NN, None)              # [Q, wide]
            pull = lanes.over(by_col[col("pull"), at, :], tile)
            dx_ref[at, cols] = (lanes.take(inside) + pull * from_dh).astype(
                dx_ref.dtype)
            db = db + _dot((pull * x32).astype(dtype), dh_in, _NT, None)
            from_h = _dot(c, h_in, _NN, None)
            dy_grown = (lanes.over(grow, tile) * dy).astype(dtype)
            dc = dc + _dot(dy_grown, h_in, _NT, None)
            out_t, in_t = (x32 * from_dh).T, (dy * from_h).T  # [wide, Q]
            kept = jnp.sum(dh * h_in.astype(f32), axis=0, keepdims=True)
            for k in range(fill):
                r = tile * fill + k
                mine = slice(k * width, (k + 1) * width)
                out = jnp.sum(out_t[mine], axis=0, keepdims=True)   # [1, Q]
                into = jnp.sum(in_t[mine], axis=0, keepdims=True)
                pulled = pull_rows[r:r + 1, :] * out
                drun = drun + of_head(
                    r, grow_rows[r:r + 1, :] * into - pulled)
                ddt = ddt + of_head(r, to_end_rows[r:r + 1, :] * out)
                dwhole = dwhole + jnp.where(
                    head_lane == r,
                    jnp.sum(pulled, axis=1, keepdims=True)
                    + grow[-1:, r:r + 1] * jnp.sum(
                        lanes.only(kept, k), axis=1, keepdims=True), 0.0)
            dh_scr[:, cols] = held(
                lanes.row_over(grow[-1:, :], tile) * dh
                + _dot(c_t, dy_grown, _NN, None))
        db_ref[at, :] = (db + _dot(g_sum.astype(dtype), c, _NN,
                                   None)).astype(db_ref.dtype)
        dc_ref[at, :] = (dc + _dot(g_sum.T.astype(dtype), b, _NN,
                                   None)).astype(dc_ref.dtype)
        # exp(run_last)'s goes to the running sum's last position
        drun_scr[:, at] = drun + jnp.where(last, dwhole, 0.0).T
        ddt_ref[0, :, at] = ddt
        return carry

    jax.lax.fori_loop(0, tiles.chunks_a_step, a_chunk, None)
    for j in range(tiles.chunks_a_step):
        at = slice(j * chunk, (j + 1) * chunk)
        dda = _running_sum(drun_scr[:, at], reverse=True)    # [R, Q]
        ddt_ref[0, :, at] += dda * a
        da_ref[0] += jnp.sum(dda * dt_ref[0, :, at], axis=1, keepdims=True)


def _scan_specs(tiles: ScanTiles, per: int, width: int, n: int, blocks,
                dtype, held_in):
    """What both launches share: the block specs of ``a`` ``[G, R, 1]``,
    Δ ``[G, R, S]``, ``x`` ``[S, H · P]`` and ``b`` / ``c`` ``[S, G · N]``
    on a grid ``(group, block of chunks)`` whose second index ``at`` turns
    into a block, the saved states' ``[chunks, G, N, R · P]``, the scratch
    (the carried state or its cotangent, the step's staged decays by row
    and by column) and the compiler's parameters."""
    rows, a_step = tiles.chunk * tiles.chunks_a_step, tiles.chunks_a_step

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda g, i: index(g, blocks(i)))

    group = spec((1, per, 1), lambda g, at: (g, 0, 0))
    dt = spec((1, per, rows), lambda g, at: (g, 0, at))
    x = spec((rows, per * width), lambda g, at: (at, g))
    bc = spec((rows, n), lambda g, at: (at, g))
    states = spec((a_step, 1, n, per * width), lambda g, at: (at, g, 0, 0))
    scratch = [pltpu.VMEM((n, per * width), jnp.float32),
               pltpu.VMEM((len(_BY_ROW), per, rows), jnp.float32),
               pltpu.VMEM((len(_BY_COL), rows, per), jnp.float32)]
    # the backward's blocks, double buffered: x, dx, the states in the
    # operands' dtype, dy float32, b, c, db, dc; the scratch, a column of
    # the decays by column padded to a lane tile
    item = jnp.dtype(dtype).itemsize
    need = (2 * rows * (per * width * (2 * item + 4) + 4 * n * item)
            + 2 * a_step * n * per * width * item + 4 * n * per * width
            + 4 * len(_BY_COL) * rows * max(per, _LANES))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=(None if need <= 12 << 20
                          else min(2 * need, _VMEM_ASK_MAX)))
    kw = dict(tiles=tiles, width=width, held_in=jnp.dtype(held_in))
    return (group, dt, x, bc, states), scratch, params, kw


def _pallas_scan_fwd(a, dt, x, b, c, *, tiles: ScanTiles, width: int,
                     save_states: bool, interpret: bool):
    """``a`` ``[G, R, 1]`` in the dtype the recurrence is held in, Δ ``[G,
    R, S]`` float32, ``x`` ``[S, H · P]``, ``b`` / ``c`` ``[S, G · N]``, ``S``
    whole grid steps → ``y`` ``[S, H · P]`` float32 and, with
    ``save_states``, the state entering every chunk ``[chunks, G, N, R ·
    P]`` in ``x``'s dtype."""
    groups, per, s = dt.shape
    n = b.shape[1] // groups
    steps = s // (tiles.chunk * tiles.chunks_a_step)
    (group, by_row, wide, narrow, states), scratch, params, kw = _scan_specs(
        tiles, per, width, n, lambda i: i, x.dtype, a.dtype)
    out_specs = [wide]
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.float32)]
    if save_states:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct(
            (s // tiles.chunk, groups, n, per * width), x.dtype))
    return pl.pallas_call(
        functools.partial(_scan_fwd_kernel, **kw),
        grid=(groups, steps),
        in_specs=[group, by_row, wide, narrow, narrow],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params, interpret=interpret,
        name="ssd_scan_fwd")(a, dt, x, b, c)


def _pallas_scan_bwd(a, dt, x, b, c, states, dy, *, tiles: ScanTiles,
                     width: int, interpret: bool):
    """→ ``(da [G, R, 1] float32, dΔ [G, R, S] float32, dx, db, dc)``, the
    last three in their operands' dtypes; ``db`` and ``dc`` are a group's,
    summed over its heads in the kernel."""
    groups, per, s = dt.shape
    n = b.shape[1] // groups
    steps = s // (tiles.chunk * tiles.chunks_a_step)
    (group, by_row, wide, narrow, saved), scratch, params, kw = _scan_specs(
        tiles, per, width, n, lambda i: steps - 1 - i, x.dtype, a.dtype)
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, **kw),
        grid=(groups, steps),
        in_specs=[group, by_row, wide, narrow, narrow, saved, wide],
        out_specs=[group, by_row, wide, narrow, narrow],
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        # and the running sum's cotangent, by row, of the step's chunks
        scratch_shapes=[*scratch, pltpu.VMEM((per, s // steps), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="ssd_scan_bwd")(a, dt, x, b, c, states, dy)


@functools.lru_cache(maxsize=32)
def _scan_fn(tiles: ScanTiles, width: int, interpret: bool):
    """The custom-VJP'd scan over prepared operands. Three kernels: the
    forward as it is called, the forward that also writes the state
    entering each chunk (the ``fwd`` rule's: under ``jax.checkpoint`` it
    runs inside the backward pass, a sequence at a time), and the
    backward. Residuals are the operands and those states."""
    kw = dict(tiles=tiles, width=width, interpret=interpret)

    @jax.custom_vjp
    def f(a, dt, x, b, c):
        return _pallas_scan_fwd(a, dt, x, b, c, save_states=False, **kw)[0]

    def fwd(a, dt, x, b, c):
        y, states = _pallas_scan_fwd(a, dt, x, b, c, save_states=True, **kw)
        return y, (a, dt, x, b, c, states)

    def bwd(res, dy):
        da, *rest = _pallas_scan_bwd(*res, dy, **kw)
        return (da.astype(res[0].dtype), *rest)

    f.defvjp(fwd, bwd)
    return f


def ssd_scan(x, dt, a, b, c, *, chunk: int, interpret: bool | None = None):
    """The selective state-space recurrence of ONE sequence, ``H_t =
    exp(Δ_t A) H_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = H_t C_t``, in chunks of
    ``chunk`` positions (Mamba-2's state-space duality), as one Pallas
    kernel with its backward.

    ``x`` ``[S, H, P]``, ``dt`` ``[S, H]`` (Δ, after the softplus), ``a``
    ``[H]`` (negative), ``b`` / ``c`` ``[S, G, N]``; head ``h`` uses group
    ``h // (H / G)``. Returns ``y`` ``[S, H, P]`` float32. **``a``'s dtype is
    what the recurrence is held in**: Δ·A, its running sum inside a chunk,
    the decays ``L`` and the state carried from chunk to chunk (and its
    cotangent) are values of that dtype; the arithmetic between them is
    float32, the running sum a float32 ladder on the VPU. The products take
    operands in ``x``'s dtype and accumulate in float32. A length that
    ``chunk`` does not divide is padded with Δ = 0: no decay, no input.

    Nothing of shape ``[chunks, H, Q, Q]`` is ever in HBM: decays, masked
    scores and the running state stay in VMEM. The backward sweeps the
    chunks in reverse; its residuals are the operands and the state
    entering each chunk (``[chunks, G, N, R · P]`` in ``x``'s dtype), which
    the forward writes only where a gradient is taken. What the shapes
    allow is decided from the shapes (:func:`scan_tiles`). Compiled, a
    group's heads have to fill whole lane tiles (``R · P`` and ``N``
    multiples of 128, or one group) and ``chunk`` has to be one.

    ``interpret=None`` picks from the process's default backend, as
    :func:`flash_attention` does. Counts ``pallas.ssd.*`` once per call,
    which under ``jax.jit`` is once per TRACE of the caller's program."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (s, heads, width), groups = x.shape, b.shape[1]
    if heads % groups or dt.shape != (s, heads) or a.shape != (heads,):
        raise ValueError(f"{heads} heads of {dt.shape} steps and {a.shape} "
                         f"decays over {groups} groups")
    tiles = scan_tiles(s, heads // groups, width, chunk)
    _metrics.counter("pallas.ssd.launches").inc()
    _metrics.gauge("pallas.ssd.chunk").set(chunk)
    _metrics.gauge("pallas.ssd.heads_a_step").set(heads // groups)
    _metrics.gauge("pallas.ssd.states_saved").set(1)
    return _scan_call(x, dt, a, b, c, tiles=tiles, interpret=interpret)


def _scan_traced(x, dt, a, b, c, *, tiles: ScanTiles, interpret: bool):
    (s, heads, width), (groups, n) = x.shape, b.shape[1:]
    per = heads // groups
    pad = -s % (tiles.chunk * tiles.chunks_a_step)

    def rows(v):            # [S, ...] -> [S + pad, the rest as columns]
        return jnp.pad(v.reshape(s, -1), ((0, pad), (0, 0)))

    # Δ with its positions along lanes, a group's heads along sublanes
    dt = rows(dt.astype(jnp.float32)).reshape(-1, groups, per).transpose(
        1, 2, 0)
    y = _scan_fn(tiles, width, interpret)(
        a.reshape(groups, per, 1), dt, rows(x), rows(b), rows(c))
    return y[:s].reshape(s, heads, width)


# the traced name is what device traces file the kernels under
_scan_traced.__name__ = _scan_traced.__qualname__ = "ssd_scan"
_scan_call = jax.jit(_scan_traced, static_argnames=("tiles", "interpret"))
