"""Space-to-depth stem transform — built, measured, and REJECTED on v5e.

The standard TPU counter-move for skinny-channel stem convs (used by
the MLPerf ResNet submissions): re-express the stem in block-2
space-to-depth form so every 2×2 spatial patch becomes 4× the
channels, trading 1.78× FLOPs (2×2 windows over 4c channels replace
3×3 windows over c) for fatter MXU-lane contractions.

**Measured outcome (July 2026 v5e profile, ROADMAP.md A8): a 19%
REGRESSION on the real chip — 40.83 ms/step vs the canonical stem's
34.26 ms — so ``TPUDL_S2D_STEM`` defaults OFF.** Two reasons: XLA's
TPU convolutions contract over kh·kw·ci, so the canonical 3×3×32 stem
conv is already a 288-element contraction (≥ the 128 lanes — the
underfill premise only ever held for the 27-tap input conv), and the
s2d entry/exit reshuffles materialize ~4.4 ms of HBM copies. The
module stays because the transforms are exact, tested reformulations
(tests/test_s2d.py) and the negative result is part of the perf
record; a backend whose convs contract over ci alone could flip the
flag back on.

``stride2_valid_kernel`` / ``unit_stride_kernel`` rewrite HWIO conv
kernels into the s2d domain (zero-padded kernel taps — exact, not
approximate); ``inception_stem_s2d`` chains the whole InceptionV3 stem
(conv s2 VALID → conv s1 VALID → conv s1 SAME, each with BN+ReLU)
without leaving s2d space.

Reference anchor: sparkdl transformers/keras_applications.py
InceptionV3Model (the judged featurize architecture); SURVEY.md §6
(perf north star). The reference has no equivalent.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["space_to_depth", "depth_to_space", "stride2_valid_kernel",
           "unit_stride_kernel", "tile_bn_params", "inception_stem_s2d"]


def space_to_depth(x, block: int = 2):
    """NHWC → NH/bW/b(b²C); channel layout (row-in-block, col-in-block)
    major, original channel minor."""
    n, h, w, c = x.shape
    b = block
    x = x.reshape(n, h // b, b, w // b, b, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // b, w // b, b * b * c)


def depth_to_space(x, block: int = 2):
    n, h, w, c4 = x.shape
    b = block
    c = c4 // (b * b)
    x = x.reshape(n, h, w, b, b, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h * b, w * b, c)


def stride2_valid_kernel(w):
    """HWIO [3,3,ci,co] stride-2 VALID kernel → [2,2,4ci,co] stride-1
    VALID kernel over the s2d input.

    out[m,n] of the original conv reads the 3×3 x-window at (2m,2n);
    in s2d space that window lives inside the 2×2 y-window at (m,n)
    (a 4×4 x-region), so embedding the kernel in a zero-padded 4×4 and
    folding the block dims into channels is an exact rewrite. The
    output is at y resolution — i.e. already the stride-2 output — in
    NORMAL channel layout."""
    kh, kw, ci, co = w.shape
    assert (kh, kw) == (3, 3), "stem transform is for 3x3 kernels"
    w4 = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))       # [4,4,ci,co]
    w4 = w4.reshape(2, 2, 2, 2, ci, co)       # [br, ir, bs, ic, ci, co]
    w4 = w4.transpose(0, 2, 1, 3, 4, 5)       # [br, bs, ir, ic, ci, co]
    return w4.reshape(2, 2, 4 * ci, co)


def unit_stride_kernel(w):
    """HWIO [3,3,ci,co] stride-1 VALID kernel → [2,2,4ci,4co] stride-1
    VALID kernel mapping s2d input to s2d OUTPUT.

    Each y-site's 4 output sub-positions (pr,pc) read 3×3 x-windows at
    offsets (pr,pc) inside the same 4×4 x-region, so the s2d kernel
    holds one shifted zero-embedded copy of ``w`` per sub-position:
    W'[br,bs,(ir,ic,ci),(pr,pc,co)] = w[2br+ir-pr, 2bs+ic-pc, ci, co]
    (zero outside 0..2)."""
    kh, kw, ci, co = w.shape
    assert (kh, kw) == (3, 3), "stem transform is for 3x3 kernels"
    rows = []
    for pr in range(2):
        cols = []
        for pc in range(2):
            w4 = jnp.pad(w, ((pr, 1 - pr), (pc, 1 - pc), (0, 0), (0, 0)))
            w4 = w4.reshape(2, 2, 2, 2, ci, co)
            cols.append(w4.transpose(0, 2, 1, 3, 4, 5))  # [br,bs,ir,ic,ci,co]
        rows.append(jnp.stack(cols, axis=-2))        # [...,ci,pc,co]
    stacked = jnp.stack(rows, axis=-3)               # [br,bs,ir,ic,ci,pr,pc,co]
    return stacked.reshape(2, 2, 4 * ci, 4 * co)


def tile_bn_params(p: dict) -> dict:
    """Per-channel BN params for s2d-layout activations: the (ir,ic)
    block slots replicate the channel axis 4×, matching the s2d channel
    order (block-position major, channel minor)."""
    return {k: jnp.tile(v, 4) for k, v in p.items()}


def _zero_tail_slots(y, c: int, valid_rows: int, valid_cols: int):
    """Zero every s2d slot whose ORIGINAL-space row/col index is >= the
    valid extent (the padded/garbage tail a chained valid conv wrote)."""
    n, h, w, _ = y.shape
    y = y.reshape(n, h, w, 2, 2, c)
    rows = 2 * jnp.arange(h)[:, None] + jnp.arange(2)[None]     # [h,2]
    cols = 2 * jnp.arange(w)[:, None] + jnp.arange(2)[None]     # [w,2]
    y = y * (rows < valid_rows)[None, :, None, :, None, None]
    y = y * (cols < valid_cols)[None, None, :, None, :, None]
    return y.reshape(n, h, w, 4 * c)


def _shift_in_zero_block(y):
    """Prepend one zero block row and column (= two original-space
    zero rows/cols: the SAME-conv left pad, block-aligned), growing the
    spatial extent by one block each way."""
    n, h, w, c = y.shape
    y = jnp.concatenate([jnp.zeros((n, 1, w, c), y.dtype), y], 1)
    y = jnp.concatenate([jnp.zeros((n, h + 1, 1, c), y.dtype), y], 2)
    return y


def inception_stem_s2d(x, conv1, bn1, conv2, bn2, conv3, bn3, *,
                       bn_apply, relu):
    """The InceptionV3 stem (ref keras layout: conv 3×3/2 VALID 3→32,
    conv 3×3/1 VALID 32→32, conv 3×3/1 SAME 32→64, each +BN+ReLU)
    computed in block-2 space-to-depth form, exactly.

    ``convN``/``bnN`` are the CANONICAL param dicts (HWIO kernels,
    per-channel BN) — the transform is applied to the weights inside
    the traced function, so checkpoints, Keras conversion, and the
    param pytree are unchanged. ``bn_apply(x, p)`` and ``relu`` are
    injected so this module stays import-light.

    Requires odd H, W (InceptionV3's VALID-padding geometry, e.g. 299).
    """
    from tpudl.zoo import nn

    n, h, w, _c = x.shape
    if h % 2 == 0 or w % 2 == 0 or h < 7 or w < 7:
        raise ValueError(f"s2d stem needs odd H,W >= 7, got {h}x{w}")
    h1, w1 = (h - 3) // 2 + 1, (w - 3) // 2 + 1          # conv1 out (odd)
    h2, w2 = h1 - 2, w1 - 2                              # conv2 out

    # conv1 (stride 2 VALID): pad input to the even y-grid, contract in
    # s2d space; the output lands at y resolution in normal layout.
    xp = jnp.pad(x, ((0, 0), (0, 2 * h1 + 2 - h), (0, 2 * w1 + 2 - w),
                     (0, 0)))
    y = space_to_depth(xp)                               # [*, (h1+1), (w1+1), 12]
    out1 = nn.conv2d(y, stride2_valid_kernel(conv1["kernel"]),
                     strides=(1, 1), padding="VALID")    # [*, h1, w1, 32]
    out1 = relu(bn_apply(out1, bn1))

    # conv2 (stride 1 VALID): back into s2d space (pad h1 odd → even).
    y2 = space_to_depth(jnp.pad(out1, ((0, 0), (0, 1), (0, 1), (0, 0))))
    y2 = nn.conv2d(y2, unit_stride_kernel(conv2["kernel"]),
                   strides=(1, 1), padding="VALID")      # s2d of conv2 out
    y2 = relu(bn_apply(y2, tile_bn_params(bn2)))
    c2 = conv2["kernel"].shape[-1]

    # conv3 (stride 1 SAME over [h2, w2]): zero the tail slots conv2's
    # zero-padded input fabricated past h2-1 (SAME pads with ZEROS, and
    # BN+ReLU above made the fabricated rows nonzero), then shift one
    # block in — a block-aligned spelling of SAME's 1-pixel pad whose
    # VALID output is the SAME output off by one row/col, sliced after
    # depth-to-space.
    y2 = _zero_tail_slots(y2, c2, h2, w2)
    y3 = _shift_in_zero_block(y2)
    y3 = nn.conv2d(y3, unit_stride_kernel(conv3["kernel"]),
                   strides=(1, 1), padding="VALID")
    y3 = relu(bn_apply(y3, tile_bn_params(bn3)))
    out3 = depth_to_space(y3)                            # [*, h2+1, w2+1, 64]
    return out3[:, 1:h2 + 1, 1:w2 + 1]
