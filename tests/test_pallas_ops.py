"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh —
identical kernel semantics; the TPU path compiles the same pallas_call).
The kernel must match the dense oracle exactly, compose across blocks
via its log-sum-exp output, and back-propagate to the oracle's gradients
through the ONE tiled Pallas backward kernel (custom VJP from the
saved log-sum-exp — no S^2 tensor in either direction)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpudl import mesh as M
from tpudl.attention import (attention_reference, ring_attention,
                             shard_sequence)
from tpudl.pallas_ops import flash_attention
from tpudl.zoo import moe


@pytest.fixture(scope="module")
def qkv(rng):
    return tuple(rng.normal(size=(2, 64, 2, 32)).astype(np.float32)
                 for _ in range(3))


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_oracle(self, qkv, causal):
        q, k, v = (jnp.asarray(a) for a in qkv)
        want = np.asarray(attention_reference(q, k, v, causal=causal))
        got = np.asarray(flash_attention(q, k, v, causal=causal,
                                         block_q=16, block_k=16,
                                         interpret=True))
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    def test_lse_makes_blocks_composable(self, qkv):
        """The ring contract: two half-K calls must merge into the full
        answer through their lse weights."""
        q, k, v = (jnp.asarray(a) for a in qkv)
        o1, l1 = flash_attention(q, k[:, :32], v[:, :32], block_q=16,
                                 block_k=16, interpret=True,
                                 return_lse=True)
        o2, l2 = flash_attention(q, k[:, 32:], v[:, 32:], block_q=16,
                                 block_k=16, interpret=True,
                                 return_lse=True)
        m = jnp.maximum(l1, l2)
        w1, w2 = jnp.exp(l1 - m)[..., None], jnp.exp(l2 - m)[..., None]
        merged = np.asarray((o1 * w1 + o2 * w2) / (w1 + w2))
        want = np.asarray(attention_reference(q, k, v))
        np.testing.assert_allclose(merged, want, rtol=2e-6, atol=2e-6)

    def test_traced_offsets_shift_causal_mask(self, qkv):
        """Ring blocks pass their global positions as traced values; a Q
        block at offset 32 sees ALL of a K block at offset 0."""
        q, k, v = (jnp.asarray(a[:, :32]) for a in qkv)
        got = np.asarray(flash_attention(
            q, k, v, causal=True, q_offset=jnp.asarray(32, jnp.int32),
            k_offset=0, block_q=16, block_k=16, interpret=True))
        want = np.asarray(attention_reference(q, k, v, causal=False))
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    def test_grad_matches_dense(self, qkv):
        q, k, v = (jnp.asarray(a[:, :32]) for a in qkv)

        def loss_flash(a, b, c):
            return jnp.sum(flash_attention(a, b, c, causal=True,
                                           block_q=16, block_k=16,
                                           interpret=True) ** 2)

        def loss_dense(a, b, c):
            return jnp.sum(attention_reference(a, b, c, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_indivisible_length_pads_and_masks(self, qkv, causal):
        """A length that is no block multiple (50 over blocks of 16/24)
        is padded up to one — pad keys masked in-kernel, pad query rows
        dropped — in both kernels: values AND grads match dense."""
        q, k, v = (jnp.asarray(a[:, :50]) for a in qkv)

        def flash(a, b, c):
            return jnp.sum(flash_attention(
                a, b, c, causal=causal, block_q=16, block_k=24,
                interpret=True) ** 2)

        def dense(a, b, c):
            return jnp.sum(attention_reference(a, b, c, causal=causal) ** 2)

        got, g_got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want, g_want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestRingWithPallas:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_oracle(self, qkv, causal):
        mesh = M.build_mesh()
        q, k, v = qkv
        want = np.asarray(attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
        qs, ks, vs = shard_sequence((q, k, v), mesh)
        got = np.asarray(ring_attention(qs, ks, vs, mesh, causal=causal,
                                        use_pallas=True))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_grad_matches_plain_ring(self, qkv):
        mesh = M.build_mesh()
        q, k, v = (a[:1, :16, :1, :] for a in qkv)
        qs, ks, vs = shard_sequence(tuple(
            np.ascontiguousarray(a) for a in (q, k, v)), mesh)

        def loss(use_pallas):
            def f(a, b, c):
                return jnp.sum(ring_attention(
                    a, b, c, mesh, causal=True,
                    use_pallas=use_pallas) ** 2)
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(qs, ks, vs)

        gp = loss(True)
        gj = loss(False)
        for a, b in zip(gp, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestReviewRegressions:
    def test_fully_future_k_block_reports_masked(self, qkv):
        """A strictly-future K block (causal, k_offset > every q position)
        must yield zeros + -inf-equivalent lse — NOT mean(V)."""
        q, k, v = (jnp.asarray(a[:, :16]) for a in qkv)
        out, lse = flash_attention(
            q, k, v, causal=True, q_offset=0, k_offset=1000,
            block_q=8, block_k=8, interpret=True, return_lse=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert np.all(np.asarray(lse) < -1e29)

    def test_ring_pallas_accepts_non_multiple_shards(self, rng):
        """s_loc=24 (not a multiple of 128) must work as one clipped
        block, matching the plain ring path."""
        mesh = M.build_mesh()
        q, k, v = (rng.normal(size=(1, 24 * 8, 2, 16)).astype(np.float32)
                   for _ in range(3))
        want = np.asarray(attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
        qs, ks, vs = shard_sequence((q, k, v), mesh)
        got = np.asarray(ring_attention(qs, ks, vs, mesh, causal=True,
                                        use_pallas=True))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestGroupedQueries:
    """32-over-8-style heads at toy size: K/V heads are shared in place by
    the kernels' index maps, dk/dv summed over each group in float32."""

    @pytest.fixture(scope="class")
    def gqa(self, rng):
        q = rng.normal(size=(2, 48, 8, 16)).astype(np.float32)
        k, v = (rng.normal(size=(2, 48, 2, 16)).astype(np.float32)
                for _ in range(2))
        return q, k, v

    @staticmethod
    def _oracle(q, k, v, causal):
        group = q.shape[2] // k.shape[2]
        return attention_reference(q, jnp.repeat(k, group, axis=2),
                                   jnp.repeat(v, group, axis=2),
                                   causal=causal)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_repeated_heads(self, gqa, causal):
        q, k, v = (jnp.asarray(a) for a in gqa)
        got = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        np.testing.assert_allclose(got, self._oracle(q, k, v, causal),
                                   rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("blocks", [(16, 16), (48, 16), (16, 48)])
    def test_backward_sums_each_group(self, gqa, blocks):
        q, k, v = (jnp.asarray(a) for a in gqa)
        w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
            interpret=True)), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: self._oracle(
            q, k, v, True)), (0, 1, 2))(q, k, v)
        for g, w_ in zip(got, want):
            assert g.shape == w_.shape
            np.testing.assert_allclose(g, w_, rtol=2e-5, atol=2e-5)

    def test_padded_length_and_lse(self, gqa):
        q, k, v = (jnp.asarray(a[:, :37]) for a in gqa)
        out, lse = flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True,
                                   return_lse=True)
        np.testing.assert_allclose(out, self._oracle(q, k, v, True),
                                   rtol=2e-6, atol=2e-6)
        assert lse.shape == (2, 37, 8)

    def test_bfloat16_operands_stay_bfloat16_on_the_mxu(self, gqa):
        """bf16 inputs are multiplied as bf16 with float32 accumulation
        (one MXU pass), not upcast: within bf16's rounding of the oracle."""
        q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in gqa)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        assert got.dtype == jnp.bfloat16
        want = self._oracle(*(a.astype(jnp.float32) for a in (q, k, v)),
                            True)
        err = jnp.linalg.norm(got.astype(jnp.float32) - want)
        assert float(err / jnp.linalg.norm(want)) < 2e-2

    def test_heads_must_divide(self, gqa):
        q, k, v = (jnp.asarray(a) for a in gqa)
        with pytest.raises(ValueError, match="query heads"):
            flash_attention(q[:, :, :7], k, v, interpret=True)
        with pytest.raises(ValueError, match="query heads"):
            flash_attention(q, k, v[:, :, :1], interpret=True)


def _dense(q, k, v, *, causal, q_offset=0, k_offset=0):
    """The dense oracle with global positions: (out, lse), a row with no
    visible key reported as the kernels report it (zeros, lse -1e30)."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / q.shape[-1] ** 0.5
    if causal:
        seen = ((q_offset + jnp.arange(q.shape[1]))[:, None]
                >= (k_offset + jnp.arange(k.shape[1]))[None, :])
        s = jnp.where(seen, s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.where(l == 0, 1.0, l), v,
                     precision="highest")
    lse = jnp.where(l == 0, -1e30, m + jnp.log(jnp.where(l == 0, 1.0, l)))
    return out, lse[..., 0].transpose(0, 2, 1)


def _weighted(fn, shape_q, heads):
    """A scalar of BOTH outputs, so the cotangents of out and of lse
    reach every class of tile; masked rows' lse (a constant) left out."""
    w = jnp.cos(jnp.arange(np.prod(shape_q), dtype=jnp.float32)
                ).reshape(shape_q)
    u = jnp.sin(jnp.arange(np.prod(shape_q[:3]), dtype=jnp.float32)
                ).reshape(shape_q[:3])

    def loss(*operands):
        out, lse = fn(*operands)
        return (jnp.sum(out * w)
                + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * u))
    return loss


# name: (Sq, Sk, block_q, block_k, causal, q_offset, k_offset),
#       (interior, crossing, dead) tiles a head
TILE_CASES = {
    "every tile interior": ((32, 32, 16, 16, True, 64, 0), (4, 0, 0)),
    "every tile crossing": ((64, 64, 16, 64, True, 0, 0), (0, 4, 0)),
    "whole rows of dead tiles": ((64, 32, 16, 16, True, 0, 32), (1, 2, 5)),
    "block_q over block_k": ((64, 64, 32, 16, True, 0, 0), (2, 4, 2)),
    "block_k over block_q": ((64, 64, 16, 32, True, 0, 0), (2, 4, 2)),
    "pad in an interior row": ((32, 40, 16, 16, True, 64, 0), (4, 2, 0)),
    "pad without a mask": ((32, 40, 16, 16, False, 0, 0), (4, 2, 0)),
    "no mask at all": ((32, 32, 16, 16, False, 0, 0), (4, 0, 0)),
}


class TestTileClasses:
    """Interior tiles run a body with no mask, no no-key guard and no
    ``alive`` factor; dead tiles run nothing and fetch nothing: each
    where it can go wrong, forward and gradients against the dense
    oracle, and the per-trace counter beside them."""

    @pytest.mark.parametrize("case", list(TILE_CASES))
    def test_forward_and_gradients_match_dense(self, rng, case):
        from tpudl import obs

        (sq, sk, bq, bk, causal, qo, ko), want_tiles = TILE_CASES[case]
        q = jnp.asarray(rng.normal(size=(2, sq, 4, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(2, sk, 2, 16)), jnp.float32)
                for _ in range(2))
        kw = dict(causal=causal, q_offset=qo, k_offset=ko)

        def flash(q, k, v):
            return flash_attention(q, k, v, block_q=bq, block_k=bk,
                                   interpret=True, return_lse=True, **kw)

        def tiles():
            snap = obs.snapshot("pallas.flash.tiles.")
            return [snap.get(f"pallas.flash.tiles.{c}", {"value": 0})["value"]
                    for c in ("interior", "crossing", "dead")]

        before = tiles()
        (out, lse), (want_out, want_lse) = flash(q, k, v), _dense(
            q, k, v, **kw)
        assert tuple(a - b for a, b in zip(tiles(), before)) == want_tiles
        np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
        if case == "whole rows of dead tiles":
            np.testing.assert_array_equal(np.asarray(out[:, :32]), 0.0)
            assert np.all(np.asarray(lse[:, :32]) < -1e29)
        got = jax.grad(_weighted(flash, q.shape, 4), (0, 1, 2))(q, k, v)
        want = jax.grad(_weighted(lambda *a: _dense(*a, **kw), q.shape, 4),
                        (0, 1, 2))(q, k, v)
        for g, w_ in zip(got, want):
            np.testing.assert_allclose(g, w_, rtol=1e-4, atol=1e-4)

    def test_traced_offsets_give_the_bits_of_static_ones(self, rng):
        """The ring passes its offsets as traced values: the same scalar
        predicate from SMEM, so the same tiles run the same bodies."""
        q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
                   for _ in range(3))

        def run(qo, ko):
            def f(q, k, v):
                return flash_attention(
                    q, k, v, causal=True, q_offset=qo, k_offset=ko,
                    block_q=16, block_k=32, interpret=True,
                    return_lse=True)
            loss = _weighted(f, q.shape, 2)
            return f(q, k, v) + jax.grad(loss, (0, 1, 2))(q, k, v)

        static = run(16, 32)
        traced = jax.jit(run)(jnp.asarray(16, jnp.int32),
                              jnp.asarray(32, jnp.int32))
        for a, b in zip(static, traced):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("head_dim, lse_limit", [(64, 3e-3),
                                                     (128, 1e-5)])
    def test_bfloat16_out_lse_and_ring_merge_against_dense(
            self, rng, head_dim, lse_limit):
        """bfloat16 operands. Under a 128-wide head the normaliser comes
        out of ``p @ [V | 1]``: the returned lse is the log of the sum of
        the bfloat16-ROUNDED weights, at most a rounding (2^-9) from the
        float32 sum's and 1.0e-3 to 1.3e-3 over four seeds here; a
        128-wide head has no spare column and keeps the float32 sum
        (1e-6). The output carries the same 1.9e-3 either way (its own
        rounding and the weights'), and so does what the ring makes of
        two K/V blocks merged through their lse."""
        q = jnp.asarray(rng.normal(size=(1, 256, 4, head_dim)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, head_dim)),
                            jnp.bfloat16) for _ in range(2))
        want, want_lse = _dense(*(a.astype(jnp.float32) for a in (q, k, v)),
                                causal=True)
        kw = dict(causal=True, block_q=64, block_k=64, interpret=True,
                  return_lse=True)

        def rel(got):
            got = np.asarray(got, np.float64)
            return np.linalg.norm(got - want) / np.linalg.norm(want)

        out, lse = flash_attention(q, k, v, **kw)
        assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
        assert rel(out) < 4e-3
        assert np.abs(np.asarray(lse) - want_lse).max() < lse_limit
        # the ring's merge: rows 0..127 see nothing of the second block,
        # which reports them masked (zeros, lse -1e30: weight 0)
        o1, l1 = flash_attention(q, k[:, :128], v[:, :128], **kw)
        o2, l2 = flash_attention(q, k[:, 128:], v[:, 128:], k_offset=128,
                                 **kw)
        m = jnp.maximum(l1, l2)
        w1, w2 = jnp.exp(l1 - m)[..., None], jnp.exp(l2 - m)[..., None]
        assert rel((o1 * w1 + o2 * w2) / (w1 + w2)) < 4e-3
        merged_lse = m + jnp.log(w1 + w2)[..., 0]
        assert np.abs(np.asarray(merged_lse) - want_lse).max() < lse_limit

    @pytest.mark.parametrize("s", [257, 300, 520, 650, 1000, 2047, 2560,
                                   8192])
    def test_a_derived_tile_is_a_multiple_of_the_lane_tile(self, s):
        """Compiled, a block is a multiple of 128 lanes or Mosaic refuses
        the lse block beside it: at every length, awkward ones too, and
        the padded length is a multiple of the block."""
        from tpudl.pallas_ops import tile_shapes

        block_q, block_k, *_ = tile_shapes(s, s + 3, 4, 64, 2)
        assert block_q % 128 == 0 and block_k % 128 == 0
        assert block_q == min(1024, s + (-s % 128))
        assert block_k == min(1024, s + 3 + (-(s + 3) % 128))

    @pytest.mark.parametrize("heads, kv_heads", [(8, 2), (2, 2)])
    def test_grouped_queries_at_the_derived_tile_shape(self, rng, heads,
                                                       kv_heads):
        """No block argument, as the decoder calls it: LFM2's four query
        heads of 64 to a key/value head (four heads a grid step; its 32
        over 8 are four times the rows of the same grid, compiled in
        test_tpu_compile.py and counted below), and group 1, over a
        length that takes several derived tiles of every class."""
        from tpudl.pallas_ops import tile_counts, tile_shapes

        s = 1536
        tiles = tile_shapes(s, s, heads // kv_heads, 64, 4, align=1)
        assert tiles == (1024, 1024, *((4, 512) if heads > kv_heads
                                       else (1, 1024)), 2048)
        assert min(tile_counts(s, s, 1024, 1024,
                               causal=True).values()) > 0
        q = jnp.asarray(rng.normal(size=(1, s, heads, 64)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(1, s, kv_heads, 64)),
                            jnp.float32) for _ in range(2))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True,
                                   return_lse=True)

        got = jax.jit(jax.value_and_grad(
            _weighted(flash, q.shape, heads), (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(_weighted(
            lambda *a: _dense(*a, causal=True), q.shape, heads),
            (0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
        for g, w_ in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("blocks, shape, tiles", [
        ((512, 512), (512, 512), (120, 16, 120)),   # the cell before PR 31
        (None, (1024, 1024), (28, 8, 28))])         # what the kernels derive
    def test_counter_at_the_cells_shape(self, blocks, shape, tiles):
        """``pallas.flash.*`` per TRACE at four sequences of 8,192 tokens,
        32 query heads over 8 of 64, bfloat16: the tile shape and the
        tiles a head by class (a constant of the shapes: nothing runs
        here)."""
        from tpudl import obs

        def snap():
            return {n: m["value"]
                    for n, m in obs.snapshot("pallas.flash.").items()}

        kw = {} if blocks is None else dict(block_q=blocks[0],
                                            block_k=blocks[1])
        q = jax.ShapeDtypeStruct((4, 8192, 32, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((4, 8192, 8, 64), jnp.bfloat16)
        before = snap()
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, **kw), q, kv, kv)
        after = snap()
        assert after["pallas.flash.launches"] - before.get(
            "pallas.flash.launches", 0) == 1
        for cls, n in zip(("interior", "crossing", "dead"), tiles):
            name = f"pallas.flash.tiles.{cls}"
            assert after[name] - before.get(name, 0) == n
        assert (after["pallas.flash.block_q"],
                after["pallas.flash.block_k"]) == shape
        assert after["pallas.flash.heads_a_step"] == 4


class TestUnequalHeadWidths:
    """Latent attention's heads: queries and keys 192 wide (128 without
    position + 64 rotated), values 128; and a toy 24 / 16 whose value
    head leaves the accumulator spare columns for the row sum. Every
    operand keeps its own width; the scale is the query/key width's."""

    @pytest.mark.parametrize("length", [32, 40])      # on and off a tile
    @pytest.mark.parametrize("heads, kv_heads", [(4, 4), (4, 2)])
    @pytest.mark.parametrize("d_qk, d_v", [(24, 16), (192, 128)])
    def test_forward_and_gradients_match_dense(self, rng, d_qk, d_v, heads,
                                               kv_heads, length):
        q = jnp.asarray(rng.normal(size=(2, length, heads, d_qk)),
                        jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, length, kv_heads, d_qk)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, length, kv_heads, d_v)),
                        jnp.float32)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True,
                                   return_lse=True, precision="highest")

        (out, lse), (want_out, want_lse) = flash(q, k, v), _dense(
            q, k, v, causal=True)
        assert out.shape == (2, length, heads, d_v)
        np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
        shape = (2, length, heads, d_v)
        got = jax.grad(_weighted(flash, shape, heads), (0, 1, 2))(q, k, v)
        want = jax.grad(_weighted(lambda *a: _dense(*a, causal=True), shape,
                                  heads), (0, 1, 2))(q, k, v)
        for g, w_, operand in zip(got, want, (q, k, v)):
            assert g.shape == operand.shape
            np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4)

    def test_equal_widths_trace_the_program_they_traced(self, rng):
        """A value head as wide as the query/key head: the jaxpr names no
        width twice, and ``out`` has ``q``'s shape as it always had."""
        q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
                   for _ in range(3))

        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True)

        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: f(*a).sum(), (0, 1, 2)))(q, k, v))
        assert f(q, k, v).shape == q.shape
        # the scale of a 16-wide head is a power of two and rides on q
        assert "mul" in text and text.count("pallas_call") == 2

    def test_the_widths_are_gauged_and_a_mismatch_is_named(self, rng):
        from tpudl import obs

        q = jax.ShapeDtypeStruct((4, 8192, 32, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16)
        out = jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), q, q, v)
        assert out.shape == v.shape
        snap = {n: m["value"]
                for n, m in obs.snapshot("pallas.flash.").items()}
        assert (snap["pallas.flash.head_dim_qk"],
                snap["pallas.flash.head_dim_v"]) == (192, 128)
        assert (snap["pallas.flash.block_q"],
                snap["pallas.flash.block_k"]) == (1024, 1024)
        with pytest.raises(ValueError, match="queries 192 wide against "
                                             "keys 128 wide"):
            jax.eval_shape(lambda q, k, v: flash_attention(
                q, k, v, interpret=True), q, v, v)


class TestSavedAcrossRemat:
    """The forward kernel's output and row statistics carry the
    ``checkpoint_name`` ``SAVED`` where they are the backward's
    residuals: a ``jax.checkpoint`` whose policy saves that name (the
    decoder's) keeps them and launches no second forward kernel; any
    other caller compiles what it compiled."""

    ROUTES_ONLY = (moe.ROUTES,)

    @staticmethod
    def _loss(q, k, v, **kw):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16, interpret=True,
                               **kw).astype(jnp.float32).sum()

    @staticmethod
    def _policy(names):
        return jax.checkpoint_policies.save_only_these_names(*names)

    @pytest.fixture(scope="class")
    def operands(self, rng):
        shapes = ((2, 32, 4, 24), (2, 32, 2, 24), (2, 32, 2, 16))
        return tuple(jnp.asarray(rng.normal(size=s), jnp.bfloat16)
                     for s in shapes)

    def test_the_residuals_are_listed_by_name_with_their_shapes(
            self, operands):
        from jax._src.ad_checkpoint import saved_residuals

        from tpudl import pallas_ops
        from tpudl.zoo import decoder

        # head-major, as the backward kernels take them: 2 x 4 heads in
        # rows of 2 (the group), 32 positions, a 16-wide value head
        out, lse = ((4, 2, 32, 16), "bfloat16"), ((4, 2, 32), "float32")

        def named(fn, *args):
            return [((aval.shape, str(aval.dtype)), why) for aval, why
                    in saved_residuals(fn, *args)]

        # the custom_vjp itself: its residuals ARE the named values
        q, k, v = operands
        head_major = (q.transpose(0, 2, 1, 3).reshape(4, 2, 32, 24),
                      k.transpose(0, 2, 1, 3).reshape(4, 32, 24),
                      v.transpose(0, 2, 1, 3).reshape(4, 32, 16))
        off = jnp.zeros((1,), jnp.int32)
        flash = pallas_ops._flash_fn(True, pallas_ops.Tiles(16, 16, 2, 16, 32), None,
                                     True, None, 2, 24 ** -0.5)
        inner = jax.checkpoint(lambda *a: flash(*a, off, off),
                               policy=decoder._SAVE_NAMED)
        by_name = [what for what, why in named(inner, *head_major)
                   if f"named '{pallas_ops.SAVED}'" in why]
        assert sorted(by_name) == [lse, out]
        # through the public call (a jit of its own) they come out of
        # that call; under the routes-only policy nothing does
        for policy, kept in ((decoder._SAVE_NAMED, [lse, out]),
                             (self._policy(self.ROUTES_ONLY), [])):
            got = [what for what, why in named(
                jax.checkpoint(self._loss, policy=policy), *operands)
                if "from the argument" not in why]
            assert sorted(got) == kept

    @pytest.mark.parametrize("scanned", [False, True])
    def test_three_kernels_a_call_where_routes_only_holds_four(
            self, operands, count_eqns, scanned):
        from tpudl import pallas_ops

        def layers(policy):
            layer = jax.checkpoint(self._loss, policy=policy)
            if not scanned:
                return layer

            def twice(q, k, v):   # a scanned run of two layers
                return jax.lax.scan(
                    lambda total, scale: (total + layer(q * scale, k, v),
                                          None),
                    jnp.float32(0), jnp.asarray([1, 2], q.dtype))[0]
            return twice

        def grads(names):
            return jax.grad(layers(self._policy(names)), (0, 1, 2))

        kept = (*self.ROUTES_ONLY, pallas_ops.SAVED)
        counts = {names: count_eqns(jax.make_jaxpr(grads(names))(*operands),
                                    "pallas_call")
                  for names in (self.ROUTES_ONLY, kept)}
        assert counts == {self.ROUTES_ONLY: 3, kept: 2}
        for got, want in zip(jax.jit(grads(kept))(*operands),
                             jax.jit(grads(self.ROUTES_ONLY))(*operands)):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("return_lse", [False, True])
    def test_without_a_checkpoint_the_program_is_the_parents(
            self, operands, count_eqns, return_lse):
        """Forward only, the jaxpr holds no name at all (the primal call
        never enters the ``fwd`` rule); under a gradient the parent's
        two kernels and two ``name`` equations that compile to
        nothing; and so with ``return_lse`` and traced offsets, as the
        ring calls it."""
        def f(q, k, v, offset):
            got = flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16,
                interpret=True, return_lse=return_lse, q_offset=offset,
                k_offset=offset)
            return sum(x.astype(jnp.float32).sum()
                       for x in (got if return_lse else [got]))

        args = (*operands, jnp.int32(0))
        forward = jax.make_jaxpr(f)(*args)
        assert count_eqns(forward, "pallas_call") == 1
        assert count_eqns(forward, "name") == 0
        backward = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(*args)
        assert count_eqns(backward, "pallas_call") == 2
        assert count_eqns(backward, "name") == 2
        compiled = jax.jit(jax.grad(f, (0, 1, 2))).lower(
            *args).compile().as_text()
        assert "pallas.flash.saved" not in compiled


class TestSharedKeyAndLayout:
    """Operands as their producers wrote them: a block of key columns that
    every head of a batch entry shares comes as an operand of its own
    (``k_shared``: latent attention's ONE rotated key) and is read in
    place, and ``layout="bhsd"`` takes head-major operands without a
    copy. Both against the call they replace: the shared columns
    broadcast over the heads and concatenated to each head's own, in
    sequence-major order."""

    NOPE, ROPE, D_V = 16, 8, 16

    @staticmethod
    def _call(q, k, v, **kw):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True, return_lse=True,
                               precision="highest", **kw)

    @pytest.mark.parametrize("heads, kv_heads", [(4, 4), (4, 2)])
    @pytest.mark.parametrize("length", [48, 40])      # on and off a tile
    @pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                            ("bfloat16", 2e-2)])
    def test_against_the_concatenated_sequence_major_call(
            self, rng, dtype, tol, length, heads, kv_heads):
        """Three tiles a side at 16 x 16: interior, crossing and dead
        ones (and a padded last one at 40). Output, lse, dq, dk of the
        head's own columns, dv; the shared key's cotangent is the head
        sum of the concatenated call's last columns. The head-major
        entry equals the sequence-major one to the bit."""
        from tpudl.pallas_ops import tile_counts

        assert min(tile_counts(48, 48, 16, 16, causal=True).values()) == 3
        b, (nope, rope, d_v) = 2, (self.NOPE, self.ROPE, self.D_V)
        q = jnp.asarray(rng.normal(size=(b, length, heads, nope + rope)),
                        dtype)
        k_own = jnp.asarray(rng.normal(size=(b, length, kv_heads, nope)),
                            dtype)
        k_shared = jnp.asarray(rng.normal(size=(b, length, rope)), dtype)
        v = jnp.asarray(rng.normal(size=(b, length, kv_heads, d_v)), dtype)
        shape = (b, length, heads, d_v)

        def concatenated(q, k_own, k_shared, v):
            everywhere = jnp.broadcast_to(
                k_shared[:, :, None], (b, length, kv_heads, rope))
            return self._call(q, jnp.concatenate([k_own, everywhere], -1), v)

        def shared(q, k_own, k_shared, v):
            return self._call(q, k_own, v, k_shared=k_shared)

        def head_major(q, k_own, k_shared, v):
            out, lse = self._call(
                *(x.transpose(0, 2, 1, 3) for x in (q, k_own, v)),
                k_shared=k_shared, layout="bhsd")
            assert out.shape == (b, heads, length, d_v)
            assert lse.shape == (b, heads, length)
            return out.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)

        def both(fn):
            return jax.jit(lambda *a: (fn(*a), jax.grad(_weighted(
                fn, shape, heads), (0, 1, 2, 3))(*a)))(q, k_own, k_shared, v)

        def close(got, want, limit):
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            assert got.shape == want.shape
            assert np.linalg.norm(got - want) <= limit * max(
                np.linalg.norm(want), 1e-30)

        (want_out, want_lse), want = both(concatenated)
        (out, lse), got = both(shared)
        assert out.dtype == q.dtype and lse.dtype == jnp.float32
        close(out, want_out, tol)
        close(lse, want_lse, tol)
        for g, w_, operand in zip(got, want, (q, k_own, k_shared, v)):
            assert g.shape == operand.shape and g.dtype == operand.dtype
            close(g, w_, tol)
        (hm_out, hm_lse), hm = both(head_major)
        for g, w_ in zip((hm_out, hm_lse, *hm), (out, lse, *got)):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w_, np.float32))

    def test_the_counter_and_the_gauge_at_the_cells_shape(self):
        """``pallas.flash.shared_key`` and ``.head_dim_shared`` per call
        (so per TRACE): 1 / 64 with latent attention's operands, 0 / 0 for
        the same heads with whole keys; a mismatch is named."""
        from tpudl import obs

        def snap():
            got = {n: m["value"]
                   for n, m in obs.snapshot("pallas.flash.").items()}
            return (got.get("pallas.flash.shared_key", 0),
                    got.get("pallas.flash.launches", 0),
                    got.get("pallas.flash.head_dim_shared"))

        def shapes(*widths):
            return [jax.ShapeDtypeStruct((4, 32, 8192, w), jnp.bfloat16)
                    for w in widths]

        k_r = jax.ShapeDtypeStruct((4, 8192, 64), jnp.bfloat16)

        def call(q, k, v, k_shared=None):
            return flash_attention(q, k, v, causal=True, interpret=False,
                                   layout="bhsd", k_shared=k_shared)

        engaged, launches, _ = snap()
        out = jax.eval_shape(call, *shapes(192, 128, 128), k_r)
        assert out.shape == (4, 32, 8192, 128)
        assert snap() == (engaged + 1, launches + 1, 64)
        jax.eval_shape(call, *shapes(192, 192, 128))
        assert snap() == (engaged + 1, launches + 2, 0)
        tiles = {n: m["value"] for n, m in obs.snapshot(
            "pallas.flash.").items()}
        assert (tiles["pallas.flash.head_dim_qk"],
                tiles["pallas.flash.head_dim_v"],
                tiles["pallas.flash.heads_a_step"]) == (192, 128, 1)
        with pytest.raises(ValueError, match="queries 192 wide against "
                                             "keys 256 wide"):
            jax.eval_shape(call, *shapes(192, 192, 128), k_r)
        with pytest.raises(ValueError, match="a shared key of shape"):
            jax.eval_shape(call, *shapes(192, 128, 128),
                           jax.ShapeDtypeStruct((4, 4096, 64), jnp.bfloat16))
        with pytest.raises(ValueError, match="layout 'bsdh'"):
            flash_attention(*shapes(192, 192, 128), layout="bsdh")

    def test_without_either_the_program_is_the_parents(self):
        """No shared key and no layout: forward and gradient trace, jaxpr
        for jaxpr (kernels, specs, grids and what surrounds them), what
        the commit before this interface traced, at toy shapes and at the
        two grouped-query cells'. The digest is of that commit's text,
        taken with this very loop; a deliberate change to the kernels
        replaces it (PR 39 did: the one backward kernel)."""
        import hashlib

        text = []
        for b, s_q, s_k, h, h_kv, d, d_v, kw in [
                (2, 48, 48, 4, 2, 16, 16,
                 dict(causal=True, block_q=16, block_k=16)),
                (2, 40, 56, 4, 4, 24, 16,
                 dict(causal=True, block_q=16, block_k=16)),
                (1, 64, 64, 2, 2, 64, 64, dict(causal=False)),
                (4, 8192, 8192, 32, 8, 64, 64,
                 dict(causal=True, interpret=False)),
                (4, 8192, 8192, 32, 2, 128, 128,
                 dict(causal=True, interpret=False))]:
            q = jax.ShapeDtypeStruct((b, s_q, h, d), jnp.bfloat16)
            k = jax.ShapeDtypeStruct((b, s_k, h_kv, d), jnp.bfloat16)
            v = jax.ShapeDtypeStruct((b, s_k, h_kv, d_v), jnp.bfloat16)

            def loss(q, k, v):
                out, lse = flash_attention(q, k, v, return_lse=True, **kw)
                return out.astype(jnp.float32).sum() + lse.sum()

            text.append(str(jax.make_jaxpr(
                lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)))
            text.append(str(jax.make_jaxpr(
                jax.grad(loss, (0, 1, 2)))(q, k, v)))
        assert hashlib.sha256("\n".join(text).encode()).hexdigest() == (
            "3ae1916243467a57912a48c91a1711a11693e85974f269c08fec32398ccf2631")

    def test_the_shared_key_is_a_residual_and_the_forward_runs_once(
            self, rng, count_eqns):
        """Under the decoder's policy the gradient of a call with a shared
        key holds two kernels, as one without does: the forward's output
        and row statistics are saved, ``q``, both keys and ``v`` recomputed."""
        from tpudl.zoo import decoder

        b, s, h = 2, 32, 4
        q = jnp.asarray(rng.normal(size=(b, h, s, 24)), jnp.bfloat16)
        k = v = jnp.asarray(rng.normal(size=(b, h, s, 16)), jnp.bfloat16)
        k_r = jnp.asarray(rng.normal(size=(b, s, 8)), jnp.bfloat16)

        def loss(q, k, k_r, v):
            return flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16, interpret=True,
                layout="bhsd", k_shared=k_r).astype(jnp.float32).sum()

        grads = jax.grad(jax.checkpoint(loss, policy=decoder._SAVE_NAMED),
                         (0, 1, 2, 3))
        assert count_eqns(jax.make_jaxpr(grads)(q, k, k_r, v),
                          "pallas_call") == 2


class TestOneBackwardKernel:
    """The backward is ONE kernel: every live score tile formed once for
    dq, dk and dv, dq held in VMEM for a span of Q rows while the K tiles
    pass. The span is derived from the shapes and ``_VMEM_ASK_MAX``
    (never asked for); a K/V head whose cotangents leave in parts (more
    spans than one, more rows of heads than one) gets them summed in
    float32 outside."""

    @staticmethod
    def _gauges():
        from tpudl import obs

        snap = obs.snapshot("pallas.flash.dq_")
        return (snap["pallas.flash.dq_span"]["value"],
                snap["pallas.flash.dq_spans"]["value"])

    def _spans(self, rng, monkeypatch, spans):
        """Six Q tiles swept as ``spans`` spans against all K tiles (a
        padded K length, dead tiles above the diagonal, a lse cotangent):
        the gradients of the one span that the shapes derive, to float32
        round-off. The span is forced through the derivation's own
        input."""
        from tpudl import pallas_ops

        heads, kv_heads, d, block = 4, 2, 24, 16
        q = jnp.asarray(rng.normal(size=(2, 96, heads, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 72, kv_heads, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 72, kv_heads, 16)), jnp.float32)

        def grads():
            loss = _weighted(lambda *a: flash_attention(
                *a, causal=True, q_offset=8, block_q=block, block_k=block,
                interpret=True, return_lse=True), (2, 96, heads, 16), heads)
            return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)

        want = grads()
        assert self._gauges() == (96, 1)
        need = pallas_ops._vmem_need(2, block, block, d, 4)
        room = 6 // spans * pallas_ops._dq_bytes(2, block, d)
        monkeypatch.setattr(pallas_ops, "_VMEM_ASK_MAX", 2 * need + room)
        got = grads()
        assert self._gauges() == (96 // spans, spans)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        for g, w_ in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-5)

    def _group_of_16(self, rng):
        """The hybrid cell's heads at toy size: 16 query heads over ONE
        K/V head, four a grid step, so four rows' float32 parts of dk and
        dv are summed outside the kernel."""
        q = jnp.asarray(rng.normal(size=(1, 48, 32, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(1, 48, 2, 16)), jnp.float32)
                for _ in range(2))
        got = jax.value_and_grad(_weighted(lambda *a: flash_attention(
            *a, causal=True, block_q=16, block_k=16, interpret=True,
            return_lse=True), q.shape, 32), (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(_weighted(
            lambda *a: _dense(*a, causal=True), q.shape, 32),
            (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w_ in zip(got[1], want[1]):
            assert g.shape == w_.shape and g.dtype == w_.dtype
            np.testing.assert_allclose(g, w_, rtol=2e-5, atol=2e-5)
        text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
            *a, causal=True, block_q=16, block_k=16,
            interpret=True).sum(), (0, 1, 2)))(q, k, v))
        assert text.count("pallas_call") == 2
        # the parts: 8 rows of four heads, float32, for 2 K/V heads
        assert "f32[8,48,16]" in text

    def _gauges_at(self, heads, kv_heads, d_qk, d_v):
        """Per TRACE at a cell's shape (nothing runs): the whole sequence
        is one span."""
        q = jax.ShapeDtypeStruct((4, 8192, heads, d_qk), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((4, 8192, kv_heads, d_qk), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((4, 8192, kv_heads, d_v), jnp.bfloat16)
        jax.eval_shape(lambda *a: flash_attention(
            *a, causal=True, interpret=False), q, k, v)
        assert self._gauges() == (8192, 1)

    @pytest.mark.parametrize("case", [
        "two spans", "three spans", "a group of 16 at four heads a step",
        "gauges: lfm2-8b-a1b-ep4", "gauges: nemotron-twotower-30b-a3b-ep16",
        "gauges: joyai-llm-flash-ep32"])
    def test_one_kernel_in_spans_in_parts_and_counted(self, rng,
                                                      monkeypatch, case):
        if case.endswith("spans"):
            self._spans(rng, monkeypatch, {"two": 2, "three": 3}[
                case.split()[0]])
        elif case.startswith("a group"):
            self._group_of_16(rng)
        else:
            self._gauges_at(*{"lfm2-8b-a1b-ep4": (32, 8, 64, 64),
                              "nemotron-twotower-30b-a3b-ep16":
                                  (32, 2, 128, 128),
                              "joyai-llm-flash-ep32": (32, 32, 192, 128)}[
                                  case.split()[1]])


# ---- the selective scan ----------------------------------------------------
def _scan_over_time(x, dt, a, b, c):
    """``H_t = exp(Δ_t A) H_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = H_t C_t``, one
    position after the other, float32."""
    per = x.shape[1] // b.shape[1]

    def step(h, now):
        xt, dtt, bt, ct = now
        bt, ct = jnp.repeat(bt, per, 0), jnp.repeat(ct, per, 0)   # [H, N]
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + dtt[:, None, None] * xt[:, :, None] * bt[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, ct)

    zero = jnp.zeros((*x.shape[1:], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (x, dt, b, c))[1]


def _scan_operands(seed, length, groups, per, width, state):
    rng = np.random.default_rng(seed)
    heads = groups * per
    return (jnp.asarray(rng.standard_normal((length, heads, width)),
                        jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.5, (length, heads)), jnp.float32),
            jnp.asarray(-rng.uniform(1, 16, heads), jnp.float32),
            jnp.asarray(rng.standard_normal((length, groups, state)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((length, groups, state)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((length, heads, width)),
                        jnp.float32))


def _rel(a, b):
    a, b = (np.asarray(v, np.float32).ravel() for v in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


class TestSelectiveScan:
    """``pallas_ops.ssd_scan``, forward and backward kernels through the
    interpreter, against a loop over time."""

    @pytest.mark.parametrize("chunk, lengths", [(16, (48, 37)),
                                                (128, (256, 200))])
    @pytest.mark.parametrize("divides", [True, False])
    @pytest.mark.parametrize("groups", [1, 8])
    @pytest.mark.parametrize("per", [1, 8])
    def test_result_and_five_gradients_against_a_loop_over_time(
            self, chunk, lengths, divides, groups, per):
        """Lengths the chunk does and does not divide (padded with Δ = 0),
        one and eight groups, one and eight heads a group; eight heads of
        64 are worked on two a lane tile, a lone head of 12 as it is."""
        from tpudl.pallas_ops import scan_tiles, ssd_scan

        width, length = (64 if per == 8 else 12), lengths[not divides]
        assert scan_tiles(length, per, width, chunk).heads_a_tile == (
            2 if per == 8 else 1)
        *ops, w = _scan_operands(chunk + length, length, groups, per, width, 8)
        with jax.default_matmul_precision("highest"):
            got = ssd_scan(*ops, chunk=chunk)
            want = _scan_over_time(*ops)
            assert got.shape == want.shape and got.dtype == jnp.float32
            assert _rel(got, want) < 1e-5
            g_got = jax.grad(lambda *v: (ssd_scan(*v, chunk=chunk) * w).sum(),
                             (0, 1, 2, 3, 4))(*ops)
            g_want = jax.grad(lambda *v: (_scan_over_time(*v) * w).sum(),
                              (0, 1, 2, 3, 4))(*ops)
        for name, mine, theirs in zip("x dt a b c".split(), g_got, g_want):
            assert mine.shape == theirs.shape and mine.dtype == theirs.dtype
            # a head's one number for `a` is a sum over every position
            # that cancels: float32 noise of either side shows there
            assert _rel(mine, theirs) < (3e-4 if name == "a" else 3e-5), name

    def test_bfloat16_operands_against_the_float32_loop(self):
        """The products' operands in bfloat16 (as the cell trains), the
        recurrence held in float32: within the limit tests/test_lm_hybrid.py
        holds a group's gradient to, and the cotangents in their operands'
        dtypes."""
        from tpudl.pallas_ops import ssd_scan

        x, dt, a, b, c, w = _scan_operands(5, 300, 2, 4, 32, 16)
        low = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
               c.astype(jnp.bfloat16))
        got = ssd_scan(*low, chunk=128)
        g_got = jax.grad(lambda *v: (ssd_scan(*v, chunk=128) * w).sum(),
                         (0, 1, 2, 3, 4))(*low)
        with jax.default_matmul_precision("highest"):
            want = _scan_over_time(x, dt, a, b, c)
            g_want = jax.grad(lambda *v: (_scan_over_time(*v) * w).sum(),
                              (0, 1, 2, 3, 4))(x, dt, a, b, c)
        assert got.dtype == jnp.float32 and _rel(got, want) < 0.02
        for mine, mine_of, theirs in zip(g_got, low, g_want):
            assert mine.dtype == mine_of.dtype
            assert _rel(mine, theirs) < 0.065

    def test_the_recurrence_is_held_in_the_dtype_of_a(self):
        """``a``'s dtype is what Δ·A, its running sum, the decays and the
        carried state are held in: in bfloat16 a chunk of 128 log-decays
        keeps 8 bits and the result is off by percents, with float32
        operands everywhere else; the gradient of ``a`` comes back in its
        dtype."""
        from tpudl.pallas_ops import ssd_scan

        x, dt, a, b, c, w = _scan_operands(6, 256, 1, 2, 16, 8)
        with jax.default_matmul_precision("highest"):
            want = _scan_over_time(x, dt, a, b, c)
            sound = _rel(ssd_scan(x, dt, a, b, c, chunk=128), want)
            held_low = _rel(ssd_scan(x, dt, a.astype(jnp.bfloat16), b, c,
                                     chunk=128), want)
            da = jax.grad(lambda v: (ssd_scan(x, dt, v, b, c, chunk=128)
                                     * w).sum())(a.astype(jnp.bfloat16))
        assert sound < 1e-5 and held_low > 100 * sound and held_low > 3e-3
        assert da.dtype == jnp.bfloat16 and da.shape == a.shape

    def test_a_batch_of_sequences_through_vmap(self):
        """``jax.vmap`` over sequences (the decays shared) is each sequence
        alone: the carried state starts at zero for every one."""
        from tpudl.pallas_ops import ssd_scan

        x, dt, a, b, c, w = _scan_operands(7, 2 * 40, 2, 2, 8, 4)
        two = [v.reshape(2, 40, *v.shape[1:]) for v in (x, dt, b, c, w)]

        def loss(x, dt, a, b, c, w):
            y = jax.vmap(lambda x, dt, b, c: ssd_scan(x, dt, a, b, c,
                                                      chunk=16))(x, dt, b, c)
            return (y * w).sum(), y

        with jax.default_matmul_precision("highest"):
            (_, y), grads = jax.value_and_grad(loss, (0, 2), has_aux=True)(
                two[0], two[1], a, two[2], two[3], two[4])
            for i in range(2):
                alone = [v[i] for v in two]
                assert _rel(y[i], _scan_over_time(
                    alone[0], alone[1], a, alone[2], alone[3])) < 1e-5
            want = jax.grad(lambda x, a: sum(
                (_scan_over_time(x[i], two[1][i], a, two[2][i], two[3][i])
                 * two[4][i]).sum() for i in range(2)), (0, 1))(two[0], a)
        for mine, theirs in zip(grads, want):
            assert _rel(mine, theirs) < 3e-5

    @pytest.mark.parametrize("shape, tiles", [
        # the hybrid cell's: 64 chunks of 128 eight a grid step, 64-wide
        # heads two a lane tile
        ((8192, 8, 64, 128), (128, 8, 2)),
        ((8192, 8, 128, 256), (256, 4, 1)),     # a head as wide as a tile
        ((300, 8, 64, 128), (128, 3, 2)),       # 3 chunks: one step
        ((1664, 8, 64, 128), (128, 1, 2)),      # 13 chunks: a prime
        ((4096, 3, 64, 128), (128, 8, 1)),      # three heads: no pairs
        ((4096, 8, 48, 128), (128, 8, 1)),      # 48 does not divide 128
        ((4096, 8, 32, 64), (64, 16, 4)),       # four heads a tile
    ])
    def test_the_shapes_decide_the_tiles(self, shape, tiles):
        from tpudl.pallas_ops import scan_tiles

        assert tuple(scan_tiles(*shape)) == tiles

    def test_counters_per_trace_and_a_wrong_shape_named(self):
        from tpudl import obs
        from tpudl.pallas_ops import ssd_scan

        def read():
            snap = obs.snapshot("pallas.ssd.")
            return {k[len("pallas.ssd."):]: v["value"]
                    for k, v in snap.items()}

        x, dt, a, b, c, _ = _scan_operands(8, 64, 2, 4, 8, 4)
        before = read().get("launches", 0)
        fn = jax.jit(lambda *v: ssd_scan(*v, chunk=16))
        out = jax.eval_shape(fn, x, dt, a, b, c)        # nothing has to run
        assert out.shape == x.shape and out.dtype == jnp.float32
        after = read()
        assert after["launches"] - before == 1
        assert (after["chunk"], after["heads_a_step"],
                after["states_saved"]) == (16, 4, 1)
        with pytest.raises(ValueError, match="8 heads .* over 3 groups"):
            ssd_scan(x, dt, a, b[:, :1].repeat(3, 1), c, chunk=16)
        with pytest.raises(ValueError, match="8 heads"):
            ssd_scan(x, dt[:, :4], a, b, c, chunk=16)


# ---- grouped matrix products (the routed experts) --------------------------
class TestGroupedDot:
    """``pallas_ops.grouped_dot`` in interpret mode at small shapes, against
    ``jax.lax.ragged_dot`` and its ``jax.vjp`` on the held prefix. Rows past
    the prefix hold NaN in the rows and in the cotangent, as the routed
    layer's buffers may: no held output and no weights' gradient may see
    them."""

    M, K, N, E = 1024, 128, 256, 4

    @staticmethod
    def _rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)

    @pytest.mark.parametrize("sizes, pair", [
        ((200, 120, 74, 400), False),  # a prefix, NaN past it
        ((300, 0, 600, 80), True),     # groups cross the 256-row tiles' edges
        ((0, 0, 77, 0), False),        # empty groups around the one held
        ((0, 0, 0, 0), True),          # nothing held at all
        ((256, 256, 256, 256), True),  # every row held, on the tiles' edges
    ])
    def test_held_rows_and_both_gradients_against_ragged_dot(self, sizes,
                                                             pair):
        from tpudl.pallas_ops import grouped_dot

        rng = np.random.default_rng(sum(sizes))
        m, k, n, e = self.M, self.K, self.N, self.E
        sizes = jnp.asarray(sizes, jnp.int32)
        held = int(sizes.sum())
        past = (np.arange(m) >= held)[:, None]
        x = np.where(past, np.nan, rng.standard_normal((m, k)))
        g = np.where(past[None], np.nan, rng.standard_normal((2, m, n)))
        x, g = jnp.asarray(x, jnp.float32), jnp.asarray(g, jnp.float32)
        ws = tuple(jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
                   for _ in range(2 if pair else 1))
        clean = jnp.where(past, 0.0, x)
        cots = tuple(jnp.where(past, 0.0, g[i]) for i in range(len(ws)))

        def mine(a, *w):
            return grouped_dot(a, w if pair else w[0], sizes)

        def theirs(a, *w):
            out = tuple(jax.lax.ragged_dot(a, v, sizes) for v in w)
            return out if pair else out[0]

        out, pull = jax.vjp(mine, x, *ws)
        want, want_pull = jax.vjp(theirs, clean, *ws)
        got_grads = pull(tuple(g[:len(ws)]) if pair else g[0])
        want_grads = want_pull(cots if pair else cots[0])
        for o, w in zip(out if pair else [out], want if pair else [want]):
            assert o.shape == (m, n) and o.dtype == jnp.float32
            assert self._rel(o[:held], w[:held]) < 1e-6
        # d_lhs: the row cotangents of a pair summed inside the kernel
        assert self._rel(got_grads[0][:held], want_grads[0][:held]) < 1e-5
        for d_w, want_w in zip(got_grads[1:], want_grads[1:]):
            assert bool(jnp.isfinite(d_w).all())
            assert self._rel(d_w, want_w) < 1e-5 or not held
            assert not held or np.abs(np.asarray(want_w)).max() > 0
            # an empty group's weights get an exact zero
            for i in np.flatnonzero(np.asarray(sizes) == 0):
                assert not np.asarray(d_w[i]).any()

    def test_the_fused_row_cotangent_is_the_sum_of_two_calls(self):
        from tpudl.pallas_ops import grouped_dot

        rng = np.random.default_rng(5)
        sizes = jnp.asarray((180, 420, 0, 66), jnp.int32)
        x = jnp.asarray(rng.standard_normal((self.M, self.K)), jnp.float32)
        w1, w3 = (jnp.asarray(rng.standard_normal(
            (self.E, self.K, self.N)), jnp.float32) for _ in range(2))
        g1, g3 = (jnp.asarray(rng.standard_normal((self.M, self.N)),
                              jnp.float32) for _ in range(2))
        _, pull = jax.vjp(lambda a: grouped_dot(a, (w1, w3), sizes), x)
        (fused,) = pull((g1, g3))
        one = [jax.vjp(lambda a: grouped_dot(a, w, sizes), x)[1](g)[0]
               for w, g in ((w1, g1), (w3, g3))]
        held = int(sizes.sum())
        assert self._rel(fused[:held], (one[0] + one[1])[:held]) < 1e-6
        # one kernel for the rows' cotangent, one for each weight
        text = str(jax.make_jaxpr(lambda a: pull((g1, g3)))(x))
        assert text.count("name=grouped_dot_rows") == 1

    @pytest.mark.parametrize("shape, tiles", [
        # the three LM cells': joyai-llm-flash-ep32 (a sequence's 65,536
        # pairs, gated), nemotron-twotower (196,608, relu2), lfm2 (131,072,
        # gated): K and N whole
        ((65536, 2048, 768, 2), (256, 2048, 768)),
        ((196608, 2816, 2048, 1), (256, 2816, 2048)),
        ((131072, 2048, 1792, 2), (256, 2048, 1792)),
        ((200, 256, 512, 1), (208, 256, 512)),   # a buffer under one tile
        ((64, 64, 64, 2), (64, 64, 64)),         # a toy buffer
        # widths whose blocks outgrow the VMEM a step may ask: N is cut first
        ((8192, 8192, 8192, 2), (256, 8192, 512)),
    ])
    def test_the_tile_rule_from_the_shapes(self, shape, tiles):
        from tpudl.pallas_ops import _GROUP_VMEM, _group_vmem, group_tiles

        m, k, n, products = shape
        got = group_tiles(m, k, n, products=products)
        assert tuple(got) == tiles
        assert _group_vmem(got, products, 2) <= _GROUP_VMEM

    @pytest.mark.parametrize("sizes, block, visited", [
        ((150, 0, 300, 40), 128, 2 + 3 + 1),
        ((128, 128, 0, 0), 128, 2),
        ((0, 0, 0, 0), 128, 0),
        ((1000, 1, 1), 256, 4 + 1 + 1),
    ])
    def test_the_grid_visits_only_the_held_groups_tiles(self, sizes, block,
                                                        visited):
        from tpudl.pallas_ops import _group_steps, tiles_visited

        sizes = jnp.asarray(sizes, jnp.int32)
        held, m_tiles = int(sizes.sum()), 2048 // block
        assert tiles_visited(sizes, block) == visited
        offsets, groups, tiles, steps = _group_steps(sizes, m_tiles, block,
                                                     False)
        assert int(steps) == visited
        groups, tiles = np.asarray(groups)[:visited], np.asarray(tiles)[
            :visited]
        # in order, no tile past the prefix, each group's rows covered
        assert (np.diff(tiles) >= 0).all() and (np.diff(groups) >= 0).all()
        assert not visited or tiles.max() <= (held - 1) // block
        for g, size in enumerate(np.asarray(sizes)):
            start = int(offsets[g])
            mine = tiles[groups == g]
            assert list(mine) == list(range(
                start // block, (start + size - 1) // block + 1)) if size \
                else not len(mine)
        # the weights' kernel visits an empty group once, to write zeros
        _, _, _, every = _group_steps(sizes, m_tiles, block, True)
        assert int(every) == visited + int((np.asarray(sizes) == 0).sum())

    def test_gauges_per_trace_and_a_wrong_shape_named(self):
        from tpudl import obs
        from tpudl.pallas_ops import grouped_dot

        x = jax.ShapeDtypeStruct((1000, 256), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((8, 256, 384), jnp.bfloat16)
        sizes = jax.ShapeDtypeStruct((8,), jnp.int32)
        out = jax.eval_shape(lambda a, b, s: grouped_dot(
            a, (b, b), s), x, w, sizes)
        assert [o.shape for o in out] == [(1000, 384)] * 2
        assert out[0].dtype == jnp.bfloat16
        snap = obs.snapshot("pallas.gmm.")
        assert [snap[f"pallas.gmm.{a}"]["value"] for a in (
            "block_m", "block_k", "block_n")] == [256, 256, 384]
        with pytest.raises(ValueError, match="weights"):
            grouped_dot(jnp.ones((8, 4)), jnp.ones((2, 5, 3)),
                        jnp.ones((2,), jnp.int32))
