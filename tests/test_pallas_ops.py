"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh —
identical kernel semantics; the TPU path compiles the same pallas_call).
The kernel must match the dense oracle exactly, compose across blocks
via its log-sum-exp output, and back-propagate to the oracle's gradients
through the tiled Pallas dq/dk/dv backward kernels (custom VJP from the
saved log-sum-exp — no S^2 tensor in either direction)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpudl import mesh as M
from tpudl.attention import (attention_reference, ring_attention,
                             shard_sequence)
from tpudl.pallas_ops import flash_attention


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
        dropped — in all three kernels: values AND grads match dense."""
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
