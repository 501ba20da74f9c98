"""Space-to-depth stem: exact-equivalence oracle tests.

The transform (tpudl/zoo/s2d.py) re-expresses the InceptionV3 stem in
block-2 s2d form for MXU lane occupancy (the July 2026 profile's
stem convs).
It must be numerically a REFORMULATION, not an approximation: every
test here checks against the canonical stem/model at fp32 noise
tolerance, including the edge machinery (garbage-slot masking where
chained VALID convs over-ran the true extent, and the block-aligned
spelling of SAME's one-pixel pad).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpudl.zoo import nn
from tpudl.zoo.s2d import (depth_to_space, inception_stem_s2d,
                           space_to_depth, stride2_valid_kernel,
                           unit_stride_kernel)


def _bn(c, rng):
    return {"beta": rng.normal(size=c).astype(np.float32) * 0.1,
            "moving_mean": rng.normal(size=c).astype(np.float32) * 0.1,
            "moving_var": (1 + rng.uniform(size=c)).astype(np.float32)}


def bn_apply(t, p):
    return nn.batch_norm(t, p, train=False, epsilon=1e-3)


class TestPrimitives:
    def test_s2d_roundtrip(self):
        x = np.arange(2 * 8 * 6 * 3, dtype=np.float32).reshape(2, 8, 6, 3)
        np.testing.assert_array_equal(
            np.asarray(depth_to_space(space_to_depth(jnp.asarray(x)))), x)

    def test_s2d_channel_layout(self):
        """Channel order is (row-in-block, col-in-block) major, original
        channel minor — the order tile_bn_params and the kernel
        transforms assume."""
        x = np.zeros((1, 4, 4, 2), np.float32)
        x[0, 1, 0, 1] = 7.0  # block (0,0), in-block (ir=1, ic=0), c=1
        y = np.asarray(space_to_depth(jnp.asarray(x)))
        assert y[0, 0, 0, (1 * 2 + 0) * 2 + 1] == 7.0
        assert y.sum() == 7.0

    def test_stride2_kernel_equivalence(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(2, 11, 9, 3)).astype(np.float32)
        w = rng.normal(size=(3, 3, 3, 5)).astype(np.float32)
        ref = nn.conv2d(jnp.asarray(x), jnp.asarray(w), strides=(2, 2),
                        padding="VALID")
        h1, w1 = (11 - 3) // 2 + 1, (9 - 3) // 2 + 1
        xp = jnp.pad(jnp.asarray(x),
                     ((0, 0), (0, 2 * h1 + 2 - 11), (0, 2 * w1 + 2 - 9),
                      (0, 0)))
        got = nn.conv2d(space_to_depth(xp), stride2_valid_kernel(w),
                        strides=(1, 1), padding="VALID")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_unit_stride_kernel_equivalence(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(2, 10, 8, 4)).astype(np.float32)
        w = rng.normal(size=(3, 3, 4, 6)).astype(np.float32)
        ref = nn.conv2d(jnp.asarray(x), jnp.asarray(w), strides=(1, 1),
                        padding="VALID")                    # [2, 8, 6, 6]
        got_y = nn.conv2d(space_to_depth(jnp.asarray(x)),
                          unit_stride_kernel(w), strides=(1, 1),
                          padding="VALID")                  # s2d output
        got = depth_to_space(got_y)[:, :8, :6]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestStem:
    @pytest.mark.parametrize("h,w", [(19, 19), (31, 27), (75, 75)])
    def test_full_stem_matches_canonical(self, h, w):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(2, h, w, 3)).astype(np.float32)
        k1 = rng.normal(size=(3, 3, 3, 32)).astype(np.float32) * 0.1
        k2 = rng.normal(size=(3, 3, 32, 32)).astype(np.float32) * 0.1
        k3 = rng.normal(size=(3, 3, 32, 64)).astype(np.float32) * 0.1
        b1, b2, b3 = _bn(32, rng), _bn(32, rng), _bn(64, rng)

        ref = jnp.asarray(x)
        ref = nn.relu(bn_apply(nn.conv2d(ref, k1, strides=(2, 2),
                                         padding="VALID"), b1))
        ref = nn.relu(bn_apply(nn.conv2d(ref, k2, strides=(1, 1),
                                         padding="VALID"), b2))
        ref = nn.relu(bn_apply(nn.conv2d(ref, k3, strides=(1, 1),
                                         padding="SAME"), b3))

        got = inception_stem_s2d(
            jnp.asarray(x), {"kernel": k1}, b1, {"kernel": k2}, b2,
            {"kernel": k3}, b3, bn_apply=bn_apply, relu=nn.relu)
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_even_size_rejected(self):
        with pytest.raises(ValueError, match="odd"):
            inception_stem_s2d(
                jnp.zeros((1, 20, 20, 3)), {}, {}, {}, {}, {}, {},
                bn_apply=bn_apply, relu=nn.relu)


class TestModelIntegration:
    def test_inception_features_match_both_stems(self, monkeypatch):
        """The judged path end to end: InceptionV3 featurize output is
        identical (fp32 noise) with the s2d stem on and off, on the
        real 299×299 geometry."""
        from tpudl.zoo.registry import getKerasApplicationModel

        model = getKerasApplicationModel("InceptionV3")
        params = model.init(0)
        x = np.random.default_rng(4).normal(
            size=(2, 299, 299, 3)).astype(np.float32)
        monkeypatch.setenv("TPUDL_S2D_STEM", "0")
        ref = np.asarray(model.featurize(params, jnp.asarray(x)))
        monkeypatch.setenv("TPUDL_S2D_STEM", "1")
        got = np.asarray(model.featurize(params, jnp.asarray(x)))
        assert got.shape == ref.shape == (2, 2048)
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)

    def test_init_and_train_modes_untouched(self, monkeypatch):
        """Param creation and train-mode BN statistics must go through
        the canonical stem regardless of the flag (the s2d layout's
        tiled channels would skew per-channel batch stats)."""
        from tpudl.zoo.core import Store
        from tpudl.zoo import inception_v3

        monkeypatch.setenv("TPUDL_S2D_STEM", "1")
        s = Store(rng=np.random.default_rng(0))
        x = jnp.zeros((1, 75, 75, 3))
        inception_v3.build(s, x, include_top=False, pooling="avg")
        assert s.params["conv2d"]["kernel"].shape == (3, 3, 3, 32)
        st = Store(params=s.params, train=True)
        assert not inception_v3._use_s2d_stem(st, x)
