"""The decoder's ``nemotron_h`` stack (Mamba-2 mixers, attention without
positions, routed relu² experts beside a shared expert, one part a layer,
an untied head) against its plain float32 reference, at toy widths on the
CPU.

Seeded weights; float32 comparisons at 1e-5 under highest matmul
precision; bfloat16 (the precision the cell trains in) at the stated
tolerances, on the program's own routes. Three formulations of the
state-space layer meet here: the program's chunks, the reference's
quadratic form, and a loop over time."""

import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl.train import Trainer, with_compute_dtype
from tpudl.zoo import lm_blocks, moe
from tpudl.zoo.decoder import Decoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the plain reference lives with the benchmark's configuration
R = _load(os.path.join(REPO, "benchmark", "configs",
                       "nemotron-twotower-30b-a3b-ep16.py"),
          "nemotron_reference")


def rel(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


BASE = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
            ssm_state_size=16, conv_kernel=4, chunk_size=8,
            use_conv_bias=True, n_routed_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
            n_shared_experts=1, routed_scaling_factor=2.5,
            norm_topk_prob=True, n_group=1, topk_group=1,
            mlp_hidden_act="relu2", tie_word_embeddings=False,
            vocab_size=512, vocab_slice=(0, 128), experts_held=(4, 4),
            norm_eps=1e-5, rope_theta=10000, time_step_min=0.001,
            time_step_max=0.1, time_step_floor=1e-4,
            time_step_limit=[0, None])
REF = dict(top_k=3, held_first=4, head_dim=16, n_groups=2, attention_rows=8,
           ssm_rows=8)


def build(pattern, seed=3, **over):
    lm = Decoder({**BASE, "hybrid_override_pattern": pattern, **over})
    p = lm.init(seed)
    # init leaves the selection bias and the convolution's bias at zero;
    # the tests want them to matter
    rng = np.random.default_rng(seed)
    for name in p:
        if name.endswith("expert_bias") or name.endswith("conv_bias"):
            assert not np.any(p[name])
            scale = 0.02 if name.endswith("expert_bias") else 0.3
            p[name] = (scale * rng.standard_normal(p[name].shape)).astype(
                np.float32)
    return lm, p


def tokens(seed=0, shape=(2, 24)):
    return np.random.default_rng(seed).integers(
        0, 128, shape).astype(np.int32)


# ---- against the reference ------------------------------------------------
@pytest.mark.parametrize("pattern", ["M", "*", "E", "MEM*E"])
def test_logits_and_gradients_match_the_reference_in_float32(pattern):
    lm, p = build(pattern)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        assert rel(jax.jit(lm.logits)(p, ids),
                   jax.jit(lambda q: R.forward(q, ids, **REF))(p)) < 1e-5
        got_l, got = jax.jit(jax.value_and_grad(lm.loss_fn()))(p, ids)
        want_l, want = jax.jit(jax.value_and_grad(
            lambda q: R.loss(q, ids, **REF)))(p)
    assert abs(float(got_l) - float(want_l)) < 1e-5 * float(want_l)
    assert set(got) == set(want)
    for name in want:
        if name.endswith("expert_bias"):   # a buffer: no gradient reaches it
            assert not np.any(got[name]) and not np.any(want[name])
        else:
            assert rel(got[name], want[name]) < 1e-5, name


def _over_time(x, dt, a, b, c):
    """``H_t = exp(Δ_t A) H_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = H_t C_t``,
    one position after the other."""
    per = x.shape[1] // b.shape[1]

    def step(h, now):
        xt, dtt, bt, ct = now
        bt, ct = jnp.repeat(bt, per, 0), jnp.repeat(ct, per, 0)   # [H, N]
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + dtt[:, None, None] * xt[:, :, None] * bt[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, ct)

    zero = jnp.zeros((*x.shape[1:], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (x, dt, b, c))[1]


@pytest.mark.parametrize("length", [8, 16, 24, 21])
def test_chunked_scan_and_its_gradient_against_a_loop_over_time(length):
    """One, two and three chunks of 8, and a length no chunk divides
    (padded with Δ = 0); two groups of two heads."""
    rng = np.random.default_rng(length)
    x = jnp.asarray(rng.standard_normal((length, 4, 6)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (length, 4)), jnp.float32)
    a = jnp.asarray(-rng.uniform(1, 16, 4), jnp.float32)
    b = jnp.asarray(rng.standard_normal((length, 2, 5)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((length, 2, 5)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((length, 4, 6)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = lm_blocks.ssd_scan(x, dt, a, b, c, 8)
        want = _over_time(x, dt, a, b, c)
        assert got.shape == want.shape and got.dtype == jnp.float32
        assert rel(got, want) < 1e-5
        g_got = jax.grad(lambda *v: (lm_blocks.ssd_scan(*v, 8) * w).sum(),
                         (0, 1, 2, 3, 4))(x, dt, a, b, c)
        g_want = jax.grad(lambda *v: (_over_time(*v) * w).sum(),
                          (0, 1, 2, 3, 4))(x, dt, a, b, c)
    for mine, theirs in zip(g_got, g_want):
        assert rel(mine, theirs) < 1e-5


def test_the_mixer_is_causal_and_a_sequence_at_a_time():
    lm, p = build("M")
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 24, 64)).astype(np.float32)
    kw = dict(heads=8, groups=2, state=16, chunk=8, eps=1e-5)
    layer = {k[len("layers.0."):]: v for k, v in p.items()
             if k.startswith("layers.0.")}
    got = np.asarray(lm_blocks.mamba2_op(layer, "ssm", jnp.asarray(x), **kw))
    later = x.copy()
    later[:, 17:] += 1.0
    again = np.asarray(lm_blocks.mamba2_op(layer, "ssm", jnp.asarray(later),
                                           **kw))
    np.testing.assert_array_equal(again[:, :17], got[:, :17])
    assert np.any(again[:, 17:] != got[:, 17:])
    # a sequence of the batch sees nothing of another
    alone = np.asarray(lm_blocks.mamba2_op(layer, "ssm", jnp.asarray(x[1:]),
                                           **kw))
    np.testing.assert_allclose(alone[0], got[1], rtol=1e-6, atol=1e-6)
    text = str(jax.make_jaxpr(functools.partial(
        lm_blocks.mamba2_op, layer, "ssm", **kw))(jnp.asarray(x)))
    assert "remat" in text and text.count("scan[") >= 2


def _values_outside_kernels(jaxpr):
    """Every value a traced program defines outside its ``pallas_call``s
    (a kernel's own values live in VMEM), through every nested jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _values_outside_kernels(inner)


def test_no_plane_of_decays_reaches_hbm():
    """Six chunks of four positions: the loss and its gradient hold no
    float32 value ``[chunks, ·, Q, Q]`` (the decays ``L``, the masked
    scores, their cotangents) outside a kernel, nor the float32 chunk
    states ``[chunks, G, R, P, N]`` the scan over chunks carried: what
    the backward keeps of a mixer's states is in the operands' dtype."""
    lm, p = build("MEM*E", chunk_size=4)
    ids = tokens()
    chunks, n = ids.shape[1] // 4, BASE["ssm_state_size"]
    cast = functools.partial(with_compute_dtype, dtype=jnp.bfloat16,
                             keep=lm.float32_leaves)
    seen = list(_values_outside_kernels(jax.make_jaxpr(jax.value_and_grad(
        cast(lm.loss_fn(remat=True))))(p, ids)))
    assert len(seen) > 1000
    planes = [a for a in seen if getattr(a, "ndim", 0) >= 3
              and a.shape[-2:] == (4, 4) and chunks in a.shape[:-2]]
    assert planes == []
    states = [a for a in seen if getattr(a, "ndim", 0) >= 3
              and a.shape[0] == chunks and n in a.shape[1:]]
    assert states and all(a.dtype == jnp.bfloat16 for a in states)


# the gradient limit lies between two readings at these toy widths: clean
# bfloat16, the largest by group over eight token seeds, 0.013-0.032 on 2 x 32
# tokens in chunks of 8 and 0.012-0.037 on 512 tokens in chunks of 128; the
# smallest reading a fault leaves in the group it has to move, 0.14 (the
# recurrence in bfloat16; test_the_check_fails_on_each_fault prints them all)
GROUPS = ("ssm", "attention", "experts", "routers", "shared_ff", "table",
          "head", "norms")
LIMITS = {"grad_rel_l2": {g: 0.065 for g in GROUPS}, "loss_rel": 0.002,
          "route_agreement_min": 0.9, "update_rel_l2": 3e-4,
          "moment2_rel_l2": 1e-3}
ADAMW = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}


@pytest.fixture(scope="module")
def hybrid():
    sys.path.insert(0, REPO)
    return _load(os.path.join(REPO, "benchmark", "adapters",
                              "lm_train_hybrid.py"),
                 "benchmark_adapter_lm_train_hybrid_for_tests")


@pytest.mark.parametrize("pattern", ["M", "E", "MEM*E"])
def test_bfloat16_gradients_by_group_on_the_programs_routes(pattern, hybrid):
    """bf16 compute on float32 masters with the recurrence's scalars
    left float32, as the cell trains: every group within its limit of
    the float32 gradient taken on the routes the bf16 program chose."""
    lm, p = build(pattern)
    ids = tokens()
    cast = functools.partial(with_compute_dtype, dtype=jnp.bfloat16,
                             keep=lm.float32_leaves)
    routes = jax.jit(cast(lm.routes))(p, ids)
    got = jax.jit(jax.grad(cast(lm.loss_fn())))(p, ids)
    want = jax.jit(jax.grad(lambda q: R.loss(q, ids, routes, **REF)))(p)
    assert all(leaf.dtype == jnp.float32 for leaf in got.values())
    readings = hybrid.base.compare_groups(
        {k: v for k, v in got.items() if not k.endswith("expert_bias")},
        {k: v for k, v in want.items() if not k.endswith("expert_bias")})
    assert readings and set(readings) <= set(GROUPS)
    for group, value in readings.items():
        assert value < LIMITS["grad_rel_l2"][group], (group, value)


def test_named_leaves_stay_float32_under_the_steps_cast():
    lm, p = build("ME")
    assert lm.float32_leaves == (".A_log", ".dt_bias", ".D")
    seen = {}

    def spy(params, ids):
        seen.update({k: v.dtype for k, v in params.items()})
        return jnp.float32(0)

    ids = tokens()
    with_compute_dtype(spy, jnp.bfloat16, keep=lm.float32_leaves)(p, ids)
    for name, dtype in seen.items():
        kept = name.endswith(lm.float32_leaves)
        assert dtype == (jnp.float32 if kept else jnp.bfloat16), name
    assert sum(name.endswith(lm.float32_leaves) for name in seen) == 3
    # the default casts every float32 leaf, as before
    with_compute_dtype(spy, jnp.bfloat16)(p, ids)
    assert set(seen.values()) == {jnp.dtype(jnp.bfloat16)}
    # a nested tree: the END of the last key on a leaf's path
    nested = {"a": {"x.D": np.ones(2, np.float32)},
              "b": [np.ones(2, np.float32)], "n": np.arange(2)}
    with_compute_dtype(lambda q: seen.update(q) or 0.0, jnp.bfloat16,
                       keep=(".D",))(nested)
    assert seen["a"]["x.D"].dtype == jnp.float32
    assert seen["b"][0].dtype == jnp.bfloat16
    assert seen["n"].dtype == np.arange(2).dtype


# ---- the chip's share -----------------------------------------------------
def test_the_sixteen_shares_and_the_shared_expert_once_add_up():
    """Partial results of the shares (0,2) (2,2) .. (30,2) of a
    32-expert layer, plus the shared expert counted ONCE, add up to the
    uncut reference's layer; the router, which every share computes
    alike, is the same in all."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 16, 64)), jnp.float32)
    over = dict(n_routed_experts=32, num_experts_per_tok=6)
    whole, pw = build("E", experts_held=(0, 32), **over)
    name = "layers.0.moe"
    with jax.default_matmul_precision("highest"):
        want, routes = R.routed_ff(pw, name, x, 6, 2.5, 0)
        shared = R.relu2_ff(x, pw["layers.0.shared.w1"],
                            pw["layers.0.shared.w2"])
        total = lm_blocks.relu2_ff(
            {k[len("layers.0."):]: v for k, v in pw.items()}, "shared", x)
        assert rel(total, shared) < 1e-5
        for first in range(0, 32, 2):
            lm, p = build("E", experts_held=(first, 2), **over)
            for leaf in ("moe.router", "shared.w1", "shared.w2"):
                np.testing.assert_array_equal(p["layers.0." + leaf],
                                              pw["layers.0." + leaf])
            np.testing.assert_array_equal(p[name + ".w1"],
                                          pw[name + ".w1"][first:first + 2])
            part, chosen = moe.routed_ff(p, name, x, top_k=6, scaling=2.5,
                                         held=(first, 2), act="relu2")
            np.testing.assert_array_equal(chosen, routes)
            ref_part, _ = R.routed_ff(p, name, x, 6, 2.5, first)
            assert rel(part, ref_part) < 1e-5
            total = total + part
    assert rel(total, want + shared) < 1e-5
    assert rel(part + shared, want + shared) > 0.1   # one share is not it


@pytest.mark.parametrize("skew", ["all_held", "one_expert", "none_held"])
def test_nothing_is_dropped_at_any_skew_with_two_product_experts(skew):
    """A bias that sends every token to held experts (eight times the
    balanced load on 3 of 16), to ONE held expert plus two absent, or to
    absent experts only: the layer still matches the reference, which
    has no buffer to overflow."""
    lm, p = build("E", experts_held=(4, 3))
    name = "layers.0.moe"
    bias = np.full(16, -10.0, np.float32)
    bias[{"all_held": [4, 5, 6], "one_expert": [5, 9, 12],
          "none_held": [0, 1, 15]}[skew]] = 10.0
    p[name + ".expert_bias"] = bias
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 16, 64)), jnp.float32)
    kw = dict(top_k=3, held=(4, 3), scaling=2.5, act="relu2")
    with jax.default_matmul_precision("highest"):
        got, chosen = moe.routed_ff(p, name, x, **kw)
        want, _ = R.routed_ff(p, name, x, 3, 2.5, 4)
        g_got = jax.jit(jax.grad(lambda q: moe.routed_ff(
            q, name, x, **kw)[0].sum()))(p)
        g_want = jax.jit(jax.grad(lambda q: R.routed_ff(
            q, name, x, 3, 2.5, 4)[0].sum()))(p)
    held = int(((np.asarray(chosen) >= 4) & (np.asarray(chosen) < 7)).sum())
    assert held == {"all_held": 96, "one_expert": 32, "none_held": 0}[skew]
    if skew == "none_held":
        assert not np.any(got) and not np.any(want)
    else:
        assert rel(got, want) < 1e-5
    assert name + ".w3" not in p                     # two products, no gate
    for leaf in ("w1", "w2", "router"):
        key = f"{name}.{leaf}"
        if np.any(g_want[key]):
            assert rel(g_got[key], g_want[key]) < 1e-5, key
        else:
            assert not np.any(g_got[key]), key


# ---- the generalisation leaves LFM2's program alone -----------------------
# rows and experts 256 wide: multiples of the grouped product's tile, as the
# cell's 2,048 and 1,792 are (a width that is none is padded for the
# products: moe._TILE)
LFM2 = dict(hidden_size=256, intermediate_size=96, moe_intermediate_size=256,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, experts_held=(2, 4), vocab_size=512,
            vocab_slice=(0, 128), norm_eps=1e-5, rope_theta=1e6,
            conv_L_cache=3, num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"])


def _parents_hidden(lm, params, ids):
    """``Decoder.hidden(remat=True)`` as PR 31 had it: a layer is an
    operator and a feed-forward behind two norms, written out."""
    B = lm_blocks
    save = jax.checkpoint_policies.save_only_these_names(moe.ROUTES)

    def block(op, routed, p, x, routes):
        h = B.rms_norm(x, p["operator_norm"], lm.eps)
        if op == "conv":
            x = x + B.conv_op(p, "conv", h)
        else:
            x = x + B.attention_op(p, "attn", h, heads=lm.heads,
                                   kv_heads=lm.kv_heads, eps=lm.eps,
                                   theta=lm.theta)
        h = B.rms_norm(x, p["ffn_norm"], lm.eps)
        if not routed:
            return x + B.gated_ff(p, "ff", h), None
        y, chosen = moe.routed_ff(p, "moe", h, top_k=lm.top_k, held=lm.held,
                                  scaling=lm.scaling, routes=None)
        return x + y, chosen

    def leaves(layer):
        pre = f"layers.{layer}."
        return {k[len(pre):]: v for k, v in params.items()
                if k.startswith(pre)}

    x = params["embed"][ids]
    chosen = []
    for first, count, op, routed in ((0, 1, "conv", False),
                                     (1, 1, "full_attention", True),
                                     (2, 3, "conv", True)):
        fn = jax.checkpoint(functools.partial(block, op, routed),
                            policy=save)
        if count == 1:
            x, picked = fn(leaves(first), x, None)
            picked = [picked]
        else:
            stacked = jax.tree.map(lambda *leaf: jnp.stack(leaf), *[
                leaves(layer) for layer in range(first, first + count)])
            x, picked = jax.lax.scan(
                lambda x, per: fn(per[0], x, per[1]), x, (stacked, None))
        if routed:
            chosen.extend(picked)
    return B.rms_norm(x, params["embedding_norm"], lm.eps), chosen


def _scoped_text(fn, *args):
    """The lowered program with its locations: ``jax.named_scope`` names
    ride on the operations' ``op_name`` paths, not in a jaxpr's text."""
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_the_lfm2_paths_jaxpr_is_unchanged_by_the_generalisation():
    """Parts a layer, activations by name, an optional rotation and an
    optional untied head: LFM2's program is equation for equation what
    it was (the same scopes, the same five gathers), and its leaves the
    same numbers."""
    import re

    lm = Decoder(LFM2)
    p = lm.init(3)
    assert lm.float32_leaves == () and lm.tied and lm.theta == 1e6
    assert sorted({k.split(".")[2] for k in p if k.startswith("layers.")}) == [
        "attn", "conv", "ff", "ffn_norm", "moe", "operator_norm"]
    ids = tokens(shape=(2, 16))

    def text(fn):
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(p, ids)))

    assert text(lambda q, x: lm.hidden(q, x, remat=True)) == text(
        lambda q, x: _parents_hidden(lm, q, x))
    scoped = _scoped_text(lm.loss_fn(), p, ids)
    for scope in ("lm.conv_op", "lm.attention", "lm.dense_ff", "moe.route",
                  "moe.experts", "lm.head"):
        assert scope in scoped
    assert "lm.ssm" not in scoped and "lm.shared_ff" not in scoped
    # the step's cast with nothing kept is the parent's tree.map
    cast = text(with_compute_dtype(lm.loss_fn(), jnp.bfloat16))
    assert cast == text(with_compute_dtype(lm.loss_fn(), jnp.bfloat16,
                                           keep=()))
    assert cast.count("ragged_dot_general[") == 2 * 3


# ---- structure, counts and errors -----------------------------------------
def test_published_parameter_count_of_the_chips_share(monkeypatch):
    """528.1 M at the published widths, counted from the shapes
    ``init`` makes (no number is drawn: ``normal`` hands out views of one
    zero)."""
    def shaped(rng, *shape, fan_in=None):
        return np.broadcast_to(np.float32(0), shape)

    monkeypatch.setattr(lm_blocks, "normal", shaped)
    monkeypatch.setattr(moe, "normal", shaped)
    lm = Decoder({**BASE, "hidden_size": 2688, "num_attention_heads": 32,
                  "num_key_value_heads": 2, "head_dim": 128,
                  "mamba_num_heads": 64, "mamba_head_dim": 64,
                  "n_groups": 8, "ssm_state_size": 128, "chunk_size": 128,
                  "n_routed_experts": 128, "num_experts_per_tok": 6,
                  "moe_intermediate_size": 1856,
                  "moe_shared_expert_intermediate_size": 3712,
                  "vocab_size": 131072, "vocab_slice": (0, 16384),
                  "experts_held": (0, 8),
                  "hybrid_override_pattern": "MEMEM*E"})
    shapes = {k: v.shape for k, v in lm.init(0).items()}
    size = {k: int(np.prod(s)) for k, s in shapes.items()}

    def layer(i):
        return sum(n for k, n in size.items() if k.startswith(f"layers.{i}."))

    assert shapes["layers.0.ssm.in_proj"] == (2688, 10304)
    assert shapes["layers.0.ssm.conv_kernel"] == (4, 6144)
    assert shapes["layers.1.moe.w1"] == (8, 2688, 1856)
    assert shapes["layers.1.moe.router"] == (2688, 128)
    assert shapes["layers.5.attn.k_proj"] == (2688, 256)
    assert shapes["head"] == shapes["embed"] == (16384, 2688)
    assert abs(layer(0) - 38.74e6) < 0.01e6
    assert abs(layer(5) - 23.40e6) < 0.01e6
    assert abs(layer(1) - (8 * 9.978e6 + 19.96e6 + 0.344e6)) < 0.02e6
    assert abs(sum(size.values()) - 528.1e6) < 0.05e6
    assert lm.kinds() == {"conv": 0, "attention": 1, "ssm": 3, "dense": 0,
                          "routed": 3, "shared": 3}
    assert lm.runs() == [(i, 1) for i in range(7)]   # no letter repeats


@pytest.mark.parametrize("over, said", [
    (dict(hybrid_override_pattern="MXE"), r"letters \['X'\]"),
    (dict(hybrid_override_pattern="M-E"), "dense MLP layer"),
    (dict(hybrid_override_pattern="ME", n_group=8), "n_group 8"),
    (dict(hybrid_override_pattern="ME", topk_group=4), "topk_group 4"),
    (dict(hybrid_override_pattern="ME", num_hidden_layers=3),
     "num_hidden_layers 3 but 2 letters"),
    (dict(hybrid_override_pattern="ME", experts_held=(12, 8)),
     "experts_held"),
    (dict(hybrid_override_pattern="ME", mlp_hidden_act="silu"),
     "mlp_hidden_act 'silu'"),
    (dict(hybrid_override_pattern="ME", use_conv_bias=False),
     "use_conv_bias"),
    (dict(hybrid_override_pattern="ME", time_step_limit=[0.001, 0.1]),
     "time_step_limit"),
])
def test_config_errors_name_what_is_wrong(over, said):
    with pytest.raises(ValueError, match=said):
        Decoder({**BASE, **over})


def test_layers_are_counted_and_the_chunks_gauged_while_traced():
    from tpudl import obs

    lm, p = build("MEM*E")
    before = obs.snapshot()
    jax.jit(lm.logits).lower(p, tokens())
    after = obs.snapshot()
    for kind, n in {"ssm": 2, "attention": 1, "routed": 2, "shared": 2,
                    "conv": 0, "dense": 0}.items():
        name = f"zoo.lm.layers.{kind}"
        assert (after[name]["value"]
                - before.get(name, {"value": 0})["value"]) == n
    assert after["lm.ssm.chunk"]["value"] == 8
    assert after["lm.ssm.chunks"]["value"] == 3      # 24 positions
    text = _scoped_text(lm.loss_fn(), p, tokens())
    for scope in ("lm.ssm.scan", "lm.attention", "lm.shared_ff",
                  "moe.route", "moe.experts", "lm.head"):
        assert scope in text, scope
    import re

    assert re.search(r"lm\.ssm(?!\.)", text)   # the mixer around its scan


def test_the_reference_takes_what_run_py_hands_it():
    """benchmark/run.py traces ``forward`` on float32 ids of shape
    (1, S) with the tap leaf among the parameters."""
    lm, p = build("MEM*E")
    ids = tokens()
    forward = jax.jit(lambda q, x: R.forward(q, x, **REF))
    tapped = {**p, "route_tap": np.zeros((2, 1, 24, 3), np.float32)}
    np.testing.assert_array_equal(
        forward(tapped, ids[:1].astype(np.float32)), forward(p, ids[:1]))
    own = R.routes_of(p, ids, **REF)
    assert len(own) == 2 and own[0].shape == (2, 24, 3)
    assert float(R.loss(p, ids, own, **REF)) == float(R.loss(p, ids, **REF))


# ---- the cell's check, on deliberate faults -------------------------------
def _first_step(hybrid, lm, p, ids):
    """One AdamW step of the bf16 program with the routes tap through
    Trainer.fit, as the cell's warm() takes it."""
    p = {**p, hybrid.TAP: np.zeros((lm.kinds()["routed"], *ids.shape,
                                    lm.top_k), np.float32)}
    trainer = Trainer(
        with_compute_dtype(hybrid.tapped(lm.loss_fn(with_routes=True)),
                           jnp.bfloat16, keep=lm.float32_leaves),
        optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                    mask=lm.decay_mask))
    p1, opt, history = trainer.fit(p, lambda step: (ids,), steps=1)
    adam = hybrid.base.adam_state(opt)
    got = {k: np.asarray(v) / np.float32(0.1) for k, v in adam.mu.items()}
    routes = np.rint(got.pop(hybrid.TAP)).astype(np.int32)
    update = hybrid.base.worst_leaf(jax.jit(
        lambda *state: hybrid.base.update_errors(*state, ADAMW))(
            p, p1, adam.mu, adam.nu))
    return got, list(routes), history[-1]["loss"], update


def _gate_after_the_norm(p, name, x, *, heads, groups, state, chunk, eps):
    """The mixer with ``GroupRMSNorm(y) ⊙ silu(z)``, the gate AFTER the
    norm: the other order of the family's ``norm_before_gate``."""
    f32, d_in = jnp.float32, p[name + ".out_proj"].shape[0]
    bsz, s, _ = x.shape
    z, xbc, dt = jnp.split(x @ p[name + ".in_proj"],
                           [d_in, 2 * d_in + 2 * groups * state], -1)
    taps = p[name + ".conv_kernel"].astype(f32)
    padded = jnp.pad(xbc.astype(f32), ((0, 0), (taps.shape[0] - 1, 0),
                                       (0, 0)))
    xbc = jax.nn.silu(sum(taps[j] * padded[:, j:j + s]
                          for j in range(taps.shape[0]))
                      + p[name + ".conv_bias"].astype(f32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_in, d_in + groups * state], axis=-1)
    xs = xs.reshape(bsz, s, heads, d_in // heads)
    dt = jax.nn.softplus(dt.astype(f32) + p[name + ".dt_bias"])
    a = -jnp.exp(p[name + ".A_log"])
    y = jax.vmap(lambda *seq: lm_blocks.ssd_scan(*seq, chunk),
                 (0, 0, None, 0, 0))(
        xs, dt, a, b.reshape(bsz, s, groups, state),
        c.reshape(bsz, s, groups, state))
    y = (y + p[name + ".D"][:, None] * xs.astype(f32)).reshape(
        bsz, s, groups, d_in // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = (y.reshape(bsz, s, d_in) * p[name + ".norm"].astype(f32)
         * jax.nn.silu(z.astype(f32)))
    return y.astype(x.dtype) @ p[name + ".out_proj"]


FAULTS = {   # fault -> a group that has to be over its limit
    "none": None,
    "no_d_skip": "ssm",
    "no_dt_bias": "ssm",
    "gate_after_the_norm": "ssm",
    "shared_expert_left_out": "shared_ff",
    "scaling_1_for_2.5": "experts",
    "a_rotation_applied": "attention",
    "none_in_long_chunks": None,
    "scan_in_bfloat16": "ssm",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_check_fails_on_each_fault(fault, hybrid, monkeypatch):
    """compare_groups, update_errors and verdict, as the cell uses them
    with this adapter's groups, on the step program's own first step:
    clean bf16 passes; a mixer without its D skip or its dt_bias or with
    the gate after the norm, a layer without its shared expert, weights
    scaled by 1 for 2.5, a rotation the family does not apply, and the
    control's recurrence in bfloat16 each put a reading outside its
    limit."""
    lm, p = build("MEM*E")
    ids = tokens(seed=7, shape=(2, 32))
    if fault in ("none_in_long_chunks", "scan_in_bfloat16"):
        # a running sum needs a chunk's length to lose something
        lm, p = build("MEM*E", chunk_size=128, time_step_min=0.02,
                      time_step_max=0.5)
        ids = tokens(seed=7, shape=(1, 512))
    mixer = lm_blocks.mamba2_op

    def without(leaf):
        return lambda q, name, x, **kw: mixer(
            {**q, f"{name}.{leaf}": jnp.zeros_like(q[f"{name}.{leaf}"])},
            name, x, **kw)

    if fault == "no_d_skip":
        monkeypatch.setattr(lm_blocks, "mamba2_op", without("D"))
    elif fault == "no_dt_bias":
        monkeypatch.setattr(lm_blocks, "mamba2_op", without("dt_bias"))
    elif fault == "gate_after_the_norm":
        monkeypatch.setattr(lm_blocks, "mamba2_op", _gate_after_the_norm)
    elif fault == "shared_expert_left_out":
        monkeypatch.setattr(lm_blocks, "relu2_ff",
                            lambda q, name, x: jnp.zeros_like(x))
    elif fault == "scaling_1_for_2.5":
        lm.scaling = 1.0
    elif fault == "a_rotation_applied":
        flash = lm_blocks.flash_attention
        monkeypatch.setattr(
            lm_blocks, "flash_attention", lambda q, k, v, **kw: flash(
                lm_blocks.rotary(q, 1e4), lm_blocks.rotary(k, 1e4), v, **kw))
    elif fault == "scan_in_bfloat16":
        monkeypatch.setattr(lm_blocks, "SCAN_DTYPE", jnp.bfloat16)
    got, routes, loss, update = _first_step(hybrid, lm, p, ids)
    monkeypatch.undo()
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda q: R.loss(q, ids, routes, **REF)))(p)
    own = jax.jit(lambda q: R.routes_of(q, ids, **REF))(p)
    agreement = float(np.mean([
        (mine[..., :, None] == np.asarray(theirs)[..., None, :]).any(-1)
        for mine, theirs in zip(routes, own)]))
    readings = {"grad_rel_l2": hybrid.base.compare_groups(got, want),
                "loss_rel": abs(loss - float(want_loss)) / float(want_loss),
                "route_agreement": agreement, **update,
                "loss_first": loss, "loss_again": loss - 1.0}
    assert set(readings["grad_rel_l2"]) == set(GROUPS)
    over = hybrid.base.verdict(readings, LIMITS)
    print(fault, {k: v for k, v in readings.items() if k != "grad_rel_l2"},
          {k: round(v, 4) for k, v in readings["grad_rel_l2"].items()})
    if FAULTS[fault] is None:
        assert over == {}, readings
    else:
        assert FAULTS[fault] in over, (fault, over)


def test_the_adapters_groups_cover_every_leaf(hybrid):
    lm, p = build("MEM*E")
    assert {hybrid.group_of(name) for name in p} == set(GROUPS)
    assert set(hybrid.LIMITS_WHY) == set(GROUPS)
    assert hybrid.group_of("layers.0.ssm.norm") == "ssm"
    assert hybrid.group_of("layers.0.norm") == "norms"
    with pytest.raises(KeyError):
        hybrid.group_of("layers.0.unknown.leaf")
    assert hybrid.decoder_config(
        {"n_routed_experts": 8, "vocab_size": 16, "hidden_size": 4,
         "published": {"n_routed_experts": 128, "vocab_size": 64}}) == {
             "n_routed_experts": 128, "vocab_size": 64, "hidden_size": 4}
    # LFM2's adapter is a module of its own, and still names LFM2's parts
    lfm2 = _load(os.path.join(REPO, "benchmark", "adapters", "lm_train.py"),
                 "benchmark_adapter_lm_train_beside_hybrid")
    assert "lm.conv_op" in lfm2.SCOPES and "lm.ssm" in hybrid.base.SCOPES
    assert lfm2.group_of("layers.0.conv.kernel") == "conv"


def test_the_flash_forward_is_saved_across_rematerialisation(
        check_flash_saved_once):
    lm, p = build("MEM*E")
    check_flash_saved_once(lm, p, tokens(), bodies=1)
