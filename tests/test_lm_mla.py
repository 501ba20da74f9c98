"""The decoder's ``joyai_llm_flash`` stack (latent attention with a rotated
key shared by the heads, a dense and then routed gated-SiLU feed-forwards
beside a shared expert, an untied head, a multi-token-prediction module in
the loss) against its plain float32 reference, at toy widths on the CPU.

Seeded weights; float32 comparisons at 1e-5 under highest matmul
precision; bfloat16 (the precision the cell trains in) at the stated
tolerances, on the program's own routes."""

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
                       "joyai-llm-flash-ep32.py"), "joyai_reference")


def rel(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# latent attention at 16 + 8 / 16 a head: tests/test_pallas_ops.py holds the
# kernels to 24 / 16
BASE = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            head_dim=8, q_lora_rank=24, kv_lora_rank=16, qk_head_dim=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3,
            first_k_dense_replace=1, moe_layer_freq=1, n_group=1,
            topk_group=1, scoring_func="sigmoid", topk_method="noaux_tc",
            norm_topk_prob=True, routed_scaling_factor=2.5,
            rope_theta=32000000, rope_interleave=True, rope_scaling=None,
            rms_norm_eps=1e-6, attention_bias=False, num_hidden_layers=3,
            num_nextn_predict_layers=1, mtp_weight=0.3,
            tie_word_embeddings=False, vocab_size=512, vocab_slice=(0, 128),
            experts_held=(4, 4))
REF = dict(top_k=3, held_first=4, attention_rows=8)


def build(seed=3, **over):
    lm = Decoder({**BASE, **over})
    p = lm.init(seed)
    # init leaves the selection bias at zero and every norm at one; the
    # tests want them to matter
    rng = np.random.default_rng(seed)
    for name in p:
        if name.endswith("expert_bias"):
            assert not np.any(p[name])
            p[name] = (0.02 * rng.standard_normal(p[name].shape)).astype(
                np.float32)
        elif name.endswith("norm"):
            assert np.all(p[name] == 1)
            p[name] = (1 + 0.1 * rng.standard_normal(p[name].shape)).astype(
                np.float32)
    return lm, p


def _layer(p, layer):
    pre = f"layers.{layer}."
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


def tokens(seed=0, shape=(2, 24)):
    return np.random.default_rng(seed).integers(
        0, 128, shape).astype(np.int32)


# ---- against the reference ------------------------------------------------
@pytest.mark.parametrize("case, over", [
    ("with the module", {}),
    ("without the module", dict(num_nextn_predict_layers=0)),
    ("the module unweighted", dict(mtp_weight=0.0)),
    ("dense layers only", dict(first_k_dense_replace=3,
                               num_nextn_predict_layers=0)),
    ("one run of four routed layers", dict(num_hidden_layers=5)),
])
def test_loss_and_gradients_match_the_reference_in_float32(case, over):
    lm, p = build(**over)
    ids = tokens()
    kw = {**REF, "mtp_weight": lm.mtp_weight}
    with jax.default_matmul_precision("highest"):
        assert rel(jax.jit(lm.logits)(p, ids),
                   jax.jit(lambda q: R.forward(q, ids, **kw))(p)) < 1e-5
        got_l, got = jax.jit(jax.value_and_grad(lm.loss_fn()))(p, ids)
        want_l, want = jax.jit(jax.value_and_grad(
            lambda q: R.loss(q, ids, **kw)))(p)
    assert abs(float(got_l) - float(want_l)) < 1e-5 * float(want_l)
    assert set(got) == set(want)
    for name in want:
        if name.endswith("expert_bias"):   # a buffer: no gradient reaches it
            assert not np.any(got[name]) and not np.any(want[name])
        elif case == "the module unweighted" and name.startswith("mtp."):
            assert not np.any(got[name]) and not np.any(want[name])
        else:
            assert np.any(want[name]), name
            assert rel(got[name], want[name]) < 1e-5, name
    routes = jax.jit(lm.routes)(p, ids)
    assert len(routes) == lm.kinds()["routed"]
    if case == "one run of four routed layers":
        assert lm.runs() == [(0, 1), (1, 4)]


def test_the_second_term_is_the_modules_loss_on_the_token_after_next():
    """``loss = L_main + 0.3 L_mtp``: the weight is the configuration's,
    and the module's term counts the S - 2 positions that have a token
    after next, whatever the last two positions' tokens are."""
    lm, p = build()
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        whole = float(lm.loss_fn()(p, ids))
        lm.mtp_weight = 0.0
        main = float(lm.loss_fn()(p, ids))
        lm.mtp_weight = 1.0
        both = float(lm.loss_fn()(p, ids))
        assert whole == pytest.approx(main + 0.3 * (both - main), rel=1e-6)
        logits, ahead, _ = R._run(p, ids, None, REF)
        want = R._nll(ahead[:, :-2], jnp.asarray(ids[:, 2:]))
        assert both - main == pytest.approx(float(want), rel=1e-5)
        # the last token reaches the module's input at the last two
        # positions only (as a next token, and as the last position's
        # own), and neither is counted
        other = ids.copy()
        other[:, -1] = (other[:, -1] + 1) % 128
        _, ahead2, _ = R._run(p, other, None, REF)
    np.testing.assert_array_equal(np.asarray(ahead[:, :-2]),
                                  np.asarray(ahead2[:, :-2]))
    for last in (-2, -1):
        assert np.any(np.asarray(ahead[:, last]) != np.asarray(
            ahead2[:, last]))
    assert Decoder({**BASE, "num_nextn_predict_layers": 0}).mtp_weight == 0
    # the other families have no module and no second term
    assert Decoder(dict(hidden_size=64, num_attention_heads=2,
                        layer_types=["conv"], intermediate_size=8,
                        vocab_size=16)).mtp == 0


# ---- the rotation ---------------------------------------------------------
def _pair_by_pair(x, theta):
    """Position t turns (x_2i, x_2i+1) by t . theta^(-2i/d), one pair
    and one position at a time, in numpy."""
    x = np.asarray(x, np.float64)
    out = np.empty_like(x)
    d = x.shape[-1]
    for t in range(x.shape[1]):
        for i in range(d // 2):
            angle = t * theta ** (-2 * i / d)
            a, b = x[:, t, :, 2 * i], x[:, t, :, 2 * i + 1]
            out[:, t, :, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            out[:, t, :, 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    return out


@pytest.mark.parametrize("theta", [32e6, 1e4])
def test_interleaved_rotation_pair_by_pair(theta):
    x = np.random.default_rng(0).standard_normal((2, 12, 3, 8)).astype(
        np.float32)
    got = np.asarray(lm_blocks.rotary(jnp.asarray(x), theta,
                                      interleaved=True))
    np.testing.assert_allclose(got, _pair_by_pair(x, theta), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])   # position 0: identity
    np.testing.assert_allclose(
        np.asarray(R.rotate_pairs(jnp.asarray(x), theta)),
        _pair_by_pair(x, theta), rtol=1e-5, atol=1e-5)
    # the half-split rotation is another one, and is what it was
    half = np.asarray(lm_blocks.rotary(jnp.asarray(x), theta))
    assert np.abs(half - got).max() > 0.1
    assert np.linalg.norm(got) == pytest.approx(np.linalg.norm(x), rel=1e-5)


def test_one_rotated_key_serves_every_head_and_the_scale_is_the_192s():
    """The operator against attention written out: per-head scores
    ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)`` with the ONE
    rotated key, a value head of its own width."""
    lm, p = build()
    layer = _layer(p, 1)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 16, 64)),
                    jnp.float32)
    kw = dict(heads=4, nope=16, rope=8, eps=1e-6, theta=32e6)
    with jax.default_matmul_precision("highest"):
        got = lm_blocks.mla_op(layer, "attn", x, **kw)
        want = R.mla_op(p, "layers.1.attn", x, 1e-6, 32e6, 8)
        assert rel(got, want) < 1e-5
        # causal
        later = x.at[:, 9:].add(1.0)
        again = lm_blocks.mla_op(layer, "attn", later, **kw)
    np.testing.assert_allclose(again[:, :9], got[:, :9], rtol=1e-5, atol=1e-6)
    assert R._head_sizes(p, "layers.1.attn") == (4, 16, 8, 16)
    text = jax.jit(functools.partial(lm_blocks.mla_op, layer, "attn",
                                     **kw)).lower(x).as_text(debug_info=True)
    assert "lm.attention.latent" in text and "lm.attention" in text


def test_nothing_is_copied_between_a_projection_and_a_kernel():
    """``mla_op``'s jaxpr: the products write head-major inside
    ``lm.attention.latent``; outside it no ``[B, S, H, d]`` operand is
    transposed, nothing is concatenated, and the ONE rotated key ``[B, S,
    rope]`` is never broadcast over the heads: it reaches the kernels as
    it is, beside a 16-wide key a head, and ``q`` whole."""
    lm, p = build()
    layer = _layer(p, 1)
    b, s, heads, nope, rope, v_dim = 2, 16, 4, 16, 8, 16
    x = jnp.zeros((b, s, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(
        lm_blocks.mla_op, layer, "attn", heads=heads, nope=nope, rope=rope,
        eps=1e-6, theta=32e6))(x)

    def walk(inner):
        for eqn in getattr(inner, "jaxpr", inner).eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":   # the kernels' own
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        yield from walk(sub)

    def shapes(eqn, which):
        return [tuple(v.aval.shape) for v in getattr(eqn, which)]

    inside, outside = [], {}
    for eqn in walk(jaxpr):
        if "lm.attention.latent" in str(eqn.source_info.name_stack):
            inside.append(eqn.primitive.name)
        else:
            outside.setdefault(eqn.primitive.name, []).append(eqn)
    assert "concatenate" not in outside
    for eqn in outside.get("transpose", []):
        assert all(len(shape) < 4 for shape in shapes(eqn, "invars")), eqn
    for eqn in outside.get("broadcast_in_dim", []):
        assert not any(heads in shape and shape[-1] == rope
                       for shape in shapes(eqn, "outvars")), eqn
    (kernel,) = outside["pallas_call"]
    assert shapes(kernel, "invars")[2:] == [
        (b * heads, 1, s, nope + rope), (b * heads, s, nope), (b, s, rope),
        (b * heads, s, v_dim)]
    # inside the scope a head-major product is a product and the transpose
    # that names its output's layout
    assert inside.count("transpose") == 3 and "concatenate" not in inside


# ---- the chip's share -----------------------------------------------------
def test_the_four_shares_and_the_shared_expert_once_add_up():
    """Partial results of the shares (0,4) (4,4) (8,4) (12,4) of a
    16-expert layer, plus the gated-SiLU shared expert counted ONCE, add up
    to the uncut reference's layer; the router, which every share computes
    alike, is the same in all."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 16, 64)), jnp.float32)
    whole, pw = build(experts_held=(0, 16))
    name = "layers.1.moe"
    with jax.default_matmul_precision("highest"):
        want, routes = R.routed_ff(pw, name, x, 3, 2.5, 0)
        shared = R.gated_ff(x, *(pw[f"layers.1.shared.{leaf}"]
                                 for leaf in ("w1", "w3", "w2")))
        total = lm_blocks.gated_ff(_layer(pw, 1), "shared", x,
                                   scope="lm.shared_ff")
        assert rel(total, shared) < 1e-5
        for first in range(0, 16, 4):
            lm, p = build(experts_held=(first, 4))
            for leaf in ("moe.router", "shared.w1", "shared.w3", "shared.w2",
                         "attn.kv_b_proj"):
                np.testing.assert_array_equal(p["layers.1." + leaf],
                                              pw["layers.1." + leaf])
            np.testing.assert_array_equal(p[name + ".w3"],
                                          pw[name + ".w3"][first:first + 4])
            part, chosen = moe.routed_ff(p, name, x, top_k=3, scaling=2.5,
                                         held=(first, 4), act="silu")
            np.testing.assert_array_equal(chosen, routes)
            ref_part, _ = R.routed_ff(p, name, x, 3, 2.5, first)
            assert rel(part, ref_part) < 1e-5
            total = total + part
    assert rel(total, want + shared) < 1e-5
    assert rel(part + shared, want + shared) > 0.1   # one share is not it
    # through the decoder: a layer's routed part is its share + the shared
    y, _ = lm._part("routed", _layer(p, 1), x, None)
    with jax.default_matmul_precision("highest"):
        assert rel(y, part + shared) < 1e-5


def test_the_vocabulary_slice_holds_the_table_the_head_and_both_losses():
    lm, p = build()
    assert p["embed"].shape == p["head"].shape == (128, 64)
    assert lm.vocab == 512 and lm.vocab_slice == (0, 128) and not lm.tied
    ids = tokens()
    logits = jax.jit(lm.logits)(p, ids)
    assert logits.shape == (2, 24, 128) and logits.dtype == jnp.float32
    whole = Decoder({**BASE, "vocab_slice": None})
    assert whole.init(0)["head"].shape == (512, 64)
    # a row of the table moves if its id stands anywhere but at a
    # sequence's end: the last position's stream reaches no counted
    # prediction, of the head or of the module
    g = jax.jit(jax.grad(lm.loss_fn()))(p, ids)
    moved = np.flatnonzero(np.abs(np.asarray(g["embed"])).sum(-1))
    np.testing.assert_array_equal(moved, np.unique(ids[:, :-1]))
    assert set(ids[:, -1]) - set(ids[:, :-1].ravel())   # and one such is left


# ---- structure, counts and errors -----------------------------------------
def test_published_parameter_count_of_the_chips_share(monkeypatch):
    """491.7 M at the published widths, counted from the shapes ``init``
    makes (no number is drawn: ``normal`` hands out views of one zero)."""
    def shaped(rng, *shape, fan_in=None):
        return np.broadcast_to(np.float32(0), shape)

    monkeypatch.setattr(lm_blocks, "normal", shaped)
    monkeypatch.setattr(moe, "normal", shaped)
    lm = Decoder({**BASE, "hidden_size": 2048, "num_attention_heads": 32,
                  "num_key_value_heads": 32, "head_dim": 64,
                  "q_lora_rank": 1536, "kv_lora_rank": 512,
                  "qk_head_dim": 192, "qk_nope_head_dim": 128,
                  "qk_rope_head_dim": 64, "v_head_dim": 128,
                  "intermediate_size": 7168, "moe_intermediate_size": 768,
                  "n_routed_experts": 256, "num_experts_per_tok": 8,
                  "num_hidden_layers": 5, "vocab_size": 129280,
                  "vocab_slice": (0, 16160), "experts_held": (0, 8)})
    shapes = {k: v.shape for k, v in lm.init(0).items()}
    size = {k: int(np.prod(s)) for k, s in shapes.items()}

    def under(pre):
        return sum(n for k, n in size.items() if k.startswith(pre))

    assert shapes["layers.0.attn.q_b_proj"] == (1536, 32 * 192)
    assert shapes["layers.0.attn.kv_a_proj"] == (2048, 512 + 64)
    assert shapes["layers.0.attn.kv_b_proj"] == (512, 32 * (128 + 128))
    assert shapes["layers.0.attn.o_proj"] == (32 * 128, 2048)
    assert shapes["layers.1.moe.w1"] == (8, 2048, 768)
    assert shapes["layers.1.moe.router"] == (2048, 256)
    assert shapes["layers.1.shared.w3"] == (2048, 768)
    assert shapes["mtp.merge"] == (4096, 2048)
    assert shapes["head"] == shapes["embed"] == (16160, 2048)
    assert abs(under("layers.0.attn.") - 26.35e6) < 0.01e6
    assert abs(under("layers.0.") - 70.39e6) < 0.01e6
    assert abs(under("layers.1.") - 69.34e6) < 0.01e6
    assert abs(under("mtp.") - 77.74e6) < 0.01e6
    assert abs(sum(size.values()) - 491.7e6) < 0.05e6
    assert lm.kinds() == {"conv": 0, "attention": 6, "ssm": 0, "dense": 1,
                          "routed": 5, "shared": 5, "mla": 6, "mtp": 1}
    assert lm.runs() == [(0, 1), (1, 4)]      # layers 1-4: one scanned body
    assert (lm.held, lm.top_k, lm.scaling, lm.eps, lm.theta,
            lm.mtp_weight) == ((0, 8), 8, 2.5, 1e-6, 32e6, 0.3)


@pytest.mark.parametrize("over, said", [
    (dict(n_group=8), "n_group 8"),
    (dict(topk_group=4), "topk_group 4"),
    (dict(scoring_func="softmax"), "scoring_func 'softmax'"),
    (dict(topk_method="group_limited_greedy"),
     "topk_method 'group_limited_greedy'"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "rope_scaling"),
    (dict(norm_topk_prob=False), "norm_topk_prob False"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers 2"),
    (dict(rope_interleave=False), "rope_interleave False"),
    (dict(experts_held=(12, 8)), "experts_held"),
])
def test_config_errors_name_the_key_that_is_not_built(over, said):
    with pytest.raises(ValueError, match=said):
        Decoder({**BASE, **over})


def test_layers_are_counted_and_the_module_scoped_while_traced():
    from tpudl import obs

    lm, p = build()
    before = obs.snapshot()
    jax.jit(lm.loss_fn()).lower(p, tokens())
    after = obs.snapshot()
    for kind, n in {"mla": 4, "attention": 4, "mtp": 1, "routed": 3,
                    "shared": 3, "dense": 1, "conv": 0, "ssm": 0}.items():
        name = f"zoo.lm.layers.{kind}"
        assert (after[name]["value"]
                - before.get(name, {"value": 0})["value"]) == n, kind
    assert (after["moe.combine.fused"]["value"]
            - before.get("moe.combine.fused", {"value": 0})["value"]) == 3
    assert after["pallas.flash.head_dim_qk"]["value"] == 24
    assert after["pallas.flash.head_dim_v"]["value"] == 16
    text = jax.jit(lm.loss_fn()).lower(p, tokens()).as_text(debug_info=True)
    for scope in ("lm.attention.latent", "lm.attention", "lm.mtp",
                  "lm.dense_ff", "lm.shared_ff", "moe.route", "moe.experts",
                  "lm.head"):
        assert scope in text, scope
    assert "lm.ssm" not in text and "lm.conv_op" not in text


def test_route_stats_count_five_routed_layers_with_the_module():
    lm, p = build(num_hidden_layers=5)
    ids = tokens()
    stats = lm.route_stats(p, ids)
    assert len(stats["layers"]) == 5 == lm.kinds()["routed"]
    assert stats["pairs_total"] == 5 * ids.size * 3
    assert 0 < stats["pairs_held"] < stats["pairs_total"]
    bare = Decoder({**BASE, "num_hidden_layers": 5,
                    "num_nextn_predict_layers": 0})
    assert len(bare.route_stats(
        {k: v for k, v in p.items() if not k.startswith("mtp.")},
        ids)["layers"]) == 4
    # given routes replace every layer's selection, the module's last
    own = jax.jit(lm.routes)(p, ids)
    given = [jnp.roll(c, 1, axis=-1) for c in own]
    _, used = jax.jit(lambda q: lm.hidden(q, ids, given))(p)
    for mine, theirs in zip(used, given):
        np.testing.assert_array_equal(mine, theirs)
    assert float(R.loss(p, ids, own, **REF)) == pytest.approx(
        float(R.loss(p, ids, **REF)), rel=1e-6)


def test_the_reference_takes_what_run_py_hands_it():
    """benchmark/run.py traces ``forward`` on float32 ids of shape
    (1, S) with the tap leaf among the parameters."""
    lm, p = build()
    ids = tokens()
    forward = jax.jit(lambda q, x: R.forward(q, x, **REF))
    tapped = {**p, "route_tap": np.zeros((3, 1, 24, 3), np.float32)}
    np.testing.assert_array_equal(
        forward(tapped, ids[:1].astype(np.float32)), forward(p, ids[:1]))
    own = R.routes_of(p, ids, **REF)
    assert len(own) == 3 and own[0].shape == (2, 24, 3)
    assert R.n_layers(p) == 3


# ---- the cell's check, on deliberate faults -------------------------------
GROUPS = ("mla", "experts", "routers", "shared_ff", "dense_ff", "mtp",
          "table", "head", "norms")
# between two readings at these toy widths (the test prints them): clean
# bfloat16, 0.013-0.036 by group over the token seeds tried; the smallest
# reading a fault leaves in the group it has to move, 0.12
LIMITS = {"grad_rel_l2": {g: 0.065 for g in GROUPS}, "loss_rel": 0.002,
          "route_agreement_min": 0.9, "update_rel_l2": 3e-4,
          "moment2_rel_l2": 1e-3}
ADAMW = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}


@pytest.fixture(scope="module")
def adapter():
    sys.path.insert(0, REPO)
    return _load(os.path.join(REPO, "benchmark", "adapters",
                              "lm_train_mla.py"),
                 "benchmark_adapter_lm_train_mla_for_tests")


def _first_step(adapter, lm, p, ids):
    """One AdamW step of the bf16 program with the routes tap through
    Trainer.fit, as the cell's warm() takes it."""
    p = {**p, adapter.TAP: np.zeros((lm.kinds()["routed"], *ids.shape,
                                     lm.top_k), np.float32)}
    trainer = Trainer(
        with_compute_dtype(adapter.tapped(lm.loss_fn(with_routes=True)),
                           jnp.bfloat16),
        optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                    mask=lm.decay_mask))
    p1, opt, history = trainer.fit(p, lambda step: (ids,), steps=1)
    adam = adapter.base.adam_state(opt)
    got = {k: np.asarray(v) / np.float32(0.1) for k, v in adam.mu.items()}
    routes = np.rint(got.pop(adapter.TAP)).astype(np.int32)
    update = adapter.base.worst_leaf(jax.jit(
        lambda *state: adapter.base.update_errors(*state, ADAMW))(
            p, p1, adam.mu, adam.nu))
    return got, list(routes), history[-1]["loss"], update


FAULTS = {   # fault -> a group that has to be over its limit
    "none": None,
    "half_split_pairs": "mla",
    "scale_of_the_128": "mla",
    "a_rotated_key_a_head": "mla",
    "relu2_shared_expert": "shared_ff",
    "scaling_1_for_2.5": "experts",
    "module_on_the_normed_stream": "mtp",
    "halves_in_the_other_order": "mtp",
    "module_weight_1": "mtp",
    "fp8_scores": "mla",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_check_fails_on_each_fault(fault, adapter, monkeypatch):
    """compare_groups, update_errors and verdict, as the cell uses them
    with this adapter's groups, on the step program's own first step:
    clean bf16 passes; a rotation on half-split pairs, the scale of the
    value head's width, a rotated key that differs by head, a relu2
    shared expert, weights scaled by 1 for 2.5, a module fed the stream
    after the final norm or its halves the other way round or weighted 1,
    and the control's fp8 score product each put a reading outside its
    limit."""
    lm, p = build()
    ids = tokens(seed=7, shape=(2, 32))
    flash, rotary = lm_blocks.flash_attention, lm_blocks.rotary
    if fault == "half_split_pairs":
        def half_split(x, theta, interleaved=False, *, axis=1, first=0):
            return jnp.concatenate([x[..., :first], rotary(
                x[..., first:], theta, axis=axis)], -1)
        monkeypatch.setattr(lm_blocks, "rotary", half_split)
    elif fault == "scale_of_the_128":
        monkeypatch.setattr(
            lm_blocks, "flash_attention", lambda q, k, v, **kw: flash(
                q * (q.shape[-1] / v.shape[-1]) ** 0.5, k, v, **kw))
    elif fault == "a_rotated_key_a_head":
        # head h's copy of the shared key turned h positions further:
        # whole keys a head, head-major as the operator hands them over
        def per_head(q, k, v, *, k_shared, **kw):
            turned = jnp.stack([jnp.roll(k_shared, h, axis=1)
                                for h in range(k.shape[1])], 1)
            return flash(q, jnp.concatenate([k, turned], -1), v, **kw)
        monkeypatch.setattr(lm_blocks, "flash_attention", per_head)
    elif fault == "relu2_shared_expert":
        gated = lm_blocks.gated_ff
        monkeypatch.setattr(
            lm_blocks, "gated_ff", lambda q, name, x, scope="lm.dense_ff": (
                gated(q, name, x, scope) if name != "shared" else
                jnp.square(jax.nn.relu(x @ q[name + ".w1"]))
                @ q[name + ".w2"]))
    elif fault == "scaling_1_for_2.5":
        lm.scaling = 1.0
    elif fault == "module_on_the_normed_stream":
        mtp = lm._mtp
        monkeypatch.setattr(lm, "_mtp", lambda parts, q, x, ahead, routes: mtp(
            parts, q, lm_blocks.rms_norm(x, p["embedding_norm"], lm.eps),
            ahead, routes))
    elif fault == "halves_in_the_other_order":
        p["mtp.merge"] = np.concatenate([p["mtp.merge"][64:],
                                         p["mtp.merge"][:64]])
    elif fault == "module_weight_1":
        lm.mtp_weight = 1.0
    elif fault == "fp8_scores":
        control = _load(os.path.join(REPO, "benchmark", "controls",
                                     "mla_fp8_scores.py"), "mla_fp8_scores")
        monkeypatch.setattr(
            lm_blocks, "flash_attention", lambda q, k, v, **kw: flash(
                control.e4m3(q), control.e4m3(k), v, **kw))
    got, routes, loss, update = _first_step(adapter, lm, p, ids)
    monkeypatch.undo()
    if fault == "halves_in_the_other_order":   # the reference's M as it was
        p["mtp.merge"] = np.concatenate([p["mtp.merge"][64:],
                                         p["mtp.merge"][:64]])
        got["mtp.merge"] = np.concatenate([got["mtp.merge"][64:],
                                           got["mtp.merge"][:64]])
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda q: R.loss(q, ids, routes, **REF)))(p)
    own = jax.jit(lambda q: R.routes_of(q, ids, **REF))(p)
    agreement = float(np.mean([
        (mine[..., :, None] == np.asarray(theirs)[..., None, :]).any(-1)
        for mine, theirs in zip(routes, own)]))
    readings = {"grad_rel_l2": adapter.base.compare_groups(got, want),
                "loss_rel": abs(loss - float(want_loss)) / float(want_loss),
                "route_agreement": agreement, **update,
                "loss_first": loss, "loss_again": loss - 1.0}
    assert set(readings["grad_rel_l2"]) == set(GROUPS)
    over = adapter.base.verdict(readings, LIMITS)
    print(fault, {k: v for k, v in readings.items() if k != "grad_rel_l2"},
          {k: round(v, 4) for k, v in readings["grad_rel_l2"].items()})
    if FAULTS[fault] is None:
        assert over == {}, readings
    else:
        assert FAULTS[fault] in over, (fault, over)


def test_the_adapters_groups_cover_every_leaf(adapter):
    lm, p = build()
    assert {adapter.group_of(name) for name in p} == set(GROUPS)
    assert set(adapter.LIMITS_WHY) == set(GROUPS)
    assert adapter.group_of("mtp.attn.q_a_norm") == "mla"
    assert adapter.group_of("mtp.moe.router") == "routers"
    assert adapter.group_of("mtp.shared.w3") == "shared_ff"
    assert adapter.group_of("mtp.final_norm") == "mtp"
    assert adapter.group_of("mtp.input_layernorm") == "mtp"
    assert adapter.group_of("layers.0.input_layernorm") == "norms"
    assert adapter.group_of("embedding_norm") == "norms"
    with pytest.raises(KeyError):
        adapter.group_of("layers.0.unknown.leaf")
    assert adapter.decoder_config(
        {"n_routed_experts": 8, "vocab_size": 16, "hidden_size": 4,
         "published": {"n_routed_experts": 256, "vocab_size": 64}}) == {
             "n_routed_experts": 256, "vocab_size": 64, "hidden_size": 4}
    # the other LM adapters are modules of their own, with their own names
    lfm2 = _load(os.path.join(REPO, "benchmark", "adapters", "lm_train.py"),
                 "benchmark_adapter_lm_train_beside_mla")
    assert "lm.conv_op" in lfm2.SCOPES and "lm.mtp" in adapter.base.SCOPES
    assert lfm2.group_of("layers.0.conv.kernel") == "conv"


def test_the_flash_forward_is_saved_across_rematerialisation(
        check_flash_saved_once):
    """The dense layer, the scanned run of two routed layers and the
    module: three traced bodies for four latent-attention layers."""
    lm, p = build()
    assert lm.runs() == [(0, 1), (1, 2)] and lm.kinds()["mla"] == 4
    check_flash_saved_once(lm, p, tokens(), bodies=3)
