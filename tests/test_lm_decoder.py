"""The config-driven decoder (tpudl.zoo.decoder) and its routed experts
against the plain float32 reference, at toy widths on the CPU.

Seeded weights; float32 comparisons at 1e-5 under highest matmul
precision; bfloat16 (the precision the cell trains in) at the stated
tolerances, on the program's own routes."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl import pallas_ops
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
R = _load(os.path.join(REPO, "benchmark", "configs", "lfm2-8b-a1b-ep4.py"),
          "lfm2_reference")


def rel(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


BASE = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, experts_held=(2, 4), vocab_size=512,
            vocab_slice=(0, 128), norm_eps=1e-5, rope_theta=1e6,
            conv_L_cache=3)
REF = dict(top_k=2, held_first=2, attention_rows=8)
STACKS = {
    "conv+dense": (["conv"], 1),
    "attention+dense": (["full_attention"], 1),
    "conv+routed": (["conv"], 0),
    "attention+routed": (["full_attention"], 0),
    "whole": (["conv", "full_attention", "conv"], 1),
    # runs of one kind, which the decoder scans
    "conv+routed x3": (["conv"] * 3, 0),
    "one period": (["conv", "full_attention", "conv", "conv", "conv"], 1),
}


def build(stack, seed=3, **over):
    layers, dense = STACKS[stack]
    lm = Decoder({**BASE, "layer_types": layers, "num_dense_layers": dense,
                  **over})
    p = lm.init(seed)
    # init leaves the selection bias at zero; the tests want it to matter
    # (the same for every share of a layer: it is the router's)
    for name in p:
        if name.endswith("expert_bias"):
            assert not np.any(p[name])
            p[name] = (0.02 * np.random.default_rng(seed).standard_normal(
                p[name].shape)).astype(np.float32)
    return lm, p


def tokens(seed=0, shape=(2, 16)):
    return np.random.default_rng(seed).integers(
        0, 128, shape).astype(np.int32)


@pytest.mark.parametrize("stack", list(STACKS))
def test_logits_and_gradients_match_the_reference_in_float32(stack):
    lm, p = build(stack)
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


@pytest.mark.parametrize("stack", ["attention+dense", "conv+routed", "whole"])
def test_bfloat16_gradients_on_the_programs_routes(stack):
    """bf16 compute on float32 masters, as the cell trains: every leaf
    within 0.08 of the float32 gradient taken on the routes the bf16
    program chose (measured 0.02-0.05 at these widths: a product's
    operands round to 8 bits, and a 64-wide contraction averages little;
    an fp8 product or a missing term is several times that,
    test_the_check_fails_on_each_fault)."""
    lm, p = build(stack)
    ids = tokens()
    loss = with_compute_dtype(lm.loss_fn(), jnp.bfloat16)
    routes = jax.jit(with_compute_dtype(lm.routes, jnp.bfloat16))(p, ids)
    got = jax.jit(jax.grad(loss))(p, ids)
    want = jax.jit(jax.grad(lambda q: R.loss(q, ids, routes, **REF)))(p)
    for name in want:
        if not name.endswith("expert_bias"):
            assert got[name].dtype == jnp.float32
            assert rel(got[name], want[name]) < 0.08, name


def test_tile_shape_keys_in_a_configuration_are_data_nothing_reads():
    """The cell's file still carries ``attention_block_q`` /
    ``attention_block_k`` (and its rehearsal block other values): a tile
    shape is the kernels' to derive, so the same decoder, the same
    parameters and the same loss, bit for bit, with the keys or without."""
    plain, p = build("whole")
    keyed, p_keyed = build("whole", attention_block_q=512,
                           attention_block_k=512)
    assert vars(keyed).keys() == vars(plain).keys()
    assert set(p) == set(p_keyed)
    assert all(np.array_equal(p[name], p_keyed[name]) for name in p)
    ids = tokens()
    assert (float(jax.jit(plain.loss_fn())(p, ids))
            == float(jax.jit(keyed.loss_fn())(p, ids)))


def test_a_run_of_identical_layers_is_one_scanned_body(count_eqns):
    """LFM2's conv, conv, conv between attentions: the program holds the
    block once (one scan, one set of grouped products), and the leaves
    stay one flat dict of single layers."""
    lm, p = build("one period")
    assert lm.runs() == [(0, 1), (1, 1), (2, 3)]
    assert Decoder({**BASE, "layer_types": ["conv", "conv", "full_attention"],
                    "num_dense_layers": 1}).runs() == [(0, 1), (1, 1),
                                                        (2, 1)]
    jaxpr = jax.make_jaxpr(lm.loss_fn(remat=True))(p, tokens())
    assert str(jaxpr).count("scan[") == 2    # the run, and the chunked head
    # layer 1 alone, and once for layers 2-4: two grouped products a
    # layer, the gate and up-projection one call
    assert count_eqns(jaxpr, "jit", named="grouped_dot") == 2 * 2
    assert all(v.ndim <= 3 and ".moe.w" in k or v.ndim <= 2
               for k, v in p.items())


def test_remat_and_chunked_loss_change_no_value():
    lm, p = build("whole")
    ids = tokens(shape=(2, 32))
    with jax.default_matmul_precision("highest"):
        a = jax.jit(jax.value_and_grad(
            lm.loss_fn(remat=True, loss_chunk=16)))(p, ids)
        b = jax.jit(jax.value_and_grad(
            lm.loss_fn(remat=False, loss_chunk=64)))(p, ids)
        c = jax.jit(lm.loss_fn(loss_chunk=48))(p, ids)   # no divisor
    assert abs(float(a[0]) - float(b[0])) < 1e-6
    assert abs(float(c) - float(b[0])) < 1e-6
    for name in a[1]:
        assert rel(a[1][name], b[1][name]) < 1e-5 or not np.any(b[1][name])


# ---- the chip's share ----------------------------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Partial results of the shares (0,2) (2,2) (4,2) (6,2) of an
    8-expert layer add up to the uncut reference's layer output; the
    router, which every share computes alike, is the same in all."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 16, 64)), jnp.float32)
    whole, pw = build("conv+routed", experts_held=(0, 8))
    name = "layers.0.moe"
    with jax.default_matmul_precision("highest"):
        want, routes = R.routed_ff(pw, name, x, 2, 1.0, 0)
        total = 0.0
        for first in (0, 2, 4, 6):
            lm, p = build("conv+routed", experts_held=(first, 2))
            np.testing.assert_array_equal(p[name + ".router"],
                                          pw[name + ".router"])
            np.testing.assert_array_equal(p[name + ".w1"],
                                          pw[name + ".w1"][first:first + 2])
            part, chosen = moe.routed_ff(p, name, x, top_k=2,
                                         held=(first, 2))
            np.testing.assert_array_equal(chosen, routes)
            ref_part, _ = R.routed_ff(p, name, x, 2, 1.0, first)
            assert rel(part, ref_part) < 1e-5
            total = total + part
    assert rel(total, want) < 1e-5
    assert rel(part, want) > 0.1    # one share alone is not the layer


@pytest.mark.parametrize("skew", ["all_held", "one_expert", "none_held"])
def test_nothing_is_dropped_at_any_skew(skew):
    """A bias that sends every token to held experts (four times the
    balanced load on 2 of 8), to ONE held expert plus one absent, or to
    absent experts only: the layer still matches the reference, which
    has no buffer to overflow."""
    lm, p = build("conv+routed", experts_held=(2, 2))
    name = "layers.0.moe"
    bias = np.full(8, -10.0, np.float32)
    bias[{"all_held": [2, 3], "one_expert": [3, 6],
          "none_held": [0, 7]}[skew]] = 10.0
    p[name + ".expert_bias"] = bias
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 16, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, chosen = moe.routed_ff(p, name, x, top_k=2, held=(2, 2))
        want, _ = R.routed_ff(p, name, x, 2, 1.0, 2)
        g_got = jax.jit(jax.grad(lambda q: moe.routed_ff(
            q, name, x, top_k=2, held=(2, 2))[0].sum()))(p)
        g_want = jax.jit(jax.grad(lambda q: R.routed_ff(
            q, name, x, 2, 1.0, 2)[0].sum()))(p)
    held = int(((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 4)).sum())
    assert held == {"all_held": 64, "one_expert": 32, "none_held": 0}[skew]
    if skew == "none_held":
        assert not np.any(got) and not np.any(want)
    else:
        assert rel(got, want) < 1e-5
    for leaf in ("w1", "w2", "w3", "router"):
        key = f"{name}.{leaf}"
        if np.any(g_want[key]):
            assert rel(g_got[key], g_want[key]) < 1e-5, key
        else:
            assert not np.any(g_got[key]), key


def test_grouped_products_backward_against_the_masked_form():
    """d/dx and d/dW of the sorted, grouped products against jax.grad of
    every-expert-on-every-token masked by the weights."""
    lm, p = build("conv+routed")
    name = "layers.0.moe"
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 24, 64)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 24, 64)), jnp.float32)

    def masked(q, x):
        experts, weights = moe.route(q, name, x, top_k=2)
        dense = (jax.nn.one_hot(experts, 8) * weights[..., None]).sum(-2)
        y = 0.0
        for e in range(4):
            h = jax.nn.silu(x @ q[name + ".w1"][e]) * (x @ q[name + ".w3"][e])
            y = y + dense[..., 2 + e, None] * (h @ q[name + ".w2"][e])
        return (y * w).sum()

    def grouped(q, x):
        return (moe.routed_ff(q, name, x, top_k=2, held=(2, 4))[0] * w).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(grouped, (0, 1)))(p, x)
        want = jax.jit(jax.grad(masked, (0, 1)))(p, x)
    assert rel(got[1], want[1]) < 1e-5
    for leaf in ("w1", "w2", "w3", "router"):
        assert rel(got[0][f"{name}.{leaf}"], want[0][f"{name}.{leaf}"]) < 1e-5


def _plain_combine(out, weights, place, held_rows):
    """The parent's form: every pair's row back in pair order as a
    ``[T, k, D]`` array, weighted, masked and summed over ``k``."""
    t, k = weights.shape
    mine = place.reshape(t, k) < held_rows
    rows = out[place].reshape(t, k, -1).astype(jnp.float32)
    return jnp.where(mine[..., None], rows * weights[..., None], 0.0).sum(1)


# ``moe._SCATTER_UNDER`` that sends the token-order moves down each path
# whatever the held share (a buffer with nothing held scatters either way)
_MOVES = {"scatter": 0, "slots": 10 ** 6}


def _pairs(t, k, seed=5):
    """A seeded routing of ``t`` tokens to ``k`` of 8 experts, 4 held."""
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((t, 8)), axis=1)[:, :k].astype(np.int32)
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    order, place, sizes = moe.pair_order(jnp.asarray(experts.reshape(-1)),
                                         (2, 4))
    return weights, order, place, int(sizes.sum())


@pytest.mark.parametrize("top_k", [2, 4])
@pytest.mark.parametrize("held_rows", ["none", "some", "all"])
def test_combine_and_its_hand_written_backward(held_rows, top_k,
                                               monkeypatch):
    """``moe.combine`` (the held prefix scatter-added, or the slots
    gathered; no ``[T, k, D]`` array either way) and its backward in
    sorted order against ``jax.grad`` of the plain form, in float32: the
    value, ``d out`` and ``d weights``, with no row held, the routed
    prefix held, and every row held, on both paths of the token-order
    move."""
    t, dim = 24, 16
    weights, order, place, routed = _pairs(t, top_k)
    held = {"none": 0, "some": routed, "all": t * top_k}[held_rows]
    assert 0 < routed < t * top_k
    rng = np.random.default_rng(6)
    out = jnp.asarray(rng.standard_normal((t * top_k, dim)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((t, dim)), jnp.float32)
    want, plain = jax.vjp(lambda o, w: _plain_combine(o, w, place, held),
                          out, weights)
    for path in _MOVES:
        monkeypatch.setattr(moe, "_SCATTER_UNDER", _MOVES[path])
        got, pull = jax.vjp(
            lambda o, w: moe.combine(o, w, order, place, held), out, weights)
        assert got.dtype == jnp.float32 and got.shape == (t, dim)
        grads = pull(ct)
        if held == 0:
            assert not np.any(got)
            assert not any(np.any(g) for g in grads)
        else:
            assert rel(got, want) < 1e-6, path
            for mine, theirs in zip(grads, plain(ct)):
                assert rel(mine, theirs) < 1e-6
        # rows of no group get no cotangent
        assert not np.any(np.asarray(grads[0])[held:])


def test_dispatch_backward_sums_a_tokens_rows_slot_by_slot(monkeypatch):
    """A token's cotangent is the sum of its held rows' cotangents, on
    both paths of the token-order move (the held rows scatter-added, or
    the ``k`` slots gathered and masked)."""
    t, k, dim = 24, 2, 16
    _, order, place, routed = _pairs(t, k)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((t, dim)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((t * k, dim)), jnp.float32)
    masked = np.where(np.arange(t * k)[:, None] < routed, ct, 0)
    want = masked[np.asarray(place)].reshape(t, k, dim).sum(1)
    for path in _MOVES:
        monkeypatch.setattr(moe, "_SCATTER_UNDER", _MOVES[path])
        rows, pull = jax.vjp(
            lambda x: moe._dispatch(x, order, place, routed, k), x)
        np.testing.assert_array_equal(
            rows, np.asarray(x)[np.asarray(order) // k])
        assert rel(pull(ct)[0], want) < 1e-6, path


@pytest.mark.parametrize("held_rows", [0, 1, 15, 16, 17, "all"])
@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_held_rows_reach_their_tokens_as_the_masked_slot_sum(
        top_k, held_rows, monkeypatch):
    """``moe._to_tokens`` on both paths (scatter-added a chunk of 16 a
    turn, or ``k`` slots gathered) against the plain masked ``k``-slot
    sum: nothing held, one row, a row under, at and over a chunk's edge
    and every row of a buffer of ``27·k`` rows, no multiple of the chunk;
    the first chunk holds one token twice (``k`` over 1). Float32 rows,
    weighted, to 1e-6; bfloat16 rows summed in float32 to 1e-6, and
    dispatch's backward that sum rounded once."""
    monkeypatch.setattr(moe, "_CHUNK", 16)
    t, dim = 27, 8
    pairs = t * top_k
    held = pairs if held_rows == "all" else held_rows
    assert pairs % 16
    rng = np.random.default_rng(13)
    # sorted row r holds pair order[r]; pairs 0 and 1 (token 0's first two
    # when k > 1) lead, the rest in a seeded order
    order = np.concatenate([[0, 1][:pairs], 2 + rng.permutation(pairs - 2)])
    order, place = jnp.asarray(order, jnp.int32), jnp.asarray(
        np.argsort(order), jnp.int32)
    weights = jnp.asarray(rng.random((t, top_k)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((pairs, dim)), jnp.float32)
    halves = rows.astype(jnp.bfloat16)
    ones = jnp.ones((t, top_k), jnp.float32)
    want = _plain_combine(rows, weights, place, held)
    want_halves = _plain_combine(halves, ones, place, held)
    for path in _MOVES:
        monkeypatch.setattr(moe, "_SCATTER_UNDER", _MOVES[path])
        move = jax.jit(functools.partial(moe._to_tokens, k=top_k))
        got = move(rows, order, place, jnp.int32(held), weights=weights)
        got_halves = move(halves, order, place, jnp.int32(held))
        assert got.dtype == got_halves.dtype == jnp.float32
        assert got.shape == (t, dim)
        if held:
            assert rel(got, want) < 1e-6, path
            assert rel(got_halves, want_halves) < 1e-6, path
        else:
            assert not np.any(got) and not np.any(got_halves)
        ct = jax.vjp(lambda x: moe._dispatch(x, order, place, jnp.int32(held),
                                             top_k),
                     jnp.zeros((t, dim), jnp.bfloat16))[1](halves)[0]
        assert ct.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(ct, np.float32), np.asarray(
            got_halves.astype(jnp.bfloat16), np.float32))


def _route_ops(text):
    """``{(op, dims, where): n}`` of the sorts, scatters and gathers that
    a compiled program's text files under ``moe.route``, and of the rows
    each scatter moves (its updates) as ``op`` ``"scattered"``: unit
    dimensions dropped; ``where`` is ``"while"`` inside the body of a
    ``while``, ``"slots"`` inside the token-order moves' slot-gather
    branch (the ``cond``'s first), else ``""``."""
    import collections

    def dims(text):
        return tuple(int(v) for v in text.split(",") if v and int(v) != 1)

    shapes = dict(re.findall(r"(%[\w.\-]+) = \(?[a-z0-9]+\[([0-9,]*)\]",
                             text))
    found = collections.Counter()
    for line in text.splitlines():
        m = re.search(r"= \(?[a-z0-9]+\[([0-9,]*)\][^=]*? "
                      r"(sort|scatter|gather)\(([^)]*)\)", line)
        if m and "moe.route" in line:
            where = ("while" if "/while/body/" in line else
                     "slots" if "/cond/branch_0_fun/" in line else "")
            found[m.group(2), dims(m.group(1)), where] += 1
            if m.group(2) == "scatter":
                updates = shapes[m.group(3).split(", ")[-1]]
                found["scattered", dims(updates), where] += 1
    return found


def _route_loop_bodies(text):
    """The text of each ``while`` body under ``moe.route`` that
    scatter-adds, by name."""
    import re

    bodies = {}
    for body, op in re.findall(
            r" while\(.*?body=(%[\w.\-]+).*?op_name=\"([^\"]+)\"", text):
        block = re.search("^" + re.escape(body) + r" .*?^}", text,
                          re.S | re.M).group(0)
        if "moe.route" in op and "scatter-add" in block:
            bodies[body] = block
    return bodies


def test_a_rematerialised_block_orders_once_gathers_thrice_and_scatters_twice(
        monkeypatch):
    """The compiled gradient of two rematerialised routed blocks: one
    sort and one index scatter a layer (the ordering is saved with the
    selection, not recomputed). Of the five moves of rows a layer the
    three in sorted order (dispatch forward and recomputed, the
    cotangent of the experts' output) are loops over chunks of the held
    prefix that gather a chunk a turn: no gather of ``[T·k, D]`` rows is
    left. The two in token order (the combine and dispatch's backward)
    are loops that scatter-add a chunk of ``(chunk, D)`` rows a turn into
    the ``[T, D]`` sum they carry, in place: no copy of a ``[T, D]``
    buffer in their bodies. Their ``k`` slot gathers of ``[T, D]`` are
    left only in the branch a large held share takes, none elsewhere."""
    monkeypatch.setattr(moe, "_CHUNK", 16)
    lm = Decoder({**BASE, "layer_types": ["conv", "full_attention"],
                  "num_dense_layers": 0})
    ids = tokens()
    text = jax.jit(jax.grad(lm.loss_fn(remat=True))).lower(
        lm.init(3), ids).compile().as_text()
    ops = _route_ops(text)
    layers, t, k = 2, ids.size, BASE["num_experts_per_tok"]
    assert moe.chunk_rows(t * k) == 16 < t * k
    assert ops["sort", (t * k,), ""] == layers
    assert ops["scatter", (t * k,), ""] == layers
    # rows 64 wide are padded to the grouped products' tile of 256, and
    # the compiler moves them at either width (the pad before or after)
    def rows(op, n, where):
        return sum(ops[op, (n, dim), where] for dim in (64, 256))

    assert rows("gather", t * k, "") == rows("gather", t * k, "while") == 0
    assert rows("gather", 16, "while") == 3 * layers
    assert rows("gather", 16, "") == 0
    # the token-order moves: a chunk's rows scatter-added in a loop into
    # the [T, D] sum
    assert rows("scattered", 16, "while") == rows("scatter", t, "while") == (
        2 * layers)
    assert rows("gather", t, "") == rows("gather", t, "while") == 0
    assert rows("gather", t, "slots") == 2 * k * layers
    # the weights of a chunk's pairs: for the cotangent in sorted order
    # and for the combine
    assert ops["gather", (16,), "while"] == 2 * layers
    assert not any(len(dims) == 3 for _, dims, _ in ops)    # no [T, k, D]
    assert not any(op == "sort" for op, _, where in ops if where)
    bodies = _route_loop_bodies(text)
    assert len(bodies) == 2 * layers
    for body in bodies.values():
        assert not re.search(rf"= f32\[{t},(64|256)\]\S* copy\(", body)

def _held_prefix_layers(dispatch, combine_rows, x, weights, scale, order,
                        place, held_rows, k):
    """Two rematerialised layers inside one ``lax.scan``: rows into sorted
    order, scaled, and back at their tokens under ``weights``."""
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def layer(x, scale):
        rows = dispatch(x, order, place, held_rows, k) * scale
        return combine_rows(rows, weights, order, place, held_rows), None

    return jax.lax.scan(layer, x, scale)[0]


@pytest.mark.parametrize("held_rows", [0, 1, 15, 16, 17, 52])
def test_held_prefix_loops_against_the_masked_whole_gather(held_rows,
                                                           monkeypatch):
    """``_dispatch`` and ``combine`` (the loops of ``_over_held`` in
    dispatch's forward and combine's backward; the token-order moves'
    scatter-add loops, and their slot gathers) against the whole gather
    masked past the prefix, with nothing held, one row, a row under, at
    and over a chunk's edge (16) and every row of a buffer (52) that is
    no multiple of a chunk, whose last chunk starts on rows the turn
    before has added: values and gradients under ``jit(grad)``,
    rematerialised inside a ``lax.scan``, the prefix's length a value on
    the device."""
    monkeypatch.setattr(moe, "_CHUNK", 16)
    t, k, dim = 26, 2, 16
    weights, order, place, _ = _pairs(t, k)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((t, dim)), jnp.float32)
    scale = jnp.asarray(rng.standard_normal((2, t * k, dim)), jnp.float32)

    def plain_dispatch(x, order, place, held_rows, k):
        return jnp.where(jnp.arange(t * k)[:, None] < held_rows,
                         x[order // k], 0.0)

    def plain_combine(rows, weights, order, place, held_rows):
        return _plain_combine(rows, weights, place, held_rows)

    def loss(forms, x, weights, held_rows):
        out = _held_prefix_layers(*forms, x, weights, scale, order, place,
                                  held_rows, k)
        return (out * jnp.cos(out)).sum()

    held = jnp.int32(held_rows)
    rows = np.asarray(jax.jit(moe._dispatch, static_argnums=4)(
        x, order, place, held, k))
    turns = -(-held_rows // 16)
    written = min(turns * 16, t * k)
    np.testing.assert_array_equal(
        rows[:written], np.asarray(x)[np.asarray(order) // k][:written])
    assert not np.any(rows[written:])
    want = jax.jit(jax.value_and_grad(functools.partial(
        loss, (plain_dispatch, plain_combine)), (0, 1)))(x, weights, held)
    for path in _MOVES:
        monkeypatch.setattr(moe, "_SCATTER_UNDER", _MOVES[path])
        got = jax.jit(jax.value_and_grad(functools.partial(
            loss, (moe._dispatch, moe.combine)), (0, 1)))(x, weights, held)
        for mine, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if np.any(theirs):
                assert rel(mine, theirs) < 1e-6, path
            else:
                assert not np.any(mine)
    assert bool(np.any(want[1][0])) == (held_rows > 0)


@pytest.mark.parametrize("held_rows", [0, 17, 52])
@pytest.mark.parametrize("act", list(moe.ACTS))
def test_the_activations_hand_written_backward(act, held_rows, monkeypatch):
    """``moe._activate`` and its backward, a chunk of the held prefix a
    turn, against ``jax.vjp`` of the plain expression on the held rows.
    Past the prefix's last chunk the value is zeros, the gate's
    cotangent, written over the incoming cotangent, is still that
    cotangent, and the up-projection's, written over the up-projection,
    still the up-projection."""
    monkeypatch.setattr(moe, "_CHUNK", 16)
    n, wide = 52, 24
    rng = np.random.default_rng(12)
    products = tuple(
        jnp.asarray(rng.standard_normal((n, wide)), jnp.float32)
        for _ in range(1 + moe.ACTS[act]))
    ct = jnp.asarray(rng.standard_normal((n, wide)), jnp.float32)
    plain = {"silu": lambda gate, up: jax.nn.silu(gate) * up,
             "relu2": lambda gate: jnp.square(jnp.maximum(gate, 0))}[act]
    got, pull = jax.vjp(
        lambda *rows: moe._activate(act, rows, jnp.int32(held_rows)),
        *products)
    want, plain_pull = jax.vjp(plain, *products)
    written = min(-(-held_rows // 16) * 16, n)
    for mine, theirs, past in zip((got, *pull(ct)), (want, *plain_pull(ct)),
                                  (jnp.zeros_like(got), ct, *products[1:])):
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype
        if written:
            assert rel(mine[:written], theirs[:written]) < 1e-6
        np.testing.assert_array_equal(mine[written:], past[written:])


def test_bfloat16_forward_is_the_parents_formula(monkeypatch):
    """``routed_ff`` in bfloat16 against ordering, grouped products and
    the ``[T, k, D]`` weighted sum written as the parent had them: the
    float32 sum before its one rounding to 1e-6, the rounded layer to a
    last place of bfloat16 in an element or two (two programs contract
    multiply-adds apart)."""
    lm, p = build("conv+routed")
    name, k, held = "layers.0.moe", 2, (2, 4)
    p = {key: jnp.asarray(v, jnp.bfloat16) for key, v in p.items()}
    x = jnp.asarray(np.random.default_rng(9).standard_normal((2, 16, 64)),
                    jnp.bfloat16)

    def sorted_outputs(p, x):
        tok = x.reshape(-1, 64)
        experts, weights = moe.route(p, name, tok, top_k=k)
        order, place, sizes = moe.pair_order(experts.reshape(-1), held)
        dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes)
        rows = tok[order // k]
        gate = jax.nn.silu(dot(rows, p[name + ".w1"])) * dot(
            rows, p[name + ".w3"])
        return dot(gate, p[name + ".w2"]), weights, order, place, sizes.sum()

    def parent(p, x):
        out, weights, _, place, held_rows = sorted_outputs(p, x)
        return _plain_combine(out, weights, place, held_rows)

    want = jax.jit(parent)(p, x)
    assert np.any(np.asarray(want))
    for path in _MOVES:
        monkeypatch.setattr(moe, "_SCATTER_UNDER", _MOVES[path])
        assert rel(jax.jit(lambda p, x: moe.combine(*sorted_outputs(p, x)))(
            p, x), want) < 1e-6, path
    monkeypatch.undo()
    got, _ = jax.jit(lambda p, x: moe.routed_ff(
        p, name, x, top_k=k, held=held))(p, x)
    assert got.dtype == jnp.bfloat16
    assert rel(got, want.astype(jnp.bfloat16).reshape(x.shape)) < 1e-4


def test_pair_order_sorts_held_pairs_first_and_counts_them():
    experts = jnp.asarray([5, 2, 9, 3, 2, 0, 3, 3], jnp.int32)
    order, place, sizes = moe.pair_order(experts, (2, 2))
    assert sizes.tolist() == [2, 3]
    assert np.asarray(experts)[np.asarray(order)].tolist()[:5] == [
        2, 2, 3, 3, 3]
    assert np.asarray(order)[np.asarray(place)].tolist() == list(range(8))


def test_short_convolution_against_a_loop_over_time():
    rng = np.random.default_rng(5)
    p = lm_blocks.init_conv(rng, "c", 8, 3)
    x = rng.standard_normal((2, 10, 8)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_blocks.conv_op(p, "c", jnp.asarray(x)))
    b, c, u = np.split(x @ p["c.in_proj"], 3, axis=-1)
    y = b * u
    v = np.zeros_like(y)
    for t in range(10):
        for j in range(3):
            if t - 2 + j >= 0:
                v[:, t] += p["c.kernel"][j] * y[:, t - 2 + j]
    want = (c * v) @ p["c.out_proj"]
    assert rel(got, want) < 1e-5
    # causal: a later input moves no earlier output
    x2 = x.copy()
    x2[:, 7:] += 1.0
    again = np.asarray(lm_blocks.conv_op(p, "c", jnp.asarray(x2)))
    np.testing.assert_array_equal(again[:, :7], got[:, :7])


def test_rotary_turns_half_split_pairs_and_keeps_norms():
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 5, 2, 8)), jnp.float32)
    y = lm_blocks.rotary(x, 1e6)
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)   # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # pair (0, 4) of position 1 turns by 1 radian
    want = x[0, 1, 0, 0] * np.cos(1.0) - x[0, 1, 0, 4] * np.sin(1.0)
    assert abs(float(y[0, 1, 0, 0]) - float(want)) < 1e-5
    assert rel(y, R.rotary(x, 1e6)) < 1e-6


# ---- the reference -------------------------------------------------------
def test_the_reference_takes_what_run_py_hands_it():
    """benchmark/run.py traces ``forward`` on float32 ids of shape
    (1, S); the tier-1 tests and the cell read one file."""
    lm, p = build("whole")
    ids = tokens()
    forward = jax.jit(lambda q, x: R.forward(q, x, **REF))
    np.testing.assert_array_equal(forward(p, ids[:1].astype(np.float32)),
                                  forward(p, ids[:1]))
    assert not os.path.exists(os.path.join(REPO, "tpudl", "testing",
                                           "lfm2_reference.py"))


def test_reference_routes_argument_replaces_only_the_choice():
    lm, p = build("conv+routed")
    ids = tokens()
    own = R.routes_of(p, ids, **REF)
    assert len(own) == 1 and own[0].shape == (2, 16, 2)
    assert float(R.loss(p, ids, own, **REF)) == float(R.loss(p, ids, **REF))
    other = [(np.asarray(own[0]) + 1) % 8]
    assert float(R.loss(p, ids, other, **REF)) != float(R.loss(p, ids, **REF))


# ---- through the trainer --------------------------------------------------
def test_one_adamw_step_through_trainer_fit_recovers_the_gradient():
    """After one AdamW step from zero moments mu = (1 - b1) g: what the
    cell's check() reads back from the timed path."""
    lm, p = build("whole")
    ids = tokens(shape=(2, 32))
    loss = lm.loss_fn()
    trainer = Trainer(loss, optax.adamw(3e-4, b1=0.9, b2=0.95,
                                        weight_decay=0.1,
                                        mask=lm.decay_mask))
    p1, opt, history = trainer.fit(p, lambda step: (ids,), steps=1)
    mu = [s for s in jax.tree.leaves(opt, is_leaf=lambda s: hasattr(s, "mu"))
          if hasattr(s, "mu")][0].mu
    want_loss, want = jax.jit(jax.value_and_grad(loss))(p, ids)
    assert abs(history[-1]["loss"] - float(want_loss)) < 1e-5
    for name in want:
        if np.any(want[name]):
            assert rel(np.asarray(mu[name]) / 0.1, want[name]) < 1e-5, name
    # the buffer is constant: no gradient, no decay
    np.testing.assert_array_equal(p1["layers.1.moe.expert_bias"],
                                  p["layers.1.moe.expert_bias"])
    assert np.any(np.asarray(p1["layers.1.moe.router"])
                  != p["layers.1.moe.router"])


def test_fit_consume_hands_the_state_over_without_a_copy():
    from tpudl.train import HorovodRunner

    lm, p = build("conv+dense")
    ids = tokens()

    def data(step):
        return (ids,)

    def main(ctx):
        trainer = ctx.trainer(lm.loss_fn(), optax.sgd(0.1))
        a, opt, _ = trainer.fit(p, data, steps=1)
        kept, _, _ = trainer.fit(a, data, steps=2, opt_state=opt)
        assert not a["embed"].is_deleted()      # the default owns a copy
        b, _, _ = trainer.fit(a, data, steps=2, opt_state=opt, consume=True)
        assert a["embed"].is_deleted()          # handed over and donated
        for name in b:
            np.testing.assert_array_equal(b[name], kept[name])
        return True

    assert HorovodRunner(np=1).run(main)


# ---- what the routing did, and what was traced ----------------------------
@pytest.mark.parametrize("by_sequence", [False, True])
def test_count_routes_counts_the_grouped_products_row_tiles(by_sequence):
    """``moe.row_tiles_run`` / ``_total``: per call of ``routed_ff`` (a
    sequence at a time where the layer takes one) the row tiles the held
    experts' rows touch, at the tile the call's buffer derives, and the
    buffer's tiles. Top 8 of 256 with 8 held, as the latent-attention
    cell routes: about one row in 32 is held, and the grouped products
    visit a few hundredths of the tiles."""
    from tpudl import obs

    lm = Decoder({**BASE, "layer_types": ["conv"], "num_dense_layers": 0,
                  "num_experts": 256, "num_experts_per_tok": 8,
                  "experts_held": (8, 8)})
    lm.routed_by_sequence = by_sequence
    rng = np.random.default_rng(11)
    chosen = [rng.integers(0, 256, (4, 8192, 8)) for _ in range(2)]
    before = obs.snapshot("moe.row_tiles")
    stats = lm.count_routes(chosen)
    after = obs.snapshot("moe.row_tiles")
    calls = [c for layer in chosen
             for c in (layer if by_sequence else [layer])]
    pairs = calls[0].size
    block = pallas_ops.row_tile(pairs)
    assert block == 256
    run = sum(pallas_ops.tiles_visited(np.bincount(
        c.reshape(-1)[(c.reshape(-1) >= 8) & (c.reshape(-1) < 16)] - 8,
        minlength=8), block) for c in calls)
    assert stats["row_tiles_run"] == run
    assert stats["row_tiles_total"] == len(calls) * pairs // block == 2048
    for key in ("row_tiles_run", "row_tiles_total"):
        assert (after[f"moe.{key}"]["value"]
                - before.get(f"moe.{key}", {"value": 0})["value"]) == stats[
                    key]
        assert stats[key] == sum(rec[key] for rec in stats["layers"])
    # the held rows over the tile, and at most one more tile an expert
    held = stats["pairs_held"]
    assert held / block <= run <= held / block + 8 * len(calls)
    assert run / stats["row_tiles_total"] <= 0.08


def test_route_stats_counts_pairs_and_publishes_counters():
    from tpudl import obs

    lm, p = build("whole")
    ids = tokens()
    before = obs.snapshot()
    stats = lm.route_stats(p, ids)
    after = obs.snapshot()
    routes = [np.asarray(r) for r in lm.routes(p, ids)]
    assert len(stats["layers"]) == 2
    assert stats["pairs_total"] == 2 * ids.size * 2
    held = sum(int(((r >= 2) & (r < 6)).sum()) for r in routes)
    assert stats["pairs_held"] == held
    assert stats["expert_tokens_max"] == max(
        max(rec["expert_tokens"]) for rec in stats["layers"])
    assert sum(stats["layers"][0]["expert_tokens"]) == stats["layers"][0][
        "pairs_held"]

    def moved(name):
        return (after[name]["value"]
                - before.get(name, {"value": 0})["value"])

    assert moved("moe.pairs_held") == held
    assert moved("moe.pairs_total") == stats["pairs_total"]
    # the turns of the routed layer's held-prefix loops: toy buffers are
    # one chunk each, run unless a layer holds nothing
    assert moe.chunk_rows(ids.size * 2) == ids.size * 2
    assert stats["chunks_total"] == moved("moe.chunks_total") == 2
    assert stats["chunks_run"] == moved("moe.chunks_run") == sum(
        rec["pairs_held"] > 0 for rec in stats["layers"])
    assert moe.chunk_rows(10 ** 6) == moe._CHUNK
    # the token-order moves: a layer holding under a sixth of its rows
    # scatter-adds them, its prefix rounded up to a chunk (here the
    # buffer), else gathers all; twice a layer, of 2·T·k
    assert stats["token_rows_total"] == moved("moe.token_rows_total") == (
        2 * stats["pairs_total"])
    assert stats["token_rows_run"] == moved("moe.token_rows_run") == sum(
        2 * (0 if rec["pairs_held"] == 0 else ids.size * 2)
        for rec in stats["layers"])
    assert [moe.token_rows(held, 6 * 2048) for held in (0, 1, 1024, 1025,
                                                        2047, 2048)] == [
        0, 1024, 1024, 2048, 2048, 6 * 2048]
    jax.eval_shape(lm.loss_fn(), p, ids)
    assert obs.snapshot()["moe.chunk_rows"]["value"] == ids.size * 2
    assert after["moe.expert_tokens_max"]["value"] == stats[
        "expert_tokens_max"]
    # bf16 arithmetic may flip a near-tie, never the totals
    assert lm.route_stats(p, ids, jnp.bfloat16)["pairs_total"] == stats[
        "pairs_total"]


def test_layers_are_counted_by_kind_while_a_program_is_traced():
    from tpudl import obs

    lm, p = build("whole")
    before = obs.snapshot()
    jax.jit(lm.logits).lower(p, tokens())
    after = obs.snapshot()
    for kind, n in {"conv": 2, "attention": 1, "dense": 1,
                    "routed": 2}.items():
        name = f"zoo.lm.layers.{kind}"
        assert (after[name]["value"]
                - before.get(name, {"value": 0})["value"]) == n
    assert lm.kinds() == {"conv": 2, "attention": 1, "dense": 1, "routed": 2,
                          "ssm": 0, "shared": 0}


@pytest.mark.parametrize("stack, routed", [("whole", 2), ("one period", 4),
                                           ("conv+dense", 0)])
def test_fused_combines_are_counted_per_routed_layer(stack, routed):
    """``moe.combine.fused``: 1 a routed layer a trace, a scanned run of
    layers counted by its length; a dense decoder counts none."""
    from tpudl import obs

    lm, p = build(stack)
    assert lm.kinds()["routed"] == routed
    before = obs.snapshot().get("moe.combine.fused", {"value": 0})["value"]
    jax.jit(jax.grad(lm.loss_fn(remat=True))).lower(p, tokens())
    after = obs.snapshot().get("moe.combine.fused", {"value": 0})["value"]
    assert after - before == routed


def test_config_errors_name_what_is_wrong():
    with pytest.raises(ValueError, match="layer_types"):
        Decoder({**BASE, "layer_types": ["mamba"]})
    with pytest.raises(ValueError, match="experts_held"):
        Decoder({**BASE, "layer_types": ["conv"], "num_dense_layers": 0,
                 "experts_held": (6, 4)})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        Decoder({**BASE, "layer_types": ["conv"], "num_hidden_layers": 2})
    with pytest.raises(ValueError, match="conv_bias"):
        Decoder({**BASE, "layer_types": ["conv"], "conv_bias": True})


def test_published_parameter_count_of_the_chips_share():
    """507.8 M at the published widths, counted from shapes alone."""
    cfg = dict(hidden_size=2048, intermediate_size=7168,
               moe_intermediate_size=1792, num_attention_heads=32,
               num_key_value_heads=8, num_experts=32, num_experts_per_tok=4,
               layer_types=["conv", "full_attention", "conv", "conv", "conv"],
               num_dense_layers=1, experts_held=(0, 8), vocab_size=65536,
               vocab_slice=(0, 16384))
    small = Decoder({**cfg, "hidden_size": 32, "intermediate_size": 112,
                     "moe_intermediate_size": 28, "num_attention_heads": 4,
                     "num_key_value_heads": 1, "head_dim": 8,
                     "vocab_slice": (0, 256)}).init(0)
    scale = {32: 2048, 96: 6144, 112: 7168, 28: 1792, 256: 16384, 8: 64}
    total = 0
    for name, leaf in small.items():
        shape = list(leaf.shape)
        if ".moe.w" in name:
            shape = [shape[0]] + [scale[d] for d in shape[1:]]
        elif name.endswith(".router"):
            shape = [scale[shape[0]], shape[1]]
        elif name.endswith(("q_norm", "k_norm")):
            shape = [64]
        elif name.endswith(".kernel"):
            shape = [3, scale[shape[1]]]
        elif name.endswith("expert_bias"):
            pass
        elif name.endswith(".k_proj") or name.endswith(".v_proj"):
            shape = [2048, 512]
        else:
            shape = [scale[d] for d in shape]
        total += int(np.prod(shape))
    assert abs(total - 507.82e6) < 0.01e6
    assert Decoder(cfg).kinds() == {"conv": 4, "attention": 1, "dense": 1,
                                    "routed": 4, "ssm": 0, "shared": 0}


# ---- the cell's check, on deliberate faults -------------------------------
@pytest.fixture(scope="module")
def lm_train():
    import sys

    sys.path.insert(0, REPO)
    return _load(os.path.join(REPO, "benchmark", "adapters", "lm_train.py"),
                 "benchmark_adapter_lm_train_for_tests")


# the gradient limit lies between two readings at these toy widths, over
# eight token seeds: clean bfloat16 0.024-0.043 by group, the experts under
# the scaled-fp8 product 0.077-0.083 (0.08 sat inside that spread, and the
# case turned on the seed's rounding)
LIMITS = {"grad_rel_l2": {g: 0.055 for g in (
    "experts", "routers", "conv", "attention", "dense_ff", "table",
    "norms")}, "loss_rel": 0.002, "route_agreement_min": 0.9,
    "update_rel_l2": 3e-4, "moment2_rel_l2": 1e-3}
ADAMW = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}


def _first_step(lm_train, lm, p, ids, optimizer=None):
    """One AdamW step of the bf16 program with the routes tap through
    Trainer.fit, as the cell's warm() takes it: the step's gradient
    (mu / (1 - b1)), the routes read from the tap, the step's loss, and
    the parameters and second moments after it."""
    p = {**p, lm_train.TAP: np.zeros((lm.kinds()["routed"], *ids.shape,
                                      lm.top_k), np.float32)}
    trainer = Trainer(
        with_compute_dtype(lm_train.tapped(lm.loss_fn(with_routes=True)),
                           jnp.bfloat16),
        optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                                 mask=lm.decay_mask))
    p1, opt, history = trainer.fit(p, lambda step: (ids,), steps=1)
    adam = lm_train.adam_state(opt)
    got = {k: np.asarray(v) / np.float32(0.1) for k, v in adam.mu.items()}
    routes = np.rint(got.pop(lm_train.TAP)).astype(np.int32)
    update = lm_train.worst_leaf(jax.jit(
        lambda *state: lm_train.update_errors(*state, ADAMW))(
            p, p1, adam.mu, adam.nu))
    return got, list(routes), history[-1]["loss"], update


def test_the_routes_tap_reads_the_steps_own_choices(lm_train):
    """The tap adds exactly 0 to the loss, whatever it holds, and its
    gradient is the experts the same program selected."""
    lm, p = build("whole")
    ids = tokens(seed=7, shape=(2, 32))
    plain = jax.jit(lm.loss_fn())(p, ids)
    loss = jax.jit(lm_train.tapped(lm.loss_fn(with_routes=True)))
    tap = np.random.default_rng(0).standard_normal(
        (2, 2, 32, 2)).astype(np.float32)
    assert float(loss({**p, lm_train.TAP: tap}, ids)) == float(plain)
    grads = jax.jit(jax.grad(lm_train.tapped(lm.loss_fn(with_routes=True))))(
        {**p, lm_train.TAP: tap}, ids)
    want = jax.jit(lm.routes)(p, ids)
    np.testing.assert_array_equal(grads[lm_train.TAP], np.stack(want))
    # through the trainer, in bf16, out of AdamW's first moment
    _, routes, _, _ = _first_step(lm_train, lm, p, ids)
    bf16 = jax.jit(with_compute_dtype(lm.routes, jnp.bfloat16))(p, ids)
    assert len(routes) == 2
    for mine, theirs in zip(routes, bf16):
        assert mine.shape == theirs.shape == (2, 32, 2)
        assert (mine == np.asarray(theirs)).mean() > 0.95


def _fp8_control():
    return _load(os.path.join(REPO, "benchmark", "controls",
                              "lm_fp8_experts.py"), "lm_fp8_experts_control")


FAULTS = {   # fault -> what has to be over its limit
    "none": set(),
    "dropped_pair": {"experts", "routers"},
    "no_renorm": {"experts", "routers"},
    "fp8_product": {"experts"},
    "lowest_k": {"route_agreement"},
    "warm_up_schedule": {"update_rel_l2"},
    "no_decay": {"update_rel_l2"},
    "wrong_b2": {"moment2_rel_l2"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_check_fails_on_each_fault(fault, lm_train, monkeypatch):
    """compare_groups, update_errors and verdict, as the cell uses them, on the step program's own first step: clean bf16 passes;
    a single dropped pair, a skipped renormalisation, a scaled fp8 product
    in the experts, a selection the reference would not make, and an
    optimizer other than the one named each put a reading outside its
    limit."""
    lm, p = build("whole")
    ids = tokens(seed=7, shape=(2, 32))
    optimizer = None
    if fault == "dropped_pair":
        real = moe.pair_order

        def dropping(experts, held):
            # the first held pair of the batch goes to no expert
            first = jnp.argmax((experts >= held[0])
                               & (experts < held[0] + held[1]))
            return real(experts.at[first].set(held[0] + held[1]), held)

        monkeypatch.setattr(moe, "pair_order", dropping)
    elif fault == "no_renorm":
        real = moe.route

        def unnormalised(q, name, x, **kw):
            experts, weights = real(q, name, x, **kw)
            scores = jax.nn.sigmoid(jnp.dot(
                x, q[name + ".router"], preferred_element_type=jnp.float32))
            return experts, jnp.take_along_axis(scores, experts, axis=-1)

        monkeypatch.setattr(moe, "route", unnormalised)
    elif fault == "fp8_product":
        fp8 = _fp8_control().fp8_ragged_dot

        def fp8_grouped(lhs, rhs, group_sizes, **kw):
            # the program's grouped product, with a tuple of weights
            if isinstance(rhs, tuple):
                return tuple(fp8(lhs, w, group_sizes) for w in rhs)
            return fp8(lhs, rhs, group_sizes)

        monkeypatch.setattr(pallas_ops, "grouped_dot", fp8_grouped)
    elif fault == "lowest_k":
        real = jax.lax.top_k
        monkeypatch.setattr(jax.lax, "top_k",
                            lambda x, k: real(-x, k))
    elif fault == "warm_up_schedule":
        optimizer = optax.adamw(optax.linear_schedule(0.0, 3e-4, 2000),
                                b1=0.9, b2=0.95, weight_decay=0.1,
                                mask=lm.decay_mask)
    elif fault == "no_decay":
        optimizer = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.0)
    elif fault == "wrong_b2":
        optimizer = optax.adamw(3e-4, b1=0.9, b2=0.999, weight_decay=0.1,
                                mask=lm.decay_mask)
    got, routes, loss, update = _first_step(lm_train, lm, p, ids, optimizer)
    monkeypatch.undo()
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda q: R.loss(q, ids, routes, **REF)))(p)
    own = jax.jit(lambda q: R.routes_of(q, ids, **REF))(p)
    agreement = float(np.mean([
        (mine[..., :, None] == np.asarray(theirs)[..., None, :]).any(-1)
        for mine, theirs in zip(routes, own)]))
    readings = {"grad_rel_l2": lm_train.compare_groups(got, want),
                "loss_rel": abs(loss - float(want_loss)) / float(want_loss),
                "route_agreement": agreement,
                **update,
                "loss_first": loss, "loss_again": loss - 1.0}
    assert set(readings["grad_rel_l2"]) == set(LIMITS["grad_rel_l2"])
    over = lm_train.verdict(readings, LIMITS)
    print(fault, {k: v for k, v in readings.items() if k != "grad_rel_l2"},
          {k: round(v, 4) for k, v in readings["grad_rel_l2"].items()})
    if fault == "none":
        assert over == {}, readings
        assert "loss_again" in lm_train.verdict(
            {**readings, "loss_again": loss}, LIMITS)   # an unchanged state
    else:
        assert set(over) & FAULTS[fault], (fault, over)


def test_gradient_groups_cover_every_leaf(lm_train):
    lm, p = build("whole")
    kinds = {lm_train.group_of(name) for name in p}
    assert kinds == set(LIMITS["grad_rel_l2"])
    with pytest.raises(KeyError):
        lm_train.group_of("layers.0.unknown.leaf")
    assert lm_train.decoder_config(
        {"num_experts": 8, "vocab_size": 16, "hidden_size": 4,
         "published": {"num_experts": 32, "vocab_size": 64}}) == {
             "num_experts": 32, "vocab_size": 64, "hidden_size": 4}


@pytest.mark.parametrize("stack, bodies", [
    ("whole", 1), ("one period", 1), ("attention+routed", 1),
    ("conv+dense", 0)])
def test_the_flash_forward_is_saved_across_rematerialisation(
        stack, bodies, check_flash_saved_once):
    lm, p = build(stack)
    check_flash_saved_once(lm, p, tokens(), bodies=bodies)
