"""Plain float32 reference of ``lfm2-8b-a1b-ep4``: one chip's share of LFM2-8B-A1B.

LiquidAI's ``lfm2_moe`` decoder as its ``config.json`` and the family's
public modelling code describe it, in straightforward ``jax.numpy``: float32,
matrix products at ``highest`` precision, no kernels, no sort, no cache.
The tier-1 tests load this file by its path (``tests/test_lm_decoder.py``).

    block l:  h  = x + Op_l(RMSNorm(x; operator_norm))
              x' = h + FF_l(RMSNorm(h; ffn_norm))
    head:     logits = RMSNorm(x; embedding_norm) . E^T      (tied table)

    Op = conv            [B, C, u] = split3(x W_in);  v_t = sum_j w_j (B u)_{t-2+j}
                         (depthwise, causal, kernel 3, zeros before the
                         sequence);  out = (C v) W_out
    Op = full_attention  q, k: RMSNorm over each head of 64, then rotary
                         (half-split pairs); causal softmax at 1/sqrt(64);
                         each key/value head serves H / Hkv query heads
    FF dense             W2 (silu(W1 x) * W3 x)
    FF routed            s = sigmoid(x Wg);  selected = top-k of s + b;
                         w = s[selected] / (sum + 1e-6) * scaling;
                         y = sum over the selected experts THIS CHIP HOLDS
                         of w_e . W2e (silu(W1e x) * W3e x)

The kind of each layer is read from the parameters' names, the numbers of
heads from their shapes; what shapes cannot say comes as keyword arguments
whose defaults are the published values (``PUBLISHED``).

Departures from the published model, each on purpose:

- the chip's share: ``moe.w1/w2/w3`` stack only the experts held here
  (``held_first`` .. ``held_first + count - 1``); the router keeps every
  published output and the top-k. What the absent experts would have added
  is left out, and that partial result goes on to the next layer;
- the table is the chip's slice of the vocabulary and is tied to the head
  (the family's convention; ``assumed`` in the configuration file);
- every held expert is applied to every token and masked by its weight:
  the obviously right form, eight times the needed work;
- attention is a masked softmax over blocks of query rows and each block
  of the decoder is rematerialised in the backward pass (``jax.checkpoint``),
  so that the gradient at 8,192 tokens fits beside nothing else on a chip.
  Neither changes a value;
- ``routes`` (expert indices per routed layer) replaces the reference's own
  discrete top-k, so that a gradient can be compared on the choices another
  program made; the scores, the weights and their gradients stay its own.
"""

import jax
import jax.numpy as jnp

PUBLISHED = {"norm_eps": 1e-5, "rope_theta": 1e6, "top_k": 4,
             "held_first": 0, "routed_scaling_factor": 1.0,
             "attention_rows": 512}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """``x``: (B, S, H, d). Position t turns the pair (x_i, x_{i+d/2}) by
    t . theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def conv_op(params, name, x):
    """The double-gated short convolution. ``x``: (B, S, D)."""
    b, c, u = jnp.split(x @ params[name + ".in_proj"], 3, axis=-1)
    y = b * u
    taps = params[name + ".kernel"]                      # (K, D), K = 3
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(y, ((0, 0), (k - 1, 0), (0, 0)))
    v = sum(taps[j] * padded[:, j:j + s] for j in range(k))
    return (c * v) @ params[name + ".out_proj"]


def attention_op(params, name, x, eps, theta, rows):
    """Grouped-query causal attention. ``x``: (B, S, D)."""
    bsz, s, _ = x.shape
    d = params[name + ".q_norm"].shape[0]
    q = (x @ params[name + ".q_proj"]).reshape(bsz, s, -1, d)
    k = (x @ params[name + ".k_proj"]).reshape(bsz, s, -1, d)
    v = (x @ params[name + ".v_proj"]).reshape(bsz, s, -1, d)
    q = rotary(rms_norm(q, params[name + ".q_norm"], eps), theta)
    k = rotary(rms_norm(k, params[name + ".k_norm"], eps), theta)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    rows = min(rows, s)
    assert s % rows == 0, (s, rows)

    def block(args):
        qb, first = args                                 # (B, rows, H, d)
        scores = jnp.einsum("brhd,bshd->bhrs", qb, k) / jnp.sqrt(
            jnp.float32(d))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhrs,bshd->brhd", p, v)

    blocks = q.reshape(bsz, s // rows, rows, -1, d).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(block),
                      (blocks, jnp.arange(0, s, rows)))
    out = out.swapaxes(0, 1).reshape(bsz, s, -1)
    return out @ params[name + ".o_proj"]


def gated_ff(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(params, name, x, top_k, scaling, routes=None):
    """Scores, the experts selected (``routes`` if given) and their
    weights: (T.., E) float32, (T.., k) int32, (T.., k) float32."""
    scores = jax.nn.sigmoid(x @ params[name + ".router"])
    if routes is None:
        biased = scores + jax.lax.stop_gradient(params[name + ".expert_bias"])
        _, routes = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, routes, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scaling
    return scores, routes, weights


def routed_ff(params, name, x, top_k, scaling, held_first, routes=None):
    """The held experts' part of the routed feed-forward, and the routes."""
    scores, routes, weights = route(params, name, x, top_k, scaling, routes)
    experts = scores.shape[-1]
    # (T.., E): the weight of expert e for this token, 0 where not selected
    dense = (jax.nn.one_hot(routes, experts, dtype=jnp.float32)
             * weights[..., None]).sum(-2)
    w1, w3, w2 = (params[name + "." + k] for k in ("w1", "w3", "w2"))
    held = dense[..., held_first:held_first + w1.shape[0]]

    def add_expert(y, expert):
        w1e, w3e, w2e, weight = expert                   # weight: (T..)
        return y + weight[..., None] * gated_ff(x, w1e, w3e, w2e), None

    # one body for the held experts, in order: the program stays small
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (w1, w3, w2, jnp.moveaxis(held, -1, 0)))
    return y, routes


def n_layers(params):
    return sum(1 for k in params if k.endswith(".operator_norm"))


def _run(params, ids, routes, cfg):
    cfg = {**PUBLISHED, **cfg}
    eps = cfg["norm_eps"]
    ids = jnp.asarray(ids).astype(jnp.int32)
    x = params["embed"][ids]
    chosen = []

    def block(x, layer, given):
        pre = f"layers.{layer}."
        h = rms_norm(x, params[pre + "operator_norm"], eps)
        if pre + "conv.in_proj" in params:
            x = x + conv_op(params, pre + "conv", h)
        else:
            x = x + attention_op(params, pre + "attn", h, eps,
                                 cfg["rope_theta"], cfg["attention_rows"])
        h = rms_norm(x, params[pre + "ffn_norm"], eps)
        if pre + "ff.w1" in params:
            return x + gated_ff(h, params[pre + "ff.w1"],
                                params[pre + "ff.w3"],
                                params[pre + "ff.w2"]), None
        y, picked = routed_ff(params, pre + "moe", h, cfg["top_k"],
                              cfg["routed_scaling_factor"],
                              cfg["held_first"], given)
        return x + y, picked

    given = iter(routes) if routes is not None else None
    for layer in range(n_layers(params)):
        routed = f"layers.{layer}.moe.router" in params
        mine = next(given) if routed and given is not None else None
        x, picked = jax.checkpoint(block, static_argnums=(1,))(x, layer, mine)
        if routed:
            chosen.append(picked)
    x = rms_norm(x, params["embedding_norm"], eps)
    return x @ params["embed"].T, chosen


def forward(params, ids, routes=None, **cfg):
    """``ids``: (B, S) token ids of any numeric dtype (they are cast) ->
    logits (B, S, V) over the table's slice of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        return _run(params, ids, routes, cfg)[0]


def routes_of(params, ids, **cfg):
    """The reference's own float32 selection: one (B, S, k) int32 array for
    each routed layer."""
    with jax.default_matmul_precision("highest"):
        return _run(params, ids, None, cfg)[1]


def loss(params, ids, routes=None, **cfg):
    """Next-token cross-entropy, mean over the B x (S - 1) predicted
    tokens."""
    ids = jnp.asarray(ids).astype(jnp.int32)
    logits = forward(params, ids, routes, **cfg)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))
