"""Plain float32 reference of ``joyai-llm-flash-ep32``: one chip's share of
JoyAI-LLM-Flash (``model_type: joyai_llm_flash``, the DeepSeek-V3 shape).

Written from the layer equations (DeepSeek-V2's report for latent
attention, DeepSeek-V3's for the sigmoid router with its selection bias and
for the multi-token-prediction module, section 2.2) in straightforward
``jax.numpy``: float32, matrix products at ``highest`` precision, no
kernels, no sort, no cache. The tier-1 tests load this file by its path
(``tests/test_lm_mla.py``).

    layer l:  x' = x  + MLA(RMSNorm(x;  input_layernorm))
              y  = x' + FF (RMSNorm(x'; post_attention_layernorm))
    head:     logits = RMSNorm(x; embedding_norm) . W_head^T      (untied)

    MLA   c_q = RMSNorm(u W_qa);  [q_nope | q_rope] = c_q W_qb   a head
          [c_kv | k_r] = u W_kva;  [k_nope | v] = RMSNorm(c_kv) W_kvb  a head
          q_rope and the ONE k_r every head shares are rotated: the pair
          (x_2i, x_2i+1) at position t by t . theta^(-2i / rope)
          scores = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope),
          causal softmax, times v, heads concatenated, W_o
    FF    layer < first_k_dense_replace: W2 (silu(W1 x) * W3 x)
          else  s = sigmoid(x Wg);  selected = top-k of s + b;
                w = s[selected] / (sum + 1e-6) * scaling;
                y = sum over the selected experts THIS CHIP HOLDS of
                    w_e . W2e (silu(W1e x) * W3e x)
                  + W2s (silu(W1s x) * W3s x)       (the shared expert)
    MTP   h'_i = M [RMSNorm(h_i; hnorm) ; RMSNorm(Emb(t_i+1); enorm)]
          (h_i the stack's stream BEFORE its final norm, Emb the main
          embedding), one more layer as above, RMSNorm(.; final_norm), the
          main head; cross-entropy against t_i+2
    loss = mean_i<S-1 nll_main(i, t_i+1)
           + mtp_weight . mean_i<S-2 nll_mtp(i, t_i+2)

The kind of each layer is read from the parameters' names, the numbers of
heads and their widths from their shapes (``_head_sizes``); what shapes
cannot say comes as keyword arguments whose defaults are the published
values (``PUBLISHED``; ``mtp_weight`` is the configuration's assumption).

Departures from the published model, each on purpose:

- the chip's share: ``moe.w1/w3/w2`` stack only the experts held here
  (``held_first`` .. ``held_first + count - 1``); the router keeps every
  published output and the top-k. What the absent experts would have added
  is left out, and that partial result goes on to the next layer. The
  table and the head are the chip's slice of the vocabulary;
- every held expert is applied to every token and masked by its weight:
  the obviously right form, many times the needed work;
- the rotated key's part of the scores is computed from the one shared
  key, never repeated over the heads;
- attention runs over blocks of query rows, and each layer is
  rematerialised in the backward pass (``jax.checkpoint``), so that the
  gradient at 8,192 tokens fits on a chip. Neither changes a value;
- the module's input at the LAST position takes the sequence's first
  token as its "next" one, so that every array keeps ``S`` positions: the
  loss leaves that position and the one before it out, and under the
  causal mask no other position sees them;
- ``routes`` (expert indices per routed layer, the module's last) replaces
  the reference's own discrete top-k, so that a gradient can be compared on
  the choices another program made; the scores, the weights and their
  gradients stay its own.
"""

import jax
import jax.numpy as jnp

PUBLISHED = {"norm_eps": 1e-6, "top_k": 8, "held_first": 0,
             "routed_scaling_factor": 2.5, "rope_theta": 32e6,
             "mtp_weight": 0.3, "attention_rows": 512}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def gated_ff(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def rotate_pairs(x, theta, first=0):
    """``x`` (B, S, ..., d), position ``first + t`` along axis 1: the pair
    ``(x_2i, x_2i+1)`` turned by ``(first + t) . theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (first + jnp.arange(x.shape[1], dtype=jnp.float32))[:, None] * inv
    angle = angle.reshape(1, x.shape[1], *([1] * (x.ndim - 3)), d // 2)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      b * jnp.cos(angle) + a * jnp.sin(angle)],
                     -1).reshape(x.shape)


def _head_sizes(params, name):
    """``(heads, nope, rope, v)`` from the projections' shapes: ``W_qb``
    has H (nope + rope) columns, ``W_kvb`` H (nope + v), ``W_o`` H v rows,
    and ``W_kva`` the latent's width + rope."""
    rope = (params[name + ".kv_a_proj"].shape[1]
            - params[name + ".kv_b_proj"].shape[0])
    q_cols = params[name + ".q_b_proj"].shape[1]
    kv_cols = params[name + ".kv_b_proj"].shape[1]
    v_cols = params[name + ".o_proj"].shape[0]
    heads = (q_cols - kv_cols + v_cols) // rope
    return heads, (kv_cols - v_cols) // heads, rope, v_cols // heads


def _row_blocks(fn, rows_of, s, rows):
    """``fn(block of rows, first row)`` over blocks of ``rows`` rows of
    ``rows_of`` ``[B, S, ...]``, rematerialised, put back as ``[B, S,
    ...]``."""
    rows = min(rows, s)
    assert s % rows == 0, (s, rows)
    bsz = rows_of.shape[0]
    blocks = rows_of.reshape(bsz, s // rows, rows, *rows_of.shape[2:])
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)),
                      (blocks.swapaxes(0, 1), jnp.arange(0, s, rows)))
    return out.swapaxes(0, 1).reshape(bsz, s, *out.shape[3:])


def mla_op(params, name, u, eps, theta, rows):
    """Latent attention, expanded. ``u``: (B, S, D)."""
    bsz, s, _ = u.shape
    heads, nope, rope, _ = _head_sizes(params, name)
    c_q = rms_norm(u @ params[name + ".q_a_proj"], params[name + ".q_a_norm"],
                   eps)
    q = (c_q @ params[name + ".q_b_proj"]).reshape(bsz, s, heads, nope + rope)
    kv_a = u @ params[name + ".kv_a_proj"]
    c_kv, k_r = kv_a[..., :-rope], kv_a[..., -rope:]
    kv = (rms_norm(c_kv, params[name + ".kv_a_norm"], eps)
          @ params[name + ".kv_b_proj"]).reshape(bsz, s, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_r = rotate_pairs(k_r, theta)                         # (B, S, rope)

    def block(qb, first):                           # (B, rows, H, nope+rope)
        q_rope = rotate_pairs(qb[..., nope:], theta, first)
        scores = (jnp.einsum("brhd,bshd->bhrs", qb[..., :nope], k_nope)
                  + jnp.einsum("brhd,bsd->bhrs", q_rope, k_r)
                  ) / jnp.sqrt(jnp.float32(nope + rope))
        seen = (first + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(
            s)[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhrs,bshd->brhd", p, v)

    out = _row_blocks(block, q, s, rows).reshape(bsz, s, -1)
    return out @ params[name + ".o_proj"]


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
    """The held experts' part of the routed feed-forward (without the
    shared expert), and the routes."""
    scores, routes, weights = route(params, name, x, top_k, scaling, routes)
    experts = scores.shape[-1]
    # (T.., E): the weight of expert e for this token, 0 where not selected
    dense = (jax.nn.one_hot(routes, experts, dtype=jnp.float32)
             * weights[..., None]).sum(-2)
    w1, w3, w2 = (params[f"{name}.{leaf}"] for leaf in ("w1", "w3", "w2"))
    held = dense[..., held_first:held_first + w1.shape[0]]

    def add_expert(y, expert):
        w1e, w3e, w2e, weight = expert                   # weight: (T..)
        return y + weight[..., None] * gated_ff(x, w1e, w3e, w2e), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (w1, w3, w2, jnp.moveaxis(held, -1, 0)))
    return y, routes


def n_layers(params):
    return sum(1 for k in params if k.startswith("layers.")
               and k.endswith(".input_layernorm"))


def _layer(params, pre, x, given, cfg):
    """One layer whose leaves are named ``pre + ...``: the stream after
    it and the experts it selected (``None`` for a dense layer)."""
    eps = cfg["norm_eps"]
    x = x + mla_op(params, pre + "attn",
                   rms_norm(x, params[pre + "input_layernorm"], eps), eps,
                   cfg["rope_theta"], cfg["attention_rows"])
    h = rms_norm(x, params[pre + "post_attention_layernorm"], eps)
    if pre + "ff.w1" in params:
        return x + gated_ff(h, *(params[f"{pre}ff.{leaf}"]
                                 for leaf in ("w1", "w3", "w2"))), None
    y, picked = routed_ff(params, pre + "moe", h, cfg["top_k"],
                          cfg["routed_scaling_factor"], cfg["held_first"],
                          given)
    if pre + "shared.w1" in params:
        y = y + gated_ff(h, *(params[f"{pre}shared.{leaf}"]
                              for leaf in ("w1", "w3", "w2")))
    return x + y, picked


def _run(params, ids, routes, cfg):
    """Main logits, the module's logits (``None`` without a module) and
    the experts every routed layer selected."""
    cfg = {**PUBLISHED, **cfg}
    eps = cfg["norm_eps"]
    ids = jnp.asarray(ids).astype(jnp.int32)
    x = params["embed"][ids]
    chosen = []
    given = iter(routes) if routes is not None else None

    def step(pre, x):
        routed = pre + "moe.router" in params
        mine = next(given) if routed and given is not None else None
        x, picked = jax.checkpoint(
            lambda x, mine: _layer(params, pre, x, mine, cfg))(x, mine)
        if routed:
            chosen.append(picked)
        return x

    for layer in range(n_layers(params)):
        x = step(f"layers.{layer}.", x)
    logits = rms_norm(x, params["embedding_norm"], eps) @ params["head"].T
    if "mtp.merge" not in params:
        return logits, None, chosen
    ahead = params["embed"][jnp.concatenate([ids[:, 1:], ids[:, :1]], 1)]
    merged = jnp.concatenate([rms_norm(x, params["mtp.hnorm"], eps),
                              rms_norm(ahead, params["mtp.enorm"], eps)],
                             -1) @ params["mtp.merge"]
    x2 = step("mtp.", merged)
    return logits, rms_norm(x2, params["mtp.final_norm"],
                            eps) @ params["head"].T, chosen


def forward(params, ids, routes=None, **cfg):
    """``ids``: (B, S) token ids of any numeric dtype (they are cast) ->
    the main head's logits (B, S, V) over its slice of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        return _run(params, ids, routes, cfg)[0]


def routes_of(params, ids, **cfg):
    """The reference's own float32 selection: one (B, S, k) int32 array for
    each routed layer, the module's last."""
    with jax.default_matmul_precision("highest"):
        return _run(params, ids, None, cfg)[2]


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, ids, routes=None, **cfg):
    """Next-token cross-entropy, mean over the B x (S - 1) predicted
    tokens, plus ``mtp_weight`` times the module's cross-entropy against
    the token after next, mean over the B x (S - 2) that have one."""
    ids = jnp.asarray(ids).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, ahead, _ = _run(params, ids, routes, cfg)
    value = _nll(logits[:, :-1], ids[:, 1:])
    weight = {**PUBLISHED, **cfg}["mtp_weight"]
    if ahead is not None and weight:
        value = value + weight * _nll(ahead[:, :-2], ids[:, 2:])
    return value
