"""Plain float32 reference of ``nemotron-twotower-30b-a3b-ep16``: one chip's
share of the ``nemotron_h`` stack that Nemotron-Labs-TwoTower-30B-A3B's
``config.json`` defines.

Written from the layer equations (the Mamba-2 paper's recurrence, the
family's public modelling code for everything around it) in straightforward
``jax.numpy``: float32, matrix products at ``highest`` precision, no
kernels, no chunks, no sort, no cache. The tier-1 tests load this file by
its path (``tests/test_lm_hybrid.py``).

    layer l:  x' = x + Part_l(RMSNorm(x; norm))          one part a layer
    head:     logits = RMSNorm(x; embedding_norm) . W_head^T     (untied)

    Part = ssm (M)   [z | xBC | dt] = u W_in;  xBC = silu(conv4(xBC) + b)
                     (depthwise, causal, zeros before the sequence);
                     [x | B | C] = xBC;  D_t = softplus(dt + dt_bias);
                     A = -exp(A_log);  head h reads group h // (H / G);
                     H_t = exp(D_t A) H_{t-1} + D_t x_t (x) B_t,
                     y_t = H_t C_t + D x_t,  computed here as the
                     QUADRATIC FORM
                       y_i = sum_{j<=i} (C_i.B_j) exp(sum_{j<s<=i} D_s A) D_j x_j
                     over blocks of rows i (no state is ever formed);
                     y = GroupRMSNorm(y * silu(z));  out = y W_out
    Part = attn (*)  q, k, v projections, NO rotation and NO head norm;
                     causal softmax at 1/sqrt(head_dim); each key/value
                     head serves H / Hkv query heads
    Part = moe (E)   s = sigmoid(x Wg);  selected = top-k of s + b;
                     w = s[selected] / (sum + 1e-6) * scaling;
                     y = sum over the selected experts THIS CHIP HOLDS of
                         w_e . W2e relu(W1e x)^2
                       + W2s relu(W1s x)^2          (the shared expert)

The kind of each layer is read from the parameters' names, the numbers of
heads from their shapes; what shapes cannot say comes as keyword arguments
whose defaults are the published values (``PUBLISHED``).

Departures from the published model, each on purpose:

- only the tower that ``config.json`` defines, causal, on next-token loss:
  the model card's second, denoising tower (adaLN, cross-tower
  conditioning, bidirectional in-block attention, block diffusion) has no
  key in the config and is not built here or in the program;
- the chip's share: ``moe.w1/w2`` stack only the experts held here
  (``held_first`` .. ``held_first + count - 1``); the router keeps every
  published output and the top-k. What the absent experts would have added
  is left out, and that partial result goes on to the next layer. The
  table and the head are the chip's slice of the vocabulary;
- every held expert is applied to every token and masked by its weight:
  the obviously right form, many times the needed work;
- attention and the state-space sum run over blocks of query rows, and
  each layer is rematerialised in the backward pass (``jax.checkpoint``),
  so that the gradient at 8,192 tokens fits on a chip. Neither changes a
  value;
- ``routes`` (expert indices per routed layer) replaces the reference's own
  discrete top-k, so that a gradient can be compared on the choices another
  program made; the scores, the weights and their gradients stay its own.
"""

import jax
import jax.numpy as jnp

PUBLISHED = {"norm_eps": 1e-5, "top_k": 6, "held_first": 0,
             "routed_scaling_factor": 2.5, "head_dim": 128, "n_groups": 8,
             "attention_rows": 512, "ssm_rows": 128}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def relu2_ff(x, w1, w2):
    return jnp.square(jax.nn.relu(x @ w1)) @ w2


def _row_blocks(fn, rows_of, s, rows):
    """``fn(block of rows, first row)`` over blocks of ``rows`` rows of
    ``rows_of`` ``[B, S, ...]``, rematerialised, put back as ``[B, S, ...]``."""
    rows = min(rows, s)
    assert s % rows == 0, (s, rows)
    bsz = rows_of.shape[0]
    blocks = rows_of.reshape(bsz, s // rows, rows, *rows_of.shape[2:])
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)),
                      (blocks.swapaxes(0, 1), jnp.arange(0, s, rows)))
    return out.swapaxes(0, 1).reshape(bsz, s, *out.shape[3:])


def ssm_op(params, name, u, groups, eps, rows):
    """The Mamba-2 mixer. ``u``: (B, S, D)."""
    bsz, s, _ = u.shape
    heads = params[name + ".A_log"].shape[0]
    d_in = params[name + ".out_proj"].shape[0]
    width = d_in // heads                               # a head's channels
    state = (params[name + ".conv_bias"].shape[0] - d_in) // (2 * groups)
    z, xbc, dt = jnp.split(u @ params[name + ".in_proj"],
                           [d_in, 2 * d_in + 2 * groups * state], axis=-1)
    taps = params[name + ".conv_kernel"]                 # (K, channels)
    k = taps.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[j] * padded[:, j:j + s] for j in range(k))
                      + params[name + ".conv_bias"])
    x, b, c = jnp.split(xbc, [d_in, d_in + groups * state], axis=-1)
    x = x.reshape(bsz, s, heads, width)
    b = b.reshape(bsz, s, groups, state)
    c = c.reshape(bsz, s, groups, state)
    dt = jax.nn.softplus(dt + params[name + ".dt_bias"])  # (B, S, H)
    a = -jnp.exp(params[name + ".A_log"])
    total = jnp.cumsum(dt * a, axis=1)      # sum_{s<=t} D_s A, (B, S, H)
    dtx = dt[..., None] * x

    def block(c_rows, first):                            # (B, rows, G, N)
        n = c_rows.shape[1]
        upto = jax.lax.dynamic_slice_in_dim(total, first, n, axis=1)
        # (B, H, rows, S): exp(sum_{j<s<=i}) where j <= i, else nothing
        span = (upto.transpose(0, 2, 1)[..., :, None]
                - total.transpose(0, 2, 1)[..., None, :])
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(s)[None, :]
        weight = jnp.exp(jnp.where(seen, span, -jnp.inf))
        # every head reads its group's C_i . B_j
        scores = jnp.repeat(jnp.einsum("bign,bjgn->bgij", c_rows, b),
                            heads // groups, axis=1)
        return jnp.einsum("bhij,bjhp->bihp", scores * weight, dtx)

    y = _row_blocks(block, c, s, rows)
    y = y + params[name + ".D"][:, None] * x
    y = y.reshape(bsz, s, d_in) * jax.nn.silu(z)
    y = y.reshape(bsz, s, groups, d_in // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(bsz, s, d_in) * params[name + ".norm"]
    return y @ params[name + ".out_proj"]


def attention_op(params, name, x, d, rows):
    """Grouped-query causal attention, positions unrotated. ``x``: (B, S, D)."""
    bsz, s, _ = x.shape
    q = (x @ params[name + ".q_proj"]).reshape(bsz, s, -1, d)
    k = (x @ params[name + ".k_proj"]).reshape(bsz, s, -1, d)
    v = (x @ params[name + ".v_proj"]).reshape(bsz, s, -1, d)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)

    def block(qb, first):                                # (B, rows, H, d)
        scores = jnp.einsum("brhd,bshd->bhrs", qb, k) / jnp.sqrt(
            jnp.float32(d))
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
    w1, w2 = params[name + ".w1"], params[name + ".w2"]
    held = dense[..., held_first:held_first + w1.shape[0]]

    def add_expert(y, expert):
        w1e, w2e, weight = expert                        # weight: (T..)
        return y + weight[..., None] * relu2_ff(x, w1e, w2e), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (w1, w2, jnp.moveaxis(held, -1, 0)))
    return y, routes


def n_layers(params):
    return sum(1 for k in params
               if k.startswith("layers.") and k.endswith(".norm")
               and k.count(".") == 2)


def _run(params, ids, routes, cfg):
    cfg = {**PUBLISHED, **cfg}
    eps = cfg["norm_eps"]
    ids = jnp.asarray(ids).astype(jnp.int32)
    x = params["embed"][ids]
    chosen = []

    def layer_fn(x, layer, given):
        pre = f"layers.{layer}."
        h = rms_norm(x, params[pre + "norm"], eps)
        if pre + "ssm.in_proj" in params:
            return x + ssm_op(params, pre + "ssm", h, cfg["n_groups"], eps,
                              cfg["ssm_rows"]), None
        if pre + "attn.q_proj" in params:
            return x + attention_op(params, pre + "attn", h, cfg["head_dim"],
                                    cfg["attention_rows"]), None
        y, picked = routed_ff(params, pre + "moe", h, cfg["top_k"],
                              cfg["routed_scaling_factor"],
                              cfg["held_first"], given)
        if pre + "shared.w1" in params:
            y = y + relu2_ff(h, params[pre + "shared.w1"],
                             params[pre + "shared.w2"])
        return x + y, picked

    given = iter(routes) if routes is not None else None
    for layer in range(n_layers(params)):
        routed = f"layers.{layer}.moe.router" in params
        mine = next(given) if routed and given is not None else None
        x, picked = jax.checkpoint(layer_fn, static_argnums=(1,))(
            x, layer, mine)
        if routed:
            chosen.append(picked)
    x = rms_norm(x, params["embedding_norm"], eps)
    return x @ params["head"].T, chosen


def forward(params, ids, routes=None, **cfg):
    """``ids``: (B, S) token ids of any numeric dtype (they are cast) ->
    logits (B, S, V) over the head's slice of the vocabulary."""
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
