"""A decoder-only language model assembled from a config dict.

``Decoder(config)`` reads the keys of a published ``config.json``
(``hidden_size``, ``layer_types``, ``num_dense_layers``, the head counts,
``intermediate_size`` / ``moe_intermediate_size``, ``num_experts`` /
``num_experts_per_tok``, ``norm_eps``, ``rope_theta``, ``conv_L_cache``,
…) and builds the stack out of :mod:`tpudl.zoo.lm_blocks` and
:mod:`tpudl.zoo.moe`: per layer an operator (``conv`` or
``full_attention``) and a feed-forward (dense before
``num_dense_layers``, routed experts after), pre-norm residual blocks, a
final norm and a head tied to the embedding table.

Two keys describe the share of a deployment this process holds, as
expert parallelism and a sharded vocabulary need them:

- ``experts_held = (first, count)``: the router scores all
  ``num_experts``; this rank stacks and applies ``count`` of them and
  passes its partial sum on (default: all of them);
- ``vocab_slice = (first, count)``: the table holds ``count`` rows of
  ``vocab_size``; ids, logits and the loss are over the slice, ids
  counted from its first row (default: the whole vocabulary).

Parameters are one flat dict of float32 leaves named
``layers.<l>.<block>.<leaf>`` (``init(seed)``, host numpy), so a leaf's
kind is its name and the plain reference
(``benchmark/configs/lfm2-8b-a1b-ep4.py``) reads the same dict. Train it
through the normal path::

    lm = Decoder(config)
    trainer = ctx.trainer(with_compute_dtype(lm.loss_fn(), jnp.bfloat16),
                          optax.adamw(3e-4, mask=lm.decay_mask))
    params, opt_state, history = trainer.fit(lm.init(0), data_fn, steps=n)

There is no decode path yet (a slot cache would hold a convolution's last
``conv_L_cache - 1`` inputs beside keys and values: ROADMAP queue B).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs import metrics as _metrics
from tpudl.obs.trace import named_scope
from tpudl.zoo import lm_blocks as B
from tpudl.zoo import moe

__all__ = ["Decoder"]

OPERATORS = ("conv", "full_attention")
# a rematerialised block recomputes everything but its routing decision
# and the ordering of the pairs that follows from it
_SAVE_ROUTES = jax.checkpoint_policies.save_only_these_names(moe.ROUTES)


def _layer_leaves(params, layer: int) -> dict:
    pre = f"layers.{layer}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


class Decoder:
    def __init__(self, config: dict):
        c = dict(config)
        self.dim = int(c["hidden_size"])
        self.layer_types = tuple(c["layer_types"])
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: this decoder "
                             f"builds {OPERATORS}")
        self.n_layers = int(c.get("num_hidden_layers",
                                  len(self.layer_types)))
        if self.n_layers != len(self.layer_types):
            raise ValueError(f"num_hidden_layers {self.n_layers} but "
                             f"{len(self.layer_types)} layer_types")
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c.get("num_key_value_heads", self.heads))
        self.head_dim = int(c.get("head_dim") or self.dim // self.heads)
        self.ff_width = int(c["intermediate_size"])
        self.dense_layers = int(c.get("num_dense_layers", self.n_layers))
        self.experts = int(c.get("num_experts", 0))
        self.top_k = int(c.get("num_experts_per_tok", 0))
        self.expert_width = int(c.get("moe_intermediate_size", 0))
        self.scaling = float(c.get("routed_scaling_factor", 1.0))
        self.held = tuple(c.get("experts_held") or (0, self.experts))
        self.vocab = int(c["vocab_size"])
        self.vocab_slice = tuple(c.get("vocab_slice") or (0, self.vocab))
        self.eps = float(c.get("norm_eps", 1e-5))
        self.theta = float(c.get("rope_theta", 1e4))
        self.taps = int(c.get("conv_L_cache", 3))
        if c.get("conv_bias"):
            raise ValueError("conv_bias: the short convolution here is "
                             "bias-free")
        if self.dense_layers < self.n_layers:
            first, count = self.held
            if not (0 <= first and count > 0
                    and first + count <= self.experts):
                raise ValueError(f"experts_held {self.held} of "
                                 f"{self.experts} experts")
            if self.top_k > self.experts:
                raise ValueError(f"top {self.top_k} of {self.experts}")
        self._programs: dict = {}

    # ---- structure -------------------------------------------------------
    def routed(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def kinds(self) -> dict:
        """Layers by kind: the operators and the feed-forwards."""
        out = {"conv": 0, "attention": 0, "dense": 0, "routed": 0}
        for layer, op in enumerate(self.layer_types):
            out["conv" if op == "conv" else "attention"] += 1
            out["routed" if self.routed(layer) else "dense"] += 1
        return out

    def init(self, seed: int) -> dict:
        """Seeded float32 leaves on the host. Every block draws from a
        stream of its own, and every expert from one keyed by its
        published index: two ranks that hold other experts of a layer
        agree on everything they share."""
        seed = int(seed)
        rows = self.vocab_slice[1]
        p = {"embed": B.normal(np.random.default_rng([seed, 0, 0]), rows,
                               self.dim, fan_in=1) * np.float32(0.02),
             "embedding_norm": np.ones((self.dim,), np.float32)}
        for layer, op in enumerate(self.layer_types):
            pre = f"layers.{layer}."
            rng = np.random.default_rng([seed, layer + 1, 0])
            p[pre + "operator_norm"] = np.ones((self.dim,), np.float32)
            p[pre + "ffn_norm"] = np.ones((self.dim,), np.float32)
            if op == "conv":
                p.update(B.init_conv(rng, pre + "conv", self.dim, self.taps))
            else:
                p.update(B.init_attention(rng, pre + "attn", self.dim,
                                          self.heads, self.kv_heads,
                                          self.head_dim))
            if self.routed(layer):
                p.update(moe.init_routed(
                    (seed, layer + 1, 1), pre + "moe", self.dim,
                    self.expert_width, self.experts, self.held))
            else:
                p.update(B.init_ff(rng, pre + "ff", self.dim, self.ff_width))
        return p

    @staticmethod
    def decay_mask(params):
        """Weight decay on matrices only: norms stay free, and the
        experts' selection bias stays the constant buffer it is."""
        return jax.tree.map(lambda leaf: np.ndim(leaf) > 1, params)

    # ---- forward ---------------------------------------------------------
    def runs(self):
        """Consecutive layers of one kind (operator and feed-forward) as
        ``(first, count)``: what one scanned body can stand for."""
        runs = []
        for layer, op in enumerate(self.layer_types):
            kind = (op, self.routed(layer))
            if runs and runs[-1][2] == kind:
                runs[-1][1] += 1
            else:
                runs.append([layer, 1, kind])
        return [(first, count) for first, count, _ in runs]

    def _block(self, op: str, routed: bool, p, x, routes):
        """One pre-norm residual block on its own leaves ``p`` (named
        without the ``layers.<l>.`` prefix)."""
        h = B.rms_norm(x, p["operator_norm"], self.eps)
        if op == "conv":
            x = x + B.conv_op(p, "conv", h)
        else:
            x = x + B.attention_op(
                p, "attn", h, heads=self.heads, kv_heads=self.kv_heads,
                eps=self.eps, theta=self.theta)
        h = B.rms_norm(x, p["ffn_norm"], self.eps)
        if not routed:
            return x + B.gated_ff(p, "ff", h), None
        y, chosen = moe.routed_ff(p, "moe", h, top_k=self.top_k,
                                  held=self.held, scaling=self.scaling,
                                  routes=routes)
        return x + y, chosen

    def hidden(self, params, ids, routes=None, remat: bool = False):
        """``ids`` ``[B, S]`` -> the normed last hidden state ``[B, S,
        D]`` and the experts each routed layer selected (``[B, S, k]``
        each). ``routes``, one array per routed layer, replaces the
        selection. A run of consecutive layers of one kind is one
        ``jax.lax.scan`` over their stacked leaves: the program holds
        that block once however often the model repeats it (LFM2's
        conv, conv, conv between attentions), for a copy of the run's
        compute-dtype weights a step. Bumps ``zoo.lm.layers.<kind>``
        once per layer while a program is TRACED, as
        ``zoo.conv_bn.folded`` is, and ``moe.combine.fused`` once per
        routed layer."""
        kinds = self.kinds()
        for kind, n in kinds.items():
            _metrics.counter(f"zoo.lm.layers.{kind}").inc(n)
        if kinds["routed"]:   # moe.routed_ff has the one path
            _metrics.counter("moe.combine.fused").inc(kinds["routed"])
        x = params["embed"][ids]
        given = iter(routes) if routes is not None else None
        chosen = []
        for first, count in self.runs():
            routed = self.routed(first)
            block = functools.partial(self._block, self.layer_types[first],
                                      routed)
            if remat:
                block = jax.checkpoint(block, policy=_SAVE_ROUTES)
            leaves = [_layer_leaves(params, layer)
                      for layer in range(first, first + count)]
            mine = ([next(given) for _ in range(count)]
                    if given is not None and routed else None)
            if count == 1:
                x, picked = block(leaves[0], x, mine and mine[0])
                picked = [picked]
            else:
                x, picked = jax.lax.scan(
                    lambda x, per: block(per[0], x, per[1]), x,
                    (jax.tree.map(lambda *leaf: jnp.stack(leaf), *leaves),
                     mine and jnp.stack(mine)))
            if routed:
                chosen.extend(picked)
        return B.rms_norm(x, params["embedding_norm"], self.eps), chosen

    def logits(self, params, ids, routes=None):
        """``[B, S, V]`` float32 over the table's slice: for small
        inputs (at training sizes the loss never holds them whole)."""
        x, _ = self.hidden(params, ids, routes)
        with named_scope("lm.head"):
            return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                              preferred_element_type=jnp.float32)

    def routes(self, params, ids):
        """The experts every routed layer selects for ``ids``."""
        return self.hidden(params, ids)[1]

    def loss_fn(self, remat: bool = True, loss_chunk: int = 4096,
                with_routes: bool = False):
        """``loss(params, ids, routes=None)``: next-token cross-entropy,
        the mean over the batch's ``B·(S-1)`` predicted tokens. One
        ``jax.checkpoint`` per block with ``remat``. The logits are never
        whole: the head and the loss run over chunks of ``loss_chunk``
        tokens, float32 inside a chunk and recomputed in the backward
        pass, so they cost ``loss_chunk × V × 4`` bytes whatever the
        batch."""

        def loss(params, ids, routes=None):
            x, chosen = self.hidden(params, ids, routes, remat=remat)
            bsz, s, dim = x.shape
            n = bsz * s
            chunk = loss_chunk if n % loss_chunk == 0 else n
            target = jnp.roll(ids, -1, axis=1)
            counted = jnp.broadcast_to(jnp.arange(s) < s - 1, (bsz, s))
            table = params["embed"]

            def chunk_nll(args):
                xc, yc, mc = args
                z = jax.lax.dot_general(
                    xc, table, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
                    z, yc[:, None], axis=-1)[:, 0]
                return jnp.sum(jnp.where(mc, nll, 0.0))

            with named_scope("lm.head"):
                parts = jax.lax.map(jax.checkpoint(chunk_nll), (
                    x.reshape(n // chunk, chunk, dim),
                    target.reshape(n // chunk, chunk),
                    counted.reshape(n // chunk, chunk)))
                value = parts.sum() / (bsz * (s - 1))
            return (value, chosen) if with_routes else value

        return loss

    # ---- what the routing did --------------------------------------------
    def route_stats(self, params, ids, compute_dtype=None) -> dict:
        """Counts of one batch's routing, from a small jitted program
        over the model's own routing code (``compute_dtype`` as the
        training step casts the parameters): per routed layer and summed
        over them ``pairs_total`` (tokens × k), ``pairs_held`` (pairs
        whose expert this rank holds) and the held experts' token
        counts. Publishes ``moe.pairs_held`` / ``moe.pairs_total``
        (counters) and ``moe.expert_tokens_max`` / ``_mean`` (gauges).
        Called outside timed work: ``Trainer``'s loss stays a scalar."""
        key = str(compute_dtype)
        if key not in self._programs:
            fn = self.routes
            if compute_dtype is not None:
                from tpudl.train.step import with_compute_dtype

                fn = with_compute_dtype(fn, compute_dtype)
            self._programs[key] = jax.jit(fn)
        chosen = [np.asarray(c) for c in self._programs[key](params, ids)]
        return self.count_routes(chosen)

    def count_routes(self, chosen) -> dict:
        first, count = self.held
        layers = []
        for c in chosen:
            local = c.reshape(-1).astype(np.int64) - first
            per_expert = np.bincount(
                local[(local >= 0) & (local < count)], minlength=count)
            layers.append({"pairs_total": int(c.size),
                           "pairs_held": int(per_expert.sum()),
                           "expert_tokens": per_expert.tolist()})
        tokens = np.array([n for rec in layers for n in rec["expert_tokens"]]
                          or [0])
        out = {"layers": layers,
               "pairs_total": sum(rec["pairs_total"] for rec in layers),
               "pairs_held": sum(rec["pairs_held"] for rec in layers),
               "expert_tokens_max": int(tokens.max()),
               "expert_tokens_mean": float(tokens.mean())}
        _metrics.counter("moe.pairs_held").inc(out["pairs_held"])
        _metrics.counter("moe.pairs_total").inc(out["pairs_total"])
        _metrics.gauge("moe.expert_tokens_max").set(
            out["expert_tokens_max"])
        _metrics.gauge("moe.expert_tokens_mean").set(
            out["expert_tokens_mean"])
        return out
