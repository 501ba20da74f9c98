"""A decoder-only language model assembled from a config dict.

``Decoder(config)`` reads the keys of a published ``config.json`` and
builds the stack out of :mod:`tpudl.zoo.lm_blocks` and
:mod:`tpudl.zoo.moe`. A layer is a list of PARTS, each behind an RMSNorm
of its own and added to the residual stream, ``x ← x + part(norm(x))``;
after the last layer a final norm and the head. Three families are built,
each recognised by its keys:

- ``lfm2_moe`` (``layer_types``, ``num_dense_layers``, ``num_experts``,
  ``conv_L_cache``, ``rope_theta``, …): two parts a layer, an operator
  (``conv`` or ``full_attention`` with per-head norms and rotary) and a
  feed-forward (gated SiLU: dense before ``num_dense_layers``, routed
  experts after); the head is the embedding table;
- ``nemotron_h`` (``hybrid_override_pattern``, ``mamba_num_heads``,
  ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
  ``chunk_size``, ``n_routed_experts``, ``moe_intermediate_size``,
  ``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``,
  ``tie_word_embeddings``, …): ONE part a layer by the pattern's letter,
  ``M`` a Mamba-2 mixer, ``*`` attention with no rotation and no head
  norm, ``E`` routed ``relu2`` experts (two products, no gate) beside a
  shared expert that every token passes; an untied head ``[V, D]``.
  Built causally, for next-token training. Of
  Nemotron-Labs-TwoTower this is the tower its ``config.json`` defines:
  the second, denoising tower of the model card (adaLN, cross-tower
  conditioning, bidirectional in-block attention, block-diffusion
  decoding) has no key there and is NOT built; nor are ``-`` (dense MLP)
  layers or a router limited to groups of experts (``n_group > 1``);
- ``joyai_llm_flash``, the DeepSeek-V3 shape (``kv_lora_rank``,
  ``q_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
  ``v_head_dim``, ``first_k_dense_replace``, ``n_routed_experts``,
  ``n_shared_experts``, ``num_nextn_predict_layers``, …): two parts a
  layer, latent attention (``mla``: queries through a low rank, keys and
  values expanded from one compressed latent, a rotated key that all
  heads share, interleaved pairs) and a feed-forward (gated SiLU: dense
  before ``first_k_dense_replace``, then routed experts by sigmoid score
  + bias beside a gated-SiLU shared expert); an untied head; and with
  ``num_nextn_predict_layers`` 1 a multi-token-prediction module (the
  stream before the final norm and the next token's embedding, each
  normed, concatenated and projected; one more such layer; a norm of
  its own; the main head) whose loss on the token after next is added
  with the weight ``mtp_weight``. Training's expanded form only: the
  absorbed decode form and a cache of the latent are NOT built, nor
  YaRN scaling, softmax scoring, group-limited routing, or more than one
  prediction module.

Two keys describe the share of a deployment this process holds, as
expert parallelism and a sharded vocabulary need them:

- ``experts_held = (first, count)``: the router scores all
  ``num_experts`` / ``n_routed_experts``; this rank stacks and applies
  ``count`` of them and passes its partial sum on (default: all of them);
- ``vocab_slice = (first, count)``: the table (and an untied head) holds
  ``count`` rows of ``vocab_size``; ids, logits and the loss are over the
  slice, ids counted from its first row (default: the whole vocabulary).

Parameters are one flat dict of float32 leaves named
``layers.<l>.<block>.<leaf>`` (``init(seed)``, host numpy), so a leaf's
kind is its name and the plain references
(``benchmark/configs/lfm2-8b-a1b-ep4.py``,
``benchmark/configs/nemotron-twotower-30b-a3b-ep16.py``,
``benchmark/configs/joyai-llm-flash-ep32.py``) read the same dict; a
prediction module's leaves are ``mtp.<block>.<leaf>``. Train it through
the normal path::

    lm = Decoder(config)
    trainer = ctx.trainer(
        with_compute_dtype(lm.loss_fn(), jnp.bfloat16,
                           keep=lm.float32_leaves),
        optax.adamw(3e-4, mask=lm.decay_mask))
    params, opt_state, history = trainer.fit(lm.init(0), data_fn, steps=n)

There is no decode path yet (a slot cache would hold a convolution's last
inputs and a mixer's recurrent state beside keys and values: ROADMAP
queue B).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpudl import pallas_ops
from tpudl.obs import metrics as _metrics
from tpudl.obs.trace import named_scope
from tpudl.zoo import lm_blocks as B
from tpudl.zoo import moe

__all__ = ["Decoder"]

OPERATORS = ("conv", "full_attention")
# nemotron_h's hybrid_override_pattern, a letter a layer
PATTERN = {"M": "ssm", "*": "attention", "E": "routed"}
# the leaves' block name by kind of part
BLOCK = {"conv": "conv", "attention": "attn", "mla": "attn", "ssm": "ssm",
         "dense": "ff", "routed": "moe"}
# the multi-token-prediction module's own leaves beside its layer's:
# the norms of its two inputs, the projection of their concatenation
# and the norm before the (shared) head
MTP_NORMS = ("mtp.hnorm", "mtp.enorm", "mtp.final_norm")
# a rematerialised block recomputes everything but its routing decision
# with the ordering of the pairs that follows from it, and the flash
# forward kernel's output and row statistics: the sort and the kernel run
# once a step, and the backward kernels and W_o's gradient read what the
# first launch wrote
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(
    moe.ROUTES, pallas_ops.SAVED)


def _leaves(params, pre: str) -> dict:
    """The leaves named ``pre + ...``, without the prefix."""
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


class Decoder:
    def __init__(self, config: dict):
        c = dict(config)
        self.dim = int(c["hidden_size"])
        self.hybrid = "hybrid_override_pattern" in c
        self.mtp, self.mtp_weight, self.routed_by_sequence = 0, 0.0, False
        if self.hybrid:
            self._read_nemotron_h(c)
        elif "kv_lora_rank" in c:
            self._read_mla_moe(c)
        else:
            self._read_lfm2(c)
        self.n_layers = int(c.get("num_hidden_layers", len(self.layers)))
        if self.n_layers != len(self.layers):
            raise ValueError(
                f"num_hidden_layers {self.n_layers} but {len(self.layers)} "
                + ("letters in hybrid_override_pattern" if self.hybrid
                   else "layer_types"))
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c.get("num_key_value_heads", self.heads))
        self.head_dim = int(c.get("head_dim") or self.dim // self.heads)
        self.v_head_dim = int(c.get("v_head_dim") or self.head_dim)
        self.top_k = int(c.get("num_experts_per_tok", 0))
        self.expert_width = int(c.get("moe_intermediate_size", 0))
        self.scaling = float(c.get("routed_scaling_factor", 1.0))
        self.held = tuple(c.get("experts_held") or (0, self.experts))
        self.vocab = int(c["vocab_size"])
        self.vocab_slice = tuple(c.get("vocab_slice") or (0, self.vocab))
        self.eps = float(c.get("norm_eps", c.get("rms_norm_eps", 1e-5)))
        if self.kinds()["routed"]:
            first, count = self.held
            if not (0 <= first and count > 0
                    and first + count <= self.experts):
                raise ValueError(f"experts_held {self.held} of "
                                 f"{self.experts} experts")
            if self.top_k > self.experts:
                raise ValueError(f"top {self.top_k} of {self.experts}")
        self._programs: dict = {}

    def _read_lfm2(self, c):
        """An operator and a feed-forward a layer, each behind its norm."""
        ops = tuple(c["layer_types"])
        unknown = set(ops) - set(OPERATORS)
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: this decoder "
                             f"builds {OPERATORS}")
        dense = int(c.get("num_dense_layers", len(ops)))
        self.layers = tuple(
            (("operator_norm", "conv" if op == "conv" else "attention"),
             ("ffn_norm", "dense" if layer < dense else "routed"))
            for layer, op in enumerate(ops))
        self.ff_width = int(c["intermediate_size"])
        self.experts = int(c.get("num_experts", 0))
        self.act, self.shared_width, self.tied = "silu", 0, True
        self.theta = float(c.get("rope_theta", 1e4))
        self.taps = int(c.get("conv_L_cache", 3))
        self.float32_leaves = ()
        if c.get("conv_bias"):
            raise ValueError("conv_bias: the short convolution here is "
                             "bias-free")

    def _read_mla_moe(self, c):
        """Latent attention and a feed-forward a layer, each behind its
        norm: dense before ``first_k_dense_replace``, routed after."""
        for key, built, why in (
                ("n_group", 1, "a router limited to groups of experts"),
                ("topk_group", 1, "a router limited to groups of experts"),
                ("scoring_func", "sigmoid", "another score than the sigmoid"),
                ("topk_method", "noaux_tc", "another selection than the "
                 "top k of score + bias"),
                ("rope_scaling", None, "scaled rotary positions (YaRN)"),
                ("norm_topk_prob", True, "weights that are not renormalised "
                 "over the selected"),
                ("attention_bias", False, "a projection with a bias"),
                ("moe_layer_freq", 1, "dense layers among the routed ones"),
                ("rope_interleave", True, "half-split pairs in latent "
                 "attention")):
            if c.get(key, built) != built:
                raise ValueError(f"{key} {c[key]!r}: {why} is not built")
        self.mtp = int(c.get("num_nextn_predict_layers", 0))
        if self.mtp > 1:
            raise ValueError(f"num_nextn_predict_layers {self.mtp}: one "
                             "multi-token-prediction module is built")
        self.mtp_weight = float(c.get("mtp_weight", 0.0)) if self.mtp else 0.0
        dense = int(c.get("first_k_dense_replace", 0))
        self.layers = tuple(
            (("input_layernorm", "mla"),
             ("post_attention_layernorm",
              "dense" if layer < dense else "routed"))
            for layer in range(int(c["num_hidden_layers"])))
        self.ff_width = int(c["intermediate_size"])
        self.experts = int(c.get("n_routed_experts", 0))
        self.act, self.tied = "silu", bool(c.get("tie_word_embeddings", False))
        self.shared_width = int(c.get("n_shared_experts", 0)) * int(
            c.get("moe_intermediate_size", 0))
        self.theta = float(c["rope_theta"])
        self.q_rank = int(c["q_lora_rank"])
        self.kv_rank = int(c["kv_lora_rank"])
        self.qk_nope = int(c["qk_nope_head_dim"])
        self.qk_rope = int(c["qk_rope_head_dim"])
        self.float32_leaves = ()
        # at 8 experts a token a routed layer's buffers of T·k rows are
        # twice LFM2's, five of 1.07 GB at 32,768 tokens, and do not fit
        # beside a latent-attention block's residuals (18.3 GB a step at
        # the cell's shapes, 14.1 GB so: compiled for a described v5e,
        # PERF.md section 6): the routed experts take a sequence at a
        # time, each rematerialised, as the nemotron_h mixer does
        self.routed_by_sequence = True

    def _read_nemotron_h(self, c):
        """One part a layer, by the letter of the pattern."""
        pattern = str(c["hybrid_override_pattern"])
        unknown = sorted(set(pattern) - set(PATTERN))
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: letters {unknown} "
                f"(this decoder builds {sorted(PATTERN)}; '-', a dense "
                "MLP layer, is not built)")
        self.layers = tuple((("norm", PATTERN[ch]),) for ch in pattern)
        for key, want in (("n_group", 1), ("topk_group", 1)):
            if int(c.get(key, 1)) != want:
                raise ValueError(f"{key} {c[key]}: a router limited to "
                                 "groups of experts is not built")
        for key in ("use_bias", "mamba_proj_bias", "attention_bias",
                    "mlp_bias"):
            if c.get(key):
                raise ValueError(f"{key}: projections here are bias-free")
        if not c.get("use_conv_bias", True):
            raise ValueError("use_conv_bias false: the mixer's "
                             "convolution here has its bias")
        if not c.get("norm_topk_prob", True):
            raise ValueError("norm_topk_prob false: the weights here are "
                             "renormalised over the selected")
        limit = tuple(c.get("time_step_limit") or (0.0, None))
        if limit[0] or limit[1] not in (None, float("inf")):
            raise ValueError(f"time_step_limit {limit}: the step here is "
                             "not clamped")
        self.act = str(c.get("mlp_hidden_act", "relu2"))
        if self.act != "relu2":
            raise ValueError(f"mlp_hidden_act {self.act!r}: the experts "
                             "of this family are built as relu2")
        self.experts = int(c.get("n_routed_experts", 0))
        self.shared_width = int(c.get("n_shared_experts", 0)) * int(
            c.get("moe_shared_expert_intermediate_size", 0))
        self.tied = bool(c.get("tie_word_embeddings", False))
        self.theta = None   # the family's code rotates nothing
        self.ssm_heads = int(c["mamba_num_heads"])
        self.ssm_head_dim = int(c["mamba_head_dim"])
        self.ssm_groups = int(c["n_groups"])
        self.ssm_state = int(c["ssm_state_size"])
        self.taps = int(c["conv_kernel"])
        self.chunk = int(c["chunk_size"])
        self.dt_range = (float(c.get("time_step_min", 1e-3)),
                         float(c.get("time_step_max", 0.1)),
                         float(c.get("time_step_floor", 1e-4)))
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"mamba_num_heads {self.ssm_heads} over "
                             f"n_groups {self.ssm_groups}")
        self.float32_leaves = B.MAMBA2_FLOAT32

    # ---- structure -------------------------------------------------------
    def routed(self, layer: int) -> bool:
        return any(kind == "routed" for _, kind in self.layers[layer])

    def _with_module(self):
        """The layers, and after them a prediction module's: one more
        layer like the last."""
        return (*self.layers, *[self.layers[-1]] * self.mtp)

    def kinds(self) -> dict:
        """Parts by kind, over the layers (``shared``: shared experts
        beside routed ones). A latent-attention part counts as ``mla``
        and as ``attention``, and a multi-token-prediction module
        (``mtp``) brings one more routed layer's parts: both keys are
        there for the family that has them."""
        out = dict.fromkeys(("conv", "attention", "ssm", "dense", "routed",
                             "shared"), 0)
        for layer in self._with_module():
            for _, kind in layer:
                out[kind] = out.get(kind, 0) + 1
        if "mla" in out:
            out["attention"] += out["mla"]
            out["mtp"] = self.mtp
        out["shared"] = out["routed"] if self.shared_width else 0
        return out

    def _init_part(self, seed, layer, rng, kind, pre) -> dict:
        name = pre + BLOCK[kind]
        if kind == "conv":
            return B.init_conv(rng, name, self.dim, self.taps)
        if kind == "attention":
            return B.init_attention(rng, name, self.dim, self.heads,
                                    self.kv_heads, self.head_dim,
                                    head_norms=self.theta is not None)
        if kind == "mla":
            return B.init_mla(rng, name, self.dim, self.heads, self.q_rank,
                              self.kv_rank, self.qk_nope, self.qk_rope,
                              self.v_head_dim)
        if kind == "ssm":
            return B.init_mamba2(rng, name, self.dim, self.ssm_heads,
                                 self.ssm_head_dim, self.ssm_groups,
                                 self.ssm_state, self.taps, self.dt_range)
        if kind == "dense":
            return B.init_ff(rng, name, self.dim, self.ff_width)
        out = moe.init_routed((seed, layer + 1, 1), name, self.dim,
                              self.expert_width, self.experts, self.held,
                              act=self.act)
        if self.shared_width:
            out.update(B.init_ff(rng, pre + "shared", self.dim,
                                 self.shared_width, gated=moe.ACTS[self.act]))
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
        if not self.tied:
            p["head"] = B.normal(np.random.default_rng([seed, 0, 1]), rows,
                                 self.dim, fan_in=self.dim)
        for layer, parts in enumerate(self._with_module()):
            pre = f"layers.{layer}." if layer < self.n_layers else "mtp."
            rng = np.random.default_rng([seed, layer + 1, 0])
            for norm, _ in parts:
                p[pre + norm] = np.ones((self.dim,), np.float32)
            for _, kind in parts:
                p.update(self._init_part(seed, layer, rng, kind, pre))
            if pre == "mtp.":
                p.update({norm: np.ones((self.dim,), np.float32)
                          for norm in MTP_NORMS})
                p["mtp.merge"] = B.normal(rng, 2 * self.dim, self.dim)
        return p

    @staticmethod
    def decay_mask(params):
        """Weight decay on matrices only: norms, biases and a mixer's
        per-head scalars stay free, and the experts' selection bias stays
        the constant buffer it is."""
        return jax.tree.map(lambda leaf: np.ndim(leaf) > 1, params)

    # ---- forward ---------------------------------------------------------
    def runs(self):
        """Consecutive layers of one kind (the same parts) as ``(first,
        count)``: what one scanned body can stand for."""
        runs = []
        for layer, parts in enumerate(self.layers):
            if runs and runs[-1][2] == parts:
                runs[-1][1] += 1
            else:
                runs.append([layer, 1, parts])
        return [(first, count) for first, count, _ in runs]

    def _part(self, kind: str, p, h, routes):
        """One part on its normed input ``h`` and its own leaves ``p``
        (named without the ``layers.<l>.`` prefix): what it adds to the
        residual stream, and the experts a routed part selected."""
        if kind == "conv":
            return B.conv_op(p, "conv", h), None
        if kind == "attention":
            return B.attention_op(
                p, "attn", h, heads=self.heads, kv_heads=self.kv_heads,
                eps=self.eps, theta=self.theta), None
        if kind == "mla":
            return B.mla_op(p, "attn", h, heads=self.heads,
                            nope=self.qk_nope, rope=self.qk_rope,
                            eps=self.eps, theta=self.theta), None
        if kind == "ssm":
            return B.mamba2_op(
                p, "ssm", h, heads=self.ssm_heads, groups=self.ssm_groups,
                state=self.ssm_state, chunk=self.chunk, eps=self.eps), None
        if kind == "dense":
            return B.gated_ff(p, "ff", h), None
        routed = functools.partial(
            moe.routed_ff, p, "moe", top_k=self.top_k, held=self.held,
            scaling=self.scaling, act=self.act)
        if self.routed_by_sequence:
            def one(seq):
                y, chosen = routed(seq[0][None], routes=(
                    None if seq[1] is None else seq[1][None]))
                return y[0], chosen[0]

            y, chosen = jax.lax.map(jax.checkpoint(one, policy=_SAVE_NAMED),
                                    (h, routes))
        else:
            y, chosen = routed(h, routes=routes)
        if self.shared_width:   # the shared expert has the experts' form
            y = y + (B.gated_ff(p, "shared", h, scope="lm.shared_ff")
                     if moe.ACTS[self.act] else B.relu2_ff(p, "shared", h))
        return y, chosen

    def _block(self, parts, p, x, routes):
        """One layer: every part behind its norm, added to the stream."""
        chosen = None
        for norm, kind in parts:
            y, picked = self._part(kind, p, self._norm(x, p[norm]), routes)
            with named_scope("lm.norm"):
                x = x + y
            chosen = picked if kind == "routed" else chosen
        return x, chosen

    def _norm(self, x, weight):
        """An RMSNorm outside every operator, under the scope ``lm.norm``
        (which the residual adds share): before each part and after the
        last layer."""
        with named_scope("lm.norm"):
            return B.rms_norm(x, weight, self.eps)

    def hidden(self, params, ids, routes=None, remat: bool = False):
        """``ids`` ``[B, S]`` -> the normed last hidden state ``[B, S,
        D]`` and the experts each routed layer selected (``[B, S, k]``
        each; a prediction module's routed layer is the last).
        ``routes``, one array per routed layer, replaces the selection.
        A run of consecutive layers of one kind is one ``jax.lax.scan``
        over their stacked leaves: the program holds that block once
        however often the model repeats it (LFM2's conv, conv, conv
        between attentions), for a copy of the run's compute-dtype
        weights a step. With ``remat`` every block is one
        ``jax.checkpoint`` that saves what carries a name (its routes
        and their ordering; an attention part's kernel output and row
        statistics) and recomputes the rest. Bumps
        ``zoo.lm.layers.<kind>`` once per layer while a program is
        TRACED, as ``zoo.conv_bn.folded`` is, ``moe.combine.fused`` once
        per routed layer and, with ``remat``,
        ``zoo.lm.attention.saved`` once per attention layer, beside the
        gauge ``zoo.lm.attention.saved_bytes``."""
        x, _, chosen = self._streams(params, ids, routes, remat)
        return self._norm(x, params["embedding_norm"]), chosen

    def _streams(self, params, ids, routes, remat):
        """One run of the stack: the stream after the last layer, BEFORE
        the final norm; the prediction module's stream before its norm
        (``None`` without a module); the experts selected."""
        kinds = self.kinds()
        for kind, n in kinds.items():
            _metrics.counter(f"zoo.lm.layers.{kind}").inc(n)
        if kinds["routed"]:   # moe.routed_ff has the one path
            _metrics.counter("moe.combine.fused").inc(kinds["routed"])
        x = self._embed(params, ids)
        if remat and kinds["attention"]:
            # what _SAVE_NAMED keeps of the flash kernels a step, by the
            # shapes: a value head a row in the stream's dtype, and a
            # float32 statistic
            _metrics.counter("zoo.lm.attention.saved").inc(kinds["attention"])
            _metrics.gauge("zoo.lm.attention.saved_bytes").set(
                kinds["attention"] * ids.size * self.heads
                * (self.v_head_dim * x.dtype.itemsize + 4))
        given = iter(routes) if routes is not None else None
        chosen = []
        for first, count in self.runs():
            routed = self.routed(first)
            block = functools.partial(self._block, self.layers[first])
            if remat:
                block = jax.checkpoint(block, policy=_SAVE_NAMED)
            leaves = [_leaves(params, f"layers.{layer}.")
                      for layer in range(first, first + count)]
            mine = ([next(given) for _ in range(count)]
                    if given is not None and routed else None)
            if count == 1:
                x, picked = block(leaves[0], x, mine and mine[0])
                picked = [picked]
            else:
                with named_scope("lm.stack"):
                    stacked = (jax.tree.map(lambda *leaf: jnp.stack(leaf),
                                            *leaves),
                               mine and jnp.stack(mine))
                x, picked = jax.lax.scan(
                    lambda x, per: block(per[0], x, per[1]), x, stacked)
            if routed:
                chosen.extend(picked)
        if not self.mtp:
            return x, None, chosen
        module = functools.partial(self._mtp, self.layers[-1])
        if remat:
            module = jax.checkpoint(module, policy=_SAVE_NAMED)
        ahead, picked = module(
            _leaves(params, "mtp."), x,
            self._embed(params, jnp.roll(ids, -1, axis=1)),
            next(given) if given is not None else None)
        return x, ahead, [*chosen, picked]

    @staticmethod
    def _embed(params, ids):
        """The table's rows for ``ids`` under the scope ``lm.embed``: a
        gather, and a scatter-add in the backward pass."""
        with named_scope("lm.embed"):
            return params["embed"][ids]

    def _mtp(self, parts, p, x, ahead, routes):
        """The multi-token-prediction module on the stack's stream ``x``
        (before the final norm) and ``ahead``, the embedding of each
        position's NEXT token: ``M [RMSNorm_h(x) ; RMSNorm_e(ahead)]``
        and one more layer. The last position's next token is the
        sequence's first, by ``roll``: the loss leaves the last two
        positions out, and under a causal mask no other sees them."""
        with named_scope("lm.mtp"):
            merged = jnp.concatenate(
                [B.rms_norm(x, p["hnorm"], self.eps),
                 B.rms_norm(ahead, p["enorm"], self.eps)], -1) @ p["merge"]
        return self._block(parts, p, merged, routes)

    def logits(self, params, ids, routes=None):
        """``[B, S, V]`` float32 over the table's slice: for small
        inputs (at training sizes the loss never holds them whole)."""
        x, _ = self.hidden(params, ids, routes)
        with named_scope("lm.head"):
            return jnp.einsum("bsd,vd->bsv", x, self._head(params),
                              preferred_element_type=jnp.float32)

    def _head(self, params):
        """``[V, D]``: the embedding table, or the untied head."""
        return params["embed" if self.tied else "head"]

    def routes(self, params, ids):
        """The experts every routed layer selects for ``ids``."""
        return self.hidden(params, ids)[1]

    def loss_fn(self, remat: bool = True, loss_chunk: int = 4096,
                with_routes: bool = False):
        """``loss(params, ids, routes=None)``: next-token cross-entropy,
        the mean over the batch's ``B·(S-1)`` predicted tokens; with a
        prediction module, plus ``mtp_weight`` times the module's
        cross-entropy against the token after next, the mean over the
        ``B·(S-2)`` positions that have one (a second pass of the same
        head, on the module's normed stream). One ``jax.checkpoint`` per
        block with ``remat``, which saves the block's routes and its
        flash kernel's output and recomputes the rest (:meth:`hidden`).
        The logits are never whole: the head and
        the loss run over chunks of ``loss_chunk`` tokens, float32
        inside a chunk and recomputed in the backward pass, so they cost
        ``loss_chunk × V × 4`` bytes whatever the batch."""

        def loss(params, ids, routes=None):
            x, ahead, chosen = self._streams(params, ids, routes, remat)
            x = self._norm(x, params["embedding_norm"])
            bsz, s, dim = x.shape
            n = bsz * s
            chunk = loss_chunk if n % loss_chunk == 0 else n
            table = self._head(params)

            def chunk_nll(args):
                xc, yc, mc = args
                z = jax.lax.dot_general(
                    xc, table, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
                    z, yc[:, None], axis=-1)[:, 0]
                return jnp.sum(jnp.where(mc, nll, 0.0))

            def mean_nll(x, skip):
                """Of ``x``'s positions against the token ``skip``
                further on, where the sequence has one."""
                target = jnp.roll(ids, -skip, axis=1)
                counted = jnp.broadcast_to(jnp.arange(s) < s - skip, (bsz, s))
                with named_scope("lm.head"):
                    parts = jax.lax.map(jax.checkpoint(chunk_nll), (
                        x.reshape(n // chunk, chunk, dim),
                        target.reshape(n // chunk, chunk),
                        counted.reshape(n // chunk, chunk)))
                    return parts.sum() / (bsz * (s - skip))

            value = mean_nll(x, 1)
            if ahead is not None and self.mtp_weight:
                value = value + self.mtp_weight * mean_nll(self._norm(
                    ahead, params["mtp.final_norm"]), 2)
            return (value, chosen) if with_routes else value

        return loss

    # ---- what the routing did --------------------------------------------
    def route_stats(self, params, ids, compute_dtype=None) -> dict:
        """Counts of one batch's routing, from a small jitted program
        over the model's own routing code (``compute_dtype`` as the
        training step casts the parameters): per routed layer and summed
        over them ``pairs_total`` (tokens × k), ``pairs_held`` (pairs
        whose expert this rank holds) and the held experts' token
        counts, ``chunks_run`` of ``chunks_total``: the turns the
        routed layer's held-prefix loops take on that batch
        (``ceil(pairs_held / chunk)`` a layer) of those a whole buffer
        would take, and ``token_rows_run`` of ``token_rows_total``: the
        rows its two token-order moves take (``moe.token_rows`` each) of
        the ``2·T·k`` that gathering every slot takes, and
        ``row_tiles_run`` of ``row_tiles_total``: the row tiles a grouped
        product visits on that batch (``moe.row_tiles``, a call of
        ``routed_ff`` each: a sequence at a time where the layer takes
        one) of those its whole buffers hold. Publishes
        ``moe.pairs_held`` / ``moe.pairs_total`` / ``moe.chunks_run`` /
        ``moe.chunks_total`` / ``moe.token_rows_run`` /
        ``moe.token_rows_total`` / ``moe.row_tiles_run`` /
        ``moe.row_tiles_total`` (counters) and
        ``moe.expert_tokens_max`` / ``_mean`` (gauges).
        Called outside timed work: ``Trainer``'s loss stays a scalar."""
        key = str(compute_dtype)
        if key not in self._programs:
            fn = self.routes
            if compute_dtype is not None:
                from tpudl.train.step import with_compute_dtype

                fn = with_compute_dtype(fn, compute_dtype,
                                        keep=self.float32_leaves)
            self._programs[key] = jax.jit(fn)
        chosen = [np.asarray(c) for c in self._programs[key](params, ids)]
        return self.count_routes(chosen)

    def count_routes(self, chosen) -> dict:
        first, count = self.held

        def per_expert(pairs):
            local = pairs.reshape(-1).astype(np.int64) - first
            return np.bincount(local[(local >= 0) & (local < count)],
                               minlength=count)

        layers = []
        for c in chosen:
            tokens = per_expert(c)
            held, chunk = int(tokens.sum()), moe.chunk_rows(c.size)
            # the grouped products' row tiles, a call of routed_ff each
            tiles = [moe.row_tiles(per_expert(call), call.size)
                     for call in (c if self.routed_by_sequence else [c])]
            layers.append({"pairs_total": int(c.size), "pairs_held": held,
                           "expert_tokens": tokens.tolist(),
                           "chunks_run": -(-held // chunk),
                           "chunks_total": -(-c.size // chunk),
                           "token_rows_run": 2 * moe.token_rows(held, c.size),
                           "token_rows_total": 2 * c.size,
                           "row_tiles_run": sum(run for run, _ in tiles),
                           "row_tiles_total": sum(n for _, n in tiles)})
        tokens = np.array([n for rec in layers for n in rec["expert_tokens"]]
                          or [0])
        out = {"layers": layers,
               **{key: sum(rec[key] for rec in layers) for key in (
                   "pairs_total", "pairs_held", "chunks_run", "chunks_total",
                   "token_rows_run", "token_rows_total", "row_tiles_run",
                   "row_tiles_total")},
               "expert_tokens_max": int(tokens.max()),
               "expert_tokens_mean": float(tokens.mean())}
        _metrics.counter("moe.pairs_held").inc(out["pairs_held"])
        _metrics.counter("moe.pairs_total").inc(out["pairs_total"])
        _metrics.counter("moe.chunks_run").inc(out["chunks_run"])
        _metrics.counter("moe.chunks_total").inc(out["chunks_total"])
        _metrics.counter("moe.token_rows_run").inc(out["token_rows_run"])
        _metrics.counter("moe.token_rows_total").inc(out["token_rows_total"])
        _metrics.counter("moe.row_tiles_run").inc(out["row_tiles_run"])
        _metrics.counter("moe.row_tiles_total").inc(out["row_tiles_total"])
        _metrics.gauge("moe.expert_tokens_max").set(
            out["expert_tokens_max"])
        _metrics.gauge("moe.expert_tokens_mean").set(
            out["expert_tokens_mean"])
        return out
