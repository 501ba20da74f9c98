"""Long-context causal transformer — the model family that exercises
sequence parallelism end-to-end.

The reference has no sequence model (its zoo is image CNNs, SURVEY.md
§2.1); tpudl's charter makes long context first-class, so this is the
TPU-native addition that turns :func:`tpudl.attention.ring_attention`
from an op into a trainable model: a pre-norm causal decoder whose
attention runs as a mesh ring when given a mesh (K/V rotating on ICI,
O(S/n) per device), and as :func:`tpudl.pallas_ops.flash_attention`
tiles when ``use_pallas``. Pure functions over a param pytree, same
style as the CNN zoo — drops straight into
``tpudl.train.Trainer``/``make_train_step`` (the batch stays sharded on
the data axis for the loss; the sequence axis shards inside attention).

Parameters follow the zoo convention: a flat dict of layer-name →
{param-name: array}, seedable via ``init``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TinyCausalLM"]


def _embed(params, ids):
    """Token-embedding lookup. ``init`` returns host NumPy arrays, and
    NumPy cannot be indexed by a traced ``ids`` — lift the table first
    (a no-op for device arrays and tracers)."""
    return jnp.asarray(params["embed"]["table"])[ids]


def _layer_norm(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


class TinyCausalLM:
    """A small pre-norm decoder LM: embed → [attn + mlp]×L → logits.

    ``apply(params, tokens, mesh=None, use_pallas=False)`` returns
    next-token logits. With ``mesh``, attention is
    :func:`ring_attention` over the mesh's data axis (the sequence must
    divide by the axis size); without, it is dense causal attention —
    identical math, proven in tests.

    The full parallelism matrix hangs off this one model:

    - SP: ``apply(mesh=...)`` — ring attention (+ ``use_pallas`` flash
      tiles), ``remat=True`` for long-context activation HBM.
    - TP: ``param_shardings``/``shard_params`` + ``apply(tp=True)`` —
      Megatron column/row-parallel layout, GSPMD collectives.
    - EP: ``experts=N`` — top-1 switch MoE, experts sharded over the
      ``model`` axis (composes with ``tp=True``).
    - PP: :meth:`apply_pipelined` — GPipe microbatch schedule over a
      mesh axis (composes with a DP ``data_axis``).
    """

    def __init__(self, vocab: int = 256, dim: int = 64, heads: int = 4,
                 layers: int = 2, max_len: int = 4096, experts: int = 0,
                 capacity_factor: float = 2.0):
        if dim % heads:
            raise ValueError(f"dim {dim} not divisible by heads {heads}")
        self.vocab = vocab
        self.dim = dim
        self.heads = heads
        self.layers = layers
        self.max_len = max_len
        # experts > 0 swaps each block's dense MLP for a top-1-routed
        # mixture of experts (switch-style): the EXPERT dim is the
        # tensor/expert-parallel dim — param_shardings lays experts out
        # over the mesh's 'model' axis, and GSPMD inserts the
        # dispatch/combine collectives (the GShard pattern)
        self.experts = experts
        self.capacity_factor = capacity_factor
        # compiled generate() programs keyed by static decode geometry
        # (a fresh jax.jit per call would retrace every time)
        self._gen_jits: dict = {}
        # the architecture's identity (the default repr carries a
        # memory address): names the model in jit-cache keys and in
        # error messages
        self.arch_token = (f"TinyCausalLM:v{vocab}:d{dim}:h{heads}:"
                          f"l{layers}:m{max_len}:e{experts}:"
                          f"c{capacity_factor}")

    # -- params -----------------------------------------------------------
    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        d, v = self.dim, self.vocab

        def w(*shape, scale=None):
            scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
            return (rng.normal(size=shape) * scale).astype(np.float32)

        params: dict = {
            "embed": {"table": w(v, d, scale=0.02)},
            "final_norm": {"gamma": np.ones(d, np.float32),
                           "beta": np.zeros(d, np.float32)},
        }
        for i in range(self.layers):
            block = {
                "norm1_gamma": np.ones(d, np.float32),
                "norm1_beta": np.zeros(d, np.float32),
                "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
                "norm2_gamma": np.ones(d, np.float32),
                "norm2_beta": np.zeros(d, np.float32),
            }
            if self.experts:
                e = self.experts
                block.update({
                    "w_gate": w(d, e, scale=0.02),
                    "w_up_e": np.stack([w(d, 4 * d) for _ in range(e)]),
                    "b_up_e": np.zeros((e, 4 * d), np.float32),
                    "w_down_e": np.stack([w(4 * d, d) for _ in range(e)]),
                    "b_down_e": np.zeros((e, d), np.float32),
                })
            else:
                block.update({
                    "w_up": w(d, 4 * d), "b_up": np.zeros(4 * d, np.float32),
                    "w_down": w(4 * d, d),
                    "b_down": np.zeros(d, np.float32),
                })
            params[f"block_{i}"] = block
        return params

    # -- tensor parallelism ------------------------------------------------
    def param_shardings(self, mesh, model_axis: str = "model"):
        """NamedSharding pytree for Megatron-style tensor parallelism
        over ``mesh[model_axis]`` — the TPU-native spelling: shard the
        PARAMS and let GSPMD partition the matmuls and insert the
        all-reduces (scaling-book recipe; no hand-written collectives).

        Layout per block: wq/wk/wv and w_up are COLUMN-parallel (output
        dim sharded → each device computes its own heads / hidden
        slice), wo and w_down are ROW-parallel (input dim sharded → XLA
        emits one psum over ``model_axis`` after each, the two
        all-reduces per layer of the Megatron pattern). Embedding,
        norms, and row-parallel biases stay replicated.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = mesh.shape[model_axis]
        if self.heads % tp or (4 * self.dim) % tp:
            raise ValueError(
                f"heads {self.heads} and mlp hidden {4 * self.dim} must "
                f"divide the {model_axis!r} axis size {tp}")
        if self.experts and self.experts % tp:
            raise ValueError(
                f"experts {self.experts} must divide the {model_axis!r} "
                f"axis size {tp}")
        col = NamedSharding(mesh, P(None, model_axis))   # output sharded
        row = NamedSharding(mesh, P(model_axis, None))   # input sharded
        rep = NamedSharding(mesh, P())
        bias_col = NamedSharding(mesh, P(model_axis))    # column bias
        shardings: dict = {
            "embed": {"table": rep},
            "final_norm": {"gamma": rep, "beta": rep},
        }
        for i in range(self.layers):
            block = {
                "norm1_gamma": rep, "norm1_beta": rep,
                "wq": col, "wk": col, "wv": col, "wo": row,
                "norm2_gamma": rep, "norm2_beta": rep,
            }
            if self.experts:
                # expert parallelism: the EXPERT (leading) dim is the
                # sharded dim — each device owns E/tp whole experts
                # (their FFN weights never move; tokens do, via the
                # dispatch einsum's collectives)
                block.update({
                    "w_gate": rep,
                    "w_up_e": NamedSharding(mesh, P(model_axis, None, None)),
                    "b_up_e": NamedSharding(mesh, P(model_axis, None)),
                    "w_down_e": NamedSharding(mesh, P(model_axis, None, None)),
                    "b_down_e": NamedSharding(mesh, P(model_axis, None)),
                })
            else:
                block.update({
                    "w_up": col, "b_up": bias_col,
                    "w_down": row, "b_down": rep,
                })
            shardings[f"block_{i}"] = block
        return shardings

    def shard_params(self, params, mesh, model_axis: str = "model"):
        """device_put ``params`` with :meth:`param_shardings` — each
        device holds 1/tp of every column/row-parallel matrix. Checked
        against ``TPUDL_DATA_HBM_BUDGET_MB`` first: a layout whose
        per-device share exceeds the budget raises a typed
        :class:`~tpudl.frame.supervisor.DeviceOOM` BEFORE any transfer
        — widen the ``model`` axis instead of crashing a chip."""
        import jax

        from tpudl import mesh as M

        shardings = self.param_shardings(mesh, model_axis)
        M.require_hbm_fit(params, shardings,
                          what=f"{self.arch_token} params")
        return jax.tree.map(jax.device_put, params, shardings)

    def _tp_hooks(self, mesh, tp):
        """``(tp_constrain, head_axis)`` shared by :meth:`apply` and
        :meth:`decode_step` — the ONE definition of how tensor
        parallelism constrains activations, so training and serving can
        never silently diverge on sharding."""
        if tp and (mesh is None or "model" not in mesh.shape):
            raise ValueError(
                "tp=True needs a mesh with a 'model' axis "
                "(tpudl.mesh.build_mesh(n_data=..., n_model=...))")
        head_axis = "model" if tp and mesh.shape["model"] > 1 else None

        def tp_constrain(t, spec):
            # Pin ONLY the model-axis dim; every None becomes
            # UNCONSTRAINED so GSPMD keeps whatever batch/seq sharding
            # the surrounding program chose (a None here would mean
            # "replicated" and force per-layer all-gathers of the
            # DP-sharded activations over the data axis — verified in
            # HLO during review).
            if head_axis is None:
                return t
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec = tuple(P.UNCONSTRAINED if s is None else s for s in spec)
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P(*spec)))

        return tp_constrain, head_axis

    # -- forward ----------------------------------------------------------
    def apply(self, params, tokens, *, mesh=None, use_pallas: bool = False,
              remat: bool = False, tp: bool = False):
        """tokens [B, S] int32 → logits [B, S, vocab].

        ``tp=True`` (requires ``mesh`` with a >1 ``model`` axis) adds
        tensor-parallel sharding constraints: attention heads and the
        MLP hidden dim live sharded over the ``model`` axis (matching
        :meth:`param_shardings`), composing with the ring path — the
        full DP(batch, data axis) × SP(ring, data axis) × TP(heads/mlp,
        model axis) program in one jit.

        ``remat=True`` wraps each decoder block in ``jax.checkpoint``:
        the backward pass recomputes block activations instead of
        holding them, so training-time activation HBM drops from
        O(layers · B · S · D) to O(B · S · D) + one block — the standard
        TPU long-context trade (FLOPs are cheap on the MXU, HBM is not).
        Composes with the ring path (shard_map/ppermute are rematable —
        under ``jax.jit``, as the Trainer always runs; eager
        checkpoint-of-shard_map is unsupported upstream) and the Pallas
        kernels (the custom VJP re-runs the tiled forward)."""
        x = self.hidden(params, tokens, mesh=mesh, use_pallas=use_pallas,
                        remat=remat, tp=tp)
        return x @ params["embed"]["table"].T              # tied head

    def hidden(self, params, tokens, *, mesh=None, use_pallas: bool = False,
               remat: bool = False, tp: bool = False):
        """tokens [B, S] int32 → final-norm hidden states [B, S, D] —
        :meth:`apply` minus the tied head projection. The embedding
        surface the LMFeaturizer pools (pre-logits representations are
        the standard text-feature contract), sharing the block body so
        the featurize and generate paths can never diverge on math."""
        from tpudl.attention import attention_reference, ring_attention

        b, s = tokens.shape
        if s > self.max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_len {self.max_len}")
        tp_constrain, head_axis = self._tp_hooks(mesh, tp)

        x = _embed(params, tokens)                         # [B, S, D]

        # rotary-free: learned-position-less (relative order comes from
        # the causal mask; adequate for the convergence tests this
        # model exists for, and keeps the ring path position-agnostic)
        def attn(q, k, v):
            if mesh is not None:
                return ring_attention(q, k, v, mesh, causal=True,
                                      head_axis=head_axis,
                                      use_pallas=use_pallas)
            if use_pallas:
                from tpudl.pallas_ops import flash_attention

                return flash_attention(q, k, v, causal=True)
            return attention_reference(q, k, v, causal=True)

        def block(x, p):
            return self._decoder_block(x, p, attn, tp_constrain,
                                       head_axis)

        if remat:
            block = jax.checkpoint(block)
        for i in range(self.layers):
            x = block(x, params[f"block_{i}"])
        return _layer_norm(x, params["final_norm"])

    def apply_pipelined(self, params, tokens, mesh, *,
                        pipe_axis: str = "model", n_micro: int = 2,
                        data_axis: str | None = None,
                        remat: bool = False):
        """Forward pass with the decoder blocks PIPELINED over
        ``mesh[pipe_axis]`` (GPipe microbatch schedule,
        :func:`tpudl.pipeline.pipeline_blocks`): stage ``i`` owns blocks
        ``[i·L/n, (i+1)·L/n)`` — weights stay put, activations hop
        stage-to-stage on neighbor ``ppermute``. Embed and head run
        replicated outside the pipe. ``data_axis`` additionally shards
        the microbatch dim over it — DP×PP in one jitted program.

        Attention inside the pipe is dense (each microbatch is whole on
        its stage); the ring/SP path is the ``apply(mesh=...)``
        spelling. ``batch % n_micro == 0``; MoE blocks unsupported here.
        """
        from tpudl.pipeline import pipeline_blocks

        if self.experts:
            raise NotImplementedError(
                "pipelined MoE blocks not supported; use apply(tp=True) "
                "for expert parallelism")
        b, s = tokens.shape
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by {n_micro} "
                             "microbatches")
        from tpudl.attention import attention_reference

        def block(x, p):
            return self._decoder_block(
                x, p, lambda q, k, v: attention_reference(q, k, v,
                                                          causal=True))

        x = _embed(params, tokens)                         # [B, S, D]
        xm = x.reshape(n_micro, b // n_micro, s, self.dim)
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[params[f"block_{i}"] for i in range(self.layers)])
        ym = pipeline_blocks(block, stacked, xm, mesh, axis=pipe_axis,
                             data_axis=data_axis, remat=remat)
        x = ym.reshape(b, s, self.dim)
        x = _layer_norm(x, params["final_norm"])
        return x @ params["embed"]["table"].T              # tied head

    def _decoder_block(self, x, p, attn, constrain=lambda t, spec: t,
                       head_axis=None):
        """ONE pre-norm decoder block — the single definition of the
        block math, shared by :meth:`apply` (dense/ring/pallas via
        ``attn``) and :meth:`apply_pipelined` (dense ``attn``), so the
        two paths can never silently diverge. ``constrain`` is the
        tensor-parallel sharding hook (identity when TP is off)."""
        b, s = x.shape[0], x.shape[1]
        h = _layer_norm(x, {"gamma": p["norm1_gamma"],
                            "beta": p["norm1_beta"]})
        q, k, v = (h @ p[w] for w in ("wq", "wk", "wv"))

        def split(t):
            return t.reshape(b, s, self.heads, self.dim // self.heads)

        q, k, v = (constrain(split(t), (None, None, head_axis, None))
                   for t in (q, k, v))
        att = attn(q, k, v)
        x = x + att.reshape(b, s, self.dim) @ p["wo"]
        h = _layer_norm(x, {"gamma": p["norm2_gamma"],
                            "beta": p["norm2_beta"]})
        if self.experts:
            return x + self._moe_ffn(h, p, constrain, head_axis)
        # hidden dim sharded over 'model' (column-parallel w_up); the
        # following row-parallel w_down matmul ends in the psum
        h = constrain(jax.nn.gelu(h @ p["w_up"] + p["b_up"]),
                      (None, None, head_axis))
        return x + h @ p["w_down"] + p["b_down"]

    def _moe_ffn(self, h, p, tp_constrain, head_axis):
        """Top-1-routed (switch-style) mixture-of-experts FFN — the
        expert-parallel layer. Per token: softmax gate picks ONE expert;
        tokens are packed into per-expert capacity buffers by a one-hot
        dispatch einsum (the GShard pattern), each expert's FFN runs on
        its buffer, and a combine einsum scatters results back weighted
        by the gate probability. Tokens over an expert's capacity
        contribute nothing — the residual passes them through unchanged
        (switch semantics).

        Parallelism: with experts sharded over ``model``
        (:meth:`param_shardings`) and the batch over ``data``, the
        dispatch/combine einsums are exactly where GSPMD inserts the
        EP collectives — tokens travel to their expert's device, FFN
        weights never move.
        """
        b, s, d = h.shape
        e = self.experts
        cap = max(1, int(math.ceil(s * self.capacity_factor / e)))
        logits = h @ p["w_gate"]                              # [B,S,E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate = probs.max(-1)                                  # [B,S]
        choice = probs.argmax(-1)                             # [B,S]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [B,S,E]
        # position of each token within its expert's buffer (per row)
        pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0        # [B,S,E]
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                              dtype=jnp.float32)               # [B,S,E,C]
        keep = ((pos >= 0) & (pos < cap)).astype(jnp.float32)  # [B,S,E]
        dispatch = slot * keep[..., None]                      # [B,S,E,C]
        combine = dispatch * gate[..., None, None]
        xe = jnp.einsum("bsec,bsd->ebcd", dispatch,
                        h.astype(jnp.float32))                 # [E,B,C,D]
        xe = tp_constrain(xe, (head_axis, None, None, None))
        u = jax.nn.gelu(jnp.einsum("ebcd,edh->ebch", xe,
                                   p["w_up_e"].astype(jnp.float32))
                        + p["b_up_e"][:, None, None, :])
        ye = (jnp.einsum("ebch,ehd->ebcd", u,
                         p["w_down_e"].astype(jnp.float32))
              + p["b_down_e"][:, None, None, :])
        ye = tp_constrain(ye, (head_axis, None, None, None))
        return jnp.einsum("bsec,ebcd->bsd", combine, ye).astype(h.dtype)

    # -- autoregressive decode (KV cache) ----------------------------------
    def init_cache(self, batch: int, max_len: int | None = None,
                   dtype=jnp.float32, *, mesh=None, tp: bool = False):
        """Per-layer K/V buffers for incremental decoding:
        ``[B, max_len, heads, head_dim]`` zeros. Static shapes — the
        decode loop writes position ``pos`` via dynamic_update_slice,
        so the whole generate() scan compiles once (no growing
        sequences under jit, the TPU-native spelling of a KV cache).

        ``tp=True`` (with a >1 ``model``-axis ``mesh``) shards the
        buffers over attention heads — each device holds the K/V slabs
        for ITS heads only, matching the column-parallel wq/wk/wv of
        :meth:`param_shardings`, so serving HBM for the cache also
        scales down 1/tp."""
        L = max_len or self.max_len
        dh = self.dim // self.heads
        buf = jnp.zeros((batch, L, self.heads, dh), dtype)
        _, head_axis = self._tp_hooks(mesh, tp)
        if head_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(mesh, P(None, None, head_axis, None))
            buf = (jax.lax.with_sharding_constraint(buf, sh)
                   if isinstance(buf, jax.core.Tracer)
                   else jax.device_put(buf, sh))
        return [{"k": buf, "v": buf} for _ in range(self.layers)]

    def decode_step(self, params, tok, cache, pos, *, mesh=None,
                    tp: bool = False):
        """One incremental step: token ids ``tok`` [B] at position
        ``pos`` (traced scalar) → (logits [B, vocab], updated cache).

        Routes through :meth:`_decoder_block` — the single definition
        of the block math — with a cache-aware ``attn`` callback: the
        block's freshly-projected K/V for this one token are written at
        ``pos`` and attention reads the whole cache masked to
        0..pos (oracle-pinned against :meth:`apply` in
        tests/test_transformer.py). MoE blocks are unsupported here
        (top-1 routing is trainable batch machinery; decode serving
        for experts would dispatch per token — not built).

        ``tp=True`` runs the step tensor-parallel: q/k/v and the cache
        writes stay sharded over heads on the ``model`` axis (same
        constraints as :meth:`apply`), so a model whose params exceed
        one chip's HBM decodes without ever gathering them."""
        if self.experts:
            raise NotImplementedError(
                "KV-cache decode for MoE blocks not supported")
        tp_constrain, head_axis = self._tp_hooks(mesh, tp)
        cache_len = cache[0]["k"].shape[1]
        try:  # concrete pos (the eager step-by-step pattern): loud OOB
            if int(pos) >= cache_len:
                raise ValueError(
                    f"pos {int(pos)} out of range for cache length "
                    f"{cache_len} — dynamic_update_slice would silently "
                    "clamp onto the last slot")
        except TypeError:
            pass  # traced pos: generate() bounds it via max_len
        x = _embed(params, tok)[:, None]                   # [B, 1, D]
        new_cache = []

        def cached_attn(layer):
            def attn(q, k_t, v_t):  # all [B, 1, H, Dh] from the block
                # scale in q's dtype (attention_reference discipline) —
                # an f32 scalar would silently promote the whole decode
                # path out of bf16
                scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
                kc = jax.lax.dynamic_update_slice_in_dim(
                    cache[layer]["k"], k_t.astype(cache[layer]["k"].dtype),
                    pos, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    cache[layer]["v"], v_t.astype(cache[layer]["v"].dtype),
                    pos, axis=1)
                # keep the updated cache sharded over heads — without
                # the pin GSPMD may gather the whole cache to satisfy
                # the replicated-output default of the update-slice
                kc = tp_constrain(kc, (None, None, head_axis, None))
                vc = tp_constrain(vc, (None, None, head_axis, None))
                new_cache.append({"k": kc, "v": vc})
                scores = jnp.einsum("bqhd,bshd->bhqs", q, kc) * scale
                live = jnp.arange(kc.shape[1]) <= pos      # [S]
                scores = jnp.where(live[None, None, None, :], scores,
                                   -jnp.inf)
                w = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum("bhqs,bshd->bqhd", w, vc)

            return attn

        for i in range(self.layers):
            x = self._decoder_block(x, params[f"block_{i}"],
                                    cached_attn(i), tp_constrain,
                                    head_axis)
        x = _layer_norm(x[:, 0], params["final_norm"])
        return x @ params["embed"]["table"].T, new_cache

    def decode_step_slots(self, params, tok, cache, pos, *, mesh=None,
                          tp: bool = False):
        """One decode step across ``S`` INDEPENDENT slots: token ids
        ``tok`` [S] at PER-SLOT positions ``pos`` [S] (traced) →
        (logits [S, vocab], updated cache) over a fixed-geometry
        ``[S, L, heads, head_dim]`` KV cache.

        The continuous-batching primitive (SERVE.md): each slot is one
        in-flight sequence at its own depth, so a churning request mix
        decodes through ONE compiled program — insert/evict are host
        bookkeeping plus a full-row cache write, never a shape change.
        Same block math as :meth:`decode_step` (shared
        :meth:`_decoder_block`); only the cache write (vmapped per-slot
        ``dynamic_update_slice``) and the mask (per-slot ``keys <=
        pos[s]``) differ. Rows are independent in every reduction, so a
        slot's logits are bitwise those of a batch-1 serial decode at
        the same position — the parity contract tests/test_serve.py
        pins. Inactive slots ride along on stale state: their write at
        ``pos[s]`` lands in a row whose NEXT insert overwrites the
        whole row before anything reads it (the same
        overwrite-before-attend invariant as :meth:`_gen_program`'s pad
        slots), and their logits are discarded host-side."""
        if self.experts:
            raise NotImplementedError(
                "KV-cache decode for MoE blocks not supported")
        tp_constrain, head_axis = self._tp_hooks(mesh, tp)
        x = _embed(params, tok)[:, None]                   # [S, 1, D]
        new_cache = []

        def cached_attn(layer):
            def attn(q, k_t, v_t):  # all [S, 1, H, Dh] from the block
                scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)

                def write(buf, t):
                    # per-slot depth: each row gets its own update
                    # position (the scalar-pos update of decode_step,
                    # vmapped over the slot dim)
                    return jax.vmap(
                        lambda row, upd, p:
                        jax.lax.dynamic_update_slice_in_dim(
                            row, upd, p, axis=0))(
                        buf, t.astype(buf.dtype), pos)

                kc = write(cache[layer]["k"], k_t)
                vc = write(cache[layer]["v"], v_t)
                kc = tp_constrain(kc, (None, None, head_axis, None))
                vc = tp_constrain(vc, (None, None, head_axis, None))
                new_cache.append({"k": kc, "v": vc})
                scores = jnp.einsum("bqhd,bshd->bhqs", q, kc) * scale
                live = (jnp.arange(kc.shape[1])[None, :]
                        <= pos[:, None])                   # [S, L]
                scores = jnp.where(live[:, None, None, :], scores,
                                   -jnp.inf)
                w = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum("bhqs,bshd->bqhd", w, vc)

            return attn

        for i in range(self.layers):
            x = self._decoder_block(x, params[f"block_{i}"],
                                    cached_attn(i), tp_constrain,
                                    head_axis)
        x = _layer_norm(x[:, 0], params["final_norm"])
        return x @ params["embed"]["table"].T, new_cache

    def _slot_step_program(self, slots: int, cache_len: int,
                           temperature: float, *, mesh=None,
                           tp: bool = False):
        """The jitted one-token-per-slot decode program for one static
        serve geometry ``(slots, cache_len, temperature)`` — the ONE
        program a continuous-batching serve loop dispatches forever:
        ``(params, cache, tok [S], pos [S], keys [S], steps [S])`` →
        ``(next_tok [S], cache')``. Sampling folds each slot's key with
        ITS generation-step index, matching :meth:`_gen_program`'s
        per-step ``fold_in`` so a sampled slot reproduces the serial
        token stream."""

        def run(params, cache, tok, pos, keys, steps):
            logits, cache = self.decode_step_slots(
                params, tok, cache, pos, mesh=mesh, tp=tp)
            if temperature > 0:
                nxt = jax.vmap(
                    lambda lg, kk, st: jax.random.categorical(
                        jax.random.fold_in(kk, st),
                        lg / temperature, axis=-1))(
                    logits, keys, steps).astype(jnp.int32)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, cache

        topo = (tuple(sorted((str(k), int(v))
                             for k, v in mesh.shape.items()))
                if tp and mesh is not None else None)
        jit_key = ("slot_step", slots, cache_len, float(temperature),
                   topo)
        fn = self._gen_jits.get(jit_key)
        if fn is None:
            if len(self._gen_jits) >= 32:
                self._gen_jits.pop(next(iter(self._gen_jits)))
            fn = self._gen_jits[jit_key] = jax.jit(run)
        return fn

    def _slot_prefill_program(self, plen: int, slots: int,
                              cache_len: int, temperature: float, *,
                              mesh=None, tp: bool = False):
        """The jitted insert program for one static ``(PADDED prompt
        len, slots, cache_len, temperature)``: scan the prompt through
        :meth:`decode_step` on a fresh batch-1 row cache of the SLOT
        length, pick the first token at ``real_plen - 1`` (the
        :meth:`_gen_program` logits-carry), then write the whole row
        into the slot cache at a TRACED slot index —
        ``(params, cache, prompt [1, plen], key, real_plen, slot)`` →
        ``(first_tok [1], cache')``. Bucketed prompts share programs:
        O(log n) prefill signatures serve every ragged admission
        (COMPILE.md), and the full-row write wipes any stale state of
        the slot's previous occupant before a single step attends it."""

        def run(params, cache, prompt, key, real_plen, slot):
            tp_constrain, head_axis = self._tp_hooks(mesh, tp)
            dtype = params["embed"]["table"].dtype
            row = self.init_cache(1, cache_len, dtype=dtype, mesh=mesh,
                                  tp=tp)

            def prefill_step(carry, t):
                rc, best = carry
                p, t_ = t
                logits, rc = self.decode_step(params, t_, rc, p,
                                              mesh=mesh, tp=tp)
                best = jnp.where(p == real_plen - 1, logits, best)
                return (rc, best), None

            (row, logits), _ = jax.lax.scan(
                prefill_step,
                (row, jnp.zeros((1, self.vocab), dtype)),
                (jnp.arange(plen), prompt.T))
            if temperature > 0:
                first = jax.random.categorical(
                    jax.random.fold_in(key, 0), logits / temperature,
                    axis=-1).astype(jnp.int32)
            else:
                first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_cache = []
            for layer in range(self.layers):
                kc = jax.lax.dynamic_update_slice(
                    cache[layer]["k"],
                    row[layer]["k"].astype(cache[layer]["k"].dtype),
                    (slot, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache[layer]["v"],
                    row[layer]["v"].astype(cache[layer]["v"].dtype),
                    (slot, 0, 0, 0))
                kc = tp_constrain(kc, (None, None, head_axis, None))
                vc = tp_constrain(vc, (None, None, head_axis, None))
                new_cache.append({"k": kc, "v": vc})
            return first, new_cache

        topo = (tuple(sorted((str(k), int(v))
                             for k, v in mesh.shape.items()))
                if tp and mesh is not None else None)
        jit_key = ("slot_prefill", plen, slots, cache_len,
                   float(temperature), topo)
        fn = self._gen_jits.get(jit_key)
        if fn is None:
            if len(self._gen_jits) >= 32:
                self._gen_jits.pop(next(iter(self._gen_jits)))
            fn = self._gen_jits[jit_key] = jax.jit(run)
        return fn

    def _gen_program(self, b: int, plen: int, max_new: int,
                     temperature: float, *, mesh=None, tp: bool = False):
        """The jitted generate program for one static geometry
        ``(batch, PADDED prompt len, max_new, temperature)`` — the real
        prompt length is a TRACED argument, so every prompt that pads
        up to the same bucket rung shares ONE compiled program
        (COMPILE.md "LM sequence bucketing"; the prefill scan runs over
        the padded length and the logits carry selects position
        ``plen-1``, and the attention mask in :meth:`decode_step` — keys
        ≤ pos — plus generation's in-place overwrites at plen, plen+1, …
        guarantee a pad slot is never attended before it is
        overwritten, so real-token results match exact-length dispatch;
        only float reduction tiling over the longer masked cache can
        differ, the DATA.md reassociation caveat class)."""

        def run(params, prompt, key, real_plen):
            def prefill_step(carry, t):
                cache, best = carry
                pos, tok = t
                logits, cache = self.decode_step(params, tok, cache, pos,
                                                 mesh=mesh, tp=tp)
                # logits ride the CARRY (only position real_plen-1's
                # are used) — a stacked scan output would materialize
                # [plen, B, vocab]
                best = jnp.where(pos == real_plen - 1, logits, best)
                return (cache, best), None

            def pick(logits, step_key):
                if temperature > 0:
                    return jax.random.categorical(
                        step_key, logits / temperature,
                        axis=-1).astype(jnp.int32)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def gen_step(carry, t):
                cache, tok = carry
                pos, step_key = t
                logits, cache = self.decode_step(params, tok, cache, pos,
                                                 mesh=mesh, tp=tp)
                nxt = pick(logits, step_key)
                return (cache, nxt), nxt

            # cache dtype follows the params (bf16 serving works)
            cache = self.init_cache(
                b, plen + max_new, dtype=params["embed"]["table"].dtype,
                mesh=mesh, tp=tp)
            (cache, logits), _ = jax.lax.scan(
                prefill_step,
                (cache, jnp.zeros((b, self.vocab),
                                  params["embed"]["table"].dtype)),
                (jnp.arange(plen), prompt.T))
            first = pick(logits, jax.random.fold_in(key, 0))
            if max_new == 1:
                return first[:, None]
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(1, max_new))
            (_c, _t), rest = jax.lax.scan(
                gen_step, (cache, first),
                (real_plen + jnp.arange(max_new - 1), keys))
            return jnp.concatenate([first[:, None], rest.T], axis=1)

        # a 2-D TP program and the 1-D program for the same geometry
        # are DIFFERENT executables — the mesh topology joins the key
        topo = (tuple(sorted((str(k), int(v))
                             for k, v in mesh.shape.items()))
                if tp and mesh is not None else None)
        jit_key = (b, plen, max_new, float(temperature), topo)
        fn = self._gen_jits.get(jit_key)
        if fn is None:
            if len(self._gen_jits) >= 32:
                # bound the per-geometry program cache (serving with
                # unbucketed prompt lengths would otherwise grow it
                # forever); FIFO eviction is fine at this size
                self._gen_jits.pop(next(iter(self._gen_jits)))
            fn = self._gen_jits[jit_key] = jax.jit(run)
        return fn

    def _gen_bucket(self, plen: int, max_new: int, prompt_buckets):
        """Padded prompt length for this call: the smallest ladder rung
        ≥ plen that still fits ``max_len`` with ``max_new`` to go.
        ``None``/off → exact."""
        from tpudl.compile import resolve_ladder

        ladder = resolve_ladder(prompt_buckets)
        if ladder is None:
            return plen
        return max(plen, min(ladder.pick(plen),
                             self.max_len - max_new))

    def generate(self, params, prompt, max_new: int, *,
                 temperature: float = 0.0, rng=None,
                 prompt_buckets=None, mesh=None, tp: bool = False):
        """Autoregressive continuation: ``prompt`` [B, P] int32 →
        [B, max_new] int32. One jitted program: prefill scans
        :meth:`decode_step` over the prompt (filling the cache),
        generation scans it over ``max_new`` steps feeding each
        prediction back in. ``temperature=0`` is greedy argmax;
        otherwise softmax sampling with ``rng`` (a jax PRNG key).
        Total length must fit ``max_len``.

        ``prompt_buckets`` (a :class:`tpudl.compile.BucketLadder`, a
        spec string, or ``True`` for the default ladder; ``None`` =
        off) right-pads the prompt to the nearest ladder rung so
        serving with ragged prompt lengths compiles O(log max_len)
        programs instead of one per novel length — the real length
        stays a traced argument (masked prefill), so results match the
        exact-length program for the real tokens.

        ``tp=True`` (with a >1 ``model``-axis ``mesh``) decodes
        tensor-parallel: pass params already placed by
        :meth:`shard_params` and the whole prefill+decode program runs
        with heads and the KV cache sharded — params larger than one
        chip's HBM serve without ever being gathered."""
        prompt = jnp.asarray(prompt, jnp.int32)
        b, plen = prompt.shape
        total = plen + max_new
        if total > self.max_len:
            raise ValueError(f"prompt {plen} + max_new {max_new} exceeds "
                             f"max_len {self.max_len}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if plen < 1:
            # an empty prompt makes the prefill scan a no-op: the first
            # token would be picked from the zero-initialized logits
            # carry (always argmax of zeros), never from the model
            raise ValueError(f"prompt must hold >= 1 token, got shape "
                             f"{tuple(prompt.shape)}")
        if temperature > 0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs rng=")
        padded = self._gen_bucket(plen, max_new, prompt_buckets)
        if padded > plen:
            prompt = jnp.concatenate(
                [prompt, jnp.zeros((b, padded - plen), jnp.int32)],
                axis=1)
        key = rng if rng is not None else jax.random.PRNGKey(0)
        fn = self._gen_program(b, padded, max_new, float(temperature),
                               mesh=mesh, tp=tp)
        return fn(params, prompt, key, jnp.int32(plen))

    # -- training loss -----------------------------------------------------
    def loss_fn(self, *, mesh=None, use_pallas: bool = False,
                remat: bool = False, tp: bool = False):
        """``loss(params, tokens)``: next-token cross-entropy, mean over
        the global batch (the allreduce contraction —
        tpudl.train.make_train_step turns it into the ICI psum).
        ``remat=True`` checkpoints each block (see :meth:`apply`);
        ``tp=True`` shards heads/MLP over the mesh's ``model`` axis
        (pair with :meth:`shard_params` and
        ``make_train_step(param_shardings=...)``)."""

        def loss(params, tokens):
            logits = self.apply(params, tokens[:, :-1], mesh=mesh,
                                use_pallas=use_pallas, remat=remat, tp=tp)
            targets = tokens[:, 1:]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(
                logp, targets[..., None].astype(jnp.int32), axis=-1)
            return -jnp.mean(picked)

        return loss
