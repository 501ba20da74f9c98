"""Required work of a latent-attention (MLA) decoder step with routed experts
and a multi-token-prediction module, from widths and pairs.

The ``joyai_llm_flash`` counterpart of ``flops_lm.py`` (same conventions,
same signatures, so that adapter ``lm_train``'s ``Cell`` calls either): two
parts a layer, latent attention and a feed-forward (dense before
``first_k_dense_replace``, then routed gated-SiLU experts beside a shared
one), an untied head, and ``num_nextn_predict_layers`` modules of one more
routed layer each behind a projection ``M`` of two concatenated streams and
in front of a second pass of the head. Nothing is traced, and a change to
the program does not move the count.

Conventions, stated because they are conventions:

- a multiply-add is two operations; only matrix products are counted
  (gates, norms, rotations and the softmax are under 0.1% of a step);
- latent attention is counted IN ITS EXPANDED FORM, the form training
  computes: five projections (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``,
  ``W_o``) and, a head, scores over ``qk_nope_head_dim + qk_rope_head_dim``
  and the weighted sum over ``v_head_dim``; the rotated key is shared by the
  heads but every head's score product contracts over it, so it counts a
  head;
- attention is counted CAUSAL: S (S + 1) / 2 of the S x S products;
- both head passes are counted at every position (the program computes all
  S and masks the one or two that have no target);
- a training step is ``passes`` = 3 forward passes; recomputed operations
  do not count;
- bytes are counted for the grouped expert products only (``experts_work``:
  three products, each pass reads its two operands and writes its result
  once in the compute dtype, rows = held pairs, weights = every held
  expert's), over the routed layers AND the modules' routed layers.

``cfg`` is a configuration file's dict (``benchmark/configs/<name>.json``):
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` count what is
HELD here, ``published`` what the model has.
"""

from __future__ import annotations


def _modules(cfg) -> int:
    return int(cfg.get("num_nextn_predict_layers", 0))


def routed_layers(cfg) -> int:
    """Routed layers of the stack and of the prediction modules."""
    return (int(cfg["num_hidden_layers"])
            - int(cfg.get("first_k_dense_replace", 0)) + _modules(cfg))


def pair_flops(cfg) -> int:
    """One gated feed-forward of one expert on one token: three products."""
    return 3 * 2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def mla_parts(cfg, seq_len: int) -> dict:
    """Forward FLOPs a token needs in ONE latent-attention block."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    q_rank, kv_rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    v = int(cfg["v_head_dim"])
    return {
        "proj": 2 * (d * q_rank + q_rank * heads * (nope + rope)
                     + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
                     + heads * v * d),
        # S (S + 1) / 2 visible keys per sequence: QK^T over nope + rope,
        # PV over v
        "causal": 2 * heads * (nope + rope + v) * (seq_len + 1) / 2,
    }


def forward_parts(cfg, seq_len: int) -> dict:
    """Forward FLOPs a token needs, by part, for everything but the routed
    experts (which follow the pairs, not the tokens)."""
    d = int(cfg["hidden_size"])
    layers, modules = int(cfg["num_hidden_layers"]), _modules(cfg)
    dense = int(cfg.get("first_k_dense_replace", 0))
    shared = int(cfg.get("n_shared_experts", 0)) * int(
        cfg["moe_intermediate_size"])
    mla = mla_parts(cfg, seq_len)
    return {
        "mla_proj": (layers + modules) * mla["proj"],
        "attention_causal": (layers + modules) * mla["causal"],
        "dense_ff": dense * 3 * 2 * d * int(cfg["intermediate_size"]),
        "shared_ff": routed_layers(cfg) * 3 * 2 * d * shared,
        "router": routed_layers(cfg) * 2 * d * int(
            cfg["published"]["n_routed_experts"]),
        "mtp_merge": modules * 2 * 2 * d * d,
        "head": (1 + modules) * 2 * d * int(cfg["vocab_size"]),
    }


def forward_flops_per_token(cfg, seq_len: int, pairs_per_token: float) -> float:
    """``pairs_per_token``: held pairs over tokens, summed over the routed
    layers (0.25 a layer when 8 of 256 experts are held and eight are
    selected evenly)."""
    return (sum(forward_parts(cfg, seq_len).values())
            + pairs_per_token * pair_flops(cfg))


def step_flops(cfg, tokens: int, seq_len: int, pairs_held: int,
               passes: int = 3) -> float:
    """What one training step on ``tokens`` tokens needs when its routed
    layers sent ``pairs_held`` pairs (all layers together) to held experts."""
    return float(passes * (tokens * sum(forward_parts(cfg, seq_len).values())
                           + pairs_held * pair_flops(cfg)))


def experts_work(cfg, pairs_held: int, passes: int = 3,
                 dtype_bytes: int = 2) -> dict:
    """Needed FLOPs and bytes of the grouped expert products of one step:
    ``pairs_held`` over all routed layers, each layer holding
    ``n_routed_experts`` experts."""
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    weights = routed_layers(cfg) * int(cfg["n_routed_experts"]) * d * f
    # per product and pass: rows x (in + out) activations + the weights
    elements = 3 * passes * (pairs_held * (d + f) + weights)
    return {"flops": float(passes * pairs_held * pair_flops(cfg)),
            "bytes": float(dtype_bytes * elements)}


def attention_flops(cfg, tokens: int, seq_len: int, passes: int = 3) -> float:
    """Needed FLOPs of the causal score and value products of one step,
    every latent-attention block: what the flash kernels are there for."""
    return float(passes * tokens * forward_parts(cfg, seq_len)[
        "attention_causal"])


def mtp_flops(cfg, tokens: int, seq_len: int, passes: int = 3) -> float:
    """Needed FLOPs a step of what the prediction modules add and the
    tokens fix: ``M``, the module's latent attention, shared expert and
    router, and the second pass of the head. The module's routed pairs
    are counted with the other layers' (``experts_work``)."""
    modules = _modules(cfg)
    if not modules:
        return 0.0
    d = int(cfg["hidden_size"])
    shared = int(cfg.get("n_shared_experts", 0)) * int(
        cfg["moe_intermediate_size"])
    per_token = (2 * 2 * d * d + sum(mla_parts(cfg, seq_len).values())
                 + 3 * 2 * d * shared
                 + 2 * d * int(cfg["published"]["n_routed_experts"])
                 + 2 * d * int(cfg["vocab_size"]))
    return float(passes * tokens * modules * per_token)
