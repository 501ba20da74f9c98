"""Required work of a decoder step with routed experts, from widths and pairs.

``flops.per_example`` traces the plain reference, which applies every held
expert to every token; a routed layer needs one gated feed-forward per
(token, expert) PAIR routed to an expert this chip holds. So the count here
is made from the configuration's widths and the pairs the traced steps
really routed (the model's ``route_stats``), nothing is traced, and a change
to the program does not move it.

Conventions, stated because they are conventions:

- a multiply-add is two operations; only matrix products are counted (the
  convolution's three taps, the gates, norms, rotations and the softmax are
  under 0.1% of a step);
- attention is counted CAUSAL: a query at position t needs t + 1 keys, so a
  sequence of S needs S (S + 1) / 2 of the S x S products, for QK^T and PV;
- a training step is ``passes`` = 3 forward passes (forward, gradient w.r.t.
  activations, gradient w.r.t. weights); recomputed operations do not count;
- bytes are counted for the grouped expert products only (the one kernel
  with a roofline share here): each of the three products, in each of the
  three passes, reads its two operands and writes its result once in the
  compute dtype, rows = held pairs, weights = every held expert's.

``cfg`` is a configuration file's dict (``benchmark/configs/<name>.json``).
"""

from __future__ import annotations


def _ops(cfg) -> list:
    return list(cfg["layer_types"])


def routed_layers(cfg) -> int:
    return len(_ops(cfg)) - int(cfg["num_dense_layers"])


def pair_flops(cfg) -> int:
    """One gated feed-forward of one expert on one token: three products."""
    return 3 * 2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def forward_parts(cfg, seq_len: int) -> dict:
    """Forward FLOPs a token needs, by part, for everything but the routed
    experts (which follow the pairs, not the tokens)."""
    d = int(cfg["hidden_size"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // heads)
    ops = _ops(cfg)
    n_conv = sum(1 for op in ops if op == "conv")
    n_attn = len(ops) - n_conv
    dense = int(cfg["num_dense_layers"])
    router_width = int(cfg["published"]["num_experts"])
    return {
        "conv_op": n_conv * (2 * d * 3 * d + 2 * d * d),
        "attention_proj": n_attn * (2 * d * heads * hd * 2
                                    + 2 * d * kv * hd * 2),
        # S (S + 1) / 2 visible keys per sequence, QK^T and PV
        "attention_causal": n_attn * 2 * 2 * heads * hd * (seq_len + 1) / 2,
        "dense_ff": dense * 3 * 2 * d * int(cfg["intermediate_size"]),
        "router": routed_layers(cfg) * 2 * d * router_width,
        "head": 2 * d * int(cfg["vocab_size"]),
    }


def forward_flops_per_token(cfg, seq_len: int, pairs_per_token: float) -> float:
    """``pairs_per_token``: held pairs over tokens, summed over the routed
    layers (1 a layer when a quarter of the experts is held and four are
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
    ``num_experts`` experts."""
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg["num_experts"])
    weights = routed_layers(cfg) * held * d * f
    # per product and pass: rows x (in + out) activations + the weights
    elements = 3 * passes * (pairs_held * (d + f) + weights)
    return {"flops": float(passes * pairs_held * pair_flops(cfg)),
            "bytes": float(dtype_bytes * elements)}
