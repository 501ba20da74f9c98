"""Required work of a ``nemotron_h`` decoder step, from widths and pairs.

The hybrid counterpart of ``flops_lm.py`` (same conventions, same
signatures, so that adapter ``lm_train``'s ``Cell`` calls either): one part
a layer by ``hybrid_override_pattern`` (``M`` Mamba-2 mixer, ``*``
attention, ``E`` routed ``relu2`` experts beside a shared expert), an
untied head. Nothing is traced, and a change to the program does not move
the count.

Conventions, stated because they are conventions:

- a multiply-add is two operations; matrix products are counted, and of the
  rest only the mixer's depthwise convolution (it is named in the layer's
  equations; 0.06% of a mixer); gates, norms, the softmax and the
  recurrence's exponentials are not;
- the selective scan is counted AS THE PROGRAM'S ALGORITHM DEFINES IT, in
  chunks of ``chunk_size`` Q: a position needs ``C·Bᵀ`` against the Q
  positions of its chunk (2·G·Q·N), the masked product with ``Δx``
  (2·H·Q·P), its share of the chunk's end state (2·H·P·N) and what the
  entering state adds (2·H·P·N). The masked products are counted WHOLE
  (Q keys a query, not (Q + 1) / 2): the mask is applied to a dense Q x Q
  tile, as the recurrence's own papers count it. A loop over time would
  need 2 x 2·H·P·N a position and no chunk terms: the count is of the
  chunked algorithm, not of a minimum over all algorithms;
- attention is counted CAUSAL: S (S + 1) / 2 of the S x S products;
- a training step is ``passes`` = 3 forward passes; recomputed operations
  do not count (the mixer runs its forward twice a step);
- bytes are counted for the grouped expert products (``experts_work``: two
  products, each pass reads its two operands and writes its result once in
  the compute dtype, rows = held pairs, weights = every held expert's) and
  for the scan (``ssm_work``: each pass reads x, B, C, Δ and writes y once).

``cfg`` is a configuration file's dict (``benchmark/configs/<name>.json``):
``n_routed_experts`` and ``vocab_size`` count what is HELD here,
``published`` what the model has.
"""

from __future__ import annotations


def _count(cfg, letter: str) -> int:
    return str(cfg["hybrid_override_pattern"]).count(letter)


def routed_layers(cfg) -> int:
    return _count(cfg, "E")


def pair_flops(cfg) -> int:
    """One relu2 expert on one token: two products."""
    return 2 * 2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _ssm_sizes(cfg):
    heads, width = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    return (heads, width, int(cfg["n_groups"]), int(cfg["ssm_state_size"]),
            int(cfg["chunk_size"]))


def scan_flops_per_token(cfg) -> int:
    """The chunked scan of one mixer, a position (see the module's
    conventions)."""
    h, p, g, n, q = _ssm_sizes(cfg)
    return 2 * g * q * n + 2 * h * q * p + 2 * 2 * h * p * n


def forward_parts(cfg, seq_len: int) -> dict:
    """Forward FLOPs a token needs, by part, for everything but the routed
    experts (which follow the pairs, not the tokens)."""
    d = int(cfg["hidden_size"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    h, p, g, n, _ = _ssm_sizes(cfg)
    d_in, conv = h * p, h * p + 2 * g * n
    n_ssm, n_attn, n_routed = (_count(cfg, c) for c in "M*E")
    shared = int(cfg.get("n_shared_experts", 0)) * int(
        cfg.get("moe_shared_expert_intermediate_size", 0))
    return {
        "ssm_proj": n_ssm * (2 * d * (d_in + conv + h) + 2 * d_in * d),
        "ssm_conv": n_ssm * 2 * int(cfg["conv_kernel"]) * conv,
        "ssm_scan": n_ssm * scan_flops_per_token(cfg),
        "attention_proj": n_attn * (2 * d * heads * hd * 2
                                    + 2 * d * kv * hd * 2),
        # S (S + 1) / 2 visible keys per sequence, QK^T and PV
        "attention_causal": n_attn * 2 * 2 * heads * hd * (seq_len + 1) / 2,
        "shared_ff": n_routed * 2 * 2 * d * shared,
        "router": n_routed * 2 * d * int(
            cfg["published"]["n_routed_experts"]),
        "head": 2 * d * int(cfg["vocab_size"]),
    }


def forward_flops_per_token(cfg, seq_len: int, pairs_per_token: float) -> float:
    """``pairs_per_token``: held pairs over tokens, summed over the routed
    layers (0.375 a layer when 8 of 128 experts are held and six are
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
    elements = 2 * passes * (pairs_held * (d + f) + weights)
    return {"flops": float(passes * pairs_held * pair_flops(cfg)),
            "bytes": float(dtype_bytes * elements)}


def ssm_work(cfg, tokens: int, passes: int = 3, dtype_bytes: int = 2) -> dict:
    """Needed FLOPs and bytes of the selective scans of one step (every
    mixer; the projections and the convolution around them are not the
    scan's): a pass reads x, B, C in the compute dtype and Δ in float32,
    and writes y."""
    h, p, g, n, _ = _ssm_sizes(cfg)
    elements = 2 * h * p + 2 * g * n
    return {"flops": float(passes * _count(cfg, "M") * tokens
                           * scan_flops_per_token(cfg)),
            "bytes": float(passes * _count(cfg, "M") * tokens
                           * (dtype_bytes * elements + 4 * h))}
