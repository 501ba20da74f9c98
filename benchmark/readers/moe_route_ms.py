"""Device milliseconds a step in the routed layers outside the experts'
products: scores, top-k, ordering the pairs, gathering rows and putting
results back under their weights (``jax.named_scope("moe.route")``), forward
and backward. The part with no FLOPs to speak of."""

from benchmark.readers.experts_ms import scope_ms


def read(facts):
    return scope_ms(facts, "moe.route")
