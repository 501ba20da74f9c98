"""Device milliseconds a step in the attention operator, whichever the
stack has (grouped-query heads with or without head norms and a rotation,
or latent attention): projections, norms, the rotation, the flash kernels
forward and backward, the out-projection. The median over the traced
fit's steps of the ``device.lm.attention`` spans, which the program writes
from the trace's operations under ``jax.named_scope("lm.attention")``."""

from benchmark.readers.experts_ms import scope_ms


def read(facts):
    return scope_ms(facts, "lm.attention")
