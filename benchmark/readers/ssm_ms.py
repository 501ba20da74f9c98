"""Device milliseconds a step in the Mamba-2 mixers of a hybrid stack,
forward, the forward again a sequence at a time inside the backward, and
the backward: in-projection, causal convolution, the selective scan, gate,
grouped norm, out-projection. The median over the traced fit's steps of
the ``device.lm.ssm`` spans (``jax.named_scope("lm.ssm")``); nothing to
read in a stack without mixers."""

from benchmark.readers.experts_ms import scope_ms


def read(facts):
    return scope_ms(facts, "lm.ssm")
