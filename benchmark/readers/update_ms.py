"""Device milliseconds a step in the optimizer's update and its addition
to the parameters (``jax.named_scope("train.update")`` in
``tpudl.train.make_train_step``): AdamW over every leaf, which no model
code runs and which moves 28 bytes a parameter. The median over the traced
fit's steps of the ``device.train.update`` spans, which the device account
(``tpudl.obs.trace.record_device_scopes``) writes for the scopes the
program declares beside those an adapter asks for; nothing to read from a
program that declares none."""

from benchmark.readers.experts_ms import scope_ms


def read(facts):
    return scope_ms(facts, "train.update")
