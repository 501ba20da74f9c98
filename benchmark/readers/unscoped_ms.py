"""Device milliseconds a step that the device account files under no
scope the program declared and no kernel the adapter named: the median
over the traced fit's steps of the ``device.unscoped`` spans
(``tpudl.obs.trace.record_device_scopes``). What a later change can not be
judged on until it has a name; nothing to read from a program that keeps
no account."""

from benchmark.readers.experts_ms import scope_ms


def read(facts):
    return scope_ms(facts, "unscoped")
