"""Median duration of the traced fit's ``train.step.dispatch`` spans: the
call into the step program, which holds the argument transfer, the enqueue
and the wait for a free slot in the device's queue. Beside ``program_ms``
it says who sets the pace."""

from benchmark.readers.step_host_ms import traced_fit


def read(facts):
    fit = traced_fit(facts)
    return fit["dispatch_ns"] / 1e6 if fit else None
