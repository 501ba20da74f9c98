"""What ``fit`` does before its first step: the traced fit's first
``train.step`` start minus its ``train.fit`` start (owning and placing the
parameters and the optimizer state, a restore)."""

from benchmark.readers.step_host_ms import traced_fit


def read(facts):
    fit = traced_fit(facts)
    return fit["start_ns"] / 1e6 if fit else None
