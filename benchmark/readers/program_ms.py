"""Median device time of one run of the cell's step program: its events on
the ``XLA Modules`` line of the first chip's plane, in the traced window."""


def read(facts):
    trace = facts.get("trace")
    return trace["program_ms"] if trace else None
