"""Compute-roof share of a whole training step whose FLOPs depend on the
routing: what the traced steps needed (``flops_lm.step_flops`` from the
configuration's widths and the pairs really routed to held experts, on
``lm.step_work``) over the step program's median device time, against the
bf16 peak of every chip. ``program_mfu`` is the same share for a model whose
FLOPs the reference's shapes give."""

from benchmark import peaks
from benchmark.readers.experts_ms import step_work


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("program_ms"):
        return None
    work = step_work(facts)
    if not work:
        return None
    peak = peaks.peak(facts["device_kind"], "bf16_flops_per_s")
    return 100.0 * work["step_flops"] / (
        trace["program_ms"] / 1e3 * peak * facts["devices"])
