"""Compute-roof share of the cell's step program: the FLOPs one run needs
(``flops.py``, from the plain reference's shapes) over its median device
time, against the bf16 peak of every chip the program spans. The compute
roof only; a share with bytes needs per-operation byte counts."""

from benchmark import peaks


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("program_ms"):
        return None
    peak = peaks.peak(facts["device_kind"], "bf16_flops_per_s")
    return 100.0 * facts["flops_per_run"] / (
        trace["program_ms"] / 1e3 * peak * facts["devices"])
