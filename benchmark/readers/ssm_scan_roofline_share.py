"""Roofline share of the selective scans of a hybrid stack's mixers: the
least time the chips could take for the work the traced steps needed (the
larger of needed FLOPs over the bf16 peak and needed bytes over the HBM
peak; both from ``flops_hybrid.ssm_work``, the chunked algorithm's products
and a pass's reads of x, B, C, Δ and write of y, as the adapter wrote them
on ``lm.step_work``) over the median ``device.lm.ssm.scan``: the scope
around the scan alone, forward, the forward again inside the backward, and
the backward. The same work whatever implements the scan; at the published
widths the bytes bind. Nothing to read in a stack without mixers, or on a
program that writes no such span."""

from benchmark import peaks
from benchmark.readers.experts_ms import scope_ms, step_work


def read(facts):
    ms, work = scope_ms(facts, "lm.ssm.scan"), step_work(facts)
    if not ms or not work or not work.get("ssm_flops"):
        return None
    kind, chips = facts["device_kind"], facts["devices"]
    least_s = max(work["ssm_flops"] / peaks.peak(kind, "bf16_flops_per_s"),
                  work["ssm_bytes"] / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / chips / (ms / 1e3)
