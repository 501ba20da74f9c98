"""Roofline share of the routed experts' grouped products: the least time
the chips could take for the work the traced steps needed (the larger of
needed FLOPs over the bf16 peak and needed bytes over the HBM peak; both
from ``flops_lm.experts_work`` on the pairs really routed to held experts,
as the adapter wrote them on ``lm.step_work``) over ``experts_ms``. The same
work whatever implements the products; at the published widths the compute
roof binds."""

from benchmark import peaks
from benchmark.readers.experts_ms import scope_ms, step_work


def read(facts):
    ms, work = scope_ms(facts, "moe.experts"), step_work(facts)
    if not ms or not work:
        return None
    kind, chips = facts["device_kind"], facts["devices"]
    least_s = max(work["experts_flops"] / peaks.peak(kind, "bf16_flops_per_s"),
                  work["experts_bytes"] / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / chips / (ms / 1e3)
