"""Device milliseconds a step inside the routed experts' grouped products,
forward and backward: the median over the traced fit's steps of the
``device.moe.experts`` spans, which the program writes into its span ring
from the device trace's operations under ``jax.named_scope("moe.experts")``
(``tpudl.obs.trace.record_device_scopes``). The traced fit is found as
``step_host_ms`` finds it; the helpers here serve the other readers of
those spans."""

import statistics

from benchmark.readers.step_host_ms import traced_fit


def traced_children(facts):
    """The spans filed under the traced fit's ``train.fit`` span, by name;
    None without such a fit (no trace, a CPU rehearsal, a program that
    records no spans)."""
    fit = traced_fit(facts)
    if fit is None:
        return None
    from tpudl.obs import get_tracer

    out: dict = {}
    for s in get_tracer().spans():
        if s.parent == fit["fit"].id:
            out.setdefault(s.name, []).append(s)
    return out


def scope_ms(facts, scope):
    """Median device ms a step of one named scope, or None where the trace
    filed nothing under it."""
    spans = (traced_children(facts) or {}).get("device." + scope)
    if not spans:
        return None
    return statistics.median(s.dur_ns for s in spans) / 1e6 or None


def step_work(facts):
    """The needed work of a traced step as the adapter counted it
    (``lm.step_work``'s attributes), or None."""
    spans = (traced_children(facts) or {}).get("lm.step_work")
    return dict(spans[-1].attrs) if spans else None


def read(facts):
    return scope_ms(facts, "moe.experts")
