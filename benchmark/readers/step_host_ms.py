"""What the trainer's loop itself costs per step: over the traced fit's
steps, the median of the ``train.step`` span's duration less its
``train.step.dispatch`` child (data, placement, bookkeeping), from the
program's span ring. The traced fit is the newest ``train.fit`` span with
as many ``train.step`` children as the trace has program runs; the
arithmetic is the program's (``tpudl.obs.trace.traced_fit``)."""


def traced_fit(facts):
    """The traced fit in numbers, or None: without a trace (every CPU
    rehearsal), without such a fit in the ring, or on a program that
    records no spans in ``fit``."""
    trace = facts.get("trace")
    if not trace or not trace.get("program_runs"):
        return None
    try:
        from tpudl.obs import get_tracer
        from tpudl.obs.trace import traced_fit as reduce
    except ImportError:
        return None
    return reduce(get_tracer().spans(), trace["program_runs"])


def read(facts):
    fit = traced_fit(facts)
    return fit["step_host_ns"] / 1e6 if fit else None
