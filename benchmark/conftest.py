"""What adapter ``lm_train`` and the program leave in the span ring after a
traced fit, written by hand under the 50-step fit of ``tests/conftest.py``:
one ``lm.step_work`` span a step (the needed work) and one ``device.<scope>``
span a step and scope (device time from the trace's named scopes). The
readers ``step_mfu``, ``experts_ms``, ``experts_roofline_share`` and
``moe_route_ms`` read these."""

import pytest

EXPERTS_NS = 60_000_000       # + 10 i us at step i
ROUTE_NS = 9_000_000          # + 2 i us
WORK = {"tokens": 32768, "pairs_held": 131000, "pairs_total": 524288,
        "step_flops": 4.0e13, "experts_flops": 8.6e12,
        "experts_bytes": 1.1e10}


@pytest.fixture(autouse=True, scope="session")
def lm_train_spans(traced_fit_spans):
    """Through the tracer's public API, as children of the hand-written
    fit; the numbers the readers should find are returned."""
    from tpudl.obs import get_tracer
    from tpudl.obs.trace import traced_fit

    tracer = get_tracer()
    fit = traced_fit(tracer.spans(), traced_fit_spans["steps"])
    for i, step in enumerate(fit["steps"]):
        tracer.record("lm.step_work", step.start_ns, step.dur_ns,
                      parent=fit["fit"], **WORK)
        tracer.record("device.moe.experts", step.start_ns,
                      EXPERTS_NS + 10_000 * i, parent=fit["fit"], run=i)
        tracer.record("device.moe.route", step.start_ns,
                      ROUTE_NS + 2_000 * i, parent=fit["fit"], run=i)
    middle = (traced_fit_spans["steps"] - 1) / 2
    return {"experts_ms": (EXPERTS_NS + 10_000 * middle) / 1e6,
            "moe_route_ms": (ROUTE_NS + 2_000 * middle) / 1e6, **WORK}
