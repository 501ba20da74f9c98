"""A hand-written traced fit in the program's span ring, for the readers
that read spans (``step_host_ms``, ``dispatch_ms``, ``fit_start_ms``): the
synthetic facts of ``test_benchmark.py`` say ``program_runs: 50``, so the
ring holds one ``train.fit`` of 50 steps with known durations."""

import pytest

STEPS = 50
FIT_START_NS = 250_000_000    # the fit's start to its first step's
DISPATCH_NS = 39_500_000      # + i us at step i, so the median is not a mode
HOST_NS = 400_000             # + 2 i us: data 100 us, the loop's own rest


@pytest.fixture(autouse=True, scope="session")
def traced_fit_spans():
    """Written through the tracer's public API; the numbers the readers
    should find are returned."""
    from tpudl.obs import get_tracer

    tracer = get_tracer()
    t = 1_790_000_000_000_000_000
    total = FIT_START_NS + sum(
        DISPATCH_NS + HOST_NS + 3_000 * i for i in range(STEPS))
    fit = tracer.record("train.fit", t, total + 1_000_000, steps=STEPS)
    t += FIT_START_NS
    for i in range(STEPS):
        dispatch, host = DISPATCH_NS + 1_000 * i, HOST_NS + 2_000 * i
        step = tracer.record("train.step", t, dispatch + host, parent=fit,
                             step=i)
        tracer.record("train.step.data", t + 50_000, 100_000, parent=step)
        tracer.record("train.step.dispatch", t + 200_000, dispatch,
                      parent=step)
        t += dispatch + host
    middle = (STEPS - 1) / 2  # the median of an arithmetic sequence
    return {"steps": STEPS, "fit_start_ms": FIT_START_NS / 1e6,
            "dispatch_ms": (DISPATCH_NS + 1_000 * middle) / 1e6,
            "step_host_ms": (HOST_NS + 2_000 * middle) / 1e6}
