"""The readers that read the program's spans, on the hand-written fit of
``conftest.py``: they select the traced fit and divide; the medians are
the program's."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import common  # noqa: E402

READERS = ("step_host_ms", "dispatch_ms", "fit_start_ms")


def facts(runs):
    return {"trace": {"program_runs": runs, "program_ms": 40.0}}


@pytest.mark.parametrize("kind", READERS)
def test_reader_finds_the_traced_fit(kind, traced_fit_spans):
    read = common.load_reader(kind + ".train").read
    assert read(facts(traced_fit_spans["steps"])) == pytest.approx(
        traced_fit_spans[kind])


@pytest.mark.parametrize("kind", READERS)
def test_nothing_to_read_is_none(kind, traced_fit_spans):
    read = common.load_reader(kind + ".train").read
    assert read({"trace": None}) is None       # a CPU rehearsal
    assert read({}) is None
    assert read(facts(0)) is None              # a trace without the program
    assert read(facts(traced_fit_spans["steps"] + 1)) is None  # no such fit


def test_step_host_and_dispatch_make_up_the_step(traced_fit_spans):
    """host + dispatch is the step: what ISSUE 25 checks against the
    median step interval on the chip."""
    f = facts(traced_fit_spans["steps"])
    host = common.load_reader("step_host_ms.train").read(f)
    dispatch = common.load_reader("dispatch_ms.train").read(f)
    assert host + dispatch == pytest.approx(
        traced_fit_spans["step_host_ms"] + traced_fit_spans["dispatch_ms"])
    assert 0 < host < dispatch


def test_the_newest_matching_fit_wins(traced_fit_spans):
    """A later fit with the same count of steps is the traced one (the
    window's fit has another count and is passed over)."""
    from tpudl.obs import get_tracer

    tracer = get_tracer()
    t = 1_790_000_100_000_000_000
    for n, fit_start in ((7, 5_000_000), (3, 9_000_000), (7, 11_000_000)):
        fit = tracer.record("train.fit", t, 1_000_000_000)
        for i in range(n):
            step = tracer.record("train.step", t + fit_start + i * 1_000_000,
                                 1_000_000, parent=fit)
            tracer.record("train.step.dispatch", step.start_ns, 600_000,
                          parent=step)
        t += 2_000_000_000
    read = common.load_reader("fit_start_ms.train").read
    assert read(facts(7)) == pytest.approx(11.0)
    assert read(facts(3)) == pytest.approx(9.0)
    assert common.load_reader("step_host_ms.train").read(
        facts(7)) == pytest.approx(0.4)


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """Laid over the parent commit, whose tracer has no ``traced_fit``,
    the readers return None and do not raise."""
    import tpudl.obs.trace as T

    monkeypatch.delattr(T, "traced_fit")
    for kind in READERS:
        assert common.load_reader(kind + ".train").read(facts(50)) is None
