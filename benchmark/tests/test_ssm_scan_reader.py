"""``ssm_scan_roofline_share`` (ISSUE 38) over a ring of its own: a fit of
seven steps written here through the tracer's public API, with the
``lm.step_work`` and ``device.lm.ssm.scan`` spans adapter ``lm_train_hybrid``
leaves under a traced fit. The ring of ``benchmark/conftest.py`` holds no
scan and is not this PR's to edit."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import common, flops_hybrid, peaks  # noqa: E402

CELL = "nemotron-twotower-30b-a3b-ep16-train-8k"
NAME = "ssm_scan_roofline_share.moe_train"
STEPS = 7
SCAN_NS, MORE_NS = 40_000_000, 100_000       # 40 ms, + 0.1 ms a step
FACTS = {"trace": {"program_ms": 900.0, "program_runs": STEPS},
         "device_kind": "TPU v5 lite", "devices": 1}


@pytest.fixture(scope="module")
def cell_work():
    cfg = common.load_json(os.path.join(
        os.path.dirname(HERE), "configs",
        "nemotron-twotower-30b-a3b-ep16.json"))
    return flops_hybrid.ssm_work(cfg, 4 * 8192)


def _fit(t0, steps, work=None, scan_ns=None, more_ns=0):
    from tpudl.obs import get_tracer

    tracer = get_tracer()
    fit = tracer.record("train.fit", t0, 1_000_000_000 * steps)
    for i in range(steps):
        step = tracer.record("train.step", t0 + i * 1_000_000_000,
                             1_000_000_000, parent=fit)
        tracer.record("train.step.dispatch", step.start_ns, 500_000,
                      parent=step)
        if work is not None:
            tracer.record("lm.step_work", step.start_ns, step.dur_ns,
                          parent=fit, **work)
        if scan_ns is not None:
            tracer.record("device.lm.ssm.scan", step.start_ns,
                          scan_ns + more_ns * i, parent=fit, run=i)
    return fit


@pytest.fixture(scope="module")
def scan_spans(cell_work):
    work = {"tokens": 32768, "pairs_held": 25000, "pairs_total": 589824,
            "step_flops": 6.9e13, "experts_flops": 1.5e12,
            "experts_bytes": 2.9e9, "ssm_flops": cell_work["flops"],
            "ssm_bytes": cell_work["bytes"]}
    _fit(1_790_000_400_000_000_000, STEPS, work, SCAN_NS, MORE_NS)
    return (SCAN_NS + MORE_NS * (STEPS - 1) / 2) / 1e6


def read(facts=FACTS):
    return common.load_reader(NAME).read(facts)


def test_the_share_is_the_needed_works_least_time_over_the_scopes(
        scan_spans, cell_work):
    """The cell's work (1.0 TFLOP and 6.1 GB a step: the bytes bind) over
    the median span: 7.5 ms of 40.3 is 18.6%."""
    least_s = cell_work["bytes"] / peaks.peak("TPU v5 lite",
                                              "hbm_bytes_per_s")
    assert least_s > cell_work["flops"] / peaks.peak("TPU v5 lite",
                                                     "bf16_flops_per_s")
    assert least_s * 1e3 == pytest.approx(7.5, abs=0.1)
    value = read()
    assert isinstance(value, float)
    assert value == pytest.approx(100 * least_s * 1e3 / scan_spans)
    assert 15 < value < 25
    # two chips would have twice the roof
    assert read({**FACTS, "devices": 2}) == pytest.approx(value / 2)


def test_nothing_to_read_is_none(scan_spans, monkeypatch):
    assert read({"trace": None}) is None            # a CPU rehearsal
    assert read({}) is None
    # a fit with no such spans under it: the window's
    assert read({**FACTS, "trace": {"program_ms": 900.0,
                                    "program_runs": STEPS + 1}}) is None
    import tpudl.obs.trace as T

    monkeypatch.delattr(T, "traced_fit")
    assert read() is None


@pytest.mark.parametrize("steps, work, scan_ns", [
    # a stack without mixers: work without the scan's, no such scope
    (5, {"tokens": 32768, "experts_flops": 8.6e12, "experts_bytes": 1.1e10},
     None),
    # the scope with no work beside it (an adapter that counts none)
    (4, None, SCAN_NS),
    # a program from before PR 32 in the hybrid cell's adapter: the work,
    # and no span of the scan
    (3, {"tokens": 32768, "ssm_flops": 1.0e12, "ssm_bytes": 6.1e9}, None),
    # a span of no length is left out, not read as an infinite share
    (2, {"tokens": 32768, "ssm_flops": 1.0e12, "ssm_bytes": 6.1e9}, 0),
])
def test_a_fit_that_lacks_a_part_reads_none(steps, work, scan_ns):
    _fit(1_790_000_500_000_000_000 + steps * 10_000_000_000, steps, work,
         scan_ns)
    assert read({**FACTS, "trace": {"program_ms": 900.0,
                                    "program_runs": steps}}) is None


def test_the_manifest_lists_it_last_under_the_hybrid_cell_alone():
    manifest = common.load_json(os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json"))
    assert manifest["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "LM blocks",
        "moves": "train_images_per_s", "workloads": [CELL]}
    assert CELL in [w["name"] for w in manifest["workloads"]]
    # the needed work is the FLOP module's, never the trace's estimates
    with open(os.path.join(os.path.dirname(HERE), "readers",
                           "ssm_scan_roofline_share.py")) as f:
        text = f.read()
    assert "bytes_accessed" not in text and "ssm_work" in text
    # the adapter asks the trace for the scope this reads
    adapter = common.load_module(
        os.path.join(os.path.dirname(HERE), "adapters",
                     "lm_train_hybrid.py"), "adapter_for_the_scan_reader")
    assert "lm.ssm.scan" in adapter.INNER_SCOPES
