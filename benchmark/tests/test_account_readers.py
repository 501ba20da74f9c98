"""The four readers over the device account (ISSUE 36): ``attention_ms``
and ``ssm_ms`` read scopes the program has written since PR 28 and PR 32,
``update_ms`` and ``unscoped_ms`` what ``record_device_scopes`` adds on its
first call for a traced fit. The ring of ``benchmark/conftest.py`` holds
two scopes and is not this PR's to edit, so the spans these four read are
recorded here, under the same hand-written fit."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import common  # noqa: E402

# span, its duration at step 0 and what a step adds (so that the median is
# no mode), and whether the account wrote it (those carry ``ops``)
SPANS = {"attention_ms": ("device.lm.attention", 100_000_000, 30_000, None),
         "ssm_ms": ("device.lm.ssm", 500_000_000, 50_000, None),
         "update_ms": ("device.train.update", 17_000_000, 4_000, 52),
         "unscoped_ms": ("device.unscoped", 20_000_000, 6_000, 10_211)}
FACTS = {"trace": {"program_ms": 1000.0, "program_runs": 50},
         "device_kind": "TPU v5 lite", "devices": 1}


@pytest.fixture(scope="module")
def account_spans(traced_fit_spans):
    """Through the tracer's public API, as children of the hand-written
    fit, one a step and span; the medians the readers should find."""
    from tpudl.obs import get_tracer
    from tpudl.obs.trace import traced_fit

    tracer = get_tracer()
    fit = traced_fit(tracer.spans(), traced_fit_spans["steps"])
    for i, step in enumerate(fit["steps"]):
        tracer.record("device.step", step.start_ns, 1_000_000_000,
                      parent=fit["fit"], run=i)
        for name, base, more, ops in SPANS.values():
            attrs = {"run": i} if ops is None else {"run": i, "ops": ops}
            tracer.record(name, step.start_ns, base + more * i,
                          parent=fit["fit"], **attrs)
    middle = (traced_fit_spans["steps"] - 1) / 2
    return {kind: (base + more * middle) / 1e6
            for kind, (_, base, more, _) in SPANS.items()}


def read(kind, facts=FACTS):
    return common.load_reader(kind + ".moe_train").read(facts)


@pytest.mark.parametrize("kind", sorted(SPANS))
def test_reader_reads_the_median_of_its_span(kind, account_spans):
    value = read(kind)
    assert isinstance(value, float) and value == pytest.approx(
        account_spans[kind])
    assert 0 < value < FACTS["trace"]["program_ms"]


@pytest.mark.parametrize("kind", sorted(SPANS))
def test_nothing_to_read_is_none(kind, account_spans, monkeypatch):
    assert read(kind, {"trace": None}) is None      # a CPU rehearsal
    assert read(kind, {}) is None
    # a fit with no such spans under it: the window's, or a parent commit's,
    # whose program keeps no account
    assert read(kind, {**FACTS, "trace": {
        "program_ms": 1000.0, "program_runs": 51}}) is None
    import tpudl.obs.trace as T

    monkeypatch.delattr(T, "traced_fit")
    assert read(kind) is None


def test_a_span_of_no_length_is_left_out(account_spans):
    """A scope the account found nothing under in any step reads as
    nothing, not as 0 ms."""
    from tpudl.obs import get_tracer

    tracer = get_tracer()
    t = 1_790_000_300_000_000_000
    fit = tracer.record("train.fit", t, 1_000_000_000)
    for i in range(9):
        step = tracer.record("train.step", t + i * 1_000, 1_000, parent=fit)
        tracer.record("train.step.dispatch", step.start_ns, 500, parent=step)
        tracer.record("device.unscoped", step.start_ns, 0, parent=fit,
                      run=i, ops=0)
    facts = {**FACTS, "trace": {"program_ms": 1000.0, "program_runs": 9}}
    assert read("unscoped_ms", facts) is None
    assert read("update_ms", facts) is None


def test_the_manifest_lists_the_four_under_their_cells():
    manifest = common.load_json(os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json"))
    lm = [w["name"] for w in manifest["workloads"]
          if w["name"] != "resnet50-sgd-1chip"]
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in SPANS}
    assert list(mine) == [kind + ".moe_train" for kind in SPANS]
    for name, m in mine.items():
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "train_images_per_s")
        assert m["workloads"] == (
            ["nemotron-twotower-30b-a3b-ep16-train-8k"]
            if name == "ssm_ms.moe_train" else lm[:3])
    # the compiler's estimates of work are not read from the trace
    for kind in SPANS:
        with open(os.path.join(os.path.dirname(HERE), "readers",
                               kind + ".py")) as f:
            text = f.read()
        assert "flops" not in text and "bytes_accessed" not in text
