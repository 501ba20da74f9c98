"""The driver-facing bench output contract (round-5 fix).

The driver stores only a ~2,000-char stdout TAIL of ``bench.py`` and
parses its last line as the judged record. Round 4 emitted one large
JSON line with the headline keys FIRST, so the tail held the cut-off
END of the record and the driver parsed nothing (the round-4 driver
record: ``parsed: null``). These tests pin the fixed contract against the REAL
round-4 rehearsal record (committed at
``bench_records/bench_r04_rehearsal.json``): the compact summary must
carry the judged keys, fit comfortably inside the tail window, and be
the LAST stdout line ``_emit`` prints.
"""

import importlib.util
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def full_record():
    path = os.path.join(REPO, "bench_records", "bench_r04_rehearsal.json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def validator():
    spec = importlib.util.spec_from_file_location(
        "validate_metrics", os.path.join(REPO, "tools",
                                         "validate_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_summary_passes_schema_validator(bench, full_record, validator):
    """tools/validate_metrics.py is the one schema authority for the
    judged last line — a drift in _compact_summary (nested objects,
    missing judged keys, oversized line) fails tier-1 here instead of
    surfacing as a driver parse failure."""
    line = json.dumps(bench._compact_summary(full_record))
    assert validator.validate_bench_summary_line(line) == []
    # the watchdog/SIGTERM partial shape must validate too
    partial = json.dumps(bench._compact_summary(
        {"metric": "m", "value": None, "unit": "u", "vs_baseline": None,
         "partial": True, "sigterm": True}))
    assert validator.validate_bench_summary_line(partial) == []


def test_trial_record_metrics_snapshot_validates(bench, validator):
    """Streaming trial records embed the process-wide registry snapshot
    (obs.snapshot()); every entry must satisfy the metric schema the
    JSONL sink promises."""
    from tpudl import obs

    obs.counter("bench_contract.demo").inc(2)
    obs.histogram("bench_contract.lat").observe(0.5)
    snap = obs.snapshot()
    errs = [e for name, entry in snap.items()
            for e in validator.validate_metric_entry(name, entry)]
    assert errs == [], errs[:5]


def test_summary_fits_driver_tail(bench, full_record):
    s = bench._compact_summary(full_record)
    line = json.dumps(s)
    # the driver tail is ~2,000 chars; the contract budgets 1,500 so a
    # few trailing log lines can never push the summary out of it
    assert len(line) < 1500, f"summary line is {len(line)} chars"
    # nothing nested deeper than one list-of-scalars level
    for v in s.values():
        if isinstance(v, list):
            assert all(isinstance(x, (int, float)) for x in v)
        else:
            assert isinstance(v, (int, float, str, bool, type(None)))


def test_summary_carries_judged_keys(bench, full_record):
    s = bench._compact_summary(full_record)
    assert s["metric"] == full_record["metric"]
    assert s["value"] == full_record["value"]
    assert s["unit"] == full_record["unit"]
    assert s["vs_baseline"] == full_record["vs_baseline"]
    # the attribution fields the VERDICT asked for in the driver record
    assert s["wire_bound_images_per_sec"] == \
        full_record["wire_bound_images_per_sec"]
    assert s["mfu_device"] == \
        full_record["device_profile"]["mfu_device"]
    # per-trial evidence rides along, attributed per arm (ADVICE.md:
    # a merged list loses which arm each trial came from)
    assert s["streaming_prefetch_trials"] == \
        full_record["featurize_streaming"]["trials"]
    assert s["streaming_serial_trials"] == \
        full_record["featurize_streaming"]["serial_trials"]
    # sub-bench scalars present (field-name drift would break these)
    assert s["horovod_resnet50"] == \
        full_record["horovod_resnet50"]["step_per_sec"]
    assert s["predictor_resnet50"] == \
        full_record["predictor_resnet50"]["images_per_sec"]


def test_summary_tolerates_partial_record(bench):
    # the watchdog emits whatever was measured at the deadline: the
    # summary must not KeyError on a near-empty record
    s = bench._compact_summary({"metric": "m", "value": None,
                                "unit": "u", "vs_baseline": None,
                                "deadline_hit": True})
    assert s["deadline_hit"] is True
    assert s["value"] is None
    assert len(json.dumps(s)) < 1500


def test_emit_writes_full_record_and_prints_summary_last(
        bench, full_record, monkeypatch):
    monkeypatch.setenv("TPUDL_BENCH_RECORD_NAME", "contract_test")
    # reset the once-only latch (module may be shared across tests)
    bench._EMITTED.clear()
    rec_path = os.path.join(REPO, "bench_records", "contract_test.json")
    try:
        buf = io.StringIO()
        with redirect_stdout(buf):
            bench._emit(dict(full_record))
        lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
        last = json.loads(lines[-1])
        assert last["value"] == full_record["value"]
        assert len(lines[-1]) < 1500
        assert os.path.join(REPO, last["full_record"]) == rec_path
        with open(rec_path) as f:
            stored = json.load(f)
        assert stored["value"] == full_record["value"]
        assert stored["featurize_streaming"]["interleaved_pairs"]
        # second emit is a no-op (watchdog/main race discipline)
        buf2 = io.StringIO()
        with redirect_stdout(buf2):
            bench._emit({"metric": "x", "value": 1, "unit": "u",
                         "vs_baseline": None})
        assert buf2.getvalue() == ""
    finally:
        # never leave a fake record for the driver's end-of-round
        # commit to pick up (bench_records/ is a committed dir)
        if os.path.exists(rec_path):
            os.remove(rec_path)
        bench._EMITTED.clear()


def test_quick_run_under_tight_budget_emits_summary_last(tmp_path):
    """The round-6 budget contract: a QUICK run whose TPUDL_BENCH_BUDGET_S
    is already spent must SKIP every sub-bench, exit 0 fast, and still
    print a parseable compact summary (flagged partial) as the LAST
    stdout line — the failure mode this kills is the round-5 driver
    record's rc=124/parsed=null timeout."""
    import subprocess

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "TPUDL_BENCH_QUICK": "1",
        "TPUDL_BENCH_BUDGET_S": "0",       # budget spent at t=0
        "TPUDL_BENCH_STREAM_TRIALS": "0",
        "TPUDL_BENCH_SKIP_BASELINE": "1",
        "TPUDL_BENCH_RECORD_NAME": "contract_budget_test",
    })
    rec_path = os.path.join(REPO, "bench_records",
                            "contract_budget_test.json")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env, capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        assert lines, "bench printed nothing to stdout"
        s = json.loads(lines[-1])  # the driver's parse of the tail
        assert s["partial"] is True
        assert "value" in s and "metric" in s
        assert len(lines[-1]) < 1500
        with open(rec_path) as f:
            stored = json.load(f)
        assert stored["skipped_sub_benches"]  # budget skips are recorded
    finally:
        if os.path.exists(rec_path):
            os.remove(rec_path)


def test_sigterm_handler_flushes_partial_summary(bench, monkeypatch,
                                                 capsys, tmp_path):
    """SIGTERM (the driver's kill) must flush whatever has been measured
    as a valid last-line summary before exiting — AND leave a
    schema-valid flight-recorder dump next to it (ISSUE 5: the rc=124
    class must produce forensics, not just an stderr tail)."""
    monkeypatch.setenv("TPUDL_BENCH_RECORD_NAME", "contract_sigterm_test")
    monkeypatch.setenv("TPUDL_FLIGHT_DIR", str(tmp_path))
    rec_path = os.path.join(REPO, "bench_records",
                            "contract_sigterm_test.json")
    bench._EMITTED.clear()
    bench._EMIT_DONE.clear()
    exits = []
    monkeypatch.setattr(bench.os, "_exit", lambda code: exits.append(code))
    try:
        record = {"metric": "m", "unit": "u", "vs_baseline": None,
                  "compute_dtype": "bfloat16"}
        handler = bench._install_sigterm_flush(record)
        handler(15, None)
        out = capsys.readouterr().out.strip().splitlines()
        s = json.loads(out[-1])
        assert s["partial"] is True and s["sigterm"] is True
        assert s["value"] is None
        assert exits == [0]
        dumps = [p for p in os.listdir(tmp_path)
                 if p.startswith("tpudl-dump-")]
        assert len(dumps) == 1
        spec = importlib.util.spec_from_file_location(
            "validate_dump", os.path.join(REPO, "tools",
                                          "validate_dump.py"))
        vd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(vd)
        assert vd.validate_dump(str(tmp_path / dumps[0])) == []
    finally:
        import signal as _signal

        _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
        if os.path.exists(rec_path):
            os.remove(rec_path)
        bench._EMITTED.clear()


def test_emit_summary_survives_unserializable_record(bench, monkeypatch,
                                                     capsys):
    """The latch is set before the sinks run: a record a sub-bench
    polluted with a non-JSON value must still produce a parseable last
    line (numpy scalars via default=str; worse objects via the
    fallback summary)."""
    monkeypatch.setenv("TPUDL_BENCH_RECORD_NAME", "contract_test2")
    rec_path = os.path.join(REPO, "bench_records", "contract_test2.json")
    bench._EMITTED.clear()
    try:
        bench._emit({"metric": "m", "value": 1.5, "unit": "u",
                     "vs_baseline": None,
                     "weird": object()})  # not JSON-serializable
        out = capsys.readouterr().out.strip().splitlines()
        last = json.loads(out[-1])
        assert last["value"] == 1.5
    finally:
        if os.path.exists(rec_path):
            os.remove(rec_path)
        bench._EMITTED.clear()


def test_peak_flops_is_keyed_by_device_kind_and_unknown_is_an_error(bench):
    """A utilisation divided by another chip's peak is a wrong number:
    the table knows the v5e by jax's device_kind and raises for any
    kind it does not list (the CPU included)."""
    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(RuntimeError, match="no published peak"):
        bench.peak_flops("TPU v7x")
    with pytest.raises(RuntimeError, match="no published peak"):
        bench.peak_flops()  # the live backend here is the CPU
