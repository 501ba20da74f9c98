"""Bench regression sentinel (tools/bench_sentinel.py).

ISSUE 6 acceptance: exits nonzero on a synthetic 30% regression, exits
zero on wire-noise-only deltas (value tracks the round's own wire
probes), and scores a partial rc=124 round on exactly the sub-benches
that completed (the round-5 shape).
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_sentinel",
        os.path.join(REPO, "tools", "bench_sentinel.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bs = _load()


def _round_payload(n, *, wire=17.0, scale=1.0, device=7470.0,
                   partial_keys=None):
    """One driver-shaped BENCH_rNN payload. Wire-sensitive throughput
    values are ``nominal_per_mbps × wire × scale`` so ``scale=1.0``
    rounds are EXACTLY wire-proportional (pure link-speed change) and
    ``scale=0.7`` is a genuine 30% normalized regression."""
    parsed = {
        "metric": "images/sec/chip", "unit": "images/sec/chip",
        "value": round(28.0 * wire * scale, 1),
        "h2d_mb_per_sec": wire,
        "horovod_resnet50": round(0.12 * wire * scale, 3),
        "predictor_resnet50": round(9.0 * wire * scale, 1),
        "keras_transformer_mlp": round(1500.0 * wire * scale, 1),
        "estimator_inception": round(0.005 * wire * scale, 4),
        "device_profile": {"device_images_per_sec": device},
        "decode": {"native_images_per_sec": 285.0},
        "tf_cpu_baseline_images_per_sec": 6.2,
    }
    if partial_keys:
        for k in partial_keys:
            parsed.pop(k, None)
    return {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": parsed}


def _write_history(tmp_path, rounds):
    paths = []
    for payload in rounds:
        p = tmp_path / f"BENCH_r{payload['n']:02d}.json"
        p.write_text(json.dumps(payload))
        paths.append(str(p))
    return paths


# wire values per round — the real history's 8–22 MB/s swing
WIRES = [22.0, 17.0, 10.0, 8.0]


class TestVerdicts:
    def test_wire_noise_only_passes_rc0(self, tmp_path):
        """Raw values swing 2.75× across rounds but track the wire
        exactly — the sentinel must NOT call that a regression."""
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES + [9.0])]
        _write_history(tmp_path, rounds)
        result = bs.evaluate_files([str(tmp_path)])
        assert result["verdict"] == "ok" and result["rc"] == 0
        assert result["regressed"] == []
        hv = result["metrics"]["headline_images_per_sec"]
        assert hv["verdict"] == "ok" and hv["wire_normalized"]
        assert abs(hv["delta_pct"]) < 1.0  # perfectly wire-tracked

    def test_30pct_regression_flagged_rc2(self, tmp_path):
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        rounds.append(_round_payload(5, wire=9.0, scale=0.70))
        _write_history(tmp_path, rounds)
        result = bs.evaluate_files([str(tmp_path)])
        assert result["verdict"] == "regress" and result["rc"] == 2
        assert "headline_images_per_sec" in result["regressed"]
        hv = result["metrics"]["headline_images_per_sec"]
        assert hv["verdict"] == "regress"
        assert hv["delta_pct"] == pytest.approx(-30.0, abs=1.0)

    def test_device_regression_has_tight_band(self, tmp_path):
        """The chip-side number does not ride the link: a 10% drop there
        regresses even though wire metrics would shrug it off."""
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        rounds.append(_round_payload(5, wire=8.0, device=6700.0))
        _write_history(tmp_path, rounds)
        result = bs.evaluate_files([str(tmp_path)])
        assert "device_images_per_sec" in result["regressed"]
        assert result["rc"] == 2

    def test_improvement_reported_not_fatal(self, tmp_path):
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        rounds.append(_round_payload(5, wire=9.0, scale=1.8))
        _write_history(tmp_path, rounds)
        result = bs.evaluate_files([str(tmp_path)])
        assert result["rc"] == 0
        assert "headline_images_per_sec" in result["improved"]

    def test_single_round_insufficient(self, tmp_path):
        _write_history(tmp_path, [_round_payload(1)])
        result = bs.evaluate_files([str(tmp_path)])
        assert result["verdict"] == "insufficient"
        assert result["rc"] == 0  # nothing to fail against

    def test_no_input_rc1(self, tmp_path):
        result = bs.evaluate_files([str(tmp_path)])
        assert result["rc"] == 1


class TestPartialRounds:
    def test_rc124_round_scored_from_tail(self, tmp_path):
        """The round-5 shape: parsed=null, rc=124, stderr tail only.
        The completed sub-benches (horovod, predictor, MLP, compute,
        device profile — plus bracketing wire probes) are recovered
        and scored; the rest are skipped, not failed."""
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        tail = (
            "compute-only featurize: 256x8 images in 0.40s -> 5144.1 "
            "images/sec/chip (input device-resident)\n"
            "wire bandwidth (64 MB buffer): H2D 8 MB/s, D2H 10 MB/s\n"
            "device-profile featurize: 34.26 ms/step on-device -> 7471 "
            "img/s (batch=256, dispatch-free)\n"
            "wire bandwidth (8 MB buffer): H2D 10 MB/s, D2H 12 MB/s\n"
            "HorovodRunner ResNet50: 0.41 steps/sec (25.9 images/sec, "
            "batch 64)\n"
            "wire bandwidth (8 MB buffer): H2D 10 MB/s, D2H 7 MB/s\n"
            "DeepImagePredictor ResNet50: 512 images in 5.71s -> 89.6 "
            "images/sec/chip\n"
            "KerasTransformer MLP: 65536 rows in 4.08s -> 16045 "
            "rows/sec\n")
        rounds.append({"n": 5, "cmd": "python bench.py", "rc": 124,
                       "tail": tail, "parsed": None})
        _write_history(tmp_path, rounds)
        loaded = bs.load_history([str(tmp_path)])
        last = loaded[-1]
        assert last["partial"] is True
        assert last["wire_mbps"] == 10.0  # median of 8/10/10
        assert last["metrics"]["horovod_resnet50_step_per_sec"] == 0.41
        assert last["metrics"]["device_images_per_sec"] == 7471.0
        result = bs.evaluate_rounds(loaded)
        # completed sub-benches scored; missing ones skipped
        assert result["metrics"]["device_images_per_sec"]["verdict"] \
            in ("ok", "improve", "regress")
        assert result["metrics"]["headline_images_per_sec"]["verdict"] \
            == "skipped"
        assert result["latest_partial"] is True


class TestLiveRecordHook:
    def test_sentinel_for_record(self, tmp_path):
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        _write_history(tmp_path, rounds)
        live = dict(_round_payload(99, wire=9.0, scale=0.65)["parsed"])
        result = bs.sentinel_for_record(live, [str(tmp_path)])
        assert result["verdict"] == "regress"
        assert bs.summary_token(result).startswith("regress:")
        ok = dict(_round_payload(99, wire=9.0)["parsed"])
        result = bs.sentinel_for_record(ok, [str(tmp_path)])
        assert result["verdict"] == "ok"
        assert bs.summary_token(result) == "ok"

    def test_empty_record_insufficient(self, tmp_path):
        result = bs.sentinel_for_record({"metric": "x"},
                                        [str(tmp_path)])
        assert result["rc"] == 1


class TestCLI:
    def test_cli_rc_contract(self, tmp_path, capsys):
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        rounds.append(_round_payload(5, wire=9.0, scale=0.7))
        _write_history(tmp_path, rounds)
        rc = bs.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 2
        assert "regress" in out and "headline_images_per_sec" in out

    def test_cli_json_and_threshold_override(self, tmp_path, capsys):
        rounds = [_round_payload(i + 1, wire=w)
                  for i, w in enumerate(WIRES)]
        rounds.append(_round_payload(5, wire=9.0, scale=0.9))
        _write_history(tmp_path, rounds)
        # default bands absorb a 10% normalized dip ...
        assert bs.main([str(tmp_path)]) == 0
        capsys.readouterr()  # drain the text report
        # ... an explicit 5% threshold does not
        rc = bs.main([str(tmp_path), "--threshold", "0.05", "--json"])
        assert rc == 2
        assert json.loads(capsys.readouterr().out)["verdict"] \
            == "regress"
