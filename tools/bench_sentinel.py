#!/usr/bin/env python
"""Bench regression sentinel: wire-normalized round-over-round verdicts.

A bench history (``BENCH_r*.json``) taken over a host→device wire whose
speed differs BETWEEN rounds (the July 2026 rounds swung 8–22 MB/s) is
noisy in a very specific way: every device-facing throughput number
rides the wire — a 2× drop in ``predictor_resnet50`` img/s across
rounds is the link, not a code regression, whenever the round's own
bracketing wire probes dropped 2× too. Raw thresholds therefore cannot
distinguish "the change made it worse" from "the wire was slower that
round". This sentinel can:

1. **Parse** each round file — the driver's ``{n, rc, tail, parsed}``
   shape, or a full/compact bench record directly (``bench_records/``).
   Rounds whose ``parsed`` is null (round 4's tail-truncation, round
   5's rc=124 external timeout) are RECOVERED from the stderr/stdout
   tail: the log-line and flat-JSON regexes below score exactly the
   sub-benches that completed, so a partial round still contributes
   history instead of a hole.
2. **Normalize** wire-sensitive metrics by the round's own wire
   measurement (median of every H2D probe the record carries) —
   img/s-per-(MB/s) is the quantity that should be stable across link
   speeds.
3. **Classify** the latest round against the median of the prior
   rounds, per metric: ``regress`` / ``improve`` / ``ok`` (noise band =
   the larger of the metric's floor threshold and the history's own
   spread), ``no_history`` / ``skipped`` when either side is missing.

Importable (``from bench_sentinel import evaluate_files,
sentinel_for_record``) and runnable::

    python tools/bench_sentinel.py <dir-or-round-files...> [--json]

Exit codes: 0 = pass (ok/improve/insufficient history), 2 = at least
one metric regressed beyond its noise band, 1 = no scorable input.
``bench.py`` runs this at the end of every round over whatever round
records sit beside it and puts the verdict on the judged summary line.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

__all__ = ["Metric", "METRICS", "load_round", "load_history",
           "evaluate_rounds", "evaluate_files", "sentinel_for_record",
           "extract_metrics", "extract_wire_mbps", "format_report"]


class Metric:
    """One judged number: where it lives in a parsed record, how to
    recover it from a bare round tail, and how noisy it is allowed to
    be. ``wire_sensitive`` metrics are scored per-MB/s of the round's
    own wire; metrics are higher-is-better (seconds-shaped fields are
    inverted into rates upstream) unless ``lower_is_better`` flips the
    verdicts (latency-shaped figures that read wrong inverted)."""

    def __init__(self, name: str, *, keys, tail_patterns=(),
                 wire_sensitive: bool = False, floor: float = 0.15,
                 lower_is_better: bool = False):
        self.name = name
        self.keys = keys  # [(record_key, subfield-or-None), ...]
        self.tail_patterns = [re.compile(p) for p in tail_patterns]
        self.wire_sensitive = wire_sensitive
        self.floor = floor  # minimum relative noise band
        self.lower_is_better = lower_is_better

    def from_record(self, record: dict):
        for key, field in self.keys:
            v = record.get(key)
            if isinstance(v, dict):
                v = v.get(field) if field else None
            elif field is not None and not isinstance(v, (int, float)):
                v = None
            if isinstance(v, (int, float)) and v > 0:
                return float(v)
        return None

    def from_tail(self, tail: str):
        for pat in self.tail_patterns:
            hits = pat.findall(tail)
            if hits:
                try:
                    return float(hits[-1].replace(",", ""))
                except ValueError:
                    continue
        return None


_NUM = r"([\d,]+(?:\.\d+)?)"

METRICS = [
    # the judged headline (DeepImageFeaturizer InceptionV3 img/s/chip)
    Metric("headline_images_per_sec",
           keys=[("value", None)],
           tail_patterns=[r'"value": ' + _NUM],
           wire_sensitive=True, floor=0.20),
    Metric("horovod_resnet50_step_per_sec",
           keys=[("horovod_resnet50", "step_per_sec")],
           tail_patterns=[r"HorovodRunner ResNet50: " + _NUM
                          + r" steps/sec",
                          r'"step_per_sec": ' + _NUM],
           wire_sensitive=True, floor=0.20),
    Metric("predictor_resnet50_images_per_sec",
           keys=[("predictor_resnet50", "images_per_sec")],
           tail_patterns=[r"DeepImagePredictor ResNet50: .*?-> " + _NUM
                          + r" images/sec"],
           wire_sensitive=True, floor=0.20),
    Metric("keras_transformer_rows_per_sec",
           keys=[("keras_transformer_mlp", "rows_per_sec")],
           tail_patterns=[r"KerasTransformer MLP: .*?-> " + _NUM
                          + r" rows/sec",
                          r'"rows_per_sec": ' + _NUM],
           wire_sensitive=True, floor=0.20),
    Metric("estimator_inception_step_per_sec",
           keys=[("estimator_inception", "step_per_sec")],
           wire_sensitive=True, floor=0.20),
    # dispatch-latency-shaped, but carries no per-step wire payload:
    # scored raw with a wide band (dispatch latency varies run to run)
    Metric("compute_only_images_per_sec",
           keys=[("compute_only_images_per_sec", None)],
           tail_patterns=[r"compute-only featurize: .*?-> " + _NUM
                          + r" images/sec"],
           wire_sensitive=False, floor=0.60),
    # the chip-side truth: dispatch-free, wire-free — tight band; a
    # drop HERE is a compiled-program regression, never the link
    Metric("device_images_per_sec",
           keys=[("device_profile", "device_images_per_sec")],
           tail_patterns=[r"device-profile featurize: .*?-> " + _NUM
                          + r" img/s",
                          r'"device_images_per_sec": ' + _NUM],
           wire_sensitive=False, floor=0.05),
    # async-dispatch A/B: both are within-round ratios (depth-D over
    # blocking; share of dispatch seconds the window hid), so the wire
    # largely cancels — scored raw with a moderate band. A drop here is
    # the in-flight window failing to overlap round-trips: an executor
    # regression, flagged like the wire metrics
    Metric("async_speedup",
           keys=[("async_dispatch", "async_speedup")],
           tail_patterns=[r'"async_speedup": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    Metric("dispatch_overlap_pct",
           keys=[("async_dispatch", "dispatch_overlap_pct")],
           tail_patterns=[r'"dispatch_overlap_pct": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # device-cache: a within-round ratio (epoch-2 HBM-resident over
    # epoch-1 cold, same program/rows) — scored raw like async_speedup.
    # A drop is residency regressing (hits falling back to the wire:
    # key churn, budget mis-accounting, donation fallback copies) — an
    # executor/cache regression, never the link. (hbm_epoch2_bytes_
    # shipped also rides the judged line as the hard zero-wire claim
    # but is an exact-0 contract, not a banded rate.)
    Metric("hbm_warm_speedup",
           keys=[("device_cache", "hbm_warm_speedup")],
           tail_patterns=[r'"hbm_warm_speedup": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # cold start: a within-round ratio (empty-program-store first-
    # result over warmed-store first-result, identical child program,
    # persistent XLA cache disabled in both arms) — scored raw like
    # async_speedup. A drop means the AOT store stopped restoring
    # (serialize/deserialize breakage, fingerprint churn re-keying
    # every process, manifest corruption) — a compile-subsystem
    # regression, never the link.
    Metric("cold_start_speedup",
           keys=[("cold_start", "cold_start_speedup")],
           tail_patterns=[r'"cold_start_speedup": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # fault-recovery: a within-round ratio (clean wall over
    # recovered-from-one-injected-fault wall, same program/rows — the
    # higher-is-better twin of degraded_recovery_overhead_pct on the
    # judged line) — scored raw like async_speedup. A drop is recovery
    # getting more expensive (extra attempts, a deeper rung than the
    # fault needs, lost warm state across the retry) — a supervisor
    # regression, never the link
    Metric("fault_recovery_efficiency",
           keys=[("fault_recovery", "fault_recovery_efficiency")],
           tail_patterns=[r'"fault_recovery_efficiency": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # mesh-scaling: a within-round ratio (sharded executor over the
    # single-chip fast path on the virtual 8-device CPU mesh, same
    # program/rows) — no wire in the loop; scored raw like
    # async_speedup. A drop is the mesh path re-growing overhead
    # (blocking transfers, lost fusion/window) — an executor
    # regression, never the link. (mesh_pad_overhead_pct also rides the
    # judged line but is lower-is-better waste, so it is not banded.)
    Metric("mesh_parallel_efficiency",
           keys=[("mesh_scaling", "mesh_parallel_efficiency")],
           tail_patterns=[r'"mesh_parallel_efficiency": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # 2-D twin (ISSUE 16): 4x2 tensor-parallel over 8x1 data-parallel,
    # one Megatron-shaped program, interleaved in one child — a drop is
    # the model axis re-growing overhead (gathered params, lost
    # residency, extra collectives), never the link
    Metric("mesh2d_parallel_efficiency",
           keys=[("mesh_2d", "mesh2d_parallel_efficiency")],
           tail_patterns=[r'"mesh2d_parallel_efficiency": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # host-side stages: no wire in the loop
    Metric("decode_native_images_per_sec",
           keys=[("decode", "native_images_per_sec")],
           tail_patterns=[r'"native_images_per_sec": ' + _NUM],
           wire_sensitive=False, floor=0.25),
    Metric("tf_cpu_baseline_images_per_sec",
           keys=[("tf_cpu_baseline_images_per_sec", None)],
           tail_patterns=[r"TF-CPU baseline median of \d+: " + _NUM
                          + r" images/sec",
                          r'"tf_cpu_baseline_images_per_sec": ' + _NUM],
           wire_sensitive=False, floor=0.25),
    # serve plane (ISSUE 17): closed-loop continuous batching in one
    # CPU child — no wire in the loop; scored raw like async_speedup.
    # A QPS drop is the serve loop re-growing per-tick overhead
    # (lost slot batching, retraces on admission, queue stalls) — a
    # serving regression, never the link.
    Metric("serve_sustained_qps",
           keys=[("serve", "sustained_qps")],
           tail_patterns=[r'"sustained_qps": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # p99 end-to-end latency under the same closed loop: latency reads
    # wrong inverted into a rate, so it is banded lower-is-better
    Metric("serve_p99_ms",
           keys=[("serve", "p99_ms")],
           tail_patterns=[r'"p99_ms": ' + _NUM],
           wire_sensitive=False, floor=0.30, lower_is_better=True),
    # warm TTFT (program store restored before the first request): a
    # rise means registration stopped warm-starting from the store —
    # the TTFT = deserialization contract regressing
    Metric("serve_warm_ttft_s",
           keys=[("serve", "warm_ttft_s")],
           tail_patterns=[r'"warm_ttft_s": ' + _NUM],
           wire_sensitive=False, floor=0.30, lower_is_better=True),
    # windowed p99 from the SLO engine (ISSUE 18): the same closed
    # loop read through the recent-window plane instead of lifetime
    # tallies — a rise with a flat serve_p99_ms means the WINDOW math
    # (or the trace stamps feeding it) regressed, not the serving
    Metric("serve_slo_window_p99_ms",
           keys=[("serve", "slo_window_p99_ms")],
           tail_patterns=[r'"slo_window_p99_ms": ' + _NUM],
           wire_sensitive=False, floor=0.30, lower_is_better=True),
    # text plane (ISSUE 19): tokens/s through the tokenized pipeline.
    # lm_train's judged arm is the WARM epoch — tokenize + wire paid
    # in epoch 1, epoch 2 replays HBM-resident packed batches — so the
    # rate is compute-shaped, not wire-shaped; scored raw
    Metric("lm_train_tokens_per_sec",
           keys=[("lm_train", "lm_train_tokens_per_sec")],
           tail_patterns=[r'"lm_train_tokens_per_sec": ' + _NUM],
           wire_sensitive=False, floor=0.30),
    # generated tokens/s over a ragged prompt column on warmed bucket-
    # ladder programs: decode-loop-shaped, no per-token wire payload
    Metric("lm_generate_tokens_per_sec",
           keys=[("lm_generate", "lm_generate_tokens_per_sec")],
           tail_patterns=[r'"lm_generate_tokens_per_sec": ' + _NUM],
           wire_sensitive=False, floor=0.30),
]

# every H2D figure a round can carry, in preference-free union (the
# round's wire is the MEDIAN of all probes — one early probe on a
# drifting link must not speak for the whole round)
_WIRE_TAIL = [re.compile(r"H2D " + _NUM + r" MB/s"),
              re.compile(r'"h2d_mb_per_sec(?:_pre|_post)?": ' + _NUM)]


def extract_wire_mbps(record: dict | None, tail: str = ""):
    """The round's wire figure: median over every H2D probe found in
    the parsed record and/or the tail. None = round carried no probe
    (wire-sensitive metrics are then scored raw)."""
    vals: list[float] = []

    def _walk(obj):
        if isinstance(obj, dict):
            for k, v in obj.items():
                if (isinstance(v, (int, float)) and v > 0
                        and k.startswith("h2d_mb_per_sec")):
                    vals.append(float(v))
                else:
                    _walk(v)
        elif isinstance(obj, list):
            for v in obj:
                _walk(v)

    if record:
        _walk(record)
    for pat in _WIRE_TAIL:
        for hit in pat.findall(tail or ""):
            try:
                vals.append(float(hit.replace(",", "")))
            except ValueError:
                pass
    return round(statistics.median(vals), 2) if vals else None


def extract_metrics(record: dict | None, tail: str = "") -> dict:
    """{metric name: raw value} for whatever the round completed."""
    out = {}
    for m in METRICS:
        v = m.from_record(record) if record else None
        if v is None and tail:
            v = m.from_tail(tail)
        if v is not None:
            out[m.name] = v
    return out


def load_round(path: str) -> dict | None:
    """One round file → ``{round, rc, partial, wire_mbps, metrics}``.

    Accepts the driver's ``{n, cmd, rc, tail, parsed}`` shape AND a
    bare bench record (full or compact — anything with a ``value`` /
    ``metric`` key). Returns None when nothing scorable was found."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(payload, dict):
        return None
    if "tail" in payload or "parsed" in payload:
        record = payload.get("parsed")
        tail = payload.get("tail") or ""
        rc = payload.get("rc")
        n = payload.get("n")
    else:  # a bench record directly (bench_records/*.json)
        record, tail, rc = payload, "", 0
        n = None
    metrics = extract_metrics(record, tail)
    if not metrics:
        return None
    return {
        "path": os.path.basename(path),
        "round": n,
        "rc": rc,
        # rc=124 (external timeout) or an unparsed summary = the round
        # is PARTIAL: only the sub-benches that completed get scored
        "partial": bool(rc not in (0, None) or record is None
                        or (record or {}).get("partial")),
        "wire_mbps": extract_wire_mbps(record, tail),
        "metrics": metrics,
    }


def _round_sort_key(path: str):
    m = re.search(r"r(\d+)", os.path.basename(path))
    return (int(m.group(1)) if m else 1 << 30, os.path.basename(path))


def load_history(paths) -> list[dict]:
    """Round files (or directories holding ``BENCH_r*.json``) →
    ordered scorable rounds."""
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            # both the driver's BENCH_rNN.json wrappers and the bare
            # records bench.py leaves under bench_records/ (lowercase
            # bench_rNN_*.json) count as history
            hits = (glob.glob(os.path.join(p, "BENCH_r*.json"))
                    + glob.glob(os.path.join(p, "bench_r*.json")))
            files.extend(sorted(set(hits), key=_round_sort_key))
        else:
            files.append(p)
    rounds = []
    for f in files:
        r = load_round(f)
        if r is not None:
            rounds.append(r)
    return rounds


def _normalized(rnd: dict, metric: Metric, use_wire: bool):
    v = rnd["metrics"].get(metric.name)
    if v is None:
        return None
    if use_wire:
        if not rnd.get("wire_mbps"):
            return None  # unit-incomparable with normalized rounds
        return v / rnd["wire_mbps"]
    return v


def evaluate_rounds(rounds: list[dict],
                    threshold: float | None = None) -> dict:
    """Classify the LAST round against the ones before it.

    Per metric: ``value`` (raw), ``normalized`` (per-MB/s for
    wire-sensitive metrics when the round measured its wire),
    ``baseline`` (median of prior rounds' normalized values),
    ``delta_pct``, ``band_pct`` (noise band actually applied), and
    ``verdict`` in {regress, improve, ok, no_history, skipped}.

    The band is ``max(metric floor, 1.25 × the history's own relative
    spread)`` — a metric whose history already swings ±40% cannot flag
    a 30% move, while a dead-stable one can. ``threshold`` overrides
    every floor (the CLI's --threshold).
    """
    if not rounds:
        return {"verdict": "insufficient", "rc": 1, "metrics": {},
                "regressed": [], "improved": [],
                "reason": "no scorable rounds"}
    latest, history = rounds[-1], rounds[:-1]
    if not history:
        return {"verdict": "insufficient", "rc": 0, "metrics": {},
                "regressed": [], "improved": [],
                "latest": latest.get("path"),
                "reason": "one round only — nothing to compare against"}
    per: dict[str, dict] = {}
    regressed, improved = [], []
    for m in METRICS:
        raw = latest["metrics"].get(m.name)
        entry: dict = {"value": raw, "wire_sensitive": m.wire_sensitive}
        if raw is None:
            entry["verdict"] = "skipped"
            entry["reason"] = ("sub-bench absent from the latest round"
                               + (" (partial)" if latest.get("partial")
                                  else ""))
            per[m.name] = entry
            continue
        # wire normalization applies only when the latest round AND at
        # least one history round measured their wire — per-MB/s and
        # raw values are different units and must never share a median
        use_wire = bool(
            m.wire_sensitive and latest.get("wire_mbps")
            and any(r.get("wire_mbps")
                    and r["metrics"].get(m.name) is not None
                    for r in history))
        hist = [nv for r in history
                if (nv := _normalized(r, m, use_wire)) is not None]
        nv = _normalized(latest, m, use_wire)
        entry["normalized"] = round(nv, 4) if nv is not None else None
        entry["wire_normalized"] = use_wire
        if not hist:
            entry["verdict"] = "no_history"
            per[m.name] = entry
            continue
        base = statistics.median(hist)
        spread = ((max(hist) - min(hist)) / base) if base else 0.0
        band = (threshold if threshold is not None
                else max(m.floor, 1.25 * spread))
        delta = (nv - base) / base if base else 0.0
        entry.update({
            "baseline": round(base, 4),
            "delta_pct": round(100 * delta, 1),
            "band_pct": round(100 * band, 1),
            "history_rounds": len(hist),
        })
        # lower-is-better metrics keep delta_pct as the true relative
        # change; only the verdict mapping flips
        signed = -delta if m.lower_is_better else delta
        if m.lower_is_better:
            entry["lower_is_better"] = True
        if signed < -band:
            entry["verdict"] = "regress"
            regressed.append(m.name)
        elif signed > band:
            entry["verdict"] = "improve"
            improved.append(m.name)
        else:
            entry["verdict"] = "ok"
        per[m.name] = entry
    verdict = "regress" if regressed else "ok"
    return {
        "verdict": verdict,
        "rc": 2 if regressed else 0,
        "latest": latest.get("path"),
        "latest_partial": bool(latest.get("partial")),
        "latest_wire_mbps": latest.get("wire_mbps"),
        "history_rounds": len(history),
        "metrics": per,
        "regressed": regressed,
        "improved": improved,
    }


def evaluate_files(paths, threshold: float | None = None) -> dict:
    return evaluate_rounds(load_history(paths), threshold=threshold)


def sentinel_for_record(record: dict, history_paths) -> dict:
    """Score a LIVE bench record (the dict ``bench.py`` is about to
    emit) against the committed round history — the end-of-round hook.
    The record becomes the latest round; history rounds come from
    ``history_paths`` (files or dirs of ``BENCH_r*.json``)."""
    rounds = load_history(history_paths)
    metrics = extract_metrics(record)
    if not metrics:
        return {"verdict": "insufficient", "rc": 1, "metrics": {},
                "regressed": [], "improved": [],
                "reason": "live record carries no judged metrics"}
    rounds.append({
        "path": "<live>",
        "round": None,
        "rc": 0,
        "partial": bool(record.get("partial")),
        "wire_mbps": extract_wire_mbps(record),
        "metrics": metrics,
    })
    return evaluate_rounds(rounds)


def summary_token(result: dict) -> str:
    """The one scalar that rides the judged summary line:
    ``ok`` / ``regress:a,b`` / ``insufficient``."""
    if result.get("verdict") == "regress":
        return "regress:" + ",".join(result.get("regressed", []))
    return str(result.get("verdict", "insufficient"))


def format_report(result: dict) -> str:
    lines = [f"bench sentinel: {result['verdict']} "
             f"(latest={result.get('latest')}, "
             f"history={result.get('history_rounds', 0)} round(s), "
             f"wire={result.get('latest_wire_mbps')} MB/s"
             + (", PARTIAL" if result.get("latest_partial") else "")
             + ")"]
    for name, e in (result.get("metrics") or {}).items():
        v = e.get("verdict")
        if v == "skipped":
            lines.append(f"  {name:<40} skipped — {e.get('reason')}")
            continue
        norm = (" [/MB/s]" if e.get("wire_sensitive")
                and e.get("normalized") != e.get("value") else "")
        lines.append(
            f"  {name:<40} {v:<10} value={e.get('value')}"
            + (f" norm={e.get('normalized')}{norm}"
               f" base={e.get('baseline')}"
               f" delta={e.get('delta_pct')}%"
               f" band=±{e.get('band_pct')}%"
               if e.get("baseline") is not None else ""))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="wire-normalized bench regression sentinel")
    p.add_argument("paths", nargs="+",
                   help="BENCH_r*.json files, or dirs holding them")
    p.add_argument("--threshold", type=float, default=None,
                   help="override every metric's noise band (relative)")
    p.add_argument("--json", action="store_true",
                   help="print the full result as JSON")
    args = p.parse_args(argv)
    result = evaluate_files(args.paths, threshold=args.threshold)
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        print(format_report(result))
    return int(result["rc"])


if __name__ == "__main__":
    sys.exit(main())
