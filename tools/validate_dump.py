#!/usr/bin/env python
"""Schema + ring-bound audit for tpudl flight-recorder dumps.

The third member of the validator family (validate_metrics.py for the
JSONL sink, validate_shards.py for the batch cache): a
``tpudl-dump-*.json.gz`` written by :mod:`tpudl.obs.flight` must parse,
carry every schema key with the right type, keep its rings inside
their declared bounds (a dump bigger than its rings means the recorder
leaked), and hold NO batch data — descriptors are shapes/dtypes/
fingerprints only.

Pure stdlib, importable (``from validate_dump import validate_dump``)
and runnable (``python tools/validate_dump.py <dump-or-dir>``); wired
into tier-1 by tests/test_obs_flight.py the same way the other two
validators are.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

_NUM = (int, float)
SCHEMA = "tpudl-flight-dump"
VERSION = 3

# key -> required python types of the top-level payload
_TOP_KEYS = {
    "schema": str,
    "version": int,
    "reason": str,
    "ts": _NUM,
    "pid": int,
    "process_index": int,
    "process_count": int,
    "argv": list,
    "python": str,
    "backend": dict,
    "env": dict,
    "error": (dict, type(None)),
    "batches": list,
    "errors": list,
    "stalls": list,
    "metric_ticks": list,
    "restarts": list,
    "events": list,
    "metrics": dict,
    "pipeline_reports": dict,
    "spans": list,
    "heartbeats": dict,
}
# ring ceilings (generous: the env can raise the defaults, but a dump
# orders of magnitude past these means an unbounded recorder)
_RING_CAPS = {"batches": 4096, "errors": 4096, "stalls": 1024,
              "metric_ticks": 4096, "restarts": 64, "events": 64,
              "requests": 1024, "spans": 65536}
_BATCH_KEYS = {"ts": _NUM, "stage": str, "index": int,
               "shapes": list, "dtypes": list}
_ERROR_KEYS = {"ts": _NUM, "kind": str, "message": str}
_STALL_KEYS = {"ts": _NUM, "name": str, "age_s": _NUM, "stall_s": _NUM,
               "stacks": dict}
# the serve request ring (version >= 2): one descriptor per TERMINAL
# request — ids, sizes and millisecond timings, NEVER prompt content
_REQUEST_KEYS = {"ts": _NUM, "trace_id": (str, type(None)),
                 "model": str, "prompt_len": int, "max_new": int,
                 "outcome": str, "latency_ms": (int, float, type(None)),
                 "segments": (dict, type(None))}
# keys that would mean a request descriptor leaked content
_REQUEST_FORBIDDEN = ("prompt", "tokens", "text")
# the attribution ledger (version >= 3): per-scope running aggregates
# — mirrors tpudl.obs.attribution.LEDGER_FIELDS (kept literal here:
# the validator family is pure stdlib, importable without tpudl)
_LEDGER_FIELDS = ("rows_in", "rows_out", "tokens_in", "tokens_out",
                  "wire_bytes", "hbm_bytes", "hbm_peak_bytes",
                  "dispatch_s", "retries", "degradations",
                  "serve_completed", "slo_samples")
# one status/dump ledger holds at most this many scope rows: the table
# is LRU-bounded at TPUDL_OBS_SCOPES (default 64) — orders of magnitude
# past this means the cardinality guard broke
_LEDGER_SCOPES_CAP = 4096


def _check_keys(obj: dict, spec: dict, where: str) -> list[str]:
    errs = []
    for key, types in spec.items():
        if key not in obj:
            errs.append(f"{where}: missing key {key!r}")
        elif not isinstance(obj[key], types):
            errs.append(f"{where}: {key}={type(obj[key]).__name__} "
                        f"is not {types}")
    return errs


def validate_ledger_section(led, where: str = "ledger") -> list[str]:
    """Errors in one attribution-ledger section (shared by the dump
    and status validators — the section's shape is identical, the
    status flavor just adds per-row rates/shares). Bound audit rides
    along: the scope table must stay LRU-capped."""
    if led is None:
        return []
    if not isinstance(led, dict):
        return [f"{where}: not an object"]
    errs = []
    scopes = led.get("scopes")
    if not isinstance(scopes, dict):
        errs.append(f"{where}.scopes: missing/not an object")
        scopes = {}
    cap = led.get("cap")
    if not isinstance(cap, int) or cap < 1:
        errs.append(f"{where}.cap: {cap!r} is not a positive int")
    if len(scopes) > _LEDGER_SCOPES_CAP \
            or (isinstance(cap, int) and cap >= 1
                and len(scopes) > cap):
        errs.append(f"{where}.scopes: {len(scopes)} rows past the "
                    f"cardinality bound (cap {cap})")
    evicted = led.get("evicted")
    if not isinstance(evicted, int) or evicted < 0:
        errs.append(f"{where}.evicted: {evicted!r} is not a "
                    "non-negative int")
    rows = [(f"{where}.scopes[{k}]", v) for k, v in scopes.items()]
    rows.append((f"{where}.unattributed", led.get("unattributed")))
    for rw, row in rows:
        if not isinstance(row, dict):
            errs.append(f"{rw}: not an object")
            continue
        for f in _LEDGER_FIELDS:
            if not isinstance(row.get(f), _NUM):
                errs.append(f"{rw}.{f}: missing/not numeric")
        share = row.get("hbm_share")
        if share is not None and (not isinstance(share, _NUM)
                                  or not 0 <= share <= 1.0001):
            errs.append(f"{rw}.hbm_share: {share!r} is not a fraction")
        for f in ("rows_s", "tokens_s"):
            v = row.get(f)
            if v is not None and not isinstance(v, _NUM):
                errs.append(f"{rw}.{f}: {type(v).__name__} is not "
                            "numeric")
    rec = led.get("reconcile")
    if rec is not None:
        if not isinstance(rec, dict) \
                or not isinstance(rec.get("ok"), bool) \
                or not isinstance(rec.get("checks"), list):
            errs.append(f"{where}.reconcile: must carry ok: bool + "
                        "checks: list")
        else:
            for j, c in enumerate(rec["checks"]):
                if not isinstance(c, dict) \
                        or not isinstance(c.get("field"), str) \
                        or not isinstance(c.get("ledger"), _NUM) \
                        or not isinstance(c.get("global"), _NUM) \
                        or not isinstance(c.get("ok"), bool):
                    errs.append(f"{where}.reconcile.checks[{j}]: "
                                "malformed check entry")
    return errs


def validate_payload(payload) -> list[str]:
    """Errors in one parsed dump payload (empty list = valid)."""
    if not isinstance(payload, dict):
        return ["payload is not a JSON object"]
    errs = _check_keys(payload, _TOP_KEYS, "dump")
    if payload.get("schema") not in (None, SCHEMA):
        errs.append(f"dump: schema {payload['schema']!r} != {SCHEMA!r}")
    if isinstance(payload.get("version"), int) \
            and payload["version"] > VERSION:
        errs.append(f"dump: version {payload['version']} is newer than "
                    f"this validator ({VERSION})")
    # the request ring arrived with version 2; a v1 dump without it is
    # still valid (back-compat), a v2 dump must carry it
    if isinstance(payload.get("version"), int) \
            and payload["version"] >= 2:
        errs.extend(_check_keys(payload, {"requests": list}, "dump"))
    # the attribution ledger arrived with version 3 (same back-compat
    # shape: older dumps without it stay valid; a v3 dump must carry
    # the key — None marks a dying-interpreter gap, a dict is audited)
    if isinstance(payload.get("version"), int) \
            and payload["version"] >= 3:
        if "ledger" not in payload:
            errs.append("dump: missing key 'ledger'")
        else:
            errs.extend(f"dump: {e}" for e in validate_ledger_section(
                payload["ledger"]))
    # ring bounds: a leaked (unbounded) recorder shows up here
    for ring, cap in _RING_CAPS.items():
        entries = payload.get(ring)
        if isinstance(entries, list) and len(entries) > cap:
            errs.append(f"dump: ring {ring!r} holds {len(entries)} "
                        f"entries (bound audit cap {cap})")
    for i, b in enumerate(payload.get("batches") or []):
        if not isinstance(b, dict):
            errs.append(f"batches[{i}]: not an object")
            continue
        errs.extend(_check_keys(b, _BATCH_KEYS, f"batches[{i}]"))
        # the never-data contract: a descriptor is shapes/dtypes/
        # fingerprint — any list-of-numbers payload key is a leak
        for k, v in b.items():
            if k in ("shapes",):
                continue
            if isinstance(v, list) and len(v) > 64:
                errs.append(f"batches[{i}].{k}: {len(v)}-element list "
                            "(descriptors must not carry data)")
    for i, e in enumerate(payload.get("errors") or []):
        if isinstance(e, dict):
            errs.extend(_check_keys(e, _ERROR_KEYS, f"errors[{i}]"))
        else:
            errs.append(f"errors[{i}]: not an object")
    for i, s in enumerate(payload.get("stalls") or []):
        if isinstance(s, dict):
            errs.extend(_check_keys(s, _STALL_KEYS, f"stalls[{i}]"))
        else:
            errs.append(f"stalls[{i}]: not an object")
    for i, r in enumerate(payload.get("requests") or []):
        if not isinstance(r, dict):
            errs.append(f"requests[{i}]: not an object")
            continue
        errs.extend(_check_keys(r, _REQUEST_KEYS, f"requests[{i}]"))
        # never-content contract, request flavor: a descriptor carries
        # lengths and timings — token/prompt payloads are a leak
        for k in _REQUEST_FORBIDDEN:
            if k in r:
                errs.append(f"requests[{i}].{k}: request descriptors "
                            "must not carry prompt/token content")
        for k, v in r.items():
            if isinstance(v, list) and len(v) > 64:
                errs.append(f"requests[{i}].{k}: {len(v)}-element list "
                            "(descriptors must not carry data)")
        segs = r.get("segments")
        if isinstance(segs, dict):
            for k, v in segs.items():
                if not isinstance(v, _NUM):
                    errs.append(f"requests[{i}].segments.{k}: "
                                f"{type(v).__name__} is not numeric")
    # metrics reuse the sink's typed-dict schema when the validator is
    # importable (a wheel install may not ship tools/)
    try:
        from validate_metrics import validate_metric_entry

        for name, entry in (payload.get("metrics") or {}).items():
            errs.extend(f"metrics: {e}"
                        for e in validate_metric_entry(name, entry))
    except ImportError:
        pass
    return errs


def validate_dump(path: str) -> list[str]:
    """Errors for one dump file (parse + schema + ring bounds)."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt", encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError, EOFError) as e:
        return [f"{path}: unreadable ({e!r})"]
    return [f"{path}: {e}" for e in validate_payload(payload)]


def validate_path(path: str) -> tuple[list[str], int]:
    """(errors, n_dumps) for a dump file or a directory of dumps."""
    if os.path.isdir(path):
        files = sorted(
            glob.glob(os.path.join(path, "tpudl-dump-*.json.gz"))
            + glob.glob(os.path.join(path, "tpudl-dump-*.json")))
    else:
        files = [path]
    if not files:
        return [f"{path}: no tpudl-dump-*.json[.gz] files"], 0
    errs: list[str] = []
    for f in files:
        errs.extend(validate_dump(f))
    return errs, len(files)


def main(argv) -> int:
    if len(argv) != 2:
        print("usage: validate_dump.py <tpudl-dump-*.json.gz | dir>",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    errors, n = validate_path(argv[1])
    for e in errors:
        print(f"INVALID: {e}", file=sys.stderr)
    print(f"{argv[1]}: {n} dump(s), "
          f"{'OK' if not errors else str(len(errors)) + ' errors'}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
