#!/usr/bin/env python
"""Schema + atomicity audit for tpudl live status files.

Fourth member of the validator family (validate_metrics.py,
validate_shards.py, validate_dump.py): a ``tpudl-status-<pid>.json``
written by :mod:`tpudl.obs.live` must

- parse as ONE complete JSON object — the atomic tmp+rename write
  contract means a torn/partial file is a bug, not bad luck;
- carry every schema key with the right type, with the filename's pid
  matching the payload's;
- stay SMALL (< 1 MB): the status file is a heads-up display, not a
  dump — unbounded growth means something leaked a whole registry or
  ring into it;
- keep each run entry consistent (rows_done never past rows_total,
  percentages in [0, 100]).

Pure stdlib, importable (``from validate_status import
validate_status``) and runnable (``python tools/validate_status.py
<file-or-dir>``); wired into tier-1 by tests/test_obs_live.py the same
way the other validators are.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

_NUM = (int, float)
SCHEMA = "tpudl-status"
VERSION = 1
MAX_BYTES = 1 << 20  # the "HUD, not a dump" bound
_NAME_RE = re.compile(r"^tpudl-status-(\d+)\.json$")

_TOP_KEYS = {
    "schema": str,
    "version": int,
    "ts": _NUM,
    "pid": int,
    "host": str,
    "argv": list,
    "interval_s": _NUM,
    "alive": bool,
    "runs": list,
    "heartbeats": dict,
    "metrics": dict,
    "roofline": (dict, type(None)),
}
_RUN_KEYS = {
    "run_id": (str, type(None)),
    "rows_total": (int, type(None)),
    "rows_done": int,
    "finished": bool,
    "wall_s": _NUM,
    "stage_seconds": dict,
    "config": dict,
}


def _check_keys(obj: dict, spec: dict, where: str) -> list[str]:
    errs = []
    for key, types in spec.items():
        if key not in obj:
            errs.append(f"{where}: missing key {key!r}")
        elif not isinstance(obj[key], types):
            errs.append(f"{where}: {key}={type(obj[key]).__name__} "
                        f"is not {types}")
    return errs


def validate_payload(payload) -> list[str]:
    """Errors in one parsed status payload (empty list = valid)."""
    if not isinstance(payload, dict):
        return ["payload is not a JSON object"]
    errs = _check_keys(payload, _TOP_KEYS, "status")
    if payload.get("schema") not in (None, SCHEMA):
        errs.append(f"status: schema {payload['schema']!r} != {SCHEMA!r}")
    if isinstance(payload.get("version"), int) \
            and payload["version"] > VERSION:
        errs.append(f"status: version {payload['version']} is newer "
                    f"than this validator ({VERSION})")
    for i, run in enumerate(payload.get("runs") or []):
        if not isinstance(run, dict):
            errs.append(f"runs[{i}]: not an object")
            continue
        errs.extend(_check_keys(run, _RUN_KEYS, f"runs[{i}]"))
        total, done = run.get("rows_total"), run.get("rows_done")
        if (isinstance(total, int) and isinstance(done, int)
                and done > total):
            errs.append(f"runs[{i}]: rows_done {done} > rows_total "
                        f"{total}")
        pct = run.get("pct")
        if isinstance(pct, _NUM) and not 0 <= pct <= 100:
            errs.append(f"runs[{i}]: pct {pct} outside [0, 100]")
        for k, v in (run.get("stage_seconds") or {}).items():
            if not isinstance(v, _NUM) or v < 0:
                errs.append(f"runs[{i}].stage_seconds[{k}]: {v!r} is "
                            "not a non-negative number")
    for name, hb in (payload.get("heartbeats") or {}).items():
        if not isinstance(hb, dict):
            errs.append(f"heartbeats[{name}]: not an object")
            continue
        for k in ("age_s", "beats"):
            if not isinstance(hb.get(k), _NUM):
                errs.append(f"heartbeats[{name}]: missing/invalid {k}")
    srv = payload.get("serve")
    if srv is not None and not isinstance(srv, dict):
        errs.append("serve: not an object")
    elif isinstance(srv, dict):
        for k in ("requests", "rejects", "completed", "queue_depth",
                  "queue_cap"):
            if not isinstance(srv.get(k), _NUM):
                errs.append(f"serve.{k}: missing/invalid")
        slo = srv.get("slo")
        if slo is not None and not isinstance(slo, dict):
            errs.append("serve.slo: not an object")
        elif isinstance(slo, dict):
            for k in ("target_ms", "window_s", "window_n"):
                if not isinstance(slo.get(k), _NUM):
                    errs.append(f"serve.slo.{k}: missing/invalid")
            for k in ("window_p50_ms", "window_p99_ms", "availability",
                      "burn_short", "burn_long", "window_qps"):
                v = slo.get(k)
                if v is not None and not isinstance(v, _NUM):
                    errs.append(f"serve.slo.{k}: {type(v).__name__} "
                                "is not numeric")
            av = slo.get("availability")
            if isinstance(av, _NUM) and not 0 <= av <= 1.0001:
                errs.append(f"serve.slo.availability: {av!r} is not "
                            "a fraction")
            samples = slo.get("window_samples_ms")
            if samples is not None:
                # bounded sample tail: the HUD contract again — a
                # whole latency ring in the status file is a leak
                if not isinstance(samples, list) or len(samples) > 256:
                    errs.append("serve.slo.window_samples_ms: must be "
                                "a bounded list (<= 256 entries)")
                else:
                    for j, v in enumerate(samples):
                        if not isinstance(v, _NUM):
                            errs.append(
                                f"serve.slo.window_samples_ms[{j}]: "
                                f"{type(v).__name__} is not numeric")
    rl = payload.get("roofline")
    if isinstance(rl, dict):
        attr = rl.get("gap_attribution")
        if attr is not None:
            if not isinstance(attr, dict):
                errs.append("roofline.gap_attribution: not an object")
            else:
                for k, v in attr.items():
                    if not isinstance(v, _NUM) or not 0 <= v <= 1.0001:
                        errs.append(f"roofline.gap_attribution[{k}]: "
                                    f"{v!r} is not a fraction")
    # the attribution ledger section (optional, like serve/hbm/compile:
    # present once anything charged); the shape is the dump validator's
    # — shared checker, status rows just add rates/shares it tolerates
    if "ledger" in payload:
        try:
            from validate_dump import validate_ledger_section

            errs.extend(f"status: {e}" for e in
                        validate_ledger_section(payload["ledger"]))
        except ImportError:
            if not isinstance(payload["ledger"], (dict, type(None))):
                errs.append("status: ledger: not an object")
    # metrics entries reuse the sink's typed schema when importable
    try:
        from validate_metrics import validate_metric_entry

        for name, entry in (payload.get("metrics") or {}).items():
            errs.extend(f"metrics: {e}"
                        for e in validate_metric_entry(name, entry))
    except ImportError:
        pass
    return errs


def validate_status(path: str) -> list[str]:
    """Errors for one status file (atomicity = parse + size, name↔pid
    match, schema)."""
    errs = []
    try:
        size = os.path.getsize(path)
        if size > MAX_BYTES:
            errs.append(f"{path}: {size} bytes breaks the < {MAX_BYTES}"
                        " HUD-size contract")
        with open(path, encoding="utf-8") as f:
            raw = f.read()
        payload = json.loads(raw)
    except (OSError, json.JSONDecodeError) as e:
        # the atomic-write contract makes ANY parse failure an error
        return [f"{path}: unreadable/torn ({e!r})"]
    m = _NAME_RE.match(os.path.basename(path))
    if m and isinstance(payload, dict) \
            and payload.get("pid") != int(m.group(1)):
        errs.append(f"{path}: filename pid {m.group(1)} != payload pid "
                    f"{payload.get('pid')}")
    errs.extend(f"{path}: {e}" for e in validate_payload(payload))
    return errs


def validate_path(path: str) -> tuple[list[str], int]:
    """(errors, n_files) for a status file or a directory of them."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path,
                                              "tpudl-status-*.json")))
    else:
        files = [path]
    if not files:
        return [f"{path}: no tpudl-status-*.json files"], 0
    errs: list[str] = []
    for f in files:
        errs.extend(validate_status(f))
    return errs, len(files)


def main(argv) -> int:
    if len(argv) != 2:
        print("usage: validate_status.py <tpudl-status-*.json | dir>",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    errors, n = validate_path(argv[1])
    for e in errors:
        print(f"INVALID: {e}", file=sys.stderr)
    print(f"{argv[1]}: {n} status file(s), "
          f"{'OK' if not errors else str(len(errors)) + ' errors'}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
