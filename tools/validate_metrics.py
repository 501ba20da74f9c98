#!/usr/bin/env python
"""Schema checks for tpudl's observability emissions.

One contract is always checked (wired into tier-1 via
tests/test_obs_metrics.py, so a malformed emission fails CI, not a
downstream dashboard): the metrics JSONL a ``TPUDL_METRICS_FILE`` sink
appends (:mod:`tpudl.obs.metrics` — one JSON object per line:
``{ts, event, pid, metrics: {name: typed-dict}}``).

Opt-in second contract (``--check-names``): every metric NAME in the
sink must be declared in the registry
(:mod:`tpudl.analysis.metric_names`, ANALYSIS.md) — opt-in because a
sink file may legitimately carry user-defined metrics, but tpudl's own
emissions must match the schema the dashboards key on.

Always-on third contract (ISSUE 20): the labeled-series bound. The
attribution plane keeps per-tenant aggregates in ONE bounded ledger
precisely so nobody multiplies metric names by scope; a snapshot whose
name family (first two dot segments) holds more distinct series than
``--series-bound`` (default 256) is a cardinality explosion — someone
is minting per-label names into the registry — and exits rc 2, louder
than a schema error.

Pure stdlib (the registry import is lazy, only under ``--check-names``),
importable (``from validate_metrics import ...``) and runnable
(``python tools/validate_metrics.py <file.jsonl>``).
"""

from __future__ import annotations

import json
import sys

_NUM = (int, float)
_METRIC_KEYS = {
    "counter": {"value": _NUM},
    "gauge": {"value": (*_NUM, type(None)), "count": int,
              "max": (*_NUM, type(None)), "mean": (*_NUM, type(None))},
    "histogram": {"count": int, "sum": _NUM,
                  "min": (*_NUM, type(None)), "max": (*_NUM, type(None)),
                  "mean": (*_NUM, type(None)), "p50": (*_NUM, type(None)),
                  "p95": (*_NUM, type(None)), "p99": (*_NUM, type(None))},
}
# cardinality bound per name family in one snapshot: generously above
# any legitimate tpudl prefix (serve.* tops out around a dozen), far
# below what per-tenant name-minting produces
SERIES_BOUND = 256


def validate_metric_entry(name: str, entry) -> list[str]:
    """Errors in one ``metrics[name]`` typed dict (empty list = valid)."""
    errs = []
    if not isinstance(entry, dict):
        return [f"metric {name!r}: not an object"]
    kind = entry.get("type")
    if kind not in _METRIC_KEYS:
        return [f"metric {name!r}: unknown type {kind!r}"]
    if isinstance(entry.get("value"), bool) or any(
            isinstance(entry.get(k), bool) for k in _METRIC_KEYS[kind]):
        errs.append(f"metric {name!r}: boolean where number expected")
    for key, types in _METRIC_KEYS[kind].items():
        if key not in entry:
            errs.append(f"metric {name!r} ({kind}): missing key {key!r}")
        elif not isinstance(entry[key], types):
            errs.append(
                f"metric {name!r} ({kind}): {key}="
                f"{entry[key]!r} is not {types}")
    return errs


def validate_metrics_line(line: str, lineno: int = 0) -> list[str]:
    """Errors in one JSONL line (empty list = valid)."""
    where = f"line {lineno}" if lineno else "line"
    try:
        obj = json.loads(line)
    except json.JSONDecodeError as e:
        return [f"{where}: not JSON ({e})"]
    if not isinstance(obj, dict):
        return [f"{where}: not a JSON object"]
    errs = []
    if not isinstance(obj.get("ts"), _NUM):
        errs.append(f"{where}: ts missing or non-numeric")
    if obj.get("event") not in ("snapshot", "final"):
        errs.append(f"{where}: event must be snapshot|final, "
                    f"got {obj.get('event')!r}")
    if not isinstance(obj.get("pid"), int):
        errs.append(f"{where}: pid missing or non-int")
    metrics = obj.get("metrics")
    if not isinstance(metrics, dict):
        errs.append(f"{where}: metrics missing or not an object")
    else:
        for name, entry in metrics.items():
            errs.extend(f"{where}: {e}"
                        for e in validate_metric_entry(name, entry))
    return errs


def validate_metrics_file(path: str):
    """(errors, n_lines, last_parsed_line) for a metrics JSONL file."""
    errors, n, last = [], 0, None
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            if not line.strip():
                continue
            n += 1
            errs = validate_metrics_line(line, i)
            errors.extend(errs)
            if not errs:
                last = json.loads(line)
    if n == 0:
        errors.append(f"{path}: no JSONL lines")
    return errors, n, last


def unknown_sink_names(metrics: dict) -> list[str]:
    """Names in one line's ``metrics`` dict that the registry does not
    declare (the ``--check-names`` cross-check)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:  # runnable from anywhere, like the CLI
        sys.path.insert(0, repo)
    from tpudl.analysis.metric_names import unknown_metric_names

    return unknown_metric_names(metrics)


def check_file_names(path: str) -> list[str]:
    """Undeclared metric names across every parseable line of a sink
    file (empty = all names declared)."""
    unknown: set[str] = set()
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue  # the schema pass reports these
            metrics = obj.get("metrics")
            if isinstance(metrics, dict):
                unknown.update(unknown_sink_names(metrics))
    return sorted(unknown)


def series_family(name: str) -> str:
    """A metric name's cardinality family: the first two dot segments
    (``serve.slo.burn_short`` → ``serve.slo``). Per-label name minting
    multiplies series INSIDE one family, which is what the bound
    catches."""
    return ".".join(str(name).split(".")[:2])


def labeled_series_breaches(path: str,
                            bound: int = SERIES_BOUND) -> list[str]:
    """Families whose distinct-series count in any single snapshot
    line breaches ``bound`` (empty = cardinality healthy). Counted per
    LINE, not across the file — a long-lived sink legitimately
    accumulates history, but one snapshot is one registry."""
    worst: dict[str, int] = {}
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue  # the schema pass reports these
            metrics = obj.get("metrics") if isinstance(obj, dict) \
                else None
            if not isinstance(metrics, dict):
                continue
            fams: dict[str, int] = {}
            for name in metrics:
                fam = series_family(name)
                fams[fam] = fams.get(fam, 0) + 1
            for fam, n in fams.items():
                if n > worst.get(fam, 0):
                    worst[fam] = n
    return [f"family {fam!r}: {n} distinct series in one snapshot "
            f"(labeled-series bound {bound}; keep per-scope aggregates "
            f"in the attribution ledger, not in metric names)"
            for fam, n in sorted(worst.items()) if n > bound]


def main(argv) -> int:
    args = list(argv[1:])
    check_names = "--check-names" in args
    if check_names:
        args.remove("--check-names")
    bound = SERIES_BOUND
    if "--series-bound" in args:
        at = args.index("--series-bound")
        try:
            bound = int(args[at + 1])
        except (IndexError, ValueError):
            print("--series-bound needs an integer", file=sys.stderr)
            return 2
        del args[at:at + 2]
    if len(args) != 1:
        print("usage: validate_metrics.py [--check-names] "
              "[--series-bound N] <metrics.jsonl>", file=sys.stderr)
        return 2
    errors, n, _last = validate_metrics_file(args[0])
    if check_names:
        errors.extend(f"undeclared metric name: {name!r} (declare it "
                      f"in tpudl/analysis/metric_names.py)"
                      for name in check_file_names(args[0]))
    breaches = labeled_series_breaches(args[0], bound)
    for e in errors:
        print(f"INVALID: {e}", file=sys.stderr)
    for b in breaches:
        print(f"CARDINALITY: {b}", file=sys.stderr)
    n_bad = len(errors) + len(breaches)
    print(f"{args[0]}: {n} lines, "
          f"{'OK' if not n_bad else str(n_bad) + ' errors'}")
    # rc contract: a cardinality breach outranks schema errors (2) —
    # it is the signal the attribution plane's guard exists to raise
    if breaches:
        return 2
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
