#!/usr/bin/env python3
"""The benchmark's command: one cell, one seed, one window.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, model or metric by name. ``--workload`` is looked up in
``BENCHMARK.json``; its configuration file, the plain reference beside it,
its traffic file, its adapter and each per-layer metric's reader are found
by the names written there (``README.md`` in this directory). Set-up (data
and weights from the seed, compile or cache load, warm-up, the reference
half of the correctness check) is timed from process start; then the window
runs with nothing compiling inside it. The last line of standard output is
the result object; facts go on the lines before it.

``--rehearse`` shrinks sizes by the files' own ``rehearse`` blocks and runs
on any backend, to try the control flow before chip time is spent. It is not
a fallback: its facts line says ``"rehearsal": true`` and no result follows.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import common, flops  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest = common.load_json(os.path.join(os.path.dirname(HERE),
                                             "BENCHMARK.json"))
    spec = common.resolve(manifest, args.workload, args.seed, args.rehearse)
    adapter = common.load_adapter(spec.config["adapter"])

    import jax

    from tpudl import compile as tcompile

    imported = time.perf_counter() - T0
    dev = jax.devices()[0]
    backend = time.perf_counter() - T0 - imported
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"benchmark: cell {spec.name} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace}; platform={dev.platform} "
          f"kind={dev.device_kind!r} devices={device['count']} "
          f"jax={jax.__version__}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"benchmark: platform is {dev.platform!r}, not "
                         "'tpu': no result (--rehearse tries the control "
                         "flow on any backend)")
    if device["count"] != spec.chips:
        raise SystemExit(f"benchmark: cell {spec.name} is for {spec.chips} "
                         f"chip(s), JAX shows {device['count']}")
    cache = tcompile.enable_compilation_cache()
    print(f"benchmark: compile cache {cache}", flush=True)
    meter = common.Meter()
    memory = common.MemoryPeak(jax.devices())

    def drive(cell):
        cell.setup()
        cell.warm()
        cell.check()
        setup = {"setup_s": time.perf_counter() - T0, "imports_s": imported,
                 "backend_s": backend,
                 "compile_s": meter.compile_s, "programs": meter.programs,
                 "cache_hits": meter.hits, "cache_misses": meter.misses}
        print(f"benchmark: set-up {setup['setup_s']:.2f}s (imports "
              f"{imported:.2f}s, backend up {backend:.2f}s, trace + lower + "
              f"compile-or-load {setup['compile_s']:.2f}s over "
              f"{setup['programs']} programs, persistent cache "
              f"{setup['cache_hits']} hit / {setup['cache_misses']} miss)",
              flush=True)
        built = meter.programs
        memory.start()
        print(f"benchmark: device memory in use + reserved at the window's "
              f"start {memory.peak / 1e9:.3f} GB", flush=True)
        facts = cell.window(args.seconds)
        facts["compiles_in_window"] = meter.programs - built
        if facts["compiles_in_window"]:
            print(f"benchmark: !!! {facts['compiles_in_window']} PROGRAM(S) "
                  "COMPILED INSIDE THE WINDOW: a shape was not warmed up",
                  flush=True)
        if args.trace:
            with tempfile.TemporaryDirectory(prefix="benchmark_trace_") as d:
                facts.update(cell.traced(d))
                facts["trace_bytes"] = sum(
                    os.path.getsize(os.path.join(base, f))
                    for base, _, files in os.walk(d) for f in files)
            if facts["trace"] is None:
                print("benchmark: no device plane in the trace "
                      f"({facts['trace_bytes']} bytes): no device metric",
                      flush=True)
        memory.stop()
        facts.update(
            setup=setup, device_kind=dev.device_kind, devices=spec.chips,
            flops_per_run=cell.examples_per_run * flops.per_example(
                spec.reference.forward, cell.params0, **spec.config["flops"]))
        return facts

    facts = adapter.run(spec, drive)

    def reported(section):
        return [m for m in manifest[section]
                if spec.name in m.get("workloads", [spec.name])]

    metrics = {}
    if args.trace:
        for m in reported("per_layer"):
            value = common.load_reader(m["name"]).read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace = facts["trace"] or {}
        device.update({k: trace[k] for k in ("busy_s", "window_s")
                       if k in trace})
    else:
        values = {**facts["end_to_end"], "setup_s": facts["setup"]["setup_s"]}
        for m in reported("end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = memory.peak
    print("benchmark: facts " + json.dumps(
        {"rehearsal": args.rehearse, **facts}, default=str), flush=True)
    if args.rehearse:
        return 0
    result = {"correct": bool(facts["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": metrics, "device": device}
    if facts.get("trace"):
        result["breakdown"] = {k: facts["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
