"""What run.py and the adapters share: the cell's resolved description,
jax.monitoring compile accounting, device memory, and the error norm."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import threading
import types

ROOT = os.path.dirname(os.path.abspath(__file__))

# jax.monitoring event names (jax/_src/dispatch.py, compilation_cache.py)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = _COMPILE_EVENTS[2]
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Meter:
    """Seconds spent tracing, lowering and compiling (or loading from the
    persistent cache), the number of programs built, and cache hits and
    misses, process-wide (``chip_smoke.Meter``)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.programs = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
        if event == _BACKEND_COMPILE:
            self.programs += 1

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1


@dataclasses.dataclass
class Spec:
    """One cell, resolved: the workload entry of BENCHMARK.json, its
    configuration and traffic files (rehearsal overrides applied) and the
    configuration's plain reference."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    rehearse: bool
    reference: types.ModuleType


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _shrunk(data: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        out.update(data.get("rehearse", {}))
    return out


def resolve(manifest: dict, workload: str, seed: int = 0,
            rehearse: bool = False, root: str = ROOT) -> Spec:
    """Everything is found by the names in the manifest: the cell in
    ``workloads``, its configuration's ``file`` (and ``<file>.py`` beside
    it, the plain reference), its traffic in ``traffic/<traffic>.json``."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    file = os.path.join(os.path.dirname(root), configs[cell["config"]]["file"])
    reference = load_module(file[:-len(".json")] + ".py",
                            "benchmark_reference_" + cell["config"])
    return Spec(
        name=workload, chips=int(cell["chips"]), seed=seed, rehearse=rehearse,
        config=_shrunk(load_json(file), rehearse), reference=reference,
        traffic=_shrunk(load_json(os.path.join(
            root, "traffic", cell["traffic"] + ".json")), rehearse))


def load_adapter(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "adapters", name + ".py"),
                       "benchmark_adapter_" + name)


def load_reader(metric: str, root: str = ROOT):
    """A per-layer metric's reader is ``readers/<name before the first
    dot>.py``: ``program_ms.train`` and ``program_ms.featurize`` share one."""
    kind = metric.split(".", 1)[0]
    return load_module(os.path.join(root, "readers", kind + ".py"),
                       "benchmark_reader_" + kind)


@contextlib.contextmanager
def profiled(directory: str):
    """A profiler session that keeps the device planes only. They are all the
    readers use; with the defaults the host's runtime threads alone made a
    413 MB trace that took 58 s to write, inside the traced window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


class MemoryPeak:
    """Fullest chip's occupancy, sampled ten times a second from a thread of
    its own between ``start()`` and ``stop()``. On this TPU runtime a
    program's temporaries are not in ``bytes_in_use``: they sit in
    ``bytes_reserved`` while the executable is loaded (free = limit - in_use
    - reserved in every reading), so occupancy is the sum, taken per sample
    and never of the two peak counters, whose peaks may fall apart in time.
    Sampling starts after set-up, when the float32 reference's executable
    has been dropped: its temporaries are the yardstick's, not the cell's."""

    def __init__(self, devices, period_s: float = 0.1):
        self.devices = list(devices)
        self.period_s = period_s
        self.peak = 0
        self._stop = threading.Event()
        self._thread = None

    def sample(self) -> int:
        for d in self.devices:
            stats = d.memory_stats()
            if stats:
                self.peak = max(self.peak, int(stats["bytes_in_use"])
                                + int(stats.get("bytes_reserved", 0)))
        return self.peak

    def _loop(self):
        while not self._stop.wait(self.period_s):
            self.sample()

    def start(self):
        self.sample()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="benchmark-memory")
        self._thread.start()

    def stop(self) -> int:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        return self.sample()
