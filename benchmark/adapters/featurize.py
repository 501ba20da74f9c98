"""Adapter ``featurize``: files -> ``imageIO.readImages`` ->
``DeepImageFeaturizer.transform``, rows materialised, in whole passes
(a copy of ``chip_smoke.phase_featurize`` with a warm-up and a window)."""

from __future__ import annotations

import gc
import os
import tempfile
import time

import numpy as np

from benchmark import trace_reduce, traffic
from benchmark.common import profiled, rel_l2

STAGES = ("prepare", "infeed_wait", "h2d", "dispatch", "dispatch_wait", "d2h")


def run(spec, drive):
    with tempfile.TemporaryDirectory(prefix="benchmark_featurize_") as d:
        return drive(Cell(spec, d))


class Cell:
    def __init__(self, spec, workdir):
        self.spec, self.cfg, self.workdir = spec, spec.config, workdir
        self.passes = 0

    def setup(self):
        import tpudl
        from tpudl import mesh as M
        from tpudl import native
        from tpudl.image import imageIO
        from tpudl.zoo.registry import getKerasApplicationModel

        cfg = self.cfg
        t0 = time.perf_counter()
        images = os.path.join(self.workdir, "images")
        os.mkdir(images)
        made = traffic.generate(self.spec.traffic, self.spec.seed,
                                directory=images)
        self.n = made["n"]
        self.examples_per_run = cfg["batch_size"]
        self.frame = imageIO.readImages(images)
        t1 = time.perf_counter()
        # weights="random" is the zoo's init(0) for every seed, and has to
        # be: the featurizer compiles its weights into the program as
        # constants, so weights that changed with the seed would make every
        # run compile anew (30 s on the v5e, and a new 50 MB cache entry).
        # The seed varies the images; the reference gets the same init(0),
        # and would disagree if the program's weights were anything else.
        self.params0 = getKerasApplicationModel(cfg["model"]).init(0)
        mesh = M.build_mesh()
        self.feat = tpudl.DeepImageFeaturizer(
            inputCol="image", outputCol="features", modelName=cfg["model"],
            weights="random", batchSize=cfg["batch_size"],
            computeDtype=cfg["compute_dtype"], mesh=mesh)
        print(f"[featurize] {self.n} files written and listed in "
              f"{t1 - t0:.2f}s, weights in {time.perf_counter() - t1:.2f}s; "
              f"decoder={'tpudl.native' if native.available() else 'PIL'}; "
              f"batchSize {cfg['batch_size']} on mesh {dict(mesh.shape)}",
              flush=True)

    def warm(self):
        """The one program at its one shape: full batches of the model's own
        geometry (mixed-size files are resized on the host, and the pass is
        a whole number of batches). No pass is run: a pass of one batch
        keeps one prepare worker busy for as long as a timed pass takes."""
        if self.n % self.cfg["batch_size"]:
            raise SystemExit(
                f"benchmark: {self.n} files is not a whole number of batches "
                f"of {self.cfg['batch_size']}: the tail would compile a "
                "second program")
        t0 = time.perf_counter()
        h, w, _ = self.cfg["input_shape"]
        self.feat.warmup(h, w)
        print(f"[featurize] warmup({h}, {w}) {time.perf_counter() - t0:.2f}s",
              flush=True)

    def check(self):
        """Reference half of the check, before the window: a seeded sample of
        rows, decoded and resized by the same public ``imageIO`` calls the
        featurizer's pack uses (which also loads the decoder), through the
        plain float32 forward of the same weights at highest precision."""
        import jax

        from tpudl.image import imageIO

        t0 = time.perf_counter()
        h, w, _ = self.cfg["input_shape"]
        rng = np.random.default_rng(self.spec.seed)
        self.sample = np.sort(rng.choice(
            self.n, size=min(self.cfg["check"]["rows"], self.n),
            replace=False))
        structs = self.frame["image"][self.sample]
        x = np.stack([
            imageIO.imageStructToArray(imageIO.resizeImage(r, h, w))[:, :, ::-1]
            for r in structs]).astype(np.float32)  # BGR storage -> RGB
        with jax.default_matmul_precision("highest"):
            self.reference = np.asarray(
                jax.jit(self.spec.reference.forward)(self.params0, x))
        gc.collect()  # drops the reference's executable before the window
        print(f"[featurize] reference: {len(self.sample)} sampled rows "
              f"through the plain float32 forward in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)

    def _pass(self):
        from tpudl import obs

        t0 = time.perf_counter()
        out = self.feat.transform(self.frame)
        rows = list(out["features"])
        wall = time.perf_counter() - t0
        rep = obs.last_pipeline_report()
        stages = {k: float(rep["stage_seconds"].get(k, 0.0)) for k in STAGES}
        bad = sum(1 for r in rows
                  if r is None or not np.isfinite(np.asarray(r)).all())
        knobs = " ".join(f"{k}={rep.get(k)}" for k in (
            "executor", "batch_size", "fuse_steps", "dispatch_depth",
            "prefetch_depth", "prepare_workers", "wire_codec", "donate",
            "device_cache", "autotuned"))
        print(f"[featurize] pass {self.passes}: {len(rows) / wall:.1f} "
              f"img/s ({wall:.3f}s); {knobs}; stages "
              + " ".join(f"{k}={v:.3f}" for k, v in stages.items()),
              flush=True)
        self.passes += 1
        return rows, {"wall_s": wall, "images": len(rows), "failed": bad,
                      "stages": stages}

    def window(self, seconds):
        first, done, elapsed = None, [], 0.0
        while elapsed < seconds:
            rows, rec = self._pass()
            first = rows if first is None else first
            done.append(rec)
            elapsed += rec["wall_s"]
        images = sum(p["images"] for p in done)
        facts = {
            "attempted": images, "failed": sum(p["failed"] for p in done),
            "window_s": elapsed, "passes": len(done), "images": images,
            "end_to_end": {"featurize_images_per_s": images / elapsed},
            "pipeline": {
                "pass_wall_s": elapsed,
                **{k + "_s": sum(p["stages"][k] for p in done)
                   for k in STAGES}},
        }
        facts["correct"], facts["check"] = self._verdict(first)
        return facts

    def _verdict(self, rows):
        """Shape, finite, not constant, no None row, and the sampled rows of
        the first timed pass against the reference. bf16 compute on bf16
        weights measured rel-l2 1.66e-2 on the v5e; 0.03 leaves room for
        seeds, and a drop to 8-bit arithmetic or a wrong layer is O(1)."""
        check = {"rows": len(rows)}
        if any(r is None for r in rows):
            return False, {**check, "why": "None rows"}
        got = np.stack([np.asarray(r) for r in rows])
        check["shape"] = list(got.shape)
        rel = rel_l2(got[self.sample], self.reference)
        limit = self.cfg["check"]["rel_l2"]
        check.update(rel_l2=rel, limit=limit)
        ok = (got.shape == (self.n, self.cfg["feature_dim"])
              and bool(np.isfinite(got).all())
              and float(got.std(axis=0).max()) > 0.0 and rel <= limit)
        print(f"[featurize] first pass: shape {got.shape}, "
              f"{len(self.sample)} sampled rows vs float32 reference rel-l2 "
              f"{rel:.3e} (limit {limit}) -> {'ok' if ok else 'WRONG'}",
              flush=True)
        return ok, check

    def traced(self, trace_dir):
        """One whole pass under the profiler: the idle share is a property
        of a pass. The window is the pass's wall time on the host's clock,
        busy time is the device plane's."""
        with profiled(trace_dir):
            _, rec = self._pass()
        planes = trace_reduce.load_planes(trace_dir)
        return {"traced_pass": rec,
                "trace": trace_reduce.reduce(planes, self.cfg["program"],
                                             window_ns=rec["wall_s"] * 1e9)}

