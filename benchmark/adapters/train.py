"""Adapter ``train``: ``HorovodRunner(np=chips).run`` -> ``ctx.trainer(...)
.fit`` on seeded band batches (a copy of ``chip_smoke.phase_train`` with a
warm-up, a window of one ``fit`` and step intervals taken in ``data_fn``)."""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark import trace_reduce, traffic
from benchmark.common import profiled, rel_l2


def run(spec, drive):
    from tpudl.train import HorovodRunner

    # np is the TOTAL chip count (np=-1 is the reference's one-device mode)
    return HorovodRunner(np=spec.chips).run(lambda ctx: drive(Cell(spec, ctx)))


class Cell:
    def __init__(self, spec, ctx):
        self.spec, self.cfg, self.ctx = spec, spec.config, ctx
        self.offset = 0      # steps done so far: keeps the batch rotation
        self.stamps = []     # perf_counter at each data_fn call

    def setup(self):
        import jax
        import jax.numpy as jnp
        import optax

        from tpudl.train import with_compute_dtype
        from tpudl.zoo.registry import getKerasApplicationModel

        cfg = self.cfg
        t0 = time.perf_counter()
        side, _, _ = cfg["input_shape"]
        self.batch = self.examples_per_run = (cfg["per_chip_batch"]
                                              * self.spec.chips)
        data = traffic.generate(self.spec.traffic, self.spec.seed,
                                batch=self.batch, side=side,
                                classes=cfg["classes"])
        self.xs, self.ys = data["xs"], data["ys"]
        model = getKerasApplicationModel(cfg["model"])
        self.params0 = model.init(self.spec.seed, image_size=(side, side))
        if cfg["classes"] != model.classes:  # rehearsal: a small head
            self.params0["predictions"] = {
                "kernel": np.zeros((2048, cfg["classes"]), np.float32),
                "bias": np.zeros((cfg["classes"],), np.float32)}

        def loss_fn(p, x, y):
            x = (x.astype(jnp.bfloat16) - 127.5) / 127.5
            probs = model.predict(p, x)
            logp = jnp.log(jnp.clip(probs.astype(jnp.float32), 1e-7, 1.0))
            return -jnp.mean(jnp.sum(y * logp, axis=-1))

        # bf16 compute on fp32 MASTER weights
        train_loss = with_compute_dtype(loss_fn, jnp.dtype(
            cfg["compute_dtype"]))
        self.eval_fn = jax.jit(train_loss)
        self.fixed = self.ctx.shard_batch((self.xs[0], self.ys[0]))
        self.lr = cfg["learning_rate"]
        self.trainer = self.ctx.trainer(
            train_loss, getattr(optax, cfg["optimizer"])(self.lr))
        print(f"[train] {cfg['model']} {side}x{side}, {cfg['classes']} "
              f"classes, global batch {self.batch} on mesh "
              f"{dict(self.ctx.mesh.shape)}; data and weights in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)

    def _data(self, step):
        self.stamps.append(time.perf_counter())
        i = (step + self.offset) % len(self.xs)
        return self.xs[i], self.ys[i]

    def _fit(self, params, steps, opt_state=None):
        import jax

        self.stamps = []
        t0 = time.perf_counter()
        params, opt_state, history = self.trainer.fit(
            params, self._data, steps=steps, opt_state=opt_state)
        jax.block_until_ready(params)
        end = time.perf_counter()
        wall = end - t0
        self.loop_s = end - self.stamps[0]  # without the fit's own start
        self.offset += steps
        return params, opt_state, history, wall

    def warm(self):
        """Loads the eval and step programs, then a fit shaped exactly like
        the window's (device params and optimizer state in), whose rate
        sizes the window."""
        t0 = time.perf_counter()
        self.loss_before = float(self.eval_fn(
            self.ctx.replicate(self.params0), *self.fixed))
        self.params1, opt, _, _ = self._fit(self.params0, 1)
        self.params, self.opt, _, wall = self._fit(
            self.params1, self.cfg["warm_steps"], opt)
        # from the first data_fn call to the drained device: the fit's own
        # start-up would make steps / wall read a third too slow, and the
        # first intervals are not yet held back by the device
        self.rate = self.cfg["warm_steps"] / self.loop_s
        print(f"[train] warm-up {time.perf_counter() - t0:.2f}s: fixed-batch "
              f"loss {self.loss_before:.4f}; {self.cfg['warm_steps']} steps "
              f"in {wall:.2f}s, steady at {self.rate:.2f} step/s", flush=True)

    def check(self):
        """The first update against the plain float32 gradient of the same
        batch at highest precision. SGD's update is -lr x gradient, so
        (params0 - params1) / lr is the gradient the program computed in
        bf16 on fp32 masters: measured rel-l2 8.6e-3 to 9.4e-3 on the v5e.
        The limit leaves room for seeds; int8 or fp8 arithmetic, a missing
        term or a wrong scale is far outside it."""
        import jax

        t0 = time.perf_counter()
        # in chunks: the loss is a mean over examples (batch norm runs on
        # moving statistics), so the mean of the chunks' gradients is the
        # batch's, and the reference's temporaries stay far below the step
        # program's, whose memory the result line reports
        chunk = self.cfg["check"]["chunk"]
        grad = jax.jit(jax.grad(self.spec.reference.loss))
        parts = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, self.batch, chunk):
                parts.append(jax.tree.map(np.asarray, grad(
                    self.params0, self.xs[0][i:i + chunk],
                    self.ys[0][i:i + chunk])))
        ref = jax.tree.map(lambda *g: np.mean(g, axis=0), *parts)
        del grad, parts
        got = jax.tree.map(
            lambda p0, p1: (np.asarray(p0) - np.asarray(p1)) / self.lr,
            self.params0, self.params1)

        def flat(tree):
            return np.concatenate([np.ravel(v) for v in jax.tree.leaves(tree)])

        self.update_rel = rel_l2(flat(got), flat(ref))
        gc.collect()  # drops the reference's executable before the window
        print(f"[train] first update vs float32 gradient: rel-l2 "
              f"{self.update_rel:.3e} (limit {self.cfg['check']['rel_l2']}); "
              f"reference took {time.perf_counter() - t0:.2f}s", flush=True)

    def window(self, seconds):
        steps = max(self.cfg["drop_intervals"] + 2, round(self.rate * seconds))
        params, self.opt, history, wall = self._fit(self.params, steps,
                                                    self.opt)
        stamps = np.asarray(self.stamps)
        gaps = np.diff(stamps)[self.cfg["drop_intervals"]:] * 1e3
        loss_after = float(self.eval_fn(params, *self.fixed))
        losses = [h["loss"] for h in history]
        bad = sum(1 for v in losses if not np.isfinite(v))
        limit = self.cfg["check"]["rel_l2"]
        correct = (bad == 0 and bool(losses) and np.isfinite(loss_after)
                   and loss_after < self.loss_before
                   and self.update_rel <= limit)
        p95 = float(np.percentile(gaps, 95))
        print(f"[train] {steps} steps in {wall:.3f}s; {len(gaps)} step "
              f"intervals after the first {self.cfg['drop_intervals']}: "
              f"median {statistics.median(gaps):.3f} ms, p95 {p95:.3f} ms, "
              f"max {gaps.max():.3f} ms; fixed-batch loss "
              f"{self.loss_before:.4f} -> {loss_after:.4f} (last step "
              f"{losses[-1]:.4f}) -> {'ok' if correct else 'WRONG'}",
              flush=True)
        self.params = params
        return {
            "attempted": steps, "failed": bad, "correct": bool(correct),
            "window_s": wall, "steps": steps, "images": steps * self.batch,
            "intervals": len(gaps),
            "end_to_end": {"train_images_per_s": steps * self.batch / wall,
                           "train_step_p95_ms": p95},
            "check": {"update_rel_l2": self.update_rel, "limit": limit,
                      "loss_before": self.loss_before,
                      "loss_after": loss_after},
        }

    def traced(self, trace_dir):
        """A short second fit under the profiler, started and stopped between
        fit calls. The window is the step program's first start to its last
        end on the device's own clock."""
        with profiled(trace_dir):
            self.params, self.opt, _, wall = self._fit(
                self.params, self.cfg["trace_steps"], self.opt)
        planes = trace_reduce.load_planes(trace_dir)
        return {"traced_fit": {"steps": self.cfg["trace_steps"],
                               "wall_s": wall},
                "trace": trace_reduce.reduce(planes, self.cfg["program"])}
