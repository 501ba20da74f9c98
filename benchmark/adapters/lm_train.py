"""Adapter ``lm_train``: ``HorovodRunner(np=chips).run`` -> ``ctx.trainer(
with_compute_dtype(loss, bf16), adamw).fit`` on packed token batches.

The same entry points, step builder, compile cache and ``train.*`` spans as
adapter ``train``; what differs is the model (``tpudl.zoo.decoder.Decoder``
built from the configuration file), the optimizer (AdamW) and the batches
(int32 ids from ``lm_train_tokens``). The facts have the same names, so the
readers of the host loop and of the step program read both.

The routes tap. The reference's gradient has to be taken on the discrete
choices the STEP PROGRAM made, and a second program does not make the same
ones: compiled apart, the same bf16 arithmetic rounds otherwise, about one
near-tie in 150 falls the other way, and the token it moves changes every
layer after it (first chip runs of PR 28: 6% in every group, 12% in the
experts, on the routes of a forward-only program). ``Trainer``'s loss is a
scalar, so the choices leave the step through its gradient: a leaf
``route_tap`` of zeros, shaped like the choices, enters the loss as
``sum((tap - stop_gradient(tap)) * choices)``, which is 0 whatever the leaf
holds and whose gradient with respect to the leaf IS the choices. After one
AdamW step ``mu[route_tap] / (1 - b1)`` are the experts the timed program
selected, exactly (small integers in float32). The term is in every step
of every fit, the window's too: 0.5 M multiply-adds beside 47 TFLOP.

Order, chosen so that the float32 reference and the trainer's state are
never resident together (masters, two moments and the step program's
temporaries fill the chip) and so that the reference's seconds are no part
of ``setup_s``:

1. ``setup``: data and weights from the seed, on the host;
2. ``warm``: one step from the seed's weights, whose update and second
   moment are held against AdamW written out and whose first moment is
   copied to the host (it gives the step's gradient and, through the tap,
   its routes), then the warm-up fit, which continues from the device
   state, then the reference's programs compiled or loaded;
3. ``window``: the timed fit. After it the state goes to the host and
   ``_judge`` decides ``correct``: the reference's float32 loss and
   gradient of batch 0 under the step's routes, one sequence at a time
   (equal token counts, so the mean of the sequences' gradients is the
   batch's);
4. ``traced``: the state back on the device, a short fit under the
   profiler.

Every fit but the first continues from device state with ``consume=True``:
the state is handed over, not copied (a second copy does not fit).
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark import flops_lm, trace_reduce, traffic
from benchmark.adapters import lm_train_tokens  # noqa: F401  registers the generator
from benchmark.common import profiled, rel_l2

SCOPES = ("lm.conv_op", "lm.attention", "lm.dense_ff", "moe.route",
          "moe.experts", "lm.head")

# XLA's own grouped matrix product (what jax.lax.ragged_dot becomes on a TPU)
# carries the compiler's name, not the program's scope
KERNELS = {"ragged-dot": "moe.experts"}

# Why each limit of configs/<config>.json "check" is what it is. The
# program computes in bfloat16 on float32 masters; the reference in float32
# at highest precision, on the routes the step program chose (the tap).
# Each gradient limit lies between two chip readings (PERF.md section 6):
# the largest that sound runs gave over their seeds, and the same step with
# per-tensor-scaled fp8 (e4m3) operands and cotangents in the experts'
# products (controls/lm_fp8_experts.py), which has to fail.
LIMITS_WHY = {
    "experts": "grouped products in bf16; a dropped pair or an fp8 product "
               "moves this group first",
    "routers": "small leaves (2048 x 32) whose gradient comes only through "
               "the renormalised weights: a missing renormalisation or "
               "scale shows here and nowhere else at this size",
    "conv": "the short convolution's two projections and three taps",
    "attention": "q/k/v/o and the per-head norms: a wrong scale, rotation "
                 "or missing query/key norm",
    "dense_ff": "the one dense feed-forward",
    "table": "embedding rows and the tied head, summed",
    "norms": "the RMSNorm weights of every block and the final one",
    "loss_rel": "the first step's loss against the reference's, relative: "
                "100 times the largest reading, a tenth of what one wrong "
                "layer moves it by",
    "route_agreement_min": "share of the step's (token, expert) pairs that "
                           "the reference's own float32 top-k of scores + "
                           "bias also selects: near-ties flip under bf16 "
                           "(0.982 measured); a wrong k, bias or score "
                           "function agrees on far fewer",
    "update_rel_l2": "the first step's change of every leaf against AdamW "
                     "written out from the configuration's rate, "
                     "b1, b2 and weight decay and the step's own gradient, "
                     "worst leaf, in units of the update: float32 rounding "
                     "reads 1e-5; a schedule, a rate or a decay (0.1 x "
                     "|p| = 2e-3 of the unit step) reads over the limit",
    "moment2_rel_l2": "AdamW's second moment after that step against "
                      "(1 - b2) g^2, worst leaf: what holds b2, which the "
                      "first update itself does not depend on",
}


def group_of(name: str) -> str:
    """The group a parameter's gradient is compared in. One global norm
    would let the large leaves (experts: 70% of the parameters) hide a
    wrong router."""
    if name == "embed":
        return "table"
    if name.endswith("_norm"):
        return "norms"
    if ".moe.w" in name:
        return "experts"
    if ".moe." in name:
        return "routers"
    if ".conv." in name:
        return "conv"
    if ".attn." in name:
        return "attention"
    if ".ff." in name:
        return "dense_ff"
    raise KeyError(name)


def compare_groups(got: dict, ref: dict) -> dict:
    """rel-l2 of ``got`` against ``ref`` per group of leaves."""
    num, den = {}, {}
    for name, want in ref.items():
        group = group_of(name)
        want = np.asarray(want, np.float32).ravel()
        diff = np.asarray(got[name], np.float32).ravel() - want
        num[group] = num.get(group, 0.0) + float(np.dot(diff, diff))
        den[group] = den.get(group, 0.0) + float(np.dot(want, want))
    return {g: float(np.sqrt(num[g] / max(den[g], 1e-60)))
            for g in sorted(num)}


def adamw_step(p0, g, o, decayed):
    """One AdamW step from zero moments, written out: ``(p1, nu)``.
    After the bias corrections the first moment is ``g`` and the second
    ``g^2``, so the step is ``g / (|g| + eps)`` plus the decay."""
    step = g / (abs(g) + np.float32(1e-8))
    if decayed:
        step = step + np.float32(o["weight_decay"]) * p0
    return (p0 - np.float32(o["learning_rate"]) * step,
            np.float32(1.0 - o["b2"]) * g * g)


def update_errors(p0: dict, p1: dict, mu: dict, nu: dict, o: dict) -> dict:
    """The first step's parameters ``p1`` and second moments ``nu``
    against ``adamw_step`` on the step's own gradient ``mu / (1 - b1)``:
    per leaf the squared norms ``[p1 - want, want - p0, nu - want, want]``.
    Matrices decay, vectors do not (``Decoder.decay_mask``). Pure array
    arithmetic: the cell runs it jitted on the device, where the state is."""
    out = {}
    for name, m in mu.items():
        if name == TAP:
            continue
        want, nu_want = adamw_step(p0[name], m / np.float32(1.0 - o["b1"]),
                                   o, decayed=p0[name].ndim > 1)
        out[name] = [((a - b) ** 2).sum() for a, b in (
            (p1[name], want), (want, p0[name]), (nu[name], nu_want),
            (nu_want, 0.0))]
    return out


def worst_leaf(errors: dict) -> dict:
    """``update_errors`` as two readings: rel-l2 of the worst leaf, the
    update's in units of the update."""
    def ratio(diff, unit):
        diff, unit = float(diff), float(unit)
        return (diff / unit) ** 0.5 if unit else float(diff > 0)

    return {"update_rel_l2": max(ratio(e[0], e[1]) for e in errors.values()),
            "moment2_rel_l2": max(ratio(e[2], e[3]) for e in errors.values())}


def verdict(readings: dict, limits: dict) -> dict:
    """What is outside its limit: ``{}`` when the step is correct."""
    over = {g: v for g, v in readings["grad_rel_l2"].items()
            if not v <= limits["grad_rel_l2"][g]}
    for key in ("loss_rel", "update_rel_l2", "moment2_rel_l2"):
        if not readings[key] <= limits[key]:
            over[key] = readings[key]
    if not readings["route_agreement"] >= limits["route_agreement_min"]:
        over["route_agreement"] = readings["route_agreement"]
    if not readings["loss_again"] < readings["loss_first"]:
        over["loss_again"] = readings["loss_again"]
    return over


def adam_state(opt_state):
    """AdamW's moments out of an optax state, whatever wraps it."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"{len(found)} states with a first moment")
    return found[0]


TAP = "route_tap"


def tapped(loss_with_routes):
    """``loss(params, ids)`` with the routes tap of the module's docstring:
    ``params[TAP]`` takes no part in the model, adds exactly 0 to the loss,
    and its gradient is the experts every routed layer selected,
    ``[layers, B, S, k]``."""
    import jax
    import jax.numpy as jnp

    def loss(params, ids):
        tap = params[TAP]
        value, chosen = loss_with_routes(
            {k: v for k, v in params.items() if k != TAP}, ids)
        seen = jax.lax.stop_gradient(jnp.stack(chosen).astype(tap.dtype))
        return value + jnp.sum(
            (tap - jax.lax.stop_gradient(tap)) * seen).astype(value.dtype)

    return loss


def decoder_config(cfg: dict) -> dict:
    """The configuration file's keys as ``Decoder`` takes them: the file's
    ``num_experts`` / ``vocab_size`` count what is held here, the decoder's
    what is published."""
    out = {k: v for k, v in cfg.items() if k not in (
        "num_experts", "vocab_size", "published")}
    out["num_experts"] = cfg["published"]["num_experts"]
    out["vocab_size"] = cfg["published"]["vocab_size"]
    return out


def run(spec, drive):
    from tpudl.train import HorovodRunner

    return HorovodRunner(np=spec.chips).run(lambda ctx: drive(Cell(spec, ctx)))


class Cell:
    def __init__(self, spec, ctx):
        self.spec, self.cfg, self.ctx = spec, spec.config, ctx
        self.offset = 0
        self.stamps = []

    # ---- set-up ----------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp
        import optax

        from tpudl.train import with_compute_dtype
        from tpudl.zoo.decoder import Decoder

        cfg, t0 = self.cfg, time.perf_counter()
        if self.spec.chips != 1:
            raise SystemExit("lm_train: the chip's share runs on one chip "
                             "(no exchange over the model axis yet)")
        data = traffic.generate(self.spec.traffic, self.spec.seed,
                                vocab=cfg["vocab_size"])
        self.ids = data["ids"]
        self.seqs, self.seq_len = self.ids[0].shape
        self.batch = self.examples_per_run = self.seqs
        self.tokens = self.seqs * self.seq_len
        self.lm = Decoder(decoder_config(cfg))
        self.params0 = self.lm.init(self.spec.seed)
        self.params0[TAP] = np.zeros(
            (self.lm.kinds()["routed"], self.seqs, self.seq_len,
             cfg["num_experts_per_tok"]), np.float32)
        self.dtype = jnp.dtype(cfg["compute_dtype"])
        o = cfg["optimizer"]
        optimizer = getattr(optax, o["name"])(
            o["learning_rate"], b1=o["b1"], b2=o["b2"],
            weight_decay=o["weight_decay"], mask=self.lm.decay_mask)
        self.trainer = self.ctx.trainer(
            with_compute_dtype(tapped(self.lm.loss_fn(
                remat=cfg["remat"], loss_chunk=cfg["loss_chunk"],
                with_routes=True)), self.dtype), optimizer)
        # what the reference cannot read from the parameters' shapes
        self.ref_kw = {"top_k": cfg["num_experts_per_tok"],
                       "held_first": cfg["experts_held"][0],
                       "norm_eps": cfg["norm_eps"],
                       "rope_theta": float(cfg["rope_theta"]),
                       "routed_scaling_factor": float(
                           cfg["routed_scaling_factor"]),
                       "attention_rows": min(512, self.seq_len)}
        n = sum(int(np.size(v)) for k, v in self.params0.items() if k != TAP)
        print(f"[lm_train] {cfg['name']}: {self.lm.kinds()} layers, "
              f"{n / 1e6:.1f} M parameters, experts "
              f"{self.lm.held[0]}..{sum(self.lm.held) - 1} of "
              f"{self.lm.experts}, {self.seqs} x {self.seq_len} tokens a "
              f"step in {data['documents']} documents (median "
              f"{data['median_document']:.0f}); data and weights in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)

    def _route_stats(self, params, batches):
        """``route_stats`` summed over ``batches``, a sequence at a time
        (the forward-only program then needs a quarter of the memory)."""
        total = {"pairs_held": 0, "pairs_total": 0, "expert_tokens_max": 0}
        for ids in batches:
            for seq in ids:
                s = self.lm.route_stats(params, seq[None], self.dtype)
                total["pairs_held"] += s["pairs_held"]
                total["pairs_total"] += s["pairs_total"]
                total["expert_tokens_max"] = max(
                    total["expert_tokens_max"], s["expert_tokens_max"])
        return total

    def _compile_reference(self):
        """The reference's two programs (loss and gradient on given
        routes; its own routes), compiled or loaded from the cache: that
        is set-up, and nothing compiles once the window has begun. They
        run after the window."""
        import jax

        ref, kw = self.spec.reference, self.ref_kw
        params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in self.params0.items() if k != TAP}
        seq = jax.ShapeDtypeStruct((1, self.seq_len), self.ids[0].dtype)
        routes = [jax.ShapeDtypeStruct(
            (1, self.seq_len, self.cfg["num_experts_per_tok"]), np.int32)
        ] * self.lm.kinds()["routed"]
        grad = jax.jit(jax.value_and_grad(
            lambda p, x, r: ref.loss(p, x, r, **kw)))
        own = jax.jit(lambda p, x: ref.routes_of(p, x, **kw))
        return (grad.lower(params, seq, routes).compile(),
                own.lower(params, seq).compile())

    def _reference(self, routes):
        """The reference's float32 loss and gradient of batch 0 on
        ``routes`` (``[layers, B, S, k]``, the step program's own
        choices), and the share of those pairs its own routing selects."""
        import jax

        t0 = time.perf_counter()
        ids = self.ids[0]
        params = jax.device_put(
            {k: v for k, v in self.params0.items() if k != TAP})
        grad, own = self.reference
        losses, total, agree = [], None, []
        for i, seq in enumerate(ids):
            chosen = [layer[i:i + 1] for layer in routes]
            value, g = grad(params, seq[None], chosen)
            losses.append(float(value))
            g = jax.tree.map(np.asarray, g)
            total = g if total is None else jax.tree.map(np.add, total, g)
            for mine, theirs in zip(chosen, own(params, seq[None])):
                same = (mine[..., :, None] == np.asarray(theirs)[
                    ..., None, :]).any(-1)
                agree.append(float(same.mean()))
        del grad, own, params, g, self.reference
        gc.collect()
        print(f"[lm_train] reference: float32 loss and gradient of batch 0 "
              f"on the step program's routes, {len(ids)} sequences one at a "
              f"time, {time.perf_counter() - t0:.2f}s", flush=True)
        return ({k: v / len(ids) for k, v in total.items()},
                float(np.mean(losses)), float(np.mean(agree)))

    def _data(self, step):
        self.stamps.append(time.perf_counter())
        return (self.ids[(step + self.offset) % len(self.ids)],)

    def _fit(self, params, steps, opt_state=None):
        import jax

        self.stamps = []
        t0 = time.perf_counter()
        params, opt_state, history = self.trainer.fit(
            params, self._data, steps=steps, opt_state=opt_state,
            consume=opt_state is not None)
        jax.block_until_ready(params)
        end = time.perf_counter()
        self.loop_s = end - self.stamps[0]
        self.offset += steps
        return params, opt_state, history, end - t0

    def warm(self):
        """One step, whose update is held against ``adamw_step`` where
        the state lies and whose first moment is copied to the host (what
        ``_judge`` reads after the window), then a fit shaped like the
        window's, whose rate sizes the window. The warm-up's rotation
        starts where its last step falls on batch 0 again, the batch of
        the first step: the two losses are compared."""
        import jax

        t0 = time.perf_counter()
        params, opt, history, _ = self._fit(self.params0, 1)
        adam, o = adam_state(opt), self.cfg["optimizer"]
        self.first = {
            "loss": history[-1]["loss"],
            "mu": jax.tree.map(np.asarray, adam.mu),
            **worst_leaf(jax.jit(lambda *state: update_errors(*state, o))(
                self.params0, params, adam.mu, adam.nu))}
        print(f"[lm_train] first step {time.perf_counter() - t0:.2f}s: loss "
              f"{self.first['loss']:.5f}; its update held against AdamW "
              f"written out, its first moment copied to the host",
              flush=True)
        steps = self.cfg["warm_steps"]
        self.offset = (1 - steps) % len(self.ids)
        self.params, self.opt, history, wall = self._fit(params, steps, opt)
        self.first["loss_again"] = history[-1]["loss"]
        self.rate = steps / self.loop_s
        print(f"[lm_train] warm-up: {steps} steps in {wall:.2f}s, steady at "
              f"{self.rate:.3f} step/s; loss on batch 0 again "
              f"{history[-1]['loss']:.5f}", flush=True)
        t0 = time.perf_counter()
        self.reference = self._compile_reference()
        print(f"[lm_train] the reference's programs compiled or loaded in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)

    def check(self):
        """Nothing before the window: the reference half of the check
        needs the room the trainer's state takes, and its seconds are no
        part of set-up. ``window`` calls ``_judge`` after its fit."""

    def _judge(self):
        """``correct``, on what the timed path produced. After one AdamW
        step from zero moments the first moment is (1 - b1) x gradient, so
        mu / (1 - b1) is what the step program computed, at the timed
        sizes: held against the float32 reference by group of leaves, on
        the step's own routes. The same step's update and second moment
        were held against AdamW written out (``warm``), and the loss on
        batch 0 has to have fallen by the warm-up's end."""
        import jax

        t0 = time.perf_counter()
        self.params, self.opt = jax.tree.map(np.asarray,
                                             (self.params, self.opt))
        gc.collect()  # the device copies go: the reference needs the room
        o, first = self.cfg["optimizer"], self.first
        got = {k: v / np.float32(1.0 - o["b1"])
               for k, v in first.pop("mu").items()}
        routes = np.rint(got.pop(TAP)).astype(np.int32)
        ref_grad, ref_loss, agreement = self._reference(list(routes))
        readings = {
            "grad_rel_l2": compare_groups(got, ref_grad),
            "loss_rel": abs(first["loss"] - ref_loss) / ref_loss,
            "route_agreement": agreement,
            "update_rel_l2": first["update_rel_l2"],
            "moment2_rel_l2": first["moment2_rel_l2"],
            "loss_first": first["loss"], "loss_reference": ref_loss,
            "loss_again": first["loss_again"]}
        limits = self.cfg["check"]
        over = verdict(readings, limits)
        del self.first, got, ref_grad
        gc.collect()
        print("[lm_train] first gradient (mu / (1 - b1)) vs float32 "
              "reference, rel-l2 by group: " + ", ".join(
                  f"{g} {v:.3e} (limit {limits['grad_rel_l2'][g]})"
                  for g, v in readings["grad_rel_l2"].items())
              + f"; loss rel {readings['loss_rel']:.3e} (limit "
              f"{limits['loss_rel']}); routes agreed "
              f"{readings['route_agreement']:.4f} (at least "
              f"{limits['route_agreement_min']}); first update vs AdamW "
              f"written out {readings['update_rel_l2']:.3e} (limit "
              f"{limits['update_rel_l2']}), second moment "
              f"{readings['moment2_rel_l2']:.3e} (limit "
              f"{limits['moment2_rel_l2']}); loss on batch 0 "
              f"{readings['loss_first']:.4f} -> {readings['loss_again']:.4f}"
              f" -> {'ok' if not over else 'OVER: ' + str(over)} "
              f"({time.perf_counter() - t0:.2f}s, after the window)",
              flush=True)
        return {**readings, "over": over, "limits": limits}

    # ---- the window ------------------------------------------------------
    def window(self, seconds):
        steps = max(self.cfg["drop_intervals"] + 2, round(self.rate * seconds))
        self.params, self.opt, history, wall = self._fit(
            self.params, steps, self.opt)
        gaps = np.diff(np.asarray(self.stamps))[
            self.cfg["drop_intervals"]:] * 1e3
        losses = [h["loss"] for h in history]
        bad = sum(1 for v in losses if not np.isfinite(v))
        p95 = float(np.percentile(gaps, 95))
        print(f"[lm_train] {steps} steps in {wall:.3f}s; {len(gaps)} step "
              f"intervals after the first {self.cfg['drop_intervals']}: "
              f"median {statistics.median(gaps):.3f} ms, p95 {p95:.3f} ms, "
              f"max {gaps.max():.3f} ms; loss {losses[-1]:.4f} at the "
              f"window's last step", flush=True)
        check = self._judge()
        correct = bad == 0 and bool(losses) and not check["over"]
        return {
            "attempted": steps, "failed": bad, "correct": bool(correct),
            "window_s": wall, "steps": steps, "images": steps * self.batch,
            "tokens": steps * self.tokens, "intervals": len(gaps),
            "end_to_end": {"train_images_per_s": steps * self.batch / wall,
                           "train_step_p95_ms": p95},
            "check": {**check, "loss_last": losses[-1]},
        }

    def traced(self, trace_dir):
        """A short fit under the profiler. The routing counts of the four
        batches it rotates through are taken outside it, at its first and
        at its last parameters (``route_stats``: ``Trainer``'s loss stays a
        scalar); the device time of the program's named scopes goes into
        the span ring (``record_device_scopes``) before the directory is
        removed, and one ``lm.step_work`` span a step carries the needed
        work that ``flops_lm`` makes of the counts."""
        import jax

        steps = self.cfg["trace_steps"]
        self.params, self.opt = jax.device_put((self.params, self.opt))
        before = self._route_stats(self.params, self.ids)
        with profiled(trace_dir):
            self.params, self.opt, _, wall = self._fit(
                self.params, steps, self.opt)
        after = self._route_stats(self.params, self.ids)
        planes = trace_reduce.load_planes(trace_dir)
        reduced = trace_reduce.reduce(planes, self.cfg["program"])
        drift = abs(after["pairs_held"] - before["pairs_held"]) / max(
            before["pairs_held"], 1)
        per_step = {k: (before[k] + after[k]) / (2 * len(self.ids))
                    for k in ("pairs_held", "pairs_total")}
        work = flops_lm.experts_work(self.cfg, round(per_step["pairs_held"]))
        facts = {"tokens": self.tokens, "seq_len": self.seq_len, **per_step,
                 "pairs_held_first": before["pairs_held"] / len(self.ids),
                 "pairs_held_last": after["pairs_held"] / len(self.ids),
                 "pairs_held_drift": drift,
                 "expert_tokens_max": max(before["expert_tokens_max"],
                                          after["expert_tokens_max"]),
                 "step_flops": flops_lm.step_flops(
                     self.cfg, self.tokens, self.seq_len,
                     round(per_step["pairs_held"])),
                 "experts_flops": work["flops"],
                 "experts_bytes": work["bytes"]}
        print(f"[lm_train] traced fit of {steps} steps in {wall:.2f}s; "
              f"pairs held a step {facts['pairs_held_first']:.0f} at its "
              f"first parameters, {facts['pairs_held_last']:.0f} at its "
              f"last ({100 * drift:.3f}% apart) of "
              f"{per_step['pairs_total']:.0f}; fullest expert "
              f"{facts['expert_tokens_max']} tokens of a sequence's "
              f"{self.seq_len}", flush=True)
        scopes = self._record_spans(trace_dir, steps, facts)
        return {"traced_fit": {"steps": steps, "wall_s": wall},
                "lm": {**facts, "device_scope_ms": scopes},
                "trace": reduced}

    def _record_spans(self, trace_dir, steps, facts):
        """Device time by scope and the needed work into the span ring,
        as children of the traced fit's ``train.fit`` span. Returns the
        median device ms a step of every scope (``{}`` without a device
        plane, as in every CPU rehearsal)."""
        from tpudl.obs import get_tracer
        from tpudl.obs import trace as obs_trace

        tracer = get_tracer()
        fit = obs_trace.traced_fit(tracer.spans(), steps)
        if fit is None:
            return {}
        for step in fit["steps"]:
            tracer.record("lm.step_work", step.start_ns, step.dur_ns,
                          parent=fit["fit"], **{k: facts[k] for k in (
                              "tokens", "pairs_held", "pairs_total",
                              "step_flops", "experts_flops",
                              "experts_bytes")})
        runs = obs_trace.record_device_scopes(
            trace_dir, self.cfg["program"], SCOPES, parent=fit["fit"],
            kernels=KERNELS)
        if not runs:
            return {}
        out = {str(scope): statistics.median(
            r["scopes"].get(scope, 0) for r in runs) / 1e6
            for scope in (*SCOPES, None)}
        print("[lm_train] device ms a step by scope: " + ", ".join(
            f"{k} {v:.2f}" for k, v in out.items()), flush=True)
        return out
