"""Adapter ``lm_train_hybrid``: adapter ``lm_train`` for a ``nemotron_h``
stack (Mamba-2 mixers, attention, routed ``relu2`` experts beside a shared
expert, one part a layer, an untied head).

Everything that makes the cell is ``lm_train``'s ``Cell``, unedited: the
routes tap, the first step held against AdamW written out, the float32
reference after the window, ``Trainer.fit(consume=True)``, the traced fit.
That module names LFM2's parts in a handful of module-level names, so this
one loads a COPY OF ITS OWN of the module (the LFM2 cell's copy is never
touched) and replaces exactly those: the named scopes, the gradient groups,
the FLOP module, the configuration's keys as ``Decoder`` takes them. Its
``Cell`` then overrides two methods: ``setup`` (the mixer's ``A_log``,
``dt_bias`` and ``D`` stay float32 under the step's cast; what the
reference cannot read from shapes) and ``_record_spans`` (``lm.step_work``
carries the scan's needed work too; ``lm.ssm.scan`` lies INSIDE ``lm.ssm``
and the trace files an operation under the outermost wanted scope, so it
is read in a pass of its own).
"""

from __future__ import annotations

import os
import statistics

from benchmark import common, flops_hybrid

SCOPES = ("lm.ssm", "lm.attention", "lm.shared_ff", "moe.route",
          "moe.experts", "lm.head")
# scopes nested inside one of SCOPES: a second pass over the trace
INNER_SCOPES = ("lm.ssm.scan",)

# Why each gradient limit of configs/<config>.json "check" is what it is;
# the rest as lm_train.LIMITS_WHY. Each lies between two chip readings
# (PERF.md section 6): the largest that sound runs gave over their seeds,
# and the same step with the scan's decays, sums and carried state in
# bfloat16 (controls/hybrid_bf16_scan.py), which has to fail.
LIMITS_WHY = {
    "ssm": "the mixers' projections, convolution, per-head scalars "
           "(A_log, dt_bias, D) and gated norm: a missing D skip, a gate "
           "after the norm or a recurrence in bfloat16 shows here first",
    "attention": "q/k/v/o of the one attention layer: a rotation or a head "
                 "norm that the family does not apply",
    "experts": "the grouped relu2 products in bf16; a dropped pair moves "
               "this group first",
    "routers": "small leaves (2688 x 128) whose gradient comes only through "
               "the renormalised, scaled weights: scaling 1 for 2.5 shows "
               "here and in the experts",
    "shared_ff": "the shared expert every token passes, unweighted",
    "table": "embedding rows (the head is untied: a group of its own)",
    "head": "the untied head",
    "norms": "the RMSNorm weight of every layer and the final one",
}


def group_of(name: str) -> str:
    """The group a parameter's gradient is compared in."""
    if name == "embed":
        return "table"
    if name == "head":
        return "head"
    if ".ssm." in name:
        return "ssm"
    if ".attn." in name:
        return "attention"
    if ".shared." in name:
        return "shared_ff"
    if ".moe.w" in name:
        return "experts"
    if ".moe." in name:
        return "routers"
    if name.endswith("norm"):
        return "norms"
    raise KeyError(name)


def decoder_config(cfg: dict) -> dict:
    """The configuration file's keys as ``Decoder`` takes them: the file's
    ``n_routed_experts`` / ``vocab_size`` count what is held here, the
    decoder's what is published."""
    out = {k: v for k, v in cfg.items() if k != "published"}
    out["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    out["vocab_size"] = cfg["published"]["vocab_size"]
    return out


# this cell's own copy of adapter lm_train, with LFM2's names replaced
base = common.load_module(
    os.path.join(common.ROOT, "adapters", "lm_train.py"),
    "benchmark_adapter_lm_train_as_hybrid")
base.SCOPES, base.group_of = SCOPES, group_of
base.flops_lm, base.decoder_config = flops_hybrid, decoder_config
TAP, tapped = base.TAP, base.tapped


def run(spec, drive):
    from tpudl.train import HorovodRunner
    from tpudl.zoo import lm_blocks

    if not hasattr(lm_blocks, "mamba2_op"):   # a program from before PR 32
        raise SystemExit("lm_train_hybrid: this program has no Mamba-2 "
                         "mixer (tpudl.zoo.lm_blocks.mamba2_op): it cannot "
                         f"run {spec.name}")
    return HorovodRunner(np=spec.chips).run(lambda ctx: drive(Cell(spec, ctx)))


class Cell(base.Cell):
    def setup(self):
        import optax

        from tpudl.train import with_compute_dtype

        super().setup()
        cfg, o = self.cfg, self.cfg["optimizer"]
        # the same step, with the recurrence's scalars left in float32
        optimizer = getattr(optax, o["name"])(
            o["learning_rate"], b1=o["b1"], b2=o["b2"],
            weight_decay=o["weight_decay"], mask=self.lm.decay_mask)
        self.trainer = self.ctx.trainer(
            with_compute_dtype(tapped(self.lm.loss_fn(
                remat=cfg["remat"], loss_chunk=cfg["loss_chunk"],
                with_routes=True)), self.dtype,
                keep=self.lm.float32_leaves), optimizer)
        self.ref_kw = {"top_k": cfg["num_experts_per_tok"],
                       "held_first": cfg["experts_held"][0],
                       "norm_eps": cfg["norm_eps"],
                       "routed_scaling_factor": float(
                           cfg["routed_scaling_factor"]),
                       "head_dim": cfg["head_dim"],
                       "n_groups": cfg["n_groups"],
                       "attention_rows": min(512, self.seq_len),
                       "ssm_rows": min(128, self.seq_len)}

    def _record_spans(self, trace_dir, steps, facts):
        """As ``lm_train``'s, with the scan's needed work on the
        ``lm.step_work`` span and on the facts line (``facts`` is the
        dict that ``traced`` goes on to print), and the nested scopes
        read in a second pass over the trace."""
        from tpudl.obs import get_tracer
        from tpudl.obs import trace as obs_trace

        work = flops_hybrid.ssm_work(self.cfg, self.tokens)
        facts.update(ssm_flops=work["flops"], ssm_bytes=work["bytes"])
        tracer = get_tracer()
        fit = obs_trace.traced_fit(tracer.spans(), steps)
        if fit is None:
            return {}
        for step in fit["steps"]:
            tracer.record("lm.step_work", step.start_ns, step.dur_ns,
                          parent=fit["fit"], **{k: facts[k] for k in (
                              "tokens", "pairs_held", "pairs_total",
                              "step_flops", "experts_flops",
                              "experts_bytes", "ssm_flops", "ssm_bytes")})
        program, out = self.cfg["program"], {}
        for scopes, kernels in ((SCOPES, base.KERNELS), (INNER_SCOPES, None)):
            runs = obs_trace.record_device_scopes(
                trace_dir, program, scopes, parent=fit["fit"],
                kernels=kernels)
            if not runs:
                return {}
            for scope in (*scopes, *((None,) if kernels else ())):
                out[str(scope)] = statistics.median(
                    r["scopes"].get(scope, 0) for r in runs) / 1e6
        print("[lm_train_hybrid] device ms a step by scope (lm.ssm.scan "
              "lies inside lm.ssm): " + ", ".join(
                  f"{k} {v:.2f}" for k, v in out.items()), flush=True)
        return out
