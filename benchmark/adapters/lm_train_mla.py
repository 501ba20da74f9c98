"""Adapter ``lm_train_mla``: adapter ``lm_train`` for a ``joyai_llm_flash``
stack (latent attention, a dense and then routed gated-SiLU feed-forwards
beside a shared expert, an untied head, a multi-token-prediction module in
the loss).

Everything that makes the cell is ``lm_train``'s ``Cell``, unedited: the
routes tap (five routed layers here, the module's the last), the first step
held against AdamW written out, the float32 reference after the window,
``Trainer.fit(consume=True)``, the traced fit. That module names LFM2's
parts in a handful of module-level names, so this one loads a COPY OF ITS
OWN of the module (the other cells' copies are never touched) and replaces
exactly those, as ``lm_train_hybrid`` does: the named scopes, the gradient
groups, the FLOP module, the configuration's keys as ``Decoder`` takes
them. Its ``Cell`` then overrides two methods: ``setup`` (what the
reference cannot read from shapes) and ``_record_spans`` (``lm.step_work``
carries the causal products' and the module's needed work too;
``lm.attention.latent`` lies INSIDE ``lm.attention`` and the trace files an
operation under the outermost wanted scope, so it is read in a pass of its
own; the flash kernels' own time a step is summed from the trace's
``flash_attention.N`` operations and set against the compute roof).
"""

from __future__ import annotations

import os
import statistics

from benchmark import common, flops_mla, peaks, trace_reduce

SCOPES = ("lm.attention", "lm.mtp", "lm.dense_ff", "lm.shared_ff",
          "moe.route", "moe.experts", "lm.head")
# scopes nested inside one of SCOPES: a second pass over the trace
INNER_SCOPES = ("lm.attention.latent",)
# the traced name of pallas_ops.flash_attention: its kernels are the
# operations ``flash_attention.N`` of the trace's ``XLA Ops`` line
FLASH = "flash_attention"

# Why each gradient limit of configs/<config>.json "check" is what it is;
# the rest as lm_train.LIMITS_WHY. Each lies between two chip readings
# (PERF.md section 6): the largest that sound runs gave over their seeds,
# and the same step with the attention's score product on per-tensor-scaled
# e4m3 queries and keys (controls/mla_fp8_scores.py), which has to fail.
LIMITS_WHY = {
    "mla": "the five projections and two latent norms of every "
           "latent-attention block, the module's too: a rotation on "
           "half-split pairs, a scale of 1/sqrt(128) for 1/sqrt(192), a "
           "rotated key taken per head, or a score product in fp8 shows "
           "here first",
    "experts": "the grouped gated-SiLU products in bf16; a dropped pair "
               "moves this group first",
    "routers": "small leaves (2048 x 256) whose gradient comes only through "
               "the renormalised, scaled weights: scaling 1 for 2.5 shows "
               "here and in the experts",
    "shared_ff": "the shared expert every token passes, unweighted: relu2 "
                 "for the gated SiLU shows here",
    "dense_ff": "the one dense feed-forward (layer 0)",
    "mtp": "the module's own leaves: M over the two concatenated streams "
           "and its five norms; halves in the other order, the stream "
           "taken after the final norm, or a weight other than 0.3 shows "
           "here and nowhere else",
    "table": "embedding rows: the main stack's gradient and the module's "
             "(the embedding of the next token), summed",
    "head": "the untied head, both passes summed",
    "norms": "the RMSNorm weight of every layer of the stack and the final "
             "one",
}


def group_of(name: str) -> str:
    """The group a parameter's gradient is compared in."""
    if name == "embed":
        return "table"
    if name == "head":
        return "head"
    if ".attn." in name:
        return "mla"
    if ".shared." in name:
        return "shared_ff"
    if ".moe.w" in name:
        return "experts"
    if ".moe." in name:
        return "routers"
    if ".ff." in name:
        return "dense_ff"
    if name.startswith("mtp."):
        return "mtp"
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


def kernels_ms(planes: dict, program: str) -> dict:
    """Device ms a run of ``program`` of every operation of the first
    chip's ``XLA Ops`` line that is a flash kernel, by name (an event is
    named by its instruction's text, ``%flash_attention.56 = (bf16[...``)."""
    if not planes:
        return {}
    lines = planes[min(planes)]
    runs = sum(1 for name, _, _ in lines.get(trace_reduce.MODULES, ())
               if trace_reduce.program_name(name) == program)
    out: dict = {}
    for name, _, dur in lines.get(trace_reduce.OPS, ()):
        name = name.lstrip("%").split(" ", 1)[0]
        if name.split(".")[0] == FLASH:
            out[name] = out.get(name, 0.0) + dur / 1e6 / max(runs, 1)
    return out


# this cell's own copy of adapter lm_train, with LFM2's names replaced
base = common.load_module(
    os.path.join(common.ROOT, "adapters", "lm_train.py"),
    "benchmark_adapter_lm_train_as_mla")
base.SCOPES, base.group_of = SCOPES, group_of
base.flops_lm, base.decoder_config = flops_mla, decoder_config
TAP, tapped = base.TAP, base.tapped


def run(spec, drive):
    from tpudl.train import HorovodRunner
    from tpudl.zoo import lm_blocks

    if not hasattr(lm_blocks, "mla_op"):   # a program from before PR 34
        raise SystemExit("lm_train_mla: this program has no latent "
                         "attention (tpudl.zoo.lm_blocks.mla_op): it cannot "
                         f"run {spec.name}")
    return HorovodRunner(np=spec.chips).run(lambda ctx: drive(Cell(spec, ctx)))


class Cell(base.Cell):
    def setup(self):
        super().setup()
        cfg = self.cfg
        self.ref_kw = {"top_k": cfg["num_experts_per_tok"],
                       "held_first": cfg["experts_held"][0],
                       "norm_eps": cfg["rms_norm_eps"],
                       "rope_theta": float(cfg["rope_theta"]),
                       "routed_scaling_factor": float(
                           cfg["routed_scaling_factor"]),
                       "mtp_weight": float(cfg["mtp_weight"]),
                       "attention_rows": min(512, self.seq_len)}

    def _record_spans(self, trace_dir, steps, facts):
        """As ``lm_train``'s, with the causal products' and the module's
        needed work on the ``lm.step_work`` span and on the facts line
        (``facts`` is the dict that ``traced`` goes on to print), the
        nested scope read in a second pass over the trace, and the flash
        kernels' ms a step with their share of the compute roof."""
        import jax

        from tpudl.obs import get_tracer
        from tpudl.obs import trace as obs_trace

        facts.update(
            attention_flops=flops_mla.attention_flops(
                self.cfg, self.tokens, self.seq_len),
            mtp_flops=flops_mla.mtp_flops(self.cfg, self.tokens,
                                          self.seq_len))
        tracer = get_tracer()
        fit = obs_trace.traced_fit(tracer.spans(), steps)
        if fit is None:
            return {}
        for step in fit["steps"]:
            tracer.record("lm.step_work", step.start_ns, step.dur_ns,
                          parent=fit["fit"], **{k: facts[k] for k in (
                              "tokens", "pairs_held", "pairs_total",
                              "step_flops", "experts_flops",
                              "experts_bytes", "attention_flops",
                              "mtp_flops")})
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
        flash = kernels_ms(trace_reduce.load_planes(trace_dir), program)
        total, share = sum(flash.values()), 0.0
        facts["flash_kernels_ms"] = {**flash, "sum": total}
        if total:
            peak = peaks.peak(jax.devices()[0].device_kind,
                              "bf16_flops_per_s")
            share = facts["attention_flops"] / peak / (total / 1e3)
            facts["flash_kernels_compute_roof_share"] = share
        print("[lm_train_mla] device ms a step by scope "
              "(lm.attention.latent lies inside lm.attention): " + ", ".join(
                  f"{k} {v:.2f}" for k, v in out.items())
              + f"; the flash kernels {total:.2f} ms a step ("
              + ", ".join(f"{k} {v:.2f}" for k, v in sorted(flash.items()))
              + f"), {100 * share:.2f}% of the compute roof for their "
              "needed (causal, unrecomputed) work", flush=True)
        return out
