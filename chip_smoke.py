#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpudl still starts on the chip.

Drives the three main paths once, through the entry points a user calls,
at the full published width of models the repo supports, on every
device JAX shows (one chip or a four-chip host — same command):

- *featurize*: seeded mixed-size JPEGs → ``imageIO.readImages`` →
  ``DeepImageFeaturizer(InceptionV3, bfloat16, batch 256, mesh)``,
  checked against a ``mesh=None`` run of the same frame;
- *train*: ``HorovodRunner`` → ``ctx.trainer(...).fit`` on ResNet50
  (224×224×3, 1000 classes, global batch 128, bf16 compute on fp32
  masters); the loss on a fixed batch must fall;
- *lm*: ``TinyCausalLM`` at dim 2048 / 16 heads of 128 / 4 layers —
  the three COMPILED Pallas flash kernels against
  ``attention_reference``, ring attention over the mesh when there is
  more than one device, then ``ModelRegistry`` → ``Server`` answering
  ragged requests, compared with serial ``lm.generate``.

Weights are random from ``--seed``; there is no network and no weights
directory. It is ONE process (a chip belongs to one process) and it
exits non-zero unless JAX's platform is ``tpu`` and every phase passed
its check; no phase's exception is caught. The line before last on
stdout is one JSON object of facts (per-phase compile and run seconds,
compile-cache entries and hits, what each check measured); the last
line is the verdict, exactly ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}`` and nothing else.

``--rehearse`` runs the same control flow at toy sizes on any backend
(Pallas interpreted off-TPU) so it can be tried before chip time is
spent. It is not a fallback: its facts line says ``"rehearsal": true``
and it prints no verdict line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REAL = dict(
    n_images=1024, image_sides=(160, 512), feat_batch=256,
    train_side=224, train_classes=1000, train_batch=128, train_steps=20,
    lm=dict(vocab=32768, dim=2048, heads=16, layers=4, max_len=4096),
    lm_tokens=(2, 2048), n_requests=16, prompt_lens=(16, 512), max_new=32,
    slots=8, cache_len=1024, prompt_rungs="64,256,512",
)
TOY = dict(
    n_images=24, image_sides=(24, 72), feat_batch=8,
    train_side=32, train_classes=10, train_batch=8, train_steps=3,
    lm=dict(vocab=256, dim=128, heads=8, layers=2, max_len=256),
    lm_tokens=(2, 64), n_requests=6, prompt_lens=(3, 24), max_new=4,
    slots=2, cache_len=32, prompt_rungs="8,16,24",
)

# jax.monitoring event names (jax/_src/dispatch.py, compilation_cache.py)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Meter:
    """Per-phase wall / compile seconds and persistent-cache hit and
    miss counts, fed by jax.monitoring (process-wide, so compiles on the
    executor's dispatch threads are counted too)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = self.misses = 0
        self.phases: dict = {}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def run(self, name, fn, *args):
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        facts = fn(*args)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        rec = {"wall_s": round(wall, 2), "compile_s": round(comp, 2),
               "run_s": round(wall - comp, 2),
               "cache_hits": self.hits - h0,
               "cache_misses": self.misses - m0, **facts}
        self.phases[name] = rec
        print(f"[{name}] PASS wall {rec['wall_s']}s = compile "
              f"{rec['compile_s']}s + run {rec['run_s']}s; persistent "
              f"cache {rec['cache_hits']} hit / {rec['cache_misses']} "
              f"miss", flush=True)
        return rec


def check(cond, msg):
    """A failed check ends the run: the smoke never reports a phase it
    did not see pass (a plain raise — ``assert`` dies under -O)."""
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED — {msg}")


def rel_l2(a, b):
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def every_device_holds(tree, devices, what):
    """Each leaf of ``tree`` has an addressable shard on EVERY device."""
    import jax

    want = set(devices)
    for leaf in jax.tree.leaves(tree):
        got = {s.device for s in leaf.addressable_shards}
        check(leaf.sharding.device_set == want and got == want,
              f"{what}: a {leaf.shape} leaf lives on "
              f"{sorted(d.id for d in got)}, not on all of "
              f"{sorted(d.id for d in want)}")


def peak_bytes(devices, rehearse):
    """``peak_bytes_in_use`` per device; must be > 0 everywhere on the
    chip (a CPU backend reports no memory stats — rehearsal only)."""
    peaks = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            check(rehearse, f"device {d.id} reports no memory_stats")
            peaks[d.id] = None
            continue
        peaks[d.id] = int(stats["peak_bytes_in_use"])
        check(peaks[d.id] > 0, f"device {d.id} peak_bytes_in_use == 0: "
              "nothing ever ran or landed there")
    return peaks


# ---------------------------------------------------------------- featurize

def write_jpegs(directory, n, sides, seed):
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    lo, hi = sides
    for i in range(n):
        h, w = (int(v) for v in rng.integers(lo, hi + 1, size=2))
        # low-frequency content so the JPEGs look like photographs to
        # the codec (pure noise is its worst case), unique per file
        base = rng.integers(0, 256, size=(h // 8 + 1, w // 8 + 1, 3),
                            dtype=np.uint8)
        img = Image.fromarray(base).resize((w, h), Image.BILINEAR)
        img.save(os.path.join(directory, f"img_{i:05d}.jpg"), quality=90)


def phase_featurize(cfg, mesh, seed, rehearse):
    import numpy as np

    import tpudl
    from tpudl import native, obs
    from tpudl.image import imageIO

    had_lib = os.path.exists(native.lib_path())
    with tempfile.TemporaryDirectory(prefix="chip_smoke_jpeg_") as d:
        write_jpegs(d, cfg["n_images"], cfg["image_sides"], seed)
        frame = imageIO.readImages(d)

        def featurize(use_mesh):
            feat = tpudl.DeepImageFeaturizer(
                inputCol="image", outputCol="features",
                modelName="InceptionV3", weights="random",
                batchSize=cfg["feat_batch"], computeDtype="bfloat16",
                mesh=use_mesh)
            t0 = time.perf_counter()
            out = feat.transform(frame)
            rows = list(out["features"])
            return rows, time.perf_counter() - t0, \
                obs.last_pipeline_report()

        rows, wall_mesh, rep = featurize(mesh)
        peaks = peak_bytes(mesh.devices.flat, rehearse)
        ref_rows, wall_ref, _ = featurize(None)

    decoder = "tpudl.native" if native.available() else "PIL"
    check(not any(r is None for r in rows), "featurize produced None rows")
    got = np.stack([np.asarray(r) for r in rows])
    ref = np.stack([np.asarray(r) for r in ref_rows])
    check(got.shape == (cfg["n_images"], 2048),
          f"features shape {got.shape} != ({cfg['n_images']}, 2048)")
    check(np.isfinite(got).all(), "non-finite features")
    check(float(got.std(axis=0).max()) > 0.0,
          "features are constant across images")
    check(rep["mesh"] == {k: int(v) for k, v in mesh.shape.items()},
          f"pipeline report mesh {rep['mesh']} != {dict(mesh.shape)}")
    # Mesh parity. The verify skill states rtol=1e-3 for FLOAT32
    # full-zoo mesh parity (partitioned-conv reassociation). This run
    # computes in bfloat16 (eps 2^-8 = 3.9e-3): a different per-device
    # batch changes XLA's conv tiling and so where values round, and a
    # per-element rtol has no meaning next to a feature near zero.
    # Parity is therefore stated against the feature scale: worst
    # element within 2 bf16 ulps of the largest feature, the whole
    # matrix within one (measured on four v5e chips: 0.4 ulp and
    # 7.8e-4; on one chip the two runs are bitwise equal). A permuted,
    # padded-in or dropped row is an O(1) error.
    scale = float(np.abs(ref).max())
    worst = float(np.abs(got - ref).max()) / scale
    overall = rel_l2(got, ref)
    check(worst <= 2 * 2.0 ** -8 and overall <= 2.0 ** -8,
          f"mesh vs mesh=None parity: worst {worst:.3e} of scale, "
          f"rel-l2 {overall:.3e}")
    print(f"[featurize] {got.shape} finite; decoder={decoder}"
          f"{'' if had_lib else ' (built from decode.cpp this run)'}; "
          f"report: executor={rep['executor']} mesh={rep['mesh']} "
          f"fuse_steps={rep['fuse_steps']} "
          f"dispatch_depth={rep['dispatch_depth']} "
          f"wire_codec={rep['wire_codec']} donate={rep['donate']} "
          f"device_cache={rep['device_cache']} "
          f"first_dispatch_s="
          f"{rep['stage_calls'].get('first_dispatch_s', 0):.1f}; parity "
          f"vs mesh=None worst {worst:.2e} rel-l2 {overall:.2e}",
          flush=True)
    return {"rows": int(got.shape[0]), "decoder": decoder,
            "mesh_wall_s": round(wall_mesh, 2),
            "single_wall_s": round(wall_ref, 2),
            "parity_worst": worst, "parity_rel_l2": overall,
            "report": {k: rep[k] for k in (
                "executor", "mesh", "fuse_steps", "dispatch_depth",
                "wire_codec", "donate", "device_cache")},
            "peak_bytes": peaks}


# -------------------------------------------------------------------- train

def phase_train(cfg, devices, seed, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpudl.train import HorovodRunner, with_compute_dtype
    from tpudl.zoo.registry import getKerasApplicationModel

    side, n_cls, batch = (cfg["train_side"], cfg["train_classes"],
                          cfg["train_batch"])
    bands = 8  # separable by construction: class = which band is bright
    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for _ in range(4):
        cls = rng.integers(0, bands, size=batch)
        x = rng.integers(0, 96, size=(batch, side, side, 3), dtype=np.uint8)
        for i, c in enumerate(cls):
            x[i, c * side // bands:(c + 1) * side // bands] += 128
        xs.append(x)
        ys.append(np.eye(n_cls, dtype=np.float32)[cls])

    model = getKerasApplicationModel("ResNet50")
    params = model.init(seed, image_size=(side, side))
    if n_cls != 1000:  # rehearsal only: a small head on the full graph
        params["predictions"] = {
            "kernel": np.zeros((2048, n_cls), np.float32),
            "bias": np.zeros((n_cls,), np.float32)}

    def loss_fn(p, x, y):
        x = (x.astype(jnp.bfloat16) - 127.5) / 127.5
        logits = model.predict(p, x)
        logp = jnp.log(jnp.clip(logits.astype(jnp.float32), 1e-7, 1.0))
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    # bf16 compute on fp32 MASTER weights (training the masters in bf16
    # stalls once updates drop under the 8-bit mantissa)
    train_loss = with_compute_dtype(loss_fn, jnp.bfloat16)

    def train_fn(ctx):
        every = list(ctx.mesh.devices.flat)
        eval_fn = jax.jit(train_loss)
        fixed = ctx.shard_batch((xs[0], ys[0]))
        every_device_holds(fixed, every, "train batch")
        check(fixed[0].addressable_shards[0].data.shape[0]
              == batch // len(every), "batch is not split over the mesh")
        trainer = ctx.trainer(train_loss, optax.sgd(0.05))
        before = float(eval_fn(ctx.replicate(params), *fixed))
        t0 = time.perf_counter()
        new_params, _opt, history = trainer.fit(
            params, lambda step: (xs[step % 4], ys[step % 4]),
            steps=cfg["train_steps"])
        jax.block_until_ready(new_params)
        fit_wall = round(time.perf_counter() - t0, 2)
        every_device_holds(new_params, every, "trained params")
        return {"fit_wall_s": fit_wall, "loss_before": before,
                "loss_after": float(eval_fn(new_params, *fixed)),
                "last_step_loss": history[-1]["loss"],
                "mesh": {k: int(v) for k, v in ctx.mesh.shape.items()}}

    # np is the TOTAL chip count (np=-1 is the reference's one-device
    # debug mode and would leave the other chips idle)
    facts = HorovodRunner(np=len(devices)).run(train_fn)
    b, a = facts["loss_before"], facts["loss_after"]
    check(np.isfinite(b) and np.isfinite(a), f"loss not finite: {b}, {a}")
    check(a < b, f"fixed-batch loss did not fall over "
          f"{cfg['train_steps']} steps: {b:.4f} -> {a:.4f}")
    facts["peak_bytes"] = peak_bytes(devices, rehearse)
    print(f"[train] ResNet50 {side}x{side} batch {batch} on mesh "
          f"{facts['mesh']}: fixed-batch loss {b:.4f} -> {a:.4f} over "
          f"{cfg['train_steps']} steps (fit {facts['fit_wall_s']}s incl. "
          f"compile)", flush=True)
    return facts


# ----------------------------------------------------------------------- lm

def _sq_of(attn):
    import jax.numpy as jnp

    return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)


def _qkv(rng, b, s, heads, dh):
    import numpy as np

    return tuple(rng.normal(size=(b, s, heads, dh)).astype(np.float32)
                 for _ in range(3))


def lm_kernels(lm, params, cfg, devices, rng, rehearse):
    """The three flash kernels, COMPILED, against attention_reference:
    their math at full precision, then the LM loss and all its grads
    through them at the precision users run."""
    import jax
    import numpy as np

    from tpudl.attention import attention_reference
    from tpudl.pallas_ops import flash_attention

    b, s_tok = cfg["lm_tokens"]
    heads, dh = lm.heads, lm.dim // lm.heads
    # (a) the LM's own attention shape: S = tokens - 1 is no multiple
    # of 128, so this is the padded-tile path
    q, k, v = jax.device_put(_qkv(rng, b, s_tok - 1, heads, dh), devices[0])
    flash_sq = _sq_of(lambda q, k, v: flash_attention(
        q, k, v, causal=True, precision=jax.lax.Precision.HIGHEST))
    dense_sq = _sq_of(lambda q, k, v: attention_reference(
        q, k, v, causal=True))
    got, g_got = jax.jit(jax.value_and_grad(flash_sq, (0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(
            jax.value_and_grad(dense_sq, (0, 1, 2)))(q, k, v)
    kern = [rel_l2(got, want)] + [rel_l2(a, w)
                                  for a, w in zip(g_got, g_want)]
    # f32 math both sides, different summation order: 1e-3 leaves two
    # orders over the 3e-5 measured on the v5e and still catches a
    # mask, scale or tile-skip error (those are O(1))
    check(max(kern) <= 1e-3, f"flash fwd/dq/dkv vs attention_reference "
          f"(highest precision): rel-l2 {kern}")

    # (b) TPU default precision: bf16 MXU passes on both sides
    toks = jax.device_put(rng.integers(
        0, lm.vocab, size=(b, s_tok), dtype=np.int32), devices[0])
    lowered = jax.jit(jax.value_and_grad(
        lm.loss_fn(use_pallas=True))).lower(params, toks)
    n_mosaic = lowered.as_text().count("tpu_custom_call")
    check(rehearse or n_mosaic >= 3,
          f"lowered LM loss+grad holds {n_mosaic} Mosaic custom calls "
          f"(want fwd+dq+dkv): the kernels did not compile for the TPU")
    loss_p, grad_p = lowered.compile()(params, toks)
    loss_d, grad_d = jax.jit(jax.value_and_grad(lm.loss_fn()))(params, toks)
    loss_p, loss_d = float(loss_p), float(loss_d)
    leaf_err = jax.tree.leaves(jax.tree.map(rel_l2, grad_p, grad_d))
    check(np.isfinite(loss_p) and abs(loss_p - loss_d) <= 1e-3 * abs(loss_d),
          f"LM loss flash {loss_p} vs dense {loss_d}")
    # only attention differs between the two programs, and both round
    # matmul inputs to bf16 (eps 2^-8 = 3.9e-3): measured worst leaf
    # 1.8e-2 on the v5e; 5e-2 is ~13 eps
    check(max(leaf_err) <= 5e-2,
          f"LM grads flash vs dense: worst leaf rel-l2 {max(leaf_err)}")
    print(f"[lm] kernels vs attention_reference (highest) rel-l2 "
          f"{max(kern):.2e}; LM loss {loss_p:.4f} (dense {loss_d:.4f}), "
          f"worst grad leaf rel-l2 {max(leaf_err):.2e}; {n_mosaic} Mosaic "
          f"custom calls in the lowered text", flush=True)
    return {"kernel_rel_l2": kern, "mosaic_calls": n_mosaic,
            "lm_loss": loss_p, "lm_grad_rel_l2_max": max(leaf_err)}


def lm_ring(lm, cfg, devices, rng):
    """More than one device: the compiled shard_map + pallas_call ring
    over the whole mesh against dense attention on one device."""
    import jax

    from tpudl import mesh as M
    from tpudl.attention import (attention_reference, ring_attention,
                                 shard_sequence)

    mesh = M.build_mesh(devices=devices)
    b, s = cfg["lm_tokens"]  # S divides by the ring size
    q, k, v = _qkv(rng, b, s, lm.heads, lm.dim // lm.heads)
    qs, ks, vs = shard_sequence((q, k, v), mesh)
    every_device_holds((qs, ks, vs), devices, "ring q/k/v")
    ring_sq = _sq_of(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, use_pallas=True))
    dense_sq = _sq_of(lambda q, k, v: attention_reference(
        q, k, v, causal=True))
    r_val, r_grad = jax.jit(
        jax.value_and_grad(ring_sq, (0, 1, 2)))(qs, ks, vs)
    d_val, d_grad = jax.jit(jax.value_and_grad(dense_sq, (0, 1, 2)))(
        *jax.device_put((q, k, v), devices[0]))
    ring = [rel_l2(r_val, d_val)] + [rel_l2(a, w)
                                     for a, w in zip(r_grad, d_grad)]
    # default precision on both sides (bf16 passes): the same
    # comparison measured 5e-3 on one chip; 2e-2 is ~5 eps
    check(max(ring) <= 2e-2, f"ring_attention(use_pallas) over "
          f"{len(devices)} devices vs dense: rel-l2 {ring}")
    print(f"[lm] ring_attention(use_pallas) S={s} over {len(devices)} "
          f"devices vs dense rel-l2 {max(ring):.2e}", flush=True)
    return {"ring_rel_l2": ring}


def lm_serve(lm, host_params, params, cfg, devices, rng):
    """ModelRegistry → Server over ragged requests, against serial
    generate. Every chip holds its Megatron shard of the weights and
    its heads of the KV cache (a 1-wide model axis is the no-op arm)."""
    import jax
    import numpy as np

    from tpudl import mesh as M
    from tpudl.serve import ModelRegistry, Server

    tp_mesh = M.build_mesh(n_data=1, n_model=len(devices), devices=devices)
    tp_params = lm.shard_params(host_params, tp_mesh)
    every_device_holds(tp_params, devices, "serve params")
    rungs = cfg["prompt_rungs"]
    reg = ModelRegistry()
    entry = reg.add_model("default", lm, tp_params, slots=cfg["slots"],
                          cache_len=cfg["cache_len"], prompt_buckets=rungs,
                          mesh=tp_mesh, tp=True)
    every_device_holds(entry.engine._cache, devices, "serve KV cache")
    srv = Server(reg)
    lo, hi = cfg["prompt_lens"]
    lens = [lo, hi] + [int(n) for n in rng.integers(
        lo, hi + 1, size=cfg["n_requests"] - 2)]
    prompts = [rng.integers(0, lm.vocab, size=(1, n), dtype=np.int32)
               for n in lens]
    max_new = cfg["max_new"]
    reqs = [srv.submit(p, max_new) for p in prompts]
    srv._stop.set()  # drain synchronously: the deterministic mode
    summary = srv.run()
    check(summary["completed"] == len(prompts),
          f"server completed {summary['completed']}/{len(prompts)}")

    # On the CPU rig served tokens equal serial generate's bitwise
    # (tests/test_serve.py). On the chip the batch-1 and slot-batch
    # programs tile their matmuls differently, and with random weights
    # some greedy argmax is a near-tie that the last bits decide — so a
    # stream may part from the reference ONLY where the reference's own
    # logits for the two tokens are within 2 bf16 ulps of the top
    # logit; every token before that point must be identical.
    logits_fn = jax.jit(lambda p, t: lm.apply(p, t))
    exact, ties = 0, []
    for p, req in zip(prompts, reqs):
        got_t = np.asarray(req.result())
        ref_t = np.asarray(lm.generate(
            tp_params, p, max_new, prompt_buckets=rungs, mesh=tp_mesh,
            tp=True))[0]
        check(got_t.shape == (max_new,), f"served {got_t.shape} tokens")
        if np.array_equal(got_t, ref_t):
            exact += 1
            continue
        i = int(np.argmax(got_t != ref_t))
        n = p.shape[1] + i
        seq = np.zeros((1, cfg["cache_len"]), np.int32)  # one program
        seq[0, :n] = np.concatenate([p[0], ref_t[:i]])
        row = np.asarray(logits_fn(params, seq)[0, n - 1], np.float32)
        gap = abs(float(row[ref_t[i]] - row[got_t[i]]))
        tol = 2.0 ** -7 * float(np.abs(row).max())
        check(gap <= tol, f"prompt len {p.shape[1]}: served token "
              f"{got_t[i]} != generate's {ref_t[i]} at step {i}, and the "
              f"logit gap {gap:.4f} exceeds the tie tolerance {tol:.4f}")
        ties.append({"prompt_len": int(p.shape[1]), "step": i,
                     "gap": round(gap, 5), "tol": round(tol, 5)})
    print(f"[lm] served {len(prompts)} ragged requests (prompts "
          f"{min(lens)}-{max(lens)}, max_new {max_new}, {cfg['slots']} "
          f"slots, mesh {dict(tp_mesh.shape)}): {exact} token-exact vs "
          f"lm.generate, {len(ties)} parted at a near-tie {ties}",
          flush=True)
    return {"served": len(prompts), "exact": exact, "tie_flips": ties,
            "serve_ticks": summary["ticks"],
            "serve_wall_s": summary["wall_s"]}


def phase_lm(cfg, devices, seed, rehearse):
    import jax
    import numpy as np

    from tpudl.zoo.transformer import TinyCausalLM

    lm = TinyCausalLM(**cfg["lm"])
    host_params = lm.init(seed)
    params = jax.device_put(host_params, devices[0])
    rng = np.random.default_rng(seed + 1)
    facts = lm_kernels(lm, params, cfg, devices, rng, rehearse)
    if len(devices) > 1:
        facts.update(lm_ring(lm, cfg, devices, rng))
    facts.update(lm_serve(lm, host_params, params, cfg, devices, rng))
    facts["peak_bytes"] = peak_bytes(devices, rehearse)
    return facts


# --------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend, to try the control "
                         "flow; never prints ok: true")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: platform={dev['platform']} kind={dev['kind']!r} "
          f"count={dev['count']} jax={jax.__version__}", flush=True)
    if dev["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(
            f"chip_smoke: platform is {dev['platform']!r}, not 'tpu' — "
            f"this check only vouches for the chip (use --rehearse to "
            f"try the control flow elsewhere)")

    from tpudl import compile as tcompile
    from tpudl import mesh as M
    from tpudl.data import device_cache

    cache_dir = tcompile.enable_compilation_cache()
    check(cache_dir is not None, "compilation cache could not be enabled")
    entries_before = len(os.listdir(cache_dir))
    placed = "JAX_COMPILATION_CACHE_DIR" in os.environ
    print(f"chip_smoke: compile cache {cache_dir} "
          f"({'placed by JAX_COMPILATION_CACHE_DIR' if placed else 'fixed in-checkout path'}), "
          f"{entries_before} entries", flush=True)
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    hbm_branch = ("TPUDL_DATA_HBM_BUDGET_MB"
                  if os.environ.get("TPUDL_DATA_HBM_BUDGET_MB")
                  else "reported bytes_limit x 0.25" if limit
                  else "ASSUMED default (backend reports no bytes_limit)")
    print(f"chip_smoke: HBM residency budget "
          f"{device_cache.budget_bytes()} bytes from {hbm_branch}",
          flush=True)

    cfg = TOY if args.rehearse else REAL
    mesh = M.build_mesh()
    check(mesh.devices.size == len(devices), "mesh leaves devices idle")
    meter = Meter()
    t0 = time.perf_counter()
    meter.run("featurize", phase_featurize, cfg, mesh, args.seed,
              args.rehearse)
    meter.run("train", phase_train, cfg, devices, args.seed, args.rehearse)
    meter.run("lm", phase_lm, cfg, devices, args.seed, args.rehearse)
    result = {
        "device": dev, "jax": jax.__version__, "seed": args.seed,
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": len(os.listdir(cache_dir)),
                          "hits": meter.hits, "misses": meter.misses},
        "hbm_budget_from": hbm_branch,
        "phases": meter.phases,
    }
    if args.rehearse:  # facts only: a rehearsal has no verdict
        print(json.dumps({"rehearsal": True, **result}), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
