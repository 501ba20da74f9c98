#!/usr/bin/env python
"""Fine-tune a tiny causal LM over a STRING column and generate from it
through the text pipeline (TEXT.md).

    python examples/generate_text.py

Beyond the reference's capability surface (sparkdl has no LM path),
end to end on the PR-19 text subsystem:

1. a fingerprintable ByteTokenizer, persisted + verified as a vocab
   manifest (tools/validate_text.py audits the same file),
2. ``lm_dataset`` — tokenize + dense-pack on the prepare pool,
   TokenCodec uint16 ids on the wire, HBM-resident epoch replay
   (watch ``text.tokenize.calls`` / ``data.wire.bytes_shipped`` stay
   FLAT in epoch 2),
3. ``LMGenerator`` — completions over a ragged prompt column, every
   dispatch snapped to the bucket ladders (zero retraces once warm).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax


def main():
    import jax.numpy as jnp
    import optax

    from tpudl import obs
    from tpudl.frame import Frame
    from tpudl.ml import LMGenerator
    from tpudl.text import ByteTokenizer, lm_dataset, load_vocab
    from tpudl.zoo.transformer import TinyCausalLM

    # -- 1. tokenizer: deterministic identity, persisted manifest ------
    tok = ByteTokenizer()
    vocab_path = "/tmp/tpudl_example_vocab.json"
    tok.save(vocab_path)
    tok = load_vocab(vocab_path)  # format + fingerprint verified
    print(f"tokenizer {tok!r} (manifest: {vocab_path})")

    # -- 2. tokenized fine-tune: a string column IS the training set ---
    seq, batch = 32, 8
    corpus = [("the quick brown fox jumps over the lazy dog "
               f"episode {i:02d}")[: seq - 1] for i in range(32)]
    frame = Frame({"text": np.array(corpus, dtype=object)})
    lm = TinyCausalLM(vocab=tok.vocab_size, dim=64, heads=4, layers=2,
                      max_len=seq)
    params = jax.tree.map(jnp.asarray, lm.init(0))
    ds = lm_dataset(frame, "text", tok, seq_len=seq, batch_size=batch,
                    device_cache=True)

    def counters():
        snap = obs.snapshot()
        return {k: int((snap.get(k) or {}).get("value") or 0)
                for k in ("text.tokenize.calls",
                          "data.wire.bytes_shipped")}

    loss = lm.loss_fn()
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, o, wire):
        tokens = wire.astype(jnp.int32)  # the TokenCodec prologue
        l, g = jax.value_and_grad(loss)(p, tokens)
        updates, o = opt.update(g, o)
        return optax.apply_updates(p, updates), o, l

    for epoch in range(2):
        c0 = counters()
        for (wire,) in ds.iter_epoch(epoch):
            params, opt_state, l = step(params, opt_state, wire)
        c1 = counters()
        print(f"epoch {epoch}: loss {float(l):.3f}, "
              f"{c1['text.tokenize.calls'] - c0['text.tokenize.calls']}"
              f" tokenize calls, "
              f"{c1['data.wire.bytes_shipped'] - c0['data.wire.bytes_shipped']}"
              " wire bytes"
              + ("  <- warm replay: both zero" if epoch else ""))

    # -- 3. ragged prompts -> completions, bucketed programs ----------
    gen = LMGenerator(inputCol="prompt", outputCol="story", model=lm,
                      weights=params, tokenizer=tok, maxNew=12,
                      promptBuckets="pow2", batchSize=4)
    prompts = Frame({"prompt": np.array(
        ["the quick", "the quick brown fox", "episode", "the lazy d"],
        dtype=object)})
    out = gen.transform(prompts)
    for p, s in zip(prompts["prompt"], out["story"]):
        print(f"  {p!r:24} -> {s!r}")
    sampled = LMGenerator(inputCol="prompt", outputCol="story", model=lm,
                          weights=params, tokenizer=tok, maxNew=12,
                          temperature=0.7, seed=1).transform(prompts)
    print("sampled:", [repr(s) for s in sampled["story"]])


if __name__ == "__main__":
    main()
