"""Token traffic for adapter ``lm_train``: registers the generator
``packed_tokens`` in ``traffic.GENERATORS`` on import, so that
``traffic.generate`` stays the one entry and ``traffic.py`` is not edited."""

from __future__ import annotations

from benchmark import traffic


def packed_tokens(n_batches, sequences_per_step, seq_len, doc_median,
                  doc_sigma, successors, stay, eos, seed, *, vocab, **_):
    """``n_batches`` int32 batches ``[sequences_per_step, seq_len]`` of
    documents packed end to end, ``eos`` after each and no document mask.

    Lengths are log-normal (median ``doc_median``, sigma ``doc_sigma`` of
    the logarithm, cut at ``seq_len``), drawn from generator 0 until they
    fill the batches, so every seed packs the same lengths in another
    order. Ids come from ``1 .. vocab - 1`` by a first-order Markov chain:
    every id has ``successors`` likely successors (a table drawn from the
    seed) and moves to one of them with probability ``stay``, else to any
    id; a document starts anywhere. A model learns the table within tens
    of steps, so the loss of a fixed batch falls inside a run's window."""
    import numpy as np

    total = n_batches * sequences_per_step * seq_len
    sizes, lengths = np.random.default_rng(0), []
    while sum(lengths) < total:
        lengths.append(int(np.clip(np.rint(
            sizes.lognormal(np.log(doc_median), doc_sigma)), 2, seq_len)))
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(lengths)
    ends = np.zeros(total, bool)          # positions that hold an eos
    ends[np.minimum(np.cumsum(lengths), total) - 1] = True
    table = rng.integers(1, vocab, size=(vocab, successors))
    pick = rng.integers(0, successors, size=total)
    anywhere = rng.integers(1, vocab, size=total)
    jump = rng.random(total) >= stay
    out = np.empty(total, np.int32)
    start, cur = True, 0
    for i in range(total):
        if ends[i]:
            out[i], start = eos, True
            continue
        cur = anywhere[i] if start or jump[i] else table[cur, pick[i]]
        out[i], start = cur, False
    return {"ids": list(out.reshape(n_batches, sequences_per_step, seq_len)),
            "documents": len(lengths),
            "median_document": float(np.median(lengths))}


traffic.GENERATORS["packed_tokens"] = packed_tokens
