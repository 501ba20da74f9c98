"""Rung-bucketed dynamic batching for ragged serve payloads.

The featurize/UDF/embed half of the serve plane (the LM half lives in
slots.py): in-flight requests carry ragged row counts, and dispatching
each alone is the per-request-dispatch tax the paper's serving surface
exists to kill. :class:`RungBatcher` concatenates whatever is in
flight, pads the row count UP to the PR-15 :class:`BucketLadder` rung
(``pad_to`` — row-0 repeat, bitwise-honest: pad rows are stripped
before results fan back out), and dispatches ONE program. The rung set
is O(log n), so at steady state every dispatch replays an
already-traced signature — traceck-provably zero retraces.

``serve.batches`` counts dispatches; ``serve.batch_occupancy`` gauges
real rows over rung rows (the saturation SLO: > 0.5 under load);
padding cost lands on the shared ``compile.bucket_pad_rows`` counter.
"""

from __future__ import annotations

import numpy as np

from tpudl.obs import metrics as _metrics

__all__ = ["RungBatcher"]


class RungBatcher:
    """Pack ragged per-request payloads onto bucket rungs and dispatch
    one compiled program per batch.

    ``fn`` maps ``[N, ...] -> [N, ...]`` (leading dim preserved — the
    UDF/featurize/embed contract); ``buckets`` resolves through
    :func:`tpudl.compile.resolve_ladder` (``None`` = consult
    ``TPUDL_COMPILE_BUCKETS``, ``True`` = default pow2ish ladder)."""

    def __init__(self, fn, *, buckets=True):
        from tpudl.compile import resolve_ladder

        self._fn = fn
        self._ladder = resolve_ladder(buckets)

    def rung_for(self, n: int) -> int:
        return self._ladder.pick(int(n)) if self._ladder else int(n)

    def run(self, payloads) -> list:
        """Dispatch one padded batch for ``payloads`` (a list of
        ``[rows_i, ...]`` arrays, ragged in ``rows_i``) and split the
        result back per request, pad rows stripped."""
        from tpudl.compile import count_pad_rows, pad_to

        payloads = [np.asarray(p) for p in payloads]
        if not payloads:
            return []
        sizes = [int(p.shape[0]) for p in payloads]
        batch = (np.concatenate(payloads, axis=0) if len(payloads) > 1
                 else payloads[0])
        n = int(batch.shape[0])
        rung = self.rung_for(n)
        padded = pad_to(batch, rung)
        count_pad_rows(rung - n)
        out = np.asarray(self._fn(padded))[:n]
        _metrics.counter("serve.batches").inc()
        _metrics.gauge("serve.batch_occupancy").set(n / max(rung, 1))
        cuts = np.cumsum(sizes)[:-1]
        return [np.asarray(a) for a in np.split(out, cuts)] \
            if len(sizes) > 1 else [out]
