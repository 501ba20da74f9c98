"""tpudl.compile — the compile-cost subsystem (COMPILE.md).

XLA compilation is this backend's analogue of the reference's per-stage
Spark dispatch overhead: a minute or more per CNN program, paid again
on every process start and again for every novel batch shape. Two
tiers remove it:

1. the **persistent XLA compilation cache**
   (:func:`enable_compilation_cache`, placed by
   ``JAX_COMPILATION_CACHE_DIR``) — JAX's own disk cache of compiled
   binaries keyed by HLO, so a fresh process that shares the directory
   loads every program it has seen instead of compiling it;
2. **shape bucketing** (:class:`BucketLadder`,
   ``TPUDL_COMPILE_BUCKETS``) — ragged batch sizes snap to an
   O(log n) rung ladder so the cache above has a bounded program set
   to be warm FOR.

``Frame.map_batches`` and ``TinyCausalLM.generate(prompt_buckets=)``
consult the ladder (PIPELINE.md "Bucket pick").
"""

from tpudl.compile.buckets import (BucketLadder, count_pad_rows, pad_to,
                                   resolve_ladder)
from tpudl.compile.cache import DEFAULT_CACHE_DIR, enable_compilation_cache

__all__ = [
    "enable_compilation_cache", "DEFAULT_CACHE_DIR",
    "BucketLadder", "resolve_ladder", "pad_to", "count_pad_rows",
]
