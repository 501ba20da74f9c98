"""tpudl.compile — the compile-cost subsystem (COMPILE.md).

XLA compilation is this backend's analogue of the reference's per-stage
Spark dispatch overhead: a minute or more per CNN program, paid again
on every process start and again for every novel batch shape. Three
tiers remove it:

1. the **persistent XLA compilation cache**
   (:func:`enable_compilation_cache`, placed by
   ``JAX_COMPILATION_CACHE_DIR``) — JAX's own disk cache of compiled
   binaries keyed by HLO;
2. the **AOT program store** (:class:`ProgramStore`,
   ``TPUDL_COMPILE_AOT``) — whole serialized executables keyed by
   fn-fingerprint + shapes + donate + mesh + backend, restored into a
   fresh process with no trace at all, background-compiled on miss;
3. **shape bucketing** (:class:`BucketLadder`,
   ``TPUDL_COMPILE_BUCKETS``) — ragged batch sizes snap to an
   O(log n) rung ladder so the store above has a bounded signature set
   to be warm FOR.

``Frame.map_batches`` consults all three (PIPELINE.md "Bucket pick &
AOT dispatch"); ``ImageBatchWarmup`` and
``TinyCausalLM.precompile_generate`` declare signatures ahead of
traffic; ``tpudl.jobs`` warm-starts the store on resume.
"""

from tpudl.compile.buckets import (BucketLadder, count_pad_rows, pad_to,
                                   resolve_ladder)
from tpudl.compile.cache import DEFAULT_CACHE_DIR, enable_compilation_cache
from tpudl.compile.store import (MANIFEST_NAME, MANIFEST_SCHEMA,
                                 MANIFEST_VERSION, ProgramStore,
                                 aot_enabled, backend_token,
                                 fn_fingerprint, get_program_store,
                                 reset_program_store, store_dir,
                                 warm_start)

__all__ = [
    "enable_compilation_cache", "DEFAULT_CACHE_DIR",
    "BucketLadder", "resolve_ladder", "pad_to", "count_pad_rows",
    "ProgramStore", "get_program_store", "reset_program_store",
    "aot_enabled", "store_dir", "warm_start", "fn_fingerprint",
    "backend_token", "MANIFEST_NAME", "MANIFEST_SCHEMA",
    "MANIFEST_VERSION",
]
