"""The registry of ``tpudl.obs`` metric names.

Every counter/gauge/histogram name the codebase publishes is declared
here, exactly once, so the name schema is reviewable in one place
(ANALYSIS.md). Consumers:

1. the static checker (rule ``undeclared-metric``): a literal (or
   f-string) name at a ``counter(...)``/``gauge(...)``/
   ``histogram(...)`` call site must match a declaration — dashboards
   key on these strings, so an unreviewed rename is a silent break;
2. ``tools/validate_metrics.py``: the JSONL-sink validator can
   cross-check emitted names against this registry (opt-in
   ``--check-names`` — sink files may legitimately carry user-defined
   metrics);
3. the round-trip test (tests/test_analysis.py): declared ⊆ used and
   used ⊆ declared over ``tpudl/`` and ``tools/``.

Families with a runtime-computed segment (``frame.stage.<name>.seconds``)
are declared as patterns with exactly one ``*`` segment; the checker
matches an f-string's constant head/tail against them.
"""

from __future__ import annotations

from dataclasses import dataclass
from fnmatch import fnmatchcase

__all__ = ["Metric", "METRICS", "METRIC_NAMES", "METRIC_PATTERNS",
           "is_declared_metric", "unknown_metric_names",
           "render_metric_table"]


@dataclass(frozen=True)
class Metric:
    name: str     # exact dotted name, or a pattern with one '*'
    kind: str     # counter | gauge | histogram | report-gauge
    help: str


METRICS: tuple[Metric, ...] = (
    # -- frame executor ------------------------------------------------
    Metric("frame.map_batches.runs", "counter",
           "map_batches runs finished"),
    Metric("frame.map_batches.rows", "counter",
           "rows processed across runs"),
    Metric("frame.map_batches.batches", "counter",
           "dispatches issued across runs"),
    Metric("frame.map_batches.wall_seconds", "histogram",
           "wall time per run"),
    Metric("frame.stage.*.seconds", "counter",
           "cumulative seconds per executor stage "
           "(prepare/h2d/dispatch/d2h/infeed_wait)"),
    Metric("frame.overlap_efficiency", "gauge",
           "1 - infeed_wait/prepare for the last run"),
    Metric("frame.dispatch.inflight", "gauge",
           "mean in-flight dispatch-window occupancy of the last "
           "async run"),
    Metric("frame.dispatch.overlap_s", "gauge",
           "dispatch seconds the in-flight window hid from the "
           "consumer (last async run)"),
    Metric("frame.degraded.rungs", "counter",
           "degradation-ladder rungs applied by the fault-containment "
           "supervisor (FAULTS.md)"),
    Metric("frame.degraded.recovered_batches", "counter",
           "batches completed by runs that survived on a degraded "
           "rung"),
    Metric("frame.degraded.exhausted", "counter",
           "supervised runs whose ladder ran out (typed error + flight "
           "dump)"),
    Metric("frame.mesh.pad_rows", "gauge",
           "rows of SPMD batch padding the last mesh run shipped and "
           "discarded"),
    Metric("frame.mesh.pad_overhead_pct", "gauge",
           "pad rows as a percent of the last mesh run's dispatched "
           "rows"),
    Metric("frame.mesh.model_axis", "gauge",
           "model-axis size of the last mesh run's grid (1 = pure "
           "data parallelism, >1 = GSPMD tensor parallelism)"),
    Metric("frame.mesh.idle_devices", "gauge",
           "devices stranded by a grid smaller than the host's device "
           "count (build_mesh warn-once rides along)"),
    Metric("queue_depth", "report-gauge",
           "infeed queue depth sampled per batch (PipelineReport)"),
    Metric("dispatch_inflight", "report-gauge",
           "in-flight dispatches sampled per submit (PipelineReport; "
           "max can never exceed dispatch_depth)"),
    Metric("mesh_pad_rows", "report-gauge",
           "SPMD pad rows sampled per mesh batch (PipelineReport)"),
    Metric("slot_occupancy", "report-gauge",
           "active decode slots over total, sampled per serve tick "
           "(PipelineReport; feeds serve.batch_occupancy at finish)"),
    Metric("wire_batch_bytes", "report-gauge",
           "bytes shipped per batch (PipelineReport)"),
    # -- data: codecs + shard cache ------------------------------------
    Metric("data.wire.bytes_shipped", "counter",
           "encoded bytes put on the H2D wire"),
    Metric("data.wire.bytes_dense", "counter",
           "what the same batches would have shipped un-encoded"),
    Metric("data.wire.bytes_saved", "counter",
           "dense minus shipped"),
    Metric("data.codec.encode_seconds", "counter",
           "host time spent wire-encoding"),
    Metric("data.codec.*.batches", "counter",
           "batches encoded per codec (identity/u8/bf16)"),
    Metric("data.cache.hits", "counter", "shard-cache verified hits"),
    Metric("data.cache.misses", "counter", "shard-cache misses"),
    Metric("data.cache.puts", "counter", "shards written"),
    Metric("data.cache.corrupt", "counter",
           "shards failing checksum (re-prepared, never fatal)"),
    Metric("data.cache.evicted", "counter",
           "shards unlinked by eviction mid-read (treated as a miss)"),
    Metric("data.cache.bytes_read", "counter", "shard bytes read"),
    Metric("data.cache.bytes_written", "counter", "shard bytes written"),
    # -- data: HBM-tier device cache (DATA.md "Cache hierarchy") -------
    Metric("data.hbm.bytes_resident", "gauge",
           "bytes currently pinned in the device batch cache"),
    Metric("data.hbm.budget_bytes", "gauge",
           "device-cache resident-byte budget "
           "(TPUDL_DATA_HBM_BUDGET_MB or derived)"),
    Metric("data.hbm.hits", "counter",
           "batches served device-resident (zero wire bytes)"),
    Metric("data.hbm.misses", "counter",
           "device-cache lookups that fell through to the lower tiers"),
    Metric("data.hbm.puts", "counter", "batches made resident"),
    Metric("data.hbm.evictions", "counter",
           "LRU entries evicted to fit the budget"),
    Metric("data.hbm.bytes_served", "counter",
           "bytes served from HBM instead of the wire (the roofline "
           "subtracts these from its wire attribution)"),
    Metric("data.hbm.put_failed", "counter",
           "batches that failed to become resident mid-placement "
           "(tallies stayed consistent; fell back to the wire)"),
    Metric("data.hbm.donation_blocked", "counter",
           "resident batches routed away from a donating program "
           "(resident buffers are never donated)"),
    # -- image IO ------------------------------------------------------
    Metric("imageio.files_read", "counter", "files read off disk"),
    Metric("imageio.bytes_read", "counter", "bytes read off disk"),
    Metric("imageio.decode_errors", "counter",
           "undecodable images (null row, error ring sample)"),
    Metric("imageio.memo_hits", "counter",
           "LazyFileColumn memo hits (no re-read)"),
    Metric("imageio.uris_loaded", "counter",
           "URIs loaded via load_uri_batch"),
    # -- ml / hpo / tuning ---------------------------------------------
    Metric("estimator.trials", "counter", "estimator tuning trials run"),
    Metric("estimator.train_steps", "counter",
           "estimator train steps across trials"),
    Metric("estimator.trial_final_loss", "gauge",
           "last trial's final loss"),
    Metric("hpo.trials_started", "counter", "HPO trials started"),
    Metric("hpo.trials_completed", "counter", "HPO trials completed"),
    Metric("hpo.trials_failed", "counter",
           "HPO trials failed (after retries)"),
    Metric("hpo.trial_seconds", "histogram", "wall time per HPO trial"),
    Metric("hpo.trial_retries", "counter",
           "HPO trial attempts beyond the first"),
    Metric("ml.*.transforms", "counter",
           "transform() calls per ml transformer class"),
    Metric("ml.*.rows_in", "counter",
           "rows entering transform() per transformer class"),
    Metric("ml.*.rows_out", "counter",
           "rows leaving transform() per transformer class"),
    Metric("ml.*.fits", "counter",
           "fit() calls per estimator class"),
    Metric("udf.*.calls", "counter",
           "invocations per registered UDF"),
    Metric("udf.*.rows", "counter",
           "rows processed per registered UDF"),
    Metric("tuning.cv_folds", "counter", "cross-validation folds run"),
    Metric("tuning.cv_evaluations", "counter",
           "cross-validation model evaluations"),
    Metric("tuning.cv_last_metric", "gauge", "last CV fold metric"),
    Metric("tuning.cv_best_metric", "gauge", "best CV metric so far"),
    # -- train ---------------------------------------------------------
    Metric("train.steps", "counter", "optimizer steps taken"),
    Metric("train.examples", "counter", "examples consumed"),
    Metric("train.step_seconds", "histogram",
           "host loop's time per step (the train.step span): the device's "
           "only while the device throttles the host"),
    Metric("train.last_step", "gauge",
           "last completed step (live progress)"),
    Metric("train.restarts", "counter", "gang restarts"),
    Metric("train.restart_backoff_s", "histogram",
           "backoff slept before each gang restart"),
    Metric("train.checkpoint_save_seconds", "histogram",
           "wall time per checkpoint save"),
    Metric("train.checkpoint_restore_seconds", "histogram",
           "wall time per checkpoint restore"),
    Metric("train.checkpoint.corrupt", "counter",
           "checkpoints failing checksum on restore (fell back)"),
    # -- zoo (counted while a program is traced, not per step) ---------
    Metric("zoo.conv_bn.folded", "counter",
           "conv + batch-norm pairs traced with the norm's moving "
           "statistics folded into the kernel and bias (53 per trace of "
           "ResNet50 with train=False)"),
    Metric("zoo.conv_bn.unfolded", "counter",
           "conv + batch-norm pairs traced as written, on batch "
           "statistics (Store(train=True)): nothing can be folded"),
    Metric("zoo.lm.layers.*", "counter",
           "decoder parts traced, by kind (conv / attention / ssm mixers, "
           "dense / routed feed-forwards, shared experts beside routed "
           "ones; mla: latent-attention parts, counted under attention "
           "too; mtp: multi-token-prediction modules, whose layer counts "
           "by its parts as well): what a config-driven Decoder program "
           "is made of (conv 4, attention 1, dense 1, routed 4 per trace "
           "of lfm2-8b-a1b-ep4; ssm 3, attention 1, routed 3, shared 3 of "
           "nemotron-twotower-30b-a3b-ep16; mla 6, attention 6, dense 1, "
           "routed 5, shared 5, mtp 1 of joyai-llm-flash-ep32)"),
    Metric("zoo.lm.attention.saved", "counter",
           "attention and latent-attention layers traced under remat: "
           "each keeps its flash forward kernel's output and row "
           "statistics (checkpoint_name pallas_ops.SAVED) from the "
           "forward pass to its backward, so the recomputed block "
           "launches no forward kernel (6 per trace of "
           "joyai-llm-flash-ep32's loss, 1 of lfm2-8b-a1b-ep4's and of "
           "nemotron-twotower-30b-a3b-ep16's)"),
    Metric("zoo.lm.attention.saved_bytes", "gauge",
           "bytes of those saved values a step in the last traced "
           "program, by their shapes: layers x tokens x heads x (value "
           "head x compute itemsize + 4); 1.64 GB / 0.14 / 0.27 in the "
           "three cells (a row narrower than the 128-lane tile is padded "
           "to it on the device: lfm2's 64-wide head holds 0.27)"),
    Metric("lm.ssm.chunk", "gauge",
           "positions a chunk of the Mamba-2 mixer's selective scan in "
           "the last traced program (the configuration's chunk_size: "
           "128 in nemotron-twotower-30b-a3b-ep16)"),
    Metric("lm.ssm.chunks", "gauge",
           "chunks a sequence in that program: the length of the "
           "lax.scan over chunk states (64 at 8,192 positions)"),
    Metric("moe.combine.fused", "counter",
           "routed layers traced through moe.combine, the hand-written "
           "forward/backward pair that puts the experts' rows back at "
           "their tokens (4 per trace of lfm2-8b-a1b-ep4; none in a dense "
           "decoder)"),
    # -- flash kernels (counted per call of flash_attention, which under
    # jax.jit is per TRACE of the caller's program, not per step) -------
    Metric("pallas.flash.launches", "counter",
           "flash_attention calls traced: each launches the forward "
           "kernel and, under a gradient, the ONE backward kernel (1 per "
           "trace of lfm2-8b-a1b-ep4's loss: one attention layer)"),
    Metric("pallas.flash.tiles.*", "counter",
           "the (Q, K) tiles a head by class, where the "
           "offsets are known while the program is traced: interior "
           "(every pair visible: no mask is built), crossing (the "
           "diagonal or padded keys: the masked body), dead (nothing "
           "computed, nothing fetched); 28 / 8 / 28 at S = 8,192 in the "
           "derived 1,024 x 1,024 tiles, 120 / 16 / 120 in 512 x 512"),
    Metric("pallas.flash.block_q", "gauge",
           "rows of the tile the last traced call runs its forward kernel "
           "at: tile_shapes' derivation (1,024 clipped to the sequence) "
           "or the caller's block_q"),
    Metric("pallas.flash.block_k", "gauge",
           "columns (keys) of that tile, the backward kernel's too"),
    Metric("pallas.flash.bwd_block_q", "gauge",
           "rows of the backward kernel's tile in that call: block_q "
           "halved until heads_a_step x rows x block_k is at most 2 Mi "
           "score-plane elements (1,024 at one or two heads a step, 512 "
           "at four)"),
    Metric("pallas.flash.dq_span", "gauge",
           "rows of Q whose dq the backward kernel of that call holds in "
           "float32 VMEM scratch while the K tiles pass, derived from "
           "the shapes and the VMEM a kernel may ask for (the whole "
           "padded sequence, 8,192, in every LM cell)"),
    Metric("pallas.flash.dq_spans", "gauge",
           "spans the backward kernel of that call sweeps the padded "
           "sequence in (1 in every LM cell; more only where dq of the "
           "whole sequence outgrows VMEM: each span's dk/dv part is "
           "then summed in float32 outside the kernel)"),
    Metric("pallas.flash.heads_a_step", "gauge",
           "query heads of one key/value head the last traced call "
           "takes in one grid step (4 for 32 heads over 8)"),
    Metric("pallas.flash.head_dim_qk", "gauge",
           "width of a query/key head in the last traced call (192 under "
           "latent attention: 128 without position + 64 rotated)"),
    Metric("pallas.flash.head_dim_v", "gauge",
           "width of a value head in that call (128 under latent "
           "attention; the query/key head's width everywhere else)"),
    Metric("pallas.flash.shared_key", "counter",
           "flash_attention calls traced with a shared key (k_shared: "
           "key columns every head of a batch entry reads in place, "
           "latent attention's ONE rotated key): as many as "
           "pallas.flash.launches in a latent-attention program, none "
           "anywhere else"),
    Metric("pallas.flash.head_dim_shared", "gauge",
           "key columns the shared key brings in the last traced call (64 "
           "of the 192 under latent attention; 0 without one)"),
    # -- the grouped products' kernels (per call of pallas_ops.grouped_dot,
    # which under jax.jit is per TRACE) ---------------------------------------
    Metric("pallas.gmm.block_m", "gauge",
           "row tile of the last traced grouped product: 256 rows, or the "
           "whole of a smaller buffer (pallas_ops.row_tile)"),
    Metric("pallas.gmm.block_k", "gauge",
           "contraction block of that call (K whole where VMEM allows)"),
    Metric("pallas.gmm.block_n", "gauge",
           "column block of that call (N whole where VMEM allows)"),
    # -- the selective scan's kernels (per call of pallas_ops.ssd_scan,
    # which under jax.jit is per TRACE, as the flash kernels' are) -------
    Metric("pallas.ssd.launches", "counter",
           "ssd_scan calls traced: each launches the scan's forward "
           "kernel and, under a gradient, the forward that also writes "
           "the chunk states and the backward (3 per trace of "
           "nemotron-twotower-30b-a3b-ep16's loss: one a mixer)"),
    Metric("pallas.ssd.chunk", "gauge",
           "positions a chunk in the last traced call (the caller's: 128 "
           "in that cell)"),
    Metric("pallas.ssd.heads_a_step", "gauge",
           "heads one grid step of the scan's kernels takes: a group's, "
           "which share B and C (8 for 64 heads in 8 groups)"),
    Metric("pallas.ssd.states_saved", "gauge",
           "1 where the backward reads the state entering each chunk "
           "from what the forward wrote, 0 where it would rebuild them by "
           "a sweep of its own (1 always: under the mixer's "
           "rematerialisation they live for one sequence's turn)"),
    # -- routed experts (published by Decoder.route_stats, outside steps)
    Metric("moe.pairs_held", "counter",
           "(token, expert) pairs routed to experts this rank holds, "
           "summed over routed layers: what the grouped products work on"),
    Metric("moe.pairs_total", "counter",
           "pairs routed in all (tokens x experts per token x routed "
           "layers)"),
    Metric("moe.chunks_run", "counter",
           "turns the routed layer's held-prefix loops take on the "
           "route_stats batches: ceil(pairs_held / moe.chunk_rows) a "
           "routed layer"),
    Metric("moe.chunks_total", "counter",
           "turns they would take over the whole buffers: chunks_run / "
           "chunks_total is the share of the sorted-order work still done"),
    Metric("moe.token_rows_run", "counter",
           "rows the routed layer's two token-order moves take on the "
           "route_stats batches: twice the held prefix rounded up to whole "
           "chunks where they scatter-add it, twice T x k where they "
           "gather every slot (moe.token_rows)"),
    Metric("moe.token_rows_total", "counter",
           "rows the slot gathers take, 2 x T x k a routed layer: "
           "token_rows_run / token_rows_total is the share of that work "
           "still done"),
    Metric("moe.row_tiles_run", "counter",
           "row tiles a grouped product of the routed layer visits on the "
           "route_stats batches: per call of routed_ff the tiles the held "
           "experts' rows touch (moe.row_tiles, as the kernel counts them)"),
    Metric("moe.row_tiles_total", "counter",
           "row tiles its whole buffers hold, T x k / tile a call: "
           "row_tiles_run / row_tiles_total is how far the grouped products "
           "follow the held prefix"),
    Metric("moe.chunk_rows", "gauge",
           "rows a turn of those loops takes in the last TRACED routed "
           "layer (moe.chunk_rows of its T x k pairs)"),
    Metric("moe.expert_tokens_max", "gauge",
           "tokens of the fullest held expert in the last route_stats "
           "batch (nothing is dropped: the skew, not an overflow)"),
    Metric("moe.expert_tokens_mean", "gauge",
           "mean tokens per held expert in the last route_stats batch"),
    # -- jobs / retries ------------------------------------------------
    Metric("retry.attempts", "counter",
           "retry attempts across all RetryPolicy call sites"),
    Metric("retry.*", "counter",
           "retry attempts per kind (io.read, hpo.trial, ...)"),
    Metric("retry.backoff_s", "histogram",
           "seconds slept per retry backoff"),
    # -- obs self-metrics ----------------------------------------------
    Metric("obs.watchdog.stalls", "counter",
           "heartbeats flagged stalled (once per episode)"),
    Metric("obs.trace.account_gap_ns", "gauge",
           "device account of a traced step (record_device_scopes, once "
           "per run of the step program): the run's nanoseconds less "
           "what the requested scopes, the other declared scopes and "
           "device.unscoped sum to; under 1% of the step when every "
           "operation is filed once"),
    Metric("tsan.lock_order_inversions", "counter",
           "armed sanitizer: observed ABBA inversions (once per edge "
           "pair)"),
    Metric("tsan.deadlocks", "counter",
           "armed sanitizer: wait-for cycles / self-deadlocks detected"),
    Metric("tsan.lockset_violations", "counter",
           "armed sanitizer: registered structure mutated without its "
           "declared guard lock"),
    Metric("traceck.traces", "counter",
           "armed sentinel: jitted-fn traces observed (one per "
           "compile)"),
    Metric("traceck.retraces", "counter",
           "armed sentinel: second-or-later traces of one fn identity "
           "(each one a recompile)"),
    Metric("traceck.storms", "counter",
           "armed sentinel: identities tracing past "
           "TPUDL_TRACECK_STORM (one recompile-storm finding each)"),
    # -- compile subsystem (COMPILE.md) --------------------------------
    Metric("compile.bucket_pad_rows", "counter",
           "rows of bucket-ladder padding shipped and stripped "
           "(the price of O(log n) program signatures)"),
    Metric("compile.cache_disabled", "counter",
           "persistent-compilation-cache setup failures (a cold fleet "
           "is diagnosable: warn-once + flight breadcrumb ride along)"),
    Metric("obs.roofline.achieved_rows_per_s", "gauge",
           "measured end-to-end throughput (roofline input)"),
    Metric("obs.roofline.achievable_rows_per_s", "gauge",
           "modeled throughput with the gap closed"),
    Metric("obs.roofline.predicted_gain_pct", "gauge",
           "top advisor recommendation's predicted gain"),
    Metric("obs.roofline.gap_frac.*", "gauge",
           "device-vs-e2e gap share attributed per component "
           "(prepare/wire_h2d/dispatch/d2h/other/collective)"),
    Metric("obs.roofline.collective_s", "gauge",
           "gap seconds attributed to model-axis collectives (2-D "
           "mesh runs with a measured comm share)"),
    # -- serve plane (SERVE.md) ----------------------------------------
    Metric("serve.requests", "counter",
           "requests ADMITTED by the queue (offered load = requests "
           "+ rejects)"),
    Metric("serve.rejects", "counter",
           "typed admission rejects (queue_full / hbm_budget) — the "
           "load-shedding evidence obs doctor's overload_shed reads"),
    Metric("serve.deadline_sheds", "counter",
           "requests shed on an expired deadline (queued or "
           "mid-decode, both typed DeadlineExceeded)"),
    Metric("serve.queue_depth", "gauge",
           "current request-queue depth (bounded by "
           "TPUDL_SERVE_QUEUE_CAP)"),
    Metric("serve.queue_cap", "gauge",
           "the admission cap the queue was built with (at-death "
           "evidence for overload_shed)"),
    Metric("serve.inserts", "counter",
           "prompt prefills inserted into decode slots"),
    Metric("serve.evictions", "counter",
           "slots freed EARLY (deadline shed, cancel, supervised "
           "retry) — natural completions are serve.completed"),
    Metric("serve.steps", "counter",
           "slot decode-step dispatches (one compiled program per "
           "step, every active slot rides it)"),
    Metric("serve.tokens", "counter",
           "tokens emitted across all slots"),
    Metric("serve.tokens_per_s", "gauge",
           "sustained token rate of the last finished serve session"),
    Metric("serve.completed", "counter",
           "requests finished with their full token budget"),
    Metric("serve.batches", "counter",
           "rung-bucketed dynamic batches dispatched for ragged "
           "featurize/UDF payloads (RungBatcher)"),
    Metric("serve.batch_occupancy", "gauge",
           "real rows/slots over rung/slot capacity for the last "
           "dispatch (session mean committed at finish; the "
           "saturation SLO: > 0.5 under load)"),
    Metric("serve.latency_ms", "histogram",
           "end-to-end request latency, submit to completion "
           "(p50/p99 are the serving SLO line)"),
    Metric("serve.ttft_s", "histogram",
           "time-to-first-token, submit to prefill completion"),
    Metric("serve.models", "gauge",
           "models registered in the serve registry"),
    # -- serve SLO plane (ISSUE 18: tpudl.obs.slo windows) -------------
    Metric("serve.slo.target_ms", "gauge",
           "the configured latency objective "
           "(TPUDL_SERVE_SLO_P99_MS) the windowed gauges judge "
           "against"),
    Metric("serve.slo.window_p50_ms", "gauge",
           "p50 latency over the short SLO window "
           "(TPUDL_SERVE_SLO_WINDOW_S) — recent, not lifetime"),
    Metric("serve.slo.window_p99_ms", "gauge",
           "p99 latency over the short SLO window — the number an "
           "operator pages on"),
    Metric("serve.slo.availability", "gauge",
           "fraction of short-window requests meeting the objective"),
    Metric("serve.slo.burn_short", "gauge",
           "error-budget burn rate over the short window (violating "
           "fraction / the 1% p99 budget; >= 1 = burning)"),
    Metric("serve.slo.burn_long", "gauge",
           "burn rate over the long (10x) window — page when BOTH "
           "burn, investigate when only the short one does"),
    Metric("serve.slo.exemplars", "counter",
           "tail exemplars captured into the error ring (latency > "
           "TPUDL_SERVE_SLO_TAIL_K x the windowed median)"),
    # -- attribution plane (ISSUE 20: tpudl.obs.attribution) -----------
    Metric("attribution.scopes_evicted", "counter",
           "ledger scope rows LRU-evicted at the TPUDL_OBS_SCOPES "
           "cardinality bound (totals fold into the unattributed "
           "bucket — the reconciliation invariant survives)"),
    # -- text plane (TEXT.md: tokenizer codec + LM stages) -------------
    Metric("text.tokenize.calls", "counter",
           "tokenize_pack invocations on the prepare pool (epoch-2 "
           "delta MUST be 0 on a cached tokenized Dataset — the "
           "zero-decode warm-replay evidence)"),
    Metric("text.tokenize.tokens", "counter",
           "token ids produced by tokenization (pre-padding)"),
    Metric("text.tokenize.seconds", "histogram",
           "host tokenize+pack latency per prepare call"),
    Metric("text.pack.rows", "counter",
           "packed batch rows emitted (ragged right-padded or dense "
           "chunked)"),
    Metric("text.pack.pad_tokens", "counter",
           "pad ids written into packed batches (the padding tax "
           "bucketing bounds)"),
    Metric("text.pack.fill_pct", "gauge",
           "real-token fraction of the last packed batch (100 = no "
           "padding; dense packing pins this near 100)"),
    Metric("lm.embed.rows", "counter",
           "strings embedded by LMFeaturizer (masked mean-pooled "
           "hidden states)"),
    Metric("lm.classify.rows", "counter",
           "strings labeled by LMClassifier (argmax over class-token "
           "logits at the last real position)"),
    Metric("lm.generate.requests", "counter",
           "prompts completed by LMGenerator transforms"),
    Metric("lm.generate.tokens", "counter",
           "tokens generated by LMGenerator (post-EOS-trim)"),
)

METRIC_NAMES = frozenset(m.name for m in METRICS if "*" not in m.name)
METRIC_PATTERNS = tuple(m.name for m in METRICS if "*" in m.name)


def is_declared_metric(name: str) -> bool:
    """Exact-name membership, falling back to the one-'*' patterns."""
    if name in METRIC_NAMES:
        return True
    return any(fnmatchcase(name, p) for p in METRIC_PATTERNS)


def matches_pattern_prefix(head: str, tail: str = "") -> bool:
    """True when an f-string name with constant ``head``/``tail`` around
    one dynamic segment fits a declared pattern (the checker's view of
    ``f"frame.stage.{name}.seconds"``: head ``frame.stage.``, tail
    ``.seconds``). Containment, not equality: ``f"retry.io.{op}"``
    (head ``retry.io.``) expands only to names the declared ``retry.*``
    already covers, so a sub-family under a declared pattern needs no
    redundant registry entry."""
    for p in METRIC_PATTERNS:
        ph, _, pt = p.partition("*")
        if head.startswith(ph) and tail.endswith(pt):
            return True
    return False


def unknown_metric_names(names) -> list[str]:
    """The subset of ``names`` not declared here (for the JSONL-sink
    validator's opt-in cross-check)."""
    return sorted(n for n in set(names) if not is_declared_metric(n))


def render_metric_table() -> str:
    """Markdown table of the declared names (ANALYSIS.md embeds it)."""
    lines = ["| metric | kind | meaning |", "|---|---|---|"]
    for m in METRICS:
        lines.append(f"| `{m.name}` | {m.kind} | {m.help} |")
    return "\n".join(lines)
