#!/usr/bin/env python
"""tpudl benchmark — the BASELINE.json judged matrix.

Headline: ``DeepImageFeaturizer(InceptionV3).transform`` throughput
(images/sec/chip) — BASELINE.json configs[0] — plus the rest of the
judged matrix as sub-benches:

- HorovodRunner ResNet50 train step/sec (configs[3], the other judged
  number),
- DeepImagePredictor ResNet50 batch inference (configs[1]),
- KerasTransformer tabular-MLP rows/sec (configs[4]),
- KerasImageFileEstimator time-to-fit (configs[2]).

Output contract (round-5 fix — the driver keeps only a ~2,000-char
stdout TAIL, so the LAST line must be the judged record): stdout's
final line is a COMPACT summary JSON (< 1,500 chars) with metric /
value / unit / vs_baseline plus one scalar per sub-bench; the FULL
record is written to ``bench_records/<name>.json`` (path echoed in the
summary as ``full_record``) and to stderr.

``vs_baseline`` compares against the reference's execution substrate on
this host — Keras/TF InceptionV3 inference on CPU (the reference
publishes no numbers; we measure both sides ourselves).

Env knobs: TPUDL_BENCH_SKIP_BASELINE=1 skips the TF-CPU side;
TPUDL_BENCH_QUICK=1 runs the headline config only (and shrinks the
streaming phase to 1 trial/arm); TPUDL_BENCH_N / _BATCH / _TRIALS
resize the featurize run; TPUDL_BENCH_DTYPE picks the compute
precision. Streaming-phase knobs: TPUDL_BENCH_STREAM_TRIALS (per-arm
subprocess trials, 0 disables), TPUDL_BENCH_STREAM_BUDGET_S (stop
starting trials past this wall-clock), TPUDL_BENCH_TRIAL_TIMEOUT_S
(per-subprocess kill). TPUDL_BENCH_BUDGET_S (default 2400) is the
run's wall-clock budget: once spent, remaining sub-benches are SKIPPED
(recorded in ``skipped_sub_benches``, summary flagged ``partial``) so
the final line always lands inside the driver's window;
TPUDL_BENCH_DEADLINE_S is the hard watchdog backstop for a wedged
backend RPC, and SIGTERM flushes a partial summary before exit.
Everything except the final JSON line goes to stderr.
"""

import json
import os
import signal
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

# keras/TF sub-benches: silence the C++ log flood BEFORE any tf import
# (the round-5 driver record's kept stderr tail was mostly TF log noise
# burying the actual failure); absl needs a post-import call too (_silence_tf_logs)
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _silence_tf_logs():
    """Quiet absl + tf.logging (possible only AFTER import) — called at
    the top of every keras-importing sub-bench so the stderr tail keeps
    measurements, not retracing warnings. setdefault: an operator's
    explicit TF_CPP_MIN_LOG_LEVEL=0 debug run stays loud."""
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    try:
        from absl import logging as absl_logging

        absl_logging.set_verbosity(absl_logging.ERROR)
    # tpudl: ignore[swallowed-except] — best-effort silencing; absl
    # absent/odd just means a louder stderr tail, never a failed bench
    except Exception:
        pass
    import logging

    logging.getLogger("tensorflow").setLevel(logging.ERROR)


def _arm_flight_recorder():
    """Register the tpudl.obs flight recorder: dumps land next to the
    full record (bench_records/), so an external kill — a driver
    timeout, rc=124 — leaves a black box `python -m tpudl.obs doctor` can
    classify, not just an stderr tail. The stall watchdog rides along
    (a wedged backend RPC is flagged with thread stacks while the
    process is still alive)."""
    try:
        from tpudl.obs import flight as _flight

        os.environ.setdefault("TPUDL_FLIGHT_DIR", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "bench_records"))
        os.environ.setdefault("TPUDL_WATCHDOG_STALL_S", "300")
        _flight.install()
        return _flight
    except Exception as e:
        log(f"flight recorder install failed: {e!r}")
        return None


_EMITTED = threading.Event()
_EMIT_DONE = threading.Event()  # summary line fully printed
# NOTE: never call _emit from a signal handler — it may interrupt an
# in-progress _emit on this very thread and deadlock on this lock; the
# SIGTERM handler prints its summary line directly instead
_EMIT_LOCK = threading.Lock()

# -- wall-clock budget (round-5 fix: the driver record read rc=124/parsed=null —
# the run outlived the driver's timeout and never printed the summary).
# Sub-benches are SKIPPED once the budget is spent, so the final JSON
# line always lands well inside the driver's window; the watchdog
# (TPUDL_BENCH_DEADLINE_S) stays as the hard backstop for a wedged RPC.
_BUDGET_T0 = time.monotonic()


def _budget_s() -> float:
    return float(os.environ.get("TPUDL_BENCH_BUDGET_S", "2400"))


def _budget_left() -> float:
    return _budget_s() - (time.monotonic() - _BUDGET_T0)


def _gate(record: dict, key: str) -> bool:
    """True = run the sub-bench; False = budget spent — record the skip
    and mark the run partial."""
    if _budget_left() > 0:
        return True
    log(f"bench budget {_budget_s():.0f}s spent — skipping {key}")
    record.setdefault("skipped_sub_benches", []).append(key)
    record["partial"] = True
    return False


def _sub_deadline_s() -> float:
    """Per-sub-bench deadline derived from the REMAINING budget: a
    sub-bench may spend at most ``TPUDL_BENCH_SUBBENCH_FRAC`` (default
    half) of what's left, floored at 45 s so a short probe still fits.
    Round 5 proved the between-sub-bench budget gate alone is not
    enough — one slow sub-bench ate the whole window and the run died
    rc=124 without a summary line; with the per-sub-bench ceiling the
    later sub-benches and the final line always get their share."""
    try:
        frac = float(os.environ.get("TPUDL_BENCH_SUBBENCH_FRAC", "0.5"))
    except ValueError:
        frac = 0.5
    return max(45.0, _budget_left() * min(1.0, max(0.05, frac)))


def _call_with_deadline(key: str, fn, record: dict):
    """Run one sub-bench on a worker thread under its deadline.

    On expiry the sub-bench is ABANDONED (the daemon thread keeps
    running — a wedged backend RPC cannot be interrupted from Python,
    which is exactly the observed failure mode; an abandoned healthy
    thread merely finishes into the void), the record gains a
    ``deadline_sub_benches`` entry, the run is flagged partial, and a
    TimeoutError propagates to the caller's per-sub-bench handler so
    the loop moves on. The flight recorder notes the event — a later
    dump shows which sub-bench overran."""
    deadline = _sub_deadline_s()
    result: dict = {}
    done = threading.Event()

    def run():
        try:
            result["value"] = fn()
        except BaseException as e:  # re-raised on the caller's thread
            result["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True, name=f"bench-{key}")
    t.start()
    done.wait(deadline)
    if not done.is_set():
        log(f"sub-bench {key} overran its {deadline:.0f}s deadline "
            f"(budget left {_budget_left():.0f}s) — abandoning it")
        record.setdefault("deadline_sub_benches", []).append(
            {"key": key, "deadline_s": round(deadline, 1)})
        record["partial"] = True
        try:
            from tpudl.obs import flight as _flight

            _flight.get_recorder().record_event(
                "bench.sub_deadline", key=key,
                deadline_s=round(deadline, 1))
        # tpudl: ignore[swallowed-except] — guards the breadcrumb
        # itself; the TimeoutError below is the real signal
        except Exception:
            pass
        raise TimeoutError(
            f"sub-bench {key} exceeded {deadline:.0f}s deadline")
    if "error" in result:
        raise result["error"]
    return result.get("value")


def _install_sigterm_flush(record: dict):
    """SIGTERM (the driver's kill) flushes whatever has been measured so
    far as the final summary line and exits 0 — the judged record must
    survive an external timeout. Returns the handler (tests call it
    directly)."""

    # tpudl: ignore[signal-handler, signal-lock] — this handler
    # terminates the process: it dumps on a bounded worker thread
    # (timeout= — any obs lock the interrupted frame holds is waited
    # on OFF the signal frame and abandoned, never deadlocked on),
    # prints the judged line lock-free (the whole point, see comments
    # below), and os._exit()s — nothing returns into interrupted code
    def handler(signum, frame):
        log(f"signal {signum} received — flushing partial record")
        try:
            # black box FIRST: the dump is the forensic record the
            # summary line can't carry. timeout= is mandatory here —
            # the handler may have interrupted a frame holding an obs
            # lock, so the snapshot runs on a worker thread and is
            # abandoned (not deadlocked on) if it can't finish
            from tpudl.obs import flight as _flight

            _flight.dump(reason=f"signal:{signum}", timeout=10.0)
        except Exception as e:
            log(f"flight dump failed: {e!r}")
        if _EMIT_DONE.is_set():
            os._exit(0)  # summary already fully printed
        # Print the summary line DIRECTLY — not via _emit: the handler
        # may have interrupted an in-progress _emit on this very thread
        # (which can never resume once we _exit), so taking its lock or
        # honoring its latch could deadlock or drop the line. The
        # leading newline terminates any half-printed line so this one
        # is always a clean, parseable LAST line.
        partial = dict(record)
        partial.setdefault("value", None)
        partial["partial"] = True
        partial["sigterm"] = True
        try:
            line = json.dumps(_compact_summary(partial), default=str)
        except Exception as e:
            line = json.dumps(
                {"metric": partial.get("metric"),
                 "value": partial.get("value"),
                 "unit": partial.get("unit"), "vs_baseline": None,
                 "summary_error": repr(e)[:200]}, default=str)
        print("\n" + line, flush=True)
        os._exit(0)

    try:
        signal.signal(signal.SIGTERM, handler)
    except ValueError:  # not the main thread (in-process tests)
        pass
    return handler


def _scalar(v):
    return v if isinstance(v, (int, float, str, bool, type(None))) else None


def _compact_summary(record: dict) -> dict:
    """The judged LAST-line record. The driver keeps only a ~2,000-char
    stdout TAIL; round 4 emitted one large JSON line with the headline
    keys FIRST, so the tail preserved the tail-end sub-benches and the
    driver parsed nothing (round-4 record: parsed=null). This summary is
    built to stay well under the tail window: headline keys + one scalar
    per sub-bench, nothing nested deeper than one level."""
    s = {k: record.get(k) for k in ("metric", "value", "unit",
                                    "vs_baseline")}
    from tpudl.testing import traceck as _traceck
    from tpudl.testing import tsan as _tsan

    # main() refuses to start armed, so these are always false on a
    # judged line — recorded anyway so a stray TPUDL_TSAN=1 /
    # TPUDL_TRACECK=1 can never silently tax the numbers without
    # showing on the record
    s["tsan_armed"] = bool(_tsan.enabled())
    s["traceck_armed"] = bool(_traceck.enabled())
    for k in ("headline_mode", "compute_dtype", "batch_size",
              "deadline_hit", "partial", "sigterm"):
        if k in record:
            s[k] = _scalar(record[k])
    stream = record.get("featurize_streaming") or {}
    if stream.get("trials") is not None:
        # per-arm keys: a merged list loses which arm each trial came
        # from on the judged line (ADVICE.md)
        s["streaming_prefetch_trials"] = stream.get("trials", [])
        s["streaming_serial_trials"] = stream.get("serial_trials", [])
    for k in ("rate_over_sync_ceiling_median",  # matches the headline
              "prefetch_over_sync_ceiling_median",
              "serial_over_sync_ceiling_median"):
        if stream.get(k) is not None:
            # > 1 = streaming pipelining beat the contemporaneous
            # synchronized wire ceiling — the wire-bound diagnosis
            # readable off the one judged line
            s[k] = _scalar(stream[k])
    sync = record.get("featurize_sync_mode") or {}
    if sync.get("value") is not None:
        s["sync_mode_value"] = sync["value"]
    wire = record.get("wire_bandwidth") or {}
    s["h2d_mb_per_sec"] = _scalar(wire.get("h2d_mb_per_sec"))
    s["wire_bound_images_per_sec"] = _scalar(
        record.get("wire_bound_images_per_sec"))
    dev = record.get("device_profile") or {}
    s["mfu_device"] = _scalar(dev.get("mfu_device"))
    s["mfu_end_to_end"] = _scalar(record.get("mfu_end_to_end"))
    s["compute_only_images_per_sec"] = _scalar(
        record.get("compute_only_images_per_sec"))
    s["tf_cpu_baseline_images_per_sec"] = _scalar(
        record.get("tf_cpu_baseline_images_per_sec"))
    for key, field in (("horovod_resnet50", "step_per_sec"),
                       ("predictor_resnet50", "images_per_sec"),
                       ("keras_transformer_mlp", "rows_per_sec"),
                       ("estimator_inception", "step_per_sec"),
                       ("decode", "native_images_per_sec")):
        sub = record.get(key)
        if isinstance(sub, dict):
            # explicit None-chain: a present-but-0.0 primary field must
            # NOT be silently replaced by a different metric
            v = sub.get(field)
            if v is None:
                v = sub.get("value")
            if v is None and key == "decode":
                v = sub.get("pil_images_per_sec")
            s[key] = _scalar(v)
    dp = record.get("data_pipeline") or {}
    for k in ("u8_wire_shrink", "u8_speedup", "cache_warm_speedup",
              "cache_warm_files_read"):
        if dp.get(k) is not None:
            # the tpudl.data one-line evidence: u8 ships ~4x fewer
            # bytes; a warm epoch reads ZERO files
            s[k] = _scalar(dp[k])
    dc = record.get("device_cache") or {}
    for k in ("hbm_warm_speedup", "hbm_epoch2_bytes_shipped"):
        if dc.get(k) is not None:
            # the ISSUE-12 one-liners: epoch-2 resident over epoch-1
            # cold, and the hard zero-wire claim (epoch-2 wire bytes
            # MUST read 0 — any other value is a residency regression)
            s[k] = _scalar(dc[k])
    ad = record.get("async_dispatch") or {}
    for k in ("async_speedup", "dispatch_overlap_pct"):
        if ad.get(k) is not None:
            # the ROADMAP-2 one-liners: depth-D over blocking, and how
            # much of the dispatch round-trip the window actually hid
            s[k] = _scalar(ad[k])
    fr = record.get("fault_recovery") or {}
    for k in ("degraded_recovery_overhead_pct",
              "fault_recovery_efficiency"):
        if fr.get(k) is not None:
            # the ISSUE-14 one-liners: what one absorbed fault costs
            # end-to-end (lower is better) and its higher-is-better
            # twin the sentinel bands
            s[k] = _scalar(fr[k])
    ms = record.get("mesh_scaling") or {}
    for k in ("mesh_parallel_efficiency", "mesh_pad_overhead_pct"):
        if ms.get(k) is not None:
            # the ISSUE-11 one-liners: sharded executor over single-chip
            # on the virtual 8-device mesh (1.0 = the mesh fast path
            # costs nothing), and the SPMD padding waste
            s[k] = _scalar(ms[k])
    m2 = record.get("mesh_2d") or {}
    for k in ("mesh2d_parallel_efficiency",
              "model_axis_param_bytes_per_device"):
        if m2.get(k) is not None:
            # the ISSUE-16 one-liners: 4x2 tensor-parallel over 8x1
            # data-parallel on one program (1.0 = the model axis costs
            # nothing), and what sharding buys per device in HBM
            s[k] = _scalar(m2[k])
    cs = record.get("cold_start") or {}
    for k in ("cold_start_speedup", "aot_programs_restored"):
        if cs.get(k) is not None:
            # the ISSUE-15 one-liners: second-process first-result over
            # the empty-store arm, and how many serialized programs the
            # warm arm restored before its first batch
            s[k] = _scalar(cs[k])
    sv = record.get("serve") or {}
    for k in ("sustained_qps", "p99_ms", "warm_ttft_s",
              "serve_ttft_speedup", "batch_occupancy",
              "slo_window_p99_ms", "slo_burn"):
        if sv.get(k) is not None:
            # the ISSUE-17 one-liners: closed-loop sustained QPS at the
            # fixed p99 target, the p99 itself, warm TTFT (programs
            # restored, not compiled) + its cold ratio, and slot
            # saturation under load — plus the ISSUE-18 windowed pair
            # (SLO-engine recent p99 + burn) beside the lifetime p99
            s[k] = _scalar(sv[k])
    if sv.get("tenants"):
        # the ISSUE-20 one-liners: how many attribution scopes the
        # two-tenant serve load produced, and whether their ledger
        # reconciled exactly against the global counters (the full
        # per-tenant block stays on the trial record — too nested for
        # the judged line)
        s["serve_tenants"] = len(sv["tenants"])
        s["serve_ledger_ok"] = bool(sv.get("ledger_ok"))
    lt = record.get("lm_train") or {}
    for k in ("lm_train_tokens_per_sec", "lm_warm_epoch_speedup",
              "lm_epoch2_tokenize_calls", "lm_epoch2_wire_bytes"):
        if lt.get(k) is not None:
            # the ISSUE-19 tokens/s one-liners: warm-epoch fine-tune
            # throughput, its cold-epoch ratio, and the epoch-2
            # zero-decode/zero-wire evidence (both deltas must be 0 —
            # tokenized batches replay from HBM, never re-tokenized,
            # never re-shipped)
            s[k] = _scalar(lt[k])
    lg = record.get("lm_generate") or {}
    for k in ("lm_generate_tokens_per_sec", "lm_generate_programs"):
        if lg.get(k) is not None:
            # generated tokens/s over a ragged prompt column, plus how
            # few bucketed programs served the whole mix (the O(log n)
            # signature claim on the judged line)
            s[k] = _scalar(lg[k])
    snap = record.get("metrics_snapshot") or {}
    for name, key in (("compile.hits", "compile_hits"),
                      ("compile.misses", "compile_misses")):
        v = (snap.get(name) or {}).get("value")
        if v is not None:
            # every round's judged line stamps the parent process's own
            # AOT hit/miss counts — a round that silently stopped
            # hitting the program store is visible on the one line
            s[key] = _scalar(int(v))
    pre = record.get("preemption") or {}
    if pre.get("graceful_kill_rc") is not None:
        # the robustness one-liners (JOBS.md): graceful kill exits 75,
        # hard-kill resume rework in seconds (bounded by save_every)
        s["preempt_rc"] = _scalar(pre.get("graceful_kill_rc"))
        s["preempt_rework_s"] = _scalar(pre.get("hard_rework_s"))
    if record.get("bench_sentinel_token") is not None:
        # one scalar: "ok" / "regress:<metric,metric>" / "insufficient"
        # — the wire-normalized round-over-round verdict on the judged
        # line itself (bench_sentinel.summary_token is the one
        # authority for the format; the full table is in the record)
        s["sentinel"] = _scalar(record["bench_sentinel_token"])
    if "full_record_path" in record:
        s["full_record"] = record["full_record_path"]
    return s


def _emit(record: dict):
    """Emit the judged result exactly once (lock-guarded: the watchdog
    thread and the main thread may race at the deadline).

    Three sinks, in order:
    1. the FULL record → ``bench_records/<name>.json`` (committed dir),
    2. the full record → stderr (logs keep everything),
    3. a compact summary (< 1,500 chars) as the LAST stdout line — the
       only part the driver's stdout tail is guaranteed to keep."""
    with _EMIT_LOCK:
        if _EMITTED.is_set():
            return
        _EMITTED.set()
    try:
        rec_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_records")
        os.makedirs(rec_dir, exist_ok=True)
        # stable default so the driver's end-of-round run lands at the
        # path the judge looks for (the driver commits leftover files)
        name = os.environ.get("TPUDL_BENCH_RECORD_NAME", "BENCH_r05_full")
        path = os.path.join(rec_dir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1, default=str)
        record["full_record_path"] = os.path.relpath(
            path, os.path.dirname(os.path.abspath(__file__)))
    except Exception as e:
        log(f"full-record write failed: {e!r}")
    try:
        log("FULL RECORD: " + json.dumps(record, default=str))
    except Exception as e:
        log(f"full-record log failed: {e!r}")
    # the last line must survive ANY per-sink failure above or a
    # summary bug below — a raise here after the latch is set would
    # reproduce the round-4 parsed=null failure permanently
    try:
        line = json.dumps(_compact_summary(record), default=str)
    except Exception as e:
        line = json.dumps(
            {"metric": record.get("metric"), "value": record.get("value"),
             "unit": record.get("unit"),
             "vs_baseline": record.get("vs_baseline"),
             "summary_error": repr(e)[:200]}, default=str)
    print(line, flush=True)
    _EMIT_DONE.set()


def _start_watchdog(record: dict):
    """A backend RPC can wedge forever (observed in July 2026: futex-wait
    in the PJRT client with zero CPU). The watchdog guarantees the driver
    ALWAYS gets a JSON line: at the deadline it emits whatever has been
    measured so far (flagged ``deadline_hit``) and exits."""
    deadline = float(os.environ.get("TPUDL_BENCH_DEADLINE_S", "3300"))

    def run():
        time.sleep(deadline)
        if not _EMITTED.is_set():
            log(f"bench deadline {deadline:.0f}s hit — emitting partial "
                "record and exiting (a backend RPC is likely wedged)")
            try:
                from tpudl.obs import flight as _flight

                # a wedged main thread may hold an obs lock mid-RPC:
                # bounded dump, same rationale as the SIGTERM path
                _flight.dump(reason="bench_deadline", timeout=15.0)
            except Exception as e:
                log(f"flight dump failed: {e!r}")
            child = _ACTIVE_CHILD.get("proc")
            if child is not None and child.poll() is None:
                child.kill()  # orphan would keep holding the chip
            partial = dict(record)
            partial.setdefault("value", None)
            partial["deadline_hit"] = True
            partial["partial"] = True
            _emit(partial)
            os._exit(0)

    threading.Thread(target=run, daemon=True, name="bench-watchdog").start()


def make_frame(n, h=299, w=299, seed=0):
    from tpudl.frame import Frame
    from tpudl.image import imageIO

    rng = np.random.default_rng(seed)
    structs = [
        imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8),
            origin=f"synthetic_{i}")
        for i in range(n)
    ]
    return Frame({"image": structs})


def run_featurize_trial(arm, n, batch, dtype):
    """Subprocess body for ONE streaming-mode featurize trial (invoked
    as ``bench.py --featurize-trial <arm> <n> <batch> <dtype>``).

    The product path on a fresh process: ``DeepImageFeaturizer.warmup``
    compiles and warms without fetching, and ``transform`` (map_batches
    acc-mode) fetches exactly ONCE at the end — so the whole timed
    transform runs with uploads and dispatches enqueued asynchronously,
    and the single final fetch (where they actually drain) is INSIDE the
    timed window. This is the rate a real user sees on a fresh process:
    load → transform → read. (The July 2026 rounds saw a process's
    uploads slow down for good after its first device→host read; that
    is not measured on the current machine.)

    The wire probe runs AFTER the transform, so it cannot perturb the
    timed window. Emits one JSON line on stdout."""
    from tpudl.compilation_cache import enable_compilation_cache
    from tpudl.ml import DeepImageFeaturizer

    _arm_flight_recorder()  # a killed trial leaves its own black box
    enable_compilation_cache()
    os.environ["TPUDL_FRAME_PREFETCH"] = "1" if arm == "prefetch" else "0"
    if arm == "prefetch":
        # the pipelined arm is the FULL staged executor: parallel
        # prepare + K-deep infeed + multi-step fused dispatch (one
        # host round-trip per M batches — the headline lever); the
        # serial arm (TPUDL_FRAME_PREFETCH=0) force-disables all three
        os.environ.setdefault("TPUDL_FRAME_FUSE_STEPS", "4")
    feat = DeepImageFeaturizer(inputCol="image", outputCol="features",
                               modelName="InceptionV3", batchSize=batch,
                               computeDtype=dtype)
    t0 = time.perf_counter()
    feat.warmup(299, 299)  # compile + one execution; nothing fetched
    warm_s = time.perf_counter() - t0
    frame = make_frame(n)
    t0 = time.perf_counter()
    out = feat.transform(frame)
    np.asarray(out["features"][-1])  # already host; paranoia barrier
    dt = time.perf_counter() - t0
    rec = {"arm": arm, "images_per_sec": round(n / dt, 1),
           "transform_seconds": round(dt, 2),
           "warmup_seconds": round(warm_s, 1), "n": n, "batch": batch}
    try:
        from tpudl import obs

        # per-stage executor breakdown (decode/pack, h2d, dispatch, d2h)
        # + queue-depth/overlap gauges — the judged record carries the
        # pipeline's own accounting of where the wall-clock went
        rec["pipeline"] = obs.last_pipeline_report()
        # the process-wide registry snapshot rides along (files/bytes
        # decoded, transformer rows, stage-second totals): the trial
        # record carries the run's whole observability surface
        rec["metrics"] = obs.snapshot()
    except Exception as e:
        log(f"pipeline report unavailable: {e!r}")
    try:
        bw = measure_wire_bandwidth(mb=8)
        rec["h2d_mb_per_sec_post"] = bw["h2d_mb_per_sec"]
        img_mb = 299 * 299 * 3 / 2**20
        rec["sync_wire_bound_images_per_sec"] = round(
            bw["h2d_mb_per_sec"] / img_mb, 1)
    except Exception as e:
        log(f"trial wire probe failed: {e!r}")
    print(json.dumps(rec), flush=True)


_ACTIVE_CHILD: dict = {}  # watchdog kills this on deadline


def measure_featurize_streaming(n, batch, dtype, per_arm=4, extra=None):
    """Headline configs[0] measured the way the product actually runs on
    a fresh process: each trial is its OWN subprocess (warmup without
    fetch → one timed transform → one final fetch), so no trial
    inherits another's device state. Trials alternate
    prefetch/serial (counterbalanced) and each carries a post-transform
    wire probe, so the record keeps the drift-visible (arm, rate,
    contemporaneous sync-mode ceiling) pairs. The persistent XLA
    compilation cache makes subprocess compile costs one-time."""
    import subprocess

    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))
    # stop STARTING new trials past this wall-clock budget so the phase
    # can never out-run the watchdog deadline on a degraded link —
    # and never past the whole run's TPUDL_BENCH_BUDGET_S either
    budget = min(float(os.environ.get("TPUDL_BENCH_STREAM_BUDGET_S", "1500")),
                 max(0.0, _budget_left()))
    phase_start = time.perf_counter()
    arms = {"prefetch": [], "serial": []}
    pairs, failures = [], []
    # live record: visible to the watchdog's partial emit from the first
    # completed trial on (the "every sub-bench writes in as soon as it
    # completes" contract)
    out = {"trials": [], "serial_trials": [], "interleaved_pairs": pairs}
    if extra is not None:
        extra["featurize_streaming"] = out
    budget_hit = False
    for t in range(per_arm):
        order = (("prefetch", "serial") if t % 2 == 0
                 else ("serial", "prefetch"))
        for arm in order:
            elapsed = time.perf_counter() - phase_start
            if elapsed > budget and (arms["prefetch"] or t > 0):
                budget_hit = True
                break
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--featurize-trial", arm, str(n), str(batch), dtype]
            try:
                t0 = time.perf_counter()
                proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                        stderr=subprocess.PIPE, text=True)
                _ACTIVE_CHILD["proc"] = proc
                stdout, stderr = proc.communicate(timeout=timeout)
                wall = time.perf_counter() - t0
                sys.stderr.write(stderr[-2000:])
                rec = json.loads(stdout.strip().splitlines()[-1])
            except Exception as e:
                child = _ACTIVE_CHILD.pop("proc", None)
                if child is not None and child.poll() is None:
                    child.kill()  # single-process-per-chip: must not
                    child.wait()  # leave an orphan holding the TPU
                log(f"streaming trial {t} [{arm}] failed: {e!r}")
                failures.append({"arm": arm, "error": repr(e)[:200]})
                out["failed_trials"] = failures
                continue
            finally:
                _ACTIVE_CHILD.pop("proc", None)
            rec["subprocess_wall_seconds"] = round(wall, 1)
            arms[arm].append(rec["images_per_sec"])
            pairs.append(rec)
            _update_streaming_summary(out, arms, extra)
            log(f"streaming trial {t} [{arm}]: {rec['images_per_sec']} "
                f"img/s (warmup {rec['warmup_seconds']}s, sync-mode "
                f"ceiling {rec.get('sync_wire_bound_images_per_sec')}, "
                f"subprocess {wall:.0f}s)")
        if budget_hit:
            log(f"streaming phase budget {budget:.0f}s reached after "
                f"{len(pairs)} trials — not starting more")
            out["budget_hit"] = True
            break
    if not arms["prefetch"] and not arms["serial"]:
        # keep the failure evidence in the record (the phase RAN and
        # failed N times — popping it would hide that); only the
        # headline falls back to the in-process measurement
        out["all_trials_failed"] = True
        return None
    return out


def _update_streaming_summary(out, arms, extra):
    """Recompute the streaming record's derived fields after each trial
    (kept incremental so a watchdog partial emit carries them)."""
    pairs = out["interleaved_pairs"]
    out["trials"] = [round(r, 1) for r in arms["prefetch"]]
    out["serial_trials"] = [round(r, 1) for r in arms["serial"]]
    # Headline = median over ALL streaming trials (both arms): where
    # the transform is wire-DELIVERY-bound the two arms are the same
    # operating point and the per-trial spread is the link's — an
    # arm-restricted median would just sample fewer draws (July 2026:
    # arm medians 70 vs 108 img/s from one night; both arms'
    # wire-normalized medians agreed).
    # The sync-mode record below is where prefetch-vs-serial is a real
    # A/B (pack/transfer overlap matters when each batch round-trips).
    both = arms["prefetch"] + arms["serial"]
    out["value"] = round(statistics.median(both), 2)
    if arms["prefetch"] and arms["serial"]:
        out["headline_arm"] = "combined"
    else:  # one arm produced nothing — the record SAYS so rather than
        out["headline_arm"] = ("prefetch_only" if arms["prefetch"]
                               else "serial_only")  # silently standing in
    if arms["prefetch"]:
        out["prefetch_median"] = round(
            statistics.median(arms["prefetch"]), 2)
    if arms["serial"]:
        out["serial_median"] = round(statistics.median(arms["serial"]), 2)
    # rate ÷ contemporaneous SYNC-mode wire ceiling: values > 1 are the
    # pipelining win made visible (the fresh-process run beats what
    # the post-fetch wire probe could carry); per-arm medians let the
    # arm comparison be read off the record
    ratios = {arm: [p["images_per_sec"] / p["sync_wire_bound_images_per_sec"]
                    for p in pairs
                    if p["arm"] == arm
                    and p.get("sync_wire_bound_images_per_sec")]
              for arm in ("prefetch", "serial")}
    for arm, over in ratios.items():
        if over:
            out[f"{arm}_over_sync_ceiling_median"] = round(
                statistics.median(over), 2)
    combined = ratios["prefetch"] + ratios["serial"]
    if combined:
        out["rate_over_sync_ceiling_median"] = round(
            statistics.median(combined), 2)
    if extra is not None and "value" in out:
        extra["value"] = out["value"]
        extra["headline_mode"] = (
            "streaming_fresh_process"
            if out["headline_arm"] == "combined"
            else f"streaming_fresh_process_{out['headline_arm']}")


def measure_featurize(n, batch, dtype, trials=5):
    """Headline: configs[0], measured as an INTERLEAVED prefetch/serial
    A/B (round-3 verdict item 1): trials alternate
    prefetch/serial/prefetch/serial (≥4 per arm) and EVERY trial is
    bracketed by a short H2D bandwidth probe, so the record itself shows
    (a) whether rate tracks the contemporaneous wire ceiling (the
    wire-bound proof) and (b) the prefetch-vs-serial comparison under
    the SAME link speed — drift between trials can no longer confound
    either claim. ``value`` is the prefetch-arm median."""
    from tpudl.ml import DeepImageFeaturizer

    per_arm = max(1, trials)  # TPUDL_BENCH_TRIALS is per arm; the
    # ≥4-per-arm A/B contract lives on the STREAMING record now
    # (measure_featurize_streaming) — this in-process synchronized-mode
    # A/B is the cross-round-comparable secondary and may run shorter
    log(f"synchronized-mode in-process A/B: {per_arm} trials/arm")
    feat = DeepImageFeaturizer(inputCol="image", outputCol="features",
                               modelName="InceptionV3", batchSize=batch,
                               computeDtype=dtype)
    prev = os.environ.get("TPUDL_FRAME_PREFETCH")  # restore user's choice
    prev_fuse = os.environ.get("TPUDL_FRAME_FUSE_STEPS")
    t0 = time.perf_counter()
    feat.transform(make_frame(batch))  # compile+warmup (per-batch program)
    if prev_fuse is None:
        os.environ["TPUDL_FRAME_FUSE_STEPS"] = "4"
    try:
        fuse_now = int(os.environ.get("TPUDL_FRAME_FUSE_STEPS", "1"))
    except ValueError:
        fuse_now = 1
    if fuse_now > 1:
        # the prefetch arm below runs the FULL pipelined executor with
        # fused dispatch; warm that compile here, OUTSIDE the timed
        # trials (warmup() compiles the fused scan too, without a
        # fetch) — whether the fuse depth came from our default above
        # or the operator's own env
        try:
            feat.warmup(299, 299)
        except Exception as e:
            log(f"fused warmup failed (arm falls back per-batch): {e!r}")
            if prev_fuse is None:
                os.environ["TPUDL_FRAME_FUSE_STEPS"] = "1"
    warmup_s = time.perf_counter() - t0
    log(f"compile+warmup: {warmup_s:.1f}s")

    frame = make_frame(n)
    img_mb = 299 * 299 * 3 / 2**20  # uint8 struct bytes per image on the wire

    def probe():
        try:
            return measure_wire_bandwidth(mb=8)["h2d_mb_per_sec"]
        except Exception as e:  # probe failure must not kill the trial
            log(f"wire probe failed: {e!r}")
            return None

    arms = {"prefetch": [], "serial": []}
    pairs = []
    stage_reports = {}  # one per arm: the executor's own breakdown
    try:
        for t in range(per_arm):
            # counterbalanced order: a drifting link otherwise favors
            # whichever arm consistently runs second in the pair
            order = (("prefetch", "serial") if t % 2 == 0
                     else ("serial", "prefetch"))
            for arm in order:
                # the pipelined arm is the FULL staged executor (prefetch
                # pool + the fused dispatch warmed above); PREFETCH=0
                # force-disables both in the serial arm
                os.environ["TPUDL_FRAME_PREFETCH"] = (
                    "1" if arm == "prefetch" else "0")
                bw_pre = probe()
                t0 = time.perf_counter()
                out = feat.transform(frame)
                np.asarray(out["features"][-1])  # materialized; paranoia
                dt = time.perf_counter() - t0
                try:
                    from tpudl import obs

                    stage_reports[arm] = obs.last_pipeline_report()
                # tpudl: ignore[swallowed-except] — stage breakdown is
                # advisory evidence; the trial's rate is already taken
                except Exception:
                    pass
                bw_post = probe()
                rate = n / dt
                arms[arm].append(rate)
                bws = [b for b in (bw_pre, bw_post) if b is not None]
                bw = sum(bws) / len(bws) if bws else None
                pairs.append({
                    "arm": arm, "images_per_sec": round(rate, 1),
                    "h2d_mb_per_sec": round(bw, 1) if bw else None,
                    "wire_bound_images_per_sec":
                        round(bw / img_mb, 1) if bw else None,
                })
                log(f"featurize trial {t} [{arm}]: {n} images in "
                    f"{dt:.2f}s -> {rate:.1f} img/s (H2D "
                    f"{bw_pre}/{bw_post} MB/s -> ceiling "
                    f"{(bw / img_mb) if bw else float('nan'):.1f})")
    finally:
        if prev is None:
            os.environ.pop("TPUDL_FRAME_PREFETCH", None)
        else:
            os.environ["TPUDL_FRAME_PREFETCH"] = prev
        if prev_fuse is None:
            os.environ.pop("TPUDL_FRAME_FUSE_STEPS", None)
        else:
            os.environ["TPUDL_FRAME_FUSE_STEPS"] = prev_fuse

    value = statistics.median(arms["prefetch"])
    serial = statistics.median(arms["serial"])
    spread = ((max(arms["prefetch"]) - min(arms["prefetch"])) / value
              if value else 0.0)
    # drift-free arm comparison: each trial's rate NORMALIZED by its own
    # contemporaneous wire ceiling — raw medians confound the A/B with
    # the link when its speed swings within a session
    eff = {arm: [p["images_per_sec"] / p["wire_bound_images_per_sec"]
                 for p in pairs
                 if p["arm"] == arm and p["wire_bound_images_per_sec"]]
           for arm in arms}
    eff_med = {arm: (round(statistics.median(v), 3) if v else None)
               for arm, v in eff.items()}
    log(f"featurize interleaved medians: prefetch {value:.1f}, serial "
        f"{serial:.1f} img/s/chip (prefetch spread {spread:.0%}); "
        f"wire-normalized efficiency prefetch {eff_med['prefetch']} vs "
        f"serial {eff_med['serial']}")

    return {"value": round(value, 2),
            "trials": [round(r, 1) for r in arms["prefetch"]],
            "serial_trials": [round(r, 1) for r in arms["serial"]],
            "interleaved_pairs": pairs,
            "wire_normalized_efficiency": eff_med,
            "spread_pct": round(100 * spread, 1),
            "serial_infeed_images_per_sec": round(serial, 1),
            "pipeline_reports": stage_reports,
            "warmup_seconds": round(warmup_s, 1)}


def measure_compute_only(batch, dtype, iters=None):
    """Compute-only featurize rate: input RESIDENT on device, iterations
    chained into one data-dependent scalar fetched ONCE at the end: a
    reduction the host actually reads is a barrier on every backend
    (``block_until_ready`` is the barrier on the current machine). This
    is the MFU numerator the end-to-end number is judged against."""
    import jax
    import jax.numpy as jnp

    from tpudl.zoo.registry import cast_params, getKerasApplicationModel

    iters = iters or int(os.environ.get("TPUDL_BENCH_COMPUTE_ITERS", "8"))
    model = getKerasApplicationModel("InceptionV3")
    params = model.init(0)
    if dtype != "float32":
        params = cast_params(params, dtype)
    params = jax.device_put(params)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(batch, 299, 299, 3), dtype=np.uint8)
    xd = jax.block_until_ready(jax.device_put(x))

    @jax.jit
    def step(p, xb):
        z = model.preprocess(xb.astype(jnp.float32))
        feats = model.featurize(p, z.astype(jnp.dtype(dtype)))
        return jnp.sum(feats.astype(jnp.float32))

    float(step(params, xd))  # compile + warm
    t0 = time.perf_counter()
    total = jnp.zeros((), jnp.float32)
    for _ in range(iters):
        total = total + step(params, xd)
    val = float(total)  # ONE fetch, data-dependent on every iteration
    dt = time.perf_counter() - t0
    assert np.isfinite(val)
    ips = batch * iters / dt
    log(f"compute-only featurize: {batch}x{iters} images in {dt:.2f}s -> "
        f"{ips:.1f} images/sec/chip (input device-resident)")
    return ips


def build_featurize_step(batch, dtype):
    """THE profiled program — jitted InceptionV3 featurize-and-reduce
    with device-resident input. One definition shared by
    ``measure_device_profile`` (the per-run bench record) and
    ``tools/profile_featurize.py`` (the per-op attribution), so the
    two can never measure different programs."""
    import jax
    import jax.numpy as jnp

    from tpudl.zoo.registry import cast_params, getKerasApplicationModel

    model = getKerasApplicationModel("InceptionV3")
    params = model.init(0)
    if dtype != "float32":
        params = cast_params(params, dtype)
    params = jax.device_put(params)
    x = np.random.default_rng(0).integers(
        0, 256, size=(batch, 299, 299, 3), dtype=np.uint8)
    xd = jax.block_until_ready(jax.device_put(x))

    @jax.jit
    def step(p, xb):
        z = model.preprocess(xb.astype(jnp.float32))
        return jnp.sum(model.featurize(p, z.astype(jnp.dtype(dtype)))
                       .astype(jnp.float32))

    return step, params, xd


def build_resnet_train_step(batch, dtype):
    """THE profiled TRAINING program — the HorovodRunner bench's ResNet50
    SGD step (uint8 input, device-normalized) with device-resident data,
    shaped for chained profiling: returns (step, carry, (xd, yd)) where
    ``step(carry, x, y) -> (carry', loss)``."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpudl.zoo.registry import cast_params, getKerasApplicationModel

    model = getKerasApplicationModel("ResNet50")
    params = model.init(0)
    if dtype != "float32":
        params = cast_params(params, dtype)

    def loss_fn(p, x, y):
        x = (x.astype(jnp.dtype(dtype)) - 127.5) / 127.5
        logits = model.predict(p, x)
        logp = jnp.log(jnp.clip(logits, 1e-7, 1.0))
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    opt = optax.sgd(0.05)

    @jax.jit
    def step(carry, x, y):
        p, o = carry
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        up, o = opt.update(g, o, p)
        return (optax.apply_updates(p, up), o), loss

    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(batch, 224, 224, 3), dtype=np.uint8)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    carry = jax.device_put((params, opt.init(params)))
    xd, yd = jax.block_until_ready(jax.device_put((x, y)))
    return step, carry, (xd, yd)


def _profile_device(run_reps, reps):
    """Trace ``run_reps(reps)`` (which must END with one data-dependent
    host fetch) and return (device-trace summary, wall_seconds). The
    summary's "XLA Modules" time is on-device wall time — free of
    host dispatch latency."""
    import tempfile as _tf

    from tpudl.obs import load_trace_events, profile, summarize_device_trace

    with _tf.TemporaryDirectory(prefix="tpudl_prof_") as d:
        t0 = time.perf_counter()
        with profile(d):
            run_reps(reps)
        wall = time.perf_counter() - t0
        s = summarize_device_trace(load_trace_events(d))
    return s, wall


def profile_featurize_device(batch, dtype, reps=4):
    """Warm the shared featurize step, run ``reps`` chained iterations
    under a jax.profiler trace → (device summary, wall_s)."""
    import jax.numpy as jnp

    step, params, xd = build_featurize_step(batch, dtype)
    float(step(params, xd))  # compile + warm

    def run(reps):
        acc = jnp.zeros((), jnp.float32)
        for _ in range(reps):
            acc = acc + step(params, xd)
        float(acc)  # one data-dependent fetch drains the queue

    return _profile_device(run, reps)


def profile_train_device(batch, dtype, reps=4):
    """Same, for the ResNet50 train step: ``reps`` chained SGD steps
    (the carry is the data dependency) → (device summary, wall_s)."""
    step, carry, (xd, yd) = build_resnet_train_step(batch, dtype)
    carry, loss = step(carry, xd, yd)  # compile + warm
    float(loss)

    def run(reps):
        c, l = carry, loss
        for _ in range(reps):
            c, l = step(c, xd, yd)
        float(l)  # drains the chained steps

    return _profile_device(run, reps)


def measure_device_profile(batch, dtype, reps=4):
    """Device-side step time from a jax.profiler trace (round-3 verdict
    item 3): img/s and MFU derived from the "XLA Modules" lane, so the
    record carries the dispatch-free chip number every run.
    ``tools/profile_featurize.py`` prints the full per-op attribution
    table behind this number."""
    s, _wall = profile_featurize_device(batch, dtype, reps)
    if not s["module_count"]:
        return None  # no device lanes (CPU backend)
    ms = s["module_us"] / reps / 1e3
    ips = batch / (ms / 1e3)
    log(f"device-profile featurize: {ms:.2f} ms/step on-device -> "
        f"{ips:.0f} img/s ({batch=}, dispatch-free)")
    return {"device_ms_per_step": round(ms, 2),
            "device_images_per_sec": round(ips, 1),
            "mfu_device": round(ips * _INCEPTION_FLOPS / peak_flops(), 4),
            "batch": batch}


def measure_train_step(dtype):
    """configs[3]: HorovodRunner ResNet50 train step/sec on the live
    backend (single chip here; the SPMD program is mesh-size-agnostic).
    Fresh host batches every step — the transfer is part of the step,
    as it is for the reference's NCCL path."""
    import jax

    from tpudl.train import HorovodRunner

    batch = int(os.environ.get("TPUDL_BENCH_TRAIN_BATCH", "64"))
    steps = int(os.environ.get("TPUDL_BENCH_TRAIN_STEPS", "10"))
    rng = np.random.default_rng(0)
    # uint8 images, normalized on device — the TPU-native input pipeline
    # (4x fewer host->device bytes than feeding pre-normalized float32)
    xs = [rng.integers(0, 256, size=(batch, 224, 224, 3), dtype=np.uint8)
          for _ in range(4)]
    ys = [np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)] for _ in range(4)]

    def train_fn(ctx):
        import jax.numpy as jnp
        import optax

        from tpudl.zoo.registry import getKerasApplicationModel

        from tpudl.zoo.registry import cast_params

        model = getKerasApplicationModel("ResNet50")
        params = model.init(0)
        if dtype != "float32":
            params = cast_params(params, dtype)

        def loss_fn(p, x, y):
            x = (x.astype(jnp.dtype(dtype)) - 127.5) / 127.5
            logits = model.predict(p, x)
            logp = jnp.log(jnp.clip(logits, 1e-7, 1.0))
            return -jnp.mean(jnp.sum(y * logp, axis=-1))

        trainer = ctx.trainer(loss_fn, optax.sgd(0.05))
        data = lambda step: (xs[step % len(xs)], ys[step % len(ys)])
        trainer.fit(params, data, steps=1)  # compile + warm step
        t0 = time.perf_counter()
        trainer.fit(params, data, steps=steps)
        dt = time.perf_counter() - t0
        return steps / dt, batch * steps / dt

    sps, ips = HorovodRunner(np=1).run(train_fn)
    log(f"HorovodRunner ResNet50: {sps:.2f} steps/sec "
        f"({ips:.1f} images/sec, batch {batch})")
    out = {"step_per_sec": round(sps, 3), "images_per_sec": round(ips, 1),
           "batch_size": batch}
    try:
        out.update(measure_resnet50_convergence(dtype))
    except Exception as e:  # curve failure must not kill the timing bench
        log(f"convergence-curve sub-bench failed: {e!r}")
        out["loss_curve_error"] = repr(e)
    return out


def measure_resnet50_convergence(dtype):
    """configs[3]'s OTHER half (round-3 verdict item 4): a visible loss
    CURVE, not just step/sec. ResNet50 trains on a seeded separable
    synthetic set (class c = bright horizontal band c of 8) for
    ``TPUDL_BENCH_CURVE_STEPS`` steps. The curve is the loss on ONE
    FIXED batch evaluated every 10 steps — the rolling training loss
    cycles through pool batches of visibly different difficulty, so
    sampling it aliases batch identity into the curve (the rehearsal's
    'spikes every 40 steps' were batch 0, not divergence)."""
    import jax.numpy as jnp
    import optax

    import jax

    from tpudl.train import make_train_step
    from tpudl.zoo.registry import getKerasApplicationModel

    steps = int(os.environ.get("TPUDL_BENCH_CURVE_STEPS", "120"))
    batch = int(os.environ.get("TPUDL_BENCH_CURVE_BATCH", "32"))
    n_cls, side = 8, 224
    rng = np.random.default_rng(0)
    # separable by construction: a bright band whose position is the class
    n_pool = 8  # distinct pre-built batches, cycled (wire cost bounded)
    xs, ys = [], []
    for b in range(n_pool):
        cls = rng.integers(0, n_cls, size=batch)
        x = rng.integers(0, 96, size=(batch, side, side, 3), dtype=np.uint8)
        for i, c in enumerate(cls):
            x[i, c * side // n_cls:(c + 1) * side // n_cls] += 128
        xs.append(x)
        ys.append(np.eye(1000, dtype=np.float32)[cls])

    from tpudl.train import with_compute_dtype

    model = getKerasApplicationModel("ResNet50")
    params = model.init(0)  # fp32 MASTER weights (see below)

    def loss_fn(p, x, y):
        x = (x.astype(jnp.dtype(dtype)) - 127.5) / 127.5
        logits = model.predict(p, x)
        logp = jnp.log(jnp.clip(logits, 1e-7, 1.0))
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    # mixed precision: dtype (bf16) compute on fp32 masters — training
    # the masters IN bf16 stalls once SGD updates drop below the 8-bit
    # mantissa ULP (the earlier plateau at ~4.2; proven in
    # tests/test_train.py::TestMixedPrecision)
    train_loss = (with_compute_dtype(loss_fn, dtype)
                  if dtype != "float32" else loss_fn)
    opt = optax.sgd(0.05)
    step = make_train_step(train_loss, opt)
    eval_fn = jax.jit(train_loss)
    x0, y0 = jax.device_put((xs[0], ys[0]))  # the fixed eval batch
    p = jax.device_put(params)
    o = opt.init(p)
    curve = [{"step": 0, "loss": round(float(eval_fn(p, x0, y0)), 4)}]
    t0 = time.perf_counter()
    for s in range(steps):
        p, o, _l = step(p, o, xs[s % n_pool], ys[s % n_pool])
        if (s + 1) % 10 == 0:
            curve.append({"step": s + 1,
                          "loss": round(float(eval_fn(p, x0, y0)), 4)})
    dt = time.perf_counter() - t0
    log(f"ResNet50 convergence: {steps} steps (batch {batch}) in {dt:.1f}s; "
        f"fixed-batch eval loss {curve[0]['loss']} -> {curve[-1]['loss']}")
    # the timed window includes the 12 eval forwards (renamed so it
    # can't be read as the pure train-step throughput, which is
    # measure_train_step's `images_per_sec`)
    return {"loss_curve": curve,
            "curve_steps": steps, "curve_batch": batch,
            "curve_examples_per_sec_with_eval": round(
                batch * steps / dt, 1),
            "curve_loss_first": curve[0]["loss"],
            "curve_loss_last": curve[-1]["loss"]}


def measure_predictor(dtype):
    """configs[1]: DeepImagePredictor ResNet50 batch inference."""
    from tpudl.ml import DeepImagePredictor

    n = int(os.environ.get("TPUDL_BENCH_PRED_N", "512"))
    n = max(256, n - n % 256)  # whole batches: a ragged tail would compile
    pred = DeepImagePredictor(inputCol="image", outputCol="preds",
                              modelName="ResNet50", batchSize=256,
                              computeDtype=dtype)
    frame = make_frame(n, h=224, w=224)
    pred.transform(frame.head(256))  # compile+warmup
    t0 = time.perf_counter()
    pred.transform(frame)
    dt = time.perf_counter() - t0
    ips = n / dt
    log(f"DeepImagePredictor ResNet50: {n} images in {dt:.2f}s -> "
        f"{ips:.1f} images/sec/chip")
    return {"images_per_sec": round(ips, 1)}


def measure_keras_transformer():
    """configs[4]: KerasTransformer over a tabular array column."""
    _silence_tf_logs()
    import keras

    from tpudl.frame import Frame
    from tpudl.ml import KerasTransformer

    rows = int(os.environ.get("TPUDL_BENCH_MLP_ROWS", "65536"))
    dim = 100
    keras.utils.set_random_seed(0)
    m = keras.Sequential([
        keras.layers.Input((dim,)),
        keras.layers.Dense(256, activation="relu"),
        keras.layers.Dense(64, activation="relu"),
        keras.layers.Dense(10, activation="softmax"),
    ])
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "mlp.keras")
        m.save(path)
        kt = KerasTransformer(inputCol="x", outputCol="y", modelFile=path,
                              batchSize=8192)
        rng = np.random.default_rng(0)
        data = rng.normal(size=(rows, dim)).astype(np.float32)
        frame = Frame({"x": data})
        kt.transform(Frame({"x": data[:8192]}))  # compile+warmup
        t0 = time.perf_counter()
        kt.transform(frame)
        dt = time.perf_counter() - t0
    rps = rows / dt
    log(f"KerasTransformer MLP: {rows} rows in {dt:.2f}s -> {rps:.0f} rows/sec")
    return {"rows_per_sec": round(rps, 1)}


def measure_estimator_fit():
    """configs[2]: KerasImageFileEstimator time-to-fit (transfer-learning
    loop: ingest keras model -> train over image files -> transformer)."""
    _silence_tf_logs()
    import keras
    from PIL import Image

    from tpudl.frame import Frame
    from tpudl.ml import KerasImageFileEstimator

    n_files = 32
    keras.utils.set_random_seed(0)
    m = keras.Sequential([
        keras.layers.Input((32, 32, 3)),
        keras.layers.Conv2D(8, 3, activation="relu"),
        keras.layers.GlobalAveragePooling2D(),
        keras.layers.Dense(2, activation="softmax"),
    ])

    def loader(uri):
        img = Image.open(uri).convert("RGB").resize((32, 32), Image.BILINEAR)
        return np.asarray(img, dtype=np.float32) / 255.0

    with tempfile.TemporaryDirectory() as d:
        rng = np.random.default_rng(0)
        uris, labels = [], []
        for i in range(n_files):
            arr = rng.integers(0, 255, size=(48, 48, 3), dtype=np.uint8)
            p = os.path.join(d, f"im{i}.png")
            Image.fromarray(arr).save(p)
            uris.append(p)
            labels.append(np.eye(2, dtype=np.float32)[i % 2])
        path = os.path.join(d, "cnn.keras")
        m.save(path)
        est = KerasImageFileEstimator(
            inputCol="uri", outputCol="out", labelCol="label",
            imageLoader=loader, modelFile=path,
            kerasOptimizer="adam", kerasLoss="categorical_crossentropy",
            kerasFitParams={"epochs": 2, "batch_size": 16})
        frame = Frame({"uri": uris, "label": labels})
        t0 = time.perf_counter()
        model = est.fit(frame)
        dt = time.perf_counter() - t0
    log(f"KerasImageFileEstimator: fit {n_files} files x 2 epochs in {dt:.2f}s")
    return {"fit_seconds": round(dt, 2)}


def measure_estimator_inception():
    """configs[2] at its REAL scale (round-3 verdict item 3): full
    InceptionV3 (313 layers) + fresh 2-class head ingested through
    ``TFInputGraph.fromKerasTrainable`` and fine-tuned end-to-end by
    KerasImageFileEstimator on ~100 synthetic 299×299 images — the
    sparkdl transfer-learning shape, timed. The tiny-CNN entry stays as
    the quick smoke; this is the judged config."""
    _silence_tf_logs()
    import keras
    from PIL import Image

    from tpudl.frame import Frame
    from tpudl.image.imageIO import createNativeImageLoader
    from tpudl.ml import KerasImageFileEstimator

    n_files = int(os.environ.get("TPUDL_BENCH_EST_INC_FILES", "96"))
    batch = int(os.environ.get("TPUDL_BENCH_EST_INC_BATCH", "16"))
    keras.utils.set_random_seed(0)
    base = keras.applications.InceptionV3(weights=None, include_top=False,
                                          pooling="avg")
    head = keras.layers.Dense(2, activation="softmax", name="head")(
        base.output)
    m = keras.Model(base.input, head)

    loader = createNativeImageLoader(299, 299, scale=1.0 / 255.0)
    with tempfile.TemporaryDirectory() as d:
        rng = np.random.default_rng(0)
        uris, labels = [], []
        for i in range(n_files):
            arr = rng.integers(0, 255, size=(299, 299, 3), dtype=np.uint8)
            if i % 2:  # separable: dark top vs dark bottom half
                arr[:150] //= 4
            else:
                arr[150:] //= 4
            p = os.path.join(d, f"im{i}.jpg")
            Image.fromarray(arr).save(p, quality=90)
            uris.append(p)
            labels.append(np.eye(2, dtype=np.float32)[i % 2])
        path = os.path.join(d, "inception_tl.keras")
        m.save(path)
        est = KerasImageFileEstimator(
            inputCol="uri", outputCol="out", labelCol="label",
            imageLoader=loader, modelFile=path,
            kerasOptimizer="adam", kerasLoss="categorical_crossentropy",
            kerasFitParams={"epochs": 1, "batch_size": batch})
        frame = Frame({"uri": uris, "label": labels})
        t0 = time.perf_counter()
        est.fit(frame)
        dt = time.perf_counter() - t0
    n_steps = -(-n_files // batch)
    log(f"KerasImageFileEstimator InceptionV3 transfer-learning: fit "
        f"{n_files} files x 1 epoch (batch {batch}) in {dt:.1f}s")
    return {"fit_seconds": round(dt, 2), "n_files": n_files,
            "batch_size": batch,
            "step_per_sec": round(n_steps / dt, 3)}


def measure_decode():
    """Input-pipeline decode stage (the reference's historic bottleneck,
    SURVEY.md §3.1): native threaded libjpeg batch decode+resize vs the
    PIL loop, on ~VGA JPEGs resized to 299×299."""
    import io

    from PIL import Image

    from tpudl import native

    k = int(os.environ.get("TPUDL_BENCH_DECODE_N", "256"))
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, size=(60, 80, 3), dtype=np.uint8)
    photo = np.asarray(Image.fromarray(base).resize((800, 600),
                                                    Image.BILINEAR))
    raws = []
    for q in range(k):
        buf = io.BytesIO()
        Image.fromarray(photo).save(buf, "JPEG", quality=80 + q % 15)
        raws.append(buf.getvalue())

    t0 = time.perf_counter()
    for raw in raws:
        img = Image.open(io.BytesIO(raw)).convert("RGB")
        np.asarray(img.resize((299, 299), Image.BILINEAR))
    pil_ips = k / (time.perf_counter() - t0)

    out = {"pil_images_per_sec": round(pil_ips, 1)}
    if native.available():
        native.decode_resize_batch(raws[:8], 299, 299)  # warm build/load
        t0 = time.perf_counter()
        _batch, ok = native.decode_resize_batch(raws, 299, 299)
        nat_ips = k / (time.perf_counter() - t0)
        assert all(ok)
        out["native_images_per_sec"] = round(nat_ips, 1)
        out["native_speedup"] = round(nat_ips / pil_ips, 2)
    log(f"decode 800x600 JPEG -> 299x299: {out}")
    return out


def measure_data_pipeline():
    """tpudl.data sub-bench (DATA.md): (a) a wire-codec A/B — the SAME
    jitted reduction over float32 image batches, shipped identity vs u8
    vs bf16, trials interleaved and bracketed by the 8 MB wire probe so
    the arm comparison is attributable when the link speed drifts (the
    measure_featurize discipline); (b) shard-cache cold/warm epochs
    over real JPEG files — epoch 1 decodes + persists, epoch 2 replays
    memory-mapped shards with ZERO decodes (asserted off the decode
    counters, recorded in the trial's obs snapshot). The wire-byte
    counters (data.wire.bytes_shipped/dense) ride into the record, so
    the u8 shrink is auditable, not inferred."""
    import tempfile as _tempfile

    import jax

    from tpudl import obs
    from tpudl.frame import Frame
    from tpudl.image import imageIO

    n = int(os.environ.get("TPUDL_BENCH_DATA_N", "512"))
    batch = 64
    h = w = 128
    rng = np.random.default_rng(0)
    u8 = rng.integers(0, 256, size=(n, h, w, 3), dtype=np.uint8)
    f32 = u8.astype(np.float32) * np.float32(1.0 / 255.0)
    col = np.empty(n, dtype=object)
    col[:] = list(f32)
    frame = Frame({"x": col})
    # light compute on purpose: the arm difference is the WIRE
    # tpudl: ignore[jit-cache-churn] — one program per sub-bench process
    # run by design; bench.py measures, it does not serve
    fn = jax.jit(lambda x: x.reshape(x.shape[0], -1).mean(axis=1))
    out = {"n": n, "image_hw": h, "batch": batch}

    def one_pass(codec):
        t0 = time.perf_counter()
        res = frame.map_batches(fn, ["x"], ["y"], batch_size=batch,
                                wire_codec=codec)
        np.asarray(res["y"])  # materialized
        return n / (time.perf_counter() - t0)

    arms = {"identity": [], "u8": [], "bf16": []}
    shrink = {}
    for arm in arms:  # compile each arm's wrapped program OUTSIDE timing
        one_pass(arm)
    for _t in range(2):
        for arm in arms:
            before = obs.snapshot()
            bw_pre = _quiet_wire_probe()
            rate = one_pass(arm)
            after = obs.snapshot()

            def delta(name):
                return (after.get(name, {}).get("value", 0)
                        - before.get(name, {}).get("value", 0))

            shipped = delta("data.wire.bytes_shipped")
            dense = delta("data.wire.bytes_dense")
            shrink[arm] = round(dense / shipped, 2) if shipped else None
            arms[arm].append(rate)
            log(f"data codec arm [{arm}]: {rate:.1f} img/s "
                f"(wire shrink {shrink[arm]}x, H2D probe {bw_pre} MB/s)")
    med = {arm: round(statistics.median(r), 1) for arm, r in arms.items()}
    out["codec_images_per_sec"] = med
    out["codec_wire_shrink"] = shrink
    out["u8_wire_shrink"] = shrink.get("u8")
    if med.get("identity"):
        out["u8_speedup"] = round(med["u8"] / med["identity"], 2)

    # -- shard cache: cold decode+persist vs warm mmap replay ------------
    k = int(os.environ.get("TPUDL_BENCH_DATA_FILES", "192"))
    from PIL import Image

    pack = lambda sl: np.stack(  # noqa: E731
        [imageIO.imageStructToArray(r, copy=False) for r in sl])
    pack.thread_safe = True
    with _tempfile.TemporaryDirectory() as d:
        img_dir = os.path.join(d, "imgs")
        os.makedirs(img_dir)
        base = rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
        for i in range(k):
            Image.fromarray(np.roll(base, i, axis=0)).save(
                os.path.join(img_dir, f"im{i:04d}.jpg"), quality=85)
        cache_dir = os.path.join(d, "cache")

        def epoch():
            files = imageIO.readImages(img_dir)
            before = obs.snapshot()
            t0 = time.perf_counter()
            res = files.map_batches(fn, ["image"], ["y"], batch_size=batch,
                                    pack=pack, wire_codec="u8",
                                    cache_dir=cache_dir)
            np.asarray(res["y"])
            dt = time.perf_counter() - t0
            after = obs.snapshot()
            reads = (after.get("imageio.files_read", {}).get("value", 0)
                     - before.get("imageio.files_read", {}).get("value", 0))
            return dt, reads

        hits_before = obs.snapshot().get("data.cache.hits",
                                         {}).get("value", 0)
        cold_s, cold_reads = epoch()
        warm_s, warm_reads = epoch()
        out["cache_cold_seconds"] = round(cold_s, 3)
        out["cache_warm_seconds"] = round(warm_s, 3)
        out["cache_cold_files_read"] = int(cold_reads)
        out["cache_warm_files_read"] = int(warm_reads)  # contract: 0
        out["cache_warm_speedup"] = (round(cold_s / warm_s, 2)
                                     if warm_s > 0 else None)
        # delta, not the absolute process-wide counter: earlier
        # sub-benches' cache traffic must not inflate this record
        out["cache_hits"] = obs.snapshot().get(
            "data.cache.hits", {}).get("value", 0) - hits_before
        log(f"data cache epochs ({k} JPEGs): cold {cold_s:.2f}s "
            f"({cold_reads:.0f} reads) vs warm {warm_s:.2f}s "
            f"({warm_reads:.0f} reads) -> "
            f"{out['cache_warm_speedup']}x")
    return out


def measure_device_cache():
    """device-cache sub-bench (DATA.md "Cache hierarchy", ISSUE 12):
    the SAME u8-encoded featurize-shaped program over the SAME frame,
    epoch 1 cold (batches ship + become HBM-resident) vs epoch 2 warm
    (every batch served from device memory — ZERO wire bytes, asserted
    off the data.wire.bytes_shipped counter). Emits ``hbm_warm_speedup``
    (warm over cold — a within-round ratio, scored raw by
    bench_sentinel like async_speedup) and ``hbm_epoch2_bytes_shipped``
    (the hard zero-wire claim) onto the judged summary line."""
    import jax

    from tpudl import obs
    from tpudl.data import device_cache as _dc
    from tpudl.frame import Frame

    n = int(os.environ.get("TPUDL_BENCH_HBM_N", "512"))
    batch = 64
    h = w = 96
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n, h, w, 3), dtype=np.uint8)
    frame = Frame({"x": x})
    # wire-shaped on purpose: light compute, image-sized inputs — the
    # epoch difference is the H2D transfer residency removes
    # tpudl: ignore[jit-cache-churn] — one program per sub-bench process
    # run by design; bench.py measures, it does not serve
    fn = jax.jit(lambda b: b.reshape(b.shape[0], -1).mean(axis=1))
    out = {"n": n, "image_hw": h, "batch": batch}

    def one_pass():
        before = obs.snapshot()
        t0 = time.perf_counter()
        res = frame.map_batches(fn, ["x"], ["y"], batch_size=batch,
                                wire_codec="u8", device_cache=True,
                                autotune=False)
        np.asarray(res["y"])  # materialized
        dt = time.perf_counter() - t0
        after = obs.snapshot()

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        return n / dt, delta("data.wire.bytes_shipped"), \
            delta("data.hbm.hits")

    _dc.reset_device_cache()  # this sub-bench owns a cold epoch 1
    one_pass()  # compile the wrapped program outside timing (still
    _dc.reset_device_cache()  # populates — reset back to cold)
    cold_rate, cold_shipped, _ = one_pass()
    warm_rates = []
    warm_shipped = warm_hits = 0
    for _t in range(3):
        r, shipped, hits = one_pass()
        warm_rates.append(r)
        warm_shipped += shipped
        warm_hits += hits
    warm_rate = statistics.median(warm_rates)
    out["cold_images_per_sec"] = round(cold_rate, 1)
    out["warm_images_per_sec"] = round(warm_rate, 1)
    out["hbm_epoch1_bytes_shipped"] = int(cold_shipped)
    out["hbm_epoch2_bytes_shipped"] = int(warm_shipped)  # contract: 0
    out["hbm_warm_hits"] = int(warm_hits)
    if cold_rate > 0:
        out["hbm_warm_speedup"] = round(warm_rate / cold_rate, 2)
    out["hbm_bytes_resident"] = int(
        _dc.get_device_cache().bytes_resident)
    log(f"device cache epochs ({n} imgs): cold {cold_rate:.1f} vs warm "
        f"{warm_rate:.1f} img/s -> {out.get('hbm_warm_speedup')}x "
        f"(epoch-2 wire bytes {warm_shipped})")
    return out


def measure_async_dispatch():
    """async-dispatch A/B sub-bench (PIPELINE.md "Async dispatch"): the
    SAME jitted featurize-shaped reduction over the SAME frame, blocking
    executor (dispatch_depth=1, autotune off — the pre-ISSUE-10
    dispatch loop) vs the D-deep in-flight window, trials interleaved so
    machine drift hits both arms alike. Emits ``async_speedup``
    (depth-D over blocking, the ROADMAP-2 headline) and
    ``dispatch_overlap_pct`` (share of pool dispatch seconds the window
    actually hid, off the PipelineReport's ``dispatch_overlap_s``) onto
    the judged summary line; bench_sentinel bands both, so an overlap
    regression flags like the wire metrics."""
    import jax

    from tpudl import obs
    from tpudl.frame import Frame

    n = int(os.environ.get("TPUDL_BENCH_ASYNC_N", "768"))
    depth = max(2, int(os.environ.get("TPUDL_BENCH_ASYNC_DEPTH", "4")))
    batch = 64
    h = w = 64
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n, h, w, 3)).astype(np.float32)
    frame = Frame({"x": x})
    # dispatch-latency-shaped on purpose: light compute, small outputs —
    # the arm difference is the per-dispatch round-trip the window hides
    # tpudl: ignore[jit-cache-churn] — one program per sub-bench process
    # run by design; bench.py measures, it does not serve
    fn = jax.jit(lambda b: b.reshape(b.shape[0], -1).mean(axis=1))
    out = {"n": n, "batch": batch, "dispatch_depth": depth}

    def one_pass(d):
        t0 = time.perf_counter()
        res = frame.map_batches(fn, ["x"], ["y"], batch_size=batch,
                                dispatch_depth=d, fuse_steps=1,
                                autotune=False)
        np.asarray(res["y"])  # materialized
        rate = n / (time.perf_counter() - t0)
        return rate, obs.last_pipeline_report()

    for d in (1, depth):  # compile + warm both arms outside timing
        one_pass(d)
    arms = {1: [], depth: []}
    overlaps = []
    for _t in range(3):
        for d in (1, depth):
            rate, rep = one_pass(d)
            arms[d].append(rate)
            if d > 1 and rep:
                tot = (rep.get("stage_seconds") or {}).get("dispatch", 0)
                ov = rep.get("dispatch_overlap_s")
                if tot and ov is not None:
                    overlaps.append(100.0 * ov / tot)
    med = {d: statistics.median(r) for d, r in arms.items()}
    out["blocking_images_per_sec"] = round(med[1], 1)
    out["async_images_per_sec"] = round(med[depth], 1)
    if med[1] > 0:
        out["async_speedup"] = round(med[depth] / med[1], 2)
    out["dispatch_overlap_pct"] = (round(statistics.median(overlaps), 1)
                                   if overlaps else None)
    log(f"async dispatch A/B: blocking {out['blocking_images_per_sec']} "
        f"vs depth-{depth} {out['async_images_per_sec']} img/s -> "
        f"{out.get('async_speedup')}x "
        f"(overlap {out['dispatch_overlap_pct']}%)")
    return out


def measure_fault_recovery():
    """fault-recovery sub-bench (FAULTS.md, ISSUE 14): the SAME jitted
    featurize-shaped program over the SAME frame, clean supervised runs
    vs runs with ONE injected transient dispatch fault the supervisor
    recovers (a degradation rung + a full-run retry), trials
    interleaved so machine drift hits both arms alike. Emits
    ``degraded_recovery_overhead_pct`` (recovered wall over clean wall,
    minus 1 — what one absorbed fault costs end-to-end) and
    ``fault_recovery_efficiency`` (clean/recovered, its monotone
    higher-is-better twin — THE bench_sentinel band for this arm) onto
    the judged summary line, plus the hard contracts: recovered output
    bitwise-identical, zero runs died."""
    import jax

    from tpudl import obs
    from tpudl.frame import Frame
    from tpudl.testing import faults

    n = int(os.environ.get("TPUDL_BENCH_FAULT_N", "512"))
    batch = 64
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 48, 48, 3)).astype(np.float32)
    frame = Frame({"x": x})
    # tpudl: ignore[jit-cache-churn] — one program per sub-bench process
    # run by design; bench.py measures, it does not serve
    fn = jax.jit(lambda b: b.reshape(b.shape[0], -1).mean(axis=1))
    out = {"n": n, "batch": batch}

    def one_pass(inject):
        plan = (faults.FaultPlan.raise_in_stage("dispatch", at_call=1)
                if inject else None)
        t0 = time.perf_counter()
        if plan is not None:
            with plan.armed():
                res = frame.map_batches(fn, ["x"], ["y"],
                                        batch_size=batch,
                                        supervise=True,
                                        dispatch_depth=2,
                                        autotune=False)
            assert plan.fired, "the fault must actually have injected"
        else:
            res = frame.map_batches(fn, ["x"], ["y"], batch_size=batch,
                                    supervise=True, dispatch_depth=2,
                                    autotune=False)
        y = np.asarray(res["y"])  # materialized
        return time.perf_counter() - t0, y

    for inject in (False, True):  # compile + warm both arms untimed
        one_pass(inject)
    clean_t, fault_t = [], []
    parity = True
    for _t in range(3):
        clean_y = fault_y = None
        for inject in (False, True):
            dt, y = one_pass(inject)
            (fault_t if inject else clean_t).append(dt)
            if inject:
                fault_y = y
            else:
                clean_y = y
        # parity accumulated over EVERY interleaved trial pair (the
        # mesh_scaling contract): an intermittent supervisor race
        # that garbles one recovery must fail the gate
        # deterministically, not hide behind the last pair
        parity = parity and np.array_equal(clean_y, fault_y)
    med_clean = statistics.median(clean_t)
    med_fault = statistics.median(fault_t)
    out["clean_images_per_sec"] = round(n / med_clean, 1)
    out["recovered_images_per_sec"] = round(n / med_fault, 1)
    out["recovered_bitwise_identical"] = bool(parity)
    if med_clean > 0:
        out["degraded_recovery_overhead_pct"] = round(
            100.0 * (med_fault / med_clean - 1.0), 1)
        out["fault_recovery_efficiency"] = round(
            med_clean / med_fault, 3)
    rep = obs.last_pipeline_report() or {}
    out["degraded_to"] = rep.get("degraded_to")
    log(f"fault recovery ({n} imgs): clean {out['clean_images_per_sec']}"
        f" vs recovered {out['recovered_images_per_sec']} img/s -> "
        f"overhead {out.get('degraded_recovery_overhead_pct')}% "
        f"(bitwise {out['recovered_bitwise_identical']})")
    return out


def run_mesh_child(out_path):
    """Subprocess body of the mesh-scaling sub-bench (``bench.py
    --mesh-child``): on the virtual 8-device CPU mesh (the parent sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), run the
    SAME fused+async+donating+u8-codec featurize-shaped program twice —
    single-chip (mesh=None) and sharded over the 8-device mesh — via
    the ONE public ``map_batches`` API, trials interleaved. Writes a
    result JSON with both rates, their ratio, the pad overhead, and a
    bitwise parity flag (ISSUE 11 acceptance)."""
    import jax

    from tpudl import mesh as M, obs
    from tpudl.frame import Frame

    n = int(os.environ.get("TPUDL_BENCH_MESH_N", "1024"))
    batch = 64  # divisible by the 8-wide data axis: fusion stays armed
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n, 24, 24, 3)).astype(np.uint8)
    frame = Frame({"x": x})
    import jax.numpy as jnp

    def featurize(b):
        # featurize-shaped: per-row compute deep enough that the arm
        # difference is the EXECUTOR's sharding overhead, not launch
        # noise (the two arms share one CPU on the virtual mesh)
        y = b.reshape(b.shape[0], -1).astype(jnp.float32)
        for _ in range(8):
            y = jnp.tanh(y * 0.25 + 0.1)
        return y.mean(axis=1)

    # tpudl: ignore[jit-cache-churn] — one program per mesh-child
    # subprocess by design; bench.py measures, it does not serve
    jfn = jax.jit(featurize)
    mesh = M.build_mesh(n_data=8)
    kw = dict(batch_size=batch, fuse_steps=4, dispatch_depth=4,
              donate=True, wire_codec="u8", autotune=False)

    def one_pass(use_mesh):
        t0 = time.perf_counter()
        res = frame.map_batches(jfn, ["x"], ["y"],
                                mesh=mesh if use_mesh else None, **kw)
        y = np.asarray(res["y"])
        return n / (time.perf_counter() - t0), y

    for use_mesh in (False, True):  # compile + warm both arms
        one_pass(use_mesh)
    arms = {False: [], True: []}
    parity = True
    ys = {}
    for _t in range(3):
        for use_mesh in (False, True):  # interleaved: noise hits alike
            rate, y = one_pass(use_mesh)
            arms[use_mesh].append(rate)
            ys[use_mesh] = y
        # EVERY trial pair must agree — an intermittent executor race
        # that garbles one run must fail the gate deterministically
        parity = parity and bool(np.array_equal(ys[False], ys[True]))
    rep = obs.last_pipeline_report() or {}
    pad = (rep.get("stage_calls") or {}).get("pad_rows", 0)
    out = {
        "n": n, "batch": batch, "devices": 8,
        "mesh": rep.get("mesh"),
        "single_images_per_sec": round(statistics.median(arms[False]), 1),
        "mesh_images_per_sec": round(statistics.median(arms[True]), 1),
        "mesh_pad_overhead_pct": round(100.0 * pad / (n + pad), 2),
        "bitwise_parity": parity,
    }
    if out["single_images_per_sec"] > 0:
        # on the VIRTUAL mesh all 8 devices share one CPU, so this
        # ratio measures the mesh executor's OVERHEAD against the
        # single-chip fast path (1.0 = sharding costs nothing); on
        # real multi-chip hardware the same arm reads as scaling
        out["mesh_parallel_efficiency"] = round(
            out["mesh_images_per_sec"] / out["single_images_per_sec"],
            3)
    with open(out_path, "w") as f:
        json.dump(out, f)


def _cpu_child_env():
    """Environment for a bench child that is a CPU program by design (a
    virtual-device mesh, a toy model, a count): ``JAX_PLATFORMS=cpu``,
    so it never reaches for a chip this parent already holds — a chip
    belongs to one process, and a child that needs it fails or hangs."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def measure_mesh_scaling():
    """mesh-scaling sub-bench (PIPELINE.md "Mesh-native execution"):
    a virtual 8-device CPU child runs the identical fused/async/
    donating/u8 program single-chip vs data-sharded through the one
    public API. Emits ``mesh_parallel_efficiency`` (mesh over single —
    a ratio within one round, scored raw by bench_sentinel like
    ``async_speedup``) and ``mesh_pad_overhead_pct`` on the judged
    line; a parity failure is an executor bug and fails the
    sub-bench."""
    import subprocess

    me = os.path.abspath(__file__)
    env = _cpu_child_env()
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = flags.strip()
    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))
    with tempfile.TemporaryDirectory(prefix="tpudl-bench-mesh-") as td:
        out_path = os.path.join(td, "mesh.json")
        r = subprocess.run([sys.executable, me, "--mesh-child", out_path],
                           capture_output=True, text=True, env=env,
                           timeout=timeout)
        if r.returncode != 0 or not os.path.exists(out_path):
            raise RuntimeError(
                f"mesh child rc={r.returncode}: {r.stderr[-400:]}")
        with open(out_path) as f:
            out = json.load(f)
    if not out.get("bitwise_parity"):
        raise RuntimeError("mesh vs single outputs diverged (parity "
                           "failure on the virtual 8-device mesh)")
    log(f"mesh scaling (virtual 8-device): single "
        f"{out['single_images_per_sec']} vs mesh "
        f"{out['mesh_images_per_sec']} img/s -> efficiency "
        f"{out.get('mesh_parallel_efficiency')} (pad "
        f"{out['mesh_pad_overhead_pct']}%)")
    return out


def run_mesh2d_child(out_path):
    """Subprocess body of the 2-D mesh sub-bench (``bench.py
    --mesh2d-child``): on the virtual 8-device CPU mesh, run the SAME
    Megatron-shaped featurize program (column-parallel W1, row-parallel
    W2 — one model-axis all-reduce) through ``map_batches`` on an 8×1
    data-parallel grid (weights replicated) and a 4×2
    tensor-parallel grid (weights model-sharded, resident — only the
    batch rides the transfer edge), trials interleaved. Writes both
    rates, their ratio, per-device model-axis parameter bytes, and a
    parity flag (allclose — the model-axis all-reduce reassociates the
    W2 contraction, the DATA.md caveat class, so bitwise is the wrong
    bar)."""
    import jax

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudl import mesh as M
    from tpudl.frame import Frame

    n = int(os.environ.get("TPUDL_BENCH_MESH2D_N", "1024"))
    batch = 64  # divides both data axes (8 and 4): fusion stays armed
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n, 24, 24, 3)).astype(np.uint8)
    frame = Frame({"x": x})
    d_in, d_hid, d_out = 24 * 24 * 3, 512, 256
    w1 = (rng.standard_normal((d_in, d_hid)).astype(np.float32)
          / np.sqrt(d_in))
    w2 = (rng.standard_normal((d_hid, d_out)).astype(np.float32)
          / np.sqrt(d_hid))

    mesh81 = M.build_mesh(n_data=8, n_model=1)
    mesh42 = M.build_mesh(n_data=4, n_model=2)
    # the 2-D arm's weights live SHARDED over the model axis and stay
    # device-resident across every batch (the tentpole claim: only
    # activations ride the transfer edge)
    plan42 = (NamedSharding(mesh42, P(None, "model")),
              NamedSharding(mesh42, P("model", None)))
    placed = {
        "8x1": (jax.device_put(w1, NamedSharding(mesh81, P())),
                jax.device_put(w2, NamedSharding(mesh81, P()))),
        "4x2": (jax.device_put(w1, plan42[0]),
                jax.device_put(w2, plan42[1])),
    }

    def make_fn(weights):
        a, b2 = weights

        def featurize(b):
            y = b.reshape(b.shape[0], -1).astype(jnp.float32) / 255.0
            h = jnp.tanh(y @ a)      # column-parallel: hidden sharded
            return (h @ b2).mean(axis=1)  # row-parallel: one all-reduce

        return jax.jit(featurize)

    fns = {arm: make_fn(w) for arm, w in placed.items()}
    meshes = {"8x1": mesh81, "4x2": mesh42}
    kw = dict(batch_size=batch, fuse_steps=4, dispatch_depth=4,
              donate=True, wire_codec="u8", autotune=False)

    def one_pass(arm):
        t0 = time.perf_counter()
        res = frame.map_batches(fns[arm], ["x"], ["y"],
                                mesh=meshes[arm], **kw)
        y = np.asarray(res["y"])
        return n / (time.perf_counter() - t0), y

    for arm in ("8x1", "4x2"):  # compile + warm both arms
        one_pass(arm)
    arms = {"8x1": [], "4x2": []}
    parity = True
    max_dev = 0.0
    ys = {}
    for _t in range(3):
        for arm in ("8x1", "4x2"):  # interleaved: noise hits alike
            rate, y = one_pass(arm)
            arms[arm].append(rate)
            ys[arm] = y
        # EVERY trial pair must agree to the partitioned-reduction
        # tolerance — an executor race garbling one run fails the gate
        parity = parity and bool(np.allclose(ys["8x1"], ys["4x2"],
                                             rtol=1e-5, atol=1e-6))
        max_dev = max(max_dev, float(np.max(np.abs(ys["8x1"]
                                                   - ys["4x2"]))))
    out = {
        "n": n, "batch": batch, "devices": 8,
        "grid_data": {"data": 8, "model": 1},
        "grid_2d": {"data": 4, "model": 2},
        "mesh81_images_per_sec": round(statistics.median(arms["8x1"]), 1),
        "mesh42_images_per_sec": round(statistics.median(arms["4x2"]), 1),
        # what tensor parallelism buys in HBM: per-device parameter
        # bytes on each grid (the 4×2 arm holds HALF of every matrix)
        "model_axis_param_bytes_per_device": M.bytes_per_device(
            (w1, w2), plan42),
        "replicated_param_bytes_per_device": M.bytes_per_device(
            (w1, w2)),
        "allclose_parity": parity,
        "parity_max_abs_dev": max_dev,
    }
    if out["mesh81_images_per_sec"] > 0:
        # on the VIRTUAL mesh all devices share one CPU, so this ratio
        # measures the 2-D executor's overhead (model-axis collectives
        # included) against the 1-D data-parallel fast path; on real
        # hardware the same arm reads as model-sharded scaling
        out["mesh2d_parallel_efficiency"] = round(
            out["mesh42_images_per_sec"] / out["mesh81_images_per_sec"],
            3)
    with open(out_path, "w") as f:
        json.dump(out, f)


def measure_mesh_2d():
    """2-D mesh sub-bench (ISSUE 16, PIPELINE.md "Mesh-native
    execution"): a virtual 8-device CPU child runs one Megatron-shaped
    program 8×1 data-parallel vs 4×2 tensor-parallel through the one
    public API, interleaved. Emits ``mesh2d_parallel_efficiency`` (4×2
    over 8×1 — scored raw by bench_sentinel like
    ``mesh_parallel_efficiency``, floor 0.30) and the per-device
    model-axis parameter bytes on the judged line; a parity failure is
    an executor/GSPMD bug and fails the sub-bench."""
    import subprocess

    me = os.path.abspath(__file__)
    env = _cpu_child_env()
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = flags.strip()
    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))
    with tempfile.TemporaryDirectory(prefix="tpudl-bench-mesh2d-") as td:
        out_path = os.path.join(td, "mesh2d.json")
        r = subprocess.run([sys.executable, me, "--mesh2d-child",
                            out_path], capture_output=True, text=True,
                           env=env, timeout=timeout)
        if r.returncode != 0 or not os.path.exists(out_path):
            raise RuntimeError(
                f"mesh2d child rc={r.returncode}: {r.stderr[-400:]}")
        with open(out_path) as f:
            out = json.load(f)
    if not out.get("allclose_parity"):
        raise RuntimeError(
            f"4x2 vs 8x1 outputs diverged beyond the partitioned-"
            f"reduction tolerance (max abs dev "
            f"{out.get('parity_max_abs_dev')})")
    log(f"mesh 2-D (virtual 8-device): 8x1 "
        f"{out['mesh81_images_per_sec']} vs 4x2 "
        f"{out['mesh42_images_per_sec']} img/s -> efficiency "
        f"{out.get('mesh2d_parallel_efficiency')} (params/device "
        f"{out['model_axis_param_bytes_per_device']} vs replicated "
        f"{out['replicated_param_bytes_per_device']} B)")
    return out


def _cold_start_program():
    """The cold-start child's featurize-shaped program: a small conv
    stack whose XLA compile is non-trivial (seconds on CPU) while its
    restore is a deserialization.
    Deterministic seed → identical fn fingerprint in every child, so
    the warm arm's store keys match the cold arm's."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    kernels = [rng.standard_normal((3, 3, c_in, c_out)).astype(
        np.float32) * 0.1 for c_in, c_out in
        [(3, 16), (16, 16), (16, 32), (32, 32), (32, 32), (32, 32)]]

    def net(b):
        x = b.astype(jnp.float32) / 255.0
        for k in kernels:
            x = jax.nn.relu(jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")))
        return x.mean(axis=(1, 2, 3))

    return jax.jit(net)  # factory return: the child owns the program


def run_cold_start_child(out_path):
    """Subprocess body of the cold-start sub-bench (``bench.py
    --cold-start-child``): measure FIRST-RESULT latency of a
    featurize-shaped pipeline in this fresh process. The parent arms
    ``TPUDL_COMPILE_AOT`` at either an empty store (arm A: the run
    traces + compiles) or a warmed one (arm B: warm_start restores
    serialized executables and the first dispatch hits). The clock
    starts BEFORE jax import — first-result latency is a process-level
    claim, exactly what a serving relaunch pays."""
    t0 = time.perf_counter()
    from tpudl import compile as _compile, obs
    from tpudl.frame import Frame

    n = int(os.environ.get("TPUDL_BENCH_COLD_N", "256"))
    batch = 64
    restored = _compile.warm_start(block=True)  # before the first batch
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n, 48, 48, 3), dtype=np.uint8)
    frame = Frame({"x": x})
    res = frame.map_batches(_cold_start_program(), ["x"], ["y"],
                            batch_size=batch, autotune=False, aot=True)
    np.asarray(res["y"])  # first (and only) result materialized
    first_s = time.perf_counter() - t0
    # persist the background-compiled programs before exit: the warm
    # arm reads this store
    _compile.get_program_store().drain(180)
    snap = obs.snapshot()

    def val(name):
        return int(snap.get(name, {}).get("value") or 0)

    with open(out_path, "w") as f:
        json.dump({"first_result_s": round(first_s, 4),
                   "aot_programs_restored": restored,
                   "compile_hits": val("compile.hits"),
                   "compile_misses": val("compile.misses")}, f)


def measure_cold_start():
    """cold-start sub-bench (COMPILE.md, ISSUE 15): subprocess A/B of
    first-result latency with an EMPTY vs a WARMED AOT program store.
    Both arms run with the jax persistent compilation cache DISABLED so
    the A/B isolates the program store (arm A must really compile).
    Emits ``cold_start_speedup`` (cold over warm — a within-round
    ratio, scored raw by bench_sentinel like ``async_speedup``) and
    ``aot_programs_restored`` onto the judged summary line; the store
    the warm arm reads is audited by tools/validate_programs."""
    import subprocess

    me = os.path.abspath(__file__)
    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))

    def run_child(store_dir):
        env = _cpu_child_env()
        env["TPUDL_COMPILE_AOT"] = store_dir
        # isolate the A/B: no persistent XLA cache under the store
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        with tempfile.TemporaryDirectory(
                prefix="tpudl-bench-cold-") as td:
            out_path = os.path.join(td, "cold.json")
            r = subprocess.run(
                [sys.executable, me, "--cold-start-child", out_path],
                capture_output=True, text=True, env=env,
                timeout=timeout)
            if r.returncode != 0 or not os.path.exists(out_path):
                raise RuntimeError(
                    f"cold-start child rc={r.returncode}: "
                    f"{r.stderr[-400:]}")
            with open(out_path) as f:
                return json.load(f)

    out = {}
    with tempfile.TemporaryDirectory(prefix="tpudl-aot-") as warm_root:
        warm_dir = os.path.join(warm_root, "store")
        os.makedirs(warm_dir)
        seed = run_child(warm_dir)  # populates the store (a cold run)
        out["seed_first_result_s"] = seed["first_result_s"]
        colds, warms = [], []
        warm_last = None
        for _t in range(2):  # interleaved A/B (the house discipline)
            with tempfile.TemporaryDirectory(
                    prefix="tpudl-aot-empty-") as empty:
                colds.append(run_child(
                    os.path.join(empty, "s"))["first_result_s"])
            warm_last = run_child(warm_dir)
            warms.append(warm_last["first_result_s"])
        # the warmed store must audit clean (importable validator, the
        # tier-1 contract) — a corrupt store invalidates the warm arm
        sys.path.insert(0, os.path.join(os.path.dirname(me), "tools"))
        from validate_programs import validate_store_dir

        errs, n_entries, n_exe = validate_store_dir(warm_dir)
        if errs:
            raise RuntimeError(f"warm program store failed audit: "
                               f"{errs[:3]}")
        out["store_programs"] = n_entries
        out["store_executables"] = n_exe
    cold_s = statistics.median(colds)
    warm_s = statistics.median(warms)
    out["cold_first_result_s"] = round(cold_s, 4)
    out["warm_first_result_s"] = round(warm_s, 4)
    out["aot_programs_restored"] = int(
        warm_last.get("aot_programs_restored") or 0)
    out["warm_compile_hits"] = int(warm_last.get("compile_hits") or 0)
    out["warm_compile_misses"] = int(
        warm_last.get("compile_misses") or 0)
    if warm_s > 0:
        out["cold_start_speedup"] = round(cold_s / warm_s, 2)
    log(f"cold start A/B: empty store {cold_s:.2f}s vs warmed "
        f"{warm_s:.2f}s first-result -> "
        f"{out.get('cold_start_speedup')}x "
        f"({out['aot_programs_restored']} programs restored)")
    return out


def run_serve_child(out_path):
    """Subprocess body of the serve sub-bench (``bench.py
    --serve-child``): one continuous-batching serve session in a fresh
    process. The clock starts BEFORE jax import — ``first_token_s`` is
    process-start → first decoded token of the first request, model
    registration included: the TTFT a serving relaunch actually pays.
    The parent arms ``TPUDL_COMPILE_AOT`` at an empty store (cold arm:
    registration traces + compiles every serve program) or a warmed
    one (warm arm: ``warm_start`` restores serialized executables and
    registration is a deserialization). After the TTFT probe a
    closed-loop load-gen drives the sustained-QPS / p99 figures in the
    SAME process over a ragged prompt mix (every rung is already a
    compiled signature — the zero-retrace steady state the serve loop
    promises)."""
    t0 = time.perf_counter()
    from tpudl import compile as _compile, obs, serve as S
    from tpudl.zoo.transformer import TinyCausalLM

    n = int(os.environ.get("TPUDL_BENCH_SERVE_N", "48"))
    clients = int(os.environ.get("TPUDL_BENCH_SERVE_CLIENTS", "4"))
    restored = _compile.warm_start(block=True)  # before registration
    lm = TinyCausalLM(vocab=128, dim=32, heads=4, layers=2, max_len=64)
    params = lm.init(0)
    reg = S.ModelRegistry()
    # slots == default client count: the closed loop can actually
    # saturate (occupancy > 0.5 is the judged saturation claim)
    entry = reg.add_model("default", lm, params,
                          slots=max(2, clients), cache_len=48)
    # TTFT probe straight on the engine: insert() returns WITH the
    # first token decoded — the honest first-token stamp
    rng = np.random.default_rng(0)
    probe = S.ServeRequest(rng.integers(1, 128, size=4,
                                        dtype=np.int64), 4)
    slot = entry.engine.insert(probe)
    first_token_s = time.perf_counter() - t0
    entry.engine.evict(slot)
    # sustained load: closed-loop clients over a ragged length mix
    plens = (3, 5, 8, 12, 17, 24)  # 6+ distinct admission rungs

    def make_prompt(i):
        return rng.integers(1, 128, size=plens[i % len(plens)],
                            dtype=np.int64)

    srv = S.Server(reg).start_async()
    try:
        # two-tenant attribution (ISSUE 20): clients alternate between
        # tenants "a" and "b", so the child's ledger carries two scope
        # rows and the reconciliation invariant is exercised end to end
        # under real closed-loop serve load
        load = S.run_closed_loop(srv, make_prompt, requests=n,
                                 clients=clients, max_new=8,
                                 tenant=("a", "b"))
    finally:
        srv.close()
    _compile.get_program_store().drain(180)  # the warm arm reads this
    from tpudl.obs import attribution as _attr

    ledger = _attr.ledger_snapshot()
    ledger["reconcile"] = _attr.reconcile()
    snap = obs.snapshot()
    occ = (snap.get("serve.batch_occupancy") or {}).get("value")
    # the WINDOWED SLO view (ISSUE 18): same run, but recent-window
    # p99 + burn from the engine instead of the loadgen's lifetime
    # tallies — the judged line carries both so a drift between them
    # would be visible in the record
    from tpudl.obs import slo as _slo

    slo_view = _slo.get_slo_engine().publish(force=True) or {}
    with open(out_path, "w") as f:
        json.dump({"first_token_s": round(first_token_s, 4),
                   "aot_programs_restored": restored,
                   "warm_signatures": entry.warm_signatures,
                   "register_s": round(entry.warm_s, 4),
                   "qps": load["qps"],
                   "p50_ms": load["p50_ms"],
                   "p99_ms": load["p99_ms"],
                   "completed": load["completed"],
                   "rejected": load["rejected"],
                   "batch_occupancy": occ,
                   "slo_window_p99_ms": slo_view.get("window_p99_ms"),
                   "slo_burn": slo_view.get("burn_short"),
                   # the attribution evidence: the full per-tenant
                   # ledger block (validate_dump.validate_ledger_section
                   # schema) plus the scalars the judged line carries
                   "ledger": ledger,
                   "tenants": sorted(ledger["scopes"]),
                   "ledger_ok": bool(ledger["reconcile"]["ok"])}, f)


def measure_serve():
    """serve sub-bench (SERVE.md, ISSUE 17): subprocess A/B of serving
    TTFT with an EMPTY vs a WARMED AOT program store, interleaved like
    the cold-start A/B, plus a closed-loop load-gen in every child.
    Emits ``sustained_qps`` (scored raw by bench_sentinel like
    ``async_speedup``), ``p99_ms`` and ``warm_ttft_s`` (both banded
    lower-is-better), the warm/cold TTFT ratio, and slot saturation
    (``batch_occupancy``) onto the judged summary line; the p99 is
    judged against the fixed ``TPUDL_BENCH_SERVE_P99_MS`` target."""
    import subprocess

    me = os.path.abspath(__file__)
    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))
    p99_target = float(os.environ.get("TPUDL_BENCH_SERVE_P99_MS",
                                      "2000"))

    def run_child(store_dir):
        env = _cpu_child_env()
        env["TPUDL_COMPILE_AOT"] = store_dir
        # isolate the A/B: no persistent XLA cache under the store
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        with tempfile.TemporaryDirectory(
                prefix="tpudl-bench-serve-") as td:
            out_path = os.path.join(td, "serve.json")
            r = subprocess.run(
                [sys.executable, me, "--serve-child", out_path],
                capture_output=True, text=True, env=env,
                timeout=timeout)
            if r.returncode != 0 or not os.path.exists(out_path):
                raise RuntimeError(
                    f"serve child rc={r.returncode}: "
                    f"{r.stderr[-400:]}")
            with open(out_path) as f:
                return json.load(f)

    out = {}
    with tempfile.TemporaryDirectory(prefix="tpudl-serve-") as warm_root:
        warm_dir = os.path.join(warm_root, "store")
        os.makedirs(warm_dir)
        seed = run_child(warm_dir)  # populates the store (a cold run)
        out["seed_first_token_s"] = seed["first_token_s"]
        colds, warms = [], []
        warm_runs: list = []
        for _t in range(2):  # interleaved A/B (the house discipline)
            with tempfile.TemporaryDirectory(
                    prefix="tpudl-serve-empty-") as empty:
                colds.append(run_child(
                    os.path.join(empty, "s"))["first_token_s"])
            warm_runs.append(run_child(warm_dir))
            warms.append(warm_runs[-1]["first_token_s"])
    cold_ttft = statistics.median(colds)
    warm_ttft = statistics.median(warms)
    out["cold_ttft_s"] = round(cold_ttft, 4)
    out["warm_ttft_s"] = round(warm_ttft, 4)
    if warm_ttft > 0:
        out["serve_ttft_speedup"] = round(cold_ttft / warm_ttft, 2)
    last = warm_runs[-1]
    out["aot_programs_restored"] = int(
        last.get("aot_programs_restored") or 0)
    out["warm_signatures"] = int(last.get("warm_signatures") or 0)
    # SLO figures from the WARM arms (steady state, store restored)
    out["sustained_qps"] = round(statistics.median(
        [w["qps"] for w in warm_runs if w.get("qps")]), 3)
    out["p50_ms"] = statistics.median(
        [w["p50_ms"] for w in warm_runs if w.get("p50_ms")])
    out["p99_ms"] = statistics.median(
        [w["p99_ms"] for w in warm_runs if w.get("p99_ms")])
    out["p99_target_ms"] = p99_target
    out["p99_met"] = bool(out["p99_ms"] <= p99_target)
    out["batch_occupancy"] = last.get("batch_occupancy")
    out["completed"] = int(last.get("completed") or 0)
    out["rejected"] = int(last.get("rejected") or 0)
    # windowed SLO figures from the engine (ISSUE 18), medianed over
    # the warm arms like the loadgen figures they ride beside
    slo_p99s = [w["slo_window_p99_ms"] for w in warm_runs
                if isinstance(w.get("slo_window_p99_ms"), (int, float))]
    burns = [w["slo_burn"] for w in warm_runs
             if isinstance(w.get("slo_burn"), (int, float))]
    out["slo_window_p99_ms"] = (round(statistics.median(slo_p99s), 3)
                                if slo_p99s else None)
    out["slo_burn"] = (round(statistics.median(burns), 3)
                       if burns else None)
    # the two-tenant attribution evidence (ISSUE 20) from the last warm
    # arm: the per-tenant ledger block rides on the trial record, the
    # tenant count and reconciliation verdict on the judged line
    out["ledger"] = last.get("ledger")
    out["tenants"] = last.get("tenants") or []
    out["ledger_ok"] = last.get("ledger_ok")
    log(f"serve A/B: cold TTFT {cold_ttft:.2f}s vs warm "
        f"{warm_ttft:.2f}s ({out.get('serve_ttft_speedup')}x, "
        f"{out['aot_programs_restored']} programs restored) | "
        f"sustained {out['sustained_qps']} qps, p99 "
        f"{out['p99_ms']}ms (target {p99_target:.0f}ms "
        f"{'met' if out['p99_met'] else 'MISSED'}), occupancy "
        f"{out['batch_occupancy']} | windowed p99 "
        f"{out['slo_window_p99_ms']}ms, burn {out['slo_burn']}")
    return out


def run_lm_train_child(out_path):
    """Subprocess body of the lm_train sub-bench (``bench.py
    --lm-train-child``): a 2-epoch tokenized fine-tune of the zoo LM
    over a string column via ``tpudl.text.lm_dataset`` — tokenize +
    dense-pack on the prepare pool, TokenCodec u16 ids on the wire,
    HBM-tier batch residency. Epoch 1 is the cold arm (tokenize +
    ship); epoch 2 is the judged warm arm and must replay RESIDENT
    batches: the child records the epoch-2 ``text.tokenize.calls`` and
    ``data.wire.bytes_shipped`` deltas, which the tier-1 warm-replay
    test (tests/test_text.py) pins to exactly zero."""
    import jax

    import jax.numpy as jnp
    import optax

    from tpudl import obs
    from tpudl.frame import Frame
    from tpudl.text import ByteTokenizer, lm_dataset
    from tpudl.zoo.transformer import TinyCausalLM

    rows = int(os.environ.get("TPUDL_BENCH_LM_ROWS", "192"))
    seq = int(os.environ.get("TPUDL_BENCH_LM_SEQ", "64"))
    batch = int(os.environ.get("TPUDL_BENCH_LM_BATCH", "32"))
    rows -= rows % batch or batch  # full frame batches: stable shapes
    # uniform (seq-1)-byte docs: each +eos packs to exactly seq tokens,
    # so every prepared batch is [batch, seq] — ONE compiled train step
    base = "the quick brown fox jumps over the lazy dog again and "
    texts = [(f"{i:06d} " + base)[: seq - 1] for i in range(max(rows, 1))]
    frame = Frame({"text": np.array(texts, dtype=object)})
    tok = ByteTokenizer()
    lm = TinyCausalLM(vocab=tok.vocab_size, dim=64, heads=4, layers=2,
                      max_len=seq)
    params = jax.tree.map(jnp.asarray, lm.init(0))
    ds = lm_dataset(frame, "text", tok, seq_len=seq, batch_size=batch,
                    device_cache=True)
    loss = lm.loss_fn()
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, o, wire):
        tokens = wire.astype(jnp.int32)  # the TokenCodec prologue
        l, g = jax.value_and_grad(loss)(p, tokens)
        updates, o = opt.update(g, o)
        return optax.apply_updates(p, updates), o, l

    def counters():
        snap = obs.snapshot()
        return {k: int((snap.get(k) or {}).get("value") or 0)
                for k in ("text.tokenize.calls",
                          "data.wire.bytes_shipped")}

    epochs = []
    losses = []
    for epoch in range(2):
        c0 = counters()
        t0 = time.perf_counter()
        n_tok = 0
        for (wire,) in ds.iter_epoch(epoch):
            params, opt_state, l = step(params, opt_state, wire)
            n_tok += int(np.prod(np.shape(wire)))
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0
        c1 = counters()
        losses.append(float(l))
        epochs.append({
            "tokens": n_tok, "seconds": round(dt, 4),
            "tokens_per_sec": round(n_tok / dt, 1) if dt > 0 else None,
            "tokenize_calls": c1["text.tokenize.calls"]
            - c0["text.tokenize.calls"],
            "wire_bytes": c1["data.wire.bytes_shipped"]
            - c0["data.wire.bytes_shipped"]})
    cold, warm = epochs
    out = {"tokens_per_sec": warm["tokens_per_sec"],
           "cold_tokens_per_sec": cold["tokens_per_sec"],
           "epoch2_tokenize_calls": warm["tokenize_calls"],
           "epoch2_wire_bytes": warm["wire_bytes"],
           "warm_epoch_speedup": (
               round(cold["seconds"] / warm["seconds"], 2)
               if warm["seconds"] > 0 else None),
           "loss_first": round(losses[0], 4),
           "loss_last": round(losses[-1], 4),
           "forward": "zoo", "rows": len(texts), "seq_len": seq,
           "batch_rows": batch}
    with open(out_path, "w") as f:
        json.dump(out, f)


def run_lm_generate_child(out_path):
    """Subprocess body of the lm_generate sub-bench (``bench.py
    --lm-generate-child``): an LMGenerator transform over a RAGGED
    prompt column (6 distinct byte lengths → a handful of pow2 rungs).
    A one-prompt-per-rung warmup compiles the bucketed programs first,
    so ``tokens_per_sec`` is the steady state the zero-retrace sweep
    proves; ``first_transform_s`` keeps the compile cost on the
    record."""
    t0 = time.perf_counter()
    from tpudl import obs
    from tpudl.frame import Frame
    from tpudl.ml import LMGenerator
    from tpudl.text import ByteTokenizer
    from tpudl.zoo.transformer import TinyCausalLM

    n = int(os.environ.get("TPUDL_BENCH_LM_PROMPTS", "48"))
    max_new = int(os.environ.get("TPUDL_BENCH_LM_MAX_NEW", "8"))
    tok = ByteTokenizer()
    lm = TinyCausalLM(vocab=tok.vocab_size, dim=32, heads=4, layers=2,
                      max_len=64)
    params = lm.init(0)
    plens = (3, 5, 8, 12, 17, 24)  # the serve child's ragged mix
    base = "abcdefghijklmnopqrstuvwxyz"
    prompts = [base[: plens[i % len(plens)]] for i in range(n)]
    gen = LMGenerator(inputCol="text", outputCol="gen", model=lm,
                      weights=params, tokenizer=tok, maxNew=max_new,
                      batchSize=8, promptBuckets="pow2")
    # warmup: one prompt per distinct length compiles every (batch
    # rung=1, prompt rung) program this mix can dispatch
    warm_frame = Frame({"text": np.array(
        [base[: p] for p in plens], dtype=object)})
    gen.transform(warm_frame)
    first_transform_s = time.perf_counter() - t0

    def gen_tokens():
        snap = obs.snapshot()
        return int((snap.get("lm.generate.tokens") or {}).get("value")
                   or 0)

    g0 = gen_tokens()
    t1 = time.perf_counter()
    frame = Frame({"text": np.array(prompts, dtype=object)})
    gen.transform(frame)
    dt = time.perf_counter() - t1
    n_new = gen_tokens() - g0
    with open(out_path, "w") as f:
        json.dump({"tokens_per_sec": (round(n_new / dt, 1)
                                      if dt > 0 else None),
                   "generated_tokens": n_new,
                   "requests": n,
                   "max_new": max_new,
                   "first_transform_s": round(first_transform_s, 4),
                   "gen_programs": len(lm._gen_jits)}, f)


def _run_lm_child(flag, prefix):
    """Run one lm child subprocess (a CPU program, see
    :func:`_cpu_child_env`) and return its JSON record."""
    import subprocess

    me = os.path.abspath(__file__)
    timeout = float(os.environ.get("TPUDL_BENCH_TRIAL_TIMEOUT_S", "450"))
    with tempfile.TemporaryDirectory(prefix=prefix) as td:
        out_path = os.path.join(td, "lm.json")
        r = subprocess.run([sys.executable, me, flag, out_path],
                           capture_output=True, text=True,
                           env=_cpu_child_env(), timeout=timeout)
        if r.returncode != 0 or not os.path.exists(out_path):
            raise RuntimeError(
                f"{flag} child rc={r.returncode}: {r.stderr[-400:]}")
        with open(out_path) as f:
            return json.load(f)


def measure_lm_train():
    """lm_train sub-bench (ROADMAP item 4, TEXT.md): tokens/s of a
    tokenized 2-epoch LM fine-tune through the full text pipeline —
    tokenize+pack on the prepare pool, TokenCodec wire, HBM-resident
    epoch 2. The judged scalar is the WARM epoch's tokens/s; the
    epoch-2 tokenize-call and wire-byte deltas ride the record as the
    zero-decode/zero-wire evidence (both must read 0)."""
    trials = [_run_lm_child("--lm-train-child", "tpudl-lm-train-")
              for _ in range(2)]
    out = dict(trials[-1])
    rates = [t["tokens_per_sec"] for t in trials
             if t.get("tokens_per_sec")]
    if rates:
        out["lm_train_tokens_per_sec"] = round(statistics.median(rates),
                                               1)
    out["lm_epoch2_tokenize_calls"] = int(
        max(t.get("epoch2_tokenize_calls") or 0 for t in trials))
    out["lm_epoch2_wire_bytes"] = int(
        max(t.get("epoch2_wire_bytes") or 0 for t in trials))
    out["lm_warm_epoch_speedup"] = out.get("warm_epoch_speedup")
    log(f"lm_train: {out.get('lm_train_tokens_per_sec')} tokens/s warm "
        f"(cold {out.get('cold_tokens_per_sec')}), epoch-2 deltas: "
        f"{out['lm_epoch2_tokenize_calls']} tokenize calls, "
        f"{out['lm_epoch2_wire_bytes']} wire bytes "
        f"[forward={out.get('forward')}]")
    return out


def measure_lm_generate():
    """lm_generate sub-bench: steady-state generated tokens/s of an
    LMGenerator transform over a ragged prompt column, every dispatch
    on warmed bucket-ladder programs (the O(log n) signature claim,
    traceck-proven in tier-1)."""
    trials = [_run_lm_child("--lm-generate-child", "tpudl-lm-gen-")
              for _ in range(2)]
    out = dict(trials[-1])
    rates = [t["tokens_per_sec"] for t in trials
             if t.get("tokens_per_sec")]
    if rates:
        out["lm_generate_tokens_per_sec"] = round(
            statistics.median(rates), 1)
    out["lm_generate_programs"] = int(out.get("gen_programs") or 0)
    log(f"lm_generate: {out.get('lm_generate_tokens_per_sec')} tokens/s "
        f"({out.get('generated_tokens')} tokens over "
        f"{out.get('requests')} ragged prompts, "
        f"{out['lm_generate_programs']} compiled programs)")
    return out


def run_preemption_job(workdir, out_path, steps, save_every,
                       progress_path):
    """Subprocess body of the preemption sub-bench (``bench.py
    --preemption-job``): one toy-linreg JobRuntime fit. Writes a result
    JSON {start_step, wall_s} on completion; a SIGTERM mid-run exits
    RC_PREEMPTED (75) with resume state in ``workdir``; every step's
    index is appended to ``progress_path`` so the parent knows how far
    the killed run got."""
    import numpy as _np

    import jax.numpy as jnp
    import optax

    from tpudl.jobs import JobRuntime, JobSpec
    from tpudl.train import Trainer

    rng = _np.random.default_rng(0)
    X = rng.normal(size=(512, 8)).astype(_np.float32)
    w_true = rng.normal(size=(8, 1)).astype(_np.float32)
    yv = X @ w_true + 0.1
    started = {"step": None}

    def data_fn(step, batch=64):
        if started["step"] is None:
            started["step"] = int(step)  # the resume point, observed
        with open(progress_path, "a") as f:
            f.write(f"{step}\n")
        i = (step * batch) % (len(X) - batch + 1)
        return X[i:i + batch], yv[i:i + batch]

    def loss_fn(p, x, t):
        return jnp.mean((x @ p["w"] + p["b"] - t) ** 2)

    params0 = {"w": jnp.zeros((8, 1)), "b": jnp.zeros(())}
    spec = JobSpec("fit", workdir,
                   material={"model": "bench-linreg", "steps": int(steps)},
                   save_every=int(save_every))
    rt = JobRuntime(spec)
    trainer = Trainer(loss_fn, optax.adam(0.05))
    t0 = time.perf_counter()
    rt.run_fit(trainer, params0, data_fn, int(steps),
               exit_on_preempt=True)
    with open(out_path, "w") as f:
        json.dump({"start_step": started["step"] or 0,
                   "wall_s": time.perf_counter() - t0}, f)


def measure_preemption(steps=None, save_every=25):
    """The robustness sub-bench (JOBS.md): kill a JobRuntime fit at
    ~50% of its measured budget and measure RESUME REWORK — the
    seconds the relaunched run spends re-executing steps it had already
    done. Two kills: SIGTERM (graceful — the runtime checkpoints at the
    boundary, expected rework ≈ 0 and rc=75) and SIGKILL (hard — no
    boundary, rework bounded by ``save_every`` steps). The judged line
    carries ``preempt_rework_s`` (the hard-kill figure: the honest
    worst case) and the graceful rc."""
    import shutil
    import subprocess
    import tempfile

    steps = int(steps if steps is not None
                else os.environ.get("TPUDL_BENCH_PREEMPT_STEPS", "300"))
    base = tempfile.mkdtemp(prefix="tpudl-bench-preempt-")
    me = os.path.abspath(__file__)
    # a toy linear regression, launched while this parent holds the
    # device: it must never ask for the chip
    child_env = _cpu_child_env()

    def launch(tag, workdir):
        out = os.path.join(base, f"{tag}.json")
        progress = os.path.join(base, f"{tag}.progress")
        cmd = [sys.executable, me, "--preemption-job", workdir, out,
               str(steps), str(save_every), progress]
        return cmd, out, progress

    def last_progress(progress):
        try:
            with open(progress) as f:
                lines = f.read().split()
            return int(lines[-1]) if lines else 0
        except (OSError, ValueError, IndexError):
            return 0

    rec = {"steps": steps, "save_every": save_every}
    # 1) uninterrupted reference: the 100% budget + per-step seconds.
    # per_step comes from the CHILD's own run_fit wall clock (written
    # to its result JSON), not the subprocess wall — interpreter + jax
    # import dominate the latter, and rework seconds derived from it
    # would mostly measure startup, not rework
    cmd, out, _prog = launch("ref", os.path.join(base, "ref_job"))
    t0 = time.perf_counter()
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=child_env)
    t_full = time.perf_counter() - t0
    if r.returncode != 0:
        raise RuntimeError(f"reference job failed rc={r.returncode}: "
                           f"{r.stderr[-400:]}")
    with open(out) as f:
        ref_res = json.load(f)
    per_step = float(ref_res["wall_s"]) / max(1, steps)
    rec["full_run_s"] = round(t_full, 3)
    rec["fit_wall_s"] = round(float(ref_res["wall_s"]), 3)
    rec["per_step_s"] = round(per_step, 5)

    for tag, sig, rc_expected in (("graceful", signal.SIGTERM, 75),
                                  ("hard", signal.SIGKILL, -9)):
        workdir = os.path.join(base, f"{tag}_job")
        cmd, out, prog = launch(tag, workdir)
        proc = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL, env=child_env)
        # the 50%-budget kill point, measured in actual step progress
        # (wall-clock timing would race the child's interpreter/jax
        # startup and kill before the runtime even armed its handler)
        deadline = time.time() + 120
        while time.time() < deadline:
            if last_progress(prog) >= steps // 2 \
                    or proc.poll() is not None:
                break
            time.sleep(0.02)
        proc.send_signal(sig)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = proc.wait()
        at_kill = last_progress(prog)
        rec[f"{tag}_kill_rc"] = rc
        rec[f"{tag}_kill_step"] = at_kill
        rec[f"{tag}_rc_contract"] = (rc == rc_expected)
        # relaunch the SAME spec → must complete, resuming from the
        # persisted state
        cmd2, out2, _ = launch(f"{tag}_resume", workdir)
        r2 = subprocess.run(cmd2, capture_output=True, text=True,
                            timeout=600, env=child_env)
        if r2.returncode != 0:
            rec[f"{tag}_resume_error"] = r2.stderr[-300:]
            continue
        with open(out2) as f:
            res = json.load(f)
        start = int(res.get("start_step") or 0)
        rework = max(0, at_kill - start)
        rec[f"{tag}_resume_start_step"] = start
        rec[f"{tag}_rework_steps"] = rework
        rec[f"{tag}_rework_s"] = round(rework * per_step, 4)
        rec[f"{tag}_resume_wall_s"] = round(float(res.get("wall_s", 0)), 3)
    # rework bound audit: hard-kill rework must stay ≤ save_every
    if isinstance(rec.get("hard_rework_steps"), int):
        rec["hard_rework_bounded"] = (rec["hard_rework_steps"]
                                      <= save_every)
    log(f"preemption: graceful rc={rec.get('graceful_kill_rc')} "
        f"rework={rec.get('graceful_rework_steps')} steps; hard "
        f"rework={rec.get('hard_rework_steps')} steps "
        f"({rec.get('hard_rework_s')}s, save_every={save_every})")
    shutil.rmtree(base, ignore_errors=True)
    return rec


def measure_flash_attention():
    """Pallas flash-attention kernel vs dense XLA attention on the live
    backend (causal, H=8, D=128) at an S-SCALING ladder — round-3
    verdict item 6: show the kernel at lengths where dense's S² score
    tensor actually hurts (S=8192 causal: 8 heads × 8192² × 4B ≈ 2 GB of
    scores dense must materialize; the flash kernel streams O(S·block)).
    A dense OOM at the top length is recorded as the structural win it
    is, not an error. Honest barrier: the reps' scalar outputs chain
    into ONE data-dependent value fetched at the end, so the queue fully
    drains (per-call dispatch latency is amortized across reps — this
    measures sustained throughput, not round-trip latency)."""
    import jax
    import jax.numpy as jnp

    from tpudl.attention import attention_reference
    from tpudl.pallas_ops import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    b, h, d = 1, 8, 128
    s_ladder = ([256] if not on_tpu else
                [int(s) for s in os.environ.get(
                    "TPUDL_BENCH_FLASH_SEQS",
                    "2048,4096,8192,16384").split(",")])
    reps = 8
    rng = np.random.default_rng(1)
    ladder = []
    for s in s_ladder:
        q, k, v = (jnp.asarray(
            rng.normal(size=(b, s, h, d)).astype(np.float32))
            for _ in range(3))
        # tpudl: ignore[jit-cache-churn] — a fresh program per rung of
        # the sequence-length ladder IS the sub-bench (each shape
        # compiles its own kernel); the trace cost is outside the timer
        flash = jax.jit(lambda a, x, y: jnp.sum(
            flash_attention(a, x, y, causal=True)))
        # tpudl: ignore[jit-cache-churn] — same ladder contract as the
        # flash arm above: per-shape programs, traced outside the timer
        dense = jax.jit(lambda a, x, y: jnp.sum(
            attention_reference(a, x, y, causal=True)))

        def timed_once(compiled):
            t0 = time.perf_counter()
            acc = jnp.zeros(())
            for _ in range(reps):
                acc = acc + compiled(q, k, v)
            float(acc)
            return (time.perf_counter() - t0) / reps * 1e3

        entry = {"seq_len": s}
        compiled = {}
        for kind, fn in (("flash", flash), ("dense", dense)):
            # ONE AOT compile serves both the memory record and the
            # timing (a second jit-path compile would double the rung's
            # compile cost at long S)
            try:
                compiled[kind] = fn.lower(q, k, v).compile()
            except Exception as e:
                entry[f"{kind}_error"] = repr(e)[:200]
                continue
            try:
                # compiler-certified STRUCTURAL memory: XLA's own
                # memory_analysis (static — no timing in it). The S² score materialization lives in temp;
                # the flash kernel's VMEM tiles do not. Recorded even
                # when EXECUTION below fails — a dense OOM at long S is
                # exactly when this number is the result.
                ma = compiled[kind].memory_analysis()
                if ma:
                    entry[f"{kind}_temp_mb"] = round(
                        ma.temp_size_in_bytes / 2**20, 1)
            except Exception as e:
                log(f"memory_analysis failed: {e!r}")
        # Interleaved counterbalanced trials (round-4 verdict weak #3:
        # single wall-clock values per rung couldn't distinguish "XLA
        # got lucky" from "flash stops winning"). Each trial times both
        # kernels back-to-back in alternating order; medians + the full
        # trial lists land in the record, same pattern as the featurize
        # bench.
        trials = {"flash": [], "dense": []}
        for kind in compiled:
            try:
                float(compiled[kind](q, k, v))  # warm once
            except Exception as e:
                entry[f"{kind}_error"] = repr(e)[:200]
                compiled = {k2: c for k2, c in compiled.items()
                            if k2 != kind}
        for t in range(3):
            order = (("flash", "dense") if t % 2 == 0
                     else ("dense", "flash"))
            for kind in order:
                if kind not in compiled:
                    continue
                try:
                    trials[kind].append(timed_once(compiled[kind]))
                except Exception as e:
                    # dense falling over at long S IS a result; keep it
                    # alongside the structural temp bytes above
                    entry[f"{kind}_error"] = repr(e)[:200]
                    compiled.pop(kind, None)
        for kind, ts in trials.items():
            if not ts:
                continue
            if f"{kind}_error" in entry:
                # failed mid-ladder: keep the partial evidence but do
                # NOT present a median as a clean counterbalanced
                # measurement (or feed it into speedup)
                entry[f"{kind}_partial_trials_ms"] = [round(x, 2)
                                                     for x in ts]
                continue
            entry[f"{kind}_ms"] = round(statistics.median(ts), 2)
            entry[f"{kind}_trials_ms"] = [round(x, 2) for x in ts]
        if "flash_ms" in entry and "dense_ms" in entry:
            entry["speedup"] = round(entry["dense_ms"] / entry["flash_ms"],
                                     2)
        ladder.append(entry)
        log(f"attention S={s} H={h} D={d} causal: "
            f"dense {entry.get('dense_ms', entry.get('dense_error'))} ms, "
            f"pallas flash "
            f"{entry.get('flash_ms', entry.get('flash_error'))} ms"
            + (" [interpret mode — not a kernel measurement]"
               if not on_tpu else ""))
        del q, k, v

    out = dict(ladder[0])  # S=2048 keeps the round-3 record's shape
    out["s_ladder"] = ladder
    # off-TPU the kernel runs in interpret mode: timings there are an
    # interpreter artifact, flagged so the record can't be read as a
    # kernel regression
    out["interpret"] = not on_tpu
    return out


def measure_healthy_channel_e2e(batch, dtype, n_batches=4):
    """End-to-end featurize on a process that has read nothing back yet
    — must run FIRST, before any device→host read in the process.

    The July 2026 rounds saw a process's uploads drop to per-transfer
    synchronization for good after its first device→host read, so every
    earlier e2e number (whose compile warm-up fetched a value) was a
    post-fetch number. Whether a first fetch changes anything on the
    current machine is not measured; this sub-bench is the arm that
    would show it.

    It compiles AOT (``.lower().compile()`` — no execution, no fetch),
    uploads + executes ``n_batches`` exactly like ``map_batches``
    acc-mode (one materialization at the end), and times everything
    INCLUDING the final fetch, which is where the enqueued uploads
    actually drain. ``enqueue_seconds`` (before any await) and
    ``blocked_seconds`` (after ``block_until_ready``) are kept to show
    the enqueue/delivery asymmetry against the fetched total."""
    import jax
    import jax.numpy as jnp

    step, params, xd = build_featurize_step(batch, dtype)
    lowered = step.lower(params, xd)
    compiled = lowered.compile()  # AOT: no execution, no fetch
    del xd
    rng = np.random.default_rng(1)
    hosts = [rng.integers(0, 256, size=(batch, 299, 299, 3),
                          dtype=np.uint8) for _ in range(n_batches)]
    # one warm execution, result left on device (block, never read)
    jax.block_until_ready(compiled(params, jax.device_put(hosts[0])))

    t0 = time.perf_counter()
    outs = []
    for x in hosts:
        outs.append(compiled(params, jax.device_put(x)))
    t_enq = time.perf_counter() - t0      # true enqueue (nothing awaited)
    jax.block_until_ready(outs)
    t_blocked = time.perf_counter() - t0  # after the barrier
    total = float(sum(outs))  # the ONE fetch (device-side add chain)
    dt = time.perf_counter() - t0
    assert np.isfinite(total)
    n = batch * n_batches
    log(f"streaming-mode e2e: {n} images in {dt:.2f}s "
        f"(enqueue {t_enq:.2f}s, blocked {t_blocked:.2f}s) -> "
        f"{n / dt:.1f} img/s/chip (pre-first-fetch pipelined mode)")
    return {"images_per_sec": round(n / dt, 1),
            "enqueue_seconds": round(t_enq, 2),
            "blocked_seconds": round(t_blocked, 2),
            "n_images": n, "batch": batch}


def _quiet_wire_probe(mb=8):
    """8 MB H2D probe that returns None instead of raising — the
    bracketing probes around sub-benches must never kill the sub-bench
    they annotate."""
    try:
        return measure_wire_bandwidth(mb=mb)["h2d_mb_per_sec"]
    except Exception as e:
        log(f"wire probe failed: {e!r}")
        return None


def measure_wire_bandwidth(mb=64):
    """Raw host→device and device→host bandwidth of the backend link,
    measured with a bare device_put / device_get of one contiguous
    buffer. Where e2e img/s ≈ wire_MBps / image_bytes, the executor is
    wire-bound and the gap to compute-only is the link, not the code
    (the 'prove the wire bound' artifact)."""
    import jax

    x = np.random.default_rng(0).integers(
        0, 256, size=(mb << 20,), dtype=np.uint8)
    jax.block_until_ready(jax.device_put(x[: 1 << 20]))  # warm path
    t0 = time.perf_counter()
    xd = jax.block_until_ready(jax.device_put(x))
    h2d = mb / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    np.asarray(xd)
    d2h = mb / (time.perf_counter() - t0)
    log(f"wire bandwidth ({mb} MB buffer): H2D {h2d:.0f} MB/s, "
        f"D2H {d2h:.0f} MB/s")
    return {"h2d_mb_per_sec": round(h2d, 1), "d2h_mb_per_sec": round(d2h, 1),
            "buffer_mb": mb}


def measure_tf_cpu_baseline(k=64, batch=32, trials=3):
    """The reference path's substrate: Keras InceptionV3 (no top, avg
    pool) on TF-CPU — what sparkdl's executors ran when no GPU was
    present. Random weights; arithmetic cost is identical. 3-trial
    median with every trial reported, so the record shows the baseline
    is measured live each run."""
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
    _silence_tf_logs()
    import keras

    log("building TF-CPU InceptionV3 baseline ...")
    model = keras.applications.InceptionV3(weights=None, include_top=False,
                                           pooling="avg")
    x = np.random.default_rng(0).integers(
        0, 256, size=(k, 299, 299, 3)).astype(np.float32)
    x = x / 127.5 - 1.0
    model.predict(x[:batch], batch_size=batch, verbose=0)  # warmup
    rates = []
    for t in range(trials):
        t0 = time.perf_counter()
        model.predict(x, batch_size=batch, verbose=0)
        dt = time.perf_counter() - t0
        rates.append(k / dt)
        log(f"TF-CPU baseline trial {t}: {k} images in {dt:.3f}s -> "
            f"{rates[-1]:.3f} images/sec")
    value = statistics.median(rates)
    log(f"TF-CPU baseline median of {trials}: {value:.3f} images/sec")
    # 3 decimals so consecutive runs visibly differ (a .2f record showed
    # bit-identical trials two rounds running)
    return {"value": value, "trials": [round(r, 3) for r in rates]}


# InceptionV3 forward ≈ 6 GFLOPs/image; ResNet50 forward ≈ 4.1 GFLOPs
# (train ≈ 3× forward).
_INCEPTION_FLOPS = 6e9
_RESNET50_TRAIN_FLOPS = 3 * 4.1e9
# bf16 peak FLOP/s of ONE chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A kind that
# is not in the table is an error, never a default: a utilisation
# divided by another chip's peak is a wrong number, not a rough one.
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops(device_kind=None) -> float:
    """Peak bf16 FLOP/s of ``device_kind`` (default: the live backend's
    first device); raises for a chip the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r} in "
            f"bench._PEAK_BF16_FLOPS (have {sorted(_PEAK_BF16_FLOPS)}): "
            f"add it with its source before reporting a utilisation"
        ) from None


def main():
    from tpudl.testing import tsan as _tsan

    if _tsan.enabled():
        # the sanitizer instruments every product lock — a judged
        # round under TPUDL_TSAN=1 would silently tax the numbers.
        # Refuse loudly instead of benching slow (CONCURRENCY.md).
        print("bench: refusing to run judged rounds with the lock "
              "sanitizer armed (unset TPUDL_TSAN)", file=sys.stderr)
        raise SystemExit(1)
    from tpudl.testing import traceck as _traceck

    if _traceck.enabled():
        # same contract for the recompile-storm sentinel: its jax.jit
        # shim adds a bookkeeping hop per trace, and judged numbers
        # must never carry an invisible tax (ANALYSIS.md)
        print("bench: refusing to run judged rounds with the traceck "
              "sentinel armed (unset TPUDL_TRACECK)", file=sys.stderr)
        raise SystemExit(1)
    dtype = os.environ.get("TPUDL_BENCH_DTYPE", "bfloat16")
    log(f"compute dtype: {dtype} (standard TPU inference precision; "
        "set TPUDL_BENCH_DTYPE=float32 for full-precision numbers)")
    batch = int(os.environ.get("TPUDL_BENCH_BATCH", "256"))
    n = int(os.environ.get("TPUDL_BENCH_N", "1024"))
    n = max(batch, n - n % batch)  # whole batches, at least one
    # per-arm counts: the ≥4-per-arm interleaved-A/B contract (round-3
    # verdict item 1) now lives on the streaming record — the product's
    # real fresh-process rate; the in-process synchronized A/B stays as
    # the cross-round-comparable secondary at a reduced default
    quick = os.environ.get("TPUDL_BENCH_QUICK", "0") == "1"
    stream_trials = int(os.environ.get("TPUDL_BENCH_STREAM_TRIALS",
                                       "1" if quick else "4"))
    trials = int(os.environ.get("TPUDL_BENCH_TRIALS", "2"))

    # the watchdog emits this dict if a backend RPC wedges — every
    # sub-bench writes its result in as soon as it completes
    extra = {
        "metric": "images/sec/chip (DeepImageFeaturizer InceptionV3)",
        "unit": "images/sec/chip",
        "compute_dtype": dtype,
        "batch_size": batch,
        "baseline": "keras InceptionV3 on TF-CPU (fp32), this host",
    }
    _arm_flight_recorder()  # before the handlers below: SIGTERM path
    _start_watchdog(extra)  # dumps via _install_sigterm_flush's handler
    _install_sigterm_flush(extra)
    log(f"bench budget: {_budget_s():.0f}s (TPUDL_BENCH_BUDGET_S)")

    # 1) Fresh-process subprocess trials FIRST, before this process
    #    initializes its backend: TPU runtimes are single-process-per-
    #    chip, so the parent must not hold the device while a trial
    #    subprocess needs it (see run_featurize_trial).
    feat_stream = None
    if stream_trials > 0 and _gate(extra, "featurize_streaming"):
        try:
            # writes value/headline_mode/featurize_streaming into
            # ``extra`` incrementally as trials complete (watchdog-safe)
            feat_stream = measure_featurize_streaming(n, batch, dtype,
                                                      stream_trials,
                                                      extra=extra)
        except Exception as e:
            log(f"streaming featurize sub-bench failed: {e!r}")

    # 2) Only now bring up this process's backend.
    import jax

    from tpudl.compilation_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devs = jax.devices()
    log(f"backend: {devs[0].platform} x{len(devs)} ({devs[0].device_kind})")
    log(f"persistent compile cache: {cache_dir or 'disabled'}")

    if devs[0].platform == "tpu" and _gate(extra, "streaming_mode_e2e"):
        try:
            # valid only before the parent's first device->host read —
            # the subprocess trials above fetched in THEIR processes,
            # not this one (see measure_healthy_channel_e2e)
            extra["streaming_mode_e2e"] = measure_healthy_channel_e2e(
                batch, dtype)
        except Exception as e:
            log(f"streaming-mode sub-bench failed: {e!r}")

    feat = None
    if _gate(extra, "featurize_sync_mode"):
        try:
            feat = _call_with_deadline(
                "featurize_sync_mode",
                lambda: measure_featurize(n, batch, dtype, trials),
                extra)
        except Exception as e:
            log(f"synchronized featurize sub-bench failed: {e!r}")
            extra["featurize_sync_mode"] = {"error": repr(e)[:200]}
    if feat is not None:
        extra.update({
            "featurize_sync_mode": {
                "value": feat["value"],
                "trials": feat["trials"],
                "serial_trials": feat["serial_trials"],
                "interleaved_pairs": feat["interleaved_pairs"],
                "wire_normalized_efficiency":
                    feat["wire_normalized_efficiency"],
                "spread_pct": feat["spread_pct"],
                "serial_infeed_images_per_sec":
                    feat["serial_infeed_images_per_sec"],
                "pipeline_reports": feat["pipeline_reports"],
            },
            "compile_warmup_seconds": feat["warmup_seconds"],
        })
        if not feat_stream:
            extra["value"] = feat["value"]
            extra["headline_mode"] = "synchronized_in_process"
    elif not feat_stream:
        extra.setdefault("value", None)
        extra["headline_mode"] = "skipped_budget"
    compute_ips = None
    if _gate(extra, "compute_only"):
        try:
            # batch 256 profiled BEST for device MFU (July 2026 sweep:
            # 256→22.8%, 1024→20.4%) and ships a 68 MB device_put, not
            # 1024's 274 MB
            compute_batch = int(os.environ.get("TPUDL_BENCH_COMPUTE_BATCH",
                                               "256"))
            compute_ips = _call_with_deadline(
                "compute_only",
                lambda: measure_compute_only(compute_batch, dtype),
                extra)
            extra["compute_only_images_per_sec"] = round(compute_ips, 1)
            extra["compute_only_batch"] = compute_batch
        except Exception as e:  # sub-bench failure must not kill the bench
            log(f"compute-only sub-bench failed: {e!r}")
            extra["compute_only_images_per_sec"] = None
    if _gate(extra, "wire_bandwidth"):
        try:
            extra["wire_bandwidth"] = measure_wire_bandwidth()
            # each 299x299x3 uint8 image is ~268KB on the wire; the implied
            # ceiling makes the wire-bound diagnosis auditable in the record
            img_mb = 299 * 299 * 3 / 2**20
            extra["wire_bound_images_per_sec"] = round(
                extra["wire_bandwidth"]["h2d_mb_per_sec"] / img_mb, 1)
        except Exception as e:
            log(f"wire-bandwidth probe failed: {e!r}")
    if devs[0].platform == "tpu":
        peak = peak_flops(devs[0].device_kind)  # unknown chip: error
        if extra.get("value"):
            extra["mfu_end_to_end"] = round(
                extra["value"] * _INCEPTION_FLOPS / peak, 5)
        if compute_ips:
            extra["mfu_compute"] = round(
                compute_ips * _INCEPTION_FLOPS / peak, 5)
        if _gate(extra, "device_profile"):
            try:
                # dispatch-free chip-side number (batch 256 profiled best
                # in the July 2026 sweep)
                dev = _call_with_deadline(
                    "device_profile",
                    lambda: measure_device_profile(batch, dtype), extra)
                if dev:
                    extra["device_profile"] = dev
            except Exception as e:
                log(f"device-profile sub-bench failed: {e!r}")

    if os.environ.get("TPUDL_BENCH_QUICK", "0") != "1":
        # device-facing sub-benches get contemporaneous wire probes
        # (round-4 verdict weak #2): an 8 MB H2D probe before and after,
        # so round-over-round swings in these rows are attributable to
        # the link INSIDE the same record
        probed = {"horovod_resnet50", "predictor_resnet50",
                  "estimator_inception", "data_pipeline",
                  "async_dispatch", "device_cache", "lm_train",
                  "lm_generate"}
        for key, fn in [("horovod_resnet50", lambda: measure_train_step(dtype)),
                        ("predictor_resnet50", lambda: measure_predictor(dtype)),
                        ("keras_transformer_mlp", measure_keras_transformer),
                        ("estimator", measure_estimator_fit),
                        ("estimator_inception", measure_estimator_inception),
                        ("decode", measure_decode),
                        ("data_pipeline", measure_data_pipeline),
                        ("device_cache", measure_device_cache),
                        ("async_dispatch", measure_async_dispatch),
                        ("fault_recovery", measure_fault_recovery),
                        ("mesh_scaling", measure_mesh_scaling),
                        ("mesh_2d", measure_mesh_2d),
                        ("cold_start", measure_cold_start),
                        ("serve", measure_serve),
                        ("lm_train", measure_lm_train),
                        ("lm_generate", measure_lm_generate),
                        ("preemption", measure_preemption),
                        ("flash_attention", measure_flash_attention)]:
            if not _gate(extra, key):
                continue
            try:
                pre = _quiet_wire_probe() if key in probed else None
                # per-sub-bench deadline from the remaining budget: an
                # overrun abandons THIS sub-bench (TimeoutError caught
                # below), never the rest of the round
                rec = _call_with_deadline(key, fn, extra)
                if key in probed and isinstance(rec, dict):
                    rec["h2d_mb_per_sec_pre"] = pre
                    rec["h2d_mb_per_sec_post"] = _quiet_wire_probe()
                extra[key] = rec
            except Exception as e:  # sub-bench failure must not kill the bench
                log(f"sub-bench {key} failed: {e!r}")
                extra[key] = {"error": repr(e)}

    base = None
    if (os.environ.get("TPUDL_BENCH_SKIP_BASELINE", "0") != "1"
            and _gate(extra, "tf_cpu_baseline")):
        try:
            base = _call_with_deadline("tf_cpu_baseline",
                                       measure_tf_cpu_baseline, extra)
            extra["tf_cpu_baseline_images_per_sec"] = round(base["value"], 2)
            extra["tf_cpu_baseline_trials"] = base["trials"]
        except Exception as e:  # baseline failure must not kill the bench
            log(f"baseline measurement failed: {e!r}")

    try:
        from tpudl import obs as _obs

        # the parent process's own registry snapshot (the subprocess
        # trials carry theirs per-trial in featurize_streaming)
        extra["metrics_snapshot"] = _obs.snapshot()
    except Exception as e:
        log(f"metrics snapshot unavailable: {e!r}")
    try:
        # regression sentinel: this run's judged numbers vs whatever
        # round records sit beside bench.py, wire-normalized so a
        # slower link doesn't read as regression (tools/bench_sentinel.py); the
        # verdict token rides the judged summary line
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from bench_sentinel import (format_report, sentinel_for_record,
                                    summary_token)

        here = os.path.dirname(os.path.abspath(__file__))
        sent = sentinel_for_record(
            extra, [here, os.path.join(here, "bench_records")])
        extra["bench_sentinel"] = sent
        extra["bench_sentinel_token"] = summary_token(sent)[:120]
        log(format_report(sent))
    except Exception as e:
        log(f"bench sentinel failed: {e!r}")
    extra.setdefault("value", None)
    extra["vs_baseline"] = (round(extra["value"] / base["value"], 3)
                            if base and extra["value"] else None)
    # canonical key order for the judged line
    out = {k: extra[k] for k in ("metric", "value", "unit", "vs_baseline")}
    out.update({k: v for k, v in extra.items() if k not in out})
    _emit(out)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--featurize-trial":
        arm, trial_n, trial_batch, trial_dtype = sys.argv[2:6]
        run_featurize_trial(arm, int(trial_n), int(trial_batch), trial_dtype)
    elif len(sys.argv) > 1 and sys.argv[1] == "--mesh-child":
        run_mesh_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--mesh2d-child":
        run_mesh2d_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--cold-start-child":
        run_cold_start_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--serve-child":
        run_serve_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--lm-train-child":
        run_lm_train_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--lm-generate-child":
        run_lm_generate_child(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--preemption-job":
        wd, outp, n_steps, save_ev, progp = sys.argv[2:7]
        run_preemption_job(wd, outp, int(n_steps), int(save_ev), progp)
    else:
        main()
