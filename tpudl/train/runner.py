"""HorovodRunner contract + the training loop that owns it.

Rebuild of the L7 capability surface (SURVEY.md §3.6): the Databricks
``sparkdl.HorovodRunner(np=N).run(train_fn, **kwargs)`` API — MPI gang
launch + NCCL allreduce — re-owned as SPMD over a jax mesh:

- ``np > 0``: data-parallel mesh over the first ``np`` local devices
  (the reference's N distributed GPU ranks → N TPU chips on the slice).
- ``np < 0``: |np|-device debug mesh, mirroring HorovodRunner's
  negative-np local-mode debugging contract (runs on whatever local
  devices exist; under the CPU simulation flag this is a real multi-
  device mesh on one host).

Differences owned deliberately (NOT ported): there are no per-rank
processes and no hvd.* mutable global — ``train_fn`` receives a
:class:`TrainContext` as its first argument and is executed ONCE as an
SPMD program driver. Rank-0-only conventions collapse: in SPMD the
driver *is* logically rank 0 (``ctx.rank == 0`` is kept for code that
checks it). Gang semantics match TPU reality (§5.3): a failure kills the
whole program; ``max_restarts`` re-launches ``train_fn`` which resumes
from the last checkpoint.
"""

from __future__ import annotations

import functools
import logging
import time

import jax
import numpy as np

from tpudl import distributed as D
from tpudl import mesh as M
from tpudl.jobs.retry import RetryPolicy, is_fatal
from tpudl.obs import attribution as _attr
from tpudl.obs import flight as _obs_flight
from tpudl.obs import metrics as _obs_metrics
from tpudl.obs import tracer as _obs_tracer
from tpudl.obs import watchdog as _obs_watchdog
from tpudl.testing import faults as _faults
from tpudl.train.checkpoint import CheckpointManager
from tpudl.train.step import make_train_step

__all__ = ["HorovodRunner", "TrainContext", "Trainer", "Preempted",
           "RestartsExhausted"]

log = logging.getLogger("tpudl.train")


class Preempted(Exception):
    """Cooperative-stop signal: ``Trainer.fit(stop=...)`` saw the stop
    flag, force-saved a checkpoint at ``step`` and unwound. Marked
    ``tpudl_fatal`` so NO retry layer (gang restart, RetryPolicy, trial
    retry) fights the preemption — the job runtime (tpudl.jobs) catches
    it and turns it into an orderly preempted-resumable exit."""

    tpudl_fatal = True

    def __init__(self, step: int, saved: bool = True):
        super().__init__(f"preempted at step {step}"
                         + ("" if saved else " (no checkpoint dir — "
                            "state NOT saved)"))
        self.step = int(step)
        self.saved = bool(saved)


class RestartsExhausted(RuntimeError):
    """The gang-restart budget ran out. Carries the LAST cause (also
    chained as ``__cause__``) so the terminal error names why the gang
    kept dying, not just that it did. Subclasses RuntimeError — and
    embeds the cause's message — for compatibility with callers that
    matched the previously re-raised original."""

    def __init__(self, attempts: int, last_cause: BaseException):
        super().__init__(
            f"gang restart budget exhausted after {attempts} attempt(s); "
            f"last cause: {type(last_cause).__name__}: {last_cause}")
        self.attempts = int(attempts)
        self.last_cause = last_cause


def _restart_backoff_base_s() -> float:
    import os

    try:
        return float(os.environ.get("TPUDL_TRAIN_RESTART_BACKOFF_S",
                                    "") or 0.1)
    except ValueError:
        return 0.1


def _nbytes(batch) -> int:
    return sum(getattr(b, "nbytes", 0) for b in batch)


@functools.lru_cache(maxsize=1)
def _owning_identity():
    """The ONE cached jitted identity program ``Trainer.fit``'s
    ``_own`` runs to take ownership of an already-mesh-sharded tree
    without a host gather. A fresh ``jax.jit(lambda t: t)`` at the
    call site would be a fresh fn identity — a retrace per fit
    (jit-cache-churn); jit's own cache then keys per tree structure."""
    return jax.jit(lambda t: t)


class TrainContext:
    """What a ``train_fn`` gets instead of the hvd.* globals."""

    def __init__(self, mesh, checkpoint_dir=None, save_every=100):
        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.save_every = save_every
        self.attempt = 0  # restart count, set by the runner

    # hvd-parity accessors
    @property
    def size(self) -> int:
        return self.mesh.shape[M.DATA_AXIS]

    @property
    def rank(self) -> int:
        return 0  # SPMD driver == logical rank 0 (see module docstring)

    # mesh edges
    def shard_batch(self, tree):
        return M.shard_batch(tree, self.mesh)

    def replicate(self, tree):
        return M.replicate(tree, self.mesh)

    def checkpoints(self, subdir: str | None = None) -> CheckpointManager | None:
        if self.checkpoint_dir is None:
            return None
        d = self.checkpoint_dir if subdir is None else f"{self.checkpoint_dir}/{subdir}"
        return CheckpointManager(d, save_every=self.save_every)

    def trainer(self, loss_fn, optimizer, **kw) -> "Trainer":
        kw.setdefault("checkpoint_dir", self.checkpoint_dir)
        kw.setdefault("save_every", self.save_every)
        return Trainer(loss_fn, optimizer, mesh=self.mesh, **kw)


class HorovodRunner:
    """``HorovodRunner(np=2).run(train_fn)`` — the reference's public
    training entry point, mesh-native."""

    def __init__(self, np: int = -1, *, checkpoint_dir: str | None = None,
                 save_every: int = 100, max_restarts: int = 0,
                 devices=None, retry_policy: RetryPolicy | None = None):
        self._np = int(np)
        self.checkpoint_dir = checkpoint_dir
        self.save_every = save_every
        self.max_restarts = int(max_restarts)
        self._devices = devices
        # the shared RetryPolicy governs restart PACING + classification
        # (max_restarts stays the budget): exponential backoff + jitter
        # between re-launches replaces the old immediate unbounded-rate
        # re-spawn — a gang dying in a tight loop no longer hammers the
        # backend while it is down (TPUDL_TRAIN_RESTART_BACKOFF_S base)
        self.retry_policy = retry_policy or RetryPolicy(
            max_attempts=self.max_restarts + 1,
            backoff_s=_restart_backoff_base_s(), max_backoff_s=30.0,
            transient="all")

    def _build_mesh(self):
        devs = list(self._devices) if self._devices else jax.devices()
        n = abs(self._np) if self._np != 0 else len(devs)
        if n > len(devs):
            raise ValueError(
                f"HorovodRunner(np={self._np}) needs {n} devices, have "
                f"{len(devs)} ({devs[0].platform})")
        # TPUDL_MESH_MODEL>1 folds the same n devices into a 2-D
        # (data, model) grid — np keeps meaning TOTAL chips (the
        # reference's contract), the model axis comes out of it
        n_model = M.model_axis_size()
        if n % n_model:
            raise ValueError(
                f"HorovodRunner(np={self._np}): {n} devices do not "
                f"divide into TPUDL_MESH_MODEL={n_model} model shards")
        return M.build_mesh(n_data=n // n_model, n_model=n_model,
                            devices=devs[:n])

    def run(self, main, **kwargs):
        """Run ``main(ctx, **kwargs)`` over the mesh; on exception,
        re-launch up to ``max_restarts`` times (gang restart semantics —
        main must resume from its checkpoints; Trainer does)."""
        mesh = self._build_mesh()
        ctx = TrainContext(mesh, self.checkpoint_dir, self.save_every)
        attempt = 0
        while True:
            ctx.attempt = attempt
            try:
                with _obs_tracer.span("train.run", attempt=attempt,
                                      mesh_size=ctx.size):
                    with M.use_mesh(mesh):
                        return main(ctx, **kwargs)
            except Exception as e:
                if is_fatal(e) or not self.retry_policy.is_transient(e):
                    # a Preempted unwind (or a classified-permanent
                    # failure) is an orderly stop, not a gang death:
                    # restarting would fight the scheduler/caller
                    raise
                attempt += 1
                # the step the gang died at (train.last_step gauge, set
                # by Trainer.fit's finally) + the triggering exception
                # go into the flight recorder: max_restarts exhaustion
                # then explains WHY, not just how often (the
                # train.restarts counter alone couldn't)
                last_step = _obs_metrics.gauge("train.last_step").value
                _obs_flight.get_recorder().record_restart(
                    attempt, e, step=last_step,
                    max_restarts=self.max_restarts)
                if attempt > self.max_restarts:
                    _obs_flight.record_error(
                        "train.exhausted", e, attempts=attempt,
                        max_restarts=self.max_restarts, step=last_step)
                    raise RestartsExhausted(attempt, e) from e
                # restart count is a first-class metric (a silently
                # restarting gang looks healthy in logs-only setups);
                # pacing via the shared policy: exponential backoff +
                # jitter, published so a backing-off gang is visible
                _obs_metrics.counter("train.restarts").inc()
                self.retry_policy.record("train.restart", e,
                                         attempt=attempt)
                delay = self.retry_policy.backoff_s(attempt)
                _obs_metrics.histogram(
                    "train.restart_backoff_s").observe(delay)
                log.exception(
                    "train_fn failed; gang restart %d/%d from last "
                    "checkpoint in %.2fs", attempt, self.max_restarts,
                    delay)
                if delay > 0:
                    # tpudl: ignore[adhoc-retry] — the pacing COMES
                    # from the shared RetryPolicy (recorded above);
                    # this sleep is the gang-restart boundary itself
                    time.sleep(delay)


class Trainer:
    """Step-loop engine: sharded batches → one jitted SPMD step, periodic
    orbax checkpoints, resume, throughput metrics.

    ``data_fn(step) -> tuple_of_host_arrays`` must be stateless in
    ``step`` (index-addressable), which makes the data cursor exactly the
    step counter — resume is then correct by construction.
    """

    def __init__(self, loss_fn, optimizer, *, mesh=None,
                 checkpoint_dir=None, save_every=100, log_every=0,
                 param_shardings=None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.save_every = save_every
        self.log_every = log_every
        # tensor parallelism through the standard Trainer: a pytree of
        # NamedSharding matching params (e.g. TinyCausalLM
        # .param_shardings(mesh)) — params and optimizer state then live
        # SHARDED over the model axis for the whole fit, checkpoints
        # included (orbax round-trips the shardings via `like`)
        self.param_shardings = param_shardings
        if param_shardings is not None and mesh is None:
            raise ValueError(
                "param_shardings without mesh= would be silently ignored "
                "— pass the mesh the shardings were built on")
        self.history: list[dict] = []
        # one compiled SPMD program per Trainer: rebuilding the jit wrapper
        # per fit() would retrace+recompile every call (loss_fn/optimizer/
        # mesh are fixed at construction, so the program is too)
        self._step_fn = make_train_step(loss_fn, optimizer, mesh,
                                        param_shardings=param_shardings)

    def fit(self, params, data_fn, steps: int, *,
            opt_state=None, stop=None, consume: bool = False):
        """Train for ``steps`` total steps (resuming included). Returns
        (params, opt_state, history).

        ``stop`` (optional zero-arg callable → bool) is the cooperative
        preemption check, polled at every step boundary: when it turns
        truthy the trainer force-saves a checkpoint AT THE CURRENT STEP
        (when a ``checkpoint_dir`` is configured) and raises
        :class:`Preempted` — the checkpoint-then-exit half of the job
        runtime's SIGTERM contract (JOBS.md), with resume rework bounded
        at zero steps on the graceful path (≤ ``save_every`` when the
        save itself is lost).

        ``consume=True`` hands ``params`` and ``opt_state`` over: a tree
        that already lies on the mesh is not copied, and the first step
        donates the very buffers that were passed, so the caller's
        arrays are dead after the call. Without it a fit that continues
        from device state holds that state twice for its whole length
        (the owning copy and the caller's original), which a model whose
        masters and moments fill a third of the chip cannot afford."""
        self.history = []  # per-fit; stale entries would misreport results
        tracer = _obs_tracer.get_tracer()
        with tracer.span("train.fit", steps=steps) as fit_span:
            return self._loop(tracer.span, fit_span, params, opt_state,
                              data_fn, steps, stop, consume)

    def _place(self, params, opt_state, consume=False):
        """``params`` and ``opt_state`` (built when None) as buffers this
        fit owns, placed on the mesh. ``consume``: the caller has given
        them up, so a tree on the mesh is owned as it is."""
        # own the buffers: the step donates params/opt_state, and device_put
        # may alias the caller's arrays — donating an alias would delete the
        # caller's data out from under them. Host arrays are copied
        # host-side; mesh-spanning device trees are copied by a jitted
        # identity (fresh output buffers, SAME shardings — an np.asarray
        # here would gather a TP-sharded state to host, losing its
        # layout and failing outright on multi-host non-addressable
        # shards).
        mesh_devices = (set(self.mesh.devices.flat)
                        if self.mesh is not None else None)

        def _spans_mesh(x):
            sh = getattr(x, "sharding", None)
            return (sh is not None and mesh_devices is not None
                    and sh.device_set == mesh_devices)

        def _own(tree):
            if all(_spans_mesh(leaf) for leaf in jax.tree.leaves(tree)):
                return tree if consume else _owning_identity()(tree)
            return jax.tree.map(np.asarray, tree)

        params = _own(params)
        if opt_state is not None:
            opt_state = _own(opt_state)
        if self.mesh is not None and not all(
                _spans_mesh(leaf) for leaf in jax.tree.leaves(params)):
            if self.param_shardings is not None:
                # typed refusal BEFORE any transfer when the per-device
                # share exceeds TPUDL_DATA_HBM_BUDGET_MB (the "widen the
                # model axis" signal, same rail as zoo shard_params)
                M.require_hbm_fit(params, self.param_shardings,
                                  what="model-sharded params")
                params = jax.tree.map(jax.device_put, params,
                                      self.param_shardings)
                # an opt_state built from SHARDED params gets sharded
                # moment buffers for free
            else:
                params = M.replicate(params, self.mesh)
        fresh_opt = opt_state is None
        if fresh_opt:
            opt_state = self.optimizer.init(params)
        if self.mesh is not None and any(
                not _spans_mesh(leaf)
                for leaf in jax.tree.leaves(opt_state)):
            # optax states mix param-shaped buffers with FRESH scalars
            # (adam's `count`) that land on one default device — a
            # mixed-device jit call is an error. Param-shaped leaves get
            # the sharding the optimizer WOULD give them when built from
            # the placed params (so a caller-passed host state on the TP
            # path comes back model-SHARDED, not replicated — replicated
            # fp32 moments defeat the point of TP); everything else is
            # replicated. A freshly-built state is its own template;
            # otherwise the template is derived structurally from
            # param_shardings WITHOUT materializing a second opt state.
            # Zero-allocation routes that DON'T work (tried,
            # review-caught): eval_shape loses shardings entirely, and
            # AOT output_shardings of optimizer.init come back
            # replicated/single-device (XLA leaves trivial zeros_like
            # outputs unconstrained). What does: optax embeds the
            # params PYTREE verbatim in its moment subtrees, so a state
            # leaf whose path ends with a param's full path (and
            # matches its shape) takes that param's sharding; scalars
            # and everything else replicate.
            if fresh_opt or self.param_shardings is None:
                # each leaf's own sharding (None for host leaves)
                template = jax.tree.map(
                    lambda leaf: getattr(leaf, "sharding", None),
                    opt_state)
            else:
                from jax.tree_util import (tree_flatten_with_path,
                                           tree_map_with_path)

                sh_flat = tree_flatten_with_path(self.param_shardings)[0]
                p_flat = tree_flatten_with_path(params)[0]
                suffix = {tuple(str(k) for k in path): (sh, leaf.shape)
                          for (path, sh), (_p, leaf)
                          in zip(sh_flat, p_flat)}
                struct = jax.eval_shape(self.optimizer.init, params)

                def _sh_for(path, leaf):
                    keys = tuple(str(k) for k in path)
                    # + 1: the EMPTY suffix must be tried too — a
                    # bare-leaf params tree has path (), and any state
                    # leaf whose shape matches it is its moment
                    for start in range(len(keys) + 1):
                        hit = suffix.get(keys[start:])
                        if hit and hit[1] == leaf.shape:
                            return hit[0]
                    return None

                template = tree_map_with_path(_sh_for, struct)

            def _sharding_spans(sh):
                try:
                    return (sh is not None
                            and sh.device_set == mesh_devices)
                except Exception:  # AbstractMesh shardings
                    return False

            def _place_like(x, ref_sh):
                if _spans_mesh(x):
                    return x
                target = (ref_sh if _sharding_spans(ref_sh)
                          else M.replicated(self.mesh))
                return jax.device_put(np.asarray(x), target)

            opt_state = jax.tree.map(_place_like, opt_state, template)
            del template
        return params, opt_state

    def _loop(self, span, fit_span, params, opt_state,  # tpudl: hot-path
              data_fn, steps, stop, consume=False):
        """Placement, restore, the step loop and the drain, inside
        ``fit``'s ``train.fit`` span. Every span here times the host:
        nothing synchronises with the device for a span's sake, and what
        the host waits for inside ``train.step.dispatch`` (argument
        transfer on a 1-wide data axis, a free slot in the device's
        queue) is part of what it shows."""
        with span("train.fit.place"):
            params, opt_state = self._place(params, opt_state, consume)
        start = 0
        mgr = None
        if self.checkpoint_dir is not None:
            with span("train.fit.restore") as restore_span:
                mgr = CheckpointManager(self.checkpoint_dir,
                                        save_every=self.save_every)
                # `like` is built AFTER placement, so restored arrays come
                # back with the same (possibly TP-sharded) shardings
                restored = mgr.restore(
                    like={"params": params, "opt_state": opt_state,
                          "step": np.asarray(0, np.int64)})
                if restored is not None:
                    # rebinding drops the pre-restore placed buffers,
                    # which would otherwise pin ~2x params+opt HBM for
                    # the whole fit
                    params = restored["params"]
                    opt_state = restored["opt_state"]
                    start = int(restored["step"])
                    restore_span.set(resumed_at=start)
            if restored is not None:
                _obs_metrics.histogram(
                    "train.checkpoint_restore_seconds").observe(
                        restore_span.dur_ns / 1e9)
                log.info("resumed from checkpoint at step %d", start)
        fit_span.set(start=start, devices=(
            self.mesh.devices.size if self.mesh is not None else 1))

        step_fn = self._step_fn

        # Multi-host: data_fn returns THIS host's slice of the global
        # batch (use tpudl.distributed.host_shard to pick the host's
        # files); slices assemble into one globally-sharded array whose
        # collectives ride ICI/DCN (SURVEY.md §5.8 input data plane).
        # Single host, multi-device: plain shard_batch. A 1-wide data
        # axis needs no explicit sharding: host arrays go straight into
        # the jitted step, whose own arg transfer pipelines (an explicit
        # per-step device_put is one more blocking call per step).
        multi_host = self.mesh is not None and D.process_count() > 1
        shard_inputs = (self.mesh is not None
                        and self.mesh.shape[M.DATA_AXIS] > 1)
        examples = 0
        executed = 0  # steps actually run (a failed run must not
        loss = None   # report the PLANNED count to the registry)
        # the host loop's time per step (the `train.step` span: equal to
        # the device's only while the device throttles the host — the
        # honest wall denominator is examples_per_sec in history) and
        # checkpoint save durations, published run-wide
        step_hist = _obs_metrics.histogram("train.step_seconds")
        ckpt_hist = _obs_metrics.histogram("train.checkpoint_save_seconds")
        # watchdog heartbeat: one beat per step — a wedged data_fn or a
        # hung device dispatch flags a stall naming the step it froze
        # at; train.last_step feeds the runner's restart forensics
        step_gauge = _obs_metrics.gauge("train.last_step")
        hb = _obs_watchdog.heartbeat("train.fit", steps=steps,
                                     start=start)

        def _state(at):
            return {"params": params, "opt_state": opt_state,
                    "step": np.asarray(at, np.int64)}

        t0 = time.perf_counter()
        try:
            for step in range(start, steps):
                with span("train.step", step=step) as step_span:
                    if stop is not None and stop():
                        # checkpoint-then-exit: the state BEFORE this step
                        # is saved at `step` (steps 0..step-1 completed),
                        # so an identical relaunch resumes with zero
                        # re-work
                        if mgr is not None:
                            with span("train.step.checkpoint") as ck:
                                mgr.save(step, _state(step), force=True)
                            ckpt_hist.observe(ck.dur_ns / 1e9)
                        raise Preempted(step, saved=mgr is not None)
                    # step + examples ride the beat: the live status plane
                    # (obs top) shows training progress from the heartbeat
                    # info without a second instrumentation channel
                    hb.beat(step=step, examples=examples)
                    # fault point for the preemption suite: a FaultPlan
                    # can SIGTERM-to-self or raise at an exact step
                    # (unarmed: one global None-check)
                    _faults.fire("train.step", step=step)
                    with span("train.step.data"):
                        batch = data_fn(step)
                    if not isinstance(batch, tuple):
                        batch = (batch,)
                    if multi_host:
                        with span("train.step.place", bytes=_nbytes(batch)):
                            batch = tuple(
                                # tpudl: ignore[hot-sync] — data_fn yields
                                # HOST arrays; this asarray is the H2D
                                # staging copy of the local shard, not a
                                # device round-trip
                                D.global_batch(np.asarray(b), self.mesh)
                                for b in batch)
                    elif shard_inputs:
                        # ONE batched async transfer for the whole step
                        # tuple (mesh.transfer_batch underneath — the same
                        # edge the frame executor and the estimator use)
                        with span("train.step.place", bytes=_nbytes(batch)):
                            batch = M.shard_batch(batch, self.mesh)
                    with span("train.step.dispatch"):
                        params, opt_state, loss = step_fn(params, opt_state,
                                                          *batch)
                    step_gauge.set(step + 1)
                    executed += 1
                    examples += int(np.shape(batch[0])[0])
                    # attribution: training rows consumed under the
                    # caller's scope — fit publishes on the calling
                    # thread, so the contextvar needs no explicit carry
                    _attr.charge("rows_in", int(np.shape(batch[0])[0]))
                    done = step + 1
                    if mgr is not None and done < steps and mgr.due(done):
                        with span("train.step.checkpoint") as ck:
                            mgr.save(done, _state(done))
                        ckpt_hist.observe(ck.dur_ns / 1e9)
                        log.debug("checkpoint at step %d", done)
                    if self.log_every and done % self.log_every == 0:
                        dt = time.perf_counter() - t0
                        # tpudl: ignore[hot-sync] — opt-in loss logging:
                        # the fetch is the feature, paid once per
                        # log_every steps and off by default
                        l = float(jax.device_get(loss))
                        self.history.append(
                            {"step": done, "loss": l,
                             "examples_per_sec": examples / max(dt, 1e-9)})
                        log.info("step %d loss %.5f (%.1f ex/s)", done, l,
                                 examples / max(dt, 1e-9))
                step_hist.observe(step_span.dur_ns / 1e9)
            with span("train.fit.drain"):
                if loss is not None and (not self.history
                                         or self.history[-1]["step"] != steps):
                    dt = time.perf_counter() - t0
                    self.history.append(
                        {"step": steps,
                         # tpudl: ignore[hot-sync] — after the last step:
                         # the run's final loss fetch, no pipeline behind
                         "loss": float(jax.device_get(loss)),
                         "examples_per_sec": examples / max(dt, 1e-9)})
                if mgr is not None and steps > start:
                    with span("train.step.checkpoint") as ck:
                        mgr.save(steps, _state(steps), force=True)
                    ckpt_hist.observe(ck.dur_ns / 1e9)
        finally:
            hb.__exit__(None, None, None)
            if mgr is not None:
                mgr.close()
            _obs_metrics.counter("train.steps").inc(executed)
            _obs_metrics.counter("train.examples").inc(examples)
            _obs_metrics.get_registry().maybe_flush()
        return params, opt_state, self.history
