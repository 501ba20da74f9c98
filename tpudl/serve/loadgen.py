"""Closed-loop load generation for the serve plane.

The demand side of the serving SLOs (SERVE.md): ``clients`` threads
each keep exactly one request in flight (the closed-loop discipline —
offered load tracks service rate, so the measured QPS is SUSTAINED
throughput, not an open-loop fantasy), and the run reports the SLO
truths: sustained QPS, p50/p99 end-to-end latency,
TTFT percentiles, rejects and deadline sheds.

Two chaos points make overload testable under ``TPUDL_FAULT_PLAN``:

- ``serve.tick`` fires once per client iteration; a ``burst`` rule
  returns a COUNT and the client submits that many extra requests
  back-to-back (fire-and-forget) — the deterministic spike that drives
  admission past queue capacity;
- ``serve.client`` fires before each submit; a ``delay`` rule
  (``FaultPlan.slow_client``) stalls the client so queued requests age
  into their deadlines.

A rejected submit is an ANSWER (typed), recorded and moved past; a
completed/shed request's latency comes from its own stamps. Every wait
is bounded (``timeout``) — the zero-hangs contract holds even when the
server dies mid-run.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from tpudl.obs import attribution as _attr
from tpudl.obs.metrics import percentile
from tpudl.serve.queue import AdmissionError
from tpudl.testing import faults as _faults
from tpudl.testing import tsan as _tsan

__all__ = ["run_closed_loop"]


def _percentile(xs: list, q: float):
    # the one shared nearest-rank implementation (tpudl.obs.metrics):
    # the loadgen's ground truth and the obs plane's windows can never
    # disagree by construction
    return percentile(sorted(xs), q)


def run_closed_loop(server, make_prompt, *, requests: int,
                    clients: int = 4, max_new: int = 8,
                    model: str = "default",
                    deadline_s: float | None = None,
                    timeout: float = 120.0,
                    tenant=None) -> dict:
    """Drive ``requests`` total requests through ``server`` with
    ``clients`` closed-loop threads; returns the SLO summary.

    ``make_prompt(i)`` supplies the i-th prompt (ragged lengths are
    the point — the serve loop buckets them). The server must already
    be started (or be run concurrently by the caller).

    ``tenant`` stamps the generated requests with an attribution scope
    (tpudl.obs.attribution): a string tags every client with that
    tenant; a sequence assigns client ``c`` the ``c % len``-th entry
    (``tenant=("a", "b")`` alternates two tenants). None leaves
    requests unattributed."""
    # one leaf lock for every tally: the critical sections are scalar
    # bumps/list appends and never nest with the server's locks
    lock = _tsan.named_lock("serve.loadgen")
    counter = [0]
    latencies: list = []
    ttfts: list = []
    rejected = [0]
    shed = [0]
    errors: list = []

    def _next_index():
        with lock:
            i = counter[0]
            counter[0] += 1
            return i

    def _submit(i, wait: bool):
        try:
            req = server.submit(np.asarray(make_prompt(i),
                                           dtype=np.int32),
                                max_new, model=model,
                                deadline_s=deadline_s)
        except AdmissionError:
            with lock:
                rejected[0] += 1
            return
        if not wait:
            return
        try:
            req.result(timeout=timeout)
        except Exception as e:
            with lock:
                if type(e).__name__ in ("DeadlineExceeded", "Evicted"):
                    shed[0] += 1
                else:
                    errors.append(e)
            return
        with lock:
            latencies.append(req.latency_s)
            if req.ttft_s is not None:
                ttfts.append(req.ttft_s)

    def _tenant_of(cid: int):
        if tenant is None or isinstance(tenant, str):
            return tenant
        seq = list(tenant)
        return seq[cid % len(seq)] if seq else None

    def _client(cid: int):
        # the client thread IS the submit thread, so entering the
        # scope here is exactly where ServeRequest captures it
        ctx = (_attr.scope(tenant=_tenant_of(cid))
               if _tenant_of(cid) is not None
               else contextlib.nullcontext())
        with ctx:
            while True:
                i = _next_index()
                if i >= int(requests):
                    return
                burst = _faults.fire("serve.tick", tick=i, client=cid)
                if burst:
                    # the injected spike: count extra submits in ONE
                    # tick, fire-and-forget — their fate (served or
                    # typed-rejected) is exactly what the chaos case
                    # asserts on
                    for j in range(int(burst)):
                        _submit(i, wait=False)
                _faults.fire("serve.client", client=cid, i=i)
                _submit(i, wait=True)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=_client, args=(c,),
                                name=f"tpudl-loadgen-{c}", daemon=True)
               for c in range(int(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    completed = len(latencies)
    return {
        "requests": int(requests),
        "clients": int(clients),
        "completed": completed,
        "rejected": rejected[0],
        "deadline_shed": shed[0],
        "wall_s": round(wall, 4),
        "qps": round(completed / wall, 3) if wall > 0 else None,
        "p50_ms": (round(_percentile(latencies, 0.50) * 1000, 3)
                   if latencies else None),
        "p99_ms": (round(_percentile(latencies, 0.99) * 1000, 3)
                   if latencies else None),
        "ttft_p50_s": (round(_percentile(ttfts, 0.50), 4)
                       if ttfts else None),
        "ttft_p99_s": (round(_percentile(ttfts, 0.99), 4)
                       if ttfts else None),
    }
