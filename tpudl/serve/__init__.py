"""tpudl.serve — continuous-batching online inference (SERVE.md).

The serving tentpole: an admission-controlled request queue with
per-request deadlines, rung-bucketed dynamic batching for ragged
featurize/UDF payloads, slot-based continuous batch decoding for
``TinyCausalLM`` on a fixed-geometry KV cache (one compiled decode-step
program serves a churning request mix with zero retraces), and a
multi-model registry. Overload rides the PR-14 degradation ladder; SLO
metrics (``serve.*``) publish through ``tpudl.obs``.

Request-scoped telemetry (ISSUE 18): every request carries a
:class:`~tpudl.serve.reqtrace.ReqTrace` of lifecycle stamps that
decompose its latency into queue_wait/batching/prefill/decode
segments; completed requests feed the windowed SLO engine
(:mod:`tpudl.obs.slo`) and the flight recorder's request ring.
"""

from tpudl.serve.queue import (AdmissionError, DeadlineExceeded,
                               Evicted, RequestQueue, ServeRequest)
from tpudl.serve.reqtrace import ReqTrace
from tpudl.serve.batching import RungBatcher
from tpudl.serve.slots import SlotDecoder
from tpudl.serve.registry import ModelRegistry
from tpudl.serve.server import Server
from tpudl.serve.loadgen import run_closed_loop

__all__ = ["AdmissionError", "DeadlineExceeded", "Evicted",
           "RequestQueue", "ServeRequest", "ReqTrace", "RungBatcher",
           "SlotDecoder", "ModelRegistry", "Server", "run_closed_loop"]
