"""Flow-as-a-service: the online inference path.

Composes the training-side ingredients into a request path — canonical
``ShapeBuckets`` quantization (PR 4), compact wire formats decoded inside
the jitted program (PR 2), the compiled-program registry with AOT export
(PR 7), structured telemetry (PR 1) — behind a continuous-batching
scheduler with bounded-queue admission control:

- :mod:`.batcher` — request/result types, typed rejection/error classes,
  per-bucket coalescing with deterministic batch selection (numpy-only);
- :mod:`.scheduler` — admission, the dispatch loop, sticky per-client
  response ordering, per-request latency spans;
- :mod:`.session` — the model replica: variables, the registered eval
  program, and the warm pool of precompiled executables per
  (model, bucket, wire) triple;
- :mod:`.loadgen` — the open-loop synthetic load generator behind
  the ``serve`` CLI's built-in client;
- :mod:`.ladder` — iteration-ladder latency classes (PR 11): adaptive
  recurrence budgets over chained fixed-``iterations`` rung programs;
- :mod:`.observe` — the live observability plane (PR 13): /metrics
  (Prometheus text), /healthz readiness+liveness, /statusz snapshots,
  /profilez on-demand profiler captures.

Video streams (PR 15) ride the same path: a ``video=True`` session adds
the registered warm-start program per bucket, the scheduler keys each
client's previous-frame carry in a bounded TTL-evicted
:class:`~..video.SessionCache`, and ``submit(sequence=True)`` requests
coalesce on their own lanes onto the warm program (``products=True``
adds fw/bw occlusion + confidence from a same-program reversed
dispatch).
"""

from . import batcher, ladder, loadgen, observe, scheduler, session
from .batcher import (BucketBatcher, FlowRequest, FlowResult, ServeError,
                      ServeRejected)
from .ladder import CLASSES, LadderSpec
from .observe import Observer, ObserverServer, serve_observer
from .scheduler import Scheduler, Ticket
from .session import ServeSession

__all__ = [
    "batcher", "ladder", "loadgen", "observe", "scheduler", "session",
    "BucketBatcher", "CLASSES", "FlowRequest", "FlowResult", "LadderSpec",
    "Observer", "ObserverServer", "serve_observer",
    "ServeError", "ServeRejected", "Scheduler", "Ticket", "ServeSession",
]
