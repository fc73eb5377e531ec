"""Fault-tolerant serving fleet (PR 20).

One router process fronting N serve replica processes:

- :mod:`.supervisor` — spawn/watch/restart replicas (capped exponential
  backoff + jitter, port-file rendezvous, /healthz boot gate);
- :mod:`.router` — least-loaded per-(bucket, class) dispatch, bounded
  retry on safe failures, typed ``queue_full``/``replica_unavailable``
  sheds, SLO-burn/liveness drain, sticky-session affinity + carry
  handoff, HTTP front-end;
- :mod:`.replica` — the replica-side API (/v1/flow /sessionz /drainz on
  the shared observability sidecar);
- :mod:`.wire` — edge encode/decode for the PR-2 wire presets plus the
  meta-header framing both hops speak;
- :mod:`.client` — stdlib HTTP client with the typed transport failure
  classes (:class:`~.client.ReplicaDown` is safe to retry,
  :class:`~.client.ReplicaTimeout` is not);
- :mod:`.drill` — the kill/rejoin chaos drill (``serve --fleet N
  --drill``).
"""

from .client import ReplicaClient, ReplicaDown, ReplicaTimeout
from .drill import run_drill
from .router import FleetTicket, Router, FrontendServer, serve_frontend
from .supervisor import Supervisor
from .replica import ReplicaAPI, ReplicaServer, serve_replica
from .wire import EdgeCodec

__all__ = [
    "EdgeCodec",
    "FleetTicket",
    "FrontendServer",
    "ReplicaAPI",
    "ReplicaClient",
    "ReplicaDown",
    "ReplicaServer",
    "ReplicaTimeout",
    "Router",
    "Supervisor",
    "run_drill",
    "serve_frontend",
    "serve_replica",
]
