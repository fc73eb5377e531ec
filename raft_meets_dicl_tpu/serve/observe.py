"""Live observability HTTP plane for one serve replica.

The HTTP server itself (routes, daemon thread, profile capture) is the
shared sidecar in :mod:`..telemetry.sidecar` — the trainer binds the
same server — and this module keeps only the serve-side observer:

- ``/metrics`` — Prometheus text exposition of the ``rmd_*`` registry
  (telemetry.metrics), with the scrape-time gauges (queue depth,
  dropped telemetry events, readiness, per-class SLO burn) refreshed
  just before render;
- ``/healthz`` — readiness (warm pool complete: every bucket's program
  compiled or AOT-loaded) and liveness (dispatch-loop heartbeat age
  under the threshold); 200 only when both hold, 503 otherwise, JSON
  body either way — the router's drain signal;
- ``/statusz`` — JSON snapshot: per-lane queue depths (a lane is named
  ``[model:]HxW[/klass][/seq]``), shed/error
  counts, per-class p50/p99 plus the slowest-decile critical-path
  breakdown (telemetry.trace.TraceSummary), SLO windows;
- ``/profilez?seconds=N`` — on-demand ``jax.profiler`` capture to a
  fresh directory (the generalized form of the train ``--profile``
  hook), single-flight and capped so a scrape loop can't stack
  captures.

The server binds ``127.0.0.1`` (an observability sidecar, not the
serving API) and ``port=0`` picks an ephemeral port (tests).
"""

import threading
from collections.abc import Mapping

from ..telemetry import metrics as metrics_mod
from ..telemetry import sidecar
from ..telemetry.sidecar import (  # noqa: F401 - back-compat re-exports
    DEFAULT_PROFILE_S,
    MAX_PROFILE_S,
    STALE_HEARTBEAT_S,
    ProfileBusy,
)

# the handler/server formerly defined here; kept importable under the
# old names so callers and tests bind serve observers unchanged
_Handler = sidecar.Handler


class Observer:
    """Aggregates one replica's live state for the HTTP plane and keeps
    the scrape-time gauges fresh. ``session`` is the replica's session,
    or the scheduler's mapping ``model id -> session`` where it holds
    several: ready when all are, compiles summed."""

    def __init__(self, session, scheduler, sink=None, registry=None,
                 stale_heartbeat_s=STALE_HEARTBEAT_S):
        self.session = session
        self.scheduler = scheduler
        self.sink = sink
        self.registry = registry or metrics_mod.registry()
        self.stale_heartbeat_s = float(stale_heartbeat_s)  # graftlint: disable=host-sync -- config scalar, not a device value
        self._draining = False
        self._profile_lock = threading.Lock()
        self._m_ready = self.registry.gauge(
            "rmd_serve_ready", "replica readiness (warm pool complete)")
        self._m_heartbeat = self.registry.gauge(
            "rmd_serve_heartbeat_age_seconds",
            "seconds since the dispatch loop last went around")
        self._m_dropped = self.registry.gauge(
            "rmd_telemetry_dropped_total",
            "telemetry events shed by the bounded non-blocking buffer")
        self._m_burn = self.registry.gauge(
            "rmd_slo_burn_rate",
            "per-class SLO burn rate over the rolling window",
            ("klass", "model"))
        self._m_attain = self.registry.gauge(
            "rmd_slo_attainment",
            "per-class SLO attainment over the rolling window",
            ("klass", "model"))

    # -- state ---------------------------------------------------------------

    def _sessions(self):
        return (list(self.session.values())
                if isinstance(self.session, Mapping) else [self.session])

    def ready(self):
        return all(getattr(s, "ready", False) for s in self._sessions())

    def heartbeat_age(self):
        age = getattr(self.scheduler, "heartbeat_age", None)
        return age() if age else 0.0

    def live(self):
        return self.heartbeat_age() < self.stale_heartbeat_s

    def draining(self):
        return self._draining

    def begin_drain(self):
        """Flip the replica into draining: /healthz goes 503 with a
        ``draining`` body so external probes and the fleet router share
        one signal. In-flight and queued requests still complete (the
        scheduler keeps dispatching); only *routing* decisions change.
        Idempotent; returns True on the first transition."""
        first = not self._draining
        self._draining = True
        return first

    def _refresh_gauges(self):
        self._m_ready.set(1.0 if self.ready() else 0.0)
        self._m_heartbeat.set(round(self.heartbeat_age(), 3))
        if self.sink is not None:
            self._m_dropped.set(self.sink.dropped())
        slo = getattr(self.scheduler, "slo", None)
        if slo:
            for snap in slo.snapshot().values():
                labels = dict(klass=snap["klass"] or "default",
                              model=snap["model"])
                self._m_burn.labels(**labels).set(snap["burn_rate"])
                self._m_attain.labels(**labels).set(snap["attainment"])

    # -- endpoint payloads ---------------------------------------------------

    def metrics_text(self):
        self._refresh_gauges()
        return self.registry.render()

    def health(self):
        ready, age = self.ready(), self.heartbeat_age()
        live = age < self.stale_heartbeat_s
        payload = {
            "ready": ready,
            "live": live,
            "heartbeat_age_s": round(age, 3),
        }
        if self._draining:
            # a draining replica is deliberately unhealthy to probes:
            # finish what it holds, take nothing new
            payload["draining"] = True
            return payload, 503
        return payload, (200 if ready and live else 503)

    def status(self):
        sched = self.scheduler
        summary = getattr(sched, "trace_summary", None)
        slo = getattr(sched, "slo", None)
        snap = summary.snapshot() if summary is not None else {}
        depths = (sched.queue_depths()
                  if hasattr(sched, "queue_depths") else {})
        return {
            "ready": self.ready(),
            "draining": self._draining,
            "heartbeat_age_s": round(self.heartbeat_age(), 3),
            "queues": depths,
            "pending": sum(depths.values()),
            "requests": snap.get("count", 0),
            "compiles": (sum(s.compiles() for s in self._sessions())
                         if all(hasattr(s, "compiles")
                                for s in self._sessions()) else None),
            "classes": snap.get("classes", {}),
            "tail": snap.get("tail"),
            "slo": slo.snapshot() if slo else {},
            "telemetry_dropped": (self.sink.dropped()
                                  if self.sink is not None else 0),
        }

    def profile(self, seconds):
        """Capture ``seconds`` of jax profiler trace; returns the
        directory holding the capture plus an inline graftprof
        attribution summary (``RMD_PROFILE_ATTRIBUTION``).
        Single-flight: a second request while one runs gets a 409."""
        return sidecar.capture_profile(self._profile_lock, seconds,
                                       registry=self.registry)


class ObserverServer(sidecar.SidecarServer):
    """The bound HTTP server + its daemon thread (shared sidecar)."""

    def __init__(self, observer, port, host="127.0.0.1"):
        super().__init__(observer, port, host=host,
                         thread_name="serve-observe")


def serve_observer(session, scheduler, port, sink=None, registry=None):
    """Build and start the observability server; returns the
    :class:`ObserverServer` (``.port`` resolves port 0)."""
    obs = Observer(session, scheduler, sink=sink, registry=registry)
    return ObserverServer(obs, port).start()
