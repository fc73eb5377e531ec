"""Fleet front-end router: dispatch, backpressure, drain, affinity.

The router is the fleet's admission plane. It exposes the same
``submit(...) -> ticket`` surface as the in-process scheduler (so the
loadgen and the CLI drive a fleet unchanged) plus an HTTP
front-end for real network clients, and routes every request to one of
N replica processes:

- **least-loaded dispatch per (bucket, class)**: each replica tracks
  in-flight counts per lane; the eligible replica with the fewest
  in-flight requests on the request's lane wins (total in-flight breaks
  ties), so a slow replica backs up only its own lanes and a skewed
  bucket/class mix spreads by *load*, not round-robin luck.
- **bounded retry on safe failures**: transport failures that provably
  returned no response (connection refused/reset, replica died
  mid-exchange) and typed replica sheds (429 queue_full, 503 draining)
  re-dispatch to another replica with jittered backoff, at most
  ``RMD_FLEET_RETRIES`` times within the per-request
  ``RMD_FLEET_TIMEOUT_MS`` deadline. Application errors (400/500) are
  deterministic and complete the ticket typed, never retried.
- **typed fleet shed**: when no eligible replica exists the request
  sheds ``replica_unavailable``; when every try shed ``queue_full`` the
  fleet-wide answer is ``queue_full``. Callers see exactly the
  :class:`~..serve.batcher.ServeRejected` contract the single-replica
  scheduler pins.
- **health/drain from the PR-13 plane**: a poll thread reads every
  replica's /healthz (readiness, liveness age, draining) and /statusz
  (per-class SLO burn). Burn above ``RMD_FLEET_BURN_DRAIN`` or a stale
  liveness heartbeat drains the replica: traffic shifts off, sticky
  sessions hand off, the supervisor recycles it.
- **session affinity + handoff**: sticky video clients pin to one
  replica (their carry lives there). On drain the carry snapshot moves
  to the new owner via /sessionz (at most one *handoff* blip, zero cold
  frames when the import validates); on death it is evicted and the
  stream restarts with exactly one cold frame — never a dropped stream.
"""

import json
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlparse

from .. import telemetry
from ..serve.batcher import FlowResult, ServeError, ServeRejected
from ..telemetry import metrics as metrics_mod
from ..telemetry import sidecar
from ..utils import env
from . import wire as fwire
from .client import ReplicaClient, ReplicaDown, ReplicaTimeout

# the router's own HTTP surface (front-end, not sidecar);
# graftlint:sidecar-route checks these against README
ROUTES = ("/v1/flow", "/fleetz", "/healthz")

# consecutive health-poll transport failures before a replica is
# declared dead (distinguishes a lost poll from a lost process)
_HEALTH_FAILURES_DOWN = 3
# jittered retry backoff base; doubles per attempt
_RETRY_BACKOFF_S = 0.025


class FleetTicket:
    """Caller handle for one routed request (scheduler-Ticket shaped:
    ``result(timeout)`` returns the FlowResult or raises the typed
    ServeError/ServeRejected)."""

    def __init__(self, rid, client):
        self.rid = rid
        self.client = client
        self._event = threading.Event()
        self._result = None
        self._error = None

    def _complete(self, result=None, error=None):
        self._result = result
        self._error = error
        self._event.set()

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight "
                               f"after {timeout} s")
        if self._error is not None:
            raise self._error
        return self._result


class ReplicaState:
    """Router-side view of one replica: health + per-lane load."""

    def __init__(self, name, url):
        self.name = name
        self.url = url
        self.client = ReplicaClient(url)
        self.up = True
        self.ready = True
        self.live = True
        self.draining = False
        self.generation = 0
        self.health_failures = 0
        self.burn = 0.0
        self.inflight = {}  # (bucket, klass) -> count
        self.total_inflight = 0

    def eligible(self):
        return self.up and self.ready and self.live and not self.draining

    def lane_load(self, lane):
        return self.inflight.get(lane, 0)

    def describe(self):
        return {
            "url": self.url, "up": self.up, "ready": self.ready,
            "live": self.live, "draining": self.draining,
            "generation": self.generation,
            "burn": round(self.burn, 3),
            "inflight": self.total_inflight,
        }


class Router:
    """The fleet dispatch plane over N replica processes."""

    def __init__(self, codec, retries=None, timeout_ms=None,
                 burn_drain=None, health_interval_s=None, workers=16,
                 on_recycle=None):
        self.codec = codec
        self.retries = int(retries if retries is not None
                           else env.get_int("RMD_FLEET_RETRIES"))
        timeout_ms = (timeout_ms if timeout_ms is not None
                      else env.get_float("RMD_FLEET_TIMEOUT_MS"))
        self.timeout_s = float(timeout_ms) / 1e3
        self.burn_drain = float(burn_drain if burn_drain is not None
                                else env.get_float("RMD_FLEET_BURN_DRAIN"))
        self.health_interval_s = float(
            health_interval_s if health_interval_s is not None
            else env.get_float("RMD_FLEET_HEALTH_S"))
        # supervisor hook: called with a replica name after drain-handoff
        # completes, so the process can be recycled
        self.on_recycle = on_recycle

        self._replicas = {}
        self._affinity = {}  # sticky client -> replica name
        self._lock = threading.Lock()
        self._rid = 0
        self._pool = ThreadPoolExecutor(max_workers=int(workers),
                                        thread_name_prefix="fleet-route")
        self._health_thread = None
        self._stopping = threading.Event()
        self.sheds = {}   # reason -> count (fleet-level, typed)
        self.retries_done = 0

        reg = metrics_mod.registry()
        self._m_requests = reg.counter(
            "rmd_fleet_requests_total",
            "requests completed per replica", ("replica",))
        self._m_retries = reg.counter(
            "rmd_fleet_retries_total",
            "safe-failure re-dispatches to another replica")
        self._m_shed = reg.counter(
            "rmd_fleet_shed_total",
            "fleet-level typed request sheds", ("reason",))
        self._m_handoffs = reg.counter(
            "rmd_fleet_handoffs_total",
            "sticky sessions moved or evicted on drain/death",
            ("outcome",))
        self._m_drains = reg.counter(
            "rmd_fleet_drains_total",
            "replicas drained by trigger", ("reason",))
        self._m_ready = reg.gauge(
            "rmd_fleet_replicas_ready",
            "replicas currently eligible for dispatch")
        self._m_inflight = reg.gauge(
            "rmd_fleet_inflight", "requests in flight across the fleet")

    # -- membership (supervisor callbacks) -----------------------------------

    def add_replica(self, name, url):
        """(Re)register a replica — fresh state, traffic eligible.

        Idempotent while the replica is up at the same URL (the
        supervisor's boot announce and an explicit registration loop
        may race); a re-add after death/drain bumps the generation."""
        with self._lock:
            prior = self._replicas.get(name)
            if prior is not None and prior.up and not prior.draining \
                    and prior.url == url:
                return prior
            state = ReplicaState(name, url)
            state.generation = prior.generation + 1 if prior else 0
            self._replicas[name] = state
        telemetry.get().emit("fleet", event="replica_up", replica=name,
                             url=url, generation=state.generation)
        self._refresh_ready_gauge()
        return state

    def mark_down(self, name, reason="died"):
        """A replica process is gone: stop routing, evict its sticky
        sessions (the carry died with it — one cold frame per stream)."""
        with self._lock:
            state = self._replicas.get(name)
            if state is None or not state.up:
                return
            state.up = False
            orphans = [c for c, owner in self._affinity.items()
                       if owner == name]
            for c in orphans:
                del self._affinity[c]
        for c in orphans:
            self._m_handoffs.labels(outcome="evicted").inc()
            telemetry.get().emit("fleet", event="handoff", client=c,
                                 source=name, outcome="evicted",
                                 reason=reason)
        telemetry.get().emit("fleet", event="replica_down", replica=name,
                             reason=reason)
        self._refresh_ready_gauge()

    def replicas(self):
        with self._lock:
            return dict(self._replicas)

    def _refresh_ready_gauge(self):
        with self._lock:
            ready = sum(1 for s in self._replicas.values() if s.eligible())
            inflight = sum(s.total_inflight
                           for s in self._replicas.values())
        self._m_ready.set(ready)
        self._m_inflight.set(inflight)
        return ready

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        self._health_thread = threading.Thread(
            target=self._health_loop, name="fleet-health", daemon=True)
        self._health_thread.start()
        return self

    def stop(self):
        self._stopping.set()
        if self._health_thread is not None:
            self._health_thread.join(timeout=5.0)
            self._health_thread = None
        self._pool.shutdown(wait=True)

    # -- admission -----------------------------------------------------------

    def submit(self, img1, img2, client="default", klass=None,
               sequence=False, products=False):
        """Scheduler-shaped admission: encode at the edge, dispatch on
        the pool, return a ticket. Payload errors raise synchronously
        (same typed contract as in-process admission); routing failures
        and replica sheds complete the ticket with the typed error."""
        if products:
            raise ServeError(
                "malformed",
                "fw/bw products are not served over the fleet wire")
        e1, e2, bucket, shape = self.codec.encode_pair(img1, img2)
        meta = {
            "bucket": list(bucket),
            "shape": list(shape),
            "dtype": str(e1.dtype),
            "client": client,
            "sequence": bool(sequence),
        }
        if klass is not None:
            meta["klass"] = klass
        return self.submit_wire(meta, fwire.pack_pair(e1, e2))

    def submit_wire(self, meta, body):
        """Admit one already-encoded request (the HTTP front-end path:
        client bytes go to the device untouched)."""
        with self._lock:
            rid = self._rid
            self._rid += 1
        ticket = FleetTicket(rid, str(meta.get("client", "default")))
        self._pool.submit(self._route, ticket, meta, body)
        return ticket

    # -- dispatch ------------------------------------------------------------

    def _lane(self, meta):
        bucket = tuple(meta.get("bucket", ()))
        return (bucket, meta.get("klass") or "")

    def _pick(self, lane, client, sequence, exclude=()):
        """The target replica, honoring sticky affinity then least
        lane load. Returns (state, sticky) or (None, False)."""
        with self._lock:
            if sequence:
                owner = self._affinity.get(client)
                if owner is not None:
                    state = self._replicas.get(owner)
                    if state is not None and state.eligible() \
                            and owner not in exclude:
                        return state, True
            candidates = [s for s in self._replicas.values()
                          if s.eligible() and s.name not in exclude]
            if not candidates:
                # a retry may have excluded every live replica; better
                # a repeated target than a spurious shed
                candidates = [s for s in self._replicas.values()
                              if s.eligible()]
            if not candidates:
                return None, False
            state = min(candidates,
                        key=lambda s: (s.lane_load(lane),
                                       s.total_inflight, s.name))
            if sequence:
                self._affinity[client] = state.name
            return state, False

    def _track(self, state, lane, delta):
        with self._lock:
            state.inflight[lane] = max(
                0, state.inflight.get(lane, 0) + delta)
            state.total_inflight = max(0, state.total_inflight + delta)

    def _shed(self, ticket, reason, detail=""):
        with self._lock:
            self.sheds[reason] = self.sheds.get(reason, 0) + 1
        self._m_shed.labels(reason=reason).inc()
        telemetry.get().emit("fleet", event="shed", rid=ticket.rid,
                             client=ticket.client, reason=reason)
        ticket._complete(error=ServeRejected(reason, detail))

    def _route(self, ticket, meta, body):
        try:
            self._route_inner(ticket, meta, body)
        except Exception as e:  # noqa: BLE001 - a routing bug must fail the ticket, not the pool thread
            ticket._complete(error=ServeError("internal", str(e)))

    def _route_inner(self, ticket, meta, body):
        lane = self._lane(meta)
        client = ticket.client
        sequence = bool(meta.get("sequence", False))
        deadline = time.monotonic() + self.timeout_s
        tried = []
        last_queue_full = False
        for attempt in range(self.retries + 1):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            state, sticky = self._pick(lane, client, sequence,
                                       exclude=tried)
            if state is None:
                self._shed(ticket, "replica_unavailable",
                           "no eligible replica")
                return
            if attempt > 0:
                self.retries_done += 1
                self._m_retries.inc()
                telemetry.get().emit(
                    "fleet", event="retry", rid=ticket.rid,
                    client=client, attempt=attempt, replica=state.name)
                backoff = (_RETRY_BACKOFF_S * (2 ** (attempt - 1))
                           * random.uniform(0.5, 1.5))
                time.sleep(min(backoff, max(0.0, remaining)))
            self._track(state, lane, +1)
            try:
                status, out_meta, out_body = state.client.flow(
                    meta, body, timeout=remaining)
            except ReplicaTimeout:
                # the per-request deadline is spent waiting on this
                # replica; answering late AND re-executing elsewhere
                # would blow the deadline anyway — fail typed
                self._shed(ticket, "replica_unavailable",
                           f"replica {state.name} deadline "
                           f"({self.timeout_s} s)")
                return
            except ReplicaDown as e:
                # no response ever arrived: safe to retry elsewhere
                tried.append(state.name)
                self.mark_down(state.name, reason=str(e)[:120])
                continue
            finally:
                self._track(state, lane, -1)

            if status == 200:
                self._finish(ticket, state, out_meta, out_body)
                return
            reason = (out_meta or {}).get("error", "internal")
            if status in fwire.SAFE_RETRY_STATUS:
                # typed replica shed (queue_full/draining/shutdown):
                # another replica may have room
                tried.append(state.name)
                last_queue_full = (status == 429)
                continue
            # deterministic application error: complete typed, no retry
            kind = reason if reason in fwire.STATUS_BY_ERROR else "internal"
            ticket._complete(error=ServeError(
                kind, (out_meta or {}).get("detail", "")))
            return
        self._shed(ticket,
                   "queue_full" if last_queue_full
                   else "replica_unavailable",
                   f"retries exhausted after {len(tried)} replicas")

    def _finish(self, ticket, state, out_meta, out_body):
        try:
            flow, out_meta = fwire.unpack_result(out_meta or {}, out_body)
        except ServeError as e:
            ticket._complete(error=e)
            return
        shape = tuple(out_meta["shape"])
        spans = {k: float(v)
                 for k, v in (out_meta.get("spans") or {}).items()}
        self._m_requests.labels(replica=state.name).inc()
        telemetry.get().emit(
            "fleet", event="route", rid=ticket.rid, client=ticket.client,
            replica=state.name, klass=out_meta.get("klass", ""),
            warm=bool(out_meta.get("warm", False)))
        ticket._complete(result=FlowResult(
            rid=ticket.rid, client=ticket.client,
            bucket=shape, shape=shape, flow=flow, spans=spans,
            klass=out_meta.get("klass", ""),
            iterations=int(out_meta.get("iterations", 0)),
            warm=bool(out_meta.get("warm", False))))

    # -- health / drain ------------------------------------------------------

    def _health_loop(self):
        while not self._stopping.wait(self.health_interval_s):
            self.poll_health()

    def poll_health(self):
        """One pass over every replica's /healthz + /statusz (also
        callable directly by tests/drills for determinism)."""
        for state in list(self.replicas().values()):
            if not state.up:
                continue
            try:
                payload, _status = state.client.health(
                    timeout=self.health_interval_s * 4)
                state.health_failures = 0
            except (ReplicaDown, ReplicaTimeout):
                state.health_failures += 1
                if state.health_failures >= _HEALTH_FAILURES_DOWN:
                    self.mark_down(state.name, reason="unreachable")
                continue
            state.ready = bool(payload.get("ready", False))
            state.live = bool(payload.get("live", False))
            replica_draining = bool(payload.get("draining", False))
            if replica_draining and not state.draining:
                # the replica began draining on its own (operator poke
                # at /drainz): honor it — shift traffic + hand off
                self.drain_replica(state.name, reason="replica")
                continue
            if not state.live and not state.draining:
                self.drain_replica(state.name, reason="liveness")
                continue
            try:
                status = state.client.status(
                    timeout=self.health_interval_s * 4)
            except (ReplicaDown, ReplicaTimeout):
                continue
            burns = [s.get("burn_rate", 0.0)
                     for s in (status.get("slo") or {}).values()]
            state.burn = max(burns) if burns else 0.0
            if self.burn_drain > 0 and state.burn > self.burn_drain \
                    and not state.draining:
                self.drain_replica(state.name, reason="slo_burn")
        self._refresh_ready_gauge()

    def drain_replica(self, name, reason="manual"):
        """Shift traffic off a replica and hand off its sticky sessions.

        The replica keeps serving its queue (drain is graceful); new
        requests stop routing to it immediately. Each sticky client's
        carry snapshot moves to a newly-pinned replica — a failed
        export/import degrades that one stream to a single cold frame
        (evicted), never a dropped stream."""
        with self._lock:
            state = self._replicas.get(name)
            if state is None or state.draining:
                return
            state.draining = True
        self._m_drains.labels(reason=reason).inc()
        telemetry.get().emit("fleet", event="drain", replica=name,
                            reason=reason, source="router")
        try:
            state.client.drain()
        except (ReplicaDown, ReplicaTimeout):
            self.mark_down(name, reason="died during drain")
            return
        self._handoff_sessions(state)
        if self.on_recycle is not None:
            self.on_recycle(name)

    def _handoff_sessions(self, source):
        with self._lock:
            stuck = [c for c, owner in self._affinity.items()
                     if owner == source.name]
        for c in stuck:
            target, _ = self._pick(((0, 0), ""), c, False,
                                   exclude=[source.name])
            outcome = "evicted"
            if target is not None:
                try:
                    snapshot = source.client.export_session(c)
                    if snapshot is not None and \
                            target.client.import_session(snapshot):
                        outcome = "moved"
                except (ReplicaDown, ReplicaTimeout):
                    outcome = "evicted"
            with self._lock:
                if outcome == "moved":
                    self._affinity[c] = target.name
                else:
                    self._affinity.pop(c, None)
            self._m_handoffs.labels(outcome=outcome).inc()
            telemetry.get().emit(
                "fleet", event="handoff", client=c, source=source.name,
                target=target.name if outcome == "moved" else None,
                outcome=outcome)

    # -- introspection -------------------------------------------------------

    def describe(self):
        with self._lock:
            replicas = {n: s.describe()
                        for n, s in self._replicas.items()}
            affinity = len(self._affinity)
            sheds = dict(self.sheds)
        return {
            "replicas": replicas,
            "sticky_sessions": affinity,
            "sheds": sheds,
            "retries": self.retries_done,
        }


class _FrontendObserver:
    """Adapter giving the router a sidecar-shaped health surface."""

    def __init__(self, router):
        self.router = router

    def health(self):
        ready = sum(1 for s in self.router.replicas().values()
                    if s.eligible())
        return ({"ready": ready > 0, "replicas_ready": ready},
                200 if ready > 0 else 503)


class FrontendHandler(sidecar.Handler):
    """HTTP front-end: the network boundary real clients speak to."""

    def do_GET(self):  # noqa: N802 - BaseHTTPRequestHandler contract
        url = urlparse(self.path)
        router = self.observer.router
        try:
            if url.path == "/fleetz":
                self._send_json(200, router.describe())
            elif url.path == "/healthz":
                payload, code = self.observer.health()
                self._send_json(code, payload)
            else:
                self._send_json(404, {"error": f"no route {url.path}"})
        except Exception as e:  # noqa: BLE001 - a scrape must not kill the router
            self._send_json(500, {"error": f"{type(e).__name__}: {e}"})

    def do_POST(self):  # noqa: N802 - BaseHTTPRequestHandler contract
        url = urlparse(self.path)
        router = self.observer.router
        try:
            if url.path != "/v1/flow":
                self._send_json(404, {"error": f"no route {url.path}"})
                return
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length) if length else b""
            try:
                meta = fwire.loads_meta(self.headers.get(fwire.META_HEADER))
            except ServeError as e:
                self._send_json(400, {"error": e.kind, "type": "error",
                                      "detail": str(e)})
                return
            ticket = router.submit_wire(meta, body)
            try:
                result = ticket.result(timeout=router.timeout_s + 1.0)
            except ServeRejected as e:
                self._send_json(
                    fwire.STATUS_BY_REJECT.get(e.reason, 503),
                    {"error": e.reason, "type": "rejected",
                     "detail": str(e)})
                return
            except (ServeError, TimeoutError) as e:
                kind = getattr(e, "kind", "timeout")
                self._send_json(
                    fwire.STATUS_BY_ERROR.get(kind, 500),
                    {"error": kind, "type": "error", "detail": str(e)})
                return
            wire = router.codec.wire
            flow_dtype = ("float16" if wire is not None
                          and wire.flow == "f16" else "float32")
            out_meta, out_body = fwire.pack_result(result, flow_dtype)
            data = out_body
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header(fwire.META_HEADER, fwire.dumps_meta(out_meta))
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        except Exception as e:  # noqa: BLE001 - a request must not kill the router
            try:
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"})
            except OSError:
                pass  # client went away mid-reply


class FrontendServer(sidecar.SidecarServer):
    """The router's bound HTTP server (daemon thread)."""

    def __init__(self, router, port, host="127.0.0.1"):
        obs = _FrontendObserver(router)
        super().__init__(obs, port, host=host,
                         thread_name="fleet-frontend",
                         handler_cls=FrontendHandler)


def serve_frontend(router, port):
    """Bind and start the fleet HTTP front-end; returns the
    :class:`FrontendServer` (``.port`` resolves port 0)."""
    return FrontendServer(router, port).start()
