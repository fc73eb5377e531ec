"""Continuous-batching request scheduler for the serving path.

One dispatch thread pulls batches from the :class:`BucketBatcher` and
runs them through a :class:`~.session.ServeSession`; callers submit image
pairs from any thread and block on the returned :class:`Ticket`. A server
may hold several models, one session each, all resident: a request names
its model, the lanes are keyed by it, a batch runs on its lane's session
and never mixes models, and every record says which model it was. Three
invariants the tests pin:

- **The dispatch loop never stalls.** Overload sheds at admission with a
  typed :class:`ServeRejected` (bounded per-bucket queues); a request
  that fails mid-flight (fault-injected decode error, device failure)
  completes its ticket with a typed :class:`ServeError` while the rest of
  its batch — and the loop — carry on.
- **No batch poisoning.** Per-request failures are removed from the
  batch before assembly; the surviving requests still dispatch (refilled
  to the full batch size by tiling, so they keep the same compiled
  program).
- **Sticky per-client ordering.** Responses release to each client in
  submission order: a finished ticket whose predecessor (same client) is
  still in flight is held until the predecessor completes, so clients
  can stream results without reordering buffers.

With a video session (``ServeSession(video=True)``) a client id is also
a *sticky video session*: ``submit(..., sequence=True)`` requests ride
their own batcher lanes onto the warm-start program, seeded per member
from the bounded TTL-evicted :class:`~..video.SessionCache` (previous
frame's coarse carry, keyed by client). A member without a usable carry
gets a zero row — bit-exact with the plain cold rung — so cache
eviction and resolution switches degrade, never corrupt.
``submit(..., products=True)`` additionally dispatches the batch's
reversed pairs through the *same* compiled program (no new shapes) and
attaches fw/bw occlusion masks + confidence to the result.

This module is host-side only (no jax import — device work lives in the
session); per-request telemetry lands as ``serve`` events: ``request``
(success, with admission/queue/dispatch/device spans), ``error``,
``reject``, and per-dispatch ``batch`` records. Every time on this path
is a mark of the request's or the batch's trace (``telemetry.trace``),
stamped once; spans and phases are differences of those marks.
"""

import logging
import threading
import time
from collections.abc import Mapping

import numpy as np

from .. import telemetry
from ..telemetry import metrics as metrics_mod
from ..telemetry import slo as slo_mod
from ..telemetry import trace as trace_mod
from ..testing import faults
from ..utils import env
from .batcher import (BucketBatcher, FlowRequest, FlowResult, ServeError,
                      ServeRejected, lane_name)

# the dispatch loop wakes at least this often even when idle, so the
# liveness heartbeat (observe.py /healthz) keeps advancing
_HEARTBEAT_WAKE_S = 1.0


class Ticket:
    """Caller handle for one admitted request: blocks on :meth:`result`
    until the scheduler releases the response (in per-client submission
    order)."""

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
        """The :class:`FlowResult`, or raises the request's typed
        :class:`ServeError`; ``TimeoutError`` if nothing arrives in
        ``timeout`` seconds."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight "
                               f"after {timeout} s")
        if self._error is not None:
            raise self._error
        return self._result


def model_id(session):
    """The id a session's model answers to ("" for a stand-in without a
    spec): the lane key's first element and every record's ``model``."""
    return getattr(getattr(session, "spec", None), "id", "") or ""


class Scheduler:
    """Admission control + dispatch loop over one serve session, or over
    several: a mapping ``model id -> session`` (each model's buckets, wire
    and batch size are its session's own).

    ``batch_size``/``max_wait_ms``/``queue_limit`` default to each
    session's batch size and the ``RMD_SERVE_MAX_WAIT_MS`` /
    ``RMD_SERVE_QUEUE`` knobs. Ladder and video sessions are one model's
    server's: a server of several models refuses them at start.
    """

    def __init__(self, session, batch_size=None, max_wait_ms=None,
                 queue_limit=None):
        if max_wait_ms is None:
            max_wait_ms = env.get_float("RMD_SERVE_MAX_WAIT_MS")
        if queue_limit is None:
            queue_limit = env.get_int("RMD_SERVE_QUEUE")
        if isinstance(session, Mapping):
            self.models = dict(session)
            if not self.models:
                raise ValueError("a scheduler needs at least one session")
        else:
            self.models = {model_id(session): session}
        several = len(self.models) > 1
        if several and any(getattr(s, "ladder", None) is not None
                           or getattr(s, "video", False)
                           for s in self.models.values()):
            raise ValueError(
                "a server of several models serves no iteration ladder and "
                "no video sessions: both are one model's server's")
        # the one session of a one-model server (None with several)
        self.session = None if several else next(iter(self.models.values()))
        self.batcher = None
        for model, sess in self.models.items():
            size = sess.batch_size if batch_size is None else batch_size
            if self.batcher is None:
                self.batcher = BucketBatcher(sess.buckets, size, queue_limit,
                                             model=model)
            else:
                self.batcher.add_model(model, sess.buckets, size, queue_limit)
        self.max_wait_s = float(max_wait_ms) / 1e3

        # live observability plane: per-request trace summary, per-class
        # SLO burn windows (empty unless RMD_SLO_* targets are set), and
        # the rmd_serve_* metrics every instrumentation point feeds
        self.trace_summary = trace_mod.TraceSummary()
        self.slo = slo_mod.SLOTracker(by_model=several)
        self._heartbeat = time.monotonic()
        reg = metrics_mod.registry()
        self._m_requests = reg.counter(
            "rmd_serve_requests_total", "completed serve requests",
            ("klass", "bucket", "model"))
        self._m_errors = reg.counter(
            "rmd_serve_errors_total", "failed serve requests by typed kind",
            ("error", "model"))
        self._m_shed = reg.counter(
            "rmd_serve_shed_total", "admission rejections by reason",
            ("reason", "model"))
        self._m_batches = reg.counter(
            "rmd_serve_batches_total", "dispatched device batches",
            ("bucket", "klass", "model"))
        self._m_fill = reg.counter(
            "rmd_serve_fill_slots_total",
            "pad-tile fill slots dispatched in partial batches", ("model",))
        self._m_latency = reg.histogram(
            "rmd_serve_request_latency_seconds",
            "end-to-end request latency (submit to release)",
            ("klass", "model"))
        self._m_depth = reg.gauge(
            "rmd_serve_queue_depth", "queued requests across all lanes")
        self._m_switches = reg.counter(
            "rmd_serve_model_switches_total",
            "dispatched batches whose model differs from the batch before")
        self._last_model = None   # of the last batch (dispatch thread's)

        # video sessions: per-client warm-start carry, bounded + TTL
        # (hits/misses/evictions surface as rmd_serve_session_* metrics)
        self.sessions = None
        self._carry_factor = None  # (fy, fx) image-to-coarse-grid ratio
        if getattr(self.session, "video", False):
            from ..video import SessionCache

            self.sessions = SessionCache()

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._rid = 0
        self._seq = {}            # client -> next sequence number to assign
        self._release_next = {}   # client -> next sequence number to release
        self._held = {}           # client -> {seq: (request, result, error)}
        self._stopping = False
        self._thread = None

    # -- admission (caller threads) -----------------------------------------

    def submit(self, img1, img2, client="default", klass=None,
               sequence=False, products=False, model=None):
        """Admit one raw (un-normalized f32 HWC) image pair.

        ``model`` names the model that answers: with one session ``None``
        means that session; with several an unknown or missing model is
        a :class:`ServeError` (``unknown_model``), never a default.

        ``klass`` picks the latency class (``ladder.CLASSES``) when the
        session serves an iteration ladder — defaulting to ``balanced``;
        requests only batch with same-class neighbors. Without a ladder
        the class must stay unset.

        ``sequence=True`` marks a video-session frame: the request is
        warm-started from the client's cached carry and routed to the
        fast rung (``klass`` is ignored — warm-start requests ride the
        warm program by construction). Needs a video session.
        ``products=True`` additionally returns fw/bw occlusion +
        confidence on the result.

        Returns a :class:`Ticket` on acceptance. Raises synchronously:
        :class:`ServeError` (``malformed``/``oversized``/``unknown_model``/
        ``unknown_class``/``no_video``) when the payload can never be
        served, :class:`ServeRejected` (``queue_full``/``shutdown``)
        when the system sheds it — admission is where backpressure
        surfaces, the dispatch loop never blocks on overload.
        """
        t0 = time.perf_counter()
        with self._lock:
            rid = self._rid
            self._rid += 1

        held = ""
        try:
            held, session = self._session_of(model)
            klass = self._admit_klass(session, klass, sequence)
            self._validate(rid, img1, img2)
            h, w = int(img1.shape[0]), int(img1.shape[1])
            bucket = self.batcher.assign(h, w, model=held)
            if bucket is None or faults.fire("serve_oversized", index=rid):
                raise ServeError(
                    "oversized",
                    f"{h}x{w} fits no bucket ({session.buckets.describe()})")
        except ServeError as e:
            self._refused(rid, client, held, e)
            raise

        e1, e2 = self.batcher.encode_pair(img1, img2, bucket,
                                          session.encode_image, model=held)
        return self._enqueue(rid, client, bucket, (h, w), e1, e2, t0,
                             klass, sequence, products, held)

    def submit_encoded(self, e1, e2, shape, client="default", klass=None,
                       sequence=False, products=False, model=None):
        """Admit one *pre-encoded* pair: bucket-shaped arrays already in
        the session's wire dtype (the fleet front-end path — the client
        or router encoded at the edge, the bytes land on device
        untouched). ``shape`` is the original (H, W) the response crops
        to; the bucket is the arrays' spatial extent and must be one of
        the configured buckets of ``model``. Same typed error/shed
        contract as :meth:`submit`.
        """
        t0 = time.perf_counter()
        with self._lock:
            rid = self._rid
            self._rid += 1

        held = ""
        try:
            held, session = self._session_of(model)
            klass = self._admit_klass(session, klass, sequence)
            for img in (e1, e2):
                if not isinstance(img, np.ndarray) or img.ndim != 3 \
                        or img.shape[-1] != 3:
                    raise ServeError(
                        "malformed",
                        f"expected bucket-shaped HWC wire arrays, got "
                        f"{getattr(img, 'shape', type(img).__name__)}")
            if e1.shape != e2.shape:
                raise ServeError(
                    "malformed", f"pair shapes differ: {e1.shape} vs "
                                 f"{e2.shape}")
            want = getattr(session, "image_dtype", None)
            if want is not None and e1.dtype != want():
                raise ServeError(
                    "malformed",
                    f"wire dtype {e1.dtype} does not match the "
                    f"session's {want()}")
            bucket = (int(e1.shape[0]), int(e1.shape[1]))
            if bucket not in session.buckets.sizes:
                raise ServeError(
                    "oversized",
                    f"{bucket[0]}x{bucket[1]} is not a configured "
                    f"bucket ({session.buckets.describe()})")
            h, w = int(shape[0]), int(shape[1])
            if h > bucket[0] or w > bucket[1] or h < 1 or w < 1:
                raise ServeError(
                    "malformed",
                    f"crop shape {h}x{w} outside bucket "
                    f"{bucket[0]}x{bucket[1]}")
        except ServeError as e:
            self._refused(rid, client, held, e)
            raise

        return self._enqueue(rid, client, bucket, (h, w), e1, e2, t0,
                             klass, sequence, products, held)

    def _session_of(self, model):
        """``(model id, session)`` of the model a request names."""
        if model is None and self.session is not None:
            model = next(iter(self.models))
        if model not in self.models:
            raise ServeError(
                "unknown_model",
                f"{model!r} is not one of {sorted(self.models)}"
                if model is not None else
                f"a request to a server of several models names one of "
                f"{sorted(self.models)}")
        return model, self.models[model]

    def _refused(self, rid, client, model, e):
        """A request that can never be served, at admission. ``model`` is
        "" where the request named none this server holds."""
        # field name is 'error' (not 'kind'): the envelope's 'kind'
        # slot is the event kind itself
        self._m_errors.labels(error=e.kind, model=model).inc()
        telemetry.get().emit("serve", event="error", rid=rid,
                             client=client, error=e.kind, model=model)

    def _admit_klass(self, session, klass, sequence):
        if not sequence:
            return self._validate_klass(klass, session)
        if self.sessions is None:
            raise ServeError(
                "no_video",
                "sequence requests need a video session (serve --video)")
        # warm-start frames always enter at the fast rung; the warm
        # program rides its own batcher lanes per bucket
        return "fast" if getattr(session, "ladder", None) is not None else ""

    def _enqueue(self, rid, client, bucket, shape, e1, e2, t0, klass,
                 sequence, products, model):
        ticket = Ticket(rid, client)
        rtrace = trace_mod.RequestTrace(klass=klass, bucket=bucket,
                                        model=model)
        rtrace.mark("submit", t0)
        req = FlowRequest(rid=rid, client=client, seq=0, bucket=bucket,
                          shape=shape, img1=e1, img2=e2, ticket=ticket,
                          t_submit=t0, klass=klass,
                          sequence=bool(sequence), products=bool(products),
                          trace=rtrace, model=model)

        with self._cond:
            if self._stopping:
                self._m_shed.labels(reason="shutdown", model=model).inc()
                telemetry.get().emit("serve", event="reject", rid=rid,
                                     client=client, reason="shutdown",
                                     model=model)
                raise ServeRejected("shutdown")
            if not self.batcher.offer(req):
                self._m_shed.labels(reason="queue_full", model=model).inc()
                telemetry.get().emit(
                    "serve", event="reject", rid=rid, client=client,
                    reason="queue_full", bucket=f"{bucket[0]}x{bucket[1]}",
                    model=model)
                raise ServeRejected(
                    "queue_full",
                    f"lane {lane_name(model, bucket)} queue at bound "
                    f"({self.batcher.queue_limit})")
            rtrace.mark("enqueue", req.t_enqueue)
            self._m_depth.set(self.batcher.pending())
            req.seq = self._seq.get(client, 0)
            self._seq[client] = req.seq + 1
            self._cond.notify()
        return ticket

    def _validate_klass(self, klass, session=None):
        from . import ladder as ladder_mod

        session = self.session if session is None else session
        has_ladder = getattr(session, "ladder", None) is not None
        if klass is None:
            return "balanced" if has_ladder else ""
        if not has_ladder:
            raise ServeError(
                "unknown_class",
                f"latency class {klass!r} needs a session with an "
                f"iteration ladder (serve --ladder)")
        if klass not in ladder_mod.CLASSES:
            raise ServeError(
                "unknown_class",
                f"{klass!r} is not one of {'/'.join(ladder_mod.CLASSES)}")
        return klass

    def _validate(self, rid, img1, img2):
        if faults.fire("serve_malformed", index=rid):
            raise ServeError("malformed", "fault injected")
        for img in (img1, img2):
            if not isinstance(img, np.ndarray) or img.ndim != 3 \
                    or img.shape[-1] != 3:
                raise ServeError(
                    "malformed",
                    f"expected HWC RGB arrays, got "
                    f"{getattr(img, 'shape', type(img).__name__)}")
        if img1.shape != img2.shape:
            raise ServeError(
                "malformed", f"pair shapes differ: {img1.shape} vs "
                             f"{img2.shape}")

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        self._thread = threading.Thread(
            target=self._loop, name="serve-dispatch", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain=True):
        """Stop admitting; by default drain queued requests (partials
        dispatch immediately), otherwise fail them with a typed error."""
        with self._cond:
            self._stopping = True
            if not drain:
                flushed = []
                while True:
                    bucket, batch = self.batcher.take(
                        time.perf_counter(), 0.0, drain=True)
                    if bucket is None:
                        break
                    flushed.extend(batch)
                self._cond.notify_all()
            else:
                flushed = []
                self._cond.notify_all()
        for r in flushed:
            self._complete(r, error=ServeError("internal", "shutdown"))
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def pending(self):
        with self._lock:
            return self.batcher.pending()

    def heartbeat_age(self):
        """Seconds since the dispatch loop last went around — the
        /healthz liveness signal (the loop wakes at least every
        ``_HEARTBEAT_WAKE_S`` even when idle)."""
        return time.monotonic() - self._heartbeat

    def queue_depths(self):
        """Per-lane queue depths (``[model:]HxW[/klass]`` -> count)."""
        with self._lock:
            return self.batcher.depths()

    # -- dispatch loop -------------------------------------------------------

    def _loop(self):
        while True:
            t_wait = time.perf_counter()    # back to waiting for a batch
            with self._cond:
                while True:
                    self._heartbeat = time.monotonic()
                    now = time.perf_counter()
                    bucket, batch = self.batcher.take(
                        now, self.max_wait_s, drain=self._stopping)
                    if bucket is not None:
                        break
                    if self._stopping:
                        return
                    deadline = batch  # (None, deadline) overload of take()
                    # idle waits are capped so the liveness heartbeat
                    # keeps advancing with nothing queued
                    timeout = (_HEARTBEAT_WAKE_S if deadline is None
                               else min(_HEARTBEAT_WAKE_S,
                                        max(0.0, deadline - now)))
                    self._cond.wait(timeout)
            try:
                self._dispatch(bucket, batch, t_wait)
            except Exception as e:  # noqa: BLE001 - loop must survive
                logging.exception(
                    f"serve: dispatch of a {len(batch)}-request batch on "
                    f"bucket {bucket} failed")
                for r in batch:
                    self._complete(r, error=ServeError("internal", str(e)))

    def _dispatch(self, bucket, batch, t_wait=None):
        t0 = time.perf_counter()

        # per-request decode faults: remove the poisoned request, keep the
        # rest of the batch (assemble refills to the full size by tiling)
        live = []
        for r in batch:
            if faults.fire("serve_decode_error", index=r.rid):
                self._complete(
                    r, error=ServeError("decode", "fault injected"))
            else:
                live.append(r)
        if not live:
            return
        klass = live[0].klass  # lanes are same-class by construction
        model = live[0].model  # and same-model: the batch's session
        session = self.models[model]
        if self._last_model is not None and model != self._last_model:
            self._m_switches.inc()
        self._last_model = model
        # test stand-in sessions may not expose a program fingerprint
        fingerprint = getattr(session, "program_fingerprint", None)
        btrace = trace_mod.BatchTrace(
            bucket, klass,
            program=fingerprint(klass) if fingerprint else None,
            model=model)
        if t_wait is not None:
            btrace.mark("wait", t_wait)
        btrace.mark("dispatch", t0)
        for r in live:
            r.trace.mark("dispatch", t0)
            btrace.link(r.trace)

        img1, img2, fill = self.batcher.assemble(live)
        btrace.mark("assembled")
        btrace.fill = fill
        c0 = session.compiles()
        sequence = live[0].sequence  # lanes are same-sequence-ness too
        warm_rows = [None] * len(live)
        state = None
        if sequence:
            carry, warm_rows = self._gather_carry(live, bucket, fill)
            flow, state, info = session.run_video(img1, img2, carry)
        elif klass:
            flow, info = session.run_ladder(img1, img2, klass)
        else:
            flow, info = session.run(img1, img2), None
        called, ready = self._run_marks(session)
        products = any(r.products for r in live)
        flow_bw = None
        if products:
            # fw/bw products: the reversed pairs ride the *same*
            # compiled program (same shapes — zero new programs); video
            # batches reverse cold, a carry has no meaning backwards
            if sequence:
                bw_dev, _, _ = session.run_video(img2, img1)
            elif klass:
                bw_dev, _ = session.run_ladder(img2, img1, klass)
            else:
                bw_dev = session.run(img2, img1)
            _, ready = self._run_marks(session)
        btrace.mark("called", called)
        t1 = btrace.mark("ready", ready)
        flow = session.fetch(flow)
        if products:
            flow_bw = session.fetch(bw_dev)
        if sequence:
            self._store_carry(live, bucket, state)
        t2 = btrace.mark("fetched")

        tele = telemetry.get()
        batch_event = dict(
            model=model, bucket=f"{bucket[0]}x{bucket[1]}", size=len(live),
            fill=fill,
            compiles=session.compiles() - c0,
            seconds=round(t1 - t0, 6))
        if info is not None:
            batch_event.update(klass=klass, rungs=info["rungs"],
                               iterations=info["iterations"])
        if sequence:
            batch_event.update(
                video=True,
                warm_members=sum(1 for row in warm_rows if row is not None))
        if products:
            batch_event.update(products=True)
        tele.emit("serve", event="batch", **batch_event)
        self._m_batches.labels(
            bucket=f"{bucket[0]}x{bucket[1]}", klass=klass,
            model=model).inc()
        if fill > 0:
            self._m_fill.labels(model=model).inc(fill)
        self._m_depth.set(self.batcher.pending())

        for i, r in enumerate(live):
            h, w = r.shape
            r.trace.mark("launched", t1)
            r.trace.mark("fetched", t2)
            occ = conf = None
            if r.products and flow_bw is not None:
                from ..video.products import fw_bw_products

                occ, conf = fw_bw_products(flow[i, :h, :w, :],
                                           flow_bw[i, :h, :w, :])
            self._complete(r, result=FlowResult(
                rid=r.rid, client=r.client, bucket=bucket, shape=r.shape,
                flow=flow[i, :h, :w, :], spans={}, klass=klass,
                iterations=(info["iterations"] if info else 0),
                warm=warm_rows[i] is not None,
                occlusion=occ, confidence=conf, model=model))
        btrace.mark("completed")
        tele.emit("trace", event="batch", **btrace.record())

    def _run_marks(self, session):
        """``(called, ready)`` of the session's last run: when the program
        call returned and when its result was ready on the device. The
        session stamps them around its own ``block_until_ready``; a
        stand-in session without them has just returned from both."""
        marks = getattr(session, "run_marks", None)
        if marks is None:
            now = time.perf_counter()
            return now, now
        return marks

    # -- video session carry -------------------------------------------------

    def _carry_shape(self, bucket):
        """Expected coarse-carry row shape for ``bucket``, or None until
        the model's downsampling factor has been observed (before any
        video dispatch the cache is necessarily empty)."""
        if self._carry_factor is None:
            return None
        fy, fx = self._carry_factor
        return (int(round(bucket[0] / fy)), int(round(bucket[1] / fx)), 2)

    def carry_shapes(self):
        """Every configured bucket's expected carry shape — what an
        imported session-handoff snapshot must match — or None until the
        model's downsampling factor has been observed (then the
        cache's shape-checked lookup is the only guard)."""
        if self._carry_factor is None:
            return None
        return {self._carry_shape(b) for b in self.session.buckets.sizes}

    def _gather_carry(self, live, bucket, fill):
        """Per-member cached carries stacked into one batch array.

        Members without a usable carry (new client, TTL-evicted,
        resolution switch) get zero rows — the warm program is bit-exact
        with the cold rung on zeros, so a partial-warm batch is always
        correct. Returns ``(carry | None, per-member rows)``; None when
        no member is warm (the batch runs the plain cold rung)."""
        expected = self._carry_shape(bucket)
        rows = [self.sessions.get(r.client, expected) for r in live]
        have = [row for row in rows if row is not None]
        if not have:
            return None, rows
        proto = have[0]
        carry = np.stack([row if row is not None else np.zeros_like(proto)
                          for row in rows])
        if fill > 0:
            carry = np.concatenate(
                [carry, np.repeat(carry[-1:], fill, axis=0)])
        return carry, rows

    def _store_carry(self, live, bucket, state):
        """Store each member's fresh coarse-flow carry for its client
        (fill rows are dropped); the first store also pins the
        image-to-coarse-grid factor the shape check needs."""
        coarse = self.session.fetch(state["flow"])
        if self._carry_factor is None:
            self._carry_factor = (bucket[0] / coarse.shape[1],
                                  bucket[1] / coarse.shape[2])
        for i, r in enumerate(live):
            self.sessions.put(r.client, coarse[i])

    # -- completion / sticky per-client release ------------------------------

    def _complete(self, req, result=None, error=None):
        with self._lock:
            held = self._held.setdefault(req.client, {})
            held[req.seq] = (req, result, error)
            nxt = self._release_next.get(req.client, 0)
            ready = []
            while nxt in held:
                ready.append(held.pop(nxt))
                nxt += 1
            self._release_next[req.client] = nxt
        for r, res, err in ready:
            total = r.trace.mark("released").total()
            tele = telemetry.get()
            if err is None:
                # the latency spans are the trace's marks under their
                # older names, total included
                res.spans.update(r.trace.spans())
                extra = ({"klass": res.klass, "iterations": res.iterations}
                         if res.klass else {})
                tele.emit(
                    "serve", event="request", rid=r.rid, client=r.client,
                    model=r.model, bucket=f"{r.bucket[0]}x{r.bucket[1]}",
                    seconds=round(total, 6),
                    spans={k: round(v, 6) for k, v in res.spans.items()},
                    **extra)
                self._m_requests.labels(
                    klass=r.klass, bucket=f"{r.bucket[0]}x{r.bucket[1]}",
                    model=r.model).inc()
                self._m_latency.labels(klass=r.klass,
                                       model=r.model).observe(total)
                record = r.trace.record()
                tele.emit("trace", event="request", rid=r.rid, **record)
                self.trace_summary.add(record)
                self.slo.record(r.klass, total, model=r.model)
                self.slo.maybe_emit(tele)
            else:
                self._m_errors.labels(
                    error=getattr(err, "kind", "internal"),
                    model=r.model).inc()
                tele.emit("serve", event="error", rid=r.rid,
                          client=r.client, model=r.model,
                          error=getattr(err, "kind", "internal"),
                          seconds=round(total, 6))
            r.ticket._complete(result=res, error=err)
