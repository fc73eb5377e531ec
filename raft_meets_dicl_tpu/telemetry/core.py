"""Run-wide structured telemetry: spans, step phases, JSONL event sink.

Round 5 cut the real training loop from 5.8 to 1.2 s/step only after
hand-timing exposed three invisible host-side stalls (PERF.md); this
module makes those visible on *every* run. Each run directory gets an
``events.jsonl`` whose records follow a versioned schema (``SCHEMA``),
covering per-step phase timings, throughput, compiles and persistent
compile-cache hits/misses, memory watermarks, non-finite-guard flushes,
and stage/epoch/checkpoint boundaries.

Design constraints, in order:

1. **The hot path must stay hot.** A step or a batch stamps each mark
   once (``steptrace``, ``trace``) and its event carries the marks;
   events buffer in memory and flush at boundaries
   (epoch/stage/run) or every ``_FLUSH_EVERY`` records; device step time
   is sampled by piggybacking on the amortized finiteness fetch instead
   of a per-step ``block_until_ready`` (which would serialize the async
   pipeline — the exact regression round 5 removed).
2. **Off means off.** ``RMD_TELEMETRY=0`` routes every call site through
   :class:`NullTelemetry` no-ops; no file is opened, no listener fires.
3. **One sink per process.** ``activate()`` installs the process-wide
   sink returned by ``get()``; the jax.monitoring listeners (compile
   durations, compile-cache hits/misses) are registered once and forward
   to whatever sink is active.
"""

import collections
import contextlib
import json
import os
import threading
import time

from . import blackbox as _blackbox
from . import goodput as _goodput
from . import witness

SCHEMA_VERSION = 1

# Minor revision within the major schema: bumped when kinds or optional
# fields are *added*. Producers stamp the plain major in ``v`` (older
# readers keep working); a reader seeing ``v`` with the same major but a
# larger fractional minor (e.g. 1.2 from a newer producer) should skip
# the record, not reject the file — see :class:`NewerSchema`.
SCHEMA_MINOR = 7

# kind -> required payload fields (beyond the {v, t, kind} envelope).
# Extra fields are allowed everywhere: the schema pins the floor a
# consumer can rely on, not the ceiling.
SCHEMA = {
    "run_start": {"dir"},
    "run_end": set(),
    "stage_start": {"stage", "step"},
    "stage_end": {"stage", "step"},
    "epoch_start": {"stage", "epoch", "step"},
    "epoch_end": {"stage", "epoch", "step"},
    "step": {"step", "phases", "step_time", "throughput_ema"},
    # one amortized pipeline drain (every RMD_FINITE_CHECK_EVERY steps):
    # how long the host blocked, and the loss of the step it resolved —
    # with epoch_end's, the only loss values in the stream
    "device_sync": {"step", "seconds"},
    # a backend compile; with the counts the owning Program's trace
    # noted (``note_trace``: sw_fused_calls, sw_fallback_calls,
    # matching_volume_bytes, matching_levels_batched) when it noted any,
    # and the ``mesh`` ({axis: size}) of a step built over one
    "compile": {"label", "seconds"},
    "cache": {"event"},
    "memory": {"host_rss_gib", "live_arrays"},
    # non-finite guard: "action" says what the policy did (raise | skip |
    # rollback); skip/rollback events carry trip counts and the recent
    # sample-id window so a trip is reproducible offline
    "nonfinite": {"step"},
    "checkpoint": {"path", "step", "seconds"},
    # one evaluation/validation sweep: samples/s, per-bucket batch and
    # compile counts, pad-waste ratio (see evaluation.EvalRunStats)
    "eval": {"name", "samples", "batches", "seconds"},
    # SPMD state placement (PR 6): the mesh shape plus per-chip vs.
    # replicated byte accounting for params and optimizer state
    # (parallel.partition.Partitioner.report) — emitted once per stage
    # when the training state is placed on the mesh
    "sharding": {"mesh", "params_bytes_per_chip", "opt_bytes_per_chip"},
    # compiled-program registry (PR 7): one event per AOT artifact
    # interaction — event is save | hit | miss | fallback (plus the
    # fleet store transfers publish | fetch), with program
    # kind/model/digest and bytes/seconds where applicable. A 'fallback'
    # means an artifact existed but could not be used (corruption,
    # version mismatch, incompatible inputs): the boot paid a cold JIT
    # it expected to skip, which the report flags as an anomaly. The
    # events that hold an executable (hit | save | skip_save) carry
    # mosaic_calls, the Pallas TPU custom calls in its HLO: the kernels
    # give way to their XLA references at trace time without a word,
    # and this is how the run shows which form it got. They carry the
    # program's trace-time counts too (see "compile"): a hit reads them
    # from the artifact, so a boot that never traces still says which
    # path each sampler call took. Of a step built over a mesh they carry
    # ``mesh`` ({axis: size}) and ``collectives`` (PR 39: counts, bytes
    # (one chip's result buffers) and total_bytes by kind, from
    # analysis/collectives.parse_schedule on the same text as the owners
    # record, stored with the artifact). Each of them is followed by one
    # event='owners' (PR 37; compile/owners.py): from the same text,
    # every instruction that runs as a device operation, keyed
    # name:dtype[dims] as a capture shows it, grouped by phase of the
    # model, scope and direction, with the counts instructions,
    # inferred, unowned, the seconds text and parse took this boot and
    # source: "text", or "artifact" where a hit hands on the record the
    # saving boot stored with the executable (0.0 seconds, no text taken).
    "aot": {"event"},
    # boot configuration: the effective persistent compile-cache and AOT
    # program directories (instead of silently defaulting), plus the
    # prefetch knob — emitted once per CLI run. Where it ran (platform,
    # device_kind, device_count, backend) rides on the first event after
    # device selection: serve's boot, train's run_start.
    "boot": {"compile_cache"},
    # fault-tolerance trail (PR 5): graceful-stop request (SIGTERM/SIGINT),
    # --resume auto pickup, corrupt-checkpoint quarantine, decode-worker
    # respawn, per-sample decode failure absorbed by the loader
    # graftlint static-analysis/HLO-audit findings (PR 8): one event per
    # finding when the lint pass runs with a telemetry sink attached;
    # status is open | baselined | suppressed, severity error | warn
    "lint": {"rule", "path", "line", "status"},
    # graftcost static cost model (PR 12): one event per audited
    # program — deterministic StableHLO-walker FLOP/byte totals,
    # arithmetic intensity, compiled collective-schedule bytes, and the
    # tile-utilization verdict / hazard counts the budget gate pins
    "cost": {"program", "flops", "bytes"},
    # serving path (serve/): event is request (success, with
    # admission/queue/dispatch/device latency spans) | error (typed
    # per-request failure, kind = malformed | oversized | decode |
    # internal) | reject (admission shed, reason = queue_full |
    # shutdown) | batch (one dispatch: bucket, size, fill, compiles) |
    # warmup (one warm-pool triple: compiles, AOT hits/saves)
    "serve": {"event"},
    "preempt": {"signal", "step"},
    "resume": {"path", "step"},
    "quarantine": {"path"},
    "respawn": {"worker"},
    "bad_sample": {"index"},
    # live observability plane (PR 13): event is request (one completed
    # request with its trace id, batch linkage and exact critical-path
    # phase decomposition — phases sum to total) | batch (one dispatch
    # span: batch id, bucket/class, member trace ids, compiled-program
    # fingerprint)
    "trace": {"event"},
    # rolling per-latency-class SLO window: attainment = good/(good+bad)
    # within window_s, burn_rate = (1-attainment)/(1-objective) — burn
    # > 1 means the class is missing its objective at the current rate
    "slo": {"klass", "target_ms", "attainment", "burn_rate"},
    # trainer step-trace window (steptrace.StepTraceSummary.event):
    # per-phase rolling p50/p99 + straggler/data-starved flags, emitted
    # at the amortized finite-check cadence; also reused by evaluation
    # as a per-bucket progress heartbeat (scope="eval")
    "steptrace": {"step", "phases"},
    # wall-clock goodput breakdown (goodput.GoodputLedger.snapshot):
    # classes sum to total; emitted at stage boundaries and run end
    "goodput": {"total", "classes"},
    # flight-recorder bundle written next to the emergency checkpoint
    # on crash / nonfinite escalation / SIGTERM (blackbox.dump)
    "postmortem": {"reason", "path"},
    # streaming-video engine (PR 15): event is frame (one sequence-runner
    # frame: warm/cold start, iterations spent, EPE when ground truth is
    # known) | sequence (one finished sequence: frames, mean iterations,
    # warm-hit ratio) | products (one fw/bw pass: occlusion ratio, mean
    # confidence)
    "video": {"event"},
    # serve video-session cache (video.cache.SessionCache): event is
    # hit (warm-start state served) | miss (cold start: absent, expired,
    # or shape mismatch) | evict (capacity LRU or TTL expiry) | import
    # (a handed-off carry snapshot installed on the fleet handoff path)
    "session": {"event"},
    # serving fleet (fleet/, PR 20): event is route (one request
    # dispatched to a replica) | retry (safe-failure re-dispatch) |
    # shed (typed fleet rejection, reason = queue_full |
    # replica_unavailable) | drain (burn/liveness-triggered replica
    # drain) | handoff (one sticky session's carry moved or evicted,
    # outcome = moved | evicted) | replica_up | replica_down |
    # restart (supervisor respawn, with backoff_ms)
    "fleet": {"event"},
    # graftprof measured attribution (PR 16): one event per profiled
    # program — measured device seconds vs the roofline-predicted
    # seconds, per-op-class breakdown, the machine the calibration ran
    # on, and whether the measured/predicted ratio drifted outside its
    # pinned prof-budget.json band (the report flags drift=true rows as
    # anomalies)
    "profile": {"program", "seconds"},
    # one timeline (PR 24). Every mark in the stream (``step.marks``,
    # ``step.put``, ``trace`` events' ``marks``, ``span.t0/t1``) is a
    # ``time.perf_counter()`` reading of this process; ``clock`` pairs one
    # such reading with ``time.time_ns()`` taken back to back, so marks map
    # to Unix time and so onto a profiler capture, whose events are
    # offsets from its ``profile_start_time``. Emitted at ``activate()``,
    # every ``stage_start`` and every serve ``warmup``
    "clock": {"perf_counter", "time_ns"},
    # an interval that is neither a step nor a request: set-up (``boot``,
    # ``backend_init``, ``model_load``, ``strategy_load``, ``prepare`` and
    # its children ``data``/``state``/``step_build``) and the stall
    # witness (``gc``, ``stall``; telemetry.witness). Optional ``thread``
    "span": {"name", "t0", "t1"},
}


class UnknownKind(ValueError):
    """An event kind this reader's SCHEMA doesn't know — typically a
    file written by a newer producer. Readers that want forward compat
    catch this and skip the record; everything else treats it as the
    plain ValueError it is."""


class NewerSchema(ValueError):
    """Same major schema version, newer minor revision — the record is
    from a newer producer and safe to skip, not a corrupt line."""

_FLUSH_EVERY = 128
_EMA_ALPHA = 0.1


def validate_event(ev):
    """Check one event against the schema; raises ValueError on mismatch.

    Returns the event for chaining. This is the contract the tests and
    ``telemetry_report`` hold every producer to.
    """
    if not isinstance(ev, dict):
        raise ValueError(f"event is not an object: {ev!r}")
    v = ev.get("v")
    if v != SCHEMA_VERSION:
        if (isinstance(v, float) and not isinstance(v, bool)
                and int(v) == SCHEMA_VERSION and v > SCHEMA_VERSION):
            raise NewerSchema(
                f"newer minor schema revision {v!r}: {ev!r}")
        raise ValueError(f"unknown schema version {v!r}: {ev!r}")
    if not isinstance(ev.get("t"), (int, float)):
        raise ValueError(f"missing/invalid timestamp: {ev!r}")
    kind = ev.get("kind")
    if kind not in SCHEMA:
        raise UnknownKind(f"unknown event kind {kind!r}: {ev!r}")
    missing = SCHEMA[kind] - ev.keys()
    if missing:
        raise ValueError(f"{kind} event missing {sorted(missing)}: {ev!r}")
    if kind == "step":
        phases = ev["phases"]
        if not isinstance(phases, dict) or not all(
                isinstance(v, (int, float)) for v in phases.values()):
            raise ValueError(f"step phases must map name -> seconds: {ev!r}")
        counters = ev.get("counters", {})
        if not isinstance(counters, dict) or not all(
                isinstance(v, (int, float)) for v in counters.values()):
            raise ValueError(f"step counters must map name -> number: {ev!r}")
    if kind == "cache" and ev["event"] not in ("hit", "miss"):
        raise ValueError(f"cache event must be hit|miss: {ev!r}")
    return ev


def enabled():
    """The documented kill switch: RMD_TELEMETRY=0 disables everything."""
    from ..utils import env

    return env.get_bool("RMD_TELEMETRY")


class NullTelemetry:
    """No-op sink — the RMD_TELEMETRY=0 path and the default before
    ``activate``. Call sites never branch; they just talk to this."""

    path = None
    last_step = None
    enabled = False

    def emit(self, kind, **fields):
        pass

    def clock(self):
        pass

    def add_count(self, name, value):
        pass

    def step_event(self, step, phases=None, **fields):
        pass

    def counts(self):
        return {}

    def dropped(self):
        return 0

    def flush(self):
        pass

    def close(self):
        pass


class Telemetry:
    """JSONL event sink.

    ``path=None`` keeps events in memory only (``self.events``) — used by
    tests; a path appends JSON lines to that file.

    ``nonblocking=True`` (the serve hot path) hands disk I/O to a daemon
    writer thread behind a bounded queue (``RMD_TELEMETRY_BUFFER``): a
    slow disk can never backpressure the scheduler. On overflow the
    event is dropped and counted (:meth:`dropped`, surfaced as the
    ``rmd_telemetry_dropped_total`` metric) — losing a trace record
    under pressure is the contract; losing a request is not.

    ``RMD_TELEMETRY_MAX_MB`` > 0 rotates ``events.jsonl`` once it would
    exceed that size: the current file moves to ``<path>.1`` (replacing
    any previous rotation) and writing restarts. Default off — training
    runs keep one unbroken file.
    """

    enabled = True

    def __init__(self, path=None, nonblocking=False):
        from ..utils import env

        self.path = os.fspath(path) if path is not None else None
        self.events = []          # in-memory tail (memory-only mode: all)
        self.last_step = None
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self._buffer = []
        self._fd = None
        self._size = None
        self._max_bytes = int(env.get_float("RMD_TELEMETRY_MAX_MB") * 2 ** 20)
        self._step_counters = {}
        self._counts = {}
        self._dropped = 0
        self._last_step_t = None
        self._ema = None
        self._nonblocking = bool(nonblocking) and self.path is not None
        if self._nonblocking:
            self._capacity = max(1, env.get_int("RMD_TELEMETRY_BUFFER"))
            self._queue = collections.deque()
            self._wake = threading.Event()
            self._stopping = False
            self._writer = threading.Thread(
                target=self._writer_loop, name="telemetry-writer",
                daemon=True)
            self._writer.start()

    # -- event plumbing ----------------------------------------------------

    def emit(self, kind, **fields):
        ev = {"v": SCHEMA_VERSION, "t": time.time(), "kind": kind, **fields}
        # taps run before the sink lock so a consumer may itself emit
        # (goodput events at stage boundaries, postmortem on dump)
        _goodput.observe(kind, fields)
        _blackbox.observe(kind, fields)
        with self._lock:
            self._counts[kind] = self._counts.get(kind, 0) + 1
            if kind == "compile":
                # label-qualified count: lets consumers (eval compile
                # accounting) separate the instrumented program they care
                # about from incidental eager-op compiles
                k = f"compile:{fields.get('label')}"
                self._counts[k] = self._counts.get(k, 0) + 1
            if self.path is None:
                self.events.append(ev)
                return ev
            if self._nonblocking:
                if len(self._queue) >= self._capacity:
                    self._dropped += 1
                else:
                    self._queue.append(ev)
                    self._wake.set()
                return ev
            self._buffer.append(ev)
            if (len(self._buffer) >= _FLUSH_EVERY
                    or kind not in ("step", "device_sync", "compile", "cache",
                                    "steptrace")):
                self._flush_locked()
        return ev

    def _flush_locked(self):
        if not self._buffer:
            return
        batch, self._buffer = self._buffer, []
        self._write_batch(batch)

    def _write_batch(self, batch):
        with self._io_lock:
            if self._fd is None:
                self._fd = open(self.path, "a")
                self._size = os.path.getsize(self.path)
            data = "".join(json.dumps(ev) + "\n" for ev in batch)
            if (self._max_bytes > 0 and self._size > 0
                    and self._size + len(data) > self._max_bytes):
                self._fd.close()
                os.replace(self.path, self.path + ".1")
                self._fd = open(self.path, "a")
                self._size = 0
            self._fd.write(data)
            self._fd.flush()
            self._size += len(data)

    def _writer_loop(self):
        while True:
            self._wake.wait(0.2)
            self._wake.clear()
            self._drain()
            with self._lock:
                if self._stopping and not self._queue:
                    return

    def _drain(self):
        with self._lock:
            if not self._queue:
                return
            batch = list(self._queue)
            self._queue.clear()
        self._write_batch(batch)

    def flush(self):
        if self._nonblocking:
            self._drain()
            return
        with self._lock:
            if self.path is not None:
                self._flush_locked()

    def close(self):
        if self._nonblocking:
            with self._lock:
                self._stopping = True
            self._wake.set()
            self._writer.join(timeout=5.0)
            self._drain()
            with self._io_lock:
                if self._fd is not None:
                    self._fd.close()
                    self._fd = None
            return
        with self._lock:
            if self.path is not None:
                self._flush_locked()
        with self._io_lock:
            if self._fd is not None:
                self._fd.close()
                self._fd = None

    def counts(self):
        """Event counts by kind (cheap snapshot)."""
        with self._lock:
            return dict(self._counts)

    def dropped(self):
        """Events shed by the bounded non-blocking buffer (0 in the
        default blocking mode)."""
        with self._lock:
            return self._dropped

    # -- clock / steps -----------------------------------------------------

    def clock(self):
        """One ``clock`` event: ``perf_counter`` and Unix ns, back to back."""
        return self.emit("clock", perf_counter=time.perf_counter(),
                         time_ns=time.time_ns())

    def add_count(self, name, value):
        """Per-step scalar counter (e.g. ``wire_bytes``, the host→device
        transfer volume): accumulates and drains into the next ``step``
        event under ``counters``."""
        with self._lock:
            self._step_counters[name] = self._step_counters.get(name, 0) + value

    def step_event(self, step, phases=None, **fields):
        """Close out one optimizer step: drain the counters, update the
        throughput EMA, emit the ``step`` record. ``phases`` are the
        caller's, computed from the step's marks (``steptrace``); the
        marks themselves ride in ``fields`` (``marks``, ``put``, ``pull``),
        with the host's two readings (``fetch``, ``cpu``: see
        ``steptrace``)."""
        now = time.perf_counter()
        phases = dict(phases or {})
        with self._lock:
            counters = self._step_counters
            self._step_counters = {}
        if self._last_step_t is None:
            step_time = sum(phases.values())
        else:
            step_time = now - self._last_step_t
        self._last_step_t = now

        inst = 1.0 / step_time if step_time > 0 else 0.0
        self._ema = (inst if self._ema is None
                     else _EMA_ALPHA * inst + (1 - _EMA_ALPHA) * self._ema)

        if counters:
            fields = dict(fields, counters=counters)
        ev = self.emit(
            "step", step=step,
            phases={k: round(v, 6) for k, v in phases.items()},
            step_time=round(step_time, 6),
            throughput_ema=round(self._ema, 4),
            **fields,
        )
        self.last_step = ev
        return ev


# -- process-wide active sink + jax.monitoring forwarding -------------------

_active = NullTelemetry()
_listeners_installed = False
_jit_label = threading.local()


def get():
    """The process's active sink (NullTelemetry unless activated)."""
    return _active


def activate(sink):
    """Install ``sink`` as the process-wide telemetry target and hook the
    jax.monitoring compile/cache events into it. An enabled sink also gets
    a ``clock`` event, the spans taken before any sink existed (``boot``
    first), and the stall witness. Returns the sink."""
    global _active, _hold_early
    now = time.perf_counter()
    _active = sink
    early, _hold_early = list(_early), False
    _early.clear()
    if sink.enabled:
        _install_listeners()
        sink.clock()
        for fields in early:
            sink.emit("span", **fields)
        _emit_boot(now)
        witness.start()
    return sink


def deactivate():
    """Swap back to the null sink (closing the old one)."""
    global _active
    witness.stop()
    old, _active = _active, NullTelemetry()
    old.close()
    return old


# -- spans: intervals that are neither a step nor a request -----------------

# spans taken before the process's first ``activate()`` (the device
# selection, a model loaded by a caller that activates later) wait here
# and are delivered by it; afterwards a span without an enabled sink is
# dropped like any other event
_early = collections.deque(maxlen=64)
_hold_early = True
_boot_done = False


def process_start():
    """This process's start on the ``perf_counter`` clock (from
    ``/proc/self/stat`` and the boot time of ``/proc/stat``), or None."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
    except (OSError, ValueError, IndexError, StopIteration):
        return None
    age = time.time() - (btime + ticks / os.sysconf("SC_CLK_TCK"))
    return time.perf_counter() - max(0.0, age)


def emit_span(name, t0, t1, **fields):
    """One ``span`` event: ``[t0, t1]`` on ``perf_counter``."""
    _emit_boot(t0)
    fields = dict(fields, name=name, t0=round(t0, 6), t1=round(t1, 6))
    if _active.enabled:
        _active.emit("span", **fields)
    elif _hold_early:
        _early.append(fields)


def _emit_boot(until):
    """Once per process, the span ``boot``: process start to the first
    thing the program marks (its first span's start or its first
    ``activate()``): interpreter start-up and imports. It carries ``cpus``,
    the CPUs this process may run on: what a step's CPU-seconds are a share
    of."""
    global _boot_done
    if _boot_done:
        return
    _boot_done = True
    start = process_start()
    if start is not None:
        emit_span("boot", start, until, cpus=len(os.sched_getaffinity(0)))


@contextlib.contextmanager
def interval(name, **fields):
    """Emit the ``span`` ``name`` around the block."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        emit_span(name, t0, time.perf_counter(), **fields)


def create(path=None, nonblocking=False):
    """Factory honoring the kill switch: a real sink, or the null one.

    ``nonblocking=True`` is the serve-path variant: disk writes move to
    a bounded background writer so ``emit`` never blocks the scheduler.
    """
    return Telemetry(path, nonblocking=nonblocking) if enabled() \
        else NullTelemetry()


@contextlib.contextmanager
def jit_label(label, program=None):
    """Scope the compile-attribution label (and, optionally, the owning
    registry Program whose per-program counters the monitoring listener
    increments) around a jitted call."""
    prev = getattr(_jit_label, "value", None)
    prev_prog = getattr(_jit_label, "program", None)
    _jit_label.value = label
    _jit_label.program = program
    try:
        yield
    finally:
        _jit_label.value = prev
        _jit_label.program = prev_prog


def instrument_jit(label, fn):
    """Label a jitted callable so compiles triggered inside it are
    attributed to ``label`` in compile events. Pure passthrough wrapper —
    donation/sharding semantics of ``fn`` are untouched."""

    def wrapped(*args, **kwargs):
        with jit_label(label):
            return fn(*args, **kwargs)

    wrapped.__wrapped__ = fn
    wrapped.telemetry_label = label
    if hasattr(fn, "lower"):
        # forward the AOT entry point so instrumented step builders stay
        # lowerable (tests lower every model id; compile events from an
        # explicit .lower().compile() are attributed to the bare 'jit')
        wrapped.lower = fn.lower
    return wrapped


# -- trace-time counts --------------------------------------------------------

_trace_sites = threading.local()


@contextlib.contextmanager
def trace_site(label, repeat=1):
    """Name a part of the program being traced that runs ``repeat`` times
    an execution (a scan's body: ``repeat`` is its length). The tracer
    may visit such a body more than once (flax's lifted scan traces it
    twice), so a count noted inside a site is kept once per site and
    name, whatever the number of visits, and multiplied by ``repeat``:
    what one execution of the program does, not what its tracing did."""
    stack = getattr(_trace_sites, "stack", ())
    _trace_sites.stack = stack + ((str(label), int(repeat)),)
    try:
        yield
    finally:
        _trace_sites.stack = stack


def note_trace(name, value, scale=True):
    """A count known while a program traces (which path a kernel's
    dispatch took, the bytes a shape makes a layer move). It belongs to
    the registry Program whose trace is running (the ``jit_label``
    scope), which carries it in its ``compile`` and ``aot`` events,
    stores it with the executable and hands it to the next ``step``
    event's counters on every boot, traced or loaded. Outside a
    Program's trace (``model.init``, an eager apply) the count describes
    no program and is dropped. ``scale=False`` notes a property of the
    traced part (how many levels one evaluation covers) and not a count
    of what it does: the enclosing sites' repeats leave it as it is."""
    program = getattr(_jit_label, "program", None)
    if program is not None:
        site = getattr(_trace_sites, "stack", ())
        if not scale:
            site = tuple((label, 1) for label, _ in site)
        program.note_trace(name, value, site)


def install_listeners():
    """Register the process-wide jax.monitoring forwarders (idempotent).

    jax emits '/jax/compilation_cache/cache_{hits,misses}' per
    persistent-cache lookup and '/jax/core/compile/backend_compile_duration'
    around ``compile_or_get_cached`` — i.e. around the lookup *and* the
    backend compile, so the duration also fires when the executable came
    out of the persistent cache. A duration that follows a cache hit on
    the same thread is that retrieval, not a compile: it is dropped, so
    that ``compile`` events and ``Program.compiles`` mean "a backend
    compile ran". Everything forwards to whatever sink is active at fire
    time, labeled by the innermost ``jit_label`` scope. Compile durations
    also increment the scoped registry Program's counters — those count
    even with the sink disabled, so eval/warmup compile accounting never
    falls back to guessing (the pre-PR-7 overcount).
    """
    global _listeners_installed
    if _listeners_installed:
        return
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover - jax always present in practice
        return

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            _jit_label.cache_hit = True
            if _active.enabled:
                _active.emit("cache", event="hit",
                             label=getattr(_jit_label, "value", None))
        elif event == "/jax/compilation_cache/cache_misses":
            if _active.enabled:
                _active.emit("cache", event="miss",
                             label=getattr(_jit_label, "value", None))

    def on_duration(event, duration, **kwargs):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if getattr(_jit_label, "cache_hit", False):
            _jit_label.cache_hit = False
            return
        program = getattr(_jit_label, "program", None)
        if program is not None:
            program.record_compile(float(duration))
        if not _active.enabled:
            return
        counts = program.trace_counts() if program is not None else {}
        if getattr(program, "mesh_axes", None):
            # a partitioned step says over what (its collectives need the
            # compiled text: on the ``aot`` event that holds the executable)
            counts["mesh"] = program.mesh_axes
        _active.emit("compile",
                     label=getattr(_jit_label, "value", None) or "jit",
                     seconds=round(float(duration), 6), **counts)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _listeners_installed = True


# backwards-compatible internal name
_install_listeners = install_listeners


def memory_snapshot():
    """Host RSS + live jax arrays + device peak bytes (where exposed).

    Cheap enough to take at every epoch boundary.
    """
    rss = 0.0
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss = int(line.split()[1]) / 2 ** 20
                    break
    except OSError:  # pragma: no cover - non-procfs platforms
        pass

    snap = {"host_rss_gib": round(rss, 3), "live_arrays": 0}
    try:
        import jax

        snap["live_arrays"] = len(jax.live_arrays())
        # the fullest device: what decides whether the step fits. On the
        # TPU runtime the allocator's *_in_use counts buffers only; a
        # loaded program's temporaries are held as *_reserved (measured
        # on a v5e: a step with 512 MiB of temporaries moved
        # peak_bytes_reserved by exactly that and peak_bytes_in_use not
        # at all), so the two add up to what the device had to hold
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        for name, used, reserved in (
                ("device_peak_gib", "peak_bytes_in_use",
                 "peak_bytes_reserved"),
                ("device_bytes_gib", "bytes_in_use", "bytes_reserved")):
            values = [s[used] + s.get(reserved, 0)
                      for s in stats if used in s]
            if values:
                snap[name] = round(max(values) / 2 ** 30, 3)
    except Exception:  # noqa: BLE001 - telemetry must never break the run
        pass
    return snap
