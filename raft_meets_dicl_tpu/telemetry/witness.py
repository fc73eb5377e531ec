"""A witness for stalls: which pause was a garbage collection, and which
was the whole process not running.

Two observers, started by ``telemetry.activate`` only for an enabled sink
and stopped by ``deactivate``; neither costs anything per step or request:

- a ``gc.callbacks`` hook notes every collection longer than ``GC_MIN_S``
  for a ``span`` named ``gc`` (``generation``, ``collected``). It only
  notes: a collection can start inside the sink's own locked region (any
  allocation there may trigger one), and an emit from the callback would
  then wait for the lock its own thread holds. The ticker emits the note
  on its next tick;
- a daemon ticker sleeps ``TICK_S`` at a time and emits a ``span`` named
  ``stall`` when it wakes more than ``LATE_S`` after it should have. A
  thread that only sleeps is late when nobody let it run: the GIL was
  held throughout, or the process was not scheduled. So a late tick
  inside one mark interval of the loop or dispatch thread says "nobody
  ran"; a long interval with the ticker on time says "that one thread
  was blocked". The span says which of the two it was: ``cpu_s`` is the
  CPU time the whole process used between going to sleep and waking
  (about the stall's length or more: threads were running, one of them
  holding the GIL; near zero: the process did not run), ``majflt`` the
  major page faults and ``nivcsw`` the involuntary context switches in
  between.

Both emit to whatever sink is active when they fire.
"""

import collections
import gc
import resource
import threading
import time

GC_MIN_S = 1e-3
TICK_S = 0.02
LATE_S = 0.05

_lock = threading.Lock()
_ticker = None          # (thread, stop event) while running
_gc_t0 = None
_gc_noted = collections.deque(maxlen=1024)   # (t0, t1, info) to emit


def _emit(name, t0, t1, **fields):
    from . import core      # core imports this module

    core.emit_span(name, t0, t1, **fields)


def _on_gc(phase, info):
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        t0, t1, _gc_t0 = _gc_t0, time.perf_counter(), None
        if t1 - t0 >= GC_MIN_S:
            _gc_noted.append((t0, t1, info.get("generation"),
                              info.get("collected")))


def _emit_noted():
    while _gc_noted:
        t0, t1, generation, collected = _gc_noted.popleft()
        _emit("gc", t0, t1, generation=generation, collected=collected)


def late_span(slept_at, woke_at, tick=TICK_S, late=LATE_S):
    """The ``(t0, t1)`` of a stall, or None: a tick that went to sleep at
    ``slept_at`` for ``tick`` seconds was due at ``slept_at + tick``; it is
    a stall when it woke more than ``late`` seconds after that."""
    due = slept_at + tick
    return (due, woke_at) if woke_at - due > late else None


def _usage():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime, r.ru_majflt, r.ru_nivcsw


def _tick(stop, clock=time.perf_counter):
    while True:
        slept_at, before = clock(), _usage()
        if stop.wait(TICK_S):
            return
        span = late_span(slept_at, clock())
        if span is not None:
            cpu, majflt, nivcsw = (b - a for a, b in zip(before, _usage()))
            _emit("stall", span[0], span[1], thread="witness-ticker",
                  cpu_s=round(cpu, 4), majflt=majflt, nivcsw=nivcsw)
        _emit_noted()


def start():
    """Idempotent: one hook and one ticker per process."""
    global _ticker
    with _lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        if _ticker is None:
            stop = threading.Event()
            thread = threading.Thread(target=_tick, args=(stop,),
                                      name="witness-ticker", daemon=True)
            _ticker = (thread, stop)
            thread.start()


def stop():
    global _ticker, _gc_t0
    with _lock:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        _gc_t0 = None
        ticker, _ticker = _ticker, None
    if ticker is not None:
        ticker[1].set()
        ticker[0].join(timeout=1.0)
    _emit_noted()       # what the last tick did not see, to the old sink


def running():
    """``(gc hook registered, ticker alive)``."""
    with _lock:
        return (_on_gc in gc.callbacks,
                _ticker is not None and _ticker[0].is_alive())
