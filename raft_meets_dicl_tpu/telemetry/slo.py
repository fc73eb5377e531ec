"""Per-latency-class SLO tracking: rolling burn-rate windows.

Each ladder class gets a target (``RMD_SLO_FAST_MS`` /
``RMD_SLO_BALANCED_MS`` / ``RMD_SLO_QUALITY_MS``; ladderless requests
and classes without their own knob fall back to ``RMD_SLO_DEFAULT_MS``;
0 disables tracking for that class). Within a rolling window
(``RMD_SLO_WINDOW_S``) each completed request is *good* iff its
end-to-end latency met the target; the standard SRE pair follows:

- ``attainment = good / (good + bad)``
- ``burn_rate = (1 - attainment) / (1 - objective)``

with ``objective`` from ``RMD_SLO_OBJECTIVE`` (default 0.99). Burn 1.0
means the class is consuming its error budget exactly at the sustainable
rate; >1 means the window misses the objective — the telemetry report
flags it, and pairs it with the trace summary's tail decomposition so a
burning class is immediately attributable to queue vs. batch-formation
vs. device time.

Snapshots feed the ``rmd_slo_*`` gauges and periodic ``slo`` events;
everything is host-side arithmetic on a deque.
"""

import threading
import time
from collections import deque


class ClassSLO:
    """Rolling good/bad window for one latency class."""

    def __init__(self, klass, target_ms, objective=0.99, window_s=60.0,
                 model=""):
        if target_ms <= 0:
            raise ValueError(f"target_ms must be > 0, got {target_ms}")
        if not 0.0 < objective < 1.0:
            raise ValueError(f"objective must be in (0, 1), got {objective}")
        self.klass = klass
        self.model = model
        self.target_ms = float(target_ms)
        self.objective = float(objective)
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._window = deque()  # (monotonic stamp, good)

    def record(self, total_s, now=None):
        """One completed request with end-to-end latency ``total_s``."""
        now = time.monotonic() if now is None else now
        good = total_s * 1e3 <= self.target_ms
        with self._lock:
            self._window.append((now, good))
            self._prune(now)
        return good

    def _prune(self, now):
        horizon = now - self.window_s
        while self._window and self._window[0][0] < horizon:
            self._window.popleft()

    def snapshot(self, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            self._prune(now)
            good = sum(1 for _, g in self._window if g)
            total = len(self._window)
        bad = total - good
        attainment = good / total if total else 1.0
        burn = (1.0 - attainment) / (1.0 - self.objective)
        return {
            "klass": self.klass,
            "model": self.model,
            "target_ms": self.target_ms,
            "objective": self.objective,
            "window_s": self.window_s,
            "good": good,
            "bad": bad,
            "attainment": round(attainment, 6),
            "burn_rate": round(burn, 4),
        }


def targets():
    """Configured per-class targets (ms) from the knob registry; classes
    at 0 are untracked. The empty-string class is the ladderless
    default and the fallback for classes without their own knob."""
    from ..utils import env

    return {
        "fast": env.get_float("RMD_SLO_FAST_MS"),
        "balanced": env.get_float("RMD_SLO_BALANCED_MS"),
        "quality": env.get_float("RMD_SLO_QUALITY_MS"),
        "": env.get_float("RMD_SLO_DEFAULT_MS"),
    }


class SLOTracker:
    """Per-class :class:`ClassSLO` map fed from the serve release path.

    Unconfigured classes are ignored (no target — nothing to burn).
    ``maybe_emit`` rate-limits ``slo`` events to one per class per
    ``emit_interval_s``. A server of several models (``by_model``) keeps
    a window per model and class, keyed ``model:klass`` and opened by the
    model's first request; a one-model server's are keyed by class as
    ever, and say their model in the snapshot.
    """

    def __init__(self, class_targets=None, objective=None, window_s=None,
                 emit_interval_s=None, by_model=False):
        from ..utils import env

        if class_targets is None:
            class_targets = targets()
        if objective is None:
            objective = env.get_float("RMD_SLO_OBJECTIVE")
        if window_s is None:
            window_s = env.get_float("RMD_SLO_WINDOW_S")
        if emit_interval_s is None:
            emit_interval_s = max(1.0, window_s / 6.0)
        self.emit_interval_s = float(emit_interval_s)
        default = class_targets.get("", 0.0)
        self.by_model = bool(by_model)
        self._slo_args = dict(objective=objective, window_s=window_s)
        self._targets = {klass: target or default
                         for klass, target in class_targets.items()
                         if (target or default) > 0}
        self._slos = {}
        if not self.by_model:
            for klass, target in self._targets.items():
                self._slos[klass] = ClassSLO(klass, target, **self._slo_args)
        self._lock = threading.Lock()
        self._last_emit = {}

    def __bool__(self):
        return bool(self._targets)

    def classes(self):
        return sorted(self._slos)

    def record(self, klass, total_s, now=None, model=""):
        if klass not in self._targets:
            return None
        key = f"{model}:{klass}" if self.by_model else klass
        slo = self._slos.get(key)
        if slo is None:     # a model's first request opens its window
            with self._lock:
                slo = self._slos.setdefault(key, ClassSLO(
                    klass, self._targets[klass], **self._slo_args))
        slo.model = model
        return slo.record(total_s, now=now)

    def snapshot(self, now=None):
        return {k: s.snapshot(now=now)
                for k, s in sorted(self._slos.items())}

    def maybe_emit(self, sink, now=None):
        """Emit one ``slo`` event per class whose interval elapsed."""
        now = time.monotonic() if now is None else now
        emitted = []
        for klass, slo in list(self._slos.items()):
            with self._lock:
                last = self._last_emit.get(klass)
                if last is not None and now - last < self.emit_interval_s:
                    continue
                self._last_emit[klass] = now
            snap = slo.snapshot(now=now)
            sink.emit("slo", **snap)
            emitted.append(snap)
        return emitted
