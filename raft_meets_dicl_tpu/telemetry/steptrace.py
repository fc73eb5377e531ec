"""Per-step trace decomposition for the training loop.

The serve plane decomposes a request's critical path with
:mod:`telemetry.trace`; this is the trainer's twin.  A
:class:`StepTrace` records one ``time.perf_counter`` timestamp per mark
on the step loop's own thread::

    start -> data -> prep -> put -> dispatched -> synced -> done

and the phases are the differences between consecutive hit marks on
that one clock, so they telescope *exactly* to the step total — no
residual, no second clock, and crucially **no host↔device sync**: the
``device`` phase is simply how long the loop blocked on the amortized
finite-check fetch (zero on the steps in between, where ``synced``
lands immediately after ``dispatched``). The marks are the record: the
``step`` event carries them absolute (``marks``), next to ``put``, the
``[t0, t1]`` of the wire encode + ``device_put`` of the batch this step
consumed, and ``pull``, the ``[t0, t1]`` of the ``next()`` that handed
the prefetch worker that batch (its wait for the loader, before the
``put``), so a step can be laid on a timeline beside a device trace.
Two more readings ride along: ``fetch``, the mean wall seconds a loader
worker spent on one sample of that batch, and ``cpu``, the process's
``time.process_time()`` at ``start``; between two steps' ``start`` marks
the difference is the CPU-seconds every thread of the process burnt.

========== ============================================================
phase      wall time between
========== ============================================================
data_wait  start → data: blocked on the (prefetched) input queue
host_prep  data → prep: host-side batch prep, schedules, callbacks
device_put prep → put: consumer-side transfer cost (≈0: the batch was
           staged before the pull returned; its cost is ``put``)
dispatch   put → dispatched: the async ``step_fn`` dispatch call
device     dispatched → synced: blocked on the finite-check fetch
           (only at the amortized cadence)
interleave synced → done: optimizer/ckpt/eval interleave + inspector
========== ============================================================

:class:`StepTraceSummary` aggregates the bounded recent window (rolling
p50/p99 per phase, straggler/data-starved flags) and builds the
``steptrace`` telemetry events the loop emits at the finite-check
cadence.
"""

import time
from collections import deque

MARKS = ("start", "data", "prep", "put", "dispatched", "synced", "done")
PHASES = ("data_wait", "host_prep", "device_put", "dispatch", "device",
          "interleave")

# a step is a straggler when its total exceeds this multiple of the
# window median; the window is data-starved when the median data_wait
# share of the step exceeds this fraction
STRAGGLER_FACTOR = 2.0
STARVED_SHARE = 0.5


class StepTrace:
    """Timestamps of one training step on a single perf_counter clock."""

    __slots__ = ("step", "marks", "put", "pull", "fetch", "cpu")

    def __init__(self, step=None):
        self.step = step
        self.marks = {}
        self.put = None     # (t0, t1) of this batch's put, same clock
        self.pull = None    # (t0, t1) of the pull that produced the batch
        self.fetch = None   # mean seconds a worker spent on one sample
        self.cpu = None     # process_time() at ``start``

    def mark(self, name, t=None):
        if name not in MARKS:
            raise ValueError(f"unknown step mark {name!r}")
        self.marks[name] = time.perf_counter() if t is None else float(t)
        if name == "start":
            self.cpu = time.process_time()
        return self

    def total(self):
        if "start" in self.marks and "done" in self.marks:
            return self.marks["done"] - self.marks["start"]
        return None

    def phases(self):
        """Phase durations between consecutive *hit* marks.

        Differences of one clock at consecutive marks: the phases sum
        to ``total()`` with no residual.  A phase spanning skipped
        marks is attributed to the phase named by its left mark, so
        attribution always covers the whole step.
        """
        hit = [m for m in MARKS if m in self.marks]
        out = {}
        for m0, m1 in zip(hit, hit[1:]):
            t0, t1 = self.marks[m0], self.marks[m1]
            out[PHASES[MARKS.index(m0)]] = t1 - t0
        return out

    def step_phases(self):
        """The phases as the ``step`` event reports them: ``device_put``
        is the batch's ``put`` (wire encode + transfer initiation). On the
        prefetch worker's thread it lies outside the step, the one phase
        that is not part of the telescoping sum."""
        out = self.phases()
        if self.put is not None:
            p0, p1 = self.put
            out["device_put"] = out.get("device_put", 0.0) + (p1 - p0)
        return out

    def record(self):
        phases = self.phases()
        rec = {
            "step": self.step,
            "phases": {k: round(v, 6) for k, v in phases.items()},
            "total": round(self.total() or sum(phases.values()), 6),
            "marks": {m: round(self.marks[m], 6)
                      for m in MARKS if m in self.marks},
        }
        if self.put is not None:
            rec["put"] = [round(self.put[0], 6), round(self.put[1], 6)]
        if self.pull is not None:
            rec["pull"] = [round(self.pull[0], 6), round(self.pull[1], 6)]
        if self.fetch is not None:
            rec["fetch"] = round(self.fetch, 6)
        if self.cpu is not None:
            rec["cpu"] = round(self.cpu, 6)
        return rec


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = q * (len(sorted_vals) - 1)
    lo = int(idx)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = idx - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


class StepTraceSummary:
    """Bounded rolling window of step records + the pending batch that
    has not yet been emitted as a ``steptrace`` event.

    ``add`` is append-only host work (no sync); the loop drains the
    pending batch into one event per finite-check window.
    """

    def __init__(self, capacity=512, straggler_factor=STRAGGLER_FACTOR,
                 starved_share=STARVED_SHARE):
        self.capacity = int(capacity)
        self.straggler_factor = float(straggler_factor)
        self.starved_share = float(starved_share)
        self._records = deque(maxlen=self.capacity)
        self._pending = []
        self.steps = 0

    def add(self, trace):
        rec = trace.record() if isinstance(trace, StepTrace) else dict(trace)
        self._records.append(rec)
        self._pending.append(rec)
        self.steps += 1
        return rec

    def __len__(self):
        return len(self._records)

    # -- aggregation ---------------------------------------------------------

    def snapshot(self):
        """Rolling per-phase p50/p99 (ms) over the bounded window, plus
        straggler / data-starved flags."""
        records = list(self._records)
        if not records:
            return {"count": 0, "phases": {}, "total_ms": {},
                    "straggler": False, "data_starved": False}
        by_phase = {}
        totals = []
        starved = []
        for rec in records:
            totals.append(rec["total"])
            for phase, dur in rec["phases"].items():
                by_phase.setdefault(phase, []).append(dur)
            if rec["total"] > 0:
                starved.append(rec["phases"].get("data_wait", 0.0)
                               / rec["total"])
        totals.sort()
        phases = {}
        for phase, vals in by_phase.items():
            vals.sort()
            phases[phase] = {
                "p50_ms": round(_percentile(vals, 0.50) * 1e3, 3),
                "p99_ms": round(_percentile(vals, 0.99) * 1e3, 3),
            }
        median_total = _percentile(totals, 0.50)
        last_total = records[-1]["total"]
        starved.sort()
        return {
            "count": len(records),
            "phases": phases,
            "total_ms": {
                "p50": round(median_total * 1e3, 3),
                "p99": round(_percentile(totals, 0.99) * 1e3, 3),
            },
            "straggler": bool(median_total > 0 and last_total
                              > self.straggler_factor * median_total),
            "data_starved": bool(starved and _percentile(
                starved, 0.50) > self.starved_share),
        }

    def drain(self):
        """Pending records since the last drain (the emit window)."""
        pending, self._pending = self._pending, []
        return pending

    def event(self, step):
        """Build the ``steptrace`` event fields for the window since the
        last emit; drains the pending batch. Returns None when the
        window is empty."""
        window = self.drain()
        if not window:
            return None
        snap = self.snapshot()
        return {
            "step": step,
            "window": len(window),
            "phases": snap["phases"],
            "total_ms": snap["total_ms"],
            "straggler": snap["straggler"],
            "data_starved": snap["data_starved"],
        }
