"""Per-request tracing for the serving path.

Every admitted request carries a :class:`RequestTrace` from admission to
release; every dispatch gets a :class:`BatchTrace` linking the batch
span to its member request spans (and to the compiled program that ran
it). A request's marks telescope into an **exact** critical-path
decomposition:

====================  ===================================================
phase                 interval (request marks)
====================  ===================================================
``admission``         submit → enqueue (validate + quantize + wire-encode)
``queue``             enqueue → dispatch (batcher lane wait)
``batch_form``        dispatch → launched. ``launched`` is the batch's
                      ``ready`` mark: the session's run call has returned
                      *and* blocked until the result was ready on the
                      device. So this phase holds fan-in (decode faults
                      culled, pad-tile assemble, ladder pick), the
                      program call and the device's execution
``device``            launched → fetched: the result's device-to-host
                      fetch only (the execution is in ``batch_form``)
``respond``           fetched → released (crop + sticky-order release)
====================  ===================================================

The phases are differences of one monotonic clock at consecutive marks,
so ``sum(phases) == total`` to float precision — a tail request always
attributes its full latency, nothing hides between phases. The dispatch
thread's own marks are the batch's (``BATCH_MARKS``):

``wait`` (the loop went back to ``batcher.take``) → ``dispatch`` (a batch
was pulled) → ``assembled`` → ``called`` (the program call returned:
inputs handed over, execution enqueued) → ``ready``
(``block_until_ready`` returned) → ``fetched`` → ``completed`` (the last
member's ticket was released).

Both events carry their marks absolute (``perf_counter``), so batches
and requests can be laid on a timeline beside a device trace (the
``clock`` event maps the clock to Unix time). Completed requests feed a
bounded :class:`TraceSummary` whose :meth:`snapshot` gives per-class
p50/p99 and the slowest-decile phase breakdown the ``/statusz`` endpoint
serves live.

Host-side only: one ``perf_counter`` call per mark, no jax.
"""

import itertools
import threading
import time
from collections import deque

# mark order defines the telescoping phase decomposition
MARKS = ("submit", "enqueue", "dispatch", "launched", "fetched", "released")
PHASES = ("admission", "queue", "batch_form", "device", "respond")
# the dispatch thread's marks for one batch, in order
BATCH_MARKS = ("wait", "dispatch", "assembled", "called", "ready", "fetched",
               "completed")
# the latency spans a FlowResult and the ``serve``/``request`` event carry,
# as (name, from mark, to mark): the same marks under their older names
SPANS = (("admission", "submit", "enqueue"), ("queue", "enqueue", "dispatch"),
         ("dispatch", "dispatch", "launched"),
         ("device", "launched", "fetched"), ("total", "submit", "released"))

_req_ids = itertools.count(1)
_batch_ids = itertools.count(1)


class RequestTrace:
    """Ordered monotonic marks for one request's life; phases are the
    gaps between consecutive marks actually hit."""

    __slots__ = ("trace_id", "klass", "bucket", "model", "batch_id",
                 "marks")

    def __init__(self, klass="", bucket=None, model=""):
        self.trace_id = f"req-{next(_req_ids):06d}"
        self.klass = klass
        self.bucket = bucket
        self.model = model      # id of the model that answers the request
        self.batch_id = None
        self.marks = {}

    def mark(self, name, t=None):
        if name not in MARKS:
            raise ValueError(f"unknown trace mark {name!r} "
                             f"(one of {'/'.join(MARKS)})")
        self.marks[name] = time.perf_counter() if t is None else t
        return self

    def phases(self):
        """``{phase: seconds}`` between consecutive hit marks. With all
        marks present the values telescope: they sum to exactly
        ``released - submit``."""
        out = {}
        hit = [(m, self.marks[m]) for m in MARKS if m in self.marks]
        for (m0, t0), (_m1, t1) in zip(hit, hit[1:]):
            out[PHASES[MARKS.index(m0)]] = t1 - t0
        return out

    def total(self):
        if "submit" in self.marks and "released" in self.marks:
            return self.marks["released"] - self.marks["submit"]
        return None

    def spans(self):
        """``SPANS`` between the marks hit so far: what ``FlowResult.spans``
        and the ``serve``/``request`` event report."""
        return {name: self.marks[m1] - self.marks[m0]
                for name, m0, m1 in SPANS
                if m0 in self.marks and m1 in self.marks}

    def record(self):
        """The completed-request record ``slo``/``TraceSummary``/the
        ``trace`` event all share."""
        phases = self.phases()
        return {
            "trace": self.trace_id,
            "batch": self.batch_id,
            "model": self.model,
            "klass": self.klass,
            "bucket": (f"{self.bucket[0]}x{self.bucket[1]}"
                       if self.bucket else None),
            "phases": {k: round(v, 6) for k, v in phases.items()},
            "total": round(self.total() or sum(phases.values()), 6),
            "marks": {m: round(self.marks[m], 6)
                      for m in MARKS if m in self.marks},
        }


class BatchTrace:
    """One dispatch span: which requests fanned in, on which model's
    compiled program (bucket/class/fingerprint), and the dispatch thread's marks
    (``BATCH_MARKS``) from going back to wait to the last release."""

    __slots__ = ("batch_id", "bucket", "klass", "model", "size", "fill",
                 "program", "members", "marks")

    def __init__(self, bucket, klass, program=None, model=""):
        self.batch_id = f"batch-{next(_batch_ids):06d}"
        self.bucket = bucket
        self.klass = klass
        self.model = model
        self.program = program
        self.size = 0
        self.fill = 0
        self.members = []
        self.marks = {}

    def mark(self, name, t=None):
        if name not in BATCH_MARKS:
            raise ValueError(f"unknown batch mark {name!r} "
                             f"(one of {'/'.join(BATCH_MARKS)})")
        self.marks[name] = time.perf_counter() if t is None else t
        return self.marks[name]

    def link(self, request_trace):
        request_trace.batch_id = self.batch_id
        self.members.append(request_trace.trace_id)
        self.size = len(self.members)
        return request_trace

    def record(self):
        hit = [self.marks[m] for m in BATCH_MARKS[1:] if m in self.marks]
        return {
            "batch": self.batch_id,
            "model": self.model,
            "bucket": f"{self.bucket[0]}x{self.bucket[1]}",
            "klass": self.klass,
            "size": self.size,
            "fill": self.fill,
            "program": self.program,
            "members": list(self.members),
            # dispatch to the last mark hit
            "seconds": round(hit[-1] - hit[0], 6) if hit else 0.0,
            "marks": {m: round(self.marks[m], 6)
                      for m in BATCH_MARKS if m in self.marks},
        }


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class TraceSummary:
    """Bounded live aggregate of completed request records.

    Keeps the last ``capacity`` records (deque — the serve hot path adds
    one dict append per request) and answers :meth:`snapshot`: per-class
    count/p50/p99 plus the slowest-decile phase breakdown with the
    dominant phase named, so a queue-dominated tail is visible at a
    glance (``/statusz``, the obs smoke test).
    """

    def __init__(self, capacity=4096):
        self._lock = threading.Lock()
        self._records = deque(maxlen=capacity)

    def add(self, record):
        with self._lock:
            self._records.append(record)

    def __len__(self):
        with self._lock:
            return len(self._records)

    def snapshot(self):
        with self._lock:
            records = list(self._records)
        classes = {}
        for rec in records:
            classes.setdefault(rec.get("klass") or "", []).append(
                rec["total"])
        out = {"count": len(records), "classes": {}, "tail": None}
        for klass, totals in sorted(classes.items()):
            totals.sort()
            out["classes"][klass] = {
                "count": len(totals),
                "p50_ms": round(_percentile(totals, 0.50) * 1e3, 3),
                "p99_ms": round(_percentile(totals, 0.99) * 1e3, 3),
            }
        tail = self.tail(records)
        if tail is not None:
            out["tail"] = tail
        return out

    def tail(self, records=None, decile=0.9):
        """Mean phase breakdown of the slowest ``1 - decile`` fraction
        of requests (by total), with the dominant phase flagged."""
        if records is None:
            with self._lock:
                records = list(self._records)
        if not records:
            return None
        ranked = sorted(records, key=lambda r: r["total"])
        cut = max(1, len(ranked) - int(len(ranked) * decile))
        slow = ranked[-cut:]
        phases = {}
        for rec in slow:
            for name, secs in rec.get("phases", {}).items():
                phases[name] = phases.get(name, 0.0) + secs
        n = len(slow)
        mean = {k: round(v / n * 1e3, 3) for k, v in phases.items()}
        dominant = max(mean, key=mean.get) if mean else None
        return {
            "count": n,
            "total_ms": round(sum(r["total"] for r in slow) / n * 1e3, 3),
            "phases_ms": mean,
            "dominant": dominant,
            "queue_dominated": dominant == "queue",
        }
