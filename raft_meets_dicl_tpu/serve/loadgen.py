"""Open-loop synthetic load generator: the SLO measurement harness.

Open-loop means requests fire on a fixed wall-clock schedule regardless
of completions — the honest way to measure a service under load (a
closed loop self-throttles and hides queueing delay, the classic
coordinated-omission trap). The generator cycles through a
mixed-resolution shape list, submits raw synthetic pairs at ``rate_hz``,
collects every ticket, and reports p50/p99/mean latency, per-span means,
throughput, and the shed/error counts.

The generator is also a *well-behaved* client of the typed shed
contract: retryable sheds (``queue_full``, ``replica_unavailable`` —
backpressure that may clear) can re-submit with jittered exponential
backoff up to a bounded budget, while permanent sheds (``shutdown``,
``draining``) are never retried. Each ticket is collected under a
per-request timeout; a ticket that completes with a typed shed (the
fleet router resolves rejections at result time, not submit time) is
accounted exactly like a synchronous one.
"""

import random
import time

import numpy as np

from ..telemetry.report import _percentile
from .batcher import ServeError, ServeRejected

# shed reasons worth a client-side retry: transient backpressure, not a
# permanent state of the service
RETRYABLE_SHEDS = ("queue_full", "replica_unavailable")


def synthetic_pair(shape, rng):
    """One deterministic pseudo-random raw image pair in [0, 1)."""
    h, w = shape
    img1 = rng.random((h, w, 3), dtype=np.float32)
    img2 = rng.random((h, w, 3), dtype=np.float32)
    return img1, img2


def submit_with_retry(scheduler, img1, img2, client, klass, sequence,
                      retries, backoff_s, rejects, retried, model=None):
    """One submission with bounded jittered-backoff retry on retryable
    typed sheds; returns the ticket or None (shed accounted)."""
    # a router's submit knows no model: name one only where one is asked
    named = {} if model is None else {"model": model}
    for attempt in range(int(retries) + 1):
        try:
            return scheduler.submit(img1, img2, client=client, klass=klass,
                                    sequence=sequence, **named)
        except ServeRejected as e:
            if e.reason not in RETRYABLE_SHEDS or attempt >= retries:
                rejects[e.reason] = rejects.get(e.reason, 0) + 1
                return None
            retried[0] += 1
            time.sleep(backoff_s * (2 ** attempt)
                       * random.uniform(0.5, 1.5))
    return None


def run_open_loop(scheduler, shapes, requests, rate_hz, client="loadgen",
                  seed=0, result_timeout_s=120.0, classes=None,
                  sequence=False, streams=4, retries=0,
                  retry_backoff_s=0.05, models=None):
    """Drive ``scheduler`` with ``requests`` submissions at ``rate_hz``.

    ``shapes`` is the (H, W) cycle the stream draws from (mixed
    resolutions exercise bucket quantization and partial batches);
    ``classes`` an optional latency-class cycle (ladder sessions) — the
    report then carries a per-class latency/rung breakdown. With
    ``sequence=True`` (video sessions) requests are submitted as
    ``streams`` interleaved sticky client streams — each stream pins one
    shape so its frames share a bucket and its carry stays valid — and
    the report carries a warm-hit breakdown. ``retries`` > 0 re-submits
    a retryably-shed request with jittered backoff (``retry_backoff_s``
    base, doubling per attempt) before accounting the shed; the default
    0 keeps the pure open-loop measurement (a retry bends the schedule,
    which is the client's choice, not the harness's). ``models`` (a
    server of several models) is a cycle of ``(model id, shapes)``:
    request *i* asks the *i*-th model of the cycle and draws from that
    model's own shapes, and the report carries a per-model breakdown;
    ``shapes`` is then unused. Returns the
    report dict (see ``summarize``); deterministic for a fixed seed,
    shape list, and class list (retry jitter excepted).
    """
    rng = np.random.default_rng(seed)
    interval = 1.0 / float(rate_hz)
    tickets = []
    rejects = {}
    errors = {}
    retried = [0]

    t_start = time.perf_counter()
    for i in range(int(requests)):
        target = t_start + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        model = None
        if sequence:
            stream = i % max(1, int(streams))
            shape = shapes[stream % len(shapes)]
            name = f"{client}-{stream}"
        elif models:
            model, mine = models[i % len(models)]
            shape = mine[(i // len(models)) % len(mine)]
            name = client
        else:
            shape = shapes[i % len(shapes)]
            name = client
        img1, img2 = synthetic_pair(shape, rng)
        klass = classes[i % len(classes)] if classes else None
        try:
            ticket = submit_with_retry(
                scheduler, img1, img2, name, klass, sequence,
                retries, retry_backoff_s, rejects, retried, model=model)
            if ticket is not None:
                tickets.append(ticket)
        except ServeError as e:
            errors[e.kind] = errors.get(e.kind, 0) + 1

    results = []
    for ticket in tickets:
        try:
            results.append(ticket.result(timeout=result_timeout_s))
        except ServeRejected as e:
            # fleet tickets resolve sheds at result time (the router's
            # bounded retry already ran); account them with the rest
            rejects[e.reason] = rejects.get(e.reason, 0) + 1
        except TimeoutError:
            errors["timeout"] = errors.get("timeout", 0) + 1
        except ServeError as e:
            errors[e.kind] = errors.get(e.kind, 0) + 1
    wall = time.perf_counter() - t_start

    report = summarize(int(requests), results, rejects, errors, wall)
    if retried[0]:
        report["retries"] = retried[0]
    return report


def summarize(requests, results, rejects, errors, wall_s):
    """Aggregate completed :class:`FlowResult`s into the SLO report."""
    latencies = sorted(r.spans.get("total", 0.0) for r in results)
    span_names = sorted({k for r in results for k in r.spans})
    spans_ms = {}
    for name in span_names:
        vals = [r.spans[name] for r in results if name in r.spans]
        spans_ms[name] = round(1e3 * sum(vals) / len(vals), 3)

    completed = len(results)
    report = {
        "requests": requests,
        "completed": completed,
        "rejected": rejects,
        "errors": errors,
        "wall_s": round(wall_s, 3),
        "pairs_per_sec": round(completed / wall_s, 3) if wall_s > 0 else 0.0,
        "p50_ms": round(1e3 * _percentile(latencies, 0.50), 3),
        "p99_ms": round(1e3 * _percentile(latencies, 0.99), 3),
        "mean_ms": (round(1e3 * sum(latencies) / completed, 3)
                    if completed else 0.0),
        "spans_ms": spans_ms,
    }

    # ladder breakdown: per-class latency + executed-iterations histogram
    by_class = {}
    for r in results:
        if not getattr(r, "klass", ""):
            continue
        c = by_class.setdefault(r.klass, {"lat": [], "iterations": {}})
        c["lat"].append(r.spans.get("total", 0.0))
        its = c["iterations"]
        its[r.iterations] = its.get(r.iterations, 0) + 1
    if by_class:
        report["classes"] = {
            k: {
                "completed": len(c["lat"]),
                "p50_ms": round(1e3 * _percentile(sorted(c["lat"]), 0.50), 3),
                "p99_ms": round(1e3 * _percentile(sorted(c["lat"]), 0.99), 3),
                "mean_ms": round(1e3 * sum(c["lat"]) / len(c["lat"]), 3),
                "iterations": dict(sorted(c["iterations"].items())),
            } for k, c in sorted(by_class.items())
        }

    # a server of several models: latency by the model that answered
    by_model = {}
    for r in results:
        by_model.setdefault(getattr(r, "model", ""), []).append(
            r.spans.get("total", 0.0))
    if len(by_model) > 1:
        report["models"] = {
            m: {
                "completed": len(lat),
                "p50_ms": round(1e3 * _percentile(sorted(lat), 0.50), 3),
                "p99_ms": round(1e3 * _percentile(sorted(lat), 0.99), 3),
                "mean_ms": round(1e3 * sum(lat) / len(lat), 3),
            } for m, lat in sorted(by_model.items())
        }

    # video breakdown: warm-start hit ratio across completed frames
    warm = sum(1 for r in results if getattr(r, "warm", False))
    if warm:
        report["video"] = {"warm": warm, "cold": completed - warm}
    return report
