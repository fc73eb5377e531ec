"""Kill/rejoin chaos drill: the fleet's acceptance scenario.

One harness, one consumer (``main.py serve --fleet N --drill``): drive a
skewed request mix plus one sticky video stream through the router, hard-kill
a replica mid-stream, and account for what the fleet *promised*:

- zero dropped accepted requests — every submitted request ends in a
  result or a *typed* shed (``queue_full`` / ``replica_unavailable``),
  never an untyped error;
- the sticky stream survives with at most one cold frame (its carry is
  evicted with the dead replica; the next frame re-primes it);
- the rejoining replica serves warm: with the AOT store published, its
  boot compiles are zero (every program fetched, not rebuilt).

The drill only *drives and measures* — process lifecycle belongs to the
supervisor, routing policy to the router.
"""

import threading
import time

import numpy as np

from ..serve.batcher import ServeError, ServeRejected
from .client import ReplicaClient, ReplicaDown, ReplicaTimeout


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _pair(rng, h, w):
    return (rng.random((h, w, 3), dtype=np.float32),
            rng.random((h, w, 3), dtype=np.float32))


def run_drill(router, kill, shapes, classes=(None,), frames=24,
              kill_after=8, rejoin_wait_s=120.0, seed=0,
              background_per_frame=2, ticket_timeout_s=None):
    """Run the kill/rejoin scenario; returns the report dict.

    ``router`` is a started :class:`~.router.Router`; ``kill()`` is a
    callback that hard-kills one (non-sticky-owner if possible) replica
    and eventually brings it back — typically wrapping
    ``supervisor.kill`` or an in-process server shutdown. It receives
    the sticky session's current owner name (or None) and must return
    the killed replica's name. ``shapes`` is the (H, W) list for the
    skewed background mix (first = the sticky stream's shape).
    """
    rng = np.random.default_rng(seed)
    if ticket_timeout_s is None:
        ticket_timeout_s = router.timeout_s + 5.0
    sticky = "drill-stream"
    report = {
        "frames": frames,
        "submitted": 0, "completed": 0, "dropped": 0,
        "sheds": {}, "cold_frames": 0, "warm_frames": 0,
        "errors": [],
        "killed": None, "rejoined": False, "rejoin_compiles": None,
        "latencies_ms": {},
    }
    latencies = {}  # (shape, klass) -> [seconds]
    lock = threading.Lock()

    def account(ticket, key, t0, frame=None):
        report["submitted"] += 1
        try:
            result = ticket.result(timeout=ticket_timeout_s)
        except ServeRejected as e:
            with lock:
                report["sheds"][e.reason] = \
                    report["sheds"].get(e.reason, 0) + 1
            return None
        except (ServeError, TimeoutError) as e:
            with lock:
                report["dropped"] += 1
                if len(report["errors"]) < 8:
                    report["errors"].append(
                        f"{key}[{frame}]: {type(e).__name__}: {e}")
            return None
        with lock:
            report["completed"] += 1
            latencies.setdefault(key, []).append(time.monotonic() - t0)
        return result

    h0, w0 = shapes[0]
    killed_at_frame = None
    for frame in range(frames):
        # the sticky stream frame (sequence: carries flow between frames)
        img1, img2 = _pair(rng, h0, w0)
        t0 = time.monotonic()
        ticket = router.submit(img1, img2, client=sticky, klass=classes[0],
                               sequence=True)
        result = account(ticket, ("stream", f"{h0}x{w0}"), t0, frame=frame)
        if result is not None and frame > 0:
            with lock:
                if result.warm:
                    report["warm_frames"] += 1
                else:
                    report["cold_frames"] += 1
        # skewed background singles (shape 0 is hot, the rest cold)
        for j in range(background_per_frame):
            h, w = shapes[0] if (frame + j) % 3 else \
                shapes[min(1 + j % max(1, len(shapes) - 1),
                           len(shapes) - 1)]
            klass = classes[(frame + j) % len(classes)]
            b1, b2 = _pair(rng, h, w)
            t0 = time.monotonic()
            t = router.submit(b1, b2, klass=klass)
            account(t, ("single", f"{h}x{w}", klass or ""), t0)
        if frame == kill_after:
            with router._lock:
                owner = router._affinity.get(sticky)
            report["killed"] = kill(owner)
            killed_at_frame = frame

    # wait for the killed replica to rejoin and prove it serves warm
    if report["killed"] is not None:
        deadline = time.monotonic() + rejoin_wait_s
        while time.monotonic() < deadline:
            state = router.replicas().get(report["killed"])
            if state is not None and state.eligible() \
                    and state.generation > 0:
                report["rejoined"] = True
                try:
                    status = state.client.status(timeout=5.0)
                    report["rejoin_compiles"] = status.get("compiles")
                except (ReplicaDown, ReplicaTimeout):
                    pass
                break
            time.sleep(0.2)
        if report["rejoined"]:
            # a few post-rejoin frames: the stream must already be warm
            # again and the rejoined replica must take traffic
            for frame in range(4):
                img1, img2 = _pair(rng, h0, w0)
                t0 = time.monotonic()
                ticket = router.submit(img1, img2, client=sticky,
                                       klass=classes[0], sequence=True)
                account(ticket, ("stream", f"{h0}x{w0}"), t0,
                        frame=frames + frame)

    every = sorted(v for vals in latencies.values() for v in vals)
    if every:
        report["latencies_ms"]["aggregate"] = {
            "n": len(every),
            "p50": round(_percentile(every, 0.50) * 1e3, 2),
            "p99": round(_percentile(every, 0.99) * 1e3, 2),
        }
    for key, vals in latencies.items():
        vals.sort()
        report["latencies_ms"]["/".join(str(k) for k in key)] = {
            "n": len(vals),
            "p50": round(_percentile(vals, 0.50) * 1e3, 2),
            "p99": round(_percentile(vals, 0.99) * 1e3, 2),
        }
    report["killed_at_frame"] = killed_at_frame
    report["ok"] = (
        report["dropped"] == 0
        and report["cold_frames"] <= 1
        and (report["killed"] is None or report["rejoined"])
        and (report["rejoin_compiles"] is None
             or report["rejoin_compiles"] == 0))
    return report
