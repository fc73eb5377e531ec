"""Driver of serving traffic: the program's session and scheduler, loaded
open loop from one thread on a fixed schedule.

Set-up builds what ``cmd/serve.py`` builds (model, buckets, wire format,
``ServeSession``, warm pool, ``Scheduler``), hands the session weights made
from the seed, and makes every payload. Then one thread submits each
request when it is due, never earlier and never waiting for a reply;
``clients`` collector threads, one per client stream, stamp each reply as
the scheduler releases it (the scheduler releases a client's replies in
submission order, so a collector that waits in order stamps on time).
Latency runs from the time a request was *due*. Requests due in the first
``discard_s`` seconds are sent and not counted; with ``--trace 1`` the
same load goes on for ``trace_s`` seconds under the profiler after the
window. Every counted request is awaited to its end.
"""

import json
import logging
import os
import threading
import time
from pathlib import Path

from . import device, schedule, stats, xtrace


def make_payloads(traffic, seed):
    """``pool`` image pairs per shape, float32 in [0, 1] on the 8-bit grid
    (what a client decodes from a file): blocky random scenes, the second
    frame the first shifted by a few pixels plus a little noise. Made in
    set-up; the clock runs on nothing but submission."""
    import numpy as np

    rng = np.random.default_rng(int(seed))
    pool = int(traffic.get("payload_pool", 8))
    out = []
    for shape in traffic["shapes"]:
        h, w = shape["size"]
        pairs = []
        for _ in range(pool):
            coarse = rng.random((h // 16 + 3, w // 16 + 3, 3), dtype=np.float32)
            big = np.kron(coarse, np.ones((16, 16, 1), np.float32))
            dy, dx = rng.integers(-6, 7, size=2)
            a = big[16:16 + h, 16:16 + w]
            b = big[16 + dy:16 + dy + h, 16 + dx:16 + dx + w]
            noise = rng.normal(0.0, 0.02, (2, h, w, 3)).astype(np.float32)
            q = np.rint(np.clip(np.stack((a, b)) + noise, 0, 1) * 255) / 255
            pairs.append((np.ascontiguousarray(q[0], np.float32),
                          np.ascontiguousarray(q[1], np.float32)))
        out.append(pairs)
    return out


class Collector(threading.Thread):
    """Waits for one client's tickets in submission order and stamps each
    when the scheduler releases it."""

    def __init__(self, timeout_s):
        super().__init__(daemon=True)
        self.todo = []            # (record, ticket), appended by the sender
        self.cond = threading.Condition()
        self.closed = False
        self.timeout_s = timeout_s

    def add(self, record, ticket):
        with self.cond:
            self.todo.append((record, ticket))
            self.cond.notify()

    def close(self):
        with self.cond:
            self.closed = True
            self.cond.notify()

    def run(self):
        i = 0
        while True:
            with self.cond:
                while i >= len(self.todo) and not self.closed:
                    self.cond.wait()
                if i >= len(self.todo):
                    return
                record, ticket = self.todo[i]
            i += 1
            try:
                result = ticket.result(timeout=self.timeout_s)
                record["done"] = time.perf_counter()
                record["ok"] = True
                if record.get("keep"):
                    record["flow"] = result.flow
            except Exception as e:  # noqa: BLE001 - every failure is counted
                record["done"] = time.perf_counter()
                record["ok"] = False
                record["error"] = f"{type(e).__name__}: {e}"[:200]


def run(cell, seed, seconds, trace, out_dir, boot, platform="tpu"):
    for knob, value in cell.config.get("knobs", {}).items():
        os.environ[knob] = str(value)

    from raft_meets_dicl_tpu import compile as programs
    from raft_meets_dicl_tpu import models, serve as serving, telemetry, utils
    from raft_meets_dicl_tpu.cmd.train import select_devices
    from raft_meets_dicl_tpu.models.input import ShapeBuckets
    from raft_meets_dicl_tpu.models.wire import WireFormat
    from raft_meets_dicl_tpu.utils.compcache import enable_persistent_cache

    enable_persistent_cache()
    programs.enable_aot()
    try:
        select_devices(platform, None)
    except ValueError as e:
        raise device.NoAccelerator(str(e)) from e
    devices = device.require(cell.chips, platform)

    import importlib
    import random

    import jax

    from ..reference import common as refc

    jax.config.update("jax_default_device", devices[0])
    utils.logging.setup()
    logging.getLogger().setLevel(logging.WARNING)
    tele = telemetry.activate(telemetry.Telemetry(None))

    traffic, scfg = cell.traffic, cell.config["serve"]
    spec = models.load(cell.config["model"])
    session = serving.ServeSession(
        spec, ShapeBuckets.from_config(scfg["buckets"]),
        wire=WireFormat.from_config(scfg["wire-format"]), checkpoint=None,
        batch_size=int(scfg["batch-size"]))

    ref = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    ref_spec = ref.spec(cell.config["model"])
    flat = refc.init(ref_spec, seed)
    want = {k: tuple(v.shape) for k, v in refc.flatten(
        jax.tree.map(lambda x: x, dict(session.variables))).items()}
    if want != {k: tuple(v.shape) for k, v in flat.items()}:
        raise RuntimeError("the reference's parameter tree is not the "
                           "program's")
    session.variables = refc.nest(flat)
    del flat

    t_warm = time.perf_counter()
    outcomes = session.warm_pool()
    warmup_s = time.perf_counter() - t_warm
    scheduler = serving.Scheduler(
        session, batch_size=int(scfg["batch-size"]),
        max_wait_ms=float(scfg["max-wait-ms"]),
        queue_limit=int(scfg["queue-limit"])).start()

    payloads = make_payloads(traffic, seed)
    discard = float(traffic.get("discard_s", 2.0))
    trace_s = float(traffic.get("trace_s", 3.0)) if trace else 0.0
    plan = schedule.build(traffic, seed, seconds + trace_s)
    end_window = discard + float(seconds)
    clients = int(traffic.get("clients", 8))
    pool = len(payloads[0])

    # the sample the reference will follow: drawn from the seed among the
    # counted requests, the same number of each shape
    rng = random.Random(int(seed) + 1)
    counted = [i for i, (due, _) in enumerate(plan)
               if discard <= due < end_window]
    keep = set()
    per_shape = int(traffic.get("check_per_shape", 4))
    for s in range(len(traffic["shapes"])):
        mine = [i for i in counted if plan[i][1] == s]
        keep.update(rng.sample(mine, min(per_shape, len(mine))))

    collectors = [Collector(float(traffic.get("timeout_s", 60.0)))
                  for _ in range(clients)]
    for c in collectors:
        c.start()

    compiles = lambda: tele.counts().get("compile", 0) + session.compiles()  # noqa: E731
    records = []
    out_dir = Path(out_dir)
    trace_dir, trace_wall, tracing = out_dir / "trace", None, False
    compiles_open = compiles_close = wall_open = wall_close = None
    peak = 0

    # what set-up wrote (compile cache, AOT artifacts) goes to disk now,
    # not as a writeback stall inside the window
    os.sync()
    t_start = time.perf_counter()
    for i, (due, shape) in enumerate(plan):
        if compiles_open is None and due >= discard:
            compiles_open, wall_open = compiles(), time.time()
        if compiles_close is None and due >= end_window:
            compiles_close, wall_close = compiles(), time.time()
            peak = device.memory_peak_bytes(devices)
            if trace:
                t_trace = time.perf_counter()
                jax.profiler.start_trace(
                    str(trace_dir), profiler_options=xtrace.profile_options())
                tracing = True
        delay = t_start + due - time.perf_counter()
        if delay > 0:
            with jax.profiler.TraceAnnotation("bench:sleep_until_due"):
                time.sleep(delay)
        payload = (i // len(payloads)) % pool
        img1, img2 = payloads[shape][payload]
        record = {"i": i, "due": due, "shape": shape, "payload": payload,
                  "client": i % clients,
                  "counted": discard <= due < end_window, "keep": i in keep,
                  "submit": time.perf_counter() - t_start}
        try:
            ticket = scheduler.submit(img1, img2, client=f"c{i % clients}")
            collectors[i % clients].add(record, ticket)
        except Exception as e:  # noqa: BLE001 - sheds and errors are counted
            record.update(ok=False, done=time.perf_counter(),
                          error=f"{type(e).__name__}: {e}"[:200])
        records.append(record)
    if compiles_close is None:
        compiles_close, wall_close = compiles(), time.time()
        peak = device.memory_peak_bytes(devices)

    for c in collectors:
        c.close()
    for c in collectors:
        c.join()
    if tracing:
        jax.profiler.stop_trace()
        trace_wall = time.perf_counter() - t_trace
    for r in records:
        if "done" in r:
            r["done"] -= t_start
    scheduler.stop(drain=True)
    events = list(tele.events)
    # free the program's state before the reference runs
    session.variables = None
    scheduler = session = None
    device.release_programs()

    return {
        "kind": "serve", "cell": cell, "seed": seed, "out_dir": out_dir,
        "devices": devices, "events": events, "records": records,
        "boot": boot, "t_start": t_start, "discard": discard,
        "warmup_s": warmup_s, "warm_pool": outcomes,
        "compiles": (compiles_open, compiles_close),
        "window_wall": (wall_open, wall_close), "peak": peak,
        "payloads": payloads, "reference": ref, "spec": ref_spec,
        "trace_dir": trace_dir if trace else None, "trace_wall_s": trace_wall,
        "buckets": [tuple(b) for b in
                    ShapeBuckets.from_config(scfg["buckets"]).sizes],
    }


def readings(run):
    counted = [r for r in run["records"] if r["counted"]]
    good = [r for r in counted if r.get("ok")]
    lat = [1e3 * (r["done"] - r["due"]) for r in good]
    late = [1e3 * (r["submit"] - r["due"]) for r in counted]
    return {
        "serve_p50_ms": stats.percentile(lat, 50),
        "serve_p95_ms": stats.percentile(lat, 95),
        "serve_mean_ms": sum(lat) / len(lat) if lat else None,
        "loadgen_late_ms": stats.percentile(late, 95),
        "counted": len(counted), "completed": len(good),
        "setup_s": run["boot"]["offset_s"] + (run["t_start"] + run["discard"]
                                              - run["boot"]["t0"]),
        "warmup_s": run["warmup_s"],
        "window_compiles": run["compiles"][1] - run["compiles"][0],
        "window_wall": run["window_wall"],
    }


def write_records(run):
    with open(run["out_dir"] / "requests.jsonl", "w") as f:
        for r in run["records"]:
            f.write(json.dumps({k: v for k, v in r.items()
                                if k not in ("flow",)}) + "\n")
    with open(run["out_dir"] / "events.jsonl", "w") as f:
        for ev in run["events"]:
            f.write(json.dumps(ev, default=str) + "\n")


def print_rates(run):
    r = run["readings"]
    # latency by thirds of the window, so an outlying run explains itself
    counted = [x for x in run["records"] if x["counted"] and x.get("ok")]
    thirds = []
    for k in range(3):
        part = counted[k * len(counted) // 3:(k + 1) * len(counted) // 3]
        thirds.append(stats.percentile(
            [1e3 * (x["done"] - x["due"]) for x in part], 50))
    print(f"[requests] counted={r['counted']} completed={r['completed']} "
          f"p50_ms={r['serve_p50_ms']:.2f} p95_ms={r['serve_p95_ms']:.2f} "
          f"mean_ms={r['serve_mean_ms']:.2f} p50_by_third_ms="
          f"{[round(t, 1) for t in thirds]} late_p95_ms="
          f"{r['loadgen_late_ms']:.3f} setup_s={r['setup_s']:.2f} "
          f"compiles_in_window={r['window_compiles']}", flush=True)
    tail = [1e3 * (x["done"] - x["due"]) for x in run["records"]
            if x.get("ok") and not x["counted"] and x["due"] > run["discard"]]
    if tail:
        # does the profiler slow the server it records?
        print(f"[trace] traced tail p50 {stats.percentile(tail, 50):.1f} ms "
              f"over {len(tail)} requests, untraced window "
              f"{r['serve_p50_ms']:.1f}", flush=True)


def memory_peak(run):
    return run["peak"]


def attempted_failed(run):
    r = run["readings"]
    return r["counted"], r["counted"] - r["completed"]


def trace_module(run):
    return run["cell"].traffic.get("trace_module", "jit_step")


def check(run, verdict, limits):
    from . import serve_check

    notes = serve_check.check(run, verdict, limits)
    print(f"[check] notes {json.dumps(notes)}", flush=True)
