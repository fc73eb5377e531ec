"""Driver of serving traffic that draws a model a request: one scheduler
over several sessions, all resident on the chip, loaded open loop as
``harness/serve.py`` loads one.

Set-up builds what ``cmd/serve.py`` builds for a ``models:`` list (each
model's buckets, wire format, ``ServeSession`` and warm pool, then one
``Scheduler`` over the mapping ``model id -> session``) and hands every
session weights made from the seed by its own reference's specification.
The schedule is ``harness/schedule.py``'s; each request's model is drawn
beside it, independently of its size, by a seeded permutation inside every
group laid out from the models' weights: the popularity is exact for every
seed and only the order changes. The run it leaves has the shape of
``harness/serve.py``'s (``kind`` ``serve``, the same records with a
``model`` field), so every reader of a serve run reads this one; ``check``
holds each model's sampled flows against that model's reference and limit.
"""

import inspect
import json
import logging
import os
import random
import time
from pathlib import Path
from types import SimpleNamespace

from . import device, schedule, xtrace
from .serve import (Collector, attempted_failed, make_payloads,  # noqa: F401
                    memory_peak, print_rates, readings, trace_module,
                    write_records)


def model_plan(traffic, seed, n):
    """The model of each of ``n`` scheduled requests: ``group`` at a time
    in the proportion of ``traffic["models"]``' weights, shuffled by the
    seed apart from the sizes' own shuffle."""
    group = int(traffic.get("group", 8))
    pattern = schedule.shape_pattern(traffic["models"], group)
    rng = random.Random(int(seed) + 2)
    out = []
    while len(out) < n:
        order = pattern[:]
        rng.shuffle(order)
        out += [traffic["models"][k]["id"] for k in order]
    return out[:n]


def run(cell, seed, seconds, trace, out_dir, boot, platform="tpu"):
    for knob, value in cell.config.get("knobs", {}).items():
        os.environ[knob] = str(value)

    from raft_meets_dicl_tpu import serve as serving

    if "model" not in inspect.signature(serving.Scheduler.submit).parameters:
        # a program from before the lanes were keyed by model: say so now,
        # before any session is built
        raise SystemExit(
            "this program's Scheduler holds one session and its submit "
            "names no model: it cannot run a server of several models")

    from raft_meets_dicl_tpu import compile as programs
    from raft_meets_dicl_tpu import models, telemetry, utils
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

    import jax

    from ..reference import common as refc
    from ..reference import one_server

    jax.config.update("jax_default_device", devices[0])
    utils.logging.setup()
    logging.getLogger().setLevel(logging.WARNING)
    tele = telemetry.activate(telemetry.Telemetry(None))

    traffic = cell.traffic
    ids = [m["id"] for m in traffic["models"]]
    sessions, held, outcomes = {}, {}, []
    t_warm = time.perf_counter()
    for model_id in ids:
        entry = one_server.entry_of(cell.config, model_id)
        scfg = entry["serve"]
        session = serving.ServeSession(
            models.load(entry["model"]),
            ShapeBuckets.from_config(scfg["buckets"]),
            wire=WireFormat.from_config(scfg["wire-format"]), checkpoint=None,
            batch_size=int(scfg["batch-size"]))
        ref = one_server.module_of(entry)
        ref_spec = ref.spec(entry["model"])
        flat = refc.init(ref_spec, seed)
        want = {k: tuple(v.shape) for k, v in refc.flatten(
            jax.tree.map(lambda x: x, dict(session.variables))).items()}
        if want != {k: tuple(v.shape) for k, v in flat.items()}:
            raise RuntimeError(f"{model_id}: the reference's parameter tree "
                               f"is not the program's")
        session.variables = refc.nest(flat)
        del flat
        outcomes += session.warm_pool()
        sessions[model_id] = session
        held[model_id] = {
            "entry": entry, "reference": ref, "spec": ref_spec,
            "buckets": [tuple(b) for b in
                        ShapeBuckets.from_config(scfg["buckets"]).sizes]}
    warmup_s = time.perf_counter() - t_warm
    # the server's max-wait and queue bound (a lane's): every model's
    # entry states the same, as one server has one of each
    scfg = cell.config["models"][0]["serve"]
    scheduler = serving.Scheduler(
        sessions, max_wait_ms=float(scfg["max-wait-ms"]),
        queue_limit=int(scfg["queue-limit"])).start()

    payloads = make_payloads(traffic, seed)
    discard = float(traffic.get("discard_s", 2.0))
    trace_s = float(traffic.get("trace_s", 3.0)) if trace else 0.0
    plan = schedule.build(traffic, seed, seconds + trace_s)
    asked = model_plan(traffic, seed, len(plan))
    end_window = discard + float(seconds)
    clients = int(traffic.get("clients", 8))
    pool = len(payloads[0])

    # the sample the reference will follow: drawn from the seed among the
    # counted requests, the same number of each model and size
    rng = random.Random(int(seed) + 1)
    counted = [i for i, (due, _) in enumerate(plan)
               if discard <= due < end_window]
    keep = set()
    per_shape = int(traffic.get("check_per_shape", 2))
    for model_id in ids:
        for s in range(len(traffic["shapes"])):
            mine = [i for i in counted
                    if plan[i][1] == s and asked[i] == model_id]
            keep.update(rng.sample(mine, min(per_shape, len(mine))))

    collectors = [Collector(float(traffic.get("timeout_s", 60.0)))
                  for _ in range(clients)]
    for c in collectors:
        c.start()

    compiles = lambda: tele.counts().get("compile", 0) + sum(  # noqa: E731
        s.compiles() for s in sessions.values())
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
        record = {"i": i, "due": due, "shape": shape, "model": asked[i],
                  "payload": payload, "client": i % clients,
                  "counted": discard <= due < end_window, "keep": i in keep,
                  "submit": time.perf_counter() - t_start}
        try:
            ticket = scheduler.submit(img1, img2, client=f"c{i % clients}",
                                      model=asked[i])
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
    # free the program's state before the references run
    for session in sessions.values():
        session.variables = None
    scheduler = session = None
    sessions.clear()
    device.release_programs()

    return {
        "kind": "serve", "cell": cell, "seed": seed, "out_dir": out_dir,
        "devices": devices, "events": events, "records": records,
        "boot": boot, "t_start": t_start, "discard": discard,
        "warmup_s": warmup_s, "warm_pool": outcomes,
        "compiles": (compiles_open, compiles_close),
        "window_wall": (wall_open, wall_close), "peak": peak,
        "payloads": payloads, "models": held,
        "trace_dir": trace_dir if trace else None, "trace_wall_s": trace_wall,
    }


def views(run):
    """``{model id: run}``: the run as one model's alone, in the shape
    ``harness/serve_check.py`` reads (that model's records, configuration
    entry, reference, specification and buckets)."""
    out = {}
    for model_id, held in run["models"].items():
        out[model_id] = dict(
            run, cell=SimpleNamespace(config=held["entry"]),
            reference=held["reference"], spec=held["spec"],
            buckets=held["buckets"],
            records=[r for r in run["records"] if r["model"] == model_id])
    return out


def check(run, verdict, limits, control=False):
    """Each model's sampled flows against its own reference and its own
    limit; one model outside its limit makes the run incorrect.

    ``control`` (``benchmark/tests/control_one_server.py`` alone; a run of
    the benchmark never) puts in the served flows' place those of the same
    reference with its operands rounded to the model's
    ``control_precision``: the comparison that must come out not correct,
    by this function and these limits."""
    from . import serve_check

    notes = {}
    for model_id, view in views(run).items():
        quants = (None,)
        if control:
            import jax.numpy as jnp

            quants += (getattr(jnp, view["cell"].config["control_precision"]),)
        pairs = [serve_check.relative_epe(flows[-1] if control else served,
                                          flows[0])
                 for served, flows in serve_check.sample_flows(view, quants)]
        gaps = [g for g, _ in pairs]
        verdict.hold(f"serve_flow_gap[{model_id}]",
                     max(gaps) if gaps else None,
                     limits["serve_flow_gap"][model_id])
        notes[model_id] = {"sampled": len(gaps), "gaps": gaps,
                           "flow_magnitude_px": [m for _, m in pairs]}
    sampled = sum(n["sampled"] for n in notes.values())
    verdict.hold("sample_missing",
                 float(sum(1 for r in run["records"] if r.get("keep"))
                       - sampled), 0)
    verdict.hold("requests_failed",
                 float(run["readings"]["counted"]
                       - run["readings"]["completed"]), 0)
    print(f"[check] notes {json.dumps(notes)}", flush=True)
    by_model = {}
    for r in run["records"]:
        if r["counted"]:
            by_model[r["model"]] = by_model.get(r["model"], 0) + 1
    print(f"[models] counted {json.dumps(by_model)}", flush=True)
    return notes
