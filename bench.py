"""Benchmark: RAFT training throughput in image-pairs/sec/chip.

Mirrors the reference's FlyingThings3D training configuration (batch 6,
720x400 crops, 12 GRU iterations, AdamW + grad clip —
cfg/strategy/baseline/raft/s1-things.yaml) as a synthetic-data training-step
benchmark on one chip. Prints the primary metric as a JSON line as soon
as it is measured, then (flagship enabled) a second, enriched JSON line
with the thesis flagship's (raft+dicl/ctf-l3) throughput added —
consumers read the LAST line, which is always the most complete.

``vs_baseline`` compares against the north-star target of 400 image-pairs/s
on a v4-32 (32 chips) => 12.5 pairs/s/chip (BASELINE.json; the reference
repo publishes no throughput numbers of its own).
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from raft_meets_dicl_tpu.utils import env

BASELINE_PAIRS_PER_SEC_PER_CHIP = 400.0 / 32.0


def _emit(result):
    """Print one cumulative JSON result line with the goodput breakdown
    attached: every BENCH_* line carries the wall-clock ledger
    (productive vs compile vs data-starved vs ... seconds) so a slow
    bench is attributable without re-running under a profiler."""
    from raft_meets_dicl_tpu.telemetry import goodput

    # every BENCH_* row names its augmentation arm ("off" unless a bench
    # sets one), so result consumers can split host/device/synth series
    result.setdefault("augment", "off")
    ledger = goodput.get()
    if ledger.enabled:
        snap = ledger.snapshot()
        result["goodput"] = {
            "total_s": snap["total"],
            "goodput": snap["goodput"],
            "classes_s": snap["classes"],
        }
    print(json.dumps(result), flush=True)
    return result


def _profile_step(run):
    """Measured graftprof attribution of one profiled step execution —
    the per-op-class receipt every BENCH_* line carries (BENCH_PROFILE=0
    disables). Advisory: returns an ``{"error": ...}`` stub instead of
    raising, so a profiler/parser failure never loses the bench line."""
    import shutil
    import tempfile

    from raft_meets_dicl_tpu.analysis import profile as prof

    tmp = tempfile.mkdtemp(prefix="rmd-bench-prof-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            out = run()
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        summary = prof.attribute_trace(tmp)
        classes = {}
        for m in summary["modules"]:
            for c, s in m["classes"].items():
                classes[c] = round(classes.get(c, 0.0) + s, 6)
        return {
            "device_seconds": summary["device_seconds"],
            "source": summary["source"],
            "classes": dict(sorted(classes.items(),
                                   key=lambda kv: -kv[1])),
            "modules": [{"module": m["module"], "program": m["program"],
                         "seconds": m["seconds"]}
                        for m in summary["modules"][:4]],
        }
    except Exception as e:  # noqa: BLE001 - attribution is advisory
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _measure(model_cfg, loss_cfg, batch, height, width, model_args, steps,
             nonfinite=None):
    """One synthetic training-step throughput measurement; all device
    state is local, so buffers free when it returns.

    Returns (pairs_per_sec, peak_bytes, telemetry_summary) — the summary
    carries compile/cache counts from the active telemetry sink plus
    dispatch-time stats, so BENCH_*.json records more than one number.
    ``nonfinite='skip'`` builds the step with the non-finite skip guard
    (BENCH_FAULT overhead measurement)."""
    import optax

    import raft_meets_dicl_tpu.models as models
    from raft_meets_dicl_tpu import parallel, telemetry

    spec = models.load({
        "name": "bench", "id": "bench",
        "model": model_cfg, "loss": loss_cfg, "input": None,
    })
    model, loss = spec.model, spec.loss

    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
    img2 = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(batch, height, width, 2), jnp.float32)
    valid = jnp.ones((batch, height, width), bool)

    init_args = dict(model_args)
    if "iterations" in init_args:
        init_args["iterations"] = (
            (1,) * len(model_args["iterations"])
            if isinstance(model_args["iterations"], tuple) else 1)
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1],
                           **init_args)

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(4e-4))
    state = parallel.TrainState.create(variables, tx)
    step = parallel.make_train_step(model, loss, tx, model_args=model_args,
                                    nonfinite=nonfinite)

    tele = telemetry.get()
    tail0 = len(getattr(tele, "events", ()))

    # warmup / compile
    t0 = time.perf_counter()
    state, aux = step(state, img1, img2, flow, valid)
    jax.block_until_ready(aux["loss"])
    compile_wall = time.perf_counter() - t0

    # per-step dispatch timing only when telemetry is on: RMD_TELEMETRY=0
    # must restore the bare measurement loop
    dispatch = []
    t0 = time.perf_counter()
    if tele.enabled:
        for _ in range(steps):
            ts = time.perf_counter()
            state, aux = step(state, img1, img2, flow, valid)
            dispatch.append(time.perf_counter() - ts)
    else:
        for _ in range(steps):
            state, aux = step(state, img1, img2, flow, valid)
    jax.block_until_ready(aux["loss"])
    dt = time.perf_counter() - t0

    summary = None
    if tele.enabled:
        # the bench sink is memory-only: the tail since tail0 is exactly
        # this measurement's compile/cache activity
        tail = getattr(tele, "events", [])[tail0:]
        compiles = [e for e in tail if e["kind"] == "compile"]
        caches = [e for e in tail if e["kind"] == "cache"]
        dispatch.sort()
        summary = {
            "compiles": len(compiles),
            "compile_s": round(sum(e["seconds"] for e in compiles), 3),
            "cache_hits": sum(1 for e in caches if e["event"] == "hit"),
            "cache_misses": sum(1 for e in caches if e["event"] == "miss"),
            "warmup_wall_s": round(compile_wall, 3),
            "step_ms_mean": round(dt / steps * 1e3, 3),
            "dispatch_ms_mean": round(sum(dispatch) / steps * 1e3, 3),
            "dispatch_ms_p95": round(
                dispatch[min(steps - 1, int(round(0.95 * (steps - 1))))]
                * 1e3, 3),
        }
        # measured device-time attribution (graftprof): one extra
        # profiled step, parsed into per-op-class seconds
        if os.environ.get("BENCH_PROFILE", "1") != "0":
            summary["profile"] = _profile_step(
                lambda: step(state, img1, img2, flow, valid))

    # peak_bytes_in_use is a process-lifetime high-water mark: meaningful
    # for the first measurement in a process, an upper bound afterwards
    stats = jax.local_devices()[0].memory_stats() or {}
    return batch * steps / dt, stats.get("peak_bytes_in_use", 0), summary


def _bench_input():
    """Standalone input-pipeline benchmark (``BENCH_INPUT=1``): for each
    wire preset, decode throughput through the adapter+loader path,
    collate time, and the wire volume one bench-shaped batch moves across
    the host→device boundary. Host-only — no device work, so the numbers
    isolate the pipeline from the step it feeds. ``RMD_LOADER_PROCS``
    selects the decode-process pool; prints one (cumulative) JSON line
    per preset."""
    from raft_meets_dicl_tpu.data.collection import (
        Metadata, SampleArgs, SampleId,
    )
    from raft_meets_dicl_tpu.models import input as minput
    from raft_meets_dicl_tpu.models.wire import WireFormat

    batch = int(os.environ.get("BENCH_BATCH", "6"))
    height = int(os.environ.get("BENCH_HEIGHT", "400"))
    width = int(os.environ.get("BENCH_WIDTH", "720"))
    n = int(os.environ.get("BENCH_INPUT_SAMPLES", "48"))
    procs = env.get_int("RMD_LOADER_PROCS")

    class Synth:
        """Raw [0, 1] pairs generated per access — a stand-in for the
        decoded-dataset read the real pipeline amortizes via `cache`."""

        def __init__(self, n, h, w):
            self.n, self.h, self.w = n, h, w

        def __getitem__(self, index):
            rng = np.random.RandomState(index)
            img1 = rng.rand(1, self.h, self.w, 3).astype(np.float32)
            img2 = rng.rand(1, self.h, self.w, 3).astype(np.float32)
            flow = rng.randn(1, self.h, self.w, 2).astype(np.float32)
            valid = np.ones((1, self.h, self.w), bool)
            meta = [Metadata(True, "synth",
                             SampleId("s", SampleArgs(), SampleArgs()),
                             ((0, self.h), (0, self.w)))]
            return img1, img2, flow, valid, meta

        def __len__(self):
            return self.n

    spec = minput.InputSpec(clip=(0, 1), range=(-1, 1))
    result = {
        "metric": "input-pipeline",
        "batch": batch, "height": height, "width": width, "samples": n,
        "loader_procs": procs,
    }
    for preset in (None, "f32", "bf16", "u8"):
        wire = WireFormat.from_config(preset, clip=spec.clip,
                                      range=spec.range)
        adapter = spec.apply(Synth(n, height, width),
                             normalize=wire is None).jax(wire=wire)
        loader = adapter.loader(batch_size=batch, shuffle=False,
                                procs=procs)

        t0 = time.perf_counter()
        decoded, last = 0, None
        for b in loader:
            decoded += b[0].shape[0]
            last = b
        dt = time.perf_counter() - t0

        samples = [adapter[i] for i in range(min(batch, n))]
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            minput.collate(samples)
        collate_ms = (time.perf_counter() - t0) / reps * 1e3

        wire_batch = (last[:4] if wire is None
                      else wire.encode_batch(last[:4]))
        wire_mb = sum(a.nbytes for a in wire_batch
                      if a is not None) / 2 ** 20

        result[preset or "host-f32"] = {
            "samples_per_sec": round(decoded / dt, 2),
            "collate_ms": round(collate_ms, 2),
            "wire_mb_per_step": round(wire_mb, 3),
        }
        _emit(result)

    # augmentation arms (PR 19): the same raw source decoded three ways —
    # "host" augments inside the decode path (seeded-Generator numpy
    # transforms), "device" ships raw batches and runs the jitted
    # DeviceAugment pipeline on the accelerator, "synth" renders
    # exact-flow pairs on device and never decodes at all. samples/s is
    # end-to-end; data_wait_share is the fraction of wall time spent
    # outside device compute (what a training step would stall on).
    from raft_meets_dicl_tpu.data import augment as haug
    from raft_meets_dicl_tpu.data import synth as dsynth
    from raft_meets_dicl_tpu.data.device_augment import DeviceAugment

    def _collate_ms(adapter):
        samples = [adapter[i] for i in range(min(batch, n))]
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            minput.collate(samples)
        return (time.perf_counter() - t0) / reps * 1e3

    host_src = haug.Augment(
        [haug.ColorJitter(0.2, 0.4, 0.4, 0.4, 0.1),
         haug.Flip([0.5, 0.1]),
         haug.NoiseNormal([0.0, 0.02]),
         haug.OcclusionForward(0.5, [1, 3], [10, 10], [30, 30])],
        Synth(n, height, width), sync=True)
    adapter = spec.apply(host_src, normalize=True).jax()
    loader = adapter.loader(batch_size=batch, shuffle=False, procs=procs)
    t0 = time.perf_counter()
    decoded = 0
    for b in loader:
        decoded += b[0].shape[0]
    dt = time.perf_counter() - t0
    result["augment"] = "host"
    result["host-augment"] = {
        "samples_per_sec": round(decoded / dt, 2),
        "collate_ms": round(_collate_ms(adapter), 2),
        "data_wait_share": 1.0,
    }
    _emit(result)

    dev = DeviceAugment(occlusion_size=(10, 30))
    dev_fn = jax.jit(lambda ids, a, b, f, v: dev.apply(
        dev.batch_keys(ids, 0), a, b, f, v))
    adapter = spec.apply(Synth(n, height, width), normalize=True).jax()
    loader = adapter.loader(batch_size=batch, shuffle=False, procs=procs)
    warm = [jnp.asarray(a) for a in next(iter(loader))[:4]]
    jax.block_until_ready(dev_fn(
        jnp.arange(warm[0].shape[0], dtype=jnp.uint32), *warm))
    t0 = time.perf_counter()
    decoded, device_s = 0, 0.0
    for i, b in enumerate(loader):
        arrs = [jnp.asarray(a) for a in b[:4]]
        ids = jnp.arange(i * batch, i * batch + arrs[0].shape[0],
                         dtype=jnp.uint32)
        t1 = time.perf_counter()
        out = dev_fn(ids, *arrs)
        jax.block_until_ready(out)
        device_s += time.perf_counter() - t1
        decoded += arrs[0].shape[0]
    dt = time.perf_counter() - t0
    result["augment"] = "device"
    result["device-augment"] = {
        "samples_per_sec": round(decoded / dt, 2),
        "collate_ms": round(_collate_ms(adapter), 2),
        "device_ms_per_batch": round(
            device_s / max(1, decoded // batch) * 1e3, 2),
        "data_wait_share": round(max(0.0, 1.0 - device_s / dt), 4),
    }
    _emit(result)

    render = jax.jit(lambda k: dsynth.render_pair(k, (height, width)))
    k0 = jax.random.PRNGKey(0)
    jax.block_until_ready(render(k0))
    t0 = time.perf_counter()
    for i in range(n):
        out = render(jax.random.fold_in(k0, i))
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    result["augment"] = "synth"
    result["synth-source"] = {
        "samples_per_sec": round(n / dt, 2),
        "collate_ms": 0.0,
        "data_wait_share": 0.0,
    }
    _emit(result)
    return result


def _bench_eval():
    """Shape-bucketed evaluation benchmark (``BENCH_EVAL=1``): a synthetic
    mixed-resolution eval set (three distinct raw shapes, KITTI-style) run
    through (a) the batch-1 unbucketed baseline — one jit compile per
    distinct padded shape — and (b) the bucketed pipeline (ShapeBuckets +
    shape-grouping loader + partial-batch padding + precompile warmup).
    Reports samples/s end-to-end (compiles included: that is what a
    validation sweep costs), steady-state samples/s, compile counts, and
    the pad-overhead ratio per preset. One cumulative JSON line per
    measurement; consumers read the last."""
    import jax

    from raft_meets_dicl_tpu import evaluation, telemetry
    from raft_meets_dicl_tpu.data.collection import (
        Metadata, SampleArgs, SampleId,
    )
    from raft_meets_dicl_tpu.models import input as minput
    import raft_meets_dicl_tpu.models as models

    # KITTI's per-image resolutions: many *slightly different* raw shapes
    # (375x1242, 370x1224, 374x1238, ...) — the baseline compiles one
    # program per distinct padded shape, bucketing quantizes them all
    # onto two canonical sizes
    cpu = jax.default_backend() == "cpu"
    if cpu:
        shapes = [(64, 96), (64, 88), (64, 80), (56, 88), (56, 80),
                  (56, 72), (48, 72), (48, 64)]
        bucket_sizes = [(64, 96), (56, 88)]
        per_shape = int(os.environ.get("BENCH_EVAL_SAMPLES", "6"))
        batch = int(os.environ.get("BENCH_EVAL_BATCH", "4"))
        iters = 2
        model_params = {"corr-levels": 2, "corr-radius": 2,
                        "corr-channels": 32, "context-channels": 16,
                        "recurrent-channels": 16}
    else:
        shapes = [(376, 1248), (376, 1232), (368, 1232), (368, 1224),
                  (360, 1224), (352, 1216)]
        bucket_sizes = [(376, 1248), (368, 1232)]
        per_shape = int(os.environ.get("BENCH_EVAL_SAMPLES", "8"))
        batch = int(os.environ.get("BENCH_EVAL_BATCH", "8"))
        iters = 12
        model_params = {}

    spec = models.load({
        "name": "bench-eval", "id": "bench-eval",
        "model": {"type": "raft/baseline", "parameters": model_params,
                  "arguments": {"iterations": iters}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    })
    model = spec.model

    class Synth:
        """Mixed-shape raw samples, round-robin over the shape list."""

        def __init__(self, shapes, per_shape):
            self.items = [s for s in shapes for _ in range(per_shape)]

        def __getitem__(self, index):
            h, w = self.items[index]
            rng = np.random.RandomState(index)
            img1 = rng.rand(1, h, w, 3).astype(np.float32)
            img2 = rng.rand(1, h, w, 3).astype(np.float32)
            flow = rng.randn(1, h, w, 2).astype(np.float32)
            valid = np.ones((1, h, w), bool)
            meta = [Metadata(True, "synth-mixed",
                             SampleId(f"s{index}", SampleArgs(), SampleArgs()),
                             ((0, h), (0, w)))]
            return img1, img2, flow, valid, meta

        def __len__(self):
            return len(self.items)

    source = Synth(shapes, per_shape)
    init = source[0]
    variables = model.init(jax.random.PRNGKey(0), init[0], init[1])

    buckets = minput.ShapeBuckets(bucket_sizes)

    def sweep(buckets, batch_size, pad_to=None, precompile=False, label=""):
        tele = telemetry.get()
        tail0 = len(getattr(tele, "events", ()))
        loader = spec.input.apply(source, buckets=buckets).jax().loader(
            batch_size=batch_size, shuffle=False,
            group_by_shape=buckets is not None, num_workers=2)
        stats = evaluation.EvalRunStats(name=label)
        fn = evaluation.make_eval_fn(model, None)
        t0 = time.perf_counter()
        if precompile:
            evaluation.warmup_eval_fn(fn, variables, buckets.sizes,
                                      pad_to or batch_size, stats=stats)
        epe_sum = n = 0.0
        for s in evaluation.evaluate(model, variables, loader, eval_fn=fn,
                                     show_progress=False, pad_to=pad_to,
                                     stats=stats):
            err = np.linalg.norm(s.final - s.target, axis=-1)
            epe_sum += float(err[np.asarray(s.valid, bool)].mean())
            n += 1
        wall = time.perf_counter() - t0
        # steady state: the sweep minus compile/warmup cost — what a
        # second epoch over the same buckets would cost
        tail = getattr(tele, "events", [])[tail0:]
        compile_s = sum(e["seconds"] for e in tail
                        if e["kind"] == "compile"
                        and e.get("label") == "eval_step")
        warm = stats.phases.get("warmup", 0.0)
        steady = max(wall - max(warm, compile_s), 1e-9)
        return {
            "samples": int(n),
            "samples_per_sec": round(n / wall, 3),
            "samples_per_sec_steady": round(n / steady, 3),
            "compiled_shapes": stats.compiles,
            "compile_s": round(compile_s, 3),
            "batches": stats.batches,
            "pad_waste_ratio": round(stats.pad_waste_ratio(), 4),
            "mean_epe": round(epe_sum / max(n, 1), 5),
            "wall_s": round(wall, 3),
        }

    result = {
        "metric": "eval-throughput-mixed-shapes",
        "backend": jax.default_backend(),
        "shapes": [f"{h}x{w}" for h, w in shapes],
        "samples": len(source), "batch": batch,
        "buckets": [f"{h}x{w}" for h, w in buckets.sizes],
    }

    # (a) baseline: batch 1, no bucketing — one compile per distinct shape
    evaluation._EVAL_FN_CACHE.clear()
    result["baseline_b1"] = sweep(None, 1, label="baseline-b1")
    _emit(result)

    # (b) bucketed: grouped full batches, remainder padding, warm buckets
    evaluation._EVAL_FN_CACHE.clear()
    result["bucketed"] = sweep(buckets, batch, pad_to=batch,
                               precompile=True, label="bucketed")
    result["speedup_end_to_end"] = round(
        result["bucketed"]["samples_per_sec"]
        / max(result["baseline_b1"]["samples_per_sec"], 1e-9), 2)
    result["speedup_steady"] = round(
        result["bucketed"]["samples_per_sec_steady"]
        / max(result["baseline_b1"]["samples_per_sec_steady"], 1e-9), 2)
    result["epe_rel_diff"] = round(
        abs(result["bucketed"]["mean_epe"] - result["baseline_b1"]["mean_epe"])
        / max(abs(result["baseline_b1"]["mean_epe"]), 1e-9), 6)
    _emit(result)
    return result


def _bench_serve():
    """Serving-path benchmark (``BENCH_SERVE=1``): an open-loop synthetic
    request stream over 8 mixed resolutions through the continuous-batching
    scheduler (serve/). Three phases: (1) a cold replica — the warm pool
    pays at most one compile per bucket up front, then the whole stream
    (partial batches included: they pad-tile onto the full batch's program)
    serves with zero further compiles; (2) a warm-pool prebuild exporting
    AOT artifacts for every (model, bucket, wire) triple into a fresh
    store; (3) a fresh replica against that store — prepared with zero
    compiles (AOT hits only) and serving the full stream the same way.
    Budget permitting, a fourth phase streams fast-class requests
    through a ladder'd replica on the quantized matching tier
    (``BENCH_SERVE_QUANT``, default u8; see ``ops.quant``), and a fifth
    runs the serving-fleet kill/rejoin drill (two video replicas behind
    the router, skewed mix + sticky stream, one replica hard-killed
    mid-stream and rejoining warm from the published AOT store;
    ``BENCH_FLEET_FRAMES`` sizes the stream). Reports p50/p99 latency,
    wall + steady-state pairs/s, and shed/error counts; every phase row
    carries a ``quant`` field. One cumulative JSON line per phase;
    consumers read the last."""
    import shutil
    import tempfile

    import jax

    from raft_meets_dicl_tpu import compile as programs
    from raft_meets_dicl_tpu import evaluation, serve, telemetry
    from raft_meets_dicl_tpu.models import input as minput
    from raft_meets_dicl_tpu.models import wire as mwire
    import raft_meets_dicl_tpu.models as models

    cpu = jax.default_backend() == "cpu"
    if cpu:
        shapes = [(64, 96), (64, 88), (64, 80), (56, 88), (56, 80),
                  (56, 72), (48, 72), (48, 64)]
        bucket_sizes = [(64, 96), (56, 88)]
        batch = int(os.environ.get("BENCH_SERVE_BATCH", "4"))
        requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "24"))
        rate = float(os.environ.get("BENCH_SERVE_RATE", "50"))
        iters = 2
        model_params = {"corr-levels": 2, "corr-radius": 2,
                        "corr-channels": 32, "context-channels": 16,
                        "recurrent-channels": 16}
    else:
        shapes = [(376, 1248), (376, 1232), (368, 1232), (368, 1224),
                  (360, 1224), (352, 1216), (368, 1248), (360, 1232)]
        bucket_sizes = [(376, 1248), (368, 1232)]
        batch = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
        requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "64"))
        rate = float(os.environ.get("BENCH_SERVE_RATE", "20"))
        iters = 12
        model_params = {}

    model_cfg = {
        "name": "bench-serve", "id": "bench-serve",
        "model": {"type": "raft/baseline", "parameters": model_params,
                  "arguments": {"iterations": iters}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    wire_name = os.environ.get("BENCH_SERVE_WIRE", "u8")
    wire = mwire.WireFormat.from_config(wire_name)

    def run_phase(quant=None, ladder=None, classes=None):
        # a fresh replica each time: new model spec, new session — the
        # only thing phases may share is the AOT store on disk
        tele = telemetry.get()
        spec = models.load(model_cfg)
        session = serve.ServeSession(
            spec, minput.ShapeBuckets(bucket_sizes), wire=wire,
            batch_size=batch, ladder=ladder, quant=quant)
        t0 = time.perf_counter()
        outcomes = session.warm_pool()
        warm_s = time.perf_counter() - t0
        mark = len(getattr(tele, "events", ()))
        sched = serve.Scheduler(session, max_wait_ms=20.0,
                                queue_limit=64).start()
        if not sched.slo:
            # no RMD_SLO_* knobs set: pin a bench-local default target so
            # the attainment/burn columns always render
            from raft_meets_dicl_tpu.telemetry import slo as rmd_slo
            sched.slo = rmd_slo.SLOTracker(
                class_targets={"": float(os.environ.get(
                    "BENCH_SERVE_SLO_MS", "250"))},
                objective=0.99, window_s=300.0)
        report = serve.loadgen.run_open_loop(
            sched, shapes, requests=requests, rate_hz=rate,
            classes=classes)
        slo_snap = sched.slo.snapshot()
        trace_snap = sched.trace_summary.snapshot()
        sched.stop(drain=True)
        tail = getattr(tele, "events", [])[mark:]
        labels = ("eval_step", "rung_step") if ladder else ("eval_step",)
        serve_compiles = [e for e in tail if e["kind"] == "compile"
                          and e.get("label") in labels]
        compile_s = sum(e["seconds"] for e in serve_compiles)
        steady = max(report["wall_s"] - compile_s, 1e-9)
        return {
            "quant": session.quant,
            "completed": report["completed"],
            "rejected": report["rejected"],
            "errors": report["errors"],
            "wall_s": report["wall_s"],
            "pairs_per_sec": report["pairs_per_sec"],
            "pairs_per_sec_steady": round(report["completed"] / steady, 3),
            "p50_ms": report["p50_ms"],
            "p99_ms": report["p99_ms"],
            "spans_ms": report["spans_ms"],
            # per-class SLO attainment over the stream + the slowest-decile
            # critical-path breakdown (queue vs batch-formation vs device)
            "slo": {(k or "default"): {
                "target_ms": s["target_ms"],
                "attainment": s["attainment"],
                "burn_rate": s["burn_rate"],
            } for k, s in slo_snap.items()},
            "classes": {(k or "default"): c
                        for k, c in trace_snap["classes"].items()},
            "tail": trace_snap["tail"],
            # zero expected in every phase: partial batches ride the full
            # batch's compiled program, so serving never compiles
            "serve_compiles": len(serve_compiles),
            "warm_pool": {
                "compiles": sum(o["compiles"] for o in outcomes),
                "aot_hits": sum(o["aot_hits"] for o in outcomes),
                "aot_saves": sum(o["aot_saves"] for o in outcomes),
                "seconds": round(warm_s, 3),
            },
        }

    result = {
        "metric": "serve-throughput-mixed-shapes",
        "backend": jax.default_backend(),
        "shapes": [f"{h}x{w}" for h, w in shapes],
        "buckets": [f"{h}x{w}" for h, w in bucket_sizes],
        "batch": batch, "requests": requests, "rate_hz": rate,
        "wire": wire_name,
    }
    budget_s = float(os.environ.get("BENCH_SERVE_BUDGET_S", "900"))
    t_start = time.monotonic()

    # phase 1: cold replica, no AOT store — at most one compile per bucket
    programs.disable_aot()
    programs.reset()
    evaluation._EVAL_FN_CACHE.clear()
    result["cold"] = run_phase()
    _emit(result)

    # phases 2+3 replay the compile work against a fresh AOT store; skip
    # explicitly when the cold phase already ate the budget rather than
    # letting an external timeout kill the run (BENCH rc=124 discipline)
    elapsed = time.monotonic() - t_start
    if 2.5 * elapsed > budget_s:
        result["prebuild_skipped"] = f"budget ({elapsed:.0f}s elapsed)"
        print(f"SKIPPED prebuild/warm-replica: budget "
              f"({elapsed:.0f}s of {budget_s:.0f}s used)", flush=True)
        _emit(result)
        return result

    tmp = tempfile.mkdtemp(prefix="bench-serve-aot-")
    try:
        # phase 2: prebuild — compile + AOT-export every triple
        programs.enable_aot(tmp)
        programs.reset()
        evaluation._EVAL_FN_CACHE.clear()
        spec = models.load(model_cfg)
        session = serve.ServeSession(
            spec, minput.ShapeBuckets(bucket_sizes), wire=wire,
            batch_size=batch)
        t0 = time.perf_counter()
        outcomes = session.warm_pool()
        result["prebuild"] = {
            "triples": len(outcomes),
            "compiles": sum(o["compiles"] for o in outcomes),
            "aot_saves": sum(o["aot_saves"] for o in outcomes),
            "seconds": round(time.perf_counter() - t0, 3),
        }
        _emit(result)

        # phase 3: fresh replica against the exported store — prepared and
        # serving the full stream with zero compiles
        programs.reset()
        evaluation._EVAL_FN_CACHE.clear()
        result["warm_replica"] = run_phase()
        result["zero_compile_serve"] = (
            result["warm_replica"]["warm_pool"]["compiles"] == 0
            and result["warm_replica"]["serve_compiles"] == 0)
        _emit(result)
    finally:
        programs.disable_aot()
        shutil.rmtree(tmp, ignore_errors=True)

    # phase 4 (budget permitting): the quantized fast class — a fresh
    # ladder'd replica on the quant matching tier (BENCH_SERVE_QUANT,
    # default u8; 'off' skips), streaming fast-class requests — the
    # class the tier exists for. Every phase row carries a ``quant``
    # field; only this one is non-null.
    from raft_meets_dicl_tpu.ops import quant as quant_ops

    qmode = quant_ops.normalize_mode(
        os.environ.get("BENCH_SERVE_QUANT", "u8"))
    elapsed = time.monotonic() - t_start
    if qmode is not None:
        if elapsed * 4 / 3 > budget_s:
            result["quant_fast_skipped"] = (
                f"budget ({elapsed:.0f}s elapsed)")
            print(f"SKIPPED quant-fast phase: budget "
                  f"({elapsed:.0f}s of {budget_s:.0f}s used)", flush=True)
        else:
            programs.reset()
            evaluation._EVAL_FN_CACHE.clear()
            result["quant_fast"] = run_phase(
                quant=qmode,
                ladder=serve.LadderSpec(
                    rungs=(iters, 2 * iters, 3 * iters)),
                classes=["fast"])
        _emit(result)

    # phase 5 (budget permitting): the serving fleet (PR 20) — two video
    # replicas behind the router, a skewed bucket mix plus one sticky
    # stream, and the kill/rejoin chaos drill: a replica is hard-killed
    # mid-stream, every affected request ends in a result or a *typed*
    # shed, the stream pays at most one cold frame, and the rejoining
    # replica boots against the published AOT store with zero compiles.
    elapsed = time.monotonic() - t_start
    if elapsed * 2 > budget_s:
        result["fleet_skipped"] = f"budget ({elapsed:.0f}s elapsed)"
        print(f"SKIPPED fleet phase: budget "
              f"({elapsed:.0f}s of {budget_s:.0f}s used)", flush=True)
        _emit(result)
        return result

    from raft_meets_dicl_tpu import fleet as fleet_mod
    from raft_meets_dicl_tpu.serve.observe import Observer

    store = tempfile.mkdtemp(prefix="bench-serve-fleet-aot-")
    replicas = {}

    def boot_replica(index):
        programs.reset()
        evaluation._EVAL_FN_CACHE.clear()
        spec = models.load(model_cfg)
        session = serve.ServeSession(
            spec, minput.ShapeBuckets(bucket_sizes), wire=wire,
            batch_size=batch, video=True)
        outcomes = session.warm_pool()
        programs.publish(store)
        sched = serve.Scheduler(session, max_wait_ms=20.0,
                                queue_limit=64).start()
        obs = Observer(session, sched)
        server = fleet_mod.serve_replica(session, sched, obs, 0,
                                         index=index)
        return {"session": session, "scheduler": sched, "server": server,
                "compiles": sum(o["compiles"] for o in outcomes),
                "aot_hits": sum(o["aot_hits"] for o in outcomes)}

    try:
        programs.enable_aot(store)
        codec = fleet_mod.EdgeCodec(
            minput.ShapeBuckets(bucket_sizes), wire=wire)
        router = fleet_mod.Router(codec, retries=2)
        boot_compiles = {}
        for i in range(2):
            replicas[i] = boot_replica(i)
            boot_compiles[f"replica-{i}"] = replicas[i]["compiles"]
            router.add_replica(f"replica-{i}", replicas[i]["server"].url)

        def kill(owner):
            index = int(owner.rsplit("-", 1)[1]) if owner else 0
            name = f"replica-{index}"
            replicas[index]["server"].close()
            replicas[index]["scheduler"].stop(drain=False)
            router.mark_down(name, reason="drill kill")

            def rejoin():
                replicas[index] = boot_replica(index)
                router.add_replica(name, replicas[index]["server"].url)

            threading.Thread(target=rejoin, daemon=True).start()
            return name

        frames = int(os.environ.get("BENCH_FLEET_FRAMES", "16"))
        drill_report = fleet_mod.run_drill(
            router, kill, bucket_sizes, frames=frames,
            kill_after=frames // 3, background_per_frame=2,
            rejoin_wait_s=max(60.0, budget_s - (time.monotonic()
                                                - t_start)))
        router.stop()
        result["fleet"] = {
            "replicas": 2,
            "boot_compiles": boot_compiles,
            "drill": drill_report,
            "zero_compile_rejoin":
                drill_report["rejoin_compiles"] == 0,
        }
    finally:
        for rep in replicas.values():
            try:
                rep["server"].close()
                rep["scheduler"].stop(drain=False)
            except Exception:
                pass
        programs.disable_aot()
        shutil.rmtree(store, ignore_errors=True)
    _emit(result)
    return result


def _bench_ladder():
    """Iteration-ladder frontier (``BENCH_LADDER=1``): EPE-vs-latency
    across fixed recurrence budgets plus the adaptive policy, per model
    family.

    Synthetic constant-shift pairs (img2 is img1 rolled by a known
    offset) give an exact ground-truth flow, so EPE is measurable without
    a dataset. For each family: every fixed rung (4/8/12 iterations) is
    one compiled rung program timed over the eval set; the adaptive
    policy starts at the base rung and escalates through continuation
    programs while the batch's flow-delta norm exceeds a threshold.

    The threshold is *calibrated from the measurement itself*: at random
    init the delta signal never shrinks (untrained GRU updates don't
    converge), so a fixed production threshold would escalate every
    batch. Calibrating to an upper quantile (``BENCH_LADDER_PCTL``,
    default 90) of the measured base-rung deltas emulates the converged-
    model operating point — most requests stop at the base rung, the
    stragglers pay for continuation rungs — which is the regime the
    ladder is built for. ``adaptive.vs_full`` reports the latency ratio
    and EPE regression against the monolithic full budget — the
    acceptance frontier. One cumulative JSON line per family; consumers
    read the last.

    ``BENCH_LADDER_QUANT`` (default ``u8,i8``) appends quantized base
    rungs to each family's frontier — the fast class's serving point on
    the u8/i8 matching tier (``ops.quant``) — with the masked-metric EPE
    delta against the full-precision base rung, p50/p99 latency, and the
    correlation-volume bytes per step at each width. Every frontier row
    carries a ``quant`` field (``null`` = full precision)."""
    from raft_meets_dicl_tpu import evaluation, models
    from raft_meets_dicl_tpu.metrics import functional as mfunc
    from raft_meets_dicl_tpu.ops import quant as quant_ops

    cpu = jax.default_backend() == "cpu"
    rungs = tuple(int(r) for r in
                  os.environ.get("BENCH_LADDER_RUNGS", "4,8,12").split(","))
    pctl = float(os.environ.get("BENCH_LADDER_PCTL", "90"))
    if cpu:
        h, w, batch, n_batches = 64, 96, 2, 8
        tiny = {"corr-levels": 2, "corr-radius": 2, "corr-channels": 32,
                "context-channels": 16, "recurrent-channels": 16}
        families = [
            ("raft", {"type": "raft/baseline", "parameters": tiny}),
            ("raft_fs", {"type": "raft/fs", "parameters": tiny}),
            ("raft_dicl_sl", {"type": "raft+dicl/sl", "parameters": {
                "corr-radius": 2, "corr-channels": 16,
                "context-channels": 16, "recurrent-channels": 16}}),
        ]
    else:
        h, w, batch, n_batches = 384, 704, 2, 8
        families = [
            ("raft", {"type": "raft/baseline",
                      "parameters": {"mixed-precision": True}}),
            ("raft_fs", {"type": "raft/fs",
                         "parameters": {"mixed-precision": True}}),
            ("raft_dicl_sl", {"type": "raft+dicl/sl",
                              "parameters": {"mixed-precision": True}}),
        ]

    budget_s = float(os.environ.get("BENCH_LADDER_BUDGET_S", "900"))
    t_start = time.monotonic()
    increments = tuple(b - a for a, b in zip(rungs, rungs[1:]))

    # constant-shift ground truth: a different (dy, dx) per batch so the
    # adaptive policy sees per-batch variation
    shifts = [(2, 3), (1, -2), (-2, 1), (3, 2), (-1, -3), (2, -1),
              (1, 1), (-3, 2)]
    rng = np.random.RandomState(7)
    batches = []
    for i in range(n_batches):
        dy, dx = shifts[i % len(shifts)]
        i1 = rng.rand(batch, h, w, 3).astype(np.float32)
        i2 = np.roll(i1, (dy, dx), axis=(1, 2))
        gt = np.zeros((batch, h, w, 2), np.float32)
        gt[..., 0] = dx
        gt[..., 1] = dy
        batches.append((jnp.asarray(i1), jnp.asarray(i2), gt))

    def epe(flow, gt):
        d = np.asarray(flow, np.float32) - gt
        return float(np.mean(np.sqrt(np.sum(d * d, axis=-1))))

    def volume_bytes(levels, bytes_per_elem):
        # all-pairs pyramid at 1/8 feature resolution: level l is
        # (B, h8, w8, h8/2^l, w8/2^l); for raft_fs this is the upper
        # bound covering the materialized (non-windowed) suffix
        h8, w8 = h // 8, w // 8
        elems = sum(batch * h8 * w8 * (h8 >> l) * (w8 >> l)
                    for l in range(levels))
        return elems * bytes_per_elem

    result = {"metric": "ladder-frontier", "rungs": list(rungs),
              "shape": f"{batch}x{h}x{w}", "families": {}}
    for name, model_cfg in families:
        elapsed = time.monotonic() - t_start
        if result["families"] and elapsed > budget_s * 0.8:
            result["families"][name] = {
                "skipped": f"budget ({elapsed:.0f}s elapsed)"}
            _emit(result)
            continue
        spec = models.load({
            "name": name, "id": f"bench-ladder-{name}",
            "model": model_cfg, "loss": {"type": "raft/sequence"},
            "input": {"padding": {"type": "modulo", "mode": "zeros",
                                  "size": [8, 8]}}})
        model = spec.model
        variables = model.init(jax.random.PRNGKey(0), batches[0][0],
                               batches[0][1], iterations=1)

        progs = {}
        for k in rungs:
            progs[(k, False)] = evaluation.make_rung_fn(
                model, k, model_id=spec.id)
        for inc in sorted(set(increments)):
            progs[(inc, True)] = evaluation.make_rung_fn(
                model, inc, cont=True, model_id=spec.id)

        fam = {"frontier": [], "adaptive": {}}

        # fixed budgets: one program each, warmed then timed
        base_deltas = []
        for k in rungs:
            step = progs[(k, False)]
            flow, st = step(variables, *batches[0][:2])
            jax.block_until_ready(flow)
            times, errs = [], []
            for i1, i2, gt in batches:
                t0 = time.perf_counter()
                flow, st = step(variables, i1, i2)
                jax.block_until_ready(flow)
                times.append(time.perf_counter() - t0)
                errs.append(epe(flow, gt))
                if k == rungs[0]:
                    base_deltas.append(float(np.max(np.asarray(st["delta"]))))
            fam["frontier"].append({
                "iterations": k, "quant": None,
                "epe": round(sum(errs) / len(errs), 4),
                "mean_ms": round(1e3 * sum(times) / len(times), 3)})

        # quantized matching tier: the base rung — the fast class's
        # serving point — re-registered per mode with u8/i8 volumes
        # dequantized in-register by the lookup. EPE via the masked
        # metric (all-valid synthetic mask: the same number the
        # acceptance gate reads); p50/p99 because the tier exists for
        # latency-critical classes. The dicl families have no quant
        # path, so they report full-precision rows only.
        qmodes = [quant_ops.normalize_mode(m) for m in
                  os.environ.get("BENCH_LADDER_QUANT", "u8,i8").split(",")
                  if m.strip()]
        if not model_cfg["type"].startswith("raft/"):
            qmodes = []
        params = model_cfg.get("parameters", {})
        full_itemsize = 2 if params.get("mixed-precision") else 4
        levels = params.get("corr-levels", 4)
        base_epe = fam["frontier"][0]["epe"]
        for mode in [m for m in qmodes if m is not None]:
            qstep = evaluation.make_rung_fn(model, rungs[0],
                                            model_id=spec.id, quant=mode)
            flow, _ = qstep(variables, *batches[0][:2])
            jax.block_until_ready(flow)
            valid = jnp.ones((batch, h, w), bool)
            times, errs = [], []
            for i1, i2, gt in batches:
                t0 = time.perf_counter()
                flow, _ = qstep(variables, i1, i2)
                jax.block_until_ready(flow)
                times.append(time.perf_counter() - t0)
                errs.append(float(np.mean(np.asarray(
                    mfunc.end_point_error(flow, jnp.asarray(gt),
                                          valid)["mean"]))))
            ms = [1e3 * t for t in times]
            q_epe = sum(errs) / len(errs)
            fam["frontier"].append({
                "iterations": rungs[0], "quant": mode,
                "epe": round(q_epe, 4),
                "epe_delta_vs_full_precision": round(q_epe - base_epe, 4),
                "mean_ms": round(sum(ms) / len(ms), 3),
                "p50_ms": round(float(np.percentile(ms, 50)), 3),
                "p99_ms": round(float(np.percentile(ms, 99)), 3),
                "volume_bytes_per_step": volume_bytes(levels, 1),
                "volume_bytes_full_precision": volume_bytes(
                    levels, full_itemsize)})

        # adaptive: threshold at an upper quantile of the base-rung
        # deltas (see docstring — emulates the converged-model regime
        # where only straggler batches escalate)
        threshold = float(np.percentile(base_deltas, pctl))
        step0 = progs[(rungs[0], False)]
        for inc in sorted(set(increments)):
            s = progs[(inc, True)]
            flow, st = step0(variables, *batches[0][:2])
            flow, st = s(variables, *batches[0][:2], st["flow"],
                         st["hidden"])
            jax.block_until_ready(flow)
        times, errs, iters_run = [], [], []
        for i1, i2, gt in batches:
            t0 = time.perf_counter()
            flow, st = step0(variables, i1, i2)
            executed = rungs[0]
            for inc in increments:
                worst = float(np.max(np.asarray(st["delta"])))
                if worst <= threshold:
                    break
                flow, st = progs[(inc, True)](variables, i1, i2,
                                              st["flow"], st["hidden"])
                executed += inc
            jax.block_until_ready(flow)
            times.append(time.perf_counter() - t0)
            errs.append(epe(flow, gt))
            iters_run.append(executed)
        full = fam["frontier"][-1]
        adaptive_ms = 1e3 * sum(times) / len(times)
        adaptive_epe = sum(errs) / len(errs)
        fam["adaptive"] = {
            "quant": None,
            "threshold": round(threshold, 4),
            "epe": round(adaptive_epe, 4),
            "mean_ms": round(adaptive_ms, 3),
            "mean_iterations": round(sum(iters_run) / len(iters_run), 2),
            "vs_full": {
                "latency_ratio": round(adaptive_ms / full["mean_ms"], 4),
                "epe_regression": round(
                    (adaptive_epe - full["epe"]) / max(full["epe"], 1e-9),
                    4)},
        }
        result["families"][name] = fam
        _emit(result)


def _bench_video():
    """Streaming-video warm-start benchmark (``BENCH_VIDEO=1``): frames/s
    and EPE at fixed quality, cold vs warm, on synthetic constant-motion
    sequences.

    Each sequence drifts a random texture by a fixed (dy, dx) per frame
    (np.roll, exact ground truth). The cold arm runs every frame through
    the monolithic full-budget rung — the fixed-quality baseline the
    warm arm must match. The warm arm carries the previous frame's flow
    through the registered warm-start program at the bottom rung and
    escalates by the ladder's delta policy; the acceptance claim is that
    it reaches the cold arm's EPE with fewer mean iterations per frame
    (a frames/s uplift). The escalation threshold is calibrated like
    BENCH_LADDER's (upper ``BENCH_VIDEO_PCTL`` quantile of warm-entry
    deltas — random-init deltas never shrink, see _bench_ladder).

    A fw/bw occlusion-product measurement rides along: the doubled-batch
    dispatch's cost per frame plus the resulting occlusion ratio (~0 on
    constant motion away from frame edges). ``BENCH_VIDEO_DATA`` names a
    Sintel-layout frame directory to run instead of one synthetic
    sequence (no ground truth there, EPE omitted). One cumulative JSON
    line per stage; consumers read the last."""
    from raft_meets_dicl_tpu import models
    from raft_meets_dicl_tpu.serve.ladder import LadderSpec
    from raft_meets_dicl_tpu.video import (SequenceRunner, fw_bw_flows,
                                           fw_bw_products_batch)

    cpu = jax.default_backend() == "cpu"
    rungs = tuple(int(r) for r in
                  os.environ.get("BENCH_VIDEO_RUNGS", "4,8,12").split(","))
    pctl = float(os.environ.get("BENCH_VIDEO_PCTL", "90"))
    n_frames = int(os.environ.get("BENCH_VIDEO_FRAMES", "8"))
    budget_s = float(os.environ.get("BENCH_VIDEO_BUDGET_S", "900"))
    t_start = time.monotonic()
    if cpu:
        h, w, batch = 64, 96, 1
        model_cfg = {"type": "raft/baseline", "parameters": {
            "corr-levels": 2, "corr-radius": 2, "corr-channels": 32,
            "context-channels": 16, "recurrent-channels": 16}}
    else:
        h, w, batch = 384, 704, 1
        model_cfg = {"type": "raft/baseline",
                     "parameters": {"mixed-precision": True}}

    spec = models.load({
        "name": "bench-video", "id": "bench-video",
        "model": model_cfg, "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}}})
    model = spec.model

    # synthetic constant-motion sequences: exact per-pair ground truth
    motions = [(2, 3), (1, -2), (-2, 1)]
    rng = np.random.RandomState(7)
    sequences = []
    for dy, dx in motions:
        base = rng.rand(batch, h, w, 3).astype(np.float32)
        frames = [np.roll(base, (t * dy, t * dx), axis=(1, 2))
                  for t in range(n_frames)]
        gt = np.zeros((batch, h, w, 2), np.float32)
        gt[..., 0] = dx
        gt[..., 1] = dy
        sequences.append((frames, [gt] * (n_frames - 1)))

    # plus one layered-scene sequence from the synthetic scenario
    # generator (PR 19): coherent per-layer affine motion with exact
    # per-pair dense flow — the warm-start signal a roll-drift sequence
    # can't probe (flow varies across the frame and over time)
    from raft_meets_dicl_tpu.data import synth as dsynth

    imgs, flows, _ = dsynth.render_sequence(
        jax.random.PRNGKey(19), (h, w), frames=n_frames, motion=3.0)
    imgs = np.repeat(np.asarray(imgs)[:, None], batch, axis=1)
    flows = np.repeat(np.asarray(flows)[:, None], batch, axis=1)
    sequences.append(([imgs[t] for t in range(n_frames)],
                      [flows[t] for t in range(n_frames - 1)]))

    variables = model.init(jax.random.PRNGKey(0),
                           jnp.asarray(sequences[0][0][0]),
                           jnp.asarray(sequences[0][0][1]), iterations=1)

    # calibration pass: warm frames with escalation disabled, collect the
    # warm-entry delta signal the threshold quantile pins
    cal = SequenceRunner(
        model, variables, model_id=spec.id,
        ladder=LadderSpec(rungs=rungs, threshold=float("inf")))
    cal_run = cal.run(sequences[0][0], keep_flows=False)
    deltas = [float(np.max(np.asarray(f.carry["delta"])))
              for f in cal_run.frames if f.warm]
    threshold = float(np.percentile(deltas, pctl))

    runner = SequenceRunner(
        model, variables, model_id=spec.id,
        ladder=LadderSpec(rungs=rungs, threshold=threshold))

    # untimed warm-up: a tight-threshold pass escalates through every
    # continuation rung, so all programs either arm can touch are
    # compiled before the measured passes (same registry, shared
    # programs) — frames/s then measures serving, not compilation
    warmup = SequenceRunner(
        model, variables, model_id=spec.id,
        ladder=LadderSpec(rungs=rungs, threshold=1e-12))
    warmup.run(sequences[0][0][:3], keep_flows=False)

    result = {"metric": "video-warmstart", "rungs": list(rungs),
              "shape": f"{batch}x{h}x{w}", "frames": n_frames,
              "sequences": len(sequences),
              "threshold": round(threshold, 4), "arms": {}}

    def run_arm(warm):
        epes, its, fps, warm_frames = [], [], [], 0
        for frames, targets in sequences:
            run = runner.run(frames, targets=targets, warm=warm,
                             keep_flows=False)
            epes.append(run.mean_epe())
            its.append(run.mean_iterations())
            fps.append(run.frames_per_sec())
            warm_frames += run.warm_frames()
        return {
            "epe": round(sum(epes) / len(epes), 4),
            "mean_iterations": round(sum(its) / len(its), 2),
            "frames_per_sec": round(sum(fps) / len(fps), 3),
            "warm_frames": warm_frames,
        }

    result["arms"]["cold"] = run_arm(False)
    _emit(result)
    result["arms"]["warm"] = run_arm(True)
    cold, warmed = result["arms"]["cold"], result["arms"]["warm"]
    result["uplift"] = {
        "frames_per_sec_ratio": round(
            warmed["frames_per_sec"] / max(cold["frames_per_sec"], 1e-9),
            4),
        "iterations_ratio": round(
            warmed["mean_iterations"] / max(cold["mean_iterations"], 1e-9),
            4),
        "epe_regression": round(
            (warmed["epe"] - cold["epe"]) / max(cold["epe"], 1e-9), 4),
    }
    _emit(result)

    # fw/bw products: one doubled-batch dispatch on the full rung + the
    # host-side occlusion/confidence products
    if time.monotonic() - t_start < budget_s * 0.9:
        full = runner._full
        i1 = jnp.asarray(sequences[0][0][0])
        i2 = jnp.asarray(sequences[0][0][1])
        fw, bw = fw_bw_flows(full, variables, i1, i2)  # warm the shape
        jax.block_until_ready(fw)
        t0 = time.perf_counter()
        fw, bw = fw_bw_flows(full, variables, i1, i2)
        jax.block_until_ready(fw)
        dispatch_ms = 1e3 * (time.perf_counter() - t0)
        occ, conf = fw_bw_products_batch(np.asarray(fw), np.asarray(bw))
        result["fwbw"] = {
            "doubled_batch_ms": round(dispatch_ms, 3),
            "occlusion_ratio": round(float(occ.mean()), 5),
            "confidence_mean": round(float(conf.mean()), 5),
        }
        _emit(result)

    # optional Sintel-layout sequence (a directory of ordered frames);
    # no ground truth — the warm arm's iteration/fps accounting only
    data_dir = os.environ.get("BENCH_VIDEO_DATA")
    if data_dir:
        import glob

        import cv2

        paths = sorted(
            glob.glob(os.path.join(data_dir, "*.png"))
            + glob.glob(os.path.join(data_dir, "*.jpg")))[:n_frames]
        if len(paths) >= 2:
            imgs = []
            for p in paths:
                img = cv2.imread(p)[:, :, ::-1].astype(np.float32) / 255.0
                hh = img.shape[0] - img.shape[0] % 8
                ww = img.shape[1] - img.shape[1] % 8
                imgs.append(img[None, :hh, :ww])
            run = runner.run(imgs, keep_flows=False)
            result["sintel"] = {
                "frames": len(run.frames),
                "mean_iterations": round(run.mean_iterations(), 2),
                "frames_per_sec": round(run.frames_per_sec(), 3),
                "warm_frames": run.warm_frames(),
            }
        else:
            result["sintel"] = {"skipped": f"no frames in '{data_dir}'"}
        _emit(result)


def _bench_dicl():
    """Matching-phase breakdown (``BENCH_DICL=1``): window-sample ms (XLA
    gather vs fused Pallas sampler) and matching-net ms (per-level loop vs
    level-batched) at the ml hybrid's 1/8-resolution matching shape, plus
    the per-iteration matching-volume bytes each path moves. One JSON line
    per measurement group (cumulative; consumers read the last line)."""
    import jax
    import jax.numpy as jnp

    from raft_meets_dicl_tpu.models.common.corr.common import sample_window
    from raft_meets_dicl_tpu.models.common.grid import coordinate_grid
    from raft_meets_dicl_tpu.models.impls.raft_dicl_ml import (
        MlCorrelationModule,
    )
    from raft_meets_dicl_tpu.ops.pallas import sample_window_fused

    cpu = jax.default_backend() == "cpu"
    if cpu:
        batch, height, width, c, levels, radius, reps = 1, 64, 128, 8, 2, 2, 3
    else:
        batch = int(os.environ.get("BENCH_BATCH", "6"))
        height = int(os.environ.get("BENCH_HEIGHT", "384"))
        width = int(os.environ.get("BENCH_WIDTH", "704"))
        c, levels, radius, reps = 32, 4, 4, 10
    hc, wc = height // 8, width // 8

    rng = np.random.RandomState(0)
    fmap1 = tuple(jnp.asarray(rng.randn(batch, hc, wc, c), jnp.float32)
                  for _ in range(levels))
    fmap2 = tuple(
        jnp.asarray(rng.randn(batch, hc // 2 ** i, wc // 2 ** i, c),
                    jnp.float32)
        for i in range(levels))
    coords = coordinate_grid(batch, hc, wc) + jnp.asarray(
        rng.randn(batch, hc, wc, 2) * 2, jnp.float32)

    def timed(fn, *args):
        f = jax.jit(fn)
        float(f(*args))  # compile + sync (value transfer, see _measure)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*args)
        float(out)
        return round((time.perf_counter() - t0) / reps * 1e3, 3)

    result = {
        "metric": "dicl-matching-breakdown",
        "batch": batch, "height": height, "width": width,
        "levels": levels, "radius": radius, "channels": c,
        "backend": jax.default_backend(),
    }

    def sample_all(sampler, f2s):
        return sum(
            jnp.sum(sampler(f2, coords / 2 ** i, radius))
            for i, f2 in enumerate(f2s))

    def sample_all_grad(sampler, f2s):
        return sum(jnp.sum(jnp.abs(g)) for g in jax.grad(
            lambda fs: sample_all(sampler, fs))(f2s))

    result["window_sample_ms"] = {
        "xla": timed(lambda fs: sample_all(sample_window, fs), fmap2),
        "fused": timed(lambda fs: sample_all(sample_window_fused, fs), fmap2),
        "xla_fwd_bwd": timed(
            lambda fs: sample_all_grad(sample_window, fs), fmap2),
        "fused_fwd_bwd": timed(
            lambda fs: sample_all_grad(sample_window_fused, fs), fmap2),
    }
    _emit(result)

    # matching nets: reference per-level loop vs the level-batched call,
    # on identical parameters (bf16 matching like the mixed policy)
    from raft_meets_dicl_tpu import telemetry
    tele = telemetry.get()
    for share in (False, True):
        m = MlCorrelationModule(feature_dim=c, levels=levels, radius=radius,
                                share=share, dtype=jnp.bfloat16)
        v = m.init(jax.random.PRNGKey(0), fmap1, fmap2, coords)

        def fwd(v, fast, m=m):
            return jnp.sum(jnp.abs(m.apply(
                v, fmap1, fmap2, coords, train=True, frozen_bn=True,
                fast=fast)))

        def fwd_bwd(v, fast, m=m):
            return jax.grad(lambda p: fwd({**v, "params": p}, fast))(
                v["params"])["MatchingNet_0"]["Conv_0"]["bias"].sum()

        key = "shared" if share else "per_level_params"
        result[f"matching_net_ms_{key}"] = {
            "loop": timed(lambda vv: fwd(vv, False), v),
            "batched": timed(lambda vv: fwd(vv, True), v),
            "loop_fwd_bwd": timed(lambda vv: fwd_bwd(vv, False), v),
            "batched_fwd_bwd": timed(lambda vv: fwd_bwd(vv, True), v),
        }
        _emit(result)

    # per-iteration matching-volume bytes (bf16 fast path vs f32 stacked
    # reference): window + f1 in matching dtype vs the 2C stacked volume
    win = batch * (2 * radius + 1) ** 2 * hc * wc * c
    f1b = batch * hc * wc * c
    result["matching_volume_bytes"] = {
        "fast_bf16_unstacked": levels * (win + f1b) * 2,
        "reference_f32_stacked": levels * 2 * win * 4,
    }
    if tele.enabled:
        result["telemetry_events"] = tele.counts()
    _emit(result)
    return result


def _bench_spmd():
    """SPMD scale-out benchmark (``BENCH_SPMD=1``): step time and
    per-chip param/opt-state bytes across mesh shapes on the 8-device
    virtual CPU topology — the replicated 1-D baseline ``(8,1)`` against
    partitioned ``(4,2)`` / ``(2,4)`` meshes (params + Adam moments
    sharded over ``model`` per parallel.partition's rules), plus in-step
    gradient accumulation (``accumulate=2``). Re-execs itself onto a
    virtual 8-device CPU backend when the current backend is smaller
    (same trick as ``__graft_entry__.dryrun_multichip``; the parent has
    initialised its backend by then and holds the chip, harmless because
    the child is forced onto the CPU platform and never asks for it).
    One cumulative JSON line per measurement; consumers read the last."""
    if jax.device_count() < 8:
        import re
        import subprocess
        import sys

        if os.environ.get("_BENCH_SPMD_CHILD"):
            raise RuntimeError(
                f"BENCH_SPMD child still sees {jax.device_count()} devices "
                "— platform forcing failed")

        repo = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        env["_BENCH_SPMD_CHILD"] = "1"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.path.insert(0, {repo!r}); "
            "import bench; bench._bench_spmd()"
        )
        rc = subprocess.run([sys.executable, "-c", code], env=env,
                            cwd=repo).returncode
        if rc != 0:
            raise RuntimeError(f"BENCH_SPMD subprocess failed (rc={rc})")
        return None

    import optax

    import raft_meets_dicl_tpu.models as models
    from raft_meets_dicl_tpu import parallel

    batch, height, width, iters = 8, 64, 96, 2
    steps = int(os.environ.get("BENCH_STEPS", "3"))
    # elapsed budget: measurements run cheapest-signal-first and later
    # configs are skipped (marked explicitly) rather than letting an
    # external timeout kill the whole run — same discipline as
    # dryrun_multichip's RMD_DRYRUN_BUDGET_S
    budget_s = float(os.environ.get("BENCH_SPMD_BUDGET_S", "420"))
    t_start = time.monotonic()

    spec = models.load({
        "name": "bench-spmd", "id": "bench-spmd",
        "model": {"type": "raft/baseline", "parameters": {}},
        "loss": {"type": "raft/sequence"}, "input": None,
    })
    model, loss = spec.model, spec.loss

    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, height, width, 3)), jnp.zeros((1, height, width, 3)),
        iterations=1)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))

    def measure(mesh_spec, accumulate=1):
        # fresh fixed-seed data per measurement so the cross-mesh loss
        # comparison is apples to apples
        rng = np.random.RandomState(0)
        mesh = parallel.make_mesh(mesh_spec)
        part = parallel.Partitioner(mesh)
        state = part.shard_state(parallel.TrainState.create(variables, tx))
        step = parallel.make_train_step(
            model, loss, tx, mesh=mesh, model_args={"iterations": iters},
            state_sharding=part.state_shardings(state),
            accumulate=accumulate, donate=False)

        b = batch * accumulate
        img1 = jnp.asarray(rng.rand(b, height, width, 3), jnp.float32)
        img2 = jnp.asarray(rng.rand(b, height, width, 3), jnp.float32)
        flow = jnp.asarray(rng.randn(b, height, width, 2), jnp.float32)
        valid = jnp.ones((b, height, width), bool)
        bt = parallel.shard_batch((img1, img2, flow, valid), mesh)

        t0 = time.perf_counter()
        state, aux = step(state, *bt)
        float(aux["loss"])
        warm = time.perf_counter() - t0

        t0 = time.perf_counter()
        for _ in range(steps):
            state, aux = step(state, *bt)
        loss_val = float(aux["loss"])
        dt = (time.perf_counter() - t0) / steps

        rep = part.report(state)
        return {
            "mesh": rep["mesh"],
            "accumulate": accumulate,
            "loss": round(loss_val, 5),
            "step_ms": round(dt * 1e3, 2),
            "pairs_per_sec": round(b / dt, 3),
            "warmup_s": round(warm, 2),
            "params_mib_per_chip": round(
                rep["params_bytes_per_chip"] / 2 ** 20, 3),
            "opt_mib_per_chip": round(
                rep["opt_bytes_per_chip"] / 2 ** 20, 3),
            "params_mib_replicated": round(
                rep["params_bytes_replicated"] / 2 ** 20, 3),
            "opt_mib_replicated": round(
                rep["opt_bytes_replicated"] / 2 ** 20, 3),
            "params_sharded_leaves": rep["params_sharded_leaves"],
        }

    result = {
        "metric": "spmd-mesh-shapes",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "batch": batch, "height": height, "width": width,
        "iterations": iters, "steps": steps,
    }
    slowest = 0.0
    for key, mesh_spec, acc in (("mesh_8x1", (8, 1), 1),
                                ("mesh_4x2", (4, 2), 1),
                                ("mesh_2x4", (2, 4), 1),
                                ("mesh_4x2_accum2", (4, 2), 2)):
        elapsed = time.monotonic() - t_start
        if result and elapsed + 1.5 * max(slowest, 30.0) > budget_s:
            result[f"{key}_skipped"] = f"budget ({elapsed:.0f}s elapsed)"
            _emit(result)
            continue
        t0 = time.monotonic()
        result[key] = measure(mesh_spec, acc)
        slowest = max(slowest, time.monotonic() - t0)
        _emit(result)

    base = result.get("mesh_8x1")
    for key in ("mesh_4x2", "mesh_2x4"):
        m = result.get(key)
        if base is None or m is None:
            continue
        result[f"{key}_hbm_ratio"] = round(
            (m["params_mib_per_chip"] + m["opt_mib_per_chip"])
            / max(base["params_mib_per_chip"] + base["opt_mib_per_chip"],
                  1e-9), 4)
        result[f"{key}_loss_rel_diff"] = round(
            abs(m["loss"] - base["loss"]) / max(abs(base["loss"]), 1e-9), 6)
    _emit(result)
    return result


def _bench_compile_child():
    """One BENCH_COMPILE scenario, in a fresh process (jit caches are
    process-local, so cold/warm can only be compared across processes).

    ``_BENCH_COMPILE_CHILD`` selects the workload (``train`` | ``eval``);
    the parent controls cache state via RMD_NO_COMPILE_CACHE /
    RMD_COMPILE_CACHE / RMD_AOT / RMD_AOT_DIR. Prints one JSON line:
    ``time_to_first_step_s`` is the step-warmup window — program build,
    tracing, compilation or artifact load, first dispatch, sync — i.e.
    exactly the cost the registry/AOT store addresses; ``setup_s``
    (model load + init + data) and ``total_s`` give the full boot for
    context.
    """
    mode = os.environ["_BENCH_COMPILE_CHILD"]

    import optax

    import raft_meets_dicl_tpu.models as models
    from raft_meets_dicl_tpu import (
        compile as programs, evaluation, parallel, telemetry,
    )
    from raft_meets_dicl_tpu.utils.compcache import enable_persistent_cache

    enable_persistent_cache()
    programs.enable_aot()
    telemetry.activate(telemetry.create())
    # wall-clock ledger from process start: the emitted line's goodput
    # block is the compile-vs-productive split the scenarios compare
    from raft_meets_dicl_tpu.telemetry import goodput
    goodput.activate()

    cpu = jax.default_backend() == "cpu"
    if cpu:
        batch, height, width, iters = 2, 64, 96, 4
        params = {"corr-levels": 2, "corr-radius": 2, "corr-channels": 32,
                  "context-channels": 16, "recurrent-channels": 16}
    else:
        batch = int(os.environ.get("BENCH_BATCH", "6"))
        height = int(os.environ.get("BENCH_HEIGHT", "400"))
        width = int(os.environ.get("BENCH_WIDTH", "720"))
        iters = int(os.environ.get("BENCH_ITERS", "12"))
        params = {"mixed-precision": True}

    spec = models.load({
        "name": "bench-compile", "id": "bench-compile",
        "model": {"type": "raft/baseline", "parameters": params},
        "loss": {"type": "raft/sequence"}, "input": None,
    })
    model, loss = spec.model, spec.loss

    rng = np.random.RandomState(0)
    t_boot = time.perf_counter()
    if mode == "train":
        img = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
        flow = jnp.asarray(rng.randn(batch, height, width, 2), jnp.float32)
        valid = jnp.ones((batch, height, width), bool)
        variables = model.init(jax.random.PRNGKey(0), img[:1], img[:1],
                               iterations=1)
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(4e-4))
        state = parallel.TrainState.create(variables, tx)
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        key = programs.ProgramKey(
            kind="train_step", model="bench-compile",
            flags=programs.flag_items(shape=(batch, height, width),
                                      iterations=iters))
        step = parallel.make_train_step(model, loss, tx,
                                        model_args={"iterations": iters},
                                        key=key)
        state, aux = step(state, img, img, flow, valid)
        float(aux["loss"])
        prog = step
    else:
        # bucketed eval: warmup over two bucket shapes + one real batch
        shapes = [(height, width), (height - 8, width - 16)]
        variables = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, height, width, 3)), jnp.zeros((1, height, width, 3)),
            iterations=1)
        img = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
        jax.block_until_ready(jax.tree.leaves(variables)[0])
        t0 = time.perf_counter()
        fn = evaluation.make_eval_fn(model, {"iterations": iters},
                                     model_id="bench-compile")
        evaluation.warmup_eval_fn(fn, variables, shapes, batch)
        out = fn(variables, img, img)
        jax.block_until_ready(out[1])
        prog = fn
    t_end = time.perf_counter()
    tts = t_end - t0

    tele = telemetry.get()
    _emit({
        "mode": mode,
        "backend": jax.default_backend(),
        "time_to_first_step_s": round(tts, 3),
        "setup_s": round(t0 - t_boot, 3),
        "total_s": round(t_end - t_boot, 3),
        "compiles": prog.compiles,
        "compile_s": round(prog.compile_seconds, 3),
        "compile_events": tele.counts().get("compile", 0),
        "cache_hits": sum(1 for e in getattr(tele, "events", ())
                          if e["kind"] == "cache" and e["event"] == "hit"),
        "aot_hits": prog.aot_hits,
        "aot_saves": prog.aot_saves,
        "aot_fallbacks": prog.aot_fallbacks,
    })


def _bench_compile():
    """Cold-start benchmark (``BENCH_COMPILE=1``): time-to-first-step for
    the train step and the bucketed eval path under three boot regimes —
    (a) cold (no caches at all), (b) persistent-compile-cache warm
    (tracing + cache lookup, no backend compile), (c) AOT warm
    (deserialized executables, no tracing, zero compiles). Each regime
    runs in a fresh subprocess against a temp cache/program directory; a
    ``populate`` run in between fills both stores. One cumulative JSON
    line per measurement; consumers read the last."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench-compile-")
    cache_dir = os.path.join(tmp, "cache")
    aot_dir = os.path.join(tmp, "programs")

    def run_child(mode, scenario):
        env = dict(os.environ)
        env.pop("BENCH_COMPILE", None)
        env["_BENCH_COMPILE_CHILD"] = mode
        # the scenarios need a cache they control: cold means empty
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env["RMD_COMPILE_CACHE"] = cache_dir
        env["RMD_AOT_DIR"] = aot_dir
        if scenario == "cold":
            env["RMD_NO_COMPILE_CACHE"] = "1"
            env["RMD_AOT"] = "0"
        elif scenario == "populate":
            env["RMD_AOT"] = "1"
        elif scenario == "warm_cache":
            env["RMD_AOT"] = "0"
        elif scenario == "aot":
            env["RMD_AOT"] = "1"
        code = (f"import sys; sys.path.insert(0, {repo!r}); "
                "import bench; bench._bench_compile_child()")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=repo, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"BENCH_COMPILE child ({mode}/{scenario}) failed:\n"
                f"{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    # the parent stays off the backend: a chip belongs to one process,
    # and every child needs it — the backend's name comes from a child
    result = {"metric": "compile-cold-start"}
    for mode in ("train", "eval"):
        m = {}
        m["cold"] = run_child(mode, "cold")
        result.setdefault("backend", m["cold"]["backend"])
        _emit(result | {mode: m})
        run_child(mode, "populate")  # fills compile cache + AOT store
        m["warm_cache"] = run_child(mode, "warm_cache")
        m["aot"] = run_child(mode, "aot")
        cold = m["cold"]["time_to_first_step_s"]
        m["speedup_warm_cache"] = round(
            cold / max(m["warm_cache"]["time_to_first_step_s"], 1e-9), 2)
        m["speedup_aot"] = round(
            cold / max(m["aot"]["time_to_first_step_s"], 1e-9), 2)
        # compile-vs-productive per scenario, read off the child's
        # goodput ledger (one classifier for every bench, rather than
        # this bench's old ad-hoc compile_s/total_s arithmetic)
        for scen in ("cold", "warm_cache", "aot"):
            gp = m[scen].get("goodput")
            if gp and gp.get("total_s"):
                m[scen]["compile_share"] = round(
                    gp["classes_s"].get("compile", 0.0) / gp["total_s"], 4)
        result[mode] = m
        _emit(result)
    return result


def _bench_fault():
    """Fault-tolerance overhead (``BENCH_FAULT=1``): per-step cost of the
    non-finite recovery machinery. Measures the same synthetic training
    step (a) unguarded (policy ``raise``: one isfinite reduce over the
    final flow, as always) and (b) with the skip guard compiled in
    (policies ``skip``/``rollback``: isfinite over the update tree plus
    the conditional state select). Target: within noise. One JSON line;
    consumers read the last."""
    cpu = jax.default_backend() == "cpu"
    if cpu:
        batch, height, width, iters, steps = 2, 64, 96, 4, 3
    else:
        batch = int(os.environ.get("BENCH_BATCH", "6"))
        height = int(os.environ.get("BENCH_HEIGHT", "400"))
        width = int(os.environ.get("BENCH_WIDTH", "720"))
        iters = int(os.environ.get("BENCH_ITERS", "12"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))

    model_cfg = {"type": "raft/baseline",
                 "parameters": {"mixed-precision": not cpu}}
    loss_cfg = {"type": "raft/sequence"}

    result = {
        "metric": "fault-overhead",
        "backend": jax.default_backend(),
        "batch": batch, "height": height, "width": width,
        "iterations": iters, "steps": steps,
    }
    plain, _, psum = _measure(model_cfg, loss_cfg, batch, height, width,
                              {"iterations": iters}, steps)
    result["plain_pairs_per_sec"] = round(plain, 3)
    if psum is not None:
        result["plain_step_ms"] = psum["step_ms_mean"]
    _emit(result)

    guarded, _, gsum = _measure(model_cfg, loss_cfg, batch, height, width,
                                {"iterations": iters}, steps,
                                nonfinite="skip")
    result["guarded_pairs_per_sec"] = round(guarded, 3)
    if gsum is not None:
        result["guarded_step_ms"] = gsum["step_ms_mean"]
    result["overhead_pct"] = round((plain / guarded - 1.0) * 100, 2) \
        if guarded else None
    _emit(result)
    return result


def main():
    if os.environ.get("_BENCH_COMPILE_CHILD"):
        # one cold-start scenario delegated by the BENCH_COMPILE parent
        _bench_compile_child()
        return

    # every BENCH_* mode runs on a goodput ledger from here: telemetry
    # compile/checkpoint/eval events are classified as they are emitted
    # and _emit attaches the breakdown to each JSON line
    from raft_meets_dicl_tpu.telemetry import goodput
    goodput.activate()

    if os.environ.get("BENCH_COMPILE", "0") != "0":
        # cold vs persistent-cache-warm vs AOT-warm time-to-first-step
        _bench_compile()
        return

    if os.environ.get("BENCH_SPMD", "0") != "0":
        # SPMD mesh-shape benchmark: replicated vs partitioned state,
        # per-chip HBM + step time on the 8-device virtual CPU topology
        from raft_meets_dicl_tpu.utils.compcache import (
            enable_persistent_cache,
        )
        enable_persistent_cache()
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_spmd()
        return

    if os.environ.get("BENCH_FAULT", "0") != "0":
        # non-finite guard overhead: unguarded vs skip-guarded train step
        from raft_meets_dicl_tpu.utils.compcache import (
            enable_persistent_cache,
        )
        enable_persistent_cache()
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_fault()
        return

    if os.environ.get("BENCH_INPUT", "0") != "0":
        # input-pipeline-only mode: host-side decode/collate/wire-volume
        # numbers, no device required
        _bench_input()
        return

    if os.environ.get("BENCH_EVAL", "0") != "0":
        # shape-bucketed evaluation: batch-1 per-shape baseline vs the
        # bucketed recompile-free pipeline on a mixed-resolution set.
        # No persistent compile cache here: cold compiles per distinct
        # shape are exactly the cost being measured.
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_eval()
        return

    if os.environ.get("BENCH_SERVE", "0") != "0":
        # serving path: open-loop mixed-resolution load through the
        # continuous-batching scheduler, cold vs AOT-prebuilt replica.
        # No persistent compile cache: the warm-pool/AOT mechanics are
        # exactly the cost being measured.
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_serve()
        return

    if os.environ.get("BENCH_LADDER", "0") != "0":
        # iteration-ladder frontier: EPE vs latency at fixed recurrence
        # budgets plus the adaptive escalation policy. Persistent cache
        # on: program compiles are not the measurement, the per-rung
        # execution times are.
        from raft_meets_dicl_tpu.utils.compcache import (
            enable_persistent_cache,
        )
        enable_persistent_cache()
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_ladder()
        return

    if os.environ.get("BENCH_VIDEO", "0") != "0":
        # streaming-video warm-start: cold vs warm frames/s + EPE on
        # synthetic constant-motion sequences, plus fw/bw products.
        # Persistent cache on: the warm-start claim is about iterations
        # per frame, not compiles.
        from raft_meets_dicl_tpu.utils.compcache import (
            enable_persistent_cache,
        )
        enable_persistent_cache()
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_video()
        return

    if os.environ.get("BENCH_DICL", "0") != "0":
        # matching-phase microbench for the DICL-hybrid fast path
        from raft_meets_dicl_tpu.utils.compcache import (
            enable_persistent_cache,
        )
        enable_persistent_cache()
        from raft_meets_dicl_tpu import telemetry
        telemetry.activate(telemetry.create())
        _bench_dicl()
        return

    # persistent compile cache: cold zoo compiles total ~40 min and have
    # overrun the harness budget (round 4, rc=124); with a warmed cache
    # the full run is measurement-dominated (~5 min)
    from raft_meets_dicl_tpu.utils.compcache import enable_persistent_cache
    enable_persistent_cache()

    # memory-only telemetry sink: compile/cache events feed the per-model
    # summaries attached to the JSON lines (RMD_TELEMETRY=0 disables and
    # drops the summaries, restoring the bare measurement path)
    from raft_meets_dicl_tpu import telemetry
    telemetry.activate(telemetry.create())

    batch = int(os.environ.get("BENCH_BATCH", "6"))
    height = int(os.environ.get("BENCH_HEIGHT", "400"))
    width = int(os.environ.get("BENCH_WIDTH", "720"))
    iters = int(os.environ.get("BENCH_ITERS", "12"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))

    # elapsed budget for the scenario loop below: the primary metric always
    # runs, then flagship/zoo scenarios are skipped (marked explicitly in
    # the JSON line, SKIPPED printed) once the projected cost would overrun
    # — same discipline as BENCH_SPMD / dryrun_multichip, and the fix for
    # the external-timeout rc=124 runs that lost everything after the kill
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1200"))
    t_start = time.monotonic()
    slowest = [0.0]

    def budget_allows(tag, factor):
        elapsed = time.monotonic() - t_start
        need = factor * max(slowest[0], 30.0)
        if elapsed + need <= budget_s:
            return True
        result[f"{tag}_skipped"] = (
            f"budget ({elapsed:.0f}s elapsed, est {need:.0f}s)")
        print(f"SKIPPED {tag}: budget ({elapsed:.0f}s of {budget_s:.0f}s "
              f"used, est {need:.0f}s)", flush=True)
        _emit(result)
        return False

    if jax.default_backend() == "cpu":
        # CPU fallback (no TPU attached): tiny shapes, still one JSON line
        batch, height, width, iters, steps = 2, 64, 96, 4, 3

    # mixed-precision bf16 is the TPU-native policy (the reference's
    # autocast equivalent). Profiling history at this config:
    # - scalar-gather corr lookup: ~17 s/step; einsum lookup: 0.67 s
    # - convex Up8 hoisted out of the remat'd scan, compact mask layout,
    #   remat policy saving the corr lookups: 0.43 s
    # - fused Pallas softmax+combine Up8 kernel (ops/pallas.py): 0.39 s
    t0 = time.monotonic()
    pairs_per_sec, _, tsum = _measure(
        {"type": "raft/baseline", "parameters": {"mixed-precision": True}},
        {"type": "raft/sequence"},
        batch, height, width, {"iterations": iters}, steps,
    )
    slowest[0] = max(slowest[0], time.monotonic() - t0)

    result = {
        "metric": "train-throughput-raft-things",
        "value": round(pairs_per_sec, 3),
        "unit": "image-pairs/sec/chip",
        "vs_baseline": round(pairs_per_sec / BASELINE_PAIRS_PER_SEC_PER_CHIP, 3),
    }
    if tsum is not None:
        result["telemetry"] = tsum

    # publish the primary metric immediately: the flagship measurement
    # below adds a cold ~10 min compile, and a harness timeout must not
    # lose this line (consumers read the LAST json line printed)
    _emit(result)

    if os.environ.get("BENCH_FLAGSHIP", "1") != "0" \
            and budget_allows("ctf_l3", 3.0):
        # the thesis flagship at a Things-like config (pyramid needs
        # multiples of 64) under the bf16 policy; a flagship failure must
        # not lose the main measurement
        try:
            if jax.default_backend() == "cpu":
                fb, fh, fw, fi, fs = 1, 64, 128, (2, 1, 1), 2
            else:
                fb, fh, fw, fi, fs = 6, 384, 704, (4, 3, 3), 5
            t0 = time.monotonic()
            ctf_pairs, _, ctf_tsum = _measure(
                {"type": "raft+dicl/ctf-l3",
                 "parameters": {"mixed-precision": True}},
                {"type": "raft+dicl/mlseq",
                 "arguments": {"alpha": [0.38, 0.6, 1.0]}},
                fb, fh, fw, {"iterations": fi}, fs,
            )
            slowest[0] = max(slowest[0], time.monotonic() - t0)
            result["ctf_l3_pairs_per_sec"] = round(ctf_pairs, 3)
            if ctf_tsum is not None:
                result["ctf_l3_telemetry"] = ctf_tsum
        except Exception as e:  # noqa: BLE001 - report, don't lose the line
            result["ctf_l3_error"] = f"{type(e).__name__}: {str(e)[:120]}"

        _emit(result)

    if os.environ.get("BENCH_ZOO", "1") != "0":
        # one throughput line per model family at its reference training
        # shape, so a perf regression anywhere in the zoo is visible —
        # not just in the headline models. The enriched JSON line reprints
        # after every measurement: a harness timeout keeps what finished.
        cpu = jax.default_backend() == "cpu"
        zoo = [
            # raft/fs: the windowed (no-volume) lookup strategy, bf16
            ("raft_fs", {"type": "raft/fs",
                         "parameters": {"mixed-precision": True}},
             {"type": "raft/sequence"},
             (1, 64, 96, {"iterations": 2}, 2) if cpu else
             (6, 400, 720, {"iterations": 12}, 3)),
            # raft/sl-ctf-l3: single-lookup coarse-to-fine (thesis ablation)
            ("raft_sl_ctf3", {"type": "raft/sl-ctf-l3", "parameters": {}},
             {"type": "raft+dicl/mlseq",
              "arguments": {"gamma": 0.85, "alpha": [0.38, 0.6, 1.0]}},
             (1, 64, 128, {"iterations": (2, 1, 1)}, 2) if cpu else
             (6, 384, 704, {"iterations": (4, 3, 3)}, 3)),
            # raft+dicl/ml: multi-level DICL lookup, single RAFT loop,
            # at the reference Things shape (b6, 384x704, 12 iters)
            ("raft_dicl_ml", {"type": "raft+dicl/ml", "parameters": {}},
             {"type": "raft/sequence"},
             (1, 64, 128, {"iterations": 2}, 2) if cpu else
             (6, 384, 704, {"iterations": 12}, 3)),
            # dicl/baseline: pure DICL coarse-to-fine (GA-Net encoder)
            ("dicl_baseline",
             {"type": "dicl/baseline",
              "parameters": {"displacement-range": {
                  f"level-{lvl}": [3, 3] for lvl in range(2, 7)}}},
             {"type": "dicl/multiscale",
              "arguments": {"weights": [1.0, 0.8, 0.75, 0.6, 0.5,
                                        0.4, 0.5, 0.4, 0.5, 0.4],
                            "ord": 2}},
             (1, 128, 128, {}, 2) if cpu else (6, 384, 768, {}, 3)),
        ]
        # labeled fallback shapes: if a model fails at its reference shape
        # (e.g. a compiler-service crash) the bench still reports a number,
        # and the JSON says explicitly which config produced it (so reduced
        # measurements are never silently comparable to full ones)
        fallbacks = {
            "raft_dicl_ml": [((2, 256, 448, {"iterations": 6}, 3),
                              "reduced:b2/256x448/6-iters")],
        }
        for name, model_cfg, loss_cfg, shape in zoo:
            if not budget_allows(name, 1.5):
                continue
            candidates = [(shape, None)]
            if not cpu:
                candidates += fallbacks.get(name, [])
            for (zb, zh, zw, zargs, zsteps), label in candidates:
                try:
                    t0 = time.monotonic()
                    pairs, _, zsum = _measure(model_cfg, loss_cfg, zb, zh, zw,
                                              zargs, zsteps)
                    slowest[0] = max(slowest[0], time.monotonic() - t0)
                    result[f"{name}_pairs_per_sec"] = round(pairs, 3)
                    if zsum is not None:
                        result[f"{name}_telemetry"] = zsum
                    if label:
                        result[f"{name}_config"] = label
                    result.pop(f"{name}_error", None)
                    break
                except Exception as e:  # noqa: BLE001
                    result[f"{name}_error"] = (
                        f"{type(e).__name__}: {str(e)[:120]}")
            _emit(result)


if __name__ == "__main__":
    main()
