"""Render a run's ``events.jsonl`` into a phase-breakdown report.

Pure functions over the event stream (no jax import): used by
``scripts/telemetry_report.py`` for the CLI rendering and by the tests
to hold the producers to the schema. The report answers the question
round 5 needed a dedicated debugging round for: *where do each step's
milliseconds go, and did anything anomalous happen?*
"""

import json
import logging

from . import trace as _trace
from .core import NewerSchema, UnknownKind, validate_event

# a compile this many optimizer steps after its stage started is a
# recompile — the per-stage step build compiles during the first step
DEFAULT_WARMUP_STEPS = 3
DEFAULT_SPIKE_FACTOR = 3.0

# one SLO window consuming error budget faster than this sustains is
# worth a flag (burn 1.0 = exactly at the objective)
SLO_BURN_FLAG = 1.0


def load_events(path, skipped=None):
    """Parse + validate a JSONL file. Returns (events, errors) where
    errors are (line_number, message) for records that fail the schema —
    a report over a partially-corrupt file still renders what it can.

    Forward compatibility: records an *older* reader can't know —
    unknown event kinds and same-major/newer-minor schema revisions —
    are warn-and-skipped rather than counted as errors, so old reports
    read new runs. Pass a list as ``skipped`` to collect their
    (line_number, message) pairs; they are logged either way.
    """
    events, errors = [], []
    with open(path) as fd:
        for n, line in enumerate(fd, 1):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(validate_event(json.loads(line)))
            except (UnknownKind, NewerSchema) as e:
                logging.warning(f"{path}:{n}: skipping record from a "
                                f"newer producer: {e}")
                if skipped is not None:
                    skipped.append((n, str(e)))
            except (json.JSONDecodeError, ValueError) as e:
                errors.append((n, str(e)))
    return events, errors


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def phase_stats(events):
    """Per-phase timing stats over all step events.

    Returns {phase: {mean, p95, max, total, share}} in seconds, where
    ``share`` is the phase's fraction of total step wall time, plus the
    synthetic phases ``step`` (step wall time) and ``other`` (wall time
    not covered by any span: callbacks, validation, scheduler ticks).
    """
    steps = [e for e in events if e["kind"] == "step"]
    if not steps:
        return {}

    total_wall = sum(e["step_time"] for e in steps)
    names = sorted({n for e in steps for n in e["phases"]})
    out = {}
    for name in names:
        vals = sorted(e["phases"].get(name, 0.0) for e in steps)
        total = sum(vals)
        out[name] = {
            "mean": total / len(vals),
            "p95": _percentile(vals, 0.95),
            "max": vals[-1],
            "total": total,
            "share": total / total_wall if total_wall else 0.0,
        }

    walls = sorted(e["step_time"] for e in steps)
    out["step"] = {
        "mean": total_wall / len(walls),
        "p95": _percentile(walls, 0.95),
        "max": walls[-1],
        "total": total_wall,
        "share": 1.0,
    }
    covered = sum(s["total"] for n, s in out.items() if n != "step")
    other = max(0.0, total_wall - covered)
    out["other"] = {
        "mean": other / len(steps),
        "p95": float("nan"),
        "max": float("nan"),
        "total": other,
        "share": other / total_wall if total_wall else 0.0,
    }
    return out


def counter_stats(events):
    """Per-step scalar counters (``wire_bytes`` & co.) aggregated over
    all step events: {name: {mean, max, total}}. Counters accumulate at
    the producer's cadence (the prefetcher may attribute two puts to one
    step event), so ``mean`` is total / number of steps — the per-step
    average that survives the bunching."""
    steps = [e for e in events if e["kind"] == "step"]
    names = sorted({n for e in steps for n in e.get("counters", {})})
    out = {}
    for name in names:
        vals = [e.get("counters", {}).get(name, 0) for e in steps]
        out[name] = {
            "mean": sum(vals) / len(vals),
            "max": max(vals),
            "total": sum(vals),
        }
    return out


def device_step_time(events):
    """Mean device-pipeline seconds/step from the periodic sync samples.

    Each ``device_sync`` event covers the ``steps`` dispatches since the
    previous sample; ``wall`` (when present) is the wall time across them
    and ``seconds`` the drain time at the sample point — drain ≈ 0 means
    the host, not the device, is the bottleneck.
    """
    syncs = [e for e in events if e["kind"] == "device_sync"]
    covered = sum(e.get("steps", 1) for e in syncs)
    if not covered:
        return None
    wall = sum(e.get("wall", e["seconds"]) for e in syncs)
    drain = sum(e["seconds"] for e in syncs)
    return {"samples": len(syncs), "steps_covered": covered,
            "mean_step": wall / covered, "mean_drain": drain / len(syncs)}


def find_anomalies(events, warmup_steps=DEFAULT_WARMUP_STEPS,
                   spike_factor=DEFAULT_SPIKE_FACTOR):
    """Flag step-time spikes, recompiles after warmup, and non-finite
    flushes. Returns a list of human-readable strings (empty = clean)."""
    flags = []

    # per-stage spike detection: stages change shapes/optimizers, so a
    # global median would mis-flag every stage transition
    by_stage = {}
    for e in events:
        if e["kind"] == "step":
            by_stage.setdefault(e.get("stage"), []).append(e)
    for stage, steps in by_stage.items():
        if len(steps) < 4:
            continue
        walls = sorted(s["step_time"] for s in steps)
        median = walls[len(walls) // 2]
        if median <= 0:
            continue
        for s in steps:
            if s["step_time"] > spike_factor * median:
                flags.append(
                    f"step-time spike: step {s['step']} took "
                    f"{s['step_time'] * 1e3:.0f} ms "
                    f"({s['step_time'] / median:.1f}x the stage median)")

    # recompiles: a compile after `warmup_steps` optimizer steps of the
    # current stage means something re-traced mid-stage (shape drift,
    # cache invalidation) — exactly the silent cost telemetry exists for
    steps_in_stage = 0
    for e in events:
        if e["kind"] == "stage_start":
            steps_in_stage = 0
        elif e["kind"] == "step":
            steps_in_stage += 1
        elif e["kind"] == "compile" and steps_in_stage > warmup_steps:
            flags.append(
                f"recompile after warmup: '{e['label']}' compiled for "
                f"{e['seconds']:.2f} s after {steps_in_stage} steps in-stage")

    # AOT fallbacks: an artifact existed but could not be used (corrupt,
    # version-mismatched, incompatible inputs) — the boot paid a cold JIT
    # it expected to skip
    for e in events:
        if e["kind"] == "aot" and e.get("event") == "fallback":
            flags.append(
                f"AOT fallback to cold JIT: "
                f"{e.get('program', '?')}[{e.get('model', '?')}]"
                + (f" ({e['reason']})" if "reason" in e else ""))

    # SLO burn: any window that consumed error budget faster than
    # sustainable; paired with the trace tail so a burning class is
    # attributable to a phase (queue-dominated = load/batching, not
    # the model)
    slo = slo_stats(events)
    if slo:
        for klass, s in slo["classes"].items():
            if s["worst_burn_rate"] > SLO_BURN_FLAG:
                flags.append(
                    f"SLO burn: class '{klass or 'default'}' hit burn "
                    f"rate {s['worst_burn_rate']:.2f} "
                    f"(target {s['target_ms']:.0f} ms, latest attainment "
                    f"{s['attainment'] * 100:.1f}%)")
    traces = trace_stats(events)
    if traces and traces["tail"]["queue_dominated"]:
        tail = traces["tail"]
        flags.append(
            f"queue-dominated tail: slowest decile "
            f"({tail['count']} requests, mean "
            f"{tail['total_s'] * 1e3:.1f} ms) spends most of its time "
            f"queued ({tail['phases_s'].get('queue', 0.0) * 1e3:.1f} ms "
            f"mean) — add capacity or shrink max-wait, the model is "
            f"not the bottleneck")

    for e in events:
        if e["kind"] == "nonfinite":
            action = e.get("action", "raise")
            detail = f" ({e['trips']} update(s) dropped)" \
                if action == "skip" and "trips" in e else ""
            flags.append(
                f"non-finite guard tripped at step {e['step']} "
                f"[{action}]{detail}"
                + (f" (stage {e['stage']})" if "stage" in e else ""))
        elif e["kind"] == "quarantine":
            flags.append(f"corrupt checkpoint quarantined: {e['path']}")
        elif e["kind"] == "respawn":
            flags.append(
                f"decode worker {e['worker']} died "
                f"(exit code {e.get('exitcode')}) and was respawned")
        elif e["kind"] == "bad_sample":
            flags.append(
                f"sample {e['index']} failed to decode and was substituted"
                + (f": {e['error']}" if "error" in e else ""))
        elif e["kind"] == "preempt":
            flags.append(
                f"run preempted by {e['signal']} at step {e['step']} "
                "(emergency checkpoint written)")
        elif e["kind"] == "postmortem":
            flags.append(
                f"postmortem bundle written ({e.get('reason', '?')}): "
                f"{e.get('path', '?')}")

    # chronic data starvation: the steptrace summary marking the run as
    # starved means the input pipeline — not the device — paces training
    straces = steptrace_stats(events)
    if straces and straces["last"] is not None:
        if straces["last"].get("data_starved"):
            flags.append(
                "data-starved training: median step spends most of its "
                "time in data_wait — scale the input pipeline")
        if straces["starved"] > 1:
            flags.append(
                f"{straces['starved']} steptrace window(s) flagged "
                "data-starved")

    # calibration drift: a program's measured/predicted ratio left its
    # pinned prof-budget.json band — the device got slower (or faster)
    # without the static cost model noticing
    for e in prof_stats(events)["drifted"]:
        ratio = e.get("ratio")
        ratio_s = f"{ratio:.2f}" if ratio is not None else "?"
        flags.append(
            f"calibration drift: {e.get('program', '?')[:72]} "
            f"measured/predicted ratio {ratio_s} outside its pinned "
            f"band on {e.get('machine', '?')} — profile regression or "
            f"stale pin (scripts/graftprof.py --update)")

    return flags


def lint_stats(events):
    """Aggregate ``lint`` events (graftlint findings forwarded via
    ``analysis.lint.emit_events``): per-rule counts split by status,
    plus the open findings themselves (the ones that fail the gate)."""
    per_rule = {}
    open_findings = []
    total = 0
    for e in events:
        if e["kind"] != "lint":
            continue
        total += 1
        rule = e["rule"]
        status = e.get("status", "open")
        agg = per_rule.setdefault(rule, {"open": 0, "suppressed": 0,
                                         "baselined": 0})
        agg[status] = agg.get(status, 0) + 1
        if status == "open":
            open_findings.append(e)
    return {"total": total, "per_rule": per_rule,
            "open": open_findings}


def cost_stats(events):
    """Aggregate ``cost`` events (graftcost per-program summaries
    forwarded via ``analysis.cost.emit_events``): one row per audited
    program plus hazard totals across the set."""
    programs = [e for e in events if e["kind"] == "cost"]
    hazards = {}
    for e in programs:
        for name, n in (e.get("hazards") or {}).items():
            hazards[name] = hazards.get(name, 0) + n
    return {"programs": programs, "hazards": hazards}


def prof_stats(events):
    """Aggregate ``profile`` events (graftprof measured attributions
    forwarded via ``analysis.profile.emit_events``): one row per
    profiled program plus the drifted subset the anomaly section
    flags."""
    programs = [e for e in events if e["kind"] == "profile"]
    drifted = [e for e in programs if e.get("drift")]
    return {"programs": programs, "drifted": drifted}


def fault_events(events):
    """The run's fault-tolerance trail, in order: non-finite skips and
    rollbacks, preemption stops, auto-resume pickups, checkpoint
    quarantines, decode-worker respawns, absorbed bad samples, and
    flight-recorder postmortem dumps."""
    kinds = ("nonfinite", "preempt", "resume", "quarantine", "respawn",
             "bad_sample", "postmortem")
    return [e for e in events if e["kind"] in kinds]


def goodput_stats(events):
    """The run's wall-clock goodput breakdown, from the last ``goodput``
    event (the ledger's snapshots are cumulative, so the newest one —
    run-end when the run finished cleanly — covers the whole run)."""
    snaps = [e for e in events if e["kind"] == "goodput"]
    if not snaps:
        return None
    last = snaps[-1]
    classes = dict(last.get("classes") or {})
    total = last.get("total") or sum(classes.values())
    return {
        "total": total,
        "classes": classes,
        "goodput": last.get("goodput",
                            (classes.get("productive", 0.0)
                             / total if total else 0.0)),
        "replayed_steps": last.get("replayed_steps", 0),
        "snapshots": len(snaps),
        "final": bool(last.get("final")),
    }


def steptrace_stats(events):
    """Trainer step-trace windows + eval progress heartbeats from the
    ``steptrace`` events. The trainer events carry rolling per-phase
    p50/p99 snapshots — the last one is the freshest view; the eval
    events (scope="eval") are per-bucket liveness markers."""
    train = [e for e in events
             if e["kind"] == "steptrace" and e.get("scope") != "eval"]
    evals = [e for e in events
             if e["kind"] == "steptrace" and e.get("scope") == "eval"]
    if not train and not evals:
        return None
    out = {"windows": len(train), "last": train[-1] if train else None,
           "stragglers": sum(1 for e in train if e.get("straggler")),
           "starved": sum(1 for e in train if e.get("data_starved")),
           "eval_buckets": [
               {"name": e.get("name"), "bucket": e.get("bucket"),
                "batches": e.get("window"), "samples": e.get("samples"),
                "seconds": e.get("total"), "phases": e.get("phases", {})}
               for e in evals]}
    return out


def postmortem_stats(events):
    """Flight-recorder dumps: one entry per ``postmortem`` event."""
    return [{"reason": e.get("reason"), "path": e.get("path"),
             "steps": e.get("steps"), "events": e.get("events"),
             "checkpoint": e.get("checkpoint")}
            for e in events if e["kind"] == "postmortem"]


def aot_stats(events):
    """Compiled-program / AOT summaries: per (program kind, model) the
    artifact hits, misses, saves, fallbacks, bytes moved, and
    serialize/deserialize milliseconds, plus the boot configuration
    (effective compile-cache and program directories) when present."""
    out = {"boot": None, "programs": {}}
    for e in events:
        if e["kind"] == "boot":
            out["boot"] = {
                "compile_cache": e.get("compile_cache"),
                "aot_dir": e.get("aot_dir"),
                "aot": e.get("aot"),
            }
        elif e["kind"] == "aot" and e.get("event") != "owners":
            # (an ``owners`` record's seconds are its parse's, not an
            # artifact's: the benchmark and /profilez read it)
            key = (e.get("program", "?"), e.get("model", "?"))
            agg = out["programs"].setdefault(key, {
                "hit": 0, "miss": 0, "save": 0, "fallback": 0,
                "bytes": 0, "seconds": 0.0, "reasons": []})
            ev = e.get("event")
            if ev in agg:
                agg[ev] += 1
            agg["bytes"] += e.get("bytes", 0)
            agg["seconds"] += e.get("seconds", 0.0)
            if ev == "fallback" and "reason" in e:
                agg["reasons"].append(e["reason"])
    return out


def eval_stats(events):
    """Per-sweep evaluation summaries from ``eval`` events: name,
    samples/s, compile count, pad-waste ratio, and the per-bucket batch
    breakdown (shape-bucketed evaluation, PR 4)."""
    out = []
    for e in events:
        if e["kind"] != "eval":
            continue
        secs = e["seconds"]
        out.append({
            "name": e["name"],
            "samples": e["samples"],
            "batches": e["batches"],
            "seconds": secs,
            "samples_per_sec": e.get(
                "samples_per_sec",
                e["samples"] / secs if secs else 0.0),
            "compiles": e.get("compiles", 0),
            "pad_waste_ratio": e.get("pad_waste_ratio", 0.0),
            "buckets": e.get("buckets", {}),
            "phases": e.get("phases", {}),
        })
    return out


def serve_stats(events):
    """Aggregate the serving path's ``serve`` events: request latency
    percentiles, per-span means, typed rejects/errors, per-bucket batch
    and compile counts, and warm-pool outcomes (PR 10)."""
    requests = []
    rejects = {}
    errors = {}
    buckets = {}
    warmups = []
    spans = {}
    classes = {}
    # a server of several models: two of them may share a bucket's size,
    # so a bucket's row is a model's (one model: rows by bucket as ever)
    several = len({e.get("model") for e in events if e["kind"] == "serve"
                   and e.get("event") == "batch"} - {None, ""}) > 1
    for e in events:
        if e["kind"] != "serve":
            continue
        ev = e.get("event")
        if ev == "request":
            requests.append(e)
            for name, secs in e.get("spans", {}).items():
                spans.setdefault(name, []).append(secs)
            # ladder requests carry their latency class + the iteration
            # budget actually spent (the adaptive classes vary it)
            k = e.get("klass")
            if k:
                c = classes.setdefault(
                    k, {"lat": [], "iterations": {}, "rungs": {}})
                c["lat"].append(e.get("seconds", 0.0))
                it = e.get("iterations", 0)
                c["iterations"][it] = c["iterations"].get(it, 0) + 1
        elif ev == "reject":
            reason = e.get("reason", "?")
            rejects[reason] = rejects.get(reason, 0) + 1
        elif ev == "error":
            err = e.get("error", "?")
            errors[err] = errors.get(err, 0) + 1
        elif ev == "batch":
            name = e.get("bucket", "?")
            if several:
                name = f"{e.get('model', '')}:{name}"
            b = buckets.setdefault(name, {
                "batches": 0, "requests": 0, "fill": 0, "compiles": 0})
            b["batches"] += 1
            b["requests"] += e.get("size", 0)
            b["fill"] += e.get("fill", 0)
            b["compiles"] += e.get("compiles", 0)
            k = e.get("klass")
            if k:
                c = classes.setdefault(
                    k, {"lat": [], "iterations": {}, "rungs": {}})
                rung = e.get("rungs", 0)
                c["rungs"][rung] = c["rungs"].get(rung, 0) + 1
        elif ev == "warmup":
            warmups.append(e)
    if not (requests or rejects or errors or buckets or warmups):
        return None

    latencies = sorted(e.get("seconds", 0.0) for e in requests)
    return {
        "requests": len(requests),
        "rejects": rejects,
        "errors": errors,
        "p50_s": _percentile(latencies, 0.50),
        "p99_s": _percentile(latencies, 0.99),
        "mean_s": (sum(latencies) / len(latencies) if latencies else 0.0),
        "spans_s": {name: sum(vals) / len(vals)
                    for name, vals in sorted(spans.items())},
        "buckets": buckets,
        "classes": {k: {
            "requests": len(c["lat"]),
            "p50_s": _percentile(sorted(c["lat"]), 0.50),
            "p99_s": _percentile(sorted(c["lat"]), 0.99),
            "iterations": dict(sorted(c["iterations"].items())),
            "rungs": dict(sorted(c["rungs"].items())),
        } for k, c in sorted(classes.items())},
        "warmups": [{
            "model": w.get("model", "?"), "bucket": w.get("bucket", "?"),
            "wire": w.get("wire", "?"), "compiles": w.get("compiles", 0),
            "aot_hits": w.get("aot_hits", 0),
            "aot_saves": w.get("aot_saves", 0),
            "rung": w.get("rung"),
        } for w in warmups],
    }


def fleet_stats(events):
    """Aggregate the serving-fleet plane (PR 20): routed requests per
    replica, safe-failure retries, typed fleet sheds, drains by trigger,
    session handoffs by outcome, and supervisor restarts."""
    flt = [e for e in events if e["kind"] == "fleet"]
    if not flt:
        return {}
    stats = {
        "routes": 0, "per_replica": {}, "retries": 0,
        "sheds": {}, "drains": {}, "handoffs": {},
        "replicas_up": 0, "replicas_down": 0, "restarts": [],
    }
    for e in flt:
        ev = e.get("event")
        if ev == "route":
            stats["routes"] += 1
            r = str(e.get("replica", "?"))
            stats["per_replica"][r] = stats["per_replica"].get(r, 0) + 1
        elif ev == "retry":
            stats["retries"] += 1
        elif ev == "shed":
            reason = e.get("reason", "?")
            stats["sheds"][reason] = stats["sheds"].get(reason, 0) + 1
        elif ev == "drain":
            # both sides emit a drain event (router trigger + replica
            # acknowledgement); count triggers by reason once per side
            reason = e.get("reason", e.get("source", "?"))
            stats["drains"][reason] = stats["drains"].get(reason, 0) + 1
        elif ev == "handoff":
            outcome = e.get("outcome", "?")
            stats["handoffs"][outcome] = \
                stats["handoffs"].get(outcome, 0) + 1
        elif ev == "replica_up":
            stats["replicas_up"] += 1
        elif ev == "replica_down":
            stats["replicas_down"] += 1
        elif ev == "restart":
            stats["restarts"].append({
                "replica": e.get("replica"),
                "exit_code": e.get("exit_code"),
                "backoff_ms": e.get("backoff_ms"),
            })
    return stats


def video_stats(events):
    """Aggregate the streaming-video plane (PR 15): ``video`` frame and
    sequence events from the sequence runner, ``session``
    warm-start cache events, and the serving path's video batches."""
    frames = []
    sequences = []
    sessions = {"hits": 0, "misses": 0, "evictions": {}}
    session_seen = False
    batches = {"batches": 0, "requests": 0, "warm": 0, "products": 0}
    for e in events:
        kind = e["kind"]
        if kind == "video":
            ev = e.get("event")
            if ev == "frame":
                frames.append(e)
            elif ev == "sequence":
                sequences.append(e)
        elif kind == "session":
            session_seen = True
            ev = e.get("event")
            if ev == "hit":
                sessions["hits"] += 1
            elif ev == "miss":
                sessions["misses"] += 1
            elif ev == "evict":
                reason = e.get("reason", "?")
                sessions["evictions"][reason] = \
                    sessions["evictions"].get(reason, 0) + 1
        elif (kind == "serve" and e.get("event") == "batch"
                and e.get("video")):
            batches["batches"] += 1
            batches["requests"] += e.get("size", 0)
            batches["warm"] += e.get("warm_members", 0)
            if e.get("products"):
                batches["products"] += 1
    if not (frames or sequences or session_seen or batches["batches"]):
        return None

    def frame_summary(group):
        if not group:
            return None
        its = [e.get("iterations", 0) for e in group]
        epes = [e["epe"] for e in group if "epe" in e]
        return {
            "frames": len(group),
            "mean_iterations": sum(its) / len(its),
            "mean_epe": sum(epes) / len(epes) if epes else None,
        }

    return {
        "warm": frame_summary([e for e in frames if e.get("warm")]),
        "cold": frame_summary([e for e in frames if not e.get("warm")]),
        "sequences": [{
            "frames": s.get("frames", 0),
            "warm_frames": s.get("warm_frames", 0),
            "mean_iterations": s.get("mean_iterations", 0.0),
            "frames_per_sec": s.get("frames_per_sec", 0.0),
            "mean_epe": s.get("mean_epe"),
        } for s in sequences],
        "sessions": sessions if session_seen else None,
        "batches": batches if batches["batches"] else None,
    }


def slo_stats(events):
    """Per-class SLO window summaries from the periodic ``slo`` events: the
    *latest* window per class (the current state) plus the worst burn
    rate seen across the run."""
    latest, worst = {}, {}
    for e in events:
        if e["kind"] != "slo":
            continue
        k = e.get("klass", "")
        latest[k] = e
        if e["burn_rate"] > worst.get(k, {}).get("burn_rate", -1.0):
            worst[k] = e
    if not latest:
        return None
    return {
        "classes": {k: {
            "target_ms": e["target_ms"],
            "objective": e.get("objective"),
            "window_s": e.get("window_s"),
            "good": e.get("good", 0),
            "bad": e.get("bad", 0),
            "attainment": e["attainment"],
            "burn_rate": e["burn_rate"],
            "worst_burn_rate": worst[k]["burn_rate"],
        } for k, e in sorted(latest.items())},
    }


def trace_stats(events, decile=0.9):
    """Aggregate per-request ``trace`` events: per-class counts and the
    slowest-decile critical-path phase breakdown (mean ms per phase,
    dominant phase named) — the offline twin of TraceSummary.tail()."""
    requests = [e for e in events
                if e["kind"] == "trace" and e.get("event") == "request"]
    batches = [e for e in events
               if e["kind"] == "trace" and e.get("event") == "batch"]
    if not requests:
        return None
    ranked = sorted(requests, key=lambda e: e.get("total", 0.0))
    cut = max(1, len(ranked) - int(len(ranked) * decile))
    slow = ranked[-cut:]
    phases = {}
    for e in slow:
        for name, secs in (e.get("phases") or {}).items():
            phases.setdefault(name, []).append(secs)
    mean = {name: sum(vals) / len(vals) for name, vals in phases.items()}
    dominant = max(mean, key=mean.get) if mean else None
    classes = {}
    for e in requests:
        k = e.get("klass") or ""
        classes.setdefault(k, []).append(e.get("total", 0.0))
    return {
        "requests": len(requests),
        "batches": len(batches),
        "classes": {k: {
            "count": len(v),
            "p50_s": _percentile(sorted(v), 0.50),
            "p99_s": _percentile(sorted(v), 0.99),
        } for k, v in sorted(classes.items())},
        "tail": {
            "count": len(slow),
            "total_s": sum(e.get("total", 0.0) for e in slow) / len(slow),
            "phases_s": {k: mean[k] for k in sorted(mean)},
            "dominant": dominant,
            "queue_dominated": dominant == "queue",
        },
    }


def timeline_stats(events):
    """What lies on the one timeline (``clock``/``span`` events and the
    marks of ``step`` and ``trace`` events; all optional, files older than
    schema 1.7 have none): spans by name (count, total and longest, in
    seconds), the drift between the run's ``clock`` events, and the mean
    of each interval between the dispatch thread's batch marks."""
    spans = {}
    for e in events:
        if e["kind"] == "span":
            s = spans.setdefault(e["name"], {"count": 0, "total": 0.0,
                                             "max": 0.0})
            s["count"] += 1
            s["total"] += e["t1"] - e["t0"]
            s["max"] = max(s["max"], e["t1"] - e["t0"])
    offsets = [e["time_ns"] - e["perf_counter"] * 1e9 for e in events
               if e["kind"] == "clock"]
    batches = [e["marks"] for e in events if e["kind"] == "trace"
               and e.get("event") == "batch" and e.get("marks")]
    legs = {}
    for m in batches:
        hit = [k for k in _trace.BATCH_MARKS if k in m]
        for a, b in zip(hit, hit[1:]):
            legs.setdefault(f"{a}->{b}", []).append(m[b] - m[a])
    if not (spans or offsets or batches):
        return None
    return {
        "spans": spans, "clocks": len(offsets),
        "drift_us": (max(offsets) - min(offsets)) / 1e3 if offsets else None,
        "marked_steps": sum(1 for e in events
                            if e["kind"] == "step" and e.get("marks")),
        "batch_legs_s": {k: sum(v) / len(v) for k, v in legs.items()},
    }


def sharding_stats(events):
    """Per-stage SPMD placement summaries from ``sharding`` events: mesh
    shape and the per-chip vs. replicated byte accounting the partitioner
    reported when it placed the training state (PR 6)."""
    out = []
    for e in events:
        if e["kind"] != "sharding":
            continue
        out.append({
            "stage": e.get("stage"),
            "mesh": e.get("mesh", {}),
            "params_per_chip": e["params_bytes_per_chip"],
            "params_replicated": e.get("params_bytes_replicated", 0),
            "opt_per_chip": e["opt_bytes_per_chip"],
            "opt_replicated": e.get("opt_bytes_replicated", 0),
            "params_sharded_leaves": e.get("params_sharded_leaves", 0),
            "params_leaves": e.get("params_leaves", 0),
        })
    return out


def _fmt_ms(seconds):
    try:
        return f"{seconds * 1e3:9.2f}"
    except (TypeError, ValueError):  # pragma: no cover
        return "        -"


def render(events, errors=(), warmup_steps=DEFAULT_WARMUP_STEPS,
           spike_factor=DEFAULT_SPIKE_FACTOR):
    """The full plain-text report."""
    lines = []
    steps = [e for e in events if e["kind"] == "step"]
    compiles = [e for e in events if e["kind"] == "compile"]
    caches = [e for e in events if e["kind"] == "cache"]
    stages = [e for e in events if e["kind"] == "stage_start"]
    memory = [e for e in events if e["kind"] == "memory"]
    checkpoints = [e for e in events if e["kind"] == "checkpoint"]

    lines.append("== run summary ==")
    lines.append(
        f"events: {len(events)}  stages: {len(stages)}  "
        f"optimizer steps: {len(steps)}  checkpoints: {len(checkpoints)}")
    if errors:
        lines.append(f"schema errors: {len(errors)} "
                     f"(first: line {errors[0][0]}: {errors[0][1]})")
    if steps:
        ema = steps[-1]["throughput_ema"]
        lines.append(f"final throughput EMA: {ema:.3f} steps/s")

    stats = phase_stats(events)
    if stats:
        lines.append("")
        lines.append("== step phase breakdown (ms) ==")
        lines.append(f"{'phase':<14} {'mean':>9} {'p95':>9} {'max':>9} "
                     f"{'share':>7}")
        order = sorted((n for n in stats if n not in ("step", "other")),
                       key=lambda n: -stats[n]["total"])
        for name in order + ["other", "step"]:
            s = stats[name]
            lines.append(
                f"{name:<14} {_fmt_ms(s['mean'])} {_fmt_ms(s['p95'])} "
                f"{_fmt_ms(s['max'])} {s['share'] * 100:6.1f}%")

    counters = counter_stats(events)
    if counters:
        lines.append("")
        lines.append("== step counters ==")
        for name, s in counters.items():
            if name.endswith("_bytes"):
                lines.append(
                    f"{name:<14} {s['mean'] / 2 ** 20:9.2f} MiB/step mean  "
                    f"{s['total'] / 2 ** 20:9.2f} MiB total")
            else:
                lines.append(
                    f"{name:<14} {s['mean']:9.2f}/step mean  "
                    f"{s['total']:9.2f} total")

    dev = device_step_time(events)
    if dev:
        lines.append("")
        lines.append(
            f"device pipeline: {dev['mean_step'] * 1e3:.2f} ms/step over "
            f"{dev['steps_covered']} sampled steps "
            f"({dev['samples']} syncs, mean drain "
            f"{dev['mean_drain'] * 1e3:.2f} ms)")

    straces = steptrace_stats(events)
    if straces and straces["last"]:
        last = straces["last"]
        lines.append("")
        lines.append(f"== step traces ({straces['windows']} windows) ==")
        lines.append(f"{'phase':<12} {'p50':>9} {'p99':>9}")
        for phase, pcts in last.get("phases", {}).items():
            lines.append(f"{phase:<12} {pcts['p50_ms']:9.2f} "
                         f"{pcts['p99_ms']:9.2f}")
        total = last.get("total_ms", {})
        lines.append(f"{'total':<12} {total.get('p50', 0):9.2f} "
                     f"{total.get('p99', 0):9.2f}")
        if straces["stragglers"] or straces["starved"]:
            lines.append(
                f"flags: {straces['stragglers']} straggler window(s), "
                f"{straces['starved']} data-starved window(s)")
    if straces and straces["eval_buckets"]:
        lines.append("")
        lines.append(f"== eval progress ({len(straces['eval_buckets'])} "
                     f"buckets) ==")
        for b in straces["eval_buckets"]:
            lines.append(
                f"{b['name'] or 'eval':<16} {b['bucket'] or '?':<12} "
                f"{b['batches'] or 0:4d} batches  "
                f"{b['samples'] or 0:5d} samples  "
                f"{b['seconds'] or 0:8.2f} s")

    goodput = goodput_stats(events)
    if goodput:
        lines.append("")
        lines.append("== goodput ==")
        total = goodput["total"]
        lines.append(
            f"wall clock: {total:.2f} s, goodput "
            f"{goodput['goodput'] * 100:.1f}% productive"
            + (f", {goodput['replayed_steps']} step(s) replayed"
               if goodput["replayed_steps"] else ""))
        for klass, secs in sorted(goodput["classes"].items(),
                                  key=lambda kv: -kv[1]):
            if secs <= 0 and klass != "productive":
                continue
            share = secs / total * 100 if total else 0.0
            lines.append(f"{klass:<14} {secs:9.2f} s {share:6.1f}%")

    shardings = sharding_stats(events)
    if shardings:
        lines.append("")
        lines.append("== sharding ==")
        for s in shardings:
            mesh = " × ".join(f"{k}={v}" for k, v in s["mesh"].items()) \
                or "?"
            stage = f"stage {s['stage']}" if s["stage"] is not None else "-"
            mib = 2 ** 20

            def ratio(per, full):
                return f"{per / full * 100:.0f}%" if full else "-"

            lines.append(
                f"{stage:<10} mesh [{mesh}]  params "
                f"{s['params_per_chip'] / mib:.1f} MiB/chip "
                f"({ratio(s['params_per_chip'], s['params_replicated'])} of "
                f"replicated), opt "
                f"{s['opt_per_chip'] / mib:.1f} MiB/chip "
                f"({ratio(s['opt_per_chip'], s['opt_replicated'])}), "
                f"{s['params_sharded_leaves']}/{s['params_leaves']} "
                "param tensors sharded")

    evals = eval_stats(events)
    if evals:
        lines.append("")
        lines.append("== evaluation ==")
        lines.append(f"{'sweep':<16} {'samples':>8} {'smp/s':>8} "
                     f"{'compiles':>9} {'pad-waste':>10}")
        for ev in evals:
            lines.append(
                f"{ev['name']:<16} {ev['samples']:>8d} "
                f"{ev['samples_per_sec']:>8.2f} {ev['compiles']:>9d} "
                f"{ev['pad_waste_ratio'] * 100:>9.1f}%")
            for key, b in sorted(ev["buckets"].items()):
                lines.append(
                    f"  bucket {key:<12} {b['samples']:>6d} samples in "
                    f"{b['batches']} batches, {b.get('compiles', 0)} "
                    "compiles")

    srv = serve_stats(events)
    if srv:
        lines.append("")
        lines.append("== serving ==")
        shed = sum(srv["rejects"].values())
        errs = sum(srv["errors"].values())
        summary = f"requests: {srv['requests']} served"
        if shed:
            detail = ", ".join(f"{r}={n}" for r, n in
                               sorted(srv["rejects"].items()))
            summary += f", {shed} rejected ({detail})"
        if errs:
            detail = ", ".join(f"{k}={n}" for k, n in
                               sorted(srv["errors"].items()))
            summary += f", {errs} errors ({detail})"
        lines.append(summary)
        if srv["requests"]:
            lines.append(
                f"latency: p50 {srv['p50_s'] * 1e3:.1f} ms, "
                f"p99 {srv['p99_s'] * 1e3:.1f} ms, "
                f"mean {srv['mean_s'] * 1e3:.1f} ms")
            spans = srv["spans_s"]
            if spans:
                lines.append("spans:   " + ", ".join(
                    f"{name} {secs * 1e3:.1f} ms"
                    for name, secs in spans.items()))
        for k, c in sorted(srv.get("classes", {}).items()):
            its = ", ".join(f"{n} its x{cnt}"
                            for n, cnt in c["iterations"].items())
            lines.append(
                f"  class {k:<9} {c['requests']:>4d} requests: "
                f"p50 {c['p50_s'] * 1e3:.1f} ms, "
                f"p99 {c['p99_s'] * 1e3:.1f} ms [{its or '-'}]")
        for key, b in sorted(srv["buckets"].items()):
            lines.append(
                f"  bucket {key:<12} {b['requests']:>6d} requests in "
                f"{b['batches']} batches ({b['fill']} pad fill), "
                f"{b['compiles']} compiles")
        for w in srv["warmups"]:
            rung = f", rung {w['rung']}" if w.get("rung") else ""
            lines.append(
                f"  warm pool {w['model']}[{w['bucket']}] ({w['wire']}"
                f"{rung}): {w['compiles']} compiles, {w['aot_hits']} AOT "
                f"hits, {w['aot_saves']} AOT saves")

    video = video_stats(events)
    if video:
        lines.append("")
        lines.append("== video ==")
        for arm in ("cold", "warm"):
            s = video[arm]
            if not s:
                continue
            epe = (f", EPE {s['mean_epe']:.3f}"
                   if s["mean_epe"] is not None else "")
            lines.append(
                f"{arm} frames: {s['frames']}, mean "
                f"{s['mean_iterations']:.1f} iterations{epe}")
        for s in video["sequences"]:
            epe = (f", EPE {s['mean_epe']:.3f}"
                   if s.get("mean_epe") is not None else "")
            lines.append(
                f"  sequence: {s['frames']} frames "
                f"({s['warm_frames']} warm), "
                f"{s['mean_iterations']:.1f} mean iterations, "
                f"{s['frames_per_sec']:.2f} frames/s{epe}")
        sess = video["sessions"]
        if sess:
            total = sess["hits"] + sess["misses"]
            ratio = sess["hits"] / total * 100 if total else 0.0
            evict = ", ".join(f"{r}={n}" for r, n in
                              sorted(sess["evictions"].items()))
            lines.append(
                f"sessions: {sess['hits']} warm hits / {total} lookups "
                f"({ratio:.0f}%)"
                + (f", evictions {evict}" if evict else ""))
        b = video["batches"]
        if b:
            lines.append(
                f"serve batches: {b['batches']} video batches, "
                f"{b['requests']} requests ({b['warm']} warm members, "
                f"{b['products']} with fw/bw products)")

    flt = fleet_stats(events)
    if flt:
        lines.append("")
        lines.append("== fleet ==")
        per = ", ".join(f"{r}={n}" for r, n in
                        sorted(flt["per_replica"].items()))
        lines.append(
            f"routed: {flt['routes']} requests"
            + (f" ({per})" if per else "")
            + (f", {flt['retries']} retries" if flt["retries"] else ""))
        if flt["sheds"]:
            lines.append("sheds:  " + ", ".join(
                f"{r}={n}" for r, n in sorted(flt["sheds"].items())))
        if flt["drains"]:
            lines.append("drains: " + ", ".join(
                f"{r}={n}" for r, n in sorted(flt["drains"].items())))
        if flt["handoffs"]:
            lines.append("handoffs: " + ", ".join(
                f"{o}={n}" for o, n in sorted(flt["handoffs"].items())))
        if flt["replicas_up"] or flt["replicas_down"]:
            lines.append(
                f"membership: {flt['replicas_up']} up, "
                f"{flt['replicas_down']} down, "
                f"{len(flt['restarts'])} supervisor restarts")
        for r in flt["restarts"][:8]:
            lines.append(
                f"  restart replica {r['replica']}: exit "
                f"{r['exit_code']}, backoff {r['backoff_ms']} ms")

    traces = trace_stats(events)
    if traces:
        lines.append("")
        lines.append("== tracing ==")
        lines.append(
            f"traced: {traces['requests']} requests in "
            f"{traces['batches']} batches")
        for k, c in sorted(traces["classes"].items()):
            lines.append(
                f"  class {k or 'default':<9} {c['count']:>4d} requests: "
                f"p50 {c['p50_s'] * 1e3:.1f} ms, "
                f"p99 {c['p99_s'] * 1e3:.1f} ms")
        tail = traces["tail"]
        breakdown = ", ".join(
            f"{name} {secs * 1e3:.1f} ms"
            for name, secs in tail["phases_s"].items())
        lines.append(
            f"slowest decile ({tail['count']} requests, mean "
            f"{tail['total_s'] * 1e3:.1f} ms): {breakdown or '-'} "
            f"[dominant: {tail['dominant'] or '-'}]")

    timeline = timeline_stats(events)
    if timeline:
        lines.append("")
        lines.append("== timeline ==")
        drift = ("-" if timeline["drift_us"] is None
                 else f"{timeline['drift_us']:.1f} us")
        lines.append(
            f"clock events: {timeline['clocks']} (perf_counter to Unix "
            f"time; drift over the run {drift}); steps with marks: "
            f"{timeline['marked_steps']}")
        for name, sp in sorted(timeline["spans"].items(),
                               key=lambda kv: -kv[1]["total"]):
            lines.append(
                f"  span {name:<14} {sp['count']:>4d} x, total "
                f"{sp['total']:.3f} s, longest {sp['max']:.3f} s")
        if timeline["batch_legs_s"]:
            lines.append("dispatch thread, mean per batch: " + ", ".join(
                f"{k} {v * 1e3:.2f} ms"
                for k, v in timeline["batch_legs_s"].items()))

    slo = slo_stats(events)
    if slo:
        lines.append("")
        lines.append("== slo ==")
        lines.append(f"{'class':<10} {'target':>9} {'attain':>8} "
                     f"{'burn':>7} {'worst':>7} {'window':>12}")
        for k, s in slo["classes"].items():
            window = f"{s['good']}+{s['bad']}/{s['window_s']:.0f}s"
            lines.append(
                f"{k or 'default':<10} {s['target_ms']:>7.1f}ms "
                f"{s['attainment'] * 100:>7.1f}% {s['burn_rate']:>7.2f} "
                f"{s['worst_burn_rate']:>7.2f} {window:>12}")

    aot = aot_stats(events)
    if aot["boot"] or aot["programs"]:
        lines.append("")
        lines.append("== compiled programs ==")
        boot = aot["boot"]
        if boot:
            lines.append(
                f"compile cache: {boot['compile_cache'] or 'disabled'}")
            lines.append(
                f"AOT programs:  {boot['aot_dir'] or 'disabled'}")
        for (program, model), agg in sorted(aot["programs"].items()):
            lines.append(
                f"{program}[{model}]: {agg['hit']} AOT hits, "
                f"{agg['miss']} misses, {agg['save']} saves, "
                f"{agg['fallback']} fallbacks "
                f"({agg['bytes'] / 2 ** 20:.1f} MiB, "
                f"{agg['seconds'] * 1e3:.0f} ms serialize/load)")

    if compiles or caches:
        lines.append("")
        lines.append("== compiles ==")
        by_label = {}
        for c in compiles:
            agg = by_label.setdefault(c["label"], [0, 0.0])
            agg[0] += 1
            agg[1] += c["seconds"]
        for label, (n, secs) in sorted(by_label.items()):
            lines.append(f"{label:<20} {n:3d} compiles  {secs:8.2f} s")
        hits = sum(1 for c in caches if c["event"] == "hit")
        misses = sum(1 for c in caches if c["event"] == "miss")
        lines.append(f"persistent compile cache: {hits} hits, "
                     f"{misses} misses")

    fault = fault_events(events)
    if fault:
        lines.append("")
        lines.append(f"== fault tolerance ({len(fault)} events) ==")
        for e in fault:
            kind = e["kind"]
            if kind == "nonfinite":
                action = e.get("action", "raise")
                if action == "rollback":
                    lines.append(
                        f"  rollback at step {e.get('from_step', e['step'])}"
                        f" -> step {e.get('to_step', '?')} "
                        f"('{e.get('path', '?')}')")
                elif action == "skip":
                    lines.append(
                        f"  skip at step {e['step']}: {e.get('trips', 1)} "
                        f"update(s) dropped "
                        f"({e.get('window_trips', '?')} in window)")
                else:
                    lines.append(f"  non-finite abort at step {e['step']}")
            elif kind == "preempt":
                lines.append(
                    f"  preempt ({e['signal']}) at step {e['step']}")
            elif kind == "resume":
                lines.append(
                    f"  resume from '{e['path']}' at step {e['step']}")
            elif kind == "quarantine":
                lines.append(f"  quarantined '{e['path']}'")
            elif kind == "respawn":
                lines.append(
                    f"  respawned decode worker {e['worker']} "
                    f"(exit code {e.get('exitcode')})")
            elif kind == "bad_sample":
                lines.append(
                    f"  substituted bad sample {e['index']}"
                    + (f" ({e['error']})" if "error" in e else ""))
            elif kind == "postmortem":
                lines.append(
                    f"  postmortem bundle ({e.get('reason', '?')}): "
                    f"'{e.get('path', '?')}'")

    posts = postmortem_stats(events)
    if posts:
        lines.append("")
        lines.append(f"== postmortem ({len(posts)}) ==")
        for p in posts:
            lines.append(
                f"{p['reason'] or '?':<20} {p['steps'] or 0:4d} step "
                f"trace(s), {p['events'] or 0:4d} event(s): '{p['path']}'"
                + (f" (checkpoint '{p['checkpoint']}')"
                   if p.get("checkpoint") else ""))

    lint = lint_stats(events)
    if lint["total"]:
        lines.append("")
        lines.append(f"== lint ({lint['total']} findings) ==")
        for rule, agg in sorted(lint["per_rule"].items()):
            lines.append(
                f"{rule:<16} {agg['open']:3d} open, "
                f"{agg['suppressed']:3d} suppressed, "
                f"{agg['baselined']:3d} baselined")
        for e in lint["open"]:
            lines.append(f"  ! {e['path']}:{e['line']}: {e['rule']}: "
                         f"{e.get('message', '')}")

    cost = cost_stats(events)
    if cost["programs"]:
        lines.append("")
        lines.append(f"== program costs ({len(cost['programs'])} "
                     f"programs) ==")
        for e in cost["programs"]:
            verd = ", ".join(f"{k}={v}" for k, v in
                             sorted((e.get("verdicts") or {}).items()))
            lines.append(
                f"{e.get('program', '?')[:72]}: "
                f"{e['flops'] / 1e6:.1f} MFLOP, "
                f"{e['bytes'] / 2**20:.1f} MiB, "
                f"{e.get('intensity', 0):.1f} flop/B, collectives "
                f"{e.get('collective_bytes', 0) / 2**20:.2f} MiB"
                + (f" [{verd}]" if verd else ""))
        if cost["hazards"]:
            lines.append("  hazards: " + ", ".join(
                f"{k}={v}" for k, v in sorted(cost["hazards"].items())))

    prof = prof_stats(events)
    if prof["programs"]:
        machines = sorted({e.get("machine", "?")
                           for e in prof["programs"]})
        lines.append("")
        lines.append(f"== profiling ({len(prof['programs'])} programs, "
                     f"machine {', '.join(machines)}) ==")
        for e in prof["programs"]:
            ratio = e.get("ratio")
            ratio_s = f"{ratio:.2f}" if ratio is not None else "-"
            classes = ", ".join(
                f"{k} {v * 1e3:.1f}ms" for k, v in sorted(
                    (e.get("classes") or {}).items(),
                    key=lambda kv: -kv[1])[:3])
            lines.append(
                f"{e.get('program', '?')[:72]}: measured "
                f"{e['seconds'] * 1e3:.1f} ms vs predicted "
                f"{e.get('predicted_seconds', 0) * 1e3:.1f} ms "
                f"(ratio {ratio_s})"
                + (f" [{classes}]" if classes else "")
                + (" [drift]" if e.get("drift") else "")
                + (" [stale fingerprint]"
                   if e.get("stale_fingerprint") else ""))

    if memory:
        peak_rss = max(m["host_rss_gib"] for m in memory)
        lines.append("")
        line = (f"memory watermarks: host rss {peak_rss:.2f} GiB, "
                f"live arrays max {max(m['live_arrays'] for m in memory)}")
        dev_peaks = [m["device_peak_gib"] for m in memory
                     if "device_peak_gib" in m]
        if dev_peaks:
            line += f", device peak {max(dev_peaks):.2f} GiB"
        lines.append(line)

    flags = find_anomalies(events, warmup_steps=warmup_steps,
                           spike_factor=spike_factor)
    lines.append("")
    if flags:
        lines.append(f"== anomalies ({len(flags)}) ==")
        lines.extend(f"  ! {f}" for f in flags)
    else:
        lines.append("== anomalies: none ==")

    return "\n".join(lines)


# -- multi-run merge ---------------------------------------------------------

# merged-timeline landmarks: the low-rate run-shape events worth
# interleaving across hosts (the per-step firehose would drown them)
MERGE_KINDS = ("run_start", "stage_start", "stage_end", "compile",
               "checkpoint", "resume", "preempt", "postmortem",
               "nonfinite", "run_end")

# eager-op compiles (model init fires hundreds of ms-scale 'jit' ones)
# are noise at timeline granularity; only program-scale compiles are
# landmarks
MERGE_COMPILE_MIN_S = 0.5


def _is_landmark(e):
    if e["kind"] not in MERGE_KINDS:
        return False
    if e["kind"] == "compile":
        return e.get("seconds", 0.0) >= MERGE_COMPILE_MIN_S
    return True


def merge_stats(runs):
    """Cross-run statistics for a merged report.

    ``runs`` is a list of ``{"label": str, "events": [...]}`` dicts (one
    per host / run id, events already schema-validated). All runs share
    the ``t`` wall clock (``time.time()``), so cross-host deltas are as
    honest as the hosts' NTP. Returns per-run rows (start skew vs the
    earliest host, median step time, straggler delta vs the fastest
    host, goodput) plus the merged landmark timeline.
    """
    rows = []
    t0s, medians = {}, {}
    for run in runs:
        label, events = run["label"], run["events"]
        ts = [e["t"] for e in events]
        steps = sorted(e["step_time"] for e in events
                       if e["kind"] == "step")
        t0s[label] = min(ts) if ts else None
        medians[label] = steps[len(steps) // 2] if steps else None
        gp = goodput_stats(events)
        rows.append({
            "label": label,
            "t0": t0s[label],
            "t_end": max(ts) if ts else None,
            "events": len(events),
            "steps": len(steps),
            "median_step_s": medians[label],
            "goodput": gp["goodput"] if gp else None,
        })

    anchor = min((t for t in t0s.values() if t is not None), default=None)
    fastest = min((m for m in medians.values() if m is not None),
                  default=None)
    for row in rows:
        # skew: how late this host's stream starts vs the earliest one
        row["skew_s"] = (row["t0"] - anchor
                         if anchor is not None and row["t0"] is not None
                         else None)
        # straggler delta: median step time vs the fastest host's median
        row["straggler_x"] = (row["median_step_s"] / fastest
                              if fastest and row["median_step_s"]
                              else None)

    timeline = []
    for run in runs:
        for e in run["events"]:
            if _is_landmark(e):
                timeline.append((e["t"], run["label"], e))
    timeline.sort(key=lambda item: item[0])
    return {"anchor": anchor, "rows": rows, "timeline": timeline}


def _describe_landmark(e):
    kind = e["kind"]
    if kind == "compile":
        return f"compile '{e.get('label', '?')}' {e['seconds']:.2f} s"
    if kind == "checkpoint":
        return f"checkpoint @ step {e.get('step', '?')}"
    if kind == "stage_start":
        return f"stage {e.get('stage', '?')} start"
    if kind == "stage_end":
        return f"stage {e.get('stage', '?')} end"
    if kind == "resume":
        return f"resume @ step {e.get('step', '?')}"
    if kind == "preempt":
        return f"preempt ({e.get('signal', '?')}) @ step {e.get('step', '?')}"
    if kind == "postmortem":
        return f"postmortem ({e.get('reason', '?')})"
    if kind == "nonfinite":
        return f"nonfinite @ step {e.get('step', '?')}"
    return kind


def render_merged(runs):
    """Render multiple runs' event streams as one report: a per-host
    table (skew / median step / straggler delta / goodput) followed by
    the merged landmark timeline on the shared wall clock."""
    merged = merge_stats(runs)
    width = max([len(r["label"]) for r in merged["rows"]] + [4])
    lines = [f"== merged report ({len(runs)} run(s)) ==", ""]
    lines.append(f"{'run':<{width}} {'events':>7} {'steps':>6} "
                 f"{'skew':>9} {'med step':>9} {'straggler':>9} "
                 f"{'goodput':>8}")
    for r in merged["rows"]:
        skew = (f"{r['skew_s']:+8.2f}s" if r["skew_s"] is not None
                else f"{'-':>9}")
        med = (_fmt_ms(r["median_step_s"])
               if r["median_step_s"] is not None else "-")
        strag = (f"{r['straggler_x']:8.2f}x"
                 if r["straggler_x"] is not None else f"{'-':>9}")
        gp = (f"{r['goodput'] * 100:7.1f}%"
              if r["goodput"] is not None else f"{'-':>8}")
        lines.append(f"{r['label']:<{width}} {r['events']:>7} "
                     f"{r['steps']:>6} {skew:>9} {med:>9} {strag:>9} "
                     f"{gp:>8}")

    stragglers = [r for r in merged["rows"]
                  if r["straggler_x"] is not None
                  and r["straggler_x"] > DEFAULT_SPIKE_FACTOR / 2]
    for r in stragglers:
        lines.append(f"  ! straggler: '{r['label']}' steps "
                     f"{r['straggler_x']:.2f}x slower than the fastest "
                     f"host")

    if merged["timeline"]:
        anchor = merged["anchor"] or merged["timeline"][0][0]
        lines.append("")
        lines.append(f"== merged timeline ({len(merged['timeline'])} "
                     f"landmark(s), t0 = earliest host) ==")
        for t, label, e in merged["timeline"]:
            lines.append(f"  +{t - anchor:9.2f}s  {label:<{width}}  "
                         f"{_describe_landmark(e)}")

    return "\n".join(lines)
