#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of ``raft/baseline`` on one TPU chip, with random weights made
from a seed and data rendered from a seed (no dataset, no network):

1. ``main.py train`` — 12 optimizer steps at batch 6, 400x720, 12
   iterations, bf16 policy (the Things-stage step), on the synthetic
   source of cfg/strategy/dev/synth-things.yaml;
2. ``main.py serve`` — cfg/serve/example.yaml on the 448x1024 bucket
   (batch 4, u8 wire), answering the built-in open-loop client's 32
   requests at 50/s.

3. where the host has four chips, ``main.py train`` once more with no
   ``--device-ids``: the default ``data=4`` mesh, 12 steps of the same
   stage at six pairs a chip (cfg/strategy/dev/synth-things-dp4.yaml,
   a global batch of 24), with the collectives of the compiled step
   printed. On fewer chips the leg is skipped, and says so.

Each phase is one child process, one after the other: a chip belongs to
one process at a time, so this parent never imports jax. What the phases
did is read back from the run's own records — the telemetry event stream
and the serve report — and checked: the platform is ``tpu``, every step
and request completed, the loss is finite, nothing compiled twice or
after warm-up, no program fell back from the AOT path, the Mosaic
kernels are in the compiled programs, peak device memory was reported.
Any failed check, a phase that raises, or a platform other than ``tpu``
ends the run with exit code 1 and no result line.

    python chip_smoke.py [OUT_DIR]      # default: chiprun_out/chip_smoke

Compiled programs go where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<repo>/.jax_cache``; a second run against the same directory starts
warm (zero ``train_step`` compiles) and says so. Times are printed for
the record, not gated. The last line of stdout is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import json
import math
import os
import signal
import statistics
import subprocess
import sys
import time
from importlib import metadata
from pathlib import Path

REPO = Path(__file__).resolve().parent

STEPS = 12          # > RMD_FINITE_CHECK_EVERY: one mid-run loss sample
REQUESTS = 32
BUDGET_S = 1150     # the whole run, compilation included
MESH_BUDGET_S = 600  # more for the four-chip leg: its step compiles for four minutes


class Failed(Exception):
    """A check that did not hold, or a phase that did not finish."""


def check(ok, what):
    if not ok:
        raise Failed(what)


def run_phase(name, argv, log_path, deadline):
    """Run one child to its end, its output in ``log_path``; returns its
    stdout. The child gets its own process group, which is gone by the
    time this returns — whatever the child started goes with it."""
    budget = deadline - time.monotonic()
    check(budget > 0, f"{name}: no time left to start")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, stdout=subprocess.PIPE,
            stderr=log, text=True, start_new_session=True)
        try:
            stdout, _ = proc.communicate(timeout=budget)
        except subprocess.TimeoutExpired:
            raise Failed(f"{name}: not done after {budget:.0f} s") from None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    if proc.returncode != 0:
        tail = Path(log_path).read_text().strip().splitlines()[-15:]
        raise Failed(f"{name}: exit code {proc.returncode}\n"
                     + "\n".join(tail))
    print(f"[{name}] done in {time.monotonic() - t0:.1f} s "
          f"(log: {log_path})", flush=True)
    return stdout


def read_events(path):
    check(Path(path).is_file(), f"no telemetry at {path}")
    with open(path) as fd:
        return [json.loads(line) for line in fd if line.strip()]


def first(events, kind):
    found = [e for e in events if e["kind"] == kind]
    check(found, f"no '{kind}' event in the telemetry")
    return found[0]


def on_the_chip(ev):
    """The device fields of a run_start/boot event; fails off-TPU."""
    check(ev.get("platform") == "tpu" and ev.get("backend") == "tpu",
          f"ran on platform '{ev.get('platform')}', default backend "
          f"'{ev.get('backend')}' — not on a TPU")
    return {"platform": ev["platform"], "kind": ev["device_kind"],
            "count": ev["device_count"]}


def program_record(events, program):
    """How one registered program came to be in this boot: backend
    compiles, AOT hits, and the Mosaic calls in its executable."""
    aot = [e for e in events if e["kind"] == "aot"]
    fallbacks = [e for e in aot if e["event"] == "fallback"]
    check(not fallbacks, f"AOT fallback: {fallbacks}")
    mine = [e for e in aot if e.get("program") == program]
    held = [e for e in mine if e["event"] in ("hit", "save", "skip_save")]
    check(len(held) == 1,
          f"{program}: expected one executable, got {len(held)}: {mine}")
    compiles = [e["seconds"] for e in events
                if e["kind"] == "compile" and e["label"] == program]
    hits = sum(e["event"] == "hit" for e in held)
    check(len(compiles) + hits <= 1,
          f"{program}: {len(compiles)} compiles and {hits} AOT hits")
    return {
        "compiles": len(compiles),
        "compile_s": round(sum(compiles), 1),
        "aot_hits": hits,
        "cache_hits": sum(e["kind"] == "cache" and e["event"] == "hit"
                          and e.get("label") == program for e in events),
        "mosaic_calls": held[0]["mosaic_calls"],
    }


def read_train_run(out_dir, mosaic_what):
    """What both training legs check of a run's records: it ran on the
    chip, every step completed, the loss is finite, the step compiled at
    most once and holds the Up8 kernels (forward and backward,
    ops/pallas._combine), the peak device memory was reported. Returns
    the run's events and its record."""
    runs = sorted(out_dir.iterdir())
    events = read_events(runs[-1] / "events.jsonl")

    start = first(events, "run_start")
    device = on_the_chip(start)
    boot = first(events, "boot")

    steps = [e for e in events if e["kind"] == "step"]
    check(len(steps) == STEPS, f"{len(steps)} step events, not {STEPS}")
    losses = [e["loss"] for e in events
              if e["kind"] in ("device_sync", "epoch_end")]
    check(len(losses) >= 2 and all(
        isinstance(x, float) and math.isfinite(x) for x in losses),
        f"losses sampled from the event stream: {losses}")

    program = program_record(events, "train_step")
    check(program["mosaic_calls"] >= 2,
          f"train_step holds {program['mosaic_calls']} Mosaic calls: "
          f"{mosaic_what}")
    memory = [e for e in events if e["kind"] == "memory"]
    check(memory and memory[-1].get("device_peak_gib", 0) > 0,
          f"no peak device memory reported: {memory}")

    steady = [e["step_time"] for e in steps[2:]]
    record = {
        **device, "devices_used": start["devices_used"],
        "compile_cache": boot["compile_cache"], "steps": len(steps),
        "loss": losses, "train_step": program,
        "steady_step_s": round(statistics.median(steady), 4),
        "device_peak_gib": memory[-1]["device_peak_gib"],
    }
    return events, device, record


def train_phase(out, deadline):
    run_phase("train", [
        "main.py", "train", "-m", "cfg/model/raft-baseline.yaml",
        "-d", "cfg/strategy/dev/synth-things.yaml",
        "-s", "cfg/seeds/fixed.yaml",
        "--device", "tpu", "--device-ids", "0",
        "--limit-steps", str(STEPS), "-o", str(out / "train"),
    ], out / "train.log", deadline)
    _, device, record = read_train_run(
        out / "train",
        "the Up8 kernels gave way to their XLA reference")
    print(f"[train] {json.dumps(record)}", flush=True)
    return device, record


MESH_CHIPS = 4


def mesh_phase(out, deadline):
    """The data-parallel leg: the same step over every chip of the host,
    the mesh `main.py train` builds by default."""
    run_phase("mesh", [
        "main.py", "train", "-m", "cfg/model/raft-baseline.yaml",
        "-d", "cfg/strategy/dev/synth-things-dp4.yaml",
        "-s", "cfg/seeds/fixed.yaml", "--device", "tpu",
        "--limit-steps", str(STEPS), "-o", str(out / "mesh"),
    ], out / "mesh.log", deadline)
    events, device, record = read_train_run(
        out / "mesh", "the Up8 kernels are not in the partitioned step")

    check(record["devices_used"] == MESH_CHIPS,
          f"the mesh leg used {record['devices_used']} devices")
    sharding = first(events, "sharding")
    check(sharding["mesh"] == {"data": MESH_CHIPS},
          f"mesh {sharding['mesh']}, not data={MESH_CHIPS}")
    steps = [e for e in events if e["kind"] == "step"]
    check(all(e.get("devices") == MESH_CHIPS and e["batch"] == 24
              for e in steps), "a step that did not feed four chips")
    (held,) = [e for e in events if e["kind"] == "aot"
               and e.get("program") == "train_step"
               and e["event"] in ("hit", "save", "skip_save")]
    said = held.get("collectives")
    check(held.get("mesh") == {"data": MESH_CHIPS} and said
          and said["counts"].get("all-reduce", 0) >= 1,
          f"the mesh step says nothing of its collectives: {held}")

    record.update(mesh=sharding["mesh"], collectives=said)
    print(f"[mesh] {json.dumps(record)}", flush=True)
    return device, record


def serve_phase(out, deadline):
    tele = out / "serve-events.jsonl"
    stdout = run_phase("serve", [
        "main.py", "serve", "-c", "cfg/serve/example.yaml",
        "--buckets", "448x1024", "--device", "tpu", "--device-ids", "0",
        "--requests", str(REQUESTS), "--telemetry", str(tele),
    ], out / "serve.log", deadline)
    report = json.loads(stdout.strip().splitlines()[-1])
    events = read_events(tele)

    boot = first(events, "boot")
    device = on_the_chip(boot)
    check(report["requests"] == REQUESTS
          and report["completed"] == REQUESTS,
          f"served {report['completed']} of {report['requests']}")
    check(not report["rejected"] and not report["errors"],
          f"sheds {report['rejected']}, errors {report['errors']}")

    program = program_record(events, "eval_step")
    # the Up8 convex combine, forward only
    check(program["mosaic_calls"] >= 1,
          "eval_step holds no Mosaic call: the Up8 kernel gave way to "
          "its XLA reference")
    warmups = [e for e in events
               if e["kind"] == "serve" and e["event"] == "warmup"]
    check(warmups, "no warm-up event")
    warm_at = max(e["t"] for e in warmups)
    late = [e for e in events if e["kind"] == "compile" and e["t"] > warm_at]
    batches = [e for e in events
               if e["kind"] == "serve" and e["event"] == "batch"]
    check(not late and not any(e["compiles"] for e in batches),
          f"compiled after warm_pool(): {late}")

    record = {
        **device, "compile_cache": boot["compile_cache"],
        "completed": report["completed"], "requests": report["requests"],
        "eval_step": program,
        "warm_pool_s": round(sum(e["seconds"] for e in warmups), 1),
        "p50_ms": report["p50_ms"], "p99_ms": report["p99_ms"],
        "batches": len(batches),
    }
    print(f"[serve] {json.dumps(record)}", flush=True)
    return device, record


def main(argv):
    out = Path(argv[0]).resolve() if argv else \
        REPO / "chiprun_out" / "chip_smoke"
    out = out / time.strftime("%Y%m%dT%H%M%S")
    out.mkdir(parents=True)
    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    print(f"[versions] {json.dumps(versions)}", flush=True)

    t0 = time.monotonic()
    deadline = t0 + BUDGET_S
    try:
        device, train = train_phase(out, deadline)
        served_on, serve = serve_phase(out, deadline)
        check(served_on == device,
              f"train ran on {device}, serve on {served_on}")
        mesh = None
        if device["count"] >= MESH_CHIPS:
            meshed_on, mesh = mesh_phase(out, deadline + MESH_BUDGET_S)
            check(meshed_on == device,
                  f"train ran on {device}, the mesh leg on {meshed_on}")
        else:
            print(f"[mesh] skipped: {device['count']} chip(s) here, the "
                  f"data-parallel leg needs {MESH_CHIPS}", flush=True)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    summary = {"versions": versions, "train": train, "serve": serve,
               "mesh": mesh, "wall_s": round(time.monotonic() - t0, 1)}
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    print(f"[total] {summary['wall_s']} s, records in {out}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
