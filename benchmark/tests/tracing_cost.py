#!/usr/bin/env python3
"""What the program's telemetry costs: one cell's untraced window with the
sink as the benchmark has it (in memory, enabled: marks, ``clock`` and
``span`` events, GC hook, ticker) against ``RMD_TELEMETRY=0``, in fresh
processes, one after the other, alternating, the arrangement of
``run_set.py``.

    python3 benchmark/tests/tracing_cost.py --workload W --seeds 11,12,13 \
        [--seconds S] --out chiprun_out/<tag>

``run.py`` always activates an enabled sink (its readers need the events),
so the "off" side runs the same ``run_cell`` with the kill switch set and
the harness's sink replaced by the null one. The parent never imports JAX.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.tests.run_set import spread  # noqa: E402


def child(args):
    from benchmark import run as bench_run
    from benchmark.harness import spec

    if not args.telemetry:
        os.environ["RMD_TELEMETRY"] = "0"
        from raft_meets_dicl_tpu import telemetry

        class Off(telemetry.NullTelemetry):
            events = ()

        telemetry.Telemetry = lambda path=None: Off()
    cell = spec.load_cell(args.workload)
    tag = "on" if args.telemetry else "off"
    result = bench_run.run_cell(cell, args.seed, args.seconds, 0,
                                ROOT / "bench_out" / f"telemetry_{tag}")
    print(json.dumps(result), flush=True)
    os._exit(0)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds")
    p.add_argument("--seed", type=int)
    p.add_argument("--seconds", type=float)
    p.add_argument("--telemetry", type=int, default=1)
    p.add_argument("--out")
    args = p.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    args.seconds = args.seconds or bench["run_seconds"]
    if args.seed is not None:
        return child(args)

    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    values = {"on": {}, "off": {}}
    runs = []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        for mode in (("on", "off") if k % 2 == 0 else ("off", "on")):
            cmd = [sys.executable, __file__, "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(args.seconds),
                   "--telemetry", str(int(mode == "on"))]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            lines = proc.stdout.strip().splitlines()
            notes = [ln for ln in lines if ln.startswith("[")]
            ok = proc.returncode == 0 and lines \
                and json.loads(lines[-1]).get("correct")
            runs.append({"seed": seed, "mode": mode, "rc": proc.returncode,
                         "wall_s": time.time() - t0, "notes": notes,
                         "correct": bool(ok)})
            print(f"seed {seed} telemetry {mode}: rc {proc.returncode}, "
                  f"correct {bool(ok)}, {time.time() - t0:.1f} s", flush=True)
            for ln in notes:
                if ln.startswith(("[end_to_end]", "[blocks]", "[requests]")):
                    print("   ", ln, flush=True)
                if ln.startswith("[end_to_end] ") and ok:
                    for name, v in json.loads(ln.split(" ", 1)[1]).items():
                        values[mode].setdefault(name, []).append(v)
            if not ok:
                print(proc.stderr[-3000:], flush=True)
    summary = {"workload": args.workload, "seconds": args.seconds,
               "runs": runs, "metrics": {}}
    for name in sorted(values["on"]):
        on, off = values["on"][name], values["off"].get(name, [])
        if not off:
            continue
        row = {"on": on, "off": off, "on_median": statistics.median(on),
               "off_median": statistics.median(off), "on_spread": spread(on),
               "off_spread": spread(off)}
        row["on_over_off"] = row["on_median"] / row["off_median"]
        summary["metrics"][name] = row
        print(f"{name}: on {row['on_median']:.6g} (spread {row['on_spread']})"
              f" off {row['off_median']:.6g} (spread {row['off_spread']}) "
              f"on/off {row['on_over_off']:.5f}", flush=True)
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    sys.exit(0 if all(r["correct"] for r in runs) else 1)


if __name__ == "__main__":
    main()
