#!/usr/bin/env python3
"""A set of fresh-process runs of one cell, one after the other, the way
the driver makes them; the spread of each metric the way the driver reads
it (distance between the quartiles of ``statistics.quantiles(n=4)`` over
the median).

    python3 benchmark/tests/run_set.py --workload W --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] --out chiprun_out/<tag>

The parent never imports JAX. Each run's last line, the lines before it
that start with ``[`` and its per-step or per-request records are kept
under ``--out``.
"""

import argparse
import json
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--trace-last", type=int, default=0,
                   help="1: the last seed's run is a traced one")
    p.add_argument("--out", required=True)
    args = p.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)

    runs = []
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        trace = 1 if args.trace_last and seed == seeds[-1] else args.trace
        cmd = [*bench["command"], "--workload", args.workload, "--seed",
               str(seed), "--seconds", str(seconds), "--trace", str(trace)]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        lines = proc.stdout.strip().splitlines()
        notes = [ln for ln in lines if ln.startswith("[")]
        result = None
        if proc.returncode == 0 and lines:
            try:
                result = json.loads(lines[-1])
            except ValueError:
                pass
        tag = f"seed{seed}_trace{trace}"
        (out / f"{tag}.stderr.txt").write_text(proc.stderr[-20000:])
        src = ROOT / "bench_out" / args.workload / tag
        for name in ("steps.jsonl", "requests.jsonl"):
            if (src / name).is_file():
                shutil.copy(src / name, out / f"{tag}.{name}")
        runs.append({"seed": seed, "rc": proc.returncode, "wall_s": wall,
                     "notes": notes, "result": result})
        print(f"seed {seed}: rc {proc.returncode}, {wall:.1f} s", flush=True)
        for ln in notes:
            print("   ", ln, flush=True)
        if result is None:
            print(proc.stderr[-3000:], flush=True)
        else:
            print("   ", json.dumps(result), flush=True)

    # end-to-end readings come from the ``[end_to_end]`` line, which a
    # traced run prints too (it takes the same untraced window first)
    for r in runs:
        for ln in r["notes"]:
            if r["result"] and ln.startswith("[end_to_end] "):
                for k, v in json.loads(ln.split(" ", 1)[1]).items():
                    r["result"]["metrics"].setdefault(k, {"value": v})
    good = [r["result"] for r in runs if r["result"]]
    names = sorted({k for r in good for k in r["metrics"]})
    summary = {"workload": args.workload, "seconds": seconds,
               "trace": args.trace, "runs": runs, "metrics": {}}
    for name in names:
        values = [r["metrics"][name]["value"] for r in good
                  if name in r["metrics"]]
        summary["metrics"][name] = {
            "values": values, "median": statistics.median(values),
            "spread": spread(values),
            "spread_after_first": spread(values[1:])}
    summary["all_correct"] = bool(good) and all(r["correct"] for r in good) \
        and len(good) == len(runs)
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    for name, m in summary["metrics"].items():
        print(f"{name}: median {m['median']:.6g} spread {m['spread']} "
              f"(without the first run {m['spread_after_first']})")
    print("all correct:", summary["all_correct"])
    sys.exit(0 if summary["all_correct"] else 1)


if __name__ == "__main__":
    main()
