#!/usr/bin/env python3
"""One sweep of offered rates over a serve cell, in one process: which is
the highest rate the system sustains with no growing backlog and no shed.
A cell's rate is fixed at about four fifths of it; the benchmark never
searches.

    python3 benchmark/tests/rate_sweep.py --workload raft-serve-mixed \
        --rates 16,20,24,28,32 --seconds 15 --out chiprun_out/<tag>

A rate is sustained when nothing was shed or failed and the median latency
of the last third of the window is within 15% of the first third's.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seed", type=int, default=77)
    p.add_argument("--out", required=True)
    args = p.parse_args()

    from benchmark.harness import spec, stats

    cell = spec.load_cell(args.workload)
    driver = spec.load_driver(cell.traffic["kind"])
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_per_s=rate, check_per_shape=0)
        boot = {"t0": time.perf_counter(), "offset_s": 0.0}
        run = driver.run(cell, args.seed, args.seconds, 0, out / f"rate{rate}",
                         boot)
        r = driver.readings(run)
        good = [x for x in run["records"] if x["counted"] and x.get("ok")]
        lat = [1e3 * (x["done"] - x["due"]) for x in good]
        n = len(lat) // 3
        first, last = stats.percentile(lat[:n], 50), stats.percentile(lat[-n:], 50)
        batches = [e for e in run["events"]
                   if e["kind"] == "serve" and e.get("event") == "batch"]
        row = {"rate": rate, "counted": r["counted"],
               "completed": r["completed"], "p50_ms": r["serve_p50_ms"],
               "p95_ms": r["serve_p95_ms"], "p50_first_third_ms": first,
               "p50_last_third_ms": last,
               "late_p95_ms": r["loadgen_late_ms"],
               "batch_fill": (sum(b["size"] for b in batches)
                              / max(1, sum(b["size"] + b["fill"]
                                           for b in batches))),
               "sustained": bool(r["completed"] == r["counted"]
                                 and last <= 1.15 * first)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    (out / "sweep.json").write_text(json.dumps(rows, indent=1))
    ok = [r["rate"] for r in rows if r["sustained"]]
    print("highest sustained rate:", max(ok) if ok else None)


if __name__ == "__main__":
    main()
