#!/usr/bin/env python3
"""Does the profiler slow what it records? One cell's short traced runs in
one process, one for each level of the profiler's host tracer, each after
its own short untraced window: the traced tail's pace beside the untraced
window's, the device's busy share, and the names the idle gaps get.
``xtrace.HOST_TRACER_LEVEL`` is set from what this prints.

    python3 benchmark/tests/trace_levels.py --workload raft-serve-mixed \
        --levels 2,1,0 --seconds 4 --out chiprun_out/<tag>
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
    p.add_argument("--workload")
    p.add_argument("--toy", choices=("train", "serve"),
                   help="CPU rehearsal on the toy cell of that kind")
    p.add_argument("--levels", default="2,1,0")
    p.add_argument("--seconds", type=float, default=4.0)
    p.add_argument("--seed", type=int, default=78)
    p.add_argument("--out", required=True)
    args = p.parse_args()

    from benchmark.harness import spec, xtrace

    if args.toy:
        from benchmark.tests.toy_cell import toy_cell
        cell, platform = toy_cell(args.toy), "cpu"
    else:
        cell, platform = spec.load_cell(args.workload), "tpu"
    driver = spec.load_driver(cell.traffic["kind"])
    out = ROOT / args.out
    rows = []
    for level in [int(x) for x in args.levels.split(",")]:
        xtrace.HOST_TRACER_LEVEL = level
        cell.traffic = dict(cell.traffic, min_blocks=1, check_per_shape=0)
        boot = {"t0": time.perf_counter(), "offset_s": 0.0}
        run = driver.run(cell, args.seed, args.seconds, 1,
                         out / f"level{level}", boot, platform=platform)
        run["readings"] = driver.readings(run)
        driver.print_rates(run)
        row = {"host_tracer_level": level}
        if platform == "tpu":
            t = xtrace.reduce(xtrace.load(xtrace.find_xplane(
                run["trace_dir"])), driver.trace_module(run))
            row.update(busy_s=t["busy_s"], window_s=t["window_s"],
                       executions=t["executions"],
                       exec_busy_ms=[round(1e3 * b, 2)
                                     for b in t["exec_busy_s"]],
                       idle_gaps=xtrace.breakdown(t)["idle_gaps"])
        rows.append(row)
        print(json.dumps(row), flush=True)
    out.mkdir(parents=True, exist_ok=True)
    (out / "levels.json").write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
