#!/usr/bin/env python3
"""Sound readings and the control, on the chip, many seeds in one process.

    python3 benchmark/tests/control.py --workload W --seeds 1,2,3 [--control-seeds 1,2,3] --out chiprun_out/<tag>

For each seed it drives the cell's timed path exactly as a run does (same
driver, same programs, same sizes; a window just long enough to finish the
work that is compared), reads the numbers the run's check compares, and
then puts the control in the program's place: the plain reference computed
with every convolution and contraction operand rounded to the precision
below the configuration's (``control_precision`` in the configuration
file). The limits in ``reference/limits/<cell>.json`` were set from the
table this prints: above the sound runs' largest, below the control's
smallest. The benchmark's own runs never run the control.
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
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default=None)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--toy", action="store_true",
                   help="toy shapes of tests/toy (CPU test of this script)")
    p.add_argument("--out", required=True)
    args = p.parse_args()

    import jax.numpy as jnp

    from benchmark.harness import check, spec

    cell = spec.load_cell(args.workload)
    if args.toy:
        from benchmark.tests.toy_cell import toy_cell

        cell = toy_cell(cell.traffic["kind"])
    kind = cell.traffic["kind"]
    driver = spec.load_driver(kind)
    quant = getattr(jnp, cell.config["control_precision"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = ([int(s) for s in args.control_seeds.split(",")]
                     if args.control_seeds else seeds)
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    limits = {k: float("inf") for k in
              ("loss_gap", "flow_gap", "grad_norm_gap", "param_change_gap",
               "serve_flow_gap")}

    if kind == "train":
        cell.traffic = dict(cell.traffic, min_blocks=1)
        seconds = args.seconds if args.seconds is not None else 0.0
    else:
        seconds = args.seconds if args.seconds is not None else 6.0

    rows = []
    for seed in seeds:
        t0 = time.time()
        boot = {"t0": time.perf_counter(), "offset_s": 0.0}
        # run directories (checkpoints, events) stay out of --out: what
        # comes back from the chip is the readings
        run_dir = ROOT / "bench_out" / "control" / args.workload / f"seed{seed}"
        run_dir.mkdir(parents=True, exist_ok=True)
        run = driver.run(cell, seed, seconds, 0, run_dir, boot,
                         platform=args.platform)
        run["readings"] = driver.readings(run)
        row = {"seed": seed}
        if kind == "train":
            from benchmark.harness import train_check
            from benchmark.reference import train as reftrain

            t1 = time.time()
            gaps, notes, reference = train_check.check(
                run, check.Verdict(), limits)
            row.update(sound=gaps, notes=notes, reference_s=time.time() - t1)
            if seed in control_seeds:
                from benchmark.reference import common as refc

                flat = refc.init(run["spec"], seed)
                low = reftrain.run(run["reference"], cell.config["model"],
                                   run["stage"], flat, run["ctl"].batches,
                                   quant=quant)
                row["control"], row["control_notes"] = reftrain.compare(
                    low, reference)
        else:
            from benchmark.harness import serve_check

            t1 = time.time()
            notes = serve_check.check(run, check.Verdict(), limits)
            row.update(sound={"serve_flow_gap": max(notes["gaps"])},
                       notes=notes, reference_s=time.time() - t1)
            if seed in control_seeds:
                row["control"] = serve_check.control(run, quant)
        row["wall_s"] = time.time() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out / "readings.json").write_text(json.dumps(rows, indent=1))

    names = sorted(rows[0]["sound"])
    print("number: sound largest | control smallest | ratio")
    for name in names:
        sound = max(r["sound"][name] for r in rows)
        ctl = [r["control"][name] for r in rows if "control" in r]
        low = min(ctl) if ctl else float("nan")
        print(f"{name}: {sound:.6g} | {low:.6g} | {low / sound:.3g}")


if __name__ == "__main__":
    main()
