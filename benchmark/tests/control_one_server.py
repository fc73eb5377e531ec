#!/usr/bin/env python3
"""``control.py`` for a cell whose server holds several models: sound
readings and the control for each model, on the chip, many seeds in one
process.

    python3 benchmark/tests/control_one_server.py --workload raft-dicl-serve-mixed \
        --seeds 1,2,3 [--control-seeds 1] --out chiprun_out/<tag>

``control.py`` hands the whole run to ``harness/serve_check.py``, which
knows one model, one reference and one tree of weights; this script drives
the cell exactly as ``control.py`` does (same driver, programs, sizes and
load, a window just long enough) and then calls the driver's own ``check``
with the cell's committed limits, twice: on the served flows, which must
come out correct, and with ``control=True``, which puts in their place each
model's reference with every convolution and contraction operand rounded to
the model's ``control_precision`` and must come out not correct, for each
model by its own limit. So both verdicts are the harness's (the largest gap
over a model's sampled requests against its limit), not this script's. The
limits in ``reference/limits/<cell>.json`` were set from the table this
prints, a model a row: above the sound runs' largest, below the control's
smallest.
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
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--toy", action="store_true",
                   help="toy shapes of tests/toy (CPU test of this script)")
    p.add_argument("--out", required=True)
    args = p.parse_args()

    from benchmark.harness import check, spec

    cell = spec.load_cell(args.workload)
    limits = check.limits_for(cell.name)
    if args.toy:
        from benchmark.tests.rehearse_one_server import toy_one_server_cell

        cell = toy_one_server_cell()
        limits = cell.traffic["rehearsal_limits"]
    driver = spec.load_driver(cell.traffic["kind"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = ([int(s) for s in args.control_seeds.split(",")]
                     if args.control_seeds else seeds)
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)

    def held(verdict, model_id):
        return next(r for r in verdict.rows
                    if r["name"] == f"serve_flow_gap[{model_id}]")

    rows = []
    for seed in seeds:
        t0 = time.time()
        boot = {"t0": time.perf_counter(), "offset_s": 0.0}
        run_dir = ROOT / "bench_out" / "control" / args.workload / f"seed{seed}"
        run_dir.mkdir(parents=True, exist_ok=True)
        run = driver.run(cell, seed, args.seconds, 0, run_dir, boot,
                         platform=args.platform)
        run["readings"] = driver.readings(run)
        sound = check.Verdict()
        notes = driver.check(run, sound, limits)
        sound.print()
        row = {"seed": seed, "sound_correct": sound.correct,
               "sound": {m: held(sound, m)["value"] for m in notes},
               "sampled": {m: n["sampled"] for m, n in notes.items()},
               "control": {}, "control_ok": {}, "control_smallest": {}}
        if seed in control_seeds:
            ctl = check.Verdict()
            notes = driver.check(run, ctl, limits, control=True)
            ctl.print()
            row["control_correct"] = ctl.correct
            for m, n in notes.items():
                row["control"][m] = held(ctl, m)["value"]
                row["control_ok"][m] = held(ctl, m)["ok"]
                row["control_smallest"][m] = min(n["gaps"])
        row["completed"] = [run["readings"]["completed"],
                            run["readings"]["counted"]]
        row["wall_s"] = time.time() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out / "readings.json").write_text(json.dumps(rows, indent=1))

    print("model: limit | sound largest | control's verdicts (largest "
          "request), smallest | its smallest request | passed by the control")
    bad = not all(r["sound_correct"] for r in rows)
    for model_id in rows[0]["sound"]:
        sound = max(r["sound"][model_id] for r in rows)
        ctl = [r["control"][model_id] for r in rows if model_id in r["control"]]
        low = [r["control_smallest"][model_id] for r in rows
               if model_id in r["control"]]
        passed = [r["seed"] for r in rows if r["control_ok"].get(model_id)]
        bad = bad or bool(passed)
        print(f"{model_id}: {limits['serve_flow_gap'][model_id]} | "
              f"{sound:.6g} | {min(ctl) if ctl else float('nan'):.6g} | "
              f"{min(low) if low else float('nan'):.6g} | {passed}")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
