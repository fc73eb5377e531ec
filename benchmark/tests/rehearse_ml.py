#!/usr/bin/env python3
"""CPU rehearsal of the cell ``ml-train-things`` at toy shapes: the cell's
own metric lists, reference and readers with the configuration of
``tests/toy/configs/toy-ml.json`` (128x128, batch 2, 2 iterations, four
levels) and the toy train traffic, through the same driver as a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_ml.py [--trace 1]

As with ``rehearse.py`` its numbers say that the control flow holds
together and nothing else: metrics come out under ``cpu_rehearsal.<name>``.
Off the TPU the per-level MatchingNets run one after the other and the
plain sampler is taken, so ``matching_levels_batched`` reads 1 and
``sw_ms`` and ``sw_roofline`` must stay away (the CPU's capture has no
device plane, and the program reports no sampler path).
"""

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))


def toy_ml_cell():
    from benchmark.harness import spec

    cell = spec.load_cell("ml-train-things")
    cell.name = "toy-ml"
    cell.config = json.loads((HERE / "toy/configs/toy-ml.json").read_text())
    cell.traffic = json.loads((HERE / "toy/traffic/toy-train.json").read_text())
    return cell


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import run as bench_run

    result = bench_run.run_cell(toy_ml_cell(), args.seed, args.seconds,
                                args.trace,
                                bench_run.ROOT / "bench_out" / "rehearsal",
                                platform="cpu")
    result["metrics"] = {f"cpu_rehearsal.{k}": v
                         for k, v in result["metrics"].items()}
    result.pop("breakdown", None)
    print(json.dumps(result), flush=True)
    os._exit(0 if result["correct"] else 1)


if __name__ == "__main__":
    main()
