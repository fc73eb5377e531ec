#!/usr/bin/env python3
"""CPU rehearsal of ``run.py``, end to end, at toy shapes.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py --kind train|serve [--trace 1]

Takes a real cell's metric lists from ``BENCHMARK.json`` and swaps in the
toy configuration and traffic of ``tests/toy/``; drives the same driver,
readers, reference and check as a chip run. Its numbers say that the
control flow holds together and nothing else: every metric is printed
under ``cpu_rehearsal.<name>``, never under a device metric's name, and
the device block says ``cpu``.
"""

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--kind", choices=("serve", "train"), required=True)
    parser.add_argument("--seed", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import run as bench_run
    from benchmark.tests.toy_cell import toy_cell

    cell = toy_cell(args.kind)
    result = bench_run.run_cell(cell, args.seed, args.seconds, args.trace,
                                bench_run.ROOT / "bench_out" / "rehearsal",
                                platform="cpu")
    result["metrics"] = {f"cpu_rehearsal.{k}": v
                         for k, v in result["metrics"].items()}
    result.pop("breakdown", None)
    print(json.dumps(result), flush=True)
    os._exit(0 if result["correct"] else 1)


if __name__ == "__main__":
    main()
