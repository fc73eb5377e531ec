#!/usr/bin/env python3
"""CPU rehearsal of the cell ``ctf3-train-things`` at toy shapes: the
cell's own metric lists, reference and readers with the configuration of
``tests/toy/configs/toy-ctf3.json`` (64x128, batch 2, iterations 2/1/1)
and the toy train traffic, through the same driver as a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_ctf3.py [--trace 1]

As with ``rehearse.py`` its numbers say that the control flow holds
together and nothing else: metrics come out under ``cpu_rehearsal.<name>``.
On the CPU every sampler call takes the XLA path, so ``sw_ms`` and
``sw_roofline`` must stay away and the ``[sw]`` line must say why.
"""

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))


def toy_ctf3_cell():
    from benchmark.harness import spec

    cell = spec.load_cell("ctf3-train-things")
    cell.name = "toy-ctf3"
    cell.config = json.loads((HERE / "toy/configs/toy-ctf3.json").read_text())
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

    result = bench_run.run_cell(toy_ctf3_cell(), args.seed, args.seconds,
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
