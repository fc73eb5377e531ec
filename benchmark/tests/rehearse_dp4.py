#!/usr/bin/env python3
"""CPU rehearsal of the cell ``raft-train-things-dp4`` at toy shapes on four
virtual devices: the cell's own metric lists, reference, check and readers
with the configuration of ``tests/toy/configs/toy-dp4.json`` (64x96, two
pairs a device, eight global, 2 iterations) and the toy train traffic,
through the same driver as a chip run: the ``data=4`` mesh, ``shard_batch``,
the partitioned step, its ``collectives`` record.

    python3 benchmark/tests/rehearse_dp4.py [--trace 1]

The driver takes a cell only on exactly the devices it names, so this
script gives the CPU backend four before jax is imported (whatever
``XLA_FLAGS`` said of the host's device count). As with ``rehearse.py`` its
numbers say that the control flow holds together and nothing else: metrics
come out under ``cpu_rehearsal.<name>``.
"""

import argparse
import json
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))

CELL = "raft-train-things-dp4"


def four_cpu_devices():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()


def toy_dp4_cell():
    from benchmark.harness import spec

    cell = spec.load_cell(CELL)
    cell.name = "toy-dp4"
    cell.config = json.loads((HERE / "toy/configs/toy-dp4.json").read_text())
    cell.traffic = json.loads((HERE / "toy/traffic/toy-train.json").read_text())
    return cell


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    four_cpu_devices()

    from benchmark import run as bench_run

    result = bench_run.run_cell(toy_dp4_cell(), args.seed, args.seconds,
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
