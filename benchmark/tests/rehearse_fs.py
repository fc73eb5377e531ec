#!/usr/bin/env python3
"""CPU rehearsal of the cell ``fs-train-1080p`` at toy shapes: the cell's
own metric lists, reference and readers with the configuration of
``tests/toy/configs/toy-fs.json`` (128x128, batch 2, 2 iterations, four
levels, every width the cell's) and the toy train traffic, through the
same driver as a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_fs.py [--trace 1]

As with ``rehearse.py`` its numbers say that the control flow holds
together and nothing else: metrics come out under ``cpu_rehearsal.<name>``.
At the toy grid every level's volume fits the budget, so
``wcp_levels_windowed`` reads 0 and the windowed correlation is never
called; ``RMD_FS_VOLUME_GIB=0`` in the environment puts all four levels
on it, through the XLA composition a CPU takes (``wcp_fallback_calls``),
so ``wcp_ms`` and ``wcp_roofline`` must stay away either way.
"""

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))


def toy_fs_cell():
    from benchmark.harness import spec

    cell = spec.load_cell("fs-train-1080p")
    cell.name = "toy-fs"
    cell.config = json.loads((HERE / "toy/configs/toy-fs.json").read_text())
    cell.traffic = json.loads((HERE / "toy/traffic/toy-train.json").read_text())
    # the toy traffic's limits are raft/baseline's toy readings; here the
    # gradient's worst leaf is a convolution bias in front of an instance
    # norm (its gradient is zero but for bf16 rounding, and without the
    # 1/sqrt(C) the costs' gradients are sixteen times larger): 3.5 to
    # 5.8 on a CPU. The gradient is held leaf by leaf in float32 by
    # tests/test_reference_fs.py and at the cell's own size on the chip
    cell.traffic["rehearsal_limits"] = dict(
        cell.traffic["rehearsal_limits"], grad_norm_gap=30.0)
    return cell


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import run as bench_run

    result = bench_run.run_cell(toy_fs_cell(), args.seed, args.seconds,
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
