#!/usr/bin/env python3
"""CPU rehearsal of the cell ``raft-dicl-serve-mixed`` at toy shapes: the
cell's own metric lists, driver (``harness/serve_models.py``), reference
dispatch and readers with the configuration of
``tests/toy/configs/toy-one-server.json`` (``toy-raft``'s and ``toy-dicl``'s
models behind one scheduler, device batch 2) and the traffic of
``tests/toy/traffic/toy-serve-two-models.json``.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_one_server.py [--trace 1]

As with ``rehearse.py`` its numbers say that the control flow holds
together and nothing else: metrics come out under ``cpu_rehearsal.<name>``.
The CPU's capture has no device plane, so of the cell's six own metrics
``serve_switch_gap_ms`` stays away; the five that read records and events
(a percentile and a fill a model, the share of switches) must read.
"""

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))


def toy_one_server_cell():
    from benchmark.harness import spec

    cell = spec.load_cell("raft-dicl-serve-mixed")
    cell.name = "toy-one-server"
    cell.config = json.loads(
        (HERE / "toy/configs/toy-one-server.json").read_text())
    cell.traffic = json.loads(
        (HERE / "toy/traffic/toy-serve-two-models.json").read_text())
    return cell


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import run as bench_run

    result = bench_run.run_cell(toy_one_server_cell(), args.seed,
                                args.seconds, args.trace,
                                bench_run.ROOT / "bench_out" / "rehearsal",
                                platform="cpu")
    result["metrics"] = {f"cpu_rehearsal.{k}": v
                         for k, v in result["metrics"].items()}
    result.pop("breakdown", None)
    print(json.dumps(result), flush=True)
    os._exit(0 if result["correct"] else 1)


if __name__ == "__main__":
    main()
