"""The toy cells of the CPU rehearsal and the self-tests: a real cell's
metric lists with the toy configuration and traffic of ``tests/toy/``."""

import json
from pathlib import Path

TOY = Path(__file__).resolve().parent / "toy"
LIKE = {"train": "raft-train-things", "serve": "raft-serve-mixed"}


def toy_cell(kind):
    from benchmark.harness import spec

    cell = spec.load_cell(LIKE[kind])
    cell.name = f"toy-{kind}"
    cell.config = json.loads((TOY / "configs/toy-raft.json").read_text())
    cell.traffic = json.loads((TOY / f"traffic/toy-{kind}.json").read_text())
    return cell
