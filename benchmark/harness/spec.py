"""What one run is: the cell's entry of ``BENCHMARK.json`` and the data
files that entry names. Nothing here knows a cell, a configuration, a
traffic mix or a metric by name; later PRs add entries and files."""

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list     # metric entries this cell reports, untraced
    per_layer: list      # metric entries this cell reports, traced
    run_seconds: int


def _applies(metric, cell_name, reported):
    """A metric with a ``workloads`` key belongs to those cells; without
    one, to every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reported


def load_cell(workload, benchmark_json=None, bench_dir=None):
    benchmark_json = Path(benchmark_json or ROOT / "BENCHMARK.json")
    bench_dir = Path(bench_dir or BENCH_DIR)
    bench = json.loads(benchmark_json.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload '{workload}'; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((benchmark_json.parent
                         / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, None)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=layer,
                run_seconds=int(bench["run_seconds"]))


def load_reader(metric_name, package="benchmark.layers"):
    """The reader of one per-layer metric: ``layers/<metric>.py`` with a
    ``read(run)`` function that returns a number, or None when the run
    holds nothing for it to read."""
    module = importlib.import_module(
        f"{package}.{metric_name.replace('.', '_').replace('-', '_')}")
    return module.read


def load_driver(kind):
    """The driver of one kind of traffic: ``harness/<kind>.py``."""
    return importlib.import_module(f"benchmark.harness.{kind}")
