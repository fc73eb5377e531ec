"""``fetch_ms`` and ``host_cpu_pct`` on a recorded events file: the boot
span and the step events round the window of one CPU rehearsal of the toy
train cell (``data/input_events.json``).

    python3 -m pytest benchmark/tests/test_input_readers.py -q

The readers give what the file's numbers give by hand, and None on a
program that writes neither reading (the parent of PR 40).
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec  # noqa: E402
from benchmark.layers import fetch_ms, host_cpu_pct  # noqa: E402

RECORD = Path(__file__).parent / "data" / "input_events.json"


def _run():
    record = json.loads(RECORD.read_text())
    return {"kind": "train", "events": record["events"],
            "readings": {"window_wall": tuple(record["window_wall"])}}


def _without(run, *keys):
    return dict(run, events=[{k: v for k, v in e.items() if k not in keys}
                             for e in run["events"]])


def test_readers_on_the_recorded_window():
    run = _run()
    # the median of the twenty steps' means; nineteen pairs of steps on a
    # host of eight CPUs, the median pair burning 5.41 CPU-seconds a second
    assert fetch_ms.read(run) == pytest.approx(13.0235, abs=1e-4)
    assert host_cpu_pct.read(run) == pytest.approx(67.6427, abs=1e-3)


def test_steps_outside_the_window_are_not_read():
    run = _run()
    inside = [e for e in run["events"] if e["kind"] == "step"
              and run["readings"]["window_wall"][0] <= e["t"]
              <= run["readings"]["window_wall"][1]]
    assert len(inside) == 20 < sum(e["kind"] == "step" for e in run["events"])
    assert fetch_ms.read(dict(run, events=inside)) == fetch_ms.read(run)


def test_a_program_without_the_readings_reads_none():
    run = _run()
    old = _without(run, "fetch", "cpu", "cpus")
    assert fetch_ms.read(old) is None
    assert host_cpu_pct.read(old) is None
    # the CPU-seconds are a share of something: no count of CPUs, no share
    assert host_cpu_pct.read(_without(run, "cpus")) is None
    # and one step alone has no step to be measured against
    first = [e for e in run["events"] if e["kind"] != "step"] + \
        [e for e in run["events"] if e["kind"] == "step"][2:3]
    assert fetch_ms.read(dict(run, events=first)) is not None
    assert host_cpu_pct.read(dict(run, events=first)) is None


def test_both_are_listed_for_the_train_cells_only():
    for name in ("fetch_ms", "host_cpu_pct"):
        assert callable(spec.load_reader(name))
    train = spec.load_cell("raft-train-things-dp4")
    serve = spec.load_cell("raft-serve-mixed")
    assert {"fetch_ms", "host_cpu_pct"} <= {m["name"] for m in train.per_layer}
    assert not {"fetch_ms", "host_cpu_pct"} & {m["name"]
                                               for m in serve.per_layer}
