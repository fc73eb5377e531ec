"""``pull_ms`` on a recorded events file: the boot span and the step events
round the window of one CPU rehearsal of the toy train cell with ``pull``
beside ``put`` in its step events (``data/pull_events.json``), and on the
one recorded before the program wrote it (``data/input_events.json``).

    python3 -m pytest benchmark/tests/test_pull_reader.py -q

The reader gives what the file's numbers give by hand, and None on a
program that writes no ``pull`` (the parent of PR 42).
"""

import json
import statistics
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec  # noqa: E402
from benchmark.layers import fetch_ms, pull_ms, put_ms  # noqa: E402

DATA = Path(__file__).parent / "data"
TRAIN = ["raft-train-things", "ctf3-train-things", "ml-train-things",
         "fs-train-1080p", "raft-train-things-dp4"]


def _run(name):
    record = json.loads((DATA / name).read_text())
    return {"kind": "train", "events": record["events"],
            "readings": {"window_wall": tuple(record["window_wall"])}}


def test_the_reader_on_the_recorded_window():
    run = _run("pull_events.json")
    # the median of the twenty steps' pulls: 0.269 and 0.270 ms the middle
    assert pull_ms.read(run) == pytest.approx(0.2695, abs=1e-4)
    inside = [e for e in run["events"] if e["kind"] == "step"
              and run["readings"]["window_wall"][0] <= e["t"]
              <= run["readings"]["window_wall"][1]]
    assert len(inside) == 20 < sum(e["kind"] == "step" for e in run["events"])
    assert pull_ms.read(run) == pytest.approx(1e3 * statistics.median(
        e["pull"][1] - e["pull"][0] for e in inside))
    assert pull_ms.read(dict(run, events=inside)) == pull_ms.read(run)
    # one thread, one thing after the other: a batch's pull ends before its
    # put starts, and the two readers read the same steps
    assert all(e["pull"][1] <= e["put"][0] for e in inside)
    assert put_ms.read(run) == pytest.approx(2.092, abs=1e-3)
    assert fetch_ms.read(run) is not None


def test_a_program_without_pull_reads_none():
    run = _run("pull_events.json")
    old = dict(run, events=[{k: v for k, v in e.items() if k != "pull"}
                            for e in run["events"]])
    assert pull_ms.read(old) is None
    assert put_ms.read(old) == put_ms.read(run)
    # and the run recorded at PR 40, whose program wrote none
    before = _run("input_events.json")
    assert pull_ms.read(before) is None
    assert fetch_ms.read(before) is not None


def test_it_is_listed_for_the_train_cells_only():
    assert callable(spec.load_reader("pull_ms"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = [m for m in bench["per_layer"] if m["name"] == "pull_ms"]
    assert entry == {"name": "pull_ms", "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "input pipeline",
                     "moves": "train_pairs_per_s", "workloads": TRAIN}
    for name in TRAIN:
        assert "pull_ms" in {m["name"] for m in
                             spec.load_cell(name).per_layer}
    for name in ("raft-serve-mixed", "dicl-serve-mixed", "raft-serve-sintel"):
        assert "pull_ms" not in {m["name"] for m in
                                 spec.load_cell(name).per_layer}
