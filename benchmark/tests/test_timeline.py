"""The timeline join on the recorded capture of ``test_xtrace.py`` (two
train steps of ``raft-train-things`` on one v5e chip) with synthetic marks
of the loop thread laid around its two executions.

    python3 -m pytest benchmark/tests/test_timeline.py -q

The split of the gap sums to the gap exactly, agrees with the outside-in
``step_gap_ms`` of the same capture, and a clock that is off makes every
join reader return None.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import xtrace  # noqa: E402
from benchmark.layers import (_timeline, step_gap_host_ms,  # noqa: E402
                              step_gap_launch_ms, step_gap_ms)
from benchmark.tests.toy_cell import toy_cell  # noqa: E402

CAPTURE = Path(__file__).parent / "data" / "train_capture_small.json.gz"
START_NS = 1_790_000_000_000_000_000     # the capture's Unix origin
PERF0 = 5000.0                           # perf_counter at that instant


def _perf(ns):
    return PERF0 + ns / 1e9


def _run(monkeypatch, tmp_path, host_share, clock_error_ns=0.0):
    """A traced train run whose capture is the recorded one and whose loop
    entered step 1's call after ``host_share`` of the gap behind step 0."""
    capture = xtrace.load_saved(CAPTURE)
    (s0, e0), (s1, e1) = _timeline.device_intervals(capture, "jit_step")[0]
    cut = e0 + host_share * (s1 - e0)
    steps = []
    # as on the chip: the loop enters the step's call ('put') and the call
    # returns ('dispatched') 60 ms later, long after the device started
    for step, (put, synced) in enumerate(
            [(s0 - 1e6, s0 + 59.1e6), (cut, e1 + 0.3e6)]):
        start = put - 2e6
        marks = {"start": start, "data": start + 1e5, "prep": put,
                 "put": put, "dispatched": put + 60e6,
                 "synced": max(synced, put + 60.1e6),
                 "done": max(synced, put + 60.1e6) + 5e5}
        steps.append({"kind": "step", "step": step, "t": 0.0,
                      "marks": {k: _perf(v) for k, v in marks.items()}})
    events = [{"kind": "clock", "t": 0.0, "perf_counter": PERF0,
               "time_ns": START_NS + clock_error_ns},
              {"kind": "device_sync", "t": 0.0, "step": 1, "seconds": 0.3},
              *steps]
    monkeypatch.setattr(xtrace, "find_xplane", lambda d: tmp_path / "x.pb")
    monkeypatch.setattr(xtrace, "load", lambda p: capture)
    monkeypatch.setattr(_timeline, "profile_start_ns", lambda p: START_NS)
    return {"kind": "train", "cell": toy_cell("train"), "events": events,
            "trace_dir": tmp_path, "trace": xtrace.reduce(capture, "jit_step")}


@pytest.mark.parametrize("host_share", [0.0, 0.25, 0.8, 1.0])
def test_gap_split_sums_exactly(monkeypatch, tmp_path, capsys, host_share):
    run = _run(monkeypatch, tmp_path, host_share)
    host, launch = step_gap_host_ms.read(run), step_gap_launch_ms.read(run)
    gap = step_gap_ms.read(run)               # the outside-in reading
    assert host + launch == pytest.approx(gap, abs=1e-9)
    # the device is idle nearly all through this gap, so the parts follow
    # the mark's place in it
    assert host == pytest.approx(host_share * gap, abs=0.02 * gap)
    j = _timeline.of(run)
    assert j["ok"] and j["matched"] == j["executions"] == 2
    assert j["lag_ns"] == [pytest.approx(0.3e6, abs=1e3)]   # step 1 alone
    assert j["idle_between_s"] * 1e3 == pytest.approx(gap, abs=1e-9)
    # the rest of the tail's idle time lies inside the executions
    # ('in_step', not split) or outside the first and the last
    assert j["idle_s"] >= j["idle_between_s"] + j["idle_in_step_s"] - 1e-9
    # the loop's phases inside the host part: before the call it was in
    # host_prep or between two steps, never waiting on the device
    if host_share > 0:
        assert "device" not in j["host_shares"]
        assert sum(j["host_shares"].values()) <= 1.0 + 1e-9
    out = capsys.readouterr().out
    assert out.count("[clock] ok=True") == 1 and out.count("[gaps] ") == 1


@pytest.mark.parametrize("error_ms", [-3.0, 60.0])
def test_a_shifted_clock_leaves_the_split_out(monkeypatch, tmp_path, capsys,
                                              error_ms):
    """Marks 3 ms early put 'synced' before the device's end; marks 60 ms
    late put the call's entry after the device's start."""
    run = _run(monkeypatch, tmp_path, 0.25, clock_error_ns=-error_ms * 1e6)
    assert step_gap_host_ms.read(run) is None
    assert step_gap_launch_ms.read(run) is None
    assert "[clock] ok=False" in capsys.readouterr().out


def test_nothing_to_join_returns_none(monkeypatch, tmp_path):
    run = _run(monkeypatch, tmp_path, 0.5)
    # a program older than the marks: no clock event, no marks
    old = dict(run, events=[e for e in run["events"]
                            if e["kind"] == "device_sync"])
    assert step_gap_host_ms.read(old) is None
    # an untraced run, and a capture without a device plane (the CPU)
    assert step_gap_host_ms.read(dict(run, trace_dir=None)) is None
    monkeypatch.setattr(xtrace, "load", lambda p: {"planes": []})
    run.pop("_timeline", None)
    assert step_gap_launch_ms.read(run) is None
