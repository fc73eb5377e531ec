"""The sampler's readers and the new note's on a recorded capture of two
train steps of ``ml-train-things`` on one v5e chip (my chip run, PR 32,
seed 1638155874; ``tests/dump_ops.py`` made it): twelve iterations of four
levels, each level's map coarser than the centres by another factor of two.

    python3 -m pytest benchmark/tests/test_ml_readers.py -q
"""

import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import sw_kernel, xtrace  # noqa: E402
from benchmark.layers import (_sw, matching_levels_batched,  # noqa: E402
                              matching_mb_per_step, sw_ms, sw_roofline)

DATA = Path(__file__).parent / "data"
WINDOW = (6, 48, 80, 81, 32)
# the padded maps of the four levels: 48x80 centres, the map of level l
# 2^l times coarser, 9 + 10 samples of padding on each axis (x leading)
MAPS = {(99, 67): 0, (59, 43): 1, (39, 31): 2, (29, 25): 3}


@pytest.fixture(scope="module")
def run():
    reduced = xtrace.reduce(
        xtrace.load_saved(DATA / "ml_capture_small.json.gz"), "jit_step")
    return {"kind": "train",
            "events": json.loads((DATA / "ml_capture_events.json")
                                 .read_text()),
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": reduced}


def _level(text):
    """Which level's call: by the padded map among its types, operand of
    the forward call and result of the backward one."""
    for dims in re.findall(r"f32\[6,(\d+),(\d+),32\]", text):
        if tuple(map(int, dims)) in MAPS:
            return MAPS[tuple(map(int, dims))]
    return None


def test_forward_and_backward_calls_of_all_four_levels_are_told(run):
    assert run["trace"]["executions"] == 2
    found = {}
    for text, count in run["trace"]["op_count"].items():
        parsed = sw_kernel.call(text)
        if parsed and not text.lstrip("%").startswith("custom-call"):
            direction, window, map_bytes = parsed
            assert window == WINDOW
            assert map_bytes == (4 if direction == "forward" else None)
            key = (direction, _level(text))
            found[key] = found.get(key, 0) + count / 2
    # a step: twelve iterations, each level sampled forward in the forward
    # pass and again when the backward pass recomputes the iteration, and
    # once backward
    assert found == {("forward", lvl): 24 for lvl in range(4)} | {
        ("backward", lvl): 12 for lvl in range(4)}


def test_the_program_says_forty_eight_kernel_calls_and_four_levels(run, capsys):
    assert _sw.path_counts(run) == (48, 0)
    assert matching_levels_batched.read(run) == 4.0
    assert matching_mb_per_step.read(run) == 5803.86816
    assert len(_sw.calls(run)) == 12
    assert "sw_fused_calls=48 sw_fallback_calls=0" in capsys.readouterr().out


def test_readers_on_the_recorded_capture(run):
    # as the traced run itself read them over its ten steps (my chip run,
    # PR 32): 224.21 of the 232.42 ms of Mosaic time, 18.98% of the
    # roofline; the two steps kept here read the same to the second digit
    ms, share = sw_ms.read(run), sw_roofline.read(run)
    assert abs(ms - 224.21) < 0.1 and abs(share - 18.98) < 0.02
    mosaic = 1e3 * run["trace"]["class_s_per_exec"]["mosaic"]
    assert abs(mosaic - 232.42) < 0.1
    # the share from the shapes alone: 96 forward and 48 backward calls
    # of one window each, the map counted at the centres' resolution
    # (the yardstick's convention: at most 1.2% over the coarser levels'
    # own bytes, 128 B of map against 10,368 B of window a position)
    least = (96 * sw_kernel.forward_bytes(*WINDOW, 4)
             + 48 * sw_kernel.backward_bytes(*WINDOW)) / 819e9
    assert abs(share - 100 * least / (ms / 1e3)) < 1e-6
    assert share < 23.0
