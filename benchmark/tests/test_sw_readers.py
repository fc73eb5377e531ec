"""The window sampler's readers (``sw_ms``, ``sw_roofline``) and their
byte functions.

    python3 -m pytest benchmark/tests/test_sw_readers.py -q

On a small recorded capture, two train steps of ``ctf3-train-things`` on
one v5e chip (my chip run, PR 26; ``tests/dump_ops.py`` made it), and on
instruction texts of the shapes the calls have there.
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import sw_kernel, xtrace  # noqa: E402
from benchmark.layers import (_sw, matching_mb_per_step, sw_ms,  # noqa: E402
                              sw_roofline)

DATA = Path(__file__).parent / "data"
CAPTURE = DATA / "ctf3_capture_small.json.gz"
EVENTS = DATA / "ctf3_capture_events.json"

FWD = ("%sampler.39 = f32[6,48,88,81,32]{4,3,2,1,0:T(8,128)} custom-call("
       "f32[6,48,88,2]{3,2,1,0} %bitcast_add_fusion.5, bf16[6,66,129,32]"
       "{3,2,1,0} %pad.118), custom_call_target=\"tpu_custom_call\"")
BWD = ("%sampler.40 = f32[6,66,129,32]{3,2,1,0:T(8,128)S(1)} custom-call("
       "f32[6,48,88,2]{3,2,1,0} %copy-done.698, f32[6,48,88,81,32]"
       "{4,3,2,1,0} %copy.3281), custom_call_target=\"tpu_custom_call\"")
UP8 = ("%Up8Network_0.1 = (bf16[76032,576]{1,0:T(8,128)(2,1)S(1)}, "
       "f32[76032,18]{1,0}) custom-call(bf16[76032,576]{1,0} %pad.94, "
       "f32[76032,18]{1,0} %pad.96, f32[76032,128]{1,0} %pad.97), "
       "custom_call_target=\"tpu_custom_call\"")
MARKER = "%custom-call.9 = f32[6,48,88,81,32]{4,3,2,1,0} custom-call()"


def test_a_call_is_told_by_its_result_and_operands():
    assert sw_kernel.call(FWD) == ("forward", (6, 48, 88, 81, 32), 2)
    assert sw_kernel.call(BWD) == ("backward", (6, 48, 88, 81, 32), None)
    assert sw_kernel.call(UP8) is None
    assert sw_kernel.call("%fusion.3 = f32[6,48,88,81,32]{4,3,2,1,0} "
                          "fusion(f32[2] %x), kind=kLoop") is None
    # a window has an odd square of taps
    assert sw_kernel.call(FWD.replace("88,81,32]", "88,80,32]")) is None


def test_logical_bytes():
    b, i, j, taps, c = 6, 48, 88, 81, 32
    positions = b * i * j
    assert sw_kernel.forward_bytes(b, i, j, taps, c, 2) == positions * (
        c * 2 + 8 + taps * c * 4)
    assert sw_kernel.backward_bytes(b, i, j, taps, c) == positions * (
        8 + taps * c * 4 + c * 4)


def _run(events, op_s, executions=2):
    return {"kind": "train", "events": events,
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": {"executions": executions, "op_s": op_s,
                      "op_count": {k: executions for k in op_s}}}


def _aot(**counts):
    return {"kind": "aot", "event": "hit", "program": "train_step", **counts}


def test_readers_on_texts(capsys):
    ops = {FWD: 0.020, BWD: 0.010, UP8: 0.004, MARKER: 2e-9}
    run = _run([_aot(sw_fused_calls=10, matching_volume_bytes=1)], ops)
    assert abs(sw_ms.read(run) - 15.0) < 1e-9
    least = 2 * (sw_kernel.forward_bytes(6, 48, 88, 81, 32, 2)
                 + sw_kernel.backward_bytes(6, 48, 88, 81, 32)) / 819e9
    assert abs(sw_roofline.read(run) - 100 * least / 0.030) < 1e-9
    out = capsys.readouterr().out
    assert "sw_fused_calls=10 sw_fallback_calls=0" in out
    assert "'48x88:backward': 5.0" in out and "'48x88:forward': 10.0" in out


@pytest.mark.parametrize("events, says", [
    ([_aot(sw_fused_calls=7, sw_fallback_calls=3)], "sw_fallback_calls=3"),
    ([_aot(sw_fallback_calls=10)], "sw_fused_calls=0"),
    ([{"kind": "compile", "label": "train_step", "seconds": 1.0,
       "sw_fused_calls": 9, "sw_fallback_calls": 1}], "sw_fallback_calls=1"),
    ([_aot()], "reports no sampler path"),          # an older program
    ([_aot(sw_fused_calls=10) | {"program": "eval_step"}],
     "reports no sampler path"),
])
def test_a_fallback_or_a_silent_program_yields_no_reading(events, says, capsys):
    run = _run(events, {FWD: 0.020, BWD: 0.010})
    assert sw_ms.read(run) is None and sw_roofline.read(run) is None
    assert says in capsys.readouterr().out


def test_no_trace_no_reading():
    run = _run([_aot(sw_fused_calls=10)], {FWD: 0.02})
    run["trace"] = None
    assert sw_ms.read(run) is None and sw_roofline.read(run) is None


def test_readers_on_the_recorded_capture():
    reduced = xtrace.reduce(xtrace.load_saved(CAPTURE), "jit_step")
    run = {"kind": "train", "events": json.loads(EVENTS.read_text()),
           "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
           "trace": reduced}
    assert reduced["executions"] == 2
    found = _sw.calls(run)
    # three levels, each forward (twice a step: the backward pass
    # recomputes it) and backward
    assert sorted({(d, s[1], s[2]) for d, s, *_ in found}) == [
        ("backward", 12, 22), ("backward", 24, 44), ("backward", 48, 88),
        ("forward", 12, 22), ("forward", 24, 44), ("forward", 48, 88)]
    per_step = {}
    for d, s, _, _, count in found:
        per_step[(d, s[1])] = per_step.get((d, s[1]), 0) + count / 2
    assert per_step == {("forward", 12): 8, ("forward", 24): 6,
                        ("forward", 48): 6, ("backward", 12): 4,
                        ("backward", 24): 3, ("backward", 48): 3}
    # as the traced run itself read them (my chip run, PR 26): 69.30 of
    # the 71.56 ms of Mosaic time, 5.61% of the roofline, 532.02 MB
    ms, share = sw_ms.read(run), sw_roofline.read(run)
    assert abs(ms - 69.30) < 0.05 and abs(share - 5.606) < 0.01
    assert abs(1e3 * reduced["class_s_per_exec"]["mosaic"] - 71.56) < 0.05
    assert matching_mb_per_step.read(run) == 532.021248
