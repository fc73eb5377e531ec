"""The trace reduction on a small recorded capture: two train steps of
``raft-train-things`` on one v5e chip (my chip run, PR 23), trimmed to the
device's ``XLA Modules`` and ``XLA Ops`` lines and the host spans over
0.2 ms, instruction texts cut down to ``%name = type opcode(), kind=...``.

    python3 -m pytest benchmark/tests/test_xtrace.py -q

What the reduction says is held against a brute-force reading of the same
lists (a microsecond grid), so nobody has to trust its interval logic.
"""

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import kernels, xtrace  # noqa: E402

CAPTURE = Path(__file__).parent / "data" / "train_capture_small.json.gz"


def _device_ops(capture):
    plane = next(p for p in capture["planes"] if p["name"] == "/device:TPU:0")
    line = next(ln for ln in plane["lines"] if ln["name"] == "XLA Ops")
    return [e for e in line["events"] if e[2] > 0
            and xtrace.parse_op(e[0])[1] not in ("while", "call")]


def test_reduction_agrees_with_a_brute_force_reading():
    capture = xtrace.load_saved(CAPTURE)
    r = xtrace.reduce(capture, "jit_step")
    ops = _device_ops(capture)
    t0 = min(e[1] for e in ops)
    t1 = max(e[1] + e[2] for e in ops)
    grid = np.zeros(int((t1 - t0) / 1e3) + 2, bool)       # 1 us cells
    for _, s, d, _ in ops:
        grid[int((s - t0) / 1e3): int((s + d - t0) / 1e3) + 1] = True
    assert abs(r["window_s"] - (t1 - t0) / 1e9) < 1e-9
    assert abs(r["busy_s"] - grid.sum() / 1e6) < 0.02 * r["busy_s"]
    assert r["chips"] == 1 and r["executions"] == 2
    # the step as the ledger of PR 22 and every run of PR 23 read it
    assert all(abs(b - 0.3473) < 0.0005 for b in r["exec_busy_s"])
    assert len(r["exec_gap_s"]) == 1 and 0.03 < r["exec_gap_s"][0] < 0.07
    # classes cover the step's busy time, each op in exactly one
    assert abs(sum(r["class_s_per_exec"].values())
               - sum(r["exec_busy_s"]) / 2) < 0.002
    assert abs(r["class_s_per_exec"]["mosaic"] - 0.00963) < 1e-4
    assert 0.17 < r["class_s_per_exec"]["conv"] < 0.18


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    r = xtrace.reduce(xtrace.load_saved(CAPTURE), "jit_step")
    name, longest = r["idle_gaps"][0]
    assert name == "XlaLinearize" and 0.03 < longest < 0.05
    idle = r["window_s"] - r["busy_s"]
    named = sum(r["idle_by_host_span"].values()) + r["idle_small_gaps_s"]
    assert abs(idle - named) < 1e-6
    b = xtrace.breakdown(r)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion.3196:bf16[6,50,90,50,90]:conv"


def test_op_class_reads_the_instructions_own_opcode_not_its_operands():
    cases = {
        "%fusion.3196 = bf16[6,50,90,50,90]{4,3,2,1,0:T(8,128)(2,1)} fusion("
        "bf16[6,50,90,256] %custom-call.7), kind=kOutput, calls=%fc": "conv",
        "%convolution_convert_fusion.31 = bf16[6,50,90,9,90]{4,3,2,1,0} fusion("
        "f32[1] %p), kind=kOutput": "conv",
        "%Up8Network_0.3 = (bf16[324096,576]{1,0:T(8,128)(2,1)}, f32[324096,18])"
        " custom-call(f32[324096,128] %fusion.1), custom_call_target="
        '"tpu_custom_call"': "mosaic",
        "%fusion.2428 = f32[72,50,90,8]{3,2,1,0:T(8,128)} fusion(f32[2] "
        "%convolution.3), kind=kLoop, calls=%f": "elementwise",
        "%all-reduce-start.3 = f32[256]{0} all-reduce-start(f32[256] %x)":
            "collective",
        "%copy.3925 = bf16[6,50,90,128]{3,1,2,0} copy(bf16[6,50,90,128] %c)":
            "copy",
        "%reduce_fusion.2 = f32[6]{0} fusion(f32[6,400] %x), kind=kInput":
            "reduce",
    }
    for text, want in cases.items():
        assert xtrace.op_class(text) == want, text
    assert xtrace.parse_op("XlaLinearize") == ("XlaLinearize", "", "")


def test_combine_bytes_and_peaks():
    assert kernels.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        kernels.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device got peaks")
    rows = 324096
    assert kernels.combine_forward_bytes(rows, 2) == rows * (1152 + 72 + 512)
    assert kernels.combine_backward_bytes(rows, 2) == rows * (2304 + 144 + 512)
    assert kernels.combine_call("(bf16[324096,576]{1,0}, f32[324096,18]{1,0})") \
        == ("backward", 324096, 2)
    assert kernels.combine_call("f32[324096,128]{1,0:T(8,128)}") \
        == ("forward", 324096, None)
