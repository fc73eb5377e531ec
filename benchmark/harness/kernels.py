"""What a kernel has to move, from its shapes: the yardstick's side of a
roofline share. Peaks come from ``peaks.json`` keyed by ``device_kind``; a
device that is not listed is an error, not a default."""

import json
import re
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent.parent / "peaks.json"

_K, _S, _C = 9, 64, 2      # neighbours, sub-pixels of an 8x8 cell, channels


def peaks(device_kind):
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind '{device_kind}' in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def combine_forward_bytes(rows, logit_bytes):
    """``ops/pallas._combine`` forward: reads the (rows, 576) mask logits
    and the (rows, 18) float32 neighbour windows, writes (rows, 128)
    float32."""
    return rows * (_K * _S * logit_bytes + _K * _C * 4 + _C * _S * 4)


def combine_backward_bytes(rows, logit_bytes):
    """Its backward: reads logits, windows and the (rows, 128) cotangent,
    writes the logits' and the windows' cotangents."""
    return rows * (2 * _K * _S * logit_bytes + 2 * _K * _C * 4 + _C * _S * 4)


_SHAPE = re.compile(r"(bf16|f32|f16)\[(\d+),(\d+)\]")


def combine_call(text):
    """``(direction, rows, logit_bytes)`` of one ``_combine`` custom call,
    read off its result type in the profiler's text (the forward returns
    f32[rows,128], the backward leads with the logits' cotangent
    [rows,576]); None when the text names neither."""
    for dtype, rows, cols in _SHAPE.findall(text):
        if int(cols) == _C * _S and dtype == "f32":
            return "forward", int(rows), None
        if int(cols) == _K * _S:
            return "backward", int(rows), 2 if dtype == "bf16" else 4
    return None
