"""What the window sampler ``ops/pallas._sw`` has to move, from the shapes
of its calls: the yardstick's side of ``sw_roofline``.

The forward call takes the window centres (B, I, J, 2) in float32 and the
second frame's feature map (zero-padded so that every window is an
in-bounds slice) and returns the (2r+1)^2 bilinear samples of every
centre, (B, I, J, K*K, C) in float32. The backward call takes the centres
and the window's cotangent and returns the padded map's cotangent in
float32. The kernel does a handful of operations per byte, so the HBM
bandwidth bounds it. Only logical bytes are counted: the map at its own
size (the padding is the kernel's convenience, and in the coarse-to-fine
models the sampled map has the resolution of the centres), each array
once. More than that the kernel may move; less it cannot, so the share
cannot pass 100%.
"""

import math
import re

_F32 = 4
_TYPE = re.compile(r"(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def forward_bytes(b, i, j, taps, c, map_bytes):
    """Reads the map (B, I, J, C) and the centres, writes the window."""
    return b * i * j * (c * map_bytes + 2 * _F32 + taps * c * _F32)


def backward_bytes(b, i, j, taps, c):
    """Reads the centres and the window's cotangent, writes the map's."""
    return b * i * j * (2 * _F32 + taps * c * _F32 + c * _F32)


def _window(dims):
    """``(b, i, j, taps, c)`` if ``dims`` is a window's shape: five axes,
    the fourth an odd square."""
    if len(dims) != 5:
        return None
    k = math.isqrt(dims[3])
    return tuple(dims) if k * k == dims[3] and k % 2 == 1 and k > 1 else None


def call(text):
    """``(direction, (b, i, j, taps, c), map_bytes)`` of one ``_sw`` custom
    call, from its instruction text as the profiler names the event; None
    when the text is no such call. The forward call's result is the
    window; the backward call's result is a padded map (four axes) and
    the window is among its operands. ``map_bytes`` is the width of the
    map's elements where the text shows the map operand, else None."""
    head, sep, rest = text.partition(" = ")
    if not sep or " custom-call(" not in rest:
        return None
    result, _, operands = rest.partition(" custom-call(")
    if result.lstrip().startswith("("):        # a tuple: another kernel
        return None
    found = _TYPE.search(result)
    if not found or found.group(1) != "f32":
        return None
    dims = [int(d) for d in found.group(2).split(",")]
    others = [(t, [int(d) for d in ds.split(",")])
              for t, ds in _TYPE.findall(operands)]
    window = _window(dims)
    if window:
        b, c = window[0], window[4]
        maps = [_BYTES[t] for t, ds in others
                if len(ds) == 4 and ds[0] == b and ds[3] == c and ds[3] != 2]
        return "forward", window, (maps[0] if maps else None)
    if len(dims) == 4:
        for t, ds in others:
            window = _window(ds)
            if (window and t == "f32" and window[0] == dims[0]
                    and window[4] == dims[3]):
                return "backward", window, None
    return None
