"""Share of the HBM roofline the window sampler's Mosaic calls reach: the
logical bytes of map, centres and window (``harness/sw_kernel.py``) over
the chip's HBM bandwidth, over the device time of the calls. A forward
call whose text does not show its map takes the width another forward call
of the run shows."""
from ..harness import kernels, sw_kernel
from . import _sw


def read(run):
    found = _sw.calls(run)
    widths = [w for d, _, w, _, _ in found or () if w]
    if found is None or not widths:
        return None
    peak = kernels.peaks(run["devices"][0].device_kind)["hbm_bytes_per_s"]
    least = spent = 0.0
    for direction, shape, width, seconds, count in found:
        if direction == "forward":
            moved = sw_kernel.forward_bytes(*shape, width or widths[0])
        else:
            moved = sw_kernel.backward_bytes(*shape)
        least += moved / peak * count
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
