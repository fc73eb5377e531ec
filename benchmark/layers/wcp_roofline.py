"""Share of its roofline the windowed correlation's Mosaic kernels reach:
the least time the windowed levels' work of a train step can take
(``harness/wcp_kernel.py``: logical bytes over the chip's HBM bandwidth or
multiply-adds over its peak, whichever is larger, reckoned from the cell's
shapes and the program's ``wcp_levels_windowed``) over the device time of
the kernels' calls a step (``wcp_ms``)."""
from ..harness import kernels, wcp_kernel
from . import _wcp


def read(run):
    found = _wcp.calls(run)
    said = _wcp.notes(run) or {}
    if found is None or not said.get(_wcp.LEVELS):
        return None
    peaks = kernels.peaks(run["devices"][0].device_kind)
    least = wcp_kernel.least_seconds(run["cell"].config, run["batch"],
                                     said[_wcp.LEVELS], peaks)
    spent = _wcp.seconds_a_step(run, found)
    return 100.0 * least / spent if spent > 0 else None
