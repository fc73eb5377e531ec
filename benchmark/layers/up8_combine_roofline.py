"""Share of the HBM roofline the Up8 ``_combine`` Pallas calls reach.

The kernel is bandwidth-bound (a softmax over 9 neighbours and a weighted
sum: about one operation per byte). Its least time is the bytes its shapes
make it move (``harness/kernels.py``) over the chip's HBM bandwidth; the
share is that over the device time of its calls in the traced steps. The
forward call cannot tell its logits' width from its own result, so it takes
the width the backward call of the same step shows."""
from ..harness import kernels
from ._common import trace_of


def read(run):
    t = trace_of(run, "train")
    if t is None:
        return None
    calls = []
    for name, seconds in t["op_s"].items():
        if "Up8Network" not in name.split(" = ")[0]:
            continue
        found = kernels.combine_call(name.partition(" = ")[2].split(
            " custom-call(")[0])
        if found:
            calls.append((found, seconds, t["op_count"][name]))
    widths = [w for (d, _, w), _, _ in calls if d == "backward"]
    if not calls or not widths:
        return None
    peak = kernels.peaks(run["devices"][0].device_kind)["hbm_bytes_per_s"]
    least = spent = 0.0
    for (direction, rows, width), seconds, count in calls:
        fn = (kernels.combine_forward_bytes if direction == "forward"
              else kernels.combine_backward_bytes)
        least += fn(rows, width or widths[0]) / peak * count
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
