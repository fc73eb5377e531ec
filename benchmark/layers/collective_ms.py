"""Device time a train step spends in collective operations on the first
chip: the gradient all-reduces, the resharding of the pair concatenation
and of whatever else the partitioner moved (``all-to-all``,
``collective-permute``), their asynchronous halves included. From the
reduced trace's operation classes; nothing on one chip."""
from . import _chips


def read(run):
    t = _chips.traced(run)
    return None if t is None else \
        1e3 * t["class_s_per_exec"].get("collective", 0.0)
