"""Largest less smallest of the chips' busy time a step: a straggler, or an
uneven slice of the batch. Each chip's busy time is the union of the
intervals in which an operation ran on it over the traced tail."""
from . import _chips


def read(run):
    t = _chips.traced(run)
    if t is None:
        return None
    busy = t["busy_s_per_chip"]
    return 1e3 * (max(busy) - min(busy)) / t["executions"]
