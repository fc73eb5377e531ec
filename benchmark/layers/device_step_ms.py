"""Device busy time inside one execution of the train step, median."""
from ._common import median_ms, trace_of


def read(run):
    t = trace_of(run, "train")
    return None if t is None else median_ms(t["exec_busy_s"])
