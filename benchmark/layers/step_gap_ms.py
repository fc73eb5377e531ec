"""Device-idle time between consecutive train-step executions, median."""
from ._common import median_ms, trace_of


def read(run):
    t = trace_of(run, "train")
    return None if t is None else median_ms(t["exec_gap_s"])
