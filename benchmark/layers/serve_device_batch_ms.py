"""Device busy time inside one served batch, median."""
from ._common import median_ms, trace_of


def read(run):
    t = trace_of(run, "serve")
    return None if t is None else median_ms(t["exec_busy_s"])
