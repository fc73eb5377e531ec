"""1 - union of device-op intervals over the traced tail, serve cells."""
from ._common import trace_of


def read(run):
    t = trace_of(run, "serve")
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
