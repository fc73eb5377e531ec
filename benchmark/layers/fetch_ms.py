"""Wall time one loader worker spends on one sample (``source[index]``:
render or decode, adapter and all): the mean over its batch that the step's
event carries as ``fetch``, median over the window's steps."""
from ._common import median_ms, window_events


def read(run):
    return median_ms([e["fetch"] for e in window_events(run, "step")
                      if "fetch" in e])
