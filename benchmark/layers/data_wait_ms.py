"""Median of the loop's ``data_wait`` phase over the window's steps."""
from ._common import median_ms, window_events


def read(run):
    return median_ms([e["phases"]["data_wait"]
                      for e in window_events(run, "step")
                      if "data_wait" in e.get("phases", {})])
