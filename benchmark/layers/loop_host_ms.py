"""Loop-thread time of a step outside its waits: ``data`` to
``dispatched`` (callbacks, schedules, the step's dispatch) plus ``synced``
to ``done`` (metrics, inspector, events), median over the window's steps."""
from ._common import median_ms, window_events


def read(run):
    marks = [e["marks"] for e in window_events(run, "step") if "marks" in e]
    return median_ms([(m["dispatched"] - m["data"]) + (m["done"] - m["synced"])
                      for m in marks])
