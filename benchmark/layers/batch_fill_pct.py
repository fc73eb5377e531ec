"""Real rows over batch rows of the batches dispatched in the window."""
from ._common import window_events


def read(run):
    batches = window_events(run, "serve", event="batch")
    rows = sum(e["size"] + e["fill"] for e in batches)
    return 100.0 * sum(e["size"] for e in batches) / rows if rows else None
