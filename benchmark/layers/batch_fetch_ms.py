"""Dispatch thread, per batch: ``ready`` to ``fetched``, the result's
device-to-host fetch, median."""
from ._common import median_ms
from ._timeline import batch_marks


def read(run):
    return median_ms([m["fetched"] - m["ready"] for m in batch_marks(run)])
