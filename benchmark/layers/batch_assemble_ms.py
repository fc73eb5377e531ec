"""Dispatch thread, per batch: ``dispatch`` to ``assembled`` (faults
culled, members stacked, the batch padded by tiling), median."""
from ._common import median_ms
from ._timeline import batch_marks


def read(run):
    return median_ms([m["assembled"] - m["dispatch"]
                      for m in batch_marks(run)])
