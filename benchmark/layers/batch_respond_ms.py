"""Dispatch thread, per batch: ``fetched`` to ``completed`` (events,
crops, releases) plus ``completed`` to the loop's next ``wait``, median."""
from ._common import median_ms
from ._timeline import batch_marks


def read(run):
    marks = batch_marks(run)
    after = [b["wait"] - a["completed"] for a, b in zip(marks, marks[1:])]
    return median_ms([(m["completed"] - m["fetched"]) + back
                      for m, back in zip(marks, after + [0.0])])
