"""Summed time the witness's ticker woke late by more than 50 ms in the
window of a serve cell: nobody ran (the GIL was held, or the process was
not scheduled); the ``stall`` spans."""
from ._timeline import window_span_ms


def read(run):
    return window_span_ms(run, "serve", "stall")
