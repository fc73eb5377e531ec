"""Summed length of the garbage collections over 1 ms that fell into the
window of a serve cell: the witness's ``gc`` spans."""
from ._timeline import window_span_ms


def read(run):
    return window_span_ms(run, "serve", "gc")
