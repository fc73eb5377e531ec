"""Seconds from process start to the first thing the program marks (its
first span or ``activate()``): interpreter start-up and imports; the
program's ``boot`` span."""
from ._timeline import span_seconds


def read(run):
    return span_seconds(run, "boot")
