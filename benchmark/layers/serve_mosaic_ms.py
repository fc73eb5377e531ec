"""Device time of the ``mosaic`` operation class per served batch."""
from ._common import class_ms


def read(run):
    return class_ms(run, "serve", "mosaic")
