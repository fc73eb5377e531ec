"""Device time of the ``mosaic`` operation class per train step."""
from ._common import class_ms


def read(run):
    return class_ms(run, "train", "mosaic")
