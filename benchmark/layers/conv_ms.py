"""Device time of the ``conv`` operation class per train step."""
from ._common import class_ms


def read(run):
    return class_ms(run, "train", "conv")
