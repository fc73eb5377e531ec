"""Device time a train step spends in what is built once a step for the look-
ups: the all-pairs pyramid, the pooled or strided feature pyramids (scopes
``corr``, ``pyramid``), forward and backward: the traced operations whose
instruction the program's ``owners`` record gives to the phase ``corr``.
Nothing where the run holds no such record or the records cover under 90% of
the traced time: see ``_owners.table``."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "train", "corr")
