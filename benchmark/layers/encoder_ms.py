"""Device time a train step spends in the feature and context encoders with
their norms (scope ``encoders``), forward and backward: the traced
operations whose instruction the program's ``owners`` record gives to the
phase ``encoders``. Nothing where the run holds no such record or the
records cover under 90% of the traced time: see ``_owners.table``."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "train", "encoders")
