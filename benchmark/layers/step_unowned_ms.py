"""Device time a train step spends in operations no phase owns: the
instructions the program's ``owners`` record could give to nobody (no
``op_name`` of their own, in their fused computation or on an agreeing
neighbour), those in no record, and those two records own differently.
``input`` and scopes outside the table are printed by ``[owners]`` and are
in no metric. Nothing where there is no record: see ``_owners.table``."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "train", _owners.UNOWNED)
