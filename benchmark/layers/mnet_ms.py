"""Device time a train step spends in the MatchingNet (scope
``matching/mnet``; ``mnet`` inside ``matching`` in the multi-level model),
forward, rematerialised and backward: the largest owner of the learned-cost
cells; 0.0 in a model that has none. Nothing where there is no record: see
``_owners.table``."""
from . import _owners


def read(run):
    return _owners.scope_ms(run, "train", "mnet")
