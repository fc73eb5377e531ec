"""Device time a served batch spends in the MatchingNets (scope
``matching/mnet``): as ``mnet_ms`` of the train cells, per executed batch of
the eval program; in the ladder the net runs in its stacked form, one call a
level over all (2r+1)^2 hypotheses. Nothing where the program states no such
scope: see ``_ladder``."""
from . import _ladder


def read(run):
    return _ladder.scope_ms(run, "mnet")
