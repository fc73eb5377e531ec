"""Device time a served batch spends in the context networks (phase
``context``: the resize of frame one to each level, the stacking of flow,
entropy, features and image, and the dilated convolutions that refine the
level's flow), per executed batch of the eval program. Nothing where the
program states no such scope: see ``_ladder``."""
from . import _ladder


def read(run):
    return _ladder.phase_ms(run, "context")
