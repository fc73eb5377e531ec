"""Device time a served batch spends warping (phase ``warp``: the 2x resize of
the coarser level's flow and ``warp_backwards``, the four gathered taps, the
mask and its product, before each of the ladder's levels but the coarsest),
per executed batch of the eval program, both buckets' records joined. Nothing
where the program states no such scope: see ``_ladder``."""
from . import _ladder


def read(run):
    return _ladder.phase_ms(run, "warp")
