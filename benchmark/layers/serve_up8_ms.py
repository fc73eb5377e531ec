"""Device time a served batch spends in upsampling: mask head, convex combine,
pixel shuffle, the bilinear 2x between levels (scope ``up8``): as
``up8_ms``, per executed batch of the eval program, both buckets' records
joined (an instruction name that two of them own differently counts as
unowned)."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", "up8")
