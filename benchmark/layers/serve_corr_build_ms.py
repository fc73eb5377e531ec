"""Device time a served batch spends in what is built once a step for the look-
ups: the all-pairs pyramid, the pooled or strided feature pyramids (scopes
``corr``, ``pyramid``): as ``corr_build_ms``, per executed batch of the eval
program, both buckets' records joined (an instruction name that two of them
own differently counts as unowned)."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", "corr")
