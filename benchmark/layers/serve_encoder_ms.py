"""Device time a served batch spends in the feature and context encoders with
their norms (scope ``encoders``): as ``encoder_ms``, per executed batch of
the eval program, both buckets' records joined (an instruction name that two
of them own differently counts as unowned)."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", "encoders")
