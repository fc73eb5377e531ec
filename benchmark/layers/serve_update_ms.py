"""Device time a served batch spends in the update block: motion encoder, GRU,
flow head (scope ``update``): as ``update_ms``, per executed batch of the
eval program, both buckets' records joined (an instruction name that two of
them own differently counts as unowned)."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", "update")
