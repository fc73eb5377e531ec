"""Device time a served batch spends in operations no phase owns: as
``step_unowned_ms``, per executed batch of the eval program; with two
buckets' programs in one run it holds the instruction names the two own
differently."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", _owners.UNOWNED)
