"""Device time a train step spends in upsampling: mask head, convex combine,
pixel shuffle, the bilinear 2x between levels (scope ``up8``), forward and
backward: the traced operations whose instruction the program's ``owners``
record gives to the phase ``up8``. Nothing where the run holds no such
record or the records cover under 90% of the traced time: see
``_owners.table``."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "train", "up8")
