"""Bytes of stacked feature pairs one served batch feeds the MatchingNets (the
five levels' ``(B, du, dv, H, W, 2C)`` volumes, float32), from the eval
program's ``matching_volume_bytes`` note: counted from shapes while the
program traces, kept with the stored executable. One executable a bucket;
the mean over them (the mix sends both alike). Nothing where the program
notes nothing."""
from . import _ladder


def read(run):
    sizes = _ladder.notes(run, "matching_volume_bytes")
    return sum(sizes) / len(sizes) / 1e6 if sizes else None
