"""The part of ``collective_ms`` during which no other operation runs on
the first chip: what a step really waits for its collectives. Where the
device runs one operation at a time the two are the same number, and the
time hidden behind compute is then in neither (an asynchronous collective's
transfer lies between its ``-start`` and its ``-done``, and only those two
are operations)."""
from . import _chips


def read(run):
    found = _chips.first_plane_ops(run)
    if found is None:
        return None
    seconds = _chips.exposed_s(*found)
    return None if seconds is None else 1e3 * seconds
