"""Real rows over batch rows of the DICL model's batches dispatched in the
window (``serve``/``batch`` events by model). A batch costs the same
whatever its fill, so a quarter-full batch of the less popular model costs
the popular one a whole turn. Nothing where no event names one."""
from . import _models


def read(run):
    return _models.fill_pct(run, "dicl")
