"""Real rows over batch rows of the RAFT model's batches dispatched in the
window (``serve``/``batch`` events by model): ``batch_fill_pct`` of one
model of a server of several. Nothing where no event names one."""
from . import _models


def read(run):
    return _models.fill_pct(run, "raft")
