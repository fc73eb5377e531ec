"""Of the window's consecutive batches, the share whose model differs from
the one before (``trace``/``batch`` records in dispatch order; the program
counts the same as ``rmd_serve_model_switches_total``). Nothing where the
records name no model or only one."""
from . import _models


def read(run):
    return _models.switch_pct(run)
