"""The prefetch worker's ``next()`` on the loader for one batch (the wait
for the loader's workers and whatever its pulling thread does to a batch
itself): the ``pull`` interval the step's event carries for the batch it
consumed, median over the window's steps. ``pull_ms`` + ``put_ms`` is the
period of the one thread that feeds the device."""
from ._common import median_ms, window_events


def read(run):
    return median_ms([e["pull"][1] - e["pull"][0]
                      for e in window_events(run, "step") if "pull" in e])
