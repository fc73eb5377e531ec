"""Wire encode + ``device_put`` of one batch on the prefetch worker: the
``put`` interval the step's event carries for the batch it consumed."""
from ._common import median_ms, window_events


def read(run):
    return median_ms([e["put"][1] - e["put"][0]
                      for e in window_events(run, "step") if "put" in e])
