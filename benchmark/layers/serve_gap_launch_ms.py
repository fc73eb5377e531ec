"""The rest of each device gap between two served batches: from the
``called`` mark to the batch's first device operation (the inputs'
host-to-device transfer and the launch). Median over the traced tail."""
from . import _timeline
from ._common import median_ms


def read(run):
    j = _timeline.of(run) if run["kind"] == "serve" else None
    return None if j is None else median_ms(j["launch_s"])
