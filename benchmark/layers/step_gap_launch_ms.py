"""The rest of each device gap before a train step's execution: from the
entry of the step's ``step_fn`` call (its ``put`` mark) to the first device
operation (arguments, launch). Median over the traced tail."""
from . import _timeline
from ._common import median_ms


def read(run):
    j = _timeline.of(run) if run["kind"] == "train" else None
    return None if j is None else median_ms(j["launch_s"])
