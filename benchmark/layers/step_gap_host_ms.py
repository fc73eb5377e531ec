"""Of each device gap before a train step's execution, the idle time
before the loop entered that step's ``step_fn`` call (its ``put`` mark):
the device waited for the host. Median over the traced tail; ``_timeline``
makes the join and says why the call's entry and not its return."""
from . import _timeline
from ._common import median_ms


def read(run):
    j = _timeline.of(run) if run["kind"] == "train" else None
    return None if j is None else median_ms(j["host_s"])
