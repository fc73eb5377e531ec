"""Of each device gap between two served batches, the idle time before
the next batch's ``called`` mark (the program call returned): fetch,
replies, events, take, assemble and the call itself. Median over the
traced tail; ``_timeline`` makes the join."""
from . import _timeline
from ._common import median_ms


def read(run):
    j = _timeline.of(run) if run["kind"] == "serve" else None
    return None if j is None else median_ms(j["host_s"])
