"""95th percentile of the latency, due time to result, of the counted
requests that named the DICL model (``dicl/...``) in a server of several
models: the harness's records by model. The less popular model's lanes
fill slowest, so its requests wait longest for a batch. Nothing where no
record names one."""
from . import _models


def read(run):
    return _models.p95_ms(run, "dicl")
