"""95th percentile of the latency, due time to result, of the counted
requests that named the RAFT model (``raft/...``) in a server of several
models: the harness's records by model. Beside ``serve_dicl_p95_ms`` it
says whose tail ``serve_p95_ms`` is. Nothing where no record names one."""
from . import _models


def read(run):
    return _models.p95_ms(run, "raft")
