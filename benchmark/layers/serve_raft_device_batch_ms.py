"""Device busy time inside one served batch of the RAFT model (``raft/...``)
in a server of several models, median over the traced tail:
``serve_device_batch_ms`` of that model's executions alone
(``_models.alone``). Nothing where no traced batch names that model."""
from . import _models, serve_device_batch_ms


def read(run):
    return _models.of_model(run, "raft", serve_device_batch_ms.read)
