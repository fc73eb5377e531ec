"""Device time a served batch of the RAFT model (``raft/...``) spends in the
update block (motion encoder, GRU, flow head), in a server of several models:
``serve_update_ms`` on that model's traced executions joined with that model's
own ``owners`` records (``_models.alone``), where the one-model reader would
average over both models' batches. Nothing where no record or traced batch
names that model."""
from . import _models, serve_update_ms


def read(run):
    return _models.of_model(run, "raft", serve_update_ms.read)
