"""Device time a served batch of the RAFT model (``raft/...``) spends in the
iterations' correlation look-ups, in a server of several models:
``serve_lookup_ms`` on that model's traced executions joined with that model's
own ``owners`` records (``_models.alone``), where the one-model reader would
average over both models' batches. Nothing where no record or traced batch
names that model."""
from . import _models, serve_lookup_ms


def read(run):
    return _models.of_model(run, "raft", serve_lookup_ms.read)
