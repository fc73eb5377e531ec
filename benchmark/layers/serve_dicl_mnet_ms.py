"""Device time a served batch of the DICL model (``dicl/...``) spends in the
MatchingNets (inside ``serve_dicl_lookup_ms``), in a server of several models:
``serve_mnet_ms`` on that model's traced executions joined with that model's
own ``owners`` records (``_models.alone``), where the one-model reader would
average over both models' batches. Nothing where no record or traced batch
names that model."""
from . import _models, serve_mnet_ms


def read(run):
    return _models.of_model(run, "dicl", serve_mnet_ms.read)
