"""Device time a served batch of the DICL model (``dicl/...``) spends warping
before each level but the coarsest, in a server of several models:
``serve_warp_ms`` on that model's traced executions joined with that model's
own ``owners`` records (``_models.alone``), where the one-model reader would
average over both models' batches. Nothing where no record or traced batch
names that model."""
from . import _models, serve_warp_ms


def read(run):
    return _models.of_model(run, "dicl", serve_warp_ms.read)
