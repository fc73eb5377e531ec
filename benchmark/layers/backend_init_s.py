"""Seconds the first ``jax.devices()`` took to bring the backend up: the
``backend_init`` span of ``cmd/train.select_devices``."""
from ._timeline import span_seconds


def read(run):
    return span_seconds(run, "backend_init")
