"""Mean time a request waited between admission and dispatch."""
from ._common import request_phase_ms


def read(run):
    return request_phase_ms(run, "queue")
