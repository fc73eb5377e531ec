"""Mean time from a batch's dispatch to its result being ready on the device:
the request trace's ``batch_form`` phase (marks ``dispatch`` to ``launched``;
the session blocks on the result before ``launched`` is stamped)."""
from ._common import request_phase_ms


def read(run):
    return request_phase_ms(run, "batch_form")
