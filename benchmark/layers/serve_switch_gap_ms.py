"""Device gap before a batch whose model differs from the one before, median
over the traced tail: the trace joined with the dispatch thread's marks as
``serve_gap_host_ms`` is, each batch's model beside its marks. Beside
``serve_gap_host_ms`` + ``serve_gap_launch_ms`` (all gaps) it says whether
two models living together cost anything on the device: another
executable's first operation, other weights. Nothing without the join or
where no batch names a model."""
from . import _models


def read(run):
    return _models.switch_gap_ms(run)
