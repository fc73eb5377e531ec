"""Device time a train step spends in what each iteration does to get its
costs: einsum look-ups, the window sampler with MatchingNet and DAP, the
windowed correlation kernels, the flow regression on costs (scopes
``lookup``, ``matching``, ``sampler``, ``mnet``, ``dap``, ``wcp``), forward
and backward: the traced operations whose instruction the program's
``owners`` record gives to the phase ``lookup``. Nothing where the run holds
no such record or the records cover under 90% of the traced time: see
``_owners.table``."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "train", "lookup")
