"""Device time a served batch spends in what each iteration does to get its
costs: einsum look-ups, the window sampler with MatchingNet and DAP, the
windowed correlation kernels, the flow regression on costs (scopes
``lookup``, ``matching``, ``sampler``, ``mnet``, ``dap``, ``wcp``): as
``lookup_ms``, per executed batch of the eval program, both buckets' records
joined (an instruction name that two of them own differently counts as
unowned)."""
from . import _owners


def read(run):
    return _owners.phase_ms(run, "serve", "lookup")
