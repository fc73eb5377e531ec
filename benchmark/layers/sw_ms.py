"""Device time a train step spends in the window sampler's Mosaic calls
(``ops/pallas._sw``, forward and backward), told from the other custom
calls by name and result shape. Nothing when a sampler call took the XLA
path: see ``_sw.calls``."""
from . import _sw


def read(run):
    found = _sw.calls(run)
    if found is None:
        return None
    n = run["trace"]["executions"]
    print(f"[sw] ms a step by level: {_sw.by_level(found, n)}", flush=True)
    return 1e3 * sum(seconds for *_, seconds, _ in found) / n
