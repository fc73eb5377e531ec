"""Device time a train step spends in the windowed correlation's Mosaic
kernels (``ops/pallas.windowed_corr_pyramid``: forward, backward to frame
one's features, backward to each pooled map), told from every other
operation by the name their scope gives them. Nothing when the program
says nothing of its path or a call took the XLA composition: see
``_wcp.calls``."""
from . import _wcp


def read(run):
    found = _wcp.calls(run)
    if found is None:
        return None
    n = run["trace"]["executions"]
    print(f"[wcp] ms a step by kernel: {_wcp.by_kernel(found, n)}",
          flush=True)
    return 1e3 * _wcp.seconds_a_step(run, found)
