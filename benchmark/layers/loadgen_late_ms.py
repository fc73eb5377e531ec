"""95th percentile of submit time minus due time of the counted requests."""


def read(run):
    return run["readings"].get("loadgen_late_ms")
