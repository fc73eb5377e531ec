"""Sum over the window's steps of the time beyond 1.5x the median step."""


def read(run):
    return run["readings"].get("train_stall_ms")
