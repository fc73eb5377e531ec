"""Harness clock from the first step (or the warm pool) to window open."""


def read(run):
    return run["readings"].get("warmup_s")
