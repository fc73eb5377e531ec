"""Pairs per block over the median block between the loop's sync points:
the rate of a window with no stall in it."""


def read(run):
    return run["readings"].get("train_block_pairs_per_s")
