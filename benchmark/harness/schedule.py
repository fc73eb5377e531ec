"""The serve cells' arrival schedule: fixed in time, seeded in order.

Arrivals are evenly spaced at the cell's rate. The shape of each request is drawn by a seeded
permutation inside every group of ``group`` requests, laid out from the
mix's weights, so the mix is exact for every seed and only the order
changes. A seed changes nothing else: the same due times, the same number
of requests of each shape.
"""

import random


def shape_pattern(shapes, group):
    """``group`` shape indices in the proportion of the shapes' weights."""
    total = sum(s.get("weight", 1) for s in shapes)
    pattern = []
    for i, s in enumerate(shapes):
        count = group * s.get("weight", 1) / total
        if count != int(count):
            raise ValueError(f"group of {group} does not hold the mix exactly")
        pattern += [i] * int(count)
    return pattern


def build(traffic, seed, seconds):
    """``[(due_s, shape_index)]`` for every request due in
    [0, discard_s + seconds)."""
    rate = float(traffic["rate_per_s"])
    group = int(traffic.get("group", 8))
    horizon = float(traffic.get("discard_s", 2.0)) + float(seconds)
    pattern = shape_pattern(traffic["shapes"], group)
    rng = random.Random(int(seed))

    n = int(horizon * rate)
    n -= n % group                      # whole groups: the mix stays exact
    out = []
    order = []
    for i in range(n):
        if i % group == 0:
            order = pattern[:]
            rng.shuffle(order)
        out.append((i / rate, order[i % group]))
    return out
