"""Arithmetic of the readings: quantiles, window and block rates, stall time.

Kept apart from the drivers so that ``tests/test_stats.py`` can hold it
against synthetic step records."""

import math
import statistics


def percentile(values, q):
    """Linear-interpolated percentile of ``values`` (q in [0, 100])."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def blocks_from_steps(step_ends, sync_every, open_index):
    """Durations of the equal blocks of a window.

    ``step_ends`` are host-clock stamps taken when each step's callbacks
    ended, ``open_index`` the index of the sync step at which the window
    opened. A block runs from one program sync point to the next
    (``sync_every`` steps). The stamps past ``open_index`` must end on a
    sync step.
    """
    inside = step_ends[open_index:]
    if (len(inside) - 1) % sync_every:
        raise ValueError("window does not end on a sync point")
    edges = inside[::sync_every]
    return [b - a for a, b in zip(edges, edges[1:])]


def train_readings(step_ends, sync_every, open_index, pairs_per_step):
    """The train cell's rates from its step stamps.

    ``train_pairs_per_s`` is every pair of the window over all its time,
    from the sync point that opened it to the one that closed it: a stall
    inside the window lowers it. ``train_block_pairs_per_s``, pairs per
    block over the *median* block duration, stands beside it as the rate of
    a window with no stall in it, and the stall time says how much time the
    stalls took: the sum over steps of the time beyond 1.5 times the median
    step.
    """
    blocks = blocks_from_steps(step_ends, sync_every, open_index)
    inside = step_ends[open_index:]
    steps = [b - a for a, b in zip(inside, inside[1:])]
    per_block = pairs_per_step * sync_every
    med_block = statistics.median(blocks)
    med_step = statistics.median(steps)
    window = inside[-1] - inside[0]
    return {
        "blocks": len(blocks),
        "steps": len(steps),
        "window_s": window,
        "block_rates": [per_block / b for b in blocks],
        "train_pairs_per_s": pairs_per_step * len(steps) / window,
        "train_block_pairs_per_s": per_block / med_block,
        "train_stall_ms": 1e3 * sum(max(0.0, s - 1.5 * med_step)
                                    for s in steps),
        "median_step_ms": 1e3 * med_step,
    }
