"""Host-to-device bytes per step, from the loop's ``wire_bytes`` counter."""
import statistics

from ._common import window_events


def read(run):
    sizes = [e["counters"]["wire_bytes"] for e in window_events(run, "step")
             if "wire_bytes" in e.get("counters", {})]
    return statistics.median(sizes) / 1e6 if sizes else None
