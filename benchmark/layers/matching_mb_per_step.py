"""Bytes the step's forward pass feeds the matching nets (frame one's
features and the sampled windows, in the matching's dtype), from the
program's ``matching_volume_bytes`` counter: counted from shapes while the
step traces, kept with the stored executable, and handed to the first
``step`` event of every run, traced or loaded."""


def read(run):
    sizes = [e["counters"]["matching_volume_bytes"] for e in run["events"]
             if e["kind"] == "step"
             and "matching_volume_bytes" in e.get("counters", {})]
    return max(sizes) / 1e6 if sizes and run["kind"] == "train" else None
