"""Share of the host's CPUs the process keeps busy: ``process_time()``
(every thread's) between the ``start`` marks of consecutive steps over the
wall time between them, over the CPUs the process may run on (``cpus`` of
the ``boot`` span), in percent; median over the window's steps."""
import statistics

from ._common import window_events


def read(run):
    cpus = next((e["cpus"] for e in run["events"]
                 if e["kind"] == "span" and e.get("name") == "boot"
                 and e.get("cpus")), None)
    steps = [e for e in window_events(run, "step")
             if "cpu" in e and "start" in e.get("marks", {})]
    busy = [(b["cpu"] - a["cpu"]) / (b["marks"]["start"] - a["marks"]["start"])
            for a, b in zip(steps, steps[1:])
            if b["step"] == a["step"] + 1
            and b["marks"]["start"] > a["marks"]["start"]]
    if cpus is None or not busy:
        return None
    return 100.0 * statistics.median(busy) / cpus
