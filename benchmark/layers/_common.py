"""Helpers the per-layer readers share. A reader is ``read(run)``: it takes
the run (the program's events, the harness's readings and records, the
reduced trace or None) and returns a number, or None when it finds nothing
to read, in which case the harness leaves the metric out of the line."""

import statistics


def window_events(run, kind, **match):
    """The program's events of ``kind`` stamped inside the untraced window."""
    t0, t1 = run["readings"]["window_wall"]
    return [e for e in run["events"]
            if e["kind"] == kind and t0 <= e["t"] <= t1
            and all(e.get(k) == v for k, v in match.items())]


def median_ms(seconds):
    return 1e3 * statistics.median(seconds) if seconds else None


def mean_ms(seconds):
    return 1e3 * statistics.fmean(seconds) if seconds else None


def trace_of(run, kind):
    """The reduced trace, if this is a traced run of ``kind`` traffic."""
    t = run.get("trace")
    return t if t and run["kind"] == kind and t["executions"] else None


def class_ms(run, kind, cls):
    t = trace_of(run, kind)
    return None if t is None else 1e3 * t["class_s_per_exec"].get(cls, 0.0)


def request_phase_ms(run, phase):
    """Mean of one phase of the program's request traces in the window."""
    values = [e["phases"][phase] for e in window_events(run, "trace",
                                                        event="request")
              if phase in e.get("phases", {})]
    return mean_ms(values)
