"""Seconds the set-up spent loading or compiling programs: the ``aot`` hit
events (deserialising an executable) and the backend ``compile`` events."""


def read(run):
    loads = [e.get("seconds", 0.0) for e in run["events"]
             if e["kind"] == "aot" and e.get("event") == "hit"]
    compiles = [e.get("seconds", 0.0) for e in run["events"]
                if e["kind"] == "compile"]
    return float(sum(loads) + sum(compiles))
