"""Seconds of set-up the program spent building what it runs, outside
loading or compiling programs: the spans ``model_load`` + ``strategy_load``
+ ``prepare`` (train: ``run_stage`` entry to ``stage_start``; serve: the
session's construction), less the ``aot`` hit and ``compile`` seconds that
fall inside them, which ``program_load_s`` already counts."""
from ._timeline import span_seconds, spans, wall_of

NAMES = ("model_load", "strategy_load", "prepare")


def read(run):
    total = span_seconds(run, *NAMES)
    if total is None:
        return None
    inside = [(wall_of(run, e["t0"]), wall_of(run, e["t1"]))
              for n in NAMES for e in spans(run, n)]
    counted = sum(
        e.get("seconds", 0.0) for e in run["events"]
        if (e["kind"] == "compile"
            or (e["kind"] == "aot" and e.get("event") == "hit"))
        and any(w0 <= e["t"] <= w1 + 1e-3 for w0, w1 in inside))
    return max(0.0, total - counted)
