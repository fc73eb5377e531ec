"""Bytes one chip's collectives produce a step (the result buffers of every
collective of the compiled train step, one chip's), from the program's
``collectives`` record: read off the compiled text by
``analysis/collectives.parse_schedule`` when the step is compiled, kept with
the stored executable, and carried by the ``aot`` event that holds it.
Nothing where the program says nothing (a program from before the record, a
one-chip step)."""


def read(run):
    if run["kind"] != "train":
        return None
    said = [e["collectives"] for e in run["events"]
            if e["kind"] == "aot" and e.get("program") == "train_step"
            and isinstance(e.get("collectives"), dict)]
    if not said or "total_bytes" not in said[-1]:
        return None
    return said[-1]["total_bytes"] / 1e6
