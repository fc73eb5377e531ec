"""How many pyramid levels one MatchingNet evaluation of the train step
covers, from the program's ``matching_levels_batched`` note: taken while
the step traces (the number of levels on the level-batched path, 1 where
the levels run one after the other), kept with the stored executable, and
carried by the step's ``compile`` event when this run traced it and by the
``aot`` event that holds its executable when it came from the store.
Nothing where the program says nothing (a program from before the note)."""

NOTE = "matching_levels_batched"


def read(run):
    if run["kind"] != "train":
        return None
    for ev in run["events"]:
        carrier = (ev["kind"] == "aot" and ev.get("program") == "train_step"
                   or ev["kind"] == "compile"
                   and ev.get("label") == "train_step")
        if carrier and NOTE in ev:
            return float(ev[NOTE])
    return None
