"""Whose device time is it: the traced operations of a run joined with what
the program says of its compiled instructions. Shared by the phase metrics
(``encoder_ms`` ... ``step_unowned_ms``, ``mnet_ms`` and the ``serve_*`` ones).

For every executable it loads or compiles the program emits one ``aot``
event with ``event="owners"`` (``compile/owners.py``): every instruction
that runs as a device operation, keyed ``name:dtype[dims]``, grouped by
phase of the model (``input``, ``encoders``, ``corr``, ``lookup``,
``update``, ``up8``, ``loss``, ``optimizer``), by the innermost scope that
named it and by direction, with the keys whose owner was inferred from a
neighbour (``inferred_keys``). The reduced trace holds the device seconds
of each operation of the cell's module by its event text, from which the
same key is cut. The join gives milliseconds an execution by phase,
direction and operation class (``xtrace.op_class``).

A program that emits no such record (one from before the record), or
records that cover under 90% of the traced time (another tree's program:
its instruction names differ), give nothing: every reader returns None and
``[owners]`` says why. A key to which two records of the run give
different owners (the serve cell's two buckets) counts as unowned.
"""

import re

from ..harness import xtrace
from ._common import trace_of

PHASES = ("input", "encoders", "corr", "lookup", "update", "up8", "loss",
          "optimizer")
OTHER, UNOWNED = "other", "unowned"
NOBODY = (UNOWNED, "", "fwd")
MIN_COVERED = 0.90

_RESULT = re.compile(r"\(*([a-z0-9]+\[[0-9,]*\])")


def key_of(text):
    """``name:dtype[dims]`` of a device event: its instruction's name and
    the first array of its result type, as the program keys its record."""
    head, sep, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    m = _RESULT.match(rest) if sep else None
    return f"{name}:{m.group(1) if m else ''}"


def records(run, modules):
    """The run's ``owners`` records of the traced modules."""
    return [ev for ev in run["events"]
            if ev["kind"] == "aot" and ev.get("event") == "owners"
            and ev.get("module") in modules]


def _flat(recs):
    """``{key: (phase, scope, direction)}`` over the records, ``None``
    where two of them disagree; and the keys any of them inferred."""
    owner, inferred = {}, set()
    for rec in recs:
        inferred.update(rec.get("inferred_keys", ()))
        for phase, scopes in rec["owners"].items():
            for scope, directions in scopes.items():
                for direction, keys in directions.items():
                    for key in keys:
                        mine = (phase, scope, direction)
                        if owner.setdefault(key, mine) != mine:
                            owner[key] = None
    return owner, inferred


def table(run, kind):
    """The join, once a run: ``{"rows": {(phase, scope, direction): {class:
    ms}}, "unowned_ops": [(key, ms, why)], "covered", "inferred",
    "unowned", "total_ms"}`` with milliseconds an execution, or None."""
    if run["kind"] != kind:
        return None
    if "owners_table" not in run:
        run["owners_table"] = _table(run, kind)
        _print(run["owners_table"])
    return run["owners_table"]


def _table(run, kind):
    t = trace_of(run, kind)
    if t is None:
        return None
    modules = {m.split("(")[0] for m in t["module"]}
    recs = records(run, modules)
    if not recs:
        print(f"[owners] no owners record for {sorted(modules)}: the "
              f"program says nothing of its instructions", flush=True)
        return None
    owner, inferred = _flat(recs)
    n = t["executions"]
    rows, lost = {}, []
    total = uncovered = inferred_s = 0.0
    for text, seconds in t["op_s"].items():
        ms = 1e3 * seconds / n
        total += ms
        key = key_of(text)
        if key not in owner:
            mine, why = NOBODY, "in no record"
            uncovered += ms
        elif owner[key] is None:
            mine, why = NOBODY, "two owners"
        else:
            mine, why = owner[key], "no owner found"
            if key in inferred:
                inferred_s += ms
        if mine[0] == UNOWNED:
            lost.append((key, ms, why))
        by_class = rows.setdefault(mine, {})
        cls = xtrace.op_class(text)
        by_class[cls] = by_class.get(cls, 0.0) + ms
    covered = 1.0 - uncovered / total if total else 0.0
    if covered < MIN_COVERED:
        print(f"[owners] {len(recs)} record(s) cover {100 * covered:.1f}% "
              f"of the traced time (under {100 * MIN_COVERED:.0f}%): not "
              f"this program's records", flush=True)
        return None
    unowned = sum(ms for _, ms, _ in lost)
    return {"rows": rows, "records": len(recs), "executions": n,
            "modules": sorted(modules), "total_ms": total,
            "covered": covered, "inferred": inferred_s / total,
            "unowned": unowned / total,
            "unowned_ops": sorted(lost, key=lambda o: -o[1])}


def _classes(by_class):
    return " ".join(f"{c}={ms:.2f}" for c, ms in
                    sorted(by_class.items(), key=lambda kv: -kv[1]))


def _sum(rows, pick):
    """Classes summed over the rows ``pick`` takes, keyed by its value."""
    out = {}
    for owner, by_class in rows.items():
        group = pick(owner)
        if group is None:
            continue
        into = out.setdefault(group, {})
        for cls, ms in by_class.items():
            into[cls] = into.get(cls, 0.0) + ms
    return out


def _print(tab):
    if tab is None:
        return
    rows = tab["rows"]
    print(f"[owners] modules={tab['modules']} records={tab['records']} "
          f"executions={tab['executions']} ms an execution "
          f"{tab['total_ms']:.2f}: covered={100 * tab['covered']:.2f}% "
          f"inferred={100 * tab['inferred']:.2f}% "
          f"unowned={100 * tab['unowned']:.2f}%", flush=True)
    order = {p: i for i, p in enumerate(PHASES + (OTHER, UNOWNED))}
    by_phase = _sum(rows, lambda o: (o[0], o[2]))
    for (phase, direction), by_class in sorted(
            by_phase.items(), key=lambda kv: (order.get(kv[0][0], 99),
                                              kv[0][1] != "fwd")):
        print(f"[owners] {phase:9s} {direction} "
              f"{sum(by_class.values()):9.2f}  {_classes(by_class)}",
              flush=True)
    for phase, label in (("lookup", "lookup by scope"),
                         (OTHER, "other scopes")):
        split = _sum(rows, lambda o: (o[1] or "-", o[2])
                     if o[0] == phase else None)
        for (scope, direction), by_class in sorted(split.items()):
            print(f"[owners] {label}: {scope} {direction} "
                  f"{sum(by_class.values()):.2f}  {_classes(by_class)}",
                  flush=True)
    for key, ms, why in tab["unowned_ops"][:10]:
        print(f"[owners] unowned: {key} {ms:.3f} ms ({why})", flush=True)


def phase_ms(run, kind, phase):
    """Milliseconds an execution under one phase, both directions."""
    tab = table(run, kind)
    if tab is None:
        return None
    return sum(sum(by_class.values()) for owner, by_class in
               tab["rows"].items() if owner[0] == phase)


def scope_ms(run, kind, scope):
    """Milliseconds an execution under one scope of the table."""
    tab = table(run, kind)
    if tab is None:
        return None
    return sum(sum(by_class.values()) for owner, by_class in
               tab["rows"].items() if owner[1] == scope)
