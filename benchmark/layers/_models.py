"""What the readers of a server of several models share (``serve_raft_p95_ms``,
``serve_dicl_p95_ms``, ``serve_raft_fill_pct``, ``serve_dicl_fill_pct``,
``serve_model_switch_pct``, ``serve_switch_gap_ms`` and a model's own phases,
``serve_raft_update_ms`` ... ``serve_dicl_mnet_ms``): the harness's records,
the ``serve``/``batch`` events, the ``trace``/``batch`` records and the
``owners`` records taken by the model they name. A model is picked by its
family, the part of its id before the slash (``raft/baseline`` -> ``raft``).

A program from before the field (a parent commit's) names no model on any
record, and a one-model run of the harness none on its requests: every
reader then returns nothing, never 0.0, which would say "no request of that
model" or "no switch" where nothing is known.
"""

import bisect
import statistics

from ..harness import stats, xtrace
from . import _timeline
from ._common import window_events


def family(model_id):
    return model_id.split("/")[0] if model_id else None


def latencies_ms(run, fam):
    """Due time to result of the counted, completed requests of one
    family's model, or None where the records name no model."""
    if run["kind"] != "serve":
        return None
    good = [r for r in run["records"] if r["counted"] and r.get("ok")
            and family(r.get("model")) == fam]
    return [1e3 * (r["done"] - r["due"]) for r in good] or None


def p95_ms(run, fam):
    lat = latencies_ms(run, fam)
    return None if lat is None else stats.percentile(lat, 95)


def fill_pct(run, fam):
    """Real rows over batch rows of one family's batches in the window."""
    if run["kind"] != "serve":
        return None
    batches = [e for e in window_events(run, "serve", event="batch")
               if family(e.get("model")) == fam]
    rows = sum(e["size"] + e["fill"] for e in batches)
    return 100.0 * sum(e["size"] for e in batches) / rows if rows else None


def window_batches(run):
    """The window's ``trace``/``batch`` records that name a model, in
    dispatch order."""
    if run["kind"] != "serve":
        return []
    recs = [e for e in window_events(run, "trace", event="batch")
            if e.get("model") and "dispatch" in e.get("marks", {})]
    return sorted(recs, key=lambda e: e["marks"]["dispatch"])


def switch_pct(run):
    """Of the window's consecutive batches, the share whose model differs
    from the one before."""
    recs = window_batches(run)
    if len(recs) < 2 or len({e["model"] for e in recs}) < 2:
        return None
    pairs = list(zip(recs, recs[1:]))
    return 100.0 * sum(a["model"] != b["model"] for a, b in pairs) / len(pairs)


def joined(run):
    """The traced tail's executions of the cell's module, each with the
    ``trace``/``batch`` record that launched it: the join of ``_timeline``
    (the same capture, clock and matching) with the model of each record
    kept beside its marks. ``{"pairs": [((start, end), item)], "execs",
    "merged", "ops"}`` in the capture's ns, or None where the join gives
    nothing or no record names a model."""
    if "_model_join" not in run:
        run["_model_join"] = _joined(run)
    return run["_model_join"]


def _joined(run):
    if run["kind"] != "serve" or _timeline.of(run) is None:
        return None
    off = _timeline.offset_ns(run)
    path = xtrace.find_xplane(run["trace_dir"])
    start = _timeline.profile_start_ns(path)
    module = run["cell"].traffic.get("trace_module", "jit_step")
    capture = xtrace.load(path)
    execs, merged = _timeline.device_intervals(capture, module)
    items = []
    for e in _timeline.events(run, "trace", event="batch"):
        if e.get("model") and "called" in e.get("marks", {}):
            it = _timeline._on_axis(e["marks"], off, start)
            it["model"] = e["model"]
            items.append(it)
    if not items:
        return None
    return {"pairs": _timeline.match_contained(execs, items, "called",
                                               "ready"),
            "execs": execs, "merged": merged, "ops": _device_ops(capture)}


def _device_ops(capture):
    """``[(text, start, duration)]`` of the first chip's operations: the
    same choice of events as ``xtrace.reduce``."""
    for plane in capture["planes"]:
        line = xtrace._line(plane, "XLA Ops") \
            if xtrace._DEVICE.match(plane["name"]) else None
        ops = []
        for n, s, d, _ in (line["events"] if line else ()):
            name, opcode, _ = xtrace.parse_op(n)
            if d > 0 and opcode not in xtrace._CONTAINERS \
                    and not name.startswith(xtrace._CONTAINERS):
                ops.append((n, s, d))
        if ops:
            return ops
    return []


def gaps(run):
    """The traced tail's device gaps between two consecutive executions,
    as ``[(idle seconds, model before, model after)]``, or None."""
    if "_model_gaps" not in run:
        run["_model_gaps"] = _gaps(run)
    return run["_model_gaps"]


def _gaps(run):
    j = joined(run)
    if j is None:
        return None
    pairs = j["pairs"]
    index = {ex: k for k, ex in enumerate(j["execs"])}
    out = []
    for (ex0, it0), (ex1, it1) in zip(pairs, pairs[1:]):
        if index[ex1] == index[ex0] + 1:
            out.append((_timeline._idle(j["merged"], ex0[1], ex1[0]) / 1e9,
                        it0["model"], it1["model"]))
    switch = [g for g, a, b in out if a != b]
    same = [g for g, a, b in out if a == b]
    med = lambda xs: round(1e3 * statistics.median(xs), 4) if xs else None  # noqa: E731
    print(f"[switch] gaps={len(out)} switch_n={len(switch)} "
          f"switch_gap_ms={med(switch)} same_n={len(same)} "
          f"same_gap_ms={med(same)}", flush=True)
    return out


def switch_gap_ms(run):
    """Median device gap before a batch whose model differs from the one
    before."""
    out = gaps(run)
    if not out:
        return None
    switch = [g for g, a, b in out if a != b]
    return 1e3 * statistics.median(switch) if switch else None


def alone(run, fam):
    """The traced tail as one family's model alone, in the shape the
    one-model readers take (``_owners``, ``_ladder``): ``trace`` reduced to
    the executions that ran that model's batches, ``events`` that model's
    ``owners`` records (the program names the model on each). None where
    the run has no such record, no join, or no traced batch of that
    model."""
    views = run.setdefault("_model_alone", {})
    if fam not in views:
        views[fam] = _alone(run, fam)
    return views[fam]


def _alone(run, fam):
    recs = [ev for ev in run["events"]
            if ev["kind"] == "aot" and ev.get("event") == "owners"
            and family(ev.get("model")) == fam]
    j = joined(run) if recs else None
    mine = [ex for ex, it in j["pairs"] if family(it["model"]) == fam] \
        if j else []
    if not mine:
        return None
    starts = [s for s, _ in mine]
    op_s = {}
    for text, s, d in j["ops"]:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < mine[k][1]:
            op_s[text] = op_s.get(text, 0.0) + d / 1e9
    trace = {"executions": len(mine), "op_s": op_s,
             "module": run["trace"]["module"],
             "exec_busy_s": [xtrace._overlap(j["merged"], s, e) / 1e9
                             for s, e in mine]}
    print(f"[owners] model={recs[0]['model']}: {len(mine)} of "
          f"{len(j['pairs'])} traced batches, {len(recs)} record(s)",
          flush=True)
    view = {k: v for k, v in run.items() if k != "owners_table"}
    view.update(trace=trace, events=recs)
    return view


def of_model(run, fam, read):
    """What a one-model reader reads of that model's batches alone:
    ``read`` on the model's view of the run, or nothing without one."""
    view = alone(run, fam)
    return None if view is None else read(view)
