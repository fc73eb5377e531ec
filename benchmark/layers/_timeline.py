"""One timeline: the program's marks laid on the device trace's clock.

The program stamps every mark with ``time.perf_counter()`` and says in its
``clock`` events which Unix time that is. The profiler's capture carries its
start in Unix ns (``profile_start_time`` of the ``Task Environment`` plane)
and every event's start as an offset from it. So a mark lies at
``mark * 1e9 + offset_ns - start_ns`` on the capture's axis, without the
profiler's host tracer (which slows what it records, PERF.md section 5).

``of(run)`` reads the capture under ``run["trace_dir"]`` once, joins the
traced tail's executions of the cell's module with the marks of the thread
that launched them (the training loop's ``step`` events, the dispatch
thread's ``trace``/``batch`` events), prints one ``[clock]`` and one
``[gaps]`` line, and caches the result on the run. Each device gap between
two executions is split at the launching mark: before it the device waited
for the host, after it for arguments and launch; the two parts sum to the
gap by construction. Where the run has no capture, no device plane, no
marks (a program older than the marks) or the clock check fails, the join
is None and the readers return None: no split is better than a wrong one.

The launching mark is where the host hands the work over. Serving: the
batch's ``called`` (the program call returned; the device starts 9-12 ms
later, after the inputs' transfer). Training: the step's ``put``, the last
mark before the ``step_fn`` call, not ``dispatched`` after it: on the chip
that call returns 60-66 ms *after* the device has started the step (PERF.md
section 5), so its return is no hand-over. ``[clock]`` prints both.
"""

import statistics
import time

from ..harness import xtrace
from ._common import window_events

# a mark may lie on the wrong side of its device interval by this much
# (the profiler converts the device's clock to the host's), and a serving
# batch's ``block_until_ready`` may return this long after the device
# finished it (median)
WRONG_SIDE_S = 0.2e-3
LATE_READY_S = 5e-3

# what the launching thread was doing, as (phase, from mark, to mark)
TRAIN_PHASES = (("data_wait", "start", "data"), ("host_prep", "data", "prep"),
                ("dispatch", "put", "dispatched"),
                ("device", "dispatched", "synced"),
                ("interleave", "synced", "done"))
SERVE_PHASES = (("take", "wait", "dispatch"),
                ("assemble", "dispatch", "assembled"),
                ("call", "assembled", "called"), ("execute", "called", "ready"),
                ("fetch", "ready", "fetched"),
                ("respond", "fetched", "completed"))


def events(run, kind, **match):
    return [e for e in run["events"] if e["kind"] == kind
            and all(e.get(k) == v for k, v in match.items())]


def offset_ns(run):
    """``unix_ns - perf_counter * 1e9`` from the program's newest ``clock``
    event, or None when the program emits none."""
    clocks = events(run, "clock")
    if not clocks:
        return None
    c = clocks[-1]
    return c["time_ns"] - c["perf_counter"] * 1e9


def wall_of(run, mark):
    """Unix seconds of a ``perf_counter`` mark, as the events' ``t``."""
    off = offset_ns(run)
    return None if off is None else mark + off / 1e9


def spans(run, name):
    return events(run, "span", name=name)


def span_seconds(run, *names):
    """Summed length of the run's spans of these names, or None when the
    program emits no spans at all."""
    if not events(run, "span"):
        return None
    return float(sum(e["t1"] - e["t0"] for n in names for e in spans(run, n)))


def window_span_ms(run, kind, name):
    """Summed length in ms of the spans ``name`` emitted inside the
    untraced window of a ``kind`` cell (0.0 when none was); None for
    another kind of cell or a program that emits no ``clock`` event."""
    if run["kind"] != kind or offset_ns(run) is None:
        return None
    return 1e3 * sum(e["t1"] - e["t0"]
                     for e in window_events(run, "span", name=name))


def batch_marks(run):
    """The dispatch thread's marks of the batches emitted inside the
    untraced window, in dispatch order; whole sets only."""
    marks = [e["marks"] for e in window_events(run, "trace", event="batch")
             if len(e.get("marks", ())) == 7]
    return sorted(marks, key=lambda m: m["dispatch"])


def profile_start_ns(path):
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            return int(stats["profile_start_time"])
    return None


def device_intervals(capture, module_filter):
    """``(executions, merged)`` of the first chip in the capture's ns:
    the ``[start, end]`` of each execution of the module and the union of
    the intervals in which an operation ran; None without a device plane.
    The same choice of events as ``xtrace.reduce``."""
    planes = [p for p in capture["planes"] if xtrace._DEVICE.match(p["name"])]
    for plane in planes:
        ops_line = xtrace._line(plane, "XLA Ops")
        mod_line = xtrace._line(plane, "XLA Modules")
        if ops_line is None or mod_line is None:
            continue
        ops = []
        for n, s, d, _ in ops_line["events"]:
            name, opcode, _ = xtrace.parse_op(n)
            if d > 0 and opcode not in xtrace._CONTAINERS \
                    and not name.startswith(xtrace._CONTAINERS):
                ops.append([s, s + d])
        execs = sorted((s, s + d) for n, s, d, _ in mod_line["events"]
                       if d > 0 and module_filter in n.split("(")[0])
        if ops and execs:
            return execs, xtrace._union(ops)
    return None


def _idle(merged, a, b):
    return max(0.0, (b - a) - xtrace._overlap(merged, a, b))


def match_in_order(execs, items, call):
    """Training: a loop that runs ahead launches a step while the one
    before it executes, so no interval of marks holds an execution. The
    capture starts at a sync point, so the k-th execution belongs to the
    k-th item launched inside the capture."""
    inside = [it for it in items if it[call] >= 0.0]
    return list(zip(execs, inside))


def match_contained(execs, items, call, done):
    """Serving: the dispatch thread blocks on each batch, so an execution
    belongs to the batch whose ``call``..``done`` interval holds its
    middle. An execution cut by the capture's start finds none."""
    out = []
    for s, e in execs:
        mid = 0.5 * (s + e)
        for it in items:
            if it[call] <= mid <= it[done]:
                out.append(((s, e), it))
                break
    return out


def join(execs, merged, pairs, call, done=None, phases=(), items=(),
         late_ns=None, returned=None):
    """The join of matched ``(execution, item)`` pairs; every time in the
    capture's ns, item marks already on that axis.

    ``call`` is the launching mark, ``done`` the mark at which the host saw
    the result (None in an item that did not wait for it), ``returned`` the
    mark after the launching call where that is another one. Returns
    ``lead`` (device start - call) and ``lag`` (done - device end) per
    pair, the clock verdict (a mark on the wrong side of its device
    interval; with ``late_ns``, a median ``lag`` over it), and per gap
    between two consecutive matched executions its idle time split into
    ``host`` (before the next ``call``) and ``launch`` (after it)."""
    lead = [s - it[call] for (s, _), it in pairs]
    lag = [it[done] - e for (_, e), it in pairs
           if done is not None and it.get(done) is not None]
    why = None
    if not pairs:
        why = "no execution matches a mark"
    elif min(lead) < -WRONG_SIDE_S * 1e9:
        why = f"device started {-min(lead) / 1e6:.3f} ms before '{call}'"
    elif lag and min(lag) < -WRONG_SIDE_S * 1e9:
        why = f"'{done}' lies {-min(lag) / 1e6:.3f} ms before the device's end"
    elif lag and late_ns is not None and statistics.median(lag) > late_ns:
        why = (f"median '{done}' - device end "
               f"{statistics.median(lag) / 1e6:.3f} ms")

    index = {ex: k for k, ex in enumerate(execs)}
    host, launch, parts = [], [], []
    for (ex0, _), (ex1, it) in zip(pairs, pairs[1:]):
        if index[ex1] != index[ex0] + 1:
            continue
        e0, s1 = ex0[1], ex1[0]
        cut = min(max(it[call], e0), s1)
        host.append(_idle(merged, e0, cut))
        launch.append(_idle(merged, cut, s1))
        parts.append((e0, cut))

    # what the launching thread did inside the host parts
    shares, total = {}, sum(b - a for a, b in parts)
    for it in items:
        for name, m0, m1 in phases:
            if it.get(m0) is None or it.get(m1) is None:
                continue
            cover = sum(max(0.0, min(b, it[m1]) - max(a, it[m0]))
                        for a, b in parts)
            if cover > 0:
                shares[name] = shares.get(name, 0.0) + cover
    shares = {k: v / total for k, v in shares.items()} if total > 0 else {}

    window = merged[-1][1] - merged[0][0]
    busy = xtrace._covered(merged)
    in_step = sum(_idle(merged, s, e) for s, e in execs)
    between = sum(_idle(merged, a[1], b[0]) for a, b in zip(execs, execs[1:]))
    return {"ok": why is None, "why": why, "lead_ns": lead, "lag_ns": lag,
            "return_lead_ns": [s - it[returned] for (s, _), it in pairs
                               if returned and it.get(returned) is not None],
            "host_s": [h / 1e9 for h in host],
            "launch_s": [x / 1e9 for x in launch], "host_shares": shares,
            "idle_s": (window - busy) / 1e9, "idle_in_step_s": in_step / 1e9,
            "idle_between_s": between / 1e9, "executions": len(execs),
            "matched": len(pairs)}


def _on_axis(marks, off, start):
    return {k: v * 1e9 + off - start for k, v in marks.items()}


def _items(run, off, start):
    """The launching thread's marks on the capture's axis, in time order,
    with the loop's own time between two items as a last phase."""
    if run["kind"] == "train":
        synced = {e["step"] for e in events(run, "device_sync")}
        items = []
        for e in events(run, "step"):
            if "marks" not in e:
                continue
            it = _on_axis(e["marks"], off, start)
            # only a step that fetched its finiteness flag waited for the
            # device; elsewhere 'synced' follows 'dispatched' at once
            it["seen"] = it["synced"] if e["step"] in synced else None
            items.append(it)
        key, last, first, gap = "put", "done", "start", "between_steps"
    else:
        items = [_on_axis(e["marks"], off, start)
                 for e in events(run, "trace", event="batch") if "marks" in e]
        key, last, first, gap = "called", "completed", "wait", "loop_back"
    items = sorted((it for it in items if key in it), key=lambda it: it[key])
    for a, b in zip(items, items[1:]):
        if last in a and first in b:
            a["next"] = b[first]
    return items, (gap, last, "next")


def of(run):
    """The run's join, or None; built once, with its two lines printed."""
    if "_timeline" not in run:
        run["_timeline"] = _build(run)
    return run["_timeline"]


def _build(run):
    if offset_ns(run) is None or not run.get("trace_dir"):
        return None
    try:
        path = xtrace.find_xplane(run["trace_dir"])
    except FileNotFoundError:
        return None
    start = profile_start_ns(path)
    module = run["cell"].traffic.get("trace_module", "jit_step")
    dev = device_intervals(xtrace.load(path), module)
    if start is None or dev is None:
        return None
    j = join_run(run, start, *dev)
    _print(run, j)
    return j if j["ok"] else None


def join_run(run, start, execs, merged):
    """The join of a run's events with device intervals whose axis starts
    at ``start`` Unix ns (also what ``tests/dump_timeline.py`` keeps)."""
    items, loop_phase = _items(run, offset_ns(run), start)
    if run["kind"] == "train":
        # 'synced' follows a fetch of several scalars and the finiteness
        # bookkeeping, milliseconds after the device's end: only its side
        # is checked, not how late it is
        return join(execs, merged, match_in_order(execs, items, "put"),
                    "put", "seen", TRAIN_PHASES + (loop_phase,), items,
                    returned="dispatched")
    return join(execs, merged,
                match_contained(execs, items, "called", "ready"),
                "called", "ready", SERVE_PHASES + (loop_phase,), items,
                late_ns=LATE_READY_S * 1e9)


def _ms(ns, fn):
    return round(fn(ns) / 1e6, 4) if ns else None


def _print(run, j):
    clocks = events(run, "clock")
    offs = [c["time_ns"] - c["perf_counter"] * 1e9 for c in clocks]
    now = time.time_ns() - time.perf_counter() * 1e9
    print("[clock] " + " ".join(f"{k}={v}" for k, v in {
        "ok": j["ok"], "why": (j["why"] or "-").replace(" ", "_"),
        "matched": f"{j['matched']}/{j['executions']}",
        "start_minus_call_ms_min": _ms(j["lead_ns"], min),
        "start_minus_call_ms_median": _ms(j["lead_ns"], statistics.median),
        "start_minus_return_ms_median": _ms(j["return_lead_ns"],
                                            statistics.median),
        "seen_minus_end_ms_min": _ms(j["lag_ns"], min),
        "seen_minus_end_ms_median": _ms(j["lag_ns"], statistics.median),
        "seen_samples": len(j["lag_ns"]),
        "clock_events": len(clocks),
        "drift_over_run_us": round((offs[-1] - offs[0]) / 1e3, 1),
        "drift_to_now_us": round((now - offs[-1]) / 1e3, 1)}.items()),
        flush=True)
    mean = lambda xs: statistics.fmean(xs) if xs else 0.0  # noqa: E731
    host, launch = 1e3 * mean(j["host_s"]), 1e3 * mean(j["launch_s"])
    print("[gaps] " + " ".join(f"{k}={v}" for k, v in {
        "n": len(j["host_s"]), "host_ms": round(host, 4),
        "launch_ms": round(launch, 4), "host_plus_launch_ms":
        round(host + launch, 4), "device_gap_ms": round(
            1e3 * j["idle_between_s"] / max(1, j["executions"] - 1), 4),
        "idle_ms": round(1e3 * j["idle_s"], 3),
        "between_ms": round(1e3 * j["idle_between_s"], 3),
        "in_step_ms": round(1e3 * j["idle_in_step_s"], 3),
        "outside_ms": round(1e3 * (j["idle_s"] - j["idle_between_s"]
                                   - j["idle_in_step_s"]), 3),
        "host_part_by_phase": ",".join(
            f"{k}:{v:.3f}" for k, v in sorted(j["host_shares"].items(),
                                              key=lambda kv: -kv[1])) or "-",
    }.items()), flush=True)
