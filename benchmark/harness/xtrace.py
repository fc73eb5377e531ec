"""From a profiler capture to numbers: the one reduction of device traces.

``load`` reads an ``.xplane.pb`` with nothing but JAX into plain lists;
``reduce`` turns those lists into what the per-layer readers take: per
chip the busy time (union of the intervals in which an operation ran),
the executions of each compiled module with their own busy time and the
gaps between them, device time per operation class, per-operation totals,
and the idle gaps named by what the host was doing. ``tests/test_xtrace.py``
holds it against a small recorded capture.

``op_class`` started as a copy of ``analysis/profile.op_class`` (listed in
PERF.md's open questions as the original to delete). That one reads
``hlo_op`` names from trace-event JSON; the TPU's xplane, read with
``jax.profiler.ProfileData``, names each event by its whole HLO
instruction text and carries no category, so this one parses the
instruction's own opcode out of the text first.
"""

import gzip
import json
import re
from pathlib import Path

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
                "reduce-scatter", "collective-broadcast")
_GATHERS = ("gather", "scatter", "dynamic-slice", "dynamic-update-slice")
# the instruction's own opcode: the first lower-case word followed by "("
# after the result type (layout tiles are upper-case: T(8,128), S(1))
_OPCODE = re.compile(r"[\s)}]([a-z][a-z0-9\-]*)\(")


# The profiler's host tracer stays off: with it on (level 1 or 2) the traced
# tail runs slower than the untraced window it follows (a served batch
# waits 0.5 s for its input, a train step 40 ms; PERF.md, section 5), and
# the device's idle share would describe the profiler. The price is that
# idle gaps carry no host span's name. ``tests/trace_levels.py`` sets
# another level to measure this again.
HOST_TRACER_LEVEL = 0


def profile_options():
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = HOST_TRACER_LEVEL
    return options


def parse_op(text):
    """``(name, opcode, kind)`` of a device event. On the TPU the event's
    name is the instruction's HLO text, ``%name = type opcode(operands),
    kind=..., calls=...``; anything else is its own name with no opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.strip().lstrip("%"), "", ""
    m = _OPCODE.search(" " + rest)
    kind = re.search(r"kind=k(\w+)", rest)
    return (head.strip().lstrip("%"), m.group(1) if m else "",
            kind.group(1) if kind else "")


def op_class(text):
    """Bucket one device operation by its own opcode and name, never by
    its operands': collective, infeed, mosaic (custom calls: the Pallas
    kernels), conv (every MXU contraction: the TPU compiler lowers
    ``dot_general`` to convolution, so einsums and convolutions share the
    class), gather, reduce, copy or elementwise."""
    name, opcode, kind = parse_op(text)
    base = opcode.replace("-start", "").replace("-done", "")
    if base in _COLLECTIVES or any(t in name for t in _COLLECTIVES):
        return "collective"
    if "infeed" in base or "outfeed" in base:
        return "infeed"
    if base == "custom-call":
        return "mosaic"
    if (base in ("convolution", "dot") or "convolution" in name
            or kind in ("Output", "Convolution")):
        return "conv"
    if base in _GATHERS or any(t in name for t in _GATHERS):
        return "gather"
    if base in ("reduce", "reduce-window") or "reduce" in name:
        return "reduce"
    if base in ("copy", "transpose", "bitcast", "slice") or name.startswith(
            ("copy", "transpose")):
        return "copy"
    return "elementwise"


def find_xplane(trace_dir):
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _plain(value):
    if isinstance(value, (int, float, str)):
        return value
    if isinstance(value, bytes):
        return value[:200].decode("utf-8", "replace")
    return str(value)[:200]


def load(path):
    """``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, dur_ns, stats]]}]}]}``. On device planes the first
    event of each name keeps its stats (they describe the operation, not
    the execution); host events keep none (they are many and large)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events, seen = [], set()
            for ev in line.events:
                stats = {}
                if on_device and ev.name not in seen:
                    seen.add(ev.name)
                    stats = {str(k): _plain(v) for k, v in ev.stats}
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(capture, path):
    with gzip.open(path, "wt") as f:
        json.dump(capture, f)


def load_saved(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged):
    return sum(e - s for s, e in merged)


def _overlap(merged, start, end):
    """Length of [start, end] covered by the merged intervals."""
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in merged
               if e > start and s < end)


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_CONTAINERS = ("while", "tuple", "call", "conditional")


def _line(plane, *names):
    for line in plane["lines"]:
        if line["name"] in names:
            return line
    return None


def _host_spans(capture):
    """Host events that may explain a device gap: everything on the host
    planes except the python tracer's frames, longest first per lookup."""
    spans = []
    for plane in capture["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            if line["name"] == "python":
                continue
            for name, start, dur, _ in line["events"]:
                if dur > 0:
                    spans.append((start, start + dur, name))
    return spans


def _name_gap(spans, start, end):
    """The host span that covers most of a gap (ties: the shortest span,
    which is the most specific)."""
    best, best_key = "unattributed", (0.0, 0.0)
    for s, e, name in spans:
        cover = min(e, end) - max(s, start)
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(capture, module_filter=None, min_gap_ns=50_000.0):
    """The reduced trace. Times in seconds unless the key says otherwise.

    ``module_filter`` picks the module executions that count as a step or
    a served batch (a substring of the module's name, e.g. ``jit_step``);
    None takes the module with the most device time.
    """
    devices = [p for p in capture["planes"] if _DEVICE.match(p["name"])]
    if not devices:
        return None
    spans = _host_spans(capture)
    per_chip = []
    for plane in devices:
        ops_line = _line(plane, "XLA Ops")
        mod_line = _line(plane, "XLA Modules")
        if ops_line is None:
            continue
        ops = []
        for n, s, d, st in ops_line["events"]:
            name, opcode, _ = parse_op(n)
            if d > 0 and opcode not in _CONTAINERS \
                    and not name.startswith(_CONTAINERS):
                ops.append((n, s, d, st))
        if not ops:
            continue
        merged = _union([[s, s + d] for _, s, d, _ in ops])
        t0, t1 = merged[0][0], merged[-1][1]
        modules = [(n, s, d) for n, s, d, _ in
                   (mod_line["events"] if mod_line else []) if d > 0]
        per_chip.append({"plane": plane["name"], "ops": ops, "merged": merged,
                         "t0": t0, "t1": t1, "modules": modules})
    if not per_chip:
        return None

    # one window for all chips: first op start to last op end
    t0 = min(c["t0"] for c in per_chip)
    t1 = max(c["t1"] for c in per_chip)
    window = (t1 - t0) / 1e9
    busy = [_covered(c["merged"]) / 1e9 for c in per_chip]

    first = per_chip[0]
    # which module is "the step"
    totals = {}
    for n, s, d in first["modules"]:
        totals[n.split("(")[0]] = totals.get(n.split("(")[0], 0.0) + d
    if module_filter is not None:
        chosen = [n for n in totals if module_filter in n]
    else:
        chosen = sorted(totals, key=totals.get, reverse=True)[:1]
    execs = sorted((s, s + d) for n, s, d in first["modules"]
                   if n.split("(")[0] in chosen)

    exec_busy = [_overlap(first["merged"], s, e) / 1e9 for s, e in execs]
    gaps = []
    for (s0, e0), (s1, _) in zip(execs, execs[1:]):
        idle = (s1 - e0) - _overlap(first["merged"], e0, s1)
        gaps.append(max(0.0, idle) / 1e9)

    # per class and per op, inside the chosen executions only
    by_class, by_op, op_count = {}, {}, {}
    for n, s, d, st in first["ops"]:
        if not any(es <= s < ee for es, ee in execs):
            continue
        cls = op_class(n)
        by_class[cls] = by_class.get(cls, 0.0) + d / 1e9
        by_op[n] = by_op.get(n, 0.0) + d / 1e9
        op_count[n] = op_count.get(n, 0) + 1

    # idle gaps of the first chip inside the window, named by the host
    idle = []
    for (_, e0), (s1, _) in zip(first["merged"], first["merged"][1:]):
        if s1 - e0 >= min_gap_ns:
            idle.append((_name_gap(spans, e0, s1), (s1 - e0) / 1e9))
    small = (window - busy[0]) - sum(g for _, g in idle)
    totals_by_name = {}
    for name, g in idle:
        totals_by_name[name] = totals_by_name.get(name, 0.0) + g

    n_exec = max(1, len(execs))
    return {
        "chips": len(per_chip),
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_chip": busy,
        "module": chosen,
        "executions": len(execs),
        "exec_busy_s": exec_busy,
        "exec_gap_s": gaps,
        "class_s_per_exec": {k: v / n_exec for k, v in by_class.items()},
        "op_s": by_op,
        "op_count": op_count,
        "idle_gaps": sorted(idle, key=lambda g: -g[1]),
        "idle_by_host_span": totals_by_name,
        "idle_small_gaps_s": max(0.0, small),
    }


def breakdown(reduced, top=10):
    """The result line's ``breakdown``: longest device operations and the
    longest idle gaps by what the host was doing, at most ``top`` each."""
    if not reduced:
        return None
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    longest = reduced["idle_gaps"][: top // 2]
    totals = sorted(reduced["idle_by_host_span"].items(),
                    key=lambda kv: -kv[1])[: top - len(longest) - 1]
    gaps = ([[n, g] for n, g in longest]
            + [[f"total:{n}", g] for n, g in totals]
            + [["total:gaps_under_50us", reduced["idle_small_gaps_s"]]])
    return {"device_ops": [[_short(n), s] for n, s in ops],
            "idle_gaps": [[_short(n), g] for n, g in gaps[:top]]}


def _short(text):
    """``name:type:class`` of a device operation, or a host span's name."""
    name, opcode, _ = parse_op(text)
    if not opcode:
        return re.sub(r"[^A-Za-z0-9_.:/()\-]", "_", name)[:64]
    result = re.search(r"= \(?([a-z0-9]+\[[0-9,]*\])", text)
    return f"{name}:{result.group(1) if result else opcode}:{op_class(text)}"[:80]
