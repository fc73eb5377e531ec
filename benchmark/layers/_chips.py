"""What exists only across chips, read off a traced run of a cell with more
than one: shared by ``collective_ms``, ``collective_exposed_ms`` and
``chip_step_spread_ms``.

The reduced trace (``harness/xtrace.reduce``) already holds every chip's
busy time (``busy_s_per_chip``) and the first chip's time by operation class
inside the cell's module (``class_s_per_exec``: ``collective`` is every
``all-reduce``, ``all-gather``, ``all-to-all``, ``collective-permute`` and
``reduce-scatter``, their ``-start`` and ``-done`` halves included). It
keeps no intervals, so the exposed part is read from the capture again: the
first device plane's ``XLA Ops`` line alone, inside the executions of the
cell's module on that plane.
"""

from ..harness import xtrace
from ._common import trace_of


def traced(run):
    """The reduced trace of a traced train run over several chips."""
    t = trace_of(run, "train")
    return t if t is not None and t.get("chips", 1) > 1 else None


def first_plane_ops(run):
    """``(executions, operations)`` of the first device plane:
    ``[(start_ns, end_ns)]`` of the cell's module and ``[(text, start_ns,
    duration_ns)]`` of its operations, the containers left out as the
    reduction leaves them out. Read once a run."""
    if "first_plane_ops" not in run:
        run["first_plane_ops"] = _first_plane_ops(run)
    return run["first_plane_ops"]


def _first_plane_ops(run):
    import jax

    t = traced(run)
    if t is None or not run.get("trace_dir"):
        return None
    data = jax.profiler.ProfileData.from_file(
        str(xtrace.find_xplane(run["trace_dir"])))
    plane = next((p for p in data.planes if xtrace._DEVICE.match(p.name)),
                 None)
    if plane is None:
        return None
    lines = {line.name: line for line in plane.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        return None
    chosen = {m.split("(")[0] for m in t["module"]}
    return split(
        [(ev.name, float(ev.start_ns), float(ev.duration_ns))
         for ev in lines["XLA Modules"].events],
        [(ev.name, float(ev.start_ns), float(ev.duration_ns))
         for ev in lines["XLA Ops"].events], chosen)


def split(modules, ops, chosen):
    """The plain lists of one plane's two lines into ``(executions,
    operations inside them)``; ``chosen``: the module names that count."""
    execs = sorted((s, s + d) for n, s, d in modules
                   if d > 0 and n.split("(")[0] in chosen)
    inside = []
    for text, start, dur in ops:
        name, opcode, _ = xtrace.parse_op(text)
        if dur <= 0 or opcode in xtrace._CONTAINERS \
                or name.startswith(xtrace._CONTAINERS):
            continue
        if any(s <= start < e for s, e in execs):
            inside.append((text, start, dur))
    return execs, inside


def exposed_s(execs, ops):
    """Seconds an execution in which a collective operation runs on the
    chip and no other operation does: what the step really waits."""
    if not execs:
        return None
    spans = {True: [], False: []}
    for text, s, d in ops:
        spans[xtrace.op_class(text) == "collective"].append([s, s + d])
    mine, others = xtrace._union(spans[True]), xtrace._union(spans[False])
    hidden = sum(xtrace._overlap(others, s, e) for s, e in mine)
    return (xtrace._covered(mine) - hidden) / 1e9 / len(execs)
