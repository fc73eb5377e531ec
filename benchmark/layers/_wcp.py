"""The windowed correlation's Mosaic calls in a traced train run, and what
the program says of them. Shared by ``wcp_ms``, ``wcp_roofline`` and
``wcp_levels_windowed``.

The calls are told by the name their scope gives them: the program runs
``ops/pallas.windowed_corr_pyramid`` inside ``jax.named_scope("wcp")``,
and the compiler names a custom call after its innermost scope, so the
three kernels (forward, backward to frame one's features, backward to
each map) are the device operations ``wcp.N``. Result types, shapes and
order are not read: they are the kernels' to change."""

from ..harness import xtrace
from ._common import trace_of

SCOPE = "wcp"
PATHS = ("wcp_fused_calls", "wcp_fallback_calls")
LEVELS = "wcp_levels_windowed"


def notes(run):
    """The train step's trace-time notes: they ride on its ``compile``
    event when this run traced it and on the ``aot`` event that holds its
    executable when it came from the store. None when no such event says
    anything of the windowed correlation (an older program, or a model
    without one)."""
    if run["kind"] != "train":
        return None
    for ev in run["events"]:
        carrier = (ev["kind"] == "aot" and ev.get("program") == "train_step"
                   or ev["kind"] == "compile"
                   and ev.get("label") == "train_step")
        if carrier and any(n in ev for n in PATHS + (LEVELS,)):
            return {n: ev[n] for n in PATHS + (LEVELS,) if n in ev}
    return None


def calls(run):
    """``[(text, seconds, count)]`` of the kernels' device operations over
    the traced executions, or None: no trace, the program does not say
    which path its calls took, one of them fell back to the XLA
    composition (the time of the calls that are left would pass as the
    correlation's), or no level is windowed. Read once a run."""
    if "wcp_calls" not in run:
        run["wcp_calls"] = _calls(run)
    return run["wcp_calls"]


def _calls(run):
    t = trace_of(run, "train")
    if t is None:
        return None
    said = notes(run)
    if said is None:
        print("[wcp] the program reports no windowed-correlation path",
              flush=True)
        return None
    fused, fallback = (int(said.get(p, 0)) for p in PATHS)
    print(f"[wcp] wcp_fused_calls={fused} wcp_fallback_calls={fallback} "
          f"wcp_levels_windowed={said.get(LEVELS)}", flush=True)
    if fallback or not fused:
        return None
    found = []
    for text, seconds in t["op_s"].items():
        name, opcode, _ = xtrace.parse_op(text)
        if opcode == "custom-call" and name.split(".")[0] == SCOPE:
            found.append((text, seconds, t["op_count"][text]))
    return found or None


def seconds_a_step(run, found):
    """Device seconds one traced execution of the step spends in the
    calls ``found``."""
    return sum(seconds for _, seconds, _ in found) \
        / run["trace"]["executions"]


def by_kernel(found, executions):
    """Milliseconds a step by operation name and result type, for the
    print alone (which of the three kernels a name is shows in its
    result: the costs, a row of features, a padded map)."""
    split = {}
    for text, seconds, _ in found:
        head, _, rest = text.partition(" = ")
        key = f"{head.strip().lstrip('%')}:{rest.split('{')[0].strip()}"
        split[key] = split.get(key, 0.0) + 1e3 * seconds / executions
    return dict(sorted(split.items()))
