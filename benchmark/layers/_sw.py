"""The window sampler's calls in a traced train run, and which path the
program says its sampler calls took. Shared by ``sw_ms`` and
``sw_roofline``."""

from ..harness import sw_kernel
from ._common import trace_of

PATHS = ("sw_fused_calls", "sw_fallback_calls")


def path_counts(run):
    """``(fused, fallback)`` as the train step's program reports them: the
    counts ride on its ``compile`` event when this run traced it and on
    the ``aot`` event that holds its executable when it came from the
    store. None when the program says nothing (an older program)."""
    for ev in run["events"]:
        carrier = (ev["kind"] == "aot" and ev.get("program") == "train_step"
                   or ev["kind"] == "compile"
                   and ev.get("label") == "train_step")
        if carrier and any(p in ev for p in PATHS):
            return tuple(int(ev.get(p, 0)) for p in PATHS)
    return None


def calls(run):
    """``[(direction, (b, i, j, taps, c), map_bytes, seconds, count)]`` over
    the traced executions, or None: no trace, the program does not say
    which path its calls took, or one of them fell back to XLA (the time
    of the calls that are left would pass as the sampler's). Read once a
    run: both readers take it."""
    if "sw_calls" not in run:
        run["sw_calls"] = _calls(run)
    return run["sw_calls"]


def _calls(run):
    t = trace_of(run, "train")
    if t is None:
        return None
    counts = path_counts(run)
    if counts is None:
        print("[sw] the program reports no sampler path", flush=True)
        return None
    fused, fallback = counts
    print(f"[sw] sw_fused_calls={fused} sw_fallback_calls={fallback}",
          flush=True)
    if fallback or not fused:
        return None
    found = []
    for text, seconds in t["op_s"].items():
        # Mosaic kernels carry their scope's name; ``custom-call.N`` are
        # the compiler's own markers
        if text.lstrip("%").startswith("custom-call"):
            continue
        parsed = sw_kernel.call(text)
        if parsed:
            found.append((*parsed, seconds, t["op_count"][text]))
    return found or None


def by_level(found, executions):
    """Milliseconds a step by window shape and direction, for the print."""
    split = {}
    for direction, (b, i, j, taps, c), _, seconds, _ in found:
        key = f"{i}x{j}:{direction}"
        split[key] = split.get(key, 0.0) + 1e3 * seconds / executions
    return dict(sorted(split.items()))
