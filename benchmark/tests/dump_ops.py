#!/usr/bin/env python3
"""What a traced run's device operations are called: every Mosaic custom
call with its whole instruction text, its count, its time and the stats
the profiler attaches to it, and the stat keys any operation carries (is
there a name stack to tell a scope by?). Also keeps the last executions of
the cell's module as a small capture for the readers' self-tests: the
device's ``XLA Modules`` and ``XLA Ops`` lines, texts cut down to
``%name = type opcode(``, custom calls whole.

    python3 benchmark/tests/dump_ops.py bench_out/<cell>/<run>/trace out_dir [module] [executions]
"""

import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.harness import xtrace  # noqa: E402


def short_text(text):
    """``%name = type opcode(`` of an instruction; custom calls whole (a
    kernel is told by its operands' shapes too)."""
    name, opcode, kind = xtrace.parse_op(text)
    if not opcode or opcode == "custom-call":
        return text[:900]
    head = text.split(f" {opcode}(")[0]
    return f"{head} {opcode}()" + (f", kind=k{kind}" if kind else "")


def small_capture(capture, module, executions):
    """The last ``executions`` runs of ``module`` on the first device."""
    plane = next(p for p in capture["planes"]
                 if xtrace._DEVICE.match(p["name"]))
    mods = [e for e in xtrace._line(plane, "XLA Modules")["events"]
            if module in e[0] and e[2] > 0][-executions:]
    t0, t1 = mods[0][1], mods[-1][1] + mods[-1][2]
    ops = [[short_text(n), s, d, st]
           for n, s, d, st in xtrace._line(plane, "XLA Ops")["events"]
           if t0 <= s < t1]
    return {"planes": [{"name": plane["name"], "lines": [
        {"name": "XLA Modules", "events": [[n, s, d, {}] for n, s, d, _
                                           in mods]},
        {"name": "XLA Ops", "events": ops}]}]}


def main():
    trace_dir, out = Path(sys.argv[1]), Path(sys.argv[2])
    module = sys.argv[3] if len(sys.argv) > 3 else "jit_step"
    executions = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    out.mkdir(parents=True, exist_ok=True)
    capture = xtrace.load(xtrace.find_xplane(trace_dir))
    reduced = xtrace.reduce(capture, module)
    n = max(1, reduced["executions"])

    keys = {}
    plane = next(p for p in capture["planes"]
                 if xtrace._DEVICE.match(p["name"]))
    for text, _, _, stats in xtrace._line(plane, "XLA Ops")["events"]:
        for k, v in stats.items():
            keys.setdefault(k, (xtrace.parse_op(text)[0], str(v)[:300]))
    print("stat keys on device operations (first carrier, value):")
    for k, (name, value) in sorted(keys.items()):
        print(f"  {k}: {name}: {value}")

    print(f"mosaic operations, {n} executions of {module}:")
    for text, seconds in sorted(reduced["op_s"].items(), key=lambda kv: -kv[1]):
        # custom calls of a nanosecond are the compiler's markers
        if xtrace.op_class(text) != "mosaic" or seconds / n < 1e-6:
            continue
        stats = next((st for t, _, _, st in
                      xtrace._line(plane, "XLA Ops")["events"]
                      if t == text and st), {})
        print(json.dumps({"text": text[:900], "ms_per_exec": 1e3 * seconds / n,
                          "count_per_exec": reduced["op_count"][text] / n,
                          "stats": stats}))
    print("classes ms per execution:",
          {k: round(1e3 * v, 3) for k, v in
           reduced["class_s_per_exec"].items()})

    xtrace.save(small_capture(capture, module, executions),
                out / "capture_small.json.gz")
    events = trace_dir.parent / "events.jsonl"
    if events.is_file():
        shutil.copy(events, out / "events.jsonl")
    print("kept", out / "capture_small.json.gz",
          (out / "capture_small.json.gz").stat().st_size, "bytes")


if __name__ == "__main__":
    main()
