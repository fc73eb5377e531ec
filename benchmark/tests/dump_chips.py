#!/usr/bin/env python3
"""What a traced run over several chips looks like, and a small capture of
it for the readers' self-tests: the last executions of the cell's module on
the first two device planes (``XLA Modules`` and ``XLA Ops`` lines, texts
cut down to ``%name = type opcode(``), every plane's lines by name and
size, and per plane the busy time and the collective time an execution.

    python3 benchmark/tests/dump_chips.py bench_out/<cell>/<run>/trace out_dir [module] [executions]
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.harness import xtrace  # noqa: E402
from benchmark.layers import _chips  # noqa: E402
from benchmark.tests.dump_ops import short_text  # noqa: E402


def small_capture(capture, module, executions, planes=2):
    """The last ``executions`` runs of ``module`` on the first ``planes``
    device planes, each plane cut at its own executions."""
    out = []
    for plane in [p for p in capture["planes"]
                  if xtrace._DEVICE.match(p["name"])][:planes]:
        mods = [e for e in xtrace._line(plane, "XLA Modules")["events"]
                if module in e[0] and e[2] > 0][-executions:]
        t0, t1 = mods[0][1], mods[-1][1] + mods[-1][2]
        ops = [[short_text(n), s, d, {}]
               for n, s, d, _ in xtrace._line(plane, "XLA Ops")["events"]
               if t0 <= s < t1]
        out.append({"name": plane["name"], "lines": [
            {"name": "XLA Modules",
             "events": [[n, s, d, {}] for n, s, d, _ in mods]},
            {"name": "XLA Ops", "events": ops}]})
    return {"planes": out}


def main():
    trace_dir, out = Path(sys.argv[1]), Path(sys.argv[2])
    module = sys.argv[3] if len(sys.argv) > 3 else "jit_step"
    executions = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    out.mkdir(parents=True, exist_ok=True)
    capture = xtrace.load(xtrace.find_xplane(trace_dir))
    for plane in capture["planes"]:
        print("PLANE", plane["name"], {ln["name"]: len(ln["events"])
                                       for ln in plane["lines"]})
    for plane in capture["planes"]:
        if not xtrace._DEVICE.match(plane["name"]):
            continue
        mods = xtrace._line(plane, "XLA Modules")
        ops = xtrace._line(plane, "XLA Ops")
        if mods is None or ops is None:
            continue
        execs, inside = _chips.split(
            [(n, s, d) for n, s, d, _ in mods["events"]],
            [(n, s, d) for n, s, d, _ in ops["events"]], {module})
        n = max(1, len(execs))
        busy = xtrace._covered(xtrace._union(
            [[s, s + d] for _, s, d in inside])) / 1e6 / n
        exposed = _chips.exposed_s(execs, inside)
        by_op = {}
        for t, _, d in inside:
            if xtrace.op_class(t) == "collective":
                key = xtrace.parse_op(t)[1]
                by_op[key] = by_op.get(key, 0.0) + d / 1e6 / n
        coll = sum(by_op.values())
        print(f"[chips] {plane['name']} executions={len(execs)} busy_ms="
              f"{busy:.3f} collective_ms={coll:.3f} exposed_ms="
              f"{1e3 * (exposed or 0.0):.3f} by_opcode="
              f"{ {k: round(v, 3) for k, v in sorted(by_op.items())} }",
              flush=True)
    small = small_capture(capture, module, executions)
    xtrace.save(small, out / "chips_capture_small.json.gz")
    print("kept", sum(len(ln["events"]) for p in small["planes"]
                      for ln in p["lines"]), "events of",
          [p["name"] for p in small["planes"]])


if __name__ == "__main__":
    main()
