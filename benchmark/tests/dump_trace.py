#!/usr/bin/env python3
"""Save a run's profiler capture as the plain lists ``xtrace.load`` makes
(``.json.gz``), host events under ``--min-host-us`` left out, and print the
capture's structure: what a person reads before trusting the reduction.

    python3 benchmark/tests/dump_trace.py bench_out/<cell>/<run>/trace out.json.gz
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.harness import xtrace  # noqa: E402


def _event_attributes(path):
    """What the profiler's reader offers on a device event."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        print("plane attributes", [a for a in dir(plane) if a[0] != "_"])
        for line in plane.lines:
            for ev in line.events:
                print("line", line.name, "event attributes",
                      [a for a in dir(ev) if a[0] != "_"])
                print("   stats", [(k, str(v)[:80]) for k, v in ev.stats])
                break


def main():
    trace_dir, out = sys.argv[1], sys.argv[2]
    min_host_ns = 1e3 * float(sys.argv[3]) if len(sys.argv) > 3 else 20e3
    capture = xtrace.load(xtrace.find_xplane(trace_dir))
    _event_attributes(xtrace.find_xplane(trace_dir))
    for plane in capture["planes"]:
        host = plane["name"].startswith("/host:")
        print("PLANE", plane["name"])
        for line in plane["lines"]:
            if host:
                line["events"] = [e for e in line["events"]
                                  if e[2] >= min_host_ns]
            print("  LINE", line["name"], len(line["events"]))
            for e in line["events"][:3]:
                print("     ", e[0][:100], e[1], e[2], e[3])
    xtrace.save(capture, out)
    reduced = xtrace.reduce(capture, "jit_step")
    if reduced:
        for k, v in reduced.items():
            if k not in ("op_s", "idle_gaps", "op_text", "op_count"):
                print(k, v)
        print(xtrace.breakdown(reduced))


if __name__ == "__main__":
    main()
