#!/usr/bin/env python3
"""Keep what the timeline join needs of a traced run, small enough to
bring back from the chip: the capture's Unix origin, the executions of the
cell's module, the device's busy intervals, and the program's events.

    python3 benchmark/tests/dump_timeline.py bench_out/<cell>/<run> out_dir [module]

``events.jsonl`` lies beside ``timeline.json.gz`` in ``out_dir``; a mark
``m`` of an event lies at ``m * 1e9 + time_ns - perf_counter * 1e9 -
start_ns`` (the newest ``clock`` event) on the axis of ``execs`` and
``busy``.
"""

import gzip
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.harness import xtrace  # noqa: E402
from benchmark.layers import _timeline  # noqa: E402


def main():
    run_dir, out = Path(sys.argv[1]), Path(sys.argv[2])
    module = sys.argv[3] if len(sys.argv) > 3 else "jit_step"
    out.mkdir(parents=True, exist_ok=True)
    path = xtrace.find_xplane(run_dir / "trace")
    dev = _timeline.device_intervals(xtrace.load(path), module)
    execs, busy = dev if dev else ([], [])
    with gzip.open(out / "timeline.json.gz", "wt") as f:
        json.dump({"start_ns": _timeline.profile_start_ns(path),
                   "module": module, "execs": execs, "busy": busy}, f)
    for name in ("events.jsonl", "steps.jsonl", "requests.jsonl"):
        if (run_dir / name).is_file():
            shutil.copy(run_dir / name, out / name)
    print(f"{len(execs)} executions of {module}, {len(busy)} busy intervals "
          f"-> {out}")


if __name__ == "__main__":
    main()
