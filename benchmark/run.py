#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell's entry, its configuration file, its traffic file and, for a
traced run, the reader of each per-layer metric the cell lists; drives the
program through the driver of the traffic's kind (``harness/train.py``,
``harness/serve.py``); checks the timed path against the plain reference;
prints one JSON object as the last line of its standard output. It needs
the accelerator and the chips the cell names and exits non-zero, with no
result line, without them. ``PERF.md`` says what each piece is for.
"""

import time

_T0 = time.perf_counter()
_WALL0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def process_age_s():
    """Seconds this process had lived when ``_WALL0`` was taken: interpreter
    start-up belongs to set-up too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        started = btime + start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, _WALL0 - started)
    except (OSError, ValueError, StopIteration):
        return 0.0


def run_cell(cell, seed, seconds, trace, out_root, platform="tpu"):
    """Everything but argument parsing and the result line: also what the
    CPU rehearsal and the self-tests call."""
    from benchmark.harness import check, device, spec, xtrace

    out_dir = Path(out_root) / cell.name / f"seed{seed}_trace{int(trace)}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    boot = {"t0": _T0, "offset_s": process_age_s()}

    driver = spec.load_driver(cell.traffic["kind"])
    run = driver.run(cell, seed, seconds, trace, out_dir, boot,
                     platform=platform)
    run["readings"] = driver.readings(run)
    driver.write_records(run)
    driver.print_rates(run)
    # the host-clock readings of the untraced window, in a traced run too
    print("[end_to_end] " + json.dumps(
        {m["name"]: run["readings"][m["name"]] for m in cell.end_to_end}),
        flush=True)

    run["peak_bytes"] = driver.memory_peak(run)
    run["trace"] = None
    if trace:
        capture = xtrace.load(xtrace.find_xplane(run["trace_dir"]))
        run["trace"] = xtrace.reduce(capture, driver.trace_module(run))

    verdict = check.Verdict()
    limits = check.limits_for(cell.name) if platform == "tpu" else \
        cell.traffic.get("rehearsal_limits", {})
    verdict.hold("window_compiles", run["readings"]["window_compiles"], 0)
    driver.check(run, verdict, limits)
    verdict.print()

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(run["readings"][m["name"]]),
                                  "unit": m["unit"]}

    attempted, failed = driver.attempted_failed(run)
    dev = device.describe(run["devices"])
    dev["memory_peak_bytes"] = int(run["peak_bytes"])
    result = {"correct": verdict.correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace and run["trace"]:
        dev["busy_s"] = run["trace"]["busy_s"]
        dev["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = xtrace.breakdown(run["trace"])
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark.harness import spec

    cell = spec.load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, args.trace,
                      ROOT / "bench_out")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # the program's loader and telemetry keep daemon threads; leave at once
    os._exit(0)


if __name__ == "__main__":
    main()
