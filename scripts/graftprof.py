#!/usr/bin/env python3
"""graftprof CLI: measured device-time attribution, gated against the
machine-scoped calibration pins in ``prof-budget.json``.

Two modes:

- **capture-and-attribute** (default): runs every graftcost audit
  program (the exact set ``hlo-budget.json`` pins) inside its own
  profiler trace segment, attributes measured device time per op class,
  diffs it against the roofline-predicted seconds, and gates the
  measured/predicted ratio per program against the pins for *this*
  machine (``platform:device_kind``).
- **attribute-only** (``--trace-dir DIR``): parses an existing capture
  (a ``/profilez`` artifact, a ``train --profile`` dir) and prints the per-module attribution —
  no gating, module→program matching is best-effort.

    python scripts/graftprof.py                     # audit vs prof-budget.json
    python scripts/graftprof.py --update            # re-pin this machine
    python scripts/graftprof.py --format json       # machine-readable report
    python scripts/graftprof.py --trace-dir /tmp/t  # attribute a capture
    python scripts/graftprof.py --events out.jsonl  # 'profile' telemetry

Exit codes: 0 — every profiled program within its calibration band
(stale pins alone don't fail; prune them with --update); 1 — findings
(calibration drift, unpinned program); 2 — usage error.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from raft_meets_dicl_tpu.analysis import profile as prof  # noqa: E402


def json_report(report):
    """Stable machine-readable schema (graftcost discipline): bump
    ``schema`` on any incompatible change."""
    out = report.to_dict()
    out["schema"] = 1
    out["exit_code"] = 0 if report.ok else 1
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="exit codes: 0 within calibration; 1 findings; "
               "2 usage error")
    ap.add_argument("--budget", default=None, metavar="FILE",
                    help=f"pinned calibration JSON (default: <repo>/"
                         f"{prof.BUDGET_NAME})")
    ap.add_argument("--update", action="store_true",
                    help="re-pin this machine's calibration from this "
                         "run's ratios (other machines' pins are "
                         "preserved) instead of gating")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="report format (default: text)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="attribute an existing capture directory "
                         "instead of capturing (no gating)")
    ap.add_argument("--no-mesh2d", action="store_true",
                    help="skip the 8-device (4, 2)-mesh SPMD variant "
                         "(faster; its pins then report stale)")
    ap.add_argument("--repeats", type=int, default=2, metavar="N",
                    help="traced executions per program (default: 2)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="keep the segmented capture under DIR instead "
                         "of a deleted tempdir")
    ap.add_argument("--events", default=None, metavar="FILE",
                    help="append per-program 'profile' telemetry "
                         "events")
    args = ap.parse_args(argv)

    if args.trace_dir:
        try:
            summary = prof.attribute_trace(args.trace_dir)
        except prof.TraceError as e:
            print(f"graftprof: {e}", file=sys.stderr)
            return 2
        if args.format == "json":
            summary["schema"] = 1
            json.dump(summary, sys.stdout, indent=2)
            print()
        else:
            print(prof.render_attribution(summary))
        return 0

    budget_path = Path(args.budget) if args.budget else \
        Path(__file__).parent.parent / prof.BUDGET_NAME
    budget = (prof.ProfBudget.load(budget_path) if budget_path.exists()
              else prof.ProfBudget.empty())

    from raft_meets_dicl_tpu.analysis import cost

    entries = cost.build_entries(include_mesh2d=not args.no_mesh2d)
    report = prof.audit_profiles(entries=entries, budget=budget,
                                 out_dir=args.keep_trace,
                                 repeats=args.repeats)

    if args.events:
        from raft_meets_dicl_tpu import telemetry

        tele = telemetry.Telemetry(args.events)
        try:
            prof.emit_events(report, tele)
        finally:
            tele.close()

    if args.update:
        machine_id = report.machine["machine_id"]
        budget.path = str(budget_path)
        budget_path.write_text(
            json.dumps(budget.pinned_data(report.reports, machine_id),
                       indent=2) + "\n")
        print(f"pinned {len(report.reports)} calibration(s) for "
              f"{machine_id} -> {budget_path}")
        for k in report.stale:
            print(f"  dropped stale entry: {k}")
        return 0

    if args.format == "json":
        json.dump(json_report(report), sys.stdout, indent=2)
        print()
    else:
        print(prof.render_reports(report))
    return 0 if report.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
