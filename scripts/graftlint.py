#!/usr/bin/env python3
"""graftlint CLI: the TPU-hazard lint pass + HLO program auditor.

Static pass (default) — AST rules over the repo's Python surface
(host-sync, tracer-branch, f32-literal, env-knob, env-docs), with
line-level ``# graftlint: disable=<rule> -- <reason>`` suppressions and
the committed ``graftlint-baseline.json`` of grandfathered findings.
Exit code is 0 iff no finding is *open* (suppressed/baselined don't
fail) — so CI stays green on the committed tree and goes red the moment
a new hazard lands without a justification.

HLO pass (``--hlo``) — lowers the registered flagship step programs
twice each and audits fingerprint stability, collective counts
(post-partitioner), f32 convolutions, and baked-in constants. Needs jax; the
static pass does not. (The quantitative cost/budget gate lives in
``scripts/graftcost.py``.)

    python scripts/graftlint.py                  # lint, human-readable
    python scripts/graftlint.py --format json    # machine-readable
    python scripts/graftlint.py --baseline b.json --root /path/to/repo
    python scripts/graftlint.py --prune          # drop stale baseline entries
    python scripts/graftlint.py --fix-knob-table # regenerate README table
    python scripts/graftlint.py --hlo            # add the program audit
    python scripts/graftlint.py --events out.jsonl  # findings as telemetry

Exit codes: 0 — no open findings (suppressed/baselined/stale don't
fail); 1 — at least one open finding; 2 — usage or config error
(unreadable baseline, bad flags).
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from raft_meets_dicl_tpu.analysis import lint  # noqa: E402


def fix_knob_table(root):
    from raft_meets_dicl_tpu.utils import env

    readme = Path(root) / "README.md"
    text = readme.read_text()
    new = env.splice_readme(text)
    if new == text:
        print("README knob table already up to date")
        return 0
    readme.write_text(new)
    print("README knob table regenerated from utils.env.KNOBS")
    return 0


def prune_baseline(root, baseline_path):
    """Rewrite the baseline with this run's unused entries removed.

    The run itself decides staleness (an entry is stale iff it matched
    no finding), so pruning is always relative to the *current* tree.
    The file's header comment and version ride through untouched.
    """
    path = Path(baseline_path) if baseline_path else \
        Path(root) / lint.BASELINE_NAME
    if not path.exists():
        print(f"no baseline at {path}; nothing to prune")
        return 0
    baseline = lint.Baseline.load(path)
    lint.run(root, baseline=baseline)
    stale = baseline.unused_entries()
    if not stale:
        print(f"{path}: no stale entries; baseline unchanged")
        return 0
    data = json.loads(path.read_text())
    keep = [e for e in baseline.entries if e not in stale]
    data["entries"] = keep
    path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"{path}: pruned {len(stale)} stale entr"
          f"{'y' if len(stale) == 1 else 'ies'}, {len(keep)} kept")
    for e in stale:
        print(f"  dropped: {e['rule']} @ {e['glob']}")
    return 0


def json_report(report, hlo_reports=None):
    """Stable machine-readable schema for CI consumers. Contract:
    ``schema`` bumps on any incompatible change; ``exit_code`` mirrors
    the process exit code (0 iff no open finding); findings carry
    rule/path/line/severity/status/message (+justification when
    suppressed or baselined); ``stale_baseline_entries`` lists baseline
    entries that matched nothing."""
    out = report.to_dict()
    out["schema"] = 1
    out["exit_code"] = 0 if report.ok else 1
    if hlo_reports is not None:
        out["hlo"] = hlo_reports
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="exit codes: 0 no open findings; 1 open findings; "
               "2 usage/config error")
    ap.add_argument("--root", default=str(Path(__file__).parent.parent),
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: <root>/"
                         f"{lint.BASELINE_NAME} if present)")
    ap.add_argument("--format", choices=("text", "json"), default=None,
                    help="report format (default: text)")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--prune", action="store_true",
                    help="rewrite the baseline without stale entries "
                         "(those matching nothing on this tree) and exit")
    ap.add_argument("--fix-knob-table", action="store_true",
                    help="regenerate the README env-knob table and exit")
    ap.add_argument("--hlo", action="store_true",
                    help="also lower + audit the registered flagship "
                         "programs (requires jax)")
    ap.add_argument("--events", default=None, metavar="FILE",
                    help="append findings as 'lint' telemetry events")
    args = ap.parse_args(argv)

    if args.fix_knob_table:
        return fix_knob_table(args.root)
    if args.prune:
        return prune_baseline(args.root, args.baseline)

    baseline = (lint.Baseline.load(args.baseline)
                if args.baseline else None)
    report = lint.run(args.root, baseline=baseline)

    hlo_reports, hlo_findings = [], []
    if args.hlo:
        from raft_meets_dicl_tpu.analysis import hlo

        hlo_reports, hlo_findings = hlo.audit_registry()
        report.findings.extend(hlo_findings)

    if args.events:
        from raft_meets_dicl_tpu import telemetry

        tele = telemetry.Telemetry(args.events)
        try:
            lint.emit_events(report, tele)
        finally:
            tele.close()

    fmt = args.format or ("json" if args.json else "text")
    if fmt == "json":
        json.dump(json_report(report, hlo_reports if args.hlo else None),
                  sys.stdout, indent=2)
        print()
    else:
        print(lint.render_text(report))
        if args.hlo:
            from raft_meets_dicl_tpu.analysis import hlo

            print(hlo.render_reports(hlo_reports))

    return 0 if report.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
