"""graftlint: the AST lint framework (rules live in sibling modules).

The framework owns everything rule-independent: walking the repo's
Python surface, parsing modules once, line-level suppressions, the
grandfathered-findings baseline, and the run report. Each rule module
exports a ``RULES`` list of :class:`Rule` objects whose ``check``
(per-module) and ``check_project`` (whole-surface, e.g. knob-registry
completeness) hooks yield :class:`Finding`s.

Suppression syntax, on the offending line::

    x = float(loss)  # graftlint: disable=host-sync -- eval summary, post-step

The ``-- reason`` is mandatory: a suppression without one is itself a
finding (``bad-suppression``), as is one naming an unknown rule. For
legacy cold-path clusters the committed ``graftlint-baseline.json``
carries glob-scoped entries with justifications instead of littering
dozens of files with pragmas; ``scripts/graftlint.py`` is the CLI.
"""

import ast
import fnmatch
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_,-]+)"
    r"(?:\s*--\s*(?P<reason>\S.*))?")

# repo surface the lint pass covers by default, relative to the root;
# tests are exempt (they exercise violations on purpose)
DEFAULT_TARGETS = ("raft_meets_dicl_tpu", "scripts", "main.py")
EXCLUDE_PARTS = {"__pycache__", ".git", "runs", ".jax_cache"}

BASELINE_NAME = "graftlint-baseline.json"


@dataclass
class Finding:
    """One rule hit at a source location."""
    rule: str
    path: str  # repo-relative, posix separators
    line: int
    message: str
    severity: str = "error"  # error | warn
    status: str = "open"     # open | suppressed | baselined
    justification: str = ""

    @property
    def location(self):
        return f"{self.path}:{self.line}"

    def to_dict(self):
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "severity": self.severity, "status": self.status,
             "message": self.message}
        if self.justification:
            d["justification"] = self.justification
        return d


@dataclass
class Rule:
    """A named rule: ``check(module)`` runs per module, ``project(ctx)``
    once over the whole surface. Either may be None."""
    name: str
    doc: str
    check: object = None
    project: object = None


class Module:
    """One parsed source module plus its suppression table."""

    def __init__(self, path, rel, source):
        self.path = Path(path)
        self.rel = rel
        self.source = source
        self.tree = ast.parse(source, filename=rel)
        self.lines = source.splitlines()
        # lineno -> (frozenset(rule names) or None for all, reason)
        self.suppressions = {}
        self.bad_suppressions = []  # Findings, attached by the runner
        for i, text in enumerate(self.lines, 1):
            m = SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = frozenset(r.strip() for r in m.group(1).split(",")
                              if r.strip())
            reason = (m.group("reason") or "").strip()
            self.suppressions[i] = (rules, reason)

    def suppressed(self, rule, line):
        entry = self.suppressions.get(line)
        if entry is None:
            return None
        rules, reason = entry
        if rule in rules or "all" in rules:
            return reason or ""
        return None


class Baseline:
    """Grandfathered findings: ``{rule, glob, justification}`` entries
    matched against a finding's rule + repo-relative path."""

    def __init__(self, entries, path=None):
        self.path = path
        self.entries = list(entries)
        self._hits = [0] * len(self.entries)
        for i, e in enumerate(self.entries):
            for k in ("rule", "glob", "justification"):
                if not str(e.get(k, "")).strip():
                    raise ValueError(
                        f"baseline entry {i} missing '{k}' "
                        f"(justification is mandatory): {e!r}")

    @classmethod
    def load(cls, path):
        data = json.loads(Path(path).read_text())
        if data.get("version") != 1:
            raise ValueError(
                f"unsupported baseline version {data.get('version')!r}")
        return cls(data.get("entries", ()), path=str(path))

    @classmethod
    def empty(cls):
        return cls(())

    def match(self, finding):
        """Justification for a baselined finding, or None."""
        for i, e in enumerate(self.entries):
            if e["rule"] != finding.rule:
                continue
            if fnmatch.fnmatch(finding.path, e["glob"]):
                self._hits[i] += 1
                return e["justification"]
        return None

    def unused_entries(self):
        """Entries that matched nothing this run — stale once the code
        they grandfathered is fixed; the CLI reports them so the file
        shrinks instead of rotting."""
        return [e for e, n in zip(self.entries, self._hits) if n == 0]


@dataclass
class Report:
    """One lint run: every finding (with status resolved), per-status
    partitions, and the inputs that shaped the run."""
    findings: list = field(default_factory=list)
    stale_baseline: list = field(default_factory=list)
    n_modules: int = 0

    @property
    def open(self):
        return [f for f in self.findings if f.status == "open"]

    @property
    def suppressed(self):
        return [f for f in self.findings if f.status == "suppressed"]

    @property
    def baselined(self):
        return [f for f in self.findings if f.status == "baselined"]

    @property
    def ok(self):
        return not self.open

    def to_dict(self):
        return {
            "ok": self.ok,
            "modules": self.n_modules,
            "open": len(self.open),
            "suppressed": len(self.suppressed),
            "baselined": len(self.baselined),
            "stale_baseline_entries": self.stale_baseline,
            "findings": [f.to_dict() for f in self.findings],
        }


class ProjectContext:
    """What project-level rule hooks see: every parsed module plus the
    repo root (for non-Python artifacts like README.md)."""

    def __init__(self, root, modules):
        self.root = Path(root)
        self.modules = modules


def default_rules():
    from . import envknobs, hostsync, precision, telemetrykinds, tracerflow

    rules = []
    for mod in (hostsync, tracerflow, precision, envknobs, telemetrykinds):
        rules.extend(mod.RULES)
    return rules


def rule_names(rules):
    return {r.name for r in rules} | {"all", "bad-suppression",
                                      "parse-error"}


def iter_sources(root, targets=DEFAULT_TARGETS):
    """Yield (abs_path, rel_posix) for the lintable Python surface."""
    root = Path(root)
    for target in targets:
        p = root / target
        if p.is_file():
            yield p, Path(target).as_posix()
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if EXCLUDE_PARTS.intersection(f.parts):
                    continue
                yield f, f.relative_to(root).as_posix()


def load_modules(root, targets=DEFAULT_TARGETS):
    """Parse the lint surface; a syntax error becomes a finding, not a
    crash (the linter must never take the build down harder than the
    interpreter would)."""
    modules, findings = [], []
    for path, rel in iter_sources(root, targets):
        try:
            source = path.read_text()
            modules.append(Module(path, rel, source))
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(Finding(
                rule="parse-error", path=rel,
                line=getattr(e, "lineno", 1) or 1,
                message=f"cannot parse: {e}"))
    return modules, findings


def run(root, baseline=None, rules=None, targets=DEFAULT_TARGETS):
    """Run the lint pass over ``root``; returns a :class:`Report`."""
    rules = list(default_rules() if rules is None else rules)
    if baseline is None:
        bl_path = Path(root) / BASELINE_NAME
        baseline = (Baseline.load(bl_path) if bl_path.exists()
                    else Baseline.empty())
    known = rule_names(rules)

    modules, findings = load_modules(root, targets)
    for m in modules:
        for line, (names, reason) in sorted(m.suppressions.items()):
            unknown = names - known
            if unknown:
                findings.append(Finding(
                    rule="bad-suppression", path=m.rel, line=line,
                    message=f"suppression names unknown rule(s) "
                            f"{sorted(unknown)}"))
            if not reason:
                findings.append(Finding(
                    rule="bad-suppression", path=m.rel, line=line,
                    message="suppression without a reason (write "
                            "'graftlint: disable=<rule> -- <why>')"))
        for rule in rules:
            if rule.check is None:
                continue
            findings.extend(rule.check(m))

    ctx = ProjectContext(root, modules)
    for rule in rules:
        if rule.project is not None:
            findings.extend(rule.project(ctx))

    by_module = {m.rel: m for m in modules}
    for f in findings:
        m = by_module.get(f.path)
        if m is not None and f.rule != "bad-suppression":
            reason = m.suppressed(f.rule, f.line)
            if reason is not None:
                f.status = "suppressed"
                f.justification = reason
                continue
        just = baseline.match(f)
        if just is not None:
            f.status = "baselined"
            f.justification = just

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(findings=findings,
                  stale_baseline=baseline.unused_entries(),
                  n_modules=len(modules))


def emit_events(report, tele):
    """Forward a report's findings as ``lint`` telemetry events."""
    for f in report.findings:
        tele.emit("lint", rule=f.rule, path=f.path, line=f.line,
                  status=f.status, severity=f.severity,
                  message=f.message)


def render_text(report):
    """Human-readable report text (the CLI's default output)."""
    out = []
    for f in report.open:
        out.append(f"{f.location}: {f.rule}: {f.message}")
    out.append(f"graftlint: {report.n_modules} modules, "
               f"{len(report.open)} open, "
               f"{len(report.suppressed)} suppressed, "
               f"{len(report.baselined)} baselined")
    for e in report.stale_baseline:
        out.append(f"stale baseline entry (matched nothing): "
                   f"{e['rule']} @ {e['glob']}")
    return "\n".join(out)
