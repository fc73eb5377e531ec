"""Rules ``env-knob`` / ``env-docs``: the central knob registry contract.

``utils/env.py`` is the single source of truth for every ``RMD_*``
environment variable — its typed accessors are the only sanctioned read
path, and the README knob table is generated from its registry. Three
checks hold that together:

- **env-knob (module)**: a direct ``os.environ``/``os.getenv`` *read* of
  an ``RMD_*`` name anywhere outside ``utils/env.py`` (writes — fault
  injection, save/restore in tests — stay legal);
- **env-knob (project)**: every ``RMD_*`` string literal in the lint
  surface must name a registered knob (catches typos like
  ``env.get("RMD_PREFTCH")``), and every registered knob must be
  referenced somewhere (catches knobs that died in a refactor but kept
  their registry row and README line);
- **env-docs (project)**: the committed README table between the
  generation markers must match ``env.readme_table()`` byte for byte
  (``scripts/graftlint.py --fix-knob-table`` rewrites it);
- **env-dead-knob (project)**: every registered knob must be *read*
  through a typed accessor (``get``/``get_bool``/``get_int``/
  ``get_float``/``get_str``/``raw``/``is_set``/``knob``) somewhere in
  the lint surface. Stricter than the reference check above: a knob
  that tests still save/restore (a write) or a docstring still names
  stays "referenced" long after the code path that *consumed* it died
  in a refactor — registry row and README line intact, knob silently a
  no-op for every user who sets it.
"""

import ast
import re

from . import astutil
from .lint import Finding, Rule

RULE = "env-knob"
DOCS_RULE = "env-docs"
DEAD_RULE = "env-dead-knob"

# the sanctioned read surface of utils.env: a registered knob is *live*
# iff some call through one of these names passes its literal
ACCESSORS = frozenset({"get", "get_bool", "get_int", "get_float",
                       "get_str", "raw", "is_set", "knob"})

ENV_MODULE = "raft_meets_dicl_tpu/utils/env.py"
KNOB_RE = re.compile(r"^RMD_[A-Z0-9_]+$")


def _knob_literal(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and KNOB_RE.match(node.value):
        return node.value
    return None


def _environ_read_calls(tree):
    """(node, knob_name) for os.environ.get / os.getenv / environ
    subscript *reads* of RMD_* literals."""
    # subscript targets of plain assignments / deletes are writes
    write_subscripts = set()
    for node in ast.walk(tree):
        targets = ()
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = (node.target,)
        elif isinstance(node, ast.Delete):
            targets = node.targets
        for t in targets:
            if isinstance(t, ast.Subscript):
                write_subscripts.add(id(t))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dotted = astutil.dotted_name(node.func) or ""
            if dotted.endswith("environ.get") or \
                    dotted.endswith("getenv") or \
                    dotted.endswith("environ.setdefault"):
                for arg in node.args[:1]:
                    name = _knob_literal(arg)
                    if name:
                        yield node, name
        elif isinstance(node, ast.Subscript) and \
                id(node) not in write_subscripts:
            dotted = astutil.dotted_name(node.value) or ""
            if dotted.endswith("environ"):
                name = _knob_literal(node.slice)
                if name:
                    yield node, name
        elif isinstance(node, ast.Compare) and node.ops and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)):
            dotted = astutil.dotted_name(node.comparators[0]) or ""
            if dotted.endswith("environ"):
                name = _knob_literal(node.left)
                if name:
                    yield node, name


def check(module):
    if module.rel == ENV_MODULE:
        return []
    findings = []
    for node, name in _environ_read_calls(module.tree):
        findings.append(Finding(
            rule=RULE, path=module.rel, line=node.lineno,
            message=f"direct environment read of {name}; go through "
                    f"utils.env (get/get_bool/get_int/get_float/raw) "
                    f"so the knob stays registered and documented"))
    return findings


def _knobs():
    from ..utils import env
    return env


def _covers_env_module(ctx):
    """Registry-completeness and docs checks only make sense when the
    linted tree actually contains the knob registry — a fixture tree or
    a partial ``--root`` doesn't reference every knob and has no README
    table to keep honest."""
    return any(m.rel == ENV_MODULE for m in ctx.modules)


def check_project(ctx):
    if not _covers_env_module(ctx):
        return []
    env = _knobs()
    findings = []
    referenced = set()
    for m in ctx.modules:
        if m.rel == ENV_MODULE:
            continue
        for node in ast.walk(m.tree):
            name = _knob_literal(node)
            if not name:
                continue
            referenced.add(name)
            if name not in env.KNOBS:
                findings.append(Finding(
                    rule=RULE, path=m.rel, line=node.lineno,
                    message=f"unregistered knob {name}: add it to "
                            f"utils.env.KNOBS (or fix the typo)"))
    for name in sorted(set(env.KNOBS) - referenced):
        findings.append(Finding(
            rule=RULE, path=ENV_MODULE, line=1,
            message=f"stale knob {name}: registered in utils.env.KNOBS "
                    f"but referenced nowhere in the lint surface"))
    return findings


def check_dead_knobs(ctx):
    """Registered knobs no typed accessor ever reads — dead controls.

    Direct ``environ`` reads also count as live (they draw their own
    ``env-knob`` finding; double-reporting the knob as dead on top would
    punish the same line twice). The accessor match is by call-name
    suffix, deliberately loose: ``rmd_env.get_bool(...)``, ``env.raw``,
    a bare ``get_int`` after ``from ..utils.env import get_int`` all
    count. Over-matching (some unrelated ``.get("RMD_X")``) only makes
    a knob *live*, never falsely dead — the safe direction for a gate.
    """
    if not _covers_env_module(ctx):
        return []
    env = _knobs()
    read = set()
    for m in ctx.modules:
        if m.rel == ENV_MODULE:
            continue
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Call) and node.args:
                dotted = astutil.dotted_name(node.func) or ""
                if dotted.rpartition(".")[2] in ACCESSORS:
                    name = _knob_literal(node.args[0])
                    if name:
                        read.add(name)
        for _node, name in _environ_read_calls(m.tree):
            read.add(name)
    return [
        Finding(
            rule=DEAD_RULE, path=ENV_MODULE, line=1,
            message=f"dead knob {name}: registered in utils.env.KNOBS "
                    f"but never read through a typed accessor — the "
                    f"code path that consumed it is gone; drop the "
                    f"registry row (and regenerate the README table) "
                    f"or re-wire the read")
        for name in sorted(set(env.KNOBS) - read)
    ]


def check_docs(ctx):
    if not _covers_env_module(ctx):
        return []
    env = _knobs()
    readme = ctx.root / "README.md"
    if not readme.exists():
        return [Finding(rule=DOCS_RULE, path="README.md", line=1,
                        message="README.md missing")]
    text = readme.read_text()
    begin, end = text.find(env.TABLE_BEGIN), text.find(env.TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        return [Finding(
            rule=DOCS_RULE, path="README.md", line=1,
            message=f"README knob-table markers missing; add "
                    f"'{env.TABLE_BEGIN}' / '{env.TABLE_END}' and run "
                    f"scripts/graftlint.py --fix-knob-table")]
    committed = text[begin + len(env.TABLE_BEGIN):end].strip("\n")
    if committed != env.readme_table():
        line = text[:begin].count("\n") + 1
        return [Finding(
            rule=DOCS_RULE, path="README.md", line=line,
            message="README knob table is stale vs utils.env.KNOBS; "
                    "run scripts/graftlint.py --fix-knob-table")]
    return []


RULES = [
    Rule(name=RULE,
         doc="RMD_* env reads must route through utils.env; literals "
             "must name registered knobs; registered knobs must be "
             "referenced",
         check=check, project=check_project),
    Rule(name=DOCS_RULE,
         doc="README env-knob table generated from utils.env.KNOBS "
             "must not drift",
         project=check_docs),
    Rule(name=DEAD_RULE,
         doc="registered knobs must be read through a typed utils.env "
             "accessor somewhere (a knob nothing reads is a silent "
             "no-op for everyone who sets it)",
         project=check_dead_knobs),
]
