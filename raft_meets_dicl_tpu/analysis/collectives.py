"""Sharding-contract auditor: expected vs actual collective schedule.

The PR-6 ZeRO execution model is a *contract*: params stored sharded are
**all-gathered** once per step for the pure data-parallel
forward/backward, then gradients are **reduced** back onto the param
shards for the shard-local optimizer update. ``analysis.hlo`` already
checks the gather/reduce pair *exists*; this module derives the full
expected schedule — which phases, in what order, moving how many bytes —
from the :class:`parallel.partition.Partitioner` rules + the actual
parameter tree, and diffs it against the collective sequence the SPMD
partitioner (Shardy in the installed jax; the thresholds below were
first measured under GSPMD) really emitted into the compiled HLO.

What the diff catches, each with a prior in this repo's history:

- **collective-missing** — a partition rule stops matching (module
  rename, regex typo) and the param gather silently disappears: params
  replicate again and the per-chip HBM win evaporates with no error.
  Detected by *volume collapse*, not mere absence: even a fully
  replicated program carries a few incidental small all-gathers
  (boundary handling on the batch-sharded spatial ops — measured on the
  flagship), so the check is "actual gather volume fell below half the
  sharded-parameter mass". Symmetrically, a vanished grad reduce means
  shards silently diverge.
- **collective-doubled** — PR 6 paid for a GSPMD miscompile that
  reduced gradients *twice* (double-counted all-reduce); actual reduce
  bytes ≫ the parameter mass is exactly that signature.
- **collective-order** — a gather scheduled after the reduces it feeds
  means the program is no longer the gather-compute form at all.

Two drift classes deliberately live in the *pinned budget*
(``analysis.cost.Budget``), not here: byte growth within the contract,
and resharding-op growth (``all-to-all``/``collective-permute``). The
healthy flagship programs legitimately contain a handful of permutes
(halo/boundary movement on batch-sharded spatial ops) and, under
Shardy, three all-to-alls: the encoder concatenates the image pair
along the batch axis, which is the sharded one (GSPMD moved the same
bytes as a pad + all-reduce). So "any reshard is a bug" would be red on
day one; "more than the pinned count" is the actionable signal.
"""

import re
from dataclasses import dataclass, field

from .lint import Finding

# one compiled-HLO collective op line, e.g.
#   %all-gather.3 = f32[16,64]{0,1} all-gather(f32[2,64]{0,1} %p), ...
# async "-start" forms return a tuple whose last element is the output;
# "-done" lines just unwrap it and are skipped to avoid double counting.
_COLL_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
    # sub-f32 widths (compiled-HLO spellings): quantized-tier volumes
    # and f8 recipes must not fall through to the 4-byte unknown default
    "f8e4m3": 1, "f8e3m4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1, "f8e8m0fnu": 1,
}

# sub-byte element widths in bits; byte counts round up per shape
_DTYPE_BITS = {"s4": 4, "u4": 4, "s2": 2, "u2": 2}

REDUCE_OPS = ("all-reduce", "reduce-scatter")
RESHARD_OPS = ("all-to-all", "collective-permute")

# doubled-reduction threshold: actual reduce volume this many times the
# expected gradient mass flags the PR-6 double-reduce signature. The
# slack absorbs the legitimate small extras (global-norm scalars, loss
# metrics, counter syncs) riding the same schedule — measured 1.27x on
# the healthy (4, 2)-mesh flagship train step.
DOUBLED_FACTOR = 1.8

# gather-collapse threshold: the param all-gather phase counts as
# *missing* when its actual volume falls below this fraction of the
# sharded-parameter mass (incidental boundary gathers survive even in a
# fully replicated program, so absence alone is not the signal; the
# healthy sharded step runs at ~1.1x expected)
GATHER_COLLAPSE = 0.5


def _shape_bytes(dtype, dims):
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)  # graftlint: disable=host-sync -- parses an HLO shape string, not a device value
    if dtype in _DTYPE_BITS:
        return (n * _DTYPE_BITS[dtype] + 7) // 8
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveOp:
    """One collective instruction in compiled-HLO schedule order."""
    op: str
    index: int   # position in the schedule (line order)
    bytes: int   # result buffer volume (output element of async tuples)

    def to_dict(self):
        return {"op": self.op, "index": self.index, "bytes": self.bytes}


def _tuple_elements(result):
    """Top-level elements of an HLO result type: ``(a, (b, c))`` gives
    ``["a", "(b, c)"]``, a bare shape gives itself. Commas inside
    dimension lists and layouts do not split."""
    result = result.strip()
    if not result.startswith("("):
        return [result]
    elements, depth, start = [], 0, 1
    for i, ch in enumerate(result):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                elements.append(result[start:i])
                break
        elif ch == "," and depth == 1:
            elements.append(result[start:i])
            start = i + 1
    return [e.strip() for e in elements if e.strip()]


def _result_bytes(op, suffix, result):
    """Output volume of one collective from its result type.

    The combiner passes merge neighbouring collectives into one variadic
    op whose result is the tuple of every output — the flagship's 174
    gradient all-reduces arrive as a handful of tuple-valued ones — so
    every element counts. The async ``all-gather-start`` and
    ``collective-permute-start`` differ: their tuple is (operands,
    outputs, context...), of which only the second element is output.
    """
    if suffix == "-start" and op in ("all-gather", "collective-permute"):
        elements = _tuple_elements(result)
        result = elements[1] if len(elements) > 1 else elements[0]
    return sum(_shape_bytes(*shape) for shape in _SHAPE_RE.findall(result))


def parse_schedule(text):
    """Collective ops of a compiled (post-partitioner) HLO module, in
    schedule order, each with its output byte volume.

    The result type precedes the op name on an HLO instruction line
    (:func:`_result_bytes` reads it); ``-done`` lines are skipped — they
    unwrap a start op already counted.
    """
    ops = []
    for line in text.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        m = _COLL_OP_RE.search(rhs)
        if not m or m.group(2) == "-done":
            continue
        ops.append(CollectiveOp(
            op=m.group(1), index=len(ops),
            bytes=_result_bytes(m.group(1), m.group(2), rhs[:m.start()])))
    return ops


def summarize_schedule(schedule):
    counts, volumes = {}, {}
    for op in schedule:
        counts[op.op] = counts.get(op.op, 0) + 1
        volumes[op.op] = volumes.get(op.op, 0) + op.bytes
    return {
        "counts": counts,
        "bytes": volumes,
        "total_bytes": sum(volumes.values()),
        "order": [op.op for op in schedule],
    }


@dataclass
class Expectation:
    """The collective schedule the sharding contract implies."""
    kind: str
    n_devices: int
    phases: tuple = ()       # ordered phase names: "all-gather", "reduce"
    gather_bytes: int = 0    # full bytes of rule-sharded params
    reduce_bytes: int = 0    # gradient mass (total param bytes)
    sharded_leaves: int = 0
    notes: list = field(default_factory=list)

    def to_dict(self):
        return {"kind": self.kind, "n_devices": self.n_devices,
                "phases": list(self.phases),
                "gather_bytes": self.gather_bytes,
                "reduce_bytes": self.reduce_bytes,
                "sharded_leaves": self.sharded_leaves}


def expected_schedule(kind, n_devices, partitioner=None, params=None):
    """Derive the expected schedule from the partitioner rules + the
    actual parameter tree.

    - a rule-sharded param tree ⇒ one **all-gather** phase whose volume
      is the *full* bytes of every sharded leaf (the gathered output —
      the transient params-sized buffer the execution model budgets);
    - any multi-device ``train_step`` ⇒ one **reduce** phase (all-reduce
      or reduce-scatter) whose volume is the gradient mass ≈ total param
      bytes;
    - eval / single-device programs ⇒ no collectives at all.
    """
    import jax

    exp = Expectation(kind=kind, n_devices=n_devices)
    if n_devices <= 1:
        return exp

    phases = []
    if partitioner is not None and params is not None:
        shardings = partitioner.param_shardings(params)
        for leaf, sh in zip(jax.tree.leaves(params),
                            jax.tree.leaves(shardings)):
            if tuple(sh.spec):
                exp.sharded_leaves += 1
                exp.gather_bytes += int(leaf.nbytes)
        if exp.sharded_leaves:
            phases.append("all-gather")
    if kind == "train_step":
        phases.append("reduce")
        if params is not None:
            exp.reduce_bytes = sum(int(x.nbytes)
                                   for x in jax.tree.leaves(params))
    exp.phases = tuple(phases)
    return exp


def diff(expectation, summary, key=""):
    """Structural findings: the contract's phases vs what the
    partitioner emitted.

    Operates on a :func:`summarize_schedule` dict (not the raw op list)
    so reports pinned in ``hlo-budget.json`` — which store exactly that
    summary — can be re-diffed against a fresh expectation without
    recompiling the program.
    """
    path = "analysis/collectives"
    findings = []
    counts, volumes = summary["counts"], summary["bytes"]
    order = summary.get("order", [])

    if "all-gather" in expectation.phases:
        actual = volumes.get("all-gather", 0)
        if actual < GATHER_COLLAPSE * expectation.gather_bytes:
            findings.append(Finding(
                rule="collective-missing", path=path, line=1,
                message=f"{key}: partitioner shards "
                        f"{expectation.sharded_leaves} param leaves "
                        f"({expectation.gather_bytes / 2**20:.1f} MiB) "
                        f"but the compiled schedule gathers only "
                        f"{actual / 2**20:.1f} MiB — the ZeRO param "
                        f"all-gather vanished (dead partition rule? "
                        f"dropped sharding constraint?); params are "
                        f"silently replicated again"))

    n_reduce = sum(counts.get(op, 0) for op in REDUCE_OPS)
    if "reduce" in expectation.phases and not n_reduce:
        findings.append(Finding(
            rule="collective-missing", path=path, line=1,
            message=f"{key}: multi-device train step with no gradient "
                    f"all-reduce/reduce-scatter — shards will diverge"))

    if expectation.reduce_bytes:
        actual = sum(volumes.get(op, 0) for op in REDUCE_OPS)
        if actual > DOUBLED_FACTOR * expectation.reduce_bytes:
            findings.append(Finding(
                rule="collective-doubled", path=path, line=1,
                message=f"{key}: reduce volume {actual / 2**20:.1f} MiB "
                        f"vs ~{expectation.reduce_bytes / 2**20:.1f} MiB "
                        f"gradient mass — the PR-6 doubled-reduction "
                        f"signature (a gradient is being reduced more "
                        f"than once)"))

    gathers = [i for i, op in enumerate(order) if op == "all-gather"]
    reduces = [i for i, op in enumerate(order) if op in REDUCE_OPS]
    if gathers and reduces and "all-gather" in expectation.phases \
            and min(gathers) > max(reduces):
        findings.append(Finding(
            rule="collective-order", path=path, line=1,
            message=f"{key}: first param all-gather is scheduled after "
                    f"the last gradient reduce — the program is no "
                    f"longer the gather-compute form"))

    return findings
