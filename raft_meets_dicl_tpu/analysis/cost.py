"""graftcost: static per-op cost model over lowered StableHLO.

``analysis.hlo`` audits *hazard presence* (fingerprints, collectives,
f32 convs); this module puts *numbers* on a program — per-op-class
FLOPs, HBM bytes, arithmetic intensity — and classifies every dot/conv
against the measured TPU cost structure of PERF.md:

- **MXU tile waste** — the MXU consumes (8, 128)-shaped register tiles;
  a dot whose matrix dims don't fill them pays for the padding. The
  flagship's windowed-lookup einsums are the canonical case: a
  (9, H2)×(H2, W2) contraction uses ~15% of the tiles it occupies
  ("a 9-row operand uses 9/128 of the systolic array", PERF.md), which
  is why the lookup is *shape*-bound, not FLOP-bound. Ops below
  ``TILE_OK`` utilization get verdict ``shape-bound``; well-tiled
  dots/convs get ``mxu-bound``; everything else is ``memory-bound``.
- **f32 upcast surfaces** — a bf16-policy program whose dots/convs
  produce f32 results lost its policy between Flax and XLA: 2× the
  matching-volume HBM and half the MXU rate, silently.
- **gather scalarization** — XLA:TPU scalarizes *strip-sliced* gathers
  (slice extent between 1 and the full dim): the measured 23×
  ``lax.gather`` cliff vs ``take_along_axis`` rows (PERF.md). Row
  gathers (all-1 slices) and whole-dim slices are fine.

The walker is deterministic over the canonical StableHLO text (the
fingerprint-stability audit pins exactly that), so its FLOP/byte totals
can be *pinned* per ProgramKey in ``hlo-budget.json`` and enforced on
CPU in tier-1 with zero TPU time: a refactor that silently doubles a
program's reduction bytes, regrows an f32 surface, or adds a strip
gather turns the gate red before any TPU run pays for it.

Where the backend provides ``Compiled.cost_analysis()`` /
``memory_analysis()`` their totals ride along in the report
(informational — backend estimates vary across XLA versions; the
*pinned* numbers are the walker's).
"""

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

from .hlo import _DTYPE_BITS, _DTYPE_BYTES
from .lint import Finding

# the MXU register tile: operands stream as (sublane=8, lane=128) tiles
TILE_SUBLANE = 8
TILE_LANE = 128
# minimum tile utilization for a dot/conv to count as well-shaped
TILE_OK = 0.5
# hazard noise floor: a shape-bound op only counts as tile *waste* when
# it carries a visible share of the program's FLOPs
TILE_WASTE_FLOP_SHARE = 0.01

BUDGET_NAME = "hlo-budget.json"

_TENSOR_RE = re.compile(r"tensor<(?:([0-9][0-9x]*)x)?([a-z][a-z0-9]*)>")
_OP_RE = re.compile(r"=\s*\"?stablehlo\.([a-z0-9_]+)\"?")
_DIMS_PAIR_RE = re.compile(
    r"{}\s*=\s*\[([0-9,\s]*)\]\s*x\s*\[([0-9,\s]*)\]")
_SLICE_SIZES_RE = re.compile(r"slice_sizes\s*=\s*array<i64:\s*([0-9,\s]*)>")
_KERNEL_SPEC_RE = re.compile(r"x\[([^\]]*)\]->")

_CLASS = {
    "dot_general": "dot",
    "dot": "dot",
    "convolution": "conv",
    "gather": "gather",
    "scatter": "gather",
    "dynamic_slice": "gather",
    "dynamic_update_slice": "gather",
    "reduce": "reduce",
    "reduce_window": "reduce",
}

# structural ops that move no tensor data worth accounting
_SKIP = {"return", "func", "constant", "iota", "tuple", "get_tuple_element",
         "optimization_barrier", "custom_call", "partition_id",
         "replica_id", "after_all"}


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _parse_tensor(m):
    dims = tuple(int(d) for d in m.group(1).split("x")) if m.group(1) else ()  # graftlint: disable=host-sync -- parses a StableHLO tensor type, not a device value
    return dims, m.group(2)


def _tensor_nbytes(dims, dtype):
    """Bytes of one ``tensor<dims x dtype>``.

    Sub-f32 element widths count at their true size — the u8/i8 volumes
    of the quantized matching tier, f8 formats, packed sub-byte ints
    (rounded up per tensor) — never at the 4-byte fallback, which is
    reserved for genuinely unknown dtypes. Charging a quantized operand
    4 B would erase exactly the HBM-traffic saving the quant tier is
    pinned to demonstrate.
    """
    if dtype in _DTYPE_BITS:
        return (_prod(dims) * _DTYPE_BITS[dtype] + 7) // 8
    return _prod(dims) * _DTYPE_BYTES.get(dtype, 4)


def _pad(n, to):
    return ((n + to - 1) // to) * to or to


def tile_utilization(m, k, n):
    """Fraction of the streamed (8, 128) MXU register tiles an
    (M, K) × (K, N) contraction actually fills — the smaller of the two
    operand utilizations (the worse operand stalls the array)."""
    u_lhs = (m * k) / (_pad(m, TILE_SUBLANE) * _pad(k, TILE_LANE))
    u_rhs = (k * n) / (_pad(k, TILE_SUBLANE) * _pad(n, TILE_LANE))
    return min(u_lhs, u_rhs)


def _int_list(text):
    return [int(p) for p in text.replace(" ", "").split(",") if p]  # graftlint: disable=host-sync -- parses attribute text, not a device value


@dataclass
class OpCost:
    """Cost estimate for one StableHLO op instance."""
    op: str
    klass: str       # dot | conv | gather | reduce | elementwise
    line: int        # 1-based line in the module text
    flops: int
    bytes: int
    result_dtype: str
    mkn: tuple = None        # (M, K, N) for dot/conv
    tile_util: float = None  # dot/conv only
    verdict: str = "memory-bound"
    hazards: tuple = ()      # hazard tags this op instance triggers

    def to_dict(self):
        d = {"op": self.op, "class": self.klass, "line": self.line,
             "flops": self.flops, "bytes": self.bytes,
             "dtype": self.result_dtype, "verdict": self.verdict}
        if self.mkn is not None:
            d["mkn"] = list(self.mkn)
        if self.tile_util is not None:
            d["tile_util"] = round(self.tile_util, 4)
        if self.hazards:
            d["hazards"] = list(self.hazards)
        return d


def _line_types(line):
    """(operand_types, result_types) for one op line, each a list of
    (dims, dtype). Handles both ``: (a, b) -> r`` and the elementwise
    ``: tensor<...>`` form (operands and result share the type)."""
    _, sep, sig = line.rpartition(" : ")
    if not sep:
        return [], []
    if "->" in sig:
        opnds, _, res = sig.rpartition("->")
        return ([_parse_tensor(m) for m in _TENSOR_RE.finditer(opnds)],
                [_parse_tensor(m) for m in _TENSOR_RE.finditer(res)])
    types = [_parse_tensor(m) for m in _TENSOR_RE.finditer(sig)]
    # elementwise form: every operand and the result share one type;
    # approximate operands as two reads of it (add/mul arity)
    return types * 2, types


def _dot_cost(line, operands, results):
    lhs = operands[0][0] if operands else ()
    rhs = operands[1][0] if len(operands) > 1 else ()
    m_c = _DIMS_PAIR_RE.pattern  # noqa: F841 - doc anchor
    c = re.search(r"contracting_dims\s*=\s*\[([0-9,\s]*)\]\s*x", line)
    b = re.search(r"batching_dims\s*=\s*\[([0-9,\s]*)\]\s*x", line)
    contract = _int_list(c.group(1)) if c else []
    batching = _int_list(b.group(1)) if b else []
    k = _prod(lhs[d] for d in contract) if lhs else 1
    bsz = _prod(lhs[d] for d in batching) if lhs else 1
    m = _prod(lhs) // max(1, bsz * k)
    n = _prod(rhs) // max(1, bsz * k) if rhs else 1
    return 2 * bsz * m * k * n, (m, k, n)


def _conv_cost(line, operands, results):
    kernel = operands[1][0] if len(operands) > 1 else ()
    out = results[0][0] if results else ()
    co = 1
    spec = _KERNEL_SPEC_RE.search(line)
    if spec and kernel:
        parts = [p.strip() for p in spec.group(1).split(",")]
        if "o" in parts and parts.index("o") < len(kernel):
            co = kernel[parts.index("o")]
    k = _prod(kernel) // max(1, co)
    m = _prod(out) // max(1, co)
    return 2 * m * k * co, (m, k, co)


def _gather_hazard(line, operands):
    """Strip-sliced gather: any slice extent strictly between 1 and the
    full operand dim — the scalarization cliff."""
    m = _SLICE_SIZES_RE.search(line)
    if not m or not operands:
        return False
    sizes = _int_list(m.group(1))
    dims = operands[0][0]
    for s, d in zip(sizes, dims):
        if 1 < s < d:
            return True
    return False


def op_costs(text, expect_bf16=False):
    """Walk a lowered StableHLO module's text into per-op cost records.

    Purely textual (no jax import): deterministic over the
    location-stripped canonical text the fingerprint audit pins.
    """
    ops = []
    for lineno, line in enumerate(text.splitlines(), 1):
        m = _OP_RE.search(line)
        if not m:
            continue
        name = m.group(1)
        if name in _SKIP:
            continue
        operands, results = _line_types(line)
        if not results:
            continue
        rbytes = sum(_tensor_nbytes(d, t) for d, t in results)
        obytes = sum(_tensor_nbytes(d, t) for d, t in operands)
        rdtype = results[0][1]
        klass = _CLASS.get(name, "elementwise")

        flops = 0
        mkn = None
        util = None
        hazards = []
        if klass == "dot":
            flops, mkn = _dot_cost(line, operands, results)
        elif klass == "conv":
            flops, mkn = _conv_cost(line, operands, results)
        elif klass == "reduce":
            flops = _prod(operands[0][0]) if operands else 0
        elif klass == "elementwise":
            flops = _prod(results[0][0])

        if mkn is not None:
            util = tile_utilization(*mkn)
            verdict = "mxu-bound" if util >= TILE_OK else "shape-bound"
            if expect_bf16 and rdtype == "f32":
                hazards.append("f32-upcast")
        else:
            verdict = "memory-bound"
        if name == "gather" and _gather_hazard(line, operands):
            hazards.append("gather-scalarization")

        ops.append(OpCost(op=name, klass=klass, line=lineno, flops=flops,
                          bytes=obytes + rbytes, result_dtype=rdtype,
                          mkn=mkn, tile_util=util, verdict=verdict,
                          hazards=tuple(hazards)))
    return ops


def summarize(ops):
    """Per-class aggregates + hazard counts over one program's ops.

    The ``mxu-tile-waste`` hazard is resolved here (not per-op): a
    shape-bound dot/conv only counts as *waste* when it carries at least
    ``TILE_WASTE_FLOP_SHARE`` of the program's FLOPs — a handful of tiny
    setup contractions isn't the hazard; the lookup running 4×12 times a
    step is.
    """
    total_flops = sum(o.flops for o in ops)
    total_bytes = sum(o.bytes for o in ops)
    classes = {}
    verdicts = {}
    hazards = {"mxu-tile-waste": 0, "f32-upcast": 0,
               "gather-scalarization": 0}
    for o in ops:
        c = classes.setdefault(o.klass, {"ops": 0, "flops": 0, "bytes": 0})
        c["ops"] += 1
        c["flops"] += o.flops
        c["bytes"] += o.bytes
        verdicts[o.verdict] = verdicts.get(o.verdict, 0) + 1
        for h in o.hazards:
            hazards[h] = hazards.get(h, 0) + 1
        if o.verdict == "shape-bound" and total_flops and \
                o.flops >= TILE_WASTE_FLOP_SHARE * total_flops:
            hazards["mxu-tile-waste"] += 1
    for c in classes.values():
        c["intensity"] = round(c["flops"] / c["bytes"], 3) if c["bytes"] \
            else 0.0
    return {
        "flops": total_flops,
        "bytes": total_bytes,
        "intensity": round(total_flops / total_bytes, 3) if total_bytes
        else 0.0,
        "classes": classes,
        "verdicts": verdicts,
        "hazards": {k: v for k, v in hazards.items() if v},
    }


def backend_analysis(compiled):
    """Totals from the backend's own cost/memory analyses, where it
    provides them (informational; never pinned — XLA's estimates move
    across versions, the walker's don't)."""
    out = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            out["backend_flops"] = int(ca.get("flops", 0))
            out["backend_bytes"] = int(ca.get("bytes accessed", 0))
    except Exception:  # noqa: BLE001 - optional backend surface
        pass
    try:
        ma = compiled.memory_analysis()
        out["peak_temp_bytes"] = int(ma.temp_size_in_bytes)
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
    except Exception:  # noqa: BLE001 - optional backend surface
        pass
    return out


def program_cost(program, args, expect_bf16=False, n_devices=1,
                 partitioner=None, params=None, kind=None,
                 do_compile=True, **hlo_context):
    """Full static cost report for one registered program.

    Returns ``(report, findings)`` — findings here are the *contract*
    violations (collective schedule vs the partitioner-derived
    expectation, via ``analysis.collectives``); budget drift is judged
    separately by :class:`Budget` so one audit pass can serve both the
    gate and ``--update`` re-pinning.

    ``hlo_context`` (``expect_gather``) is accepted and unused — the
    ``hlo`` builders return one shared entry list whose audit kwargs
    serve both auditors.
    """
    from . import collectives
    from .hlo import strip_locations

    key = program.key.canonical() if program.key else program.label
    lowered = program.lower(*args)
    text = strip_locations(lowered.as_text())
    ops = op_costs(text, expect_bf16=expect_bf16)
    report = {
        "key": key,
        "label": program.label,
        "kind": kind or (program.key.kind if program.key else "?"),
        "n_devices": n_devices,
        **summarize(ops),
        "ops": [o.to_dict() for o in ops
                if o.hazards or o.klass in ("dot", "conv")],
    }

    findings = []
    if do_compile:
        compiled = lowered.compile()
        report.update(backend_analysis(compiled))
        schedule = collectives.parse_schedule(compiled.as_text())
        summary = collectives.summarize_schedule(schedule)
        report["collectives"] = summary
        expectation = collectives.expected_schedule(
            kind=report["kind"], n_devices=n_devices,
            partitioner=partitioner, params=params)
        findings.extend(collectives.diff(expectation, summary, key=key))
        report["expected_collectives"] = expectation.to_dict()
    return report, findings


# -- pinned budgets -----------------------------------------------------------

DEFAULT_TOLERANCE = {"flops": 0.05, "bytes": 0.08, "collective_bytes": 0.02}


class Budget:
    """Per-ProgramKey pinned cost budgets, ``graftlint-baseline.json``
    discipline: every entry is exact numbers + tolerances, entries that
    match no audited program are reported stale, programs with no entry
    fail the gate (a new program must be pinned deliberately via
    ``scripts/graftcost.py --update``)."""

    VERSION = 1

    def __init__(self, data=None, path=None):
        data = data or {}
        if data and data.get("version", self.VERSION) != self.VERSION:
            raise ValueError(
                f"unsupported budget version {data.get('version')!r}")
        self.path = path
        self.comment = data.get("comment", "")
        self.tolerance = {**DEFAULT_TOLERANCE, **data.get("tolerance", {})}
        self.entries = dict(data.get("entries", {}))
        self._hits = {k: 0 for k in self.entries}

    @classmethod
    def load(cls, path):
        return cls(json.loads(Path(path).read_text()), path=str(path))

    @classmethod
    def empty(cls):
        return cls()

    def unused_entries(self):
        """Pinned keys no audited program produced this run — stale the
        moment a program family is renamed or removed; ``--update``
        drops them so the file tracks the registry instead of rotting."""
        return [k for k, n in self._hits.items() if n == 0]

    def _drift(self, name, actual, pinned, key, findings):
        tol = self.tolerance.get(name, 0.0)
        lo, hi = pinned * (1 - tol), pinned * (1 + tol)
        if not (lo <= actual <= hi):
            rel = (actual - pinned) / pinned if pinned else float("inf")
            findings.append(Finding(
                rule="cost-budget", path="analysis/cost", line=1,
                message=f"{key}: {name} {actual:,} vs pinned {pinned:,} "
                        f"({rel:+.1%}, tolerance ±{tol:.0%}) — re-pin "
                        f"deliberately with scripts/graftcost.py --update "
                        f"if the change is intended"))

    def check(self, report):
        """Findings for one program report against its pinned entry."""
        key = report["key"]
        entry = self.entries.get(key)
        findings = []
        if entry is None:
            findings.append(Finding(
                rule="cost-unpinned", path="analysis/cost", line=1,
                message=f"{key}: program has no pinned budget entry in "
                        f"{self.path or BUDGET_NAME}; pin it with "
                        f"scripts/graftcost.py --update"))
            return findings
        self._hits[key] += 1
        self._drift("flops", report["flops"], entry["flops"], key, findings)
        self._drift("bytes", report["bytes"], entry["bytes"], key, findings)
        actual_cb = report.get("collectives", {}).get("total_bytes", 0)
        self._drift("collective_bytes", actual_cb,
                    entry.get("collective_bytes", 0), key, findings)
        pinned_h = entry.get("hazards", {})
        for name, n in sorted(report.get("hazards", {}).items()):
            if n > pinned_h.get(name, 0):
                findings.append(Finding(
                    rule="cost-hazard", path="analysis/cost", line=1,
                    message=f"{key}: {n} {name} hazard(s) vs "
                            f"{pinned_h.get(name, 0)} grandfathered — a "
                            f"new TPU hazard class grew into this "
                            f"program"))
        # resharding ops are grandfathered per pinned count (the healthy
        # flagship legitimately carries a few boundary permutes and the
        # image-pair concat's all-to-alls);
        # only growth beyond the pin flags
        from .collectives import RESHARD_OPS
        pinned_c = entry.get("collectives", {})
        actual_c = report.get("collectives", {}).get("counts", {})
        for op in RESHARD_OPS:
            if actual_c.get(op, 0) > pinned_c.get(op, 0):
                findings.append(Finding(
                    rule="collective-reshard", path="analysis/cost",
                    line=1,
                    message=f"{key}: {actual_c.get(op, 0)} {op} op(s) vs "
                            f"{pinned_c.get(op, 0)} pinned — the "
                            f"partitioner is "
                            f"resharding an activation the contract "
                            f"never asks to move; a sharding constraint "
                            f"disagrees with its neighbours"))
        return findings

    @staticmethod
    def entry_for(report):
        entry = {
            "flops": report["flops"],
            "bytes": report["bytes"],
            "collective_bytes": report.get("collectives", {}).get(
                "total_bytes", 0),
            "collectives": report.get("collectives", {}).get("counts", {}),
            "verdicts": report.get("verdicts", {}),
        }
        if report.get("hazards"):
            entry["hazards"] = dict(report["hazards"])
        return entry

    def pinned_data(self, reports):
        """The re-pinned budget payload for ``--update``: one entry per
        audited program, header comment and tolerances preserved."""
        return {
            "version": self.VERSION,
            "comment": self.comment or (
                "Pinned per-program static cost budgets "
                "(scripts/graftcost.py). flops/bytes are the "
                "deterministic StableHLO-walker totals, "
                "collective_bytes the compiled post-partitioner schedule "
                "volume. Re-pin deliberately with --update; stale "
                "entries are reported so this file tracks the program "
                "registry."),
            "tolerance": dict(self.tolerance),
            "programs": len(reports),
            "entries": {r["key"]: self.entry_for(r) for r in reports},
        }


@dataclass
class CostReport:
    """One graftcost run over the audited program set."""
    reports: list = field(default_factory=list)
    findings: list = field(default_factory=list)
    stale: list = field(default_factory=list)

    @property
    def ok(self):
        return not self.findings

    def to_dict(self):
        return {
            "ok": self.ok,
            "programs": len(self.reports),
            "findings": [f.to_dict() for f in self.findings],
            "stale_budget_entries": list(self.stale),
            "reports": self.reports,
        }


def build_entries(include_mesh2d=True, shape=(48, 64)):
    """The audited program set: the flagship tiny-shape train/eval pair,
    the (4, 2)-mesh ZeRO SPMD variant (8 virtual devices), every
    iteration-ladder rung, the video warm-start variant, the quantized
    matching-tier variants (u8/i8 base rung + u8 warm), and the
    on-device data-engine pair (augmented train step + synth renderer)
    — exactly the programs ``hlo-budget.json`` pins."""
    import jax

    from . import hlo

    entries = list(hlo.build_flagship_programs(n_devices=2, shape=shape))
    if include_mesh2d and jax.device_count() >= 8:
        entries += hlo.build_flagship_programs(n_devices=8, shape=shape,
                                               mesh2d=True)
    entries += hlo.build_ladder_programs()
    entries += hlo.build_warm_programs()
    entries += hlo.build_quant_programs()
    entries += hlo.build_aug_programs()
    return entries


def audit_costs(entries=None, budget=None, **build_kwargs):
    """Run the cost model + collective audit + budget gate over every
    entry (defaults to :func:`build_entries`). Returns a
    :class:`CostReport`."""
    if entries is None:
        entries = build_entries(**build_kwargs)
    if budget is None:
        budget = Budget.empty()
    out = CostReport()
    for program, args, kwargs in entries:
        report, findings = program_cost(program, args, **kwargs)
        out.reports.append(report)
        out.findings.extend(findings)
        if budget.entries or budget.path:
            out.findings.extend(budget.check(report))
    # stale pins are reported, not findings: a shrunk program set should
    # prompt an --update, not break the build (graftlint's stale-entry
    # discipline)
    out.stale = budget.unused_entries()
    return out


def emit_events(cost_report, tele):
    """Forward per-program cost summaries as ``cost`` telemetry."""
    for r in cost_report.reports:
        tele.emit(
            "cost", program=r["key"], program_kind=r["kind"],
            flops=r["flops"],
            bytes=r["bytes"], intensity=r["intensity"],
            collective_bytes=r.get("collectives", {}).get("total_bytes", 0),
            verdicts=r.get("verdicts", {}),
            hazards=r.get("hazards", {}))


def render_reports(cost_report):
    """Human-readable "program costs" section (CLI + telemetry_report)."""
    out = ["== program costs =="]
    for r in cost_report.reports:
        coll = r.get("collectives", {})
        verd = ", ".join(f"{k}={v}" for k, v in
                         sorted(r.get("verdicts", {}).items())) or "-"
        haz = ", ".join(f"{k}={v}" for k, v in
                        sorted(r.get("hazards", {}).items()))
        out.append(
            f"{r['key']}: {r['flops'] / 1e6:.1f} MFLOP, "
            f"{r['bytes'] / 2 ** 20:.1f} MiB, intensity "
            f"{r['intensity']:.1f} flop/B, collectives "
            f"{coll.get('total_bytes', 0) / 2 ** 20:.2f} MiB "
            f"[{verd}]" + (f" hazards: {haz}" if haz else ""))
    for f in cost_report.findings:
        out.append(f"  ! {f.rule}: {f.message}")
    for key in cost_report.stale:
        out.append(f"  stale budget entry: {key}")
    return "\n".join(out)
