"""graftprof: measured device-time attribution over jax.profiler captures.

``analysis.cost`` (graftcost) *predicts* per-program, per-op-class
FLOP/byte totals from the lowered StableHLO; this module *measures*
them. It parses the capture directories the existing surfaces already
write (``train --profile``, ``/profilez``)
— trace-event JSON always, ``.xplane.pb`` where a TF protobuf reader is
installed — attributes device time to the PR-7 registry's programs, and
buckets every op into graftcost's op classes plus the two runtime-only
ones (collective, infeed). The product is the **calibration table**:
measured seconds vs roofline-predicted seconds per program and op
class, with the measured/predicted ratio pinned per machine in
``prof-budget.json`` and drift-gated the same way graftcost gates
FLOP/byte totals.

Two attribution modes, because module names are not unique:

- **segmented capture** (``profile_entries`` / ``audit_profiles``, the
  CLI's default): every audited program runs inside its *own* trace
  segment, so attribution is exact regardless of module naming — all
  three ladder rungs lower to ``module @jit_step`` and would be
  indistinguishable in one mixed capture. The segment manifest records
  key, fingerprint and predicted costs next to the raw trace.
- **post-hoc attribution** (``attribute_trace``, used by ``/profilez``
  and ``train --profile``): an existing unsegmented capture is
  aggregated per ``hlo_module`` and op class, and per phase of the
  model (encoders, lookup, update ...) from the ``owners`` record of
  the registered program whose instructions cover the module's
  operations (``compile/owners.py``); that program names the module.

The roofline prediction is deliberately crude (peak FLOP/s and
bandwidth per device kind, no overlap model): the *ratio* is the
calibrated quantity, pinned per machine with wide multiplicative
tolerances, so machine constants and model error cancel out of the
gate. What the gate catches is the ratio *moving* — a kernel change
that doubles measured time without touching the static cost model, the
exact regression class the static budget is blind to.
"""

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from pathlib import Path

from .lint import Finding

BUDGET_NAME = "prof-budget.json"
MANIFEST_NAME = "graftprof-manifest.json"

# graftcost's op classes plus the two that only exist at runtime
CLASSES = ("dot", "conv", "gather", "reduce", "elementwise",
           "collective", "infeed")

# measured/predicted ratios drift multiplicatively: pinned r gates
# [r / (1 + tol), r * (1 + tol)] — wide bands, the machine pin absorbs
# the roofline model's constants and only *movement* flags
DEFAULT_TOLERANCE = {"ratio": 1.5, "class_ratio": 3.0}

# per-class gating only where the class carries a visible share of the
# predicted step (tiny classes have noise-dominated ratios)
MIN_CLASS_SHARE = 0.05

# (peak FLOP/s, peak memory bytes/s) keyed by ``device_kind`` as jax
# reports it. "TPU v5 lite" is one v5e chip: 197 TFLOP/s bf16, 819 GB/s
# HBM (Google Cloud documentation, "TPU v5e"). An accelerator that is
# not listed is an error, not a default.
#
# The "cpu" row is NOT a device peak: XLA:CPU is nobody's deployment
# target. It is the order-of-magnitude constant under the ``cpu:cpu``
# calibration pins in prof-budget.json that tier-1 gates on (the pinned
# ratio absorbs the constant, see module docstring).
_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (1.0e11, 2.0e10),
}

_COLLECTIVE_TOKENS = ("all_reduce", "all_gather", "all_to_all",
                      "collective_permute", "reduce_scatter",
                      "collective_broadcast")
_GATHER_TOKENS = ("gather", "scatter", "dynamic_slice",
                  "dynamic_update_slice")
# "conv" only as a delimited token ("conv", "conv2d", "convolution...")
# — a bare substring test would claim every "convert" fusion
_CONV_RE = re.compile(r"(?<![a-z])conv(?:olution)?(?![a-z])|convolution")


class TraceError(ValueError):
    """A capture directory that cannot be attributed: no profiler
    output under it, unparseable trace JSON, or a trace with zero
    device op events (profiler ran but nothing executed)."""


def op_class(name):
    """Bucket one device-op name into graftcost's op classes.

    Works over both HLO spellings (hyphens: ``all-reduce``,
    ``dynamic-update-slice``) and StableHLO spellings (underscores),
    over fused names (``convolution_fusion``) and over instance
    suffixes (``dot.42``). Order matters: collectives before ``reduce``
    (``all-reduce``), gather tokens after collectives
    (``reduce-scatter``).
    """
    n = name.lower().lstrip("%").replace("-", "_")
    if any(t in n for t in _COLLECTIVE_TOKENS):
        return "collective"
    if "infeed" in n or "outfeed" in n:
        return "infeed"
    if _CONV_RE.search(n):
        return "conv"
    if "dot" in n or "einsum" in n:
        return "dot"
    if any(t in n for t in _GATHER_TOKENS):
        return "gather"
    if "reduce" in n:
        return "reduce"
    return "elementwise"


# -- trace parsing ------------------------------------------------------------


def find_trace_files(trace_dir, suffixes=(".trace.json.gz", ".trace.json")):
    """Every trace-event JSON file under a jax.profiler capture dir
    (``<dir>/plugins/profile/<ts>/<host>.trace.json.gz``); also accepts
    files placed directly under ``trace_dir`` (test fixtures)."""
    out = []
    for suffix in suffixes:
        out += glob.glob(f"{trace_dir}/**/*{suffix}", recursive=True)
    return sorted(set(out))


def load_trace_events(path):
    """The ``traceEvents`` list of one trace-event JSON file (.gz or
    plain). Raises :class:`TraceError` on malformed content."""
    try:
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "rt") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise TraceError(f"unreadable trace file {path}: {e}") from e
    events = data.get("traceEvents") if isinstance(data, dict) else None
    if not isinstance(events, list):
        raise TraceError(f"no traceEvents array in {path}")
    return events


def device_ops(events):
    """``(module, op, seconds)`` per device op execution.

    A device op event is a complete event (``ph == "X"``) whose args
    carry ``hlo_op`` — the XLA runtimes stamp every op execution with
    its HLO module and op name; host-side python/runtime events carry
    neither and are skipped. Durations are trace-event microseconds.
    """
    out = []
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        op = args.get("hlo_op")
        if not op:
            continue
        module = args.get("hlo_module", "?")
        out.append((module, op, float(ev.get("dur", 0)) / 1e6))  # graftlint: disable=host-sync -- trace-event microseconds, not a device value
    return out


def xplane_ops(path):
    """``(module, op, seconds)`` from an ``.xplane.pb`` — TPU/GPU
    captures where the trace JSON is absent. Requires the TF xplane
    protobuf; callers gate on :func:`have_xplane`."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xspace = xplane_pb2.XSpace()
    try:
        xspace.ParseFromString(Path(path).read_bytes())
    except Exception as e:  # noqa: BLE001 - protobuf parse errors vary
        raise TraceError(f"unreadable xplane {path}: {e}") from e

    out = []
    for plane in xspace.planes:
        if "TPU" not in plane.name and "/device:" not in plane.name:
            continue
        module = "?"
        evmeta = plane.event_metadata
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                name = evmeta[event.metadata_id].name
                # container events double-count their children
                if name.startswith(("%while", "jit_", "%tuple")):
                    continue
                out.append((module, name, event.duration_ps / 1e12))
    return out


def have_xplane():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: F401
        return True
    except Exception:  # noqa: BLE001 - tf optional, import errors vary
        return False


def collect_trace(trace_dir):
    """Parse one capture directory into device-op records.

    Returns ``{"ops": [(module, op, seconds)], "source", "files"}``.
    Prefers trace-event JSON (always written, module names included);
    falls back to ``.xplane.pb`` where the TF protobuf is importable.
    Raises :class:`TraceError` when the directory holds no capture or
    the capture holds no device ops.
    """
    trace_dir = str(trace_dir)
    files = find_trace_files(trace_dir)
    ops, source = [], "trace-json"
    for path in files:
        ops += device_ops(load_trace_events(path))
    if not ops:
        pbs = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                               recursive=True))
        if pbs and have_xplane():
            source = "xplane"
            for path in pbs:
                ops += xplane_ops(path)
            files = pbs
        elif not files and not pbs:
            raise TraceError(
                f"no profiler capture under {trace_dir} (expected "
                f"*.trace.json[.gz] or *.xplane.pb)")
    if not ops:
        raise TraceError(
            f"capture under {trace_dir} contains no device op events "
            f"(nothing executed inside the trace window?)")
    return {"ops": ops, "source": source, "files": files}


def class_seconds(ops):
    """``{class: seconds}`` rollup over ``(module, op, seconds)``."""
    out = {}
    for _, op, s in ops:
        c = op_class(op)
        out[c] = out.get(c, 0.0) + s
    return out


# -- machine + roofline -------------------------------------------------------


def machine_spec():
    """The identity + peaks of the attached accelerator; calibration
    pins are scoped per ``machine_id`` so a CPU pin never gates a TPU
    run."""
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    kind = getattr(dev, "device_kind", platform) or platform
    machine_id = f"{platform}:{kind}".lower().replace(" ", "-")
    if kind not in _PEAKS:
        raise ValueError(
            f"no peak FLOP/s / bytes/s on record for device_kind "
            f"'{kind}' (platform '{platform}'); add a row with its "
            f"source to analysis.profile._PEAKS")
    peak_flops, peak_bw = _PEAKS[kind]
    return {"machine_id": machine_id, "platform": platform,
            "device_kind": str(kind), "n_devices": jax.device_count(),
            "peak_flops": peak_flops, "peak_bytes_per_s": peak_bw}


def predicted_classes(op_cost_list, spec):
    """Re-bucket graftcost's per-op records with :func:`op_class` (so
    collectives/infeed land in their runtime classes, not elementwise)
    and roofline each class: ``max(flops/peak, bytes/bw)`` seconds."""
    classes = {}
    for o in op_cost_list:
        c = classes.setdefault(op_class(o.op),
                               {"flops": 0, "bytes": 0, "ops": 0})
        c["flops"] += o.flops
        c["bytes"] += o.bytes
        c["ops"] += 1
    for c in classes.values():
        c["seconds"] = max(c["flops"] / spec["peak_flops"],
                           c["bytes"] / spec["peak_bytes_per_s"])
    return classes


# -- segmented capture --------------------------------------------------------


def profile_entries(entries, out_dir, repeats=2):
    """Run every ``(program, args, kwargs)`` audit entry inside its own
    trace segment under ``out_dir`` and write the segment manifest.

    Per entry: lower (fingerprint + static per-class costs), one
    un-traced warmup call (compile outside the window), then
    ``repeats`` traced calls with a ``block_until_ready`` inside the
    window. Returns the manifest dict (also written to
    ``out_dir/graftprof-manifest.json``).
    """
    import jax

    from . import cost
    from .hlo import fingerprint, strip_locations

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    spec = machine_spec()
    segments = []
    for i, (program, args, kwargs) in enumerate(entries):
        key = program.key.canonical() if program.key else program.label
        text = strip_locations(program.lower(*args).as_text())
        ops = cost.op_costs(text,
                            expect_bf16=kwargs.get("expect_bf16", False))
        seg = out_dir / f"seg-{i:03d}"
        outv = program(*args)  # warmup: compile outside the window
        jax.block_until_ready(outv)  # graftlint: disable=host-sync -- profiling harness: sync fences the warmup out of the capture window
        jax.profiler.start_trace(str(seg))
        try:
            for _ in range(repeats):
                outv = program(*args)
            jax.block_until_ready(outv)  # graftlint: disable=host-sync -- profiling harness: sync closes the timed window so the trace holds all repeats
        finally:
            jax.profiler.stop_trace()
        segments.append({
            "dir": seg.name,
            "key": key,
            "label": program.label,
            "kind": kwargs.get("kind") or
            (program.key.kind if program.key else "?"),
            "fingerprint": fingerprint(text),
            "repeats": repeats,
            "predicted_classes": predicted_classes(ops, spec),
        })
    manifest = {"version": 1, "machine": spec, "segments": segments}
    (out_dir / MANIFEST_NAME).write_text(
        json.dumps(manifest, indent=2) + "\n")
    return manifest


def attribute_segments(out_dir, manifest=None):
    """Per-program measured reports from a segmented capture dir."""
    out_dir = Path(out_dir)
    if manifest is None:
        path = out_dir / MANIFEST_NAME
        if not path.exists():
            raise TraceError(f"no {MANIFEST_NAME} under {out_dir}")
        manifest = json.loads(path.read_text())
    spec = manifest["machine"]
    reports = []
    for seg in manifest["segments"]:
        collected = collect_trace(out_dir / seg["dir"])
        repeats = max(1, seg.get("repeats", 1))
        measured = {c: s / repeats
                    for c, s in class_seconds(collected["ops"]).items()}
        reports.append(_build_report(seg, measured, spec,
                                     source=collected["source"]))
    return reports


def _build_report(seg, measured_classes, spec, source):
    """One calibration-table row: measured vs predicted per class."""
    predicted = seg["predicted_classes"]
    classes = {}
    for c in sorted(set(measured_classes) | set(predicted)):
        m = measured_classes.get(c, 0.0)
        p = predicted.get(c, {}).get("seconds", 0.0)
        classes[c] = {"seconds": round(m, 6),
                      "predicted_seconds": round(p, 6)}
        if p > 0:
            classes[c]["ratio"] = round(m / p, 4)
    device_s = sum(measured_classes.values())
    predicted_s = sum(p.get("seconds", 0.0) for p in predicted.values())
    flops = sum(p.get("flops", 0) for p in predicted.values())
    nbytes = sum(p.get("bytes", 0) for p in predicted.values())
    report = {
        "key": seg["key"],
        "label": seg.get("label", seg["key"]),
        "kind": seg.get("kind", "?"),
        "fingerprint": seg.get("fingerprint"),
        "repeats": seg.get("repeats", 1),
        "source": source,
        "device_seconds": round(device_s, 6),
        "predicted_seconds": round(predicted_s, 6),
        "classes": classes,
        "flops": flops,
        "bytes": nbytes,
    }
    if predicted_s > 0:
        report["ratio"] = round(device_s / predicted_s, 4)
    if device_s > 0:
        report["achieved_flops"] = round(flops / device_s, 1)
        report["achieved_bytes_per_s"] = round(nbytes / device_s, 1)
    return report


# -- pinned calibration budget ------------------------------------------------


class ProfBudget:
    """Machine-scoped pinned calibration ratios, graftcost's ``Budget``
    discipline: unpinned program → finding, ratio outside the pinned
    multiplicative band → finding, stale pins reported (pruned by
    ``--update``). A fingerprint mismatch against the pin is *not* a
    finding — graftcost already gates the static side; here it renders
    as a stale-calibration note so a tolerated model tweak doesn't go
    red twice."""

    VERSION = 1

    def __init__(self, data=None, path=None):
        data = data or {}
        if data and data.get("version", self.VERSION) != self.VERSION:
            raise ValueError(
                f"unsupported prof-budget version {data.get('version')!r}")
        self.path = path
        self.comment = data.get("comment", "")
        self.tolerance = {**DEFAULT_TOLERANCE, **data.get("tolerance", {})}
        self.machines = {m: dict(v.get("entries", {}))
                         for m, v in data.get("machines", {}).items()}
        self._hits = {m: {k: 0 for k in e}
                      for m, e in self.machines.items()}

    @classmethod
    def load(cls, path):
        return cls(json.loads(Path(path).read_text()), path=str(path))

    @classmethod
    def empty(cls):
        return cls()

    def entries_for(self, machine_id):
        return self.machines.get(machine_id, {})

    def unused_entries(self, machine_id):
        """Pinned keys for this machine no profiled program matched."""
        return [k for k, n in self._hits.get(machine_id, {}).items()
                if n == 0]

    def _band(self, pinned, tol):
        return pinned / (1.0 + tol), pinned * (1.0 + tol)

    def check(self, report, machine_id):
        """Findings for one measured report against its machine pin."""
        key = report["key"]
        entries = self.machines.get(machine_id, {})
        entry = entries.get(key)
        findings = []
        if entry is None:
            findings.append(Finding(
                rule="prof-unpinned", path="analysis/profile", line=1,
                message=f"{key}: no pinned calibration for machine "
                        f"{machine_id} in {self.path or BUDGET_NAME}; "
                        f"pin it with scripts/graftprof.py --update"))
            return findings
        self._hits[machine_id][key] += 1
        if entry.get("fingerprint") and report.get("fingerprint") and \
                entry["fingerprint"] != report["fingerprint"]:
            # rendered as a note, not gated: the program changed since
            # the pin (graftcost's jurisdiction) — the ratio band below
            # still applies and catches real slowdowns
            report["stale_fingerprint"] = True
        ratio = report.get("ratio")
        pinned = entry.get("ratio")
        tol = self.tolerance.get("ratio", DEFAULT_TOLERANCE["ratio"])
        if ratio is not None and pinned:
            lo, hi = self._band(pinned, tol)
            if not (lo <= ratio <= hi):
                findings.append(Finding(
                    rule="prof-calibration", path="analysis/profile",
                    line=1,
                    message=f"{key}: measured/predicted ratio {ratio:.2f}"
                            f" vs pinned {pinned:.2f} on {machine_id} "
                            f"(band [{lo:.2f}, {hi:.2f}]) — re-pin "
                            f"deliberately with scripts/graftprof.py "
                            f"--update if the change is intended"))
        ctol = self.tolerance.get("class_ratio",
                                  DEFAULT_TOLERANCE["class_ratio"])
        total_pred = report.get("predicted_seconds") or 0.0
        pinned_classes = entry.get("classes", {})
        for cls, c in sorted(report.get("classes", {}).items()):
            p = pinned_classes.get(cls)
            share = (c.get("predicted_seconds", 0.0) / total_pred
                     if total_pred else 0.0)
            if p is None or "ratio" not in c or not p.get("ratio") or \
                    share < MIN_CLASS_SHARE:
                continue
            lo, hi = self._band(p["ratio"], ctol)
            if not (lo <= c["ratio"] <= hi):
                findings.append(Finding(
                    rule="prof-calibration", path="analysis/profile",
                    line=1,
                    message=f"{key}: {cls} ratio {c['ratio']:.2f} vs "
                            f"pinned {p['ratio']:.2f} on {machine_id} "
                            f"(band [{lo:.2f}, {hi:.2f}], "
                            f"{share:.0%} of predicted step)"))
        return findings

    @staticmethod
    def entry_for(report):
        entry = {
            "device_seconds": report["device_seconds"],
            "fingerprint": report.get("fingerprint"),
            "classes": {c: {k: v for k, v in d.items() if k == "ratio"}
                        for c, d in report.get("classes", {}).items()
                        if "ratio" in d},
        }
        if "ratio" in report:
            entry["ratio"] = report["ratio"]
        return entry

    def pinned_data(self, reports, machine_id):
        """The re-pinned payload for ``--update``: replaces this
        machine's entries, preserves every other machine's pins."""
        machines = {m: {"entries": e} for m, e in self.machines.items()}
        machines[machine_id] = {
            "entries": {r["key"]: self.entry_for(r) for r in reports}}
        return {
            "version": self.VERSION,
            "comment": self.comment or (
                "Pinned measured/predicted calibration ratios "
                "(scripts/graftprof.py). Scoped per machine_id — a "
                "ratio pinned on one accelerator never gates another. "
                "Tolerances are wide multiplicative bands: the roofline "
                "constants cancel in the ratio, only movement flags. "
                "Re-pin deliberately with --update."),
            "tolerance": dict(self.tolerance),
            "machines": machines,
        }


@dataclass
class ProfReport:
    """One graftprof run: measured reports + calibration findings."""
    reports: list = field(default_factory=list)
    findings: list = field(default_factory=list)
    stale: list = field(default_factory=list)
    machine: dict = field(default_factory=dict)

    @property
    def ok(self):
        return not self.findings

    def to_dict(self):
        return {
            "ok": self.ok,
            "machine": self.machine,
            "programs": len(self.reports),
            "findings": [f.to_dict() for f in self.findings],
            "stale_budget_entries": list(self.stale),
            "reports": self.reports,
        }


def audit_profiles(entries=None, budget=None, out_dir=None, repeats=2,
                   **build_kwargs):
    """Capture + attribute + gate every audit entry (defaults to
    graftcost's :func:`analysis.cost.build_entries` set, so the
    calibration table covers exactly the programs ``hlo-budget.json``
    pins). Returns a :class:`ProfReport`."""
    from . import cost

    if entries is None:
        entries = cost.build_entries(**build_kwargs)
    if budget is None:
        budget = ProfBudget.empty()
    tmp = None
    if out_dir is None:
        tmp = out_dir = tempfile.mkdtemp(prefix="rmd-graftprof-")
    try:
        manifest = profile_entries(entries, out_dir, repeats=repeats)
        out = ProfReport(machine=manifest["machine"])
        machine_id = manifest["machine"]["machine_id"]
        for report in attribute_segments(out_dir, manifest):
            out.reports.append(report)
            if budget.machines or budget.path:
                out.findings.extend(budget.check(report, machine_id))
        out.stale = budget.unused_entries(machine_id)
        return out
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


# -- post-hoc attribution (unsegmented captures) ------------------------------


def _program_records():
    """``[(program key, owners record)]`` over the live registry: what
    each executable's compiled text says of its instructions
    (``compile/owners.py``; kept by the program when the telemetry sink
    is on)."""
    from ..compile.registry import registry as program_registry

    out = []
    for prog in program_registry().programs():
        key = prog.key.canonical() if prog.key else prog.label
        out += [(key, rec) for rec in getattr(prog, "owners", {}).values()]
    return out


def _op_name(op):
    """An operation's instruction name: trace JSON gives the name alone,
    a TPU xplane the whole instruction text."""
    return op.partition(" = ")[0].strip().lstrip("%")


def _phases(module, ops, records):
    """``(program key, {phase: seconds}, covered share)`` of one module's
    operations from the record whose keys cover most of their time, or
    ``(None, {}, 0.0)`` when none covers nine tenths (no record of this
    program: the sink was off, or the capture is another process's)."""
    from ..compile import owners

    total = sum(ops.values())
    best = (None, {}, 0.0)
    for key, rec in records:
        if module != "?" and rec.get("module") != module:
            continue
        by_name = {k.split(":")[0]: owner
                   for k, owner in owners.flat(rec).items()}
        phases, covered = {}, 0.0
        for op, s in ops.items():
            owner = by_name.get(_op_name(op))
            if owner is not None:
                covered += s
            phase = owner[0] if owner else owners.UNOWNED
            phases[phase] = phases.get(phase, 0.0) + s
        share = covered / total if total else 0.0
        if share > best[2]:
            best = (key, phases, share)
    return best if best[2] >= 0.9 else (None, {}, 0.0)


def attribute_trace(trace_dir, top_ops=5):
    """Best-effort attribution of an *unsegmented* capture (the
    ``/profilez`` and ``train --profile`` artifacts): device time per
    hlo module and op class, and per phase of the model where a
    registered program's ``owners`` record covers the module's
    operations; that record's program names the module.

    Raises :class:`TraceError` on an unusable capture — callers on the
    serving path wrap this (an attribution failure must never fail the
    capture that produced the artifact).
    """
    from ..compile import owners

    collected = collect_trace(trace_dir)
    records = _program_records()
    per_module = {}
    for module, op, s in collected["ops"]:
        m = per_module.setdefault(module, {"seconds": 0.0, "classes": {},
                                           "ops": {}})
        m["seconds"] += s
        c = op_class(op)
        m["classes"][c] = m["classes"].get(c, 0.0) + s
        m["ops"][op] = m["ops"].get(op, 0.0) + s
    order = {p: i for i, p in enumerate(owners.PHASES
                                         + owners.LADDER_PHASES)}
    modules = []
    for name in sorted(per_module,
                       key=lambda n: -per_module[n]["seconds"]):
        m = per_module[name]
        program, phases, covered = _phases(name, m["ops"], records)
        modules.append({
            "module": name,
            "program": program,
            "seconds": round(m["seconds"], 6),
            "classes": {c: round(s, 6)
                        for c, s in sorted(m["classes"].items(),
                                           key=lambda kv: -kv[1])},
            "phases": {p: round(s, 6)
                       for p, s in sorted(phases.items(),
                                          key=lambda kv: order.get(
                                              kv[0], len(order)))},
            "covered": round(covered, 4),
            "top_ops": [{"op": o, "seconds": round(s, 6)}
                        for o, s in sorted(m["ops"].items(),
                                           key=lambda kv: -kv[1])
                        [:top_ops]],
        })
    return {
        "source": collected["source"],
        "device_seconds": round(sum(m["seconds"]
                                    for m in per_module.values()), 6),
        "op_events": len(collected["ops"]),
        "modules": modules,
    }


# -- telemetry / metrics / rendering ------------------------------------------


def emit_events(prof_report, tele):
    """Forward per-program calibration rows as ``profile`` telemetry."""
    drifted = {f.message.split(":", 1)[0] for f in prof_report.findings
               if f.rule == "prof-calibration"}
    for r in prof_report.reports:
        tele.emit(
            "profile", program=r["key"], program_kind=r["kind"],
            seconds=r["device_seconds"],
            predicted_seconds=r["predicted_seconds"],
            ratio=r.get("ratio"),
            classes={c: d.get("seconds", 0.0)
                     for c, d in r.get("classes", {}).items()},
            machine=prof_report.machine.get("machine_id", "?"),
            drift=r["key"] in drifted,
            stale_fingerprint=bool(r.get("stale_fingerprint")))


def publish_metrics(prof_report, registry):
    """Export the calibration table as ``rmd_prof_*`` gauges."""
    g_sec = registry.gauge(
        "rmd_prof_device_seconds",
        "measured device seconds per step, last attribution",
        ("program",))
    g_ratio = registry.gauge(
        "rmd_prof_calibration_ratio",
        "measured/predicted roofline-seconds ratio, last attribution",
        ("program",))
    g_cls = registry.gauge(
        "rmd_prof_class_seconds",
        "measured device seconds per op class, last attribution",
        ("klass",))
    totals = {}
    for r in prof_report.reports:
        g_sec.labels(program=r["kind"]).set(r["device_seconds"])
        if "ratio" in r:
            g_ratio.labels(program=r["kind"]).set(r["ratio"])
        for c, d in r.get("classes", {}).items():
            totals[c] = totals.get(c, 0.0) + d.get("seconds", 0.0)
    for c, s in totals.items():
        g_cls.labels(klass=c).set(round(s, 6))


def publish_attribution_metrics(summary, registry):
    """Export an :func:`attribute_trace` summary (module-granular) as
    the same ``rmd_prof_*`` gauges — the /profilez path."""
    g_sec = registry.gauge(
        "rmd_prof_device_seconds",
        "measured device seconds per step, last attribution",
        ("program",))
    g_cls = registry.gauge(
        "rmd_prof_class_seconds",
        "measured device seconds per op class, last attribution",
        ("klass",))
    totals = {}
    for m in summary.get("modules", []):
        g_sec.labels(program=m["program"] or m["module"]).set(m["seconds"])
        for c, s in m.get("classes", {}).items():
            totals[c] = totals.get(c, 0.0) + s
    for c, s in totals.items():
        g_cls.labels(klass=c).set(round(s, 6))


def render_reports(prof_report):
    """The human-readable calibration table (CLI text format)."""
    mach = prof_report.machine
    out = ["== profiling ==",
           f"machine: {mach.get('machine_id', '?')} "
           f"({mach.get('n_devices', '?')} device(s), roofline "
           f"{mach.get('peak_flops', 0) / 1e12:.1f} TFLOP/s, "
           f"{mach.get('peak_bytes_per_s', 0) / 2 ** 30:.0f} GiB/s)"]
    for r in prof_report.reports:
        ratio = f"{r['ratio']:.2f}" if "ratio" in r else "-"
        stale = " [stale fingerprint]" if r.get("stale_fingerprint") \
            else ""
        out.append(
            f"{r['key']}: measured {r['device_seconds'] * 1e3:.1f} ms "
            f"vs predicted {r['predicted_seconds'] * 1e3:.1f} ms "
            f"(ratio {ratio}), "
            f"{r.get('achieved_flops', 0) / 1e9:.2f} GFLOP/s, "
            f"{r.get('achieved_bytes_per_s', 0) / 2 ** 30:.2f} GiB/s"
            f"{stale}")
        for c, d in sorted(r.get("classes", {}).items(),
                           key=lambda kv: -kv[1].get("seconds", 0.0)):
            cr = f"{d['ratio']:.2f}" if "ratio" in d else "-"
            out.append(f"    {c:12s} {d.get('seconds', 0) * 1e3:8.2f} ms"
                       f" vs {d.get('predicted_seconds', 0) * 1e3:8.2f}"
                       f" ms  (ratio {cr})")
    for f in prof_report.findings:
        out.append(f"  ! {f.rule}: {f.message}")
    for key in prof_report.stale:
        out.append(f"  stale calibration entry: {key}")
    return "\n".join(out)


def render_attribution(summary, top_modules=6):
    """Compact text form of an :func:`attribute_trace` summary."""
    out = [f"device op time: {summary['device_seconds'] * 1e3:.1f} ms "
           f"over {summary['op_events']} op event(s) "
           f"[{summary['source']}]"]
    for m in summary.get("modules", [])[:top_modules]:
        who = m["module"]
        if m.get("program"):
            who += f" -> {m['program']}"
        classes = ", ".join(
            f"{c} {100 * s / m['seconds']:.0f}%"
            for c, s in list(m["classes"].items())[:4]) if m["seconds"] \
            else "-"
        out.append(f"  {m['seconds'] * 1e3:8.1f} ms  {who}  [{classes}]")
        if m.get("phases"):
            out.append("            phases: " + ", ".join(
                f"{p} {s * 1e3:.1f} ms" for p, s in m["phases"].items()))
    return "\n".join(out)
