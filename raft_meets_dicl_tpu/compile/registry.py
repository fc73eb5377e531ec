"""Compiled-program registry: the single constructor for jitted steps.

Before PR 7 every layer built its jitted step ad hoc — the training loop
through ``parallel.make_train_step``, evaluation through ``make_eval_fn``
plus its module-level cache, training-validation through a private jit in
``inspect/summary.py`` — so the same (model, shape bucket, wire) triple
could compile more than once per process and *always* recompiled per
boot. The registry gives every step program one identity
(:class:`ProgramKey`), one owner (:class:`Program` — lowering,
compilation, AOT artifacts, warmup, per-program compile counters), and
one dedupe point (:class:`ProgramRegistry`).

Key discipline: a ProgramKey built only from *stable* configuration
(model id string, config reprs, shapes) is content-addressable — equal
across boots, so its programs can round-trip through the AOT artifact
store (``aot.py``). Callers that cannot name their configuration exactly
mark the key with a ``pyid:`` component (process-local object identity):
such programs still dedupe within the process and still count compiles,
but never touch the artifact store.
"""

import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Tuple

from .. import telemetry
from . import aot, owners

_UNSTABLE = "pyid:"

# sentinel: this shape signature cannot use an AOT executable; stay on JIT
_FALLBACK = object()


def unstable(obj):
    """Process-local identity marker for a key component that has no
    stable serialization (keeps dedupe, disables AOT)."""
    return f"{_UNSTABLE}{id(obj)}"


def flag_items(**kwargs):
    """Normalize keyword policy flags into the sorted (name, repr) tuple
    a ProgramKey stores. Values must repr deterministically — the
    :func:`static_args_key` discipline; callers pass ``unstable(obj)``
    for anything that doesn't."""
    return tuple(sorted((k, repr(v)) for k, v in kwargs.items()))


def static_args_key(args):
    """Repr-key an argument dict for memoizing jitted fns, or None when any
    value can't be keyed exactly.

    Array-valued args (e.g. ``flow_init``) are traced into the jit as
    constants, and their reprs truncate — two different arrays could share a
    key. Such calls must bypass the registry instead. Shared by every
    program key in the framework (inference, validation, intermediates
    capture).
    """
    parts = []
    for k, v in sorted(args.items()):
        if hasattr(v, "shape") or (
            isinstance(v, (list, tuple)) and any(hasattr(x, "shape") for x in v)
        ):
            return None
        parts.append((k, repr(v)))
    return tuple(parts)


def effective_args_key(model, model_args):
    """:func:`static_args_key` of the arguments the model will run with:
    its *config-default* arguments merged under the explicit overrides,
    exactly how ``Model.apply`` resolves them at call time. Two models
    with the same id but different config defaults (e.g. ``iterations``)
    must NOT share a program or an AOT artifact; keys made of the
    explicit arguments alone silently collided."""
    return static_args_key(dict(getattr(model, "arguments", {})) | model_args)


@dataclass(frozen=True)
class ProgramKey:
    """Identity of one compiled step program.

    ``kind`` is the program family ('train_step', 'eval_step',
    'val_loss', ...) — it doubles as the telemetry compile label.
    ``model`` is the stable model id (or a ``pyid:`` marker). ``flags``
    carries every policy that changes the traced computation: wire
    format, mesh spec, nonfinite guard, accumulation, donation, static
    model/loss args, stage config. Concrete input shapes are *not* part
    of the key — one Program owns all shape buckets of its computation,
    and the AOT store addresses artifacts by (key digest, shape
    signature).
    """

    kind: str
    model: str
    flags: Tuple[Tuple[str, str], ...] = field(default=())

    @property
    def stable(self):
        """Whether the key survives across processes (AOT-addressable)."""
        if self.model.startswith(_UNSTABLE):
            return False
        return not any(_UNSTABLE in v for _, v in self.flags)

    def canonical(self):
        return repr((self.kind, self.model, self.flags))


def notes_flag(model):
    """``{"notes": n}`` for a model that counts its trace-time notes'
    revisions (``Model.notes_revision``), else nothing: a key without
    the flag is byte for byte what it was."""
    revision = getattr(model, "notes_revision", None)
    return {} if revision is None else {"notes": revision}


def inference_key(kind, model, model_args, mesh=None, wire=None,
                  variables_sharding=None, model_id=None, **flags):
    """Identity of an inference program, or None when something cannot be
    keyed exactly: an array-valued argument, or a sharding pytree for
    the variables (no stable value key). Such a program is built fresh
    each call.

    Stable when the caller names the model (``model_id``, a config id
    string); otherwise pinned to this model object, which the program
    must then keep alive (``Program._refs``) so that its id stays
    unique. ``flags`` are the variant's own (``iterations``, ``cont``,
    ``warm``, ``quant``...), beside the ``args``, ``mesh`` and ``wire``
    every inference program carries.
    """
    args_key = effective_args_key(model, model_args)
    if args_key is None or variables_sharding is not None:
        return None
    mesh_key = None if mesh is None else tuple(d.id for d in mesh.devices.flat)
    wire_key = None if wire is None else (
        wire.images, wire.flow, wire.pack_valid, wire.clip, wire.range)
    return ProgramKey(
        kind=kind, model=model_id or unstable(model),
        flags=flag_items(args=args_key, mesh=mesh_key, wire=wire_key,
                         **notes_flag(model), **flags))


def mosaic_calls(text):
    """Mosaic (Pallas TPU) custom calls in a compiled executable's HLO
    text (``compiled.as_text()``).

    The Pallas kernels give way to their XLA references at trace time
    (off-TPU, or when a shape does not fit VMEM) without a word; this
    count, carried by the program's ``aot`` events, is how a run shows
    from the executable itself which form it got."""
    return text.count('custom_call_target="tpu_custom_call"')


def shape_signature(args):
    """Concrete (shape, dtype) tuple over every array leaf of ``args`` —
    the per-call index into a Program's compiled-executable family."""
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(args):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            parts.append(type(leaf).__name__)
    return tuple(parts)


class Program:
    """One registered step program: a jitted callable plus its identity,
    compile counters, and (for stable keys) its AOT executable family.

    Calls route through a per-shape-signature compiled executable when
    the AOT store is enabled — loaded from disk when an artifact exists
    (zero compiles), otherwise compiled ahead of time once and saved for
    the next boot. An unusable artifact (corrupt, stale version, not
    reloadable on this backend) or an executable that rejects the
    caller's argument types/placement puts that signature on the plain
    JIT path for the rest of the process; every such decision emits an
    ``aot`` ``fallback`` event, on every boot. A failed compile or a
    failed execution is not a fallback: it raises.

    ``compiles``/``compile_seconds`` count actual backend compiles
    attributed to this program via the jax.monitoring listener — they
    increment even when the telemetry sink is disabled, which is what
    lets eval warmup report 0 compiles on a warm cache instead of
    guessing 1 per shape (the pre-PR-7 overcount).
    """

    def __init__(self, key, fn, label=None):
        self.key = key
        self.label = label or key.kind
        self._fn = fn
        # compat with instrument_jit's wrapper contract
        self.__wrapped__ = fn
        self.telemetry_label = self.label
        self.compiles = 0
        self.compile_seconds = 0.0
        self.aot_hits = 0
        self.aot_misses = 0
        self.aot_saves = 0
        self.aot_fallbacks = 0
        self._compiled = {}
        # per shape signature, whose instruction is whose in the
        # executable this boot got (``owners.parse``; sink on only)
        self.owners = {}
        # the mesh a partitioned step was built over, ``{axis: size}``
        # (the step builders set it); None for a one-device program
        self.mesh_axes = None
        # what the running trace has noted (telemetry.note_trace),
        # keyed by (site, name); reset before each lowering
        self._trace_notes = {}
        self._lock = threading.Lock()
        # callers may pin objects their pyid: key components reference so
        # the ids stay unique for the program's lifetime
        self._refs = ()

    # -- counters (jax.monitoring listener callback) -----------------------

    def record_compile(self, seconds):
        self.compiles += 1
        self.compile_seconds += seconds

    # -- trace-time counts (telemetry.note_trace) ---------------------------

    def note_trace(self, name, value, site=()):
        """One count from the trace that is running. Inside a
        ``telemetry.trace_site`` the count is kept once per site and
        name (the tracer may visit a scan's body twice) times the
        site's repeats; outside any site counts add up."""
        key = (tuple(label for label, _ in site), name)
        if site:
            self._trace_notes[key] = value * math.prod(n for _, n in site)
        else:
            self._trace_notes[key] = self._trace_notes.get(key, 0) + value

    def trace_counts(self):
        """The running (or last) trace's counts by name, over its sites."""
        counts = {}
        for (_, name), value in self._trace_notes.items():
            counts[name] = counts.get(name, 0) + value
        return counts

    def _hand_counts(self, counts):
        """The program's counts into the next ``step`` event's counters:
        once per executable this boot got, traced or loaded."""
        tele = telemetry.get()
        for name, value in counts.items():
            tele.add_count(name, value)

    def _take_counts(self):
        """Close the running trace's account: its counts, handed on."""
        counts = self.trace_counts()
        self._trace_notes = {}
        self._hand_counts(counts)
        return counts

    # -- call paths --------------------------------------------------------

    def lower(self, *args, **kwargs):
        with telemetry.jit_label(self.label, self):
            return self._fn.lower(*args, **kwargs)

    def __call__(self, *args):
        if self.key.stable and aot.aot_enabled():
            sig = shape_signature(args)
            entry = self._compiled.get(sig)
            if entry is None:
                entry = self._ensure(sig, args)
            if entry is not _FALLBACK:
                try:
                    return entry(*args)
                except (TypeError, ValueError) as e:
                    # the executable's argument checks (pytree, avals,
                    # shardings, layouts) — they run before execution,
                    # so the args (donated included) are intact; pin
                    # this signature to the JIT path and carry on. A
                    # runtime failure (out of memory, a device fault) is
                    # a JaxRuntimeError and propagates.
                    self._compiled[sig] = _FALLBACK
                    self.aot_fallbacks += 1
                    self._emit("fallback",
                               reason=f"call: {type(e).__name__}: "
                                      f"{str(e)[:160]}")
        with telemetry.jit_label(self.label, self):
            out = self._fn(*args)
        if self._trace_notes:
            # this call traced: its counts go to the step that ran it
            self._take_counts()
        return out

    def _ensure(self, sig, args):
        """Resolve one shape signature: load its artifact, or compile
        ahead of time and save one. Called once per (program, sig)."""
        with self._lock:
            entry = self._compiled.get(sig)
            if entry is not None:
                return entry

            path = aot.artifact_path(self.key, sig)
            if aot.tombstoned(path):
                # a previous boot proved this executable doesn't survive
                # serialization on this backend: plain JIT, no save/fail
                # churn — but said on every boot, not only the first
                self.aot_fallbacks += 1
                self._emit("fallback", reason="tombstoned: not reloadable "
                                              "on this backend")
                self._compiled[sig] = _FALLBACK
                return _FALLBACK
            compiled, status, info = aot.load(path, self.key, sig)
            if compiled is not None:
                self.aot_hits += 1
                facts = info["text_facts"]
                if facts:
                    # stored with the executable it describes: this boot
                    # takes no text and parses nothing
                    facts = dict(facts, owners=dict(
                        facts["owners"], source="artifact", seconds=0.0))
                self._emit("hit", compiled, sig, facts,
                           bytes=info["bytes"],
                           seconds=round(info["seconds"], 4),
                           **info["trace_counts"])
                self._hand_counts(info["trace_counts"])
                self._compiled[sig] = compiled
                return compiled

            if status == "missing":
                self.aot_misses += 1
                self._emit("miss")
            else:
                # an artifact existed but was unusable: this boot pays a
                # cold JIT it expected to skip — the anomaly the report
                # flags
                self.aot_fallbacks += 1
                self._emit("fallback", reason=f"{status}: {info}")
                if status == "error":
                    # the artifact deserialized on save but not on load:
                    # this executable doesn't round-trip on this backend
                    # (e.g. XLA-CPU fusion symbol collisions). Tombstone
                    # it so later boots take the JIT path instead of
                    # re-saving and re-failing forever; the marker is
                    # fingerprint-scoped, so a jax/backend upgrade
                    # retries.
                    try:
                        os.remove(path)
                    except OSError:
                        pass
                    aot.tombstone(path)

            lower = getattr(self._fn, "lower", None)
            if lower is None:
                self._compiled[sig] = _FALLBACK
                return _FALLBACK

            # a compile that fails here (a kernel the compiler refuses,
            # a program that does not fit) fails the same way through
            # plain jit: it raises
            c0 = self.compiles
            self._trace_notes = {}
            with telemetry.jit_label(self.label, self):
                compiled = lower(*args).compile()
            counts = self._take_counts()

            if self.compiles == c0:
                # the compile was served from the persistent XLA cache:
                # no backend compile ran, and such executables serialize
                # without their object code on XLA:CPU (jax 0.9.0: the
                # payload is half the size and fails to load with
                # "Function ... not found") — writing them would poison
                # the next boot. This boot is already warm through the
                # cache; the artifact gets written by whichever boot
                # pays the real compile.
                self._emit("skip_save", compiled, sig,
                           reason="compile served from persistent cache",
                           **counts)
            else:
                facts = self._text_facts(compiled)
                try:
                    nbytes, seconds = aot.save(path, self.key, sig,
                                               compiled, counts, facts)
                    self.aot_saves += 1
                    self._emit("save", compiled, sig, facts, bytes=nbytes,
                               seconds=round(seconds, 4), **counts)
                except Exception as e:  # noqa: BLE001 - save is cosmetic
                    self._emit("fallback",
                               reason=f"save: {type(e).__name__}: "
                                      f"{str(e)[:160]}")

            self._compiled[sig] = compiled
            return compiled

    def _text_facts(self, compiled):
        """What this boot reads off an executable's compiled text, taken
        once: the count of Mosaic calls, the ``owners`` record (which
        phase of the model each instruction belongs to,
        ``compile/owners.py``) and, of a partitioned program, its
        ``collectives`` (``analysis/collectives.py``). None with the sink
        off: no text is taken."""
        if not telemetry.get().enabled:
            return None
        t0 = time.perf_counter()
        text = compiled.as_text()
        record = owners.parse(text)
        # what the text and its parse cost this boot's set-up
        record.update(source="text",
                      seconds=round(time.perf_counter() - t0, 4))
        facts = {"mosaic_calls": mosaic_calls(text), "owners": record}
        if record["rules"].get("collective"):
            # what the partitioner put in, from the same text: count and
            # result bytes (one chip's) by kind. A one-device program
            # holds none and says nothing
            from ..analysis import collectives

            said = collectives.summarize_schedule(
                collectives.parse_schedule(text))
            facts["collectives"] = {k: said[k] for k in
                                    ("counts", "bytes", "total_bytes")}
        return facts

    def _emit(self, event, compiled=None, sig=None, facts=None, **fields):
        """One ``aot`` event; with an executable in hand (``hit``,
        ``save``, ``skip_save``) and the sink on it carries
        ``mosaic_calls`` (a partitioned program's ``collectives`` too)
        and is followed by the ``owners`` event, all from ``facts``: what
        the artifact holds of its executable's text
        (a ``hit`` of an artifact a sink-on boot saved; ``source:
        "artifact"``), else read off the text now (``"text"``)."""
        tele = telemetry.get()
        record = None
        if compiled is not None and tele.enabled:
            facts = facts or self._text_facts(compiled)
            fields["mosaic_calls"] = facts["mosaic_calls"]
            if "collectives" in facts:
                fields["collectives"] = facts["collectives"]
            record = self.owners[sig] = facts["owners"]
        if self.mesh_axes:
            fields["mesh"] = self.mesh_axes
        tele.emit("aot", event=event, program=self.key.kind,
                  model=self.key.model, **fields)
        if record is not None:
            tele.emit("aot", event="owners", program=self.key.kind,
                      model=self.key.model, **record)

    def stats(self):
        return {
            "kind": self.key.kind,
            "model": self.key.model,
            "stable": self.key.stable,
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 3),
            "aot_hits": self.aot_hits,
            "aot_misses": self.aot_misses,
            "aot_saves": self.aot_saves,
            "aot_fallbacks": self.aot_fallbacks,
            "signatures": len(self._compiled),
        }


class ProgramRegistry:
    """Process-wide Program store: dedupe by key, bounded FIFO.

    Evicting an entry only drops the registry's reference — callers
    holding the Program keep a fully working step (same contract as the
    old evaluation fn cache)."""

    def __init__(self, max_programs=64):
        self.max_programs = max_programs
        self._programs = {}
        self._anonymous = []
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            return self._programs.get(key)

    def register(self, key, fn, label=None, dedupe=True):
        telemetry.install_listeners()
        with self._lock:
            if dedupe:
                existing = self._programs.get(key)
                if existing is not None:
                    return existing
            program = Program(key, fn, label)
            if dedupe:
                while len(self._programs) >= self.max_programs:
                    self._programs.pop(next(iter(self._programs)))
                self._programs[key] = program
            else:
                self._anonymous.append(program)
                del self._anonymous[:-self.max_programs]
            return program

    def programs(self):
        with self._lock:
            return list(self._programs.values()) + list(self._anonymous)

    def stats(self):
        return [p.stats() for p in self.programs()]

    def clear(self):
        with self._lock:
            self._programs.clear()
            self._anonymous.clear()


_registry = ProgramRegistry()


def registry():
    """The process-wide registry."""
    return _registry


def reset():
    """Drop every registered program (tests)."""
    _registry.clear()


def register_step(kind, fn, key=None, label=None):
    """Route one freshly built jitted step through the registry.

    With a ``key`` the program dedupes (a second build of the same key
    returns the first Program, jit closure discarded — check
    ``registry().get(key)`` first to skip the build). Without one the
    program is anonymous: tracked for stats and compile attribution,
    never shared, never AOT'd — the safe default for callers whose
    closures (optimizer, loss) have no stable identity.
    """
    if key is None:
        key = ProgramKey(kind=kind, model=unstable(fn))
        return _registry.register(key, fn, label or kind, dedupe=False)
    return _registry.register(key, fn, label or key.kind, dedupe=True)
