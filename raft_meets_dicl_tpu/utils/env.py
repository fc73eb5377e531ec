"""Central registry for ``RMD_*`` environment knobs.

Every environment variable the framework reads is declared here — name,
type, default, one-line doc, and the README section it belongs to — and
every read site goes through the typed accessors below instead of
touching ``os.environ`` directly. That buys three things:

1. **One source of truth.** The README's environment-knob table is
   generated from this registry (``readme_table()``); a knob that exists
   in code but not in the table (or the reverse) cannot happen silently —
   ``graftlint``'s ``env-knob``/``env-docs`` rules fail on direct
   ``os.environ`` reads of ``RMD_*`` names outside this module, on names
   read but not registered, and on a README table that drifted from the
   registry.
2. **Uniform semantics.** Default-on switches (``RMD_TELEMETRY=0``
   disables), default-off flags (``RMD_DEVICE_AUG=1`` enables), and typed
   values (int/float/str) each parse exactly one way, instead of every
   call site re-inventing ``!= "0"`` vs ``bool(get(...))``.
3. **Greppability.** ``env.get_bool("RMD_AOT")`` names the knob as a
   literal, so the registry-completeness check (and a human) can find
   every consumer.

This module must stay dependency-free (no jax/numpy): it is imported by
loader worker processes and by the lint framework itself.
"""

import os
from dataclasses import dataclass

# knob kinds:
#   switch — default-on boolean; only the literal "0" disables
#   flag   — default-off boolean; any non-empty value enables
#   str    — raw string (default may be None)
#   int    — integer with default
#   float  — float with default
_KINDS = ("switch", "flag", "str", "int", "float")


@dataclass(frozen=True)
class Knob:
    name: str
    kind: str
    default: object
    doc: str
    section: str

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown knob kind '{self.kind}'")


def _k(name, kind, default, doc, section):
    return (name, Knob(name, kind, default, doc, section))


KNOBS = dict([
    # -- telemetry ---------------------------------------------------------
    _k("RMD_TELEMETRY", "switch", True,
       "kill switch for the telemetry sink and jax.monitoring listeners",
       "telemetry"),
    _k("RMD_FINITE_CHECK_EVERY", "int", 10,
       "amortized cadence (steps) of the device finiteness fetch / "
       "pipeline-drain sample", "telemetry"),
    _k("RMD_TELEMETRY_BUFFER", "int", 4096,
       "bounded event-queue capacity of the non-blocking serve sink; "
       "overflow drops events and counts them", "telemetry"),
    _k("RMD_TELEMETRY_MAX_MB", "float", 0.0,
       "rotate events.jsonl to <path>.1 past this size in MiB (0 = "
       "never rotate)", "telemetry"),
    _k("RMD_GOODPUT", "switch", True,
       "account the run's wall clock into goodput classes (productive/"
       "compile/data-starved/checkpoint/eval/resume-replay/preempted); "
       "0 disables the ledger", "telemetry"),
    _k("RMD_BLACKBOX_STEPS", "int", 64,
       "flight-recorder ring size: last N step traces kept in memory "
       "for the crash/SIGTERM postmortem bundle", "telemetry"),
    _k("RMD_TRAIN_METRICS_PORT", "int", 0,
       "trainer observability HTTP port (/metrics, /healthz, /statusz, "
       "/profilez); unset = off, 0 = ephemeral; CLI --metrics-port "
       "wins", "telemetry"),
    _k("RMD_PROFILE_KEEP", "int", 3,
       "retained /profilez capture directories: older rmd-profilez-* "
       "temp dirs are evicted on each capture", "telemetry"),
    _k("RMD_PROFILE_ATTRIBUTION", "switch", True,
       "attach a graftprof device-time attribution summary (and "
       "rmd_prof_* gauges) to /profilez responses and train --profile "
       "captures; 0 returns the artifact path only", "telemetry"),
    # -- input pipeline ----------------------------------------------------
    _k("RMD_WIRE_FORMAT", "str", None,
       "host-to-device wire format preset (f32 | bf16 | u8); CLI "
       "--wire-format wins", "input"),
    _k("RMD_WIRE_BF16", "switch", True,
       "legacy bf16 image put for mixed-precision models when no wire "
       "format is configured", "input"),
    _k("RMD_LOADER_PROCS", "int", 0,
       "decode worker processes (0 = thread pool); CLI --loader-procs "
       "wins", "input"),
    _k("RMD_LOADER_MP", "str", "fork",
       "multiprocessing start method for the decode pool", "input"),
    _k("RMD_LOADER_RETRIES", "int", 2,
       "per-sample decode retries before neighbor substitution", "input"),
    _k("RMD_BAD_SAMPLE_BUDGET", "int", 16,
       "substituted-sample budget per loader before aborting (0 disables "
       "healing)", "input"),
    _k("RMD_LOADER_TIMEOUT", "float", 300.0,
       "total seconds to wait for one sample before declaring the decode "
       "pool wedged", "input"),
    _k("RMD_LOADER_POLL", "float", 5.0,
       "decode-pool queue poll interval (dead-worker detection latency)",
       "input"),
    _k("RMD_LOADER_RESPAWNS", "int", 3,
       "dead decode workers respawned before the pool raises PoolBroken",
       "input"),
    _k("RMD_EVAL_BUCKETS", "str", None,
       "shape-bucket spec for evaluation/validation ('group' or "
       "'HxW,HxW,...')", "input"),
    _k("RMD_DEVICE_AUG", "flag", False,
       "compile the augmentation pipeline into the train step (on-device "
       "data engine); env-config 'augment:' section tunes it", "input"),
    _k("RMD_SYNTH_LAYERS", "int", 4,
       "default moving-layer count for the synthetic scene generator "
       "(data 'type: synth'; per-source 'layers:' wins)", "input"),
    _k("RMD_SYNTH_SEED", "int", 0,
       "default base seed of the synthetic scene generator (per-source "
       "'seed:' wins)", "input"),
    # -- training loop -----------------------------------------------------
    _k("RMD_NONFINITE", "str", None,
       "non-finite step policy (raise | skip | rollback); CLI "
       "--nonfinite wins", "training"),
    _k("RMD_ASYNC_CHECKPOINT", "switch", True,
       "background checkpoint serialization/write (0 = synchronous "
       "save)", "training"),
    # -- SPMD / parallel ---------------------------------------------------
    _k("RMD_MESH", "str", None,
       "mesh spec 'DATA,MODEL' (or 'data'); CLI --mesh wins", "parallel"),
    _k("RMD_ACCUMULATE", "str", None,
       "in-step gradient accumulation factor; CLI --accumulate wins",
       "parallel"),
    # -- compile / AOT -----------------------------------------------------
    _k("RMD_COMPILE_CACHE", "str", None,
       "persistent XLA compile-cache directory (default "
       "<repo>/.jax_cache); yields to JAX_COMPILATION_CACHE_DIR",
       "compile"),
    _k("RMD_NO_COMPILE_CACHE", "flag", False,
       "configure no persistent XLA compile cache (a cache placed from "
       "outside through JAX_COMPILATION_CACHE_DIR stays on)", "compile"),
    _k("RMD_AOT", "switch", True,
       "AOT serialized-executable program store (0 disables)", "compile"),
    _k("RMD_AOT_DIR", "str", None,
       "relocate the AOT program store (default "
       "<compile-cache>/programs)", "compile"),
    # -- model fast paths --------------------------------------------------
    _k("RMD_DICL_FAST", "switch", True,
       "level-batched MatchingNets + fused Pallas window sampler (0 = "
       "reference loop)", "models"),
    _k("RMD_FS_VOLUME_GIB", "float", 4.0,
       "raft/fs correlation-volume HBM budget steering the "
       "volume/windowed dispatch (per chip)", "models"),
    _k("RMD_ITERATIONS", "int", 0,
       "recurrence iteration override for evaluation (0 = model "
       "default); CLI --iterations wins", "models"),
    # -- serving -----------------------------------------------------------
    _k("RMD_SERVE_BUCKETS", "str", None,
       "canonical request shapes for the serve command ('HxW,HxW,...'); "
       "CLI --buckets / config wins", "serve"),
    _k("RMD_SERVE_BATCH", "int", 4,
       "serve device batch size per dispatch; CLI --batch-size / config "
       "wins", "serve"),
    _k("RMD_SERVE_MAX_WAIT_MS", "float", 50.0,
       "max milliseconds a partial batch waits before dispatching padded "
       "onto the full batch's program", "serve"),
    _k("RMD_SERVE_QUEUE", "int", 64,
       "per-bucket admission queue bound; requests beyond it shed with a "
       "typed queue_full rejection", "serve"),
    _k("RMD_LADDER", "str", "4,8,12",
       "iteration-ladder rung budgets for serve latency classes; CLI "
       "--ladder / config wins", "serve"),
    _k("RMD_LADDER_THRESHOLD", "float", 0.1,
       "flow-delta norm (coarse-grid px) below which the balanced class "
       "stops escalating rungs", "serve"),
    _k("RMD_QUANT", "str", None,
       "quantized matching tier for the fast serve class and video warm "
       "frames ('u8' or 'i8'; unset/off = full precision); CLI --quant "
       "/ config wins", "serve"),
    _k("RMD_QUANT_CLIP", "float", 1.0,
       "fraction of the per-level abs-max mapped onto the quantized "
       "range (values beyond it saturate); <1 trades outlier clipping "
       "for finer steps on the bulk", "serve"),
    _k("RMD_METRICS_PORT", "int", 0,
       "serve observability HTTP port (/metrics, /healthz, /statusz, "
       "/profilez); 0 = off; CLI --metrics-port wins", "serve"),
    _k("RMD_SLO_FAST_MS", "float", 0.0,
       "end-to-end latency SLO target (ms) for the fast ladder class "
       "(0 = untracked)", "serve"),
    _k("RMD_SLO_BALANCED_MS", "float", 0.0,
       "end-to-end latency SLO target (ms) for the balanced ladder "
       "class (0 = untracked)", "serve"),
    _k("RMD_SLO_QUALITY_MS", "float", 0.0,
       "end-to-end latency SLO target (ms) for the quality ladder "
       "class (0 = untracked)", "serve"),
    _k("RMD_SLO_DEFAULT_MS", "float", 0.0,
       "latency SLO target (ms) for ladderless requests and classes "
       "without their own RMD_SLO_* target (0 = untracked)", "serve"),
    _k("RMD_SLO_OBJECTIVE", "float", 0.99,
       "SLO attainment objective; burn_rate = (1-attainment)/"
       "(1-objective), >1 means the window misses it", "serve"),
    _k("RMD_SLO_WINDOW_S", "float", 60.0,
       "rolling SLO burn-rate window (seconds)", "serve"),
    _k("RMD_VIDEO_SESSIONS", "int", 64,
       "bounded per-client video session cache capacity in the serve "
       "scheduler (LRU past it)", "serve"),
    _k("RMD_VIDEO_SESSION_TTL_S", "float", 30.0,
       "idle seconds before a video session's warm-start state is "
       "TTL-evicted", "serve"),
    _k("RMD_VIDEO_WARM_ITERATIONS", "int", 4,
       "warm-start program iteration budget for ladderless video serve "
       "sessions (with --ladder the bottom rung wins)", "serve"),
    # -- serving fleet -----------------------------------------------------
    _k("RMD_FLEET_REPLICAS", "int", 2,
       "replica process count for the serving fleet (serve --fleet); "
       "CLI --fleet wins", "fleet"),
    _k("RMD_FLEET_RETRIES", "int", 2,
       "router retry budget per request on safe failures (connection "
       "refused/reset, replica shed) before the typed fleet shed",
       "fleet"),
    _k("RMD_FLEET_TIMEOUT_MS", "float", 30000.0,
       "per-request router deadline (ms) covering dispatch + retries; "
       "past it the request fails with a typed replica_unavailable",
       "fleet"),
    _k("RMD_FLEET_BURN_DRAIN", "float", 2.0,
       "SLO burn rate above which the router drains a replica (hands "
       "off its sticky sessions, stops routing to it, recycles it)",
       "fleet"),
    _k("RMD_FLEET_BACKOFF_MS", "float", 500.0,
       "supervisor restart backoff base (ms); doubles per consecutive "
       "crash, capped at 30 s, +-25% jitter", "fleet"),
    _k("RMD_FLEET_HEALTH_S", "float", 0.5,
       "router/supervisor health poll interval (seconds): /healthz "
       "liveness + /statusz SLO burn per replica", "fleet"),
    # -- fault injection / harness -----------------------------------------
    _k("RMD_FAULT", "str", "",
       "deterministic fault injection spec (testing.faults)", "faults"),
    _k("RMD_FAULT_STATE", "str", None,
       "directory sharing fired-once fault state across processes",
       "faults"),
])

_SECTIONS = ("telemetry", "input", "training", "parallel", "compile",
             "models", "serve", "fleet", "faults")


def knob(name):
    """The :class:`Knob` declaration for ``name`` (KeyError if absent)."""
    return KNOBS[name]


def raw(name):
    """The raw environment string for a registered knob, or None.

    The escape hatch for call sites that need "was it set at all"
    precedence logic (CLI > env var > config); everything else should use
    the typed accessors.
    """
    KNOBS[name]
    return os.environ.get(name)


def is_set(name):
    """Whether the knob is present in the environment at all."""
    KNOBS[name]
    return name in os.environ


def get(name):
    """Typed value of a registered knob, falling back to its default."""
    k = KNOBS[name]
    v = os.environ.get(name)
    if k.kind == "switch":
        return v != "0"
    if k.kind == "flag":
        return bool(v)
    if v is None or (v == "" and k.kind != "str"):
        return k.default
    if k.kind == "int":
        return int(v)
    if k.kind == "float":
        return float(v)
    return v


def get_bool(name):
    """Boolean knob (switch or flag)."""
    k = KNOBS[name]
    if k.kind not in ("switch", "flag"):
        raise TypeError(f"{name} is a {k.kind} knob, not a boolean")
    return get(name)


def get_int(name):
    return int(get(name))


def get_float(name):
    return float(get(name))


def get_str(name):
    v = get(name)
    return v if v is None else str(v)


# -- README table generation -------------------------------------------------

TABLE_BEGIN = "<!-- env-knobs:begin (generated by utils/env.py) -->"
TABLE_END = "<!-- env-knobs:end -->"


def _default_repr(k):
    if k.kind == "switch":
        return "on"
    if k.kind == "flag":
        return "off"
    if k.default is None:
        return "-"
    if k.kind == "str" and k.default == "":
        return "-"
    return str(k.default)


def readme_table():
    """The generated markdown knob table (without the begin/end markers).

    ``scripts/graftlint.py --fix-knob-table`` writes this between the
    markers in README.md; the ``env-docs`` lint rule fails when the
    committed table drifts from the registry.
    """
    lines = ["| Knob | Type | Default | Effect |", "|---|---|---|---|"]
    for section in _SECTIONS:
        knobs = [k for k in KNOBS.values() if k.section == section]
        if not knobs:
            continue
        lines.append(f"| **{section}** | | | |")
        for k in sorted(knobs, key=lambda k: k.name):
            lines.append(
                f"| `{k.name}` | {k.kind} | {_default_repr(k)} | {k.doc} |")
    return "\n".join(lines)


def splice_readme(text):
    """Return ``text`` with the region between the knob-table markers
    replaced by the current :func:`readme_table` output. Raises
    ValueError when the markers are missing or out of order."""
    begin = text.find(TABLE_BEGIN)
    end = text.find(TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        raise ValueError(
            f"README knob-table markers missing ({TABLE_BEGIN!r} ... "
            f"{TABLE_END!r})")
    head = text[:begin + len(TABLE_BEGIN)]
    tail = text[end:]
    return head + "\n" + readme_table() + "\n" + tail
