"""Unified telemetry: JSONL event sink, marks on one clock, run reports.

See ``core`` for the sink/schema and ``report`` for rendering. Typical
producer usage::

    from .. import telemetry

    tele = telemetry.activate(telemetry.create(run_dir / "events.jsonl"))
    tele.emit("run_start", dir=str(run_dir))
    with telemetry.interval("model_load"):      # a ``span`` event
        spec = models.load(cfg)
    strace = telemetry.steptrace.StepTrace(step).mark("start")
    ...                                         # one mark per boundary
    tele.step_event(step, phases=strace.phases(), marks=strace.marks)

``RMD_TELEMETRY=0`` turns every call into a no-op (``create`` returns the
null sink and ``activate`` skips the jax.monitoring hookup).
"""

from . import (
    blackbox,
    core,
    goodput,
    metrics,
    report,
    sidecar,
    slo,
    steptrace,
    trace,
    witness,
)
from .core import (
    SCHEMA,
    SCHEMA_MINOR,
    SCHEMA_VERSION,
    NewerSchema,
    NullTelemetry,
    Telemetry,
    UnknownKind,
    activate,
    create,
    deactivate,
    emit_span,
    enabled,
    get,
    install_listeners,
    instrument_jit,
    interval,
    jit_label,
    memory_snapshot,
    note_trace,
    trace_site,
    validate_event,
)

__all__ = [
    "blackbox", "core", "goodput", "metrics", "report", "sidecar",
    "slo", "steptrace", "trace", "witness",
    "SCHEMA", "SCHEMA_MINOR", "SCHEMA_VERSION",
    "NewerSchema", "NullTelemetry", "Telemetry", "UnknownKind",
    "activate", "create", "deactivate", "emit_span", "enabled", "get",
    "install_listeners", "instrument_jit", "interval", "jit_label",
    "memory_snapshot", "note_trace", "trace_site", "validate_event",
]
