"""What the readers of a coarse-to-fine ladder's phases share (``serve_warp_ms``,
``serve_context_ms``, ``serve_mnet_ms``, ``serve_matching_mb_per_batch``): the
phase and scope joins of ``_owners``, given only where the program states the
scope at all. A program from before the scopes (a parent commit's) names
``warp``, ``context`` and ``mnet`` nowhere: its whole ladder reads ``other``,
and a 0.0 there would say "no time in the warp" where nothing is known, so
the readers return nothing."""
from . import _owners

PROGRAM = "eval_step"


def _records(run):
    return [ev["owners"] for ev in run["events"]
            if ev["kind"] == "aot" and ev.get("event") == "owners"
            and ev.get("program") == PROGRAM]


def phase_ms(run, phase):
    """ms a served batch under ``phase``, if a record of the run names it."""
    if not any(phase in rec for rec in _records(run)):
        return None
    return _owners.phase_ms(run, "serve", phase)


def scope_ms(run, scope):
    """ms a served batch under ``scope``, if a record of the run names it."""
    if not any(scope in scopes for rec in _records(run)
               for scopes in rec.values()):
        return None
    return _owners.scope_ms(run, "serve", scope)


def notes(run, name):
    """The values of one trace-time note over the run's eval executables:
    carried by the ``compile`` event of a program this run traced and by the
    ``aot`` event that holds its executable (one a bucket; a compile and its
    save say the same twice, so the distinct values)."""
    if run["kind"] != "serve":
        return []
    return sorted({ev[name] for ev in run["events"]
                   if name in ev and (
                       ev["kind"] == "aot" and ev.get("program") == PROGRAM
                       or ev["kind"] == "compile"
                       and ev.get("label") == PROGRAM)})
