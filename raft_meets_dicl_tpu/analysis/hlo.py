"""Trace-time program auditor over the PR-7 compiled-program registry.

Static AST lint (``analysis.lint``) sees the source; this module sees
what XLA will actually run. For a registered :class:`compile.Program` it
lowers the jitted function and audits the result:

- **fingerprint stability** — the program is lowered *twice* and the
  canonicalized StableHLO (location metadata stripped) must hash
  identically. Nondeterministic lowering (iteration over an unordered
  container, a closure capturing fresh objects) makes every boot a
  persistent-cache miss and every AOT artifact unreachable — precisely
  the cold-start tax PR-7 exists to kill.
- **collective counts** — taken from the *compiled* (post-partitioner) HLO,
  where sharding constraints have become all-gather/all-reduce/
  reduce-scatter ops. This guards the PR-6 ZeRO contract: a sharded
  train step must contain its gather/reduce pair, and any multi-device
  step with zero cross-device ops means the gradient sync silently
  vanished.
- **f32 convolutions under a bf16 policy** — a mixed-precision model
  whose lowered graph still convolves in f32 lost its policy somewhere
  between Flax and XLA.
- **baked-in constants > 1 MiB** — closure-captured weights serialized
  into the program body: HBM paid per executable, AOT artifacts bloated,
  and the persistent cache keyed on tensor *values*.

The compile needed for the collective audit routes through jax's
persistent compile cache like any other — on a warm cache the audit
triggers zero fresh backend compiles (the acceptance bar for running it
in tier-1).
"""

import hashlib
import re

from .lint import Finding

# strip MLIR location metadata: `loc(...)` trailers and `#loc...` lines
_LOC_RE = re.compile(r"\s*loc\([^)]*\)")
_LOC_LINE_RE = re.compile(r"^#loc.*$", re.MULTILINE)

_STABLEHLO_COLLECTIVES = ("all_reduce", "all_gather", "all_to_all",
                          "reduce_scatter", "collective_permute")
_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(?:-start)?\b")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
    # sub-f32 widths the quantized matching tier (and any f8 recipe)
    # streams: counting these at the 4-byte unknown-dtype fallback would
    # erase exactly the HBM saving the tier exists for
    "f8e4m3": 1, "f8e3m4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1, "f8e8m0fnu": 1,
}

# sub-byte element widths in bits; byte counts round up per tensor
_DTYPE_BITS = {"i4": 4, "ui4": 4, "i2": 2, "ui2": 2}

_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z][a-z0-9]*)>")
_CONST_RE = re.compile(
    r"stablehlo\.constant[^:\n]*:\s*tensor<([0-9x]+)x([a-z]+[0-9]*)>")

LARGE_CONST_BYTES = 1 << 20  # 1 MiB


def strip_locations(text):
    """StableHLO text minus MLIR location metadata — the parts that may
    legitimately differ between two lowerings of the same program."""
    return _LOC_LINE_RE.sub("", _LOC_RE.sub("", text))


def fingerprint(text):
    """sha256 over the canonicalized module text."""
    return hashlib.sha256(strip_locations(text).encode()).hexdigest()


def _tensor_bytes(dims, dtype):
    n = 1
    for d in dims.split("x"):
        if d:
            n *= int(d)  # graftlint: disable=host-sync -- parses an HLO dims string, not a device value
    if dtype in _DTYPE_BITS:
        return (n * _DTYPE_BITS[dtype] + 7) // 8
    return n * _DTYPE_BYTES.get(dtype, 4)


def audit_stablehlo(text):
    """Counts over a lowered StableHLO module's text."""
    collectives = {}
    for op in _STABLEHLO_COLLECTIVES:
        n = text.count(f"stablehlo.{op} ") + text.count(f"stablehlo.{op}(")
        if n:
            collectives[op.replace("_", "-")] = n

    f32_convs = 0
    for line in text.splitlines():
        if "stablehlo.convolution" not in line:
            continue
        _, _, result = line.rpartition("->")
        m = _TENSOR_RE.search(result)
        if m and m.group(2) == "f32":
            f32_convs += 1

    large = []
    for m in _CONST_RE.finditer(text):
        nbytes = _tensor_bytes(m.group(1), m.group(2))
        if nbytes > LARGE_CONST_BYTES:
            large.append({"type": f"tensor<{m.group(1)}x{m.group(2)}>",
                          "bytes": nbytes})

    return {"collectives": collectives, "f32_convolutions": f32_convs,
            "large_constants": large}


def audit_compiled(text):
    """Collective counts over compiled (post-partitioner) HLO text."""
    counts = {}
    for line in text.splitlines():
        if " = " not in line:
            continue
        for m in _HLO_COLLECTIVE_RE.finditer(line.split(" = ", 1)[1]):
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def audit_program(program, args, expect_bf16=False, n_devices=1,
                  expect_gather=False, do_compile=True, **cost_context):
    """Audit one registered program against concrete example args.

    Returns ``(report, findings)``. The program is lowered twice for the
    fingerprint-stability check; when ``do_compile``, the second lowering
    is compiled (persistent-cache eligible) and its post-partitioner HLO
    provides the collective counts.

    ``cost_context`` (``partitioner``/``params``) is accepted and unused:
    the builders below return one ``(program, args, audit_kwargs)`` list
    shared with ``analysis.cost``, whose collective-contract auditor
    consumes those keys.
    """
    path = "analysis/hlo"  # findings anchor to the audit, not a file
    key = program.key.canonical() if program.key else program.label

    lowered_a = program.lower(*args)
    text_a = lowered_a.as_text()
    lowered_b = program.lower(*args)
    text_b = lowered_b.as_text()

    fp_a, fp_b = fingerprint(text_a), fingerprint(text_b)
    stable = fp_a == fp_b

    report = {
        "key": key,
        "label": program.label,
        "fingerprint": fp_a,
        "fingerprint_stable": stable,
        **audit_stablehlo(text_a),
    }

    findings = []
    if not stable:
        findings.append(Finding(
            rule="hlo-fingerprint", path=path, line=1,
            message=f"{key}: two lowerings produced different StableHLO "
                    f"({fp_a[:12]} vs {fp_b[:12]}) — nondeterministic "
                    f"lowering defeats the persistent compile cache and "
                    f"the AOT store"))
    if expect_bf16 and report["f32_convolutions"]:
        findings.append(Finding(
            rule="hlo-f32-conv", path=path, line=1,
            message=f"{key}: {report['f32_convolutions']} f32 "
                    f"convolution(s) lowered under a bf16 policy"))
    for c in report["large_constants"]:
        findings.append(Finding(
            rule="hlo-const-bake", path=path, line=1,
            message=f"{key}: {c['bytes'] / 2**20:.1f} MiB constant "
                    f"{c['type']} baked into the program (closure-"
                    f"captured array? pass it as an argument)"))

    if do_compile:
        compiled = lowered_b.compile()
        comp_collectives = audit_compiled(compiled.as_text())
        report["compiled_collectives"] = comp_collectives
        total = sum(comp_collectives.values())
        if n_devices > 1 and total == 0:
            findings.append(Finding(
                rule="hlo-collectives", path=path, line=1,
                message=f"{key}: compiled for {n_devices} devices with "
                        f"ZERO collectives — cross-device sync (grad "
                        f"all-reduce / ZeRO gather) vanished"))
        if expect_gather and not (
                comp_collectives.get("all-gather")
                and (comp_collectives.get("reduce-scatter")
                     or comp_collectives.get("all-reduce"))):
            findings.append(Finding(
                rule="hlo-collectives", path=path, line=1,
                message=f"{key}: sharded-state step missing its ZeRO "
                        f"gather/reduce pair (got {comp_collectives})"))

    return report, findings


def build_flagship_programs(n_devices=2, shape=(48, 64), mesh2d=False):
    """Register the raft-baseline tiny-shape train + eval steps on a CPU
    mesh and return ``[(program, args, audit_kwargs)]`` for auditing.

    The flagship model configuration at tiny shapes, so the persistent
    compile cache and AOT store warmed by an earlier audit serve the
    next without fresh compiles.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import compile as programs, models, parallel

    flagship = {
        "name": "RAFT baseline", "id": "raft-baseline",
        "model": {"type": "raft/baseline", "parameters": {}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(flagship)
    model, loss = spec.model, spec.loss
    h, w = shape
    b = n_devices
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(b, h, w, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(b, h, w, 3).astype(np.float32))
    flow = jnp.asarray(rng.randn(b, h, w, 2).astype(np.float32))
    valid = jnp.asarray(np.ones((b, h, w), bool))

    model_args = {"iterations": 2}
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1],
                           **model_args)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))

    if mesh2d and n_devices >= 2 and n_devices % 2 == 0:
        mesh = parallel.make_mesh((n_devices // 2, 2))
        partitioner = parallel.Partitioner(mesh)
    else:
        mesh = parallel.data_mesh(n_devices)
        partitioner = None

    state = parallel.TrainState.create(variables, tx)
    state_sharding = None
    expect_gather = False
    if partitioner is not None:
        state = partitioner.shard_state(state)
        state_sharding = partitioner.state_shardings(state)
        expect_gather = parallel.partition.is_sharded(
            state_sharding.params)
    else:
        state = parallel.replicate(state, mesh)

    batch = parallel.shard_batch((img1, img2, flow, valid), mesh)

    train_key = programs.ProgramKey(
        kind="train_step", model="raft-baseline",
        flags=programs.flag_items(shape=(b, h, w), audit=1,
                                  mesh2d=bool(partitioner)))
    train_prog = parallel.make_train_step(
        model, loss, tx, mesh=mesh, model_args=model_args,
        state_sharding=state_sharding, donate=False, key=train_key)

    # make_eval_step extends caller keys with the effective model args
    # (the iterations-collision fix), so use the returned program rather
    # than re-fetching the pre-extension key from the registry
    eval_key = programs.ProgramKey(
        kind="eval_step", model="raft-baseline",
        flags=programs.flag_items(shape=(b, h, w), audit=1))
    eval_prog = parallel.make_eval_step(model, mesh=mesh,
                                        model_args=model_args, key=eval_key)

    eval_variables = jax.device_put(
        variables, parallel.partition.replicated(mesh))

    out = []
    out.append((train_prog, (state, *batch),
                {"n_devices": n_devices, "expect_gather": expect_gather,
                 "partitioner": partitioner,
                 "params": variables["params"]}))
    out.append((eval_prog, (eval_variables, batch[0], batch[1]),
                {"n_devices": n_devices}))
    return out


def build_ladder_programs(rungs=(2, 4, 6), shape=(48, 64), batch=1,
                          mixed_precision=True):
    """Register every iteration-ladder rung program of a tiny
    mixed-precision raft model and return ``[(program, args,
    audit_kwargs)]`` for auditing.

    The ladder contract the audit pins: each rung the ladder executes —
    base, distinct continuation increments, monolithic full budget — is
    exactly one registered program (one ``ProgramKey`` flag variant),
    however many latency classes or batch fill levels ride it; each
    lowers fingerprint-stably (else every boot misses the AOT store);
    and the bf16 policy survives into the rung graphs (no f32
    convolutions).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import evaluation, models
    from ..serve.ladder import LadderSpec

    cfg = {
        "name": "ladder audit", "id": "ladder-audit",
        "model": {"type": "raft/baseline",
                  "parameters": {"corr-levels": 2, "corr-radius": 2,
                                 "corr-channels": 32,
                                 "context-channels": 16,
                                 "recurrent-channels": 16,
                                 "mixed-precision": mixed_precision}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(cfg)
    model = spec.model
    h, w = shape
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iterations=1)

    lad = LadderSpec(rungs=rungs)
    base = evaluation.make_rung_fn(model, lad.rungs[0], model_id=spec.id)
    # one base execution provides correctly-shaped carries for the
    # continuation rungs' example args
    _, state = base(variables, img1, img2)

    kwargs = {"expect_bf16": mixed_precision, "n_devices": 1}
    entries = [(base, (variables, img1, img2), dict(kwargs))]
    for its, cont in lad.programs():
        if (its, cont) == (lad.rungs[0], False):
            continue
        prog = evaluation.make_rung_fn(model, its, cont=cont,
                                       model_id=spec.id)
        args = ((variables, img1, img2, state["flow"], state["hidden"])
                if cont else (variables, img1, img2))
        entries.append((prog, args, dict(kwargs)))
    return entries


def build_warm_programs(rungs=(2, 4, 6), shape=(48, 64), batch=1,
                        mixed_precision=True):
    """Register the video warm-start program variants of the ladder-audit
    model and return ``[(program, args, audit_kwargs)]`` for auditing.

    The warm-start contract the audit pins: each rung has at most *one*
    warm variant — one registered program per (rung, warm) pair, keyed
    only by the added ``warm`` flag, so the plain ladder keys (and their
    pinned budgets) are untouched; each lowers fingerprint-stably; and
    the in-program forward projection does not break the bf16 policy
    (no f32 convolutions). The cost delta vs. the plain rung — the
    projection's gather/compare overhead — is pinned by graftcost.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import evaluation, models
    from ..serve.ladder import LadderSpec

    cfg = {
        "name": "ladder audit", "id": "ladder-audit",
        "model": {"type": "raft/baseline",
                  "parameters": {"corr-levels": 2, "corr-radius": 2,
                                 "corr-channels": 32,
                                 "context-channels": 16,
                                 "recurrent-channels": 16,
                                 "mixed-precision": mixed_precision}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(cfg)
    model = spec.model
    h, w = shape
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iterations=1)

    lad = LadderSpec(rungs=rungs)
    # the carry a warm program consumes is the coarse-grid flow the
    # plain base rung produces — run it once for a correctly-shaped
    # example arg
    base = evaluation.make_rung_fn(model, lad.rungs[0], model_id=spec.id)
    _, state = base(variables, img1, img2)

    kwargs = {"expect_bf16": mixed_precision, "n_devices": 1}
    entries = []
    warm = evaluation.make_warm_fn(model, lad.rungs[0], model_id=spec.id)
    entries.append((warm, (variables, img1, img2, state["flow"]),
                    dict(kwargs)))
    return entries


def build_quant_programs(rungs=(2, 4, 6), shape=(48, 64), batch=1,
                         mixed_precision=True):
    """Register the quantized matching-tier program variants of the
    ladder-audit model and return ``[(program, args, audit_kwargs)]``
    for auditing.

    The quant contract the audit pins: the u8 and i8 base rungs plus the
    u8 warm variant are each exactly one registered program, keyed only
    by the added ``quant`` flag (plain ladder/warm keys and their pinned
    budgets untouched); each lowers fingerprint-stably; the bf16 policy
    survives (the dequantized lookup runs bf16, not f32); and — the
    tier's reason to exist — the sub-f32 volume bytes show up in the
    pinned HBM traffic, which is what the integer-width byte accounting
    in ``cost._tensor_nbytes`` makes honest.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import evaluation, models
    from ..serve.ladder import LadderSpec

    cfg = {
        "name": "ladder audit", "id": "ladder-audit",
        "model": {"type": "raft/baseline",
                  "parameters": {"corr-levels": 2, "corr-radius": 2,
                                 "corr-channels": 32,
                                 "context-channels": 16,
                                 "recurrent-channels": 16,
                                 "mixed-precision": mixed_precision}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(cfg)
    model = spec.model
    h, w = shape
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iterations=1)

    lad = LadderSpec(rungs=rungs)
    kwargs = {"expect_bf16": mixed_precision, "n_devices": 1}
    entries = []
    for mode in ("u8", "i8"):
        prog = evaluation.make_rung_fn(model, lad.rungs[0], model_id=spec.id,
                                       quant=mode)
        entries.append((prog, (variables, img1, img2), dict(kwargs)))
    # the warm variant serves video warm frames on the quant tier; its
    # example carry is the quant base rung's coarse flow
    base = evaluation.make_rung_fn(model, lad.rungs[0], model_id=spec.id,
                                   quant="u8")
    _, state = base(variables, img1, img2)
    warm = evaluation.make_warm_fn(model, lad.rungs[0], model_id=spec.id,
                                   quant="u8")
    entries.append((warm, (variables, img1, img2, state["flow"]),
                    dict(kwargs)))
    return entries


def build_aug_programs(shape=(48, 64), batch=2):
    """Register the on-device data-engine program variants and return
    ``[(program, args, audit_kwargs)]`` for auditing.

    The PR-19 contract the audit pins: the augmented train step is
    exactly one registered program keyed only by the added ``augment``
    flag (the plain audit train key and its pinned budget untouched —
    ``augment=None`` returns the identical Program), and the jitted
    synthetic scenario generator registers as its own ``synth_pair``
    program, so its render cost is budgeted like any other device
    program instead of hiding in the input pipeline.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import compile as programs, models, parallel
    from ..data import synth
    from ..data.device_augment import DeviceAugment

    flagship = {
        "name": "RAFT baseline", "id": "raft-baseline",
        "model": {"type": "raft/baseline", "parameters": {}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(flagship)
    model, loss = spec.model, spec.loss
    h, w = shape
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(batch, h, w, 3).astype(np.float32))
    flow = jnp.asarray(rng.randn(batch, h, w, 2).astype(np.float32))
    valid = jnp.asarray(np.ones((batch, h, w), bool))
    sample_ids = jnp.asarray(np.arange(batch, dtype=np.uint32))

    model_args = {"iterations": 2}
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1],
                           **model_args)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))
    state = parallel.TrainState.create(variables, tx)

    # same fixed configuration as cfg/env/device-aug.yaml, so the pinned
    # program is the one a real --device-aug run compiles
    augment = DeviceAugment()
    key = programs.ProgramKey(
        kind="train_step", model="raft-baseline",
        flags=programs.flag_items(shape=(batch, h, w), audit=1,
                                  mesh2d=False))
    prog = parallel.make_train_step(
        model, loss, tx, model_args=model_args, donate=False, key=key,
        augment=augment)

    entries = [(prog, (state, img1, img2, flow, valid, sample_ids,
                       jnp.int32(0)),
                {"n_devices": 1})]

    # the synthetic generator: exact flow supervision rendered on device
    synth_key = programs.ProgramKey(
        kind="synth_pair", model="synth",
        flags=programs.flag_items(shape=(h, w), audit=1))
    synth_prog = programs.register_step(
        "synth_pair",
        jax.jit(lambda k: synth.render_pair(k, (h, w))),
        key=synth_key)
    entries.append((synth_prog, (jax.random.PRNGKey(0),),
                    {"n_devices": 1}))
    return entries


def audit_registry(entries=None, **build_kwargs):
    """Audit every (program, args, kwargs) entry; defaults to the
    flagship tiny-shape build. Returns ``(reports, findings)``."""
    if entries is None:
        entries = build_flagship_programs(**build_kwargs)
    reports, findings = [], []
    for program, args, kwargs in entries:
        rep, fnd = audit_program(program, args, **kwargs)
        reports.append(rep)
        findings.extend(fnd)
    return reports, findings


def render_reports(reports):
    """Human-readable audit section (CLI + telemetry_report reuse)."""
    out = ["== hlo audit =="]
    for r in reports:
        coll = r.get("compiled_collectives", r.get("collectives", {}))
        coll_s = (", ".join(f"{k}={v}" for k, v in sorted(coll.items()))
                  or "none")
        out.append(
            f"{r['key']}: fingerprint {r['fingerprint'][:12]} "
            f"({'stable' if r['fingerprint_stable'] else 'UNSTABLE'}), "
            f"collectives: {coll_s}, f32 convs: {r['f32_convolutions']}, "
            f"large consts: {len(r['large_constants'])}")
    return "\n".join(out)
