"""Evaluation runtime: per-sample generator over a jitted inference step.

TPU redesign of the reference evaluator (src/evaluation/evaluator.py:4-37):
the forward pass runs as one jitted function per batch shape (model output
pytree + final flow returned together), results are fetched to host once
per batch, then unbatched per sample — same yield contract as the
reference so eval commands/scripts iterate identically.
"""

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile as programs
from .. import telemetry, utils
from ..ops import quant as quant_ops
from ..ops import warp
from ..parallel.train import inference_step
from ..utils import env


@dataclass
class EvalSample:
    """One evaluated sample: inputs, ground truth, and model output.

    ``final`` is the finest full-resolution flow (H, W, 2); ``output`` is
    the model-specific raw output for this sample (what the loss consumes),
    already on host.
    """

    img1: np.ndarray
    img2: np.ndarray
    target: Optional[np.ndarray]
    valid: Optional[np.ndarray]
    final: np.ndarray
    output: Any
    meta: Any


@dataclass
class EvalRunStats:
    """Aggregate accounting for one evaluation/validation sweep.

    Tracks batches/samples per dispatch shape ("bucket"), the number of
    freshly compiled programs (read from the registry Program's exact
    per-program compile counter — 0 on warm jit/persistent/AOT caches),
    and the pad-waste ratio — the fraction of dispatched pixels that are
    padding (modulo/bucket pad plus batch fill). ``emit`` publishes the
    ``eval`` event into the active telemetry sink.
    """

    name: str = "eval"
    samples: int = 0
    batches: int = 0
    pad_samples: int = 0
    real_pixels: int = 0
    total_pixels: int = 0
    phases: Dict[str, float] = field(default_factory=dict)
    buckets: Dict[str, Dict[str, int]] = field(default_factory=dict)
    compiles: int = 0
    _t0: float = field(default_factory=time.perf_counter)

    def add_phase(self, phase, seconds):
        self.phases[phase] = self.phases.get(phase, 0.0) + seconds

    def add_batch(self, shape, samples, pad_samples, real_pixels, compiles=0):
        h, w = shape
        bucket = self._bucket(shape)
        bucket["batches"] += 1
        bucket["samples"] += samples
        bucket["compiles"] += compiles
        self.batches += 1
        self.samples += samples
        self.pad_samples += pad_samples
        self.compiles += compiles
        self.real_pixels += int(real_pixels)
        self.total_pixels += (samples + pad_samples) * h * w

    def add_warmup(self, shape, compiles):
        """Precompile-warmup compiles count toward the bucket's (and the
        run's) compile totals — they are the sweep's compile budget."""
        self._bucket(shape)["compiles"] += compiles
        self.compiles += compiles

    def _bucket(self, shape):
        key = f"{shape[0]}x{shape[1]}"
        return self.buckets.setdefault(
            key, {"batches": 0, "samples": 0, "compiles": 0})

    def pad_waste_ratio(self):
        if not self.total_pixels:
            return 0.0
        return 1.0 - self.real_pixels / self.total_pixels

    def samples_per_sec(self):
        dt = time.perf_counter() - self._t0
        return self.samples / dt if dt > 0 else 0.0

    def emit(self):
        tele = telemetry.get()
        if not tele.enabled or not self.batches:
            return
        tele.emit(
            "eval", name=self.name, samples=self.samples,
            batches=self.batches, seconds=round(time.perf_counter() - self._t0, 4),
            samples_per_sec=round(self.samples_per_sec(), 3),
            pad_samples=self.pad_samples, compiles=self.compiles,
            pad_waste_ratio=round(self.pad_waste_ratio(), 4),
            phases={k: round(v, 4) for k, v in self.phases.items()},
            buckets=self.buckets,
        )


def _real_pixels(meta, shape, samples):
    """Un-padded content pixels of a batch, from per-sample metadata
    extents; metadata without extents (plain test stubs) counts the full
    dispatch area, i.e. zero measured waste."""
    h, w = shape
    total = 0
    for m in meta:
        ext = getattr(m, "original_extents", None)
        if ext is None:
            total += h * w
        else:
            (y0, y1), (x0, x1) = ext
            total += (y1 - y0) * (x1 - x0)
    return total


def make_eval_fn(model, model_args=None, mesh=None, wire=None,
                 variables_sharding=None, model_id=None):
    """Registered eval program ``(variables, img1, img2) ->
    (raw_output, final_flow)``.

    With ``mesh`` the step runs SPMD like the training step: the batch
    shards on the leading axis over every mesh axis (reference wraps eval
    in nn.DataParallel, src/cmd/eval.py:144-145) — callers must pad
    batches to a multiple of the mesh size (``evaluate`` does). The
    shardings come from ``parallel.partition`` — the same place the train
    step gets them — so ``variables_sharding`` (e.g.
    ``Partitioner.variables_sharding(variables)``) lets eval consume
    model-sharded training params directly: they gather to replicated
    inside the step.

    ``wire`` (models.wire.WireFormat) accepts compact-dtype un-normalized
    images and decodes + normalizes them on device.

    ``raw_output`` is a program output like any other: a caller that
    reads ``final_flow`` alone (the serve session) passes
    ``{"final_only": True}`` in ``model_args`` and gets ``raw_output ==
    [final_flow]`` — its own program, its own key; ``evaluate`` hands
    ``raw_output`` on and keeps the full form.

    ``model_id`` names the model stably (config id string): the program
    then dedupes process-wide in the compile registry — the eval CLI, the
    warmup pass, and training validation all get the *same* program for
    the same (model, bucket, wire) triple, and repeated ``evaluate()``
    calls (a validation pass every N training steps) never re-trace the
    forward pass — and, when the AOT store is enabled, its per-shape
    executables round-trip through serialized artifacts so a repeat boot
    compiles nothing. Without it the program is keyed by object identity
    (process-local dedupe only). What cannot be keyed exactly (see
    ``compile.inference_key``) is built fresh each call.
    """
    model_args = dict(model_args or {})

    def body(variables, img1, img2):
        out = model.apply(variables, img1, img2, train=False, **model_args)
        result = model.get_adapter().wrap_result(out, img1.shape[1:3])
        return out, result.final()

    where = dict(mesh=mesh, wire=wire, variables_sharding=variables_sharding)
    key = programs.inference_key("eval_step", model, model_args,
                                 model_id=model_id, **where)
    return inference_step("eval_step", model, body, key, **where)


def _rung_program(model, iterations, variant, carry, extra_inputs, quant,
                  model_args, model_id, **where):
    """A fixed-``iterations`` program ``(variables, img1, img2, *extra)
    -> (final_flow, state)`` of kind ``rung_step``: what
    :func:`make_rung_fn` and :func:`make_warm_fn` share.

    ``variant`` holds the flags that tell the rungs of one ladder apart
    beside ``iterations`` (``cont``, ``warm``); ``carry(*extra)`` turns
    the program's ``extra_inputs`` into the model's ``flow_init`` /
    ``hidden_init`` keywords. The model is asked for the final flow
    only (``final_only``, in the key's ``args`` flag). The ``quant``
    flag is only present on quant programs, and the clip ratio
    (``RMD_QUANT_CLIP``) is read at build time and keyed only when
    non-default, so other keys, AOT artifacts and budget pins are
    untouched.
    """
    iterations = int(iterations)
    quant = quant_ops.normalize_mode(quant)

    # the caller's model arguments minus what the builder sets itself
    model_args = dict(model_args or {})
    for reserved in ("iterations", "flow_init", "hidden_init",
                     "return_state", "quant", "quant_clip"):
        model_args.pop(reserved, None)
    model_args["final_only"] = True

    flags = {"iterations": iterations, **variant}
    forward_args = dict(model_args, iterations=iterations, return_state=True)
    if quant is not None:
        quant_clip = float(env.get_float("RMD_QUANT_CLIP"))
        forward_args.update(quant=quant, quant_clip=quant_clip)
        flags["quant"] = quant
        if quant_clip != 1.0:
            flags["quant_clip"] = quant_clip

    def body(variables, img1, img2, *extra):
        out, state = model.apply(variables, img1, img2, train=False,
                                 **forward_args, **carry(*extra))
        result = model.get_adapter().wrap_result(out, img1.shape[1:3])
        return result.final(), state

    key = programs.inference_key("rung_step", model, model_args,
                                 model_id=model_id, **where, **flags)
    return inference_step(
        "rung_step", model, body, key, extra_inputs, **where,
        attrs={"iterations": iterations, **variant, "quant": quant})


def make_rung_fn(model, iterations, cont=False, mesh=None, wire=None,
                 variables_sharding=None, model_id=None, model_args=None,
                 quant=None):
    """Registered ladder-rung program: a fixed-``iterations`` inference
    step that returns the continuation carry alongside the final flow.

    - ``cont=False``: ``(variables, img1, img2) -> (final_flow, state)``
      — a base rung starting from zero flow.
    - ``cont=True``: ``(variables, img1, img2, flow, hidden) ->
      (final_flow, state)`` — a continuation rung re-entering the
      recurrence from a previous rung's carry (bit-exact: the models
      carry flow, not coords, across iterations).

    ``state`` is ``{"flow", "hidden", "delta"}`` — coarse-grid carry
    arrays (left on device; hand them to the next rung unfetched) plus a
    per-sample convergence norm the host reads *between* programs. Up8
    runs on the last iteration, batch b. Each (iterations, cont) pair is
    its own ``ProgramKey`` flag variant (kind ``rung_step``), so rungs
    dedupe process-wide, AOT-export, and prefetch like any other
    program; ``serve --prebuild`` exports the whole ladder this way.

    ``quant`` selects the quantized matching tier (``'u8'``/``'i8'``,
    see ``ops.quant``): the rung runs with a quantized correlation
    volume pyramid, registered as its own ``quant=...`` ProgramKey flag
    variant of the same kind.
    """
    cont = bool(cont)
    if cont:
        def carry(flow, hidden):
            return {"flow_init": flow, "hidden_init": hidden}
    else:
        def carry():
            return {}

    return _rung_program(
        model, iterations, {"cont": cont}, carry, 2 if cont else 0, quant,
        model_args, model_id, mesh=mesh, wire=wire,
        variables_sharding=variables_sharding)


def make_warm_fn(model, iterations, mesh=None, wire=None,
                 variables_sharding=None, model_id=None, model_args=None,
                 quant=None):
    """Registered temporal warm-start program for video sequences:
    ``(variables, img1, img2, flow) -> (final_flow, state)`` where
    ``flow`` is the *previous frame's* coarse flow (the ``state["flow"]``
    carry of any rung/warm program, unfetched).

    The previous flow is forward-projected to the current frame *inside*
    the program — ``warp_backwards(flow, -flow)`` approximates the
    forward splat as ``out(p) = flow(p - flow(p))`` with out-of-frame
    pixels masked to zero flow — and fed into ``flow_init``. The GRU
    hidden state is *not* re-initialised here (``hidden_init`` from a
    fresh context would break parity; cross-frame hidden carry rides the
    existing ``cont=True`` rung programs instead), so with ``flow=0`` the
    projection is exactly zero and the program is bit-exact vs the plain
    base rung — cache misses degrade to the cold path, never a different
    answer.

    Each (iterations, warm) pair is its own ``ProgramKey`` flag variant
    of kind ``rung_step`` (the ``warm=True`` flag is only present on
    warm programs, so existing rung keys/AOT artifacts/budget pins are
    untouched); warm programs dedupe, AOT-export, and prefetch like any
    rung, and ``serve --prebuild`` covers them via ``warm_pool()``.

    ``quant`` routes the warm program onto the quantized matching tier
    exactly like :func:`make_rung_fn` — video warm frames are the other
    latency-critical consumer of the quant tier, and with ``flow=0`` a
    quant warm program stays bit-exact versus the quant base rung (the
    parity argument above is mode-independent).
    """
    def carry(flow):
        flow = flow.astype(jnp.float32)
        init, _ = warp.warp_backwards(flow, -flow)
        return {"flow_init": init}

    return _rung_program(
        model, iterations, {"cont": False, "warm": True}, carry, 1, quant,
        model_args, model_id, mesh=mesh, wire=wire,
        variables_sharding=variables_sharding)


def _program_compile_counter(step):
    """Monotone compile counter for one step callable.

    Registry Programs carry an exact per-program count (incremented by
    the jax.monitoring listener on actual backend compiles, telemetry
    sink or not). Legacy callables fall back to the sink's label-
    qualified count, or — with no sink either — to a constant 0: never
    the old first-seen-shape guess of 1, which overcounted every sweep
    on a warm jit/persistent cache.
    """
    if hasattr(step, "compiles") and hasattr(step, "key"):
        return lambda: step.compiles
    tele = telemetry.get()
    if tele.enabled:
        label = getattr(step, "telemetry_label", "eval_step")
        return lambda: tele.counts().get(f"compile:{label}", 0)
    return lambda: 0


def warmup_eval_fn(eval_fn, variables, shapes, batch_size, wire=None,
                   stats=None):
    """Precompile an eval fn for every (H, W) bucket shape at
    ``batch_size`` before the sweep touches real data.

    Runs the jitted step on zero-filled dummies (one forward per shape) so
    the jit cache — and, where enabled, the persistent compile cache and
    AOT program store — is hot when the first real batch of each bucket
    arrives: a KITTI-like sweep then compiles nothing mid-epoch. Dummy
    images are created in the wire image dtype when a ``wire`` format is
    active.

    Warmup compiles are attributed through the registry Program's own
    counter, which tracks actual backend compiles even with telemetry
    disabled — so the sweep's ``compiles`` column reads 0 on a warm
    jit/persistent/AOT cache instead of overcounting one per shape (the
    pre-PR-7 fallback).
    """
    dtype = wire.image_dtype() if wire is not None else np.float32

    counter = _program_compile_counter(eval_fn)
    for h, w in shapes:
        t0 = time.perf_counter()
        c0 = counter()
        img = jnp.zeros((batch_size, int(h), int(w), 3), dtype)
        out = eval_fn(variables, img, img)
        jax.block_until_ready(out[1])
        if stats is not None:
            stats.add_phase("warmup", time.perf_counter() - t0)
            stats.add_warmup((int(h), int(w)), counter() - c0)


def evaluate(model, variables, data, model_args=None, show_progress=True,
             eval_fn=None, mesh=None, wire=None, pad_to=None, stats=None,
             variables_sharding=None):
    """Yield an ``EvalSample`` per dataset sample.

    ``data`` iterates batches ``(img1, img2, flow, valid, meta)`` in NHWC
    numpy (a ``models.input.Loader`` or any compatible iterable).
    Reference contract: src/evaluation/evaluator.py:4-37. Pass a prebuilt
    ``eval_fn`` (from ``make_eval_fn``) to control caching explicitly.

    With ``mesh`` the batch is sharded over the mesh's ``data`` axis;
    short batches are padded by repeating the last sample (padded outputs
    are dropped — only real samples are yielded). ``pad_to`` extends the
    same treatment to *every* short batch: partial batches (e.g. a
    bucket's epoch-end remainder under a shape-grouping loader) are
    filled up to a fixed batch size so they reuse the full batch's
    compiled program instead of compiling one per remainder size.

    With ``wire``, ``data`` must yield wire-format batches (an adapter
    built with the same WireFormat): images upload compact and decode on
    device; the yielded ``EvalSample.img1/img2`` are decoded back to the
    normalized f32 contract on the host.

    ``stats`` (an :class:`EvalRunStats`) accumulates throughput, per-shape
    batch/compile counts, and the pad-waste ratio; pass one to also emit
    the run's ``eval`` telemetry event via ``stats.emit()``.
    """
    adapter = model.get_adapter()
    step = (eval_fn if eval_fn is not None
            else make_eval_fn(model, model_args, mesh=mesh, wire=wire,
                              variables_sharding=variables_sharding))

    if show_progress:
        data = utils.logging.progress(data, unit="batch", leave=False)

    counter = _program_compile_counter(step)

    def dispatch(item):
        img1, img2, flow, valid, meta = item
        batch = img1.shape[0]

        target = batch
        if pad_to is not None:
            target = max(target, int(pad_to))
        if mesh is not None:
            n = mesh.devices.size
            target = -(-target // n) * n

        t0 = time.perf_counter()
        j1, j2 = jnp.asarray(img1), jnp.asarray(img2)
        pad = target - batch
        if pad:
            reps = [1] * (j1.ndim - 1)
            j1 = jnp.concatenate([j1, jnp.tile(j1[-1:], [pad] + reps)])
            j2 = jnp.concatenate([j2, jnp.tile(j2[-1:], [pad] + reps)])

        # compile accounting: the trace+compile happens synchronously
        # inside the step call, so a fresh dispatch shape that takes a
        # compile shows in the program's own counter delta — exact on
        # warm jit/persistent/AOT caches, where the pre-PR-7 first-seen-
        # shape fallback guessed 1 per shape
        c0 = counter()

        out, final = step(variables, j1, j2)
        compiles = counter() - c0

        if stats is not None:
            stats.add_phase("dispatch", time.perf_counter() - t0)
            stats.add_batch(
                img1.shape[1:3], batch, pad,
                _real_pixels(meta, img1.shape[1:3], batch),
                compiles=compiles,
            )
        return item, out, final

    def drain(dispatched):
        (img1, img2, flow, valid, meta), out, final = dispatched
        batch = img1.shape[0]
        t0 = time.perf_counter()
        if wire is not None:
            img1 = wire.decode_images_host(img1)
            img2 = wire.decode_images_host(img2)
        # device_get blocks the host, not the device — with the next
        # batch already dispatched (below) the result download and the
        # host-side metrics overlap its compute, instead of a strict
        # upload -> compute -> download serialization per batch
        out, final = jax.device_get((out, final))

        result = adapter.wrap_result(out, img1.shape[1:3])
        if stats is not None:
            stats.add_phase("drain", time.perf_counter() - t0)

        for b in range(batch):
            yield EvalSample(
                img1=img1[b],
                img2=img2[b],
                target=flow[b] if flow is not None else None,
                valid=valid[b] if valid is not None else None,
                final=np.asarray(final[b]),
                output=result.output(b),
                meta=meta[b],
            )

    # per-bucket liveness: long bucketed sweeps were silent between
    # warmup and the final ``eval`` event — emit one ``steptrace``
    # progress event (scope="eval") per finished bucket, reusing the
    # StepTrace phase vocabulary so /statusz and the report can show a
    # sweep heartbeat without per-batch events
    tele = telemetry.get()
    progress = {"bucket": None, "batches": 0, "samples": 0,
                "phases": {}, "t": time.perf_counter()}

    def bucket_progress(next_bucket):
        if stats is None or not tele.enabled:
            progress["bucket"] = next_bucket
            return
        if (progress["bucket"] is not None
                and stats.batches > progress["batches"]):
            now = time.perf_counter()
            phases = {k: round(v - progress["phases"].get(k, 0.0), 6)
                      for k, v in stats.phases.items()
                      if v - progress["phases"].get(k, 0.0) > 0}
            tele.emit("steptrace", scope="eval", name=stats.name,
                      step=stats.batches, bucket=progress["bucket"],
                      window=stats.batches - progress["batches"],
                      samples=stats.samples - progress["samples"],
                      phases=phases, total=round(now - progress["t"], 6))
            progress["t"] = now
        progress["bucket"] = next_bucket
        progress["batches"] = stats.batches
        progress["samples"] = stats.samples
        progress["phases"] = dict(stats.phases)

    pending = None
    for item in data:
        bucket = f"{item[0].shape[1]}x{item[0].shape[2]}"
        if bucket != progress["bucket"]:
            bucket_progress(bucket)
        dispatched = dispatch(item)
        if pending is not None:
            yield from drain(pending)
        pending = dispatched
    if pending is not None:
        yield from drain(pending)
    bucket_progress(None)
