"""One serving replica: model + variables + warm compiled-program pool.

The session owns everything device-side: the model spec, its variables
(freshly initialized or checkpoint-restored), the registered eval program
(``evaluation.make_eval_fn`` with the stable model id, so the program
dedupes process-wide and round-trips the AOT store), and the warm pool —
one precompiled executable per (model, bucket, wire) triple at the serve
batch size. A replica prepared with :meth:`warm_pool` against a populated
AOT store serves its first request with zero compiles; without artifacts
it pays at most one compile per bucket, up front instead of on the first
unlucky request.
"""

import logging
import time

import numpy as np

from .. import evaluation, models, telemetry
from ..models.input import ShapeBuckets


def _wait(session, flow, called=None):
    """Block until ``flow`` is ready and stamp ``session.run_marks``. The
    dispatch span must cover device compute: the scheduler's only pipeline
    stage is this call, there is no async overlap to preserve. ``called``
    is when the (first) program call returned: now, unless the caller
    stamped it earlier."""
    import jax

    if called is None:
        called = time.perf_counter()
    jax.block_until_ready(flow)  # graftlint: disable=host-sync -- serving dispatch-span boundary
    session.run_marks = (called, time.perf_counter())
    return flow


class ServeSession:
    """Device-side half of the serving path.

    ``spec`` is a loaded ``models.ModelSpec``; ``buckets`` the canonical
    ``ShapeBuckets`` (explicit sizes required — the warm pool is built
    per bucket); ``wire`` an optional ``WireFormat`` (bound to the
    model's clip/range here). Submitted images are raw un-normalized f32;
    with a wire format they cross host→device compact and decode inside
    the jitted program, without one they are normalized on the host by
    :meth:`encode_image`.
    """

    def __init__(self, spec, buckets, wire=None, checkpoint=None,
                 batch_size=4, mesh=None, ladder=None, video=False,
                 quant=None):
        t_prepare = time.perf_counter()
        buckets = ShapeBuckets.from_config(buckets) \
            if not isinstance(buckets, ShapeBuckets) else buckets
        if buckets is None or not buckets.sizes:
            raise ValueError(
                "serving needs explicit bucket sizes ('HxW,...'): the "
                "warm program pool and admission control are per bucket")
        self.spec = spec
        self.model = spec.model
        self.input = spec.input
        buckets.check_compatible(self.input.padding)

        if wire is not None:
            wire = wire.bound(self.input.clip, self.input.range)
        self.wire = wire
        # requests pad raw pixels then encode/normalize, so bucket pad
        # constants translate into raw space (same as the wire loaders)
        self.buckets = buckets.raw_variant(self.input.clip, self.input.range)
        self.batch_size = int(batch_size)  # graftlint: disable=host-sync -- config scalar, not a device value
        self.mesh = mesh

        self.variables = self._init_variables(checkpoint)
        # a request reads the final flow alone (run() drops ``out``), so
        # the program asks the model for nothing else: Up8 runs on the
        # last iteration, batch b, and ``out == [final]``
        self.eval_fn = evaluation.make_eval_fn(
            self.model, {"final_only": True}, mesh=mesh, wire=wire,
            model_id=spec.id)

        # iteration ladder (ladder.LadderSpec): one registered rung
        # program per (iterations, cont) — base rung, continuation
        # increments, monolithic full budget — all ProgramKey flag
        # variants that dedupe/AOT like the plain eval program
        self.ladder = ladder
        # readiness for /healthz: flips once warm_pool() has compiled
        # (or AOT-loaded) every bucket's program — before that a request
        # would pay a cold compile the operator thinks was prepaid
        self.ready = False
        # (called, ready) of the last run*: perf_counter when the program
        # call returned (inputs handed over, execution enqueued) and when
        # block_until_ready returned — the batch trace's marks
        self.run_marks = None
        # quantized matching tier (RMD_QUANT / --quant, ops.quant): the
        # latency-critical programs — the fast class's base rung and the
        # video warm frames — run with quantized correlation volumes.
        # Continuation increments and the monolithic full budget stay
        # full-precision, so the balanced class escalates from the quant
        # base onto full-precision rungs exactly as the ladder threshold
        # already decides, and quality is untouched.
        from ..ops import quant as quant_ops

        self.quant = quant_ops.normalize_mode(quant)
        self._rung_fns = {}
        if ladder is not None:
            for its, cont in ladder.programs():
                q = (self.quant
                     if (not cont and its == ladder.rungs[0]) else None)
                self._rung_fns[(its, cont)] = evaluation.make_rung_fn(
                    self.model, its, cont=cont, mesh=mesh, wire=wire,
                    model_id=spec.id, quant=q)

        # video sessions (PR 15): one warm-start program per bucket set —
        # the fast rung re-entered from the previous frame's carry (the
        # projection lives inside the program; see make_warm_fn) — plus
        # its plain-rung twin for cold frames. With a ladder the bottom
        # rung doubles as the twin; ladderless sessions register one at
        # RMD_VIDEO_WARM_ITERATIONS.
        self.video = bool(video)
        self._warm_fn = None
        if video:
            from ..utils import env

            self.warm_iterations = (
                ladder.rungs[0] if ladder is not None
                else env.get_int("RMD_VIDEO_WARM_ITERATIONS"))
            self._warm_fn = evaluation.make_warm_fn(
                self.model, self.warm_iterations, mesh=mesh, wire=wire,
                model_id=spec.id, quant=self.quant)
            if (self.warm_iterations, False) not in self._rung_fns:
                self._rung_fns[(self.warm_iterations, False)] = \
                    evaluation.make_rung_fn(
                        self.model, self.warm_iterations, mesh=mesh,
                        wire=wire, model_id=spec.id, quant=self.quant)
        # set-up span: everything a replica builds before its warm pool
        telemetry.emit_span("prepare", t_prepare, time.perf_counter())

    @classmethod
    def from_config(cls, model_cfg, buckets, **kwargs):
        """Build from a model config mapping (full training configs
        accepted — their ``model`` section is used)."""
        if "strategy" in model_cfg:
            model_cfg = model_cfg["model"]
        return cls(models.load(model_cfg), buckets, **kwargs)

    def _init_variables(self, checkpoint):
        import jax

        # structure init at the smallest bucket; init wants the
        # normalized f32 contract, not the wire dtype
        h, w = self.buckets.sizes[0]
        dummy = self._normalize(np.zeros((1, h, w, 3), np.float32))
        # one program, not an eager pass: op by op a deep model's init is
        # hundreds of small compiles on a cold cache (dicl/baseline: 869 of
        # them, 13 minutes of a v5e replica's boot before its warm pool)
        variables = jax.jit(self.model.init)(jax.random.PRNGKey(0), dummy,
                                             dummy)
        if checkpoint is not None:
            from .. import strategy

            logging.info(f"loading checkpoint, file='{checkpoint}'")
            chkpt = strategy.Checkpoint.load(checkpoint)
            variables, _, _ = chkpt.apply(variables=variables)
        return variables

    def _normalize(self, img):
        lo, hi = self.input.clip
        rmin, rmax = self.input.range
        x = np.clip(np.asarray(img, np.float32), lo, hi)  # graftlint: disable=host-sync -- host-side raw request pixels, never a device array
        return (rmax - rmin) * x + rmin

    # -- request encoding (host, admission path) -----------------------------

    def encode_image(self, img):
        """Raw un-normalized image → what the program's inputs expect:
        wire dtype (decode runs inside the jit) or host-normalized f32."""
        if self.wire is not None:
            return self.wire.encode_image(img)
        return self._normalize(img)

    def image_dtype(self):
        return (self.wire.image_dtype() if self.wire is not None
                else np.dtype(np.float32))

    # -- device work (dispatch thread) ---------------------------------------

    def run(self, img1, img2):
        """One batch through the eval program; returns the final flow as
        a ready device array (NHWC, f32)."""
        _, flow = self.eval_fn(self.variables, img1, img2)
        return _wait(self, flow)

    def run_ladder(self, img1, img2, klass):
        """One batch through the ladder policy for ``klass``; returns
        ``(flow, info)`` — the final flow as a ready device array plus
        ``{"rungs", "iterations"}`` accounting.

        ``fast`` and ``quality`` are single programs (base rung /
        monolithic full budget). ``balanced`` chains continuation rungs:
        the ``(flow, hidden)`` carry stays on device between programs,
        only the per-sample ``delta`` norm crosses to the host — the
        decision point that makes escalation recompile-free.
        """
        lad = self.ladder
        if klass == "quality":
            flow, _ = self._rung_fns[(lad.rungs[-1], False)](
                self.variables, img1, img2)
            return _wait(self, flow), {"rungs": 1,
                                      "iterations": lad.rungs[-1]}

        flow, state = self._rung_fns[(lad.rungs[0], False)](
            self.variables, img1, img2)
        called = time.perf_counter()    # of the first rung's call
        executed, rungs = lad.rungs[0], 1
        if klass == "balanced":
            for inc in lad.increments():
                worst = float(np.max(np.asarray(state["delta"])))  # graftlint: disable=host-sync -- rung decision point: the host reads the convergence norm between programs
                if worst <= lad.threshold:
                    break
                flow, state = self._rung_fns[(inc, True)](
                    self.variables, img1, img2,
                    state["flow"], state["hidden"])
                executed += inc
                rungs += 1
        return _wait(self, flow, called), {"rungs": rungs,
                                          "iterations": executed}

    def run_video(self, img1, img2, carry=None):
        """One video-session batch; returns ``(flow, state, info)``.

        ``carry`` is the batch's previous-frame coarse flow (stacked
        per-member rows from the scheduler's session cache) — the warm
        program forward-projects it internally. ``carry=None`` runs the
        plain rung twin: a true cold start, bit-exact with what the warm
        program produces on an all-zero carry. ``state`` stays on device
        except what the caller fetches; the scheduler stores its
        ``flow`` rows back per client.
        """
        if not self.video:
            raise RuntimeError("run_video needs a video=True session")
        warm = carry is not None
        if warm:
            flow, state = self._warm_fn(self.variables, img1, img2, carry)
        else:
            flow, state = self._rung_fns[(self.warm_iterations, False)](
                self.variables, img1, img2)
        return _wait(self, flow), state, {
            "rungs": 1, "iterations": self.warm_iterations, "warm": warm}

    def fetch(self, flow):
        """Device flow → host numpy (the per-request ``device`` span)."""
        import jax

        return np.asarray(jax.device_get(flow))  # graftlint: disable=host-sync -- response must materialize on host

    def compiles(self):
        """Exact backend-compile count across the serve programs — the
        eval program plus every ladder rung and the video warm variant
        (registry Program counters; see
        evaluation._program_compile_counter)."""
        progs = [self.eval_fn, *self._rung_fns.values()]
        if self._warm_fn is not None:
            progs.append(self._warm_fn)
        return sum(getattr(p, "compiles", 0) for p in progs)

    # -- warm pool ------------------------------------------------------------

    def _warm_plan(self):
        """``(rung, program, takes)`` in warm-up order: every program a
        bucket needs, the label its outcome record carries, and which
        entries of the carry it is fed after the images. The carry is
        the base rung's state, so continuation rungs and the warm-start
        program get correct coarse shapes without knowing the model's
        hidden width or downsampling factor."""
        plan = [(None, self.eval_fn, ())]
        if self.ladder is not None:
            lad = self.ladder
            plan.append((f"base:{lad.rungs[0]}",
                         self._rung_fns[(lad.rungs[0], False)], ()))
            plan += [(f"cont:+{inc}", self._rung_fns[(inc, True)],
                      ("flow", "hidden"))
                     for inc in sorted(set(lad.increments()))]
            plan.append((f"full:{lad.rungs[-1]}",
                         self._rung_fns[(lad.rungs[-1], False)], ()))
        if self.video:
            # the cold plain-rung twin (with a ladder the base rung above
            # already covers it), then the warm-start program
            if self.ladder is None:
                plan.append((f"base:{self.warm_iterations}",
                             self._rung_fns[(self.warm_iterations, False)],
                             ()))
            plan.append((f"warm:{self.warm_iterations}", self._warm_fn,
                         ("flow",)))
        return plan

    def warm_pool(self):
        """Compile (or AOT-load) the program for every bucket at the
        serve batch size; returns one outcome record per (model, bucket,
        wire) triple — plus, with a ladder, one per (model, bucket,
        wire, rung): compiles / AOT hits / AOT saves / seconds.

        With a populated AOT store every record reports ``compiles=0,
        aot_hits=1``; a prebuild run (``serve --prebuild``) reports the
        saves it exported.
        """
        import jax
        import jax.numpy as jnp

        def counts(step):
            return {name: getattr(step, name, 0)
                    for name in ("compiles", "aot_hits", "aot_saves")}

        plan = self._warm_plan()
        outcomes = []
        for h, w in self.buckets.sizes:
            img = jnp.zeros((self.batch_size, h, w, 3), self.image_dtype())
            carry = None
            for rung, step, takes in plan:
                t0, before = time.perf_counter(), counts(step)
                out = step(self.variables, img, img,
                           *(carry[name] for name in takes))
                jax.block_until_ready(out)  # graftlint: disable=host-sync -- warm pool must finish before serving starts
                if rung is not None and carry is None:
                    carry = out[1]      # the base rung's state
                outcome = {
                    "model": self.spec.id,
                    "bucket": f"{h}x{w}",
                    "wire": (self.wire.describe() if self.wire is not None
                             else "f32 host-normalized"),
                    "batch": self.batch_size,
                    **{name: n - before[name]
                       for name, n in counts(step).items()},
                    "seconds": round(time.perf_counter() - t0, 4),
                }
                if rung is not None:
                    outcome["rung"] = rung
                if getattr(step, "quant", None):
                    outcome["quant"] = step.quant
                outcomes.append(outcome)
                telemetry.get().clock()
                telemetry.get().emit("serve", event="warmup", **outcome)
        self.ready = True
        return outcomes

    def program_fingerprint(self, klass=""):
        """Stable identity of the compiled program a batch of ``klass``
        rides (registry ProgramKey canonical form, flags included: the
        ``args`` flag says whether the final-only form ran) — the
        batch-trace field that lets a tail batch be tied to one
        executable."""
        fn = self.eval_fn
        if klass and self.ladder is not None:
            lad = self.ladder
            rung = lad.rungs[-1] if klass == "quality" else lad.rungs[0]
            fn = self._rung_fns.get((rung, False), fn)
        key = getattr(fn, "key", None)
        if key is not None:
            return key.canonical()
        return getattr(fn, "telemetry_label", "eval_step")
