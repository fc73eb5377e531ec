"""SummaryInspector: TensorBoard observability + validation-driven checkpoints.

Capability parity with the reference inspector stack
(src/inspect/summary.py:48-663), redesigned for the jitted training loop:

- train-batch metrics read the train step's aux outputs (loss, final flow,
  optionally gradients) instead of live module state; they are launched on
  the device with the step and read one step later, once the next step is
  launched too, so that the loop never waits for the step it just started,
- validation runs a memoized jitted forward+loss step per stage and reduces
  metrics host-side, then triggers ``CheckpointManager.create`` — the only
  place checkpoints are born during training, like the reference
  (src/inspect/summary.py:372-373),
- hooks declare ``needs_intermediates``/``needs_grads`` and the inspector
  provides both (auxiliary capture-intermediates forward at the hook's
  frequency; gradients compiled into the step's aux when requested).
"""

import logging
from collections import OrderedDict, defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics, strategy, telemetry, utils, visual
from ..strategy.inspector import Inspector
from .hooks import Hook
from .writer import SummaryWriter


class MetricsGroup:
    """Frequency-gated accumulate-and-reduce over train batches
    (src/inspect/summary.py:48-93). ``compute`` launches a micro-batch's
    metrics and keeps their scalars where they are computed; ``take``
    hands the step's unread scalars over, and ``reduce`` takes them back
    once they are fetched."""

    @classmethod
    def from_config(cls, cfg):
        return cls(
            int(cfg.get("frequency", 1)),
            str(cfg.get("prefix", "")),
            [metrics.Metric.from_config(m) for m in cfg.get("metrics", [])],
        )

    def __init__(self, frequency, prefix, mtx):
        self.frequency = frequency
        self.prefix = prefix
        self.metrics = mtx
        self.values = [defaultdict(list) for _ in self.metrics]

        # the metrics that are functions of the step's outputs alone run
        # as one program: one launch and one transfer of target and valid
        # a micro-batch, whatever their number
        self._traced = [m for m in self.metrics if m.traceable]
        self._launch = telemetry.instrument_jit(
            "train_metrics", jax.jit(self._compute_traced))

    def get_config(self):
        return {
            "frequency": self.frequency,
            "prefix": self.prefix,
            "metrics": [m.get_config() for m in self.metrics],
        }

    @property
    def wants_gradients(self):
        return any(m.type.startswith("grad-") for m in self.metrics)

    def reset(self):
        self.values = [defaultdict(list) for _ in self.metrics]

    def _compute_traced(self, estimate, target, valid, loss):
        return [m.compute(None, estimate, target, valid, loss)
                for m in self._traced]

    def compute(self, ctx_m, estimate, target, valid, loss):
        traced = iter(self._launch(estimate, target, valid, loss)
                      if self._traced else ())
        for i, metric in enumerate(self.metrics):
            vals = (next(traced) if metric.traceable
                    else metric.compute(ctx_m, estimate, target, valid, loss))
            for k, v in vals.items():
                self.values[i][k].append(v)

    def take(self):
        """The scalars launched since the last ``take``, unread."""
        values = [OrderedDict(v) for v in self.values]
        self.reset()
        return values

    def reduce(self, values):
        """{tag: float} of one step from its fetched ``take``."""
        result = OrderedDict()
        for metric, vals in zip(self.metrics, values):
            for k, v in metric.reduce(vals).items():
                result[f"{self.prefix}{k}"] = v
        return result


class ImagesSpec:
    @classmethod
    def from_config(cls, cfg):
        if cfg is None:
            return None
        return cls(cfg.get("frequency", 250), cfg.get("prefix", ""))

    def __init__(self, frequency, prefix):
        self.frequency = frequency
        self.prefix = prefix

    def get_config(self):
        return {"frequency": self.frequency, "prefix": self.prefix}


class CheckpointSpec:
    @classmethod
    def from_config(cls, cfg):
        keep = cfg.get("keep", {})
        return cls(
            cfg.get("path", "checkpoints"),
            cfg.get("name", "{id_model}-s{n_stage}_e{n_epoch}_b{n_steps}.ckpt"),
            cfg.get("compare", "{n_steps}"),
            keep.get("latest"),
            keep.get("best"),
        )

    def __init__(self, path, name, compare, keep_latest=None, keep_best=None):
        self.path = Path(path)
        self.name = name
        self.compare = [compare] if isinstance(compare, str) else list(compare)
        self.keep_latest = keep_latest
        self.keep_best = keep_best

    def get_config(self):
        return {
            "path": str(self.path),
            "name": self.name,
            "compare": self.compare,
            "keep": {"latest": self.keep_latest, "best": self.keep_best},
        }

    def build(self, id, base_path):
        return strategy.CheckpointManager(
            id, Path(base_path) / self.path, self.name, self.compare,
            self.keep_latest, self.keep_best,
        )


class ValidationMetricSpec:
    @classmethod
    def from_config(cls, cfg):
        return cls(
            metrics.Metric.from_config(cfg["metric"]),
            str(cfg.get("reduce", "mean")),
            bool(cfg.get("log", True)),
        )

    def __init__(self, metric, reduce, do_log):
        self.metric = metric
        self.reduce = reduce
        self.do_log = do_log

    def get_config(self):
        return {
            "reduce": self.reduce,
            "log": self.do_log,
            "metric": self.metric.get_config(),
        }

    def build(self):
        return ValidationMetric(self.metric, self.reduce, self.do_log)


class ValidationMetric:
    """Per-validation-run accumulator (src/inspect/summary.py:192-217)."""

    def __init__(self, metric, reduce, do_log):
        if reduce not in ("mean",):
            raise ValueError("unsupported reduction type")

        self.metric = metric
        self.reduce = reduce
        self.do_log = do_log
        self.values = defaultdict(list)

    def add(self, ctx_m, estimate, target, valid, loss):
        for k, v in self.metric(ctx_m, estimate, target, valid, loss).items():
            self.values[k].append(v)

    def result(self):
        return [(k, float(np.mean(vs, axis=0))) for k, vs in self.values.items()]


class ValidationImages:
    @classmethod
    def from_config(cls, cfg):
        return cls(cfg.get("enabled", True), cfg.get("prefix", "Validation/"))

    def __init__(self, enabled, prefix):
        self.enabled = enabled
        self.prefix = prefix

    def get_config(self):
        return {"enabled": self.enabled, "prefix": self.prefix}


class Validation:
    """Base: frequency int (steps) or 'epoch' | 'stage'."""

    type: Optional[str] = None
    frequency: Union[str, int]

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(
                f"invalid validation type '{cfg['type']}', expected '{cls.type}'"
            )

    @classmethod
    def from_config(cls, cfg):
        types = {StrategyValidation.type: StrategyValidation}
        return types[cfg["type"]].from_config(cfg)

    def __init__(self, frequency):
        if not isinstance(frequency, (str, int)):
            raise ValueError(
                "frequency must be either integer or one of 'epoch', 'stage'"
            )
        if isinstance(frequency, str) and frequency not in ("epoch", "stage"):
            raise ValueError(
                "frequency must be either integer or one of 'epoch', 'stage'"
            )
        self.frequency = frequency

    def get_config(self):
        raise NotImplementedError

    def run(self, log, ctx, writer, chkpt, stage, epoch):
        raise NotImplementedError


class StrategyValidation(Validation):
    """Runs the stage's validation datasets, logs + TB-writes reduced
    metrics, and creates a checkpoint with the metric dict
    (src/inspect/summary.py:276-434)."""

    type = "strategy"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(
            cfg["frequency"],
            bool(cfg.get("checkpoint", True)),
            str(cfg.get("tb-metrics-prefix", "")),
            [ValidationMetricSpec.from_config(m) for m in cfg.get("metrics", [])],
            ValidationImages.from_config(cfg.get("images", {})),
        )

    def __init__(self, frequency, checkpoint, tb_metrics_pfx, mtx, images):
        super().__init__(frequency)
        self.checkpoint = checkpoint
        self.tb_metrics_pfx = tb_metrics_pfx
        self.metrics = mtx
        self.images = images
        self._val_steps = {}

    def get_config(self):
        return {
            "type": self.type,
            "frequency": self.frequency,
            "checkpoint": self.checkpoint,
            "tb-metrics-prefix": self.tb_metrics_pfx,
            "metrics": [m.get_config() for m in self.metrics],
            "images": self.images.get_config(),
        }

    def _val_step(self, ctx, stage):
        """Memoized (variables, batch) → (final flow, loss).

        The forward pass is the SAME registered eval program the eval
        CLI and the warmup path build (``evaluation.make_eval_fn`` +
        compile registry, keyed by the stable model id): training
        validation no longer compiles a duplicate forward for a (model,
        bucket, wire) triple the process has already paid for, and a
        warm AOT store covers it too. Only the loss reduction — a small
        program over the forward's raw output — is validation-specific.
        The returned callable exposes ``programs`` (forward, loss) so
        the sweep's compile accounting reads exact per-program counters.
        """
        from .. import compile as programs, evaluation

        model_key = programs.static_args_key(stage.model_args)
        loss_key = programs.static_args_key(stage.loss_args)
        cacheable = model_key is not None and loss_key is not None
        key = (id(ctx.model), id(ctx.loss), model_key, loss_key)
        if cacheable and key in self._val_steps:
            return self._val_steps[key]

        model, loss_fn = ctx.model, ctx.loss
        model_args = dict(stage.model_args)
        loss_args = dict(stage.loss_args)

        fwd = evaluation.make_eval_fn(
            model, model_args, model_id=getattr(ctx, "model_id", None))

        lkey = None
        if cacheable:
            # loss identity: its config when it has one (stable — the
            # val_loss program then AOT-round-trips like the forward),
            # else pinned to the object (process-local dedupe only)
            try:
                loss_id = repr(loss_fn.get_config())
            except Exception:  # noqa: BLE001 - config-less test stubs
                loss_id = programs.unstable(loss_fn)
            lkey = programs.ProgramKey(
                kind="val_loss",
                model=getattr(ctx, "model_id", None)
                or programs.unstable(model),
                flags=programs.flag_items(
                    args=loss_key, model_args=model_key, loss=loss_id))
            lprog = programs.registry().get(lkey)
        else:
            lprog = None
        if lprog is None:
            def lstep(out, flow, valid):
                result = model.get_adapter().wrap_result(
                    out, flow.shape[1:3])
                return loss_fn(model, result.output(), flow, valid,
                               **loss_args)

            lprog = programs.register_step("val_loss", jax.jit(lstep),
                                           key=lkey)
            lprog._refs = (model, loss_fn)

        def step(variables, img1, img2, flow, valid):
            out, final = fwd(variables, img1, img2)
            return final, lprog(out, flow, valid)

        step.programs = (fwd, lprog)

        if cacheable:
            self._val_steps[key] = step
        return step

    def run(self, log, ctx, writer, chkpt, stage, epoch):
        if not stage.validation:
            log.warn("no validation data specified, skipping this validation step")
            return

        # multi-process: validation (and the checkpoint it triggers) is
        # primary-only — metrics, logs, and checkpoint writes are all
        # primary-owned, the val step emits no collectives to desync on,
        # and duplicating the full sweep on every worker is wasted compute
        if jax.process_count() > 1 and jax.process_index() != 0:
            return

        chkpmetrics = {}

        for i, val in enumerate(stage.validation):
            mtx = self._evaluate_one(ctx, writer, stage, val, epoch)
            kvmetrics = {}

            writer.set_fmtargs(dict(
                n_stage=stage.index,
                id_stage=stage.id.replace("/", "."),
                n_epoch=epoch,
                n_step=ctx.step,
                id_val=val.name,
            ))

            entries = []
            for m in mtx:
                res = m.result()
                kvmetrics |= dict(res)

                for k, v in res:
                    writer.add_scalar(self.tb_metrics_pfx + k, v, ctx.step)

                if m.do_log:
                    entries += [f"{k}: {v:.4f}" for k, v in res]

            if entries:
                log.info(f"validation ({val.name}): {', '.join(entries)}")

            # first run stores the main metrics; every run also under prefix
            if i == 0:
                chkpmetrics |= kvmetrics
            chkpmetrics |= {f"{val.name}:{k}": v for k, v in kvmetrics.items()}

        if self.checkpoint:
            chkpt.create(log, ctx, stage, epoch, ctx.step, chkpmetrics)

    def _evaluate_one(self, ctx, writer, stage, val, epoch):
        images = set(val.images) if self.images.enabled else set()
        mtx = [m.build() for m in self.metrics]
        step = self._val_step(ctx, stage)

        # shape buckets (ctx.eval_buckets): quantize mixed per-sample
        # resolutions onto canonical sizes and group same-bucket samples
        # into full batches — the val step then compiles at most one
        # program per bucket instead of one per distinct shape, and the
        # extended valid mask keeps padded pixels out of every masked
        # metric and loss
        buckets = getattr(ctx, "eval_buckets", None)
        input = ctx.input.apply(val.source, buckets=buckets).jax()
        data = input.loader(batch_size=val.batch_size, shuffle=False,
                            drop_last=False,
                            group_by_shape=buckets is not None,
                            **ctx.loader_args)

        desc = f"validation ({val.name}): stage {stage.index + 1}/{len(ctx.strategy.stages)}"
        if epoch is not None:
            desc += f", epoch {epoch + 1}/{stage.data.epochs}"
        desc += f", step {ctx.step}"
        samples = utils.logging.progress(data, unit="batch", leave=False, desc=desc)

        variables = ctx.train_variables()
        part = getattr(ctx, "partitioner", None)
        if jax.process_count() > 1 or (part is not None
                                       and part.model_size > 1):
            # params live as global-mesh (possibly model-sharded) arrays;
            # localize them (committed to a local device, not host numpy
            # — numpy leaves would re-upload per batch) so the
            # process-local validation jit can't trip the partitioner
            # into emitting global-mesh collectives the other processes
            # would never join, and never computes on partially
            # replicated layouts the val step's jit has no annotations
            # for
            variables = jax.device_put(jax.device_get(variables),
                                       jax.local_devices()[0])
        ctx_m = metrics.MetricContext(lr=ctx.last_lr, params=variables["params"])

        from ..evaluation import EvalRunStats
        stats = EvalRunStats(name=f"validation:{val.name}")
        # compile accounting: exact per-program counters from the
        # registered forward + loss programs (no first-seen-shape guess,
        # no overcount on warm caches)
        progs = getattr(step, "programs", ())

        def compile_count():
            return sum(p.compiles for p in progs)

        for i, (img1, img2, flow, valid, meta) in enumerate(samples):
            batch = img1.shape[0]
            pad = val.batch_size - batch if buckets is not None else 0
            if pad > 0:
                # epoch-end bucket remainder: fill up to the full batch
                # size (reusing that bucket's compiled program) with
                # repeats of the last sample whose valid mask is cleared,
                # so the masked metrics and loss provably ignore them
                img1 = np.concatenate([img1, np.repeat(img1[-1:], pad, 0)])
                img2 = np.concatenate([img2, np.repeat(img2[-1:], pad, 0)])
                flow = np.concatenate([flow, np.repeat(flow[-1:], pad, 0)])
                valid = np.concatenate(
                    [valid, np.zeros((pad,) + valid.shape[1:], bool)])

            c0 = compile_count()

            est, loss = step(
                variables, jnp.asarray(img1), jnp.asarray(img2),
                jnp.asarray(flow), jnp.asarray(valid),
            )
            est, loss = jax.device_get((est, loss))

            compiles = compile_count() - c0
            stats.add_batch(
                img1.shape[1:3], batch, pad,
                sum((m.original_extents[0][1] - m.original_extents[0][0])
                    * (m.original_extents[1][1] - m.original_extents[1][0])
                    for m in meta),
                compiles=compiles)

            for m in mtx:
                m.add(ctx_m, est, flow, valid, loss)

            for j in images:  # expected to be a small set
                j_min, j_max = i * val.batch_size, (i + 1) * val.batch_size
                if not (j_min <= j < j_max):
                    continue

                writer.set_fmtargs(dict(
                    n_stage=stage.index,
                    id_stage=stage.id.replace("/", "."),
                    n_epoch=epoch,
                    n_step=ctx.step,
                    img_idx=j,
                    id_val=val.name,
                ))
                write_images(writer, self.images.prefix, j - j_min, img1, img2,
                             flow, est, valid, meta, ctx.step)

        stats.emit()
        return mtx


class InspectorSpec:
    @classmethod
    def from_config(cls, cfg):
        return cls(
            [MetricsGroup.from_config(m) for m in cfg.get("metrics", [])],
            [Hook.from_config(h) for h in cfg.get("hooks", [])],
            ImagesSpec.from_config(cfg.get("images")),
            CheckpointSpec.from_config(cfg.get("checkpoints", {})),
            [Validation.from_config(v) for v in cfg.get("validation", [])],
            cfg.get("tensorboard", {}).get("path", "tb.{id_model}"),
        )

    def __init__(self, mtx, hooks, images, checkpoints, validation, tb_path):
        self.metrics = mtx
        self.hooks = hooks
        self.images = images
        self.checkpoints = checkpoints
        self.validation = validation
        self.tb_path = tb_path

    def get_config(self):
        return {
            "metrics": [g.get_config() for g in self.metrics],
            "hooks": [h.get_config() for h in self.hooks],
            "images": self.images.get_config() if self.images is not None else None,
            "checkpoints": self.checkpoints.get_config(),
            "validation": [v.get_config() for v in self.validation],
            "tensorboard": {"path": self.tb_path},
        }

    def build(self, id, base_path):
        base_path = Path(base_path)
        chkpts = self.checkpoints.build(id, base_path)

        args = {"id_model": id.replace("/", "_").replace("-", ".")}
        path = base_path / self.tb_path.format_map(args)
        logging.info(f"writing tensorboard summary to '{path}'")
        writer = SummaryWriter(path)

        insp = SummaryInspector(writer, self.metrics, self.hooks, self.images,
                                chkpts, self.validation)
        return insp, chkpts


class SummaryInspector(Inspector):
    def __init__(self, writer, mtx, hooks, images, checkpoints, validation):
        super().__init__()

        self.writer = writer
        self.metrics = mtx
        self.hooks = list(hooks)
        self.images = images
        self.checkpoints = checkpoints

        self.val_step = [v for v in validation if not isinstance(v.frequency, str)]
        self.val_epoch = [v for v in validation if v.frequency == "epoch"]
        self.val_stage = [v for v in validation if v.frequency == "stage"]

        self.batch_index = 0
        self._capture_fns = {}
        # the newest closed step's scalars, launched and not yet read:
        # (step, format arguments, each group's ``take``)
        self._unread = None

    @property
    def wants_gradients(self):
        """The training context compiles gradients into the step's aux
        output iff observability asks for them."""
        return (
            any(g.wants_gradients for g in self.metrics)
            or any(h.needs_grads for h in self.hooks)
        )

    def wants_host_images(self, step):
        """Pixel values are only read on intermediates-capture and
        image-dump steps — the wire-format trainer skips the host decode
        everywhere else."""
        if any(h.active and h.needs_intermediates
               and step % getattr(h, "frequency", 1) == 0
               for h in self.hooks):
            return True
        return (self.images is not None
                and step % self.images.frequency == 0)

    # -- hook phase management (src/inspect/summary.py:530-562) -------------

    def setup(self, log, ctx):
        for hook in self.hooks:
            hook.active = False
        for hook in self.hooks:
            if hook.when in ("training", "all"):
                hook.register(ctx, self.writer)

    def _pre_validation(self, log, ctx):
        for hook in self.hooks:
            if hook.when == "training":
                hook.active = False
            elif not hook.active:
                hook.register(ctx, self.writer)

    def _post_validation(self, log, ctx):
        for hook in self.hooks:
            if hook.when == "validation":
                hook.active = False
            elif not hook.active:
                hook.register(ctx, self.writer)

    # -- intermediates capture ----------------------------------------------

    def _capture_fn(self, ctx, stage):
        from ..compile import static_args_key

        args_key = static_args_key(stage.model_args)
        key = (id(ctx.model), ctx.model.frozen_batchnorm, args_key)
        if args_key is not None and key in self._capture_fns:
            return self._capture_fns[key]

        model = ctx.model
        args = model.arguments | stage.model_args

        def fn(variables, img1, img2):
            _, mutated = model.module.apply(
                variables, img1, img2, train=False,
                frozen_bn=model.frozen_batchnorm,
                capture_intermediates=True, mutable=["intermediates"], **args,
            )
            return mutated["intermediates"]

        fn = telemetry.instrument_jit("capture_intermediates", jax.jit(fn))

        if args_key is not None:
            self._capture_fns[key] = fn
        return fn

    def _run_intermediate_hooks(self, log, ctx, stage, img1, img2):
        hooks = [
            h for h in self.hooks
            if h.active and h.needs_intermediates
            and ctx.step % getattr(h, "frequency", 1) == 0
        ]
        if not hooks:
            return

        fn = self._capture_fn(ctx, stage)
        inter = jax.device_get(
            fn(ctx.train_variables(), jnp.asarray(img1), jnp.asarray(img2))
        )
        for h in hooks:
            h.on_intermediates(log, ctx, inter)

    # -- inspector callbacks -------------------------------------------------

    def _set_fmtargs(self, ctx, stage, epoch=None):
        self.writer.set_fmtargs(dict(
            n_stage=stage.index,
            id_stage=stage.id.replace("/", "."),
            n_epoch=epoch,
            n_step=ctx.step,
        ))

    def on_batch_start(self, log, ctx, stage, epoch, i, img1, img2, target,
                       valid, meta):
        self._set_fmtargs(ctx, stage, epoch)

    def on_batch(self, log, ctx, stage, epoch, i, img1, img2, target, valid,
                 meta, result, loss):
        final = result.final()
        grads = result.aux.get("grads") if hasattr(result, "aux") else None

        ctx_m = metrics.MetricContext(
            lr=ctx.last_lr,
            params=ctx.state.params if ctx.state is not None else None,
            grads=grads,
        )

        for m in self.metrics:
            if ctx.step % m.frequency != 0:
                continue
            m.compute(ctx_m, final, target, valid, loss)

        for h in self.hooks:
            if h.active and h.needs_grads and grads is not None:
                h.on_grads(log, ctx, grads)

        # first micro-batch only: under gradient accumulation ctx.step stays
        # constant across the group, and the capture forward is expensive
        if self.batch_index == 0:
            self._run_intermediate_hooks(log, ctx, stage, img1, img2)

        # dump images (first sample, first micro-batch when accumulating)
        if (self.images is not None and ctx.step % self.images.frequency == 0
                and self.batch_index == 0):
            write_images(self.writer, self.images.prefix, 0, img1, img2,
                         target, np.asarray(final), valid, meta, ctx.step)

        self.batch_index += 1

    def on_step_start(self, log, ctx, stage, epoch, i):
        self.batch_index = 0
        for m in self.metrics:
            m.reset()

    def _write_scalars(self, counter):
        """Read the held step's scalars in one fetch and write them under
        that step's own index and format arguments."""
        unread, self._unread = self._unread, None
        if unread is None:
            return
        step, fmtargs, taken = unread
        taken = metrics.functional.fetch_scalars(taken)

        current = self.writer.fmt.fmtargs
        self.writer.set_fmtargs(fmtargs)
        for m, values in zip(self.metrics, taken):
            for k, v in m.reduce(values).items():
                self.writer.add_scalar(k, v, step)
        self.writer.set_fmtargs(current)
        telemetry.get().add_count(counter, 1)

    def flush(self):
        self._write_scalars("scalars_flushed")
        # down to the event file: a run that stops here leaves its last
        # steps readable
        self.writer.flush()

    def on_step_end(self, log, ctx, stage, epoch, i):
        # this step's program is launched, so reading the step before it
        # stalls nothing; this step's own scalars wait for the next one
        self._write_scalars("scalars_late")
        self._unread = (ctx.step, self.writer.fmt.fmtargs,
                        [m.take() for m in self.metrics])

        # mirror the newest telemetry step record into the TB scalars, so
        # phase timings sit next to the training curves without opening
        # the JSONL. A step's record is emitted once the step has closed
        # (this callback lies inside it), so it is the previous step's
        ev = telemetry.get().last_step
        if ev is not None and ev.get("step") == ctx.step - 1:
            for name, secs in ev["phases"].items():
                self.writer.add_scalar(f"Telemetry/Phase/{name}",
                                       secs * 1e3, ev["step"])
            self.writer.add_scalar("Telemetry/StepTimeMs",
                                   ev["step_time"] * 1e3, ev["step"])
            self.writer.add_scalar("Telemetry/StepsPerSecEma",
                                   ev["throughput_ema"], ev["step"])

        due = [v for v in self.val_step
               if ctx.step > 0 and ctx.step % v.frequency == 0]
        if due:
            self.flush()
            self._pre_validation(log, ctx)
            for val in due:
                val.run(log, ctx, self.writer, self.checkpoints, stage, epoch)
            self._post_validation(log, ctx)

    def on_epoch_start(self, log, ctx, stage, epoch):
        self._set_fmtargs(ctx, stage, epoch)

    def on_epoch(self, log, ctx, stage, epoch):
        if self.val_epoch:
            self._pre_validation(log, ctx)
            for val in self.val_epoch:
                val.run(log, ctx, self.writer, self.checkpoints, stage, epoch)
            self._post_validation(log, ctx)

    def on_stage_start(self, log, ctx, stage):
        self._set_fmtargs(ctx, stage)

    def on_stage(self, log, ctx, stage):
        if self.val_stage:
            self._pre_validation(log, ctx)
            for val in self.val_stage:
                val.run(log, ctx, self.writer, self.checkpoints, stage, None)
            self._post_validation(log, ctx)


def write_images(writer, pfx, i, img1, img2, target, estimate, valid, meta,
                 step, occlusion=None, confidence=None):
    """Un-pad, color-code, and write one sample's images to TB
    (src/inspect/summary.py:666-705). Inputs are NHWC host arrays.

    ``occlusion``/``confidence`` are optional forwards-backwards product
    maps (NHW); when provided they are written as extra images under the
    same prefix, so existing TB mirrors see exactly the original four
    tags unless a caller opts in."""
    (h0, h1), (w0, w1) = meta[i].original_extents

    i1 = (np.asarray(img1[i]) + 1.0) / 2.0
    i2 = (np.asarray(img2[i]) + 1.0) / 2.0
    ft = np.asarray(target[i])
    fe = np.asarray(estimate[i])
    mask = np.asarray(valid[i], bool)

    i1, i2 = i1[h0:h1, w0:w1], i2[h0:h1, w0:w1]
    ft, fe = ft[h0:h1, w0:w1], fe[h0:h1, w0:w1]
    mask = mask[h0:h1, w0:w1]

    # shared motion scale across estimate and ground truth; invalid pixels
    # (masked out or non-finite, e.g. KITTI sparse-GT sentinels) must not
    # inflate or NaN the scale
    def motion_max(f, m=None):
        norm = np.linalg.norm(f, axis=-1)
        if m is not None:
            norm = norm[m]
        norm = norm[np.isfinite(norm)]
        return float(norm.max()) if norm.size else 0.0

    mrm = max(motion_max(ft, mask), motion_max(fe), 1e-5)

    ft = visual.flow_to_rgba(ft, mrm=mrm, mask=mask)
    fe = visual.flow_to_rgba(fe, mrm=mrm)

    writer.add_image(f"{pfx}img1", i1, step, dataformats="HWC")
    writer.add_image(f"{pfx}img2", i2, step, dataformats="HWC")
    writer.add_image(f"{pfx}flow-gt", ft, step, dataformats="HWC")
    writer.add_image(f"{pfx}flow-est", fe, step, dataformats="HWC")

    if occlusion is not None:
        occ = np.asarray(occlusion[i], bool)[h0:h1, w0:w1]
        rgba = visual.occlusion_overlay(i1, occ)
        writer.add_image(f"{pfx}fwbw-occlusion", rgba, step,
                         dataformats="HWC")
    if confidence is not None:
        conf = np.asarray(confidence[i])[h0:h1, w0:w1]
        rgba = visual.confidence_to_rgba(conf)
        writer.add_image(f"{pfx}fwbw-confidence", rgba, step,
                         dataformats="HWC")
