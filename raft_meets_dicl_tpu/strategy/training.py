"""The training loop: stages → epochs → instances, on a jitted SPMD step.

Control-flow parity with the reference TrainingContext
(src/strategy/training.py:17-325): resume arithmetic, ``mode='best'``
cross-stage checkpoint promotion, per-stage optimizer/scheduler rebuilds
(checkpoints restore weights-only at stage boundaries, full state
mid-stage), invalid-batch skipping, result validation with a ``failed``
checkpoint dump, and the 9-callback Inspector protocol.

The hot path is different by design: instead of eager torch ops, each
instance calls one jitted train step (parallel.make_train_step) that holds
the whole forward/backward/update program; gradient accumulation and
clipping live inside it as optax transforms. Per-instance host work is just
the scheduler tick, callbacks, and a scalar fetch (loss + finiteness).
"""

import time
from collections import deque
from datetime import datetime
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from .. import telemetry, utils
from ..data import device_augment
from ..telemetry import blackbox, goodput
from ..telemetry import steptrace as steptrace_mod
from ..parallel import (
    Partitioner, TrainState, batch_nbytes, make_train_step, shard_batch,
)
from ..testing import faults
from .checkpoint import Checkpoint, Iteration, State
from .spec import Stage, Strategy


class NonFinitePolicy:
    """What to do when a training step produces non-finite values.

    ``raise`` (default) preserves the historical behavior: dump a
    ``failed.ckpt`` and abort the run. ``skip`` compiles the
    skip-step discipline of dynamic loss scaling (Micikevicius et al.,
    *Mixed Precision Training*, 2018) into the train step: the poisoned
    optimizer update is dropped on device (params/opt state carry
    forward bit-identically) and training continues. ``rollback`` skips
    like ``skip`` but restores the last valid checkpoint once trips
    persist. Both escalate — ``max_consecutive`` consecutive tripped
    steps, or more than ``max_consecutive`` trips within a trailing
    ``window`` of steps, trigger the rollback (or, under ``skip`` /
    when no checkpoint survives, the abort), and ``max_rollbacks``
    bounds how often a rollback may fire before the run gives up.
    """

    POLICIES = ("raise", "skip", "rollback")

    def __init__(self, policy="raise", max_consecutive=3, window=50,
                 max_rollbacks=3):
        if policy not in self.POLICIES:
            raise ValueError(
                f"invalid non-finite policy '{policy}', expected one of "
                f"{list(self.POLICIES)}")
        self.policy = policy
        self.max_consecutive = max(1, int(max_consecutive))
        self.window = max(1, int(window))
        self.max_rollbacks = max(0, int(max_rollbacks))

    @classmethod
    def from_config(cls, cfg):
        """``None`` | policy name | mapping with ``policy`` /
        ``max-consecutive`` / ``window`` / ``max-rollbacks`` keys."""
        if cfg is None:
            return cls()
        if isinstance(cfg, str):
            return cls(cfg)
        if isinstance(cfg, cls):
            return cfg
        return cls(
            cfg.get("policy", "raise"),
            cfg.get("max-consecutive", cfg.get("max_consecutive", 3)),
            cfg.get("window", 50),
            cfg.get("max-rollbacks", cfg.get("max_rollbacks", 3)),
        )

    def get_config(self):
        return {
            "policy": self.policy,
            "max-consecutive": self.max_consecutive,
            "window": self.window,
            "max-rollbacks": self.max_rollbacks,
        }


def _device_prefetch(samples, put, depth=2):
    """Double-buffered host→device prefetch: pipeline batches onto the
    device ahead of consumption.

    The per-step host->device input transfer (tens of MB per batch)
    otherwise serializes with compute. A background
    thread loads and ``put``s up to ``depth`` batches ahead (default 2:
    batch N+1 transfers while step N executes); the main loop receives
    (host_batch, device_batch, meta, put_span, pull_span) with transfers
    already in flight. Loader exceptions re-raise at the consumption point.

    The stream is the plain iterator with ``put`` applied, item for item
    and in order; the training loop runs it at ``depth`` 2.

    ``put_span`` is the ``perf_counter`` ``(t0, t1)`` of the worker's
    ``put`` (wire encode + transfer initiation) of *this* batch: it rides
    through the queue with its batch and lands in the ``put`` field of the
    step that consumes it, up to ``depth`` steps later. ``pull_span`` is
    the ``(t0, t1)`` of the ``next()`` that handed the worker this batch
    (the loader's pulling thread: the wait for its workers and whatever
    it does to a batch itself) and lands in ``pull``; pull + put is the
    period of the one thread that feeds the device. The time the
    consumer blocks on the queue (the input pipeline failing to keep
    ahead of the device) is the step trace's ``start`` → ``data``.
    """
    import queue
    import threading

    q = queue.Queue(maxsize=depth)
    _END = object()

    def worker():
        try:
            for (img1, img2, flow, valid, meta), pull_span in _timed(samples):
                host = (img1, img2, flow, valid)
                t0 = time.perf_counter()
                dev = put(host)
                q.put((host, dev, meta, (t0, time.perf_counter()), pull_span))
        except BaseException as e:  # noqa: BLE001 - re-raised by consumer
            q.put((_END, e, None, None, None))
            return
        q.put((_END, None, None, None, None))

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        host, dev, meta, put_span, pull_span = q.get()
        if host is _END:
            if dev is not None:
                raise dev
            return
        yield host, dev, meta, put_span, pull_span


def _timed(samples):
    """Each item of ``samples`` with the ``perf_counter`` ``(t0, t1)`` of
    the ``next()`` that produced it."""
    samples = iter(samples)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(samples)
        except StopIteration:
            return
        yield item, (t0, time.perf_counter())


class _StepResult:
    """Minimal Result view over the train step's aux outputs."""

    def __init__(self, aux):
        self.aux = aux

    def final(self):
        return self.aux["final"]

    def output(self, batch_index=None):
        return self.aux["final"]

    def intermediate_flow(self):
        return [self.aux["final"]]


def _make_put(base_put, wire, tele):
    """Wrap the device-placement callable with wire encoding + accounting.

    With ``wire`` the batch's flow/valid are compressed here (images come
    wire-encoded from the adapter already) before ``base_put``; either way
    the actual transfer volume is recorded as the per-step ``wire_bytes``
    counter, so compression (or its absence) is visible in events.jsonl.
    """

    def put(batch):
        if wire is not None:
            batch = wire.encode_batch(batch)
        tele.add_count("wire_bytes", batch_nbytes(batch))
        return base_put(batch)

    return put


class TrainingContext:
    def __init__(self, log, path, strategy, model_id, model, model_adapter,
                 loss, input, inspector, checkpoints, mesh=None,
                 step_limit=None, loader_args={}, wire=None,
                 eval_buckets=None, nonfinite=None, partitioner=None,
                 accumulate=1, augment=None):
        self.root_log = log
        self.log = log
        self.path = Path(path)
        self.strategy = strategy
        self.model_id = model_id
        self.model = model
        self.model_adapter = model_adapter
        self.loss = loss
        self.input = input
        self.inspector = inspector
        self.checkpoints = checkpoints
        self.mesh = mesh
        # the partitioner maps params/optimizer state onto the mesh
        # (parallel.partition): replicated on the 1-D data mesh, sharded
        # over 'model' on a 2-D mesh. Everything that places or annotates
        # state asks it, so a layout change propagates everywhere at once.
        self.partitioner = (partitioner if partitioner is not None
                            else Partitioner(mesh) if mesh is not None
                            else None)
        # in-step gradient accumulation factor (make_train_step
        # accumulate=k): the loader batches k·B samples, the step scans k
        # microbatches of B and applies ONE optimizer update — k× the
        # effective batch at one microbatch's activation HBM. Orthogonal
        # to the per-stage optax.MultiSteps accumulation, which spreads
        # microbatches over k host steps instead.
        self.accumulate = max(1, int(accumulate))
        self.loader_args = dict(loader_args)
        # wire format (models.wire.WireFormat) for the host→device batch
        # transfer; bound to the input spec's clip/range per stage. None =
        # legacy host-normalized f32 batches.
        self.wire = (wire.bound(input.clip, input.range)
                     if wire is not None else None)
        # on-device augmentation (data.device_augment.DeviceAugment):
        # compiled into the train step as a ProgramKey flag variant, keyed
        # per (sample_id, epoch). Bound to the input spec's value range so
        # photometric math happens on [0, 1]. None = host-side (or no)
        # augmentation, historical step signature and program identity.
        self.augment = (augment.bound(tuple(input.range))
                        if augment is not None else None)
        # shape buckets for the validation passes (models.input.ShapeBuckets):
        # mixed-resolution validation sets batch per bucket and compile at
        # most one val-step program per bucket
        self.eval_buckets = eval_buckets

        # non-finite step recovery policy (NonFinitePolicy); counters are
        # reset per stage in run_stage
        self.nonfinite = NonFinitePolicy.from_config(nonfinite)
        self._nf_last_count = 0
        self._nf_consecutive = 0
        self._nf_window = deque()
        self._nf_rollbacks = 0
        # sample ids of recently dispatched batches — attached to
        # nonfinite events so a trip is reproducible offline even though
        # detection is amortized (up to _finite_every-1 steps late)
        self._recent_samples = deque(maxlen=32)

        # graceful-stop flag: set by the SIGTERM/SIGINT handlers (or
        # request_stop); the loop finishes the in-flight step, writes an
        # emergency checkpoint, and returns cleanly
        self._stop = None
        self._prev_handlers = {}

        self.validate = True

        self.step = 0
        self.step_limit = step_limit

        # observability plane (telemetry.sidecar.TrainObserver reads
        # these; all host-side, refreshed at the finite-check cadence)
        self.steptraces = steptrace_mod.StepTraceSummary()
        self.steps_completed = 0     # readiness = first step completed
        self._heartbeat_t = None     # step-loop liveness stamp
        self.last_norms = None       # (grad_norm, update_norm) floats
        self.last_loss = None        # loss of the last sampled step
        self._pending_scalars = None  # staged device scalars, unfetched
        self.last_memory = None      # latest memory_snapshot fields
        self.last_checkpoint = None  # (path, step) of the newest save

        # executed micro-batches within the current stage; drives the
        # accumulation boundary in lockstep with optax.MultiSteps (which
        # counts tx.update calls) so an invalid-batch skip costs one
        # micro-batch instead of desyncing host and device counters
        self._accum = 0
        self._in_step = False
        self._step_phases = {}       # phases of the open step's micro-batches

        # per-run / per-stage state
        self.variables = None       # model variables when no stage is active
        self.state: Optional[TrainState] = None
        self.tx = None
        self.scaler = None
        self.lr_sched_inst = None
        self.lr_sched_epoch = None
        self.data = None
        self.step_fn = None
        self.base_lr = 0.0
        self.current_stage = None
        self.current_epoch = None
        self.last_lr = 0.0

    # -- state accessors (used by CheckpointManager.create) ----------------

    def train_variables(self):
        if self.state is not None:
            return {"params": self.state.params,
                    "batch_stats": self.state.batch_stats}
        return self.variables

    def opt_state(self):
        return self.state.opt_state if self.state is not None else {}

    # -- preemption / graceful stop ----------------------------------------

    def install_signal_handlers(self):
        """Route SIGTERM/SIGINT into a graceful stop: the loop finishes
        the in-flight step, writes an emergency checkpoint, and returns
        cleanly (``--resume auto`` picks the run back up). The first
        signal arms the stop and restores the previous handler, so a
        second signal still kills a wedged run the hard way. Returns
        False when handlers can't be installed (non-main thread)."""
        import signal as _signal

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                self._prev_handlers[sig] = _signal.signal(sig, self._on_signal)
            except ValueError:
                self._prev_handlers.clear()
                return False
        return True

    def _on_signal(self, signum, frame):
        import signal as _signal

        self.request_stop(_signal.Signals(signum).name)
        prev = self._prev_handlers.pop(signum, None)
        if prev is not None:
            _signal.signal(signum, prev)

    def request_stop(self, reason="request"):
        """Arm the graceful stop (signal-handler and test entry point)."""
        self._stop = reason

    def heartbeat_age(self):
        """Seconds since the step loop last went around (sidecar
        liveness); 0.0 before the first instance starts."""
        if self._heartbeat_t is None:
            return 0.0
        return time.perf_counter() - self._heartbeat_t

    def _emergency_stop(self, log):
        """Write the preemption checkpoint and log how to resume."""
        reason = self._stop
        tele = telemetry.get()
        tele.emit("preempt", signal=str(reason), step=self.step,
                  stage=getattr(self.current_stage, "index", None),
                  epoch=self.current_epoch)

        if jax.process_count() > 1 and jax.process_index() != 0:
            log.warn(f"stop requested ({reason}): exiting (secondary process)")
            return None

        if self.train_variables() is None or self.current_stage is None:
            log.warn(f"stop requested ({reason}) before training started: "
                     "nothing to checkpoint")
            return None

        stage = self.current_stage
        epoch = self.current_epoch if self.current_epoch is not None else 0
        path_dir = Path(getattr(self.checkpoints, "path", None) or self.path)
        path_dir.mkdir(parents=True, exist_ok=True)
        path = path_dir / f"emergency-s{stage.index}_e{epoch}_b{self.step}.ckpt"

        log.warn(f"stop requested ({reason}): writing emergency checkpoint "
                 f"to '{path}'")
        t0 = time.perf_counter()
        self._snapshot_checkpoint(stage, epoch, source="emergency").save(path)
        tele.emit("checkpoint", path=str(path), step=self.step,
                  seconds=round(time.perf_counter() - t0, 4),
                  source="emergency")
        self.last_checkpoint = (path, self.step)
        # flight recorder: the ring survived the signal path (the handler
        # only sets _stop; the loop broke out normally), so the bundle
        # holds the last N steps exactly as the loop saw them
        blackbox.get().dump(path_dir, f"preempt-{reason}", tele=tele,
                            checkpoint=str(path), step=self.step)
        log.warn("emergency checkpoint written; resume with '--resume auto'")
        return path

    # -- initialization ----------------------------------------------------

    def _ensure_variables(self, stage):
        """Initialize model variables from the first stage's sample shape."""
        if self.variables is not None:
            return

        self.log.info("initializing model parameters")
        img1, img2, *_ = self.input.apply(stage.data.source).jax()[0]

        seed = int(np.random.randint(0, 2**31 - 1))
        if jax.process_count() > 1:
            # every process must initialize identical parameters (replicate
            # trusts but never verifies same-value-per-process): broadcast
            # process 0's seed
            from jax.experimental import multihost_utils

            seed = int(multihost_utils.broadcast_one_to_all(np.int32(seed)))
        rng = jax.random.PRNGKey(seed)
        init_args = dict(self.model.arguments)
        # keep tracing cheap: recurrent iteration counts don't affect params
        if "iterations" in init_args:
            init_args["iterations"] = (
                1 if isinstance(init_args["iterations"], int)
                else tuple(1 for _ in init_args["iterations"])
            )

        self.variables = self.model.init(
            rng, img1[:1], img2[:1], **init_args
        )

    # -- main loop ----------------------------------------------------------

    def run(self, start_stage=None, start_epoch=None, checkpoint=None):
        n_stages = len(self.strategy.stages)

        if start_stage is None and checkpoint is not None:
            start_stage = checkpoint.iteration.stage
        if start_stage is None:
            start_stage = 0

        assert 0 <= start_stage < n_stages

        if start_epoch is None and checkpoint is not None:
            start_epoch = checkpoint.iteration.epoch + 1
        if start_epoch is None:
            start_epoch = 0

        if checkpoint is not None:
            self.step = checkpoint.iteration.step

        backend = jax.default_backend()
        self.log.info(
            f"start training: running {n_stages} stages on backend "
            f"'{backend}' ({jax.device_count()} devices)"
        )

        self._ensure_variables(self.strategy.stages[start_stage])
        self.inspector.setup(self.log, self)

        for i, stage in list(enumerate(self.strategy.stages))[start_stage:]:
            # checkpoint created at end of a stage: skip to the next
            if start_epoch >= stage.data.epochs:
                start_epoch = 0
                continue

            self.log = self.root_log.new(f"stage {i + 1}/{n_stages}")
            self.log.info(
                f"starting new stage '{stage.name}' ({stage.id}) at step {self.step}"
            )

            stage.index = i
            self.run_stage(self.log, stage, start_epoch, checkpoint)

            start_epoch = 0
            checkpoint = None

            if self._stop:
                break
            if self.step_limit is not None and self.step >= self.step_limit:
                break

        self.log = self.root_log
        if self._stop:
            self._emergency_stop(self.log)
            self.log.info(
                f"training interrupted ({self._stop}) at step {self.step:,}; "
                "state saved for auto-resume"
            )
            return
        self.log.info(
            f"training loop complete, ran {self.step:,} steps over {n_stages} stages"
        )

    def prepare_stage(self, log, stage: Stage):
        if self.strategy.mode != "best":
            return

        # load_valid: a corrupt best checkpoint is quarantined and the
        # next-best valid one used instead of aborting the stage handoff
        found = self.checkpoints.load_valid(sort="best",
                                            stage=stage.index - 1, log=log)
        if found is None:
            return

        entry, chkpt = found
        log.info(f"loading best checkpoint from previous stage, file='{entry.path}'")
        self.variables, _, _ = chkpt.apply(variables=self.variables)

    def run_stage(self, log, stage: Stage, start_epoch=0, checkpoint=None):
        assert 0 <= start_epoch < stage.data.epochs

        # set-up spans: ``prepare`` (here to stage_start) and its
        # children ``data``, ``state``, ``step_build``
        t_prepare = time.perf_counter()
        self.current_stage = stage
        self.prepare_stage(log, stage)

        # data
        log.info(f"loading dataset: {stage.data.source.description()}")
        loader_args = self.loader_args | stage.loader_args

        # multi-host: the configured batch size is GLOBAL; each process
        # loads its slice (same-seed epoch order, strided shard) and the
        # global batch is assembled in parallel.shard_batch
        n_proc = jax.process_count()
        batch_size = stage.data.batch_size
        if self.mesh is not None and batch_size % self.mesh.devices.size:
            # fail with a config-level message before the sharded step
            # rejects the global array with a partitioner traceback
            raise ValueError(
                f"global batch size {batch_size} must be a multiple of the "
                f"mesh device count ({self.mesh.devices.size})"
            )
        # in-step accumulation: the loader hands the step k microbatches
        # at once; each step call is one optimizer update over k·B
        batch_size *= self.accumulate
        if n_proc > 1:
            if batch_size % n_proc:
                raise ValueError(
                    f"global batch size {batch_size} does not divide over "
                    f"{n_proc} processes"
                )
            batch_size //= n_proc
            loader_args.setdefault("shard", (jax.process_index(), n_proc))
            if "seed" not in loader_args:
                # all processes must draw the same epoch order; broadcast a
                # seed from process 0's (run-seeded) RNG so --reproduce
                # still governs data order
                from jax.experimental import multihost_utils

                seed = int(np.random.randint(0, 2**31 - 1))
                loader_args["seed"] = int(
                    multihost_utils.broadcast_one_to_all(np.int32(seed)))

        if self.wire is not None:
            log.info(f"wire format: {self.wire.describe()} "
                     "(device-side normalization)")
        input = self.input.apply(
            stage.data.source, normalize=self.wire is None,
        ).jax(wire=self.wire)
        self.data = input.loader(
            batch_size=batch_size,
            shuffle=stage.data.shuffle,
            drop_last=stage.data.drop_last,
            **loader_args,
        )
        log.info(
            f"dataset loaded: have {len(self.data)} batches over {len(input)} samples"
        )
        if len(input) == 0:
            # combinators tolerate empty sources so bare specs can load
            # without mounted data; actually training on nothing is a
            # config error and must fail fast
            raise ValueError(
                "dataset resolved to zero samples: "
                f"{stage.data.source.description()}"
            )

        t_data = time.perf_counter()
        telemetry.emit_span("data", t_prepare, t_data)

        # optimizer (fresh per stage, like the reference)
        log.info("setting up optimizer")
        self.tx, self.base_lr = stage.optimizer.build(stage.gradient)
        self.scaler = stage.gradient.scaler.build()

        sched_vars = {
            "n_samples": len(input),
            "n_batches": len(self.data),
            "n_epochs": stage.data.epochs,
            "n_accum": stage.gradient.accumulate,
            "batch_size": stage.data.batch_size,
        }
        self.lr_sched_inst, self.lr_sched_epoch = stage.scheduler.build(
            self.base_lr, sched_vars
        )

        # state: fresh optimizer, current weights
        self.state = TrainState.create(self.variables, self.tx)

        # restore checkpoint state: stage boundary (epoch 0) restores weights
        # only — optimizer/schedulers belong to the previous stage
        if checkpoint is not None:
            log.info("restoring data from checkpoint")
            if start_epoch == 0:
                variables, _, _ = checkpoint.apply(
                    variables=self.train_variables()
                )
                self.state = TrainState.create(variables, self.tx)
            else:
                variables, opt_state, self.scaler = checkpoint.apply(
                    variables=self.train_variables(),
                    opt_state=self.state.opt_state,
                    scaler=self.scaler,
                    lr_sched_inst=self.lr_sched_inst,
                    lr_sched_epoch=self.lr_sched_epoch,
                )
                self.state = self.state.replace(
                    params=variables["params"],
                    batch_stats=variables["batch_stats"],
                    opt_state=opt_state,
                )

        state_sharding = None
        if self.mesh is not None:
            # place the fresh state per the partition rules (replicated on
            # the 1-D mesh, params/moments sharded over 'model' on a 2-D
            # one) and publish the per-chip HBM accounting
            self.state = self.partitioner.shard_state(self.state)
            state_sharding = self.partitioner.state_shardings(self.state)
            telemetry.get().emit(
                "sharding", step=self.step, stage=stage.index,
                **self.partitioner.report(self.state))

        # stage hooks before building the step: freeze_batchnorm etc. are
        # baked into the compiled program
        self.model_adapter.on_stage(stage, **stage.model_on_stage_args)

        t_state = time.perf_counter()
        telemetry.emit_span("state", t_data, t_state)

        # gradients enter the step's aux output only if observability asks
        # (gradient metrics/hooks) — they cost a params-sized live buffer
        with_grads = bool(getattr(self.inspector, "wants_gradients", False))

        self.step_fn = make_train_step(
            self.model, self.loss, self.tx, mesh=self.mesh,
            loss_args=stage.loss_args, model_args=stage.model_args,
            external_lr=True, donate=True, with_grads=with_grads,
            wire=self.wire, state_sharding=state_sharding,
            accumulate=self.accumulate,
            # skip/rollback compile the on-device skip guard into the
            # step; raise keeps the unguarded update (NaNs absorbing)
            nonfinite="skip" if self.nonfinite.policy != "raise" else None,
            # stable program identity: registry dedupe across rebuilds
            # (resume/rollback in-process) and AOT artifact addressing —
            # a repeat boot of the same stage config starts stepping
            # without a single compile when the program store is warm
            key=self._train_step_key(stage, with_grads),
            augment=self.augment,
        )
        telemetry.emit_span("step_build", t_state, time.perf_counter())

        self._accum = 0
        self._in_step = False
        self._step_phases = {}
        self._pending_finite = None
        # non-finite recovery bookkeeping: the device counter restarts at
        # zero with the fresh TrainState, host mirrors follow
        self._nf_last_count = 0
        self._nf_consecutive = 0
        self._nf_window.clear()
        # finite-check cadence (steps); 1 restores the check-every-step
        # behavior for debugging
        self._finite_every = max(
            1, utils.env.get_int("RMD_FINITE_CHECK_EVERY"))

        # device-sync sampling bookkeeping: device step time is measured
        # at the finite-fetch cadence (the fetch is already a pipeline
        # drain), never per step — a per-step sync is the serialization
        # round 5 removed
        self._dispatched = 0
        self._last_sync_dispatched = 0
        self._last_sync_t = time.perf_counter()
        self._pending_scalars = None

        self.inspector.on_stage_start(log, self, stage)
        telemetry.emit_span("prepare", t_prepare, time.perf_counter())
        telemetry.get().clock()
        telemetry.get().emit(
            "stage_start", stage=stage.index, step=self.step,
            id=stage.id, name=stage.name, epochs=stage.data.epochs,
            batch_size=stage.data.batch_size,
        )

        log.info(f"running {stage.data.epochs} epochs")
        for epoch in range(start_epoch, stage.data.epochs):
            log_ = log.new(f"epoch {epoch + 1}/{stage.data.epochs}", sep=", ")
            log_.info(f"starting new epoch at step {self.step}")
            self.log = log_

            self.run_epoch(log_, stage, epoch)

            if self._stop:
                break
            if self.step_limit is not None and self.step >= self.step_limit:
                break

        self.log = log

        # sync live variables out of the stage state
        self.variables = self.train_variables()

        if self._stop:
            # preemption: skip the stage-end validation sweep — the
            # emergency checkpoint is the only artifact that matters now
            telemetry.get().emit("stage_end", stage=stage.index,
                                 step=self.step, interrupted=True)
            goodput.get().emit_event(telemetry.get(), stage=stage.index,
                                     step=self.step)
            return

        self.inspector.on_stage(log, self, stage)
        telemetry.get().emit("stage_end", stage=stage.index, step=self.step)
        goodput.get().emit_event(telemetry.get(), stage=stage.index,
                                 step=self.step)

    def _train_step_key(self, stage, with_grads):
        """Stable ``compile.ProgramKey`` for this stage's train step.

        Everything baked into the traced program is part of the identity:
        the full stage config (model/loss args, optimizer, gradient spec —
        hashed, the repr is long), wire format, mesh layout, the
        non-finite guard, accumulation, and the aux-gradients flag.
        Returns None when the stage config has no exact serialization
        (synthetic test sources): the step then registers anonymously —
        compile-counted but never deduped or AOT'd.
        """
        import hashlib

        from .. import compile as programs

        try:
            stage_cfg = repr(stage.get_config())
        except Exception:  # noqa: BLE001 - unserializable test stubs
            return None
        mesh_key = None
        if self.mesh is not None:
            mesh_key = (tuple(self.mesh.shape.items()),
                        tuple(d.id for d in self.mesh.devices.flat))
        # the augment flag exists only on the augmented variant: with
        # device augmentation off, the key (and thus program identity,
        # AOT artifact, and budget pin) stays byte-identical to before;
        # likewise the notes flag, for a model that counts revisions of
        # its trace-time notes
        aflags = programs.notes_flag(self.model)
        if self.augment is not None:
            aflags["augment"] = self.augment.describe()
        return programs.ProgramKey(
            kind="train_step", model=self.model_id,
            flags=programs.flag_items(
                stage=stage.id,
                config=hashlib.sha256(stage_cfg.encode()).hexdigest()[:16],
                wire=None if self.wire is None else self.wire.describe(),
                mesh=mesh_key,
                nonfinite=("skip" if self.nonfinite.policy != "raise"
                           else None),
                accumulate=self.accumulate,
                with_grads=with_grads,
                **aflags,
            ))

    def run_epoch(self, log, stage, epoch):
        self.current_epoch = epoch
        tele = telemetry.get()
        tele.emit("epoch_start", stage=stage.index, epoch=epoch,
                  step=self.step)

        desc = (
            f"stage {stage.index + 1}/{len(self.strategy.stages)}, "
            f"epoch {epoch + 1}/{stage.data.epochs}"
        )
        samples = utils.logging.progress(self.data, unit="batch", leave=False,
                                         desc=desc)

        self.model_adapter.on_epoch(stage, epoch, **stage.model_on_epoch_args)
        self.inspector.on_epoch_start(log, self, stage, epoch)

        # advance epoch-seeded host augmentation BEFORE the loader starts
        # iterating (decode workers fork per iteration, so they capture
        # the value); keyed per (sample_id, epoch) like the device path
        src = getattr(stage.data, "source", None)
        if src is not None and hasattr(src, "set_epoch"):
            src.set_epoch(epoch)

        base_put = ((lambda b: shard_batch(b, self.mesh))
                    if self.mesh is not None else jax.device_put)

        if (self.wire is None
                and getattr(getattr(self.model, "module", None),
                            "mixed_precision", False)
                and utils.env.get_bool("RMD_WIRE_BF16")):
            # legacy lightweight compression (pre-wire-format): the model
            # computes its encoders in bf16 anyway, so transferring the
            # host-normalized images as bf16 halves the dominant bytes
            # without changing effective numerics; flow/valid stay exact.
            # The full wire layer (--wire-format) subsumes this path.
            import jax.numpy as jnp

            def put(b, _base=base_put):
                img1, img2, flow, valid = b
                b = (np.asarray(img1, jnp.bfloat16),
                     np.asarray(img2, jnp.bfloat16), flow, valid)
                tele.add_count("wire_bytes", batch_nbytes(b))
                return _base(b)
        else:
            put = _make_put(base_put, self.wire, tele)

        # double-buffered prefetch: batch N+1's device_put runs on a
        # background thread while step N executes, so the transfer never
        # sits on the step critical path
        batches = _device_prefetch(samples, put, depth=2)

        it = enumerate(batches)
        while True:
            # per-step trace: one perf_counter clock whose marks bracket
            # the queue pull, so data_wait lands on the step that paid it
            strace = steptrace_mod.StepTrace(step=self.step)
            strace.mark("start")
            nxt = next(it, None)
            if nxt is None:
                break
            i, (host, dev, meta, strace.put, strace.pull) = nxt
            fetched = [m.fetch_s for m in meta if m.fetch_s is not None]
            if fetched:
                strace.fetch = sum(fetched) / len(fetched)
            strace.mark("data")

            log_ = log.new(f"step {self.step}", sep=", ")
            self.log = log_

            self.run_instance(log_, stage, epoch, i, host, dev, meta,
                              strace=strace)

            if self._stop:
                break
            if self.step_limit is not None and self.step >= self.step_limit:
                break

        self.log = log
        # the loop has stopped stepping (epoch end, step limit, requested
        # stop): nothing is launched behind the last step any more, and
        # validation, checkpoints and the run's end read what was written
        self.inspector.flush()
        self._flush_finite_check(log)

        # memory watermarks: a structured per-epoch event (snapshot cost
        # is one procfs read + a live-array census — epoch-boundary cheap)
        if tele.enabled:
            snap = telemetry.memory_snapshot()
            self.last_memory = snap
            tele.emit("memory", stage=stage.index, epoch=epoch,
                      step=self.step, **snap)

        if self._stop:
            # mid-epoch preemption: the epoch didn't complete, so neither
            # the epoch schedulers nor the epoch-end validation sweep run
            tele.emit("epoch_end", stage=stage.index, epoch=epoch,
                      step=self.step, interrupted=True)
            return

        for s in self.lr_sched_epoch:
            s.step()

        self.inspector.on_epoch(log, self, stage, epoch)
        tele.emit("epoch_end", stage=stage.index, epoch=epoch,
                  step=self.step, loss=self.last_loss)

    def _flush_finite_check(self, log):
        """Resolve the deferred finite flag of the epoch's last step
        before validation/checkpointing can observe a poisoned state."""
        prev, self._pending_finite = self._pending_finite, None
        if prev is not None:
            self._sample_scalars()
            self._resolve_finite(log, prev,
                                 "non-finite flow values detected")

    def _sample_scalars(self):
        """Fetch the staged loss and grad/update norm scalars: the loss
        for the ``device_sync``/``epoch_end`` events (the only place the
        event stream says what the steps computed), the norms for the
        gauges.

        Called only at the amortized finite-fetch cadence, where the
        pipeline is already drained by the finite flag — the extra
        scalar fetches ride the same sync, never adding one.
        """
        pending, self._pending_scalars = self._pending_scalars, None
        if pending is None:
            return
        loss, g, u = pending
        try:
            self.last_loss = float(loss)  # graftlint: disable=host-sync -- rides the amortized finite fetch, pipeline already drained
            self.last_norms = (
                None if g is None else float(g),  # graftlint: disable=host-sync -- rides the amortized finite fetch, pipeline already drained
                None if u is None else float(u))  # graftlint: disable=host-sync -- rides the amortized finite fetch, pipeline already drained
        except Exception:  # noqa: BLE001 - gauges must never kill a step
            self.last_loss = self.last_norms = None

    def _resolve_finite(self, log, prev, msg):
        """Apply the non-finite policy to one resolved finite fetch.

        ``prev`` is ``(finite_flag, stage, epoch, nonfinite_count)`` as
        staged by run_instance. Under ``raise`` this is the historical
        dump-and-abort. Under ``skip``/``rollback`` the poisoned updates
        were already dropped on device; here the host reads the
        cumulative skip counter, emits the telemetry trail, and
        escalates when trips persist (see NonFinitePolicy).
        """
        finite, stage, epoch, count = prev

        if self.nonfinite.policy == "raise":
            if not bool(finite):
                self._dump_failed(log, stage, epoch)
                raise RuntimeError(msg)
            return

        finite = bool(finite)
        count = int(count) if count is not None else 0
        trips = count - self._nf_last_count
        self._nf_last_count = count

        if trips <= 0:
            self._nf_consecutive = 0
            return

        # consecutive estimate: exact at RMD_FINITE_CHECK_EVERY=1; at a
        # larger cadence the latest step's flag decides whether the trip
        # streak is still live
        self._nf_consecutive = (self._nf_consecutive + trips if not finite
                                else 0)
        self._nf_window.append((self.step, trips))
        horizon = self.step - self.nonfinite.window
        while self._nf_window and self._nf_window[0][0] < horizon:
            self._nf_window.popleft()
        in_window = sum(t for _, t in self._nf_window)

        samples = [{"step": s, "samples": ids}
                   for s, ids in self._recent_samples]
        telemetry.get().emit(
            "nonfinite", step=self.step, stage=stage.index, epoch=epoch,
            action="skip", trips=trips, consecutive=self._nf_consecutive,
            window_trips=in_window, samples=samples,
        )
        log.warn(
            f"non-finite step: dropped {trips} optimizer update(s) "
            f"(policy '{self.nonfinite.policy}'; {in_window} trips in the "
            f"last {self.nonfinite.window} steps)")

        if (self._nf_consecutive < self.nonfinite.max_consecutive
                and in_window <= self.nonfinite.max_consecutive):
            return

        if self.nonfinite.policy == "rollback":
            self._rollback(log, stage, epoch)
            return

        self._dump_failed(log, stage, epoch)
        raise RuntimeError(
            f"non-finite steps persist under policy 'skip' "
            f"({self._nf_consecutive} consecutive, {in_window} within "
            f"{self.nonfinite.window} steps): aborting ({msg})")

    def _rollback(self, log, stage, epoch):
        """Restore the last valid checkpoint after persistent trips."""
        self.inspector.flush()
        self._nf_rollbacks += 1
        if self._nf_rollbacks > self.nonfinite.max_rollbacks:
            self._dump_failed(log, stage, epoch)
            raise RuntimeError(
                f"non-finite steps persist after "
                f"{self.nonfinite.max_rollbacks} rollbacks: aborting")

        found = (self.checkpoints.load_valid(sort="latest", log=log)
                 if self.checkpoints is not None else None)
        if found is None:
            self._dump_failed(log, stage, epoch)
            raise RuntimeError(
                "non-finite steps persist and no valid checkpoint exists "
                "to roll back to")

        entry, chkpt = found
        from_step = self.step
        log.error(
            f"non-finite steps persist: rolling back to '{entry.path}' "
            f"(step {chkpt.iteration.step})")

        try:
            variables, opt_state, self.scaler = chkpt.apply(
                variables=self.train_variables(),
                opt_state=self.state.opt_state,
                scaler=self.scaler,
                lr_sched_inst=self.lr_sched_inst,
                lr_sched_epoch=self.lr_sched_epoch,
            )
        except (KeyError, TypeError, ValueError):
            # optimizer structure mismatch (checkpoint from another
            # stage): weights-only restore, optimizer restarts fresh
            log.warn("rollback checkpoint has incompatible optimizer "
                     "state: restoring weights only")
            variables, _, _ = chkpt.apply(variables=self.train_variables())
            opt_state = self.tx.init(variables["params"])

        self.state = self.state.replace(
            params=variables["params"],
            batch_stats=variables.get("batch_stats", {}),
            opt_state=opt_state,
        )
        if self.mesh is not None:
            self.state = self.partitioner.shard_state(self.state)
        self.step = chkpt.iteration.step

        self._nf_consecutive = 0
        self._nf_window.clear()
        telemetry.get().emit(
            "nonfinite", step=self.step, stage=stage.index, epoch=epoch,
            action="rollback", path=str(entry.path), from_step=from_step,
            to_step=chkpt.iteration.step, rollbacks=self._nf_rollbacks,
        )

    def run_instance(self, log, stage, epoch, i, host, dev, meta,
                     strace=None):
        accumulate = stage.gradient.accumulate
        img1, img2, flow, valid = host

        self._heartbeat_t = time.perf_counter()
        if strace is None:
            # direct callers (tests) skip the run_epoch pull bracket:
            # start the clock here with an empty data_wait phase
            strace = steptrace_mod.StepTrace(step=self.step)
            strace.mark("start")
            strace.mark("data")

        # wire mode: host images are un-normalized wire dtype. Observers
        # that consume pixel values (TB image dumps, intermediates
        # capture) expect the normalized f32 contract — decode on the
        # steps where the inspector says it will actually look, so the
        # hot path never pays the second f32 copy
        if self.wire is not None and self._wants_host_images():
            img1 = self.wire.decode_images_host(img1)
            img2 = self.wire.decode_images_host(img2)

        if not self._in_step:
            self.inspector.on_step_start(log, self, stage, epoch, i)
            self._in_step = True

        # check for degeneracies in samples and warn/skip — the boundary is
        # driven by executed micro-batches, so a skip shifts the step by one
        # batch (like the reference's zero-grad-on-boundary) instead of
        # desyncing against the in-step MultiSteps counter
        if not all(m.valid for m in meta):
            log.warn("skipping batch due to invalid data")
            return

        # learning rate from the instance schedulers (last one wins, like
        # chained torch schedulers); epoch schedulers compose the base
        lr = self.base_lr
        for s in self.lr_sched_epoch:
            lr = s.lr()
        for s in self.lr_sched_inst:
            lr = s.lr()
        self.last_lr = lr

        if faults.active():
            if faults.fire("sigterm", step=self.step) is not None:
                import os as _os
                import signal as _signal

                log.warn(f"fault injection: SIGTERM at step {self.step}")
                _os.kill(_os.getpid(), _signal.SIGTERM)
            if faults.fire("nan_update", step=self.step) is not None:
                # NaN lr -> NaN update tree: the same poison a NaN
                # gradient produces after the optimizer, without
                # depending on model internals
                log.warn(f"fault injection: NaN update at step {self.step}")
                lr = float("nan")

        self._recent_samples.append(
            (self.step,
             [f"{m.dataset_id}/{m.sample_id}" for m in meta]))

        self.inspector.on_batch_start(log, self, stage, epoch, i, img1, img2,
                                      flow, valid, meta)

        # host prep done; the transfer itself was staged before the pull
        # returned (its interval is the trace's ``put``), so the
        # consumer-side put mark lands immediately
        strace.mark("prep")
        strace.mark("put")

        tele = telemetry.get()
        if self.augment is not None:
            # device augmentation: per-sample ids + the epoch scalar
            # key the on-device draws; ids derive from the metadata
            # so they are independent of shuffle order and resume
            ids = device_augment.sample_id_array(meta)
            self.state, aux = self.step_fn(
                self.state, lr, *dev, ids, np.int32(epoch))
        else:
            self.state, aux = self.step_fn(self.state, lr, *dev)
        self._dispatched += 1
        strace.mark("dispatched")

        # validate output, check for non-finite numbers — DEFERRED and
        # AMORTIZED: bool(finite) is a device->host fetch, and fetching
        # every freshly-dispatched step would drain the dispatch
        # pipeline once per step. Only the latest step's flag
        # is fetched, every _finite_every steps; NaNs/infs are absorbing
        # through the optimizer state (NaN grads -> NaN clip scale ->
        # NaN params), so a poisoned step always trips a later check —
        # detection just fires up to _finite_every-1 steps late, and
        # _flush_finite_check resolves the epoch's last step before
        # validation or checkpointing can observe the state.
        self._pending_scalars = (aux["loss"], aux.get("grad_norm"),
                               aux.get("update_norm"))
        if self.validate:
            self._pending_finite = (aux["finite"], stage, epoch,
                                    aux.get("nonfinite_count"))
            if (i + 1) % self._finite_every == 0:
                prev, self._pending_finite = self._pending_finite, None
                # the drain runs from the 'dispatched' mark: no second
                # stamp in front of the fetch
                finite = bool(prev[0])
                drain = time.perf_counter() - strace.marks["dispatched"]
                self._sample_scalars()
                self._emit_device_sync(tele, drain)
                self._resolve_finite(
                    log, (finite,) + prev[1:],
                    "non-finite flow values detected (flagged on a "
                    "later step than the producing one; the state "
                    "dump includes the poisoned updates)")
        elif tele.enabled and (i + 1) % self._finite_every == 0:
            # validation disabled: the finite fetch (our usual free sync
            # point) never happens, so sample the pipeline drain
            # explicitly at the same amortized cadence
            jax.block_until_ready(aux["loss"])
            drain = time.perf_counter() - strace.marks["dispatched"]
            self._sample_scalars()
            self._emit_device_sync(tele, drain)
        # device phase = how long the fetch above blocked (zero on the
        # amortized steps in between) — never an extra sync
        strace.mark("synced")

        loss = aux["loss"]

        # multi-process: aux["final"] is the GLOBAL batch array, but
        # host-side metrics compare against this process's local targets —
        # reassemble the local slice from the addressable shards (ordered
        # by their global offset; each process owns one contiguous stripe)
        if self.mesh is not None and jax.process_count() > 1:
            # dedupe by batch offset: on a 2-D mesh a batch range can
            # be materialized on more than one local device (model
            # axis), and each copy must contribute exactly once
            parts = {}
            for s in aux["final"].addressable_shards:
                parts.setdefault(s.index[0].start or 0,
                                 np.asarray(s.data))
            aux = aux | {"final": np.concatenate(
                [parts[k] for k in sorted(parts)])}

        result = _StepResult(aux)

        self.inspector.on_batch(log, self, stage, epoch, i, img1, img2,
                                flow, valid, meta, result, loss)

        self._accum += 1
        boundary = self._accum % accumulate == 0
        step = self.step
        if boundary:
            # the optimizer update itself happened inside the jitted step
            # (optax.MultiSteps applies on every accumulate-th call)
            for s in self.lr_sched_inst:
                s.step()

            self.inspector.on_step_end(log, self, stage, epoch, i)
            self.step += 1
            self.steps_completed += 1
            self._in_step = False

        # close the trace: every phase is a perf_counter diff on one
        # clock, so the record telescopes exactly to the step total
        strace.mark("done")
        rec = self.steptraces.add(strace)
        blackbox.get().record_step(rec)
        if tele.enabled:
            for name, seconds in strace.step_phases().items():
                self._step_phases[name] = (self._step_phases.get(name, 0.0)
                                           + seconds)
        if boundary:
            # the step's record: the marks of its (last) micro-batch, the
            # phases of all of them. Emitted once the step is closed, so
            # the inspector's callbacks lie inside ``synced`` → ``done``
            phases, self._step_phases = self._step_phases, {}
            fields = {k: rec[k] for k in ("put", "pull", "fetch", "cpu")
                      if k in rec}
            if "put" in fields and self.mesh is not None:
                # one put a step however many chips it feeds
                # (``shard_batch``: one device_put with a sharded layout)
                fields["devices"] = int(self.mesh.devices.size)
            tele.step_event(step, phases=phases, marks=rec["marks"],
                            stage=stage.index, epoch=epoch,
                            batch=stage.data.batch_size, **fields)
        if tele.enabled and (i + 1) % self._finite_every == 0:
            ev = self.steptraces.event(self.step)
            if ev is not None:
                tele.emit("steptrace", **ev)

    def _wants_host_images(self):
        """Whether the inspector will consume pixel values this step.

        Inspectors declare via ``wants_host_images(step)``; inspectors
        that predate the wire layer get decoded images on every step
        (correct, just not free).
        """
        fn = getattr(self.inspector, "wants_host_images", None)
        return bool(fn(self.step)) if callable(fn) else True

    def _emit_device_sync(self, tele, drain):
        """Record one pipeline-drain sample: ``seconds`` is the time the
        host blocked to resolve the newest step's output (≈0 means the
        host, not the device, is the bottleneck), ``wall``/``steps`` give
        the true device pipeline rate over the sampled window."""
        if not tele.enabled:
            return
        now = time.perf_counter()
        steps = self._dispatched - self._last_sync_dispatched
        wall = now - self._last_sync_t
        self._last_sync_dispatched = self._dispatched
        self._last_sync_t = now
        tele.emit("device_sync", step=self.step, seconds=round(drain, 6),
                  steps=steps, wall=round(wall, 6), loss=self.last_loss)

    def _snapshot_checkpoint(self, stage, epoch, source="training"):
        """Full-state Checkpoint of the live context (host-side copy)."""
        from flax import serialization

        return Checkpoint(
            model=self.model_id,
            iteration=Iteration(stage.index, epoch, self.step),
            metrics=None,
            state=State(
                model=serialization.to_state_dict(
                    jax.tree.map(np.asarray, self.train_variables())
                ),
                optimizer=serialization.to_state_dict(
                    jax.tree.map(np.asarray, self.opt_state())
                ),
                scaler=dict(self.scaler or {}),
                lr_sched_inst=[s.state_dict()
                               for s in self.lr_sched_inst or []],
                lr_sched_epoch=[s.state_dict()
                                for s in self.lr_sched_epoch or []],
            ),
            metadata={
                "timestamp": datetime.now().isoformat(),
                "source": source,
            },
        )

    def _dump_failed(self, log, stage, epoch):
        log.error("detected non-finite values in final flow field")
        self.inspector.flush()
        # auto-flushes the sink (nonfinite is a boundary event): the run
        # is about to die and the JSONL must survive for the post-mortem.
        # The recent sample-id window makes the trip reproducible offline
        # even though detection is amortized (the producing batch is one
        # of the listed ones, at most _finite_every-1 steps back).
        telemetry.get().emit(
            "nonfinite", step=self.step, stage=stage.index, epoch=epoch,
            action="raise",
            samples=[{"step": s, "samples": ids}
                     for s, ids in self._recent_samples],
        )

        failed = self.path / "failed.ckpt"
        self._snapshot_checkpoint(stage, epoch).save(failed)
        self.last_checkpoint = (failed, self.step)
        blackbox.get().dump(self.path, "nonfinite", tele=telemetry.get(),
                            checkpoint=str(failed), step=self.step)
