"""The ``train`` subcommand: run-dir setup, config assembly, training loop.

Capability parity with the reference command (src/cmd/train.py:45-226),
TPU-native where the reference is CUDA-native:

- device selection picks the jax platform / device subset and (for more
  than one device) builds the SPMD data mesh — the reference's
  ``nn.DataParallel`` wrap (src/cmd/train.py:183-184) has no runtime object
  here, sharding is part of the compiled step,
- ``--detect-anomaly`` flips ``jax_debug_nans`` (the jax analog of
  ``torch.autograd.set_detect_anomaly``),
- the env config carries loader args plus an ``xla`` section instead of
  cudnn switches.
"""

import datetime
import logging
import re
from pathlib import Path

from .. import inspect as inspect_
from .. import models, parallel, strategy, telemetry, utils
from ..strategy.training import TrainingContext

_DEFAULT_ENV = Path(__file__).parent.parent.parent / "cfg" / "env" / "default.yaml"
_DEFAULT_INSPECT = Path(__file__).parent.parent.parent / "cfg" / "inspect" / "default.yaml"


class Environment:
    """Loader arguments + wire format + backend flags (reference
    Environment, src/cmd/train.py:18-42; cudnn switches become jax/XLA
    ones, plus the host→device wire-format section)."""

    @classmethod
    def load(cls, cfg):
        if isinstance(cfg, (Path, str)):
            cfg = utils.config.load(cfg)

        return cls(
            loader_args=cfg.get("loader", {}),
            wire=cfg.get("wire"),
            eval=cfg.get("eval", {}),
            nonfinite=cfg.get("nonfinite"),
            parallel=cfg.get("parallel", {}),
            compile=cfg.get("compile", {}),
            augment=cfg.get("augment"),
            debug_nans=cfg.get("jax", {}).get("debug-nans", False),
            deterministic=cfg.get("jax", {}).get("deterministic", False),
        )

    def __init__(self, loader_args={}, wire=None, eval={}, nonfinite=None,
                 parallel={}, compile={}, augment=None, debug_nans=False,
                 deterministic=False):
        self.loader_args = dict(loader_args)
        # wire config: preset name ('f32'/'bf16'/'u8') or mapping with
        # images/flow/pack-valid keys (models.wire.WireFormat.from_config)
        self.wire = wire
        # eval section: shape buckets for the validation/evaluation passes
        # ({'buckets': 'HxW,...' | 'group' | {sizes, mode}}); the
        # RMD_EVAL_BUCKETS env var overrides it
        self.eval = dict(eval or {})
        # nonfinite section: non-finite step recovery policy — a policy
        # name or {policy, max-consecutive, window, max-rollbacks}
        # (strategy.training.NonFinitePolicy); --nonfinite and
        # RMD_NONFINITE override it
        self.nonfinite = nonfinite
        # parallel section: SPMD scale-out — {mesh: 'D,M' | {data, model},
        # accumulate: k}. --mesh/--accumulate and RMD_MESH/RMD_ACCUMULATE
        # override it (parallel.parse_mesh_spec documents the mesh forms).
        self.parallel = dict(parallel or {})
        # compile section: compiled-program cold-start knobs — {cache:
        # DIR} repoints the persistent XLA compile cache, {aot: false}
        # disables the AOT program store, {aot: DIR} relocates it.
        # --compile-cache / RMD_COMPILE_CACHE / RMD_AOT* override it.
        self.compile = dict(compile or {})
        # augment section: on-device augmentation parameters
        # (data.device_augment.DeviceAugment.from_config); its presence
        # with enabled: true turns the device path on, --device-aug and
        # RMD_DEVICE_AUG force it on with these (or default) parameters.
        self.augment = augment
        self.debug_nans = debug_nans
        self.deterministic = deterministic

    def get_config(self):
        return {
            "loader": self.loader_args,
            "wire": self.wire,
            "eval": self.eval,
            "nonfinite": self.nonfinite,
            "parallel": self.parallel,
            "compile": self.compile,
            "augment": self.augment,
            "jax": {
                "debug-nans": self.debug_nans,
                "deterministic": self.deterministic,
            },
        }

    def apply(self):
        import jax

        # compile-cache / AOT-store config (lowest precedence: the CLI
        # flag and RMD_* env vars were already applied at entry; only
        # fill in what they left at the default). Runs before any
        # backend use, like every other env flag here.
        cache = self.compile.get("cache")
        if cache and not utils.env.raw("RMD_COMPILE_CACHE"):
            from ..utils.compcache import enable_persistent_cache

            enable_persistent_cache(str(cache))
        aot = self.compile.get("aot")
        if aot is not None and not utils.env.raw("RMD_AOT_DIR"):
            from .. import compile as programs

            if aot is False:
                programs.disable_aot()
            elif programs.aot_enabled():
                programs.enable_aot(
                    None if aot is True else str(aot))

        if self.debug_nans:
            jax.config.update("jax_debug_nans", True)
        if self.deterministic:
            import os

            flags = os.environ.get("XLA_FLAGS", "")
            if "--xla_gpu_deterministic_ops" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_gpu_deterministic_ops=true"
                ).strip()


def select_devices(device=None, device_ids=None):
    """Resolve --device/--device-ids to a jax device list.

    ``device`` filters by platform name ('tpu', 'cpu'); ``device_ids`` is a
    comma-separated index list into that platform's devices. Returns the
    selected devices (all of the default backend if unspecified).
    """
    import jax

    # the first jax.devices() brings the backend up (seconds on a TPU):
    # the ``backend_init`` span. Usually taken before a telemetry sink
    # exists; ``telemetry.activate`` delivers it
    with telemetry.interval("backend_init"):
        if device:
            # make the requested platform the jax default. An explicit
            # platform list turns a failed initialisation into an error
            # (left to itself jax skips a backend that does not come up
            # and hands out CPU devices). 'cpu' stays in the list because
            # the host side of the input pipeline computes there
            # (data.synth renders its samples on the host CPU, off the
            # accelerator the train step owns).
            platforms = device if device == "cpu" else f"{device},cpu"
            try:
                jax.config.update("jax_platforms", platforms)
            except RuntimeError:
                pass  # backend already initialized; filter below

            try:
                devices = jax.devices(device)
            except RuntimeError as e:
                raise ValueError(
                    f"--device '{device}': no such jax platform available "
                    f"({e})"
                ) from e
        else:
            devices = jax.devices()

    if device_ids:
        ids = [int(i.strip()) for i in device_ids.split(",")]
        devices = [devices[i] for i in ids]

    return devices


def describe_devices(devices):
    """What a run's telemetry says about where it ran: the platform and
    kind of the selected devices, how many of the platform's devices
    jax sees and how many the run uses, and the default backend (which
    decides whether the Pallas kernels or their XLA references are
    traced)."""
    import jax

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(jax.devices(devices[0].platform)),
        "devices_used": len(devices),
        "backend": jax.default_backend(),
    }


def load_config_parts(args):
    """Assemble seed/env/model/strategy/inspect configs from --config plus
    individual overrides (reference src/cmd/train.py:69-137)."""
    cfg_seeds = cfg_env = cfg_model = cfg_strat = cfg_inspc = None
    base_path = "./"

    if getattr(args, "config", None) is not None:
        logging.info(f"loading configuration: file='{args.config}'")
        config = utils.config.load(args.config)

        cfg_seeds = config.get("seeds")
        cfg_model = config.get("model")
        cfg_strat = config.get("strategy")
        cfg_inspc = config.get("inspect")
        cfg_env = config.get("environment")
        base_path = Path(args.config).parent

    if getattr(args, "seeds", None):
        cfg_seeds = utils.config.load(args.seeds)

    if getattr(args, "env", None):
        cfg_env = args.env
    if cfg_env is None:
        cfg_env = _DEFAULT_ENV

    if getattr(args, "model", None) is not None:
        cfg_model = args.model
    if getattr(args, "data", None) is not None:
        cfg_strat = args.data
        base_path = "./"
    if getattr(args, "inspect", None) is not None:
        cfg_inspc = args.inspect
    if cfg_inspc is None:
        cfg_inspc = _DEFAULT_INSPECT

    return cfg_seeds, cfg_env, cfg_model, cfg_strat, cfg_inspc, base_path


def _train(args):
    timestamp = datetime.datetime.now()

    cfg_seeds, cfg_env, cfg_model, cfg_strat, cfg_inspc, base_path = \
        load_config_parts(args)

    # env flags must land before anything touches jax (XLA parses flags at
    # backend init — and the distributed handshake below brings the
    # backend up); seeds.apply() creates the first PRNG key
    env = Environment.load(cfg_env)
    env.apply()

    # multi-host: join the process group before any other backend use;
    # only the primary process owns the run directory, logs, and
    # checkpoints (SURVEY §5.8 — the pod-scale replacement for the
    # reference's single-host nn.DataParallel, src/cmd/train.py:183-184)
    primary = True
    if getattr(args, "distributed", False):
        parallel.initialize(
            coordinator=args.dist_coordinator,
            num_processes=args.dist_num_processes,
            process_id=args.dist_process_id,
        )
        primary = parallel.is_primary()

    # devices: the first backend use of the run. --device must name the
    # platform before anything else (the seeds' first PRNG key, the
    # model's init) brings a backend up, or it cannot be honoured
    devices = select_devices(args.device, args.device_ids)

    suffix = ""
    if args.suffix:
        suffix = args.suffix if re.match(r"^[./_-].*$", args.suffix) else f"-{args.suffix}"

    if primary:
        path_out = Path(args.output) / (timestamp.strftime("%G.%m.%dT%H.%M.%S") + suffix)
        path_out.mkdir(parents=True)
        utils.logging.setup(path_out / "main.log")
    else:
        # secondary processes compute, they don't publish: artifacts go
        # to a scratch dir (checkpoint writes themselves are gated to the
        # primary in CheckpointManager.create), logging stays on console.
        # The scratch dir is removed when the process exits — worker hosts
        # otherwise accumulate one per run.
        import atexit
        import shutil
        import tempfile

        scratch = tempfile.mkdtemp(prefix="train-secondary-")
        atexit.register(shutil.rmtree, scratch, ignore_errors=True)
        path_out = Path(scratch)
        utils.logging.setup()
    logging.info(f"starting: time is {timestamp}, writing to '{path_out}'")
    logging.info(f"description: {args.comment if args.comment else '<not available>'}")

    # telemetry: structured run events (events.jsonl) — primary-only, like
    # every other run artifact. --no-telemetry / RMD_TELEMETRY=0 disable;
    # render the sink with scripts/telemetry_report.py afterwards.
    if getattr(args, "no_telemetry", False) or not primary:
        tele = telemetry.activate(telemetry.NullTelemetry())
    else:
        tele_path = getattr(args, "telemetry", None)
        tele = telemetry.activate(telemetry.create(
            Path(tele_path) if tele_path else path_out / "events.jsonl"))
        if tele.path:
            logging.info(f"writing telemetry events to '{tele.path}'")

    # goodput ledger + flight recorder ride the event stream (taps in
    # Telemetry.emit), so they activate right after the sink: the resume
    # event below must reach the ledger for replay accounting
    from ..telemetry import blackbox, goodput

    if tele.enabled and utils.env.get_bool("RMD_GOODPUT"):
        goodput.activate()
    if tele.enabled:
        blackbox.activate(
            capacity=max(1, utils.env.get_int("RMD_BLACKBOX_STEPS")),
            registry=telemetry.metrics.registry())

    # boot configuration event: the effective compile-cache and AOT
    # program directories (instead of silently defaulting) — the first
    # thing a cold-start post-mortem needs
    from .. import compile as programs
    from ..utils import compcache

    tele.emit(
        "boot",
        compile_cache=compcache.effective_dir(),
        aot_dir=str(programs.programs_dir()) if programs.aot_enabled()
        else None,
        aot=programs.aot_enabled(),
    )
    if compcache.effective_dir():
        logging.info(
            f"persistent compile cache: '{compcache.effective_dir()}'")
    if programs.aot_enabled():
        logging.info(f"AOT program store: '{programs.programs_dir()}'")

    # seeds (apply() seeds host RNGs and yields the root jax key)
    if args.reproduce or args.seeds:
        if cfg_seeds is None:
            raise ValueError("set --reproduce but no seeds specified")
        logging.info("seeding: using seeds from config")
        seeds = utils.seeds.from_config(cfg_seeds)
    else:
        seeds = utils.seeds.random_seeds()
    seeds.apply()

    # model
    if cfg_model is None:
        raise ValueError("no model configuration specified")
    if isinstance(cfg_model, str):
        logging.info(f"loading model configuration: file='{cfg_model}'")
    model = models.load(cfg_model)

    # strategy
    if cfg_strat is None:
        raise ValueError("no strategy/data configuration specified")
    if isinstance(cfg_strat, str):
        logging.info(f"loading strategy configuration: file='{cfg_strat}'")
        strat = strategy.load(cfg_strat)
    else:
        strat = strategy.load(base_path, cfg_strat)

    # inspector
    if isinstance(cfg_inspc, (str, Path)):
        logging.info(f"loading metrics/inspection configuration: file='{cfg_inspc}'")
    inspc = inspect_.load(cfg_inspc)

    # reproducibility dump
    path_config = path_out / "config.json"
    logging.info(f"writing full configuration to '{path_config}'")

    with open(path_out / "model.txt", "w") as fd:
        fd.write(repr(model.model.module))

    run_config = {
        "timestamp": timestamp.isoformat(),
        "commit": utils.vcs.get_git_head_hash(),
        "comment": args.comment if args.comment else "",
        "cwd": str(Path.cwd()),
        "args": {k: v for k, v in vars(args).items() if k != "comment"},
        "seeds": seeds.get_config(),
        "model": model.get_config(),
        "strategy": strat.get_config(),
        "inspect": inspc.get_config(),
        "environment": env.get_config(),
    }
    utils.config.store(path_config, run_config)
    blackbox.get().config = run_config

    # devices / mesh: --mesh > RMD_MESH > env 'parallel' section. Default
    # is the 1-D data mesh over every selected device (pure batch
    # parallelism, replicated params — the historical layout); 'D,M'
    # builds the 2-D (data × model) mesh whose 'model' axis shards
    # param/optimizer storage per parallel.partition's rules.
    import jax

    mesh_cfg = (getattr(args, "mesh", None)
                or utils.env.raw("RMD_MESH")
                or env.parallel.get("mesh"))
    mesh_spec = parallel.parse_mesh_spec(mesh_cfg)
    if len(devices) > 1 or (mesh_spec is not None
                            and mesh_spec[0] * mesh_spec[1] > 1):
        mesh = parallel.make_mesh(mesh_spec, devices=devices)
        if parallel.process_count() > 1 and "model" in mesh.axis_names:
            raise ValueError(
                "--mesh with a model axis is single-process only for now "
                "(sharded state save/restore is process-local)")
    else:
        # pin single-device runs to the selected device — without this the
        # jitted step would fall back to the default backend's device 0
        mesh = None
        jax.config.update("jax_default_device", devices[0])
    where = ("{devices_used}× {device_kind} [{platform}], default backend "
             "'{backend}'".format(**describe_devices(devices)))
    if mesh is not None:
        shape = ", ".join(f"{n}={mesh.shape[n]}" for n in mesh.axis_names)
        logging.info(f"devices: {where} (SPMD mesh: {shape})")
    else:
        logging.info(f"devices: {where} (single device)")

    # in-step gradient accumulation: --accumulate > RMD_ACCUMULATE > env
    # 'parallel' section; k microbatches per optimizer step inside the
    # jitted train step (k× effective batch, one microbatch's HBM)
    accumulate = int(getattr(args, "accumulate", None)
                     or utils.env.raw("RMD_ACCUMULATE")
                     or env.parallel.get("accumulate", 1) or 1)
    if accumulate > 1:
        logging.info(f"gradient accumulation: {accumulate} microbatches "
                     "per optimizer step (in-step lax.scan)")

    # build inspector and checkpoint manager
    inspector, chkptm = inspc.build(model.id, path_out)

    model_id = model.id
    model_spec, loss, input = model.model, model.loss, model.input
    model_adapter = model_spec.get_adapter()

    # checkpoint / resume
    chkpt = None
    if args.checkpoint and args.resume:
        raise ValueError("cannot set both --checkpoint and --resume")

    if args.checkpoint or args.resume:
        logging.warning(
            "saved config not sufficient for reproducibility due to checkpoint data"
        )

    # wire format: CLI flag > RMD_WIRE_FORMAT > env config. None keeps the
    # legacy host-normalized f32 batches.
    from ..models.wire import WireFormat

    wire_cfg = (getattr(args, "wire_format", None)
                or utils.env.raw("RMD_WIRE_FORMAT")
                or env.wire)
    wire = WireFormat.from_config(wire_cfg)
    if wire is not None:
        logging.info(f"input wire format: {wire.describe()}")

    loader_args = dict(env.loader_args)
    if getattr(args, "loader_procs", None) is not None:
        loader_args["procs"] = args.loader_procs

    # eval shape buckets: RMD_EVAL_BUCKETS > env config 'eval' section.
    # The validation passes group same-bucket samples into full batches
    # and compile at most one program per bucket (models.input.ShapeBuckets)
    from ..models.input import ShapeBuckets

    eval_buckets = ShapeBuckets.from_config(
        utils.env.raw("RMD_EVAL_BUCKETS") or env.eval.get("buckets"))
    if eval_buckets is not None:
        logging.info(f"validation shape buckets: {eval_buckets.describe()}")

    # non-finite step recovery policy: CLI flag > RMD_NONFINITE > env
    # config 'nonfinite' section. Default is the historical raise.
    from ..strategy.training import NonFinitePolicy

    nf_cfg = (getattr(args, "nonfinite", None)
              or utils.env.raw("RMD_NONFINITE")
              or env.nonfinite)
    nonfinite = NonFinitePolicy.from_config(nf_cfg)
    if nonfinite.policy != "raise":
        logging.info(f"non-finite step policy: {nonfinite.get_config()}")

    # on-device augmentation: --device-aug / RMD_DEVICE_AUG / the env
    # config's 'augment' section (enabled: true). The section's remaining
    # keys parameterize data.device_augment.DeviceAugment; off keeps the
    # historical host-side augmentation and registered-program identities.
    from ..data.device_augment import DeviceAugment

    aug_cfg = dict(env.augment or {})
    aug_on = bool(getattr(args, "device_aug", None)
                  or utils.env.get_bool("RMD_DEVICE_AUG")
                  or aug_cfg.pop("enabled", False))
    aug_cfg.pop("enabled", None)
    augment = DeviceAugment.from_config(aug_cfg) if aug_on else None
    if augment is not None:
        logging.info(f"on-device augmentation: {augment.describe()}")

    log = utils.logging.Logger()
    tctx = TrainingContext(
        log, path_out, strat, model_id, model_spec, model_adapter, loss, input,
        inspector, chkptm, mesh=mesh, step_limit=args.steps,
        loader_args=loader_args, wire=wire, eval_buckets=eval_buckets,
        nonfinite=nonfinite, accumulate=accumulate, augment=augment,
    )

    if args.checkpoint:
        logging.info(f"loading checkpoint '{args.checkpoint}'")
        warm = strategy.Checkpoint.load(args.checkpoint)
        tctx._ensure_variables(strat.stages[args.start_stage or 0])
        tctx.variables, _, _ = warm.apply(variables=tctx.variables)

    if args.resume == "auto":
        # preemption-safe auto-resume: find the newest valid checkpoint
        # (emergency saves included) under the output base directory —
        # corrupt files are quarantined and the next-newest one wins.
        # Stage/epoch/step reconstruct from the checkpoint's iteration.
        found = strategy.find_auto_resume(Path(args.output), model=model_id,
                                          log=log)
        if found is None:
            raise ValueError(
                f"--resume auto: no valid checkpoint for model "
                f"'{model_id}' found under '{args.output}'")
        resume_path, chkpt = found
        logging.info(
            f"auto-resume: picking up from '{resume_path}' "
            f"(stage {chkpt.iteration.stage}, epoch {chkpt.iteration.epoch}, "
            f"step {chkpt.iteration.step})")
        tele.emit("resume", path=str(resume_path), step=chkpt.iteration.step,
                  stage=chkpt.iteration.stage, epoch=chkpt.iteration.epoch)
    elif args.resume:
        logging.info(f"loading checkpoint '{args.resume}'")
        chkpt = strategy.Checkpoint.load(args.resume)
        tele.emit("resume", path=str(args.resume), step=chkpt.iteration.step)

    if args.detect_anomaly:
        log.warn("anomaly detection enabled")
        jax.config.update("jax_debug_nans", True)

    # §5.1 tracing: device-level profile of the (typically --limit-steps
    # bounded) run — the TPU analog of the reference's torch-tb-profiler
    # dev dependency
    profile_dir = getattr(args, "profile", None)
    if profile_dir:
        log.info(f"capturing jax.profiler trace to '{profile_dir}'")
        jax.profiler.start_trace(profile_dir)

    tele.emit("run_start", dir=str(path_out),
              commit=utils.vcs.get_git_head_hash(),
              comment=args.comment or "", **describe_devices(devices))

    # trainer observability sidecar: --metrics-port > RMD_TRAIN_METRICS_PORT;
    # serves /metrics, /healthz, /statusz, /profilez off the shared
    # telemetry.sidecar server (port 0 picks an ephemeral port)
    mport = getattr(args, "metrics_port", None)
    if mport is None and utils.env.is_set("RMD_TRAIN_METRICS_PORT"):
        mport = utils.env.get_int("RMD_TRAIN_METRICS_PORT")
    observer = None
    if mport is not None and primary:
        from ..telemetry import sidecar

        observer = sidecar.train_observer(tctx, mport, sink=tele,
                                          ledger=goodput.get())
        logging.info(f"trainer observability sidecar: {observer.url}")

    # preemption safety: SIGTERM/SIGINT finish the in-flight step, write
    # an emergency checkpoint, and return cleanly (--resume auto resumes)
    tctx.install_signal_handlers()

    try:
        tctx.run(args.start_stage, args.start_epoch, chkpt)
    except Exception:
        # crash postmortem: the nonfinite/preempt paths dump their own
        # bundle first (dump is once-per-process, first reason wins)
        blackbox.get().dump(path_out, "crash", tele=tele, step=tctx.step)
        raise
    finally:
        if profile_dir:
            jax.profiler.stop_trace()
            # graftprof attribution of the capture: advisory — never
            # let a parse failure mask the run's real exit path
            if utils.env.get_bool("RMD_PROFILE_ATTRIBUTION"):
                try:
                    from ..analysis import profile as prof

                    summary = prof.attribute_trace(profile_dir)
                    log.info("profile attribution:\n"
                             + prof.render_attribution(summary))
                except Exception as e:  # noqa: BLE001 - attribution is advisory
                    log.warn(f"profile attribution failed: "
                             f"{type(e).__name__}: {e}")
        if observer is not None:
            observer.close()
        ledger = goodput.get()
        if ledger.enabled:
            ledger.close()
            ledger.emit_event(tele, final=True, step=tctx.step)
        goodput.deactivate()
        blackbox.deactivate()
        tele.emit("run_end")
        tele.close()


def train(args):
    utils.debug.run(_train, args, debug=args.debug)
